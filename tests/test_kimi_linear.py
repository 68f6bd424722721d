"""Kimi Linear's hybrid block (Kimi Delta Attention beside latent attention,
brpc_tpu/models/kda.py and mla_moe.py) against its plain float32 reference
(kimi_linear_reference.py), on the CPU at a tiny size with the published
five-layer pattern: KDA with a dense FFN, KDA, KDA, MLA without rotary
embedding, KDA, each after the first with sigmoid-routed experts of which
the layer holds a share, and one shared expert.

The chunked core against the token-by-token recurrence (a ragged last
chunk; decays strong enough that a chunk-wide factorisation overflows
fp32), the trainer's loss and per-tensor gradients against ``jax.grad`` of
the reference, the expert shares against the uncut layer, the published
keys' tensor and parameter counts, a refused Kimi key, Moonlight's config
left as it was, and two planted faults of the core that the comparison
catches.
"""

import dataclasses
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from brpc_tpu.models import kda, mla_moe
from brpc_tpu.models import kimi_linear_reference as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs", "kimi_linear_48b_a3b.json")
MOONLIGHT = os.path.join(ROOT, "benchmark", "configs",
                         "moonlight_16b_a3b.json")

LINEAR = {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
          "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                         21, 22, 23, 25, 26],
          "head_dim": 16, "num_heads": 2, "short_conv_kernel_size": 4}
TINY = dict(model_type="kimi_linear", hidden_size=64, num_hidden_layers=5,
            first_k_dense_replace=1, num_attention_heads=2, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            rope_theta=10000, rope_scaling=None, mla_use_nope=True,
            rms_norm_eps=1e-5, intermediate_size=96,
            moe_intermediate_size=32, num_shared_experts=1,
            router_experts=32, held_experts=[0, 1, 2, 3], num_experts=4,
            num_experts_per_token=8, routed_scaling_factor=2.446,
            moe_router_activation_func="sigmoid", moe_renormalize=True,
            num_expert_group=1, topk_group=1, use_grouped_topk=True,
            vocab_size=128, linear_attn_config=LINEAR)
SEQ = 36   # four chunks of 8 and a ragged fifth


def _cfg(**kw):
    blocks = {"q_block": 16, "loss_chunk": 24, "kda_chunk": 8}
    blocks.update(kw)
    return mla_moe.Config.from_hf(TINY, **blocks)


def _setup(cfg, seed=0):
    params = mla_moe.init_params(cfg, jax.random.PRNGKey(seed))
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                    (cfg.moe_layers, cfg.router_experts))
    ids, labels = mla_moe.step_tokens(cfg, 2, SEQ,
                                      jax.random.PRNGKey(seed + 2))
    return params, bias, ids, labels


def _memory_paths(monkeypatch):
    """The layer's two memory-saving paths at the tiny size, as the cell's
    16,384 tokens take them: attention with its own gradient, and the held
    experts over 8 runs of the tokens."""
    monkeypatch.setattr(mla_moe, "attention_plan",
                        lambda cfg, b, s: (True, cfg.q_block))
    monkeypatch.setattr(mla_moe, "expert_passes", lambda cfg, tokens: 8)


def _rel(a, b):
    """max|a - b| over max|b|."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _core_inputs(seed, t, decay, heads=2, d=16):
    """Unit-norm q and k (q scaled as the layer scales it), v, beta in
    (0, 1), and log-decays g = -decay * u, u uniform in [0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda y: y / jnp.linalg.norm(y, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (2, t, heads, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (2, t, heads, d)))
    v = jax.random.normal(ks[2], (2, t, heads, d))
    g = -decay * jax.random.uniform(ks[3], (2, t, heads, d))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, t, heads)))
    return q, k, v, g, beta


# The core's tolerance: both sides are fp32 at the highest matmul
# precision and differ in the order of sums and in the triangular solve;
# observed below 1e-6 of the largest output, asserted at 1e-4.
@pytest.mark.parametrize("t,chunk,decay", [
    (36, 8, 0.3),       # ragged last chunk, state carried across chunks
    (64, 16, 0.02),     # decays so weak that the state reaches far back
    (128, 64, 3.2),     # Gamma falls to about -100 within a chunk
])
def test_chunked_core_matches_the_token_recurrence(t, chunk, decay):
    q, k, v, g, beta = _core_inputs(1, t, decay)
    with jax.default_matmul_precision("highest"):
        want = ref.delta_rule(q, k, v, g, beta)
    got = jax.jit(kda.chunk_gated_delta, static_argnums=5)(
        q, k, v, g, beta, chunk)
    assert got.shape == want.shape
    assert np.isfinite(np.asarray(got)).all()
    assert _rel(got, want) <= 1e-4


def test_the_strong_decays_would_overflow_a_chunk_wide_factorisation():
    """The third case above: exp(-Gamma) over a 64-token chunk passes the
    largest fp32, so the core has to keep its exponents inside sub-chunks
    (its gradient too is finite and matches the recurrence's)."""
    q, k, v, g, beta = _core_inputs(1, 128, 3.2)
    gam = np.cumsum(np.asarray(g).reshape(2, 2, 64, 2, 16), axis=2)
    assert -gam.min() > math.log(np.finfo(np.float32).max)
    cot = jax.random.normal(jax.random.PRNGKey(9), v.shape)

    def loss(f, *a):
        return jnp.sum(f(*a) * cot)

    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda *a: loss(ref.delta_rule, *a),
                        argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    got = jax.jit(jax.grad(lambda *a: loss(
        lambda *b: kda.chunk_gated_delta(*b, 64), *a),
        argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        assert _rel(a, b) <= 1e-3


def test_padding_changes_nothing():
    q, k, v, g, beta = _core_inputs(2, 40, 0.5)
    f = jax.jit(kda.chunk_gated_delta, static_argnums=5)
    np.testing.assert_allclose(np.asarray(f(q, k, v, g, beta, 16))[:, :33],
                               np.asarray(f(*(a[:, :33] for a in (
                                   q, k, v, g, beta)), 16)),
                               rtol=1e-5, atol=1e-6)


# The trainer's tolerances. float32 matmul inputs: the two computations
# differ in the order of fp32 sums (chunked recurrence, blocked attention,
# chunked loss, sorted expert rows), observed below 1e-5 relative;
# asserted at 1e-4. bfloat16 inputs (the configuration's precision): 8
# bits of mantissa, relative rounding 2^-9 per input, through five layers
# and their backward; observed ~1e-2 of a tensor's largest gradient;
# asserted at 5e-2, the loss (a mean of 72 terms) at 1e-3. The layers
# take the memory-saving paths that the cell's size takes.
@pytest.mark.parametrize("dtype,grad_tol,loss_tol", [
    ("float32", 1e-4, 1e-6), ("bfloat16", 5e-2, 1e-3)])
def test_trainer_gradients_match_the_reference(monkeypatch, dtype, grad_tol,
                                               loss_tol):
    _memory_paths(monkeypatch)
    _check_trainer(_cfg(matmul_dtype=dtype), grad_tol, loss_tol)


def test_trainer_on_the_direct_paths_matches_the_reference():
    """The tiny size's own plan: autodiff through the query blocks and one
    pass of the held experts; the float32 tolerances above."""
    cfg = _cfg(matmul_dtype="float32")
    assert mla_moe.attention_plan(cfg, 2, SEQ) == (False, 16)
    assert mla_moe.expert_passes(cfg, 2 * SEQ) == 1
    _check_trainer(cfg, 1e-4, 1e-6)


def _check_trainer(cfg, grad_tol, loss_tol):
    params, bias, ids, labels = _setup(cfg)
    trainer = mla_moe.MlaMoeTrainer(cfg, bias)
    ctx = trainer.forward(dict(params), ids, labels)
    grads = {n: trainer.backward(ctx, n) for n in reversed(trainer.names)}
    select = [np.asarray(s) for s, _ in trainer.last_routes]
    loss, want, _ = ref.loss_and_grads(cfg, params, ids, labels, bias,
                                       select=select)
    assert abs(float(ctx["loss"]) - float(loss)) <= loss_tol * abs(
        float(loss))
    assert set(grads) == set(want) == set(trainer.names)
    for n in trainer.names:
        assert grads[n].shape == trainer.shapes[n]
        assert _rel(grads[n], want[n]) <= grad_tol, n
    # layer programs by (attention, FFN): KDA+dense, KDA+experts,
    # MLA+experts; every KDA layer shares its core's own two programs
    assert set(trainer._fwd) == set(trainer._vjp) == {
        ("kda", "dense"), ("kda", "moe"), ("mla", "moe")}
    assert [f.__name__ for f in trainer._kda_programs] == [
        "kda_in_fwd", "kda_in_vjp", "kda_core_fwd", "kda_core_vjp"]
    assert ctx["kda_chunks"] == 4 * 2 * 5


def _zero_carry(monkeypatch):
    """The state entering every chunk read as zero: no chunk sees another."""
    orig = kda._chunk_step

    def step(s, *parts):
        return jnp.zeros_like(s), orig(s, *parts)[1]

    monkeypatch.setattr(kda, "_chunk_step", step)


def _decay_after_update(monkeypatch):
    """The decay applied after the delta update instead of before it: the
    update predicts v from the undecayed state."""
    def step(state, t):
        qt, kt, vt, gt, bt = t
        pred = jnp.einsum("bhkv,bhk->bhv", state, kt, precision="highest")
        state = state + bt[..., None, None] * kt[..., :, None] \
            * (vt - pred)[..., None, :]
        state = jnp.exp(gt)[..., None] * state
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt,
                                 precision="highest")

    def chunk_gated_delta(q, k, v, g, beta, chunk):
        b, _, h, d = k.shape
        seq = [jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)]
        _, o = jax.lax.scan(step, jnp.zeros((b, h, d, d)), seq)
        return jnp.moveaxis(o, 0, 1)

    monkeypatch.setattr(kda, "chunk_gated_delta", chunk_gated_delta)


@pytest.mark.parametrize("plant", [_zero_carry, _decay_after_update])
def test_a_planted_core_fault_fails_the_comparison(monkeypatch, plant):
    """Each fault moves some gradient well past the bfloat16 tolerance
    (5e-2) the trainer is held to."""
    plant(monkeypatch)
    cfg = _cfg(matmul_dtype="float32")
    params, bias, ids, labels = _setup(cfg, seed=4)
    trainer = mla_moe.MlaMoeTrainer(cfg, bias)
    ctx = trainer.forward(dict(params), ids, labels)
    grads = {n: trainer.backward(ctx, n) for n in reversed(trainer.names)}
    select = [np.asarray(s) for s, _ in trainer.last_routes]
    monkeypatch.undo()
    _, want, _ = ref.loss_and_grads(cfg, params, ids, labels, bias,
                                    select=select)
    assert max(_rel(grads[n], want[n]) for n in trainer.names) > 0.1


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips of EP-8 each hold 4 of the 32 routed experts: the parts
    their layers give, with the shared expert (which every chip computes
    alike) counted once, add up to the reference layer holding all 32."""
    full = _cfg(matmul_dtype="float32", held=tuple(range(32)))
    shapes = mla_moe.layer_shapes(full, 1)
    keys = jax.random.split(jax.random.PRNGKey(3), len(shapes))
    p = {n: jnp.ones(s) if len(s) == 1 else
         0.02 * jax.random.normal(k, s) for k, (n, s) in zip(keys, shapes)}
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(4), (32,))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 64))
    shared = mla_moe.swiglu(p, "mlp.shared_experts", x.reshape(-1, 64),
                            jnp.float32).reshape(x.shape)
    total = shared
    layer = jax.jit(mla_moe.experts_layer, static_argnums=0)
    for share in range(8):
        cfg = dataclasses.replace(full, held=tuple(range(4 * share,
                                                         4 * share + 4)))
        y, (_, sizes) = layer(cfg, p, x, bias)
        total = total + (y - shared)
        assert sizes.shape == (4,)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(full, p, x, bias)
    assert _rel(total, want) <= 1e-5


def _published_counts(c):
    """Tensors and parameters of one chip's share from the published keys:
    a KDA layer's 15 attention tensors (q, k, v projections and their
    convolutions, the two-step forget and output gates, A_log, dt_bias,
    b_proj, o_norm, o_proj), an MLA layer's 5, 2 norms each; the dense
    SwiGLU (3) or the router, the shared expert (3) and 3 per held
    expert; the embedding, the final norm and the untied head."""
    h, lin = c["hidden_size"], c["linear_attn_config"]
    n, d, w = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    kda_p = (3 * n * d * h + 3 * n * d * w + 2 * (d * h + n * d * d) + n
             + n * d + n * h + d + h * n * d + 2 * h)
    nh = c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], \
        c["v_head_dim"]
    r = c["kv_lora_rank"]
    mla_p = (nh * (dn + dr) * h + (r + dr) * h + r + nh * (dn + dv) * r
             + h * nh * dv + 2 * h)
    held = c["num_experts"]
    moe = (c["router_experts"] * h
           + 3 * h * c["num_shared_experts"] * c["moe_intermediate_size"]
           + held * 3 * h * c["moe_intermediate_size"])
    dense = 3 * h * c["intermediate_size"]
    layers = c["num_hidden_layers"]
    is_kda = [i + 1 in lin["kda_layers"] for i in range(layers)]
    tensors = 3 + sum((17 if k else 7) + (3 if i < c["first_k_dense_replace"]
                                          else 4 + 3 * held)
                      for i, k in enumerate(is_kda))
    params = 2 * c["vocab_size"] * h + h + sum(
        (kda_p if k else mla_p) + (dense if i < c["first_k_dense_replace"]
                                   else moe)
        for i, k in enumerate(is_kda))
    return tensors, params


def test_the_published_keys_give_193_tensors_and_602m_parameters():
    with open(CONFIG) as fh:
        c = json.load(fh)
    tensors, params = _published_counts(c)
    assert (tensors, params) == (193, 602_433_408)
    assert (c["n_tensors"], c["n_parameters"]) == (tensors, params)
    cfg = mla_moe.Config.from_hf(c)
    assert [cfg.attention(i) for i in range(5)] == [
        "kda", "kda", "kda", "mla", "kda"]
    assert not cfg.rope and cfg.kda_chunk == 64
    layout = mla_moe.tensor_layout(cfg)
    assert len(layout) == 193
    assert sum(math.prod(s) for _, s in layout) == 602_433_408
    assert all(len(s) in (1, 2) for _, s in layout)
    per_layer = [sum(n.startswith(f"model.layers.{i}.") for n, _ in layout)
                 for i in range(5)]
    assert per_layer == [20, 45, 45, 35, 45]
    # the widths are the published ones; only depth, experts, vocab cut
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                              "vocab_size": 163840}
    assert c["router_experts"] == 256 and len(c["held_experts"]) == 8


@pytest.mark.parametrize("key,value", [
    ("moe_router_activation_func", "softmax"), ("num_expert_group", 8),
    ("moe_renormalize", False), ("rope_scaling", {"type": "yarn"})])
def test_a_kimi_key_this_block_does_not_implement_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        mla_moe.Config.from_hf({**TINY, key: value})


def test_a_layer_of_no_attention_kind_is_refused():
    lin = {**LINEAR, "kda_layers": [1, 2, 5]}
    with pytest.raises(ValueError, match="attention kind"):
        mla_moe.Config.from_hf({**TINY, "linear_attn_config": lin})


def test_the_flash_attention_is_the_unrolled_one():
    """Past its byte budget MLA runs its query blocks with a gradient of
    their own (``attention_flash``): the same causal softmax and the same
    gradients as autodiff through the unrolled blocks, a ragged last block
    included."""
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    q = jax.random.normal(ks[0], (2, 36, 2, 12))
    k = jax.random.normal(ks[1], (2, 36, 2, 12))
    v = jax.random.normal(ks[2], (2, 36, 2, 8))
    do = jax.random.normal(ks[3], (2, 36, 2, 8))
    @jax.jit
    def both(q, k, v, do):
        out = []
        for f in (mla_moe.attention_flash, mla_moe.attention):
            o, pull = jax.vjp(lambda *a: f(*a, 16, jnp.float32), q, k, v)
            out.append((o, pull(do)))
        return out

    with jax.default_matmul_precision("highest"):
        (got, grads), (want, want_grads) = both(q, k, v, do)
    assert got.shape == want.shape
    assert _rel(got, want) <= 1e-5
    for a, b in zip(grads, want_grads):
        assert _rel(a, b) <= 1e-5


def test_expert_passes_give_the_one_pass_layer():
    """The held experts over 8 runs of the tokens (one dropless buffer of
    a run's slots each) give the layer of one pass, and the same loads."""
    cfg = _cfg(matmul_dtype="float32")
    p_all = mla_moe.init_params(cfg, jax.random.PRNGKey(7))
    p = {n[len("model.layers.2."):-len(".weight")]: v
         for n, v in p_all.items() if n.startswith("model.layers.2.")}
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(8), (32,))
    x = jax.random.normal(jax.random.PRNGKey(9), (72, 64))
    sel, w = mla_moe.route(cfg, p["mlp.gate"], x, bias)

    @jax.jit
    def both(p, x, sel, w):
        return (mla_moe._routed_by_passes(cfg, p, x, sel, w, 8),
                mla_moe.routed_experts(cfg, p, x, sel, w))

    (y, sizes), (y1, sizes1) = both(p, x, sel, w)
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(sizes1))
    assert _rel(y, y1) <= 1e-6


def _published(path, **layers):
    with open(path) as fh:
        return mla_moe.Config.from_hf(json.load(fh), **layers)


def test_the_memory_plan_follows_the_sizes():
    """Each layer picks its paths from the sizes it sees: Kimi Linear's
    cell (1 x 16,384) takes both memory-saving paths, a quarter of its
    tokens neither, and Moonlight's cell (2 x 8192) neither; Moonlight's
    block over one sequence of 16,384 takes attention's."""
    kimi, moon = _published(CONFIG), _published(MOONLIGHT)
    assert mla_moe.attention_plan(kimi, 1, 16384) == (True, 256)
    assert mla_moe.expert_passes(kimi, 16384) == 8
    assert mla_moe.attention_plan(kimi, 1, 4096) == (False, 512)
    assert mla_moe.expert_passes(kimi, 4096) == 1
    assert mla_moe.attention_plan(moon, 2, 8192) == (False, 512)
    assert mla_moe.expert_passes(moon, 2 * 8192) == 1
    assert mla_moe.attention_plan(moon, 1, 16384) == (True, 512)


def test_tokens_the_expert_passes_cannot_divide_are_refused():
    with pytest.raises(ValueError, match="expert passes"):
        mla_moe.expert_passes(_published(CONFIG), 16383)


def test_moonlights_config_keeps_its_two_programs_and_153_tensors():
    with open(MOONLIGHT) as fh:
        c = json.load(fh)
    cfg = mla_moe.Config.from_hf(c)
    assert cfg.kda_layers == () and cfg.rope
    assert cfg.q_block == 512
    assert mla_moe.attention_plan(cfg, 2, 8192) == (False, 512)
    assert mla_moe.expert_passes(cfg, 2 * 8192) == 1
    assert len(mla_moe.tensor_layout(cfg)) == 153
    trainer = mla_moe.MlaMoeTrainer(cfg, np.zeros((4, 64), np.float32))
    assert {trainer._kind(i) for i in range(5)} == {("mla", "dense"),
                                                     ("mla", "moe")}
