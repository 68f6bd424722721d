"""Moonlight-16B-A3B's block as a layered trainer (brpc_tpu/models/mla_moe.py)
against its plain float32 reference (mla_moe_reference.py), on the CPU at a
tiny size with the published structure: one dense layer then expert layers,
latent attention without q-LoRA, 16 sigmoid-routed experts of which a
layer holds 4, top-6, two shared experts.

Pure half: the per-tensor gradients the trainer yields top layer first,
and its loss, against ``jax.grad`` of the reference; the expert shares
adding up to the uncut layer; the correction bias selecting without
weighting; the published configuration's tensor and parameter counts.
Native half (skips without libbrpc_tpu.so): two steps through a
ParameterServer and the OverlappedStepDriver over tpu:// leave the server's
state equal to the reference's momentum-SGD replay.
"""

import dataclasses
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from brpc_tpu.models import mla_moe
from brpc_tpu.models import mla_moe_reference as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs", "moonlight_16b_a3b.json")

TINY = dict(hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1,
            num_attention_heads=2, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, rope_theta=50000,
            rms_norm_eps=1e-5, intermediate_size=96,
            moe_intermediate_size=32, n_shared_experts=2, router_experts=16,
            held_experts=[0, 1, 2, 3], n_routed_experts=4,
            num_experts_per_tok=6, routed_scaling_factor=2.446,
            vocab_size=128)


def _cfg(**kw):
    blocks = {"q_block": 16, "loss_chunk": 24}
    blocks.update(kw)
    return mla_moe.Config.from_hf(TINY, **blocks)


def _setup(cfg, seed=0):
    params = mla_moe.init_params(cfg, jax.random.PRNGKey(seed))
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                    (cfg.moe_layers, cfg.router_experts))
    ids, labels = mla_moe.step_tokens(cfg, 2, 32,
                                      jax.random.PRNGKey(seed + 2))
    return params, bias, ids, labels


def _rel(a, b):
    """max|a - b| over max|b|."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


# Tolerances. float32 matmul inputs: the two computations differ only in
# the order of fp32 sums (blocked attention, chunked loss, sorted expert
# rows), observed below 1e-6 relative; asserted at 1e-4. bfloat16 inputs
# (the configuration's precision): 8 bits of mantissa, relative rounding
# 2^-9 per input, amplified through five layers and their backward;
# observed ~1e-2 of a tensor's largest gradient; asserted at 5e-2, with the
# loss (an average of 64 terms) at 1e-3.
@pytest.mark.parametrize("dtype,grad_tol,loss_tol", [
    ("float32", 1e-4, 1e-6), ("bfloat16", 5e-2, 1e-3)])
def test_trainer_gradients_match_the_reference(dtype, grad_tol, loss_tol):
    cfg = _cfg(matmul_dtype=dtype)
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


def test_backward_runs_top_layer_first_and_drops_each_unit():
    cfg = _cfg(matmul_dtype="float32")
    params, bias, ids, labels = _setup(cfg)
    trainer = mla_moe.MlaMoeTrainer(cfg, bias)
    ctx = trainer.forward(dict(params), ids, labels)
    with pytest.raises(ValueError, match="top layer first"):
        trainer.backward(ctx, "model.layers.0.input_layernorm.weight")
    trainer.backward(ctx, "lm_head.weight")
    assert "lm_head.weight" not in ctx["params"]
    assert "model.norm.weight" not in ctx["params"]   # its unit's too
    assert ctx["inputs"][-1] is None
    layer2 = [n for n in trainer.names if n.startswith("model.layers.2.")]
    trainer.backward(ctx, "model.norm.weight")         # cached, no new vjp
    trainer.backward(ctx, layer2[-1])
    assert not set(layer2) & set(ctx["params"])
    assert ctx["inputs"][2] is None and ctx["inputs"][1] is not None


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of EP-4 each hold 4 of the 16 routed experts: the parts
    their layers give, with the shared experts (which every chip computes
    alike) counted once, add up to the reference layer holding all 16."""
    full = _cfg(matmul_dtype="float32", held=tuple(range(16)))
    p_all = mla_moe.init_params(full, jax.random.PRNGKey(3))
    p = {n[len("model.layers.1."):-len(".weight")]: v
         for n, v in p_all.items() if n.startswith("model.layers.1.")}
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))
    shared = mla_moe.swiglu(p, "mlp.shared_experts", x.reshape(-1, 64),
                            jnp.float32).reshape(x.shape)
    total = shared
    for share in range(4):
        cfg = dataclasses.replace(full, held=tuple(range(4 * share,
                                                         4 * share + 4)))
        y, (_, sizes) = mla_moe.experts_layer(cfg, p, x, bias)
        total = total + (y - shared)
        assert sizes.shape == (4,)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(full, p, x, bias)
    assert _rel(total, want) <= 1e-5


def test_the_bias_selects_experts_without_weighting_them():
    cfg = _cfg()
    router = 0.3 * jax.random.normal(jax.random.PRNGKey(6), (16, 64))
    x = jax.random.normal(jax.random.PRNGKey(7), (8, 64))
    s = np.asarray(jax.nn.sigmoid(jnp.dot(x, router.T,
                                          precision=mla_moe.HIGHEST)))
    # The expert each token ranks last would never be chosen unbiased.
    last = np.argmin(s, axis=1)
    bias = np.zeros((8, 16), np.float32)
    bias[np.arange(8), last] = 10.0
    sel0, w0 = mla_moe.route(cfg, router, x, jnp.zeros(16))
    for t in range(8):
        sel1, w1 = mla_moe.route(cfg, router, x[t:t + 1], bias[t])
        sel1, w1 = np.asarray(sel1[0]), np.asarray(w1[0])
        assert last[t] in sel1 and last[t] not in np.asarray(sel0[t])
        # weights from the scores alone, over the biased selection
        want = 2.446 * s[t, sel1] / s[t, sel1].sum()
        np.testing.assert_allclose(w1, want, rtol=1e-5)
    # a bias equal for every expert changes nothing
    sel2, w2 = mla_moe.route(cfg, router, x, jnp.full(16, 0.7))
    np.testing.assert_array_equal(np.sort(sel2, 1), np.sort(sel0, 1))
    np.testing.assert_allclose(np.sort(w2, 1), np.sort(w0, 1), rtol=1e-6)


def _published_counts(c):
    """Tensors and parameters of one chip's share, from the configuration's
    published keys alone: per layer 5 MLA tensors and 2 norms, then the
    dense SwiGLU (3) or the router, the shared experts (3) and 3 per held
    expert; the embedding, the final norm and the untied head."""
    h, nh = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], \
        c["v_head_dim"]
    r = c["kv_lora_rank"]
    attn = (nh * (dn + dr) * h + (r + dr) * h + r + nh * (dn + dv) * r
            + h * nh * dv + 2 * h)
    dense = 3 * h * c["intermediate_size"]
    held = c["n_routed_experts"]
    moe = (c["router_experts"] * h
           + 3 * h * c["n_shared_experts"] * c["moe_intermediate_size"]
           + held * 3 * h * c["moe_intermediate_size"])
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    tensors = 1 + n_dense * 10 + n_moe * (7 + 1 + 3 + 3 * held) + 2
    params = (2 * c["vocab_size"] * h + h + n_dense * (attn + dense)
              + n_moe * (attn + moe))
    return tensors, params


def test_the_published_keys_give_153_tensors_and_568m_parameters():
    with open(CONFIG) as fh:
        c = json.load(fh)
    tensors, params = _published_counts(c)
    assert (tensors, params) == (153, 568_484_352)
    assert (c["n_tensors"], c["n_parameters"]) == (tensors, params)
    layout = mla_moe.tensor_layout(mla_moe.Config.from_hf(c))
    assert len(layout) == 153
    assert sum(math.prod(s) for _, s in layout) == 568_484_352
    assert all(len(s) in (1, 2) for _, s in layout)
    per_layer = [sum(n.startswith(f"model.layers.{i}.") for n, _ in layout)
                 for i in range(5)]
    assert per_layer == [10, 35, 35, 35, 35]
    # the widths are the published ones; only depth, experts, vocab cut
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 27,
                              "n_routed_experts": 64, "vocab_size": 163840}
    assert c["router_experts"] == 64 and len(c["held_experts"]) == 8


def test_a_configuration_this_block_does_not_implement_is_refused():
    with pytest.raises(ValueError, match="scoring_func"):
        mla_moe.Config.from_hf({**TINY, "scoring_func": "softmax"})
    with pytest.raises(ValueError, match="held_experts"):
        mla_moe.Config.from_hf({**TINY, "n_routed_experts": 8})


# ---------------------------------------------------------------------------
# Native half: through the parameter server.
# ---------------------------------------------------------------------------

def test_two_steps_through_the_server_match_the_reference_replay():
    """Two overlapped steps over tpu://: every tensor at version 2, and
    the server's parameters and momenta equal the reference's gradients
    replayed by momentum SGD from the same start (float32 matmul inputs, so
    the gradients agree to fp32 summation order; asserted at 1e-4 of each
    tensor's largest value)."""
    from conftest import require_native_lib
    require_native_lib()
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    cfg = _cfg(matmul_dtype="float32")
    params, bias, _, _ = _setup(cfg, seed=10)
    lr, beta = 0.01, 0.9
    ps = ParameterServer(dict(params), lr=lr, momentum=beta)
    client = ParameterClient(f"tpu://127.0.0.1:{ps.start()}")
    trainer = mla_moe.MlaMoeTrainer(cfg, bias)
    try:
        driver = OverlappedStepDriver(client, trainer, overlap=True,
                                      window=4)
        driver.prime()
        batches = [mla_moe.step_tokens(cfg, 2, 32, jax.random.PRNGKey(20 + i))
                   for i in range(2)]
        routes = []
        for x, y in batches:
            driver.step(x, y)
            routes.append([np.asarray(s) for s, _ in trainer.last_routes])
        state = {}
        for n in trainer.names:
            version, stacked = client.handoff(n)
            assert version == 2
            state[n] = stacked
    finally:
        client.close()
        ps.stop()
    p = {n: np.asarray(v, np.float32) for n, v in params.items()}
    m = {n: np.zeros_like(v) for n, v in p.items()}
    for (x, y), sel in zip(batches, routes):
        _, grads, _ = ref.loss_and_grads(cfg, p, x, y, bias, select=sel)
        for n in p:
            m[n] = beta * m[n] + np.asarray(grads[n])
            p[n] = p[n] - lr * m[n]
    for n in trainer.names:
        assert _rel(state[n][0], p[n]) <= 1e-4, n
        assert _rel(state[n][1], m[n]) <= 1e-4, n
