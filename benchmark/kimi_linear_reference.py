"""The Kimi Linear trainer cell's plain reference, its seeded gates and its
layout.

Kimi Linear's hybrid block (arXiv:2510.26692) as the report and
flash-linear-attention's naive ``KimiDeltaAttention`` write it, in plain
``jax.numpy`` at float32 under ``jax.default_matmul_precision("highest")``,
with ``jax.vjp`` for the gradients. It imports nothing of the program: the
benchmark makes the weights (the KDA gates as the configuration's
``assumed`` says), the correction biases and the token ids from the seed,
hands them to the program, and computes the same two steps itself.

Kimi Delta Attention is the token-by-token recurrence, a token a step:

    S <- Diag(exp g_t) S;   S <- S + beta_t k_t (v_t - S^T k_t)^T;
    o_t = S^T q_t

with no chunks and no triangular solve. Computed in blocks so that it fits
on one chip at full width: the recurrence is a ``lax.scan`` over tokens
under ``jax.checkpoint`` every 256 tokens (its gradient keeps 64 states of
a 16k sequence, not 16,384); latent attention, without rotary embedding
(``mla_use_nope``), a causal softmax by blocks of 256 queries; the chain
rule a layer at a time, one compiled program per kind of layer; the loss
by chunks. The expert layer is trainer_reference's: the held experts a
dense loop, each applied to every token with the weight its router gave.

Departures from the published model, which the program shares: only the
``held_experts`` add to an expert layer's output (one chip's share of
expert parallelism), the vocabulary is a slice, there are fewer layers.
``select`` routes each token of an expert layer to given experts instead
of its own top-k (weights from its own scores): the check hands it the
program's choices where rounding broke a tie the other way.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmark import data, trainer_reference as base

Q_BLOCK = 256
TOKEN_BLOCK = 256      # tokens between the recurrence's kept states
GATES = 4              # generator stream beside trainer_reference's

KDA_TENSORS = ("q_proj", "k_proj", "v_proj", "q_conv1d", "k_conv1d",
               "v_conv1d", "f_a_proj", "f_b_proj", "A_log", "dt_bias",
               "b_proj", "g_a_proj", "g_b_proj", "o_norm", "o_proj")


@dataclass(frozen=True)
class Model:
    """The configuration file's keys this reference reads."""
    hidden: int
    layers: int
    first_dense: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    eps: float
    dense_width: int
    expert_width: int
    n_shared: int
    router: int
    held: Tuple[int, ...]
    top_k: int
    scaling: float
    vocab: int
    kda: Tuple[int, ...]      # 0-indexed layers whose attention is KDA
    kda_heads: int
    kda_dim: int
    conv: int

    @classmethod
    def of(cls, c: dict) -> "Model":
        lin = c["linear_attn_config"]
        n = c["num_hidden_layers"]
        return cls(c["hidden_size"], n, c["first_k_dense_replace"],
                   c["num_attention_heads"], c["kv_lora_rank"],
                   c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                   c["v_head_dim"], float(c["rms_norm_eps"]),
                   c["intermediate_size"], c["moe_intermediate_size"],
                   c["num_shared_experts"], c["router_experts"],
                   tuple(c["held_experts"]), c["num_experts_per_token"],
                   float(c["routed_scaling_factor"]), c["vocab_size"],
                   tuple(i - 1 for i in lin["kda_layers"] if i <= n),
                   lin["num_heads"], lin["head_dim"],
                   lin["short_conv_kernel_size"])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.first_dense


def layout(m: Model) -> List[Tuple[str, Tuple[int, ...]]]:
    """The parameters as Hugging Face names them, with their [out, in]
    shapes, in forward order; each expert's projections their own
    tensors."""
    h, n, d = m.hidden, m.kda_heads, m.kda_dim

    def mlp(pre, width):
        return [(f"{pre}.gate_proj.weight", (width, h)),
                (f"{pre}.up_proj.weight", (width, h)),
                (f"{pre}.down_proj.weight", (h, width))]

    kda = {"q_proj": (n * d, h), "k_proj": (n * d, h), "v_proj": (n * d, h),
           "q_conv1d": (n * d, m.conv), "k_conv1d": (n * d, m.conv),
           "v_conv1d": (n * d, m.conv), "f_a_proj": (d, h),
           "f_b_proj": (n * d, d), "A_log": (n,), "dt_bias": (n * d,),
           "b_proj": (n, h), "g_a_proj": (d, h), "g_b_proj": (n * d, d),
           "o_norm": (d,), "o_proj": (h, n * d)}
    out = [("model.embed_tokens.weight", (m.vocab, h))]
    for i in range(m.layers):
        pre = f"model.layers.{i}"
        out.append((f"{pre}.input_layernorm.weight", (h,)))
        if i in m.kda:
            out += [(f"{pre}.self_attn.{t}.weight", kda[t])
                    for t in KDA_TENSORS]
        else:
            out += [(f"{pre}.self_attn.q_proj.weight",
                     (m.heads * (m.nope + m.rope), h)),
                    (f"{pre}.self_attn.kv_a_proj_with_mqa.weight",
                     (m.kv_rank + m.rope, h)),
                    (f"{pre}.self_attn.kv_a_layernorm.weight", (m.kv_rank,)),
                    (f"{pre}.self_attn.kv_b_proj.weight",
                     (m.heads * (m.nope + m.v), m.kv_rank)),
                    (f"{pre}.self_attn.o_proj.weight", (h, m.heads * m.v))]
        out.append((f"{pre}.post_attention_layernorm.weight", (h,)))
        if i < m.first_dense:
            out += mlp(f"{pre}.mlp", m.dense_width)
            continue
        out.append((f"{pre}.mlp.gate.weight", (m.router, h)))
        out += mlp(f"{pre}.mlp.shared_experts", m.n_shared * m.expert_width)
        for e in m.held:
            out += mlp(f"{pre}.mlp.experts.{e}", m.expert_width)
    return out + [("model.norm.weight", (h,)),
                  ("lm_head.weight", (m.vocab, h))]


# ---- seeded data, made on the device ----

@functools.lru_cache(maxsize=None)
def _gate_program(heads: int, dim: int, a_range: Tuple[float, float],
                  dt_range: Tuple[float, float]):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(kd, layer):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.wrap_key_data(kd), GATES), layer)
        ka, kd_ = jax.random.split(key)
        a = jax.random.uniform(ka, (heads,), jnp.float32, *a_range)
        dt = jnp.exp(jax.random.uniform(
            kd_, (heads * dim,), jnp.float32, math.log(dt_range[0]),
            math.log(dt_range[1])))
        return jnp.log(a), dt + jnp.log(-jnp.expm1(-dt))

    return make


def make_params(m: Model, seed: int, init: dict) -> List:
    """The layout's tensors from the seed: N(0, std) matrices, unit norm
    weights, and each KDA layer's A_log = log U(a) per head and dt_bias =
    softplus^-1(dt), dt log-uniform per channel (``init``'s ranges)."""
    import jax

    lay = layout(m)
    params = base.make_params([s for _, s in lay], seed, init["param_std"])
    index = {n: t for t, (n, _) in enumerate(lay)}
    gates = _gate_program(m.kda_heads, m.kda_dim,
                          tuple(init["a_log_uniform"]),
                          tuple(init["dt_log_uniform"]))
    for i in m.kda:
        pre = f"model.layers.{i}.self_attn."
        a_log, dt_bias = gates(data.key_data(seed), np.uint32(i))
        params[index[pre + "A_log.weight"]] = a_log
        params[index[pre + "dt_bias.weight"]] = dt_bias
    return jax.block_until_ready(params)


# ---- the model, as the published description writes it ----

def _conv(x, w):
    import jax.numpy as jnp

    kw, s = w.shape[1], x.shape[1]
    xp = jnp.concatenate([jnp.zeros((x.shape[0], kw - 1, x.shape[2]),
                                    x.dtype), x], axis=1)
    return sum(w[:, j] * xp[:, j:j + s] for j in range(kw))


def _recurrence(q, k, v, g, beta):
    """o of the gated delta rule, a token at a time; the scan's state kept
    every TOKEN_BLOCK tokens. q, k, v, g [B, S, H, d]; beta [B, S, H]."""
    import jax
    import jax.numpy as jnp

    b, s, h, d = k.shape
    blk = min(TOKEN_BLOCK, s)
    nb = -(-s // blk)
    pad = nb * blk - s

    def step(state, t):
        qt, kt, vt, gt, bt = t
        state = jnp.exp(gt)[..., None] * state
        err = vt - jnp.einsum("bhkv,bhk->bhv", state, kt)
        state = state + bt[..., None, None] * kt[..., :, None] \
            * err[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    def tokens(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((nb, blk) + a.shape[1:])

    _, o = jax.lax.scan(block, jnp.zeros((b, h, d, d), jnp.float32),
                        tuple(tokens(a) for a in (q, k, v, g, beta)))
    o = o.reshape((nb * blk,) + o.shape[2:])[:s]
    return jnp.moveaxis(o, 0, 1)


def _kda_inputs(m: Model, p, x):
    """A KDA layer's q, k, v, g and beta from the layer's input ``x``."""
    import jax
    import jax.numpy as jnp

    x = base._rms(x, p["input_layernorm"], m.eps)
    b, s, _ = x.shape

    def heads(y):
        return y.reshape(b, s, m.kda_heads, m.kda_dim)

    def unit(y):
        return y / jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)

    q, k, v = (heads(jax.nn.silu(_conv(x @ p[f"self_attn.{n}_proj"].T,
                                       p[f"self_attn.{n}_conv1d"])))
               for n in "qkv")
    q, k = unit(q) * m.kda_dim ** -0.5, unit(k)
    beta = jax.nn.sigmoid(x @ p["self_attn.b_proj"].T)
    f = (x @ p["self_attn.f_a_proj"].T) @ p["self_attn.f_b_proj"].T
    g = -jnp.exp(p["self_attn.A_log"])[:, None] * jax.nn.softplus(
        heads(f + p["self_attn.dt_bias"]))
    return q, k, v, g, beta


def _kda_output(m: Model, p, x, o):
    """A KDA layer's attention output from the recurrence's ``o``."""
    import jax

    x = base._rms(x, p["input_layernorm"], m.eps)
    b, s, _ = x.shape
    o = base._rms(o, p["self_attn.o_norm"], m.eps)
    gate = (x @ p["self_attn.g_a_proj"].T) @ p["self_attn.g_b_proj"].T
    o = o * jax.nn.sigmoid(gate.reshape(o.shape))
    return o.reshape(b, s, -1) @ p["self_attn.o_proj"].T


def _mla(m: Model, p, x):
    """Latent attention without rotary embedding, by blocks of queries."""
    import jax
    import jax.numpy as jnp

    b, s, _ = x.shape
    nh, dn, dr = m.heads, m.nope, m.rope
    q = (x @ p["self_attn.q_proj"].T).reshape(b, s, nh, dn + dr)
    q = q.transpose(0, 2, 1, 3)
    kv_a = x @ p["self_attn.kv_a_proj_with_mqa"].T
    c_kv = base._rms(kv_a[..., :m.kv_rank], p["self_attn.kv_a_layernorm"],
                     m.eps)
    kv = (c_kv @ p["self_attn.kv_b_proj"].T).reshape(b, s, nh, dn + m.v)
    kv = kv.transpose(0, 2, 1, 3)
    key = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        kv_a[:, None, :, m.kv_rank:], (b, nh, s, dr))], -1)
    value = kv[..., dn:]
    scale = (dn + dr) ** -0.5
    qb = min(Q_BLOCK, s)
    nq = -(-s // qb)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, nq * qb - s), (0, 0)))

    @jax.checkpoint
    def block(_, item):
        q0, qblock = item
        sc = qblock @ key.swapaxes(-1, -2) * scale
        rows = q0 + jnp.arange(qb)
        sc = jnp.where(rows[:, None] >= jnp.arange(s)[None, :], sc, -jnp.inf)
        # The row max behind a barrier only to keep XLA's TPU compiler from
        # computing it as a row-wide reduce-window for every element.
        mx = jax.lax.stop_gradient(jax.lax.optimization_barrier(
            jnp.max(sc, axis=-1, keepdims=True)))
        e = jnp.exp(sc - mx)
        return None, (e / jnp.sum(e, axis=-1, keepdims=True)) @ value

    blocks = q.reshape(b, nh, nq, qb, dn + dr).transpose(2, 0, 1, 3, 4)
    _, out = jax.lax.scan(block, None, (jnp.arange(0, nq * qb, qb), blocks))
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, nq * qb, nh * m.v)[:, :s]
    return out @ p["self_attn.o_proj"].T


def _ffn(m: Model, dense: bool, p, h, bias, select):
    hn = base._rms(h, p["post_attention_layernorm"], m.eps)
    if dense:
        return h + base._mlp(p, "mlp", hn), None
    y, own = base._moe(m, p, hn, bias, select)
    return h + y, own


def _mla_layer(m: Model, dense: bool, p, x, bias, select):
    h = x + _mla(m, p, base._rms(x, p["input_layernorm"], m.eps))
    return _ffn(m, dense, p, h, bias, select)


def _kda_rest(m: Model, dense: bool, p, x, o, bias, select):
    """A KDA layer from the recurrence's output on."""
    return _ffn(m, dense, p, x + _kda_output(m, p, x, o), bias, select)


@functools.lru_cache(maxsize=None)
def _programs(m: Model):
    """Each piece's forward and vjp: an MLA layer, keyed by ``dense``; a
    KDA layer's inputs to the recurrence, the recurrence, and the rest,
    keyed by ``dense``; the head's loss and gradients; the embedding's
    gradient."""
    import jax
    import jax.numpy as jnp

    def vjp(f, n):
        """The vjp of ``f``'s first output in its first ``n`` arguments,
        the others held; the cotangent comes last."""
        def grad(*args):
            held, dy = args[n:-1], args[-1]
            _, pull = jax.vjp(lambda *a: f(*a, *held)[0], *args[:n])
            return pull(dy)
        return jax.jit(grad)

    dense = (True, False)
    mla = {d: functools.partial(_mla_layer, m, d) for d in dense}
    rest = {d: functools.partial(_kda_rest, m, d) for d in dense}
    inputs = functools.partial(_kda_inputs, m)
    forward = {"mla": {d: jax.jit(f) for d, f in mla.items()},
               "rest": {d: jax.jit(f) for d, f in rest.items()},
               "inputs": jax.jit(inputs), "recurrence": jax.jit(_recurrence)}
    backward = {"mla": {d: vjp(f, 2) for d, f in mla.items()},
                "rest": {d: vjp(f, 3) for d, f in rest.items()},
                "inputs": jax.jit(lambda p, x, d_in: jax.vjp(
                    inputs, p, x)[1](d_in)),
                "recurrence": jax.jit(lambda ins, do: jax.vjp(
                    _recurrence, *ins)[1](do))}
    head = jax.jit(jax.value_and_grad(functools.partial(base._head, m),
                                      argnums=(0, 1, 2)))
    embed = jax.jit(lambda ids, dx: jnp.zeros((m.vocab, m.hidden),
                                              jnp.float32)
                    .at[ids.reshape(-1)].add(dx.reshape(-1, m.hidden)))
    return forward, backward, head, embed


def _layer_forward(forward, is_kda, dense, p, x, bias, select):
    if not is_kda:
        return forward["mla"][dense](p, x, bias, select)
    o = forward["recurrence"](*forward["inputs"](p, x))
    return forward["rest"][dense](p, x, o, bias, select)


def _layer_backward(forward, backward, is_kda, dense, p, x, bias, select,
                    dy):
    """-> (gradients of ``p``, of ``x``). A KDA layer's recurrence inputs
    are computed twice, so that one piece's buffers live at a time."""
    if not is_kda:
        return backward["mla"][dense](p, x, bias, select, dy)
    o = forward["recurrence"](*forward["inputs"](p, x))
    dp, dx, do = backward["rest"][dense](p, x, o, bias, select, dy)
    del o
    d_in = backward["recurrence"](forward["inputs"](p, x), do)
    dp_in, dx_in = backward["inputs"](p, x, d_in)
    return {n: g + dp_in[n] for n, g in dp.items()}, dx + dx_in


def loss_and_grads(m: Model, params: Dict, ids, labels, bias,
                   select: Optional[Sequence] = None):
    """-> (loss, {name: gradient}, own top-k of each expert layer), at
    float32 with the highest matmul precision, a layer at a time."""
    import jax

    forward, backward, head, embed = _programs(m)
    args = []
    for i in range(m.layers):
        pre = f"model.layers.{i}."
        j = i - m.first_dense
        args.append((i in m.kda, j < 0,
                     {n[len(pre):-len(".weight")]: v
                      for n, v in params.items() if n.startswith(pre)},
                     None if j < 0 else bias[j],
                     None if j < 0 or select is None else select[j]))
    with jax.default_matmul_precision("highest"):
        x = params["model.embed_tokens.weight"][ids]
        inputs, own = [], []
        for is_kda, dense, p, b, sel in args:
            inputs.append(x)
            x, chose = _layer_forward(forward, is_kda, dense, p, x, b, sel)
            if chose is not None:
                own.append(chose)
        value, (g_norm, g_head, dx) = head(params["model.norm.weight"],
                                           params["lm_head.weight"], x,
                                           labels)
        grads = {"model.norm.weight": g_norm, "lm_head.weight": g_head}
        for i in reversed(range(m.layers)):
            is_kda, dense, p, b, sel = args[i]
            dp, dx = _layer_backward(forward, backward, is_kda, dense, p,
                                     inputs[i], b, sel, dx)
            inputs[i] = None
            grads.update({f"model.layers.{i}.{n}.weight": g
                          for n, g in dp.items()})
        grads["model.embed_tokens.weight"] = embed(ids, dx)
    return value, grads, own
