"""The trainer cell's plain reference, its seeded data and its layout.

Moonlight-16B-A3B's decoder (the DeepSeek-V3 block) as the published code
(``modeling_deepseek.py``) writes it, in plain ``jax.numpy`` at float32
under ``jax.default_matmul_precision("highest")``, with ``jax.vjp`` for
the gradients. It imports nothing of the program: the benchmark makes the
weights, the correction biases and the token ids from the seed here, hands
them to the program, and computes the same two steps itself.

Computed in blocks so that it fits on one chip, and compiles in seconds,
at full width: the chain rule is taken a layer at a time (each layer's
``jax.vjp`` recomputes its forward from the layer's input), so one compiled
program serves every expert layer; attention is a causal softmax by blocks
of 512 queries, a scan whose every block sees all keys with the later
ones masked; the loss is taken by chunks of 4096 tokens. No kernel, no sorting:
the held experts are a dense loop, each applied to every token with the
weight its router gave it (zero where the token chose another expert).

Departures from the published model, which the program shares: only the
``held_experts`` add to an expert layer's output (one chip's share of
expert parallelism), the vocabulary is a slice, there are fewer layers.
RoPE as DeepSeek-V3's code: adjacent pairs permuted to halves, then
``rotate_half``; ``softmax_scale = d_qk ** -0.5`` (no ``rope_scaling`` in
Moonlight's config). ``select`` routes each token of an expert layer to
given experts instead of its own top-k (weights from its own scores): the
check hands it the program's choices and counts how often they differ.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmark import data

Q_BLOCK = 512
LOSS_CHUNK = 4096
TOKENS, BIAS = 2, 3          # generator streams beside data.PARAMS/GRADS


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
    theta: float
    eps: float
    dense_width: int
    expert_width: int
    n_shared: int
    router: int
    held: Tuple[int, ...]
    top_k: int
    scaling: float
    vocab: int

    @classmethod
    def of(cls, c: dict) -> "Model":
        return cls(c["hidden_size"], c["num_hidden_layers"],
                   c["first_k_dense_replace"], c["num_attention_heads"],
                   c["kv_lora_rank"], c["qk_nope_head_dim"],
                   c["qk_rope_head_dim"], c["v_head_dim"],
                   float(c["rope_theta"]), float(c["rms_norm_eps"]),
                   c["intermediate_size"], c["moe_intermediate_size"],
                   c["n_shared_experts"], c["router_experts"],
                   tuple(c["held_experts"]), c["num_experts_per_tok"],
                   float(c["routed_scaling_factor"]), c["vocab_size"])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.first_dense


def layout(m: Model) -> List[Tuple[str, Tuple[int, ...]]]:
    """The parameters as Hugging Face names them, with their [out, in]
    shapes, in forward order; each expert's projections their own
    tensors."""
    h = m.hidden

    def mlp(pre, width):
        return [(f"{pre}.gate_proj.weight", (width, h)),
                (f"{pre}.up_proj.weight", (width, h)),
                (f"{pre}.down_proj.weight", (h, width))]

    out = [("model.embed_tokens.weight", (m.vocab, h))]
    for i in range(m.layers):
        pre = f"model.layers.{i}"
        out += [(f"{pre}.input_layernorm.weight", (h,)),
                (f"{pre}.self_attn.q_proj.weight",
                 (m.heads * (m.nope + m.rope), h)),
                (f"{pre}.self_attn.kv_a_proj_with_mqa.weight",
                 (m.kv_rank + m.rope, h)),
                (f"{pre}.self_attn.kv_a_layernorm.weight", (m.kv_rank,)),
                (f"{pre}.self_attn.kv_b_proj.weight",
                 (m.heads * (m.nope + m.v), m.kv_rank)),
                (f"{pre}.self_attn.o_proj.weight", (h, m.heads * m.v)),
                (f"{pre}.post_attention_layernorm.weight", (h,))]
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
def _param_program(shapes: Tuple[Tuple[int, ...], ...]):
    import jax
    import jax.numpy as jnp

    sizes = [math.prod(s) for s in shapes]
    starts = np.cumsum([0] + sizes[:-1]).tolist()

    @jax.jit
    def make(kd, std):
        key = jax.random.fold_in(jax.random.wrap_key_data(kd), data.PARAMS)
        flat = std * jax.random.normal(key, (sum(sizes),), jnp.float32)
        return [jnp.ones(s, jnp.float32) if len(s) == 1 else
                flat[a:a + n].reshape(s)
                for a, n, s in zip(starts, sizes, shapes)]

    return make


def make_params(shapes: Sequence[Tuple[int, ...]], seed: int, std: float):
    """N(0, std) matrices and unit norm weights from the seed, in one
    jitted call."""
    import jax
    import jax.numpy as jnp

    out = _param_program(tuple(tuple(s) for s in shapes))(
        data.key_data(seed), jnp.float32(std))
    return jax.block_until_ready(out)


def make_bias(m: Model, seed: int, std: float):
    """Each expert layer's e_score_correction_bias: [moe layers, router]."""
    import jax

    key = jax.random.fold_in(jax.random.wrap_key_data(data.key_data(seed)),
                             BIAS)
    return std * jax.random.normal(key, (m.moe_layers, m.router))


@functools.lru_cache(maxsize=None)
def _token_program(seqs: int, seq_len: int, vocab: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(kd, step):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.wrap_key_data(kd), TOKENS), step)
        ids = jax.random.randint(key, (seqs, seq_len + 1), 0, vocab,
                                 jnp.int32)
        return ids[:, :-1], ids[:, 1:]

    return make


def tokens(seed: int, step: int, seqs: int, seq_len: int, vocab: int):
    """(ids, next-token labels) of one step, uniform over the vocabulary
    slice: [seqs, seq_len] int32 each."""
    import jax.numpy as jnp

    return _token_program(seqs, seq_len, vocab)(data.key_data(seed),
                                                jnp.uint32(step))


# ---- the model, as the published code writes it ----

def _rms(x, w, eps):
    import jax.numpy as jnp

    return w * (x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def _rope(x, theta):
    """DeepSeek-V3's apply_rotary_pos_emb on x [B, heads, S, d]."""
    import jax.numpy as jnp

    b, nh, s, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    x = x.reshape(b, nh, s, d // 2, 2).swapaxes(3, 4).reshape(b, nh, s, d)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(emb) + half * jnp.sin(emb)


def _attention(m: Model, p, x):
    import jax
    import jax.numpy as jnp

    b, s, _ = x.shape
    nh, dn, dr = m.heads, m.nope, m.rope
    q = (x @ p["self_attn.q_proj"].T).reshape(b, s, nh, dn + dr)
    q = q.transpose(0, 2, 1, 3)
    kv_a = x @ p["self_attn.kv_a_proj_with_mqa"].T
    c_kv = _rms(kv_a[..., :m.kv_rank], p["self_attn.kv_a_layernorm"], m.eps)
    k_pe = kv_a[..., m.kv_rank:][:, None]                  # [B, 1, S, dr]
    kv = (c_kv @ p["self_attn.kv_b_proj"].T).reshape(b, s, nh, dn + m.v)
    kv = kv.transpose(0, 2, 1, 3)
    query = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], m.theta)], -1)
    key = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        _rope(k_pe, m.theta), (b, nh, s, dr))], -1)
    value = kv[..., dn:]
    scale = (dn + dr) ** -0.5
    qb = min(Q_BLOCK, s)

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

    blocks = query.reshape(b, nh, s // qb, qb, dn + dr).transpose(2, 0, 1, 3, 4)
    _, out = jax.lax.scan(block, None, (jnp.arange(0, s, qb), blocks))
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, s, nh * m.v)
    return out @ p["self_attn.o_proj"].T


def _mlp(p, pre, x):
    import jax

    return (jax.nn.silu(x @ p[f"{pre}.gate_proj"].T)
            * (x @ p[f"{pre}.up_proj"].T)) @ p[f"{pre}.down_proj"].T


def _moe(m: Model, p, x, bias, select):
    import jax
    import jax.numpy as jnp

    shape = x.shape
    x = x.reshape(-1, shape[-1])
    scores = jax.nn.sigmoid(x @ p["mlp.gate"].T)
    own = jax.lax.top_k(scores + bias, m.top_k)[1]
    sel = own if select is None else select
    w = jnp.take_along_axis(scores, sel, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * m.scaling
    y = _mlp(p, "mlp.shared_experts", x)
    for e in m.held:
        y = y + jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)[:, None] \
            * _mlp(p, f"mlp.experts.{e}", x)
    return y.reshape(shape), own


def _layer(m: Model, dense: bool, p, x, bias, select):
    h = x + _attention(m, p, _rms(x, p["input_layernorm"], m.eps))
    hn = _rms(h, p["post_attention_layernorm"], m.eps)
    if dense:
        return h + _mlp(p, "mlp", hn), None
    y, own = _moe(m, p, hn, bias, select)
    return h + y, own


def _head(m: Model, norm_w, head_w, x, labels):
    """Mean next-token cross-entropy of the last layer's output."""
    import jax
    import jax.numpy as jnp

    x = _rms(x, norm_w, m.eps).reshape(-1, m.hidden)
    labels = labels.reshape(-1)

    @jax.checkpoint
    def chunk(xc, lc):
        logits = xc @ head_w.T
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1)
                       - jnp.take_along_axis(logits, lc[:, None], -1)[:, 0])

    total = sum(chunk(x[a:a + LOSS_CHUNK], labels[a:a + LOSS_CHUNK])
                for a in range(0, x.shape[0], LOSS_CHUNK))
    return total / x.shape[0]


@functools.lru_cache(maxsize=None)
def _programs(m: Model):
    """(forward, vjp) of a dense and of an expert layer, keyed by
    ``dense``; the head's loss and gradients; the embedding's gradient."""
    import jax
    import jax.numpy as jnp

    def vjp(dense, p, x, bias, select, dy):
        _, pull = jax.vjp(
            lambda p, x: _layer(m, dense, p, x, bias, select)[0], p, x)
        return pull(dy)

    forward = {d: jax.jit(functools.partial(_layer, m, d))
               for d in (True, False)}
    backward = {d: jax.jit(functools.partial(vjp, d)) for d in (True, False)}
    head = jax.jit(jax.value_and_grad(functools.partial(_head, m),
                                      argnums=(0, 1, 2)))
    embed = jax.jit(lambda ids, dx: jnp.zeros((m.vocab, m.hidden),
                                              jnp.float32)
                    .at[ids.reshape(-1)].add(dx.reshape(-1, m.hidden)))
    return forward, backward, head, embed


def loss_and_grads(m: Model, params: Dict, ids, labels, bias,
                   select: Optional[Sequence] = None):
    """-> (loss, {name: gradient}, own top-k of each expert layer), at
    float32 with the highest matmul precision. The chain rule is taken a
    layer at a time (``jax.vjp`` of each, from its kept input), so that one
    compiled layer serves every expert layer."""
    import jax

    forward, backward, head, embed = _programs(m)
    args = []
    for i in range(m.layers):
        pre = f"model.layers.{i}."
        j = i - m.first_dense
        args.append((j < 0, {n[len(pre):-len(".weight")]: v
                             for n, v in params.items() if n.startswith(pre)},
                     None if j < 0 else bias[j],
                     None if j < 0 or select is None else select[j]))
    with jax.default_matmul_precision("highest"):
        x = params["model.embed_tokens.weight"][ids]
        inputs, own = [], []
        for dense, p, b, sel in args:
            inputs.append(x)
            x, chose = forward[dense](p, x, b, sel)
            if chose is not None:
                own.append(chose)
        value, (g_norm, g_head, dx) = head(params["model.norm.weight"],
                                           params["lm_head.weight"], x,
                                           labels)
        grads = {"model.norm.weight": g_norm, "lm_head.weight": g_head}
        for i in reversed(range(m.layers)):
            dense, p, b, sel = args[i]
            dp, dx = backward[dense](p, inputs[i], b, sel, dx)
            inputs[i] = None
            grads.update({f"model.layers.{i}.{n}.weight": g
                          for n, g in dp.items()})
        grads["model.embed_tokens.weight"] = embed(ids, dx)
    return value, grads, own


@functools.lru_cache(maxsize=None)
def _sgd():
    import jax

    @jax.jit
    def step(p, m, g, lr, beta):
        m = beta * m + g
        return p - lr * m, m

    return step


def momentum_step(p, m, g, lr: float, beta: float):
    """The server's rule: m' = beta m + g, p' = p - lr m' (m = 0 first)."""
    return _sgd()(p, m, g, np.float32(lr), np.float32(beta))
