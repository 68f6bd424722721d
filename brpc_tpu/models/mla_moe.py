"""Moonlight-16B-A3B's decoder (the DeepSeek-V3 block) as a layered trainer
for :class:`~brpc_tpu.runtime.step_driver.OverlappedStepDriver`.

Each decoder layer computes

    h = x + MLA(RMSNorm(x))
    y = h + FFN(RMSNorm(h))

where the first ``first_k_dense_replace`` layers' FFN is a dense SwiGLU and
every later one is a mixture of experts:

* MLA without q-LoRA: ``q = x W_q`` split per head into ``q_nope`` and
  ``q_pe``; ``[c_kv, k_pe] = x W_kv_a``; ``c_kv <- RMSNorm(c_kv)``;
  ``[k_nope, v] = c_kv W_kv_b`` per head; RoPE on ``q_pe`` and on the one
  ``k_pe`` all heads share; causal softmax of ``q.k / sqrt(d_qk)``; then
  ``o = concat(attn.v) W_o``. Rotary pairs are adjacent dimensions
  ``(2i, 2i+1)`` with frequency ``theta^(-2i/d)``: DeepSeek-V3's reference
  code permutes such pairs to halves before ``rotate_half``, on q and k
  alike, which leaves every score as adjacent pairs give it.
* The expert layer: scores ``s = sigmoid(x W_r^T)`` over every routed
  expert of the model (fp32), the top-k of ``s + b`` (``b`` the
  ``e_score_correction_bias``, used to select and never to weight),
  weights ``w_i = scale * s_i / sum_sel s_j``, and
  ``y = sum_{i in sel, held} w_i E_i(x) + S(x)`` with
  ``E(x) = W_down(silu(W_gate x) * W_up x)`` and ``S`` the shared experts,
  the same form at ``n_shared_experts`` times the expert width.

The layer is told which experts it holds (``Config.held``): it routes over
all of them and computes only its own experts' part, as one chip of an
expert-parallel deployment does (without the exchange). No token is
dropped: every (token, slot) pair is sorted by the held expert it chose,
the pairs that chose an expert held elsewhere sort last, and one grouped
matmul (``jax.lax.ragged_dot``) multiplies each held expert's rows by its
weights.

Precision: matmul inputs in ``Config.matmul_dtype`` (bfloat16) with fp32
accumulation; the router, the norms, softmax and the loss in fp32; the
parameters and their gradients fp32. Attention is computed by query
blocks, each block recomputed in the backward, and the loss by chunks of
tokens, so that 8192 positions fit on one chip.

Parameters keep Hugging Face's names and [out, in] layouts, one tensor per
expert and projection (the parameter server's update takes 1-D and 2-D
tensors). The plain reference is ``mla_moe_reference.py``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST

# The Hugging Face configuration keys whose values this block implements;
# any other value would be a different model.
_IMPLEMENTS = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
               "n_group": 1, "topk_group": 1, "q_lora_rank": None,
               "norm_topk_prob": True, "hidden_act": "silu",
               "tie_word_embeddings": False, "attention_bias": False}

ATTN = ("q_proj", "kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_proj",
        "o_proj")
NORMS = ("input_layernorm", "post_attention_layernorm")
SWIGLU = ("gate_proj", "up_proj", "down_proj")


@dataclass(frozen=True)
class Config:
    hidden: int
    layers: int
    first_dense: int
    heads: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    rope_theta: float
    eps: float
    dense_width: int
    expert_width: int
    n_shared: int
    router_experts: int       # the router's outputs: every routed expert
    held: Tuple[int, ...]     # the experts this layer computes
    top_k: int
    scaling: float
    vocab: int                # the rows of the vocabulary held here
    matmul_dtype: str = "bfloat16"
    q_block: int = 512        # query rows per attention block
    loss_chunk: int = 4096    # tokens per chunk of the loss

    @classmethod
    def from_hf(cls, hf: dict, **overrides) -> "Config":
        """From a Hugging Face DeepSeek-V3 ``config.json`` as the benchmark
        keeps it: ``n_routed_experts`` and ``vocab_size`` are what one chip
        holds, ``router_experts`` the model's routed experts and
        ``held_experts`` their ids here."""
        for key, want in _IMPLEMENTS.items():
            if key in hf and hf[key] != want:
                raise ValueError(f"{key}={hf[key]!r}: this block implements "
                                 f"{want!r}")
        held = tuple(int(e) for e in hf["held_experts"])
        if len(held) != hf["n_routed_experts"]:
            raise ValueError(f"held_experts lists {len(held)} experts, "
                             f"n_routed_experts says {hf['n_routed_experts']}")
        cfg = cls(hidden=hf["hidden_size"], layers=hf["num_hidden_layers"],
                  first_dense=hf["first_k_dense_replace"],
                  heads=hf["num_attention_heads"],
                  kv_lora_rank=hf["kv_lora_rank"],
                  qk_nope=hf["qk_nope_head_dim"],
                  qk_rope=hf["qk_rope_head_dim"], v_dim=hf["v_head_dim"],
                  rope_theta=float(hf["rope_theta"]),
                  eps=float(hf["rms_norm_eps"]),
                  dense_width=hf["intermediate_size"],
                  expert_width=hf["moe_intermediate_size"],
                  n_shared=hf["n_shared_experts"],
                  router_experts=hf["router_experts"], held=held,
                  top_k=hf["num_experts_per_tok"],
                  scaling=float(hf["routed_scaling_factor"]),
                  vocab=hf["vocab_size"])
        return replace(cfg, **overrides)

    @property
    def moe_layers(self) -> int:
        return self.layers - self.first_dense

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_dense


def layer_shapes(cfg: Config, layer: int) -> List[Tuple[str, Tuple[int, ...]]]:
    """(name within the layer, shape) of one decoder layer, HF layouts."""
    h, nh = cfg.hidden, cfg.heads
    out = [("input_layernorm", (h,)),
           ("self_attn.q_proj", (nh * (cfg.qk_nope + cfg.qk_rope), h)),
           ("self_attn.kv_a_proj_with_mqa", (cfg.kv_lora_rank + cfg.qk_rope,
                                             h)),
           ("self_attn.kv_a_layernorm", (cfg.kv_lora_rank,)),
           ("self_attn.kv_b_proj", (nh * (cfg.qk_nope + cfg.v_dim),
                                    cfg.kv_lora_rank)),
           ("self_attn.o_proj", (h, nh * cfg.v_dim)),
           ("post_attention_layernorm", (h,))]

    def swiglu(prefix, width):
        return [(f"{prefix}.gate_proj", (width, h)),
                (f"{prefix}.up_proj", (width, h)),
                (f"{prefix}.down_proj", (h, width))]

    if cfg.is_dense(layer):
        return out + swiglu("mlp", cfg.dense_width)
    out.append(("mlp.gate", (cfg.router_experts, h)))
    out += swiglu("mlp.shared_experts", cfg.n_shared * cfg.expert_width)
    for e in cfg.held:
        out += swiglu(f"mlp.experts.{e}", cfg.expert_width)
    return out


def tensor_layout(cfg: Config) -> List[Tuple[str, Tuple[int, ...]]]:
    """Every parameter as (HF name, shape), in forward order."""
    out = [("model.embed_tokens.weight", (cfg.vocab, cfg.hidden))]
    for i in range(cfg.layers):
        out += [(f"model.layers.{i}.{n}.weight", s)
                for n, s in layer_shapes(cfg, i)]
    out += [("model.norm.weight", (cfg.hidden,)),
            ("lm_head.weight", (cfg.vocab, cfg.hidden))]
    return out


def init_params(cfg: Config, key, std: float = 0.02) -> Dict[str, jax.Array]:
    """N(0, std) matrices and unit norm weights, in one jitted call."""
    layout = tensor_layout(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(layout))
        return {n: (jnp.ones(s, jnp.float32) if len(s) == 1 else
                    std * jax.random.normal(k, s, jnp.float32))
                for k, (n, s) in zip(keys, layout)}

    return make(key)


# ---- the block's pieces (pure functions of one layer's parameters) ----

def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mm(x, w, dtype):
    """x @ w.T for an HF [out, in] weight: inputs in ``dtype``, fp32 out."""
    return lax.dot_general(x.astype(dtype), w.astype(dtype),
                           (((x.ndim - 1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def rope(x, positions, theta):
    """Rotary embedding over the last axis, adjacent pairs (2i, 2i+1);
    ``x`` is [..., S, d] or [..., S, heads, d]."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]   # [S, d/2]
    if x.ndim == 4:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _attention_block(q, k, v, q0, dtype):
    """Causal attention of the queries starting at row ``q0`` against the
    keys up to their last row. q: [B, nq, H, d_qk]; k: [B, S, H, d_qk]; v:
    [B, S, H, d_v]. The whole k and v come in and are sliced here, so that
    every block's saved inputs are the same two arrays."""
    nk = q0 + q.shape[1]
    k, v = k[:, :nk], v[:, :nk]
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(dtype), k.astype(dtype),
                   preferred_element_type=jnp.float32) * scale
    rows = q0 + jnp.arange(q.shape[1])
    s = jnp.where(rows[:, None] >= jnp.arange(nk)[None, :], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", softmax(s).astype(dtype),
                      v.astype(dtype), preferred_element_type=jnp.float32)


def softmax(s):
    """Softmax over the last axis with its row max behind an optimization
    barrier: fused with the subtraction, XLA's TPU compiler turns the max
    into a reduce-window as wide as the row, a row's work for every
    element (54 ms for one block of 512 queries by 8192 keys on a v5e)."""
    m = lax.stop_gradient(lax.optimization_barrier(
        jnp.max(s, axis=-1, keepdims=True)))
    e = jnp.exp(s - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def attention(q, k, v, q_block: int, dtype):
    """Causal attention by query blocks: block i sees keys [0, end of i]."""
    return jnp.concatenate(
        [_attention_block(q[:, a:a + q_block], k, v, a, dtype)
         for a in range(0, q.shape[1], q_block)], axis=1)


def mla(cfg: Config, p: dict, x):
    """Multi-head latent attention of normed ``x`` [B, S, H]."""
    dt = jnp.dtype(cfg.matmul_dtype)
    b, s, _ = x.shape
    nh, dn, dr, dv = cfg.heads, cfg.qk_nope, cfg.qk_rope, cfg.v_dim
    pos = jnp.arange(s)
    q = _mm(x, p["self_attn.q_proj"], dt).reshape(b, s, nh, dn + dr)
    kv_a = _mm(x, p["self_attn.kv_a_proj_with_mqa"], dt)
    c_kv = rms_norm(kv_a[..., :cfg.kv_lora_rank],
                    p["self_attn.kv_a_layernorm"], cfg.eps)
    k_pe = rope(kv_a[..., cfg.kv_lora_rank:], pos, cfg.rope_theta)
    kv = _mm(c_kv, p["self_attn.kv_b_proj"], dt).reshape(b, s, nh, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, cfg.rope_theta)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_pe[:, :, None, :], (b, s, nh, dr))], axis=-1)
    o = attention(q, k, kv[..., dn:], cfg.q_block, dt)
    return _mm(o.reshape(b, s, nh * dv), p["self_attn.o_proj"], dt)


def swiglu(p: dict, prefix: str, x, dtype):
    a = _mm(x, p[f"{prefix}.gate_proj"], dtype)
    b = _mm(x, p[f"{prefix}.up_proj"], dtype)
    return _mm(jax.nn.silu(a) * b, p[f"{prefix}.down_proj"], dtype)


def route(cfg: Config, router_w, x, bias):
    """-> (selected experts [T, k] int32, their weights [T, k] fp32), over
    every routed expert of the model, in fp32."""
    s = jax.nn.sigmoid(jnp.dot(x, router_w.T, precision=HIGHEST))
    _, sel = lax.top_k(s + bias, cfg.top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel, cfg.scaling * w


def routed_experts(cfg: Config, p: dict, x, sel, weights):
    """The held experts' part of the expert layer for tokens ``x`` [T, H]
    that chose ``sel`` with ``weights``. -> (y [T, H], slots per held
    expert [n_held])."""
    dt = jnp.dtype(cfg.matmul_dtype)
    t, k = sel.shape
    n = len(cfg.held)
    local = np.full((cfg.router_experts,), n, np.int32)
    local[list(cfg.held)] = np.arange(n, dtype=np.int32)
    slot_expert = jnp.asarray(local)[sel].reshape(-1)          # [T*k]
    order = jnp.argsort(slot_expert, stable=True)
    sizes = jnp.bincount(slot_expert, length=n + 1)[:n].astype(jnp.int32)
    valid = (jnp.arange(t * k) < jnp.sum(sizes))[:, None]
    token = order // k
    rows = jnp.where(valid, x[token], 0.0).astype(dt)

    def stacked(proj, transpose):
        ws = jnp.stack([p[f"mlp.experts.{e}.{proj}"]
                        for e in cfg.held]).astype(dt)
        return jnp.swapaxes(ws, 1, 2) if transpose else ws

    def grouped(lhs, rhs):
        return lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)

    with jax.named_scope("expert_matmul"):
        a = grouped(rows, stacked("gate_proj", True))
        b = grouped(rows, stacked("up_proj", True))
        h = (jax.nn.silu(a) * b).astype(dt)
        out = grouped(h, stacked("down_proj", True))
    w = weights.reshape(-1)[order][:, None]
    y = jnp.zeros_like(x).at[token].add(jnp.where(valid, out * w, 0.0))
    return y, sizes


def experts_layer(cfg: Config, p: dict, x, bias):
    """The expert layer of normed ``x`` [B, S, H]. -> (y, (sel, sizes))."""
    dt = jnp.dtype(cfg.matmul_dtype)
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    with jax.named_scope("router"):
        sel, w = route(cfg, p["mlp.gate"], x, bias)
    with jax.named_scope("experts"):
        y, sizes = routed_experts(cfg, p, x, sel, w)
    with jax.named_scope("shared_expert"):
        y = y + swiglu(p, "mlp.shared_experts", x, dt)
    return y.reshape(shape), (sel, sizes)


def decoder_layer(cfg: Config, layer: int, p: dict, x, bias=None):
    """One decoder layer. ``p`` maps names within the layer (without the
    ``.weight`` suffix) to arrays. -> (y, (sel, sizes)) for an expert
    layer, (y, None) for a dense one."""
    with jax.named_scope("mla"):
        h = x + mla(cfg, p, rms_norm(x, p["input_layernorm"], cfg.eps))
    hn = rms_norm(h, p["post_attention_layernorm"], cfg.eps)
    if cfg.is_dense(layer):
        with jax.named_scope("dense_mlp"):
            return h + swiglu(p, "mlp", hn, jnp.dtype(cfg.matmul_dtype)), None
    y, aux = experts_layer(cfg, p, hn, bias)
    return h + y, aux


def head_loss(cfg: Config, norm_w, head_w, x, labels):
    """Mean next-token cross-entropy over the held vocabulary, by chunks of
    tokens (each recomputed in the backward)."""
    dt = jnp.dtype(cfg.matmul_dtype)
    x = rms_norm(x, norm_w, cfg.eps).reshape(-1, x.shape[-1])
    labels = labels.reshape(-1)

    @jax.checkpoint
    def chunk(xc, lc):
        logits = _mm(xc, head_w, dt)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(logits, lc[:, None],
                                                 axis=-1)[:, 0])

    t = x.shape[0]
    with jax.named_scope("lm_head_loss"):
        total = sum(chunk(x[a:a + cfg.loss_chunk], labels[a:a + cfg.loss_chunk])
                    for a in range(0, t, cfg.loss_chunk))
    return total / t


# ---- the layered trainer ----

def _layer_prefix(i: int) -> str:
    return f"model.layers.{i}."


class MlaMoeTrainer:
    """The harness :class:`OverlappedStepDriver` schedules: ``forward``
    runs the stack keeping each layer's input, then ``backward(ctx,
    name)``, called top layer first, yields each tensor's gradient. The
    first call for a unit (the head, a decoder layer, the embedding) runs
    ``jax.vjp`` of that unit, recomputing its forward from the kept input,
    and caches the gradients of all its tensors and its input's cotangent;
    the unit's parameters and kept input are then dropped from ``ctx``.

    ``bias``: [moe layers, router experts] fp32, each expert layer's
    ``e_score_correction_bias`` (a constant the trainer holds; it selects
    experts and never weights them). ``x`` and ``y`` of a step are token
    ids and next-token labels, [B, S] int32 each.

    ``loss(ctx)`` returns the step's loss and records the step's tokens and
    expert loads on /vars (``trainer_*``); ``last_routes`` holds the last
    forward's (selections, slots per held expert) of each expert layer.
    """

    def __init__(self, cfg: Config, bias):
        self.cfg = cfg
        self.bias = jnp.asarray(bias, jnp.float32)
        layout = tensor_layout(cfg)
        self.names = [n for n, _ in layout]
        self.shapes = dict(layout)
        self._units: List[List[str]] = [[self.names[0]]]   # embedding
        for i in range(cfg.layers):
            pre = _layer_prefix(i)
            self._units.append([n for n in self.names if n.startswith(pre)])
        self._units.append(self.names[-2:])                  # norm, lm_head
        self._unit_of = {n: u for u, ns in enumerate(self._units) for n in ns}
        self.last_routes: List[tuple] = []
        self.expert_slots = np.zeros((cfg.moe_layers, len(cfg.held)),
                                     np.int64)
        self._series = None
        self._fwd = {}
        self._vjp = {}

    # ---- the jitted programs, one per kind of unit ----

    def _layer_params(self, params, i: int) -> dict:
        pre = _layer_prefix(i)
        return {n[len(pre):-len(".weight")]: params[n]
                for n in self._units[i + 1]}

    def _kind(self, i: int) -> str:
        return "dense" if self.cfg.is_dense(i) else "moe"

    def _layer_fwd(self, i: int):
        kind = self._kind(i)
        if kind not in self._fwd:
            self._fwd[kind] = jax.jit(functools.partial(
                decoder_layer, self.cfg, i))
        return self._fwd[kind]

    def _layer_vjp(self, i: int):
        kind = self._kind(i)
        if kind not in self._vjp:
            cfg = self.cfg

            def grad(p, x, bias, dy):
                _, pull = jax.vjp(
                    lambda p, x: decoder_layer(cfg, i, p, x, bias)[0], p, x)
                return pull(dy)

            self._vjp[kind] = jax.jit(grad)
        return self._vjp[kind]

    @functools.cached_property
    def _embed(self):
        return jax.jit(lambda table, ids: table[ids])

    @functools.cached_property
    def _embed_grad(self):
        return jax.jit(lambda ids, dx: jnp.zeros(
            self.shapes[self.names[0]], jnp.float32).at[ids.reshape(-1)].add(
                dx.reshape(-1, dx.shape[-1])))

    @functools.cached_property
    def _head(self):
        return jax.jit(functools.partial(head_loss, self.cfg))

    @functools.cached_property
    def _head_grad(self):
        return jax.jit(jax.grad(functools.partial(head_loss, self.cfg),
                                argnums=(0, 1, 2)))

    # ---- the driver's protocol ----

    def place(self, name: str, arr):
        return arr

    def forward(self, params, x, y) -> dict:
        h = self._embed(params[self.names[0]], x)
        inputs, routes = [h], []
        for i in range(self.cfg.layers):
            bias = None if self.cfg.is_dense(i) else \
                self.bias[i - self.cfg.first_dense]
            h, aux = self._layer_fwd(i)(self._layer_params(params, i), h,
                                        bias)
            inputs.append(h)
            if aux is not None:
                routes.append(aux)
        loss = self._head(params[self.names[-2]], params[self.names[-1]], h,
                          y)
        self.last_routes = routes
        return {"params": dict(params), "inputs": inputs, "ids": x,
                "labels": y, "loss": loss, "grads": {}, "cot": None,
                "next": len(self._units) - 1, "tokens": int(np.prod(x.shape))}

    def backward(self, ctx: dict, name: str):
        if name not in ctx["grads"]:
            unit = self._unit_of[name]
            if unit != ctx["next"]:
                raise ValueError(
                    f"backward order violated: {name} is in unit {unit}, "
                    f"expected unit {ctx['next']} (top layer first)")
            self._unit_backward(ctx, unit)
            ctx["next"] = unit - 1
        return ctx["grads"].pop(name)

    def _unit_backward(self, ctx: dict, unit: int) -> None:
        names = self._units[unit]
        params = ctx["params"]
        if unit == len(self._units) - 1:
            grads = self._head_grad(params[names[0]], params[names[1]],
                                    ctx["inputs"][-1], ctx["labels"])
            ctx["cot"] = grads[2]
            grads = grads[:2]
        elif unit == 0:
            grads = (self._embed_grad(ctx["ids"], ctx["cot"]),)
            ctx["cot"] = None
        else:
            i = unit - 1
            bias = None if self.cfg.is_dense(i) else \
                self.bias[i - self.cfg.first_dense]
            dp, ctx["cot"] = self._layer_vjp(i)(
                self._layer_params(params, i), ctx["inputs"][i], bias,
                ctx["cot"])
            pre = _layer_prefix(i)
            grads = [dp[n[len(pre):-len(".weight")]] for n in names]
        ctx["grads"].update(zip(names, grads))
        for n in names:
            params.pop(n, None)
        if unit > 0:
            ctx["inputs"][unit - 1] = None

    def loss(self, ctx: dict) -> float:
        loss = float(ctx["loss"])
        self._record(ctx["tokens"])
        return loss

    def _record(self, tokens: int) -> None:
        """The step's tokens and expert loads on /vars."""
        if self._series is None:
            from brpc_tpu.observability import metrics

            self._series = metrics.trainer_series()
        sizes = np.stack([np.asarray(s) for _, s in self.last_routes]) \
            if self.last_routes else np.zeros((0, len(self.cfg.held)))
        self.expert_slots += sizes.astype(np.int64)
        self._series["tokens"].add(tokens)
        for row in sizes:
            for n in row:
                self._series["expert_slots"].record_us(int(n))
            mean = float(np.mean(row))
            if mean > 0:
                self._series["expert_load"].record_us(
                    int(1000 * float(np.max(row)) / mean))


def step_tokens(cfg: Config, seqs: int, seq_len: int, key):
    """Token ids [seqs, seq_len + 1] uniform over the held vocabulary, as
    (inputs, next-token labels)."""
    ids = jax.random.randint(key, (seqs, seq_len + 1), 0, cfg.vocab,
                             jnp.int32)
    return ids[:, :-1], ids[:, 1:]

