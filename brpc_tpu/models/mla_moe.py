"""Moonlight-16B-A3B's decoder (the DeepSeek-V3 block) and Kimi Linear's
hybrid of it as a layered trainer for
:class:`~brpc_tpu.runtime.step_driver.OverlappedStepDriver`.

Each decoder layer computes

    h = x + ATTN(RMSNorm(x))
    y = h + FFN(RMSNorm(h))

where ATTN is latent attention (MLA) or, in the layers ``Config.kda_layers``
names, Kimi Delta Attention (``kda.py``), and the first
``first_k_dense_replace`` layers' FFN is a dense SwiGLU and every later one
is a mixture of experts:

* MLA without q-LoRA: ``q = x W_q`` split per head into ``q_nope`` and
  ``q_pe``; ``[c_kv, k_pe] = x W_kv_a``; ``c_kv <- RMSNorm(c_kv)``;
  ``[k_nope, v] = c_kv W_kv_b`` per head; RoPE on ``q_pe`` and on the one
  ``k_pe`` all heads share (none where ``Config.rope`` is off, as Kimi
  Linear's ``mla_use_nope``); causal softmax of ``q.k / sqrt(d_qk)``; then
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
tokens, so that 8192 positions fit on one chip. Past two byte budgets
that the shapes decide, the query blocks carry a flash-attention gradient
of their own (``attention_plan``) and the held experts run over the tokens
in passes (``expert_passes``), so that a layer program's vjp fits beside a
parameter shard.

Parameters keep Hugging Face's names and [out, in] layouts, one tensor per
expert and projection (the parameter server's update takes 1-D and 2-D
tensors). The plain references are ``mla_moe_reference.py`` and
``kimi_linear_reference.py``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from brpc_tpu.models import kda as kda_layer

HIGHEST = lax.Precision.HIGHEST

# What a layer program's vjp may spend on two buffers that grow with the
# tokens before the layer trades time for memory, on a 16 GB v5e that also
# holds a parameter shard and the trainer's copy of the parameters.
# Attention's autodiff keeps each query block's partial key and value
# gradients (``attention_plan``); past ATTN_GRAD_BYTES the blocks take a
# gradient of their own, each block's fp32 scores at most SCORE_BYTES.
ATTN_GRAD_BYTES = 4 << 30
SCORE_BYTES = 512 << 20
# The held experts' dropless buffers (``expert_passes``); past EXPERT_BYTES
# they run in passes of at most EXPERT_PASS_BYTES, each recomputed in the
# backward: that costs one expert forward whatever the count, so the
# passes are small.
EXPERT_BYTES = 7 << 29
EXPERT_PASS_BYTES = 512 << 20

# The Hugging Face configuration keys whose values this block implements;
# any other value would be a different model.
_IMPLEMENTS = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
               "n_group": 1, "topk_group": 1, "q_lora_rank": None,
               "norm_topk_prob": True, "hidden_act": "silu",
               "tie_word_embeddings": False, "attention_bias": False}
# The same for Kimi Linear's keys (``model_type`` "kimi_linear").
_IMPLEMENTS_KIMI = {"moe_router_activation_func": "sigmoid",
                    "moe_renormalize": True, "num_expert_group": 1,
                    "topk_group": 1, "q_lora_rank": None,
                    "hidden_act": "silu", "tie_word_embeddings": False,
                    "rope_scaling": None, "moe_layer_freq": 1,
                    "num_nextn_predict_layers": 0}

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
    rope: bool = True         # rotary embedding on q_pe and k_pe
    kda_layers: Tuple[int, ...] = ()   # layers whose attention is KDA
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 0         # width of the short causal convolution
    kda_chunk: int = 64       # tokens per chunk of the KDA core

    @classmethod
    def from_hf(cls, hf: dict, **overrides) -> "Config":
        """From a Hugging Face DeepSeek-V3 or Kimi Linear ``config.json``
        as the benchmark keeps it: ``n_routed_experts`` (Kimi:
        ``num_experts``) and ``vocab_size`` are what one chip holds,
        ``router_experts`` the model's routed experts and ``held_experts``
        their ids here."""
        if hf.get("model_type") == "kimi_linear":
            return replace(cls._from_kimi(hf), **overrides)
        _refuse(hf, _IMPLEMENTS)
        held = _held(hf, "n_routed_experts")
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

    @classmethod
    def _from_kimi(cls, hf: dict) -> "Config":
        """Kimi Linear's keys: ``linear_attn_config`` names the KDA and
        the full-attention layers, 1-indexed; layers past
        ``num_hidden_layers`` lie on further pipeline stages."""
        _refuse(hf, _IMPLEMENTS_KIMI)
        held = _held(hf, "num_experts")
        lin = hf["linear_attn_config"]
        n = hf["num_hidden_layers"]
        kda_l = {i - 1 for i in lin["kda_layers"] if i <= n}
        full = {i - 1 for i in lin["full_attn_layers"] if i <= n}
        if kda_l & full or kda_l | full != set(range(n)):
            raise ValueError(f"linear_attn_config does not give each of the "
                             f"{n} layers one attention kind")
        return cls(hidden=hf["hidden_size"], layers=n,
                   first_dense=hf["first_k_dense_replace"],
                   heads=hf["num_attention_heads"],
                   kv_lora_rank=hf["kv_lora_rank"],
                   qk_nope=hf["qk_nope_head_dim"],
                   qk_rope=hf["qk_rope_head_dim"], v_dim=hf["v_head_dim"],
                   rope_theta=float(hf["rope_theta"]),
                   eps=float(hf["rms_norm_eps"]),
                   dense_width=hf["intermediate_size"],
                   expert_width=hf["moe_intermediate_size"],
                   n_shared=hf["num_shared_experts"],
                   router_experts=hf["router_experts"], held=held,
                   top_k=hf["num_experts_per_token"],
                   scaling=float(hf["routed_scaling_factor"]),
                   vocab=hf["vocab_size"], rope=not hf["mla_use_nope"],
                   kda_layers=tuple(sorted(kda_l)),
                   kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
                   kda_conv=lin["short_conv_kernel_size"])

    def attention(self, layer: int) -> str:
        """``"kda"`` or ``"mla"``: the attention kind of ``layer``."""
        return "kda" if layer in self.kda_layers else "mla"

    @property
    def moe_layers(self) -> int:
        return self.layers - self.first_dense

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_dense


def _refuse(hf: dict, implements: dict) -> None:
    for key, want in implements.items():
        if key in hf and hf[key] != want:
            raise ValueError(f"{key}={hf[key]!r}: this block implements "
                             f"{want!r}")


def _held(hf: dict, key: str) -> Tuple[int, ...]:
    held = tuple(int(e) for e in hf["held_experts"])
    if len(held) != hf[key]:
        raise ValueError(f"held_experts lists {len(held)} experts, "
                         f"{key} says {hf[key]}")
    return held


def layer_shapes(cfg: Config, layer: int) -> List[Tuple[str, Tuple[int, ...]]]:
    """(name within the layer, shape) of one decoder layer, HF layouts."""
    h, nh = cfg.hidden, cfg.heads
    if cfg.attention(layer) == "kda":
        attn = [(f"self_attn.{n}", s) for n, s in kda_layer.shapes(
            h, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv)]
    else:
        attn = [("self_attn.q_proj", (nh * (cfg.qk_nope + cfg.qk_rope), h)),
                ("self_attn.kv_a_proj_with_mqa",
                 (cfg.kv_lora_rank + cfg.qk_rope, h)),
                ("self_attn.kv_a_layernorm", (cfg.kv_lora_rank,)),
                ("self_attn.kv_b_proj", (nh * (cfg.qk_nope + cfg.v_dim),
                                         cfg.kv_lora_rank)),
                ("self_attn.o_proj", (h, nh * cfg.v_dim))]
    out = [("input_layernorm", (h,))] + attn + \
        [("post_attention_layernorm", (h,))]

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
    """N(0, std) matrices and unit norm weights, and each KDA layer's
    gates as ``kda.gate_init`` makes them, in one jitted call."""
    layout = tensor_layout(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(layout))
        out = {n: (jnp.ones(s, jnp.float32) if len(s) == 1 else
                   std * jax.random.normal(k, s, jnp.float32))
               for k, (n, s) in zip(keys, layout)}
        for i in cfg.kda_layers:
            pre = f"{_layer_prefix(i)}self_attn."
            out[pre + "A_log.weight"], out[pre + "dt_bias.weight"] = \
                kda_layer.gate_init(jax.random.fold_in(key, i),
                                    cfg.kda_heads, cfg.kda_head_dim)
        return out

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


def _flash_scores(qb, k, q0, dtype):
    """One query block's masked scores against keys [0, its last row]:
    [B, heads, rows, keys] fp32."""
    nk = q0 + qb.shape[1]
    sc = jnp.einsum("bqhd,bkhd->bhqk", qb.astype(dtype),
                    k[:, :nk].astype(dtype),
                    preferred_element_type=jnp.float32) * qb.shape[-1] ** -0.5
    rows = q0 + jnp.arange(qb.shape[1])
    return jnp.where(rows[:, None] >= jnp.arange(nk)[None, :], sc, -jnp.inf)


def _flash_forward(q, k, v, q_block, dtype):
    """-> (o [B, S, heads, d_v], each row's log-sum-exp [B, heads, S, 1])."""
    outs, lses = [], []
    for a in range(0, q.shape[1], q_block):
        sc = _flash_scores(q[:, a:a + q_block], k, a, dtype)
        m = lax.optimization_barrier(jnp.max(sc, axis=-1, keepdims=True))
        e = jnp.exp(sc - m)
        den = jnp.sum(e, axis=-1, keepdims=True)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", (e / den).astype(dtype),
                               v[:, :sc.shape[-1]].astype(dtype),
                               preferred_element_type=jnp.float32))
        lses.append(m + jnp.log(den))
    return jnp.concatenate(outs, axis=1), jnp.concatenate(lses, axis=2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def attention_flash(q, k, v, q_block: int, dtype):
    """Causal attention by query blocks, block i against keys [0, end of
    i], as ``attention`` computes it, with a gradient of its own
    (flash-attention's backward): each block's softmax comes back from its
    saved log-sum-exp, and its key and value gradients are added into two
    running sums before the next block starts. ``attention``'s gradient
    keeps every block's partial sums until their total, S^2 / (2 q_block)
    key rows; this one, one block's scores."""
    return _flash_forward(q, k, v, q_block, dtype)[0]


def _flash_fwd(q, k, v, q_block, dtype):
    o, lse = _flash_forward(q, k, v, q_block, dtype)
    return o, (q, k, v, o, lse)


def _flash_bwd(q_block, dtype, res, do):
    q, k, v, o, lse = res
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    delta = jnp.moveaxis(jnp.sum(do * o, axis=-1), 1, 2)[..., None]
    dqs = []
    for a in range(0, q.shape[1], q_block):
        qb, dob = q[:, a:a + q_block], do[:, a:a + q_block]
        if a:   # this block starts once the last one's sums are in
            dk, dv, qb, dob = lax.optimization_barrier((dk, dv, qb, dob))
        sc = _flash_scores(qb, k, a, dtype)
        nk = sc.shape[-1]
        p = jnp.exp(sc - lse[:, :, a:a + q_block])
        dp = jnp.einsum("bqhd,bkhd->bhqk", dob.astype(dtype),
                        v[:, :nk].astype(dtype),
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, :, a:a + q_block])).astype(dtype)
        scale = q.shape[-1] ** -0.5
        dqs.append(jnp.einsum("bhqk,bkhd->bqhd", ds, k[:, :nk].astype(dtype),
                              preferred_element_type=jnp.float32) * scale)
        dk = dk.at[:, :nk].add(jnp.einsum(
            "bhqk,bqhd->bkhd", ds, qb.astype(dtype),
            preferred_element_type=jnp.float32) * scale)
        dv = dv.at[:, :nk].add(jnp.einsum(
            "bhqk,bqhd->bkhd", p.astype(dtype), dob.astype(dtype),
            preferred_element_type=jnp.float32))
    return jnp.concatenate(dqs, axis=1), dk, dv


attention_flash.defvjp(_flash_fwd, _flash_bwd)


def attention_plan(cfg: Config, batch: int, seq: int) -> Tuple[bool, int]:
    """(flash, query block) of ``mla`` over [batch, seq] positions.
    Autodiff through ``attention``'s blocks of ``cfg.q_block`` keeps every
    block's partial key and value gradients until their sum, batch x S(S +
    q) / 2q rows of every head's key and value widths in fp32; while they
    fit ATTN_GRAD_BYTES it is the plan (Moonlight's 2 x 8192: 2.9 GB).
    Past it, ``attention_flash``, with the block halved until one block's
    fp32 scores fit SCORE_BYTES (Kimi Linear's 1 x 16,384: 11.1 GB of
    partials, and blocks of 256 queries)."""
    q = cfg.q_block
    width = cfg.heads * (cfg.qk_nope + cfg.qk_rope + cfg.v_dim)
    if batch * seq * (seq + q) // (2 * q) * width * 4 <= ATTN_GRAD_BYTES:
        return False, q
    while q > 1 and batch * cfg.heads * q * seq * 4 > SCORE_BYTES:
        q //= 2
    return True, q


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
    k_pe = kv_a[..., cfg.kv_lora_rank:]
    if cfg.rope:
        k_pe = rope(k_pe, pos, cfg.rope_theta)
    kv = _mm(c_kv, p["self_attn.kv_b_proj"], dt).reshape(b, s, nh, dn + dv)
    if cfg.rope:
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos,
                                                cfg.rope_theta)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_pe[:, :, None, :], (b, s, nh, dr))], axis=-1)
    flash, q_block = attention_plan(cfg, b, s)
    o = (attention_flash if flash else attention)(q, k, kv[..., dn:],
                                                  q_block, dt)
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


def expert_passes(cfg: Config, tokens: int) -> int:
    """The passes ``experts_layer`` makes over ``tokens`` tokens. Its
    dropless buffers hold a row for each of the tokens x top_k slots: the
    fp32 input and output (2 x hidden) and three products of the expert
    width. One pass while they fit EXPERT_BYTES (Moonlight's 16,384 tokens:
    3.3 GB); past it, as many as keep a pass within EXPERT_PASS_BYTES
    (Kimi Linear's 16,384: 4.0 GB, 8 passes). Raises ValueError where that
    count does not divide the tokens."""
    need = tokens * cfg.top_k * 4 * (2 * cfg.hidden + 3 * cfg.expert_width)
    if need <= EXPERT_BYTES:
        return 1
    n = -(-need // EXPERT_PASS_BYTES)
    if tokens % n:
        raise ValueError(f"{tokens} tokens do not split into the {n} expert "
                         f"passes that {need / 1e9:.2f} GB of dropless "
                         f"buffers need")
    return n


def _routed_by_passes(cfg: Config, p: dict, x, sel, weights, n: int):
    """``routed_experts`` over ``n`` runs of the tokens, one scan step
    each, recomputed in the backward: one run's buffers live at a time. ->
    as ``routed_experts``."""

    @jax.checkpoint
    def run(_, part):
        return None, routed_experts(cfg, p, *part)

    parts = (x, sel, weights)
    _, (y, sizes) = lax.scan(run, None, tuple(
        a.reshape((n, -1) + a.shape[1:]) for a in parts))
    return y.reshape(x.shape), jnp.sum(sizes, axis=0)


def experts_layer(cfg: Config, p: dict, x, bias):
    """The expert layer of normed ``x`` [B, S, H]. -> (y, (sel, sizes))."""
    dt = jnp.dtype(cfg.matmul_dtype)
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    with jax.named_scope("router"):
        sel, w = route(cfg, p["mlp.gate"], x, bias)
    with jax.named_scope("experts"):
        n = expert_passes(cfg, x.shape[0])
        if n == 1:
            y, sizes = routed_experts(cfg, p, x, sel, w)
        else:
            y, sizes = _routed_by_passes(cfg, p, x, sel, w, n)
    with jax.named_scope("shared_expert"):
        y = y + swiglu(p, "mlp.shared_experts", x, dt)
    return y.reshape(shape), (sel, sizes)


def kda_in(cfg: Config, p: dict, x):
    """A KDA layer up to its core, of the layer's input ``x``: (q, k, v, g,
    beta) (``kda.kda_in``). ``p`` needs ``input_layernorm`` and the
    tensors of ``kda.IN_TENSORS``."""
    with jax.named_scope("kda"):
        return kda_layer.kda_in(
            p, rms_norm(x, p["input_layernorm"], cfg.eps),
            heads=cfg.kda_heads, head_dim=cfg.kda_head_dim,
            dtype=jnp.dtype(cfg.matmul_dtype))


def kda_core(cfg: Config, q, k, v, g, beta):
    """A KDA layer's chunked recurrence: o [B, S, heads, head_dim]."""
    with jax.named_scope("kda"):
        return kda_layer.kda_core(q, k, v, g, beta, cfg.kda_chunk)


def kda_rest(cfg: Config, layer: int, p: dict, x, o, bias=None):
    """A KDA layer from its core's output ``o`` on: the attention's output
    and residual, then the FFN. -> as ``decoder_layer``."""
    with jax.named_scope("kda"):
        h = x + kda_layer.kda_out(
            p, rms_norm(x, p["input_layernorm"], cfg.eps), o, eps=cfg.eps,
            dtype=jnp.dtype(cfg.matmul_dtype))
    return ffn(cfg, layer, p, h, bias)


def ffn(cfg: Config, layer: int, p: dict, h, bias=None):
    """``h + FFN(RMSNorm(h))``, the dense SwiGLU or the expert layer. ->
    (y, (sel, sizes)) for an expert layer, (y, None) for a dense one."""
    hn = rms_norm(h, p["post_attention_layernorm"], cfg.eps)
    if cfg.is_dense(layer):
        with jax.named_scope("dense_mlp"):
            return h + swiglu(p, "mlp", hn, jnp.dtype(cfg.matmul_dtype)), None
    y, aux = experts_layer(cfg, p, hn, bias)
    return h + y, aux


def decoder_layer(cfg: Config, layer: int, p: dict, x, bias=None):
    """One decoder layer whose attention is MLA (a KDA layer is
    ``kda_rest`` of ``kda_core`` of ``kda_in``). ``p`` maps names within
    the layer (without the ``.weight`` suffix) to arrays. -> (y, (sel,
    sizes)) for an expert layer, (y, None) for a dense one."""
    with jax.named_scope("mla"):
        h = x + mla(cfg, p, rms_norm(x, p["input_layernorm"], cfg.eps))
    return ffn(cfg, layer, p, h, bias)


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

    ``loss(ctx)`` returns the step's loss and records the step's tokens,
    expert loads and KDA chunks on /vars (``trainer_*``); ``last_routes``
    holds the last forward's (selections, slots per held expert) of each
    expert layer. Layers share a program where they share their kind,
    (attention, FFN). A KDA layer runs as three programs, its core's
    inputs (``kda_in``), its core and the rest of the layer, and its vjp
    as their three vjps (``_kda_backward``).
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

    def _kind(self, i: int) -> Tuple[str, str]:
        return (self.cfg.attention(i),
                "dense" if self.cfg.is_dense(i) else "moe")

    def _layer_fwd(self, i: int):
        """An MLA layer's program, or a KDA layer's after its core."""
        kind = self._kind(i)
        if kind not in self._fwd:
            f = decoder_layer if kind[0] == "mla" else kda_rest
            self._fwd[kind] = jax.jit(functools.partial(f, self.cfg, i))
        return self._fwd[kind]

    def _layer_vjp(self, i: int):
        """The vjp of ``_layer_fwd(i)``: (p, x, bias, dy) -> (dp, dx) for
        an MLA layer, (p, x, o, bias, dy) -> (dp, dx, do) for a KDA
        layer's part after its core."""
        kind = self._kind(i)
        if kind not in self._vjp:
            cfg = self.cfg
            if kind[0] == "mla":
                def grad(p, x, bias, dy):
                    _, pull = jax.vjp(
                        lambda p, x: decoder_layer(cfg, i, p, x, bias)[0],
                        p, x)
                    return pull(dy)
            else:
                def grad(p, x, o, bias, dy):
                    _, pull = jax.vjp(
                        lambda p, x, o: kda_rest(cfg, i, p, x, o, bias)[0],
                        p, x, o)
                    return pull(dy)

            self._vjp[kind] = jax.jit(grad)
        return self._vjp[kind]

    @functools.cached_property
    def _kda_programs(self):
        """A KDA layer's programs up to and through its core, and their
        vjps, shared by every KDA layer: (in, in vjp, core, core vjp). The
        core's are programs of their own, ``jit_kda_core_fwd`` and
        ``jit_kda_core_vjp``, so that a device trace times them alone."""
        cfg = self.cfg

        def kda_in_fwd(p, x):
            return kda_in(cfg, p, x)

        def kda_in_vjp(p, x, d_in):
            return jax.vjp(kda_in_fwd, p, x)[1](d_in)

        def kda_core_fwd(q, k, v, g, beta):
            return kda_core(cfg, q, k, v, g, beta)

        def kda_core_vjp(ins, do):
            return jax.vjp(kda_core_fwd, *ins)[1](do)

        return tuple(jax.jit(f) for f in (kda_in_fwd, kda_in_vjp,
                                          kda_core_fwd, kda_core_vjp))

    def _kda_split(self, p: dict):
        """A KDA layer's parameters as (what ``kda_in`` reads, the rest);
        ``input_layernorm`` is in both."""
        names = {f"self_attn.{n}" for n in kda_layer.IN_TENSORS}
        return ({n: a for n, a in p.items()
                 if n in names or n == "input_layernorm"},
                {n: a for n, a in p.items() if n not in names})

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
            p = self._layer_params(params, i)
            if self.cfg.attention(i) == "kda":
                run_in, _, run_core, _ = self._kda_programs
                p_in, p = self._kda_split(p)
                h, aux = self._layer_fwd(i)(p, h, run_core(*run_in(p_in, h)),
                                            bias)
            else:
                h, aux = self._layer_fwd(i)(p, h, bias)
            inputs.append(h)
            if aux is not None:
                routes.append(aux)
        loss = self._head(params[self.names[-2]], params[self.names[-1]], h,
                          y)
        self.last_routes = routes
        seqs, seq_len = x.shape
        return {"params": dict(params), "inputs": inputs, "ids": x,
                "labels": y, "loss": loss, "grads": {}, "cot": None,
                "next": len(self._units) - 1, "tokens": seqs * seq_len,
                "kda_chunks": len(self.cfg.kda_layers) * kda_layer.chunks(
                    seqs, seq_len, self.cfg.kda_chunk)}

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
            p, x = self._layer_params(params, i), ctx["inputs"][i]
            if self.cfg.attention(i) == "kda":
                dp, ctx["cot"] = self._kda_backward(i, p, x, bias,
                                                    ctx["cot"])
            else:
                dp, ctx["cot"] = self._layer_vjp(i)(p, x, bias, ctx["cot"])
            pre = _layer_prefix(i)
            grads = [dp[n[len(pre):-len(".weight")]] for n in names]
        ctx["grads"].update(zip(names, grads))
        for n in names:
            params.pop(n, None)
        if unit > 0:
            ctx["inputs"][unit - 1] = None

    def _kda_backward(self, i: int, p: dict, x, bias, dy):
        """A KDA layer's vjp through its three programs. The core's inputs
        are computed twice, before the part after the core and again
        before the core's vjp, so that at most one of the layer's [S,
        heads x head_dim] sets lives beside a vjp's own buffers."""
        run_in, in_vjp, run_core, core_vjp = self._kda_programs
        p_in, p_rest = self._kda_split(p)
        dp, dx, do = self._layer_vjp(i)(
            p_rest, x, run_core(*run_in(p_in, x)), bias, dy)
        dp_in, dx_in = in_vjp(p_in, x, core_vjp(run_in(p_in, x), do))
        dp["input_layernorm"] = dp["input_layernorm"] + \
            dp_in.pop("input_layernorm")
        dp.update(dp_in)
        return dp, dx + dx_in

    def loss(self, ctx: dict) -> float:
        loss = float(ctx["loss"])
        self._record(ctx["tokens"], ctx["kda_chunks"])
        return loss

    def _record(self, tokens: int, kda_chunks: int) -> None:
        """The step's tokens, expert loads and KDA chunks on /vars."""
        if self._series is None:
            from brpc_tpu.observability import metrics

            self._series = metrics.trainer_series()
        sizes = np.stack([np.asarray(s) for _, s in self.last_routes]) \
            if self.last_routes else np.zeros((0, len(self.cfg.held)))
        self.expert_slots += sizes.astype(np.int64)
        self._series["tokens"].add(tokens)
        if kda_chunks:
            self._series["kda_chunks"].add(kda_chunks)
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

