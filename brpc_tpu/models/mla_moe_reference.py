"""The plain reference of ``mla_moe.py``: Moonlight-16B-A3B's decoder, its
loss and its gradients in straightforward ``jax.numpy`` at float32.

No kernel, no per-layer split, no sorting: the whole stack is one function
of the parameter dict, ``jax.grad`` gives the gradients, attention is one
causal softmax over every position, and the held experts are a dense loop,
each applied to every token with the weight its router gave (zero where
the token did not choose it). Call it under
``jax.default_matmul_precision("highest")`` (``loss_and_grads`` does), or
a TPU computes float32 matmuls in a lower precision.

It follows the published DeepSeek-V3 code (``modeling_deepseek.py``):
rotary embedding with DeepSeek's interleaved-to-halves permutation and
``rotate_half``, ``softmax_scale = d_qk ** -0.5`` (no YaRN: Moonlight's
config has no ``rope_scaling``), sigmoid scores, top-k of scores plus the
correction bias, weights normalised over the chosen experts (``+1e-20``)
and scaled by ``routed_scaling_factor``. Departures from the published
model, shared with the program: the chip's share of an expert-parallel
deployment (only ``held`` experts add to a layer's output) and of the
vocabulary (logits and loss over the held rows), and fewer layers.

``select`` (optional, per expert layer, [T, k] int32) makes the reference
route each token to those experts instead of its own top-k, with weights
from its own scores: the check hands it the program's choices where
rounding broke a tie the other way, and counts how often.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return w * (x / jnp.sqrt(var + eps))


def rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], axis=-1)


def apply_rope(x, theta):
    """DeepSeek-V3's ``apply_rotary_pos_emb`` on x [B, heads, S, d]."""
    b, nh, s, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb), jnp.sin(emb)
    x = x.reshape(b, nh, s, d // 2, 2).swapaxes(3, 4).reshape(b, nh, s, d)
    return x * cos + rotate_half(x) * sin


def attention(cfg, p, x):
    b, s, _ = x.shape
    nh, dn, dr, dv = cfg.heads, cfg.qk_nope, cfg.qk_rope, cfg.v_dim
    q = (x @ p["self_attn.q_proj"].T).reshape(b, s, nh, dn + dr)
    q = q.transpose(0, 2, 1, 3)
    kv_a = x @ p["self_attn.kv_a_proj_with_mqa"].T
    c_kv = rms_norm(kv_a[..., :cfg.kv_lora_rank],
                    p["self_attn.kv_a_layernorm"], cfg.eps)
    k_pe = kv_a[..., cfg.kv_lora_rank:].reshape(b, s, 1, dr)
    k_pe = k_pe.transpose(0, 2, 1, 3)
    kv = (c_kv @ p["self_attn.kv_b_proj"].T).reshape(b, s, nh, dn + dv)
    kv = kv.transpose(0, 2, 1, 3)
    q_pe = apply_rope(q[..., dn:], cfg.rope_theta)
    k_pe = apply_rope(k_pe, cfg.rope_theta)
    query = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    key = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_pe, (b, nh, s, dr))], axis=-1)
    scores = query @ key.swapaxes(-1, -2) * (dn + dr) ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ kv[..., dn:]
    out = out.transpose(0, 2, 1, 3).reshape(b, s, nh * dv)
    return out @ p["self_attn.o_proj"].T


def mlp(p, prefix, x):
    gate = x @ p[f"{prefix}.gate_proj"].T
    up = x @ p[f"{prefix}.up_proj"].T
    return (jax.nn.silu(gate) * up) @ p[f"{prefix}.down_proj"].T


def moe(cfg, p, x, bias, select=None):
    """-> (y, the reference's own top-k [T, k])."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    scores = jax.nn.sigmoid(x @ p["mlp.gate"].T)
    own = jax.lax.top_k(scores + bias, cfg.top_k)[1]
    sel = own if select is None else select
    w = jnp.take_along_axis(scores, sel, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * cfg.scaling
    y = mlp(p, "mlp.shared_experts", x)
    for e in cfg.held:
        weight = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
        y = y + weight[:, None] * mlp(p, f"mlp.experts.{e}", x)
    return y.reshape(shape), own


def layer(cfg, i, p, x, bias=None, select=None):
    h = x + attention(cfg, p, rms_norm(x, p["input_layernorm"], cfg.eps))
    hn = rms_norm(h, p["post_attention_layernorm"], cfg.eps)
    if i < cfg.first_dense:
        return h + mlp(p, "mlp", hn), None
    y, own = moe(cfg, p, hn, bias, select)
    return h + y, own


def loss_fn(cfg, params: Dict[str, jax.Array], ids, labels, bias,
            select: Optional[Sequence] = None):
    """-> (mean next-token cross-entropy, [own top-k of each expert
    layer])."""
    x = params["model.embed_tokens.weight"][ids]
    own: List = []
    for i in range(cfg.layers):
        pre = f"model.layers.{i}."
        p = {n[len(pre):-len(".weight")]: v for n, v in params.items()
             if n.startswith(pre)}
        j = i - cfg.first_dense
        x, chose = layer(cfg, i, p, x,
                         None if j < 0 else bias[j],
                         None if j < 0 or select is None else select[j])
        if chose is not None:
            own.append(chose)
    x = rms_norm(x, params["model.norm.weight"], cfg.eps)
    logits = x @ params["lm_head.weight"].T
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked), own


def loss_and_grads(cfg, params, ids, labels, bias, select=None):
    """-> (loss, gradients by name, own top-k of each expert layer), at
    float32 with the highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        (loss, own), grads = jax.jit(
            jax.value_and_grad(
                lambda p: loss_fn(cfg, p, ids, labels, bias, select),
                has_aux=True))(params)
    return loss, grads, own
