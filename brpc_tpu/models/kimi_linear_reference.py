"""The plain reference of Kimi Linear's hybrid block as ``mla_moe.py`` runs
it: the decoder, its loss and its gradients in straightforward
``jax.numpy`` at float32.

No kernel, no chunks, no per-layer split, no sorting: the whole stack is
one function of the parameter dict and ``jax.grad`` gives the gradients.
Kimi Delta Attention is the token-by-token recurrence of the Kimi Linear
report (arXiv:2510.26692) and of flash-linear-attention's naive
``KimiDeltaAttention``, one ``lax.scan`` step a token:

    S <- Diag(exp g_t) S;   S <- S + beta_t k_t (v_t - S^T k_t)^T;
    o_t = S^T q_t

with q, k, v from a causal depthwise convolution and SiLU, q and k
L2-normalised per head (q scaled by d^-1/2), beta a sigmoid per head and g
``-exp(A_log) softplus(x W_fa W_fb + dt_bias)``; the output is RMS-normed
per head, gated by ``sigmoid(x W_ga W_gb)`` and projected. Latent attention
is one causal softmax over every position, without rotary embedding
(``mla_use_nope``). The expert layer is ``mla_moe_reference.moe``: sigmoid
scores, the top-k of scores plus the correction bias, weights renormalised
over the chosen experts and scaled, the held experts a dense loop. Call it
under ``jax.default_matmul_precision("highest")`` (``loss_and_grads``
does), or a TPU computes float32 matmuls in a lower precision.

Departures from the published model, shared with the program: the chip's
share of an expert-parallel deployment (only ``held`` experts add to a
layer's output) and of the vocabulary (logits and loss over the held
rows), and fewer layers. ``select`` (per expert layer, [T, k] int32) routes
each token to given experts, with weights from its own scores, as in
``mla_moe_reference``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from brpc_tpu.models.mla_moe_reference import mlp, moe, rms_norm


def conv(x, w):
    """y_t = sum_j w[:, j] x_{t-K+1+j}, zeros before the first token."""
    kw, s = w.shape[1], x.shape[1]
    xp = jnp.concatenate([jnp.zeros(x.shape[:1] + (kw - 1,) + x.shape[2:],
                                    x.dtype), x], axis=1)
    return sum(w[:, j] * xp[:, j:j + s] for j in range(kw))


def delta_rule(q, k, v, g, beta):
    """The gated delta rule a token at a time. q, k, g [B, S, H, d_k]; v
    [B, S, H, d_v]; beta [B, S, H]. -> o [B, S, H, d_v]."""
    def step(state, t):
        qt, kt, vt, gt, bt = t                   # [B, H, d] and [B, H]
        state = jnp.exp(gt)[..., None] * state
        pred = jnp.einsum("bhkv,bhk->bhv", state, kt)
        state = state + bt[..., None, None] * kt[..., :, None] \
            * (vt - pred)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    b, _, h, dk = k.shape
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    seq = [jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)]
    _, o = jax.lax.scan(step, s0, seq)
    return jnp.moveaxis(o, 0, 1)


def kda_attention(cfg, p, x):
    b, s, _ = x.shape
    nh, d = cfg.kda_heads, cfg.kda_head_dim

    def heads(y):
        return y.reshape(b, s, nh, d)

    def unit(y):
        return y / jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)

    q, k, v = (heads(jax.nn.silu(conv(x @ p[f"self_attn.{n}_proj"].T,
                                      p[f"self_attn.{n}_conv1d"])))
               for n in "qkv")
    q, k = unit(q) * d ** -0.5, unit(k)
    beta = jax.nn.sigmoid(x @ p["self_attn.b_proj"].T)
    f = (x @ p["self_attn.f_a_proj"].T) @ p["self_attn.f_b_proj"].T
    g = -jnp.exp(p["self_attn.A_log"])[:, None] * jax.nn.softplus(
        heads(f + p["self_attn.dt_bias"]))
    o = rms_norm(delta_rule(q, k, v, g, beta), p["self_attn.o_norm"],
                 cfg.eps)
    gate = (x @ p["self_attn.g_a_proj"].T) @ p["self_attn.g_b_proj"].T
    o = o * jax.nn.sigmoid(heads(gate))
    return o.reshape(b, s, nh * d) @ p["self_attn.o_proj"].T


def mla_attention(cfg, p, x):
    """Latent attention without rotary embedding: one causal softmax."""
    b, s, _ = x.shape
    nh, dn, dr, dv = cfg.heads, cfg.qk_nope, cfg.qk_rope, cfg.v_dim
    q = (x @ p["self_attn.q_proj"].T).reshape(b, s, nh, dn + dr)
    kv_a = x @ p["self_attn.kv_a_proj_with_mqa"].T
    c_kv = rms_norm(kv_a[..., :cfg.kv_lora_rank],
                    p["self_attn.kv_a_layernorm"], cfg.eps)
    kv = (c_kv @ p["self_attn.kv_b_proj"].T).reshape(b, s, nh, dn + dv)
    key = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        kv_a[:, :, None, cfg.kv_lora_rank:], (b, s, nh, dr))], axis=-1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, key) * (dn + dr) ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                     kv[..., dn:])
    return out.reshape(b, s, nh * dv) @ p["self_attn.o_proj"].T


def layer(cfg, i, p, x, bias=None, select=None):
    attn = kda_attention if i in cfg.kda_layers else mla_attention
    h = x + attn(cfg, p, rms_norm(x, p["input_layernorm"], cfg.eps))
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
        x, chose = layer(cfg, i, p, x, None if j < 0 else bias[j],
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
