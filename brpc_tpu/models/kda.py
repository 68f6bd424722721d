"""Kimi Delta Attention (KDA), the linear-attention layer of Kimi Linear
(Moonshot AI 2025, arXiv:2510.26692), as a pure function of one layer's
parameters, with the chunked form of its gated delta rule.

Per head (d_k = d_v = d), token t, ``x`` the normed layer input:

    q, k, v = SiLU(causal depthwise conv(x W_{q,k,v}))
    q <- q / |q| * d^-1/2,  k <- k / |k|            (L2 per head, eps 1e-6)
    beta_t  = sigmoid(x W_b)                          (one value per head)
    g_t     = -exp(A_log) * softplus(x W_fa W_fb + dt_bias)   (in R^d_k)
    S_t     = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t     = S_t^T q_t                               (S_0 = 0)
    out     = W_o (RMSNorm_head(o) * w_onorm * sigmoid(x W_ga W_gb))

The chunked core (``chunk_gated_delta``) computes the same ``o`` by chunks
of C tokens. With Gamma the per-channel cumulative log-decay inside a
chunk, S_0 the state entering it and U its rows' delta updates,

    (I + M) U = diag(beta) (V - K_A S_0),    K_A = k * exp(Gamma)
    O         = Q_A S_0 + P U,               Q_A = q * exp(Gamma)
    S_C       = exp(Gamma_C) * S_0 + K_B^T U,  K_B = k * exp(Gamma_C - Gamma)

where M (strictly lower) and P (lower, diagonal included) hold
beta_r k_r.(k_s exp(Gamma_r - Gamma_s)) and q_r.(k_s exp(Gamma_r - Gamma_s)).
The unit lower-triangular solve is the WY/UT transform. exp(Gamma_r -
Gamma_s) is never factorised across a whole chunk (at the seeded gates
Gamma falls to about -100 within 64 tokens, and exp(100) overflows fp32):
the chunk is cut into sub-chunks of 16; a pair inside one sub-chunk is
taken pairwise in log space, and a pair across sub-chunks is factorised
at the boundary before the later one, where both factors are at most 1.
A ``lax.scan`` carries S over groups of 8 chunks: a step takes the parts
within its chunks at once, then the state through them in order. Its
gradient (a ``custom_vjp``) keeps the state entering each group and
nothing else, and computes each group's forward again on the way back.
The recurrence's arithmetic is fp32 with the highest matmul precision; the
projections take ``dtype`` inputs with fp32 accumulation.

The layer is three pure functions of its parameters: ``kda_in`` (the
projections, convolutions, norms and gates), ``kda_core`` (the chunked
recurrence) and ``kda_out`` (the output's norm, gate and projection).
The trainer runs each as a program of its own: a
16,384-token sequence makes [S, 4096] fp32 arrays of 268 MB, and one
program's vjp over the whole layer holds some twenty of them.

Named scopes: ``kda`` (the whole attention, set by the caller), inside it
``kda_conv``, ``kda_gate`` and ``kda_core`` (the chunked recurrence
alone). The plain token-by-token reference is ``kimi_linear_reference.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
SUB_CHUNK = 16
GROUP = 8            # chunks a step of the scan takes
L2_EPS = 1e-6

# The layer's tensors within ``self_attn``, with their [out, in] shapes as
# functions of (hidden, heads, head_dim, conv width).
TENSORS = (
    ("q_proj", lambda h, n, d, w: (n * d, h)),
    ("k_proj", lambda h, n, d, w: (n * d, h)),
    ("v_proj", lambda h, n, d, w: (n * d, h)),
    ("q_conv1d", lambda h, n, d, w: (n * d, w)),
    ("k_conv1d", lambda h, n, d, w: (n * d, w)),
    ("v_conv1d", lambda h, n, d, w: (n * d, w)),
    ("f_a_proj", lambda h, n, d, w: (d, h)),
    ("f_b_proj", lambda h, n, d, w: (n * d, d)),
    ("A_log", lambda h, n, d, w: (n,)),
    ("dt_bias", lambda h, n, d, w: (n * d,)),
    ("b_proj", lambda h, n, d, w: (n, h)),
    ("g_a_proj", lambda h, n, d, w: (d, h)),
    ("g_b_proj", lambda h, n, d, w: (n * d, d)),
    ("o_norm", lambda h, n, d, w: (d,)),
    ("o_proj", lambda h, n, d, w: (h, n * d)),
)


# The tensors ``kda_in`` reads; ``kda_out`` reads the rest.
IN_TENSORS = ("q_proj", "k_proj", "v_proj", "q_conv1d", "k_conv1d",
              "v_conv1d", "f_a_proj", "f_b_proj", "A_log", "dt_bias",
              "b_proj")


def shapes(hidden: int, heads: int, head_dim: int, conv: int):
    """[(name within ``self_attn``, shape)] of one KDA layer."""
    return [(n, f(hidden, heads, head_dim, conv)) for n, f in TENSORS]


def gate_init(key, heads: int, head_dim: int):
    """(A_log, dt_bias) as Mamba-2 initialises its gates: A uniform in
    [1, 16] per head, dt log-uniform in [0.001, 0.1] per channel and
    dt_bias its softplus inverse. Per-token decays then run from e^-1.6 to
    e^-0.001, so state crosses chunks."""
    ka, kd = jax.random.split(key)
    a_log = jnp.log(jax.random.uniform(ka, (heads,), jnp.float32, 1.0, 16.0))
    dt = jnp.exp(jax.random.uniform(kd, (heads * head_dim,), jnp.float32,
                                    math.log(1e-3), math.log(0.1)))
    return a_log, dt + jnp.log(-jnp.expm1(-dt))


def _mm(x, w, dtype):
    """x @ w.T for an [out, in] weight: inputs in ``dtype``, fp32 out."""
    return lax.dot_general(x.astype(dtype), w.astype(dtype),
                           (((x.ndim - 1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _ein(spec, *xs):
    return jnp.einsum(spec, *xs, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def causal_conv(x, w):
    """Causal depthwise convolution over the sequence: x [B, S, D], w [D,
    K]; output t sees inputs t-K+1..t, w[:, K-1] weighting x_t."""
    kw = w.shape[1]
    xp = jnp.pad(x, ((0, 0), (kw - 1, 0), (0, 0)))
    s = x.shape[1]
    return sum(xp[:, j:j + s] * w[:, j] for j in range(kw))


def l2_normalize(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


# ---- the chunked core ----

def _decay_products(lhs, k, gam, sub: int, strict: bool):
    """[..., C, C]: lhs_r . (k_s * exp(gam_r - gam_s)) for s <= r (s < r
    when ``strict``), 0 above; lhs, k, gam [..., C, d]. Inside a sub-chunk
    pairwise in log space; across sub-chunks factorised at the boundary
    before the later sub-chunk, every factor at most 1."""
    c = lhs.shape[-2]
    rows = []
    for i in range(0, c, sub):
        g_i = gam[..., i:i + sub, :]
        diff = g_i[..., :, None, :] - g_i[..., None, :, :]        # [s, s, d]
        r = jnp.arange(sub)
        keep = (r[:, None] > r[None, :]) if strict else \
            (r[:, None] >= r[None, :])
        e = jnp.exp(jnp.where(keep[..., None], diff, -jnp.inf))
        diag = jnp.sum(lhs[..., i:i + sub, None, :]
                       * k[..., None, i:i + sub, :] * e, axis=-1)
        parts = []
        if i:
            ref = gam[..., i - 1:i, :]
            lf = lhs[..., i:i + sub, :] * jnp.exp(g_i - ref)
            kf = k[..., :i, :] * jnp.exp(ref - gam[..., :i, :])
            parts.append(_ein("...rd,...sd->...rs", lf, kf))
        parts.append(diag)
        if i + sub < c:
            parts.append(jnp.zeros(diag.shape[:-1] + (c - i - sub,),
                                   jnp.float32))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _intra(q, k, v, g, beta):
    """What a chunk computes without the state entering it, for a batch of
    chunks ([..., C, d]; beta [..., C, 1]): (W_k, W_v, K_B, a, Q_A, P)."""
    dv = v.shape[-1]
    sub = math.gcd(q.shape[-2], SUB_CHUNK)
    gam = jnp.cumsum(g, axis=-2)
    m = beta * _decay_products(k, k, gam, sub, strict=True)
    p = _decay_products(q, k, gam, sub, strict=False)
    rhs = jnp.concatenate([beta * v, beta * k * jnp.exp(gam)], axis=-1)
    w = lax.linalg.triangular_solve(m, rhs, left_side=True, lower=True,
                                    unit_diagonal=True)
    last = gam[..., -1:, :]
    return (w[..., dv:], w[..., :dv], k * jnp.exp(last - gam),
            jnp.exp(last[..., 0, :]), q * jnp.exp(gam), p)


def _chunk_step(s, w_k, w_v, k_b, a, q_a, p):
    """One chunk from the state ``s`` [..., d_k, d_v] entering it. -> (the
    state leaving it, the chunk's output [..., C, d_v])."""
    u = w_v - _ein("...ck,...kv->...cv", w_k, s)
    o = _ein("...ck,...kv->...cv", q_a, s) + _ein("...cs,...sv->...cv", p, u)
    return a[..., None] * s + _ein("...ck,...cv->...kv", k_b, u), o


def _group(s, xs):
    """A group of chunks: their parts without the state at once, then the
    state through them in order. xs: q, k, v, g [B, G, C, H, d] and beta
    [B, G, C, H, 1], the sequence's own layout. -> (state leaving the
    group, o [B, G, C, H, d_v])."""
    parts = _intra(*(jnp.moveaxis(x, 3, 1) for x in xs))  # [B, H, G, C, .]
    out = []
    for j in range(xs[0].shape[1]):
        s, o = _chunk_step(s, *(x[:, :, j] for x in parts))
        out.append(o)
    return s, jnp.moveaxis(jnp.stack(out, axis=2), 1, 3)


@jax.custom_vjp
def _scan_groups(xs):
    """o of every group ([N_g, B, G, C, H, d_v]) from S_0 = 0."""
    return _groups_fwd(xs)[0]


def _groups_fwd(xs):
    b, _, _, h, dk = xs[1].shape[1:]
    s0 = jnp.zeros((b, h, dk, xs[2].shape[-1]), jnp.float32)
    _, (states, o) = lax.scan(
        lambda s, x: (lambda s1, o: (s1, (s, o)))(*_group(s, x)), s0, xs)
    return o, (xs, states)


def _groups_bwd(res, do):
    """Back through the groups, last first, each group's forward computed
    again from the state that entered it: the one residual a group keeps
    besides its inputs."""
    xs, states = res

    def step(ds, item):
        x, s, dog = item
        _, pull = jax.vjp(_group, s, x)
        ds_in, dx = pull((ds, dog))
        return ds_in, dx

    _, dxs = lax.scan(step, jnp.zeros_like(states[0]), (xs, states, do),
                      reverse=True)
    return (dxs,)


_scan_groups.defvjp(_groups_fwd, _groups_bwd)


def chunk_gated_delta(q, k, v, g, beta, chunk: int):
    """The gated delta rule by chunks. q, k, g [B, T, H, d_k]; v [B, T,
    H, d_v]; beta [B, T, H]; g the per-channel log-decay (<= 0). Chunks go
    through the state GROUP at a time, each group a step of one scan
    over the sequence in its own layout (one sequence: no copy). A length
    that is not a multiple of ``chunk`` is padded with tokens that neither
    decay nor write the state. -> o [B, T, H, d_v] fp32."""
    b, t, h, _ = k.shape
    dv = v.shape[-1]
    n = -(-t // chunk)
    group = min(GROUP, n)
    n_g = -(-n // group)
    pad = n_g * group * chunk - t

    def groups(x):
        x = x.astype(jnp.float32)
        if x.ndim == 3:
            x = x[..., None]
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        x = x.reshape(b, n_g, group, chunk, h, x.shape[-1])
        return jnp.swapaxes(x, 0, 1)               # [N_g, B, G, C, H, d]

    o = _scan_groups(tuple(groups(x) for x in (q, k, v, g, beta)))
    o = jnp.swapaxes(o, 0, 1).reshape(b, n_g * group * chunk, h, dv)
    return o[:, :t]


# ---- the layer ----

def kda_in(p: dict, x, *, heads: int, head_dim: int, dtype):
    """The KDA layer up to its core, of normed ``x`` [B, S, hidden]: (q, k,
    v, g [B, S, heads, head_dim], beta [B, S, heads]), fp32."""
    b, s, _ = x.shape

    def proj(name):
        return _mm(x, p[f"self_attn.{name}"], dtype)

    def split(y):
        return y.reshape(b, s, heads, head_dim)

    with jax.named_scope("kda_conv"):
        q, k, v = (split(jax.nn.silu(causal_conv(
            proj(f"{n}_proj"), p[f"self_attn.{n}_conv1d"])))
            for n in "qkv")
    q = l2_normalize(q) * head_dim ** -0.5
    k = l2_normalize(k)
    with jax.named_scope("kda_gate"):
        beta = jax.nn.sigmoid(proj("b_proj"))
        f = _mm(proj("f_a_proj"), p["self_attn.f_b_proj"], dtype) \
            + p["self_attn.dt_bias"]
        g = -jnp.exp(p["self_attn.A_log"])[:, None] * jax.nn.softplus(
            split(f))
    return q, k, v, g, beta


def kda_core(q, k, v, g, beta, chunk: int):
    """The chunked recurrence alone (``chunk_gated_delta``), in the
    ``kda_core`` scope."""
    with jax.named_scope("kda_core"):
        return chunk_gated_delta(q, k, v, g, beta, chunk)


def kda_out(p: dict, x, o, *, eps: float, dtype):
    """The KDA layer after its core: the core's output ``o`` [B, S, heads,
    head_dim] normed per head, gated by the normed input ``x`` and
    projected to [B, S, hidden]."""
    b, s, heads, head_dim = o.shape
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * p["self_attn.o_norm"]
    gate = _mm(_mm(x, p["self_attn.g_a_proj"], dtype),
               p["self_attn.g_b_proj"], dtype)
    o = o * jax.nn.sigmoid(gate.reshape(b, s, heads, head_dim))
    return _mm(o.reshape(b, s, heads * head_dim), p["self_attn.o_proj"],
               dtype)


def chunks(seqs: int, seq_len: int, chunk: int) -> int:
    """Chunks the core runs over ``seqs`` sequences of ``seq_len``."""
    return seqs * -(-seq_len // chunk)
