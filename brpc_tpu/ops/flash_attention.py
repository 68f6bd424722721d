"""Flash attention as a Pallas TPU kernel — block-tiled online softmax.

The device-side hot op of the long-context path (SURVEY §5/§7). The r4
implementation materialized the full [b, s, s/N] score block through HBM
(31% MFU); this kernel keeps every intermediate in VMEM: for each
(batch·head, q-block) the k/v blocks stream through the MXU while a
running (m, l, acc) triple — block max, normalizer, weighted accumulator —
is revisited in place across the innermost grid dimension. LLM-shaped:
multi-head [b, h, s, d], causal masking (fully-masked k-blocks are skipped
before touching the MXU), grouped-query attention (kv_heads | heads).

Two entry points:
- flash_attention(q, k, v, causal=...): full attention on one device.
- flash_attention_carry(...): one accumulation step with explicit
  (m, l, acc) carries + runtime q/kv position offsets — the building block
  ring_attention chains around the ICI ring (each hop folds a visiting
  kv shard into the resident queries' state).

Follows the public flash/blockwise-attention formulation (Dao et al.,
Liu et al.); implementation is original. Masking uses a large finite
negative (not -inf) so exp(m_prev - m_new) at the never-attended state is
exactly 0 and never NaN; rows with no legal key this step keep p == 0 via
an explicit mask select, so a later ring hop cannot inherit contamination.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30  # "never attended" sentinel: finite so corrections stay 0, not NaN


def _pick_block(seq: int, want: int) -> int:
    b = min(want, seq)
    while seq % b != 0:
        b //= 2
    return max(b, 1)


def _carry_kernel(off_ref, q_ref, k_ref, v_ref, m_in, l_in, acc_in,
                  m_out, l_out, acc_out, *, scale, causal, block_q, block_k):
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _():
        m_out[...] = m_in[...]
        l_out[...] = l_in[...]
        acc_out[...] = acc_in[...]

    # Global positions of this q-block's rows and k-block's columns (the
    # offsets are runtime scalars: ring hops shift the kv origin).
    q_pos = off_ref[0] + pl.program_id(1) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = off_ref[1] + jk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def attend():
        q = q_ref[0, 0]  # [block_q, d]
        k = k_ref[0, 0]  # [block_k, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            mask = q_pos >= k_pos
            s = jnp.where(mask, s, _NEG)
        m_prev = m_out[0, 0, :, 0]  # [block_q]
        l_prev = l_out[0, 0, :, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        if causal:
            # A row with NO legal key this block would otherwise see
            # exp(_NEG - _NEG) = 1 per column: force those lanes to zero.
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_out[0, 0, :, 0] = m_new
        l_out[0, 0, :, 0] = l_prev * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_out[0, 0] = acc_out[0, 0] * corr[:, None] + pv

    if causal:
        # Skip k-blocks entirely above the diagonal (no row attends):
        # first column position > last row position.
        first_k = off_ref[1] + jk * block_k
        last_q = off_ref[0] + pl.program_id(1) * block_q + (block_q - 1)
        @pl.when(first_k <= last_q)
        def _():
            attend()
    else:
        attend()


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_carry(q, k, v, m, l, acc, offsets, *, causal: bool = False,
                          block_q: int = 1024, block_k: int = 1024,
                          interpret: bool | None = None):
    """One flash accumulation pass: fold k/v into (m, l, acc) for q.

    q: [b, h, sq, d] (bf16/f32); k, v: [b, hkv, sk, d] with hkv | h (GQA).
    m, l: [b, h, sq, 1] f32 (init to the NEG sentinel / zeros — the
    trailing singleton keeps the block's last-two dims TPU-tileable);
    acc: f32 [b, h, sq, d]. offsets: int32[2] = (global q position, global
    kv position) — runtime values, so ring hops reuse the compiled kernel.
    Returns updated (m, l, acc); finalize with flash_finalize.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert h % hkv == 0, "q heads must be a multiple of kv heads"
    group = h // hkv
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    grid = (b * h, sq // bq, sk // bk)
    scale = 1.0 / (d ** 0.5)

    def qmap(bh, iq, jk):
        return (bh // h, bh % h, iq, 0)

    def kvmap(bh, iq, jk):
        return (bh // h, (bh % h) // group, jk, 0)

    # m/l share q's (bh, iq) walk; their trailing dim is the singleton.
    mlmap = qmap

    kernel = functools.partial(_carry_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk)
    m2, l2, acc2 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # offsets
            pl.BlockSpec((1, 1, bq, d), qmap),                # q
            pl.BlockSpec((1, 1, bk, d), kvmap),               # k
            pl.BlockSpec((1, 1, bk, d), kvmap),               # v
            pl.BlockSpec((1, 1, bq, 1), mlmap),               # m in
            pl.BlockSpec((1, 1, bq, 1), mlmap),               # l in
            pl.BlockSpec((1, 1, bq, d), qmap),                # acc in
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, 1), mlmap),
            pl.BlockSpec((1, 1, bq, 1), mlmap),
            pl.BlockSpec((1, 1, bq, d), qmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
        ],
        # bh and q-blocks are independent; only the k-block walk carries
        # the online-softmax state (the revisited out blocks).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(offsets.astype(jnp.int32), q, k, v, m, l, acc)
    return m2, l2, acc2


def flash_init(b: int, h: int, sq: int, d: int):
    """Fresh (m, l, acc) carries — the 'attended to nothing yet' state."""
    return (jnp.full((b, h, sq, 1), _NEG, jnp.float32),
            jnp.zeros((b, h, sq, 1), jnp.float32),
            jnp.zeros((b, h, sq, d), jnp.float32))


def flash_finalize(l, acc, dtype):
    """acc / l with never-attended rows (l == 0) mapped to 0, not NaN."""
    safe = jnp.where(l > 0, l, 1.0)  # l: [b, h, sq, 1] broadcasts over d
    return (acc / safe).astype(dtype)


def flash_attention(q, k, v, *, causal: bool = False, block_q: int = 1024,
                    block_k: int = 1024, interpret: bool | None = None):
    """Full single-device attention, [b, h, s, d] -> [b, h, s, d]."""
    b, h, sq, d = q.shape
    m, l, acc = flash_init(b, h, sq, d)
    offsets = jnp.zeros((2,), jnp.int32)
    m, l, acc = flash_attention_carry(
        q, k, v, m, l, acc, offsets, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret)
    return flash_finalize(l, acc, q.dtype)


def dense_attention_mh(q, k, v, *, causal: bool = False):
    """Dense multi-head reference oracle (materializes [b,h,s,s])."""
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    if hkv != h:
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (d ** 0.5)
    if causal:
        qi = jnp.arange(sq)[:, None]
        ki = jnp.arange(sk)[None, :]
        s = jnp.where(qi >= ki, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
