"""Ring attention: exact attention over a sequence-sharded mesh axis.

The long-context primitive (SURVEY §7 "long-context and distributed are
first-class"; the reference has no analog — its RDMA fabric moves bytes,
ours moves ATTENTION BLOCKS). Sequence length S is sharded S/N per device
on the ``shard`` axis; queries stay resident while key/value blocks rotate
around the ring with ``jax.lax.ppermute`` — after N-1 hops every query has
attended to every key, and only one S/N-sized KV block is ever in flight
per device (memory O(S/N), bandwidth fully on ICI neighbor links).

The per-hop compute is the Pallas flash kernel
(brpc_tpu/ops/flash_attention.py): block-tiled online softmax in VMEM —
no [s, s/N] score materialization — multi-head [b, h, s, d] with causal
masking and GQA. Each hop folds the visiting kv shard into the resident
queries' (m, l, acc) carries; the kv origin offset is a runtime scalar so
every hop reuses one compiled kernel and causal masks stay globally
correct across shards.

Numerically EXACT full attention (verified against the dense reference in
tests/test_data_plane.py and tests/test_flash_attention.py), not an
approximation. Public papers this follows: blockwise/ring attention
(Liu et al.) and the flash-attention online softmax (Dao et al.); the
implementation is original and shard_map-native so XLA schedules the
ppermute against the block matmuls.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from brpc_tpu.ops.flash_attention import (flash_attention_carry,
                                          flash_finalize, flash_init)
from brpc_tpu.parallel.mesh import SHARD_AXIS


def ring_attention(mesh: Mesh, axis: str = SHARD_AXIS, *,
                   causal: bool = False, block_q: int = 1024,
                   block_k: int = 1024):
    """Builds a jitted ``fn(q, k, v) -> out`` for sequence-sharded exact
    attention.

    Shapes (global): [batch, seq, d] (single-head) or [batch, heads, seq,
    d]; kv may carry fewer heads (GQA: kv_heads | heads). seq must divide
    by the mesh's ``axis`` size; in/out layouts shard the SEQUENCE
    dimension — the long-context regime where activations do not fit one
    device. causal=True masks by GLOBAL position (shard offsets ride into
    the kernel as runtime scalars).
    """
    n = mesh.shape[axis]
    fwd = [(i, (i + 1) % n) for i in range(n)]

    def _ring4(q, k, v):  # local blocks: [b, h, seq/n, d]
        b, h, sq, d = q.shape
        idx = jax.lax.axis_index(axis)
        q_off = idx * sq
        m, l, acc = flash_init(b, h, sq, d)

        def fold(kv_src, k_blk, v_blk, m, l, acc):
            offsets = jnp.stack([q_off, kv_src * sq]).astype(jnp.int32)
            return flash_attention_carry(
                q, k_blk, v_blk, m, l, acc, offsets, causal=causal,
                block_q=min(block_q, sq), block_k=min(block_k, sq))

        # Hop 0: the resident kv shard, no collective. Then exactly n-1
        # permute-and-fold hops — the final block is consumed where it
        # lands, never rotated onward.
        m, l, acc = fold(idx, k, v, m, l, acc)

        # Unrolled: n is the (small, static) mesh axis size, and unrolling
        # lets XLA overlap each ICI hop with the previous fold's matmuls.
        k_blk, v_blk = k, v
        for t in range(n - 1):
            # Rotate first; XLA overlaps the ICI hop with the matmuls.
            k_blk = jax.lax.ppermute(k_blk, axis, fwd)
            v_blk = jax.lax.ppermute(v_blk, axis, fwd)
            # After t+1 rotations this shard holds device (idx - t - 1)'s
            # kv block — its global offset drives the causal mask.
            src = jax.lax.rem(idx - t - 1 + n, n)
            m, l, acc = fold(src, k_blk, v_blk, m, l, acc)
        return flash_finalize(l, acc, q.dtype)

    spec4 = P(None, None, axis, None)
    ring4 = shard_map(_ring4, mesh=mesh, check_vma=False,
                      in_specs=(spec4, spec4, spec4), out_specs=spec4)

    @jax.jit
    def run(q, k, v):
        if q.ndim == 3:  # single-head convenience: [b, s, d]
            out = ring4(q[:, None], k[:, None], v[:, None])
            return out[:, 0]
        return ring4(q, k, v)

    return run


def dense_attention_reference(q: jax.Array, k: jax.Array,
                              v: jax.Array) -> jax.Array:
    """Single-device full softmax attention — the correctness oracle."""
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v)
