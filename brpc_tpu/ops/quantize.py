"""Pallas dequantization kernel for the quantized tensor wire format.

The receive side of the codec (brpc_tpu/runtime/codec.py): block-quantized
codes + per-block fp32 scales -> the logical fp32 tensor. On TPU this is
where the bandwidth win compounds — the H2D DMA moves ~4x fewer bytes
(int8 codes instead of fp32) and the widen-and-scale happens on-chip in
one VMEM pass, fused into the ``device_put`` path the same way
``fused_momentum_update`` fuses the optimizer (ops/fused_update.py).

Auto-routing follows fused_update exactly: the compiled Pallas kernel on
TPU, the identical plain-jnp math elsewhere; interpret=True keeps the
kernel itself testable on CPU (tile-by-tile through the interpreter —
fine for kernel-parity tests, far too slow for traffic).

Tiling: int8/fp8 VMEM tiles need >= 32 sublanes (pallas_guide.md dtype
table), so codes reshape to (nblocks, block) and tile as (32, block)
with the matching (32, 1) scale column. The tile spans the whole block
axis, so any block compiles (a block that is not a lane multiple of 128
pads inside VMEM); the codec default of 256 is two lanes exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_TILE_ROWS = 32  # int8/fp8 min sublane count


def _dequant_kernel(q_ref, s_ref, out_ref):
    out_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("block", "n", "shape", "interpret"))
def dequantize_blocks(q, scales, *, block: int, n: int, shape,
                      interpret: bool | None = None):
    """codes (n,) + scales (ceil(n/block),) -> fp32 tensor of ``shape``.

    ``q`` is an int8 or float8_e4m3fn device array of the raw wire codes;
    ``interpret=None`` auto-selects like fused_momentum_update: compiled
    Pallas on TPU, plain jnp elsewhere.
    """
    if interpret is None:
        if jax.default_backend() != "tpu":
            return dequantize_reference(q, scales, block=block, n=n,
                                        shape=shape)
        interpret = False
    nblocks = -(-n // block)
    qp = jnp.pad(q, (0, nblocks * block - n)).reshape(nblocks, block)
    sp = scales.reshape(nblocks, 1)
    pad_rows = (-nblocks) % _TILE_ROWS
    if pad_rows:
        qp = jnp.pad(qp, ((0, pad_rows), (0, 0)))
        sp = jnp.pad(sp, ((0, pad_rows), (0, 0)))
    grid = (qp.shape[0] // _TILE_ROWS,)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((_TILE_ROWS, block), lambda i: (i, 0)),
                  pl.BlockSpec((_TILE_ROWS, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_TILE_ROWS, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, jnp.float32),
        interpret=interpret,
    )(qp, sp)
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("block", "n", "shape"))
def dequantize_reference(q, scales, *, block: int, n: int, shape):
    """Plain-jnp reference — identical math, used off-TPU and by the
    kernel-parity tests."""
    nblocks = -(-n // block)
    qp = jnp.pad(q, (0, nblocks * block - n)).reshape(nblocks, block)
    y = qp.astype(jnp.float32) * scales.reshape(nblocks, 1)
    return y.reshape(-1)[:n].reshape(shape)
