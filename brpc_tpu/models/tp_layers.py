"""Tensor-parallel layer wrappers riding the standalone collective verbs
(ISSUE 20).

Megatron-style sharding of the ``LayeredMLP`` stack: even layers shard
columns (each rank computes a column block of ``z = a @ W``, an
allgather rebuilds the full activation), odd layers shard rows (each
rank computes a partial product from its input slice, a reduce-scatter +
allgather — :func:`tp_allreduce` — sums the partials). The backward pass
mirrors it: the column layer's input-gradient is a sum of per-rank
partials (allreduce), the row layer's is a column block (allgather).
One collective per layer per direction, every one of them the
``reduce_scatter``/``allgather`` verbs from ``brpc_tpu/collectives`` —
so over a real :class:`~brpc_tpu.collectives.group.CollectiveGroup` each
hop gets the int8 codec + error-feedback exactly as the DP ring does.

Everything here is numpy: TP math runs wherever the verbs run, and this
module stays tier-1 pure (the docstringed reason the compute also never
lands on a wire lane — the regime-graph lint class). The wrappers are
duck-typed over ``group``: anything with ``rank``/``world``/
``reduce_scatter``/``allgather`` works — a real wire group, or the
in-process :class:`LocalRing` below (same ``collectives.core``
algorithms over a Mailbox transport) for tests.

Sharding layout is ``ring.chunk_spans(dim, world)`` by RANK INDEX — a
static partition, deliberately the same balanced-spans helper the ring
schedule uses so shard math and chunk math can't drift apart.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

from brpc_tpu.collectives import core, ring
from brpc_tpu.collectives.quant import ChunkCodec


# ---------------------------------------------------------------------------
# Allreduce as the verb composition (rs + ag), reassembled by span.
# ---------------------------------------------------------------------------

def tp_allreduce(group, name: str, x: np.ndarray) -> np.ndarray:
    """Sum ``x`` across ``group`` via reduce_scatter + allgather.

    ``group.allgather`` indexes results by RANK while the scattered
    chunks are owned by ``ring.owned_chunk(rank, n)`` — the reassembly
    places rank ``r``'s gathered chunk at its owned span. Two verb
    calls instead of one fused allreduce, same bytes on the wire, and
    the seam the TP layers need anyway (a sharded optimizer would stop
    after the reduce_scatter)."""
    shape = np.shape(x)
    flat = np.ascontiguousarray(np.asarray(x, np.float32)).reshape(-1)
    n = group.world
    if n == 1:
        return flat.copy().reshape(shape)
    _span, chunk = group.reduce_scatter(name + "/rs", flat)
    parts = group.allgather(name + "/ag", chunk)
    spans = ring.chunk_spans(flat.size, n)
    out = np.empty(flat.size, np.float32)
    for r in range(n):
        off, ln = spans[ring.owned_chunk(r, n)]
        if ln:
            out[off:off + ln] = np.asarray(
                parts[r], np.float32).reshape(-1)
    return out.reshape(shape)


def shard_span(dim: int, rank: int, world: int):
    """This rank's (offset, length) slice of a sharded dimension."""
    return ring.chunk_spans(dim, world)[rank]


# ---------------------------------------------------------------------------
# The sharded layers.
# ---------------------------------------------------------------------------

class ColumnShardedLinear:
    """``z = a @ W`` with ``W`` column-sharded: local matmul yields a
    column block of ``z``; allgather rebuilds the full activation.
    Backward: the weight grad ``a.T @ delta[:, cols]`` is already local
    (no collective); the input grad ``delta[:, cols] @ W_loc.T`` is a
    per-rank PARTIAL sum — :func:`tp_allreduce` completes it."""

    axis = 1

    def __init__(self, name: str, w_full: np.ndarray, group):
        self.name = name
        self.group = group
        dout = w_full.shape[1]
        self.span = shard_span(dout, group.rank, group.world)
        lo, ln = self.span
        self.w = np.ascontiguousarray(w_full[:, lo:lo + ln], np.float32)
        self.m = np.zeros_like(self.w)
        self.g: Optional[np.ndarray] = None
        self._a: Optional[np.ndarray] = None

    def fwd(self, a: np.ndarray) -> np.ndarray:
        self._a = a
        parts = self.group.allgather(self.name + "/fz", a @ self.w)
        return np.concatenate(
            [np.asarray(p, np.float32) for p in parts], axis=1)

    def bwd(self, delta: np.ndarray) -> np.ndarray:
        lo, ln = self.span
        d_loc = delta[:, lo:lo + ln]
        self.g = self._a.T @ d_loc
        return tp_allreduce(self.group, self.name + "/bu",
                            d_loc @ self.w.T)

    def gather_full(self) -> np.ndarray:
        parts = self.group.allgather(self.name + "/gp", self.w)
        return np.concatenate(
            [np.asarray(p, np.float32) for p in parts], axis=1)


class RowShardedLinear:
    """``z = a @ W`` with ``W`` row-sharded: each rank multiplies its
    input slice by its row block — a partial sum tp_allreduce completes.
    Backward: the input grad ``delta @ W_loc.T`` is a COLUMN block of
    ``dL/da`` (exact, no reduction) — allgather rebuilds it."""

    axis = 0

    def __init__(self, name: str, w_full: np.ndarray, group):
        self.name = name
        self.group = group
        din = w_full.shape[0]
        self.span = shard_span(din, group.rank, group.world)
        lo, ln = self.span
        self.w = np.ascontiguousarray(w_full[lo:lo + ln, :], np.float32)
        self.m = np.zeros_like(self.w)
        self.g: Optional[np.ndarray] = None
        self._a: Optional[np.ndarray] = None

    def fwd(self, a: np.ndarray) -> np.ndarray:
        self._a = a
        lo, ln = self.span
        return tp_allreduce(self.group, self.name + "/fz",
                            a[:, lo:lo + ln] @ self.w)

    def bwd(self, delta: np.ndarray) -> np.ndarray:
        lo, ln = self.span
        self.g = self._a[:, lo:lo + ln].T @ delta
        parts = self.group.allgather(self.name + "/bu", delta @ self.w.T)
        return np.concatenate(
            [np.asarray(p, np.float32) for p in parts], axis=1)

    def gather_full(self) -> np.ndarray:
        parts = self.group.allgather(self.name + "/gp", self.w)
        return np.concatenate(
            [np.asarray(p, np.float32) for p in parts], axis=0)


class TPShardedMLP:
    """The ``LayeredMLP`` stack sharded 2-way-style across ``group``:
    layers alternate column/row sharding (the classic pairing — the
    column layer's gathered output feeds the row layer's sliced input).
    ``params_full`` is the UNSHARDED init (every rank slices the same
    dict), so TP-vs-baseline parity starts from identical weights; the
    forward/backward math is the same fp32 chain as ``LayeredMLP``
    with the batched matmuls split per rank, and the documented parity
    tolerance is fp32 reassociation of the split partial sums (~1e-5
    relative) — zero when ``world == 1``."""

    def __init__(self, sizes, group, params_full: Dict[str, np.ndarray],
                 lr: float = 0.01, momentum: float = 0.9):
        if len(sizes) < 2:
            raise ValueError("need at least one layer (two sizes)")
        self.sizes = list(sizes)
        self.group = group
        self.lr = lr
        self.momentum = momentum
        self.names = [f"layer{k:02d}" for k in range(len(sizes) - 1)]
        self.layers: List[object] = []
        for k, name in enumerate(self.names):
            w_full = np.asarray(params_full[name], np.float32)
            cls = ColumnShardedLinear if k % 2 == 0 else RowShardedLinear
            self.layers.append(cls(name, w_full, group))

    def forward(self, x: np.ndarray):
        a = np.asarray(x, np.float32)
        zs = []
        last = len(self.layers) - 1
        for k, layer in enumerate(self.layers):
            z = layer.fwd(a)
            zs.append(z)
            a = z if k == last else np.maximum(z, 0.0)
        return a, zs

    def backward(self, pred: np.ndarray, y: np.ndarray, zs) -> float:
        r = pred - np.asarray(y, np.float32)
        loss = float(np.mean(np.square(r)))
        delta = (2.0 / r.size) * r
        for k in range(len(self.layers) - 1, -1, -1):
            u = self.layers[k].bwd(delta)
            if k > 0:
                delta = u * (zs[k - 1] > 0)
        return loss

    def grads(self, x, y):
        """Local grad shards (+ loss) without an update — the parity
        test's view; full-stack grads come from slicing the serial
        reference with each layer's ``span``/``axis``."""
        pred, zs = self.forward(x)
        loss = self.backward(pred, y, zs)
        return {l.name: l.g for l in self.layers}, loss

    def train_step(self, x, y) -> float:
        pred, zs = self.forward(x)
        loss = self.backward(pred, y, zs)
        for layer in self.layers:
            layer.m = self.momentum * layer.m + layer.g
            layer.w = layer.w - self.lr * layer.m
        return loss

    def gather_params(self) -> Dict[str, np.ndarray]:
        return {l.name: l.gather_full() for l in self.layers}


# ---------------------------------------------------------------------------
# LocalRing: the pure in-process group (tests).
# ---------------------------------------------------------------------------

class _MemLink:
    """One op's transport over the ring's Mailboxes — the same contract
    ``group._RpcLink`` gives ``collectives.core`` on the wire."""

    def __init__(self, ring_obj, rank: int, op_key: tuple,
                 deadline: float):
        self._ring = ring_obj
        self._rank = rank
        self._op = op_key
        self._deadline = deadline

    def send(self, dst, ph, step, idx, meta, blob, frag=0, nfrags=1):
        detached = np.array(np.asarray(blob).reshape(-1).view(np.uint8))
        self._ring._boxes[dst].deposit(
            self._op + (ph, int(step), int(frag)),
            (idx, dict(meta), detached))

    def recv(self, ph, step, frag=0):
        return self._ring._boxes[self._rank].take(
            self._op + (ph, int(step), int(frag)), self._deadline)


class LocalRing:
    """An in-process collective group: the REAL ``collectives.core``
    ring algorithms (and codec, when asked) over ``core.Mailbox``
    rendezvous instead of RPC. Members run on caller threads — one
    thread per rank, like the wire group's users. ``codec="int8"``
    exercises the same quantization + per-member error-feedback path
    the wire takes."""

    def __init__(self, world: int, codec: Optional[str] = None,
                 ef: bool = True, timeout_s: float = 30.0):
        self.world = world
        self.codec = codec
        self.timeout_s = timeout_s
        self._boxes = [core.Mailbox() for _ in range(world)]
        self._members = [LocalMember(self, r, ChunkCodec(ef=ef))
                         for r in range(world)]

    def member(self, rank: int) -> "LocalMember":
        return self._members[rank]


class LocalMember:
    """One rank's handle on a :class:`LocalRing` — duck-compatible with
    ``CollectiveGroup`` for the verbs the TP layers use."""

    def __init__(self, ring_obj: LocalRing, rank: int,
                 codec: ChunkCodec):
        self._ring = ring_obj
        self.rank = rank
        self.world = ring_obj.world
        self._codec = codec
        self._seq: Dict[str, int] = {}
        self._mu = threading.Lock()

    def _link(self, name: str) -> _MemLink:
        with self._mu:
            seq = self._seq.get(name, 0)
            self._seq[name] = seq + 1
        return _MemLink(self._ring, self.rank, (name, seq),
                        time.monotonic() + self._ring.timeout_s)

    def reduce_scatter(self, name: str, array):
        return core.ring_reduce_scatter(
            self.rank, self.world, np.asarray(array, np.float32),
            self._codec, self._link(name), name, self._ring.codec)

    def allgather(self, name: str, array):
        return core.ring_allgather(
            self.rank, self.world, np.asarray(array, np.float32),
            self._codec, self._link(name), name, self._ring.codec)

    def allreduce(self, name: str, array, on_chunk=None):
        return core.ring_allreduce(
            self.rank, self.world, np.asarray(array, np.float32),
            self._codec, self._link(name), name, self._ring.codec,
            on_chunk=on_chunk)
