"""Tensor-on-the-wire: jax.Array payloads riding the RPC framework.

This is the Python face of the native TensorArena bridge
(native/ttpu/tensor_arena.h — the tpu-native analog of the reference's
RDMA memory registration, rdma_helper.h:48): a shm-backed arena both ends
of a ``tpu://`` connection map. The flow per tensor:

  device array --(one D2H DMA)--> arena pages --(by-reference doorbell)-->
  receiver reads the SAME physical pages in place --(jax.device_put)-->
  device array on the other side.

No host-side copies happen between the arena and the receiving handler:
the IOBuf blocks on both sides point into the shared mapping (pointer
identity is asserted by native/test/test_tensor_arena.cpp). The staging
copy INTO the arena is the registered-memory discipline the reference's
RDMA path uses too (app data lands in registered blocks before the NIC
sees it); on a real pod the arena plays the pinned-host staging buffer
role that libtpu DMAs from.

Typed tensors ride as: request/response payload = a tiny metadata header
(dtype/shape, msgpack-free manual encoding), attachment = the raw bytes in
the arena.
"""

from __future__ import annotations

import ctypes
import json
import struct
import threading
import time
from collections import deque
from typing import Callable, Optional, Tuple

import numpy as np

from brpc_tpu.observability import tracing
from brpc_tpu.runtime import native
from brpc_tpu.runtime.native import RpcError, fill_err_text, lib

# App-level error code (param_server.py holds the rest of the 2040+ range:
# E_NO_SUCH..E_EXISTS at 2040-2043): a typed tensor send whose decoded
# meta header cannot be applied to the payload (truncated/corrupt
# quantized bytes, a codec this build can't parse). Deliberately NOT
# 2004/TRPC_EINTERNAL — the client-side codec self-heal keys on this
# code, and app codes must never collide with transport codes.
E_UNDECODABLE = 2044


def _bind_tensor_api(L: ctypes.CDLL) -> ctypes.CDLL:
    if getattr(L, "_tensor_api_bound", False):
        return L
    L.tbrpc_arena_create.restype = ctypes.c_void_p
    L.tbrpc_arena_create.argtypes = [ctypes.c_size_t]
    L.tbrpc_arena_destroy.argtypes = [ctypes.c_void_p]
    L.tbrpc_arena_base.restype = ctypes.c_void_p
    L.tbrpc_arena_base.argtypes = [ctypes.c_void_p]
    L.tbrpc_arena_busy_bytes.restype = ctypes.c_int64
    L.tbrpc_arena_busy_bytes.argtypes = [ctypes.c_void_p]
    L.tbrpc_arenas_busy_bytes.restype = ctypes.c_int64
    L.tbrpc_arenas_busy_bytes.argtypes = []
    L.tbrpc_arenas_total_bytes.restype = ctypes.c_int64
    L.tbrpc_arenas_total_bytes.argtypes = []
    L.tbrpc_var_arena_gauges_create.argtypes = []
    L.tbrpc_arena_wait_reusable.restype = ctypes.c_int
    L.tbrpc_arena_wait_reusable.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int64]
    L.tbrpc_call_tensor.restype = ctypes.c_int
    L.tbrpc_call_tensor.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        ctypes.c_void_p, ctypes.c_size_t,
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_size_t),
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_size_t), ctypes.POINTER(ctypes.c_int),
        ctypes.c_char_p, ctypes.c_size_t]
    L.tbrpc_view_free.argtypes = [ctypes.c_void_p]
    L.tbrpc_server_add_tensor_service.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, _TENSOR_CB, ctypes.c_void_p]
    # ---- async tensor RPC (futures over the native async CallMethod) ----
    L.tbrpc_call_tensor_async.restype = ctypes.c_void_p
    L.tbrpc_call_tensor_async.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        ctypes.c_void_p, ctypes.c_size_t,
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_size_t,
        _TENSOR_DONE_CB, ctypes.c_void_p]
    _future_outs = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_size_t),
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_size_t), ctypes.POINTER(ctypes.c_int),
        ctypes.c_char_p, ctypes.c_size_t]
    L.tbrpc_future_wait.restype = ctypes.c_int
    L.tbrpc_future_wait.argtypes = [ctypes.c_void_p] + _future_outs
    L.tbrpc_future_timed_wait.restype = ctypes.c_int
    L.tbrpc_future_timed_wait.argtypes = [
        ctypes.c_void_p, ctypes.c_int64] + _future_outs
    L.tbrpc_future_cancel.restype = ctypes.c_int
    L.tbrpc_future_cancel.argtypes = [ctypes.c_void_p]
    L.tbrpc_future_destroy.argtypes = [ctypes.c_void_p]
    L.tbrpc_async_inflight.restype = ctypes.c_int64
    L.tbrpc_async_inflight.argtypes = []
    # ---- one-sided tensor reads (published arena windows) ----
    L.tbrpc_oneside_window_create.restype = ctypes.c_void_p
    L.tbrpc_oneside_window_create.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32]
    L.tbrpc_oneside_window_destroy.argtypes = [ctypes.c_void_p]
    L.tbrpc_oneside_publish.restype = ctypes.c_int
    L.tbrpc_oneside_publish.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
        ctypes.c_uint64, ctypes.c_int]
    L.tbrpc_oneside_begin_rewrite.argtypes = [ctypes.c_void_p,
                                              ctypes.c_char_p]
    L.tbrpc_oneside_unpublish.restype = ctypes.c_int
    L.tbrpc_oneside_unpublish.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    L.tbrpc_oneside_window_describe.restype = ctypes.c_int64
    L.tbrpc_oneside_window_describe.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t]
    L.tbrpc_oneside_map.restype = ctypes.c_void_p
    L.tbrpc_oneside_map.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64]
    L.tbrpc_oneside_read.restype = ctypes.c_int
    L.tbrpc_oneside_read.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64)]
    L.tbrpc_oneside_stat.restype = ctypes.c_int
    L.tbrpc_oneside_stat.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64)]
    L.tbrpc_oneside_read_into.restype = ctypes.c_int
    L.tbrpc_oneside_read_into.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64)]
    L.tbrpc_oneside_unmap.restype = ctypes.c_int
    L.tbrpc_oneside_unmap.argtypes = [ctypes.c_void_p]
    L.tbrpc_oneside_stats_json.restype = ctypes.c_int64
    L.tbrpc_oneside_stats_json.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    L._tensor_api_bound = True
    return L


# Completion notification for tbrpc_call_tensor_async: fired on a
# callback-pool pthread BEFORE the future becomes waitable, with the same
# values a wait would return — ownership stays with the future (the
# callback must not free anything).
_TENSOR_DONE_CB = ctypes.CFUNCTYPE(
    None,
    ctypes.c_void_p,                    # ctx
    ctypes.c_int,                       # status (0 = ok)
    ctypes.c_void_p, ctypes.c_size_t,   # resp
    ctypes.c_void_p,                    # view handle
    ctypes.c_void_p, ctypes.c_size_t,   # ratt ptr/len
    ctypes.c_int,                       # ratt_copied
    ctypes.c_char_p,                    # err_text
)

_TENSOR_CB = ctypes.CFUNCTYPE(
    None,
    ctypes.c_void_p,                    # ctx
    ctypes.c_char_p,                    # method
    ctypes.c_void_p, ctypes.c_size_t,   # req
    ctypes.c_void_p, ctypes.c_size_t,   # attachment, IN PLACE
    ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_size_t),  # resp
    ctypes.POINTER(ctypes.c_void_p),    # resp_arena
    ctypes.POINTER(ctypes.c_uint64),    # resp_att_off
    ctypes.POINTER(ctypes.c_size_t),    # resp_att_len
    ctypes.POINTER(ctypes.c_int),       # resp_att_autofree
    ctypes.POINTER(ctypes.c_int),       # error_code
    ctypes.c_void_p, ctypes.c_size_t,   # err_text buffer (C-owned)
)


# ---- data-plane metrics (brpc_tpu/observability) ----
# Created lazily on first use: importing this module must not load the
# native library. One process-wide set — every channel/arena feeds the
# same recorders, mirroring how the native side aggregates per-method.

_metrics_cache = None


def _metrics():
    global _metrics_cache
    if _metrics_cache is None:
        from brpc_tpu.observability import metrics as obs

        L = _bind_tensor_api(lib())
        # Arena occupancy gauges (tensor_arena_busy_bytes/_total_bytes)
        # are NATIVE PassiveStatus vars over every live arena — created
        # through the capi but evaluated entirely in C++, so scrapes pay
        # no callback-pool hop or GIL, and a closing arena can't race the
        # walk. They ride /vars + /brpc_metrics + /tensorz like the rest.
        L.tbrpc_var_arena_gauges_create()
        _metrics_cache = {
            "pull": obs.latency("tensor_pull"),
            "push": obs.latency("tensor_push"),
            "pull_bytes": obs.counter("tensor_pull_bytes"),
            "push_bytes": obs.counter("tensor_push_bytes"),
            "wait_stalls": obs.counter("tensor_arena_wait_stalls"),
            # Server-side complement of the client recorders: the FULL
            # per-request cost of a Python tensor service — handler body
            # PLUS response staging into the arena (which happens after
            # the handler returns, so per-service recorders can't see it).
            "serve": obs.latency("tensor_handler"),
            # One-sided pull routing: hits read the peer's published
            # window directly (no RPC); fallbacks took the two-sided
            # path (off-host, unmapped, unpublished name, torn budget).
            # The native side keeps its own oneside_* adders; these two
            # count the CLIENT-side routing decision.
            "oneside_hits": obs.counter("oneside_pull_hits"),
            "oneside_fallbacks": obs.counter("oneside_pull_fallbacks"),
        }
    return _metrics_cache


_stage = tracing.stage


# ---- pipeline in-flight gauge ----
# One process-wide gauge over every live PipelineWindow (tbvar names are a
# process-wide namespace — per-window registrations would collide); the
# native capi keeps its own `tensor_rpc_inflight` twin counting ALL async
# tensor RPCs, windowed or not.

_pipeline_mu = threading.Lock()
_pipeline_inflight = 0

# Anchors for in-flight completion-notification trampolines: a future
# dropped mid-flight must not let GC free a CFUNCTYPE the native side is
# about to call. Fired notifications remove themselves; a canceled future
# whose notification never fires leaks one small object (rare, bounded by
# the caller's cancel rate). A list, not a set — ctypes function pointers
# are unhashable.
_live_done_cbs: list = []


def _pipeline_inflight_add(delta: int) -> None:
    global _pipeline_inflight
    with _pipeline_mu:
        _pipeline_inflight += delta


def _pipeline_gauge() -> None:
    from brpc_tpu.observability import metrics as obs

    obs.gauge("tensor_pipeline_inflight", lambda: _pipeline_inflight)


def _encode_meta(arr: np.ndarray) -> bytes:
    # Delegates to codec.pack_header — the ONE home of the '<I len + JSON'
    # header framing, so the raw and quantized wire cannot drift apart.
    from brpc_tpu.runtime import codec as codec_mod

    return codec_mod.pack_header({"dtype": arr.dtype.str,
                                  "shape": list(arr.shape)})


def pad_header64(header: bytes) -> bytes:
    """Pad a [u32 n|JSON] header with trailing spaces until its TOTAL
    length is a 64-byte multiple. One-sided publications use this so the
    payload that follows the header in the blob starts 64B-aligned:
    ``read_np`` aligns the BLOB start, and the CPU backend's zero-copy
    ``device_put`` alias check needs the DATA start aligned — without
    the pad, essentially every header length breaks the alias and
    re-adds the full-payload copy the owned-buffer path exists to
    remove. JSON parsers ignore the trailing whitespace."""
    pad = -len(header) % 64
    if pad == 0:
        return header
    body = header[4:] + b" " * pad
    return struct.pack("<I", len(body)) + body


def _decode_meta_ex(buf: bytes) -> Tuple[dict, bytes]:
    """Header -> (full metadata dict, rest of payload). The dict carries
    dtype/shape always, plus codec/block when the tensor rides the
    quantized wire format (brpc_tpu/runtime/codec.py)."""
    (n,) = struct.unpack_from("<I", buf)
    return json.loads(buf[4:4 + n].decode()), buf[4 + n:]


def _decode_meta(buf: bytes) -> Tuple[np.dtype, tuple, bytes]:
    meta, rest = _decode_meta_ex(buf)
    return np.dtype(meta["dtype"]), tuple(meta["shape"]), rest


class WireTensor:
    """A response tensor already encoded for the wire: ``data`` (a uint8
    ndarray staged into the service arena as-is) plus the exact metadata
    ``header`` prefix to send — the quantized pull path's way of handing
    the trampoline pre-built bytes instead of a host array (whose header
    the trampoline would synthesize as raw).

    ``placed`` is an optional ``(off, nbytes)`` range the handler already
    wrote into the SERVICE'S OWN arena (``PullQ`` assembles its group
    payload in place to skip the concat-then-place double memcpy); the
    trampoline sends that range as-is — with autofree, so the handler
    must not free it — instead of staging ``data``."""

    __slots__ = ("data", "header", "placed")

    def __init__(self, data: Optional[np.ndarray], header: bytes,
                 placed: Optional[Tuple[int, int]] = None):
        self.data = data
        self.header = header
        self.placed = placed


def _as_host_array(array) -> np.ndarray:
    """jax.Array -> host np.ndarray (one D2H DMA on TPU; zero-copy view on
    the CPU backend); np.ndarray passes through."""
    return np.asarray(array)


def _device_put_from_view(arr: np.ndarray, device):
    """``jax.device_put`` an array that VIEWS arena/view pages, safely.

    On a real accelerator this is the zero-copy discipline: the H2D DMA
    copies by definition, so the view can be released the moment
    ``block_until_ready`` returns. On the CPU backend, XLA ZERO-COPY
    ALIASES 64-byte-aligned host buffers — and arena ranges are 64B-
    aligned — so the "device" array would keep pointing into pages the
    release hands back for reuse. Detach with a host copy there first.
    """
    import jax

    target = device if device is not None else jax.devices()[0]
    if getattr(target, "platform", "cpu") == "cpu":
        arr = np.array(arr)
    dev = jax.device_put(arr, device)
    dev.block_until_ready()  # transfer completes before the view release
    return dev


class TensorArena:
    """Registered transfer memory, exposed to numpy/jax as views."""

    def __init__(self, nbytes: int):
        self._L = _bind_tensor_api(lib())
        self._P = native.pylib()  # alloc/free keep the GIL: see pylib()
        self._h = self._L.tbrpc_arena_create(nbytes)
        if not self._h:
            raise MemoryError(f"arena create({nbytes}) failed")
        self._base = self._L.tbrpc_arena_base(self._h)
        self.nbytes = nbytes
        _metrics()  # occupancy gauges cover this arena from now on

    @property
    def handle(self) -> int:
        return self._h

    def alloc(self, nbytes: int) -> int:
        if not self._h:
            raise RuntimeError("arena is closed")
        off = self._P.tbrpc_arena_alloc(self._h, nbytes)
        if off < 0:
            raise MemoryError(f"arena alloc({nbytes}) failed (fragmented?)")
        return off

    def free(self, off: int) -> None:
        self._P.tbrpc_arena_free(self._h, off)

    def view(self, off: int, nbytes: int) -> np.ndarray:
        """A uint8 numpy view of arena pages — writes here ARE the staging
        transfer (no further copy before the wire)."""
        buf = (ctypes.c_uint8 * nbytes).from_address(self._base + off)
        return np.ctypeslib.as_array(buf)

    def place(self, array) -> Tuple[int, int, np.ndarray]:
        """Stage an array's bytes into the arena: (off, nbytes, host_copy).

        One D2H DMA for a TPU-resident jax.Array; a plain memcpy for host
        arrays. Returns the host ndarray too (carrying dtype/shape for the
        metadata header).
        """
        host = _as_host_array(array)
        if host.nbytes == 0:
            return 0, 0, host  # empty tensors ride as metadata only
        raw = host.reshape(-1).view(np.uint8)
        off = self.alloc(host.nbytes)
        self.view(off, host.nbytes)[:] = raw
        return off, host.nbytes, host

    def busy_bytes(self) -> int:
        if not self._h:
            return 0  # a closed arena holds nothing
        return self._L.tbrpc_arena_busy_bytes(self._h)

    def wait_reusable(self, off: int, timeout_ms: int = -1) -> bool:
        # Zero-timeout probe first: an actual PARK here means the data
        # plane is gated on reference drain (the wire release hasn't come
        # back) — the stall counter is the backpressure signal /tensorz
        # and dashboards watch.
        if self._L.tbrpc_arena_wait_reusable(self._h, off, 0) == 0:
            return True
        if timeout_ms == 0:
            return False
        _metrics()["wait_stalls"].add(1)
        return self._L.tbrpc_arena_wait_reusable(self._h, off,
                                                 timeout_ms) == 0

    def close(self) -> None:
        if self._h:
            self._L.tbrpc_arena_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


class OnesideMiss(Exception):
    """A one-sided read that must fall back to the RPC path for this
    call: not published (status 1) or torn past the retry budget under a
    republish storm (status 2). Transient by contract."""

    def __init__(self, name: str, status: int):
        super().__init__(f"oneside read miss for {name!r} (status {status})")
        self.name = name
        self.status = status


class OnesideGone(OnesideMiss):
    """The mapped window is gone (destroyed window, swept reader claim):
    unmap and stop trying — the permanent-fallback signal."""


class OnesideWindow:
    """Publisher side of one-sided tensor reads: seqlock-stamped
    publication slots inside a :class:`TensorArena`, readable by any
    same-host process that mapped the arena's shm segment. ``publish``
    hands over a range the caller already wrote (the window retires and
    reclaims the displaced range via epoch-based reclamation — never
    under a reader mid-copy); ``own=False`` publishes in place without
    ever freeing (serving KV pages, whose ranges the session owns)."""

    def __init__(self, arena: TensorArena, n_slots: int = 256,
                 n_readers: int = 64):
        self._L = _bind_tensor_api(lib())
        self.arena = arena
        self._h = self._L.tbrpc_oneside_window_create(arena.handle, n_slots,
                                                      n_readers)
        if not self._h:
            raise MemoryError("oneside window create failed (arena full?)")

    def publish(self, name: str, off: int, nbytes: int, version: int,
                own: bool = True) -> None:
        if not self._h:
            raise RuntimeError("oneside window is closed")
        if self._L.tbrpc_oneside_publish(self._h, name.encode(), off,
                                         nbytes, version,
                                         1 if own else 0) != 0:
            raise ValueError(
                f"oneside publish({name!r}, off={off}, n={nbytes}) refused")

    def begin_rewrite(self, name: str) -> None:
        """Write-lock ``name`` (readers retry) while its payload is
        rewritten in place; the next ``publish`` commits."""
        if self._h:
            self._L.tbrpc_oneside_begin_rewrite(self._h, name.encode())

    def unpublish(self, name: str) -> bool:
        if not self._h:
            return False
        return self._L.tbrpc_oneside_unpublish(self._h, name.encode()) == 0

    def describe(self) -> dict:
        """The mapping-handshake descriptor a server hands to clients
        (over any ordinary RPC): shm name, size, directory offset and the
        random window token a reader validates after mapping."""
        if not self._h:
            raise RuntimeError("oneside window is closed")
        n = self._L.tbrpc_oneside_window_describe(self._h, None, 0)
        buf = ctypes.create_string_buffer(n + 1)
        self._L.tbrpc_oneside_window_describe(self._h, buf, n + 1)
        doc = json.loads(buf.value.decode())
        doc["token"] = int(doc["token"])  # shipped as a decimal string
        return doc

    def close(self) -> None:
        if self._h:
            self._L.tbrpc_oneside_window_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


def oneside_stats() -> dict:
    """Process-wide one-sided counters + per-window reclamation state."""
    L = _bind_tensor_api(lib())
    n = L.tbrpc_oneside_stats_json(None, 0)
    buf = ctypes.create_string_buffer(n + 1)
    L.tbrpc_oneside_stats_json(buf, n + 1)
    return json.loads(buf.value.decode())


class OnesideReader:
    """Reader side: a same-host mapping of a peer's published window.
    ``read`` copies out one committed version under the reader's epoch
    pin (the publisher cannot reclaim the range mid-copy) and raises
    :class:`OnesideMiss`/:class:`OnesideGone` when the caller should use
    the RPC path instead."""

    def __init__(self, handle):
        self._L = _bind_tensor_api(lib())
        self._h = handle

    @classmethod
    def map(cls, desc: dict) -> Optional["OnesideReader"]:
        """Map from a window descriptor; None means stay on the RPC path
        (off-host shm name, stale token, full reader table)."""
        L = _bind_tensor_api(lib())
        try:
            h = L.tbrpc_oneside_map(str(desc["shm"]).encode(),
                                    int(desc["bytes"]),
                                    int(desc["dir_off"]),
                                    int(desc["token"]))
        except (KeyError, TypeError, ValueError):
            return None
        return cls(h) if h else None

    def read(self, name: str) -> Tuple[int, bytes]:
        """-> (version, payload bytes) of the committed publication."""
        version, arr = self.read_np(name)
        return version, arr.tobytes()

    def read_np(self, name: str) -> Tuple[int, np.ndarray]:
        """-> (version, OWNED uint8 ndarray): stat for the size, then ONE
        native memcpy straight into a 64B-aligned numpy buffer the
        caller owns — decode may view and even device_put-alias it with
        no reuse hazard (unlike arena pages, nothing ever rewrites this
        buffer). The large-tensor hot path: the bytes-returning ``read``
        costs one more copy."""
        if not self._h:
            raise OnesideGone(name, 3)
        nbytes = ctypes.c_uint64()
        version = ctypes.c_uint64()
        rc = self._L.tbrpc_oneside_stat(self._h, name.encode(),
                                        ctypes.byref(nbytes),
                                        ctypes.byref(version))
        # A republish between stat and read_into may grow the payload:
        # read_into answers TOO_SMALL (4) with the needed size — retry.
        for _ in range(8):
            if rc not in (0, 4):
                break
            need = nbytes.value
            # Over-allocate 64 bytes and slice to a 64B-aligned start so
            # the CPU backend's zero-copy device_put alias check passes.
            backing = np.empty(need + 64, np.uint8)
            shift = (-backing.ctypes.data) % 64
            arr = backing[shift:shift + need]
            rc = self._L.tbrpc_oneside_read_into(
                self._h, name.encode(), ctypes.c_void_p(backing.ctypes.data
                                                        + shift),
                need, ctypes.byref(nbytes), ctypes.byref(version))
            if rc == 0:
                return int(version.value), arr
        if rc == 3:
            raise OnesideGone(name, rc)
        raise OnesideMiss(name, rc)

    def close(self) -> None:
        if self._h:
            self._L.tbrpc_oneside_unmap(self._h)
            self._h = None

    unmap = close

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass


def consume_oneside_payload(payload, device=None,
                            note_name: Optional[str] = None,
                            to_host: bool = False):
    """Decode one one-sided payload — the same self-describing
    [u32 meta-len|meta JSON|bytes] framing the Pull RPC ships (raw or
    quantized), so the two paths CANNOT return different values for the
    same committed version (the fallback-parity contract). Returns a
    device array, or a detached host ndarray with ``to_host=True``.

    ``payload`` is either ``bytes`` or an OWNED uint8 ndarray
    (:meth:`OnesideReader.read_np`). The owned form is the large-tensor
    hot path: its buffer is never rewritten, so the raw branch may view
    it in place and let ``jax.device_put`` alias it on the CPU backend —
    the detach copy the arena-view path needs is pure waste here."""
    owned = isinstance(payload, np.ndarray)
    if owned:
        (n,) = struct.unpack("<I", payload[:4].tobytes())
        meta = json.loads(payload[4:4 + n].tobytes().decode())
        u8 = payload[4 + n:]
    else:
        meta, rest = _decode_meta_ex(payload)
        u8 = np.frombuffer(rest, dtype=np.uint8)
    if "codec" in meta:
        from brpc_tpu.runtime import codec as codec_mod

        if note_name is not None:
            nbytes = int(np.prod(meta["shape"], dtype=np.int64)
                         ) * np.dtype(meta["dtype"]).itemsize
            codec_mod.note(note_name, meta["codec"], nbytes, int(u8.nbytes))
        with _stage("dequant"):
            if to_host:
                return codec_mod.decode(meta, u8)
            return _dequant_put_from_view(meta, u8, device, codec_mod)
    arr = u8.view(np.dtype(meta["dtype"])).reshape(tuple(meta["shape"])) \
        if owned else np.frombuffer(
            u8, dtype=np.dtype(meta["dtype"])).reshape(tuple(meta["shape"]))
    if to_host:
        return arr if owned else np.array(arr)
    with _stage("device_put"):
        if owned:
            # Alias-safe: the caller-owned buffer outlives the jax array
            # (device_put keeps a reference) and is never rewritten.
            import jax

            return jax.device_put(arr, device)
        # `bytes` payloads are read-only frombuffer views — the helper's
        # detach discipline covers them.
        return _device_put_from_view(arr, device)


class TensorView:
    """A zero-copy window onto a received tensor (the peer's arena pages or
    the connection's RX segment). ``release()`` is what sends the release
    frame back and lets the sender reuse the range — call it (or use as a
    context manager) as soon as the bytes are consumed (e.g. after
    jax.device_put returns).

    ``L`` releases the view (``tbrpc_view_free`` hands the GIL off: the
    release may write on the socket), ``P`` frees a flattened copy."""

    def __init__(self, L, P, view_handle, ptr, nbytes, copied: bool):
        self._L = L
        self._P = P
        self._view = view_handle
        self._ptr = ptr
        self._copied = copied
        self.nbytes = nbytes

    def ndarray(self) -> np.ndarray:
        if not self.nbytes or not self._ptr:
            # Zero-size tensors ride as metadata only — there is no
            # attachment, so the view holds no pages (_ptr is None).
            return np.empty(0, dtype=np.uint8)
        buf = (ctypes.c_uint8 * self.nbytes).from_address(self._ptr)
        return np.ctypeslib.as_array(buf)

    @property
    def zero_copy(self) -> bool:
        return not self._copied

    def release(self) -> None:
        if self._view:
            self._L.tbrpc_view_free(self._view)
            self._view = None
        elif self._copied and self._ptr:
            self._P.tbrpc_free(self._ptr)
        self._ptr = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()

    def __del__(self):
        try:
            self.release()
        except Exception:  # noqa: BLE001
            pass


def consume_pull_reply(payload: bytes, view: "TensorView", device=None,
                       note_name: Optional[str] = None):
    """Decode a pulled-tensor reply and device_put it straight from the
    zero-copy view, releasing the view once the transfer completed.
    Returns ``(rest_of_payload, jax.Array, logical_nbytes)``.

    ONE implementation for the sync ``pull_device`` and the pipelined
    consumers (``ParameterClient.pull_all``'s on_reply) so the decode path
    and its aliasing discipline cannot drift apart. Responses are
    self-describing: a header carrying codec/block fields takes the
    dequantize path (fused into the device_put — on TPU the H2D DMA moves
    the ~4x smaller codes and the Pallas kernel widens on-chip; elsewhere
    the numpy dequant IS the detach copy, so nothing is copied twice);
    without codec fields this is byte-for-byte the raw path.
    """
    with view:
        meta, rest = _decode_meta_ex(payload)
        if "codec" in meta:
            from brpc_tpu.runtime import codec as codec_mod

            nbytes = int(np.prod(meta["shape"], dtype=np.int64)
                         ) * np.dtype(meta["dtype"]).itemsize
            if note_name is not None:
                # Decode side of the tensor_codec_* accounting contract:
                # a pull-only trainer must still show its logical/wire
                # bytes and ratio on /vars and /tensorz.
                codec_mod.note(note_name, meta["codec"], nbytes,
                               int(view.nbytes))
            with _stage("dequant"):
                try:
                    dev = _dequant_put_from_view(meta, view.ndarray(),
                                                 device, codec_mod)
                except ValueError as ve:
                    # Corrupt/truncated quantized reply (size mismatch,
                    # unknown codec, missing ml_dtypes): surface as the
                    # structural app code so pull_all's PartialPullError
                    # salvage and the fleet's per-name re-route engage —
                    # a bare ValueError would bypass both and discard
                    # every already-decoded groupmate.
                    raise RpcError(
                        E_UNDECODABLE,
                        f"undecodable tensor payload: {ve}") from ve
        else:
            arr = np.frombuffer(
                view.ndarray(), dtype=np.dtype(meta["dtype"])).reshape(
                    tuple(meta["shape"]))
            nbytes = view.nbytes
            with _stage("device_put"):
                dev = _device_put_from_view(arr, device)
    return rest, dev, nbytes


def _detach_device_put_batch(parts, device):
    """ONE ``jax.device_put`` over every (codes, scales) pair in ``parts``
    and ONE completion barrier BEFORE the caller releases the arena pages
    those buffers alias — the quantized wire's view-aliasing discipline
    lives here and nowhere else (single-tensor callers pass one pair;
    ``pull_all``'s group path amortizes the ~0.1-0.4ms per-put dispatch
    across the whole group). Mirrors ``_device_put_from_view``'s CPU
    hazard: XLA zero-copy aliases 64B-aligned host buffers, so a CPU
    target detaches with a host copy first. Returns the flat
    ``[q0, s0, q1, s1, ...]`` device list."""
    import jax

    target = device if device is not None else jax.devices()[0]
    flat = []
    for q, s in parts:
        flat.extend((q, s))
    if getattr(target, "platform", "cpu") == "cpu":
        flat = [np.array(a) for a in flat]
    devs = jax.device_put(flat, device)
    jax.block_until_ready(devs)
    return devs


def _dequant_widen(q_dev, s_dev, block, n, shape, want=None):
    """Widen-and-scale already-detached codes/scales on device (Pallas on
    TPU, the jnp reference elsewhere — ``dequantize_blocks`` auto-routes
    like ``fused_momentum_update``). The output is a FRESH buffer, so no
    further blocking; ``want`` restores a non-fp32 logical dtype."""
    from brpc_tpu.ops.quantize import dequantize_blocks

    out = dequantize_blocks(q_dev, s_dev, block=int(block), n=int(n),
                            shape=tuple(shape))
    if want is not None and np.dtype(want) != np.float32:
        out = out.astype(np.dtype(want))
    return out


def _dequant_put_from_view(meta: dict, payload_u8: np.ndarray, device,
                           codec_mod):
    """Dequantize a received ``[scales][codes]`` view into a device array.

    TPU: device_put the codes + scales (the H2D DMA detaches them from
    the arena pages by definition) and run the Pallas widen-and-scale
    kernel on-chip (brpc_tpu/ops/quantize.py — auto-routed like
    fused_momentum_update). Elsewhere: the numpy dequant writes a fresh
    fp32 buffer — detached by construction, so device_put may alias it
    safely (unlike raw views, which need an explicit detach copy).
    """
    import jax

    target = device if device is not None else jax.devices()[0]
    if getattr(target, "platform", "cpu") != "cpu":
        q, scales = codec_mod.split_wire(meta, payload_u8)
        q_dev, s_dev = _detach_device_put_batch([(q, scales)], device)
        return _dequant_widen(q_dev, s_dev, meta["block"],
                              int(np.prod(meta["shape"], dtype=np.int64)),
                              meta["shape"], want=meta["dtype"])
    host = codec_mod.decode(meta, payload_u8)  # fresh buffer: no alias risk
    dev = jax.device_put(host, device)
    dev.block_until_ready()
    return dev


class TensorFuture:
    """One in-flight async tensor RPC (``TensorChannel.call_async``).

    ``result()`` parks the calling thread until the response arrives and
    returns ``(payload, TensorView)`` — the exact ownership contract of
    the sync ``call_raw`` (release the view once the bytes are consumed).
    Results are cached on first take, so repeated ``result()`` calls
    return the same objects, and the future stays valid after its channel
    closes (the native controller owns everything it needs).

    ``cancel()`` ends an in-flight RPC with ECANCELED; ``close()`` (or
    GC) on a never-waited future cancels it and lets the native side
    release the response exactly once, whichever way the race goes.
    """

    def __init__(self, L, P, handle, service_method, done_cb=None):
        self._L = L
        self._P = P
        self._h = handle
        self._method = service_method
        self._cb = done_cb  # the ctypes trampoline must outlive the RPC
        self._payload = None
        self._view: Optional[TensorView] = None
        self._error: Optional[RpcError] = None
        self._taken = False

    def done(self) -> bool:
        """Non-blocking completion probe (moves a ready native result
        into the Python-side cache)."""
        return self._taken or self._poll(0)

    def result(self, timeout_ms: int = -1) -> Tuple[bytes, TensorView]:
        """Wait for completion -> (payload, view). ``timeout_ms >= 0``
        raises TimeoutError if still in flight (retry later); RPC
        failures raise RpcError."""
        if not self._taken and not self._poll(timeout_ms):
            raise TimeoutError(
                f"{self._method}: still in flight after {timeout_ms}ms")
        if self._error is not None:
            raise self._error
        return self._payload, self._view

    def _poll(self, timeout_ms: int) -> bool:
        if not self._h:
            raise RuntimeError("future is closed")
        L = self._L
        resp = ctypes.c_void_p()
        resp_len = ctypes.c_size_t()
        view = ctypes.c_void_p()
        ratt = ctypes.c_void_p()
        ratt_len = ctypes.c_size_t()
        copied = ctypes.c_int()
        errbuf = ctypes.create_string_buffer(256)
        outs = (ctypes.byref(resp), ctypes.byref(resp_len),
                ctypes.byref(view), ctypes.byref(ratt),
                ctypes.byref(ratt_len), ctypes.byref(copied),
                errbuf, len(errbuf))
        if timeout_ms < 0:
            rc = L.tbrpc_future_wait(self._h, *outs)
        else:
            rc = L.tbrpc_future_timed_wait(self._h, timeout_ms, *outs)
            if rc == -1:
                return False  # still in flight; nothing consumed
        self._taken = True
        if rc != 0:
            self._error = RpcError(rc, errbuf.value.decode(errors="replace"))
        else:
            try:
                self._payload = (ctypes.string_at(resp, resp_len.value)
                                 if resp_len.value else b"")
            finally:
                self._P.tbrpc_free(resp)
            self._view = TensorView(L, self._P, view.value, ratt.value,
                                    ratt_len.value, bool(copied.value))
        self.close()  # ownership is out; the native box is spent
        return True

    def cancel(self) -> None:
        """Cancel an in-flight RPC (later ``result()`` raises RpcError
        ECANCELED); a completed-but-unconsumed response is released now,
        exactly once. No-op once the result was taken."""
        if self._h and not self._taken:
            self._L.tbrpc_future_cancel(self._h)

    def close(self) -> None:
        """Release the native future (idempotent). In flight: cancels;
        the completion path frees the response."""
        if self._h:
            self._L.tbrpc_future_destroy(self._h)
            self._h = None
            # The notification trampoline unanchors ITSELF when it fires
            # (_notify); dropping our ref here is enough — a close that
            # races an unfired notification leaves the anchor in place.
            self._cb = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


class PipelineWindow:
    """Bounded-window pipelining over one ``TensorChannel``.

    Keeps up to ``window`` tensor RPCs in flight, overlapping the arena
    staging (D2H DMA + memcpy) of tensor k+1 with the wire transfer of
    tensors k, k-1, ... Submission order == delivery order: a full window
    completes the OLDEST call before staging the next, and each range is
    freed as its RPC completes — so the arena holds at most ``window``
    staged chunks (window x chunk bytes) at any moment, double-buffered
    against the wire.

    Results are handed to ``on_reply(tag, payload, view)`` in submit
    order on the submitting thread (release the view as soon as the bytes
    are consumed), or — without ``on_reply`` — collected by ``flush()``
    as ``[(tag, payload, view), ...]``.

    Observability: submissions ride the process-wide
    ``tensor_pipeline_inflight`` gauge, and the staging/drain phases
    annotate the active rpcz span as ``arena_stage`` / ``wire_wait``.
    """

    def __init__(self, channel: "TensorChannel", window: int = 4,
                 on_reply: Optional[Callable] = None):
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.channel = channel
        self.window = window
        self.on_reply = on_reply
        self._q: deque = deque()  # (tag, future, arena_off, arena_len)
        self._results: list = []
        _pipeline_gauge()

    def inflight(self) -> int:
        return len(self._q)

    def complete_one(self) -> bool:
        """Drain the OLDEST in-flight call only (delivering its reply
        through ``on_reply`` / the collected results); ``False`` when
        nothing is in flight. The overlapped step driver's per-tensor
        confirm point: ``opt-k`` drains exactly until push k's reply
        lands instead of flushing the whole window (which would serialize
        every later push behind the first confirm). A failure carries the
        failed call's tag as ``e.pipeline_tag`` so the caller can
        attribute it per tensor (partial-salvage bookkeeping)."""
        if not self._q:
            return False
        self._complete_oldest()
        return True

    def submit(self, service_method: str, array=None, request: bytes = b"",
               tag=None, encoder=None) -> None:
        """Stage ``array`` (optional) into the channel arena and start
        the RPC; blocks only while the window is full (draining the
        oldest in-flight call first).

        ``encoder(host) -> (wire_uint8, header_bytes) | None`` (optional)
        runs at arena-stage time — quantization overlaps the wire exactly
        like the staging copy already does (codes of tensor k+1 are being
        computed while tensor k's bytes fly). ``None`` means this tensor
        rides raw (the per-call degrade)."""
        while len(self._q) >= self.window:
            self._complete_oldest()
        off = length = 0
        if array is not None:
            with _stage("arena_stage"):
                enc = None
                if encoder is not None:
                    host = _as_host_array(array)
                    enc = encoder(host)
                    array = host
                if enc is None:
                    off, length, host = self.channel.place_with_meta(array)
                    request = _encode_meta(host) + request
                else:
                    wire, header = enc
                    off, length, _ = self.channel.arena.place(wire)
                    request = header + request
        try:
            fut = self.channel.call_async(service_method, request, off,
                                          length)
        except Exception:
            # Not in _q yet, so abort()/flush() would never free it — a
            # caller surviving transient submit failures must not leak one
            # staged chunk per retry.
            if length:
                self.channel.arena.free(off)
            raise
        _pipeline_inflight_add(1)
        self._q.append((tag, fut, off, length))

    def _complete_oldest(self) -> None:
        # EVERY drain point annotates failures with the failed call's
        # tag (``e.pipeline_tag``) — not just complete_one: submit's
        # window-full drain and flush() surface the same errors, and
        # per-tag salvage/retry layers (the step driver, PushQ's
        # rollback check, the collectives' shed redelivery) must be
        # able to attribute those too.
        tag, fut, off, length = self._q.popleft()
        try:
            try:
                with _stage("wire_wait"):
                    payload, view = fut.result()
            finally:
                _pipeline_inflight_add(-1)
                if length:
                    self.channel.arena.free(off)  # freed as refs drain
            if self.on_reply is not None:
                try:
                    self.on_reply(tag, payload, view)
                except Exception:
                    # The view was handed out but is in neither _q nor
                    # _results: release here or the PEER's range never
                    # drains (release() is idempotent).
                    view.release()
                    raise
            else:
                self._results.append((tag, payload, view))
        except Exception as e:  # noqa: BLE001 — annotate and re-raise
            try:
                e.pipeline_tag = tag
            except Exception:  # noqa: BLE001 — exotic immutable exception
                pass
            raise

    def flush(self) -> list:
        """Drain the window; returns (and clears) collected results when
        no ``on_reply`` consumer was given."""
        while self._q:
            self._complete_oldest()
        out, self._results = self._results, []
        return out

    def abort(self) -> None:
        """Error-path teardown: cancel and release everything in flight
        and every undelivered collected result."""
        while self._q:
            _tag, fut, off, length = self._q.popleft()
            _pipeline_inflight_add(-1)
            try:
                fut.cancel()
                fut.close()
            finally:
                if length:
                    self.channel.arena.free(off)
        for _tag, _payload, view in self._results:
            try:
                view.release()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
        self._results = []

    def __enter__(self):
        return self

    def __exit__(self, exc_type, *_exc):
        if exc_type is None:
            self.flush()
        else:
            self.abort()


class PullTicket:
    """A pull request in flight (``TensorChannel.pull_send``): when it
    was issued, its future, and the tensor name for codec accounting."""

    __slots__ = ("t0", "future", "note_name")

    def __init__(self, t0: float, future: TensorFuture,
                 note_name: Optional[str]):
        self.t0 = t0
        self.future = future
        self.note_name = note_name

    def cancel(self) -> None:
        """Drop a ticket that will not be finished: the RPC is cancelled
        and its reply's view released exactly once."""
        self.future.close()


class TensorChannel:
    """Client stub for tensor traffic: a ``tpu://`` channel plus a local
    arena the outbound tensors stage through."""

    def __init__(self, addr: str, arena: Optional[TensorArena] = None,
                 timeout_ms: int = 20000, max_retry: int = 0):
        self._L = _bind_tensor_api(lib())
        self._P = native.pylib()
        if not addr.startswith("tpu://") and "://" not in addr:
            addr = "tpu://" + addr
        self._h = self._L.tbrpc_channel_create(addr.encode(), timeout_ms,
                                               max_retry)
        if not self._h:
            raise RuntimeError(f"tensor channel init to {addr} failed")
        native._LIVE_CHANNELS.add(self)  # atexit teardown hygiene
        self.arena = arena if arena is not None else TensorArena(256 << 20)

    def call_raw(self, service_method: str, request: bytes,
                 att_off: int = 0, att_len: int = 0
                 ) -> Tuple[bytes, TensorView]:
        """One RPC: request bytes + an arena range as the attachment.
        Returns (response payload, response-attachment view)."""
        if not self._h:
            # NULL through ctypes would be a native deref, not an error.
            raise RuntimeError("tensor channel is closed")
        L = self._L
        resp = ctypes.c_void_p()
        resp_len = ctypes.c_size_t()
        view = ctypes.c_void_p()
        ratt = ctypes.c_void_p()
        ratt_len = ctypes.c_size_t()
        copied = ctypes.c_int()
        errbuf = ctypes.create_string_buffer(256)
        rc = L.tbrpc_call_tensor(
            self._h, service_method.encode(), request, len(request),
            self.arena.handle if att_len else None, att_off, att_len,
            ctypes.byref(resp), ctypes.byref(resp_len), ctypes.byref(view),
            ctypes.byref(ratt), ctypes.byref(ratt_len), ctypes.byref(copied),
            errbuf, len(errbuf))
        tracing.gil_returned()
        if rc != 0:
            raise RpcError(rc, errbuf.value.decode(errors="replace"))
        try:
            payload = (ctypes.string_at(resp, resp_len.value)
                       if resp_len.value else b"")
        finally:
            self._P.tbrpc_free(resp)
        return payload, TensorView(L, self._P, view.value, ratt.value,
                                   ratt_len.value, bool(copied.value))

    def call_async(self, service_method: str, request: bytes = b"",
                   att_off: int = 0, att_len: int = 0,
                   on_done: Optional[Callable[[int], None]] = None
                   ) -> TensorFuture:
        """Submit one RPC without blocking: ``call_raw``'s async twin,
        returning a :class:`TensorFuture`. The arena range (if any) takes
        its local reference before this returns, so ``arena.free`` any
        time after submission is safe (deferred-free semantics).

        ``on_done(status)`` (optional) fires on a callback-pool pthread
        before the future becomes waitable — a light notification hook
        (wake an event loop); consume results via ``future.result()``,
        never inside the hook."""
        if not self._h:
            raise RuntimeError("tensor channel is closed")
        L = self._L
        cb = ctypes.cast(None, _TENSOR_DONE_CB)  # NULL fn ptr: no hook
        if on_done is not None:
            def _notify(_ctx, status, *_rest):
                try:
                    on_done(status)
                except Exception:  # noqa: BLE001 — a notification hook
                    pass           # must not unwind into the pool thread
                finally:
                    try:
                        _live_done_cbs.remove(cb)
                    except ValueError:
                        pass

            cb = _TENSOR_DONE_CB(_notify)
            _live_done_cbs.append(cb)
        h = L.tbrpc_call_tensor_async(
            self._h, service_method.encode(), request, len(request),
            self.arena.handle if att_len else None, att_off, att_len,
            cb, None)
        if not h:
            raise RpcError(native.TRPC_EINTERNAL,
                           f"async submit of {service_method} failed")
        return TensorFuture(L, self._P, h, service_method, done_cb=cb)

    def call(self, service_method: str, array=None, request: bytes = b""
             ) -> Tuple[bytes, Optional[np.ndarray]]:
        """Send a tensor (or nothing), receive a tensor (or nothing).

        The outbound array stages into the local arena (freed after the
        wire release returns); the inbound one is device_put-able — it is
        materialized as an ndarray COPY here only if the caller keeps it,
        via pull() below for the zero-copy discipline.
        """
        off = length = 0
        if array is not None:
            off, length, host = self.place_with_meta(array)
            request = _encode_meta(host) + request
        try:
            payload, view = self.call_raw(service_method, request, off,
                                          length)
        finally:
            if length:
                self.arena.free(off)  # deferred until releases drain
        with view:
            if view.nbytes == 0:
                try:  # an empty tensor still carries its metadata header
                    dtype, shape, rest = _decode_meta(payload)
                    return rest, np.empty(shape, dtype=dtype)
                except Exception:  # noqa: BLE001 — tensor-less response
                    return payload, None
            meta, rest = _decode_meta_ex(payload)
            if "codec" in meta:  # self-describing quantized response
                from brpc_tpu.runtime import codec as codec_mod

                return rest, codec_mod.decode(meta, view.ndarray())
            arr = np.frombuffer(
                view.ndarray(), dtype=np.dtype(meta["dtype"])).reshape(
                    tuple(meta["shape"]))
            return rest, np.array(arr)  # detach before releasing the view

    def place_with_meta(self, array) -> Tuple[int, int, np.ndarray]:
        return self.arena.place(array)

    def pull_device(self, service_method: str, request: bytes = b"",
                    device=None, note_name: Optional[str] = None):
        """Fetch a tensor and jax.device_put it STRAIGHT from the received
        view (H2D DMA from the shared pages; no intermediate host copy),
        then release the view. Returns (rest_of_payload, jax.Array).

        Observability: records into the tensor_pull LatencyRecorder and
        tensor_pull_bytes counter, and annotates the active rpcz span with
        the rpc / device_put stage split.

        ``pull_send`` + ``pull_finish`` are the same pull split at the
        wire, for a caller that places one reply while the next request
        is in flight; this one blocks in ``call_raw`` (its ``gil_return``
        stage) instead of parking on a future."""
        t0 = time.monotonic()
        with _stage("rpc"):
            payload, view = self.call_raw(service_method, request)
        return self._place_pull(t0, payload, view, device, note_name)

    def pull_send(self, service_method: str, request: bytes = b"",
                  note_name: Optional[str] = None) -> PullTicket:
        """``pull_device``'s first half: issue the request without
        waiting for it. ``pull_finish`` completes the ticket; a ticket
        that will not be finished is ``cancel()``-ed."""
        return PullTicket(time.monotonic(),
                          self.call_async(service_method, request),
                          note_name)

    def pull_finish(self, ticket: PullTicket, device=None):
        """``pull_device``'s second half over a ``pull_send`` ticket:
        the reply wait (the ``rpc`` stage), then the same placement,
        view release and recorders. -> (rest_of_payload, jax.Array)."""
        with _stage("rpc"):
            payload, view = ticket.future.result()
        return self._place_pull(ticket.t0, payload, view, device,
                                ticket.note_name)

    def _place_pull(self, t0: float, payload: bytes, view: TensorView,
                    device, note_name: Optional[str]):
        rest, dev, nbytes = consume_pull_reply(payload, view, device,
                                               note_name=note_name)
        m = _metrics()
        m["pull"].record_s(time.monotonic() - t0)
        m["pull_bytes"].add(nbytes)
        return rest, dev

    def push_device(self, service_method: str, array,
                    request: bytes = b"", encoder=None) -> bytes:
        """Send a device array (D2H into the arena, by-reference on the
        wire); waits for the wire release so the arena cannot fill up under
        a streaming push loop. Returns the response payload.

        ``encoder`` is the same per-tensor hook ``PipelineWindow.submit``
        takes: ``(wire_uint8, header_bytes) | None`` computed at
        arena-stage time; None rides raw.

        Observability: records into the tensor_push LatencyRecorder and
        tensor_push_bytes counter, and annotates the active rpcz span with
        the arena_stage (D2H + staging copy) / rpc stage split."""
        t0 = time.monotonic()
        with _stage("arena_stage"):
            host = _as_host_array(array)
            enc = encoder(host) if encoder is not None else None
            if enc is None:
                off, length, host = self.place_with_meta(host)
                header = _encode_meta(host)
            else:
                wire, header = enc
                off, length, _ = self.arena.place(wire)
        try:
            with _stage("rpc"):
                payload, view = self.call_raw(
                    service_method, header + request, off, length)
            view.release()
            m = _metrics()
            m["push"].record_s(time.monotonic() - t0)
            m["push_bytes"].add(length)
            return payload
        finally:
            if length:
                self.arena.free(off)

    def close(self) -> None:
        if self._h:
            self._L.tbrpc_channel_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass


# Handler: (method, request_bytes, attachment_view: np.ndarray|None)
#   -> (response_bytes, response_array_or_None)
TensorHandler = Callable[[str, bytes, Optional[np.ndarray]],
                         Tuple[bytes, Optional[object]]]


def add_tensor_service(server: native.Server, name: str,
                       handler: TensorHandler,
                       arena: Optional[TensorArena] = None) -> TensorArena:
    """Host a tensor service on a native Server: the handler reads request
    tensors IN PLACE (a numpy view of the sender's pages) and returns
    response tensors through the service's own arena (by-reference on the
    wire). Returns that arena."""
    L = _bind_tensor_api(lib())
    P = native.pylib()
    srv_arena = arena if arena is not None else TensorArena(256 << 20)

    def trampoline(*args):
        # The server span's gil_enter, and "brpc.serve" on the profiler's
        # host plane around the whole callback (no-op while rpcz is off).
        with tracing.serving():
            serve(*args)

    def serve(ctx, method, req, req_len, att, att_len,
              resp, resp_len, resp_arena, resp_off, resp_att_len,
              resp_autofree, error_code, err_text, err_text_cap):
        t0 = time.monotonic()
        try:
            request = ctypes.string_at(req, req_len) if req_len else b""
            att_view = None
            if att_len:
                buf = (ctypes.c_uint8 * att_len).from_address(att)
                att_view = np.ctypeslib.as_array(buf)
                if request[:4] and len(request) >= 4:
                    # Typed sends prefix the payload with dtype/shape meta:
                    # give the handler a shaped view of the pages in place.
                    meta = None
                    try:
                        meta, request = _decode_meta_ex(request)
                    except Exception:  # noqa: BLE001 — raw-byte sender
                        pass
                    # Once a meta header DID decode (request is already
                    # header-stripped), a failure to apply it is a
                    # malformed/undecodable typed send — answer a clean
                    # RPC error, never hand the handler the flat wire
                    # bytes as if they were the tensor.
                    if meta is not None:
                        try:
                            if "codec" in meta:
                                # Quantized send: hand the handler the
                                # typed zero-copy window (codes + scales
                                # in place); dequantize() detaches when
                                # it consumes.
                                from brpc_tpu.runtime import (
                                    codec as codec_mod)

                                att_view = codec_mod.QuantizedView(
                                    meta, att_view)
                            else:
                                att_view = att_view.view(
                                    np.dtype(meta["dtype"])).reshape(
                                        tuple(meta["shape"]))
                        except Exception as e:  # noqa: BLE001
                            raise RpcError(
                                E_UNDECODABLE,
                                f"undecodable tensor payload "
                                f"(meta={meta!r}): {e}") from e
            with tracing.stage("handler"):
                r, out_arr = handler(method.decode(), request, att_view)
            # The response's staging: a pull's D2H and arena copy, its
            # meta header and the payload's copy into native memory.
            with tracing.stage("respond_stage"):
                if isinstance(out_arr, WireTensor):
                    # Pre-encoded response (quantized pull path): stage
                    # the wire bytes as-is, send the handler's exact
                    # header.
                    if out_arr.placed is not None:
                        off, nbytes = out_arr.placed
                    else:
                        off, nbytes, _ = srv_arena.place(out_arr.data)
                    r = out_arr.header + r
                    if nbytes:
                        resp_arena[0] = srv_arena.handle
                        resp_off[0] = off
                        resp_att_len[0] = nbytes
                        resp_autofree[0] = 1
                elif out_arr is not None:
                    off, nbytes, host = srv_arena.place(out_arr)
                    r = _encode_meta(host) + r
                    if nbytes:
                        resp_arena[0] = srv_arena.handle
                        resp_off[0] = off
                        resp_att_len[0] = nbytes
                        # Autofree: the C side frees AFTER taking the
                        # response ref, so the range returns once the
                        # client releases.
                        resp_autofree[0] = 1
                if r:
                    # One GIL-keeping malloc + copy (ctypes.memmove is a
                    # CFUNCTYPE: it would hand the GIL off).
                    resp[0] = P.tbrpc_dup(r, len(r))
                    resp_len[0] = len(r)
        except RpcError as e:
            error_code[0] = e.code if e.code != 0 \
                else native.TRPC_EINTERNAL
            fill_err_text(err_text, err_text_cap, e.text)
        except Exception as e:  # noqa: BLE001 — handler bug => EINTERNAL
            error_code[0] = native.TRPC_EINTERNAL
            fill_err_text(err_text, err_text_cap, f"{type(e).__name__}: {e}")
        finally:
            # Handler + response staging: what the client's tensor_pull
            # would otherwise misattribute to the network.
            _metrics()["serve"].record_s(time.monotonic() - t0)

    cb = _TENSOR_CB(trampoline)
    server._cbs.append(cb)  # keep alive alongside byte-service callbacks
    if L.tbrpc_server_add_tensor_service(
            server._h, name.encode(), cb, None) != 0:
        raise RuntimeError(f"add_tensor_service({name}) failed")
    return srv_arena
