"""ctypes bindings over the native C API (native/capi/capi.h).

The host RPC fabric (fiber scheduler, wait-free sockets, tstd protocol) is
C++; this module is the Python doorway: Server/Channel objects, Python
service handlers, and the bench harness entry points whose hot loops stay
in C. Handlers run on a small DEDICATED PTHREAD POOL on the native side
(capi PyCallbackPool, python_callback_threads flag), never on a fiber:
ctypes pairs PyGILState_Ensure/Release on one OS thread, and a fiber that
parks mid-handler (e.g. a nested RPC) could resume on a different worker.
The service fiber parks until the handler returns, and the handler's
thread carries the server's rpcz trace context, so downstream calls made
inside a handler link into the caller's trace.

Reference parity note: the reference's python/ tree is an empty "TBD" stub —
bindings here are first-class because the TPU data plane (JAX) is Python.
"""

from __future__ import annotations

import atexit
import contextlib
import ctypes
import errno
import fcntl
import os
import re
import shutil
import subprocess
import weakref
from typing import Callable, Optional, Tuple

# Request priority lanes (native/trpc/qos.h): HIGH is the control plane
# (heartbeats, version polls, Epoch/Meta, migrator handshakes) — admitted
# up to the server's full concurrency gate; BULK is tensor pull/push —
# admitted only while the gate keeps headroom free; NORMAL is the unmarked
# default (wire stays byte-identical to the pre-QoS format).
PRIORITY_HIGH = 0
PRIORITY_NORMAL = 1
PRIORITY_BULK = 2

# Transport/framework error codes — the Python mirror of native/trpc/
# errno.h, name-for-name and value-for-value (tpulint's error-code rule
# pins the parity against tools/tpulint/error_codes.lock, so the two
# registries cannot drift apart silently). Clients and handlers key on
# THESE names; a raw integer comparison where one of these exists is a
# lint finding — the bare-literal collision class that once let a
# structural code land on top of TRPC_ECONNECT.
TRPC_ENOSERVICE = 1001      # no such service
TRPC_ENOMETHOD = 1002       # no such method
TRPC_EREQUEST = 1003        # malformed request
TRPC_ERESPONSE = 1005       # malformed response
TRPC_ERPCTIMEDOUT = 1008    # RPC deadline exceeded
TRPC_EBACKUPREQUEST = 1009  # internal: backup-request timer fired
TRPC_ELIMIT = 1011          # concurrency limit rejected the request
TRPC_ECANCELED = 1012       # RPC canceled by caller
TRPC_ENODATA = 1013         # no server available from LB/naming
TRPC_EEOF = 2001            # peer closed the connection
TRPC_EFAILEDSOCKET = 2002   # the socket was SetFailed while in use
TRPC_EINTERNAL = 2004       # server internal error
TRPC_EOVERCROWDED = 2006    # write queue over the in-flight cap
TRPC_ECONNECT = 2007        # connect failed

# The connection-killed subset: a stamped frame a pre-negotiation parser
# rejects surfaces client-side as one of these (the QoS self-heal keys
# on this tuple — see ParameterClient._qos_failed).
TRANSPORT_DEAD = (TRPC_EEOF, TRPC_EFAILEDSOCKET, TRPC_ECONNECT)

# Structural app-error codes, continuing the 2040+ range (param_server.py
# holds E_NO_SUCH 2040..E_EXISTS 2043, tensor.py E_UNDECODABLE 2044,
# collectives E_COLL_EPOCH 2045/E_COLL_ABORT 2046). These two are the
# serving fleet's routing signals; they live HERE so RpcError can
# classify them without importing the serving plane:
#   E_DRAINING      — the server refuses new sessions while it migrates
#                     its live ones out: retriable elsewhere/later, text
#                     carries the standard retry_after_ms pacer hint.
#   E_SESSION_MOVED — the session now lives on another server: the text
#                     carries "moved:<addr>" and the client FOLLOWS it
#                     (Gen/Resume), exactly the E_MOVED "moved:" shape
#                     the parameter fleet uses — keyed on the CODE, never
#                     on message strings.
E_DRAINING = 2047
E_SESSION_MOVED = 2048

_RETRY_AFTER_RE = re.compile(r"retry_after_ms=(\d+)")
_MOVED_RE = re.compile(r"moved:([^\s;,]+)")


def parse_moved(text: str) -> Optional[str]:
    """The ONE parser for the "moved:<addr>" forwarding grammar (error
    texts, E-frames, shed reasons) — every consumer (RpcError.moved_to,
    SessionShed.moved, the serving fleet's forwarding table) shares it
    so the grammar cannot drift between implementations."""
    if not text:
        return None
    m = _MOVED_RE.search(text)
    return m.group(1) if m else None

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_BUILD_DIR = os.path.join(_REPO, "native", "build")
_LIB_PATH = os.path.join(_BUILD_DIR, "libbrpc_tpu.so")

_HANDLER_CB = ctypes.CFUNCTYPE(
    None,
    ctypes.c_void_p,                    # ctx
    ctypes.c_char_p,                    # method
    ctypes.c_void_p, ctypes.c_size_t,   # req
    ctypes.c_void_p, ctypes.c_size_t,   # attach
    ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_size_t),  # resp
    ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_size_t),  # resp_attach
    ctypes.POINTER(ctypes.c_int),       # error_code
    ctypes.c_void_p, ctypes.c_size_t,   # err_text buffer (C-owned)
)


def fill_err_text(err_text: int, err_text_cap: int, message: str) -> None:
    """Copy a handler failure message into the C-owned err_text buffer
    (NUL-terminated, truncated to cap-1) — it rides the wire back to the
    client's RpcError.text."""
    if not err_text or err_text_cap <= 1 or not message:
        return
    data = message.encode("utf-8", errors="replace")[:err_text_cap - 1]
    ctypes.memmove(err_text, data, len(data))
    ctypes.memset(err_text + len(data), 0, 1)

# PassiveStatus gauge callback: ctx -> current int64 value. Evaluated at
# scrape time under the native registry lock — keep the Python body trivial
# (no dump_vars/metric creation re-entry).
_GAUGE_CB = ctypes.CFUNCTYPE(ctypes.c_int64, ctypes.c_void_p)

# /sessionz provider: fill the JSON document into (buf, cap) with the dump
# copy-out convention; runs on a callback-pool pthread at page-scrape time.
_SESSIONZ_CB = ctypes.CFUNCTYPE(
    ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t)

# HTTP streaming fallback handler: (ctx, path, query, progressive_id,
# body*, body_len*, use_progressive*, status*) — setting use_progressive=1
# turns the response into an unbounded chunked body fed afterwards via
# tbrpc_progressive_write(progressive_id, ...).
_HTTP_STREAM_CB = ctypes.CFUNCTYPE(
    None, ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
    ctypes.c_uint64, ctypes.POINTER(ctypes.c_void_p),
    ctypes.POINTER(ctypes.c_size_t), ctypes.POINTER(ctypes.c_int),
    ctypes.POINTER(ctypes.c_int))

_lib = None

# Native handles torn down during interpreter FINALIZATION (module-dict
# clearing order) abort in glibc — a client channel to a live in-process
# server destroyed that late double-frees. Destroying explicitly is always
# safe, so every wrapper registers here and one atexit hook (which runs
# BEFORE module teardown) closes channels first, then servers.
_LIVE_CHANNELS: "weakref.WeakSet" = weakref.WeakSet()
_LIVE_SERVERS: "weakref.WeakSet" = weakref.WeakSet()


def _teardown_native_handles() -> None:
    for ch in list(_LIVE_CHANNELS):
        try:
            ch.close()
        except Exception:  # noqa: BLE001 — best-effort exit hygiene
            pass
    for srv in list(_LIVE_SERVERS):
        try:
            srv.close()
        except Exception:  # noqa: BLE001
            pass


def _configured_here() -> bool:
    """True when native/build was configured from THIS checkout. A build
    tree copied from another path carries a CMakeCache.txt that points at
    that path's sources: cmake refuses to reuse it, and trusting its .so
    would load code this checkout never compiled."""
    cache = os.path.join(_BUILD_DIR, "CMakeCache.txt")
    try:
        with open(cache, encoding="utf-8", errors="replace") as fh:
            for line in fh:
                if line.startswith("CMAKE_CACHEFILE_DIR:"):
                    where = line.split("=", 1)[1].strip()
                    return os.path.realpath(where) == os.path.realpath(
                        _BUILD_DIR)
    except OSError:
        pass
    return False


def _have_toolchain() -> bool:
    return shutil.which("cmake") is not None and shutil.which("ninja") is not None


def build(target: Optional[str] = None) -> str:
    """Build native/build from this checkout's own sources, incrementally;
    returns the library path. A build tree configured elsewhere (or not at
    all) is replaced by a fresh configure first. ``target`` limits the
    build to one cmake target (``"brpc_tpu"``: the library alone).
    Without cmake and ninja it raises and leaves native/build untouched."""
    if not _have_toolchain():
        raise RuntimeError("building native/ needs cmake and ninja on PATH")
    # Runs before any server, channel, or fiber exists — there is no
    # handler path to stall yet. The file lock serializes processes that
    # build at once (test workers, a bench and its children): the second
    # one finds the tree configured and its build is a no-op.
    with open(os.path.join(_REPO, "native", ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)  # tpulint: allow(py-blocking)
        if not _configured_here():
            shutil.rmtree(_BUILD_DIR, ignore_errors=True)
            subprocess.run(  # tpulint: allow(py-blocking)
                ["cmake", "-S", "native", "-B", _BUILD_DIR, "-G", "Ninja",
                 "-DCMAKE_BUILD_TYPE=RelWithDebInfo"],
                cwd=_REPO, check=True, capture_output=True)
        cmd = ["cmake", "--build", _BUILD_DIR]
        if target is not None:
            cmd += ["--target", target]
        subprocess.run(  # tpulint: allow(py-blocking)
            cmd, cwd=_REPO, check=True, capture_output=True)
    return _LIB_PATH


def lib() -> ctypes.CDLL:
    """Loads the native library, building it first when this checkout has
    no build of its own. Entry points that must run this checkout's
    current sources call ``build()`` themselves (an incremental build is
    not free, and concurrent test workers must not race one build tree).
    A prebuilt library from another path still loads where there is no
    toolchain to rebuild it."""
    global _lib
    if _lib is not None:
        return _lib
    have_lib = os.path.exists(_LIB_PATH)
    if not (have_lib and _configured_here()) and (
            _have_toolchain() or not have_lib):
        build()
    L = ctypes.CDLL(_LIB_PATH)
    L.tbrpc_server_create.restype = ctypes.c_void_p
    L.tbrpc_server_start.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    L.tbrpc_server_start_tls.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p]
    L.tbrpc_server_stop.argtypes = [ctypes.c_void_p]
    L.tbrpc_server_destroy.argtypes = [ctypes.c_void_p]
    L.tbrpc_server_add_echo_service.argtypes = [ctypes.c_void_p]
    L.tbrpc_server_set_inline.restype = ctypes.c_int
    L.tbrpc_server_set_inline.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
    L.tbrpc_server_add_callback_service.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, _HANDLER_CB, ctypes.c_void_p]
    L.tbrpc_channel_create.restype = ctypes.c_void_p
    L.tbrpc_channel_create.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int]
    L.tbrpc_channel_create_ex.restype = ctypes.c_void_p
    L.tbrpc_channel_create_ex.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int]
    L.tbrpc_channel_destroy.argtypes = [ctypes.c_void_p]
    L.tbrpc_call.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        ctypes.c_void_p, ctypes.c_size_t,
        ctypes.c_void_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_size_t),
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_size_t),
        ctypes.c_char_p, ctypes.c_size_t]
    L.tbrpc_alloc.restype = ctypes.c_void_p
    L.tbrpc_alloc.argtypes = [ctypes.c_size_t]
    L.tbrpc_free.argtypes = [ctypes.c_void_p]
    L.tbrpc_bench_echo_throughput.restype = ctypes.c_double
    L.tbrpc_bench_echo_throughput.argtypes = [
        ctypes.c_size_t, ctypes.c_int, ctypes.c_int]
    L.tbrpc_bench_echo_qps.restype = ctypes.c_double
    L.tbrpc_bench_echo_qps.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_double)]
    L.tbrpc_bench_echo_ex.restype = ctypes.c_double
    L.tbrpc_bench_echo_ex.argtypes = [
        ctypes.c_size_t, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double)]
    # ---- observability: metrics + dumps + tracing (capi.h) ----
    L.tbrpc_var_adder_create.restype = ctypes.c_void_p
    L.tbrpc_var_adder_create.argtypes = [ctypes.c_char_p]
    L.tbrpc_var_adder_add.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    L.tbrpc_var_adder_value.restype = ctypes.c_int64
    L.tbrpc_var_adder_value.argtypes = [ctypes.c_void_p]
    L.tbrpc_var_latency_create.restype = ctypes.c_void_p
    L.tbrpc_var_latency_create.argtypes = [ctypes.c_char_p]
    L.tbrpc_var_latency_record.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    L.tbrpc_var_latency_value.restype = ctypes.c_int64
    L.tbrpc_var_latency_value.argtypes = [ctypes.c_void_p, ctypes.c_int]
    L.tbrpc_var_gauge_create.restype = ctypes.c_void_p
    L.tbrpc_var_gauge_create.argtypes = [
        ctypes.c_char_p, _GAUGE_CB, ctypes.c_void_p]
    L.tbrpc_vars_dump.restype = ctypes.c_int64
    L.tbrpc_vars_dump.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t]
    L.tbrpc_vars_dump_prometheus.restype = ctypes.c_int64
    L.tbrpc_vars_dump_prometheus.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    L.tbrpc_rpcz_dump_json.restype = ctypes.c_int64
    L.tbrpc_rpcz_dump_json.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_size_t]
    # Hang forensics: callable from ANY plain pthread even when every
    # fiber worker is parked (how the socket-id-0 credit-leak wedge was
    # root-caused in an old host run, records deleted in PR 21).
    L.tbrpc_debug_dump_fibers.restype = ctypes.c_int64
    L.tbrpc_debug_dump_fibers.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    L.tbrpc_debug_dump_ici.restype = ctypes.c_int64
    L.tbrpc_debug_dump_ici.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    # Flight recorder + stall watchdog (the self-monitoring layer): all of
    # these stay callable from any plain Python thread while every fiber
    # worker is parked — brpc_tpu.observability.health rides them.
    L.tbrpc_flight_snapshot.restype = ctypes.c_int64
    L.tbrpc_flight_snapshot.argtypes = [
        ctypes.c_int64, ctypes.c_char_p, ctypes.c_size_t]
    L.tbrpc_flight_total_events.restype = ctypes.c_int64
    L.tbrpc_watchdog_start.restype = ctypes.c_int
    L.tbrpc_watchdog_start.argtypes = [ctypes.c_char_p]
    L.tbrpc_watchdog_stop.restype = ctypes.c_int
    L.tbrpc_health_state.restype = ctypes.c_int
    L.tbrpc_health_dump_json.restype = ctypes.c_int64
    L.tbrpc_health_dump_json.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    L.tbrpc_health_last_dump_path.restype = ctypes.c_int64
    L.tbrpc_health_last_dump_path.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t]
    L.tbrpc_debug_hold_workers.restype = ctypes.c_int
    L.tbrpc_debug_hold_workers.argtypes = [ctypes.c_int, ctypes.c_int64]
    L.tbrpc_debug_induce_contention.restype = ctypes.c_int64
    L.tbrpc_debug_induce_contention.argtypes = [ctypes.c_int, ctypes.c_int64]
    L.tbrpc_rpcz_enabled.restype = ctypes.c_int
    L.tbrpc_rpcz_set_enabled.argtypes = [ctypes.c_int]
    # Head sampling for Python-created ROOT spans (trace_span): combines
    # rpcz_enabled with the reloadable rpcz_sample_1_in_n flag.
    L.tbrpc_rpcz_sample_root.restype = ctypes.c_int
    L.tbrpc_rpcz_sample_root.argtypes = []
    L.tbrpc_rpcz_sample_1_in_n.restype = ctypes.c_int
    L.tbrpc_rpcz_sample_1_in_n.argtypes = []
    L.tbrpc_trace_new_id.restype = ctypes.c_uint64
    L.tbrpc_trace_current.argtypes = [
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64)]
    L.tbrpc_trace_set.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
    L.tbrpc_span_annotate.argtypes = [ctypes.c_char_p]
    L.tbrpc_span_emit.argtypes = [
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int, ctypes.c_char_p]
    L.tbrpc_now_us.restype = ctypes.c_int64
    L.tbrpc_flag_set.restype = ctypes.c_int
    L.tbrpc_flag_set.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    # Fleet: the process-global service registry (brpc_tpu/fleet rides it
    # over plain HTTP once installed; clear is test isolation).
    L.tbrpc_registry_install.restype = ctypes.c_int
    L.tbrpc_registry_install.argtypes = []
    L.tbrpc_registry_clear.restype = ctypes.c_int
    L.tbrpc_registry_clear.argtypes = []
    # Overload protection: ambient QoS context (priority lanes + tenant),
    # deadline propagation, per-tenant quotas, and the latency-injection
    # test hook (capi.h "overload protection" section).
    L.tbrpc_qos_set.restype = ctypes.c_int
    L.tbrpc_qos_set.argtypes = [ctypes.c_int, ctypes.c_char_p]
    L.tbrpc_qos_clear.restype = None
    L.tbrpc_qos_clear.argtypes = []
    L.tbrpc_qos_get.restype = ctypes.c_int64
    L.tbrpc_qos_get.argtypes = [
        ctypes.POINTER(ctypes.c_int), ctypes.c_char_p, ctypes.c_size_t]
    L.tbrpc_deadline_remaining_ms.restype = ctypes.c_int64
    L.tbrpc_deadline_remaining_ms.argtypes = []
    L.tbrpc_server_set_max_concurrency.restype = ctypes.c_int
    L.tbrpc_server_set_max_concurrency.argtypes = [
        ctypes.c_void_p, ctypes.c_int32]
    L.tbrpc_server_set_tenant_quota.restype = ctypes.c_int
    L.tbrpc_server_set_tenant_quota.argtypes = [
        ctypes.c_void_p, ctypes.c_int32]
    L.tbrpc_server_tenantz_json.restype = ctypes.c_int64
    L.tbrpc_server_tenantz_json.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t]
    L.tbrpc_debug_inject_latency.restype = ctypes.c_int
    L.tbrpc_debug_inject_latency.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    # Streaming RPC: the serving plane's transport (token streams over the
    # credit-windowed native Stream, tcp AND tpu://). Reads/writes run on
    # plain Python pthreads with the GIL released; a slow reader's
    # backpressure is confined to its own stream (manual consumption).
    L.tbrpc_stream_accept.restype = ctypes.c_int64
    L.tbrpc_stream_accept.argtypes = [ctypes.c_int64]
    L.tbrpc_stream_create.restype = ctypes.c_int64
    L.tbrpc_stream_create.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p, ctypes.c_size_t,
        ctypes.c_int64, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_size_t), ctypes.c_char_p, ctypes.c_size_t]
    L.tbrpc_stream_write.restype = ctypes.c_int
    L.tbrpc_stream_write.argtypes = [
        ctypes.c_uint64, ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int64]
    L.tbrpc_stream_read.restype = ctypes.c_int
    L.tbrpc_stream_read.argtypes = [
        ctypes.c_uint64, ctypes.c_int64, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_size_t)]
    L.tbrpc_stream_close.restype = ctypes.c_int
    L.tbrpc_stream_close.argtypes = [ctypes.c_uint64, ctypes.c_int]
    # Serving observability + HTTP streaming fallback.
    L.tbrpc_sessionz_set_provider.restype = ctypes.c_int
    L.tbrpc_sessionz_set_provider.argtypes = [_SESSIONZ_CB, ctypes.c_void_p]
    L.tbrpc_http_stream_register.restype = ctypes.c_int
    L.tbrpc_http_stream_register.argtypes = [
        ctypes.c_char_p, _HTTP_STREAM_CB, ctypes.c_void_p]
    L.tbrpc_progressive_write.restype = ctypes.c_int
    L.tbrpc_progressive_write.argtypes = [
        ctypes.c_uint64, ctypes.c_void_p, ctypes.c_size_t]
    L.tbrpc_progressive_close.restype = ctypes.c_int
    L.tbrpc_progressive_close.argtypes = [ctypes.c_uint64]
    _lib = L
    atexit.register(_teardown_native_handles)
    return L


@contextlib.contextmanager
def qos(priority: int = PRIORITY_NORMAL, tenant: str = ""):
    """Ambient QoS for calls issued inside the scope (THIS thread only —
    the native slot is per-thread, like the trace context): requests stamp
    `priority` (PRIORITY_HIGH/NORMAL/BULK) and `tenant` onto the wire, and
    the server's admission uses both (priority lanes + per-tenant quotas).
    With neither set, the wire stays byte-identical to the pre-QoS format.

    Nestable: exit restores the REAL surrounding ambient values (read back
    through the native slot), so a scope used inside a server handler —
    whose thread carries the request's own priority/tenant, installed
    natively — hands the handler's context back intact. The propagated
    DEADLINE lives in the same slot but is untouched by set/restore, so
    nested-call clamping survives any qos() nesting. Raises ValueError
    for tenants over the 256-byte wire cap."""
    L = lib()
    prev_prio = ctypes.c_int()
    prev_tenant = ctypes.create_string_buffer(512)  # cap is 256
    L.tbrpc_qos_get(ctypes.byref(prev_prio), prev_tenant, len(prev_tenant))
    if L.tbrpc_qos_set(priority,
                       tenant.encode() if tenant else b"") != 0:
        raise ValueError(f"tenant id too long ({len(tenant)} bytes > 256)")
    try:
        yield
    finally:
        L.tbrpc_qos_set(prev_prio.value, prev_tenant.value)


def deadline_remaining_ms() -> Optional[int]:
    """Remaining budget (ms) of the request this thread is handling —
    the deadline the client propagated, minus time already burned. None
    when no deadline is in scope (not inside a handler, or the client set
    no timeout). 0 means expired: shed the work, the caller is gone."""
    left = lib().tbrpc_deadline_remaining_ms()
    return None if left < 0 else int(left)


def inject_latency(service: str, ms: int) -> None:
    """TEST-ONLY (beside debug hold_workers): every admitted request to
    `service` holds its gate slot for `ms` before the handler runs —
    deterministic queueing for overload/shed tests. ms <= 0 clears;
    service='' clears all injections."""
    lib().tbrpc_debug_inject_latency(service.encode(), ms)


# Handler signature: (method: str, request: bytes, attachment: bytes)
#   -> (response: bytes, response_attachment: bytes) — raise RpcError to fail.
Handler = Callable[[str, bytes, bytes], Tuple[bytes, bytes]]


class RpcError(Exception):
    def __init__(self, code: int, text: str = ""):
        overloaded = code in (TRPC_ELIMIT, TRPC_EOVERCROWDED)
        super().__init__(
            f"rpc error {code}"
            + (" (server overloaded — back off)" if overloaded else "")
            + f": {text}")
        self.code = code
        self.text = text
        # Shed responses carry a computed drain-time hint in their text
        # (" (retry_after_ms=N)", from the server's EMA latency): clients
        # pace their retry on it instead of hot-looping into the shed
        # storm. None when the error carries no hint.
        m = _RETRY_AFTER_RE.search(text) if text else None
        self.retry_after_ms: Optional[int] = int(m.group(1)) if m else None

    @property
    def overloaded(self) -> bool:
        """True for the overload-shed codes (ELIMIT / EOVERCROWDED):
        retriable with backoff, and NEVER evidence that a parameter moved
        or a shard died (the fleet retry layer keeps them out of its
        reshard handling)."""
        return self.code in (TRPC_ELIMIT, TRPC_EOVERCROWDED)

    @property
    def draining(self) -> bool:
        """True when the server refused because it is draining
        (E_DRAINING): the request is fine, THIS server is leaving —
        retriable on another member, paced by retry_after_ms like an
        overload shed, but never counted as overload/capacity evidence."""
        return self.code == E_DRAINING

    @property
    def moved_to(self) -> Optional[str]:
        """The forwarding address of an E_SESSION_MOVED redirect (the
        "moved:<addr>" the text carries), or None — classification keys
        on the code; only a moved error is ever parsed for an address."""
        if self.code != E_SESSION_MOVED:
            return None
        return parse_moved(self.text)


class Server:
    """A native RPC server hosting Python (and native) services."""

    def __init__(self):
        self._L = lib()
        self._h = self._L.tbrpc_server_create()
        self._cbs = []  # keep CFUNCTYPE objects alive
        self.port: Optional[int] = None
        _LIVE_SERVERS.add(self)

    def add_echo_service(self) -> None:
        if self._L.tbrpc_server_add_echo_service(self._h) != 0:
            raise RuntimeError("add_echo_service failed")

    def set_inline(self, service: str, enabled: bool = True) -> None:
        """Run SMALL requests to `service` directly on the input fiber (the
        small-RPC inline fast path), skipping the dispatch hop.

        Only native services whose implementation declares itself
        non-blocking qualify; Python handler services are ALWAYS refused —
        they park the fiber on the GIL-safe callback pool, and a parked
        input fiber would head-of-line-block its whole connection."""
        if self._L.tbrpc_server_set_inline(
                self._h, service.encode(), 1 if enabled else 0) != 0:
            raise RuntimeError(
                f"set_inline({service!r}) refused: unknown service or not "
                "inline-safe (Python handlers always run on the callback "
                "pool)")

    def set_max_concurrency(self, max_inflight: int) -> None:
        """Concurrency gate applied at start() (0 = unlimited). Requests
        over the cap shed with ELIMIT + a retry_after_ms hint; the BULK
        lane additionally keeps rpc_bulk_headroom_pct of the gate free
        for control-plane traffic. Must be called BEFORE start()."""
        if self._L.tbrpc_server_set_max_concurrency(
                self._h, max_inflight) != 0:
            raise RuntimeError(
                "set_max_concurrency must be called before start()")

    def set_tenant_quota(self, max_inflight: int) -> None:
        """Per-tenant in-flight quota layered under the global gate
        (0 = off): each tenant (QoS meta field, falling back to the peer
        ip) sheds its own overflow before it can crowd out others.
        Runtime-safe."""
        if self._L.tbrpc_server_set_tenant_quota(self._h, max_inflight) != 0:
            raise RuntimeError("set_tenant_quota failed")

    def tenantz(self) -> dict:
        """The per-tenant admission table: {"quota": N, "tenants":
        [{name, admitted, shed, inflight, quota}, ...]} — the same
        document /tenantz?format=json serves."""
        import json as _json

        cap = 1 << 16
        while True:
            buf = ctypes.create_string_buffer(cap)
            need = self._L.tbrpc_server_tenantz_json(self._h, buf, cap)
            if need < cap:
                return _json.loads(buf.value.decode())
            cap = int(need) + 1

    def add_service(self, name: str, handler: Handler) -> None:
        L = self._L

        def trampoline(ctx, method, req, req_len, att, att_len,
                       resp, resp_len, resp_att, resp_att_len, error_code,
                       err_text, err_text_cap):
            try:
                request = ctypes.string_at(req, req_len) if req_len else b""
                attachment = ctypes.string_at(att, att_len) if att_len else b""
                r, ra = handler(method.decode(), request, attachment)
                for data, pp, pl in ((r, resp, resp_len),
                                     (ra, resp_att, resp_att_len)):
                    if data:
                        buf = L.tbrpc_alloc(len(data))
                        ctypes.memmove(buf, data, len(data))
                        pp[0] = buf
                        pl[0] = len(data)
            except RpcError as e:
                error_code[0] = e.code if e.code != 0 else TRPC_EINTERNAL
                fill_err_text(err_text, err_text_cap, e.text)
            except Exception as e:  # noqa: BLE001 — handler bug => EINTERNAL
                error_code[0] = TRPC_EINTERNAL
                fill_err_text(err_text, err_text_cap,
                              f"{type(e).__name__}: {e}")

        cb = _HANDLER_CB(trampoline)
        self._cbs.append(cb)
        if L.tbrpc_server_add_callback_service(
                self._h, name.encode(), cb, None) != 0:
            raise RuntimeError(f"add_service({name}) failed")

    def start(self, addr: str = "127.0.0.1:0", *, ssl_cert: str = "",
              ssl_key: str = "") -> int:
        """ssl_cert+ssl_key make the port ALSO accept TLS (sniffed, so
        plaintext clients keep working; ALPN offers h2 for gRPC-over-TLS)."""
        if not self._h:
            raise RuntimeError("server is closed")
        if ssl_cert or ssl_key:
            port = self._L.tbrpc_server_start_tls(
                self._h, addr.encode(), ssl_cert.encode(), ssl_key.encode())
        else:
            port = self._L.tbrpc_server_start(self._h, addr.encode())
        if port < 0:
            raise RuntimeError(f"server start on {addr} failed")
        self.port = port
        return port

    def stop(self) -> None:
        if self._h:  # no-op after close (stop-in-finally patterns)
            self._L.tbrpc_server_stop(self._h)

    def close(self) -> None:
        """Stop and release the native server (idempotent)."""
        if self._h:
            self._L.tbrpc_server_stop(self._h)
            self._L.tbrpc_server_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


class Channel:
    """Client stub to one server ("ip:port")."""

    def __init__(self, addr: str, timeout_ms: int = 1000, max_retry: int = 3,
                 protocol: str = "tstd"):
        """protocol: "tstd" (native framing) or "grpc" (gRPC over HTTP/2 —
        dials any standard gRPC server)."""
        self._L = lib()
        protos = {"tstd": 0, "grpc": 5}
        if protocol not in protos:
            raise ValueError(
                f"unknown protocol {protocol!r}; choose from {sorted(protos)}")
        proto = protos[protocol]
        self._h = self._L.tbrpc_channel_create_ex(
            addr.encode(), timeout_ms, max_retry, proto)
        if not self._h:
            raise RuntimeError(f"channel init to {addr} failed")
        _LIVE_CHANNELS.add(self)

    def call(self, service_method: str, request: bytes = b"",
             attachment: bytes = b"") -> Tuple[bytes, bytes]:
        if not self._h:
            # NULL through ctypes would be a native deref, not an error.
            raise RuntimeError("channel is closed")
        L = self._L
        resp = ctypes.c_void_p()
        resp_len = ctypes.c_size_t()
        resp_att = ctypes.c_void_p()
        resp_att_len = ctypes.c_size_t()
        errbuf = ctypes.create_string_buffer(256)
        rc = L.tbrpc_call(
            self._h, service_method.encode(),
            request, len(request), attachment, len(attachment),
            ctypes.byref(resp), ctypes.byref(resp_len),
            ctypes.byref(resp_att), ctypes.byref(resp_att_len),
            errbuf, len(errbuf))
        if rc != 0:
            raise RpcError(rc, errbuf.value.decode(errors="replace"))
        try:
            r = ctypes.string_at(resp, resp_len.value) if resp_len.value else b""
            ra = (ctypes.string_at(resp_att, resp_att_len.value)
                  if resp_att_len.value else b"")
        finally:
            L.tbrpc_free(resp)
            L.tbrpc_free(resp_att)
        return r, ra

    def close(self) -> None:
        """Release the native channel (idempotent)."""
        if self._h:
            self._L.tbrpc_channel_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass


# ---------------------------------------------------------------------------
# Streaming RPC: the serving plane's transport.
# ---------------------------------------------------------------------------

class StreamClosed(Exception):
    """The peer closed the stream (EOF). ``error`` carries the close code
    (0 = clean close); an abnormal close (connection death, server shed)
    surfaces it so readers can distinguish 'generation finished' from
    'stream died'."""

    def __init__(self, error: int = 0):
        super().__init__("stream closed"
                         + (f" (error {error})" if error else ""))
        self.error = error


class Stream:
    """One half of a native credit-windowed message stream (trpc/stream.h
    over the capi): ordered messages, per-stream flow control on BOTH
    transports (tcp and tpu://). Reads/writes block only the calling
    Python thread (ctypes releases the GIL); a slow reader exhausts ITS
    OWN peer window — never another stream's.

    Obtained from :func:`open_stream` (client) or :func:`accept_stream`
    (inside a server handler). Always :meth:`close` (context manager
    supported): the native read buffer lives until then."""

    def __init__(self, stream_id: int):
        self._L = lib()
        self.id = int(stream_id)
        self._closed = False

    def write(self, data: bytes, timeout_ms: int = -1) -> bool:
        """Send one message. timeout_ms < 0 blocks until the peer's
        window opens (credit backpressure), 0 probes, > 0 bounds the
        wait. Returns False when the window stayed exhausted for the
        whole bound (the caller buffers or sheds THIS stream); raises
        StreamClosed once the stream is gone."""
        rc = self._L.tbrpc_stream_write(self.id, data, len(data),
                                        timeout_ms)
        if rc == 0:
            return True
        if rc == errno.EAGAIN:  # credit stayed exhausted for the bound
            return False
        raise StreamClosed(rc)

    def read(self, timeout_ms: int = -1) -> Optional[bytes]:
        """Next message in order, or None on timeout. Raises StreamClosed
        at EOF (after the queue drained); consumption feedback — the
        peer's write credit — advances with each message taken here."""
        L = self._L
        data = ctypes.c_void_p()
        length = ctypes.c_size_t()
        rc = L.tbrpc_stream_read(self.id, timeout_ms, ctypes.byref(data),
                                 ctypes.byref(length))
        if rc == 0:
            try:
                return (ctypes.string_at(data, length.value)
                        if length.value else b"")
            finally:
                L.tbrpc_free(data)
        if rc == -1:
            return None
        if rc in (1, -2):
            raise StreamClosed(0)
        raise StreamClosed(rc)

    def close(self, error: int = 0) -> None:
        """Close the local half and release the native read buffer.
        error > 0 rides the CLOSE control frame (bypassing the data
        credit window): the peer's reads drain, then raise StreamClosed
        with that code instead of a clean EOF — how a server shed stays
        visible to a reader whose window is full. Idempotent."""
        if not self._closed:
            self._closed = True
            self._L.tbrpc_stream_close(self.id, error)

    def __enter__(self) -> "Stream":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


def open_stream(channel: Channel, service_method: str,
                request: bytes = b"", *,
                max_buf_size: int = 0) -> Tuple[Stream, bytes]:
    """Open `service_method` with a stream attached (the RPC carries the
    handshake; the handler must call :func:`accept_stream`). Returns the
    CONNECTED stream and the RPC response body. max_buf_size (<= 0 =
    default 2MB) is OUR receive window — the peer's write budget."""
    if not channel._h:
        raise RuntimeError("channel is closed")
    L = lib()
    resp = ctypes.c_void_p()
    resp_len = ctypes.c_size_t()
    errbuf = ctypes.create_string_buffer(256)
    sid = L.tbrpc_stream_create(
        channel._h, service_method.encode(), request, len(request),
        max_buf_size, ctypes.byref(resp), ctypes.byref(resp_len),
        errbuf, len(errbuf))
    if sid <= 0:
        raise RpcError(int(-sid) if sid < 0 else TRPC_EINTERNAL,
                       errbuf.value.decode(errors="replace"))
    try:
        body = (ctypes.string_at(resp, resp_len.value)
                if resp_len.value else b"")
    finally:
        L.tbrpc_free(resp)
    return Stream(sid), body


def accept_stream(max_buf_size: int = 0) -> Optional[Stream]:
    """Accept the client's stream from INSIDE a service handler (the
    callback-pool thread), before returning — the response carries the
    acceptance. None when the client didn't attach a stream (or called
    outside a handler). max_buf_size is the server's receive window."""
    sid = lib().tbrpc_stream_accept(max_buf_size)
    return Stream(sid) if sid > 0 else None


# CFUNCTYPE trampolines registered with process-lifetime native slots must
# never be collected while native may still call them (HTTP handlers).
_immortal_native_cbs: list = []

# The /sessionz provider slot holds exactly ONE trampoline: the native
# side swaps AND scrapes under one mutex, so once
# tbrpc_sessionz_set_provider returns, the previous trampoline can never
# be called again — releasing it here (instead of an immortal append)
# keeps a replaced provider's closure (a whole SessionManager graph) from
# being pinned for the process lifetime.
_sessionz_holder: dict = {"fn": None, "cb": None}


def set_sessionz_provider(fn: Optional[Callable[[], str]]) -> None:
    """(Re)point the /sessionz console page at `fn` (returns the JSON
    document string); None clears it. The callback runs on a pool pthread
    at page-scrape time — keep it snapshot-cheap."""
    L = lib()
    if fn is None:
        L.tbrpc_sessionz_set_provider(ctypes.cast(None, _SESSIONZ_CB),
                                      None)
        _sessionz_holder["fn"] = _sessionz_holder["cb"] = None
        return

    def _cb(_ctx, buf, cap) -> int:
        try:
            doc = fn().encode()
        except Exception:  # noqa: BLE001 — a failing provider reads empty
            doc = b"{}"
        if buf and cap > 0:
            n = min(len(doc), cap - 1)
            ctypes.memmove(buf, doc, n)
            ctypes.memset(buf + n, 0, 1)
        return len(doc)

    cb = _SESSIONZ_CB(_cb)
    L.tbrpc_sessionz_set_provider(cb, None)
    _sessionz_holder["fn"] = fn
    _sessionz_holder["cb"] = cb  # old trampoline unreferenced -> GC


def clear_sessionz_provider(fn: Callable[[], str]) -> None:
    """Clear the /sessionz provider IF `fn` is still the registered one
    (a shutdown must not clear a newer manager's registration)."""
    if _sessionz_holder["fn"] is fn:
        set_sessionz_provider(None)


# HTTP streaming fallback handler signature:
#   (path: str, query: str, progressive_id: int)
#     -> (status: int, body: bytes, progressive: bool)
# progressive=True keeps the response open; feed it with
# progressive_write(progressive_id, ...) then progressive_close(...).
HttpStreamHandler = Callable[[str, str, int], Tuple[int, bytes, bool]]


def register_http_stream_handler(path: str, fn: HttpStreamHandler) -> None:
    """Serve `path` on every server's builtin HTTP port with optional
    ProgressiveAttachment streaming — the plain-HTTP fallback for token
    streams (curl consumes them without speaking tstd)."""
    L = lib()

    def _cb(_ctx, cpath, cquery, pid, body, body_len, use_prog, status):
        try:
            st, payload, progressive = fn(
                cpath.decode() if cpath else "",
                cquery.decode() if cquery else "", int(pid))
        except Exception as e:  # noqa: BLE001 — handler bug => 500
            st, payload, progressive = 500, f"{type(e).__name__}: {e}\n"\
                .encode(), False
        status[0] = int(st)
        use_prog[0] = 1 if progressive else 0
        if payload:
            buf = L.tbrpc_alloc(len(payload))
            ctypes.memmove(buf, payload, len(payload))
            body[0] = buf
            body_len[0] = len(payload)

    cb = _HTTP_STREAM_CB(_cb)
    _immortal_native_cbs.append(cb)
    if L.tbrpc_http_stream_register(path.encode(), cb, None) != 0:
        raise RuntimeError(f"http path already registered: {path!r}")


def progressive_write(progressive_id: int, data: bytes) -> bool:
    """Feed a progressive HTTP response; False once the peer is gone."""
    return lib().tbrpc_progressive_write(
        progressive_id, data, len(data)) == 0


def progressive_close(progressive_id: int) -> None:
    """Terminal chunk; the connection closes after it drains."""
    lib().tbrpc_progressive_close(progressive_id)


def bench_echo_throughput(payload_size: int, seconds: int = 2,
                          concurrency: int = 4) -> float:
    """One-way payload bytes/sec through a loopback echo server."""
    return lib().tbrpc_bench_echo_throughput(payload_size, seconds,
                                             concurrency)


def bench_echo_qps(seconds: int = 2, concurrency: int = 8):
    """(calls/sec, p99_us) for small-payload loopback echo."""
    p99 = ctypes.c_double()
    qps = lib().tbrpc_bench_echo_qps(seconds, concurrency, ctypes.byref(p99))
    return qps, p99.value


def bench_echo_ex(payload_size: int, seconds: int = 2, concurrency: int = 4,
                  transport: str = "tcp", conn_type: str = "single"):
    """One bench point with full control.

    Returns (oneway_bytes_per_sec, calls_per_sec, p50_us, p99_us).
    transport: "tcp" | "tpu" (shm ICI transport over the loopback control
    channel). conn_type: "single" | "pooled" | "short".
    """
    qps = ctypes.c_double()
    p50 = ctypes.c_double()
    p99 = ctypes.c_double()
    bps = lib().tbrpc_bench_echo_ex(
        payload_size, seconds, concurrency,
        {"tcp": 0, "tpu": 1}[transport],
        {"single": 0, "pooled": 1, "short": 2}[conn_type],
        ctypes.byref(qps), ctypes.byref(p50), ctypes.byref(p99))
    return bps, qps.value, p50.value, p99.value
