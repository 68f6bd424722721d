"""rpcz tracing from Python — the ctypes boundary stops being a trace hole.

The native stack already propagates {trace_id, span_id} through a
fiber-local slot and the tstd wire (native/trpc/span.h): a traced server
handler carries the server span while it runs, and any Channel call
issued from it parents there automatically — INCLUDING calls a Python
handler makes through tbrpc_call: handlers run on the capi's dedicated
callback pthreads (never on a fiber — ctypes' GIL pairing must stay on
one OS thread), and the pool hands the server span into the callback
thread's context before invoking the handler. On a plain Python thread
the context rides a thread-local slot, so a client-side `trace_span()`
makes the calls it issues parent to a Python root span.

What this module adds on top of the native machinery:
  * trace_span(name): a real Python-created span — times the body, links
    into the surrounding context (or starts a fresh trace), and records at
    /rpcz next to the native legs;
  * stage(name) / annotate(text): stage timings ("device_put=812us")
    attached to whatever span is ACTIVE — a server handler annotates its
    server span, a trace_span() body annotates itself. Each stage is also
    a "brpc.<name>" jax.profiler.TraceAnnotation, so it lands on the
    profiler's host plane on the same clock as the device's ops;
  * the GIL waits on both sides of the ctypes boundary: serving() opens a
    Python callback (gil_enter on the server span, from the callback
    pool's stamp; the pool annotates pool_wait itself), gil_returned()
    follows a traced tbrpc_call_tensor (gil_return on the client's span);
  * rpcz control and span dumps without HTTP round-trips.

While rpcz is off (the rpcz_enabled flag, flippable live at
/flags/rpcz_enabled?setvalue=1 or rpcz_enable()) the per-call entry
points — stage, annotate, trace_span, serving, gil_returned — make no
foreign call at all: "on" is one read of the flag's storage in place.
While it is on, their native calls go through native.pylib(), which
keeps the GIL across them instead of handing it off.
"""

from __future__ import annotations

import contextlib
import ctypes
import json
import sys
import time
from typing import Iterator, List, Tuple

from brpc_tpu.runtime import native

_OFF = contextlib.nullcontext()
_flag = None                # the rpcz_enabled flag's storage, read in place
_trace_annotation = None  # jax.profiler.TraceAnnotation, once JAX is in


class RpczDisabled(RuntimeError):
    """Typed "rpcz is off" signal.

    Raised by span dumps when collection is disabled, so callers (tests,
    the fleet observer) can tell "no spans because tracing is off" from
    "traced but nothing matched" — the two used to be the same empty
    list, which silently read as 'no traffic'. `source` names where the
    dump was attempted ("local", or a shard address in fleet context).
    """

    def __init__(self, source: str = "local"):
        super().__init__(
            f"rpcz is disabled on {source} (enable with rpcz_enable() or "
            "GET /flags/rpcz_enabled?setvalue=1)")
        self.source = source


def rpcz_enable(on: bool = True) -> None:
    native.lib().tbrpc_rpcz_set_enabled(1 if on else 0)


def rpcz_enabled() -> bool:
    """One read of the native flag's int64 storage: no foreign call (a
    CDLL call would release the GIL and take it back)."""
    global _flag
    if _flag is None:
        _flag = ctypes.c_int64.from_address(
            native.lib().tbrpc_rpcz_enabled_flag())
    return _flag.value != 0


def rpcz_set_sample_1_in_n(n: int) -> None:
    """Keep rpcz live at bounded cost: collect 1 of every `n` NEW root
    traces (1 = every trace). Spans inside a sampled trace always record,
    so sampled traces stay complete fleet-wide. Reloadable — the same
    storage as the native rpcz_sample_1_in_n flag."""
    if native.lib().tbrpc_flag_set(b"rpcz_sample_1_in_n",
                                   str(int(n)).encode()) != 0:
        raise ValueError(f"rpcz_sample_1_in_n rejected {n!r} (must be >= 1)")


def rpcz_sample_1_in_n() -> int:
    return native.lib().tbrpc_rpcz_sample_1_in_n()


def current_trace() -> Tuple[int, int]:
    """The active (trace_id, span_id) on this thread/fiber; (0, 0) = none."""
    t = ctypes.c_uint64()
    s = ctypes.c_uint64()
    native.pylib().tbrpc_trace_current(ctypes.byref(t), ctypes.byref(s))
    return t.value, s.value


def set_trace(trace_id: int, span_id: int) -> None:
    native.pylib().tbrpc_trace_set(trace_id, span_id)


def clear_trace() -> None:
    native.pylib().tbrpc_trace_clear()


def new_id() -> int:
    return native.pylib().tbrpc_trace_new_id()


def annotate(text: str) -> None:
    """Attach free-form text to the active span (no-op without one, and
    while rpcz is off)."""
    if rpcz_enabled():
        native.pylib().tbrpc_span_annotate(
            text.encode("utf-8", errors="replace"))


def _profiler_event(name: str):
    """A jax.profiler.TraceAnnotation named ``name`` where this process
    runs JAX (a process without it gets a no-op: tracing never imports
    JAX itself)."""
    global _trace_annotation
    if _trace_annotation is None:
        if "jax" not in sys.modules:
            return _OFF
        from jax.profiler import TraceAnnotation

        _trace_annotation = TraceAnnotation
    return _trace_annotation(name)


class _Stage:
    __slots__ = ("name", "_event", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> None:
        self._event = _profiler_event("brpc." + self.name)
        self._event.__enter__()
        self._t0 = time.monotonic()

    def __exit__(self, *exc) -> None:
        us = int((time.monotonic() - self._t0) * 1e6)
        self._event.__exit__(*exc)
        annotate(f"{self.name}={us}us")


def stage(name: str):
    """Time the body and attach "name=<us>us" to the ACTIVE span — the
    per-stage breakdown (rpc / arena_stage / device_put / handler /
    respond_stage / update_dispatch) the tensor path reports — inside a
    "brpc.<name>" profiler annotation. While rpcz is off: a shared no-op."""
    return _Stage(name) if rpcz_enabled() else _OFF


def _since_us(stamp_us: int) -> int:
    """Microseconds from a native stamp (gettimeofday) to now, on the same
    clock (CLOCK_REALTIME); a stamp of 0 means "not traced"."""
    return max(0, time.time_ns() // 1000 - stamp_us)


def serving():
    """The first line of a Python service callback: annotate the server
    span with ``gil_enter`` (the callback pool's call stamp to here: the
    GIL wait and ctypes' argument conversion) and open a "brpc.serve"
    profiler annotation around the callback. While rpcz is off, or for a
    request without a server span: a shared no-op."""
    if not rpcz_enabled():
        return _OFF
    stamp = native.pylib().tbrpc_callback_called_us()
    if stamp == 0:
        return _OFF
    annotate(f"gil_enter={_since_us(stamp)}us")
    return _profiler_event("brpc.serve")


def gil_returned() -> None:
    """The first line after a tbrpc_call_tensor: annotate the caller's
    active span with ``gil_return``, the time from the native return to
    this thread holding the GIL again."""
    if rpcz_enabled():
        stamp = native.pylib().tbrpc_call_returned_us()
        if stamp:
            annotate(f"gil_return={_since_us(stamp)}us")


class SpanHandle:
    """The identifiers of an open trace_span (query /rpcz?trace=%016x)."""

    def __init__(self, trace_id: int, span_id: int):
        self.trace_id = trace_id
        self.span_id = span_id
        self.error_code = 0

    def set_error(self, code: int) -> None:
        self.error_code = code

    @property
    def trace_hex(self) -> str:
        return f"{self.trace_id:016x}"


@contextlib.contextmanager
def trace_span(name: str, *, server_side: bool = False
               ) -> Iterator[SpanHandle]:
    """A Python-created rpcz span around the body.

    Links into the surrounding trace context when one is active (nested
    spans, server handlers) or starts a fresh trace (a client root); the
    body runs with THIS span as the context, so downstream tbrpc calls —
    and nested trace_spans — parent here. Recorded via tbrpc_span_emit on
    exit; while rpcz is off the body runs untraced, with no foreign call.
    """
    if not rpcz_enabled():
        yield SpanHandle(0, 0)
        return
    L = native.pylib()
    parent_trace, parent_span = current_trace()
    # Head sampling: a span with NO surrounding context would start a new
    # root trace — consult the 1-in-N gate exactly like the native client
    # path does. Unsampled roots run untraced (inert handle, no context
    # set); spans inside a sampled trace never re-consult the gate.
    if parent_trace == 0 and not L.tbrpc_rpcz_sample_root():
        yield SpanHandle(0, 0)
        return
    trace_id = parent_trace if parent_trace != 0 else new_id()
    span_id = new_id()
    handle = SpanHandle(trace_id, span_id)
    set_trace(trace_id, span_id)
    start_us = L.tbrpc_now_us()
    try:
        yield handle
    except BaseException:
        handle.error_code = handle.error_code or native.TRPC_EINTERNAL
        raise
    finally:
        end_us = L.tbrpc_now_us()
        # Restore the surrounding context (or clear a root's).
        if parent_trace != 0 or parent_span != 0:
            set_trace(parent_trace, parent_span)
        else:
            clear_trace()
        L.tbrpc_span_emit(trace_id, span_id, parent_span,
                          1 if server_side else 0, start_us, end_us,
                          handle.error_code, name.encode())


def dump_rpcz(trace_id: int = 0) -> List[dict]:
    """Collected spans as dicts (annotations included): every span field
    the /rpcz page renders, without the HTTP round-trip. trace_id != 0
    narrows to one trace, oldest first.

    Raises :class:`RpczDisabled` when collection is off — an empty list
    always means "nothing matched", never "tracing wasn't running".
    """
    from brpc_tpu.observability.metrics import _snapshot_buf

    if not rpcz_enabled():
        raise RpczDisabled("local")
    raw = _snapshot_buf(native.lib().tbrpc_rpcz_dump_json, trace_id)
    return json.loads(raw.decode(errors="replace")) if raw else []
