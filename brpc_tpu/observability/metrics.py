"""Python-registered tbvar metrics — the data plane's half of /vars.

Counters, latency recorders and passive gauges created here are NATIVE
tbvar variables (capi tbrpc_var_*): they live in the same process-wide
registry as the framework's own rpc_server_*/rpc_client_* series, so one
/vars, /brpc_metrics (Prometheus) and /tensorz view covers the fiber
runtime and the Python/JAX tensor path together. Names must scan as
Prometheus series ([a-zA-Z_:][a-zA-Z0-9_:]*) — tpulint's metric-name rule
checks literal registrations in this package.

Handles are immortal by design (the native registry references them for
the process lifetime) and deduplicated here by name: get-or-create
helpers (`counter`, `latency`, `gauge`) are the intended entry points so
instrumentation can run from module scope, reloads, or multiple call
sites without tripping tbvar's name-collision failure.
"""

from __future__ import annotations

import ctypes
import threading
from typing import Callable, Dict

from brpc_tpu.runtime import native


def _snapshot_buf(call, *args) -> bytes:
    """Two-call copy-out convention of the capi dumps: size, then fetch
    (retrying if the snapshot grew between the calls)."""
    need = call(*args, None, 0)
    while need > 0:
        buf = ctypes.create_string_buffer(need + 1)
        got = call(*args, buf, need + 1)
        if got <= need:
            return buf.value
        need = got
    return b""


class Counter:
    """A native Adder<int64> exposed under `name`."""

    def __init__(self, name: str):
        self._L = native.lib()
        self._P = native.pylib()  # the write keeps the GIL: see pylib()
        self._h = self._L.tbrpc_var_adder_create(name.encode())
        if not self._h:
            raise ValueError(f"metric name already registered: {name!r}")
        self.name = name

    def add(self, delta: int = 1) -> None:
        self._P.tbrpc_var_adder_add(self._h, delta)

    def value(self) -> int:
        return self._L.tbrpc_var_adder_value(self._h)


class LatencyRecorder:
    """The native latency bundle: exposes {prefix}_latency, _max_latency,
    _qps, _count, _latency_99, _latency_999 — identical shape to what every
    native RPC leg reports, so dashboards treat Python stages uniformly."""

    def __init__(self, prefix: str):
        self._L = native.lib()
        self._P = native.pylib()  # the write keeps the GIL: see pylib()
        self._h = self._L.tbrpc_var_latency_create(prefix.encode())
        if not self._h:
            raise ValueError(f"metric prefix already registered: {prefix!r}")
        self.prefix = prefix

    def record_us(self, latency_us: int) -> None:
        self._P.tbrpc_var_latency_record(self._h, max(0, int(latency_us)))

    def record_s(self, seconds: float) -> None:
        self.record_us(int(seconds * 1e6))

    def _v(self, what: int) -> int:
        return self._L.tbrpc_var_latency_value(self._h, what)

    def count(self) -> int:
        return self._v(0)

    def qps(self) -> int:
        return self._v(1)

    def avg_us(self) -> int:
        return self._v(2)

    def max_us(self) -> int:
        return self._v(3)

    def p50(self) -> int:
        return self._v(50)

    def p90(self) -> int:
        return self._v(90)

    def p99(self) -> int:
        return self._v(99)

    def p999(self) -> int:
        return self._v(999)


class PassiveGauge:
    """A native PassiveStatus<int64> whose value is `fn()` at scrape time.

    The callback runs under the native registry lock whenever /vars,
    /brpc_metrics or a dump walks the registry: keep `fn` trivial (return
    a number; no metric creation or dump re-entry from inside it).
    """

    def __init__(self, name: str, fn: Callable[[], int]):
        self._L = native.lib()

        def _cb(_ctx) -> int:
            try:
                return int(fn())
            except Exception:  # noqa: BLE001 — a failing gauge reads as -1
                return -1

        # The CFUNCTYPE trampoline must outlive the process-lifetime native
        # registration, even if THIS instance is dropped (direct
        # construction without keeping the object) — anchor it in the
        # module-immortal list; a GC'd trampoline would leave the native
        # PassiveStatus holding a freed pointer, crashing the next scrape.
        self._cb = native._GAUGE_CB(_cb)
        _immortal_cbs.append(self._cb)
        self._h = self._L.tbrpc_var_gauge_create(name.encode(), self._cb,
                                                 None)
        if not self._h:
            raise ValueError(f"metric name already registered: {name!r}")
        self.name = name


class NullSeries:
    """No-op stand-in for a Counter/LatencyRecorder when the native
    library is absent (same read surface) — the ONE implementation of
    the tier-1 metrics shim: planes that must import pure (serving,
    collectives) build their recorder dicts from this instead of each
    re-inventing it. Importing THIS module never loads the native lib;
    only constructing the real series does."""

    def record_s(self, *_a) -> None: ...

    def record_us(self, *_a) -> None: ...

    def add(self, *_a) -> None: ...

    def count(self) -> int:
        return 0

    def p99(self) -> int:
        return 0

    def qps(self) -> int:
        return 0

    def value(self) -> int:
        return 0


# ---- get-or-create registry ----

_mu = threading.Lock()
_registry: Dict[str, object] = {}
_immortal_cbs: list = []  # gauge trampolines live as long as the process


def _get_or_create(name: str, cls, factory):
    with _mu:
        got = _registry.get(name)
        if got is None:
            got = _registry[name] = factory()
        elif not isinstance(got, cls):
            # A name can hold ONE kind of series; returning the wrong
            # type here would silently flatline the caller's metric.
            raise TypeError(
                f"metric {name!r} is already a {type(got).__name__}, "
                f"not a {cls.__name__}")
        return got


def counter(name: str) -> Counter:
    return _get_or_create(name, Counter, lambda: Counter(name))


def latency(prefix: str) -> LatencyRecorder:
    return _get_or_create(prefix, LatencyRecorder,
                          lambda: LatencyRecorder(prefix))


def gauge(name: str, fn: Callable[[], int]) -> PassiveGauge:
    """Get-or-create; an existing gauge keeps its ORIGINAL fn (the native
    registration is immortal — re-pointing it is not possible)."""
    return _get_or_create(name, PassiveGauge,
                          lambda: PassiveGauge(name, fn))


def trainer_series() -> Dict[str, object]:
    """The layered trainer's series (models/mla_moe.py), per completed
    step: ``trainer_tokens`` counts its tokens; ``trainer_kda_chunks`` the
    chunks the KDA core ran, summed over layers and sequences;
    ``trainer_expert_slots`` takes one sample per held expert of each
    expert layer, the token-slots routed to it;
    ``trainer_expert_load_permille`` one sample per expert layer, the
    largest of those over their mean, in thousandths."""
    return {"tokens": counter("trainer_tokens"),
            "kda_chunks": counter("trainer_kda_chunks"),
            "expert_slots": latency("trainer_expert_slots"),
            "expert_load": latency("trainer_expert_load_permille")}


# Roles that restart within one process (fleet clients, migrators, a
# re-created named server) can't re-register their gauges — registrations
# are immortal and keep the original callback. These route reads through a
# re-pointable table instead: the newest repointable_gauge(name, ...) wins.

_repoint_mu = threading.Lock()
_repoint_holders: Dict[str, Callable[[], int]] = {}


def repointable_gauge(name: str, fn: Callable[[], int]) -> None:
    """(Re)point gauge `name` at `fn`; the native registration happens on
    the first call for the name and reads the CURRENT holder at scrape
    time. A failing holder reads as -1 (never unwinds into the scrape)."""
    with _repoint_mu:
        first = name not in _repoint_holders
        _repoint_holders[name] = fn
    if first:
        def _read(name=name) -> int:
            with _repoint_mu:
                f = _repoint_holders.get(name)
            try:
                return int(f()) if f is not None else 0
            except Exception:  # noqa: BLE001 — a failing gauge reads as -1
                return -1

        gauge(name, _read)


# ---- dumps (the same snapshots the console pages serve) ----

def dump_vars(prefix: str = "") -> str:
    """Every exposed variable as "name : value" lines (/vars parity)."""
    L = native.lib()
    return _snapshot_buf(L.tbrpc_vars_dump, prefix.encode()).decode(
        errors="replace")


def dump_prometheus() -> str:
    """Prometheus text format — byte-identical to /brpc_metrics."""
    L = native.lib()
    return _snapshot_buf(L.tbrpc_vars_dump_prometheus).decode(
        errors="replace")


def dump_fibers() -> str:
    """Every live fiber with state and (for parked fibers) a symbolized
    stack — the /fibers page, reachable from a plain watchdog thread even
    when every fiber worker is parked (hang forensics)."""
    L = native.lib()
    return _snapshot_buf(L.tbrpc_debug_dump_fibers).decode(errors="replace")


def dump_ici() -> str:
    """Sender/receiver state of every live tpu:// endpoint (TX credit
    level, pending control bytes, parked-writer flags) — the companion
    view to dump_fibers for wedge hunting."""
    L = native.lib()
    return _snapshot_buf(L.tbrpc_debug_dump_ici).decode(errors="replace")
