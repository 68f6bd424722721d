"""One run of one cell, from the seeded data to the result's last line.

What every cell shares is here, in this order: the native library is
loaded before JAX (run.py), the platform and the chip count are checked,
the peaks looked up, and the cell's driver (benchmark/drivers/, named by
its mix) sets up, warms up and drives its window inside a ``Session``,
which marks the set-up, switches tracing on, takes ``setup_s``, runs the
compile watch and the profiler slice, and reads the rpcz spans and the
memory peak over the cell's chips. The driver checks what the window
produced against its plain reference; the harness reduces the trace,
runs the metric readers and prints the verdict.
"""

from __future__ import annotations

import functools
import json
import os
import resource
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from benchmark import check, devtrace, spec

TINY = 64              # the rehearsal's cap on every dimension
TRACE_LEAD_S = 1.0     # the traced slice starts this far into the window
TRACE_SLICE_S = 4.0    # and lasts at most this long
RPCZ_SPANS = 1 << 16   # rpcz ring in the traced run (3 spans per call)


@dataclass
class Run:
    """What the metric readers (benchmark/metrics/*.py) read."""
    cell: spec.Cell
    nbytes: List[int]
    calls: np.ndarray            # (kind, tensor, version, t0, t1) per call
    window_s: float
    setup_s: float
    peaks: Dict[str, float] = field(default_factory=dict)
    trace: Optional[devtrace.Reduced] = None
    spans: Optional[List[dict]] = None
    window_compiles: int = 0     # programs traced or compiled in the window
    extra: Dict[str, float] = field(default_factory=dict)  # driver's counts


@dataclass
class Outcome:
    """What a driver's ``run`` hands back: the window's calls (kind,
    tensor, version, t0, t1) and the bytes of each tensor they name, the
    window's length, the calls that failed and why, each compared number
    beside its limit, the readings they came from (with ``control``, the
    control's too), and counts of its own for the readers (``Run.extra``)."""
    nbytes: List[int]
    calls: np.ndarray
    window_s: float
    failed: int
    errors: List[str]
    checks: Dict[str, Dict[str, float]]
    readings: Dict[str, object]
    extra: Dict[str, float] = field(default_factory=dict)


def process_start_wall() -> float:
    """Wall-clock time at which this process started (Linux /proc)."""
    try:
        with open("/proc/self/stat") as fh:
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as fh:
            btime = next(int(ln.split()[1]) for ln in fh
                         if ln.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return time.time()


def host_counters() -> tuple:
    """(steal, all) jiffies over every CPU of the host (/proc/stat), and
    this process's CPU seconds and involuntary context switches."""
    try:
        with open("/proc/stat") as fh:
            cpu = [int(x) for x in fh.readline().split()[1:9]]
    except (OSError, ValueError):
        cpu = [0] * 8
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return cpu[7], sum(cpu), ru.ru_utime + ru.ru_stime, ru.ru_nivcsw


def host_summary(before: tuple, after: tuple, window_s: float) -> str:
    """What else held the host during the window: for the log, to tell a
    slow run's cause (other guests, or this process starved of CPU)."""
    steal, total, cpu_s, nivcsw = (b - a for a, b in zip(before, after))
    return (f"host: steal {100 * steal / max(total, 1):.2f}% of all CPUs, "
            f"process {cpu_s / window_s:.2f} cores, {nivcsw} involuntary "
            f"context switches")


def _log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def _profiler_slice(logdir: str):
    """The traced run's profiler slice, run on the main thread while the
    workers drive the window."""
    import jax

    def during(t_end: float) -> None:
        now = time.perf_counter()
        start = min(now + TRACE_LEAD_S, t_end - 0.5)
        time.sleep(max(0.0, start - now))
        length = max(0.2, min(TRACE_SLICE_S, t_end - time.perf_counter()
                              - 0.2))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(devtrace.SLICE):
                time.sleep(length)
        finally:
            jax.profiler.stop_trace()

    return during


class CompileWatch:
    """Counts the programs JAX traces and compiles while ``on``: the
    window must find every program warmed up (both counts 0). One
    listener per process (``watch()``), reset for each window."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.on = False
        self.counts = {self.TRACE: 0, self.COMPILE: 0}
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event: str, _secs: float, **_kw) -> None:
        if self.on and event in self.counts:
            self.counts[event] += 1

    def start(self) -> None:
        self.counts = dict.fromkeys(self.counts, 0)
        self.on = True

    def summary(self) -> str:
        return (f"{self.counts[self.TRACE]} traced, "
                f"{self.counts[self.COMPILE]} compiled")


@functools.lru_cache(maxsize=None)
def watch() -> CompileWatch:
    return CompileWatch()


class Session:
    """The harness's side of one run, handed to the cell's driver."""

    def __init__(self, args, t_proc: float, devices, marks: List[tuple]):
        self.args = args
        self.t_proc = t_proc
        self.devices = devices       # the cell's chips: devices[:chips]
        self.marks = marks
        self.setup_s = 0.0
        self.window_s = 0.0
        self.window_compiles = 0
        self.spans: Optional[List[dict]] = None
        self.mem_peak = 0
        self.logdir: Optional[str] = None

    @staticmethod
    def log(msg: str) -> None:
        _log(msg)

    def mark(self, name: str) -> None:
        """The end of one step of the set-up, for the set-up's log line."""
        self.marks.append((name, time.time()))

    def window(self, body: Callable, describe: str,
               detail: Callable[[], str]) -> Tuple[float, float]:
        """The measured window. Switches tracing on (``--trace 1``), takes
        ``setup_s``, runs ``body(seconds, during) -> (t_open, t_close)``
        under the compile watch, where ``during(t_end)`` is the profiler
        slice (None untraced) for the body to run while its load runs, then
        collects the rpcz spans and the memory peak over the cell's chips.
        ``describe`` and ``detail()`` go into the log. -> (t_open, t_close)"""
        args = self.args
        during = None
        if args.trace:
            from brpc_tpu.observability import tracing
            from brpc_tpu.runtime import native

            native.lib().tbrpc_flag_set(b"rpcz_max_spans",
                                        str(RPCZ_SPANS).encode())
            tracing.rpcz_set_sample_1_in_n(1)
            tracing.rpcz_enable(True)
            self.logdir = tempfile.mkdtemp(prefix="bench-trace-")
            during = _profiler_slice(self.logdir)
        self.setup_s = time.time() - self.t_proc
        edges = [self.t_proc] + [t for _, t in self.marks]
        _log(f"setup {self.setup_s:.3f} s (" + ", ".join(
            f"{k} {b - a:.3f}" for (k, _), a, b in
            zip(self.marks, edges, edges[1:])) + f"); window {args.seconds} "
             f"s, {describe}")
        compiles = watch()
        compiles.start()
        host0 = host_counters()
        t_open, t_close = body(args.seconds, during)
        host1 = host_counters()
        compiles.on = False
        self.window_compiles = sum(compiles.counts.values())
        self.window_s = t_close - t_open
        _log(f"window {self.window_s:.3f} s: {compiles.summary()}; "
             f"{detail()}; " + host_summary(host0, host1, self.window_s))
        if args.trace:
            self.spans = [s for s in tracing.dump_rpcz()
                          if str(s.get("service_method", "")).startswith(
                              "bench.")]
            tracing.rpcz_enable(False)
        self.mem_peak = max(int((x.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for x in self.devices)
        return t_open, t_close


def run(args, t_proc: float, rehearse: bool = False) -> int:
    """One run: the result's last line on stdout, each compared number
    beside its limit as the last lines of stderr. -> exit code."""
    out = execute(args, t_proc, rehearse)
    if isinstance(out, int):
        return out
    line = out[0]
    for k, c in line["checks"].items():
        print(f"check {k}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def execute(args, t_proc: float, rehearse: bool = False,
            control: bool = False, root: str = spec.ROOT):
    """Everything of a run but the printing. -> (line, Run, readings), or
    an exit code when the platform cannot run the cell. ``control`` asks
    the driver for the control's readings too (calibrate.py); ``root`` is
    the checkout whose BENCHMARK.json and benchmark/ name the cell."""
    import jax

    cell = spec.load_cell(args.workload, root)
    marks = [("native", getattr(args, "t_native", t_proc))]
    devs = jax.devices()
    marks.append(("jax_init", time.time()))
    d = devs[0]
    if not rehearse:
        if d.platform != "tpu":
            _log(f"no TPU: JAX sees platform {d.platform!r}")
            return 2
        if len(devs) < cell.chips:
            _log(f"{cell.name} needs {cell.chips} chips, JAX sees "
                 f"{len(devs)}")
            return 2
    peaks = spec.peaks(d.device_kind, cell.root) if not rehearse else {}
    session = Session(args, t_proc, devs[:cell.chips], marks)
    out = cell.driver.run(cell, args, session, rehearse=rehearse,
                          control=control)
    correct = check.passed(out.checks)

    result = Run(cell=cell, nbytes=out.nbytes, calls=out.calls,
                 window_s=out.window_s, setup_s=session.setup_s, peaks=peaks,
                 spans=session.spans, window_compiles=session.window_compiles,
                 extra=dict(out.extra))
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": cell.chips, "memory_peak_bytes": session.mem_peak}
    line = {"correct": bool(correct),
            "attempted": int(len(out.calls) + out.failed),
            "failed": int(out.failed)}
    breakdown = None
    if args.trace:
        try:
            result.trace = devtrace.reduce(devtrace.extract(
                devtrace.xplane_file(session.logdir)))
        finally:
            shutil.rmtree(session.logdir, ignore_errors=True)
        if result.trace is not None:
            device["busy_s"] = result.trace.busy_s
            device["window_s"] = result.trace.window_s
            breakdown = devtrace.breakdown(result.trace)
    metrics = {}
    if not rehearse:
        for m in cell.metrics(bool(args.trace)):
            value = cell.readers[m["name"]](result)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    if breakdown is not None and not rehearse:
        line["breakdown"] = breakdown
    if rehearse:
        line["rehearsal"] = True
    line["checks"] = out.checks
    return line, result, out.readings
