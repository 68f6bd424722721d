"""Round benchmark: the driver's metric is "RPC throughput (GB/s) + p99
latency, 64B-16MB payloads over ICI" (BASELINE.json).

Sweeps payload sizes over the tpu:// transport (shm-backed ICI endpoint —
the framework's answer to the reference's RDMA endpoint) and over plain TCP
at the 1MB headline point for comparison. Each point tries several
concurrency levels and keeps the best; the C-side loop (native/capi) keeps
Python out of the hot path.

Headline: 1MB one-way echo throughput over tpu://, compared against the
reference's BEST published number — 2.3 GB/s multi-connection echo
(docs/cn/benchmark.md:104, BASELINE.md) — not the flattering 0.8 GB/s
single-connection figure.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "sweep"}
— and persists the same document as BENCH_r<N>.json (N = one past the
highest committed round), so the machine-readable trajectory advances
with every full run.
"""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_GBPS = 2.3  # reference: multi-connection large-packet echo max

PAYLOADS = [64, 4096, 65536, 1 << 20, 16 << 20]
CONCURRENCY = [1, 2, 8, 16]


def _run_child(argv, **kw):
    """Every bench child runs through here, on the CPU platform: this
    process owns the chip (the device rows below), and the chip belongs to
    one process at a time. A child's members and servers inherit the
    setting, so none of them reaches for the chip either; the rows they
    produce say ``cpu``."""
    return subprocess.run(argv, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          **kw)


# Wedge watchdog: every tbrpc_bench_echo_ex sample runs in its OWN
# subprocess under a hard timeout. The C fiber-caller harness has a known
# failure mode on this host class (historically the socket-id-0 credit
# leak, seen in an old host run — plus any future all-threads-park bug):
# when it strikes, ALL threads park including the timer thread, so no
# in-process deadline can rescue the run. A killed subprocess records a
# {"wedged": true} sample and retries instead of hanging the whole bench.
_ECHO_EX_CHILD = r"""
import json, sys
sys.path.insert(0, {root!r})
from brpc_tpu.runtime import native
try:
    # Self-monitoring: if this sample wedges, the in-child watchdog writes
    # fiber stacks + ICI credit state + the flight tail into {dump_dir!r}
    # BEFORE the parent's hard timeout kills us — the wedge row then
    # carries its own forensics instead of only {{"wedged": true}}.
    from brpc_tpu.observability import health
    health.start_watchdog({dump_dir!r})
except Exception:
    pass
for _name, _value in {flags!r}:
    if native.lib().tbrpc_flag_set(_name.encode(), _value.encode()) != 0:
        raise SystemExit(f"tbrpc_flag_set({{_name}}={{_value}}) refused")
bps, qps, p50, p99 = native.bench_echo_ex(
    {payload}, seconds={seconds}, concurrency={conc},
    transport={transport!r}, conn_type={conn_type!r})
snap = {{}}
try:
    from brpc_tpu.observability import metrics as obs
    for line in obs.dump_vars("rpc_client").splitlines():
        name, _, value = line.partition(" : ")
        snap[name.strip()] = value.strip()
except Exception:
    pass
print(json.dumps({{"bps": bps, "qps": qps, "p50": p50, "p99": p99,
                   "rpc_client": snap}}))
"""


_BENCH_DUMP_DIR = None


def _dump_dir():
    """Stall-dump directory shared by every bench child of this run; the
    watchdog inside a wedged child writes here and the parent attaches the
    paths to the wedged sample after the kill."""
    global _BENCH_DUMP_DIR
    if _BENCH_DUMP_DIR is None:
        import tempfile
        _BENCH_DUMP_DIR = tempfile.mkdtemp(prefix="brpc_tpu_bench_dumps_")
    return _BENCH_DUMP_DIR


def _new_dump_files(seen):
    """Dump files that appeared since `seen` was last updated."""
    try:
        paths = sorted(os.path.join(_dump_dir(), n)
                       for n in os.listdir(_dump_dir()))
    except OSError:
        return []
    fresh = [p for p in paths if p not in seen]
    seen.update(fresh)
    return fresh


def _dump_transitions(path):
    """The health-state transition log a stall auto-dump carries (the
    wedged child's ok -> degraded -> stalled walk, with reasons)."""
    lines = []
    try:
        with open(path, encoding="utf-8", errors="replace") as fh:
            in_section = False
            for line in fh:
                if line.startswith("health transitions"):
                    in_section = True
                    continue
                if in_section:
                    if not line.startswith("  "):
                        break
                    lines.append(line.strip())
    except OSError:
        pass
    return lines


def bench_echo_ex_guarded(payload, seconds, concurrency, transport,
                          conn_type, retries=2, wedge_log=None, flags=()):
    """One echo sample in a watchdogged subprocess.

    Returns the child's result dict; after `retries` consecutive
    wedges/failures returns {"wedged": True, "attempts": N, "dump_files":
    [...]} — the child runs the native stall watchdog pointed at a shared
    dump dir, so a wedge row carries the auto-captured forensics (fiber
    stacks + ICI credit state + flight-recorder tail) of its own hang.
    """
    root = os.path.dirname(os.path.abspath(__file__))
    code = _ECHO_EX_CHILD.format(root=root, payload=payload, seconds=seconds,
                                 conc=concurrency, transport=transport,
                                 conn_type=conn_type, dump_dir=_dump_dir(),
                                 flags=tuple(flags))
    timeout = seconds * 3 + 30  # library load + server spin-up headroom
    wedges = 0
    seen_dumps = set(_new_dump_files(set()))  # ignore earlier samples' dumps
    dump_files = []
    for _ in range(retries + 1):
        try:
            proc = _run_child(  # tpulint: allow(py-blocking)
                [sys.executable, "-c", code], capture_output=True,
                timeout=timeout, text=True)
            out = proc.stdout.strip().splitlines()
            if proc.returncode == 0 and out:
                result = json.loads(out[-1])
                if wedges:
                    result["wedged_retries"] = wedges
                    result["dump_files"] = dump_files
                return result
            if proc.returncode != 0 and proc.stderr:
                # A fast crash (import error, stale .so) is NOT a wedge:
                # surface its traceback or the retry loop misdirects the
                # operator toward the transport.
                print(f"# bench child rc={proc.returncode}: "
                      f"{proc.stderr.strip()[-800:]}", file=sys.stderr)
        except subprocess.TimeoutExpired:
            pass
        wedges += 1
        fresh = _new_dump_files(seen_dumps)
        dump_files.extend(fresh)
        if wedge_log is not None:
            wedge_log.append({"payload": payload, "concurrency": concurrency,
                              "transport": transport, "dump_files": fresh})
        print(f"# WEDGED sample: payload={payload} conc={concurrency} "
              f"transport={transport} (attempt {wedges})"
              + (f"; watchdog dump: {' '.join(fresh)}" if fresh
                 else "; no watchdog dump captured"), file=sys.stderr)
    result = {"wedged": True, "attempts": wedges, "dump_files": dump_files}
    if dump_files:
        result["health_transitions"] = _dump_transitions(dump_files[-1])
    return result


def _ab_point(payload, a_flags, b_flags, a_key, b_key, reps=5, seconds=1,
              concurrency=16, wedge_log=None):
    """Interleaved A/B echo qps comparison (PERF.md methodology).

    Runs `reps` ADJACENT (A, B) subprocess pairs — this host's steal is
    bimodal, and a slow window hitting only one mode fabricates or destroys
    the comparison; adjacent samples see the same host state, so per-pair
    ratios are steal-robust. Reports median qps per mode plus the
    median-of-ratios speedup (A/B) with the raw per-pair ratios."""
    a_qps, b_qps, a_p99, b_p99, ratios = [], [], [], [], []
    for _ in range(reps):
        pair = {}
        for mode, flags in (("a", a_flags), ("b", b_flags)):
            r = bench_echo_ex_guarded(payload, seconds, concurrency, "tpu",
                                      "single", retries=1,
                                      wedge_log=wedge_log, flags=flags)
            pair[mode] = r
        if pair["a"].get("wedged") or pair["b"].get("wedged"):
            continue  # drop the PAIR: a half-wedged pair is not a sample
        a_qps.append(pair["a"]["qps"])
        b_qps.append(pair["b"]["qps"])
        a_p99.append(pair["a"]["p99"])
        b_p99.append(pair["b"]["p99"])
        ratios.append(pair["a"]["qps"] / max(pair["b"]["qps"], 1e-9))
    if not ratios:
        raise RuntimeError(f"every A/B pair wedged: payload={payload}")
    import statistics
    return {
        a_key + "_qps": round(statistics.median(a_qps)),
        b_key + "_qps": round(statistics.median(b_qps)),
        a_key + "_p99_us": round(statistics.median(a_p99)),
        b_key + "_p99_us": round(statistics.median(b_p99)),
        "speedup": round(statistics.median(ratios), 2),
        "speedup_samples": [round(r, 2) for r in ratios],
        "payload": payload, "concurrency": concurrency, "reps": len(ratios),
    }


def small_rpc_point(payload, reps=5, seconds=1, concurrency=16,
                    wedge_log=None):
    """Batched vs per-message dispatch at one small payload: the tentpole
    rows (rpc_small_qps_64B / rpc_small_qps_4KB). One reloadable flag flips
    the whole regime — rpc_dispatch_batch_max=1 restores fiber-per-message
    dispatch AND disables response coalescing (the seed's write path)."""
    row = _ab_point(payload,
                    a_flags=(("rpc_dispatch_batch_max", "16"),),
                    b_flags=(("rpc_dispatch_batch_max", "1"),),
                    a_key="batched", b_key="permsg", reps=reps,
                    seconds=seconds, concurrency=concurrency,
                    wedge_log=wedge_log)
    print(f"# rpc_small_qps_{payload}B: per-message {row['permsg_qps']} qps "
          f"-> batched {row['batched_qps']} qps ({row['speedup']}x, "
          f"samples {row['speedup_samples']})", file=sys.stderr)
    return row


def ici_threshold_point(reps=5, seconds=1, concurrency=16, wedge_log=None):
    """The ici_small_msg_threshold crossover at the 4KB payload (~4.1KB
    frames with tstd header+meta): threshold 16384 keeps these frames on
    the inline control channel; 64 forces every one through a TX block +
    doorbell + credit return. The winner decides the default documented in
    (old host run, records deleted in PR 21)."""
    row = _ab_point(4096,
                    a_flags=(("ici_small_msg_threshold", "16384"),),
                    b_flags=(("ici_small_msg_threshold", "64"),),
                    a_key="inline", b_key="block", reps=reps,
                    seconds=seconds, concurrency=concurrency,
                    wedge_log=wedge_log)
    print(f"# ici_threshold_4KB: block-path {row['block_qps']} qps vs "
          f"inline-path {row['inline_qps']} qps ({row['speedup']}x)",
          file=sys.stderr)
    return row


def input_poll_point(reps=5, seconds=1, wedge_log=None):
    """Doorbell-free input polling (rpc_input_poll_us) at the 64B conc=1
    ping-pong floor — the latency regime the ROADMAP's second one-sided
    tenant names. Polling keeps the input fiber re-reading its fd between
    back-to-back requests instead of parking into epoll, so each RPC
    skips the doorbell-edge wakeup (epoll_wait + dispatcher hop + fiber
    spawn). Interleaved poll/no-poll pairs, median-of-ratios on p50 (the
    floor statistic; p99 carries the steal tail)."""
    import statistics
    a_flags = (("rpc_input_poll_us", "200"),)
    b_flags = (("rpc_input_poll_us", "0"),)
    a_p50, b_p50, a_p99, b_p99, a_qps, b_qps, ratios = ([] for _ in range(7))
    for _ in range(reps):
        pair = {}
        for mode, flags in (("poll", a_flags), ("nopoll", b_flags)):
            pair[mode] = bench_echo_ex_guarded(
                64, seconds, 1, "tpu", "single", retries=1,
                wedge_log=wedge_log, flags=flags)
        if pair["poll"].get("wedged") or pair["nopoll"].get("wedged"):
            continue  # drop the PAIR (the _ab_point discipline)
        a_p50.append(pair["poll"]["p50"])
        b_p50.append(pair["nopoll"]["p50"])
        a_p99.append(pair["poll"]["p99"])
        b_p99.append(pair["nopoll"]["p99"])
        a_qps.append(pair["poll"]["qps"])
        b_qps.append(pair["nopoll"]["qps"])
        ratios.append(pair["nopoll"]["p50"] / max(pair["poll"]["p50"], 1e-9))
    if not ratios:
        raise RuntimeError("every poll/no-poll pair wedged")
    row = {
        "poll_p50_us": round(statistics.median(a_p50), 1),
        "nopoll_p50_us": round(statistics.median(b_p50), 1),
        "poll_p99_us": round(statistics.median(a_p99), 1),
        "nopoll_p99_us": round(statistics.median(b_p99), 1),
        "poll_qps": round(statistics.median(a_qps)),
        "nopoll_qps": round(statistics.median(b_qps)),
        "p50_speedup": round(statistics.median(ratios), 2),
        "speedup_samples": [round(r, 2) for r in ratios],
        "payload": 64, "concurrency": 1, "reps": len(ratios),
    }
    print(f"# rpc_poll_64B: no-poll p50 {row['nopoll_p50_us']}us -> "
          f"poll p50 {row['poll_p50_us']}us ({row['p50_speedup']}x, "
          f"samples {row['speedup_samples']})", file=sys.stderr)
    return row


def rpcz_overhead_point(reps=5, seconds=1, concurrency=16, sample_n=64,
                        wedge_log=None):
    """Always-on rpcz cost on the 64B hot path: span collection ON with
    1-in-`sample_n` root sampling vs rpcz OFF, interleaved pairs (the
    fleet-observability acceptance row — production keeps rpcz live only
    if this stays <= 5%). overhead_pct = (1 - sampled/off) * 100."""
    row = _ab_point(64,
                    a_flags=(("rpcz_enabled", "1"),
                             ("rpcz_sample_1_in_n", str(sample_n))),
                    b_flags=(("rpcz_enabled", "0"),),
                    a_key="sampled", b_key="off", reps=reps,
                    seconds=seconds, concurrency=concurrency,
                    wedge_log=wedge_log)
    row["sample_1_in_n"] = sample_n
    row["overhead_pct"] = round((1 - row["speedup"]) * 100, 1)
    print(f"# rpcz_overhead_64B: off {row['off_qps']} qps -> sampled 1/"
          f"{sample_n} {row['sampled_qps']} qps ({row['overhead_pct']}% "
          f"overhead, samples {row['speedup_samples']})", file=sys.stderr)
    return row


# The whole 10x-overload A/B runs in ONE watchdogged child: an echo server
# with a constant gate + injected (deterministic) service time, BULK
# callers offering >10x the gate's capacity, and a HIGH-lane prober whose
# time-to-success is the control-plane latency. Protection ON = priority
# lanes armed (bulk headroom reserved, callers stamp their lanes);
# protection OFF = rpc_bulk_headroom_pct=0 and every caller unmarked — the
# same drive, so the A/B isolates exactly the overload-protection plane.
_OVERLOAD_CHILD = r"""
import json, sys, threading, time
sys.path.insert(0, {root!r})
from brpc_tpu.runtime import native
try:
    from brpc_tpu.observability import health
    health.start_watchdog({dump_dir!r})
except Exception:
    pass

GATE = {gate}
SVC_MS = {svc_ms}
DRIVE_S = {drive_s}
BULK_THREADS = {bulk_threads}
BULK = b"x" * 8192  # non-batchable: every request gets its own fiber

srv = native.Server(); srv.add_echo_service()
srv.set_max_concurrency(GATE)
port = srv.start(); addr = "127.0.0.1:%d" % port
native.inject_latency("EchoService", SVC_MS)
capacity_rps = GATE * 1000.0 / SVC_MS

def high_probe(n, interval_s, priority):
    # Time-to-success per control-plane op: each op retries (1ms pause)
    # until admitted — with protection off, that retry spin against a
    # bulk-full gate IS the tail the A/B exposes.
    ch = native.Channel(addr, timeout_ms=8000, max_retry=0)
    lats = []
    for _ in range(n):
        t0 = time.monotonic()
        while True:
            try:
                with native.qos(priority, "ctl"):
                    ch.call("EchoService/Echo", b"hb")
                break
            except native.RpcError:
                time.sleep(0.001)
        lats.append((time.monotonic() - t0) * 1000.0)
        time.sleep(interval_s)
    ch.close()
    lats.sort()
    return lats

def drive(bulk_priority, high_priority, headroom_pct):
    assert native.lib().tbrpc_flag_set(
        b"rpc_bulk_headroom_pct", str(headroom_pct).encode()) == 0
    stop = threading.Event()
    mu = threading.Lock()
    stats = {{"ok": 0, "shed": 0, "attempts": 0}}
    def bulk_loop():
        ch = native.Channel(addr, timeout_ms=8000, max_retry=0)
        while not stop.is_set():
            with mu:
                stats["attempts"] += 1
            try:
                with native.qos(bulk_priority, "bulk"):
                    ch.call("EchoService/Echo", BULK)
                with mu:
                    stats["ok"] += 1
            except native.RpcError:
                with mu:
                    stats["shed"] += 1
                time.sleep(0.002)
        ch.close()
    threads = [threading.Thread(target=bulk_loop)
               for _ in range(BULK_THREADS)]
    for t in threads: t.start()
    time.sleep(0.3)  # let bulk saturate the gate first
    with mu:
        before = dict(stats)
    t0 = time.monotonic()
    n_high = max(8, int(DRIVE_S / 0.03))
    lats = high_probe(n_high, 0.03, high_priority)
    window = time.monotonic() - t0  # goodput over the PROBED window only
    with mu:
        after = dict(stats)
    stop.set()
    for t in threads: t.join()
    bulk_ok = after["ok"] - before["ok"]
    return {{
        "high_p99_ms": round(lats[max(0, int(len(lats) * 0.99) - 1)], 2),
        "high_p50_ms": round(lats[len(lats) // 2], 2),
        "goodput_rps": round((bulk_ok + n_high) / window, 1),
        "offered_x_capacity": round(
            (after["attempts"] - before["attempts"]) / window
            / capacity_rps, 1),
        "bulk_ok": after["ok"], "bulk_shed": after["shed"],
    }}

unloaded = high_probe(20, 0.01, native.PRIORITY_HIGH)
row = {{
    "gate": GATE, "svc_ms": SVC_MS, "bulk_threads": BULK_THREADS,
    "capacity_rps": capacity_rps,
    "high_p99_ms_unloaded": round(
        unloaded[max(0, int(len(unloaded) * 0.99) - 1)], 2),
    "protected": drive(native.PRIORITY_BULK, native.PRIORITY_HIGH, 10),
    "unprotected": drive(native.PRIORITY_NORMAL, native.PRIORITY_NORMAL, 0),
}}
native.inject_latency("", 0)
native.lib().tbrpc_flag_set(b"rpc_bulk_headroom_pct", b"10")
base = max(row["high_p99_ms_unloaded"], 1e-9)
row["high_p99_x_protected"] = round(row["protected"]["high_p99_ms"] / base, 2)
row["high_p99_x_unprotected"] = round(
    row["unprotected"]["high_p99_ms"] / base, 2)
row["goodput_frac_protected"] = round(
    row["protected"]["goodput_rps"] / capacity_rps, 2)
srv.close()
print(json.dumps(row))
"""


def overload_point(gate=10, svc_ms=40, drive_s=2.0, bulk_threads=16,
                   wedge_log=None):
    """The 10x-overload A/B (ISSUE 9 acceptance row): goodput + HIGH-lane
    p99 while BULK drives the gate at >10x its capacity, protection on vs
    off in the SAME child. Acceptance: protected HIGH p99 <= 2x its
    unloaded value and goodput >= 0.9x capacity; unprotected shows the
    control-plane tail blowing up."""
    root = os.path.dirname(os.path.abspath(__file__))
    code = _OVERLOAD_CHILD.format(root=root, dump_dir=_dump_dir(),
                                  gate=gate, svc_ms=svc_ms,
                                  drive_s=drive_s,
                                  bulk_threads=bulk_threads)
    timeout = 60 + drive_s * 10
    seen = set(_new_dump_files(set()))
    try:
        proc = _run_child(  # tpulint: allow(py-blocking)
            [sys.executable, "-c", code], capture_output=True,
            timeout=timeout, text=True)
    except subprocess.TimeoutExpired:
        row = {"wedged": True, "dump_files": _new_dump_files(seen)}
        if wedge_log is not None:
            wedge_log.append({"point": "overload_10x",
                              "dump_files": row["dump_files"]})
        return row
    out = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not out:
        raise RuntimeError(
            f"overload child rc={proc.returncode}: "
            f"{proc.stderr.strip()[-800:]}")
    row = json.loads(out[-1])
    print(f"# overload_10x: unloaded HIGH p99 {row['high_p99_ms_unloaded']}"
          f"ms -> protected {row['protected']['high_p99_ms']}ms "
          f"({row['high_p99_x_protected']}x) vs unprotected "
          f"{row['unprotected']['high_p99_ms']}ms "
          f"({row['high_p99_x_unprotected']}x); goodput "
          f"{row['goodput_frac_protected']}x capacity at "
          f"{row['protected']['offered_x_capacity']}x offered",
          file=sys.stderr)
    return row


_SERVING_CHILD = """
import json, sys, threading, time
sys.path.insert(0, {root!r})
import jax
from brpc_tpu.runtime import native
try:
    from brpc_tpu.observability import health
    health.start_watchdog({dump_dir!r})
except Exception:
    pass
from brpc_tpu.models.decoder import init_decoder
from brpc_tpu.serving import ServingServer, ServingClient

PARAMS = init_decoder(jax.random.PRNGKey(0))
N_TOK = {n_tok}
DRIVE_S = {drive_s}
FLOOD_THREADS = {flood_threads}
MAX_BATCH = {max_batch}

def pctl(xs, q):
    xs = sorted(xs)
    return xs[max(0, int(len(xs) * q) - 1)] if xs else 0.0

def drive(protected):
    # Protection = per-tenant SESSION quota (the serving twin of the PR 9
    # RPC quota): on, the flood tenant holds at most MAX_BATCH sessions
    # and its overflow sheds at open with a retry hint; off, every flood
    # session is admitted and queues ahead of the probing user.
    srv = ServingServer(PARAMS, max_batch=MAX_BATCH,
                        tenant_max_sessions=(MAX_BATCH if protected else 0))
    port = srv.start()
    addr = "127.0.0.1:%d" % port
    w = ServingClient(addr)
    w.generate([1], 2)  # absorb the jit compile outside every timing
    # Unloaded TTFT reference (one session, empty batch).
    unloaded = []
    for _ in range(5):
        ts = w.open([5, 2], 8)
        list(ts)
        unloaded.append(ts.ttft_s * 1000.0)
    w.close()
    stop = threading.Event()
    mu = threading.Lock()
    stats = {{"flood_tokens": 0, "flood_shed": 0, "user_tokens": 0}}
    def flood_loop():
        c = ServingClient(addr, tenant="flood")
        while not stop.is_set():
            try:
                toks = c.generate([3, 7], N_TOK)
                with mu:
                    stats["flood_tokens"] += len(toks)
            except native.RpcError as e:
                with mu:
                    stats["flood_shed"] += 1
                time.sleep((getattr(e, "retry_after_ms", None) or 20)
                           / 1000.0)
        c.close()
    threads = [threading.Thread(target=flood_loop)
               for _ in range(FLOOD_THREADS)]
    for t in threads:
        t.start()
    time.sleep(0.5)  # let the flood fill the batch (and any queue)
    uc = ServingClient(addr, tenant="user")
    ttfts = []
    with mu:
        before = dict(stats)
    t0 = time.monotonic()
    while time.monotonic() - t0 < DRIVE_S:
        ts = uc.open([5, 2], N_TOK)
        toks = list(ts)
        ttfts.append(ts.ttft_s * 1000.0)
        with mu:
            stats["user_tokens"] += len(toks)
    window = time.monotonic() - t0
    with mu:
        after = dict(stats)
    stop.set()
    for t in threads:
        t.join()
    uc.close()
    tokens = (after["flood_tokens"] - before["flood_tokens"]
              + after["user_tokens"])
    row = {{
        "stream_ttft_p50_ms": round(pctl(ttfts, 0.50), 2),
        "stream_ttft_p99_ms": round(pctl(ttfts, 0.99), 2),
        "unloaded_ttft_p50_ms": round(pctl(unloaded, 0.50), 2),
        "serving_tokens_s": round(tokens / window, 1),
        "user_sessions": len(ttfts),
        "flood_shed": after["flood_shed"],
    }}
    srv.stop()
    return row

row = {{
    "n_tok": N_TOK, "max_batch": MAX_BATCH,
    "flood_sessions_offered": FLOOD_THREADS,
    "protected": drive(True),
    "unprotected": drive(False),
}}
base = max(row["protected"]["unloaded_ttft_p50_ms"], 1e-9)
row["ttft_p99_x_protected"] = round(
    row["protected"]["stream_ttft_p99_ms"] / base, 2)
row["ttft_p99_x_unprotected"] = round(
    row["unprotected"]["stream_ttft_p99_ms"] / base, 2)
# The protection story is clearest at the MEDIAN: protected, a probe
# usually finds a free lane (the flood's overflow shed at open);
# unprotected, it queues behind the whole flood backlog.
row["ttft_p50_x_protected"] = round(
    row["protected"]["stream_ttft_p50_ms"] / base, 2)
row["ttft_p50_x_unprotected"] = round(
    row["unprotected"]["stream_ttft_p50_ms"] / base, 2)
print(json.dumps(row))
"""


def serving_point(n_tok=40, drive_s=2.0, flood_threads=8, max_batch=4,
                  wedge_log=None):
    """Streaming-inference rows (ISSUE 10): TTFT p50/p99 and aggregate
    tokens/s for a probing tenant while a flood tenant offers 2x the
    batch capacity in concurrent sessions — per-tenant session quota
    (protection) on vs off in the same child. Protection keeps the
    probe's TTFT near its unloaded value (the flood's overflow sheds at
    open with a retry hint instead of queueing ahead of everyone)."""
    root = os.path.dirname(os.path.abspath(__file__))
    code = _SERVING_CHILD.format(root=root, dump_dir=_dump_dir(),
                                 n_tok=n_tok, drive_s=drive_s,
                                 flood_threads=flood_threads,
                                 max_batch=max_batch)
    timeout = 120 + drive_s * 20
    seen = set(_new_dump_files(set()))
    try:
        proc = _run_child(  # tpulint: allow(py-blocking)
            [sys.executable, "-c", code], capture_output=True,
            timeout=timeout, text=True)
    except subprocess.TimeoutExpired:
        row = {"wedged": True, "dump_files": _new_dump_files(seen)}
        if wedge_log is not None:
            wedge_log.append({"point": "serving_stream",
                              "dump_files": row["dump_files"]})
        return row
    out = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not out:
        raise RuntimeError(
            f"serving child rc={proc.returncode}: "
            f"{proc.stderr.strip()[-800:]}")
    row = json.loads(out[-1])
    print(f"# serving_stream: ttft p50/p99 protected "
          f"{row['protected']['stream_ttft_p50_ms']}/"
          f"{row['protected']['stream_ttft_p99_ms']}ms vs unprotected "
          f"{row['unprotected']['stream_ttft_p50_ms']}/"
          f"{row['unprotected']['stream_ttft_p99_ms']}ms "
          f"(unloaded p50 {row['protected']['unloaded_ttft_p50_ms']}ms); "
          f"tokens/s {row['protected']['serving_tokens_s']} protected / "
          f"{row['unprotected']['serving_tokens_s']} unprotected",
          file=sys.stderr)
    return row


_FLEET_MEMBER = r'''
import sys
sys.path.insert(0, sys.argv[1])
import jax
from brpc_tpu.models.decoder import init_decoder
from brpc_tpu.serving import FleetServingServer
spec_k = int(sys.argv[6]) if len(sys.argv) > 6 else 0
srv = FleetServingServer(sys.argv[2], init_decoder(jax.random.PRNGKey(0)),
                         tag=sys.argv[3], role=sys.argv[4],
                         max_batch=int(sys.argv[5]), reg_ttl_s=3,
                         spec_k=spec_k)
srv.start()
print("READY", srv.addr, flush=True)
sys.stdin.readline()  # parent closes stdin to stop
srv.stop()
'''


_SERVING_FLEET_CHILD = """
import json, subprocess, sys, threading, time
sys.path.insert(0, {root!r})
from brpc_tpu.runtime import native
try:
    from brpc_tpu.observability import health
    health.start_watchdog({dump_dir!r})
except Exception:
    pass
from brpc_tpu.fleet import RegistryHub, clear_registry
from brpc_tpu.serving import ServingFleetClient

MEMBER = {member!r}
ROOT = {root!r}
N_TOK = {n_tok}
DRIVE_S = {drive_s}
WORKERS = {workers}

def pctl(xs, q):
    xs = sorted(xs)
    return xs[max(0, int(len(xs) * q) - 1)] if xs else 0.0

def spawn(hub, tag, role):
    p = subprocess.Popen([sys.executable, "-c", MEMBER, ROOT, hub, tag,
                          role, "4"], stdin=subprocess.PIPE,
                         stdout=subprocess.PIPE, text=True)
    line = p.stdout.readline().strip()
    assert line.startswith("READY"), line
    return p, line.split()[1]

def stop(procs):
    for p, _addr in procs:
        try:
            p.stdin.close()
            p.wait(timeout=15)
        except Exception:
            p.kill()

def drive(tag, roles):
    # One serving-member PROCESS per role (in-process members contend in
    # jax — the PR 6 finding); aggregate tokens/s + TTFT over WORKERS
    # concurrent session loops against the whole fleet.
    hub = RegistryHub()
    hub.start()
    procs = [spawn(hub.hostport, tag, r) for r in roles]
    try:
        c = ServingFleetClient(hub.hostport, tag=tag)
        for i in range(2 * len(roles)):  # absorb every member's jit
            c.generate([1], 2, session_key="warm-%d" % i)
        stop_ev = threading.Event()
        mu = threading.Lock()
        stats = {{"tokens": 0, "ttfts": []}}
        def worker(w):
            cl = ServingFleetClient(hub.hostport, tag=tag)
            i = 0
            while not stop_ev.is_set():
                ts = cl.open([3, 7, (i % 40) + 1], N_TOK,
                             session_key="d%d-%d" % (w, i))
                toks = list(ts)
                ts.close()
                with mu:
                    stats["tokens"] += len(toks)
                    if ts.ttft_s is not None:
                        stats["ttfts"].append(ts.ttft_s * 1000.0)
                i += 1
            cl.close()
        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(WORKERS)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        time.sleep(DRIVE_S)
        stop_ev.set()
        for t in threads:
            t.join()
        window = time.monotonic() - t0
        c.close()
        return {{
            "members": len(roles), "roles": list(roles),
            "tokens_s": round(stats["tokens"] / window, 1),
            "ttft_p50_ms": round(pctl(stats["ttfts"], 0.50), 2),
            "ttft_p99_ms": round(pctl(stats["ttfts"], 0.99), 2),
            "sessions": len(stats["ttfts"]),
        }}
    finally:
        stop(procs)
        clear_registry()
        hub.stop()

row = {{
    "fleet_1": drive("sf1", ["both"]),
    "fleet_2": drive("sf2", ["both", "both"]),
    "split_prefill_decode": drive("sfp", ["prefill", "decode"]),
}}
base = max(row["fleet_1"]["tokens_s"], 1e-9)
row["tokens_s_x_2v1"] = round(row["fleet_2"]["tokens_s"] / base, 2)
row["split_vs_colocated_tokens_s"] = round(
    row["split_prefill_decode"]["tokens_s"]
    / max(row["fleet_2"]["tokens_s"], 1e-9), 2)
print(json.dumps(row))
"""


_SERVING_DRAIN_CHILD = """
import json, subprocess, sys, threading, time
sys.path.insert(0, {root!r})
import jax
from brpc_tpu.runtime import native
try:
    from brpc_tpu.observability import health
    health.start_watchdog({dump_dir!r})
except Exception:
    pass
from brpc_tpu.fleet import RegistryHub, clear_registry
from brpc_tpu.models.decoder import decode_serial, init_decoder
from brpc_tpu.serving import ServingFleetClient

MEMBER = {member!r}
ROOT = {root!r}
N_TOK = {n_tok}
STREAMS = {streams}
PARAMS = init_decoder(jax.random.PRNGKey(0))

def pctl(xs, q):
    xs = sorted(xs)
    return xs[max(0, int(len(xs) * q) - 1)] if xs else 0.0

def spawn(hub, tag):
    p = subprocess.Popen([sys.executable, "-c", MEMBER, ROOT, hub, tag,
                          "both", "4"], stdin=subprocess.PIPE,
                         stdout=subprocess.PIPE, text=True)
    line = p.stdout.readline().strip()
    assert line.startswith("READY"), line
    return p, line.split()[1]

hub = RegistryHub()
hub.start()
pa, addr_a = spawn(hub.hostport, "sdr")
pb, addr_b = spawn(hub.hostport, "sdr")
try:
    c = ServingFleetClient(hub.hostport, tag="sdr")
    c.router.refresh()
    # Warm BOTH members' jit with sticky keys before timing anything.
    for addr in (addr_a, addr_b):
        i = 0
        while c.router.route("w-%s-%d" % (addr, i)) != addr:
            i += 1
        c.generate([1], 2, session_key="w-%s-%d" % (addr, i))
    keys, i = [], 0
    while len(keys) < STREAMS:
        k = "dr-%d" % i
        if c.router.route(k) == addr_a:
            keys.append(k)
        i += 1
    prompts = {{k: [3, 7, (j % 40) + 1] for j, k in enumerate(keys)}}
    refs = {{k: decode_serial(PARAMS, p, N_TOK, 64)
            for k, p in prompts.items()}}
    streams = {{k: c.open(p, N_TOK, session_key=k)
               for k, p in prompts.items()}}
    for ts in streams.values():
        while len(ts.tokens) < 4:
            ts.read_token(timeout_ms=10000)
    def reader(ts):
        list(ts)
    threads = [threading.Thread(target=reader, args=(ts,))
               for ts in streams.values()]
    for t in threads:
        t.start()
    t0 = time.monotonic()
    ch = native.Channel(addr_a, timeout_ms=5000, max_retry=0)
    ch.call("Gen/Drain", b"")  # async trigger; the streams show the rest
    for t in threads:
        t.join()
    drain_wall_s = time.monotonic() - t0
    ch.close()
    gaps = [ts.last_gap_s * 1000.0 for ts in streams.values()
            if ts.last_gap_s is not None]
    row = {{
        "streams": len(streams),
        "migrated": sum(1 for ts in streams.values() if ts.resumes),
        "token_parity": all(ts.tokens == refs[k]
                            for k, ts in streams.items()),
        "stream_gap_ms_p50": round(pctl(gaps, 0.50), 1),
        "stream_gap_ms_max": round(max(gaps), 1) if gaps else 0.0,
        "drain_wall_s": round(drain_wall_s, 2),
    }}
    for ts in streams.values():
        ts.close()
    c.close()
finally:
    for p in (pa, pb):
        try:
            p.stdin.close()
            p.wait(timeout=15)
        except Exception:
            p.kill()
    clear_registry()
    hub.stop()
print(json.dumps(row))
"""


_SERVING_SPEC_CHILD = """
import json, subprocess, sys, threading, time
sys.path.insert(0, {root!r})
import jax
from brpc_tpu.runtime import native
try:
    from brpc_tpu.observability import health
    health.start_watchdog({dump_dir!r})
except Exception:
    pass
from brpc_tpu.models.decoder import init_decoder
from brpc_tpu.serving import ServingClient, ServingServer

PARAMS = init_decoder(jax.random.PRNGKey(0))
SPEC_K = {spec_k}
REPS = {reps}
DRIVE_S = {drive_s}
MEMBER = {member!r}
ROOT = {root!r}
FLEET = {fleet}

# Acceptance-friendly = long prompt (the window ingests known rows k+1
# per dispatch) + whatever the n-gram draft catches in generation;
# adversarial = short prompt, generation-dominated, low lookup hit rate
# — the k-adaptation clamp's regime.
FRIENDLY = (list(range(1, 41)), 16)
ADVERSARIAL = ([3, 7, 5], 24)

def pctl(xs, q):
    xs = sorted(xs)
    return xs[max(0, int(len(xs) * q) - 1)] if xs else 0.0

def drive(client, srv, spec_k, prompt, n_tok, secs):
    # In-process toggle for the single server; Gen/Spec for fleet
    # members (the same engine attribute, over the wire).
    set_spec(client, srv, spec_k)
    t0 = time.monotonic()
    tokens = 0
    gaps = []
    i = 0
    while time.monotonic() - t0 < secs:
        if srv is not None:
            ts = client.open(prompt, n_tok)
        else:
            ts = client.open(prompt, n_tok,
                             session_key="sp%d-%d" % (spec_k, i))
        last = None
        for _tok in ts:
            now = time.monotonic()
            if last is not None:
                gaps.append((now - last) * 1e3)
            last = now
        tokens += len(ts.tokens)
        ts.close()
        i += 1
    window = time.monotonic() - t0
    return tokens / window, pctl(gaps, 0.50)

def set_spec(client, srv, spec_k):
    if srv is not None:
        srv.engine.spec_k = spec_k
    else:
        for addr in client._spec_addrs:
            ch = native.Channel(addr, timeout_ms=5000, max_retry=0)
            ch.call("Gen/Spec", json.dumps({{"spec_k": spec_k}}).encode())
            ch.close()

def warm(client, srv, tag):
    # Absorb EVERY jit compile outside the timings: both modes, both
    # workloads, full budgets (the adapted k sweeps the whole window-
    # width program set) — in EVERY engine process: fleet warm keys are
    # picked per member via the router so neither engine compiles inside
    # a timed drive.
    keys = [None]
    if srv is None:
        client.router.refresh()
        keys = []
        for addr in client._spec_addrs:
            i = 0
            while client.router.route("w%s-%d" % (tag, i)) != addr:
                i += 1
            keys.append("w%s-%d" % (tag, i))
    for k in (SPEC_K, 0):
        set_spec(client, srv, k)
        for prompt, n_tok in (FRIENDLY, ADVERSARIAL):
            for key in keys:
                if key is None:
                    client.generate(prompt, n_tok)
                else:
                    # Terminal sessions may reuse their id: the same
                    # member-targeted key warms every mode/workload.
                    client.generate(prompt, n_tok, session_key=key)

def ab_rows(client, srv):
    out = {{}}
    for name, (prompt, n_tok) in (("friendly", FRIENDLY),
                                  ("adversarial", ADVERSARIAL)):
        ratios, on_tps, off_tps, on_p50, off_p50 = [], [], [], [], []
        for _rep in range(REPS):
            off, offp = drive(client, srv, 0, prompt, n_tok, DRIVE_S)
            on, onp = drive(client, srv, SPEC_K, prompt, n_tok, DRIVE_S)
            ratios.append(on / max(off, 1e-9))
            on_tps.append(on); off_tps.append(off)
            on_p50.append(onp); off_p50.append(offp)
        ratios.sort()
        out[name] = {{
            "tokens_s_on": round(pctl(on_tps, 0.5), 1),
            "tokens_s_off": round(pctl(off_tps, 0.5), 1),
            "tokens_s_x": round(ratios[len(ratios) // 2], 2),
            "tokens_s_x_samples": [round(r, 2) for r in ratios],
            "token_p50_ms_on": round(pctl(on_p50, 0.5), 2),
            "token_p50_ms_off": round(pctl(off_p50, 0.5), 2),
        }}
    return out

# Single-server A/B (interleaved off/on pairs, median-of-ratios).
srv = ServingServer(PARAMS, max_batch=4, spec_k=SPEC_K, draft="ngram")
port = srv.start()
c = ServingClient("127.0.0.1:%d" % port)
warm(c, srv, "s")
row = {{"spec_k": SPEC_K, "reps": REPS, "single": ab_rows(c, srv)}}
accept = srv.manager.sessionz_doc()
row["single"]["accept_pct"] = accept["spec_accept_pct"]
c.close()
srv.stop()

if FLEET:
    # Fleet-size-2 drive: one member PROCESS each (the PR 6 in-process
    # contention finding), spec toggled per rep via Gen/Spec.
    from brpc_tpu.fleet import RegistryHub, clear_registry
    from brpc_tpu.serving import ServingFleetClient
    hub = RegistryHub()
    hub.start()
    procs = []
    for _ in range(2):
        p = subprocess.Popen([sys.executable, "-c", MEMBER, ROOT,
                              hub.hostport, "spec2", "both", "4",
                              str(SPEC_K)], stdin=subprocess.PIPE,
                             stdout=subprocess.PIPE, text=True)
        line = p.stdout.readline().strip()
        assert line.startswith("READY"), line
        procs.append((p, line.split()[1]))
    try:
        fc = ServingFleetClient(hub.hostport, tag="spec2")
        fc._spec_addrs = [addr for _p, addr in procs]
        warm(fc, None, "f")
        row["fleet_2"] = ab_rows(fc, None)
        fc.close()
    finally:
        for p, _addr in procs:
            try:
                p.stdin.close()
                p.wait(timeout=15)
            except Exception:
                p.kill()
        clear_registry()
        hub.stop()
print(json.dumps(row))
"""


def serving_spec_point(spec_k=4, reps=5, drive_s=1.0, fleet=True,
                       wedge_log=None):
    """Speculative decoding A/B (ISSUE 15 acceptance row): interleaved
    spec-on/off tokens/s + per-token p50 on the acceptance-friendly
    (long-prompt) and adversarial (short-prompt, low-acceptance)
    workloads, single server + a fleet-size-2 drive — median-of-ratios
    over the pairs, one wedge-guarded child."""
    root = os.path.dirname(os.path.abspath(__file__))
    code = _SERVING_SPEC_CHILD.format(root=root, dump_dir=_dump_dir(),
                                      member=_FLEET_MEMBER, spec_k=spec_k,
                                      reps=reps, drive_s=drive_s,
                                      fleet="True" if fleet else "False")
    timeout = 240 + reps * drive_s * (16 if fleet else 8)
    row = _run_guarded_child("serving_spec", code, timeout, wedge_log)
    if not row.get("wedged"):
        s = row["single"]
        msg = (f"# serving_spec: friendly "
               f"{s['friendly']['tokens_s_off']} -> "
               f"{s['friendly']['tokens_s_on']} tok/s "
               f"({s['friendly']['tokens_s_x']}x), adversarial "
               f"{s['adversarial']['tokens_s_off']} -> "
               f"{s['adversarial']['tokens_s_on']} tok/s "
               f"({s['adversarial']['tokens_s_x']}x), "
               f"accept {s['accept_pct']}%")
        if "fleet_2" in row:
            msg += (f"; fleet-2 friendly "
                    f"{row['fleet_2']['friendly']['tokens_s_x']}x / "
                    f"adversarial "
                    f"{row['fleet_2']['adversarial']['tokens_s_x']}x")
        print(msg, file=sys.stderr)
    return row


_SERVING_PAGED_CHILD = """
import json, sys, time
sys.path.insert(0, {root!r})
import jax
from brpc_tpu.runtime import native
try:
    from brpc_tpu.observability import health
    health.start_watchdog({dump_dir!r})
except Exception:
    pass
from brpc_tpu.models.decoder import init_decoder
from brpc_tpu.serving import (CallableSink, DecodeEngine, ServingClient,
                              ServingServer, SessionManager,
                              serving_metrics)

PARAMS = init_decoder(jax.random.PRNGKey(0))
REPS = {reps}
DRIVE_S = {drive_s}
N_TOK = {n_tok}
STREAMS = {streams}
MAX_LEN = 128
R = 8
ARENA = 1 << 20  # small on purpose: density = opens until first spill

# 47 tokens = 5 full R=8 blocks (prefix-cacheable) + a 7-row tail that
# shares its block with the first generated token (the CoW seam).
SHARED = list(range(1, 48))

def distinct(i):
    # Unique-per-session FIRST block (two base-63 digit tokens encode i)
    # so no two "distinct" prompts ever share a prefix block.
    p = [i % 63 + 1, i // 63 % 63 + 1]
    return p + [(i * 7 + j) % 63 + 1 for j in range(45)]

def pctl(xs, q):
    xs = sorted(xs)
    return xs[max(0, int(len(xs) * q) - 1)] if xs else 0.0

def density(paged, pick, seed_cache):
    # Admissions until the arena's first spill/shed: every admitted
    # session holds live KV residency (mono: the full (2, max_len, dim)
    # plane; paged: its block table). seed_cache runs ONE session
    # through the engine first so the shared prompt's full blocks are
    # committed into the prefix cache — later opens hit it at open().
    mgr = SessionManager(max_len=MAX_LEN, kv_arena_bytes=ARENA,
                         paged=paged, block_rows=R)
    if seed_cache:
        eng = DecodeEngine(mgr, PARAMS, max_batch=1)
        got = []
        mgr.open(pick(0), 2, CallableSink(got.append), sid="seed")
        for _ in range(MAX_LEN):
            if not eng.step():
                break
    n = 0
    spill0 = serving_metrics()["spill_out"].value()
    try:
        while n < 4096:
            mgr.open(pick(n), 4, CallableSink(lambda _b: None),
                     sid="d%d" % n)
            # Admission under pressure pages a COLD session out rather
            # than shedding: the first page-out (spill_out is a process-
            # cumulative counter, hence the delta) marks the arena's
            # resident capacity in both modes.
            if serving_metrics()["spill_out"].value() > spill0:
                break
            n += 1
    except native.RpcError as e:
        assert e.code == native.TRPC_ELIMIT, e
    doc = mgr.sessionz_doc()
    row = {{"live_sessions": n,
            "sessions_per_gb": round(n * (1 << 30) / ARENA),
            "kv_bytes": doc["kv_bytes"]}}
    if paged:
        row["blocks_shared"] = doc.get("kv_blocks_shared", 0)
        row["prefix_hit_pct"] = doc.get("prefix_hit_pct", 0.0)
    mgr.shutdown()
    return row

def drive(client, pick, secs):
    t0 = time.monotonic()
    tokens = 0
    i = 0
    while time.monotonic() - t0 < secs:
        streams = [client.open(pick(i + k), N_TOK)
                   for k in range(STREAMS)]
        i += STREAMS
        for ts in streams:
            for _tok in ts:
                pass
            tokens += len(ts.tokens)
            ts.close()
    return tokens / (time.monotonic() - t0)

row = {{"reps": REPS, "block_rows": R, "density": {{}}}}
for name, pick, seed in (("shared", lambda i: SHARED, True),
                         ("distinct", distinct, False)):
    per = {{}}
    for mode in ("paged", "mono"):
        per[mode] = density(mode == "paged", pick, seed)
    per["density_x"] = round(
        per["paged"]["live_sessions"]
        / max(per["mono"]["live_sessions"], 1), 2)
    row["density"][name] = per

# Throughput A/B: matched concurrency on two live servers (default-size
# arenas — no paging pressure; this half isolates the gather/CoW cost),
# interleaved mono/paged drives, median-of-ratios.
srv_m = ServingServer(PARAMS, max_batch=STREAMS, max_len=MAX_LEN)
srv_p = ServingServer(PARAMS, max_batch=STREAMS, max_len=MAX_LEN,
                      paged=True, block_rows=R)
cm = ServingClient("127.0.0.1:%d" % srv_m.start())
cp = ServingClient("127.0.0.1:%d" % srv_p.start())
# Paged is a drop-in: same tokens for the same prompt, pinned in-child.
assert cm.generate(SHARED, 12) == cp.generate(SHARED, 12)
for c in (cm, cp):
    # Absorb the jit compiles (every batch width up to STREAMS) and, on
    # the paged server, populate the prefix cache outside the timings.
    for pick in (lambda i: SHARED, distinct):
        drive(c, pick, 0.4)
row["throughput"] = {{}}
for name, pick in (("shared", lambda i: SHARED), ("distinct", distinct)):
    ratios, mono_tps, paged_tps = [], [], []
    for _rep in range(REPS):
        m = drive(cm, pick, DRIVE_S)
        p = drive(cp, pick, DRIVE_S)
        ratios.append(p / max(m, 1e-9))
        mono_tps.append(m)
        paged_tps.append(p)
    ratios.sort()
    row["throughput"][name] = {{
        "tokens_s_mono": round(pctl(mono_tps, 0.5), 1),
        "tokens_s_paged": round(pctl(paged_tps, 0.5), 1),
        "tokens_s_x": round(ratios[len(ratios) // 2], 2),
        "tokens_s_x_samples": [round(r, 2) for r in ratios],
    }}
doc = srv_p.manager.sessionz_doc()
row["throughput"]["prefix_hit_pct"] = doc.get("prefix_hit_pct", 0.0)
cm.close()
cp.close()
srv_m.stop()
srv_p.stop()
print(json.dumps(row))
"""


def serving_paged_point(reps=5, drive_s=1.0, n_tok=16, streams=4,
                        wedge_log=None):
    """Paged-KV A/B (ISSUE 18 acceptance row): live-sessions-per-GB at
    a fixed 1 MiB arena (opens until first spill) and matched-
    concurrency tokens/s, paged vs monolithic on shared-prompt and
    distinct-prompt workloads — median-of-ratios over interleaved
    pairs, one wedge-guarded child."""
    root = os.path.dirname(os.path.abspath(__file__))
    code = _SERVING_PAGED_CHILD.format(root=root, dump_dir=_dump_dir(),
                                       reps=reps, drive_s=drive_s,
                                       n_tok=n_tok, streams=streams)
    timeout = 240 + reps * drive_s * 8
    row = _run_guarded_child("serving_paged", code, timeout, wedge_log)
    if not row.get("wedged"):
        d, t = row["density"], row["throughput"]
        print(f"# serving_paged: density shared "
              f"{d['shared']['mono']['live_sessions']} -> "
              f"{d['shared']['paged']['live_sessions']} live/MiB "
              f"({d['shared']['density_x']}x), distinct "
              f"{d['distinct']['density_x']}x; tokens/s shared "
              f"{t['shared']['tokens_s_x']}x / distinct "
              f"{t['distinct']['tokens_s_x']}x "
              f"(prefix hit {t['prefix_hit_pct']}%)", file=sys.stderr)
    return row


def _run_guarded_child(name, code, timeout, wedge_log=None):
    """The serving/overload child-runner shape: one subprocess under a
    hard timeout; a wedge records dump files instead of hanging the
    terminal."""
    seen = set(_new_dump_files(set()))
    try:
        proc = _run_child(  # tpulint: allow(py-blocking)
            [sys.executable, "-c", code], capture_output=True,
            timeout=timeout, text=True)
    except subprocess.TimeoutExpired:
        row = {"wedged": True, "dump_files": _new_dump_files(seen)}
        if wedge_log is not None:
            wedge_log.append({"point": name,
                              "dump_files": row["dump_files"]})
        return row
    out = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not out:
        raise RuntimeError(f"{name} child rc={proc.returncode}: "
                           f"{proc.stderr.strip()[-800:]}")
    return json.loads(out[-1])


def serving_fleet_point(n_tok=24, drive_s=2.0, workers=4, wedge_log=None):
    """Serving-fleet rows (ISSUE 14): aggregate tokens/s + TTFT p50/p99
    at fleet size 1 vs 2 (one member process each), and the
    prefill/decode split vs the colocated 2-member fleet — the
    disaggregation cost/benefit on this box."""
    root = os.path.dirname(os.path.abspath(__file__))
    code = _SERVING_FLEET_CHILD.format(root=root, dump_dir=_dump_dir(),
                                       member=_FLEET_MEMBER, n_tok=n_tok,
                                       drive_s=drive_s, workers=workers)
    row = _run_guarded_child("serving_fleet", code,
                             240 + drive_s * 30, wedge_log)
    if not row.get("wedged"):
        print(f"# serving_fleet: tokens/s 1-member "
              f"{row['fleet_1']['tokens_s']} -> 2-member "
              f"{row['fleet_2']['tokens_s']} ({row['tokens_s_x_2v1']}x); "
              f"split {row['split_prefill_decode']['tokens_s']} "
              f"({row['split_vs_colocated_tokens_s']}x of colocated); "
              f"ttft p99 {row['fleet_2']['ttft_p99_ms']}ms fleet-2 / "
              f"{row['split_prefill_decode']['ttft_p99_ms']}ms split",
              file=sys.stderr)
    return row


def serving_drain_point(n_tok=40, streams=3, wedge_log=None):
    """The live-migration drive (ISSUE 14 acceptance row): STREAMS
    mid-stream sessions on member A, Gen/Drain A, every stream resumes
    on B — token parity asserted in-child, per-stream resume gap
    reported in ms."""
    root = os.path.dirname(os.path.abspath(__file__))
    code = _SERVING_DRAIN_CHILD.format(root=root, dump_dir=_dump_dir(),
                                       member=_FLEET_MEMBER, n_tok=n_tok,
                                       streams=streams)
    row = _run_guarded_child("serving_fleet_drain", code, 240, wedge_log)
    if not row.get("wedged"):
        print(f"# serving_fleet_drain: {row['migrated']}/{row['streams']} "
              f"streams migrated, parity={row['token_parity']}, gap p50 "
              f"{row['stream_gap_ms_p50']}ms max "
              f"{row['stream_gap_ms_max']}ms "
              f"(drain wall {row['drain_wall_s']}s)", file=sys.stderr)
    return row


def best_point(payload, transport, seconds=2, wedge_log=None):
    """Best (GB/s, qps, p99_us, concurrency) across the concurrency set.

    Individual wedged samples are skipped (and logged); if EVERY
    concurrency level wedges the point raises so the run records a
    failure rather than a ~0 GB/s result.
    """
    best = (-1.0, 0.0, 0.0, 0)
    for conc in CONCURRENCY:
        r = bench_echo_ex_guarded(
            payload, seconds, conc, transport,
            "pooled" if transport == "tcp" else "single",
            wedge_log=wedge_log)
        if r.get("wedged"):
            continue
        bps = r["bps"]
        if bps < 0:
            # Bench env failed (server/channel init) — a broken transport
            # must fail the run, not read as a ~0 GB/s result.
            raise RuntimeError(
                f"bench point failed: payload={payload} transport={transport}"
                f" concurrency={conc}")
        if bps > best[0]:
            best = (bps, r["qps"], r["p99"], conc)
    if best[0] < 0:
        raise RuntimeError(
            f"every concurrency level wedged: payload={payload} "
            f"transport={transport}")
    return best


def fmt_point(bps, qps, p99, conc):
    return {
        "gbps": round(bps / 1e9, 3),
        "qps": round(qps),
        "p99_us": round(p99),
        "concurrency": conc,
    }


def main() -> None:
    wedges = []
    # Warmup (first connect + fiber pool spin-up) — in its own child like
    # every sample, so a warmup wedge can't hang the run.
    bench_echo_ex_guarded(1 << 20, 1, 2, "tpu", "single", retries=0,
                          wedge_log=wedges)

    sweep = {}
    # Headline first: the 1MB point runs in the cleanest process state
    # (later points inherit page-cache/allocator churn from earlier ones).
    ordered = sorted(PAYLOADS, key=lambda p: p != (1 << 20))
    for payload in ordered:
        seconds = 2 if payload >= (1 << 20) else 1
        bps, qps, p99, conc = best_point(payload, "tpu", seconds=seconds,
                                         wedge_log=wedges)
        sweep[f"tpu_{payload}B"] = fmt_point(bps, qps, p99, conc)
        print(f"# tpu {payload}B: {bps / 1e9:.3f} GB/s, {qps:.0f} qps, "
              f"p99 {p99:.0f}us (conc={conc})", file=sys.stderr)
    # TCP comparison at the headline point.
    bps, qps, p99, conc = best_point(1 << 20, "tcp", wedge_log=wedges)
    sweep["tcp_1048576B"] = fmt_point(bps, qps, p99, conc)
    print(f"# tcp 1MB: {bps / 1e9:.3f} GB/s (conc={conc})", file=sys.stderr)

    # Latency mode (conc=1): the un-queued floor — regressions here are
    # invisible in the throughput-optimal rows above (VERDICT r3 weak #3).
    for payload, key in ((64, "lat_tpu_64B"), (1 << 20, "lat_tpu_1MB")):
        r = bench_echo_ex_guarded(payload, 2, 1, "tpu", "single",
                                  wedge_log=wedges)
        if r.get("wedged"):
            sweep[key] = {"wedged": True}
            continue
        sweep[key] = {"qps": round(r["qps"]), "p50_us": round(r["p50"]),
                      "p99_us": round(r["p99"]), "concurrency": 1}
        print(f"# latency {key}: p50 {r['p50']:.0f}us p99 {r['p99']:.0f}us "
              f"({r['qps']:.0f} qps)", file=sys.stderr)

    # Small-RPC fast path rows: batched vs per-message dispatch (the
    # rpc_dispatch_batch_max toggle) at 64B and 4KB, plus the ici
    # small-message threshold crossover at 4KB. Guarded like every point.
    for payload, key in ((64, "rpc_small_qps_64B"),
                         (4096, "rpc_small_qps_4KB")):
        try:
            sweep[key] = small_rpc_point(payload, wedge_log=wedges)
        except Exception as e:  # noqa: BLE001 - report, don't fail the bench
            print(f"# {key} skipped: {e}", file=sys.stderr)
    try:
        sweep["ici_threshold_4KB"] = ici_threshold_point(wedge_log=wedges)
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# ici_threshold_4KB skipped: {e}", file=sys.stderr)

    # Doorbell-free input polling at the conc=1 latency floor (the
    # one-sided plane's second tenant): poll vs no-poll p50/p99.
    try:
        sweep["rpc_poll_64B"] = input_poll_point(wedge_log=wedges)
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# rpc_poll_64B skipped: {e}", file=sys.stderr)

    # One-sided vs two-sided pull p50/p99 at 64B-16MB against a second
    # server process (the memory-semantics tentpole rows).
    try:
        sweep.update(oneside_pull_point())
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# oneside pull point skipped: {e}", file=sys.stderr)

    # Sampled-rpcz overhead row (fleet observability plane): the cost of
    # keeping span collection live in production at 1-in-64 root sampling.
    try:
        sweep["rpcz_overhead_64B"] = rpcz_overhead_point(wedge_log=wedges)
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# rpcz_overhead_64B skipped: {e}", file=sys.stderr)

    # 10x-overload A/B (overload-protection plane): HIGH-lane p99 +
    # goodput while BULK saturates, priority lanes on vs off.
    try:
        sweep["overload_10x"] = overload_point(wedge_log=wedges)
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# overload_10x skipped: {e}", file=sys.stderr)

    # Streaming-inference rows (serving plane): TTFT p99 + aggregate
    # tokens/s for N concurrent streamed sessions, per-tenant session
    # quota (protection) on vs off in the same child.
    try:
        sweep["serving_stream"] = serving_point(wedge_log=wedges)
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# serving_stream skipped: {e}", file=sys.stderr)

    # Serving-fleet rows (ISSUE 14): aggregate tokens/s + TTFT vs fleet
    # size 1/2 and prefill/decode split vs colocated, plus the live
    # drain-migration drive (stream-gap ms, token parity).
    try:
        sweep["serving_fleet"] = serving_fleet_point(wedge_log=wedges)
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# serving_fleet skipped: {e}", file=sys.stderr)
    # Speculative-decoding A/B (ISSUE 15): spec-on/off tokens/s +
    # per-token p50 on acceptance-friendly and adversarial workloads,
    # single server + fleet-size-2.
    try:
        sweep["serving_spec"] = serving_spec_point(wedge_log=wedges)
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# serving_spec skipped: {e}", file=sys.stderr)
    # Paged-KV A/B (ISSUE 18): live-sessions-per-GB at a fixed arena +
    # matched-concurrency tokens/s, paged vs monolithic, shared and
    # distinct prompts.
    try:
        sweep["serving_paged"] = serving_paged_point(wedge_log=wedges)
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# serving_paged skipped: {e}", file=sys.stderr)
    try:
        sweep["serving_fleet_drain"] = serving_drain_point(
            wedge_log=wedges)
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# serving_fleet_drain skipped: {e}", file=sys.stderr)

    # Overlapped-training-step rows (step-driver tentpole): serial vs
    # dependency-scheduled step on the RPC train loop. Headline config
    # rides one-sided pulls (PR 11 composing with PR 12: wire-lane CPU
    # stays low, so the wire is RTT/optimizer wait the compute hides);
    # the _rpc variant shows the pure two-sided path.
    try:
        sweep.update(step_overlap_point())
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# step overlap point skipped: {e}", file=sys.stderr)
    try:
        rpc = step_overlap_point(n_layers=8, dim=1024, batch=16, steps=5,
                                 reps=4, oneside=False)
        sweep["step_overlap_rpc"] = rpc["step_overlap"]
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# step overlap rpc point skipped: {e}", file=sys.stderr)

    # Pipelined parameter-server rows (async tensor RPC tentpole): 32x1MB
    # serial round-trips vs one bounded PipelineWindow, pull and push.
    try:
        sweep.update(param_pipeline_point())
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# param pipeline point skipped: {e}", file=sys.stderr)

    # Quantized tensor wire rows: raw vs int8 pull_all/push_all with wire
    # AND effective GB/s (the past-the-byte-ceiling metric, old host run, records deleted in PR 21).
    try:
        sweep.update(param_quant_point())
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# param quant point skipped: {e}", file=sys.stderr)

    # Sharded-fleet rows: aggregate pull_all GB/s at 1/2/4 shards (one
    # server process per shard) + the kill-a-shard recovery drive.
    try:
        sweep.update(fleet_point())
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# fleet point skipped: {e}", file=sys.stderr)

    # Collective rows (fleet collectives tentpole): ring allreduce and
    # allgather at 1/2/4 members, raw vs int8-per-hop — loopback truth
    # first, then the WIRE-BOUND config (per-member uplink paced to a
    # 1GbE-class 0.125 GB/s, where the byte cut must convert to time),
    # plus the quantized-training convergence-parity row.
    try:
        sweep.update(collective_point())
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# collective point skipped: {e}", file=sys.stderr)
    try:
        sweep.update(collective_point(counts=(2,), emu_gbps=0.125,
                                      reps=5))
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# collective wirebound point skipped: {e}",
              file=sys.stderr)
    try:
        sweep.update(collective_converge_point())
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# collective converge point skipped: {e}", file=sys.stderr)

    # Parallelism-regime rows (ISSUE 20): steps/s for DP / PP / TP /
    # PPxDP on the wire-bound config, serial-vs-overlap pairs, the T3
    # track-and-trigger exposed-wait A/B, and the live DP -> PP
    # ownership switch under push load.
    try:
        sweep.update(train_regime_point())
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# train regime point skipped: {e}", file=sys.stderr)
    try:
        sweep.update(regime_switch_point())
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# regime switch point skipped: {e}", file=sys.stderr)

    # Tensor bridge rows (the chartered workload): jax/numpy arrays riding
    # the framework through TensorArena by-reference attachments.
    try:
        sweep.update(tensor_bridge_point())
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# tensor bridge point skipped: {e}", file=sys.stderr)

    # Framework-recorder snapshots: the SAME LatencyRecorders the server
    # console serves at /vars and /brpc_metrics, read after the sweeps —
    # cross-checking the wall-clock numbers above against what the
    # framework measured about itself (drift between the two is a finding,
    # not noise). rpc_client covers every echo call the C bench loops made
    # in this process; tensor_push/tensor_pull cover the tensor rows.
    try:
        sweep["framework_recorders"] = recorder_snapshot()
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# recorder snapshot skipped: {e}", file=sys.stderr)

    # Device-compute point: ring attention (brpc_tpu/ops/ring_attention)
    # on whatever accelerator JAX sees — on the real chip this exercises
    # the MXU at bf16; on the 1-device mesh the ring degenerates to flash
    # attention with no collectives. Guarded: a JAX/device problem must
    # never cost the RPC headline above.
    try:
        sweep["ring_attention"] = ring_attention_point()
    except Exception as e:  # noqa: BLE001 - report, don't fail the bench
        print(f"# ring attention point skipped: {e}", file=sys.stderr)

    # Every row without a platform of its own ran on the CPU: in a child
    # (_run_child) or, for the host-transport rows, in host memory. Only
    # tensor_pull_device and ring_attention ran on this process's device.
    for row in sweep.values():
        if isinstance(row, dict):
            row.setdefault("platform", "cpu")
    if wedges:
        sweep["wedged_samples"] = wedges

    headline = sweep["tpu_1048576B"]["gbps"]
    tcp = sweep.get("tcp_1048576B", {}).get("gbps", 0.0)
    doc = {
        "metric": "echo_1mb_oneway_throughput_tpu",
        "value": headline,
        "unit": "GB/s",
        # Per-transport ratios (VERDICT r4 #10): the headline compares our
        # shm/ICI-class transport against the reference's best published
        # number, which is a 10GbE NIC figure — a CROSS-TRANSPORT ratio.
        # The like-for-like ratio is tcp_vs_baseline (our TCP loopback vs
        # that same 2.3 GB/s); the reference publishes no RDMA number
        # (BASELINE.md row 16) for a same-class comparison.
        "vs_baseline": round(headline / BASELINE_GBPS, 3),
        "vs_baseline_note": "tpu-shm transport vs reference 10GbE NIC "
                            "(cross-transport); see tcp_vs_baseline for "
                            "like-for-like",
        "tcp_vs_baseline": round(tcp / BASELINE_GBPS, 3),
        "sweep": sweep,
    }
    print(json.dumps(doc))
    write_bench_json(doc)


def next_bench_round() -> int:
    """One past the highest committed BENCH_r<N>.json in the repo root."""
    import glob
    import re

    root = os.path.dirname(os.path.abspath(__file__))
    rounds = [0]
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if m:
            rounds.append(int(m.group(1)))
    return max(rounds) + 1


def write_bench_json(doc) -> str:
    """Persist the machine-readable trajectory point: every FULL run
    writes BENCH_r<N>.json beside the earlier rounds (the trajectory is only useful
    if it keeps being written). Failure to write must not fail the bench
    (read-only checkouts); the stdout JSON line is still the result."""
    root = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(root, f"BENCH_r{next_bench_round():02d}.json")
    try:
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"# wrote {path}", file=sys.stderr)
    except OSError as e:
        print(f"# BENCH json not written: {e}", file=sys.stderr)
        return ""
    return path


# The whole serial-vs-pipelined measurement runs in ONE watchdogged child
# (which spawns the ParameterServer in a FURTHER process: sharing a process
# would serialize the client loop and the server's Python handlers on one
# GIL and measure lock contention, not the wire). argv:
#   n_tensors nbytes window reps pull_only(0/1)
_PARAM_CHILD = r"""
import json, statistics, sys, time, subprocess
sys.path.insert(0, ROOT)
import numpy as np

n_tensors, nbytes, window, reps, pull_only = (int(a) for a in sys.argv[1:6])
server_code = (
    "import sys, json\n"
    "sys.path.insert(0, %r)\n"
    "import jax.numpy as jnp\n"
    "from brpc_tpu.runtime.param_server import ParameterServer\n"
    "params = {'w%%02d' %% i: jnp.ones((%d // 4,), jnp.float32) * i\n"
    "          for i in range(%d)}\n"
    "ps = ParameterServer(params)\n"
    "print(json.dumps({'port': ps.start()}), flush=True)\n"
    "sys.stdin.readline()\n"
    "ps.stop()\n" % (ROOT, nbytes, n_tensors))
srv = subprocess.Popen([sys.executable, "-c", server_code],
                       stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                       text=True)
try:
    port = json.loads(srv.stdout.readline())["port"]
    from brpc_tpu.runtime.param_server import ParameterClient
    client = ParameterClient(f"tpu://127.0.0.1:{port}")
    names = sorted(client.meta())
    grads = {n: np.ones(nbytes // 4, np.float32) for n in names}
    client.pull(names[0])
    client.pull_all(names[: min(2, len(names))], window=2)
    if not pull_only:
        client.push_grad(names[0], grads[names[0]])

    def once(fn):
        t0 = time.monotonic()
        fn()
        return time.monotonic() - t0

    total = n_tensors * nbytes
    modes = [("pull", lambda: [client.pull(n) for n in names],
              lambda: client.pull_all(names, window=window))]
    if not pull_only:
        modes.append(("push",
                      lambda: [client.push_grad(n, grads[n]) for n in names],
                      lambda: client.push_all(grads, window=window)))
    rows = {}
    for kind, serial_fn, piped_fn in modes:
        # INTERLEAVED pairs: this host's steal is bimodal (old run, records deleted in PR 21) and
        # a slow window hitting only one mode fabricates or destroys the
        # comparison; adjacent serial/pipelined runs see the same host
        # state, so the per-pair ratio is steal-robust. Median of ratios,
        # alongside median absolute times.
        ts_samples, tp_samples, ratios = [], [], []
        for _ in range(reps):
            ts_i = once(serial_fn)
            tp_i = once(piped_fn)
            ts_samples.append(ts_i)
            tp_samples.append(tp_i)
            ratios.append(ts_i / tp_i)
        ts = statistics.median(ts_samples)
        tp = statistics.median(tp_samples)
        rows[kind] = {
            "serial_ms": round(ts * 1e3, 1),
            "pipelined_ms": round(tp * 1e3, 1),
            "serial_gbps": round(total / ts / 1e9, 2),
            "pipelined_gbps": round(total / tp / 1e9, 2),
            "speedup": round(statistics.median(ratios), 2),
            "speedup_samples": [round(r, 2) for r in ratios],
            "window": window, "tensors": n_tensors, "reps": reps,
        }
    client.close()
    print(json.dumps(rows))
finally:
    try:
        srv.stdin.close()
        srv.wait(timeout=10)
    except Exception:
        srv.kill()
"""


# Quantized tensor wire rows: raw vs negotiated-int8 pull_all/push_all on
# the SAME server, interleaved pairs (PERF methodology — adjacent samples
# see the same host state, median of per-pair ratios). Reports BOTH wire
# GB/s (bytes that crossed the transport / wall time) and effective GB/s
# (logical tensor bytes / wall time) — the codec's whole point is that
# the second exceeds the transport's byte ceiling. argv:
#   n_tensors nbytes window reps pull_only(0/1)
_QUANT_CHILD = r"""
import json, statistics, sys, time, subprocess
sys.path.insert(0, ROOT)
import numpy as np

n_tensors, nbytes, window, reps, pull_only = (int(a) for a in sys.argv[1:6])
server_code = (
    "import sys, json\n"
    "sys.path.insert(0, %r)\n"
    "import jax.numpy as jnp\n"
    "from brpc_tpu.runtime.param_server import ParameterServer\n"
    "import numpy as _np\n"
    "rng = _np.random.default_rng(0)\n"
    "params = {'w%%02d' %% i:\n"
    "          jnp.asarray(rng.normal(size=(%d // 4,)).astype('float32'))\n"
    "          for i in range(%d)}\n"
    "ps = ParameterServer(params)\n"
    "print(json.dumps({'port': ps.start()}), flush=True)\n"
    "sys.stdin.readline()\n"
    "ps.stop()\n" % (ROOT, nbytes, n_tensors))
srv = subprocess.Popen([sys.executable, "-c", server_code],
                       stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                       text=True)
try:
    port = json.loads(srv.stdout.readline())["port"]
    from brpc_tpu.runtime import codec as codec_mod
    from brpc_tpu.runtime.param_server import ParameterClient
    raw = ParameterClient(f"tpu://127.0.0.1:{port}")
    quant = ParameterClient(f"tpu://127.0.0.1:{port}", codec="int8")
    assert quant.negotiated_codec() == "int8", "codec negotiation failed"
    names = sorted(raw.meta())
    rng = np.random.default_rng(1)
    grads = {n: rng.normal(size=(nbytes // 4,)).astype(np.float32)
             for n in names}
    n_el = nbytes // 4
    wire_per = -(-n_el // codec_mod.DEFAULT_BLOCK) * 4 + n_el  # scales+codes
    # Warm both paths: channels, jax dispatch, the server's encode cache
    # (quantize-once-serve-many — the steady state a parameter server
    # actually runs in; the first quant pull pays the encode).
    raw.pull_all(names, window=window)
    quant.pull_all(names, window=window)
    if not pull_only:
        raw.push_all({names[0]: grads[names[0]]}, window=2)
        quant.push_all({names[0]: grads[names[0]]}, window=2)
        quant.pull_all(names[:2], window=2)  # re-warm encode cache post-push

    def timed(fn, min_s=0.4):
        # One sample = a >= min_s loop, not one call: a single pull_all is
        # 10-40ms and this host's steal comes in windows of that same
        # order, so single-shot pairs are coin flips — looping averages
        # the steal duty cycle into every sample (same reason the echo
        # samples run for a full second).
        iters = 0
        t0 = time.monotonic()
        while True:
            fn()
            iters += 1
            dt = time.monotonic() - t0
            if dt >= min_s and iters >= 2:
                return dt / iters

    logical = n_tensors * nbytes
    wire_q = n_tensors * wire_per
    modes = [("pull", lambda: raw.pull_all(names, window=window),
              lambda: quant.pull_all(names, window=window))]
    if not pull_only:
        modes.append(("push", lambda: raw.push_all(grads, window=window),
                      lambda: quant.push_all(grads, window=window)))
    rows = {}
    for kind, raw_fn, quant_fn in modes:
        tr_samples, tq_samples, ratios = [], [], []
        for _ in range(reps):
            tr = timed(raw_fn)
            tq = timed(quant_fn)
            tr_samples.append(tr)
            tq_samples.append(tq)
            ratios.append(tr / tq)
        tr = statistics.median(tr_samples)
        tq = statistics.median(tq_samples)
        rows[kind] = {
            "raw_ms": round(tr * 1e3, 1),
            "quant_ms": round(tq * 1e3, 1),
            "raw_gbps": round(logical / tr / 1e9, 2),
            "quant_eff_gbps": round(logical / tq / 1e9, 2),
            "quant_wire_gbps": round(wire_q / tq / 1e9, 2),
            "wire_ratio": round(logical / wire_q, 2),
            "speedup": round(statistics.median(ratios), 2),
            "speedup_samples": [round(r, 2) for r in ratios],
            "codec": "int8", "window": window, "tensors": n_tensors,
            "reps": reps,
        }
    raw.close()
    quant.close()
    print(json.dumps(rows))
finally:
    try:
        srv.stdin.close()
        srv.wait(timeout=10)
    except Exception:
        srv.kill()
"""


# One-sided vs two-sided pull latency (the memory-semantics data plane).
# The server runs in a FURTHER process so the client's one-sided reads
# really cross a process boundary through the shm mapping — in-process
# both paths would share one allocator and one GIL and measure neither.
# argv: reps
_ONESIDE_CHILD = r"""
import json, statistics, sys, time, subprocess
sys.path.insert(0, ROOT)
import numpy as np

reps = int(sys.argv[1])
sizes = json.loads(sys.argv[2])  # [[nbytes, key, iters], ...]
server_code = (
    "import sys, json\n"
    "sys.path.insert(0, %r)\n"
    "import numpy as np\n"
    "import jax.numpy as jnp\n"
    "from brpc_tpu.runtime.param_server import ParameterServer\n"
    "params = {'s%%d' %% n: jnp.asarray(\n"
    "    np.arange(max(n // 4, 1), dtype=np.float32))\n"
    "          for n in %s}\n"
    "ps = ParameterServer(params, oneside=True)\n"
    "print(json.dumps({'port': ps.start()}), flush=True)\n"
    "sys.stdin.readline()\n"
    "ps.stop()\n" % (ROOT, [s[0] for s in sizes]))
srv = subprocess.Popen([sys.executable, "-c", server_code],
                       stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                       text=True)
try:
    port = json.loads(srv.stdout.readline())["port"]
    from brpc_tpu.observability import metrics as obs
    from brpc_tpu.runtime.param_server import ParameterClient
    c_one = ParameterClient(f"tpu://127.0.0.1:{port}", oneside=True)
    c_rpc = ParameterClient(f"tpu://127.0.0.1:{port}")
    c_one.pull(f"s{sizes[0][0]}")  # warmup: map + first decode + compile
    c_rpc.pull(f"s{sizes[0][0]}")
    # The row is meaningless if the mapping silently fell back to RPC.
    assert obs.counter("oneside_pull_hits").value() > 0, "no one-sided hits"

    def pctl(xs, q):
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(len(xs) * q))]

    rows = {}
    for nbytes, key, iters in sizes:
        name = f"s{nbytes}"
        # Per-size warmup OUTSIDE the timed window: first-touch page
        # faults of the fresh 16MB buffers (and the first XLA transfer
        # of each shape) otherwise dominate a short p50.
        for _ in range(3):
            c_one.pull(name)
            c_rpc.pull(name)
        o50, o99, r50, r99, ratios = [], [], [], [], []
        for _ in range(reps):
            pair = {}
            # INTERLEAVED one-sided/RPC batches: adjacent batches see the
            # same host-steal state, so per-pair p50 ratios are robust
            # (PERF.md methodology).
            for mode, cl in (("one", c_one), ("rpc", c_rpc)):
                lat = []
                for _ in range(iters):
                    t0 = time.monotonic()
                    cl.pull(name)
                    lat.append((time.monotonic() - t0) * 1e6)
                pair[mode] = (pctl(lat, 0.5), pctl(lat, 0.99))
            o50.append(pair["one"][0]); o99.append(pair["one"][1])
            r50.append(pair["rpc"][0]); r99.append(pair["rpc"][1])
            ratios.append(pair["rpc"][0] / max(pair["one"][0], 1e-9))
        # The RAW memory-semantics read (epoch pin + seqlock snapshot +
        # copy-out, no decode/device dispatch): what the data movement
        # itself costs once the RPC plane is out of the path.
        rd = c_one._oneside_reader
        raw = []
        for _ in range(min(iters * 2, 500)):
            t0 = time.monotonic()
            rd.read(name)
            raw.append((time.monotonic() - t0) * 1e6)
        rows[key] = {
            "oneside_raw_p50_us": round(pctl(raw, 0.5), 1),
            "oneside_p50_us": round(statistics.median(o50), 1),
            "oneside_p99_us": round(statistics.median(o99), 1),
            "rpc_p50_us": round(statistics.median(r50), 1),
            "rpc_p99_us": round(statistics.median(r99), 1),
            "p50_speedup": round(statistics.median(ratios), 2),
            "speedup_samples": [round(r, 2) for r in ratios],
            "iters": iters, "reps": reps}
    print(json.dumps(rows))
    c_one.close()
    c_rpc.close()
finally:
    try:
        srv.stdin.write("\n")
        srv.stdin.flush()
        srv.wait(timeout=10)
    except Exception:
        srv.kill()
"""


def oneside_pull_point(reps=5, timeout=420, sizes=None):
    """One-sided read vs two-sided Pull RPC, p50/p99 at 64B-16MB against
    a REAL second server process (the same-host mapping the tentpole
    serves). The one-sided number is the whole client path — epoch pin,
    seqlock descriptor snapshot, payload copy-out, decode, device
    dispatch — just with zero RPCs in it."""
    if sizes is None:
        sizes = [[64, "oneside_pull_64B", 400],
                 [4096, "oneside_pull_4KB", 400],
                 [1 << 20, "oneside_pull_1MB", 40],
                 [16 << 20, "oneside_pull_16MB", 12]]
    code = "ROOT = %r\n%s" % (
        os.path.dirname(os.path.abspath(__file__)), _ONESIDE_CHILD)
    proc = _run_child(  # tpulint: allow(py-blocking)
        [sys.executable, "-c", code, str(reps), json.dumps(sizes)],
        capture_output=True, timeout=timeout, text=True)
    sys.stderr.write(proc.stderr[-2000:] if proc.stderr else "")
    if proc.returncode != 0 or not proc.stdout.strip():
        raise RuntimeError(f"oneside child failed rc={proc.returncode}")
    rows = json.loads(proc.stdout.strip().splitlines()[-1])
    for key, row in rows.items():
        print(f"# {key}: rpc p50 {row['rpc_p50_us']}us -> one-sided p50 "
              f"{row['oneside_p50_us']}us ({row['p50_speedup']}x, samples "
              f"{row['speedup_samples']})", file=sys.stderr)
    return rows


def param_quant_point(n_tensors=32, nbytes=1 << 20, window=8, reps=7,
                      pull_only=False, timeout=300):
    """Quantized-wire vs raw parameter traffic — the tensor-codec
    tentpole rows (param_pull_all_quant_* / param_push_all_quant_*).
    Same interleaved-pair methodology as param_pipeline_point; the
    headline number is effective GB/s = logical bytes / wall time."""
    code = "ROOT = %r\n%s" % (
        os.path.dirname(os.path.abspath(__file__)), _QUANT_CHILD)
    proc = _run_child(  # tpulint: allow(py-blocking)
        [sys.executable, "-c", code, str(n_tensors), str(nbytes),
         str(window), str(reps), "1" if pull_only else "0"],
        capture_output=True, timeout=timeout, text=True)
    sys.stderr.write(proc.stderr[-2000:] if proc.stderr else "")
    if proc.returncode != 0 or not proc.stdout.strip():
        raise RuntimeError(f"param quant child failed rc={proc.returncode}")
    rows = json.loads(proc.stdout.strip().splitlines()[-1])
    size_mb = nbytes >> 20
    out = {}
    for kind, row in rows.items():
        key = f"param_{kind}_all_quant_{n_tensors}x{size_mb}MB"
        out[key] = row
        print(f"# {key}: raw {row['raw_gbps']} GB/s -> int8 effective "
              f"{row['quant_eff_gbps']} GB/s (wire {row['quant_wire_gbps']}"
              f" GB/s, {row['speedup']}x, samples {row['speedup_samples']})",
              file=sys.stderr)
    return out


def param_pipeline_point(n_tensors=32, nbytes=1 << 20, window=8, reps=7,
                         pull_only=False, timeout=240):
    """Serial vs pipelined multi-tensor parameter traffic — the async
    tensor RPC tentpole rows. N named 1MB parameters cross the wire as N
    serial `pull`/`push_grad` round-trips, then again through one bounded
    `PipelineWindow` (`pull_all`/`push_all`); median of `reps` per mode,
    same process, back to back, so both see the same host conditions.
    Subprocess-guarded like the echo samples."""
    code = "ROOT = %r\n%s" % (
        os.path.dirname(os.path.abspath(__file__)), _PARAM_CHILD)
    proc = _run_child(  # tpulint: allow(py-blocking)
        [sys.executable, "-c", code, str(n_tensors), str(nbytes),
         str(window), str(reps), "1" if pull_only else "0"],
        capture_output=True, timeout=timeout, text=True)
    sys.stderr.write(proc.stderr[-2000:] if proc.stderr else "")
    if proc.returncode != 0 or not proc.stdout.strip():
        raise RuntimeError(f"param pipeline child failed rc={proc.returncode}")
    rows = json.loads(proc.stdout.strip().splitlines()[-1])
    size_mb = nbytes >> 20
    out = {}
    for kind, row in rows.items():
        key = f"param_{kind}_all_{n_tensors}x{size_mb}MB"
        out[key] = row
        print(f"# {key}: serial {row['serial_gbps']} GB/s -> pipelined "
              f"{row['pipelined_gbps']} GB/s ({row['speedup']}x, "
              f"window={row['window']})", file=sys.stderr)
    return out


# Overlapped-vs-serial training step (the ISSUE 12 tentpole row). ONE
# watchdogged child drives BOTH modes against one ParameterServer process
# (the deployment shape: trainer process + server process), interleaving
# serial/overlapped samples so adjacent drives see the same host-steal
# state (PERF methodology, median of per-pair ratios). The step-time
# breakdown (compute / exposed-comm / overlapped-comm) comes from the
# driver's own RunTrace accounting — the acceptance shape is exposed-comm
# shrinking while compute stays put. argv:
#   n_layers dim batch steps reps oneside(0/1)
_STEP_CHILD = r"""
import json, statistics, sys, time, subprocess
sys.path.insert(0, ROOT)
# The overlapped step runs TWO Python threads (compute + wire lane); the
# default 5ms GIL switch interval lets the wire thread's poll loops hold
# the GIL in whole scheduler quanta while jax's Python dispatch starves —
# a convoy that reads as inflated compute. 0.5ms keeps dispatch moving at
# negligible switching cost (both modes get the same setting: fair A/B).
sys.setswitchinterval(0.0005)

n_layers, dim, batch, steps, reps, oneside = (int(a) for a in sys.argv[1:7])
sizes = [dim] * (n_layers + 1)
server_code = (
    "import sys, json\n"
    "sys.path.insert(0, %r)\n"
    "from brpc_tpu.models.tensor_service import LayeredMLP\n"
    "from brpc_tpu.runtime.param_server import ParameterServer\n"
    "h = LayeredMLP(%r, seed=0)\n"
    "ps = ParameterServer(dict(h.init_params()), oneside=%d)\n"
    "print(json.dumps({'port': ps.start()}), flush=True)\n"
    "sys.stdin.readline()\n"
    "ps.stop()\n" % (ROOT, sizes, oneside))
srv = subprocess.Popen([sys.executable, "-c", server_code],
                       stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                       text=True)
try:
    port = json.loads(srv.stdout.readline())["port"]
    from brpc_tpu.models.tensor_service import LayeredMLP
    from brpc_tpu.runtime.param_server import ParameterClient
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    h = LayeredMLP(sizes, seed=0)
    drivers = {}
    for mode in ("serial", "overlapped"):
        cl = ParameterClient(f"tpu://127.0.0.1:{port}",
                             oneside=bool(oneside))
        d = OverlappedStepDriver(cl, h, overlap=(mode == "overlapped"),
                                 window=4)
        d.prime()
        drivers[mode] = d
    x, y = h.data(batch, seed=1)
    for mode in ("serial", "overlapped"):  # warm: jit + channels + meta
        for _ in range(2):
            drivers[mode].step(x, y)

    def drive(d):
        stats = []
        t0 = time.monotonic()
        for _ in range(steps):
            d.step(x, y)
            stats.append(d.last_stats)
        return time.monotonic() - t0, stats

    samples = {"serial": [], "overlapped": []}
    breakdown = {"serial": [], "overlapped": []}
    ratios = []
    for _ in range(reps):
        ts, st_s = drive(drivers["serial"])
        to, st_o = drive(drivers["overlapped"])
        samples["serial"].append(ts)
        samples["overlapped"].append(to)
        breakdown["serial"].extend(st_s)
        breakdown["overlapped"].extend(st_o)
        ratios.append(ts / to)

    def med(xs):
        return statistics.median(xs)

    row = {"speedup": round(med(ratios), 2),
           "speedup_samples": [round(r, 2) for r in ratios],
           "layers": n_layers, "dim": dim, "batch": batch,
           "steps": steps, "reps": reps, "oneside": bool(oneside),
           "param_bytes_per_layer": dim * dim * 4}
    for mode in ("serial", "overlapped"):
        t = med(samples[mode])
        bd = breakdown[mode]
        row[f"{mode}_steps_s"] = round(steps / t, 2)
        row[f"{mode}_step_ms"] = round(t / steps * 1e3, 1)
        row[f"{mode}_compute_ms"] = round(
            med([s["compute_ms"] for s in bd]), 1)
        row[f"{mode}_exposed_comm_ms"] = round(
            med([s["exposed_comm_ms"] for s in bd]), 1)
        row[f"{mode}_overlapped_comm_ms"] = round(
            med([s["overlapped_comm_ms"] for s in bd]), 1)
    for d in drivers.values():
        d.client.close()
    print(json.dumps({"step_overlap": row}))
finally:
    try:
        srv.stdin.close()
        srv.wait(timeout=10)
    except Exception:
        srv.kill()
"""


def step_overlap_point(n_layers=16, dim=512, batch=8, steps=6, reps=7,
                       oneside=True, timeout=600):
    """Serial vs overlapped step driver on the RPC train loop — the
    overlapped-training-step tentpole row: end-to-end steps/s plus the
    per-step compute / exposed-comm / overlapped-comm breakdown the
    driver accounts itself. Subprocess-guarded like every bench point."""
    code = "ROOT = %r\n%s" % (
        os.path.dirname(os.path.abspath(__file__)), _STEP_CHILD)
    proc = _run_child(  # tpulint: allow(py-blocking)
        [sys.executable, "-c", code, str(n_layers), str(dim), str(batch),
         str(steps), str(reps), "1" if oneside else "0"],
        capture_output=True, timeout=timeout, text=True)
    sys.stderr.write(proc.stderr[-2000:] if proc.stderr else "")
    if proc.returncode != 0 or not proc.stdout.strip():
        raise RuntimeError(f"step overlap child failed rc={proc.returncode}")
    rows = json.loads(proc.stdout.strip().splitlines()[-1])
    row = rows["step_overlap"]
    print(f"# step_overlap: serial {row['serial_steps_s']} steps/s -> "
          f"overlapped {row['overlapped_steps_s']} steps/s "
          f"({row['speedup']}x, samples {row['speedup_samples']}); "
          f"exposed comm {row['serial_exposed_comm_ms']} -> "
          f"{row['overlapped_exposed_comm_ms']} ms/step, compute "
          f"{row['serial_compute_ms']} -> {row['overlapped_compute_ms']}"
          " ms/step", file=sys.stderr)
    return rows


# Sharded-fleet rows. ONE watchdogged child orchestrates: an in-child
# registry hub, one SUBPROCESS per shard (a shard shares nothing with the
# client loop — same reasoning as _PARAM_CHILD, and exactly the deployment
# shape: N server processes, one trainer), persistent FleetClients per
# shard count. Samples interleave across shard counts (adjacent samples
# see the same host state; per-rep ratios are steal-robust). argv:
#   n_tensors nbytes reps do_kill(0/1) counts...
_FLEET_CHILD = r"""
import json, statistics, subprocess, sys, time
sys.path.insert(0, ROOT)
import numpy as np
from brpc_tpu.fleet import FleetClient, RegistryHub

n_tensors, nbytes, reps, do_kill = (int(a) for a in sys.argv[1:5])
counts = [int(a) for a in sys.argv[5:]]
SERVER = (
    "import sys, json\n"
    "sys.path.insert(0, %r)\n"
    "from brpc_tpu.fleet import FleetServer\n"
    "s = FleetServer(sys.argv[1], tag=sys.argv[2], ttl_s=3)\n"
    "print(json.dumps({'addr': s.start()}), flush=True)\n"
    "sys.stdin.readline()\n"
    "s.stop()\n" % ROOT)

hub = RegistryHub()
hub.start()
procs = []
try:
    shard_procs = {}
    for n in counts:
        tag = f"bench{n}"
        shard_procs[tag] = [
            subprocess.Popen([sys.executable, "-c", SERVER, hub.hostport,
                              tag], stdin=subprocess.PIPE,
                             stdout=subprocess.PIPE, text=True)
            for _ in range(n)]
        procs.extend(shard_procs[tag])
    for p in procs:  # all spawned first: jax import dominates, overlap it
        json.loads(p.stdout.readline())
    names = [f"w{i:02d}" for i in range(n_tensors)]
    fleets = {}
    for n in counts:
        fc = FleetClient(hub.hostport, tag=f"bench{n}", window=4,
                         op_deadline_s=30.0)
        for name in names:  # one registry refresh, not one per tensor
            fc.install(name, np.ones(nbytes // 4, np.float32),
                       refresh=False)
        fc.pull_all(names)  # warm: channels + arenas + meta caches
        fleets[n] = fc
    samples = {n: [] for n in counts}
    for _ in range(reps):
        for n in counts:
            t0 = time.monotonic()
            got = fleets[n].pull_all(names)
            samples[n].append(time.monotonic() - t0)
            assert len(got) == n_tensors
    total = n_tensors * nbytes
    out = {}
    for n in counts:
        med = statistics.median(samples[n])
        best = min(samples[n])
        # Median for the headline; best-of for the steal floor (this host
        # class has bimodal steal — old run, records deleted in PR 21 — and an N-process fleet
        # multiplies exposure to it; the min is what the fleet does on a
        # quiet slice of the box).
        row = {"gbps": round(total / med / 1e9, 2),
               "ms": round(med * 1e3, 1),
               "best_gbps": round(total / best / 1e9, 2),
               "best_ms": round(best * 1e3, 1), "shards": n,
               "tensors": n_tensors, "nbytes": nbytes, "reps": reps}
        if n != counts[0]:
            ratios = [samples[counts[0]][i] / samples[n][i]
                      for i in range(reps)]
            row["speedup_vs_1s"] = round(statistics.median(ratios), 2)
            row["speedup_samples"] = [round(r, 2) for r in ratios]
        out[f"fleet_pull_GBps_{n}s"] = row
    if do_kill and 2 in fleets:
        # Abrupt shard death on the 2-shard fleet: time from SIGKILL to
        # the first CLEAN partial pull_all (watch registry pruned the
        # victim at TTL, lost names report missing fast, survivors serve).
        kfc = FleetClient(hub.hostport, tag="bench2", window=4,
                          op_deadline_s=6.0)
        kfc.pull_all(names)
        victim = shard_procs["bench2"][-1]
        t0 = time.monotonic()
        victim.kill()
        survivors = None
        while time.monotonic() - t0 < 60:
            try:
                got = kfc.pull_all(names, on_missing="skip")
            except Exception:
                continue  # still inside the TTL window; retry
            if len(got) < n_tensors:
                survivors = len(got)
                break
        out["fleet_kill_recovery"] = {
            "recovery_ms": round((time.monotonic() - t0) * 1e3),
            "survivors": survivors, "lost": n_tensors - (survivors or 0),
            "ttl_s": 3}
        kfc.close()
    for fc in fleets.values():
        fc.close()
    print(json.dumps(out))
finally:
    for p in procs:
        try:
            p.stdin.close()
            p.wait(timeout=5)
        except Exception:
            p.kill()
"""


def fleet_point(counts=(1, 2, 4), n_tensors=32, nbytes=1 << 20, reps=7,
                do_kill=True, timeout=420):
    """Sharded-fleet pull rows: aggregate pull_all GB/s vs shard count
    (each shard its own server process; interleaved samples, median of
    per-rep ratios vs the 1-shard fleet) plus the kill-a-shard
    recovery-time row. Subprocess-guarded like every bench point."""
    code = "ROOT = %r\n%s" % (
        os.path.dirname(os.path.abspath(__file__)), _FLEET_CHILD)
    argv = [sys.executable, "-c", code, str(n_tensors), str(nbytes),
            str(reps), "1" if do_kill else "0"] + [str(c) for c in counts]
    proc = _run_child(  # tpulint: allow(py-blocking)
        argv, capture_output=True, timeout=timeout, text=True)
    sys.stderr.write(proc.stderr[-2000:] if proc.stderr else "")
    if proc.returncode != 0 or not proc.stdout.strip():
        raise RuntimeError(f"fleet child failed rc={proc.returncode}")
    rows = json.loads(proc.stdout.strip().splitlines()[-1])
    for key, row in rows.items():
        if key.startswith("fleet_pull"):
            speedup = row.get("speedup_vs_1s")
            print(f"# {key}: {row['gbps']} GB/s ({row['ms']} ms/pull_all)"
                  + (f", {speedup}x vs 1 shard" if speedup else ""),
                  file=sys.stderr)
        else:
            print(f"# {key}: {row}", file=sys.stderr)
    return rows


# Collective rows (ISSUE 13): ring allreduce/allgather over the tensor
# wire. ONE orchestrating child runs the registry hub and spawns one
# member PROCESS per rank (the deployment shape — and jax dispatch from
# member THREADS in one process contends, PR 6); members coordinate only
# through the registry + the wire, exactly like a real fleet. Raw and
# int8 groups alternate per rep (interleaved pairs, median-of-ratios —
# the PERF.md discipline). argv: nbytes reps emu_gbps counts...
_COLL_MEMBER = r"""
import json, sys, tempfile, time
sys.path.insert(0, ROOT)
import numpy as np
from brpc_tpu.collectives.group import CollectiveGroup
from brpc_tpu.observability import health

hub, n, size, reps, emu = (sys.argv[1], int(sys.argv[2]),
                           int(sys.argv[3]), int(sys.argv[4]),
                           float(sys.argv[5]))
health.start_watchdog(tempfile.mkdtemp(prefix="coll_bench_dumps_"))
kw = dict(window=8, op_timeout_s=120.0)
if emu > 0:
    kw["emulate_wire_gbps"] = emu
graw = CollectiveGroup(hub, tag="raw", **kw)
gq = CollectiveGroup(hub, tag="q", codec="int8", **kw)
graw.sync(expect=n, timeout_s=60)
gq.sync(expect=n, timeout_s=60)
x = np.random.RandomState(graw.rank).randn(size).astype(np.float32)
xg = x[:size // 2]
# Warmup: channels, Hello negotiation, arenas, the fused-encoder jit.
graw.allreduce("w", x, algo="ring")
gq.allreduce("w", x, algo="ring")
gq.allreduce("w2", x, algo="ring")
t_raw, t_q, t_agr, t_agq = [], [], [], []
for i in range(reps):
    t0 = time.monotonic()
    graw.allreduce("r%d" % i, x, algo="ring")
    t_raw.append(time.monotonic() - t0)
    t0 = time.monotonic()
    gq.allreduce("q%d" % i, x, algo="ring")
    t_q.append(time.monotonic() - t0)
for i in range(max(1, reps // 2)):
    t0 = time.monotonic()
    graw.allgather("gr%d" % i, xg)
    t_agr.append(time.monotonic() - t0)
    t0 = time.monotonic()
    gq.allgather("gq%d" % i, xg)
    t_agq.append(time.monotonic() - t0)
print(json.dumps({"rank": graw.rank, "raw": t_raw, "q": t_q,
                  "ag_raw": t_agr, "ag_q": t_agq}), flush=True)
graw.close()
gq.close()
"""

_COLL_CHILD = r"""
import json, statistics, subprocess, sys, tempfile, time
sys.path.insert(0, ROOT)
from brpc_tpu.fleet import RegistryHub
from brpc_tpu.observability import health

nbytes, reps, emu = int(sys.argv[1]), int(sys.argv[2]), float(sys.argv[3])
counts = [int(a) for a in sys.argv[4:]]
health.start_watchdog(tempfile.mkdtemp(prefix="coll_dumps_"))
MEMBER = "ROOT = %r\n%s" % (ROOT, MEMBER_SRC)
size = nbytes // 4
hub = RegistryHub()
hub.start()
out = {}
try:
    for n in counts:
        procs = [subprocess.Popen(
            [sys.executable, "-c", MEMBER, hub.hostport, str(n),
             str(size), str(reps), str(emu)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            for _ in range(n)]
        docs = []
        try:
            for p in procs:
                so, se = p.communicate(timeout=420)
                if p.returncode != 0 or not so.strip():
                    sys.stderr.write(se[-1500:])
                    raise RuntimeError("collective member failed")
                docs.append(json.loads(so.strip().splitlines()[-1]))
        finally:
            # One member failing must not orphan its ring mates: they
            # would sit against a dead op for up to op_timeout_s while
            # the caller's retry spawns a SECOND member set on top.
            for p in procs:
                if p.poll() is None:
                    p.kill()
        d = [x for x in docs if x["rank"] == 0][0]
        med_raw = statistics.median(d["raw"])
        med_q = statistics.median(d["q"])
        ratios = sorted(a / b for a, b in zip(d["raw"], d["q"]))
        row = {"members": n, "nbytes": nbytes, "reps": reps,
               "raw_ms": round(med_raw * 1e3, 1),
               "raw_GBps": round(nbytes / med_raw / 1e9, 3),
               "quant_ms": round(med_q * 1e3, 1),
               "quant_eff_GBps": round(nbytes / med_q / 1e9, 3),
               "quant_vs_raw": round(statistics.median(ratios), 2),
               "quant_vs_raw_samples": [round(r, 2) for r in ratios]}
        if emu > 0:
            row["emulated_wire_gbps"] = emu
        out["allreduce_GBps_%ds" % n] = row
        if n == max(counts) or (emu > 0 and n == counts[-1]):
            agm_r = statistics.median(d["ag_raw"])
            agm_q = statistics.median(d["ag_q"])
            agr = sorted(a / b for a, b in zip(d["ag_raw"], d["ag_q"]))
            ag = {"members": n, "nbytes": nbytes // 2,
                  "raw_ms": round(agm_r * 1e3, 1),
                  "raw_GBps": round((nbytes // 2) * (n - 1) / agm_r
                                    / 1e9, 3) if n > 1 else 0.0,
                  "quant_ms": round(agm_q * 1e3, 1),
                  "quant_vs_raw": round(statistics.median(agr), 2),
                  "quant_vs_raw_samples": [round(r, 2) for r in agr]}
            if emu > 0:
                ag["emulated_wire_gbps"] = emu
            out["allgather_GBps"] = ag
    print(json.dumps(out))
finally:
    hub.stop()
"""


def collective_point(counts=(1, 2, 4), nbytes=16 << 20, reps=5,
                     emu_gbps=0.0, timeout=900):
    """Ring allreduce/allgather rows: raw fp32 vs int8-quantized over
    the live wire, one member process per rank, interleaved pairs,
    median-of-ratios. ``emu_gbps`` > 0 runs the WIRE-BOUND config: each
    member's uplink paced to that bandwidth (loopback shm moves bytes
    at memcpy speed, which no cross-host fleet link does — the paced
    link is where the byte cut must convert to time; the unpaced rows
    report the loopback truth beside it)."""
    code = ("ROOT = %r\nMEMBER_SRC = %r\n%s"
            % (os.path.dirname(os.path.abspath(__file__)), _COLL_MEMBER,
               _COLL_CHILD))
    argv = [sys.executable, "-c", code, str(nbytes), str(reps),
            str(emu_gbps)] + [str(c) for c in counts]
    # One retry: the child is N jax member processes — a host-pressure
    # window (steal/paging) can starve a hop past its op timeout once
    # in a full sweep; a clean re-run distinguishes that from a real
    # regression (the wedge-guard discipline).
    for attempt in (0, 1):
        proc = _run_child(  # tpulint: allow(py-blocking)
            argv, capture_output=True, timeout=timeout, text=True)
        if proc.returncode == 0 and proc.stdout.strip():
            break
        sys.stderr.write(proc.stderr[-2000:] if proc.stderr else "")
    if proc.returncode != 0 or not proc.stdout.strip():
        raise RuntimeError(f"collective child failed rc={proc.returncode}")
    rows = json.loads(proc.stdout.strip().splitlines()[-1])
    if emu_gbps > 0:
        rows = {k + "_wirebound": v for k, v in rows.items()}
    for key, row in rows.items():
        print(f"# {key}: raw {row['raw_ms']} ms -> quant "
              f"{row['quant_ms']} ms ({row['quant_vs_raw']}x, samples "
              f"{row['quant_vs_raw_samples']})", file=sys.stderr)
    return rows


# Convergence-parity row: N-member data-parallel training where the
# gradient exchange is the quantized collective — the trajectory must
# track the fp32 reduction (EF on), with the naive requantizer as the
# pinned negative control. Each member runs all three trajectories and
# compares locally. argv: hub n steps
_COLL_TRAIN_MEMBER = r"""
import json, sys, tempfile, time
sys.path.insert(0, ROOT)
import numpy as np
from brpc_tpu.collectives.group import CollectiveGroup
from brpc_tpu.models.tensor_service import LayeredMLP
from brpc_tpu.runtime.step_driver import CollectiveStepDriver
from brpc_tpu.observability import health

hub, n, steps = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
health.start_watchdog(tempfile.mkdtemp(prefix="coll_train_dumps_"))
SIZES = [64, 256, 256, 64]


def trajectory(tag, codec, ef):
    g = CollectiveGroup(hub, tag=tag, codec=codec, ef=ef, window=8,
                        op_timeout_s=120.0)
    g.sync(expect=n, timeout_s=60)
    h = LayeredMLP(SIZES, seed=0)
    d = CollectiveStepDriver(g, h, overlap=True, wire_lanes=2)
    d.prime()
    losses = []
    for s in range(steps):
        x, y = h.data(8, seed=700 + s * n + g.rank)
        losses.append(d.step(x, y))
    params = d.params()
    g.close()
    return losses, params


l_raw, p_raw = trajectory("t_raw", None, True)
l_qef, p_qef = trajectory("t_qef", "int8", True)
l_qnv, p_qnv = trajectory("t_qnv", "int8", False)
d_ef = max(float(np.abs(p_raw[k] - p_qef[k]).max()) for k in p_raw)
d_nv = max(float(np.abs(p_raw[k] - p_qnv[k]).max()) for k in p_raw)
print(json.dumps({"steps": steps,
                  "loss_fp32": [round(x, 6) for x in l_raw],
                  "loss_quant_ef": [round(x, 6) for x in l_qef],
                  "max_param_delta_ef": d_ef,
                  "max_param_delta_naive": d_nv}), flush=True)
"""

_COLL_TRAIN_CHILD = r"""
import json, subprocess, sys, tempfile, time
sys.path.insert(0, ROOT)
from brpc_tpu.fleet import RegistryHub
from brpc_tpu.observability import health

n, steps = int(sys.argv[1]), int(sys.argv[2])
health.start_watchdog(tempfile.mkdtemp(prefix="coll_train_dumps_"))
MEMBER = "ROOT = %r\n%s" % (ROOT, MEMBER_SRC)
hub = RegistryHub()
hub.start()
try:
    procs = [subprocess.Popen(
        [sys.executable, "-c", MEMBER, hub.hostport, str(n), str(steps)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for _ in range(n)]
    docs = []
    try:
        for p in procs:
            so, se = p.communicate(timeout=420)
            if p.returncode != 0 or not so.strip():
                sys.stderr.write(se[-1500:])
                raise RuntimeError("collective train member failed")
            docs.append(json.loads(so.strip().splitlines()[-1]))
    finally:
        for p in procs:  # never orphan ring mates (see _COLL_CHILD)
            if p.poll() is None:
                p.kill()
    d = docs[0]
    d["members"] = n
    d["tolerance"] = 5e-2
    d["ef_within_tolerance"] = bool(d["max_param_delta_ef"] < 5e-2)
    d["naive_vs_ef"] = round(d["max_param_delta_naive"]
                             / max(d["max_param_delta_ef"], 1e-12), 1)
    print(json.dumps(d))
finally:
    hub.stop()
"""


def collective_converge_point(n=2, steps=6, timeout=600):
    """Training-trajectory parity: quantized-EF allreduce vs the fp32
    reduction on the LayeredMLP loop (documented 5e-2 tolerance), naive
    requantizer reported beside it as the negative control."""
    code = ("ROOT = %r\nMEMBER_SRC = %r\n%s"
            % (os.path.dirname(os.path.abspath(__file__)),
               _COLL_TRAIN_MEMBER, _COLL_TRAIN_CHILD))
    for attempt in (0, 1):  # host-pressure retry, see collective_point
        proc = _run_child(  # tpulint: allow(py-blocking)
            [sys.executable, "-c", code, str(n), str(steps)],
            capture_output=True, timeout=timeout, text=True)
        if proc.returncode == 0 and proc.stdout.strip():
            break
        sys.stderr.write(proc.stderr[-2000:] if proc.stderr else "")
    if proc.returncode != 0 or not proc.stdout.strip():
        raise RuntimeError(
            f"collective converge child failed rc={proc.returncode}")
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    print(f"# collective_converge: EF delta "
          f"{row['max_param_delta_ef']:.2e} (tol 5e-2, ok="
          f"{row['ef_within_tolerance']}), naive "
          f"{row['max_param_delta_naive']:.2e} "
          f"({row['naive_vs_ef']}x worse)", file=sys.stderr)
    return {"collective_converge": row}


# Parallelism-regime rows (ISSUE 20): steps/s per regime on the SAME
# model config — DP (ring-allreduce driver), PP (1F1B stages over
# WirePipe), TP (column/row-sharded layers over the collective verbs),
# PP x DP (stage pipes + per-stage DP rings) — one member PROCESS per
# rank, serial-vs-overlap interleaved pairs where the regime has a
# schedule to overlap, plus the T3 track-and-trigger A/B (per-chunk
# optimizer trigger vs op-completion fusion: exposed wire wait). Wire-
# bound config: every link paced to emu_gbps (the collective_point
# discipline — loopback shm moves bytes at memcpy speed, which no
# cross-host link does). argv: hub regime rank n steps reps emu
_REGIME_MEMBER = r"""
import json, sys, tempfile, time
sys.path.insert(0, ROOT)
sys.setswitchinterval(0.0005)
import numpy as np
from brpc_tpu.observability import health

hub, regime, rank, n, steps, reps, emu = (
    sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]), float(sys.argv[7]))
health.start_watchdog(tempfile.mkdtemp(prefix="regime_dumps_"))
SIZES = [128, 512, 512, 128]
BATCH = 16
MICRO = 4

from brpc_tpu.models.tensor_service import LayeredMLP

_full = LayeredMLP(SIZES, seed=0)


def group(tag, expect):
    from brpc_tpu.collectives.group import CollectiveGroup
    kw = dict(window=8, op_timeout_s=120.0)
    if emu > 0:
        kw["emulate_wire_gbps"] = emu
    g = CollectiveGroup(hub, tag=tag, **kw)
    g.sync(expect=expect, timeout_s=60)
    return g


def timed(step_fn):
    step_fn()  # warmup: channels + jit
    t0 = time.monotonic()
    for _ in range(steps):
        step_fn()
    return steps / (time.monotonic() - t0)


out = {}
if regime == "dp":
    from brpc_tpu.runtime.step_driver import CollectiveStepDriver
    x, y = _full.data(BATCH, seed=1 + rank)
    out = {"overlap": [], "serial": []}
    for rep in range(reps):
        for mode in ("overlap", "serial"):  # interleaved pair
            g = group("dp_%s%d" % (mode, rep), n)
            d = CollectiveStepDriver(g, LayeredMLP(SIZES, seed=0),
                                     overlap=(mode == "overlap"))
            d.prime()
            out[mode].append(timed(lambda: d.step(x, y)))
            g.close()
        for mode in ("op", "track"):  # T3 A/B, same discipline
            g = group("t3_%s%d" % (mode, rep), n)
            d = CollectiveStepDriver(g, LayeredMLP(SIZES, seed=0),
                                     overlap=True,
                                     track=(mode == "track"))
            d.prime()
            d.step(x, y)
            stall, join, wall = [], [], []
            for _ in range(steps):
                d.step(x, y)
                tr = d.last_trace
                stall.append(tr.exposed_stall_s)
                join.append(tr.exposed_join_s)
                wall.append(tr.wall_s)
            for key, xs in (("stall", stall), ("join", join),
                            ("wall", wall)):
                xs.sort()
                out.setdefault("%s_%s_ms" % (mode, key), []).append(
                    xs[len(xs) // 2] * 1e3)
            g.close()
elif regime == "tp":
    from brpc_tpu.models.tp_layers import TPShardedMLP
    params = {k: np.asarray(v, np.float32)
              for k, v in _full.init_params().items()}
    x, y = _full.data(BATCH, seed=1)
    x, y = np.asarray(x), np.asarray(y)
    out = {"tp": []}
    for rep in range(reps):
        g = group("tp%d" % rep, n)
        tp = TPShardedMLP(SIZES, g, params)
        out["tp"].append(timed(lambda: tp.train_step(x, y)))
        g.close()
elif regime in ("pp", "ppdp"):
    from brpc_tpu.models.pipeline import StagedMLP
    from brpc_tpu.runtime.pp_sched import PipelineStageDriver, WirePipe
    dp = 2 if regime == "ppdp" else 1
    stages = n // dp
    stage, replica = rank % stages, rank // stages
    x, y = _full.data(BATCH, seed=1 + replica)
    x, y = np.asarray(x), np.asarray(y)
    kw = {}
    if stage == 0:
        kw["x"] = x
    if stage == stages - 1:
        kw["y"] = y
    out = {"overlap": [], "serial": []}
    for rep in range(reps):
        for mode in ("overlap", "serial"):  # interleaved pair
            pipe = WirePipe(hub, stage, stages,
                            tag="%s_%s%d_r%d" % (regime, mode, rep,
                                                 replica),
                            emulate_wire_gbps=emu if emu > 0 else None)
            pipe.sync(timeout_s=60)
            dpg = group("%sg_%s%d_s%d" % (regime, mode, rep, stage),
                        dp) if dp > 1 else None
            drv = PipelineStageDriver(
                stage, stages, StagedMLP(SIZES, stage, stages, seed=0),
                pipe, microbatches=MICRO, overlap=(mode == "overlap"),
                dp_group=dpg)
            out[mode].append(timed(lambda: drv.step(**kw)))
            if dpg is not None:
                dpg.close()
            pipe.close()
print(json.dumps({"rank": rank, "rows": out}), flush=True)
"""

_REGIME_CHILD = r"""
import json, statistics, subprocess, sys, tempfile
sys.path.insert(0, ROOT)
from brpc_tpu.fleet import RegistryHub
from brpc_tpu.observability import health

regime, n, steps, reps, emu = (sys.argv[1], int(sys.argv[2]),
                               int(sys.argv[3]), int(sys.argv[4]),
                               float(sys.argv[5]))
health.start_watchdog(tempfile.mkdtemp(prefix="regime_dumps_"))
MEMBER = "ROOT = %r\n%s" % (ROOT, MEMBER_SRC)
hub = RegistryHub()
hub.start()
try:
    procs = [subprocess.Popen(
        [sys.executable, "-c", MEMBER, hub.hostport, regime, str(r),
         str(n), str(steps), str(reps), str(emu)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for r in range(n)]
    docs = []
    try:
        for p in procs:
            so, se = p.communicate(timeout=540)
            if p.returncode != 0 or not so.strip():
                sys.stderr.write(se[-1500:])
                raise RuntimeError("regime member failed")
            docs.append(json.loads(so.strip().splitlines()[-1]))
    finally:
        for p in procs:  # never orphan ring/pipe mates
            if p.poll() is None:
                p.kill()
    rows = [d for d in docs if d["rank"] == 0][0]["rows"]
    row = {"members": n, "steps": steps, "reps": reps}
    if emu > 0:
        row["emulated_wire_gbps"] = emu
    if "overlap" in rows:
        ratios = sorted(o / s for o, s in zip(rows["overlap"],
                                              rows["serial"]))
        row.update({
            "overlap_sps": round(statistics.median(rows["overlap"]), 2),
            "serial_sps": round(statistics.median(rows["serial"]), 2),
            "overlap_vs_serial": round(statistics.median(ratios), 2),
            "overlap_vs_serial_samples": [round(r, 2) for r in ratios]})
    if "tp" in rows:
        row["sps"] = round(statistics.median(rows["tp"]), 2)
    if "op_stall_ms" in rows:
        # The T3 delta: the per-chunk trigger removes the mid-step
        # op-completion STALLS (compute waiting on whole-tensor
        # reductions before each opt node); the join tail and wall are
        # published beside it — the honest full picture.
        # Stall as a DELTA, not a ratio: track-mode stall is ~0 by
        # construction (no compute node ever waits on the wire), so a
        # ratio just divides by noise.
        cuts = sorted(o - t for o, t in zip(rows["op_stall_ms"],
                                            rows["track_stall_ms"]))
        walls = sorted(o / t for o, t in zip(rows["op_wall_ms"],
                                             rows["track_wall_ms"]))
        row["t3"] = {
            "op_stall_ms": round(statistics.median(rows["op_stall_ms"]),
                                 2),
            "track_stall_ms": round(
                statistics.median(rows["track_stall_ms"]), 2),
            "op_join_ms": round(statistics.median(rows["op_join_ms"]),
                                2),
            "track_join_ms": round(
                statistics.median(rows["track_join_ms"]), 2),
            "op_wall_ms": round(statistics.median(rows["op_wall_ms"]),
                                2),
            "track_wall_ms": round(
                statistics.median(rows["track_wall_ms"]), 2),
            "stall_cut_ms": round(statistics.median(cuts), 2),
            "op_vs_track_wall": round(statistics.median(walls), 2),
            "op_vs_track_wall_samples": [round(r, 2) for r in walls]}
    print(json.dumps(row))
finally:
    hub.stop()
"""


def train_regime_point(steps=4, reps=3, emu_gbps=0.125, timeout=600,
                       regimes=(("dp", 2), ("pp", 2), ("tp", 2),
                                ("ppdp", 4))):
    """steps/s per parallelism regime on one wire-bound model config,
    serial-vs-overlap pairs where the regime schedules a graph, plus the
    T3 exposed-wait A/B inside the DP row."""
    out = {}
    for regime, n in regimes:
        code = ("ROOT = %r\nMEMBER_SRC = %r\n%s"
                % (os.path.dirname(os.path.abspath(__file__)),
                   _REGIME_MEMBER, _REGIME_CHILD))
        argv = [sys.executable, "-c", code, regime, str(n), str(steps),
                str(reps), str(emu_gbps)]
        for attempt in (0, 1):  # host-pressure retry, see collective_point
            proc = _run_child(  # tpulint: allow(py-blocking)
                argv, capture_output=True, timeout=timeout, text=True)
            if proc.returncode == 0 and proc.stdout.strip():
                break
            sys.stderr.write(proc.stderr[-2000:] if proc.stderr else "")
        if proc.returncode != 0 or not proc.stdout.strip():
            raise RuntimeError(
                f"regime child {regime} failed rc={proc.returncode}")
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        key = {"dp": "dp2", "pp": "pp2", "tp": "tp2",
               "ppdp": "pp2xdp2"}[regime]
        t3 = row.pop("t3", None)
        out.setdefault("train_steps_regime", {})[key] = row
        if t3 is not None:
            out["t3_track"] = t3
        msg = ", ".join(f"{k}={v}" for k, v in row.items()
                        if k.endswith("sps") or k == "overlap_vs_serial")
        print(f"# regime {key}: {msg}", file=sys.stderr)
        if t3 is not None:
            print(f"# t3 track-and-trigger: mid-step stall "
                  f"{t3['op_stall_ms']}ms -> {t3['track_stall_ms']}ms, "
                  f"join {t3['op_join_ms']}ms -> {t3['track_join_ms']}ms"
                  f", wall {t3['op_wall_ms']}ms -> {t3['track_wall_ms']}"
                  f"ms ({t3['op_vs_track_wall']}x, samples "
                  f"{t3['op_vs_track_wall_samples']})", file=sys.stderr)
    return out


# Live regime-switch row (ISSUE 20 crown): DP placement -> stage-aligned
# PP placement over real fleet shards via Migrator.switch_regime, with a
# trainer pushing throughout. Reports steps lost (pushes that FAILED —
# the redirect-following client should lose none), the switch duration,
# the per-step latency around it, and post-switch trajectory parity vs
# a local replay of the same grad sequence through the server's own
# update formula. argv: n_tensors size steps_pre steps_post
_REGIME_SWITCH_CHILD = r"""
import json, sys, tempfile, threading, time
sys.path.insert(0, ROOT)
import numpy as np
from brpc_tpu.fleet import (FleetClient, FleetServer, Migrator,
                            RegistryHub)
from brpc_tpu.fleet.migrator import regime_assignment
from brpc_tpu.observability import health

n_t, size, pre, post = (int(sys.argv[1]), int(sys.argv[2]),
                        int(sys.argv[3]), int(sys.argv[4]))
health.start_watchdog(tempfile.mkdtemp(prefix="rswitch_dumps_"))
LR, MU = 0.01, 0.9
names = ["layer%02d" % i for i in range(n_t)]
rng = np.random.default_rng(11)
p0 = {k: rng.standard_normal(size).astype(np.float32) for k in names}
grads = [{k: rng.standard_normal(size).astype(np.float32)
          for k in names} for _ in range(pre + post)]

hub = RegistryHub()
hub.start()
shards = []
try:
    for i in range(2):
        s = FleetServer(hub.hostport, tag="rswitch",
                        shard_name="rswitch_s%d" % i, ttl_s=2)
        s.start()
        shards.append(s)
    fc = FleetClient(hub.hostport, tag="rswitch", op_deadline_s=30.0)
    mig = Migrator(hub.hostport, tag="rswitch", window=4)
    for k in names:
        fc.install(k, p0[k])

    step_ms, lost = [], 0
    def train_step(s):
        global lost
        t0 = time.monotonic()
        for k in names:
            try:
                fc.push_grad(k, grads[s][k])
            except Exception:
                lost += 1
                return
        step_ms.append((time.monotonic() - t0) * 1e3)

    for s in range(pre):
        train_step(s)

    sw = {}
    def do_switch():
        asg = regime_assignment(names, [shards[0].addr, shards[1].addr])
        t0 = time.monotonic()
        sw["moved"] = mig.switch_regime(asg)
        sw["ms"] = (time.monotonic() - t0) * 1e3
        sw["asg"] = asg
    t = threading.Thread(target=do_switch)
    t.start()
    for s in range(pre, pre + post):
        train_step(s)
    t.join()

    # Post-switch placement equals the assignment; parity vs a local
    # replay of every push that LANDED through the server formula.
    meta = fc.meta()
    placed = all(meta[k]["shard"] == sw["asg"][k] for k in names)
    applied = len(step_ms)
    m = {k: np.zeros(size, np.float32) for k in names}
    p = {k: p0[k].copy() for k in names}
    for s in range(applied):
        for k in names:
            m[k] = MU * m[k] + grads[s][k]
            p[k] = p[k] - LR * m[k]
    delta = 0.0
    for k in names:
        _ver, arr = fc.pull(k)
        delta = max(delta, float(np.abs(np.asarray(arr) - p[k]).max()))
    pre_ms = sorted(step_ms[:pre])
    post_ms = sorted(step_ms[pre:])
    print(json.dumps({
        "tensors": n_t, "tensor_bytes": size * 4,
        "steps": pre + post, "steps_lost": lost,
        "switch_ms": round(sw["ms"], 1), "moved": sw["moved"],
        "placement_converged": bool(placed),
        "step_ms_before": round(pre_ms[len(pre_ms) // 2], 1),
        "step_ms_during_after": round(post_ms[len(post_ms) // 2], 1),
        "parity_max_delta": delta,
        "parity_ok": bool(delta < 1e-4)}))
    mig.stop()
    fc.close()
finally:
    for s in shards:
        s.stop()
    hub.stop()
"""


def regime_switch_point(n_tensors=8, nbytes=256 << 10, steps_pre=4,
                        steps_post=8, timeout=300):
    """Live DP -> PP ownership switch under push load: steps lost,
    switch duration, per-step latency impact, post-switch parity."""
    code = "ROOT = %r\n%s" % (
        os.path.dirname(os.path.abspath(__file__)),
        _REGIME_SWITCH_CHILD)
    argv = [sys.executable, "-c", code, str(n_tensors),
            str(nbytes // 4), str(steps_pre), str(steps_post)]
    for attempt in (0, 1):  # host-pressure retry, see collective_point
        proc = _run_child(  # tpulint: allow(py-blocking)
            argv, capture_output=True, timeout=timeout, text=True)
        if proc.returncode == 0 and proc.stdout.strip():
            break
        sys.stderr.write(proc.stderr[-2000:] if proc.stderr else "")
    if proc.returncode != 0 or not proc.stdout.strip():
        raise RuntimeError(
            f"regime switch child failed rc={proc.returncode}")
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    print(f"# regime_switch: {row['moved']} tensors moved in "
          f"{row['switch_ms']}ms, {row['steps_lost']} steps lost, "
          f"step {row['step_ms_before']}ms -> "
          f"{row['step_ms_during_after']}ms, parity delta "
          f"{row['parity_max_delta']:.2e} (ok={row['parity_ok']})",
          file=sys.stderr)
    return {"regime_switch": row}


def smoke() -> None:
    """`make bench-smoke`: a <=10s-scale sanity sweep — one subprocess-
    guarded 64B echo sample plus a 4x1MB pipelined pull point — usable as
    a local perf smoke test that cannot wedge the calling terminal."""
    wedges = []
    out = {"echo_64B": bench_echo_ex_guarded(64, 1, 2, "tpu", "single",
                                             retries=1, wedge_log=wedges)}
    # Fast-path rot guard: one interleaved batched-vs-per-message 64B pair
    # — if the batch dispatcher stops batching (or starts losing to the
    # seed path by a wide margin), the smoke row shows it immediately.
    try:
        out["rpc_small_qps_64B"] = small_rpc_point(
            64, reps=1, seconds=1, concurrency=8, wedge_log=wedges)
    except Exception as e:  # noqa: BLE001 - record, don't hang/crash
        out["rpc_small_qps_64B"] = {"error": str(e)}
    try:
        out.update(param_pipeline_point(n_tensors=4, window=4, reps=1,
                                        pull_only=True, timeout=90))
    except Exception as e:  # noqa: BLE001 - record, don't hang/crash
        out["param_pull_all_4x1MB"] = {"error": str(e)}
    # Guarded quant row: one raw-vs-int8 pull pair — if negotiation or the
    # codec path breaks (or the effective-bandwidth win evaporates), the
    # smoke run shows it before the full sweep would.
    try:
        out.update(param_quant_point(n_tensors=4, window=4, reps=1,
                                     pull_only=True, timeout=120))
    except Exception as e:  # noqa: BLE001 - record, don't hang/crash
        out["param_pull_all_quant_4x1MB"] = {"error": str(e)}
    # Guarded 2-shard fleet row: a quick 1-vs-2-shard aggregate pull pair
    # — if scatter/gather stops scaling (or the fleet path breaks), the
    # smoke run shows it before the full sweep would.
    try:
        out.update(fleet_point(counts=(1, 2), n_tensors=8,
                               nbytes=512 << 10, reps=1, do_kill=False,
                               timeout=150))
    except Exception as e:  # noqa: BLE001 - record, don't hang/crash
        out["fleet_pull_GBps_2s"] = {"error": str(e)}
    # Guarded one-sided mini-row: one 4KB one-sided-vs-RPC pull pair —
    # if the mapping handshake, the seqlock read path, or the fallback
    # parity breaks, the smoke run shows it before the full sweep would.
    try:
        out.update(oneside_pull_point(
            reps=1, timeout=120,
            sizes=[[4096, "oneside_pull_4KB", 100]]))
    except Exception as e:  # noqa: BLE001 - record, don't hang/crash
        out["oneside_pull_4KB"] = {"error": str(e)}
    # Guarded step-overlap mini-row: a 3-step overlapped-vs-serial drive
    # — if the scheduled step stops overlapping (or the driver breaks),
    # the smoke run shows it before the full sweep would.
    try:
        out.update(step_overlap_point(n_layers=4, dim=256, batch=8,
                                      steps=3, reps=1, timeout=150))
    except Exception as e:  # noqa: BLE001 - record, don't hang/crash
        out["step_overlap"] = {"error": str(e)}
    # Guarded overload mini-row: a short protection-on/off A/B — if the
    # priority lanes stop protecting the control plane (HIGH p99 no longer
    # flat under bulk saturation), the smoke run shows it first.
    try:
        out["overload_10x"] = overload_point(drive_s=0.6, wedge_log=wedges)
    except Exception as e:  # noqa: BLE001 - record, don't hang/crash
        out["overload_10x"] = {"error": str(e)}
    # Guarded serving mini-row: a short streamed-session TTFT/tokens-s
    # A/B — if token streaming, continuous batching, or the session
    # quota shed breaks, the smoke run shows it before the full sweep.
    try:
        out["serving_stream"] = serving_point(n_tok=16, drive_s=0.6,
                                              flood_threads=4,
                                              wedge_log=wedges)
    except Exception as e:  # noqa: BLE001 - record, don't hang/crash
        out["serving_stream"] = {"error": str(e)}
    # Guarded collective mini-row: one 2-member 4MB raw-vs-int8 ring
    # allreduce pair — if the ring schedule, the per-hop codec, or the
    # member wiring breaks, the smoke run shows it before the full
    # sweep would (wedges become watchdog dumps in the child).
    try:
        out.update(collective_point(counts=(2,), nbytes=4 << 20, reps=1,
                                    timeout=240))
    except Exception as e:  # noqa: BLE001 - record, don't hang/crash
        out["allreduce_GBps_2s"] = {"error": str(e)}
    # Guarded regime mini-row: one 2-stage 1F1B overlap-vs-serial pair
    # over the real wire pipe — if the stage graph, the pipe transport,
    # or the microbatch grad math breaks, the smoke run shows it before
    # the full sweep would.
    try:
        out.update(train_regime_point(steps=2, reps=1, emu_gbps=0.0,
                                      timeout=240,
                                      regimes=(("pp", 2),)))
    except Exception as e:  # noqa: BLE001 - record, don't hang/crash
        out["train_steps_regime"] = {"error": str(e)}
    # Guarded spec-decode mini-row: one single-server spec-on/off pair
    # per workload (no fleet) — if the verify window, the acceptance
    # walk, or the k-adaptation regresses the serving hot path, the
    # smoke run shows it before the full sweep would.
    try:
        out["serving_spec"] = serving_spec_point(
            reps=1, drive_s=0.6, fleet=False, wedge_log=wedges)
    except Exception as e:  # noqa: BLE001 - record, don't hang/crash
        out["serving_spec"] = {"error": str(e)}
    # Guarded paged-KV mini-row: one short density + throughput A/B —
    # if the block pool, the prefix cache, or the paged gather regresses
    # admission density or the decode hot path, the smoke run shows it
    # before the full sweep would.
    try:
        out["serving_paged"] = serving_paged_point(
            reps=1, drive_s=0.5, wedge_log=wedges)
    except Exception as e:  # noqa: BLE001 - record, don't hang/crash
        out["serving_paged"] = {"error": str(e)}
    # Guarded serving-fleet mini-row: one 2-member drain-migration drive
    # (2 mid-stream sessions) — if session routing, the KV ship path, or
    # the resume replay breaks token parity, the smoke run shows it
    # before the full sweep would.
    try:
        out["serving_fleet_drain"] = serving_drain_point(
            n_tok=16, streams=2, wedge_log=wedges)
    except Exception as e:  # noqa: BLE001 - record, don't hang/crash
        out["serving_fleet_drain"] = {"error": str(e)}
    if wedges:
        out["wedged_samples"] = wedges
    print(json.dumps({"metric": "bench_smoke", "sweep": out}))


def recorder_snapshot():
    """Framework-recorder rows for the BENCH json.

    rpc_client_* come from the native GlobalRpcMetrics LatencyRecorder —
    since the echo loops moved into watchdogged subprocesses it reflects
    THIS process's tensor-bridge traffic only (each echo child reports its
    own rpc_client snapshot in its sample); tensor_push/tensor_pull are
    the Python data-plane recorders brpc_tpu/runtime/tensor.py records
    into. All values are microseconds from the recorders' trailing
    window, NOT a re-measurement.
    """
    from brpc_tpu.observability import metrics as obs

    out = {}
    # Native client-side recorder: read through the exposed-vars registry
    # (the handle lives in C); same numbers /vars serves.
    rpc_client = {}
    for line in obs.dump_vars("rpc_client").splitlines():
        name, _, value = line.partition(" : ")
        rpc_client[name.strip()] = value.strip()
    if rpc_client.get("rpc_client_count", "0") != "0":
        out["rpc_client"] = {
            "count": int(rpc_client["rpc_client_count"]),
            "avg_us": int(rpc_client["rpc_client_latency"]),
            "p50_us": int(rpc_client["rpc_client_latency_50"]),
            "p99_us": int(rpc_client["rpc_client_latency_99"]),
            "max_us": int(rpc_client["rpc_client_max_latency"]),
        }
    # Python data-plane recorders (zeros mean the tensor rows were skipped).
    for key in ("tensor_push", "tensor_pull"):
        rec = obs.latency(key)
        if rec.count() > 0:
            out[key] = rec.snapshot()
    for name, label in (("tensor_push_bytes", "push_bytes"),
                        ("tensor_pull_bytes", "pull_bytes"),
                        ("tensor_arena_wait_stalls", "arena_wait_stalls")):
        out[label] = obs.counter(name).value()
    print(f"# framework recorders: {json.dumps(out)}", file=sys.stderr)
    return out


def tensor_bridge_point():
    """Tensor-on-the-wire rows: arrays crossing the framework through
    registered TensorArena memory (by-reference over tpu://).

    Host rows time the pure wire path (numpy push: one staging memcpy into
    the arena, a doorbell ref, the handler reading the pages in place).
    The device row times a parameter-server Pull with a real jax.Array on
    each end (server D2H into its arena, client device_put from the shared
    pages) and reports the MARGINAL GB/s between 1MB and 16MB — the delta
    cancels any size-independent per-op floor (same method as
    ring_attention_point).
    """
    import time

    import numpy as np
    import jax
    import jax.numpy as jnp

    from brpc_tpu.runtime import native as nat
    from brpc_tpu.runtime.tensor import (TensorArena, TensorChannel,
                                         add_tensor_service)

    server = nat.Server()
    state = {}

    def handler(method, request, att):
        if method == "Pull":
            return b"", state["arr"]
        return b"", None  # Sink: the view IS the delivery; nothing to do

    srv_arena = add_tensor_service(server, "Bench", handler)
    port = server.start("127.0.0.1:0")
    ch = TensorChannel(f"tpu://127.0.0.1:{port}", TensorArena(256 << 20))
    out = {}
    try:
        for nbytes, key in ((1 << 20, "tensor_host_1MB"),
                            (16 << 20, "tensor_host_16MB")):
            arr = np.ones(nbytes // 4, np.float32)
            ch.push_device("Bench/Sink", arr)  # warm: allocator + announce
            iters = max(4, (256 << 20) // nbytes)
            t0 = time.monotonic()
            for _ in range(iters):
                ch.push_device("Bench/Sink", arr)
            dt = time.monotonic() - t0
            gbps = nbytes * iters / dt / 1e9
            out[key] = {"gbps": round(gbps, 3), "iters": iters}
            print(f"# {key}: {gbps:.3f} GB/s ({iters} pushes)",
                  file=sys.stderr)

        dev = jax.devices()[0]

        def per_op(nbytes):
            state["arr"] = jnp.ones((nbytes // 4,), jnp.float32)
            jax.block_until_ready(state["arr"])
            ch.pull_device("Bench/Pull")  # warm/compile
            samples = []
            for _ in range(5):
                t0 = time.monotonic()
                ch.pull_device("Bench/Pull")
                samples.append(time.monotonic() - t0)
            samples.sort()
            return samples[len(samples) // 2]

        t1, t16 = per_op(1 << 20), per_op(16 << 20)
        print(f"# tensor_pull_device ({dev.platform}): 1MB {t1 * 1e3:.1f}ms,"
              f" 16MB {t16 * 1e3:.1f}ms", file=sys.stderr)
        row = {"platform": dev.platform, "ms_1MB": round(t1 * 1e3, 2),
               "ms_16MB": round(t16 * 1e3, 2)}
        # Same noise-floor discipline as ring_attention_point: a delta in
        # the jitter band publishes garbage — omit the rate instead.
        if t16 - t1 > 0.25 * t1:
            row["marginal_gbps"] = round((15 << 20) / (t16 - t1) / 1e9, 3)
        out["tensor_pull_device"] = row
    finally:
        ch.close()
        server.stop()
    return out


def ring_attention_point():
    """Sustained attention TFLOP/s via the DELTA method.

    Chain K dependent attention applications inside ONE jit (lax.scan
    whose carry feeds the next q — nothing can be elided), force
    materialization with a scalar readback, and report the MARGINAL rate
    between a small-K and large-K run — the fixed dispatch and readback
    cost cancels out.

    The op is the Pallas flash kernel (block-tiled online softmax in VMEM,
    multi-head) at the LLM shape b=8, h=8, s=4096, d=128 bf16; on the
    1-device mesh the ring degenerates to flash attention with no
    collectives. v5e bf16 peak is 197 TFLOP/s — mfu_pct is against that.
    """
    import time

    import jax
    import jax.numpy as jnp
    from jax import lax

    from brpc_tpu.ops.flash_attention import flash_attention

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    batch, heads, seq, d = (8, 8, 4096, 128) if on_tpu else (1, 2, 256, 32)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    k_small, k_large = (8, 56) if on_tpu else (1, 4)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (batch, heads, seq, d), dtype)
               for kk in keys)

    def timed(K):
        @jax.jit
        def run(q, k, v):
            def body(c, _):
                return flash_attention(c, k, v).astype(dtype), None
            out, _ = lax.scan(body, q, None, length=K)
            return jnp.sum(out.astype(jnp.float32))
        float(run(q, k, v))  # compile + warm
        samples = []
        for _ in range(5):
            t0 = time.monotonic()
            float(run(q, k, v))  # scalar readback forces full compute
            samples.append(time.monotonic() - t0)
        samples.sort()
        return samples[len(samples) // 2]

    t_small, t_large = timed(k_small), timed(k_large)
    flops_per_iter = 4.0 * batch * heads * seq * seq * d  # QK^T + PV
    dt = t_large - t_small
    # A delta that isn't comfortably above the noise floor means the
    # measurement is junk (scheduler jitter inverted it); skip the
    # point (main()'s try/except reports it) rather than publish garbage.
    if dt < 0.25 * t_small:
        raise RuntimeError(
            f"delta timing noise-dominated (K={k_small}: {t_small * 1e3:.1f}ms,"
            f" K={k_large}: {t_large * 1e3:.1f}ms)")
    tflops = (k_large - k_small) * flops_per_iter / dt / 1e12
    ms_per_iter = dt / (k_large - k_small) * 1e3
    # bf16 peak by device generation; unknown kinds get no MFU claim
    # rather than one computed against the wrong denominator.
    peaks = {"v5 lite": 197.0, "v5e": 197.0, "v4": 275.0, "v5p": 459.0,
             "v6 lite": 918.0, "v6e": 918.0}
    kind = getattr(dev, "device_kind", "").lower()
    peak = next((p for k2, p in peaks.items() if k2 in kind), None)
    row = {"tflops": round(tflops, 1), "platform": dev.platform,
           "batch": batch, "heads": heads, "seq": seq, "d": d,
           "ms_per_application": round(ms_per_iter, 3)}
    mfu_str = ""
    if on_tpu and peak:
        row["mfu_pct"] = round(tflops / peak * 100, 1)
        row["peak_tflops"] = peak
        mfu_str = f" = {row['mfu_pct']:.0f}% MFU (peak {peak:.0f})"
    print(f"# flash attention ({dev.platform}): {tflops:.1f} TFLOP/s "
          f"sustained{mfu_str} (b={batch} h={heads} s={seq} d={d} "
          f"{dtype.__name__}, {ms_per_iter:.2f}ms/application, "
          f"delta {k_small}->{k_large})", file=sys.stderr)
    return row


if __name__ == "__main__":
    from brpc_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    if "--smoke" in sys.argv[1:]:
        smoke()
    else:
        main()
