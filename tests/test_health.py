"""Flight recorder + stall watchdog (ISSUE 4 acceptance surface).

End-to-end self-monitoring:
  * a deliberately-wedged worker pool (tbrpc_debug_hold_workers blocks
    every fiber worker, the way the historical all-threads-parked wedge
    did) drives the health state machine to `stalled` within the
    configured window, with a reason naming the scheduler;
  * entering `stalled` auto-dumps a timestamped file carrying fiber
    stacks, ICI credit state, and a non-empty flight-recorder tail;
  * releasing the workers recovers health to `ok`, and /healthz serves
    the whole transition history as JSON;
  * the flight recorder decodes from Python (park/unpark + RPC phase
    events for real traffic) and its event-write path takes no lock;
  * recorder overhead on the in-process echo hot path stays within noise
    (< 5% on the C echo microbench, recorder on vs off).
"""

import json
import os
import re
import statistics
import time
import urllib.request

import pytest


@pytest.fixture(scope="module", autouse=True)
def _needs_native():
    from conftest import require_native_lib
    require_native_lib()


@pytest.fixture(scope="module")
def health():
    from brpc_tpu.observability import health
    return health


def _wait_until(pred, timeout_s, what):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out after {timeout_s}s waiting for {what}")


def test_stall_detection_autodump_and_recovery(health, tmp_path):
    """The acceptance walk: ok -> (workers held) -> stalled + auto-dump ->
    (workers released) -> ok, observed from a plain Python thread and then
    via /healthz."""
    from brpc_tpu.runtime import native

    dump_dir = tmp_path / "dumps"
    dump_dir.mkdir()
    server = native.Server()
    server.add_echo_service()
    port = server.start("127.0.0.1:0")
    # A tpu:// call first: the dump's ICI section must show real credit
    # state (free_tx of a live endpoint), and the flight tail real traffic.
    channel = native.Channel(f"tpu://127.0.0.1:{port}", timeout_ms=10000)
    try:
        channel.call("EchoService/Echo", b"m", b"x" * 65536)

        health.start_watchdog(str(dump_dir), poll_ms=50, degraded_ms=200,
                              stalled_ms=600, credit_stall_ms=30000)
        _wait_until(lambda: health.state() == "ok", 5, "watchdog warm-up")

        # /healthz is live JSON while healthy.
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10)
        assert resp.headers.get("Content-Type", "").startswith(
            "application/json")
        doc = json.loads(resp.read())
        assert doc["state"] == "ok" and doc["watchdog_running"] is True

        # Wedge the worker pool. Holder fibers BLOCK their worker pthreads,
        # so the watchdog's probe fiber cannot run anywhere.
        held = native.lib().tbrpc_debug_hold_workers(0, 20000)
        assert held > 0
        try:
            _wait_until(lambda: health.state() == "stalled", 10,
                        "health to reach stalled")
            doc = health.health()
            assert "scheduler" in doc["reason"]
            # The watchdog flips the state first and then writes the dump
            # (fiber stacks symbolized): on a loaded host the file lands
            # a moment after "stalled" reads.
            _wait_until(health.last_dump_path, 10,
                        "the auto-dump of entering stalled")
            path = health.last_dump_path()
            assert path and os.path.exists(path), \
                "entering stalled must auto-dump"
            content = open(path, encoding="utf-8").read()
            # Fiber stacks present (the held workers report as fibers).
            assert "== fibers ==" in content
            assert re.search(r"fiber \d+", content)
            # ICI credit state of the live tpu:// endpoint.
            assert "== ici endpoints ==" in content
            assert "free_tx=" in content
            # Non-empty flight-recorder tail with real events.
            tail = content.split("== flight recorder tail ==", 1)[1]
            assert re.search(r"tid=\d+ seq=\d+", tail)
        finally:
            native.lib().tbrpc_debug_release_workers()

        # Recovery: the probe runs again and health returns to ok.
        _wait_until(lambda: health.state() == "ok", 10, "recovery to ok")
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10).read())
        walked = [(t["from"], t["to"]) for t in doc["transitions"]]
        assert ("ok", "degraded") in walked, walked
        assert ("degraded", "stalled") in walked, walked
        assert walked[-1][1] == "ok", walked
        assert doc["stalls"] >= 1
        assert doc["last_dump_path"]
    finally:
        native.lib().tbrpc_debug_release_workers()
        # The watchdog outlives this test (process-global): widen the
        # windows back to defaults so later CPU-heavy tests in this pytest
        # process can't trip a spurious stall dump.
        health.configure(poll_ms=100, degraded_ms=500, stalled_ms=2000,
                         credit_stall_ms=10000)
        channel.close()
        server.close()


def test_flight_recorder_decodes_real_traffic(health):
    """RPC traffic leaves park/unpark and phase events the Python decoder
    can read back, and /flightz serves the same stream with filters."""
    from brpc_tpu.runtime import native

    server = native.Server()
    server.add_echo_service()
    port = server.start("127.0.0.1:0")
    channel = native.Channel(f"127.0.0.1:{port}", timeout_ms=5000)
    try:
        before = health.flight_total_events()
        for _ in range(3):
            channel.call("EchoService/Echo", b"m", b"payload")
        assert health.flight_total_events() > before

        events = health.flight_events(max_events=2048)
        assert events, "decoder must see events"
        types = {e["type"] for e in events}
        assert "RPC_PHASE" in types
        assert "FIBER_PARK" in types or "FIBER_UNPARK" in types
        for e in events:
            assert e["ts_us"] > 0 and e["seq"] >= 1 and e["tid"] > 0
        phases = {e["phase"] for e in events if e["type"] == "RPC_PHASE"}
        assert {"client_issue", "client_end"} <= phases
        # Server-side phases ride the same correlation id as the wire.
        assert "server_in" in phases and "server_done" in phases

        # /flightz type filter narrows to the asked-for events only.
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/flightz?type=RPC_PHASE&max=10",
            timeout=10).read().decode()
        lines = body.splitlines()
        assert "event(s) shown" in lines[0]
        assert all("RPC_PHASE" in ln for ln in lines[1:])
        assert len(lines) > 1
    finally:
        channel.close()
        server.close()


def test_flight_write_path_takes_no_lock():
    """The recorder's event-write path must stay lock-free: a mutex there
    would (a) cost the hot path and (b) let a crashed/blocked writer hang
    every other recorder. Pinned at the source level — the write path
    lives between explicit markers in flight_recorder.h; the atomics'
    lock-freedom is a static_assert in the same header."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = open(os.path.join(root, "native", "tbvar", "flight_recorder.h"),
               encoding="utf-8").read()
    m = re.search(r"// flight-write-path-begin(.*)// flight-write-path-end",
                  src, re.S)
    assert m, "write-path markers must stay in flight_recorder.h"
    body = m.group(1)
    assert "flight_record" in body
    for token in ("mutex", "lock_guard", "unique_lock", "scoped_lock",
                  "spinlock", "->mu", ".lock("):
        assert token not in body, f"write path must not use {token}"
    assert "is_always_lock_free" in src


def test_flight_recorder_overhead_within_noise(health):
    """Recorder on vs off on the in-process echo microbench: the median
    of ADJACENT-pair on/off ratios (the PERF.md steal-robust statistic —
    a difference of independent medians flakes when this host's bimodal
    steal lands across a 5% bound) must stay within 5%, with a bounded
    window rerun like test_pprof's heap sampling. The recorder's
    per-event cost is a clock read plus a handful of relaxed stores — a
    ratio that fails 3 windows straight means the write path regressed."""
    from brpc_tpu.runtime import native

    def sample(enabled):
        health.configure(flight_enabled=1 if enabled else 0)
        qps, _ = native.bench_echo_qps(seconds=1, concurrency=2)
        return qps

    try:
        sample(True)  # warm: server/channel/fiber pool spin-up
        med = 0.0
        for _window in range(3):
            ratios = []
            for _ in range(3):  # adjacent pairs see the same host state
                off = sample(False)
                on = sample(True)
                assert on > 0 and off > 0
                ratios.append(on / off)
            med = statistics.median(ratios)
            if med >= 0.95:
                break
        assert med >= 0.95, \
            f"recorder overhead over 5% in 3 windows: last ratios={ratios}"
    finally:
        health.configure(flight_enabled=1)


def test_watchdog_config_knobs_reject_garbage(health):
    with pytest.raises(ValueError, match="unknown watchdog knob"):
        health.configure(bogus_knob=1)
    with pytest.raises(ValueError, match="rejected"):
        health.configure(flight_ring_events=7)  # below the native floor
    # In-range values land (readable back through /flags via dump_vars is
    # indirect; the native setter returning 0 is the contract here).
    health.configure(flight_ring_events=4096, poll_ms=100)
