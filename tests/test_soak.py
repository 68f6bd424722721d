"""Watchdog soak: repeated async pull_all/push_all bursts over tpu://
with the stall watchdog armed (`make soak`; slow-marked, so tier-1's
`-m 'not slow'` filter skips it).

The contract under test is the SELF-MONITORING one, not throughput: if
the transport ever wedges during the soak, health must reach `stalled`
WITH a dump artifact on disk — a stall the watchdog cannot explain is the
failure mode this PR exists to eliminate. A clean soak (health never
leaves ok/degraded) passes too; a wedge WITH forensics is a captured
finding, not a test failure.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SERVER_CODE = """
import sys, json
sys.path.insert(0, %r)
import jax.numpy as jnp
from brpc_tpu.runtime.param_server import ParameterServer
params = {'w%%02d' %% i: jnp.ones((%d // 4,), jnp.float32) * i
          for i in range(%d)}
ps = ParameterServer(params)
print(json.dumps({'port': ps.start()}), flush=True)
sys.stdin.readline()
ps.stop()
"""


def test_soak_async_bursts_under_watchdog(tmp_path):
    from conftest import require_native_lib
    require_native_lib()
    from brpc_tpu.observability import health
    from brpc_tpu.runtime.param_server import ParameterClient

    n_tensors, nbytes = 8, 256 * 1024
    budget_s = float(os.environ.get("SOAK_SECONDS", "45"))

    dump_dir = tmp_path / "dumps"
    dump_dir.mkdir()
    health.start_watchdog(str(dump_dir), poll_ms=100, degraded_ms=500,
                          stalled_ms=2000, credit_stall_ms=8000)

    # The ParameterServer lives in its own process (sharing one GIL would
    # serialize client bursts against server handlers and soak the lock,
    # not the wire).
    srv = subprocess.Popen(  # tpulint: allow(py-blocking)
        [sys.executable, "-c", _SERVER_CODE % (ROOT, nbytes, n_tensors)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        port = json.loads(srv.stdout.readline())["port"]
        client = ParameterClient(f"tpu://127.0.0.1:{port}")
        names = sorted(client.meta())
        grads = {n: np.ones(nbytes // 4, np.float32) for n in names}
        state = {"bursts": 0, "stalled": False, "error": None}

        # Bursts run on a WORKER thread: in the hard all-threads-park
        # wedge class even RPC timeouts never fire (the timer thread is
        # parked too), so a burst can block forever — the main thread
        # must keep supervising health or the stall is unobservable and
        # pytest hangs instead of failing.
        def bursts_fn():
            try:
                deadline = time.monotonic() + budget_s
                while time.monotonic() < deadline \
                        and not state["stalled"]:
                    client.pull_all(names, window=4)
                    client.push_all(grads, window=4)
                    state["bursts"] += 1
            except Exception as e:  # noqa: BLE001 — supervisor reports it
                state["error"] = repr(e)

        import threading
        worker = threading.Thread(target=bursts_fn, daemon=True)
        worker.start()
        hard_deadline = time.monotonic() + budget_s + 60
        while worker.is_alive() and time.monotonic() < hard_deadline:
            if health.state() == "stalled":
                state["stalled"] = True
                # THE soak contract: a stall without forensics fails.
                path = health.last_dump_path()
                assert path and os.path.exists(path), (
                    "health reached stalled without a dump artifact: "
                    + json.dumps(health.health()))
                break
            worker.join(timeout=0.5)
        if worker.is_alive() and not state["stalled"]:
            raise AssertionError(
                "soak wedged (bursts stopped) but the watchdog never "
                "reached stalled: " + json.dumps(health.health()))
        if not state["stalled"]:
            client.close()
        assert state["error"] is None, state["error"]
        assert state["bursts"] > 0
        print(f"soak: {state['bursts']} bursts, "
              f"stalled_seen={state['stalled']}, "
              f"dumps={os.listdir(dump_dir)}")
    finally:
        try:
            srv.stdin.close()
            srv.wait(timeout=10)  # tpulint: allow(py-blocking)
        except Exception:  # noqa: BLE001 — soak teardown
            srv.kill()
