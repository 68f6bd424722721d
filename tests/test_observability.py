"""End-to-end data-plane observability (ISSUE 2 acceptance surface).

One /vars + /brpc_metrics + /rpcz view over native fibers AND the Python
tensor path:
  * Python-registered metrics (counters, latency recorders, passive
    gauges) land in the native tbvar registry and surface at /vars and
    /brpc_metrics with a parseable Prometheus exposition;
  * a Python client -> Python-handler server -> downstream-call chain
    renders as ONE linked trace at /rpcz, with Python-attached stage
    annotations on the server span;
  * RpcError text raised in a Python handler reaches the client;
  * the ParameterServer Meta/Push paths survive concurrent hammering
    (the _handle lock covers Meta's reads);
  * /tensorz summarizes arena occupancy.
"""

import json
import re
import threading
import urllib.request

import numpy as np
import pytest

ANN_RE = re.compile(r"^[\w.]+=\d+us$")


@pytest.fixture(scope="module", autouse=True)
def _needs_native():
    from conftest import require_native_lib
    require_native_lib()


@pytest.fixture(scope="module")
def obs():
    import brpc_tpu.observability as obs
    return obs


def _http(port, path):
    resp = urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                  timeout=15)
    return resp.headers.get("Content-Type", ""), resp.read().decode()


# ---- metrics: registration + exposition surfaces ----

def _parse_prometheus(text):
    """{name: value} for every sample line; asserts exposition grammar."""
    samples = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            if line.startswith("#"):
                assert re.match(r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* \w+$",
                                line), f"bad TYPE line: {line!r}"
            continue
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{[^}]*\})? (\S+)$",
                     line)
        assert m, f"unparseable sample line: {line!r}"
        samples[m.group(1)] = float(m.group(2))
    return samples


def test_python_metrics_reach_vars_and_prometheus(obs):
    c = obs.counter("obs_test_events")
    c.add(7)
    rec = obs.latency("obs_test_stage")
    rec.record_us(1500)
    state = {"v": 33}
    obs.gauge("obs_test_depth", lambda: state["v"])

    vars_text = obs.dump_vars("obs_test")
    assert "obs_test_events : 7" in vars_text
    assert "obs_test_depth : 33" in vars_text
    assert "obs_test_stage_count : 1" in vars_text
    # every facade the native LatencyRecorder bundle exposes, p50 included
    for suffix in ("latency", "max_latency", "qps", "count", "latency_50",
                   "latency_99", "latency_999"):
        assert f"obs_test_stage_{suffix} : " in vars_text

    state["v"] = 44  # passive: the NEXT scrape computes the new value
    samples = _parse_prometheus(obs.dump_prometheus())
    assert samples["obs_test_events"] == 7.0
    assert samples["obs_test_depth"] == 44.0
    assert samples["obs_test_stage_count"] == 1.0
    # (native framework series join this exposition once the first
    # server/channel runs global init — asserted in the /brpc_metrics test)


def test_metric_name_collision_fails_loudly(obs):
    obs.counter("obs_test_taken")
    with pytest.raises(ValueError, match="already registered"):
        obs.Counter("obs_test_taken")  # direct ctor: no get-or-create
    # get-or-create returns the SAME instance instead
    assert obs.counter("obs_test_taken") is obs.counter("obs_test_taken")


def test_exported_names_pass_tpulint_metric_charset(obs):
    """Every name this process exports must satisfy the same rule tpulint
    enforces on source literals — the two checks chase one invariant."""
    from tools.tpulint.rules_metrics import _VALID

    obs.counter("obs_test_charset")
    for line in obs.dump_vars().splitlines():
        name = line.split(" : ")[0].strip()
        assert _VALID.match(name), f"exported name breaks charset: {name!r}"


def test_brpc_metrics_page_content_type_and_parse(obs):
    from brpc_tpu.runtime import native

    obs.counter("obs_test_scraped").add(3)
    server = native.Server()
    server.add_echo_service()
    port = server.start("127.0.0.1:0")
    try:
        ctype, body = _http(port, "/brpc_metrics")
        assert ctype == "text/plain; version=0.0.4"
        samples = _parse_prometheus(body)
        assert samples["obs_test_scraped"] == 3.0
        # native framework series share the same exposition
        assert "process_uptime_seconds" in samples
        # /metrics stays as the alias-free original
        _, body2 = _http(port, "/metrics")
        assert "obs_test_scraped" in body2
    finally:
        server.stop()


# ---- tracing: one linked trace across Python client/server/downstream ----

def test_two_hop_python_trace_links_at_rpcz(obs):
    from brpc_tpu.runtime import native

    obs.rpcz_enable()
    server_b = native.Server()
    server_b.add_echo_service()
    port_b = server_b.start("127.0.0.1:0")
    downstream = native.Channel(f"127.0.0.1:{port_b}", timeout_ms=5000)

    def handler(method, request, attachment):
        # runs on the traced server fiber: stage() annotates the SERVER
        # span, and the downstream call parents on it automatically.
        with obs.stage("fanout"):
            r, ra = downstream.call("EchoService/Echo", request, attachment)
        return r, ra

    server_a = native.Server()
    server_a.add_service("PyHop", handler)
    port_a = server_a.start("127.0.0.1:0")
    ch = native.Channel(f"127.0.0.1:{port_a}", timeout_ms=5000)
    try:
        with obs.trace_span("client_root") as root:
            resp, _ = ch.call("PyHop/Run", b"ping")
        assert resp == b"ping"
        assert root.trace_id != 0

        spans = obs.dump_rpcz(root.trace_id)
        by_method = {}
        for s in spans:
            by_method.setdefault(
                (s["service_method"], s["server_side"]), s)
        # ONE trace: python root, C+S legs of hop 1, C+S legs of hop 2.
        assert {m for m, _ in by_method} == {
            "client_root", "PyHop/Run", "EchoService/Echo"}
        assert len({s["trace_id"] for s in spans}) == 1

        root_span = by_method[("client_root", False)]
        hop1_c = by_method[("PyHop/Run", False)]
        hop1_s = by_method[("PyHop/Run", True)]
        hop2_c = by_method[("EchoService/Echo", False)]
        hop2_s = by_method[("EchoService/Echo", True)]
        assert hop1_c["parent_span_id"] == root_span["span_id"]
        assert hop1_s["parent_span_id"] == hop1_c["span_id"]
        assert hop2_c["parent_span_id"] == hop1_s["span_id"]
        assert hop2_s["parent_span_id"] == hop2_c["span_id"]

        # the Python-attached stage annotation landed on the server span
        anns = hop1_s["annotations"]
        assert any(a.startswith("fanout=") and ANN_RE.match(a)
                   for a in anns), anns

        # /rpcz renders the same linked trace + annotation over HTTP
        _, page = _http(port_a, f"/rpcz?trace={root.trace_id:016x}")
        assert "client_root" in page and "PyHop/Run" in page
        assert "@ fanout=" in page
    finally:
        server_a.stop()
        server_b.stop()
        obs.rpcz_enable(False)


def test_trace_context_get_set_roundtrip(obs):
    t, s = obs.current_trace()
    assert (t, s) == (0, 0)
    obs.tracing.set_trace(0xabc, 0xdef)
    assert obs.current_trace() == (0xabc, 0xdef)
    obs.tracing.clear_trace()
    assert obs.current_trace() == (0, 0)


def test_nested_python_handlers_beyond_pool_target():
    """Python->Python in-process fan-out at concurrency beyond the
    callback-pool's idle target must not deadlock: each blocked handler
    needs a pool thread for its downstream handler too, so the pool grows
    on demand (a hard cap wedges every request until timeout)."""
    from brpc_tpu.runtime import native

    L = native.lib()
    assert L.tbrpc_flag_set(b"python_callback_threads", b"2") == 0
    try:
        inner = native.Server()
        inner.add_service("Inner", lambda m, req, att: (req + b"!", b""))
        inner_port = inner.start("127.0.0.1:0")
        inner_ch = native.Channel(f"127.0.0.1:{inner_port}", timeout_ms=10000)

        def outer_handler(method, request, attachment):
            r, _ = inner_ch.call("Inner/Echo", request)
            return r, b""

        outer = native.Server()
        outer.add_service("Outer", outer_handler)
        outer_port = outer.start("127.0.0.1:0")

        results, errors = [], []

        def client():
            ch = native.Channel(f"127.0.0.1:{outer_port}", timeout_ms=10000)
            try:
                r, _ = ch.call("Outer/Run", b"hi")
                results.append(r)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                ch.close()

        threads = [threading.Thread(target=client) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        assert results == [b"hi!"] * 6
        inner_ch.close()
        inner.close()
        outer.close()
    finally:
        L.tbrpc_flag_set(b"python_callback_threads", b"8")


# ---- error text across the wire ----

def test_rpc_error_text_reaches_client():
    from brpc_tpu.runtime import native

    server = native.Server()

    def failing(method, request, attachment):
        raise native.RpcError(2042, "quota exceeded for " + method)

    def buggy(method, request, attachment):
        raise KeyError("missing_param")

    server.add_service("Failing", failing)
    server.add_service("Buggy", buggy)
    port = server.start("127.0.0.1:0")
    ch = native.Channel(f"127.0.0.1:{port}", timeout_ms=5000)
    try:
        with pytest.raises(native.RpcError) as e:
            ch.call("Failing/M", b"")
        assert e.value.code == 2042
        assert "quota exceeded for M" in e.value.text
        # handler bugs surface the exception type, not a generic 2004 blob
        with pytest.raises(native.RpcError) as e:
            ch.call("Buggy/M", b"")
        assert e.value.code == 2004
        assert "KeyError" in e.value.text and "missing_param" in e.value.text
    finally:
        server.stop()


def test_tensor_handler_error_text_reaches_client():
    from brpc_tpu.runtime import native
    from brpc_tpu.runtime.tensor import TensorArena, TensorChannel, \
        add_tensor_service

    server = native.Server()

    def handler(method, request, att):
        raise native.RpcError(2077, "tensor handler says no")

    add_tensor_service(server, "T", handler)
    port = server.start("127.0.0.1:0")
    ch = TensorChannel(f"tpu://127.0.0.1:{port}", TensorArena(16 << 20))
    try:
        with pytest.raises(native.RpcError) as e:
            ch.call("T/M", np.ones(4, np.float32))
        assert e.value.code == 2077
        assert "tensor handler says no" in e.value.text
    finally:
        ch.close()
        server.stop()


# ---- ParameterServer: Meta race + instrumentation ----

def test_param_server_meta_push_race():
    """Meta reads version+shape+dtype under the same lock Push mutates
    them: hammer both concurrently and require every Meta snapshot to be
    internally consistent (no exception, version within bounds)."""
    import jax.numpy as jnp

    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)

    ps = ParameterServer({"w": jnp.ones((64, 8), jnp.float32)}, lr=0.01)
    port = ps.start()
    n_push = 30
    errors = []

    def pusher():
        client = ParameterClient(f"tpu://127.0.0.1:{port}")
        try:
            g = jnp.full((64, 8), 0.01, jnp.float32)
            for _ in range(n_push):
                client.push_grad("w", g)
        except Exception as e:  # noqa: BLE001
            errors.append(e)
        finally:
            client.close()

    def meta_reader():
        client = ParameterClient(f"tpu://127.0.0.1:{port}")
        try:
            for _ in range(n_push * 2):
                meta = client.meta()
                assert meta["w"]["shape"] == [64, 8]
                assert 0 <= meta["w"]["version"] <= n_push
        except Exception as e:  # noqa: BLE001
            errors.append(e)
        finally:
            client.close()

    threads = [threading.Thread(target=pusher),
               threading.Thread(target=meta_reader),
               threading.Thread(target=meta_reader)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    ps.stop()
    assert not errors, errors


def test_param_server_metrics_recorded():
    import jax.numpy as jnp

    import brpc_tpu.observability as obs
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)

    ps = ParameterServer({"w": jnp.ones((32, 4), jnp.float32)}, lr=0.01)
    port = ps.start()
    client = ParameterClient(f"tpu://127.0.0.1:{port}")
    before_pull = obs.latency("param_server_pull").count()
    before_push = obs.latency("param_server_push").count()
    before_bytes = obs.counter("param_server_push_bytes").value()
    try:
        version, w = client.pull("w")
        assert version == 0
        client.push_grad("w", jnp.zeros((32, 4), jnp.float32))
    finally:
        client.close()
        ps.stop()
    assert obs.latency("param_server_pull").count() == before_pull + 1
    assert obs.latency("param_server_push").count() == before_push + 1
    assert (obs.counter("param_server_push_bytes").value()
            == before_bytes + 32 * 4 * 4)
    # tensor-path recorders fed by pull_device/push_device under the hood
    assert obs.latency("tensor_pull").count() > 0
    assert obs.latency("tensor_push").count() > 0
    # ... and visible on BOTH exposition surfaces (acceptance: /vars and
    # /brpc_metrics carry the Python data-plane series)
    vars_text = obs.dump_vars()
    prom = _parse_prometheus(obs.dump_prometheus())
    for name in ("tensor_pull_latency", "tensor_push_latency",
                 "tensor_arena_busy_bytes", "param_server_pull_latency",
                 "param_server_push_bytes", "param_server_version_lag"):
        assert f"{name} : " in vars_text, name
        assert name in prom, name


# ---- the split of one call: stages on the server leg and the client ----

SERVER_STAGES = ("pool_wait", "gil_enter", "handler", "respond_stage")


def _stages(span):
    out = {}
    for a in span["annotations"]:
        name, _, us = a.partition("=")
        if ANN_RE.match(a):
            out[name] = int(us[:-2])
    return out


def test_traced_pull_and_push_split_the_server_leg(obs):
    """The server leg of a traced Pull and Push carries the callback
    pool's wait, the GIL wait into the callback, the handler and the
    response staging as sequential stages (summing to no more than the
    leg), the Push its update_dispatch, and the client's root the GIL
    wait after the call returned."""
    import jax.numpy as jnp

    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)

    ps = ParameterServer({"w": jnp.ones((32, 4), jnp.float32)}, lr=0.01)
    port = ps.start()
    client = ParameterClient(f"tpu://127.0.0.1:{port}")
    client.meta()
    obs.rpcz_enable()
    try:
        roots = {}
        for kind in ("pull", "push"):
            with obs.trace_span(f"split.{kind}") as root:
                if kind == "pull":
                    client.pull("w")
                else:
                    client.push_grad("w", jnp.ones((32, 4), jnp.float32))
            roots[kind] = root.trace_id
        for kind, trace_id in roots.items():
            spans = obs.dump_rpcz(trace_id)
            leg = next(s for s in spans if s["server_side"])
            root = next(s for s in spans
                        if s["service_method"] == f"split.{kind}")
            stages = _stages(leg)
            assert set(SERVER_STAGES) <= set(stages), leg["annotations"]
            assert sum(stages[k] for k in SERVER_STAGES) <= \
                leg["latency_us"], leg
            if kind == "push":
                assert "update_dispatch" in stages
                assert stages["update_dispatch"] <= stages["handler"]
            assert "gil_return" in _stages(root), root["annotations"]
            assert "rpc" in _stages(root)
            # the server leg lies inside the client's rpc stage
            assert leg["latency_us"] <= _stages(root)["rpc"] + 1
    finally:
        obs.rpcz_enable(False)
        client.close()
        ps.stop()


class _CountingLib:
    """A library handle that counts every foreign call made through it."""

    def __init__(self, real):
        self._real = real
        self.calls = []

    def __getattr__(self, name):
        fn = getattr(self._real, name)

        def counted(*args):
            self.calls.append(name)
            return fn(*args)

        return counted


def test_tracing_off_makes_no_foreign_call(obs, monkeypatch):
    """With rpcz off, the per-call tracing entry points decide from the
    flag's storage alone: no call through either library handle."""
    from brpc_tpu.observability import tracing
    from brpc_tpu.runtime import native

    obs.rpcz_enable(False)
    assert not tracing.rpcz_enabled()  # binds the flag's storage
    cdll = _CountingLib(native.lib())
    pydll = _CountingLib(native.pylib())
    monkeypatch.setattr(native, "_lib", cdll)
    monkeypatch.setattr(native, "_pylib", pydll)
    for _ in range(3):
        with tracing.stage("off"):
            pass
        tracing.annotate("off=1us")
        with tracing.trace_span("off") as h:
            assert h.trace_id == 0
        with tracing.serving():
            pass
        tracing.gil_returned()
    assert cdll.calls == [] and pydll.calls == []
    # the same handles do carry the calls once rpcz is on
    monkeypatch.undo()
    obs.rpcz_enable()
    try:
        monkeypatch.setattr(native, "_lib", cdll)
        monkeypatch.setattr(native, "_pylib", pydll)
        with tracing.trace_span("on"):
            with tracing.stage("on"):
                pass
        assert cdll.calls == []
        assert {"tbrpc_span_annotate", "tbrpc_span_emit",
                "tbrpc_now_us"} <= set(pydll.calls)
    finally:
        monkeypatch.undo()
        obs.rpcz_enable(False)


# Foreign calls of one raw Pull and one raw Push of an 8 KB tensor with
# rpcz off, client and server legs together, per handle. The CDLL handle
# (which hands the GIL off) carries only the RPC itself and the view
# release (its drop may write a release frame inline; see pylib()).
CENSUS = {
    "pull": {
        "cdll": {"tbrpc_call_tensor": 1, "tbrpc_view_free": 1},
        # client: QoS scope (get, set, restore), response free, the
        # tensor_pull recorder and byte counter; server: the handler's
        # recorder, the response's arena range and header copy, the
        # trampoline's recorder.
        "pydll": {"tbrpc_qos_get": 1, "tbrpc_qos_set": 2, "tbrpc_free": 1,
                  "tbrpc_var_latency_record": 3, "tbrpc_var_adder_add": 1,
                  "tbrpc_arena_alloc": 1, "tbrpc_dup": 1},
    },
    "push": {
        "cdll": {"tbrpc_call_tensor": 1},
        # client: QoS scope, the staged range's alloc and free, response
        # free, recorder and counter; server: recorder and counter, the
        # header copy, the trampoline's recorder.
        "pydll": {"tbrpc_qos_get": 1, "tbrpc_qos_set": 2,
                  "tbrpc_arena_alloc": 1, "tbrpc_arena_free": 1,
                  "tbrpc_free": 1, "tbrpc_var_latency_record": 3,
                  "tbrpc_var_adder_add": 2, "tbrpc_dup": 1},
    },
}


def test_tensor_call_census_per_handle(obs, monkeypatch):
    """Every short foreign call of a raw Pull and Push rides the handle
    that keeps the GIL; the CDLL handle sees the RPC and the audited
    exceptions only, per kind."""
    from collections import Counter

    import jax.numpy as jnp

    from brpc_tpu.observability import metrics as obs_metrics
    from brpc_tpu.runtime import native
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)
    from brpc_tpu.runtime.tensor import _bind_tensor_api

    obs.rpcz_enable(False)
    # Signatures bind on the real handle: set through the counting one
    # they would land on throwaway wrappers.
    _bind_tensor_api(native.lib())
    cdll = _CountingLib(native.lib())
    pydll = _CountingLib(native.pylib())
    monkeypatch.setattr(native, "_lib", cdll)
    monkeypatch.setattr(native, "_pylib", pydll)
    # Built after the patch: the channel, arenas and trampoline capture
    # the counting handles.
    ps = ParameterServer({"w": jnp.ones((32, 64), jnp.float32)}, lr=0.01)
    port = ps.start()
    client = ParameterClient(f"tpu://127.0.0.1:{port}")
    grad = jnp.ones((32, 64), jnp.float32)
    try:
        client.pull("w")  # negotiates QoS, creates every recorder
        client.push_grad("w", grad)
        # Recorders are process-wide and may predate the patch.
        for series in list(obs_metrics._registry.values()):
            for attr, handle in (("_L", cdll), ("_P", pydll)):
                if hasattr(series, attr):
                    monkeypatch.setattr(series, attr, handle)
        census = {}
        for kind in ("pull", "push"):
            cdll.calls.clear()
            pydll.calls.clear()
            if kind == "pull":
                client.pull("w")
            else:
                client.push_grad("w", grad)
            census[kind] = {"cdll": dict(Counter(cdll.calls)),
                            "pydll": dict(Counter(pydll.calls))}
        assert census == CENSUS
    finally:
        client.close()
        ps.stop()


# The liveness drive, in a child process: a deadlock on the GIL freezes
# every Python thread of its process, the test's own included, so only a
# parent can see it and report it.
_LIVENESS = """
import json, sys, threading, time
sys.path.insert(0, %r)
import jax.numpy as jnp
import brpc_tpu.observability as obs
from brpc_tpu.runtime.param_server import ParameterClient, ParameterServer

shapes = {"small": (32, 64), "large": (256, 256)}
state = {"scrapes": 0}
obs.gauge("liveness_test_scrapes", lambda: state["scrapes"])
ps = ParameterServer({n: jnp.ones(s, jnp.float32) for n, s in shapes.items()},
                     lr=0.01)
port = ps.start()
clients = [ParameterClient("tpu://127.0.0.1:%%d" %% port) for _ in range(4)]
stop_at = time.monotonic() + 2.0
calls = []

def worker(client):
    grads = {n: jnp.ones(s, jnp.float32) for n, s in shapes.items()}
    n = 0
    while time.monotonic() < stop_at:
        for name, g in grads.items():
            client.pull(name)
            client.push_grad(name, g)
            n += 2
    calls.append(n)

def scraper():
    while time.monotonic() < stop_at:
        text = obs.dump_vars()
        assert "tensor_arena_busy_bytes" in text, text
        assert "liveness_test_scrapes" in text, text
        ps.arena.busy_bytes()
        state["scrapes"] += 1

threads = [threading.Thread(target=worker, args=(c,)) for c in clients]
threads.append(threading.Thread(target=scraper))
for t in threads:
    t.start()
for t in threads:
    t.join()
for c in clients:
    c.close()
ps.stop()
print(json.dumps({"calls": calls, "scrapes": state["scrapes"]}), flush=True)
"""


def test_gil_keeping_calls_stay_live_under_scrapes():
    """The hazard of holding the GIL across a foreign call is a deadlock
    with a native path that waits for Python. Four threads pull and push
    for about two seconds while another dumps /vars (Python gauges run
    on the callback pool under the registry lock) and reads the arena
    gauges; every thread must finish within 30 s."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = subprocess.Popen(  # tpulint: allow(py-blocking)
        [sys.executable, "-c", _LIVENESS % root],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        out, err = child.communicate(timeout=30)
    except subprocess.TimeoutExpired:
        child.kill()
        child.communicate()
        pytest.fail("pulls, pushes and scrapes deadlocked")
    assert child.returncode == 0, err[-4000:]
    got = json.loads(out.splitlines()[-1])
    assert len(got["calls"]) == 4 and min(got["calls"]) > 0, got
    assert got["scrapes"] > 0, got


def test_stages_land_on_the_profiler_host_plane(obs, tmp_path):
    """While rpcz is on, every stage is also a brpc.<stage> annotation on
    the profiler's host plane, and the tensor service's callback a
    brpc.serve around them."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)

    ps = ParameterServer({"w": jnp.ones((32, 4), jnp.float32)}, lr=0.01)
    port = ps.start()
    client = ParameterClient(f"tpu://127.0.0.1:{port}")
    client.meta()
    obs.rpcz_enable()
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.trace_span("plane.pull"):
                client.pull("w")
        finally:
            jax.profiler.stop_trace()
    finally:
        obs.rpcz_enable(False)
        client.close()
        ps.stop()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events}
    assert {"brpc.rpc", "brpc.serve", "brpc.handler",
            "brpc.respond_stage"} <= names, sorted(
                n for n in names if n.startswith("brpc."))


# ---- /tensorz ----

def test_tensorz_page_shows_arena_occupancy():
    from brpc_tpu.runtime import native
    from brpc_tpu.runtime.tensor import TensorArena

    server = native.Server()
    server.add_echo_service()
    port = server.start("127.0.0.1:0")
    arena = TensorArena(8 << 20)
    try:
        _, body = _http(port, "/tensorz")
        assert "tensor arenas:" in body
        # this arena's row: id, size, busy column (busy counts REFERENCED
        # ranges — a bare alloc reads 0; the gauge test below drives refs)
        assert re.search(r"arena +\d+ .*8388608 bytes +busy +\d+", body), body
        # the Python data-plane vars section lists the tensor_* series
        assert "tensor_arena_busy_bytes" in body
        assert "tensor_arena_total_bytes" in body
    finally:
        arena.close()
        server.stop()


def test_python_arena_gauges_track_occupancy():
    """busy_bytes counts ranges that still carry references: hold the
    response view of a tensor RPC un-released and the SERVER arena must
    read busy through the Python-registered gauge; releasing drains it."""
    import brpc_tpu.observability as obs
    from brpc_tpu.runtime import native
    from brpc_tpu.runtime.tensor import TensorArena, TensorChannel, \
        add_tensor_service

    def handler(method, request, att):
        return b"", np.ones(1 << 18, np.float32)  # 1MB response tensor

    server = native.Server()
    srv_arena = add_tensor_service(server, "Gauge", handler)
    port = server.start("127.0.0.1:0")
    ch = TensorChannel(f"tpu://127.0.0.1:{port}", TensorArena(16 << 20))

    def gauge_value():
        vars_text = obs.dump_vars("tensor_arena")
        return int(vars_text.split("tensor_arena_busy_bytes : ")[1]
                   .splitlines()[0])

    try:
        payload, view = ch.call_raw("Gauge/Pull", b"")
        try:
            assert view.nbytes == 1 << 20
            assert gauge_value() >= 1 << 20  # server range held by our view
        finally:
            view.release()
        deadline = 100
        while gauge_value() and deadline:  # release frame drains async
            import time
            time.sleep(0.02)
            deadline -= 1
        assert gauge_value() == 0
        assert srv_arena.busy_bytes() == 0
    finally:
        ch.close()
        server.stop()


# ---- framework recorders ----

def _vars(obs, prefix):
    """{name: value} of the /vars lines under `prefix`."""
    out = {}
    for line in obs.dump_vars(prefix).splitlines():
        name, _, value = line.partition(" : ")
        out[name.strip()] = value.strip()
    return out


def test_rpc_client_recorder_vars_shape(obs):
    """The native client recorder counts every call and exposes its
    average, p50, p99 and max on /vars; the tensor path registers its
    arena wait-stall counter under its documented name."""
    from brpc_tpu.runtime import native
    from brpc_tpu.runtime.tensor import TensorArena

    server = native.Server()
    server.add_echo_service()
    port = server.start("127.0.0.1:0")
    ch = native.Channel(f"127.0.0.1:{port}", timeout_ms=5000)
    try:
        for _ in range(20):
            ch.call("EchoService/Echo", b"x", b"y" * 1024)
    finally:
        server.stop()
    rpc_client = _vars(obs, "rpc_client")
    assert int(rpc_client["rpc_client_count"]) >= 20
    for key in ("rpc_client_latency", "rpc_client_latency_50",
                "rpc_client_latency_99", "rpc_client_max_latency"):
        assert int(rpc_client[key]) >= 0, key
    arena = TensorArena(1 << 20)
    try:
        stalls = _vars(obs, "tensor_arena_wait_stalls")
        assert "tensor_arena_wait_stalls" in stalls
        assert obs.counter("tensor_arena_wait_stalls").value() >= 0
    finally:
        arena.close()
