"""Overlapped training step (ISSUE 12 acceptance surface).

Pure half (tier-1, no native lib):
  * StepGraph topology contracts (deps-first construction, duplicate /
    unknown-dep / bad-lane rejection, insertion order == serial order);
  * serial == overlapped: same node set, same results, deterministic
    per-lane sequences;
  * the overlap is real (wire nodes execute inside compute nodes'
    intervals; wall < serial wall) and the exposed/overlapped comm
    accounting splits wire time accordingly;
  * failure propagation: a failing node cancels exactly its transitive
    dependents, independent branches complete (partial salvage), the
    wire thread always joins — no deadlock;
  * LayeredMLP's per-layer manual backward == jax.grad of the same
    stack.

Native half (skips cleanly without libbrpc_tpu.so), under an ARMED
stall watchdog so a wedge in the new scheduling paths becomes a stall
dump:
  * overlapped N-step loss trajectory identical to the serial driver
    (same fp ops in the same order on one compute thread — tolerance
    documented at the assert), versions monotone and complete;
  * a mid-step push failure (name retired under the driver) surfaces as
    PartialPushError with per-name applied/unpushed salvage, no wedge;
  * raw-path byte-identity: with no codec negotiated the driver's
    pushes land bit-for-bit what plain push_grad lands;
  * quantize-at-stage rides the overlap (codec counters move, loss
    stays sane);
  * /rpcz: one overlapped step shows push spans INSIDE a later layer's
    compute span, with arena_stage/encode stages and the step's
    exposed/overlapped_comm annotations;
  * the dp+tp mesh harness (the dryrun_multichip scenario) drives the
    same scheduled step over a live ParameterServer.
"""

import contextlib
import threading
import time

import numpy as np
import pytest

from brpc_tpu.runtime.step_sched import (COMPUTE, WIRE, StepFailure,
                                         StepGraph, run_graph)

# ---------------------------------------------------------------------------
# Pure tests (no native lib).
# ---------------------------------------------------------------------------


def test_graph_topology_contracts():
    g = StepGraph()
    g.add("a", lambda r: 1)
    with pytest.raises(ValueError, match="duplicate"):
        g.add("a", lambda r: 2)
    with pytest.raises(ValueError, match="unknown node"):
        g.add("b", lambda r: 2, deps=("nope",))
    with pytest.raises(ValueError, match="lane"):
        g.add("c", lambda r: 3, lane="gpu")
    g.add("b", lambda r: r["a"] + 1, deps=("a",), lane=WIRE)
    g.add("c", lambda r: r["a"] + 2, deps=("a",))
    g.add("d", lambda r: r["b"] + r["c"], deps=("b", "c"), lane=WIRE)
    assert g.serial_order() == ["a", "b", "c", "d"]
    assert len(g) == 4 and "d" in g and "x" not in g


def _diamond():
    g = StepGraph()
    g.add("a", lambda r: 1)
    g.add("b", lambda r: r["a"] + 1, deps=("a",), lane=WIRE)
    g.add("c", lambda r: r["a"] * 10, deps=("a",))
    g.add("d", lambda r: r["b"] + r["c"], deps=("b", "c"), lane=WIRE)
    return g


def test_serial_equals_overlapped_results():
    rs, ts = run_graph(_diamond(), overlap=False)
    ro, to = run_graph(_diamond(), overlap=True)
    assert rs == ro == {"a": 1, "b": 2, "c": 10, "d": 12}
    assert sorted(n for n, *_ in ts.events) == sorted(
        n for n, *_ in to.events)
    # Serial order is the insertion order, and hides nothing.
    assert ts.order() == ["a", "b", "c", "d"]
    assert ts.exposed_wait_s == ts.wire_busy_s


def test_per_lane_sequences_deterministic():
    def lane_seq(trace, lane):
        return [n for n, ln, s, _e in sorted(trace.events,
                                             key=lambda e: e[2])
                if ln == lane]

    _r1, t1 = run_graph(_diamond(), overlap=True)
    _r2, t2 = run_graph(_diamond(), overlap=True)
    assert lane_seq(t1, WIRE) == lane_seq(t2, WIRE) == ["b", "d"]
    assert lane_seq(t1, COMPUTE) == lane_seq(t2, COMPUTE) == ["a", "c"]


def test_overlap_really_overlaps():
    """comp_a -> {wire_push, comp_b}: the wire node must run INSIDE
    comp_b's interval, cutting wall time below the serial sum."""
    def sleeper(dt):
        def fn(r):
            time.sleep(dt)  # tpulint: allow(py-blocking)
            return dt
        return fn

    def build():
        g = StepGraph()
        g.add("comp_a", sleeper(0.05))
        g.add("wire_push", sleeper(0.15), deps=("comp_a",), lane=WIRE)
        g.add("comp_b", sleeper(0.15), deps=("comp_a",))
        return g

    _rs, ts = run_graph(build(), overlap=False)
    _ro, to = run_graph(build(), overlap=True)
    assert ts.wall_s >= 0.34  # 0.05 + 0.15 + 0.15, all exposed
    assert to.wall_s <= ts.wall_s - 0.08, (
        f"overlap bought nothing: serial {ts.wall_s:.3f}s vs "
        f"overlapped {to.wall_s:.3f}s")
    assert to.overlapped("wire_push", "comp_b")
    # Wire time ran in compute's shadow: mostly overlapped, little
    # exposed (scheduling jitter allowance for a 2-core host).
    assert to.overlapped_comm_s() >= 0.08
    assert to.exposed_wait_s <= 0.10
    # Serial accounting: every wire second exposed.
    assert ts.overlapped_comm_s() == 0.0


def test_failure_cancels_dependents_not_siblings():
    g = StepGraph()
    g.add("a", lambda r: 1)
    g.add("boom", lambda r: 1 // 0, deps=("a",), lane=WIRE)
    g.add("dep", lambda r: r["boom"], deps=("boom",), lane=WIRE)
    g.add("dep2", lambda r: r["dep"], deps=("dep",))
    g.add("side", lambda r: r["a"] + 41, deps=("a",))
    for overlap in (False, True):
        with pytest.raises(StepFailure) as ei:
            run_graph(g, overlap=overlap)
        sf = ei.value
        assert set(sf.failed) == {"boom"}
        assert isinstance(sf.cause, ZeroDivisionError)
        assert sorted(sf.cancelled) == ["dep", "dep2"]
        assert sf.done == {"a": 1, "side": 42}  # salvage ran to the end


def test_compute_failure_cancels_wire_descendants_no_deadlock():
    done_side = []
    g = StepGraph()
    g.add("a", lambda r: 1)
    g.add("boom", lambda r: (_ for _ in ()).throw(RuntimeError("x")),
          deps=("a",))
    g.add("w", lambda r: done_side.append("w"), deps=("boom",), lane=WIRE)
    g.add("w2", lambda r: done_side.append("w2"), deps=("a",), lane=WIRE)
    t0 = time.monotonic()
    with pytest.raises(StepFailure) as ei:
        run_graph(g, overlap=True)
    assert time.monotonic() - t0 < 5.0, "failure path must not hang"
    assert ei.value.cancelled == ["w"]
    assert done_side == ["w2"]  # the independent wire branch completed


def test_wire_ctx_wraps_the_wire_lane():
    import contextlib

    seen = []

    @contextlib.contextmanager
    def ctx():
        seen.append(("enter", threading.current_thread().name))
        try:
            yield
        finally:
            seen.append(("exit", threading.current_thread().name))

    g = StepGraph()
    g.add("w", lambda r: threading.current_thread().name, lane=WIRE)
    results, _t = run_graph(g, overlap=True, wire_ctx=ctx)
    assert results["w"] == "step-wire"
    assert [e for e, _ in seen] == ["enter", "exit"]
    assert all(t == "step-wire" for _, t in seen)
    seen.clear()
    results, _t = run_graph(g, overlap=False, wire_ctx=ctx)
    assert results["w"] != "step-wire"  # serial: the caller's thread
    assert [e for e, _ in seen] == ["enter", "exit"]


def test_wire_lane_death_surfaces_as_failure():
    """A wire_ctx that raises on enter kills the wire thread OUTSIDE
    any node fn — that must surface as StepFailure with every wire node
    cancelled, never as a silent success with zero wire work done (and
    never as a hang for compute nodes downstream of wire nodes)."""
    import contextlib

    ran = []

    @contextlib.contextmanager
    def bad_ctx():
        raise RuntimeError("qos scope refused")
        yield  # pragma: no cover

    g = StepGraph()
    g.add("c", lambda r: ran.append("c"))
    g.add("w", lambda r: ran.append("w"), deps=("c",), lane=WIRE)
    g.add("after_w", lambda r: ran.append("after_w"), deps=("w",))
    t0 = time.monotonic()
    with pytest.raises(StepFailure) as ei:
        run_graph(g, overlap=True, wire_ctx=bad_ctx)
    assert time.monotonic() - t0 < 5.0, "dead wire lane must not hang"
    sf = ei.value
    assert "<wire-lane>" in sf.failed
    assert isinstance(sf.cause, RuntimeError)
    assert "w" in sf.cancelled and "after_w" in sf.cancelled
    assert ran == ["c"]  # no wire node ran, and no silent success


def test_abort_stops_wire_lane_promptly():
    """A BaseException on the compute thread (Ctrl-C) must stop the
    wire lane BEFORE its next node — not after the whole remaining wire
    schedule drains."""
    ran = []

    def wire(name, dt):
        def fn(r):
            time.sleep(dt)  # tpulint: allow(py-blocking)
            ran.append(name)
        return fn

    def interrupt(r):
        time.sleep(0.05)  # tpulint: allow(py-blocking)
        raise KeyboardInterrupt()

    g = StepGraph()
    g.add("a", lambda r: None)
    g.add("w1", wire("w1", 0.2), deps=("a",), lane=WIRE)
    g.add("w2", wire("w2", 0.01), deps=("w1",), lane=WIRE)
    g.add("w3", wire("w3", 0.01), deps=("w2",), lane=WIRE)
    g.add("boom", interrupt, deps=("a",))
    with pytest.raises(KeyboardInterrupt):
        run_graph(g, overlap=True)
    # w1 was already running when the interrupt landed; w2/w3 were only
    # READIED by w1's completion and must be skipped by the abort.
    assert ran == ["w1"]


def test_layered_mlp_backward_matches_jax_grad():
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models.tensor_service import LayeredMLP

    h = LayeredMLP([12, 16, 8, 4], seed=3)
    params = h.init_params()
    x, y = h.data(10, seed=7)
    grads, loss = h.grads(params, x, y)
    assert set(grads) == set(h.names)

    def ref_loss(plist):
        a = x
        for k, w in enumerate(plist):
            z = jnp.dot(a, w)
            a = z if k == len(plist) - 1 else jax.nn.relu(z)
        return jnp.mean(jnp.square(a - y))

    plist = [params[n] for n in h.names]
    ref = jax.grad(ref_loss)(plist)
    assert np.isfinite(loss)
    for n, g_ref in zip(h.names, ref):
        np.testing.assert_allclose(np.asarray(grads[n]),
                                   np.asarray(g_ref),
                                   rtol=1e-5, atol=1e-6)
    # Order contract: the deltas only propagate top-down.
    ctx = h.forward(params, x, y)
    with pytest.raises(ValueError, match="backward order"):
        h.backward(ctx, h.names[0])


# ---------------------------------------------------------------------------
# Native tests, under an armed watchdog.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def overlap_env(tmp_path_factory):
    from conftest import require_native_lib
    require_native_lib()
    from brpc_tpu.observability import health
    dump_dir = tmp_path_factory.mktemp("step_overlap_dumps")
    health.start_watchdog(str(dump_dir))
    yield {"health": health}
    deadline = time.monotonic() + 10
    while health.state() == "stalled" and time.monotonic() < deadline:
        time.sleep(0.1)
    assert health.state() != "stalled", (
        f"scheduler stalled after step-overlap tests; dump: "
        f"{health.last_dump_path()}")


def _fresh_pair(sizes=(24, 32, 32, 16), seed=0, codec=None, lr=0.05,
                oneside=False):
    """(server, client, harness) over a fresh ParameterServer holding
    the harness's init params."""
    from brpc_tpu.models.tensor_service import LayeredMLP
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)

    h = LayeredMLP(list(sizes), seed=seed)
    ps = ParameterServer(dict(h.init_params()), lr=lr, oneside=oneside)
    port = ps.start()
    client = ParameterClient(f"tpu://127.0.0.1:{port}", codec=codec,
                             oneside=oneside)
    return ps, client, h


def _codec_var(name: str) -> int:
    """A tensor_codec_* native adder's value off the /vars dump (the
    registrations are native-side; creating Python twins would collide)."""
    from brpc_tpu.observability import metrics as obs

    for line in obs.dump_vars("tensor_codec").splitlines():
        k, _, v = line.partition(":")
        if k.strip() == name:
            return int(v.strip())
    return 0


def _drive(driver, h, steps, batch=8):
    losses = []
    for i in range(steps):
        x, y = h.data(batch, seed=100 + i)
        losses.append(driver.step(x, y))
    return losses


def test_overlapped_matches_serial_trajectory(overlap_env):
    """The acceptance parity drive: same harness, same data, one driver
    overlapped and one serial against separate-but-identical servers —
    loss trajectories and final server states must match. Tolerance:
    both drivers run the same jitted ops in the same order on ONE
    compute thread and the server applies per-name updates in the same
    per-name order, so this is equality up to fp determinism of repeated
    XLA executions — observed exact; asserted at 1e-6/1e-8."""
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    ps_a, cl_a, h = _fresh_pair()
    ps_b, cl_b, _h2 = _fresh_pair()
    try:
        d_over = OverlappedStepDriver(cl_a, h, overlap=True, window=4)
        d_ser = OverlappedStepDriver(cl_b, h, overlap=False, window=4)
        d_over.prime()
        d_ser.prime()
        steps = 4
        l_over = _drive(d_over, h, steps)
        l_ser = _drive(d_ser, h, steps)
        np.testing.assert_allclose(l_over, l_ser, rtol=1e-6, atol=1e-8)
        # Versions monotone and complete: every layer pushed every step.
        for name in h.names:
            assert d_over.versions[name] == steps
            assert d_ser.versions[name] == steps
        for name in h.names:
            va, wa = cl_a.pull(name)
            vb, wb = cl_b.pull(name)
            assert va == vb == steps
            np.testing.assert_allclose(np.asarray(wa), np.asarray(wb),
                                       rtol=1e-6, atol=1e-8)
        # The overlapped driver actually overlapped something.
        assert d_over.totals["overlapped_comm_ms"] > 0.0
        assert d_ser.totals["overlapped_comm_ms"] == 0.0
    finally:
        cl_a.close()
        cl_b.close()
        ps_a.stop()
        ps_b.stop()


def test_midstep_push_failure_salvages_partially(overlap_env):
    """Retire one parameter under a running driver: that layer's push
    dies E_MOVED mid-step, its confirm/pull are cancelled, every OTHER
    layer's push lands and confirms — PartialPushError carries the
    split, and nothing wedges (module watchdog asserts on teardown)."""
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               PartialPushError)
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    # MORE layers than the window: pushes drain mid-submit too, so this
    # also pins that a failed reply is attributed to ITS tag and never
    # fails an innocent later push (the window pre-drain discipline).
    ps, client, h = _fresh_pair(sizes=(24, 32, 32, 32, 32, 32, 16))
    victim = h.names[1]
    try:
        driver = OverlappedStepDriver(client, h, overlap=True, window=2)
        driver.prime()
        x, y = h.data(8, seed=200)
        driver.step(x, y)
        ctl = ParameterClient(f"tpu://127.0.0.1:{ps.port}")
        ctl.retire(victim)
        ctl.close()
        x, y = h.data(8, seed=201)
        with pytest.raises(PartialPushError) as ei:
            driver.step(x, y)
        err = ei.value
        assert victim in err.unpushed
        assert set(err.applied) == set(h.names) - set(err.unpushed)
        for name, version in err.applied.items():
            assert version == 2  # step 1 + the salvaged step 2
        sf = err.step_failure
        assert any(n.startswith(("push:", "opt:")) for n in sf.failed)
        assert f"pull:{victim}" in sf.cancelled
    finally:
        client.close()
        ps.stop()


def test_raw_path_byte_identity(overlap_env):
    """No codec negotiated: the driver's windowed pushes must land
    BIT-FOR-BIT what plain push_grad lands (same wire framing, same
    server math) and move no codec accounting."""
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    ps_a, cl_a, h = _fresh_pair()
    ps_b, cl_b, _h2 = _fresh_pair()
    try:
        wire_before = _codec_var("tensor_codec_bytes_wire")
        driver = OverlappedStepDriver(cl_a, h, overlap=True, window=4)
        driver.prime()
        x, y = h.data(8, seed=300)
        driver.step(x, y)
        # Reference: identical grads through the plain serial client.
        params = {n: cl_b.pull(n)[1] for n in h.names}
        grads, _loss = h.grads(params, x, y)
        for name in h.names:
            cl_b.push_grad(name, grads[name])
        for name in h.names:
            _va, wa = cl_a.pull(name)
            _vb, wb = cl_b.pull(name)
            assert np.array_equal(np.asarray(wa), np.asarray(wb)), (
                f"driver push of {name} diverged from push_grad")
        assert _codec_var("tensor_codec_bytes_wire") == wire_before, \
            "raw path must not touch the codec accounting"
    finally:
        cl_a.close()
        cl_b.close()
        ps_a.stop()
        ps_b.stop()


def test_quantized_encode_rides_the_overlap(overlap_env):
    """codec='int8': gradient encode runs at arena-stage time on the
    wire lane (inside the next layer's compute shadow) and the step
    still trains — parity with the serial quantized driver within the
    documented quant tolerance (5e-2, the test_tensor_codec bound; the
    error-feedback residual keeps pushes within one quant step)."""
    from brpc_tpu.runtime import codec as codec_mod
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    if "int8" not in codec_mod.supported_codecs():
        pytest.skip("int8 codec unavailable in this build")
    # 4KB quant floor: layers must clear MIN_QUANT_BYTES to quantize.
    sizes = (48, 64, 64, 32)
    ps_a, cl_a, h = _fresh_pair(sizes=sizes, codec="int8")
    ps_b, cl_b, _h2 = _fresh_pair(sizes=sizes, codec="int8")
    try:
        logical_before = _codec_var("tensor_codec_bytes_logical")
        d_over = OverlappedStepDriver(cl_a, h, overlap=True, window=4)
        d_ser = OverlappedStepDriver(cl_b, h, overlap=False, window=4)
        d_over.prime()
        d_ser.prime()
        l_over = _drive(d_over, h, 3, batch=8)
        l_ser = _drive(d_ser, h, 3, batch=8)
        np.testing.assert_allclose(l_over, l_ser, rtol=5e-2, atol=5e-2)
        assert _codec_var("tensor_codec_bytes_logical") > \
            logical_before, "quantized pushes must account logical bytes"
        for name in h.names:
            assert d_over.versions[name] == 3
    finally:
        cl_a.close()
        cl_b.close()
        ps_a.stop()
        ps_b.stop()


def test_rpcz_shows_push_inside_compute_shadow(overlap_env):
    """The acceptance trace: one overlapped step's /rpcz dump has a
    push span whose interval sits INSIDE a LATER layer's backward span,
    and the step span carries the exposed/overlapped_comm breakdown."""
    from brpc_tpu.observability import tracing
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    # Fatter layers + batch: each bwd long enough for a push to land
    # inside it on a 2-core host.
    ps, client, h = _fresh_pair(sizes=(64, 128, 128, 128, 32))
    tracing.rpcz_enable(True)
    old_n = tracing.rpcz_sample_1_in_n()
    tracing.rpcz_set_sample_1_in_n(1)
    try:
        driver = OverlappedStepDriver(client, h, overlap=True, window=4)
        driver.prime()
        x, y = h.data(64, seed=400)
        driver.step(x, y)
        spans = tracing.dump_rpcz()
        by_name = {}
        for s in spans:
            by_name.setdefault(s["service_method"], s)
        step_span = by_name.get("train_step")
        assert step_span is not None, f"no step span in {sorted(by_name)}"
        notes = " ".join(step_span.get("annotations", []))
        assert "exposed_comm=" in notes and "overlapped_comm=" in notes
        # Push of layer k overlapping backward of a LOWER layer (bwd
        # runs top-down, so lower layers compute later).
        overlapped_pairs = []
        for k, pushed in enumerate(h.names):
            ps_span = by_name.get(f"step/push:{pushed}")
            if ps_span is None:
                continue
            for lower in h.names[:k]:
                bwd = by_name.get(f"step/bwd:{lower}")
                if bwd is None:
                    continue
                if (ps_span["start_us"] < bwd["end_us"]
                        and bwd["start_us"] < ps_span["end_us"]):
                    overlapped_pairs.append((pushed, lower))
        assert overlapped_pairs, (
            "no push span overlapped a later layer's compute span: "
            + str({n: (s['start_us'], s['end_us'])
                   for n, s in by_name.items() if n.startswith('step/')}))
        # Wire-side stage annotations land on the push node spans.
        push_notes = " ".join(
            " ".join(s.get("annotations", []))
            for n, s in by_name.items() if n.startswith("step/push:"))
        assert "arena_stage=" in push_notes
    finally:
        tracing.rpcz_set_sample_1_in_n(old_n)
        client.close()
        ps.stop()


def test_fleet_client_drives_scheduled_step(overlap_env):
    """The driver's fleet-shaped path: no ``channel`` attribute, so
    push:k confirms synchronously through ``FleetClient.push_grad`` (the
    windowing lives inside each shard stream) and pulls route by owner —
    the same scheduled step, same trajectory as the single-server serial
    driver."""
    from brpc_tpu.fleet import FleetClient, FleetServer, RegistryHub
    from brpc_tpu.fleet import clear_registry
    from brpc_tpu.models.tensor_service import LayeredMLP
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    h = LayeredMLP([24, 32, 32, 16], seed=9)
    hub = RegistryHub()
    hub.start()
    shard = None
    fc = None
    try:
        shard = FleetServer(hub.hostport, tag="steps", ttl_s=2)
        shard.start()
        fc = FleetClient(hub.hostport, tag="steps", op_deadline_s=20.0)
        for name, w in h.init_params().items():
            # install() seeds param AND zero momentum — matches the
            # reference server's fresh-parameter state exactly.
            fc.install(name, np.asarray(w), refresh=False)
        driver = OverlappedStepDriver(fc, h, overlap=True, window=4)
        driver.prime()
        losses = _drive(driver, h, 2)
        assert all(np.isfinite(v) for v in losses)
        for name in h.names:
            assert driver.versions[name] == 2
        # Same trajectory as the plain single-server serial driver.
        # lr matches the FleetServer's ParameterServer default.
        ps, cl, h2 = _fresh_pair(sizes=(24, 32, 32, 16), seed=9, lr=0.01)
        try:
            ref = OverlappedStepDriver(cl, h2, overlap=False, window=4)
            ref.prime()
            ref_losses = _drive(ref, h2, 2)
            np.testing.assert_allclose(losses, ref_losses,
                                       rtol=1e-6, atol=1e-8)
        finally:
            cl.close()
            ps.stop()
    finally:
        if fc is not None:
            fc.close()
        if shard is not None:
            shard.stop()
        clear_registry()
        hub.stop()


def test_mesh_harness_drives_scheduled_step(overlap_env):
    """The dp+tp dryrun_multichip scenario as an RPC-driven scheduled
    step: batches shard over CLIENT, weights alternate over SHARD, the
    driver pulls/pushes through a live ParameterServer — overlapped and
    serial agree on the mesh too."""
    import jax

    from brpc_tpu.models.tensor_service import LayeredMLP
    from brpc_tpu.parallel.mesh import CLIENT_AXIS, SHARD_AXIS, make_mesh
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    mesh = make_mesh(jax.devices()[:4])
    n_shard = mesh.shape[SHARD_AXIS]
    n_client = mesh.shape[CLIENT_AXIS]
    sizes = [16, 8 * n_shard, 8 * n_shard, 8]
    batch = 4 * n_client

    losses = {}
    finals = {}
    for overlap in (True, False):
        h = LayeredMLP(sizes, mesh=mesh, seed=5)
        ps = ParameterServer(dict(h.init_params()))
        port = ps.start()
        client = ParameterClient(f"tpu://127.0.0.1:{port}")
        try:
            driver = OverlappedStepDriver(client, h, overlap=overlap,
                                          window=4)
            driver.prime()
            ls = []
            for i in range(2):
                x, y = h.data(batch, seed=500 + i)
                ls.append(driver.step(x, y))
            losses[overlap] = ls
            finals[overlap] = {n: np.asarray(client.pull(n)[1])
                               for n in h.names}
        finally:
            client.close()
            ps.stop()
    assert all(np.isfinite(v) for v in losses[True])
    np.testing.assert_allclose(losses[True], losses[False],
                               rtol=1e-6, atol=1e-8)
    for n in finals[True]:
        np.testing.assert_allclose(finals[True][n], finals[False][n],
                                   rtol=1e-6, atol=1e-8)


def test_driver_releases_parameters_and_staged_gradients(overlap_env):
    """The driver keeps one copy of the parameter set: after ``bwd:k``
    neither the driver nor the harness's ctx holds layer k's parameters,
    and a gradient is dropped once its push is staged into the arena. Each
    pull and push submit runs inside the caller's ``call_span``, and each
    push's acknowledgement (version, arrival) lands in the step's
    ``RunTrace.acks``. The trainer's counters reach /vars."""
    import gc
    import weakref

    import jax

    from brpc_tpu.models import mla_moe
    from brpc_tpu.observability import metrics as obs
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    hf = dict(hidden_size=32, num_hidden_layers=2, first_k_dense_replace=1,
              num_attention_heads=2, kv_lora_rank=8, qk_nope_head_dim=8,
              qk_rope_head_dim=4, v_head_dim=8, rope_theta=50000,
              rms_norm_eps=1e-5, intermediate_size=48,
              moe_intermediate_size=16, n_shared_experts=1, router_experts=8,
              held_experts=[0, 1], n_routed_experts=2, num_experts_per_tok=3,
              routed_scaling_factor=2.0, vocab_size=64)
    cfg = mla_moe.Config.from_hf(hf, q_block=8, loss_chunk=16)
    trainer = mla_moe.MlaMoeTrainer(cfg, np.zeros((1, 8), np.float32))

    class Spy:
        names = trainer.names

        def __init__(self):
            self.done, self.grads, self.violations = [], [], []

        def place(self, name, arr):
            return arr

        def forward(self, params, x, y):
            return trainer.forward(params, x, y)

        def backward(self, ctx, name):
            held = set(self.done) & (set(driver._params)
                                     | set(ctx["params"]))
            if held:
                self.violations.append((name, sorted(held)))
            g = trainer.backward(ctx, name)
            self.grads.append(weakref.ref(g))
            self.done.append(name)
            return g

        def loss(self, ctx):
            return trainer.loss(ctx)

    spans = []

    @contextlib.contextmanager
    def call_span(kind, name):
        t0 = time.monotonic()
        yield
        spans.append((kind, name, t0))

    spy = Spy()
    ps = ParameterServer(dict(mla_moe.init_params(cfg,
                                                  jax.random.PRNGKey(0))))
    client = ParameterClient(f"tpu://127.0.0.1:{ps.start()}")
    try:
        driver = OverlappedStepDriver(client, spy, overlap=True, window=2,
                                      call_span=call_span)
        driver.prime()
        x, y = mla_moe.step_tokens(cfg, 1, 16, jax.random.PRNGKey(1))
        driver.step(x, y)
        assert spy.violations == []
        assert spy.done == list(reversed(trainer.names))
        assert driver._params == {} and set(driver._raw) == set(spy.names)
        gc.collect()
        assert all(r() is None for r in spy.grads)
        for kind in ("pull", "push"):
            assert sorted(n for k, n, _ in spans if k == kind) == \
                sorted(spy.names)
        acks = driver.last_trace.acks
        assert set(acks) == set(spy.names)
        submitted = {n: t for k, n, t in spans if k == "push"}
        assert all(v == 1 and submitted[n] <= t for n, (v, t) in acks.items())
        # the next step places what the last one pulled
        driver.step(x, y)
        assert all(v == 2 for v in driver.versions.values())
        assert "trainer_tokens" in obs.dump_vars("trainer_")
    finally:
        client.close()
        ps.stop()


# ---------------------------------------------------------------------------
# The tail's pull lookahead.
# ---------------------------------------------------------------------------


def _wait_for(pred, timeout_s=10.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while not pred():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.002)  # tpulint: allow(py-blocking)
    return True


def _drain(arena, timeout_s=5.0) -> int:
    _wait_for(lambda: arena.busy_bytes() == 0, timeout_s)
    return arena.busy_bytes()


class _Confirmed:
    """A push future whose reply wait is logged as the push's confirm."""

    def __init__(self, fut, log):
        self._fut = fut
        self._log = log

    def result(self, *a, **kw):
        try:
            return self._fut.result(*a, **kw)
        finally:
            self._log.append(("confirm", None))

    def __getattr__(self, attr):
        return getattr(self._fut, attr)


class _WireLog:
    """The client's pull issues (``sync`` through call_raw, ``async``
    through call_async), push issues and confirms, and each pulled
    array's placement on the device, in the order they happened."""

    def __init__(self, client, monkeypatch):
        import brpc_tpu.runtime.tensor as tensor_mod

        self.events = []
        log = self.events
        ch = client.channel
        call_async, call_raw = ch.call_async, ch.call_raw
        consume = tensor_mod.consume_pull_reply

        def name_of(request):
            return bytes(request).split(b"\x00")[0].decode()

        def logged_call_async(method, request=b"", *a, **kw):
            fut = call_async(method, request, *a, **kw)
            if method == "ParamService/Pull":
                log.append(("issue_async", name_of(request)))
            elif method == "ParamService/Push":
                log.append(("push", None))
                return _Confirmed(fut, log)
            return fut

        def logged_call_raw(method, request, *a, **kw):
            if method == "ParamService/Pull":
                log.append(("issue_sync", name_of(request)))
            return call_raw(method, request, *a, **kw)

        def logged_consume(payload, view, device=None, note_name=None):
            out = consume(payload, view, device, note_name=note_name)
            log.append(("placed", note_name))
            return out

        monkeypatch.setattr(ch, "call_async", logged_call_async)
        monkeypatch.setattr(ch, "call_raw", logged_call_raw)
        monkeypatch.setattr(tensor_mod, "consume_pull_reply", logged_consume)

    def index(self, kind, name):
        return self.events.index((kind, name))

    def kinds(self, kind):
        return [n for k, n in self.events if k == kind]


class _Gated:
    """A LayeredMLP whose backward of a layer can first wait for a
    condition; ``bwd_done`` is set once the bottom layer's gradient is
    out."""

    def __init__(self, h, before=None):
        self.h = h
        self.names = h.names
        self.before = dict(before or {})
        self.bwd_done = threading.Event()

    def place(self, name, arr):
        return self.h.place(name, arr)

    def forward(self, params, x, y):
        return self.h.forward(params, x, y)

    def backward(self, ctx, name):
        cond = self.before.get(name)
        if cond is not None:
            assert _wait_for(cond), f"bwd:{name} gate never opened"
        g = self.h.backward(ctx, name)
        if name == self.names[0]:
            self.bwd_done.set()
        return g

    def loss(self, ctx):
        return self.h.loss(ctx)


def _lookahead_setup(mode, h, log_box):
    """(harness, overlap, call_span) for one lookahead case:

    * ``serial``: overlap off, every pull is in the tail;
    * ``overlapped_tail``: the top layer's push waits until every
      backward has run, so every push precedes every confirm and every
      pull is in the tail;
    * ``overlapped_mixed``: the second layer's backward waits until the
      top layer's pull is placed, so that pull runs while pushes are
      still unconfirmed — synchronously, and uncounted;
    * ``oneside``: the client reads the server's mapped window, never
      sent ahead."""
    top, below = h.names[-1], h.names[-2]
    gated = _Gated(h)

    @contextlib.contextmanager
    def call_span(kind, name):
        if (mode == "overlapped_tail" and kind == "push" and name == top):
            assert gated.bwd_done.wait(10), "backward never finished"
        yield

    if mode == "overlapped_mixed":
        gated.before[below] = lambda: ("placed", top) in log_box[0].events
    return gated, mode != "serial", call_span


@pytest.mark.parametrize(
    "mode", ["serial", "overlapped_tail", "overlapped_mixed", "oneside"])
def test_tail_sends_next_pull_before_placing_current(overlap_env, mode,
                                                     monkeypatch):
    """In the tail (every push confirmed) pull k+1's request is issued
    before pull k's array is placed; no pull is sent ahead while a push
    is unconfirmed; ``pulls_ahead`` counts exactly the pulls that went
    ahead, at most ``len(names) - 1``, on /vars as on ``last_stats``."""
    from brpc_tpu.observability import metrics as obs
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    ps, client, h = _fresh_pair(sizes=(24, 32, 32, 32, 16),
                                oneside=(mode == "oneside"))
    names, rev = h.names, list(reversed(h.names))
    counter = obs.counter("step_driver_pulls_ahead")
    log_box = [None]
    harness, overlap, call_span = _lookahead_setup(mode, h, log_box)
    try:
        driver = OverlappedStepDriver(client, harness, overlap=overlap,
                                      window=2, call_span=call_span)
        driver.prime()
        for step in range(2):
            monkeypatch.undo()
            log = log_box[0] = _WireLog(client, monkeypatch)
            harness.bwd_done.clear()
            before = counter.value()
            driver.step(*h.data(8, seed=600 + step))
            ahead = driver.last_stats["pulls_ahead"]
            assert counter.value() - before == ahead
            assert all(v == step + 1 for v in driver.versions.values())
            if mode == "oneside":
                assert ahead == 0 and log.kinds("issue_async") == []
                continue
            assert sorted(log.kinds("placed")) == sorted(names)
            assert len(log.kinds("confirm")) == len(names)
            last_confirm = max(i for i, (k, _n) in enumerate(log.events)
                               if k == "confirm")
            sent = log.kinds("issue_async")
            # Every pull request through call_async went out after the
            # last push confirm: nothing rides ahead of a push.
            assert all(log.index("issue_async", n) > last_confirm
                       for n in sent)
            assert sorted(sent + log.kinds("issue_sync")) == sorted(names)
            # The first tail pull sends its own request; each later one
            # was sent from its predecessor's node, before that
            # predecessor's array was placed.
            assert ahead == max(0, len(sent) - 1)
            assert 0 < ahead <= len(names) - 1
            assert sent == [n for n in rev if n in sent]
            for prev, nxt in zip(sent, sent[1:]):
                assert log.index("issue_async", nxt) < log.index(
                    "placed", prev), (prev, nxt, log.events)
            if mode == "overlapped_mixed":
                top = names[-1]
                assert log.index("issue_sync", top) < last_confirm
                assert ahead <= len(names) - 2
            else:
                assert ahead == len(names) - 1
    finally:
        monkeypatch.undo()
        client.close()
        ps.stop()


def test_lookahead_trajectories_match_plain_pulls(overlap_env):
    """Overlapped, serial and a plain loop of ``client.pull`` /
    ``push_grad`` calls give the same losses, the same final server
    state and the same last pulls, bit for bit."""
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    pairs = [_fresh_pair(sizes=(24, 32, 32, 32, 16)) for _ in range(3)]
    h = pairs[0][2]
    steps = 3
    try:
        losses, drivers = {}, {}
        for (ps, client, _h), overlap in zip(pairs[:2], (True, False)):
            d = OverlappedStepDriver(client, h, overlap=overlap, window=2)
            d.prime()
            losses[overlap] = _drive(d, h, steps)
            drivers[overlap] = d
            assert d.last_stats["pulls_ahead"] > 0
        plain = pairs[2][1]
        losses["plain"] = []
        for i in range(steps):
            x, y = h.data(8, seed=100 + i)
            params = {n: plain.pull(n)[1] for n in h.names}
            grads, loss = h.grads(params, x, y)
            for name in reversed(h.names):
                plain.push_grad(name, grads[name])
            losses["plain"].append(loss)
        assert losses[True] == losses[False] == losses["plain"]
        for name in h.names:
            got = [client.pull(name) for _ps, client, _h in pairs]
            assert [v for v, _ in got] == [steps] * 3
            ref = np.asarray(got[2][1])
            for (_v, w), d in zip(got[:2], (drivers[True],
                                            drivers[False])):
                assert np.array_equal(np.asarray(w), ref)
                assert d.versions[name] == steps
                assert np.array_equal(np.asarray(d._raw[name]), ref)
    finally:
        for ps, client, _h in pairs:
            client.close()
            ps.stop()


@pytest.mark.parametrize("mode", ["serial", "overlapped_tail"])
def test_failed_pull_sent_ahead_keeps_its_owner(overlap_env, mode):
    """The server retires a tensor just before its request goes ahead:
    the failure is its own ``pull:<name>`` node's, every other pull
    completes, no arena range stays busy (no view leaks), and once the
    tensor is back the next step's forward fetches it."""
    from brpc_tpu.runtime import native
    from brpc_tpu.runtime.param_server import ParameterClient
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    ps, client, h = _fresh_pair(sizes=(24, 32, 32, 32, 16))
    rev = list(reversed(h.names))
    victim = rev[1]  # sent ahead from the first tail pull's node
    ctl = ParameterClient(f"tpu://127.0.0.1:{ps.port}")
    harness, overlap, base_span = _lookahead_setup(mode, h, [None])
    armed, taken = [False], {}

    @contextlib.contextmanager
    def call_span(kind, name):
        if armed[0] and kind == "pull" and name == rev[0]:
            armed[0] = False
            taken["version"], taken["stacked"] = ctl.handoff(victim)
            ctl.retire(victim)
        with base_span(kind, name):
            yield

    try:
        driver = OverlappedStepDriver(client, harness, overlap=overlap,
                                      window=2, call_span=call_span)
        driver.prime()
        driver.step(*h.data(8, seed=700))
        armed[0] = True
        harness.bwd_done.clear()
        with pytest.raises(native.RpcError) as ei:
            driver.step(*h.data(8, seed=701))
        sf = ei.value.step_failure
        assert set(sf.failed) == {f"pull:{victim}"}
        assert sf.cancelled == []
        assert set(driver._raw) == set(h.names) - {victim}
        assert all(driver.versions[n] == 2 for n in h.names)
        assert _drain(ps.arena) == 0
        assert _drain(client.channel.arena) == 0
        ctl.install(victim, taken["stacked"], taken["version"],
                    commit=True)
        harness.bwd_done.clear()
        driver.step(*h.data(8, seed=702))
        assert all(v == 3 for v in driver.versions.values())
        assert set(driver._raw) == set(h.names)
        assert _drain(ps.arena) == 0
    finally:
        ctl.close()
        client.close()
        ps.stop()


class _Abort(BaseException):
    """Ctrl-C's stand-in: a BaseException out of a wire node."""


@pytest.mark.parametrize("mode", ["serial", "overlapped_tail"])
def test_abort_cancels_the_pull_sent_ahead(overlap_env, mode):
    """A step aborted while a request is ahead cancels it: its view is
    released, its call span closes, and the next step's forward fetches
    every parameter the aborted step left unpulled."""
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    ps, client, h = _fresh_pair(sizes=(24, 32, 32, 32, 16))
    rev = list(reversed(h.names))
    harness, overlap, base_span = _lookahead_setup(mode, h, [None])
    armed, opened, closed = [False], [], []

    @contextlib.contextmanager
    def call_span(kind, name):
        if armed[0] and kind == "pull" and name == rev[2]:
            armed[0] = False  # rev[1]'s request is ahead by now
            raise _Abort()
        opened.append((kind, name))
        with base_span(kind, name):
            try:
                yield
            finally:
                closed.append((kind, name))

    try:
        driver = OverlappedStepDriver(client, harness, overlap=overlap,
                                      window=2, call_span=call_span)
        driver.prime()
        armed[0] = True
        with pytest.raises(_Abort):
            driver.step(*h.data(8, seed=900))
        assert ("pull", rev[1]) in opened
        assert sorted(opened) == sorted(closed)
        assert rev[1] not in driver._raw
        assert _drain(ps.arena) == 0
        harness.bwd_done.clear()
        driver.step(*h.data(8, seed=901))
        assert set(driver._raw) == set(h.names)
        assert sorted(opened) == sorted(closed)
        assert _drain(ps.arena) == 0
    finally:
        client.close()
        ps.stop()


@pytest.mark.parametrize("mode", ["serial", "overlapped_tail"])
def test_tail_pull_rows_and_stages_stay_per_call(overlap_env, mode):
    """Each name has one pull row per step, issue before done; traced,
    each pull's ``rpc`` and ``device_put`` stages sit on its own
    ``bench.pull`` span (none on a node span), a pull sent ahead opens
    its span inside its predecessor's node, and the pull node spans
    close in order."""
    from brpc_tpu.observability import tracing
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    ps, client, h = _fresh_pair(sizes=(24, 32, 32, 32, 16))
    rev = list(reversed(h.names))
    index = {n: i for i, n in enumerate(h.names)}
    harness, overlap, base_span = _lookahead_setup(mode, h, [None])
    rows = []

    @contextlib.contextmanager
    def call_span(kind, name):
        with base_span(kind, name):
            with tracing.trace_span(f"bench.{kind}/{index[name]}"):
                t0 = time.monotonic()
                yield
                t1 = time.monotonic()
        rows.append((kind, name, t0, t1))

    tracing.rpcz_enable(True)
    old_n = tracing.rpcz_sample_1_in_n()
    tracing.rpcz_set_sample_1_in_n(1)
    try:
        driver = OverlappedStepDriver(client, harness, overlap=overlap,
                                      window=2, call_span=call_span)
        driver.prime()
        for step in range(2):
            rows.clear()
            harness.bwd_done.clear()
            driver.step(*h.data(8, seed=800 + step))
            pulls = [(n, t0, t1) for k, n, t0, t1 in rows if k == "pull"]
            assert sorted(n for n, _t0, _t1 in pulls) == sorted(h.names)
            assert all(t0 <= t1 for _n, t0, t1 in pulls)
            assert driver.last_stats["pulls_ahead"] == len(h.names) - 1
        spans = tracing.dump_rpcz()
        last = max((s for s in spans if s["service_method"] == "train_step"),
                   key=lambda s: s["start_us"])["trace_id"]
        by_name = {s["service_method"]: s for s in spans
                   if s["trace_id"] == last}

        def notes(span, stage):
            return [a for a in span.get("annotations", [])
                    if a.startswith(stage + "=")]

        for name in h.names:
            call = by_name[f"bench.pull/{index[name]}"]
            assert len(notes(call, "device_put")) == 1, call
            assert len(notes(call, "rpc")) == 1, call
            assert notes(by_name[f"step/pull:{name}"], "device_put") == []
        nodes = [by_name[f"step/pull:{n}"] for n in rev]
        for prev, nxt, node in zip(nodes, nodes[1:], rev[1:]):
            assert prev["end_us"] <= nxt["start_us"]
            assert by_name[f"bench.pull/{index[node]}"]["start_us"] <= \
                prev["end_us"]
    finally:
        tracing.rpcz_set_sample_1_in_n(old_n)
        client.close()
        ps.stop()
