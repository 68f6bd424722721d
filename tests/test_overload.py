"""Multi-tenant overload protection (ISSUE 9 acceptance surface).

Pure-Python half (runs in tier-1 with no native build):
  * RpcError classifies ELIMIT/EOVERCROWDED as `overloaded` and parses the
    " (retry_after_ms=N)" hint shed responses carry;
  * OverloadPacer paces on the hint, escalates an exponential floor when
    sheds repeat without one, and heals instantly on success;
  * the tstd QoS wire fields are structurally pinned: an unmarked request
    serializes byte-identically to the pre-QoS meta layout (flag bit
    clear, not one extra byte), a stamped one carries priority + tenant
    behind kTstdFlagHasQos.

Native half (skips cleanly without libbrpc_tpu.so), under an ARMED stall
watchdog so a hang in the new admission path becomes a stall dump:
  * priority lanes: HIGH-lane latency stays at the (injected) service time
    while BULK saturates the gate at >10x capacity and sheds;
  * per-tenant quotas: a greedy tenant's overflow sheds with ELIMIT + a
    retry_after_ms hint BEFORE it can crowd out another tenant, and the
    /tenantz counters account for every decision;
  * deadline propagation: a nested RPC issued from a Python handler is
    clamped to min(own timeout, parent remaining); an expired parent
    deadline sheds at admission with the handler NEVER run;
  * shed-storm pacing: a hot-retrying FleetClient against an overloaded
    shard issues a BOUNDED number of attempts (measured via the server's
    per-tenant counters), not a hot loop.
"""

import socket
import struct
import threading
import time

import pytest

from brpc_tpu.runtime import native

pytestmark = []

BULK_PAYLOAD = b"x" * 8192  # > ici_small_msg_threshold: never batchable


# ---------------------------------------------------------------------------
# Tier-1 pure-Python half.
# ---------------------------------------------------------------------------

def test_rpc_error_overload_classification():
    e = native.RpcError(1011, "bulk lane shed (retry_after_ms=37)")
    assert e.overloaded
    assert e.retry_after_ms == 37
    assert "overloaded" in str(e)  # surfaced distinctly
    e2 = native.RpcError(2006, "write queue full")
    assert e2.overloaded and e2.retry_after_ms is None
    e3 = native.RpcError(2041, "moved:127.0.0.1:1")
    assert not e3.overloaded and "overloaded" not in str(e3)


def test_overload_pacer_hint_backoff_and_heal():
    from brpc_tpu.runtime.param_server import OverloadPacer

    p = OverloadPacer()
    t0 = time.monotonic()
    owed = p.note(native.RpcError(1011, "shed (retry_after_ms=50)"))
    assert 0.03 <= owed <= 0.06, owed
    # pace() sleeps out the debt
    p.pace()
    assert time.monotonic() - t0 >= 0.045
    # hint-less sheds escalate the exponential floor
    d1 = p.note(native.RpcError(2006, "write queue full"))
    d2 = p.note(native.RpcError(2006, "write queue full"))
    assert d2 >= d1 > 0
    # non-overload errors leave the pacer alone
    assert p.note(native.RpcError(2041, "moved:x")) == 0.0
    assert p.sheds == 3
    p.clear()
    t1 = time.monotonic()
    p.pace()
    assert time.monotonic() - t1 < 0.01  # healed: no debt left


# ---------------------------------------------------------------------------
# Wire pin: the QoS meta fields cost zero bytes until stamped. A raw TCP
# listener captures exactly what the native client sends.
# ---------------------------------------------------------------------------

def _capture_request_frame(priority=None, tenant=""):
    """Point a native Channel at a raw socket; return the request bytes."""
    from conftest import require_native_lib
    require_native_lib()

    lsock = socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    port = lsock.getsockname()[1]
    captured = {}

    def accept():
        conn, _ = lsock.accept()
        conn.settimeout(2)
        buf = b""
        try:
            while len(buf) < 12:
                buf += conn.recv(4096)
            meta_size, body_size = struct.unpack_from("<II", buf, 4)
            want = 12 + meta_size + body_size
            while len(buf) < want:
                buf += conn.recv(4096)
        except socket.timeout:
            pass
        captured["frame"] = buf
        conn.close()

    t = threading.Thread(target=accept)
    t.start()
    ch = native.Channel(f"127.0.0.1:{port}", timeout_ms=300, max_retry=0)
    try:
        if priority is None:
            ch.call("Svc/Method", b"payload")
        else:
            with native.qos(priority, tenant):
                ch.call("Svc/Method", b"payload")
    except native.RpcError:
        pass  # nobody answers; the request bytes are what we want
    t.join()
    ch.close()
    lsock.close()
    return captured["frame"]


def _parse_meta_layout(frame):
    """-> (flags, meta_size, fields...) walking the documented layout."""
    assert frame[:4] == b"TRPC"
    meta_size, body_size = struct.unpack_from("<II", frame, 4)
    meta = frame[12:12 + meta_size]
    off = 0
    msg_type, compress = struct.unpack_from("<BB", meta, off); off += 2
    (flags,) = struct.unpack_from("<H", meta, off); off += 2
    off += 8 + 4 + 4 + 8 + 8 + 8  # cid, att_size, timeout, trace/span/parent
    out = {"flags": flags, "meta_size": meta_size, "body_size": body_size,
           "msg_type": msg_type}
    assert off == 44
    if flags & 1:  # stream
        off += 16
    if flags & 2:  # checksum
        off += 4
    if flags & 4:  # qos
        (out["priority"],) = struct.unpack_from("<B", meta, off); off += 1
        (tlen,) = struct.unpack_from("<H", meta, off); off += 2
        out["tenant"] = meta[off:off + tlen].decode(); off += tlen
    (slen,) = struct.unpack_from("<H", meta, off); off += 2
    out["service"] = meta[off:off + slen].decode(); off += slen
    (mlen,) = struct.unpack_from("<H", meta, off); off += 2
    out["method"] = meta[off:off + mlen].decode(); off += mlen
    out["consumed"] = off
    return out


def test_qos_unset_wire_is_byte_identical_to_pre_qos_layout():
    """No priority/tenant set: the meta is EXACTLY the pre-QoS layout —
    flag bit clear, meta_size == 44 + the two length-prefixed strings,
    nothing else on the wire (the negotiated-advertisement discipline,
    pinned like the codec A/B)."""
    frame = _capture_request_frame()
    m = _parse_meta_layout(frame)
    assert m["msg_type"] == 0
    assert m["flags"] == 0, m
    assert m["service"] == "Svc" and m["method"] == "Method"
    assert m["consumed"] == m["meta_size"] == (
        44 + 2 + len("Svc") + 2 + len("Method"))
    assert m["body_size"] == len(b"payload")


def test_qos_stamped_wire_carries_priority_and_tenant():
    frame = _capture_request_frame(priority=native.PRIORITY_BULK,
                                   tenant="trainer-7")
    m = _parse_meta_layout(frame)
    assert m["flags"] & 4, m
    assert m["priority"] == native.PRIORITY_BULK
    assert m["tenant"] == "trainer-7"
    assert m["service"] == "Svc" and m["method"] == "Method"
    assert m["consumed"] == m["meta_size"]


# ---------------------------------------------------------------------------
# Native half: the admission plane end to end, under an armed watchdog.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def overload_env(tmp_path_factory):
    from conftest import require_native_lib
    require_native_lib()
    from brpc_tpu.observability import health, metrics
    dump_dir = tmp_path_factory.mktemp("overload_dumps")
    health.start_watchdog(str(dump_dir))
    yield {"health": health, "metrics": metrics}
    native.inject_latency("", 0)  # clear every injection, whatever failed
    deadline = time.monotonic() + 10
    while health.state() == "stalled" and time.monotonic() < deadline:
        time.sleep(0.1)
    assert health.state() != "stalled", (
        f"scheduler stalled after overload tests; dump: "
        f"{health.last_dump_path()}")


def _var_value(metrics, name):
    for line in metrics.dump_vars(name).splitlines():
        if line.split(":")[0].strip() == name:
            return int(line.split(":")[1].strip())
    return 0


def test_priority_lane_keeps_control_plane_flat(overload_env):
    """BULK echo at >10x the gate's capacity: the HIGH lane's latency
    stays at the injected service time (no queueing, no sheds) while the
    BULK lane saturates and sheds — the overload-protection acceptance
    shape, in miniature."""
    srv = native.Server()
    srv.add_echo_service()
    srv.set_max_concurrency(4)
    port = srv.start()
    addr = f"127.0.0.1:{port}"
    native.inject_latency("EchoService", 100)
    stop = threading.Event()
    bulk_stats = {"ok": 0, "shed": 0}

    def bulk_loop():
        ch = native.Channel(addr, timeout_ms=4000, max_retry=0)
        while not stop.is_set():
            try:
                with native.qos(native.PRIORITY_BULK, "bulk"):
                    ch.call("EchoService/Echo", BULK_PAYLOAD)
                bulk_stats["ok"] += 1
            except native.RpcError as e:
                assert e.code in (1011, 2006), e
                bulk_stats["shed"] += 1
                time.sleep(0.005)
        ch.close()

    threads = [threading.Thread(target=bulk_loop) for _ in range(8)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.3)  # let bulk saturate the gate
        hc = native.Channel(addr, timeout_ms=4000, max_retry=0)
        lat_ms = []
        for _ in range(8):
            t0 = time.monotonic()
            with native.qos(native.PRIORITY_HIGH, "ctl"):
                hc.call("EchoService/Echo", b"hb")  # raises on any shed
            lat_ms.append((time.monotonic() - t0) * 1000)
            time.sleep(0.02)
        hc.close()
    finally:
        stop.set()
        for t in threads:
            t.join()
        native.inject_latency("", 0)
    # Every HIGH call admitted first try; latency == injected service time
    # plus noise headroom, NEVER a queueing multiple of it.
    assert max(lat_ms) < 2 * 100, lat_ms
    assert bulk_stats["shed"] > bulk_stats["ok"], bulk_stats
    srv.close()


def test_bulk_lane_leaves_a_tenth_of_the_gate(overload_env):
    """Gate 20: a 20-deep BULK burst is admitted up to 18 and the last 2
    shed at once (10% of the gate, 2 slots, is kept from BULK), while a
    HIGH call during the burst still lands in the reserved slots."""
    srv = native.Server()
    srv.add_echo_service()
    srv.set_max_concurrency(20)
    port = srv.start()
    addr = f"127.0.0.1:{port}"
    native.inject_latency("EchoService", 1000)
    results = []
    barrier = threading.Barrier(20)

    def bulk():
        ch = native.Channel(addr, timeout_ms=8000, max_retry=0)
        barrier.wait()
        try:
            with native.qos(native.PRIORITY_BULK, "bulk"):
                ch.call("EchoService/Echo", BULK_PAYLOAD)
            results.append(("ok", None))
        except native.RpcError as e:
            results.append(("shed", e))
        ch.close()

    threads = [threading.Thread(target=bulk) for _ in range(20)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.3)  # burst in flight (holding its 1 s injection)
        hc = native.Channel(addr, timeout_ms=8000, max_retry=0)
        with native.qos(native.PRIORITY_HIGH, "ctl"):
            hc.call("EchoService/Echo", b"hb")  # raises on any shed
        hc.close()
    finally:
        for t in threads:
            t.join()
        native.inject_latency("", 0)
    sheds = [e for kind, e in results if kind == "shed"]
    assert len(results) == 20 and len(sheds) == 2, results
    for e in sheds:
        assert e.code == 1011 and "bulk lane shed" in e.text, e.text
    srv.close()


def test_tenant_quota_sheds_greedy_before_others(overload_env):
    """Quota 2: a 6-deep burst from one tenant admits 2, sheds 4 with
    ELIMIT + retry_after_ms, instantly (shed-before-queue); another
    tenant's request is untouched. /tenantz accounts for every call."""
    srv = native.Server()
    srv.add_echo_service()
    srv.set_max_concurrency(16)
    srv.set_tenant_quota(2)
    port = srv.start()
    addr = f"127.0.0.1:{port}"
    native.inject_latency("EchoService", 300)
    results = []
    barrier = threading.Barrier(6)

    def greedy():
        ch = native.Channel(addr, timeout_ms=8000, max_retry=0)
        barrier.wait()
        t0 = time.monotonic()
        try:
            with native.qos(native.PRIORITY_BULK, "greedy"):
                ch.call("EchoService/Echo", BULK_PAYLOAD)
            results.append(("ok", time.monotonic() - t0, None))
        except native.RpcError as e:
            results.append(("shed", time.monotonic() - t0, e))
        ch.close()

    threads = [threading.Thread(target=greedy) for _ in range(6)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.1)  # burst in flight (holding its 300ms injection)
        oc = native.Channel(addr, timeout_ms=8000, max_retry=0)
        with native.qos(native.PRIORITY_HIGH, "polite"):
            oc.call("EchoService/Echo", b"hi")  # other tenant: admitted
        oc.close()
    finally:
        for t in threads:
            t.join()
        native.inject_latency("", 0)
    sheds = [r for r in results if r[0] == "shed"]
    oks = [r for r in results if r[0] == "ok"]
    assert len(oks) == 2 and len(sheds) == 4, results
    for _, dt, e in sheds:
        assert dt < 0.15, ("shed-before-queue means the reject is "
                           "immediate, not after queueing", dt)
        assert e.code == 1011 and e.overloaded
        assert e.retry_after_ms is not None, e.text
        assert "over quota" in e.text
    tz = srv.tenantz()
    by_name = {t["name"]: t for t in tz["tenants"]}
    assert by_name["greedy"]["admitted"] == 2
    assert by_name["greedy"]["shed"] == 4
    assert by_name["polite"]["admitted"] == 1
    assert by_name["polite"]["shed"] == 0
    assert tz["quota"] == 2
    srv.close()


def test_deadline_propagates_into_nested_rpc(overload_env):
    """A Python handler's remaining budget rides into the nested RPC it
    issues: the inner server observes min(inner channel's OWN 30s
    timeout, parent remaining) — i.e. far less than 30s."""
    inner = native.Server()

    def inner_handler(method, req, att):
        left = native.deadline_remaining_ms()
        return str(-1 if left is None else left).encode(), b""

    inner.add_service("Inner", inner_handler)
    iport = inner.start()
    ich = native.Channel(f"127.0.0.1:{iport}", timeout_ms=30000, max_retry=0)

    outer = native.Server()

    def outer_handler(method, req, att):
        mine = native.deadline_remaining_ms()
        time.sleep(0.1)  # burn visible budget before the nested hop
        r, _ = ich.call("Inner/Probe", b"")
        return f"{mine},{r.decode()}".encode(), b""

    outer.add_service("Outer", outer_handler)
    oport = outer.start()
    oc = native.Channel(f"127.0.0.1:{oport}", timeout_ms=1000, max_retry=0)
    r, _ = oc.call("Outer/Go", b"")
    mine_ms, inner_ms = (int(x) for x in r.decode().split(","))
    # The outer handler sees its client's ~1000ms budget...
    assert 700 <= mine_ms <= 1000, (mine_ms, inner_ms)
    # ...and the nested call is clamped to the REMAINING budget (~900ms
    # after the 100ms burn), not the inner channel's own 30s timeout.
    assert 400 <= inner_ms <= mine_ms - 80, (mine_ms, inner_ms)
    oc.close()
    ich.close()
    outer.close()
    inner.close()


def test_expired_parent_deadline_sheds_at_admission(overload_env):
    """Queueing (injected) burns the whole propagated budget: the server
    sheds at admission — the handler NEVER runs — and counts it."""
    metrics = overload_env["metrics"]
    calls = []
    srv = native.Server()

    def handler(method, req, att):
        calls.append(method)
        return b"", b""

    srv.add_service("Doomed", handler)
    port = srv.start()
    shed_before = _var_value(metrics, "rpc_shed_deadline")
    native.inject_latency("Doomed", 300)
    ch = native.Channel(f"127.0.0.1:{port}", timeout_ms=120, max_retry=0)
    with pytest.raises(native.RpcError):
        ch.call("Doomed/Go", b"")  # client's own deadline fires too
    # Give the server's delayed dispatch time to reach its shed point.
    deadline = time.monotonic() + 3
    while (_var_value(metrics, "rpc_shed_deadline") == shed_before
           and time.monotonic() < deadline):
        time.sleep(0.02)
    native.inject_latency("", 0)
    assert _var_value(metrics, "rpc_shed_deadline") > shed_before
    time.sleep(0.1)
    assert calls == [], "handler ran although its deadline had passed"
    ch.close()
    srv.close()


def test_qos_negotiation_rides_meta_advertisement(overload_env):
    """QoS stamping is NEGOTIATED like the codec advertisement: a
    ParameterClient stamps priority/tenant only after the server's Meta
    carried "qos": 1 (lazily fetched on the first stamped call) — a
    pre-QoS server, whose parser would reject the extra meta fields,
    never sees them; Meta itself always rides unstamped so it parses on
    any build."""
    import contextlib
    import numpy as np
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)

    srv = ParameterServer({"w0": np.ones((64,), np.float32)})
    port = srv.start()
    pc = ParameterClient(f"tpu://127.0.0.1:{port}", tenant="t9")
    assert pc._srv_qos is None  # nothing negotiated yet
    v, _arr = pc.pull("w0")     # first stamped call: lazy Meta fetch
    assert v == 0 and pc._srv_qos is True
    # Against a pre-QoS advertisement, every lane helper is a no-op
    # context — zero extra wire bytes (the byte-identity pin above).
    pc._srv_qos = False
    assert isinstance(pc._qos_bulk(), contextlib.nullcontext)
    assert isinstance(pc._qos_high(), contextlib.nullcontext)
    pc.close()
    srv.stop()


def test_fleet_shed_storm_is_paced(overload_env):
    """A FleetClient hammering an overloaded shard must NOT hot-retry:
    ELIMIT answers are retriable-with-backoff (honoring retry_after_ms),
    never counted as reshard evidence (no KeyError with stable
    membership), and the per-tenant counters bound the attempt rate."""
    from brpc_tpu.fleet import FleetClient, FleetServer, RegistryHub
    from brpc_tpu.fleet import clear_registry
    import numpy as np

    hub = RegistryHub()
    hub.start()
    try:
        shard = FleetServer(hub.hostport, tag="storm", shard_name="storm_s0",
                            ttl_s=3)
        shard.ps.server.set_max_concurrency(2)
        shard.ps.server.set_tenant_quota(1)
        shard.start()
        fc = FleetClient(hub.hostport, tag="storm", op_deadline_s=3.0,
                         tenant="stormy")
        fc.install("w0", np.ones((256,), np.float32))
        # Occupy the tenant's single slot with a slow pull from a second
        # thread, then hammer from the main one.
        native.inject_latency("ParamService", 250)
        t0 = time.monotonic()
        blocker = threading.Thread(
            target=lambda: fc.pull("w0"))
        blocker.start()
        time.sleep(0.05)
        v, arr = fc.pull("w0")  # retries through the sheds, paced
        elapsed = time.monotonic() - t0
        blocker.join()
        native.inject_latency("", 0)
        assert v == 0 and float(np.asarray(arr)[0]) == 1.0
        tz = shard.ps.server.tenantz()
        stormy = {t["name"]: t for t in tz["tenants"]}["stormy"]
        assert stormy["shed"] >= 1, tz
        # Bounded retry rate: a hot loop would have issued hundreds of
        # attempts in `elapsed`; pacing keeps total attempts small.
        attempts = stormy["admitted"] + stormy["shed"]
        assert attempts <= 30, (attempts, elapsed, tz)
        fc.close()
        shard.stop()
    finally:
        native.inject_latency("", 0)
        clear_registry()
        hub.stop()
