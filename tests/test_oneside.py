"""One-sided tensor reads (ISSUE 11 acceptance surface).

Pure half (tier-1, no native lib):
  * the one-sided payload framing is byte-identical to the Pull RPC's
    self-describing wire form, so the two paths cannot return different
    values for one committed version;
  * the miss/gone exception contract the fallback routing keys on.

Native half (skips cleanly without libbrpc_tpu.so), under an ARMED stall
watchdog so a wedge in the new memory-semantics paths becomes a stall
dump:
  * publish/map/read round trip + stats, and the Meta-negotiated
    ParameterClient path: one-sided pulls bit-for-bit equal to the RPC
    path, raw AND quantized (the published region holds the encoded wire
    form);
  * torn-read retry under concurrent republish hammering — every
    successful read is internally consistent and versions never go
    backwards (the seqlock descriptor pin);
  * epoch reclamation never frees a range mid-read — large payloads
    hammered by republish stay uniform, and retired ranges DO drain once
    readers quiesce (the reclamation actually reclaims);
  * off-host/unmapped/unpublished fallback: bit-for-bit parity with the
    two-sided RPC path, counted in oneside_pull_fallbacks;
  * PUBLISH/READ_BEGIN/READ_RETRY/RECLAIM flight events on the recorder;
  * the doorbell-free input polling flag (rpc_input_poll_us) round-trips
    and echoes stay correct while armed;
  * serving KV pages are publishable: a mid-decode one-sided read of a
    session's plane matches the live KV bytes at version == rows filled,
    and release unpublishes.
"""

import json
import threading
import time

import numpy as np
import pytest

from brpc_tpu.runtime import codec as codec_mod
from brpc_tpu.runtime.tensor import (OnesideGone, OnesideMiss,
                                     consume_oneside_payload)

# ---------------------------------------------------------------------------
# Pure tests (no native lib).
# ---------------------------------------------------------------------------


def test_oneside_payload_framing_matches_rpc_wire():
    """A published payload is pack_header(meta)+bytes — decoding it with
    consume_oneside_payload reproduces the array exactly, for the same
    header framing the Pull RPC ships (codec.pack_header is the single
    home of that framing)."""
    arr = np.arange(48, dtype=np.float32).reshape(6, 8)
    payload = codec_mod.pack_header(
        {"dtype": arr.dtype.str, "shape": list(arr.shape)}
    ) + arr.tobytes()
    out = consume_oneside_payload(payload, to_host=True)
    assert out.dtype == arr.dtype and out.shape == arr.shape
    assert np.array_equal(out, arr)
    # Detached: the returned array must not alias the payload bytes.
    assert out.flags.owndata or out.base is None


def test_pad_header64_property():
    """Published headers pad to a 64-byte multiple so the payload behind
    them starts 64B-aligned (the zero-copy device_put alias condition);
    the padded header still decodes to the same meta with no payload
    bytes consumed."""
    from brpc_tpu.runtime.tensor import _decode_meta_ex, pad_header64

    for meta in ({"dtype": "<f4", "shape": [3]},
                 {"dtype": "<f4", "shape": list(range(1, 24))},
                 {"dtype": "<f4", "shape": [64, 64], "codec": "int8",
                  "block": 256}):
        padded = pad_header64(codec_mod.pack_header(meta))
        assert len(padded) % 64 == 0
        m2, rest = _decode_meta_ex(padded + b"\x01\x02")
        assert m2 == meta
        assert rest == b"\x01\x02"


def test_oneside_miss_contract():
    """OnesideGone (permanent fallback) IS an OnesideMiss (transient
    fallback) — callers that only catch the base class still fall back;
    only the routing layer distinguishes them."""
    m = OnesideMiss("w", 2)
    g = OnesideGone("w", 3)
    assert isinstance(g, OnesideMiss)
    assert (m.status, g.status) == (2, 3)
    with pytest.raises(OnesideMiss):
        raise g


# ---------------------------------------------------------------------------
# Native tests, under an armed watchdog.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def oneside_env(tmp_path_factory):
    from conftest import require_native_lib
    require_native_lib()
    from brpc_tpu.observability import health
    dump_dir = tmp_path_factory.mktemp("oneside_dumps")
    health.start_watchdog(str(dump_dir))
    yield {"health": health}
    deadline = time.monotonic() + 10
    while health.state() == "stalled" and time.monotonic() < deadline:
        time.sleep(0.1)
    assert health.state() != "stalled", (
        f"scheduler stalled after oneside tests; dump: "
        f"{health.last_dump_path()}")


def _stage_payload(arena, arr: np.ndarray):
    """Write [header|bytes] into a fresh arena range -> (off, total)."""
    header = codec_mod.pack_header({"dtype": arr.dtype.str,
                                    "shape": list(arr.shape)})
    raw = np.ascontiguousarray(arr).reshape(-1).view(np.uint8)
    total = len(header) + raw.nbytes
    off = arena.alloc(total)
    view = arena.view(off, total)
    view[:len(header)] = np.frombuffer(header, np.uint8)
    view[len(header):] = raw
    return off, total


def test_publish_map_read_roundtrip_and_stats(oneside_env):
    from brpc_tpu.runtime.tensor import (OnesideReader, OnesideWindow,
                                         TensorArena, oneside_stats)

    arena = TensorArena(8 << 20)
    win = OnesideWindow(arena, n_slots=8, n_readers=4)
    before = oneside_stats()
    arr = np.arange(1000, dtype=np.float32)
    off, total = _stage_payload(arena, arr)
    win.publish("t0", off, total, version=7)

    desc = win.describe()
    assert desc["shm"].startswith("/brpctpu_") and desc["dir_off"] >= 0
    rd = OnesideReader.map(desc)
    assert rd is not None
    v, payload = rd.read("t0")
    assert v == 7
    assert np.array_equal(consume_oneside_payload(payload, to_host=True),
                          arr)
    # The owned-buffer hot path (stat + read_into): one memcpy into a
    # 64B-aligned caller buffer, decoded in place.
    v2, owned = rd.read_np("t0")
    assert v2 == 7 and owned.ctypes.data % 64 == 0
    assert owned.tobytes() == payload
    assert np.array_equal(consume_oneside_payload(owned, to_host=True), arr)
    # Unknown name -> transient miss; after unpublish the slot misses too.
    with pytest.raises(OnesideMiss):
        rd.read("nope")
    assert win.unpublish("t0")
    with pytest.raises(OnesideMiss):
        rd.read("t0")
    # Token mismatch fails the map closed (the cross-host guard).
    bad = dict(desc)
    bad["token"] = desc["token"] ^ 1
    assert OnesideReader.map(bad) is None
    after = oneside_stats()
    assert after["publishes"] >= before["publishes"] + 1
    assert after["reads"] >= before["reads"] + 1
    rd.close()
    # Window destruction flips every later read to GONE (permanent
    # fallback), not garbage.
    rd2 = OnesideReader.map(desc)
    win.close()
    with pytest.raises(OnesideGone):
        rd2.read("t0")
    rd2.close()
    arena.close()


@pytest.fixture(scope="module")
def oneside_server(oneside_env):
    import jax

    from brpc_tpu.runtime.param_server import ParameterServer

    params = {
        "w": jax.numpy.arange(4096, dtype=jax.numpy.float32).reshape(64, 64),
        "b": jax.numpy.ones((129,), dtype=jax.numpy.float32),
        "tiny": jax.numpy.arange(4, dtype=jax.numpy.float32),
    }
    srv = ParameterServer(params, oneside=True)
    port = srv.start()
    yield {"srv": srv, "addr": f"127.0.0.1:{port}", "params": params}
    srv.stop()


def _counters():
    from brpc_tpu.observability import metrics as obs
    return (obs.counter("oneside_pull_hits"),
            obs.counter("oneside_pull_fallbacks"))


def test_oneside_pull_parity_with_rpc(oneside_server):
    from brpc_tpu.runtime.param_server import ParameterClient

    hits, _ = _counters()
    c_one = ParameterClient(f"tpu://{oneside_server['addr']}", oneside=True)
    c_rpc = ParameterClient(f"tpu://{oneside_server['addr']}")
    h0 = hits.value()
    try:
        for name in ("w", "b", "tiny"):
            v1, a1 = c_one.pull(name)
            v2, a2 = c_rpc.pull(name)
            assert v1 == v2
            assert np.array_equal(np.asarray(a1), np.asarray(a2)), name
        assert hits.value() >= h0 + 3
        # Push advances the version; the one-sided path sees the SAME
        # committed bytes the RPC path serves.
        g = np.full((64, 64), 0.25, np.float32)
        newv = c_rpc.push_grad("w", g)
        v1, a1 = c_one.pull("w")
        v2, a2 = c_rpc.pull("w")
        assert v1 == newv == v2
        assert np.array_equal(np.asarray(a1), np.asarray(a2))
        # pull_all: every name rides the window (no RPC needed), equal to
        # the RPC pull_all bit for bit.
        one = c_one.pull_all()
        rpc = c_rpc.pull_all()
        assert sorted(one) == sorted(rpc)
        for name in one:
            assert one[name][0] == rpc[name][0]
            assert np.array_equal(np.asarray(one[name][1]),
                                  np.asarray(rpc[name][1])), name
    finally:
        c_one.close()
        c_rpc.close()


def test_oneside_quantized_publication_parity(oneside_env):
    """oneside_codec publishes the ENCODED wire form; the reader's decode
    rides the same self-describing header (and _dequant path) the RPC
    codec pull uses — values match the negotiated RPC pull exactly."""
    import jax

    from brpc_tpu.runtime.param_server import ParameterClient, ParameterServer

    params = {"q": jax.numpy.asarray(
        np.linspace(-3, 3, 64 * 64, dtype=np.float32).reshape(64, 64))}
    srv = ParameterServer(params, oneside=True, oneside_codec="int8")
    port = srv.start()
    c_one = ParameterClient(f"tpu://127.0.0.1:{port}", oneside=True,
                            codec="int8")
    c_rpc = ParameterClient(f"tpu://127.0.0.1:{port}", codec="int8")
    try:
        v1, a1 = c_one.pull("q")
        v2, a2 = c_rpc.pull("q")
        assert v1 == v2
        a1, a2 = np.asarray(a1), np.asarray(a2)
        # Both decoded the same deterministic int8 encode of the same
        # committed bytes.
        assert np.array_equal(a1, a2)
        # And the codec really engaged: quantized, not raw.
        host = np.asarray(params["q"])
        assert not np.array_equal(a1, host)
        assert np.max(np.abs(a1 - host)) <= np.max(np.abs(host)) / 2
    finally:
        c_one.close()
        c_rpc.close()
        srv.stop()


def test_torn_read_retry_under_republish_hammer(oneside_env):
    """Concurrent republish hammering: every successful read is
    INTERNALLY CONSISTENT (payload uniformly stamped with its version)
    and versions never go backwards. Torn descriptor snapshots surface
    as retries/misses, never as mixed bytes."""
    from brpc_tpu.runtime.tensor import (OnesideReader, OnesideWindow,
                                         TensorArena, oneside_stats)

    arena = TensorArena(32 << 20)
    win = OnesideWindow(arena, n_slots=4, n_readers=4)
    n = 64 << 10  # 64KB payloads: long enough copies to race republishes

    def publish(version):
        fill = np.uint8(version % 251)
        arr = np.full(n, fill, np.uint8)
        off, total = _stage_payload(arena, arr)
        win.publish("h", off, total, version)

    publish(0)
    desc = win.describe()
    stop = threading.Event()
    published = [0]

    def hammer():
        v = 0
        while not stop.is_set():
            v += 1
            publish(v)
            published[0] = v

    t = threading.Thread(target=hammer, daemon=True)
    t.start()
    rd = OnesideReader.map(desc)
    assert rd is not None
    ok = torn = 0
    last_v = -1
    deadline = time.monotonic() + 2.0
    try:
        while time.monotonic() < deadline:
            try:
                v, payload = rd.read("h")
            except OnesideMiss:
                torn += 1
                continue
            arr = consume_oneside_payload(payload, to_host=True)
            # Uniformity is the torn-read detector: a read that mixed two
            # publications (or a reclaimed-and-reused range) cannot be
            # uniform AND stamped with its own version.
            assert arr.dtype == np.uint8 and arr.shape == (n,)
            u = np.unique(arr)
            assert u.size == 1, f"torn read: {u[:8]} at version {v}"
            assert int(u[0]) == v % 251, f"version/body mismatch v={v}"
            assert v >= last_v, f"version went backwards {last_v} -> {v}"
            last_v = v
            ok += 1
    finally:
        stop.set()
        t.join(timeout=10)
    assert ok > 50, (ok, torn)  # the path actually served under fire
    assert published[0] > 50    # and the publisher actually hammered
    st = oneside_stats()
    assert st["reclaims"] > 0   # displaced ranges were reclaimed live
    rd.close()
    win.close()
    arena.close()


def test_epoch_reclamation_never_frees_midread_and_drains(oneside_env):
    """Large (4MB) payloads under republish fire: the epoch pin keeps
    every range a reader is traversing unreclaimed (uniform bytes prove
    it — a freed range would be reallocated and rewritten mid-copy), and
    once the reader quiesces the retired backlog drains instead of
    leaking the arena."""
    from brpc_tpu.runtime.tensor import (OnesideReader, OnesideWindow,
                                         TensorArena, oneside_stats)

    arena = TensorArena(128 << 20)
    win = OnesideWindow(arena, n_slots=2, n_readers=2)
    n = 4 << 20

    def publish(version):
        arr = np.full(n, np.uint8(version % 251), np.uint8)
        off, total = _stage_payload(arena, arr)
        win.publish("big", off, total, version)

    publish(0)
    desc = win.describe()
    stop = threading.Event()

    def hammer():
        v = 0
        while not stop.is_set():
            v += 1
            publish(v)

    t = threading.Thread(target=hammer, daemon=True)
    t.start()
    rd = OnesideReader.map(desc)
    ok = 0
    deadline = time.monotonic() + 2.0
    try:
        while time.monotonic() < deadline:
            try:
                v, payload = rd.read("big")
            except OnesideMiss:
                continue
            arr = np.frombuffer(payload[len(payload) - n:], np.uint8)
            u = np.unique(arr)
            assert u.size == 1, f"mid-read reclaim: mixed bytes at v={v}"
            assert int(u[0]) == v % 251
            ok += 1
    finally:
        stop.set()
        t.join(timeout=10)
    assert ok > 3
    rd.close()  # reader quiesces; its pin no longer blocks reclamation
    publish(10_000_000)  # one more publish runs a reclaim pass
    st = oneside_stats()
    wins = {w["dir_off"]: w for w in st["windows"]}
    mine = wins[win.describe()["dir_off"]]
    # The retired backlog is bounded (at most the ranges displaced since
    # the last pass), not the whole hammer history.
    assert mine["retired_ranges"] <= 2, mine
    win.close()
    arena.close()


def test_fallback_parity_unmapped_and_unpublished(oneside_server,
                                                 monkeypatch):
    """Every fallback reason lands on the RPC path with bit-for-bit the
    same result: (a) map failure (the off-host shape — OnesideReader.map
    returns None), (b) a server that never advertised one-sided, (c) an
    unpublished name on a mapped window."""
    from brpc_tpu.runtime import tensor as tensor_mod
    from brpc_tpu.runtime.param_server import ParameterClient

    _, fallbacks = _counters()
    addr = oneside_server["addr"]
    c_rpc = ParameterClient(f"tpu://{addr}")
    ref = {n: c_rpc.pull(n) for n in ("w", "b")}

    # (a) unmappable window: monkeypatch map to fail like off-host does.
    monkeypatch.setattr(tensor_mod.OnesideReader, "map",
                        classmethod(lambda cls, desc: None))
    f0 = fallbacks.value()
    c_off = ParameterClient(f"tpu://{addr}", oneside=True)
    try:
        for n, (rv, ra) in ref.items():
            v, a = c_off.pull(n)
            assert v == rv
            assert np.array_equal(np.asarray(a), np.asarray(ra))
        assert fallbacks.value() > f0
        out = c_off.pull_all(["w", "b"])
        for n in ref:
            assert np.array_equal(np.asarray(out[n][1]),
                                  np.asarray(ref[n][1]))
    finally:
        c_off.close()
    monkeypatch.undo()

    # (c) unpublished name on a live mapping: the window no longer
    # carries "b", pulls of it fall back, "w" stays one-sided.
    srv = oneside_server["srv"]
    assert srv._oneside_window.unpublish("b")
    c_one = ParameterClient(f"tpu://{addr}", oneside=True)
    try:
        v, a = c_one.pull("b")
        assert np.array_equal(np.asarray(a), np.asarray(ref["b"][1]))
        v, a = c_one.pull("w")
        assert np.array_equal(np.asarray(a), np.asarray(ref["w"][1]))
    finally:
        c_one.close()
        # Republish for later tests.
        with srv._update_locks["b"]:
            srv._publish_oneside("b")
        c_rpc.close()


def test_oneside_disabled_server_negotiates_off(oneside_env):
    """Against a server that never advertised "oneside" the client asks
    nothing extra (the negotiation discipline) and serves every pull via
    RPC."""
    import jax

    from brpc_tpu.runtime.param_server import ParameterClient, ParameterServer

    srv = ParameterServer({"x": jax.numpy.ones((64,),
                                               dtype=jax.numpy.float32)})
    port = srv.start()
    c = ParameterClient(f"tpu://127.0.0.1:{port}", oneside=True)
    try:
        v, a = c.pull("x")
        assert np.array_equal(np.asarray(a), np.ones((64,), np.float32))
        assert c._oneside_reader is False  # parked on the RPC path
    finally:
        c.close()
        srv.stop()


def test_flight_events_cover_publication_lifecycle(oneside_env):
    from brpc_tpu.runtime.tensor import OnesideWindow, TensorArena

    health = oneside_env["health"]
    arena = TensorArena(8 << 20)
    win = OnesideWindow(arena, n_slots=4, n_readers=2)
    arr = np.ones(4096, np.uint8)
    for v in range(3):
        off, total = _stage_payload(arena, arr)
        win.publish("fl", off, total, v)
    from brpc_tpu.runtime.tensor import OnesideReader
    rd = OnesideReader.map(win.describe())
    rd.read("fl")
    text = health.flight_snapshot(4096)
    assert "ONESIDE_PUBLISH" in text
    assert "ONESIDE_READ_BEGIN" in text
    assert "ONESIDE_RECLAIM" in text  # the displaced v0/v1 ranges
    rd.close()
    win.close()
    arena.close()


def test_input_poll_flag_roundtrip_and_echo(oneside_env):
    """The doorbell-free polling flag reloads at runtime and echoes stay
    correct while armed."""
    from brpc_tpu.runtime import native

    L = native.lib()
    assert L.tbrpc_flag_set(b"rpc_input_poll_us", b"200") == 0
    try:
        srv = native.Server()
        srv.add_echo_service()
        port = srv.start("127.0.0.1:0")
        ch = native.Channel(f"tpu://127.0.0.1:{port}", timeout_ms=5000)
        for i in range(50):
            payload = f"poll-{i}".encode()
            out, _ = ch.call("EchoService/Echo", payload)
            assert out == payload
        ch.close()
        srv.stop()
    finally:
        assert L.tbrpc_flag_set(b"rpc_input_poll_us", b"0") == 0
    # Validator rejects nonsense.
    assert L.tbrpc_flag_set(b"rpc_input_poll_us", b"-5") != 0


def test_serving_kv_pages_publishable(oneside_env):
    """The serving tenant: KV planes published (not-owned) at version ==
    rows filled; a one-sided reader sees exactly the live plane bytes
    mid-decode; release unpublishes before the range can be reused."""
    from brpc_tpu.runtime.tensor import OnesideReader
    from brpc_tpu.serving.engine import DecodeEngine
    from brpc_tpu.serving.session import CallableSink, SessionManager

    mgr = SessionManager(max_len=16, dim=8, publish_kv=True)
    assert mgr.oneside is not None
    eng = DecodeEngine(mgr, max_batch=2)
    sess = mgr.open([1, 2, 3], 8, CallableSink(lambda f: None))
    for _ in range(4):
        eng.step()
    rd = OnesideReader.map(mgr.oneside.describe())
    assert rd is not None
    v, payload = rd.read(f"kv:{sess.id}:k")
    assert v == sess.pos  # version = rows filled
    arr = np.frombuffer(payload, np.float32).reshape(16, 8)
    assert np.array_equal(arr, np.asarray(sess.kv_k))
    assert arr[:sess.pos].any()  # real rows, not the zero init
    # Run to completion: the lane sweep releases + unpublishes.
    for _ in range(40):
        eng.step()
    with pytest.raises(OnesideMiss):
        rd.read(f"kv:{sess.id}:k")
    rd.close()


def test_oneside_stats_json_document(oneside_env):
    from brpc_tpu.runtime.tensor import oneside_stats

    st = oneside_stats()
    for key in ("publishes", "reads", "read_retries", "reads_torn",
                "reclaims", "reader_evictions", "windows"):
        assert key in st
    assert isinstance(st["windows"], list)
    json.dumps(st)  # round-trips
