"""Quantized tensor wire format (brpc_tpu/runtime/codec.py + the codec
stage in tensor.py/param_server.py/fleet).

Pure-Python tests pin the codec math itself (round-trip error bounds,
error-feedback convergence, the Pallas kernel vs its jnp reference);
native tests drive the negotiated wire end to end under an ARMED stall
watchdog: pull/push parity vs raw, mixed raw/quant fleet negotiation,
the raw path's byte-identity when no codec is configured, and the
tensor_codec_* accounting on /vars + /tensorz + /rpcz.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

from brpc_tpu.runtime import codec

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from brpc_tpu.ops.quantize import (dequantize_blocks,  # noqa: E402
                                   dequantize_reference)


# ---------------------------------------------------------------------------
# Codec math (no native library needed).
# ---------------------------------------------------------------------------

def _rng(seed=0):
    return np.random.default_rng(seed)


def _block_max_errors(a, dq, block):
    err = np.abs(dq - a).reshape(-1)
    n = a.size
    out = []
    for b in range(-(-n // block)):
        out.append(err[b * block:min((b + 1) * block, n)].max())
    return np.array(out)


def test_int8_round_trip_error_bound():
    """Per-block max-abs error <= scale/2: the uniform-quantizer bound
    the parity tests below lean on."""
    for shape in [(300,), (64, 33), (1 << 18,)]:
        a = (_rng(1).normal(size=shape).astype(np.float32)
             * _rng(2).uniform(0.01, 100))
        enc = codec.encode(a, "int8", min_bytes=0)
        meta = {"dtype": a.dtype.str, "shape": list(a.shape),
                "codec": "int8", "block": enc.block}
        dq = codec.decode(meta, enc.wire)
        _q, scales = codec.split_wire(meta, enc.wire)
        bound = codec.error_bound(meta, scales)
        # float32 slack: x*inv and q*scale each round once, so the
        # exact scale/2 bound can be exceeded by ~1ulp-scaled amounts.
        assert (_block_max_errors(a, dq, enc.block)
                <= bound * (1 + 1e-4) + 1e-7).all()
        # ~3.9x fewer wire bytes at the default block size.
        assert a.nbytes / enc.wire_bytes > 3.8


def test_fp8e4m3_round_trip_error_bound():
    if "fp8e4m3" not in codec.supported_codecs():
        pytest.skip("ml_dtypes unavailable")
    a = _rng(3).normal(size=(1 << 16,)).astype(np.float32) * 5
    enc = codec.encode(a, "fp8e4m3", min_bytes=0)
    meta = {"dtype": a.dtype.str, "shape": list(a.shape),
            "codec": "fp8e4m3", "block": enc.block}
    dq = codec.decode(meta, enc.wire)
    _q, scales = codec.split_wire(meta, enc.wire)
    # 3 mantissa bits: half-ulp relative error 2**-4 at the block max
    # (error_bound documents the same).
    bound = codec.error_bound(meta, scales)
    assert (_block_max_errors(a, dq, enc.block)
            <= bound * (1 + 1e-4) + 1e-7).all()


def test_zero_and_constant_blocks_are_exact():
    a = np.zeros(4096, np.float32)
    enc = codec.encode(a, "int8", min_bytes=0)
    assert (enc.dequantized() == 0).all()
    b = np.full(4096, 7.5, np.float32)
    encb = codec.encode(b, "int8", min_bytes=0)
    # constant block: absmax maps to code 127 exactly -> exact round-trip
    np.testing.assert_allclose(encb.dequantized(), b, rtol=1e-6)


def test_eligibility_degrades_to_raw():
    """Per-tensor degrade: wrong dtype or below the size floor -> None
    (the caller stages raw bytes, headers carry no codec)."""
    assert codec.encode(np.ones(8, np.float32), "int8") is None  # tiny
    assert codec.encode(np.ones(1 << 16, np.float64), "int8") is None
    assert codec.encode(np.ones(1 << 16, np.int32), "int8") is None
    assert codec.encode(np.ones(1 << 16, np.float32), "nope") is None
    assert codec.encode(np.ones(1 << 16, np.float32), "int8") is not None


def test_negotiation_choose():
    assert codec.choose("int8", ("int8", "fp8e4m3")) == "int8"
    assert codec.choose("int8", ()) is None          # server: codecs off
    assert codec.choose("int8", None) is None        # server: pre-codec
    assert codec.choose(None, ("int8",)) is None     # client: raw
    assert codec.choose("made_up", ("made_up",)) is None  # unknown here


def test_error_feedback_accumulation_is_unbiased():
    """N quantized pushes of the SAME gradient with error feedback land
    within one quantization step of the fp32 sum — independent of N —
    while naive requantization compounds its bias linearly."""
    g = _rng(4).normal(size=(8192,)).astype(np.float32)
    ef = codec.ErrorFeedback()
    acc = np.zeros_like(g)
    n = 25
    for _ in range(n):
        x = ef.compensate("g", g)
        enc = codec.encode(x, "int8", min_bytes=0)
        dq = enc.dequantized()
        ef.settle("g", x, dq)
        acc += dq
    meta = {"dtype": "<f4", "shape": [g.size], "codec": "int8",
            "block": codec.DEFAULT_BLOCK}
    _q, scales = codec.split_wire(
        meta, codec.encode(g, "int8", min_bytes=0).wire)
    one_step = float(codec.error_bound(meta, scales).max())
    drift = float(np.abs(acc - n * g).max())
    assert drift <= 2 * one_step, (drift, one_step)
    naive = sum(codec.encode(g, "int8", min_bytes=0).dequantized()
                for _ in range(n))
    assert float(np.abs(naive - n * g).max()) > drift  # EF actually helps


def test_error_feedback_prune_drops_unkept_names():
    """prune(keep) frees the full-gradient-sized residuals of every name
    failing the predicate (the fleet reshard hook) and keeps the rest."""
    ef = codec.ErrorFeedback()
    g = np.ones(256, np.float32)
    for n in ("a", "b", "c"):
        ef.settle(n, g, g * 0.75)
    assert ef.prune(lambda n: n == "b") == 2
    assert ef.residual("a") is None
    assert ef.residual("c") is None
    np.testing.assert_array_equal(ef.residual("b"), g * 0.25)
    assert ef.prune(lambda n: True) == 0  # idempotent on kept names


def test_split_wire_is_zero_copy():
    a = _rng(5).normal(size=(4096,)).astype(np.float32)
    enc = codec.encode(a, "int8", min_bytes=0)
    meta = {"dtype": "<f4", "shape": [a.size], "codec": "int8",
            "block": enc.block}
    q, scales = codec.split_wire(meta, enc.wire)
    assert q.base is not None and scales.base is not None  # views, no copy
    qv = codec.QuantizedView(meta, enc.wire)
    dq = qv.dequantize()
    # Detached: consuming IS the detach (never aliases the wire bytes).
    assert not np.shares_memory(dq, enc.wire)
    np.testing.assert_array_equal(dq, enc.dequantized())


def test_raw_header_byte_identical():
    """The A/B pin for 'raw unchanged': the metadata header when no codec
    runs is byte-for-byte the pre-codec encoder's output."""
    from brpc_tpu.runtime.tensor import _decode_meta_ex, _encode_meta

    a = np.ones((16, 8), np.float32)
    legacy = json.dumps({"dtype": a.dtype.str, "shape": list(a.shape)})
    import struct
    assert _encode_meta(a) == struct.pack("<I", len(legacy)) + \
        legacy.encode()
    meta, rest = _decode_meta_ex(_encode_meta(a) + b"tail")
    assert "codec" not in meta and rest == b"tail"


# ---------------------------------------------------------------------------
# Device dequant kernel (Pallas on TPU; interpret mode + jnp reference here).
# ---------------------------------------------------------------------------

def test_dequantize_reference_matches_numpy():
    a = _rng(6).normal(size=(1000,)).astype(np.float32)
    enc = codec.encode(a, "int8", min_bytes=0)
    meta = {"dtype": "<f4", "shape": [a.size], "codec": "int8",
            "block": enc.block}
    q, scales = codec.split_wire(meta, enc.wire)
    ref = dequantize_reference(jnp.asarray(q), jnp.asarray(scales),
                               block=enc.block, n=a.size, shape=(a.size,))
    np.testing.assert_allclose(np.asarray(ref), codec.decode(meta, enc.wire),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("name,n,block", [
    ("int8", 40 * 256, 256),
    ("int8", 1_000_003, 100),
    ("fp8e4m3", 1_000_003, 100),
], ids=["int8-b256", "int8-odd-b100", "fp8-odd-b100"])
def test_pallas_dequant_kernel_parity_interpret(name, n, block):
    """The compiled-path kernel evaluated tile-by-tile through the
    interpreter == the jnp reference (same discipline as
    fused_momentum_update's kernel test) — including a peer's block that
    is not a multiple of 128 with a ragged tail, which the chip runs
    through the kernel too."""
    a = _rng(7).normal(size=(n,)).astype(np.float32)
    enc = codec.encode(a, name, block=block, min_bytes=0)
    meta = {"dtype": "<f4", "shape": [a.size], "codec": name,
            "block": block}
    q, scales = codec.split_wire(meta, enc.wire)
    got = dequantize_blocks(jnp.asarray(q), jnp.asarray(scales), block=block,
                            n=a.size, shape=(a.size,), interpret=True)
    ref = dequantize_reference(jnp.asarray(q), jnp.asarray(scales),
                               block=block, n=a.size, shape=(a.size,))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# Negotiated wire, end to end (native library; armed watchdog).
# ---------------------------------------------------------------------------

def test_detach_put_batch_and_widen_match_decode():
    """The shared dequant helpers (_detach_device_put_batch +
    _dequant_widen — the one home of the view-aliasing discipline, used
    by consume_pull_reply, the PullQ group decode and the server's
    quantized-push apply) reproduce codec.decode exactly."""
    from brpc_tpu.runtime.tensor import (_dequant_widen,
                                         _detach_device_put_batch)

    pairs, metas, refs = [], [], []
    for i, n in enumerate((1 << 12, 300)):
        a = _rng(i).normal(size=(n,)).astype(np.float32) * (i + 1)
        enc = codec.encode(a, "int8", min_bytes=0)
        meta = {"dtype": a.dtype.str, "shape": [n], "codec": "int8",
                "block": enc.block}
        q, s = codec.split_wire(meta, enc.wire)
        pairs.append((q, s))
        metas.append(meta)
        refs.append(codec.decode(meta, enc.wire))
    devs = _detach_device_put_batch(pairs, None)
    for i, meta in enumerate(metas):
        val = _dequant_widen(devs[2 * i], devs[2 * i + 1], meta["block"],
                             meta["shape"][0], meta["shape"],
                             want=meta["dtype"])
        np.testing.assert_array_equal(np.asarray(val), refs[i])


@pytest.fixture(scope="module")
def codec_env(tmp_path_factory):
    from conftest import require_native_lib
    require_native_lib()
    from brpc_tpu.observability import health

    dump_dir = tmp_path_factory.mktemp("codec_dumps")
    health.start_watchdog(str(dump_dir))
    yield {"health": health}
    deadline = time.monotonic() + 10
    while health.state() == "stalled" and time.monotonic() < deadline:
        time.sleep(0.1)
    assert health.state() != "stalled", (
        f"scheduler stalled after codec tests; dump: "
        f"{health.last_dump_path()}")


def _mk_params(n=4, elems=1 << 16, seed=0):
    rng = _rng(seed)
    return {f"w{i:02d}": jnp.asarray(
        rng.normal(size=(elems,)).astype(np.float32) * (i + 1))
        for i in range(n)}


def _assert_quant_close(raw, quant, block=codec.DEFAULT_BLOCK):
    """quantized result within the per-block int8 bound of the raw one."""
    a = np.asarray(raw).astype(np.float32).reshape(-1)
    b = np.asarray(quant).astype(np.float32).reshape(-1)
    enc = codec.encode(a.copy(), "int8", min_bytes=0)
    meta = {"dtype": "<f4", "shape": [a.size], "codec": "int8",
            "block": enc.block}
    _q, scales = codec.split_wire(meta, enc.wire)
    bound = codec.error_bound(meta, scales)
    errs = _block_max_errors(a, b.reshape(a.shape), enc.block)
    tol = bound * (1 + 1e-4) + 1e-6  # float32 slack on the exact bound
    assert (errs <= tol).all(), float((errs - bound).max())


def test_pull_negotiated_parity_and_raw_byte_identity(codec_env):
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)
    from brpc_tpu.runtime.tensor import _encode_meta

    params = _mk_params(2)
    ps = ParameterServer(params)
    port = ps.start()
    raw_client = ParameterClient(f"tpu://127.0.0.1:{port}")
    q_client = ParameterClient(f"tpu://127.0.0.1:{port}", codec="int8")
    try:
        # Server advertises; quant client negotiates; raw client doesn't.
        raw_client.meta()  # populates the advertisement cache
        assert "int8" in raw_client._srv_codecs
        assert q_client.negotiated_codec() == "int8"
        assert raw_client.negotiated_codec() is None

        # RAW BYTE-IDENTITY A/B: the codec-less pull's response header and
        # attachment are exactly the pre-codec bytes.
        payload, view = raw_client.channel.call_raw("ParamService/Pull",
                                                    b"w00")
        with view:
            host = np.asarray(params["w00"])
            assert payload.startswith(_encode_meta(host))
            assert payload[len(_encode_meta(host)):] == b"0"
            assert bytes(view.ndarray()) == host.tobytes()

        vr, raw = raw_client.pull("w00")
        vq, quant = q_client.pull("w00")
        assert vr == vq == 0
        np.testing.assert_array_equal(np.asarray(raw),
                                      np.asarray(params["w00"]))
        _assert_quant_close(raw, quant)

        # pull_all through the pipeline window: every tensor within bound.
        all_raw = raw_client.pull_all(window=4)
        all_q = q_client.pull_all(window=4)
        assert all_raw.keys() == all_q.keys() == params.keys()
        for name in params:
            assert all_raw[name][0] == all_q[name][0]
            _assert_quant_close(all_raw[name][1], all_q[name][1])
    finally:
        raw_client.close()
        q_client.close()
        ps.stop()


def test_codec_disabled_server_degrades_transparently(codec_env):
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)

    params = _mk_params(1, seed=1)
    ps = ParameterServer(params, codecs=())  # feature off server-side
    port = ps.start()
    client = ParameterClient(f"tpu://127.0.0.1:{port}", codec="int8")
    try:
        assert client.negotiated_codec() is None  # nothing advertised
        _v, arr = client.pull("w00")
        np.testing.assert_array_equal(np.asarray(arr),
                                      np.asarray(params["w00"]))  # bit-exact
        # Push degrades too: raw gradient, server math untouched by codec.
        g = np.ones_like(np.asarray(params["w00"]))
        assert client.push_grad("w00", g) == 1
    finally:
        client.close()
        ps.stop()


def test_quantized_push_with_error_feedback_tracks_raw_server(codec_env):
    """The same gradient sequence driven into two identical servers — one
    through raw pushes, one through quantized pushes with error feedback
    — must land within the documented tolerance (per-step quantization is
    bounded by scale/2 and EF keeps the SUM unbiased, so the trajectories
    cannot drift apart with step count)."""
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)

    w0 = _rng(8).normal(size=(1 << 15,)).astype(np.float32)
    grads = [_rng(100 + i).normal(size=w0.shape).astype(np.float32) * 0.1
             for i in range(8)]
    results = {}
    for mode, codec_name in (("raw", None), ("quant", "int8")):
        ps = ParameterServer({"w": jnp.asarray(w0)}, lr=0.05, momentum=0.9)
        port = ps.start()
        client = ParameterClient(f"tpu://127.0.0.1:{port}",
                                 codec=codec_name)
        for i, g in enumerate(grads):
            assert client.push_grad("w", g) == i + 1
        results[mode] = np.asarray(client.pull("w")[1])
        client.close()
        ps.stop()
    # Tolerance: sum of per-step bounds — each step's grad error <= lr *
    # (1/(1-beta)) * scale/2 with scale ~ max|g|/127; measured drift is
    # far below this, the assert leaves honest slack.
    scale = max(float(np.abs(g).max()) for g in grads) / 127.0
    tol = len(grads) * 0.05 * (1.0 / (1.0 - 0.9)) * (scale / 2) * 4
    drift = float(np.abs(results["quant"] - results["raw"]).max())
    assert drift <= tol, (drift, tol)


def test_quantized_training_matches_local_fp32(codec_env):
    """ACCEPTANCE: a model trained via quantized push/pull with error
    feedback stays within a documented tolerance of the fp32 local loop
    (the quantized twin of test_tensor_bridge's flagship assert)."""
    from brpc_tpu.ops.fused_update import fused_momentum_update
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)

    rng = np.random.default_rng(0)
    w0 = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    data_x = jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32))
    data_y = jnp.asarray(rng.normal(size=(128, 32)).astype(np.float32))

    def grad_fn(w):
        return jax.grad(lambda w_: jnp.mean((data_x @ w_ - data_y) ** 2))(w)

    ps = ParameterServer({"w": w0}, lr=0.05, momentum=0.9)
    port = ps.start()
    client = ParameterClient(f"tpu://127.0.0.1:{port}", codec="int8")
    try:
        w_local = w0
        m_local = jnp.zeros_like(w0)
        for step in range(5):
            version, w_remote = client.pull("w")
            assert version == step
            if step == 0:
                # First pull: server state == w0 exactly, so the gap is
                # pure quantization — within the per-block int8 bound.
                _assert_quant_close(w_local, w_remote)
            else:
                # Later steps accumulate bounded drift (grads computed on
                # quantized weights + EF-bounded push error) on top of
                # the pull quantization; the documented envelope holds.
                assert float(np.abs(np.asarray(w_remote) -
                                    np.asarray(w_local)).max()) < 5e-2
            client.push_grad("w", grad_fn(w_remote))
            w_local, m_local = fused_momentum_update(
                w_local, m_local, grad_fn(w_local), lr=0.05)
        # Documented tolerance: quantized pull error (scale/2 per block,
        # scale ~ max|w|/127) feeds the gradient through one smooth loss,
        # plus EF-bounded push error — measured drift ~1e-3 on this
        # 5-step loop; 5e-2 leaves honest slack without hiding breakage.
        _v, w_final = client.pull("w")
        assert float(np.abs(np.asarray(w_final) -
                            np.asarray(w_local)).max()) < 5e-2
    finally:
        client.close()
        ps.stop()


def test_mixed_fleet_negotiates_per_shard(codec_env):
    """A fleet where one shard speaks int8 and one is codec-disabled:
    the SAME FleetClient(codec="int8") pulls from both — quantized where
    advertised, raw where not, values correct either way."""
    from brpc_tpu.fleet import FleetClient, FleetServer, RegistryHub

    hub = RegistryHub()
    hub.start()
    s_quant = FleetServer(hub.hostport, tag="codecmix", ttl_s=5)
    s_raw = FleetServer(hub.hostport, tag="codecmix", ttl_s=5, codecs=())
    addr_q = s_quant.start()
    addr_raw = s_raw.start()
    fc = FleetClient(hub.hostport, tag="codecmix", codec="int8",
                     op_deadline_s=20.0)
    try:
        rng = _rng(9)
        fc.refresh()
        # Pick names until BOTH shards own some: placement is ketama
        # over the ephemeral server ports, and a fixed 6-name set lands
        # entirely on one shard in ~3% of port draws — the mixed-fleet
        # assertion needs tensors on each side by construction, not by
        # luck (flaked twice in full-suite runs before this).
        names, i = [], 0
        while i < 200 and (len(names) < 6 or len(
                {fc.map.owner(n) for n in names}) < 2):
            names.append(f"t{i}")
            i += 1
        seeds = {n: rng.normal(size=(1 << 14,)).astype(np.float32)
                 for n in names}
        for name, arr in seeds.items():
            fc.install(name, arr, refresh=False)
        placed = fc.meta()
        assert {v["shard"] for v in placed.values()} == {addr_q, addr_raw}
        got = fc.pull_all(sorted(seeds))
        assert got.keys() == seeds.keys()
        for name, (version, arr) in got.items():
            assert version == 0
            if placed[name]["shard"] == addr_raw:
                np.testing.assert_array_equal(np.asarray(arr), seeds[name])
            else:
                _assert_quant_close(seeds[name], arr)
        # Per-shard negotiation went the way the advertisement said.
        assert fc._client(addr_q).negotiated_codec() == "int8"
        assert fc._client(addr_raw).negotiated_codec() is None
    finally:
        fc.close()
        s_quant.stop()
        s_raw.stop()
        hub.stop()
        from brpc_tpu.fleet import clear_registry
        clear_registry()


def test_reshard_prunes_error_feedback_residuals(codec_env):
    """A reshard edge drops a surviving shard client's error-feedback
    residuals for names whose ownership moved away: residuals are
    full-gradient-sized fp32 buffers, and without the prune hook N
    reshards leave every shard client holding residuals approaching the
    full parameter set (the stream for a moved name has ended — this
    client never pushes it again)."""
    from brpc_tpu.fleet import FleetClient, FleetServer, RegistryHub

    hub = RegistryHub()
    hub.start()
    s1 = FleetServer(hub.hostport, tag="efprune", ttl_s=5)
    s2 = None
    addr1 = s1.start()
    fc = FleetClient(hub.hostport, tag="efprune", codec="int8",
                     op_deadline_s=20.0)
    try:
        rng = _rng(11)
        seeds = {f"t{i}": rng.normal(size=(1 << 12,)).astype(np.float32)
                 for i in range(12)}
        fc.refresh()
        for name, arr in seeds.items():
            fc.install(name, arr, refresh=False)
        grads = {n: rng.normal(size=a.shape).astype(np.float32)
                 for n, a in seeds.items()}
        fc.push_all(grads)
        pc1 = fc._client(addr1)
        assert all(pc1._ef.residual(n) is not None for n in seeds), \
            "quantized pushes must have settled a residual per name"
        s2 = FleetServer(hub.hostport, tag="efprune", ttl_s=5)
        addr2 = s2.start()
        deadline = time.time() + 10.0
        while time.time() < deadline and len(fc.map.shards) < 2:
            fc.refresh()
            time.sleep(0.05)
        assert len(fc.map.shards) == 2
        moved = {n for n in seeds if fc.map.owner(n) == addr2}
        assert moved, "ketama join must move some keys onto the joiner"
        for n in seeds:
            if n in moved:
                assert pc1._ef.residual(n) is None, n
            else:
                assert pc1._ef.residual(n) is not None, n
    finally:
        fc.close()
        if s2 is not None:
            s2.stop()
        s1.stop()
        hub.stop()
        from brpc_tpu.fleet import clear_registry
        clear_registry()


def test_codec_counters_console_and_rpcz(codec_env):
    """The accounting satellite: tensor_codec_* counters + ratio on
    /vars, the per-tensor codec table on /tensorz, the capi registry
    probes, and the dequant stage annotation on /rpcz."""
    import ctypes

    import brpc_tpu.observability as obs
    from brpc_tpu.runtime.native import lib
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)

    L = lib()
    L.tbrpc_tensor_codec_id.restype = ctypes.c_int
    L.tbrpc_tensor_codec_id.argtypes = [ctypes.c_char_p]
    assert L.tbrpc_tensor_codec_id(b"int8") == 1
    assert L.tbrpc_tensor_codec_id(b"fp8e4m3") == 2
    assert L.tbrpc_tensor_codec_id(b"raw") == 0
    assert L.tbrpc_tensor_codec_id(b"nope") == -1
    buf = ctypes.create_string_buffer(256)
    L.tbrpc_tensor_codec_list.restype = ctypes.c_int64
    L.tbrpc_tensor_codec_list.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    assert L.tbrpc_tensor_codec_list(buf, len(buf)) > 0
    names = buf.value.decode().split(",")
    assert "int8" in names and "fp8e4m3" in names

    params = {"codec_counter_w": jnp.asarray(
        _rng(10).normal(size=(1 << 16,)).astype(np.float32))}
    ps = ParameterServer(params)
    port = ps.start()
    client = ParameterClient(f"tpu://127.0.0.1:{port}", codec="int8")

    def codec_vars():
        # The tensor_codec_* vars are NATIVE-owned (trpc/compress.cpp) —
        # read them through the registry dump, never obs.counter (whose
        # create would collide with the existing name).
        return dict((k.strip(), v.strip()) for k, _, v in
                    (line.partition(" : ") for line in
                     obs.dump_vars("tensor_codec").splitlines()))

    try:
        before = int(codec_vars().get("tensor_codec_bytes_wire", 0))
        obs.rpcz_enable()
        with obs.trace_span("quant_pull") as span:
            client.pull("codec_counter_w")
        # Dump the trace while collection is still ON: a dump with rpcz
        # off is now the typed RpczDisabled signal, not an empty list.
        spans = obs.dump_rpcz(span.trace_id)
        obs.rpcz_enable(False)
        g = np.ones((1 << 16,), np.float32)
        client.push_grad("codec_counter_w", g)

        # Counters grew, wire < logical (that IS the multiplier).
        lines = codec_vars()
        logical = int(lines["tensor_codec_bytes_logical"])
        wire = int(lines["tensor_codec_bytes_wire"])
        assert wire > before and logical > wire
        assert float(lines["tensor_codec_ratio"]) > 3.0

        # /tensorz renders the per-tensor table.
        page = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/tensorz", timeout=10).read().decode()
        assert "quantized tensor wire" in page
        assert "codec_counter_w" in page and "int8" in page

        # Stats JSON parses and attributes the tensor.
        L.tbrpc_tensor_codec_stats_json.restype = ctypes.c_int64
        L.tbrpc_tensor_codec_stats_json.argtypes = [ctypes.c_char_p,
                                                    ctypes.c_size_t]
        need = L.tbrpc_tensor_codec_stats_json(None, 0)
        sbuf = ctypes.create_string_buffer(int(need) + 1)
        L.tbrpc_tensor_codec_stats_json(sbuf, len(sbuf))
        doc = json.loads(sbuf.value.decode())
        assert any(t["name"] == "codec_counter_w" and t["codec"] == "int8"
                   for t in doc["tensors"])

        # /rpcz: the client span carries the dequant stage annotation.
        notes = " ".join(a for s in spans
                         for a in s.get("annotations", []))
        assert "dequant" in notes
    finally:
        client.close()
        ps.stop()


def test_server_never_advertises_undecodable_codec(codec_env):
    """An explicit codecs=() list is intersected with what THIS build can
    decode: advertising (say) fp8e4m3 on a host without ml_dtypes would
    let a client negotiate pushes the server then cannot parse. The
    declined client degrades to raw transparently and stays correct."""
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)

    params = _mk_params(n=2)
    with pytest.MonkeyPatch.context() as mp:
        # Pretend this build lost fp8 support at server-construction time.
        mp.setattr("brpc_tpu.runtime.codec.supported_codecs",
                   lambda: ("int8",))
        ps = ParameterServer(dict(params),
                             codecs=("fp8e4m3", "int8"))
    port = ps.start()
    client = ParameterClient(f"tpu://127.0.0.1:{port}", codec="fp8e4m3")
    try:
        payload, _ = client.channel.call("ParamService/Meta")
        meta = json.loads(payload.decode())
        assert meta["codecs"] == ["int8"]
        # fp8e4m3 was requested but never advertised: raw fallback, exact.
        assert client.negotiated_codec() is None
        _ver, w = client.pull("w00")
        np.testing.assert_array_equal(np.asarray(w), np.asarray(params["w00"]))
    finally:
        client.close()
        ps.stop()


def test_undecodable_quantized_push_is_clean_rpc_error(codec_env):
    """A push whose header claims a codec but whose payload cannot be
    split (truncated / corrupt) must die as a decodable RPC error at the
    service boundary — NOT be silently handed to the handler as flat
    wire bytes that fail later with an opaque numpy broadcast error."""
    from brpc_tpu.runtime.native import RpcError
    from brpc_tpu.runtime.param_server import ParameterServer
    from brpc_tpu.runtime.tensor import (E_UNDECODABLE, TensorArena,
                                         TensorChannel)

    params = _mk_params(n=1)
    ps = ParameterServer(dict(params))
    port = ps.start()
    ch = TensorChannel(f"tpu://127.0.0.1:{port}", TensorArena(8 << 20))
    g = np.zeros(params["w00"].shape, np.float32)

    hdr = codec.pack_header({"dtype": "<f4",
                             "shape": list(params["w00"].shape),
                             "codec": "int8",
                             "block": codec.DEFAULT_BLOCK})

    def corrupt_encoder(_host):
        # Header promises an int8 tensor of w00's size; 3 payload bytes
        # cannot even yield the scales array (not a float32 multiple).
        return np.zeros(3, np.uint8), hdr

    def truncated_encoder(host):
        # Scales intact, codes short by 10 bytes: numpy slicing would
        # CLAMP this silently and the reshape would only blow up deep in
        # the update handler as a generic internal error — split_wire's
        # exact length check must refuse it at the service boundary so
        # the structural code reaches the client.
        full = codec.encode(np.asarray(host), "int8", min_bytes=0).wire
        return full[:-10], hdr

    try:
        for bad in (corrupt_encoder, truncated_encoder):
            with pytest.raises(RpcError) as ei:
                ch.push_device("ParamService/Push", g, request=b"w00",
                               encoder=bad)
            # Structural app code (2044, beside E_NO_SUCH..E_EXISTS) —
            # NOT 2004/TRPC_EINTERNAL: callers must be able to tell
            # "server cannot decode this codec" (renegotiate) from
            # "server internal error" (retry/report) without matching
            # message text.
            assert ei.value.code == E_UNDECODABLE, bad.__name__
            assert "undecodable tensor payload" in ei.value.text
        # The server is unharmed: the parameter is untouched and a clean
        # raw pull still round-trips bit-for-bit.
        payload, view = ch.call_raw("ParamService/Pull", b"w00")
        view.release()
    finally:
        ch.close()
        ps.stop()


def test_group_miss_spares_groupmates_partial_result(codec_env):
    """A miss inside a PullQ group must not cost the groupmates: the
    survivors ride the PartialPullError so the fleet's salvage path
    re-routes ONLY the stragglers (previously the whole decoded group
    was discarded and re-fetched)."""
    from brpc_tpu.runtime.param_server import (E_NO_SUCH, ParameterClient,
                                               ParameterServer,
                                               PartialPullError)

    params = _mk_params(n=3)
    ps = ParameterServer(dict(params))
    port = ps.start()
    cli = ParameterClient(f"tpu://127.0.0.1:{port}", codec="int8")
    try:
        with pytest.raises(PartialPullError) as ei:
            cli.pull_all(["w00", "missing0", "w01", "w02"])
        e = ei.value
        assert e.code == E_NO_SUCH
        assert e.missing == ["missing0"]
        assert sorted(e.partial) == ["w00", "w01", "w02"]
        for k, (_ver, val) in e.partial.items():
            _assert_quant_close(params[k], val)
    finally:
        cli.close()
        ps.stop()


def test_corrupt_group_entry_rides_partial_salvage(codec_env, monkeypatch):
    """A client-side decode failure (corrupt quantized entry) surfaces
    as E_UNDECODABLE through the PartialPullError salvage — groupmates
    survive — instead of a bare ValueError that would bypass both the
    salvage and the fleet's per-name re-route."""
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer,
                                               PartialPullError)
    from brpc_tpu.runtime.tensor import E_UNDECODABLE

    params = _mk_params(3)
    ps = ParameterServer(dict(params))
    port = ps.start()
    cli = ParameterClient(f"tpu://127.0.0.1:{port}", codec="int8")
    real_decode = codec.decode

    def bad_decode(meta, wire):
        if meta.get("name") == "w01":
            raise ValueError("injected corrupt payload")
        return real_decode(meta, wire)

    monkeypatch.setattr(codec, "decode", bad_decode)
    try:
        with pytest.raises(PartialPullError) as ei:
            cli.pull_all()
        e = ei.value
        assert e.code == E_UNDECODABLE
        assert "w01" in e.text
        assert sorted(e.partial) == ["w00", "w02"]
        assert e.missing == ["w01"]
        for k, (_v, val) in e.partial.items():
            _assert_quant_close(params[k], val)
    finally:
        cli.close()
        ps.stop()


def test_zero_size_tensors_pull_without_attachment(codec_env):
    """A PullQ group of only zero-size tensors ships a manifest with NO
    attachment; the decode loop must treat that as an empty buffer, not
    None (previously a TypeError — which, not being an RpcError, escaped
    the PartialPullError salvage entirely)."""
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)

    params = {"e0": jnp.zeros((0,), jnp.float32),
              "e1": jnp.zeros((0, 8), jnp.float32)}
    ps = ParameterServer(dict(params))
    port = ps.start()
    cli = ParameterClient(f"tpu://127.0.0.1:{port}", codec="int8")
    try:
        # to_host keeps every name on the PullQ group path (the device
        # path routes predicted-ineligible names per tensor).
        got = cli.pull_all(to_host=True)
        assert sorted(got) == ["e0", "e1"]
        for k in params:
            assert got[k][1].size == 0
            assert got[k][1].shape == tuple(params[k].shape)
        # The device path (per-tensor raw routing) serves them too.
        got_dev = cli.pull_all()
        for k in params:
            assert np.asarray(got_dev[k][1]).shape == tuple(params[k].shape)
    finally:
        cli.close()
        ps.stop()


def test_ineligible_tensors_keep_per_tensor_raw_path(codec_env):
    """Codec-ineligible tensors (non-fp32 / below the size floor) pulled
    by a negotiated client ride the per-tensor raw path — exact bytes,
    zero-copy device_put — instead of paying the PullQ manifest decode's
    extra host copy; only the eligible names form groups (pinned via the
    pull_group recorder)."""
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)

    rng = _rng(7)
    params = {
        "big0": jnp.asarray(rng.normal(size=(1 << 16,)).astype(np.float32)),
        "big1": jnp.asarray(rng.normal(size=(1 << 16,)).astype(np.float32)),
        "ids": jnp.asarray(
            rng.integers(0, 1000, size=(4096,)).astype(np.int32)),
        "tiny": jnp.asarray(rng.normal(size=(16,)).astype(np.float32)),
    }
    ps = ParameterServer(dict(params))
    port = ps.start()
    cli = ParameterClient(f"tpu://127.0.0.1:{port}", codec="int8")
    try:
        before = ps._m["pull_group"].count()
        got = cli.pull_all(group=8)
        assert ps._m["pull_group"].count() - before == 1, (
            "only the two eligible names should form one PullQ group")
        # Ineligible: exact (raw wire); eligible: within the quant bound.
        np.testing.assert_array_equal(np.asarray(got["ids"][1]),
                                      np.asarray(params["ids"]))
        np.testing.assert_array_equal(np.asarray(got["tiny"][1]),
                                      np.asarray(params["tiny"]))
        for k in ("big0", "big1"):
            _assert_quant_close(params[k], got[k][1])
    finally:
        cli.close()
        ps.stop()


def test_mixed_codec_clients_get_separate_cache_slots(codec_env):
    """int8 and fp8e4m3 clients pulling the same parameter must not
    thrash a single encode-cache slot: each codec caches per name, so
    steady state stays quantize-once-serve-many for both."""
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)

    if "fp8e4m3" not in codec.supported_codecs():
        pytest.skip("fp8e4m3 needs ml_dtypes")
    params = _mk_params(n=1)
    ps = ParameterServer(dict(params))
    port = ps.start()
    a = ParameterClient(f"tpu://127.0.0.1:{port}", codec="int8")
    b = ParameterClient(f"tpu://127.0.0.1:{port}", codec="fp8e4m3")
    try:
        ref = np.asarray(params["w00"])
        for cli, tol in ((a, None), (b, 0.5)):
            for _rep in range(2):  # second pull must be a cache hit
                _ver, val = cli.pull("w00")
                if tol is None:
                    _assert_quant_close(ref, val)
                else:  # e4m3: looser bound (3 mantissa bits)
                    assert float(np.abs(np.asarray(val) - ref).max()) < tol
        assert set(ps._enc_cache["w00"]) == {"int8", "fp8e4m3"}
        assert all(ent[0] == 0 for ent in ps._enc_cache["w00"].values())
    finally:
        a.close()
        b.close()
        ps.stop()


def test_retired_name_not_reinserted_into_encode_cache(codec_env):
    """_encoded_entry encodes lock-free from a pre-retire snapshot; if
    Retire pops the name while it encodes, the response is still served
    (matching single-Pull semantics — the snapshot predates the retire)
    but the entry must NOT be re-cached: a retired-and-gone name would
    strand its wire bytes in _enc_cache until an eventual re-install."""
    from brpc_tpu.runtime.param_server import ParameterServer

    params = _mk_params(n=1)
    ps = ParameterServer(dict(params))
    p = ps._params["w00"]
    # The race, deterministically: Retire's pop lands before the encode
    # path's insert (the insert-side name-still-present re-check under
    # _mu is what's pinned here).
    with ps._mu:
        del ps._params["w00"]
        ps._enc_cache.pop("w00", None)
    meta, _data = ps._encoded_entry("w00", p, 0, "int8")
    assert meta.get("codec") == "int8"  # still served quantized
    assert "w00" not in ps._enc_cache   # but never re-cached


def test_stale_codec_advertisement_self_heals_on_push(codec_env):
    """A server 'restarted' without codec support answers quantized
    pushes with E_UNDECODABLE; the client must drop its cached
    advertisement and renegotiate (to raw) on the next call instead of
    failing every push until rebuilt."""
    from brpc_tpu.runtime.native import RpcError
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)
    from brpc_tpu.runtime.tensor import E_UNDECODABLE

    params = _mk_params(n=1)
    ps = ParameterServer(dict(params))
    port = ps.start()
    cli = ParameterClient(f"tpu://127.0.0.1:{port}", codec="int8")
    g = np.zeros_like(np.asarray(params["w00"]))
    try:
        assert cli.negotiated_codec() == "int8"
        # A successful quantized push settles an error-feedback residual
        # for the name (a full-gradient-sized fp32 buffer).
        assert cli.push_grad("w00", g) == 1
        assert cli._ef.residual("w00") is not None
        # Stop advertising AND stop decoding (the in-process handler
        # still parses int8 — simulate the build that cannot by failing
        # the wire split, server-side only: the client's encoder never
        # calls split_wire).
        ps._codecs = ()
        with pytest.MonkeyPatch.context() as mp:
            def no_split(_meta, _payload):
                raise ValueError("simulated: build lost codec support")
            mp.setattr("brpc_tpu.runtime.codec.split_wire", no_split)
            with pytest.raises(RpcError) as ei:
                cli.push_grad("w00", g)
            assert ei.value.code == E_UNDECODABLE
        # The failed push dropped the cached advertisement: the next
        # call refetches Meta (now codec-less) and rides raw, cleanly.
        assert cli.negotiated_codec() is None
        # The refetch REPOPULATED the advertisement (a full Meta, not
        # the epoch-hit cache path, which matches and skips it): choose
        # must have seen the server's real codec list, and later calls
        # must not pay an Epoch RPC each trying to renegotiate forever.
        assert cli._srv_codecs == ()
        assert cli.push_grad("w00", g) == 2
        # The degraded-to-raw stream also dropped the stranded residual:
        # raw pushes owe nothing, and keeping it would hold one fp32
        # gradient per name for the client's lifetime.
        assert cli._ef.residual("w00") is None
    finally:
        cli.close()
        ps.stop()


def test_precodec_rollback_push_self_heals(codec_env):
    """A quantized push against a server rolled back to a PRE-codec
    build has no E_UNDECODABLE answer: the old trampoline hands the
    handler the flat quantized bytes and the update math dies as a
    generic internal error (TRPC_EINTERNAL). The client must re-read
    the advertisement once — heal when the codec is gone (next push
    rides raw), keep negotiation when the server still advertises it
    (a genuine handler bug must not silently degrade the stream)."""
    from brpc_tpu.runtime.native import RpcError
    from brpc_tpu.runtime.param_server import (TRPC_EINTERNAL,
                                               ParameterClient,
                                               ParameterServer)

    params = _mk_params(n=1)
    ps = ParameterServer(dict(params))
    port = ps.start()
    cli = ParameterClient(f"tpu://127.0.0.1:{port}", codec="int8")
    g = np.zeros_like(np.asarray(params["w00"]))
    try:
        assert cli.negotiated_codec() == "int8"
        real_push = cli.channel.push_device

        def precodec_push(*a, **k):
            raise RpcError(TRPC_EINTERNAL,
                           "operands could not be broadcast together")

        # Negative control FIRST: server still advertises int8, so a
        # 2004 is a genuine internal error — negotiation must survive.
        cli.channel.push_device = precodec_push
        with pytest.raises(RpcError):
            cli.push_grad("w00", g)
        assert cli.negotiated_codec() == "int8"
        # Rollback: stop advertising. The SAME failure now heals, and
        # once the 'old server' is gone the next push rides raw.
        ps._codecs = ()
        with pytest.raises(RpcError):
            cli.push_grad("w00", g)
        assert cli.negotiated_codec() is None
        cli.channel.push_device = real_push
        assert cli.push_grad("w00", g) == 1
    finally:
        cli.close()
        ps.stop()


def test_push_all_partial_versions_survive(codec_env):
    """A push_all whose window dies on a per-name failure must not
    discard the versions already confirmed: gradient application is not
    idempotent (a second apply is a double momentum step), so the caller
    needs PartialPushError's .applied/.unpushed split to retry only the
    unconfirmed names."""
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer,
                                               PartialPushError)

    params = _mk_params(n=3)
    ps = ParameterServer(dict(params))
    port = ps.start()
    cli = ParameterClient(f"tpu://127.0.0.1:{port}")
    try:
        grads = {n: np.zeros_like(np.asarray(a))
                 for n, a in params.items()}
        grads["nope"] = np.zeros(16, np.float32)  # not on the server
        with pytest.raises(PartialPushError) as ei:
            # window=1 serializes drains: every name before the failure
            # is CONFIRMED, nothing is ambiguously in flight.
            cli.push_all(grads, window=1)
        e = ei.value
        assert set(e.applied) == set(params)
        assert e.unpushed == ["nope"]
        assert all(v == 1 for v in e.applied.values())
    finally:
        cli.close()
        ps.stop()


def test_fleet_push_partial_no_double_apply(codec_env):
    """End-to-end pin of the double-apply fix: a fleet push_all whose
    group dies mid-window (one name the fleet doesn't hold) must apply
    the confirmed groupmates EXACTLY once. Before PartialPushError the
    salvage path re-pushed the whole group — each retry round applied
    the already-confirmed gradients again (versions 2, 3, ...)."""
    from brpc_tpu.fleet import FleetClient, FleetServer, RegistryHub

    hub = RegistryHub()
    hub.start()
    srv = FleetServer(hub.hostport, tag="pushpart", ttl_s=5)
    srv.start()
    fc = FleetClient(hub.hostport, tag="pushpart", op_deadline_s=5.0)
    try:
        rng = _rng(13)
        seeds = {f"p{i}": rng.normal(size=(1 << 10,)).astype(np.float32)
                 for i in range(3)}
        fc.refresh()
        for name, arr in seeds.items():
            fc.install(name, arr, refresh=False)
        grads = {n: np.zeros_like(a) for n, a in seeds.items()}
        grads["nope"] = np.zeros(16, np.float32)
        with pytest.raises(KeyError):
            fc.push_all(grads, window=1)
        # The confirmed names were applied exactly once across the
        # scatter + salvage + per-name retry rounds.
        meta = fc.meta()
        assert {n: meta[n]["version"] for n in seeds} == {
            n: 1 for n in seeds}
    finally:
        fc.close()
        srv.stop()
        hub.stop()
        from brpc_tpu.fleet import clear_registry
        clear_registry()
