"""Drive the served tensor path once on a TPU, through the normal entry points.

    python chip_smoke.py              # one chip: the phases below, in order
    python chip_smoke.py --chips 4    # the 2x2-mesh paths only
    python chip_smoke.py --tiny       # CPU rehearsal at toy sizes

One chip, in one process (the chip belongs to one process at a time):

  native   build libbrpc_tpu.so from this checkout (incremental cmake) and
           load it BEFORE importing JAX — the order every server process
           uses, and the one that once aborted JAX at import;
  device   fail unless JAX's platform is tpu;
  params   a ParameterServer holding 1 GiB of fp32 parameters in HBM
           (64 x 2048x2048) answers a ParameterClient over tpu://: raw and
           int8 pulls, raw and int8 pushes through the on-device update,
           each checked against a host reference;
  trainer  3 steps of the overlapped step driver (LayeredMLP, 4 layers of
           width 2048) against a one-sided ParameterServer: loss falls;
  serving  a ServingServer streams 2 sessions of 16 tokens, equal to
           decode_serial.

``--chips 4`` runs only what exists across chips, on a 2x2 mesh: the
client x shard train step, causal GQA ring attention and the collective
programs, each against its single-device reference.

Every phase prints one line; any failure exits non-zero and prints no
result. The last line is the result: {"ok": true, "device": {...}}.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

KERNEL = "tpu_custom_call"  # a compiled Pallas kernel in lowered TPU text
SEED = 0
# The codec's bound (scale / 2 per block) is exact arithmetic; its fp32
# encoder reaches it to a few ulps of the code (1.00003x measured).
F32_SLACK = 1.001


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def phase(name: str, t0: float, **fields) -> None:
    """One line per phase: what ran, timings (for information only)."""
    fields["s"] = round(time.monotonic() - t0, 3)
    print(f"[{name}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def kernel_in(fn, *args, **kw) -> bool:
    """True when ``fn``'s lowering for exactly these arguments holds a
    compiled Pallas kernel (no interpreter, no reference fallback)."""
    return KERNEL in fn.lower(*args, **kw).as_text()


def block_absmax(x, block: int):
    """Per-element copy of its codec block's absmax (int8 bound = /254)."""
    import numpy as np

    flat = np.abs(np.asarray(x, np.float32).reshape(-1))
    pad = (-flat.size) % block
    per = np.pad(flat, (0, pad)).reshape(-1, block).max(axis=1)
    return np.repeat(per, block)[:flat.size].reshape(np.shape(x))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def native_phase():
    t0 = time.monotonic()
    from brpc_tpu.runtime import native

    check("jax" not in sys.modules, "jax was imported before the library")
    native.build("brpc_tpu")
    t_build = time.monotonic() - t0
    native.lib()
    check("jax" not in sys.modules, "loading the library imported jax")
    phase("native", t0, build_s=round(t_build, 3), loaded_before_jax=True)


def device_phase(want_chips: int, tiny: bool):
    t0 = time.monotonic()
    import jax

    from brpc_tpu.utils.platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    devs = jax.devices()
    d = devs[0]
    print(f"# jax {jax.__version__} platform={d.platform} "
          f"kind={d.device_kind!r} count={len(devs)}", flush=True)
    if not tiny:
        check(d.platform == "tpu", f"no TPU: JAX sees {d.platform}")
    check(len(devs) >= want_chips,
          f"need {want_chips} devices, JAX sees {len(devs)}")
    phase("device", t0, platform=d.platform, kind=repr(d.device_kind),
          count=len(devs), cache=cache_dir)
    return devs[:want_chips]


def params_phase(tiny: bool):
    """1 GiB of parameters resident in HBM behind a ParameterServer."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.ops.fused_update import (fused_momentum_update,
                                           momentum_update_reference)
    from brpc_tpu.ops.quantize import (dequantize_blocks,
                                       dequantize_reference)
    from brpc_tpu.runtime import codec
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)

    t0 = time.monotonic()
    n_tensors, side = (8, 256) if tiny else (64, 2048)
    n_pull, n_push, window = (4, 2, 2) if tiny else (16, 8, 8)
    lr, beta = 0.01, 0.9
    on_tpu = jax.default_backend() == "tpu"
    names = [f"w{i:02d}" for i in range(n_tensors)]
    normal = jax.jit(lambda k: jax.random.normal(k, (side, side),
                                                 jnp.float32))
    keys = jax.random.split(jax.random.PRNGKey(SEED), 2 * n_tensors)
    params = {n: normal(keys[i]) for i, n in enumerate(names)}
    grads = {n: normal(keys[n_tensors + i]) for i, n in enumerate(names)}
    jax.block_until_ready((params, grads))
    # Host copies of what the checks compare against, taken before the
    # server owns the arrays.
    pulled = names[:n_pull]
    pushed = names[n_pull:n_pull + n_push]
    quant = names[:n_push]  # int8 pull and push: the same tensors
    host = {n: np.asarray(params[n]) for n in set(pulled + pushed + quant)}
    host_g = {n: np.asarray(grads[n]) for n in set(pushed + quant)}
    t_init = time.monotonic() - t0

    ps = ParameterServer(params, lr=lr, momentum=beta)
    del params
    check(ps._on_device == on_tpu, "ParameterServer took the wrong branch")
    # On the chip the server keeps parameters and momenta as device
    # arrays (its HBM branch); off it, numpy.
    dev0 = jax.devices()[0]
    resident = sum(a.nbytes for a in ps._params.values()
                   if isinstance(a, jax.Array) and a.devices() == {dev0})
    if on_tpu:
        check(resident == n_tensors * side * side * 4,
              f"only {resident} parameter bytes in HBM")
    in_use = (dev0.memory_stats() or {}).get("bytes_in_use", 0)
    port = ps.start()
    addr = f"tpu://127.0.0.1:{port}"
    client = ParameterClient(addr)
    client_q = ParameterClient(addr, codec="int8")
    try:
        # Raw pulls: byte-equal to the server's copy.
        t1 = time.monotonic()
        got = client.pull_all(pulled, window=window)
        jax.block_until_ready([a for _, a in got.values()])
        t_pull = time.monotonic() - t1
        for n in pulled:
            version, arr = got[n]
            check(version == 0, f"{n}: version {version}")
            check(arr.devices() == {dev0},
                  f"{n}: pulled to {arr.devices()}")
            check(np.array_equal(np.asarray(arr).view(np.uint32),
                                 host[n].view(np.uint32)),
                  f"{n}: pulled bytes differ from the server's")
        del got

        # Raw pushes through the server's device update.
        t1 = time.monotonic()
        for n in pushed:
            check(client.push_grad(n, grads[n]) == 1, f"{n}: push version")
        back = client.pull_all(pushed, window=window)
        jax.block_until_ready([a for _, a in back.values()])
        t_push = time.monotonic() - t1
        for n in pushed:
            ref_p, _ = momentum_update_reference(
                host[n], np.zeros_like(host[n]), host_g[n], lr=lr, beta=beta)
            version, arr = back[n]
            check(version == 1, f"{n}: version {version} after push")
            np.testing.assert_allclose(np.asarray(arr), ref_p, rtol=1e-6,
                                       atol=1e-6, err_msg=n)
        del back

        # int8: one negotiated pull and one quantized push of the same
        # tensors, each within the codec's per-block bound (scale / 2).
        t1 = time.monotonic()
        check(client_q.negotiated_codec() == "int8", "int8 not negotiated")
        got_q = client_q.pull_all(quant, window=window)
        block = codec.DEFAULT_BLOCK
        for n in quant:
            _, arr = got_q[n]
            bound = block_absmax(host[n], block) / 254.0
            err = np.abs(np.asarray(arr) - host[n])
            check(np.all(err <= bound * F32_SLACK),
                  f"{n}: int8 pull error {err.max()} past the block bound")
        del got_q
        for n in quant:
            check(client_q.push_grad(n, grads[n]) == 1, f"{n}: int8 push")
        back = client.pull_all(quant, window=window)
        t_quant = time.monotonic() - t1
        for n in quant:
            ref_p, _ = momentum_update_reference(
                host[n], np.zeros_like(host[n]), host_g[n], lr=lr, beta=beta)
            bound = lr * block_absmax(host_g[n], block) / 254.0
            err = np.abs(np.asarray(back[n][1]) - ref_p)
            check(np.all(err <= bound * F32_SLACK + 1e-6),
                  f"{n}: int8 push error {err.max()} past the block bound")
        del back

        # The exact calls the server made: compiled Pallas on the chip.
        p = jax.ShapeDtypeStruct((side, side), jnp.float32)
        update_kernel = kernel_in(fused_momentum_update, p, p, p, lr=lr,
                                  beta=beta)
        n_el = side * side
        dequant_kernel = kernel_in(
            dequantize_blocks,
            jax.ShapeDtypeStruct((n_el,), jnp.int8),
            jax.ShapeDtypeStruct((-(-n_el // block),), jnp.float32),
            block=block, n=n_el, shape=(side, side))
        if on_tpu:
            check(update_kernel, "the push's update is not a Pallas kernel")
            check(dequant_kernel, "the int8 dequant is not a Pallas kernel")

        # A peer may configure a block that is not a multiple of 128: the
        # chip dequantizes it through the kernel too, equal to the reference.
        odd = 100
        x = host[quant[0]]
        q_odd, s_odd = codec.split_wire(
            {"dtype": "<f4", "shape": list(x.shape), "codec": "int8",
             "block": odd},
            codec.encode(x, "int8", block=odd, min_bytes=0).wire)
        q_odd, s_odd = jnp.asarray(q_odd), jnp.asarray(s_odd)
        odd_kw = dict(block=odd, n=n_el, shape=x.shape)
        odd_kernel = kernel_in(dequantize_blocks, q_odd, s_odd, **odd_kw)
        check(np.array_equal(
            np.asarray(dequantize_blocks(q_odd, s_odd, **odd_kw)),
            np.asarray(dequantize_reference(q_odd, s_odd, **odd_kw))),
            f"block {odd} dequant differs from the reference")
        if on_tpu:
            check(odd_kernel, f"the block {odd} dequant is not a Pallas kernel")
    finally:
        client.close()
        client_q.close()
        ps.stop()
    phase("params", t0, tensors=n_tensors, param_hbm_bytes=resident,
          device_bytes_in_use=in_use,
          init_s=round(t_init, 3), pull_s=round(t_pull, 3),
          push_s=round(t_push, 3), int8_s=round(t_quant, 3),
          update_kernel=update_kernel, dequant_kernel=dequant_kernel,
          odd_block_dequant_kernel=odd_kernel)


def trainer_phase(tiny: bool):
    """3 steps of the overlapped step driver against a one-sided server."""
    from brpc_tpu.models.tensor_service import LayeredMLP
    from brpc_tpu.runtime.param_server import (ParameterClient,
                                               ParameterServer)
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver

    t0 = time.monotonic()
    width, batch, steps = (64, 16, 3) if tiny else (2048, 256, 3)
    h = LayeredMLP([width] * 5, seed=SEED)
    ps = ParameterServer(dict(h.init_params()), oneside=True)
    port = ps.start()
    client = ParameterClient(f"tpu://127.0.0.1:{port}", oneside=True)
    try:
        driver = OverlappedStepDriver(client, h, overlap=True)
        driver.prime()
        x, y = h.data(batch, seed=SEED + 1)
        losses = [driver.step(x, y) for _ in range(steps)]
    finally:
        client.close()
        ps.stop()
    check(all(b < a for a, b in zip(losses, losses[1:])),
          f"loss did not fall every step: {losses}")
    check(all(v == steps for v in driver.versions.values())
          and len(driver.versions) == len(h.names),
          f"versions {driver.versions} != {steps}")
    phase("trainer", t0, layers=len(h.names), width=width, steps=steps,
          losses=[round(v, 6) for v in losses])


def serving_phase():
    """2 streamed sessions of 16 tokens, token-equal to serial decode."""
    import jax

    from brpc_tpu.models.decoder import decode_serial, init_decoder
    from brpc_tpu.serving import ServingClient, ServingServer

    t0 = time.monotonic()
    max_len, n_tok = 64, 16
    params = init_decoder(jax.random.PRNGKey(SEED))
    prompts = ([3, 7, 11], [5, 2])
    srv = ServingServer(params, max_len=max_len, max_batch=4)
    port = srv.start()
    outs = [[] for _ in prompts]
    clients = [ServingClient(f"tpu://127.0.0.1:{port}", tenant=f"u{i}")
               for i in range(len(prompts))]
    try:
        streams = [c.open(p, n_tok) for c, p in zip(clients, prompts)]
        threads = [threading.Thread(target=lambda s=s, o=o: o.extend(s),
                                    daemon=True)
                   for s, o in zip(streams, outs)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
            check(not th.is_alive(), "a session stream did not finish")
    finally:
        for c in clients:
            c.close()
        srv.stop()
    for p, out in zip(prompts, outs):
        want = decode_serial(params, p, n_tok, max_len)
        check(out == want, f"prompt {p}: streamed {out} != serial {want}")
    phase("serving", t0, sessions=len(prompts), tokens=[len(o) for o in outs])


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _on_distinct_devices(arr, n: int, what: str) -> None:
    devs = {s.device for s in arr.addressable_shards}
    check(len(devs) == n, f"{what}: shards on {len(devs)} devices, not {n}")


def _rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def sharded_step_phase(devs, tiny: bool):
    """client x shard train step vs the single-device train_step."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from brpc_tpu.models.tensor_service import (PSState, init_state,
                                                make_sharded_train_step,
                                                train_step)
    from brpc_tpu.parallel.mesh import CLIENT_AXIS, SHARD_AXIS, make_mesh

    t0 = time.monotonic()
    din, dh, dout, batch = ((64, 128, 64, 32) if tiny
                            else (4096, 16384, 4096, 1024))
    mesh = make_mesh(devs, client=2, shard=2)
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(SEED), 3)
    with jax.default_device(devs[0]):
        state = init_state(k0, din, dh, dout)
        x = jax.random.normal(k1, (batch, din))
        t = jax.random.normal(k2, (batch, dout))
        ref_state, ref_loss = train_step(state, x, t)
        jax.block_until_ready((ref_state, ref_loss))
    specs = PSState(w1=P(None, SHARD_AXIS), b1=P(SHARD_AXIS),
                    w2=P(SHARD_AXIS, None), b2=P(),
                    m_w1=P(None, SHARD_AXIS), m_w2=P(SHARD_AXIS, None),
                    stats=P())
    sh_state = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), state, specs)
    data = NamedSharding(mesh, P(CLIENT_AXIS, None))
    new_state, loss = make_sharded_train_step(mesh)(
        sh_state, jax.device_put(x, data), jax.device_put(t, data))
    jax.block_until_ready((new_state, loss))
    for name in ("w1", "w2", "m_w1", "m_w2"):
        _on_distinct_devices(getattr(new_state, name), len(devs), name)
    errs = {name: _rel_err(getattr(new_state, name), getattr(ref_state, name))
            for name in PSState._fields}
    errs["loss"] = _rel_err(loss, ref_loss)
    # The step differentiates through bf16 matmuls, so each gradient (the
    # new momentum) is rounded to bf16: per client before the CLIENT psum
    # here, once over the whole batch on one device. Momenta agree to
    # bf16's precision; parameters carry lr times that on top of fp32.
    tol = {name: 1e-4 for name in errs}
    tol.update(m_w1=1e-2, m_w2=1e-2)
    bad = {k: v for k, v in errs.items() if not v <= tol[k]}
    check(not bad, f"sharded step differs from train_step: {bad}")
    phase("sharded_step", t0, din=din, dh=dh, dout=dout, batch=batch,
          rel_err={k: float(f"{v:.3g}") for k, v in errs.items()})


def ring_attention_phase(devs, tiny: bool):
    """Causal GQA ring attention vs the dense single-device reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from brpc_tpu.ops.flash_attention import dense_attention_mh
    from brpc_tpu.ops.ring_attention import ring_attention
    from brpc_tpu.parallel.mesh import SHARD_AXIS, make_mesh

    t0 = time.monotonic()
    b, h, hkv, seq, d = (1, 4, 2, 64, 32) if tiny else (1, 8, 2, 8192, 128)
    mesh = make_mesh(devs, client=2, shard=2)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED + 2), 3)
    with jax.default_device(devs[0]):
        q = jax.random.normal(kq, (b, h, seq, d), jnp.bfloat16)
        k = jax.random.normal(kk, (b, hkv, seq, d), jnp.bfloat16)
        v = jax.random.normal(kv, (b, hkv, seq, d), jnp.bfloat16)
        ref = jax.jit(dense_attention_mh, static_argnames="causal")(
            q, k, v, causal=True)
        ref = np.asarray(ref, np.float32)
    seq_sh = NamedSharding(mesh, P(None, None, SHARD_AXIS, None))
    qs, ks, vs = (jax.device_put(a, seq_sh) for a in (q, k, v))
    out = ring_attention(mesh, causal=True)(qs, ks, vs)
    jax.block_until_ready(out)
    _on_distinct_devices(out, len(devs), "ring attention output")
    err = float(np.max(np.abs(np.asarray(out, np.float32) - ref)))
    check(err < 3e-2, f"ring attention off by {err}")
    phase("ring_attention", t0, b=b, heads=h, kv_heads=hkv, seq=seq, d=d,
          max_abs_err=f"{err:.3g}")


def collectives_phase(devs, tiny: bool):
    """ring_stream and fanout_gather vs numpy."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from brpc_tpu.parallel.collectives import fanout_gather, ring_stream
    from brpc_tpu.parallel.mesh import SHARD_AXIS, make_mesh

    t0 = time.monotonic()
    rows, cols = (64, 128) if tiny else (8192, 2048)  # 64 MiB of fp32
    mesh = make_mesh(devs, client=2, shard=2)
    n = mesh.shape[SHARD_AXIS]
    host = np.random.default_rng(SEED).standard_normal(
        (rows, cols), dtype=np.float32)
    x = jax.device_put(host, NamedSharding(mesh, P(SHARD_AXIS)))
    streamed = ring_stream(mesh)(x)
    gathered = fanout_gather(mesh)(x)
    jax.block_until_ready((streamed, gathered))
    for arr, what in ((x, "input"), (streamed, "ring_stream"),
                      (gathered, "fanout_gather")):
        _on_distinct_devices(arr, len(devs), what)
    # One hop moves shard i's block to shard i+1: a roll by one block.
    check(np.array_equal(np.asarray(streamed),
                         np.roll(host, rows // n, axis=0)),
          "ring_stream differs from numpy")
    check(np.array_equal(np.asarray(gathered), host),
          "fanout_gather differs from numpy")
    phase("collectives", t0, rows=rows, cols=cols, shards=n)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: toy sizes, any JAX platform")
    args = ap.parse_args()
    # Exit with a traceback well inside the caller's 1200 s limit rather
    # than hang the chip.
    faulthandler.dump_traceback_later(1100, exit=True)

    hits = {"/jax/compilation_cache/cache_hits": 0,
            "/jax/compilation_cache/cache_misses": 0}

    if args.chips == 1:
        native_phase()
    devs = device_phase(args.chips, args.tiny)

    import jax

    def count(event, **_):
        if event in hits:
            hits[event] += 1
    jax.monitoring.register_event_listener(count)

    if args.chips == 1:
        params_phase(args.tiny)
        trainer_phase(args.tiny)
        serving_phase()
    else:
        sharded_step_phase(devs, args.tiny)
        ring_attention_phase(devs, args.tiny)
        collectives_phase(devs, args.tiny)
    print(f"# compile cache: hits={hits['/jax/compilation_cache/cache_hits']}"
          f" misses={hits['/jax/compilation_cache/cache_misses']}",
          flush=True)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
