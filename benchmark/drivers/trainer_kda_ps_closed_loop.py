"""The Kimi Linear trainer driver: a hybrid of Kimi Delta Attention and
latent attention trained through one parameter-server shard, in a closed
loop of steps.

What ``trainer_ps_closed_loop`` does for the DeepSeek-V3 block, with the
model, its plain reference and its operation count swapped: the
configuration's parameters, with each KDA layer's gates as its
``assumed`` says, are made on the cell's first chip from the seed
(benchmark/kimi_linear_reference.py) and served by a ParameterServer on
``tpu://127.0.0.1:0``; one ParameterClient feeds an OverlappedStepDriver
over the program's trainer (brpc_tpu/models/mla_moe.py, whose KDA layers
run brpc_tpu/models/kda.py), which pulls each parameter's next version and
pushes each gradient inside the step's compute. The two warm-up steps are
the checked ones, the state is exported after them and after the window,
and ``correct`` reads the same eight numbers against the configuration's
limits (``trainer_ps_closed_loop``'s docstring). The helpers that name no
model are that driver's, by import.

``Run.extra`` carries that driver's keys and one KDA layer's core work of
a step (benchmark/kimi_linear_flops.py): ``kda_core_flops.fwd``,
``kda_core_bytes.fwd`` and ``.vjp`` for its backward, which the
``kda_core_roofline`` reader adds up per run of the core's programs.
``routed_rows`` is per grouped matmul: past a byte budget the expert
layer runs its held experts over its tokens in passes
(``mla_moe.expert_passes``: 8 at the cell's 16,384 tokens), one set of
grouped matmuls each.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import threading
import time
from typing import Dict, List

import numpy as np

# The program's KDA layer first: a checkout without it fails here, at once.
from brpc_tpu.models import kda  # noqa: F401
from brpc_tpu.models import mla_moe

from benchmark import harness, kimi_linear_flops, reference, traffic
from benchmark import kimi_linear_reference as kref
from benchmark import trainer_reference
from benchmark.drivers import trainer_ps_closed_loop as base

KEYS = base.KEYS
check_mix = base.check_mix
TINY_BLOCKS = {"q_block": 16, "loss_chunk": 24}


def run(cell, args, session: "harness.Session", rehearse: bool = False,
        control: bool = False) -> "harness.Outcome":
    """Set-up, the checked warm-up, the window and the check of one run."""
    from brpc_tpu.runtime import native, param_server
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver
    from brpc_tpu.runtime.tensor import TensorArena

    cfg, mix = cell.config, cell.traffic
    c = base._model(cfg, rehearse)
    seqs = int(mix["seqs"])
    seq_len = int(c["seq_len"] if rehearse else mix["seq_len"])
    ref_model = kref.Model.of(c)
    layout = kref.layout(ref_model)
    blocks = dict(TINY_BLOCKS, kda_chunk=c["kda_chunk"]) if rehearse else {}
    mcfg = mla_moe.Config.from_hf(c, **blocks)
    if mla_moe.tensor_layout(mcfg) != layout:
        raise ValueError("the program's tensors are not the configuration's")
    names = [n for n, _ in layout]
    index = {n: t for t, n in enumerate(names)}
    nbytes = [4 * math.prod(s) for _, s in layout]
    opt = cfg["optimizer"]
    lr, beta = float(opt["lr"]), float(opt["momentum"])
    init = cfg["init"]
    window = int(mix["push_window"])

    params = kref.make_params(ref_model, args.seed, init)
    bias = trainer_reference.make_bias(ref_model, args.seed, init["bias_std"])
    session.mark("data")
    largest = max(nbytes)
    server = param_server.ParameterServer(
        dict(zip(names, params)), lr=lr, momentum=beta,
        arena=TensorArena(2 * (window + 2) * largest + (256 << 20)))
    del params
    port = server.start("127.0.0.1:0")
    client = param_server.ParameterClient(
        f"tpu://127.0.0.1:{port}",
        arena=TensorArena(2 * (window + 2) * largest + (64 << 20)))
    kept = base.Kept(mla_moe.MlaMoeTrainer(mcfg, bias))
    timed = base.Calls(index)
    driver = OverlappedStepDriver(client, kept, overlap=bool(mix["overlap"]),
                                  window=window, call_span=timed)
    batch = lambda step: trainer_reference.tokens(  # noqa: E731
        args.seed, step, seqs, seq_len, ref_model.vocab)
    pushes: Dict[str, List[int]] = {n: [] for n in names}
    calls: List[tuple] = []
    stats: List[dict] = []
    slots = np.zeros((ref_model.moe_layers, len(ref_model.held)), np.int64)
    step_flops: List[int] = []
    losses: List[float] = []
    failed, errors = 0, []
    program = base.Checked()
    finals: Dict[str, tuple] = {}
    try:
        client.meta()
        driver.prime()
        session.mark("server")
        kept.keep = True
        for step in range(base.CHECKED_STEPS):
            kept.grads = {}
            program.losses.append(driver.step(*batch(step)))
            program.grads.append(kept.grads)
            program.routes.append([np.asarray(s) for s, _ in
                                   kept.trainer.last_routes])
            for name, (version, _) in driver.last_trace.acks.items():
                pushes[name].append(int(version))
        timed.rows.clear()
        kept.keep, kept.grads = False, {}
        for name in names:
            version, stacked = client.handoff(name)
            program.state[name] = (stacked[0], stacked[1])
            client.install(name, stacked, version, commit=True)
        session.mark("warm_up")

        def body(seconds, during):
            nonlocal failed
            t_open = time.perf_counter()
            t_end = t_open + seconds
            slicer = None
            if during is not None:
                slicer = threading.Thread(target=during, args=(t_end,))
                slicer.start()
            step = base.CHECKED_STEPS
            while time.perf_counter() < t_end:
                routes0 = kept.trainer.expert_slots.copy()
                try:
                    losses.append(driver.step(*batch(step)))
                except param_server.PartialPushError as e:
                    failed += max(1, len(e.unpushed))
                    errors.append(f"step {step}: {e}")
                    break
                except native.RpcError as e:
                    failed += 1
                    errors.append(f"step {step}: {e}")
                    break
                step += 1
                routed = kept.trainer.expert_slots - routes0
                slots[:] += routed
                step_flops.append(kimi_linear_flops.step_flops(
                    c, seqs, seq_len, int(routed.sum())))
                stats.append(dict(driver.last_stats))
                for name, (version, _) in driver.last_trace.acks.items():
                    pushes[name].append(int(version))
                calls.extend(timed.take(driver))
            t_close = time.perf_counter()
            if slicer is not None:
                slicer.join()
            return t_open, t_close

        session.window(body, describe=(
            f"1 trainer, {len(names)} tensors, {seqs} x {seq_len} tokens a "
            f"step, push window {window}"),
            detail=lambda: (f"steps {len(losses)}, losses "
                            f"{[round(x, 4) for x in losses[:3]]}..."))
        for name in names:
            version, stacked = client.handoff(name)
            finals[name] = (int(version), stacked[0].copy())
        last_pull = {n: (driver.versions.get(n), driver._raw.get(n))
                     for n in names}
        mismatch = sum(
            1 for n in names
            if last_pull[n][0] != finals[n][0] or last_pull[n][1] is None
            or not np.array_equal(np.asarray(last_pull[n][1]), finals[n][1]))
    finally:
        client.close()
        server.stop()
    del server, driver, kept, last_pull
    gc.collect()   # the stopped server's handler holds it in a cycle
    for e in errors:
        session.log(e)

    faults = sum(1 for n in names
                 if pushes[n] != list(range(1, len(pushes[n]) + 1))
                 or finals.get(n, (-1,))[0] != len(pushes[n]))
    t_ref = time.perf_counter()
    session.log("device memory before the check: " + ", ".join(
        f"{k} {v / 1e9:.3f} GB" for k, v in (
            session.devices[0].memory_stats() or {}).items()
        if k in ("bytes_in_use", "peak_bytes_in_use")))
    readings = compare(ref_model, names, args.seed, init, bias, batch,
                       program, lr, beta)
    readings.update(version_faults=int(faults), pull_mismatch=int(mismatch),
                    nonfinite_steps=int(sum(not np.isfinite(x)
                                            for x in losses)))
    limits = cfg["limits"]
    compared = base.checks(readings, failed, limits)
    if control:
        low = control_steps(mcfg, ref_model, names, args.seed, init, bias,
                            batch, lr, beta)
        ctl = compare(ref_model, names, args.seed, init, bias, batch, low,
                      lr, beta)
        readings.update({f"control_{k}": v for k, v in ctl.items()})
        readings["control_checks"] = base.checks({**readings, **ctl}, failed,
                                                 limits)
    session.log(f"check {time.perf_counter() - t_ref:.3f} s: losses "
                f"{program.losses}, worst grad {readings['worst_grad']}, "
                f"worst state {readings['worst_state']}")

    window_s = session.window_s
    calls_arr = np.array(calls, dtype=np.float64).reshape(-1, 5)
    steps = len(stats)
    tokens = steps * seqs * seq_len
    passes = mla_moe.expert_passes(mcfg, seqs * seq_len)
    (fwd_ops, fwd_bytes), (vjp_ops, vjp_bytes) = \
        kimi_linear_flops.kda_core_work(c, seqs, seq_len)
    extra = {"steps": float(steps), "tokens": float(tokens),
             "model_flops": float(sum(step_flops)),
             "wall_s": sum(s["wall_ms"] for s in stats) / 1e3,
             "compute_s": sum(s["compute_ms"] for s in stats) / 1e3,
             "exposed_s": sum(s["exposed_comm_ms"] for s in stats) / 1e3,
             "routed_rows": (float(slots.sum())
                             / (steps * ref_model.moe_layers * passes)
                             if steps else 0.0),
             "kda_core_flops.fwd": float(fwd_ops),
             "kda_core_bytes.fwd": float(fwd_bytes),
             "kda_core_flops.vjp": float(vjp_ops),
             "kda_core_bytes.vjp": float(vjp_bytes)}
    for e, n in zip(ref_model.held, slots.sum(axis=0)):
        extra[f"expert_slots.{e}"] = float(n)
    return harness.Outcome(nbytes=nbytes, calls=calls_arr, window_s=window_s,
                           failed=failed, errors=errors, checks=compared,
                           readings=readings, extra=extra)


def control_steps(mcfg, m, names, seed, init, bias, batch, lr,
                  beta) -> "base.Checked":
    """The control: the program's trainer with matmul inputs in float8,
    two steps from the seed, the server's rule applied here."""
    import jax

    trainer = mla_moe.MlaMoeTrainer(
        dataclasses.replace(mcfg, matmul_dtype=base.CONTROL_DTYPE), bias)
    params = dict(zip(names, kref.make_params(m, seed, init)))
    momenta = {n: jax.numpy.zeros_like(p) for n, p in params.items()}
    out = base.Checked()
    for step in range(base.CHECKED_STEPS):
        ctx = trainer.forward(dict(params), *batch(step))
        grads = {}
        for n in reversed(names):
            g = trainer.backward(ctx, n)
            grads[n] = np.asarray(g)
            params[n], momenta[n] = trainer_reference.momentum_step(
                params[n], momenta[n], g, lr, beta)
        out.losses.append(float(ctx["loss"]))
        out.grads.append(grads)
        out.routes.append([np.asarray(s) for s, _ in trainer.last_routes])
        del ctx
    out.state = {n: (np.asarray(params[n]), np.asarray(momenta[n]))
                 for n in names}
    return out


def compare(m, names, seed, init, bias, batch, got: "base.Checked", lr,
            beta) -> dict:
    """``got``'s two steps against the reference's from the seed, routed
    as ``got`` routed. -> the four gaps and where the worst lie."""
    import jax
    import jax.numpy as jnp

    params = dict(zip(names, kref.make_params(m, seed, init)))
    momenta = None
    loss_gap, grad_pairs, flips, slots = 0.0, [], 0, 0
    for step in range(base.CHECKED_STEPS):
        value, grads, own = kref.loss_and_grads(
            m, params, *batch(step), bias, select=got.routes[step])
        value = float(value)
        loss_gap = max(loss_gap, abs(got.losses[step] - value)
                       / max(abs(value), 1e-30))
        for n in names:
            grad_pairs.append((f"{n}@{step + 1}", *base._gap(
                got.grads[step][n], grads[n])))
        for mine, theirs in zip(got.routes[step], own):
            theirs = np.asarray(theirs)
            flips += int(sum(len(set(a) - set(b))
                             for a, b in zip(mine.tolist(), theirs.tolist())))
            slots += mine.size
        if momenta is None:
            momenta = {n: jnp.zeros_like(p) for n, p in params.items()}
        for n in names:
            params[n], momenta[n] = trainer_reference.momentum_step(
                params[n], momenta[n], grads[n], lr, beta)
        del grads
        jax.block_until_ready(params)
    seeded = kref.make_params(m, seed, init)
    p_pairs = [(n, *base._change_gap(got.state[n][0], params[n], p0))
               for n, p0 in zip(names, seeded)]
    del seeded
    m_pairs = [(n, *base._gap(got.state[n][1], momenta[n])) for n in names]
    grad_gap, worst_grad = reference.worst(grad_pairs)
    p_gap, p_at = reference.worst(p_pairs)
    m_gap, m_at = reference.worst(m_pairs)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "state_gap": max(p_gap, m_gap),
            "param_gap": p_gap, "momentum_gap": m_gap,
            "route_flip_share": flips / max(slots, 1),
            "worst_grad": worst_grad,
            "worst_state": f"param {p_at}" if p_gap >= m_gap
            else f"momentum {m_at}",
            "losses": list(got.losses)}
