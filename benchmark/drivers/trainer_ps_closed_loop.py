"""The trainer driver: a layered model trained through one parameter-server
shard, in a closed loop of steps.

The configuration's parameters are made on the cell's first chip from the
seed (benchmark/trainer_reference.py) and served by a ParameterServer on
``tpu://127.0.0.1:0``; one ParameterClient feeds an OverlappedStepDriver
over the program's trainer (brpc_tpu/models/mla_moe.py), which pulls each
parameter's next version and pushes each gradient inside the step's
compute. Trainer and shard share the process and the chip, as BytePS
colocates them. Each step waits for the last (a closed loop): steps run
until the window's end, and the window closes when the last step has.

Warm-up makes the two checked steps from the seeded parameters through the
same driver object and programs: their losses, their pushed gradients
(host copies), their expert choices, and then the server's state, which
every tensor's Handoff exports (each is then installed back at its version
and committed, the fleet's handshake onto the same shard, so the window
pushes on). After the window every tensor is exported again. ``correct``:

  failed_calls      steps that raised: the names they left unpushed, else 1
  version_faults    tensors whose acknowledged push versions are not exactly
                    1..N, or whose final export is not at N
  nonfinite_steps   window steps whose loss is not finite
  pull_mismatch     tensors whose last pull differs from the final export
                    (its version or a bit of its array)
  loss_gap          worst |loss - ref| / |ref| of the two checked steps
  grad_gap          worst max|g - g_ref| of a tensor over the larger of its
                    max|g_ref| and its family's median (reference.worst), of
                    the two checked steps
  state_gap         the state exported after warm-up against the reference's
                    two momentum-SGD steps from the seed: each parameter's
                    change from the seed p0, max|(p - p0) - (p_ref - p0)|
                    less two float32 spacings of p_ref (the rounding of two
                    updates), over max|p_ref - p0| (a parameter left
                    unchanged reads about 1), and each momentum as grad_gap
                    reads a gradient
  route_flip_share  share of the checked steps' (token, slot) choices whose
                    expert is not in the reference's own top-k; the
                    reference takes the program's choices, so that a tie
                    broken the other way by rounding moves no other gap

Each pull and push of the window is a call row: a pull from its issue to
its array on the device, a push from its submit to its acknowledgement
(``RunTrace.acks``). In a traced run each sits in a ``bench.<kind>/<tensor>``
rpcz span, as the parameter-server driver's calls do, so that the program's
stage annotations can be read per call.

The reference is computed on the chip at full width after the window. The
control (``control``) is the program's own trainer with matmul inputs in
float8_e4m3fn, below the bfloat16 the configuration states, run for the
same two steps from the seed without the server; ``control_checks`` holds
its verdict, which must be refused. The rehearsal builds the
configuration's ``tiny`` block, a small model of the same structure.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import math
import threading
import time
from typing import Dict, List

import numpy as np

# The program's model first: a checkout without it fails here, at once.
from brpc_tpu.models import mla_moe

from benchmark import harness, reference, trainer_flops, trainer_reference
from benchmark import traffic

KEYS = {"trainers", "seqs", "seq_len", "overlap", "push_window",
        "warm_up_steps", "ids"}
CHECKED_STEPS = 2
CONTROL_DTYPE = "float8_e4m3fn"
TINY_BLOCKS = {"q_block": 16, "loss_chunk": 24}


def check_mix(mix: dict) -> None:
    """Refuse a mix this driver cannot run as written."""
    name = mix.get("name")
    if int(mix.get("trainers", 0)) != 1:
        raise ValueError(f"traffic {name!r}: one trainer per shard")
    if int(mix.get("warm_up_steps", 0)) != CHECKED_STEPS:
        raise ValueError(f"traffic {name!r}: warm-up makes the "
                         f"{CHECKED_STEPS} checked steps")
    if mix.get("ids") != "seeded_uniform":
        raise ValueError(f"traffic {name!r}: ids {mix.get('ids')!r}; only "
                         f"seeded_uniform is implemented")
    if int(mix.get("seqs", 0)) < 1 or int(mix.get("seq_len", 0)) < 2:
        raise ValueError(f"traffic {name!r}: needs seqs >= 1, seq_len >= 2")
    if int(mix.get("push_window", 0)) < 1 or not isinstance(
            mix.get("overlap"), bool):
        raise ValueError(f"traffic {name!r}: needs push_window >= 1 and a "
                         f"boolean overlap")


class Kept:
    """The program's trainer as the step driver sees it, keeping a host
    copy of every gradient it yields while ``keep`` is on."""

    def __init__(self, trainer):
        self.trainer = trainer
        self.names = trainer.names
        self.keep = False
        self.grads: Dict[str, np.ndarray] = {}

    def place(self, name, arr):
        return self.trainer.place(name, arr)

    def forward(self, params, x, y):
        return self.trainer.forward(params, x, y)

    def backward(self, ctx, name):
        g = self.trainer.backward(ctx, name)
        if self.keep:
            self.grads[name] = np.asarray(g)
        return g

    def loss(self, ctx):
        return self.trainer.loss(ctx)


class Calls:
    """The step driver's ``call_span``: times each pull and push submit and,
    while rpcz collects, opens its ``bench.<kind>/<tensor>`` span."""

    def __init__(self, index: Dict[str, int]):
        self.index = index
        self.rows: List[tuple] = []   # (kind, name, t_issue, t_done)

    @contextlib.contextmanager
    def __call__(self, kind: str, name: str):
        from brpc_tpu.observability import tracing

        with tracing.trace_span(f"bench.{kind}/{self.index[name]}"):
            t0 = time.monotonic()
            yield
            t1 = time.monotonic()
        self.rows.append((kind, name, t0, t1))

    def take(self, driver) -> List[tuple]:
        """The last step's calls as the harness's rows, (kind, tensor,
        version, t_issue, t_done); a push is done when acknowledged."""
        acks = driver.last_trace.acks
        out = []
        for kind, name, t0, t1 in self.rows:
            if kind == "pull":
                out.append((traffic.PULL, self.index[name],
                            driver.versions[name], t0, t1))
            else:
                version, t_ack = acks[name]
                out.append((traffic.PUSH, self.index[name], version, t0,
                            t_ack))
        self.rows.clear()
        return out


class Checked:
    """Two steps' losses, gradients and expert choices, and the state
    (parameters, momenta) after them: the program's, or the control's."""

    def __init__(self):
        self.losses: List[float] = []
        self.grads: List[Dict[str, np.ndarray]] = []
        self.routes: List[List[np.ndarray]] = []
        self.state: Dict[str, tuple] = {}


def _model(cfg: dict, rehearse: bool) -> dict:
    c = dict(cfg)
    if rehearse:
        c.update(cfg["tiny"])
    return c


def run(cell, args, session: "harness.Session", rehearse: bool = False,
        control: bool = False) -> "harness.Outcome":
    """Set-up, the checked warm-up, the window and the check of one run."""
    from brpc_tpu.runtime import native, param_server
    from brpc_tpu.runtime.step_driver import OverlappedStepDriver
    from brpc_tpu.runtime.tensor import TensorArena

    cfg, mix = cell.config, cell.traffic
    c = _model(cfg, rehearse)
    seqs = int(mix["seqs"])
    seq_len = int(c["seq_len"] if rehearse else mix["seq_len"])
    ref_model = trainer_reference.Model.of(c)
    layout = trainer_reference.layout(ref_model)
    mcfg = mla_moe.Config.from_hf(c, **(TINY_BLOCKS if rehearse else {}))
    if mla_moe.tensor_layout(mcfg) != layout:
        raise ValueError("the program's tensors are not the configuration's")
    names = [n for n, _ in layout]
    index = {n: t for t, n in enumerate(names)}
    shapes = [s for _, s in layout]
    nbytes = [4 * math.prod(s) for s in shapes]
    lr, beta = float(cfg["optimizer"]["lr"]), float(cfg["optimizer"]["momentum"])
    init = cfg["init"]
    window = int(mix["push_window"])

    params = trainer_reference.make_params(shapes, args.seed,
                                           init["param_std"])
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
    kept = Kept(mla_moe.MlaMoeTrainer(mcfg, bias))
    timed = Calls(index)
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
    program = Checked()
    finals: Dict[str, tuple] = {}
    try:
        client.meta()
        driver.prime()
        session.mark("server")
        kept.keep = True
        for step in range(CHECKED_STEPS):
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
            step = CHECKED_STEPS
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
                step_flops.append(trainer_flops.step_flops(
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
    readings = compare(ref_model, shapes, names, args.seed, init, bias, batch,
                       program, lr, beta)
    readings.update(version_faults=int(faults), pull_mismatch=int(mismatch),
                    nonfinite_steps=int(sum(not np.isfinite(x)
                                            for x in losses)))
    limits = cfg["limits"]
    compared = checks(readings, failed, limits)
    if control:
        low = control_steps(mcfg, shapes, names, args.seed, init, bias, batch,
                            lr, beta)
        ctl = compare(ref_model, shapes, names, args.seed, init, bias, batch,
                      low, lr, beta)
        readings.update({f"control_{k}": v for k, v in ctl.items()})
        readings["control_checks"] = checks({**readings, **ctl}, failed,
                                            limits)
    session.log(f"check {time.perf_counter() - t_ref:.3f} s: losses "
                f"{program.losses}, worst grad {readings['worst_grad']}, "
                f"worst state {readings['worst_state']}")

    window_s = session.window_s
    calls_arr = np.array(calls, dtype=np.float64).reshape(-1, 5)
    steps = len(stats)
    tokens = steps * seqs * seq_len
    extra = {"steps": float(steps), "tokens": float(tokens),
             "model_flops": float(sum(step_flops)),
             "wall_s": sum(s["wall_ms"] for s in stats) / 1e3,
             "compute_s": sum(s["compute_ms"] for s in stats) / 1e3,
             "exposed_s": sum(s["exposed_comm_ms"] for s in stats) / 1e3,
             "routed_rows": (float(slots.sum()) / (steps * ref_model.moe_layers)
                             if steps else 0.0)}
    for e, n in zip(ref_model.held, slots.sum(axis=0)):
        extra[f"expert_slots.{e}"] = float(n)
    return harness.Outcome(nbytes=nbytes, calls=calls_arr, window_s=window_s,
                           failed=failed, errors=errors, checks=compared,
                           readings=readings, extra=extra)


def checks(readings: dict, failed: int, limits: dict) -> dict:
    """Each compared number beside its limit, in a fixed order."""
    out = {"failed_calls": {"value": int(failed), "limit": 0}}
    for k in ("version_faults", "nonfinite_steps", "pull_mismatch"):
        out[k] = {"value": int(readings[k]), "limit": 0}
    for k in ("loss_gap", "grad_gap", "state_gap", "route_flip_share"):
        out[k] = {"value": float(readings[k]), "limit": float(limits[k])}
    return out


def control_steps(mcfg, shapes, names, seed, init, bias, batch, lr,
                  beta) -> Checked:
    """The control: the program's trainer with matmul inputs in float8,
    two steps from the seed, the server's rule applied here."""
    import jax

    trainer = mla_moe.MlaMoeTrainer(
        dataclasses.replace(mcfg, matmul_dtype=CONTROL_DTYPE), bias)
    params = dict(zip(names, trainer_reference.make_params(
        shapes, seed, init["param_std"])))
    momenta = {n: jax.numpy.zeros_like(p) for n, p in params.items()}
    out = Checked()
    for step in range(CHECKED_STEPS):
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


def compare(m, shapes, names, seed, init, bias, batch, got: Checked, lr,
            beta) -> dict:
    """``got``'s two steps against the reference's from the seed, routed
    as ``got`` routed. -> the four gaps and where the worst lie."""
    import jax
    import jax.numpy as jnp

    params = dict(zip(names, trainer_reference.make_params(
        shapes, seed, init["param_std"])))
    momenta = None
    loss_gap, grad_pairs, flips, slots = 0.0, [], 0, 0
    for step in range(CHECKED_STEPS):
        value, grads, own = trainer_reference.loss_and_grads(
            m, params, *batch(step), bias, select=got.routes[step])
        value = float(value)
        loss_gap = max(loss_gap, abs(got.losses[step] - value)
                       / max(abs(value), 1e-30))
        for n in names:
            grad_pairs.append((f"{n}@{step + 1}", *_gap(
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
    seeded = trainer_reference.make_params(shapes, seed, init["param_std"])
    p_pairs = [(n, *_change_gap(got.state[n][0], params[n], p0))
               for n, p0 in zip(names, seeded)]
    del seeded
    m_pairs = [(n, *_gap(got.state[n][1], momenta[n])) for n in names]
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


@functools.lru_cache(maxsize=None)
def _gap_program():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x, ref: (jnp.max(jnp.abs(x - ref)),
                                   jnp.max(jnp.abs(ref))))


def _gap(x, ref):
    """(max |x - ref|, max |ref|) as floats, in float32."""
    d, r = _gap_program()(x, ref)
    return float(d), float(r)


@functools.lru_cache(maxsize=None)
def _change_gap_program():
    import jax
    import jax.numpy as jnp

    def gap(x, ref, p0):
        rounding = 2 * jnp.abs(jnp.spacing(ref))
        return (jnp.max(jnp.maximum(jnp.abs(x - ref) - rounding, 0)),
                jnp.max(jnp.abs(ref - p0)))

    return jax.jit(gap)


def _change_gap(x, ref, p0):
    """(max |(x - p0) - (ref - p0)| beyond two float32 spacings of ``ref``,
    max |ref - p0|) as floats: a change from the seed ``p0`` against the
    reference's change. A norm weight of 1 moves a few spacings in two
    steps, and each of the two updates rounds to its nearest spacing: that
    much of a gap is rounding, not the update."""
    d, r = _change_gap_program()(x, ref, p0)
    return float(d), float(r)
