"""The parameter-server driver: closed-loop trainers against one shard.

The configuration's tensors are made on the cell's first chip from the
seed, a ParameterServer on ``tpu://127.0.0.1:0`` and the mix's clients
start in this process on the same chip (traffic crosses the host's shm
loopback, not a link), every distinct shape is warmed up, the window runs
(benchmark/traffic.py), the server exports its final state through its own
Handoff, and the plain reference replays every acknowledged push
(benchmark/check.py). A mix that names no driver gets this one.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark import check, data, harness, spec, traffic

# The mix keys benchmark/traffic.py reads (its docstring says what each does).
KEYS = {"loop", "workers", "step", "start", "grad_scale", "sample"}


def check_mix(mix: dict) -> None:
    """Refuse a mix the generator cannot run as written."""
    if mix.get("loop") != "closed":
        raise ValueError(f"traffic {mix.get('name')!r}: only a closed loop "
                         f"is implemented, not {mix.get('loop')!r}")
    ops = mix.get("step", [])
    if not ops or set(ops) - set(traffic.KINDS):
        raise ValueError(f"traffic {mix.get('name')!r}: step {ops!r} must "
                         f"be made of {sorted(traffic.KINDS)}")
    if int(mix.get("workers", 0)) < 1:
        raise ValueError(f"traffic {mix.get('name')!r}: needs workers >= 1")


def rate_summary(calls: np.ndarray, nbytes, t_open: float,
                 window_s: float) -> str:
    """GB/s of pulls and of pushes, and the spread of GB/s over the
    window's whole seconds: a slow run uniformly slow, or stalled."""
    b = np.asarray(nbytes, np.float64)[calls[:, 1].astype(np.int64)]
    kinds = calls[:, 0].astype(np.int64)
    n_s = max(1, int(window_s))
    sec = np.minimum((calls[:, 4] - t_open).astype(np.int64), n_s - 1)
    per_s = np.bincount(sec, weights=b, minlength=n_s) / 1e9
    pulls, pushes = (b[kinds == k].sum() / window_s / 1e9
                     for k in (traffic.PULL, traffic.PUSH))
    return (f"GB/s: pulls {pulls:.4f}, pushes {pushes:.4f}; per second min {per_s.min():.4f}, median "
            f"{np.median(per_s):.4f}, max {per_s.max():.4f}")


def run(cell: spec.Cell, args, session: "harness.Session",
        rehearse: bool = False, control: bool = False) -> "harness.Outcome":
    """Set-up, warm-up, the window and the check of one run. ``control``
    adds the bfloat16 replay's readings and, under ``control_checks``, the
    compared numbers with the replay in the program's place
    (calibrate.py)."""
    from brpc_tpu.runtime import param_server
    from brpc_tpu.runtime.tensor import TensorArena

    cfg, mix = cell.config, cell.traffic
    layout = spec.tensors(cfg, tiny=harness.TINY if rehearse else None)
    names = [n for n, _ in layout]
    shapes = [s for _, s in layout]
    nbytes = [4 * math.prod(s) for s in shapes]
    opt, init = cfg["optimizer"], cfg["init"]
    lr, beta = float(opt["lr"]), float(opt["momentum"])

    gen = data.generator(shapes)
    params = data.make(gen, args.seed, data.PARAMS, init["param_std"])
    bases = data.make(gen, args.seed, data.GRADS, init["grad_std"])
    session.mark("data")
    n_workers = int(mix["workers"])
    largest = max(nbytes)
    server = param_server.ParameterServer(
        dict(zip(names, params)), lr=lr, momentum=beta,
        arena=TensorArena(2 * n_workers * largest + (256 << 20)))
    del params
    port = server.start("127.0.0.1:0")
    addr = f"tpu://127.0.0.1:{port}"
    clients = [param_server.ParameterClient(
        addr, arena=TensorArena(2 * largest + (64 << 20)))
        for _ in range(n_workers)]
    try:
        for c in clients:
            c.meta()
        session.mark("server")
        grad_fn = data.scaler()
        workers = [traffic.Worker(i, clients[i], mix, names, nbytes, bases,
                                  grad_fn, args.seed, traced=bool(args.trace))
                   for i in range(n_workers)]
        traffic.warm_up(workers[0], shapes)
        session.mark("warm_up")
        t_open, _ = session.window(
            lambda seconds, during: traffic.drive(workers, seconds, during),
            describe=f"{n_workers} workers, {len(names)} tensors",
            detail=lambda: (f"worker steps {[w.steps for w in workers]}, "
                            f"calls {[len(w.calls) for w in workers]}"))
        # The server's final state, through its own export (Handoff: the
        # stacked [params, momenta] at the latest committed version).
        finals = {}
        for t, name in enumerate(names):
            version, stacked = clients[0].handoff(name)
            finals[t] = (int(version), stacked[0], stacked[1])
    finally:
        for c in clients:
            c.close()
        server.stop()
    del server
    window_s = session.window_s
    calls = np.array([c for w in workers for c in w.calls],
                     dtype=np.float64).reshape(-1, 5)
    session.log(rate_summary(calls, nbytes, t_open, window_s))
    failed = sum(w.failed for w in workers)
    errors = [f"worker {w.index}: {e}" for w in workers for e in w.errors]
    for e in errors:
        session.log(e)
    t_ref = time.perf_counter()
    p0s = data.make(gen, args.seed, data.PARAMS, init["param_std"])
    pushes = [p for w in workers for p in w.pushes]
    pulls = [p for w in workers for p in w.sampled()]
    readings = check.compare(shapes, p0s, bases, pushes, pulls, finals,
                             lr, beta, control=control)
    compared = check.checks(readings, failed, cfg["limits"])
    if control:
        readings["control_checks"] = check.control_checks(
            readings, failed, cfg["limits"])
    session.log(f"check {time.perf_counter() - t_ref:.3f} s over "
                f"{len(pushes)} pushes, {readings['pulls_compared']} pulls; "
                f"worst pull {readings['worst_pull']}, worst state "
                f"{readings['worst_state']}")
    return harness.Outcome(nbytes=nbytes, calls=calls, window_s=window_s,
                           failed=failed, errors=errors, checks=compared,
                           readings=readings)
