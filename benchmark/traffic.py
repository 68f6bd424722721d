"""The one traffic generator: closed-loop workers against a ParameterServer.

A mix file (benchmark/traffic/<mix>.json) sets its parameters:

  workers     clients in the closed loop, one call in flight each, each on
              its own thread with its own ParameterClient and arena
  step        the operations of one worker step, in order; each runs over
              every tensor of the set ("pull": ParameterClient.pull,
              "push": ParameterClient.push_grad of a fresh gradient)
  start       "seeded_offset": each worker walks the tensor list from an
              offset drawn from the seed, wrapping round
  grad_scale  [lo, hi]: a push sends base_gradient * s, s drawn uniformly
              per worker and step, made on the device just before the
              call, so each push moves a fresh array through the D2H
  sample      how many pulled arrays each worker keeps for the check: a
              uniform reservoir of ``pulls_per_worker`` plus its newest
              pull of the largest tensor (``largest_per_worker``)

Each call is timed on the host clock from its issue to its completion: a
pull when its array is ready on the client's device, a push when the
server's acknowledgement (the new version) is back. Workers stop issuing
at the window's end; the window closes when the last call has completed.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, List, Optional, Sequence

import numpy as np

from benchmark import data

PULL, PUSH = 0, 1
KINDS = {"pull": PULL, "push": PUSH}


def _traced(kind: str, t: int):
    """The traced run's annotations around one call: a profiler host span
    named after its kind (so idle gaps can be told apart by what the host
    was doing) and an rpcz root span, under which the program's own stage
    annotations (rpc, device_put, arena_stage) record."""
    import jax

    from brpc_tpu.observability import tracing

    stack = contextlib.ExitStack()
    stack.enter_context(jax.profiler.TraceAnnotation(kind))
    if kind != "grad":
        stack.enter_context(tracing.trace_span(f"bench.{kind}/{t}"))
    return stack


def _untraced(kind: str, t: int):
    return contextlib.nullcontext()


class Worker:
    """One closed-loop client. Results: ``calls`` (kind, tensor, version,
    t_issue, t_done), ``pushes`` (tensor, version, scale) of every
    acknowledged push, ``kept`` pulled arrays for the check, ``failed``
    and ``errors`` for calls that raised."""

    def __init__(self, index: int, client, mix: dict, names: Sequence[str],
                 nbytes: Sequence[int], bases: Sequence, grad_fn: Callable,
                 seed: int, traced: bool):
        self.index = index
        self.client = client
        self.ops = [KINDS[o] for o in mix["step"]]
        self.names = list(names)
        self.bases = bases
        self.grad_fn = grad_fn
        rng = data.worker_rng(seed, index)
        self._scale_rng, self._sample_rng = rng.spawn(2)
        n = len(self.names)
        offset = int(rng.integers(n)) if mix.get("start") == "seeded_offset" \
            else 0
        self.order = [(offset + k) % n for k in range(n)]
        self.lo, self.hi = (float(x) for x in mix.get("grad_scale", (1, 1)))
        sample = mix.get("sample", {})
        self.reservoir = int(sample.get("pulls_per_worker", 0))
        self.keep_largest = int(sample.get("largest_per_worker", 0)) > 0
        self.largest = int(np.argmax(nbytes))
        self.annotate = _traced if traced else _untraced
        self.calls: List[tuple] = []
        self.pushes: List[tuple] = []
        self.kept: List[tuple] = []
        self.newest_largest: Optional[tuple] = None
        self.failed = 0
        self.errors: List[str] = []
        self._pulls_seen = 0
        self.steps = 0

    # ---- one call of each kind ----

    def pull(self, t: int) -> None:
        from brpc_tpu.runtime import native

        with self.annotate("pull", t):
            t0 = time.perf_counter()
            try:
                version, arr = self.client.pull(self.names[t])
                arr.block_until_ready()
            except native.RpcError as e:
                self._fail("pull", t, e)
                return
            t1 = time.perf_counter()
        self.calls.append((PULL, t, version, t0, t1))
        self._keep(t, version, arr)

    def push(self, t: int, scale: np.float32) -> None:
        from brpc_tpu.runtime import native

        with self.annotate("grad", t):
            grad = self.grad_fn(self.bases[t], scale)
            grad.block_until_ready()
        with self.annotate("push", t):
            t0 = time.perf_counter()
            try:
                version = self.client.push_grad(self.names[t], grad)
            except native.RpcError as e:
                self._fail("push", t, e)
                return
            t1 = time.perf_counter()
        self.calls.append((PUSH, t, version, t0, t1))
        self.pushes.append((t, version, scale))

    def _fail(self, kind: str, t: int, e) -> None:
        self.failed += 1
        if len(self.errors) < 8:
            self.errors.append(f"{kind} {self.names[t]}: {e}")

    def _keep(self, t: int, version: int, arr) -> None:
        """Uniform reservoir of pulled arrays (Algorithm R) and the newest
        pull of the largest tensor: what the check compares."""
        if self.keep_largest and t == self.largest:
            self.newest_largest = (t, version, arr)
            return
        k = self._pulls_seen
        self._pulls_seen += 1
        if k < self.reservoir:
            self.kept.append((t, version, arr))
        elif self.reservoir:
            j = int(self._sample_rng.integers(k + 1))
            if j < self.reservoir:
                self.kept[j] = (t, version, arr)

    # ---- the loop ----

    def run(self, t_end: float) -> None:
        while True:
            scale = np.float32(self._scale_rng.uniform(self.lo, self.hi))
            for op in self.ops:
                for t in self.order:
                    if time.perf_counter() >= t_end:
                        return
                    if op == PULL:
                        self.pull(t)
                    else:
                        self.push(t, scale)
            self.steps += 1

    def sampled(self) -> List[tuple]:
        out = list(self.kept)
        if self.newest_largest is not None:
            out.append(self.newest_largest)
        return out


def warm_up(worker: Worker, shapes: Sequence[tuple]) -> None:
    """Each call of the worker's step, in order, on the first tensor of
    each distinct shape: every program the window runs (the gradient
    scale, the server's update) compiles or loads from the cache here, and
    nothing the window does not run. The pushes are recorded like the
    window's, so the check replays them too."""
    seen = set()
    for t, shape in enumerate(shapes):
        if shape in seen:
            continue
        seen.add(shape)
        for op in worker.ops:
            if op == PULL:
                worker.pull(t)
            else:
                worker.push(t, np.float32(1.0))
    worker.calls.clear()
    worker.kept.clear()
    worker.newest_largest = None
    worker._pulls_seen = 0


def drive(workers: Sequence[Worker], seconds: float,
          during: Optional[Callable[[float], None]] = None):
    """Run every worker for ``seconds``; -> (t_open, t_close). ``during``
    (the traced run's profiler slice) runs on this thread meanwhile,
    given the window's end."""
    go = threading.Event()
    box = {}
    errors = []

    def body(w: Worker) -> None:
        go.wait()
        try:
            w.run(box["t_end"])
        except BaseException as e:  # noqa: BLE001 — reported after join
            errors.append(e)

    threads = [threading.Thread(target=body, args=(w,), daemon=True,
                                name=f"bench-worker-{w.index}")
               for w in workers]
    for th in threads:
        th.start()
    t_open = time.perf_counter()
    box["t_end"] = t_open + seconds
    go.set()
    if during is not None:
        during(box["t_end"])
    for th in threads:
        th.join()
    t_close = time.perf_counter()
    if errors:
        raise errors[0]
    return t_open, t_close
