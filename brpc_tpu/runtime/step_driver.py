"""Overlapped training step driver — hide the tensor wire behind compute.

The flagship training loop used to run compute -> gradient push ->
next-step pull strictly serially, so every wire byte was exposed step
time even though ``PipelineWindow`` (#83) already overlaps arena staging
with the wire one level down. This driver lifts that overlap to the
WHOLE step (PAPERS.md T3: fine-grained compute/communication overlap is
where the step time hides): the step decomposes into per-tensor nodes

    forward -> bwd:k (compute lane, top layer first)
    bwd:k   -> push:k -> opt:k -> pull:k (wire lane)

scheduled by the tier-1-pure :mod:`step_sched` core, so the gradient
push of layer k (encode included — the PR 7 ``encoder=`` hook runs at
arena-stage time on the wire lane) overlaps backward compute of the next
layer, and next-step pulls overlap the server-side optimizer applies of
the remaining pushes. Everything rides the EXISTING client machinery:
pushes go through one bounded :class:`PipelineWindow` per step (async
futures, submit-order replies, ``complete_one`` as the per-tensor
confirm point), pulls through ``client.pull`` (one-sided reads when the
client maps the server's window, quantized when negotiated, QoS-stamped,
paced). ``overlap=False`` runs the SAME nodes serially on one thread —
today's driver exactly, the A/B baseline.

Failure semantics: a mid-step push failure cancels only its dependents,
every other branch completes, and the step raises
:class:`~brpc_tpu.runtime.param_server.PartialPushError` with the
versions that DID land (``applied``) vs the names with no confirmed
apply (``unpushed``) — re-pushing an applied gradient double-steps the
server's momentum, so salvage must be per-name (the PR 7 discipline).

Instrumented end to end: one ``train_step`` rpcz root span per step with
a child span per node (wire-side spans carry the PipelineWindow's
``arena_stage``/``wire_wait`` and the driver's ``encode`` stage
annotations, so a trace VISIBLY shows push spans inside the next layer's
compute span), plus ``step_exposed_comm_ms`` / ``step_overlapped_comm_ms``
recorders on /vars (samples in milliseconds, as named).

The tail's pull lookahead: once every push of the step is confirmed,
only pull nodes are left on the wire lane, and each one pays an RPC (the
shard's D2H and staging, the transport) and then a ``device_put`` of its
reply. From then on a pull node sends the NEXT pull's request (the
client's ``pull_send``, ``pull``'s RPC path split at the wire with
``pull_finish`` as its second half) before it places its own reply, so
the shard serves pull k+1 while the client copies pull k into HBM: the
two halves run in opposite directions and the chain pays about ``max(rpc, device_put)`` per tensor instead of
their sum. Exactly one request goes ahead: a deeper window would queue
each pull behind others, and every pull's issue-to-ready time would grow
with the depth. Nothing changes before the tail: a pull the lane runs
during backward stays synchronous, and no request is outstanding while
the lane runs a push or a confirm, so backward sees no more concurrent
wire work than before. Clients without ``pull_send`` (the fleet) and
one-sided readers pull synchronously. ``step_driver_pulls_ahead`` on
/vars counts the pulls whose request went ahead (``last_stats`` has the
step's count).

Memory: the driver holds one copy of the parameter set, not three. A
parameter is released once its layer's backward has run (the next
version is already on its way through the pull lane), and a gradient once
its push is staged into the channel's arena. Each push's acknowledgement
(the new version, and when it arrived) lands in the step's
``RunTrace.acks``.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

from brpc_tpu.runtime import native
from brpc_tpu.runtime.param_server import PartialPushError
from brpc_tpu.runtime.step_sched import (COMPUTE, WIRE, StepFailure,
                                         StepGraph, run_graph)
from brpc_tpu.runtime.tensor import PipelineWindow

_metrics_cache = None


def _set_trace(ctx) -> None:
    """Make ``ctx`` (a ``tracing.current_trace()`` pair) this thread's
    trace context again; (0, 0) clears it."""
    from brpc_tpu.observability import tracing

    if ctx[0] or ctx[1]:
        tracing.set_trace(*ctx)
    else:
        tracing.clear_trace()


def _trace_handoff_ctx(tid: int, sid: int, qos=None):
    """The wire-lane context factory both drivers hand to ``run_graph``:
    each lane's thread inherits the step's rpcz trace context (and,
    when ``qos`` — a zero-arg context-manager factory — is given, the
    BULK QoS stamp: the FleetClient worker-thread discipline). Restore,
    don't clear, on exit: in serial mode this wraps the CALLER's own
    thread, whose ambient context must survive the step."""

    @contextlib.contextmanager
    def wire_ctx():
        from brpc_tpu.observability import tracing

        had_t, had_s = tracing.current_trace()
        if tid:
            tracing.set_trace(tid, sid)
        try:
            with (qos() if qos is not None
                  else contextlib.nullcontext()):
                yield
        finally:
            if tid:
                _set_trace((had_t, had_s))

    return wire_ctx


def _metrics():
    global _metrics_cache
    if _metrics_cache is None:
        from brpc_tpu.observability import metrics as obs

        _metrics_cache = {
            # Full step wall time (us, the standard recorder unit).
            "step": obs.latency("step_driver_step"),
            # Samples are MILLISECONDS, as the names say: the step
            # breakdown reads naturally next to wall-clock step times
            # (a 1MB-scale step is tens of ms; us percentiles of comm
            # slices would render as noise-width integers).
            "exposed": obs.latency("step_exposed_comm_ms"),
            "overlapped": obs.latency("step_overlapped_comm_ms"),
            "steps": obs.counter("step_driver_steps"),
            "pulls_ahead": obs.counter("step_driver_pulls_ahead"),
            "partial": obs.counter("step_driver_partial_failures"),
        }
    return _metrics_cache


class _SentPull:
    """One pull request in flight, with its caller's call span open."""

    __slots__ = ("span", "ctx", "ticket", "err")

    def __init__(self, span, ctx):
        self.span = span  # the call_span context manager, entered
        self.ctx = ctx    # the trace context inside it
        self.ticket = None
        self.err: Optional[BaseException] = None


class _TailPulls:
    """The tail's one-deep pull lookahead (module docstring) over the
    client's ``pull_send``/``pull_finish``: ``pull(name, nxt)`` makes
    sure ``name``'s request is in flight, sends ``nxt``'s, then
    completes ``name``'s. Each pull's call span opens at its send and
    closes at its finish, with the stages of both landing on it."""

    def __init__(self, client, call_span, counter):
        self.client = client
        self.call_span = call_span
        self.counter = counter
        # The running pull's request and the one ahead of it; whatever
        # is left here when the step ends is dropped.
        self.pending: Dict[str, _SentPull] = {}
        self.sent_ahead = 0

    def pull(self, name: str, nxt: Optional[str]):
        if name not in self.pending:
            self.pending[name] = self._send(name)
        if nxt is not None:
            self.pending[nxt] = self._send(nxt)
            self.sent_ahead += 1
            self.counter.add(1)
        return self._finish(self.pending.pop(name))

    def _send(self, name: str) -> _SentPull:
        """Open ``name``'s call span, issue its request inside it (the
        RPC's spans parent there), and give the caller its context back.
        A failed send is kept for ``name``'s own node to raise."""
        from brpc_tpu.observability import tracing

        outer = tracing.current_trace()
        span = self.call_span("pull", name)
        span.__enter__()
        sent = _SentPull(span, tracing.current_trace())
        try:
            sent.ticket = self.client.pull_send(name)
        except Exception as e:  # noqa: BLE001 — belongs to name's node
            sent.err = e
        finally:
            _set_trace(outer)
        return sent

    def _finish(self, sent: _SentPull):
        """-> (version, device array), inside the pull's own call span,
        which closes here."""
        from brpc_tpu.observability import tracing

        outer = tracing.current_trace()
        _set_trace(sent.ctx)
        try:
            if sent.err is not None:
                raise sent.err
            got = self.client.pull_finish(sent.ticket)
        except BaseException as e:
            sent.span.__exit__(type(e), e, e.__traceback__)
            raise
        else:
            sent.span.__exit__(None, None, None)
        finally:
            _set_trace(outer)
        return got

    def drop(self) -> None:
        """Cancel a request still ahead when the step ended without its
        node (an abort): the reply's view is released exactly once and
        the call span closes as cancelled, with no call row."""
        from brpc_tpu.observability import tracing

        while self.pending:
            name, sent = self.pending.popitem()
            if sent.ticket is not None:
                sent.ticket.cancel()
            err = native.RpcError(native.TRPC_ECANCELED,
                                  f"pull of {name} sent ahead, not consumed")
            outer = tracing.current_trace()
            try:
                sent.span.__exit__(type(err), err, None)
            finally:
                _set_trace(outer)


class OverlappedStepDriver:
    """Drive an RPC training loop over a layered harness.

    ``client``: a :class:`ParameterClient` (pushes ride one
    ``PipelineWindow`` per step over its channel) or any fleet-shaped
    object with ``pull``/``push_grad``/``pull_all`` (pushes confirm
    synchronously per name — the FleetClient path, where windowing lives
    inside each shard stream).

    ``harness`` protocol (see ``models.tensor_service.LayeredMLP``):
      * ``names``: parameter names in FORWARD order;
      * ``place(name, arr)``: apply the harness's sharding/placement;
      * ``forward(params, x, y) -> ctx``;
      * ``backward(ctx, name) -> grad`` (called top layer first);
      * ``loss(ctx) -> float``.

    ``call_span(kind, name)`` (optional) returns a context manager entered
    around each pull (``kind`` ``"pull"``) and each push submit
    (``"push"``) on the wire lane: a caller's own span or timer per call,
    which the tensor path's stage annotations (``rpc``, ``device_put``,
    ``arena_stage``) land on. A pull's span runs from its request's issue
    to its array being ready on the device, also when the request went
    ahead from the previous pull's node.

    Pulls in the tail (every push of the step confirmed) go one request
    ahead through a client's ``pull_send``/``pull_finish``: pull k sends pull k+1's request,
    then places its own reply (module docstring). The depth is one and
    not a knob: at depth one a pull's issue-to-ready time stays about
    ``rpc + device_put``, while a deeper window queues each pull behind
    the others. A failed request sent ahead fails its own ``pull:<name>``
    node; one left unconsumed when the step ends (an abort) is cancelled
    and its view released. ``last_stats["pulls_ahead"]`` and the
    ``step_driver_pulls_ahead`` counter count the pulls that went ahead.
    """

    def __init__(self, client, harness, overlap: bool = True,
                 window: int = 4, call_span=None):
        self.client = client
        self.harness = harness
        self.overlap = overlap
        self.window = max(1, window)
        self._params: Dict[str, object] = {}  # placed device arrays
        self._raw: Dict[str, object] = {}     # pulled, not yet placed
        self.versions: Dict[str, int] = {}    # last confirmed per name
        self._m = _metrics()
        self.last_stats: Optional[dict] = None
        self.last_trace = None  # RunTrace of the last SUCCESSFUL step
        self.call_span = call_span
        self.totals = {"steps": 0, "wall_ms": 0.0, "compute_ms": 0.0,
                       "wire_busy_ms": 0.0, "exposed_comm_ms": 0.0,
                       "overlapped_comm_ms": 0.0}

    # ---- setup ----

    def prime(self) -> None:
        """Fetch the full parameter set once (the step-0 pull the
        overlap then amortizes into every later step's shadow)."""
        got = self.client.pull_all(list(self.harness.names),
                                   window=self.window)
        for name, (version, arr) in got.items():
            self._raw[name] = arr
            self.versions[name] = version

    def _note_push_error(self, e: "native.RpcError") -> None:
        """The push-side healing every other push path runs on RpcError
        (push_grad/push_all): overload answers feed the client's pacer,
        and an undecodable-push / pre-codec-rollback answer drops the
        stale codec advertisement so the NEXT step renegotiates (raw).
        The driver still surfaces THIS step's failure to the caller —
        healing changes what the retry sends, not whether this step
        failed. Fleet-shaped clients run both hooks inside their own
        push_grad, so the getattr guards just no-op there."""
        pacer = getattr(self.client, "pacer", None)
        if pacer is not None:
            pacer.note(e)
        heal = getattr(self.client, "_codec_push_failed", None)
        if heal is not None:
            heal(e)

    # ---- one step ----

    def step(self, x, y) -> float:
        """One training step; returns the loss. Overlapped mode pulls
        each parameter's NEXT version inside this step's shadow, so the
        next call starts compute immediately."""
        from brpc_tpu.observability import tracing

        import jax

        t0 = time.monotonic()
        pacer = getattr(self.client, "pacer", None)
        if pacer is not None:
            pacer.pace()  # honor any shed-storm retry-after debt
        names: List[str] = list(self.harness.names)
        rev = list(reversed(names))
        grads: Dict[str, object] = {}
        step_versions: Dict[str, int] = {}
        push_failed: Dict[str, BaseException] = {}
        ctx_box: Dict[str, object] = {}
        channel = getattr(self.client, "channel", None)
        acks: Dict[str, tuple] = {}
        call_span = self.call_span or (lambda kind, name:
                                       contextlib.nullcontext())
        tail = (_TailPulls(self.client, call_span, self._m["pulls_ahead"])
                if hasattr(self.client, "pull_send")
                and not getattr(self.client, "oneside", False) else None)
        settled: set = set()  # names whose push and confirm have run out
        started: set = set()  # names whose pull node has begun

        def on_push_reply(tag, payload, view):
            view.release()  # push responses carry no tensor
            step_versions[tag] = int(payload.decode())
            acks[tag] = (step_versions[tag], time.monotonic())

        win = (PipelineWindow(channel, self.window, on_reply=on_push_reply)
               if channel is not None else None)
        # PipelineWindow.submit counts no bytes itself — the push_all
        # discipline: account per submit so the flagship loop's push
        # volume shows on /vars like every other push path (the fleet
        # path counts inside push_device).
        from brpc_tpu.runtime.tensor import _metrics as _tensor_metrics
        push_bytes = _tensor_metrics()["push_bytes"]

        def traced(span_name, fn):
            def run(done):
                with tracing.trace_span(span_name):
                    return fn(done)
            return run

        def fn_forward(done):
            for name, arr in self._raw.items():
                self._params[name] = self.harness.place(name, arr)
            self._raw.clear()
            # A failed step released the parameters whose pulls it
            # cancelled: fetch the server's current version of those.
            for name in names:
                if name not in self._params:
                    version, arr = self.client.pull(name)
                    self._params[name] = self.harness.place(name, arr)
                    self.versions[name] = version
            ctx_box["ctx"] = self.harness.forward(self._params, x, y)
            return None

        def make_bwd(name):
            def fn(done):
                g = self.harness.backward(ctx_box["ctx"], name)
                # Materialize here so compute time is attributed to the
                # compute lane (and the wire lane's staging D2H reads a
                # finished array instead of blocking on dispatch).
                grads[name] = jax.block_until_ready(g)
                self._params.pop(name, None)
                return None
            return fn

        def drain_one_recording() -> bool:
            """One complete_one() with per-tag failure recording — the
            single home of the drain discipline (opt nodes, full-window
            pre-drain, and the post-run late drain all ride it), so a
            failed reply is always attributed to ITS tag and the stale-
            advertisement / pacer healing hooks always run."""
            try:
                return win.complete_one()
            except Exception as e:  # noqa: BLE001 — ANY reply failure
                # (RpcError or a malformed-payload decode error from
                # on_push_reply) belongs to the tag that produced it,
                # never to whichever innocent node happened to drain.
                tag = getattr(e, "pipeline_tag", None)
                push_failed.setdefault(tag if tag is not None else "?", e)
                if isinstance(e, native.RpcError):
                    self._note_push_error(e)
                return True

        def make_push(name):
            if win is not None:
                def fn(done):
                    # Drain a full window HERE (recording per tag), not
                    # inside submit: submit's internal drain raises an
                    # EARLIER push's reply error untagged out of THIS
                    # node, failing an innocent layer and cancelling its
                    # salvageable push.
                    while win.inflight() >= win.window:
                        if not drain_one_recording():
                            break
                    enc = self.client._grad_encoder(name)
                    if enc is not None:
                        enc = _staged_encode(enc)
                    grad = grads.pop(name)
                    try:
                        with call_span("push", name):
                            win.submit("ParamService/Push", array=grad,
                                       request=name.encode(), tag=name,
                                       encoder=enc)
                    except BaseException:
                        settled.add(name)  # its confirm is cancelled
                        raise
                    push_bytes.add(int(getattr(grad, "nbytes", 0)))
                    return None
            else:
                def fn(done):
                    with call_span("push", name):
                        step_versions[name] = self.client.push_grad(
                            name, grads.pop(name))
                    acks[name] = (step_versions[name], time.monotonic())
                    return None
            return fn

        def make_opt(name):
            def fn(done):
                # Drain the window until THIS push's reply lands (the
                # server applied its momentum step and bumped the
                # version) — earlier-submitted replies deliver on the
                # way, later pushes stay in flight. A failed drain is
                # recorded against the tag it belongs to, so one bad
                # push never mis-attributes its neighbours.
                settled.add(name)
                while (name not in step_versions
                       and name not in push_failed and win is not None):
                    if not drain_one_recording():
                        break
                if name in step_versions:
                    self.versions[name] = step_versions[name]
                    return step_versions[name]
                err = push_failed.get(name)
                if err is None:
                    err = native.RpcError(
                        native.TRPC_EEOF,
                        f"push reply for {name} never arrived")
                raise err
            return fn

        def next_pull() -> Optional[str]:
            # The pull the lane runs next once only pulls are left: the
            # lowest-index one not begun whose confirm succeeded.
            return next((n for n in rev if n not in started
                         and n in step_versions), None)

        def make_pull(name):
            def fn(done):
                started.add(name)
                if tail is not None and len(settled) == len(names):
                    version, arr = tail.pull(name, next_pull())
                else:
                    with call_span("pull", name):
                        version, arr = self.client.pull(name)
                self._raw[name] = arr
                self.versions[name] = version
                return version
            return fn

        graph = StepGraph()
        # Insertion order IS the serial schedule: forward, every
        # backward, every push, every confirm, every pull — today's
        # driver exactly when overlap=False.
        graph.add("fwd", traced("step/fwd", fn_forward), lane=COMPUTE)
        prev = "fwd"
        for name in rev:
            prev = graph.add(f"bwd:{name}",
                             traced(f"step/bwd:{name}", make_bwd(name)),
                             deps=(prev,), lane=COMPUTE)
        for name in rev:
            graph.add(f"push:{name}",
                      traced(f"step/push:{name}", make_push(name)),
                      deps=(f"bwd:{name}",), lane=WIRE)
        for name in rev:
            graph.add(f"opt:{name}",
                      traced(f"step/opt:{name}", make_opt(name)),
                      deps=(f"push:{name}",), lane=WIRE)
        for name in rev:
            graph.add(f"pull:{name}",
                      traced(f"step/pull:{name}", make_pull(name)),
                      deps=(f"opt:{name}",), lane=WIRE)

        failure: Optional[StepFailure] = None
        trace = None
        with tracing.trace_span("train_step"):
            tid, sid = tracing.current_trace()
            wire_ctx = _trace_handoff_ctx(
                tid, sid, qos=getattr(self.client, "_qos_bulk", None))

            try:
                _results, trace = run_graph(graph, overlap=self.overlap,
                                            wire_ctx=wire_ctx)
            except StepFailure as sf:
                failure = sf
            except BaseException:
                # Ctrl-C and friends: the scheduler aborted promptly —
                # do NOT drain in-flight replies here (each blocks up
                # to the channel timeout, and this path never uses the
                # salvage data). Cancel and free the staged window
                # instead; the wire thread is joined, so no concurrent
                # access.
                if win is not None:
                    win.abort()
                raise
            finally:
                if tail is not None:
                    tail.drop()
            # Late replies still count: a push whose confirm was
            # cancelled may have landed server-side — drain the window
            # so `applied` is accurate before salvage math (the wire
            # thread is joined; no concurrent access).
            if win is not None:
                while drain_one_recording():
                    pass
            if failure is not None:
                # The success path's pulls already recorded NEWER
                # versions per name — only the failure path needs the
                # late-drained confirms merged (never backwards).
                for name, v in step_versions.items():
                    self.versions[name] = max(
                        self.versions.get(name, 0), v)
            if trace is not None:
                trace.acks = acks
                wall_ms = trace.wall_s * 1e3
                exposed_ms = trace.exposed_wait_s * 1e3
                overlapped_ms = trace.overlapped_comm_s() * 1e3
                tracing.annotate(
                    f"exposed_comm={int(exposed_ms * 1e3)}us")
                tracing.annotate(
                    f"overlapped_comm={int(overlapped_ms * 1e3)}us")
                tracing.annotate(
                    f"compute={int(trace.compute_busy_s * 1e6)}us")

        if failure is not None:
            raise self._salvage(failure, names, step_versions, push_failed)

        if pacer is not None:
            pacer.clear()  # a whole step landed: the server is admitting
        loss = float(self.harness.loss(ctx_box["ctx"]))
        stats = {
            "loss": loss, "overlap": self.overlap,
            "wall_ms": wall_ms,
            "compute_ms": trace.compute_busy_s * 1e3,
            "wire_busy_ms": trace.wire_busy_s * 1e3,
            "exposed_comm_ms": exposed_ms,
            "overlapped_comm_ms": overlapped_ms,
            "pulls_ahead": tail.sent_ahead if tail is not None else 0,
        }
        self.last_stats = stats
        self.last_trace = trace
        self.totals["steps"] += 1
        for k in ("wall_ms", "compute_ms", "wire_busy_ms",
                  "exposed_comm_ms", "overlapped_comm_ms"):
            self.totals[k] += stats[k]
        self._m["steps"].add(1)
        self._m["step"].record_s(time.monotonic() - t0)
        self._m["exposed"].record_us(int(exposed_ms))      # ms samples
        self._m["overlapped"].record_us(int(overlapped_ms))  # ms samples
        return loss

    def _salvage(self, sf: StepFailure, names, step_versions,
                 push_failed) -> BaseException:
        """Map a StepFailure onto the per-name push salvage contract."""
        wire_fail = {n: e for n, e in sf.failed.items()
                     if n.startswith(("push:", "opt:"))}
        if not wire_fail:
            # Compute- or pull-side failure: nothing ambiguous about the
            # pushes (they all confirmed or never started) — surface the
            # original cause. Not a PARTIAL-push failure, so the
            # counter stays put (operators alert on it). The post-mortem
            # rides on it, as on a partial push.
            try:
                sf.cause.step_failure = sf
            except Exception:  # noqa: BLE001 — exotic exception
                pass
            return sf.cause
        self._m["partial"].add(1)
        cause = None
        for e in list(wire_fail.values()) + list(push_failed.values()):
            if isinstance(e, native.RpcError):
                cause = e
                break
        if cause is None:
            cause = native.RpcError(native.TRPC_EEOF, str(next(iter(
                wire_fail.values()))))
        unpushed = [n for n in names if n not in step_versions]
        err = PartialPushError(cause, dict(step_versions), unpushed)
        err.step_failure = sf
        return err

    def run(self, batches) -> List[float]:
        """Convenience loop: ``batches`` yields ``(x, y)`` pairs."""
        return [self.step(x, y) for x, y in batches]


class CollectiveStepDriver:
    """Data-parallel training where the gradient exchange is a ring
    allreduce over a :class:`~brpc_tpu.collectives.group.CollectiveGroup`
    instead of N point-to-point pushes into a parameter server (ISSUE
    13): every member holds the full parameter set locally, computes
    gradients on its own batch shard, and each layer's exchange is an
    ``allreduce:k`` node scheduled on a NAMED wire lane —

        forward -> bwd:k (compute lane, top layer first)
        bwd:k   -> allreduce:k (lane ``wire:ar<k % wire_lanes>``)
        allreduce:k -> opt:k (compute lane: the local momentum update)

    A collective hop BLOCKS waiting for its ring predecessor, so one
    wire thread would serialize layer k+1's collective behind layer k's
    waits; per-peer wire lanes (the :mod:`step_sched` generalization —
    PR 12's named leftover) let reduction hops of layer k hide behind
    layer k+1's backward AND behind each other. ``overlap=False`` runs
    the same nodes serially — the A/B baseline.

    The optimizer is ONE jitted ``fused_momentum_update`` call per layer
    over the reduced buffer (the PR 13 leftover retired): the auto-routed
    Pallas kernel on TPU, the identical jnp reference elsewhere —
    trajectory parity with the explicit momentum formula is pinned, and
    the copy-on-write discipline (handed-out arrays stay immutable) is
    unchanged; ``ef=False`` on the group is the naive-requantizer
    negative control the convergence tests pin.

    Failure: a hop failure (member left, timeout) cancels exactly that
    layer's ``opt:k`` while every other layer completes (partial
    salvage across lanes); the step raises the triggering
    :class:`~brpc_tpu.collectives.core.CollectiveAborted` with the full
    graph post-mortem on ``.step_failure`` — the caller re-``sync()``\\ s
    the group and resumes on the surviving ring.

    ``track=True`` — T3 track-and-trigger (ISSUE 20, arXiv 2401.16677):
    instead of an ``opt:k`` node that waits for layer k's WHOLE
    allreduce, the momentum update rides the collective's per-chunk
    finality hook (``on_chunk``) — each reduced span is applied the
    moment it lands, while later chunks of the same layer are still on
    the wire, so by op completion the optimizer is already done and the
    op-completion ``opt:k`` nodes vanish from the graph. The per-chunk
    update is deliberately NUMPY (the param-server formula ``m' =
    beta*m + g; p' = p - lr*m'``), not the jitted fused kernel: the
    trigger fires on a WIRE lane, and jax dispatch off the caller's
    thread is the PR 6 contention class (now a tpulint finding —
    ``regime-graph``). Trajectory: chunkwise-numpy == whole-array-numpy
    exactly (elementwise math over a partition); numpy-vs-fused parity
    carries the usual fp32 tolerance, pinned in tests. The delta shows
    in ``RunTrace``: the compute lane no longer stalls on, or joins
    behind, tail-layer optimizer waits (``exposed_stall_s`` /
    ``exposed_join_s``).
    """

    def __init__(self, group, harness, overlap: bool = True,
                 wire_lanes: int = 2, lr: float = 0.01,
                 momentum: float = 0.9, average: bool = True,
                 track: bool = False):
        self.group = group
        self.harness = harness
        self.overlap = overlap
        self.wire_lanes = max(1, wire_lanes)
        self.lr = lr
        self.momentum = momentum
        self.average = average
        self.track = track
        self._params: Dict[str, object] = {}   # numpy fp32 masters
        self._momenta: Dict[str, object] = {}
        self._m = _metrics()
        self.last_stats: Optional[dict] = None
        self.last_trace = None
        # track mode: {name: [(chunk_idx, (offset, length)), ...]} of the
        # last step, in firing order — the tests' view of the trigger.
        self.last_chunk_log: Dict[str, list] = {}
        self.totals = {"steps": 0, "wall_ms": 0.0, "compute_ms": 0.0,
                       "wire_busy_ms": 0.0, "exposed_comm_ms": 0.0,
                       "overlapped_comm_ms": 0.0}

    def prime(self, params: Optional[Dict[str, object]] = None) -> None:
        """Adopt the initial parameter set (fp32 numpy masters). All
        members must start identical — ``harness.init_params()`` is
        deterministic per seed, so calling this with the default on
        every member satisfies that."""
        import numpy as np

        src = params if params is not None else self.harness.init_params()
        for name in self.harness.names:
            self._params[name] = np.array(np.asarray(src[name]),
                                          dtype=np.float32)
            self._momenta[name] = np.zeros_like(self._params[name])

    def params(self) -> Dict[str, object]:
        return dict(self._params)

    def step(self, x, y) -> float:
        from brpc_tpu.observability import tracing

        import jax
        import jax.numpy as jnp
        import numpy as np

        t0 = time.monotonic()
        names: List[str] = list(self.harness.names)
        rev = list(reversed(names))
        world = max(1, self.group.world)
        grads: Dict[str, object] = {}
        reduced: Dict[str, object] = {}
        ctx_box: Dict[str, object] = {}

        def traced(span_name, fn):
            def run(done):
                with tracing.trace_span(span_name):
                    return fn(done)
            return run

        def fn_forward(done):
            placed = {n: self.harness.place(n, self._params[n])
                      for n in names}
            ctx_box["ctx"] = self.harness.forward(placed, x, y)
            return None

        def make_bwd(name):
            def fn(done):
                g = self.harness.backward(ctx_box["ctx"], name)
                grads[name] = jax.block_until_ready(g)
                return None
            return fn

        def make_allreduce(name):
            def fn(done):
                g = np.asarray(grads[name])  # D2H on the wire lane
                red = self.group.allreduce(name, g)
                if self.average:
                    red /= np.float32(world)
                reduced[name] = red
                return None
            return fn

        def make_allreduce_tracked(name):
            def fn(done):
                g = np.asarray(grads[name])  # D2H on the wire lane
                shape = np.shape(self._params[name])
                # Copy-on-write: update fresh flats, install when the op
                # lands — handed-out arrays stay immutable, and a failed
                # op leaves params/momenta untouched.
                pf = np.array(self._params[name],
                              dtype=np.float32).reshape(-1)
                mf = np.array(self._momenta[name],
                              dtype=np.float32).reshape(-1)
                chunk_log = self.last_chunk_log.setdefault(name, [])
                chunk_log.clear()
                inv = np.float32(1.0 / world)

                def on_chunk(idx, span, vals):
                    # Numpy on purpose — this runs on a WIRE lane (see
                    # class docstring / the regime-graph lint rule).
                    off, ln = span
                    gc = vals * inv if self.average else vals
                    mf[off:off + ln] = (np.float32(self.momentum)
                                        * mf[off:off + ln] + gc)
                    pf[off:off + ln] -= np.float32(self.lr) \
                        * mf[off:off + ln]
                    chunk_log.append((idx, span))

                red = self.group.allreduce(name, g, on_chunk=on_chunk)
                if self.average:
                    red /= np.float32(world)
                reduced[name] = red
                self._momenta[name] = mf.reshape(shape)
                self._params[name] = pf.reshape(shape)
                return None
            return fn

        def make_opt(name):
            def fn(done):
                # ONE jitted fused-momentum-update call over the reduced
                # buffer (the PR 13 leftover): ops/fused_momentum_update
                # auto-routes — the Pallas kernel on TPU (one HBM round
                # trip for the whole (p, m, g) -> (p', m') update), the
                # identical jnp math elsewhere. Copy-on-write discipline
                # preserved: handed-out arrays stay immutable, the
                # detached results replace them.
                from brpc_tpu.ops.fused_update import fused_momentum_update

                p2, m2 = fused_momentum_update(
                    jnp.asarray(self._params[name]),
                    jnp.asarray(self._momenta[name]),
                    jnp.asarray(reduced[name]),
                    lr=self.lr, beta=self.momentum)
                p2, m2 = jax.block_until_ready((p2, m2))
                self._momenta[name] = np.asarray(m2)
                self._params[name] = np.asarray(p2)
                return None
            return fn

        graph = StepGraph()
        graph.add("fwd", traced("step/fwd", fn_forward), lane=COMPUTE)
        prev = "fwd"
        for name in rev:
            prev = graph.add(f"bwd:{name}",
                             traced(f"step/bwd:{name}", make_bwd(name)),
                             deps=(prev,), lane=COMPUTE)
        mk_ar = make_allreduce_tracked if self.track else make_allreduce
        for k, name in enumerate(rev):
            graph.add(f"allreduce:{name}",
                      traced(f"step/allreduce:{name}", mk_ar(name)),
                      deps=(f"bwd:{name}",),
                      lane=f"wire:ar{k % self.wire_lanes}")
        if not self.track:
            # Track mode has no opt nodes: the momentum update already
            # happened per chunk inside each allreduce as spans landed.
            for name in rev:
                graph.add(f"opt:{name}",
                          traced(f"step/opt:{name}", make_opt(name)),
                          deps=(f"allreduce:{name}",), lane=COMPUTE)

        with tracing.trace_span("train_step"):
            tid, sid = tracing.current_trace()
            # No qos factory: the collective stamps its own BULK QoS
            # per peer inside the hop sends.
            wire_ctx = _trace_handoff_ctx(tid, sid)

            try:
                _results, trace = run_graph(graph, overlap=self.overlap,
                                            wire_ctx=wire_ctx)
            except StepFailure as sf:
                self._m["partial"].add(1)
                cause = sf.cause
                try:
                    cause.step_failure = sf
                except Exception:  # noqa: BLE001 — exotic exception
                    pass
                raise cause
            wall_ms = trace.wall_s * 1e3
            exposed_ms = trace.exposed_wait_s * 1e3
            overlapped_ms = trace.overlapped_comm_s() * 1e3
            tracing.annotate(f"exposed_comm={int(exposed_ms * 1e3)}us")
            tracing.annotate(
                f"overlapped_comm={int(overlapped_ms * 1e3)}us")

        loss = float(self.harness.loss(ctx_box["ctx"]))
        stats = {
            "loss": loss, "overlap": self.overlap,
            "wall_ms": wall_ms,
            "compute_ms": trace.compute_busy_s * 1e3,
            "wire_busy_ms": trace.wire_busy_s * 1e3,
            "exposed_comm_ms": exposed_ms,
            "overlapped_comm_ms": overlapped_ms,
        }
        self.last_stats = stats
        self.last_trace = trace
        self.totals["steps"] += 1
        for k in ("wall_ms", "compute_ms", "wire_busy_ms",
                  "exposed_comm_ms", "overlapped_comm_ms"):
            self.totals[k] += stats[k]
        self._m["steps"].add(1)
        self._m["step"].record_s(time.monotonic() - t0)
        self._m["exposed"].record_us(int(exposed_ms))      # ms samples
        self._m["overlapped"].record_us(int(overlapped_ms))  # ms samples
        return loss

    def run(self, batches) -> List[float]:
        return [self.step(x, y) for x, y in batches]


def _staged_encode(enc):
    """Wrap a gradient encoder so its quantize cost shows as an
    ``encode`` stage on the push node's span — running at arena-stage
    time on the wire lane, i.e. inside the next layer's compute shadow
    (the PR 7 quantize-at-stage hook riding the overlap for free)."""
    from brpc_tpu.observability import tracing

    def run(host):
        with tracing.stage("encode"):
            return enc(host)
    return run
