"""A parameter server whose traffic rides the RPC framework as tensors.

This closes the loop SURVEY.md §2.11/§7 charters: the reference's headline
deployment is parameter-server fan-out over its RDMA transport; here the
served state is jax.Arrays in device memory, and every pull/push crosses
the framework's ``tpu://`` transport as a by-reference TensorArena
attachment (brpc_tpu/runtime/tensor.py):

  PULL:  device param --D2H--> server arena --by-ref--> client maps the
         same pages --jax.device_put--> device replica
  PUSH:  device grad --D2H--> client arena --by-ref--> server applies the
         fused Pallas momentum update ON DEVICE and bumps the version.

Reference mapping: example/parallel_echo_c++ fan-out + rdma payload path
(rdma_endpoint.h:89); the update rule matches ops/fused_update.py so a
local training loop and an RPC-driven one converge identically (asserted
by tests/test_tensor_bridge.py).
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
import weakref
from typing import Dict, List, Optional

import jax
import numpy as np

from brpc_tpu.ops.fused_update import fused_momentum_update
from brpc_tpu.runtime import codec as codec_mod
from brpc_tpu.runtime import groupwire, native
from brpc_tpu.runtime.tensor import (E_UNDECODABLE, OnesideGone, OnesideMiss,
                                     OnesideReader, OnesideWindow,
                                     PipelineWindow, TensorArena,
                                     TensorChannel, WireTensor,
                                     _dequant_widen,
                                     _detach_device_put_batch,
                                     _device_put_from_view,
                                     add_tensor_service,
                                     consume_oneside_payload, pad_header64)

# App-level error codes, disjoint from trpc/errno.h. The server
# historically answered "no such parameter" with 2007 — which COLLIDES
# with TRPC_ECONNECT, so a fleet client couldn't tell "that shard doesn't
# have it" (don't retry) from "that shard is unreachable" (do retry):
# E_NO_SUCH moves to its own code. E_MOVED's text carries the forwarding
# address as "moved:<host:port>" — the fleet client parses it to re-route
# mid-reshard; E_MIGRATING means installed-but-uncommitted (retry soon).
E_NO_SUCH = 2040
E_MOVED = 2041
E_MIGRATING = 2042
E_EXISTS = 2043  # install over a live (serving) parameter
# E_UNDECODABLE = 2044 lives in tensor.py (the raise site is the typed-send
# trampoline); it completes this 2040+ app-code range.

# trpc/errno.h transport code a handler bug surfaces as — what a PRE-codec
# server answers to a quantized push (see _codec_push_failed).
TRPC_EINTERNAL = 2004

_MOVED_RE = re.compile(r"moved:(\S+)")


def moved_dest(err: "native.RpcError") -> Optional[str]:
    """The forwarding address an E_MOVED redirect carries, or None."""
    if err.code != E_MOVED:
        return None
    m = _MOVED_RE.search(err.text or "")
    return m.group(1) if m else None


class OverloadPacer:
    """Client-side brake for shed storms (the retry-after consumer).

    ELIMIT/EOVERCROWDED answers mean the server (or the socket write
    queue) is over capacity RIGHT NOW — hot-retrying turns one shed into
    a storm that keeps the server pinned at its admission gate. The shed
    response carries a drain-time hint (" (retry_after_ms=N)", from the
    server's EMA latency); this pacer holds the NEXT call back until the
    hint elapses, doubling an exponential floor when sheds repeat without
    a hint, and heals instantly on the first success. The same role the
    native per-node CircuitBreaker (trpc/circuit_breaker.h) plays for
    transport failures, at the application layer where overload answers
    live (an ELIMIT response IS a received response, so the transport
    breaker rightly never trips on it).

    Thread-safe; `sheds` is the bounded-retry-rate observable the
    shed-storm test asserts against the server's per-tenant counters."""

    _MIN_DELAY_S = 0.005
    _MAX_DELAY_S = 0.5

    def __init__(self):
        self._mu = threading.Lock()
        self._until = 0.0   # monotonic time before which calls pace
        self._delay = 0.0   # current backoff floor
        self.sheds = 0

    def note(self, err) -> float:
        """Record an error; returns the pacing delay now owed (0 for
        non-overload errors, which also leave the pacer untouched)."""
        if not getattr(err, "overloaded", False):
            return 0.0
        hint_s = (getattr(err, "retry_after_ms", None) or 0) / 1000.0
        with self._mu:
            self.sheds += 1
            self._delay = min(max(self._delay * 2, self._MIN_DELAY_S),
                              self._MAX_DELAY_S)
            delay = max(hint_s, self._delay)
            self._until = max(self._until, time.monotonic() + delay)
            return max(0.0, self._until - time.monotonic())

    def clear(self) -> None:
        """A success: the server is admitting again — stop pacing."""
        with self._mu:
            self._delay = 0.0
            self._until = 0.0

    def pace(self) -> None:
        """Sleep out any pacing debt before issuing the next call.
        Client-side only: runs on the CALLER's thread (training loop /
        fleet worker), never inside a server handler."""
        with self._mu:
            wait = self._until - time.monotonic()
        if wait > 0:
            time.sleep(wait)  # tpulint: allow(py-blocking)


class PartialPullError(native.RpcError):
    """A ``pull_all`` that delivered SOME tensors before a per-name
    failure: ``partial`` holds the decoded ``{name: (version, value)}``,
    ``missing`` the names not delivered (the failed name plus anything
    the aborted window never drained). Raised instead of discarding the
    survivors so the fleet's salvage path re-routes ONLY the stragglers
    — mid-reshard, one moved tensor must not cost its groupmates a
    second full group RPC. Catches as a plain RpcError (same code/text
    as the first failure) for callers that don't care."""

    def __init__(self, cause: "native.RpcError",
                 partial: Dict[str, tuple], missing: List[str]):
        super().__init__(cause.code, cause.text)
        self.partial = partial
        self.missing = missing


class PartialPushError(native.RpcError):
    """A ``push_all`` that APPLIED some gradients before a per-name
    failure: ``applied`` holds the confirmed ``{name: new_version}``,
    ``unpushed`` the names with no confirmed apply (the failed name plus
    anything the aborted window never drained — those MAY have landed
    server-side with the reply lost, the usual retry ambiguity). Raised
    instead of discarding the confirmed versions: re-pushing a gradient
    the server already applied is not idempotent (a second momentum step
    and version bump corrupt training state), so the fleet's salvage
    path must re-route ONLY the unconfirmed names. Catches as a plain
    RpcError (same code/text as the first failure) for callers that
    don't care."""

    def __init__(self, cause: "native.RpcError",
                 applied: Dict[str, int], unpushed: List[str]):
        super().__init__(cause.code, cause.text)
        self.applied = applied
        self.unpushed = unpushed


# Process-wide recorders (brpc_tpu/observability): every ParameterServer
# instance feeds the same series, like native per-method stats aggregate.
_metrics_cache = None
_SERVERS: "weakref.WeakSet[ParameterServer]" = weakref.WeakSet()


def _max_version_lag() -> int:
    """Largest (max - min) parameter-version spread across live servers —
    how far the most- and least-updated parameters have drifted apart.
    Reads the lock-free mirror each Push maintains: gauge callbacks run
    at scrape time under the native registry walk, so taking srv._mu here
    would stall every metrics consumer behind an in-flight update."""
    return max((srv._version_spread for srv in list(_SERVERS)), default=0)


def _metrics():
    global _metrics_cache
    if _metrics_cache is None:
        from brpc_tpu.observability import metrics as obs

        _metrics_cache = {
            # HANDLER-BODY time only: Pull's D2H + arena staging happens
            # after the handler returns (add_tensor_service trampoline) —
            # the tensor_handler recorder carries that full server-side
            # cost; the client's tensor_pull carries the end-to-end view.
            "pull": obs.latency("param_server_pull"),
            # PullQ groups up to _GROUP tensors per sample — a separate
            # recorder, or quant traffic would read as ~8x slower/rarer
            # pulls beside the per-tensor path.
            "pull_group": obs.latency("param_server_pull_group"),
            "push": obs.latency("param_server_push"),
            # PushQ applies up to _GROUP updates per sample — its own
            # recorder for the same reason pull_group has one.
            "push_group": obs.latency("param_server_push_group"),
            "push_bytes": obs.counter("param_server_push_bytes"),
            "lag": obs.gauge("param_server_version_lag", _max_version_lag),
        }
    return _metrics_cache


def _per_server_lag_gauge(name: str, srv: "ParameterServer") -> None:
    """Expose this server's version spread as its OWN gauge
    (`param_server_version_lag_<name>`) beside the process-wide max —
    satellite: per-server (and per-shard, via the fleet's shard names)
    version-lag series on /vars, /brpc_metrics and /tensorz. Re-pointable
    (newest server claiming the name wins) and weakly bound, so a test's
    re-created server neither collides nor leaks."""
    from brpc_tpu.observability import metrics as obs

    safe = re.sub(r"[^a-zA-Z0-9_]", "_", name)
    ref = weakref.ref(srv)
    # `safe` is re.sub-sanitized to the exposition charset just above.
    obs.repointable_gauge(
        f"param_server_version_lag_{safe}",  # tpulint: allow(metric-name)
        lambda: getattr(ref(), "_version_spread", 0))


class ParameterServer:
    """Serves named jax.Arrays over RPC; Push applies momentum SGD.

    Shard-aware (brpc_tpu/fleet): Meta carries a schema epoch (bumped when
    the parameter SET changes — Install/Retire — never by plain updates,
    so clients can cache the name->shape/dtype map); Handoff/Install/
    Retire/Commit are the live-resharding handshake a fleet Migrator
    drives. Per-name migration states:

      serving  normal pulls + pushes
      frozen   Handoff exported it: pulls still served (old-owner reads
               until the handoff commits), pushes refused with E_MOVED so
               no update can land that the export missed
      pending  Installed here but not yet committed: pulls served (same
               version the old owner still serves), pushes refused with
               E_MIGRATING until Commit — so a version can never advance
               on the new owner while the old owner still answers reads

    A retired name answers E_MOVED with "moved:<dest>" so clients holding
    a stale shard map re-route without a registry round trip.
    """

    def __init__(self, params: Dict[str, jax.Array], lr: float = 0.01,
                 momentum: float = 0.9, arena: Optional[TensorArena] = None,
                 name: Optional[str] = None, codecs=None,
                 oneside: bool = False,
                 oneside_codec: Optional[str] = None):
        # Backend split for the Push hot path. On TPU the update is the
        # fused Pallas kernel over device arrays (device_put = a real H2D
        # DMA). On the CPU backend that same shape is all dispatch
        # overhead: per-push jax dispatch (~0.5ms) dominated the pipelined
        # bench, and device_put ZERO-COPY ALIASES 64B-aligned host buffers
        # — with the update dispatched async, the grad view's arena range
        # could be reused under the pending computation. The CPU path
        # keeps params/momenta as numpy and applies the update
        # synchronously, reading straight from the request view (safe:
        # the read completes before the handler returns and the view
        # releases) — but COPY-ON-WRITE, never in place; see
        # _apply_update for why handed-out arrays must stay immutable.
        self._on_device = jax.default_backend() == "tpu"
        if self._on_device:
            self._params = dict(params)
            self._momenta = {k: jax.numpy.zeros_like(v)
                             for k, v in self._params.items()}
        else:
            self._params = {k: np.array(v) for k, v in params.items()}
            self._momenta = {k: np.zeros_like(v)
                             for k, v in self._params.items()}
        self._version = {k: 0 for k in self._params}
        self._lr = lr
        self._momentum = momentum
        # Per-parameter update locks: pushes to the SAME name must
        # serialize (momentum reads its own previous write), but pushes to
        # different names are independent — and numpy releases the GIL for
        # the 1MB elementwise math, so pipelined pushes of a sharded model
        # really do update in parallel. _mu stays the dict/version lock
        # and is never held while an update lock is taken... the update
        # lock is taken FIRST (fixed order, no cycle).
        self._update_locks = {k: threading.Lock() for k in self._params}
        # Update admission: a pipelined client parks a whole window of
        # pushes on the server at once, and running every update's math
        # concurrently just thrashes the cores the transport needs (the
        # math releases the GIL, so an unbounded pool really does fan
        # out). Cap concurrent update computations near the core count;
        # excess handlers queue on the semaphore (pool pthreads — safe to
        # block) with the wire already overlapped.
        self._update_sem = threading.BoundedSemaphore(
            min(4, max(2, os.cpu_count() or 2)))
        self._mu = threading.Lock()  # handlers run on callback-pool threads
        # Lock-free mirror of max(version)-min(version), updated by Push
        # under _mu, read by the version-lag gauge without it.
        self._version_spread = 0
        # ---- shard-aware state (brpc_tpu/fleet) ----
        # Schema epoch: bumps when the parameter SET changes (Install /
        # Retire), never on plain version bumps — the client Meta cache key.
        self._schema_epoch = 1
        self._state: Dict[str, str] = {}        # absent == "serving"
        self._handoff_dest: Dict[str, str] = {}  # frozen name -> dest addr
        self._moved: Dict[str, str] = {}         # retired name -> dest addr
        # ---- quantized tensor wire (brpc_tpu/runtime/codec.py) ----
        # Codecs this server will encode pulls with / decode pushes from,
        # advertised in Meta (the per-peer negotiation); codecs=() turns
        # the feature off entirely (every call rides raw).
        # Advertising a codec this build cannot decode (e.g. fp8e4m3
        # without ml_dtypes) would let a client negotiate pushes the
        # server then cannot parse — intersect, caller order kept.
        self._codecs = tuple(codec_mod.supported_codecs() if codecs is None
                             else (c for c in codecs
                                   if c in codec_mod.supported_codecs()))
        # Quantize-once-serve-many: Pull responses are encoded per
        # (version, codec) and cached until the next update replaces them
        # — name -> {codec: (version, meta, wire uint8 array, logical)}.
        # Holds ~1/4 of the fp32 parameter bytes per codec in use when
        # clients pull quantized; invalidation pops the whole name.
        self._enc_cache: Dict[str, Dict[str, tuple]] = {}
        self.name = name
        if name is not None:
            _per_server_lag_gauge(name, self)
        _SERVERS.add(self)
        self._m = _metrics()
        self.server = native.Server()
        self.arena = add_tensor_service(self.server, "ParamService",
                                        self._handle, arena)
        # ---- one-sided tensor reads (brpc_tpu/runtime/tensor.py) ----
        # Publish every committed version into a seqlock-stamped window of
        # the service arena: a same-host client that mapped the window
        # pulls WITHOUT an RPC (no dispatch, no handler, no response
        # frame), falling back to the Pull path off-host. Published
        # regions may hold the encoded wire form (oneside_codec) — the
        # reader decodes by the same self-describing header the RPC path
        # ships, so the two paths cannot disagree.
        self._oneside_window: Optional[OnesideWindow] = None
        self._oneside_codec = (oneside_codec
                               if oneside_codec in self._codecs else None)
        if oneside:
            self._oneside_window = OnesideWindow(self.arena)
            for k in list(self._params):
                with self._update_locks[k]:
                    self._publish_oneside(k)
        self.port: Optional[int] = None

    def start(self, addr: str = "127.0.0.1:0") -> int:
        self.port = self.server.start(addr)
        return self.port

    def stop(self) -> None:
        self.server.stop()

    # ---- handler (runs inside a server fiber) ----
    def _handle(self, method: str, request: bytes, att):
        from brpc_tpu.observability import tracing

        if method == "Meta":
            # Under _mu: Push swaps self._params values and bumps
            # self._version concurrently on other fibers — an unlocked
            # read here can pair a new version with an old shape/dtype
            # (or hit a dict mutated mid-iteration).
            with self._mu:
                meta = {}
                for k, v in self._params.items():
                    entry = {"shape": list(v.shape), "dtype": str(v.dtype),
                             "version": self._version[k]}
                    state = self._state.get(k)
                    if state is not None:  # frozen/pending: the migrator's
                        entry["state"] = state  # repair pass reads this
                    meta[k] = entry
                epoch = self._schema_epoch
            # "qos": 1 is the QoS-advertisement half of the negotiation
            # discipline (same pattern as "codecs"): clients stamp
            # priority/tenant wire fields ONLY after seeing it, so an
            # upgraded client never sends a meta a pre-QoS parser would
            # reject.
            # "pushq": grouped quantized pushes served here (the PullQ
            # write-side twin) — advertised like the codec so a client
            # never sends a method an older build lacks.
            doc = {"epoch": epoch, "params": meta, "qos": 1,
                   "codecs": list(self._codecs), "pushq": 1}
            # One-sided advertisement (the codec/QoS negotiation
            # discipline): clients ask for the window descriptor only
            # after seeing it, so a pre-oneside server never receives an
            # Oneside method call it cannot parse.
            if self._oneside_window is not None:
                doc["oneside"] = 1
            return json.dumps(doc).encode(), None
        if method == "Epoch":
            # The Meta-cache validator: a tiny small-RPC-fast-path answer
            # (schema epoch only) instead of the full Meta payload.
            with self._mu:
                epoch = self._schema_epoch
            return json.dumps({"epoch": epoch}).encode(), None
        if method == "PullQ":
            return self._handle_pull_group(request)
        if method == "PushQ":
            return self._handle_push_group(request, att, tracing)
        if method == "Oneside":
            # The mapping handshake: ONE ordinary RPC hands out the
            # window descriptor; every read after it is memory-semantics.
            if self._oneside_window is None:
                raise native.RpcError(E_NO_SUCH, "one-sided reads disabled")
            desc = self._oneside_window.describe()
            # The token stays a decimal STRING on the wire (the capi
            # contract): a double-typed JSON parser would round a bare
            # u64 above 2^53 and the reader's token check would fail
            # forever. OnesideReader.map int()s either form.
            desc["token"] = str(desc["token"])
            return json.dumps(desc).encode(), None
        if method == "Handoff":
            return self._handle_handoff(request)
        if method == "Install":
            return self._handle_install(request, att)
        if method == "Retire":
            return self._handle_retire(request)
        if method == "Commit":
            return self._handle_commit(request)
        # Per-call codec negotiation marker: "<name>\x00<codec>" — only
        # sent by clients that saw this codec in our Meta advertisement,
        # so a plain name (every pre-codec client) parses unchanged.
        name_b, _, want_b = request.partition(b"\x00")
        name = name_b.decode()
        want = want_b.decode()
        with self._mu:
            known = name in self._params
            dest = self._moved.get(name)
        if not known:
            if dest is not None:
                raise native.RpcError(E_MOVED,
                                      f"parameter {name} moved:{dest}")
            raise native.RpcError(E_NO_SUCH, f"no such parameter: {name}")
        if method == "Pull":
            t0 = time.monotonic()
            with self._mu:
                if name not in self._params:  # retired under our feet
                    moved = self._moved.get(name)
                    if moved is not None:
                        raise native.RpcError(
                            E_MOVED, f"parameter {name} moved:{moved}")
                    raise native.RpcError(E_NO_SUCH,
                                          f"no such parameter: {name}")
                p = self._params[name]
                version = self._version[name]
            out = str(version).encode(), self._encode_pull(name, p, version,
                                                           want)
            self._m["pull"].record_s(time.monotonic() - t0)
            return out
        if method == "Push":
            if att is None:
                raise native.RpcError(native.TRPC_EREQUEST,
                                      "push without gradient")
            t0 = time.monotonic()
            self._update_sem.acquire()
            try:
                version = self._apply_update(name, att, tracing)
            finally:
                self._update_sem.release()
            self._m["push"].record_s(time.monotonic() - t0)
            self._m["push_bytes"].add(att.nbytes)
            return str(version).encode(), None
        raise native.RpcError(E_NO_SUCH, f"no such method: {method}")

    # ---- quantized pull encode (quantize once, serve many) ----

    def _encoded_entry(self, name: str, p, version: int, want: str):
        """-> (meta_dict, flat uint8 wire bytes) for one pull response
        tensor: the block-quantized codes when the caller negotiated a
        codec this server enables AND the tensor is eligible (fp32, above
        the size floor), else the raw bytes (meta carries no codec key —
        the per-call degrade). Quantized entries are encoded once per
        (version, codec) and cached PER CODEC until the next update
        replaces them — mixed int8/fp8 clients each get their own slot
        instead of thrashing one (a parameter server serves many more
        pulls than it takes pushes, so the quantize cost amortizes to
        ~zero; the 4x-smaller response staging is pure win)."""
        # eligible() reads dtype/nbytes only — an ineligible tensor from
        # a negotiated client skips straight to raw with NO host
        # materialization here (the trampoline's place() does the one
        # D2H the response needs).
        if want and want in self._codecs and codec_mod.eligible(p):
            with self._mu:
                ent = self._enc_cache.get(name, {}).get(want)
            if ent is None or ent[0] != version:
                host = np.asarray(p)  # one D2H on the device path
                enc = codec_mod.encode(host, want)
                if enc is None:
                    ent = None  # defensive: eligible() said yes above
                else:
                    meta = {"dtype": host.dtype.str,
                            "shape": list(host.shape),
                            "codec": want, "block": enc.block}
                    ent = (version, meta, enc.wire, int(host.nbytes))
                    with self._mu:
                        # Re-check under _mu: a concurrent Retire may
                        # have popped the name (params AND cache) while
                        # we encoded lock-free — inserting now would
                        # strand the wire bytes until a re-install.
                        # Still SERVE this response (the snapshot `p`
                        # predates the retire, matching single-Pull
                        # semantics); just don't cache it.
                        if name in self._params:
                            self._enc_cache.setdefault(name, {})[want] = ent
            if ent is not None:
                codec_mod.note(name, want, ent[3], int(ent[2].nbytes))
                return ent[1], ent[2]
        host = np.asarray(p)
        return ({"dtype": host.dtype.str, "shape": list(host.shape)},
                np.ascontiguousarray(host).reshape(-1).view(np.uint8))

    def _encode_pull(self, name: str, p, version: int, want: str):
        """The single-Pull response tensor: the array itself (raw — the
        trampoline stages it with the legacy header, byte-identical to
        the pre-codec wire) or the cached quantized bytes as a
        WireTensor."""
        if (not want or want not in self._codecs
                or not codec_mod.eligible(p)):
            return p  # raw: the trampoline's place() is the only D2H
        meta, data = self._encoded_entry(name, p, version, want)
        if "codec" not in meta:
            return p  # ineligible: identical to the never-negotiated path
        return WireTensor(data, codec_mod.pack_header(meta))

    def _handle_pull_group(self, request: bytes):
        """PullQ: one RPC carrying MANY pull responses — the quantized
        wire's second lever. Once the codec cuts a 1MB tensor to ~0.26MB,
        the per-RPC fixed cost (dispatch, handler hop, response staging
        bookkeeping) dominates a per-tensor pull stream, so the client
        groups pulls and this handler concatenates the encoded tensors
        into ONE attachment behind a JSON manifest. (Raw pulls stay
        per-tensor: at 4 logical bytes per wire byte they are transport-
        bound, and grouping would buy nothing — measured in an old host run, records deleted in PR 21.)

        Per-name misses ride the manifest as {"name", "code", "error"}
        entries instead of failing the group: mid-reshard a single moved
        tensor must not poison its groupmates; the client re-routes the
        stragglers through the per-tensor retry path.
        """
        t0 = time.monotonic()
        req = json.loads(request.decode())
        want = req.get("codec", "")
        entries, blobs, total = [], [], 0
        for name in req["names"]:
            with self._mu:
                known = name in self._params
                moved = self._moved.get(name)
                if known:
                    p = self._params[name]
                    version = self._version[name]
            if not known:
                entries.append({
                    "name": name,
                    "code": E_MOVED if moved else E_NO_SUCH,
                    "error": (f"parameter {name} moved:{moved}" if moved
                              else f"no such parameter: {name}")})
                continue
            meta, data = self._encoded_entry(name, p, version, want)
            e = dict(meta)
            e["name"] = name
            e["version"] = version
            e["nbytes"] = int(data.nbytes)
            entries.append(e)
            blobs.append(data)
            total += int(data.nbytes)
        # Write each encoded tensor straight into the service arena (the
        # writes ARE the staging transfer) and hand the trampoline the
        # pre-placed range — a concat buffer here would be memcpy'd into
        # the arena AGAIN by place(), one redundant full-payload copy per
        # group on the hot quantized pull path.
        placed = (0, 0)  # all-miss group: manifest only, no attachment
        if total:
            arena_off = self.arena.alloc(total)
            try:
                view = self.arena.view(arena_off, total)
                off = 0
                for b in blobs:
                    view[off:off + b.nbytes] = b.reshape(-1)
                    off += b.nbytes
            except BaseException:
                self.arena.free(arena_off)
                raise
            placed = (arena_off, total)
        self._m["pull_group"].record_s(time.monotonic() - t0)
        return (json.dumps({"tensors": entries}).encode(),
                WireTensor(None, b"", placed=placed))

    def _handle_push_group(self, request: bytes, att, tracing):
        """PushQ: one RPC carrying MANY gradient pushes — the write-side
        twin of PullQ (PR 7's named leftover, retired here). The client
        concatenates its quantized gradients behind a groupwire manifest;
        this handler slices the attachment per entry and applies each
        update exactly like a per-tensor Push would (same QuantizedView
        decode, same per-name update locks and admission semaphore, same
        version bumps), answering a manifest of per-name results.

        Per-name salvage is the whole point: a moved/undecodable name
        answers ``{"name", "code", "error"}`` in the results instead of
        failing its groupmates — re-pushing an APPLIED gradient is not
        idempotent (double momentum step), so a client must learn
        exactly which names landed.
        """
        t0 = time.monotonic()
        man = groupwire.parse_group(request)
        payload = None
        if att is not None:
            payload = np.ascontiguousarray(att).reshape(-1).view(np.uint8)
        try:
            pairs = list(groupwire.split_group(man, payload))
        except ValueError as ve:
            raise native.RpcError(E_UNDECODABLE,
                                  f"undecodable push group: {ve}")
        results = []
        for entry, run in pairs:
            name = entry.get("name", "?")
            try:
                if "codec" in entry:
                    grad = codec_mod.QuantizedView(entry, run)
                    logical = grad.nbytes
                else:
                    grad = run.view(np.dtype(entry["dtype"])).reshape(
                        tuple(entry["shape"]))
                    logical = int(grad.nbytes)
                self._update_sem.acquire()
                try:
                    version = self._apply_update(name, grad, tracing)
                finally:
                    self._update_sem.release()
                self._m["push_bytes"].add(logical)
                results.append({"name": name, "version": version})
            except native.RpcError as e:
                results.append({"name": name, "code": e.code,
                                "error": e.text})
            except ValueError as ve:
                # Corrupt entry (size mismatch, unknown codec): the
                # E_UNDECODABLE discipline, per name — groupmates after
                # it still apply.
                results.append({
                    "name": name, "code": E_UNDECODABLE,
                    "error": f"undecodable tensor payload for {name}: "
                             f"{ve}"})
        self._m["push_group"].record_s(time.monotonic() - t0)
        return json.dumps({"results": results}).encode(), None

    # ---- one-sided publication (memory-semantics pulls) ----

    def _publish_oneside(self, name: str) -> None:
        """Publish ``name``'s committed version into the one-sided
        window: [self-describing header|bytes] — raw, or the encoded
        wire form when ``oneside_codec`` engages — written into a fresh
        arena range the window takes ownership of (the displaced
        version's range retires through epoch reclamation, never under a
        reader mid-copy). Callers hold the per-name update lock, so
        publish order matches version order. Arena exhaustion skips the
        publish — readers of this name fall back to the RPC path, which
        serves the same committed state."""
        win = self._oneside_window
        if win is None:
            return
        with self._mu:
            if name not in self._params:
                return
            p = self._params[name]
            version = self._version[name]
        host = np.asarray(p)  # one D2H on the device path
        header = data = None
        c = self._oneside_codec
        if c and codec_mod.eligible(host):
            enc = codec_mod.encode(host, c)
            if enc is not None:
                header, data = enc.header, enc.wire
                codec_mod.note(name, c, enc.logical_bytes, enc.wire_bytes)
        if data is None:
            header = codec_mod.pack_header({"dtype": host.dtype.str,
                                            "shape": list(host.shape)})
            data = np.ascontiguousarray(host).reshape(-1).view(np.uint8)
        # 64B-multiple header => the payload starts 64B-aligned in the
        # blob, so a reader's device_put can alias it zero-copy.
        header = pad_header64(header)
        total = len(header) + int(data.nbytes)
        try:
            off = self.arena.alloc(total)
        except MemoryError:
            return  # unpublished version: one-sided readers fall back
        view = self.arena.view(off, total)
        view[:len(header)] = np.frombuffer(header, dtype=np.uint8)
        if data.nbytes:
            view[len(header):] = data.reshape(-1)
        try:
            win.publish(name, off, total, version)
        except (ValueError, RuntimeError):
            self.arena.free(off)

    # ---- live-resharding handshake (driven by brpc_tpu/fleet.Migrator) ----

    def _recompute_spread_locked(self) -> None:
        vs = self._version.values()
        self._version_spread = max(vs) - min(vs) if vs else 0

    def _handle_handoff(self, request: bytes):
        """Freeze `name` for export: pushes refuse with E_MOVED from here
        on (no update can land that the export would miss); pulls keep
        serving the frozen — latest committed — version until Retire.
        Returns {"version"} + the stacked [param, momentum] tensor.
        Idempotent: a migrator retry re-exports the same frozen state."""
        req = json.loads(request.decode())
        name, dest = req["name"], req.get("dest", "")
        with self._mu:
            lock = self._update_locks.get(name)
            if lock is None:
                moved = self._moved.get(name)
                if moved is not None:
                    raise native.RpcError(E_MOVED,
                                          f"parameter {name} moved:{moved}")
                raise native.RpcError(E_NO_SUCH,
                                      f"no such parameter: {name}")
        with lock:  # an in-flight push completes (or sees frozen) first
            with self._mu:
                if name not in self._params:  # retired while we waited
                    moved = self._moved.get(name)
                    raise native.RpcError(
                        E_MOVED, f"parameter {name} retired"
                        + (f"; moved:{moved}" if moved else ""))
                self._state[name] = "frozen"
                if dest:
                    self._handoff_dest[name] = dest
                p = self._params[name]
                m = self._momenta[name]
                version = self._version[name]
        # Updates are functional (p/m replaced, never mutated) and frozen
        # names take no more of them: stacking outside the locks reads
        # stable arrays. One D2H per array on the device path.
        stacked = np.stack([np.asarray(p), np.asarray(m)])
        return json.dumps({"name": name, "version": version}).encode(), stacked

    def _handle_install(self, request: bytes, att):
        """Adopt a handed-off tensor in `pending` state: pulls serve it
        (same version the frozen old owner still answers), pushes refuse
        with E_MIGRATING until Commit — a version can never advance here
        while the old owner still serves reads. Idempotent re-install of a
        pending name is allowed (migrator retry)."""
        req = json.loads(request.decode())
        name = req["name"]
        version = int(req.get("version", 0))
        if att is None:
            raise native.RpcError(native.TRPC_EREQUEST,
                                  "install without tensor payload")
        if att.ndim < 1 or att.shape[0] != 2:
            raise native.RpcError(
                native.TRPC_EREQUEST,
                f"install expects stacked [param, momentum], "
                f"got shape {tuple(att.shape)}")
        # Detach from the sender's arena pages BEFORE the handler returns.
        param = np.array(att[0])
        mom = np.array(att[1])
        if self._on_device:
            param = _device_put_from_view(param, None)
            mom = _device_put_from_view(mom, None)
        with self._mu:
            # Re-install over `pending` (migrator retry) or `frozen` (this
            # shard handed the name off once and a later remap brought it
            # back before the stale copy was retired) is recovery, not a
            # conflict; only a SERVING copy refuses.
            if name in self._params and self._state.get(name) not in (
                    "pending", "frozen"):
                raise native.RpcError(
                    E_EXISTS, f"install over live parameter: {name}")
            self._params[name] = param
            self._momenta[name] = mom
            self._version[name] = version
            self._enc_cache.pop(name, None)  # encoded for the old bytes
            self._update_locks.setdefault(name, threading.Lock())
            self._state[name] = "pending"
            self._moved.pop(name, None)  # keys can migrate back later
            self._handoff_dest.pop(name, None)  # any old freeze is void
            self._schema_epoch += 1
            self._recompute_spread_locked()
        # Pending names refuse pushes until Commit, so no concurrent
        # publish can race this one out of version order.
        self._publish_oneside(name)
        return json.dumps({"name": name, "version": version}).encode(), None

    def _handle_retire(self, request: bytes):
        """Drop a handed-off tensor and remember its forwarding address:
        later pulls/pushes answer E_MOVED "moved:<dest>" so stale-mapped
        clients re-route without a registry round trip. Idempotent."""
        req = json.loads(request.decode())
        name, dest = req["name"], req.get("dest", "")
        with self._mu:
            lock = self._update_locks.get(name)
        if lock is not None:
            with lock:
                with self._mu:
                    self._params.pop(name, None)
                    self._momenta.pop(name, None)
                    self._version.pop(name, None)
                    self._enc_cache.pop(name, None)
                    self._update_locks.pop(name, None)
                    self._state.pop(name, None)
                    self._handoff_dest.pop(name, None)
                    if dest:  # an empty dest would forward into "moved:"
                        self._moved[name] = dest  # — unparseable; a plain
                    self._schema_epoch += 1       # drop answers E_NO_SUCH
                    self._recompute_spread_locked()
                if self._oneside_window is not None:
                    # A retired name must not serve stale one-sided reads:
                    # mapped clients miss here and re-route via E_MOVED.
                    self._oneside_window.unpublish(name)
        else:
            with self._mu:
                if dest and self._moved.get(name) != dest:
                    # Recording a (new) redirect is a schema change too —
                    # without the bump a warm Meta cache on this server
                    # would keep validating against the pre-retire set.
                    self._moved[name] = dest
                    self._schema_epoch += 1
        return json.dumps({"name": name}).encode(), None

    def _handle_commit(self, request: bytes):
        """pending -> serving: the write-side commit point. Ordered by the
        Migrator AFTER the old owner retired, so reads and writes can
        never disagree across the two owners."""
        name = request.decode()
        with self._mu:
            if name not in self._params:
                moved = self._moved.get(name)
                if moved is not None:
                    raise native.RpcError(E_MOVED,
                                          f"parameter {name} moved:{moved}")
                raise native.RpcError(E_NO_SUCH,
                                      f"no such parameter: {name}")
            self._state.pop(name, None)
            # A stale forwarding hint must not outlive the commit: a later
            # dest-less Handoff would re-surface it as a dead redirect.
            self._handoff_dest.pop(name, None)
        return b"ok", None

    def _apply_update(self, name: str, att, tracing) -> int:
        if isinstance(att, codec_mod.QuantizedView):
            # Quantized gradient push: account the wire win, then either
            # dequantize on-device (H2D moves the ~4x smaller codes, the
            # Pallas/jnp kernel widens there) or into a fresh host buffer
            # (which IS the detach the CPU path needs anyway).
            codec_mod.note(name, att.codec, att.nbytes, att.wire_nbytes)
            if self._on_device:
                with tracing.stage("device_put"):
                    q_dev, s_dev = _detach_device_put_batch(
                        [(att.q, att.scales)], None)
                with tracing.stage("dequant"):
                    grad = _dequant_widen(q_dev, s_dev, att.block, att.n,
                                          att.shape)
            else:
                with tracing.stage("dequant"):
                    att = att.dequantize()
        elif self._on_device:
            with tracing.stage("device_put"):
                # H2D DMA from the request view, completed (and thus
                # detached from the arena pages) before the handler
                # returns and the view's range can be reused.
                grad = _device_put_from_view(np.ascontiguousarray(att), None)
        with self._mu:
            lock = self._update_locks.get(name)
            if lock is None:  # retired between the known-check and here
                moved = self._moved.get(name)
                raise native.RpcError(
                    E_MOVED, f"parameter {name} retired"
                    + (f"; moved:{moved}" if moved else ""))
        with lock:
            with self._mu:
                if name not in self._params:  # retired while we waited
                    moved = self._moved.get(name)
                    raise native.RpcError(
                        E_MOVED, f"parameter {name} retired"
                        + (f"; moved:{moved}" if moved else ""))
                state = self._state.get(name)
                if state == "frozen":
                    dest = self._handoff_dest.get(name)
                    raise native.RpcError(
                        E_MOVED, f"parameter {name} handed off"
                        + (f"; moved:{dest}" if dest else ""))
                if state == "pending":
                    raise native.RpcError(
                        E_MIGRATING,
                        f"parameter {name} migrating in; retry shortly")
                p = self._params[name]
                m = self._momenta[name]
            with tracing.stage("fused_update"):
                if self._on_device:
                    # Dispatch-only: blocking on device completion here
                    # would serialize every update behind its device
                    # round-trip; JAX's async dispatch already orders
                    # later reads of the new arrays.
                    p2, m2 = fused_momentum_update(
                        p, m, grad.astype(p.dtype),
                        lr=self._lr, beta=self._momentum)
                else:
                    # Copy-on-write numpy momentum step, read straight
                    # from the zero-copy view. NOT in-place: a Pull's
                    # response staging copies the returned array after
                    # the handler drops _mu, so arrays must stay
                    # immutable once handed out (same discipline as the
                    # jax path's functional update).
                    g = att.astype(p.dtype, copy=False)
                    m2 = self._momentum * m + g
                    p2 = p - self._lr * m2
            with self._mu:
                self._params[name] = p2
                self._momenta[name] = m2
                self._version[name] += 1
                version = self._version[name]
                self._recompute_spread_locked()
            # Inside the per-name update lock: publish order == version
            # order, so a mapped reader's versions are monotonic.
            self._publish_oneside(name)
        return version


class ParameterClient:
    """Pulls params into device arrays / pushes device grads, all over the
    framework (one TensorChannel per client).

    ``codec="int8"`` (or ``"fp8e4m3"``) asks for the quantized tensor
    wire format (brpc_tpu/runtime/codec.py): engaged per call only after
    the server advertises the codec in Meta — against an older or
    codec-disabled server everything rides raw, transparently. Pulls
    request quantized responses; pushes quantize gradients with
    error-feedback accumulators (the residual of push k rides along with
    push k+1, so repeated pushes never compound rounding bias)."""

    def __init__(self, addr: str, arena: Optional[TensorArena] = None,
                 codec: Optional[str] = None, tenant: str = "",
                 oneside: bool = False):
        self.addr = addr
        self.channel = TensorChannel(addr, arena)
        # Meta cache keyed by the server's schema epoch: the epoch bumps
        # only when the parameter SET changes (Install/Retire), so the
        # name -> shape/dtype map stays valid across ordinary pushes.
        # Cached VERSIONS are stale by design — versions ride each pull.
        self._meta_epoch: Optional[int] = None
        self._meta_cache: Optional[dict] = None
        self._codec = codec
        self._srv_codecs: Optional[tuple] = None  # unknown until Meta
        # PushQ advertisement (grouped quantized pushes): False until the
        # server's Meta carried "pushq": 1 — a PR 7-era server decodes
        # quantized per-tensor pushes but has no PushQ method, so the
        # method itself is negotiated separately from the codec.
        self._srv_pushq = False
        self._ef = codec_mod.ErrorFeedback()
        # Overload protection: the tenant id this client's requests carry
        # (the server's per-tenant quota key; "" falls back to peer ip
        # server-side), and the shed-storm pacer overload answers feed.
        self._tenant = tenant
        self.pacer = OverloadPacer()
        # QoS negotiation state: None until the first Meta fetch; True
        # when the server advertised "qos": 1. Stamping before the
        # advertisement (or against a pre-QoS server, whose parser
        # rejects the unknown meta fields) would kill the connection.
        self._srv_qos: Optional[bool] = None
        # One-sided reads: engaged only when asked for AND the server
        # advertises "oneside" in Meta AND its window maps (same host).
        # _oneside_reader: None = not tried yet, False = permanently on
        # the RPC path (off-host / disabled / gone), else the mapping.
        self._oneside = oneside
        self._oneside_reader = None
        self._srv_oneside: Optional[bool] = None

    # ---- QoS lanes (native/trpc/qos.h) ----
    # Control-plane calls (Epoch, the migrator handshake) ride HIGH —
    # they must stay live while bulk tensor traffic saturates the
    # server's gate; Pull/Push/PullQ ride BULK and accept the headroom
    # shed. NEGOTIATED like the codec advertisement: fields are stamped
    # only after the server's Meta carried "qos": 1 (a pre-QoS parser
    # reads the extra meta bytes as a corrupt service name and kills the
    # connection), and Meta itself — the negotiation vehicle — always
    # rides unstamped so renegotiation works against any build.

    def _qos(self, priority: int):
        import contextlib

        if self._srv_qos is None:
            # Lazy negotiation (the codec pattern): one Meta RPC the
            # first time a stamped call would happen. A fetch failure
            # leaves the state unknown — this call rides unstamped and a
            # later one retries the advertisement.
            try:
                self.meta()
            except Exception:  # noqa: BLE001 — the op itself will report
                pass
        if not self._srv_qos:
            return contextlib.nullcontext()
        return native.qos(priority, self._tenant)

    def _qos_high(self):
        return self._qos(native.PRIORITY_HIGH)

    def _qos_bulk(self):
        return self._qos(native.PRIORITY_BULK)

    def _qos_failed(self, e: "native.RpcError") -> bool:
        """Self-heal a stale QoS advertisement: a server rolled back to a
        pre-QoS build rejects stamped frames at PARSE time, which
        surfaces client-side as a transport error (connection killed —
        EEOF/EFAILEDSOCKET/ECONNECT). Re-read the advertisement ONCE
        (Meta rides unstamped, so it works against any build); True =
        the server no longer advertises QoS and the caller should retry
        its now-unstamped call. Genuine transport failures re-advertise
        and keep their error, costing one Meta RPC on an already-failing
        path — the _codec_pull_failed discipline."""
        if not self._srv_qos or e.code not in native.TRANSPORT_DEAD:
            return False
        self._srv_qos = None
        try:
            self.meta()
        except Exception:  # noqa: BLE001 — keep the original error
            return False
        return not self._srv_qos

    def meta(self) -> dict:
        # UNSTAMPED deliberately: Meta is the negotiation vehicle for both
        # the codec and the QoS advertisement — it must parse on any
        # build, including one that predates the QoS meta fields.
        payload, _ = self.channel.call("ParamService/Meta")
        doc = json.loads(payload.decode())
        self._meta_epoch = doc["epoch"]
        self._meta_cache = doc["params"]
        self._srv_codecs = tuple(doc.get("codecs", ()))
        self._srv_qos = bool(doc.get("qos", 0))
        self._srv_oneside = bool(doc.get("oneside", 0))
        self._srv_pushq = bool(doc.get("pushq", 0))
        return doc["params"]

    def epoch(self) -> int:
        """The server's schema epoch (a tiny small-RPC-fast-path call)."""
        with self._qos_high():
            payload, _ = self.channel.call("ParamService/Epoch")
        return json.loads(payload.decode())["epoch"]

    def cached_meta(self) -> dict:
        """The Meta map through the epoch-validated cache: one Epoch
        round trip (bytes, not the whole schema) when warm; a full Meta
        fetch only on the first call or an epoch mismatch."""
        if self._meta_cache is not None and self.epoch() == self._meta_epoch:
            return self._meta_cache
        return self.meta()

    # ---- per-call codec negotiation (quantized tensor wire) ----

    def negotiated_codec(self) -> Optional[str]:
        """The codec this client/server pair agreed on, or None (raw).
        The advertisement is fetched on first use (one Meta RPC) and
        then trusted for the client's lifetime — NOT revalidated per
        call (this runs per pull/push). Pulls from any codec-aware
        server are safe regardless of restarts (decode follows the
        response's self-describing header); the stale-advertisement
        failure modes all self-heal: a push the server can no longer
        decode answers E_UNDECODABLE (_codec_push_failed drops the
        advertisement), a push to a PRE-codec rollback dies
        TRPC_EINTERNAL (_codec_push_failed re-reads the advertisement
        and heals only when the codec is gone), and a pull a PRE-codec
        rollback reads as an unknown name/method dies E_NO_SUCH
        (_codec_pull_failed re-reads the advertisement and retries raw
        when it changed)."""
        if self._codec is None:
            return None
        if self._srv_codecs is None:
            # Full Meta fetch, NOT cached_meta(): after an invalidation
            # the schema epoch usually still matches (restarted servers
            # reuse epochs), and the epoch-hit path returns the cached
            # map without repopulating the advertisement — renegotiation
            # must actually re-read it.
            self.meta()
        return codec_mod.choose(self._codec, self._srv_codecs)

    def _codec_push_failed(self, e: "native.RpcError") -> None:
        """Self-heal a stale codec advertisement: a server restarted
        without our negotiated codec (build lost ml_dtypes, operator
        set codecs=()) cannot decode our quantized pushes."""
        if e.code == E_UNDECODABLE:
            self._srv_codecs = None  # renegotiate on the next call
            return
        if e.code != TRPC_EINTERNAL or self.negotiated_codec() is None:
            return
        # A PRE-codec build has no E_UNDECODABLE answer: its trampoline
        # hands the handler the flat quantized bytes, whose shape
        # mismatch dies in the update math as a generic internal error.
        # Mirror _codec_pull_failed: re-read the advertisement ONCE — a
        # rollback no longer carries our codec (heal; the next push
        # rides raw), while a genuine handler bug re-advertises the same
        # codec and keeps both its error and the negotiation, costing
        # one Meta RPC on an already-failing path.
        self._srv_codecs = None
        try:
            self.meta()
        except Exception:  # noqa: BLE001 — keep the original error
            pass

    def _pushq_failed(self, e: "native.RpcError") -> bool:
        """A grouped push that died E_NO_SUCH may mean the server rolled
        back to a pre-PushQ build (PR 7-era: quantized per-tensor pushes
        fine, no PushQ method) — per-NAME misses ride the result
        manifest, so a group-level E_NO_SUCH is the method itself.
        Re-read the advertisement once (the _codec_pull_failed
        discipline); True = PushQ is gone and the caller should retry
        per-tensor (still quantized if the codec survives)."""
        if e.code != E_NO_SUCH or not self._srv_pushq:
            return False
        self._srv_codecs = None  # force a FULL Meta re-read (see
        try:                     # negotiated_codec on epoch reuse)
            self.meta()
        except Exception:  # noqa: BLE001 — keep the original error
            return False
        return not self._srv_pushq

    def _codec_pull_failed(self, e: "native.RpcError") -> bool:
        """A NEGOTIATED pull that died E_NO_SUCH may mean the server was
        rolled back to a pre-codec build: such a server reads the
        "name\\x00codec" marker as part of an unknown parameter name, and
        has no PullQ method at all — every pull wedges as "no such"
        although raw would work. Re-read the advertisement ONCE: if it no
        longer carries our codec, renegotiation happened and the caller
        should retry (now raw). A genuine miss re-advertises the same
        codec, so misses cost one extra Meta RPC and keep their error —
        success paths pay nothing."""
        if e.code != E_NO_SUCH or self.negotiated_codec() is None:
            return False
        self._srv_codecs = None
        try:
            self.meta()
        except Exception:  # noqa: BLE001 — keep the original error
            return False
        return self.negotiated_codec() is None

    # ---- one-sided reads (memory-semantics pulls) ----

    def _oneside_enabled(self, oneside: Optional[bool]) -> bool:
        return self._oneside if oneside is None else bool(oneside)

    def _ensure_oneside_reader(self):
        """The mapped window, lazily established: one Meta RPC for the
        advertisement (the codec/QoS negotiation discipline), one
        Oneside RPC for the descriptor, one map. Any failure parks this
        client permanently on the RPC path — off-host mappings cannot
        start working later, and a restarted server re-advertises
        through a fresh client."""
        r = self._oneside_reader
        if r is not None:
            return r if r is not False else None
        if self._srv_oneside is None:
            try:
                self.meta()
            except native.RpcError:
                return None  # unknown stays unknown: retry next call
        if not self._srv_oneside:
            self._oneside_reader = False
            return None
        try:
            payload, _ = self.channel.call("ParamService/Oneside")
            desc = json.loads(payload.decode())
            r = OnesideReader.map(desc)
        except (native.RpcError, ValueError):
            r = None
        self._oneside_reader = r if r is not None else False
        return r

    def _drop_oneside_reader(self) -> None:
        r = self._oneside_reader
        self._oneside_reader = False  # permanent fallback
        if r not in (None, False):
            r.close()

    def _oneside_read(self, name: str, device=None, to_host: bool = False):
        """-> (version, array) straight from the peer's published window,
        or None when this pull should ride the RPC path (every miss
        counts into oneside_pull_fallbacks; the RPC path serves the same
        committed state, so fallback is invisible to the caller)."""
        from brpc_tpu.runtime.tensor import _metrics

        m = _metrics()
        r = self._ensure_oneside_reader()
        if r is None:
            m["oneside_fallbacks"].add(1)
            return None
        try:
            # read_np: the owned-ndarray form — one copy out of the
            # window, viewed (and on CPU device_put-aliased) in place.
            version, payload = r.read_np(name)
        except OnesideGone:
            self._drop_oneside_reader()
            m["oneside_fallbacks"].add(1)
            return None
        except OnesideMiss:
            m["oneside_fallbacks"].add(1)
            return None
        try:
            arr = consume_oneside_payload(payload, device, note_name=name,
                                          to_host=to_host)
        except Exception:  # noqa: BLE001 — undecodable publication
            m["oneside_fallbacks"].add(1)
            return None
        m["oneside_hits"].add(1)
        return int(version), arr

    def prune_residuals(self, keep) -> int:
        """Drop error-feedback residuals for names failing ``keep(name)``.
        Fleet reshard hook: once a name's ownership moves to another
        shard this client never pushes it again, and its residual (a
        full-gradient-sized fp32 buffer) would otherwise live for the
        client's lifetime. Dropping one costs at most a single quant
        step of accuracy on a stream that has already ended."""
        return self._ef.prune(keep)

    def _pull_request(self, name: str) -> bytes:
        """Pull request bytes: the bare name (byte-identical to the
        pre-codec wire) unless a codec is negotiated — then the per-call
        marker the server's Pull parses. Also used by the fleet's shard
        streams, so single-server and fleet negotiation cannot drift."""
        c = self.negotiated_codec()
        return name.encode() + (b"\x00" + c.encode() if c else b"")

    def _grad_encoder(self, name: str):
        """The per-tensor PipelineWindow/push_device encoder closure for
        a quantized gradient push (None when riding raw): compensates
        with the error-feedback residual, quantizes at arena-stage time,
        settles the new residual."""
        c = self.negotiated_codec()
        if c is None:
            # Raw stream: nothing will be owed, and a residual left by
            # an EARLIER quantized push (stream degraded after an
            # E_UNDECODABLE self-heal) is a full-gradient-sized fp32
            # buffer that would otherwise strand for the client's
            # lifetime. Dropping it costs at most one quant step on a
            # stream that has ended.
            self._ef.clear(name)
            return None

        def enc(host: np.ndarray):
            if not codec_mod.eligible(host):
                self._ef.clear(name)  # nothing quantized, nothing owed
                return None
            x = self._ef.compensate(name, host)
            e = codec_mod.encode(x, c)
            if e is None:
                self._ef.clear(name)
                return None
            self._ef.settle(name, x, e.dequantized())
            codec_mod.note(name, c, e.logical_bytes, e.wire_bytes)
            return e.wire, e.header

        return enc

    def pull(self, name: str, device=None, oneside: Optional[bool] = None):
        """-> (version, jax.Array) — H2D straight from the shared pages.

        ``oneside=True`` (or the constructor flag) reads the committed
        version straight from the server's published window when it is
        mapped — no RPC at all — and falls back here transparently
        otherwise."""
        if self._oneside_enabled(oneside):
            got = self._oneside_read(name, device)
            if got is not None:
                return got
        self.pacer.pace()
        try:
            with self._qos_bulk():
                rest, arr = self.channel.pull_device(
                    "ParamService/Pull", request=self._pull_request(name),
                    device=device, note_name=name)
        except native.RpcError as e:
            self.pacer.note(e)
            if not (self._codec_pull_failed(e) or self._qos_failed(e)):
                raise
            # Renegotiated (server rolled back to a pre-codec or pre-QoS
            # build): the retried request is byte-identical to the wire
            # that build speaks.
            with self._qos_bulk():
                rest, arr = self.channel.pull_device(
                    "ParamService/Pull", request=self._pull_request(name),
                    device=device)
        self.pacer.clear()
        return int(rest.decode()), arr

    def push_grad(self, name: str, grad) -> int:
        """Send a device gradient; returns the server's new version."""
        self.pacer.pace()
        try:
            with self._qos_bulk():
                payload = self.channel.push_device(
                    "ParamService/Push", grad, request=name.encode(),
                    encoder=self._grad_encoder(name))
        except native.RpcError as e:
            self.pacer.note(e)
            self._codec_push_failed(e)
            if self._qos_failed(e):
                # Pre-QoS rollback: retry once unstamped (the heal
                # re-read the advertisement; the frame is now the old
                # wire exactly).
                payload = self.channel.push_device(
                    "ParamService/Push", grad, request=name.encode(),
                    encoder=self._grad_encoder(name))
            else:
                raise
        self.pacer.clear()
        return int(payload.decode())

    # ---- live-resharding handshake (used by brpc_tpu/fleet.Migrator) ----

    def handoff(self, name: str, dest: str = ""):
        """Freeze + export `name` -> (version, stacked [param, momentum]
        host array). The server refuses pushes to it from now on."""
        req = json.dumps({"name": name, "dest": dest}).encode()
        with self._qos_high():  # migrator handshake = control plane
            payload, stacked = self.channel.call("ParamService/Handoff",
                                                 request=req)
        return json.loads(payload.decode())["version"], stacked

    def install(self, name: str, stacked, version: int,
                commit: bool = False) -> None:
        """Adopt a stacked [param, momentum] tensor at `version` in
        pending state; `commit=True` also flips it serving (reseed path)."""
        req = json.dumps({"name": name, "version": int(version)}).encode()
        with self._qos_high():
            self.channel.call("ParamService/Install", array=stacked,
                              request=req)
        if commit:
            self.commit(name)

    def retire(self, name: str, dest: str = "") -> None:
        req = json.dumps({"name": name, "dest": dest}).encode()
        with self._qos_high():
            self.channel.call("ParamService/Retire", request=req)

    def commit(self, name: str) -> None:
        with self._qos_high():
            self.channel.call("ParamService/Commit", request=name.encode())

    # ---- pipelined multi-tensor hot path (PipelineWindow) ----
    # The serial pull/push above pay one full round-trip per tensor: a
    # model with N parameter tensors pays N x the ~260us 1MB latency
    # floor (old run, records deleted in PR 21) although the transport sustains ~3x the
    # single-stream throughput at conc=8 (BENCH r05). These keep a
    # bounded window of RPCs in flight instead, so N tensors cost ~1
    # round-trip plus N wire times.

    def pull_all(self, names=None, device=None, window: int = 4,
                 group: int = 8, to_host: bool = False,
                 oneside: Optional[bool] = None) -> Dict[str, tuple]:
        """Pull many parameters through one bounded pipeline window.

        -> ``{name: (version, jax.Array)}``. ``names=None`` pulls every
        parameter the server's Meta lists. ``to_host=True`` returns
        DETACHED host ndarrays instead of device arrays (the fleet's
        shard streams use this: device dispatch from N threads contends,
        so shards stop at host copies and the caller dispatches alone).

        Raw (no negotiated codec): one RPC per tensor, each
        ``jax.device_put`` straight from its zero-copy response view —
        byte-identical to the pre-codec wire. Negotiated codec: pulls ride
        ``PullQ`` in groups of ``group`` tensors per RPC — the codec cuts
        each tensor ~4x, which leaves the per-RPC fixed cost dominating a
        per-tensor stream, so grouping is where the second half of the
        effective-bandwidth win comes from (old host run, records deleted in PR 21).
        """
        from brpc_tpu.runtime.tensor import (_decode_meta_ex, _metrics,
                                             _stage, consume_pull_reply)

        self.pacer.pace()  # shed-storm brake: honor any retry-after debt
        listed_meta = None
        if names is None:
            listed_meta = self.cached_meta()
            names = sorted(listed_meta)
        names = list(names)
        m = _metrics()
        out: Dict[str, tuple] = {}
        # One-sided pre-pass: every name the mapped window serves skips
        # the RPC plane entirely; the stragglers (unpublished, torn,
        # unmapped, off-host) ride the pipelined RPC path below — the
        # per-shard locality routing the fleet client inherits as-is.
        if self._oneside_enabled(oneside) and names:
            rest = []
            for n in names:
                got = self._oneside_read(n, device, to_host=to_host)
                if got is not None:
                    out[n] = got
                else:
                    rest.append(n)
            if not rest:
                return out
            names = rest
        c = self.negotiated_codec()

        if c is None:
            if to_host:
                def on_reply(name, payload, view):
                    with view:
                        meta, rest = _decode_meta_ex(payload)
                        host = np.array(np.frombuffer(
                            view.ndarray(),
                            dtype=np.dtype(meta["dtype"])).reshape(
                                tuple(meta["shape"])))
                    m["pull_bytes"].add(host.nbytes)
                    out[name] = (int(rest.decode()), host)
            else:
                def on_reply(name, payload, view):
                    rest, dev, nbytes = consume_pull_reply(payload, view,
                                                           device)
                    m["pull_bytes"].add(nbytes)
                    out[name] = (int(rest.decode()), dev)

            try:
                with self._qos_bulk(), PipelineWindow(
                        self.channel, window, on_reply=on_reply) as win:
                    for name in names:
                        win.submit("ParamService/Pull",
                                   request=self._pull_request(name),
                                   tag=name)
            except native.RpcError as e:
                self.pacer.note(e)
                if out:
                    raise PartialPullError(
                        e, dict(out),
                        [n for n in names if n not in out]) from e
                raise
            self.pacer.clear()
            return out

        import jax

        target = device if device is not None else jax.devices()[0]
        on_accel = getattr(target, "platform", "cpu") != "cpu"

        # Codec-ineligible tensors (non-fp32 / below the size floor) gain
        # nothing from PullQ — the server serves them raw inside the
        # group and the client's manifest decode costs a full host copy
        # the per-tensor path avoids (_device_put_from_view aliases the
        # response view). Meta already carries dtype/shape, so predict
        # eligibility and keep those names on the per-tensor raw path
        # (same window, so they still pipeline). Host-copy targets
        # (to_host) pay the copy either way — no reason to split.
        # Prediction misses (name absent from the cached map, or the
        # server swapped the tensor since) just ride the group, whose
        # raw-entry decode stays correct.
        singles: list = []
        if not to_host:
            try:
                meta_map = (listed_meta if listed_meta is not None
                            else self.cached_meta())
            except native.RpcError:
                meta_map = {}

            def _predict_eligible(n: str) -> bool:
                e = meta_map.get(n)
                if e is None:
                    return True  # unknown: the group reports it per-name
                return (e["dtype"] == "float32"
                        and int(np.prod(e["shape"], dtype=np.int64)) * 4
                        >= codec_mod.MIN_QUANT_BYTES)

            singles = [n for n in names if not _predict_eligible(n)]
        single_set = set(singles)
        grouped = ([n for n in names if n not in single_set]
                   if singles else names)

        def on_group(_tag, payload, view):
            # Decode every tensor of the group while the view is held
            # (the codes live in the peer's pages), then dispatch ONE
            # jax.device_put for the whole group: per-tensor dispatch is
            # ~0.1-0.4ms of pure overhead on this path (PR 6 measured the
            # contention flavor of the same cost), and the dequant output
            # is a FRESH buffer — no view-release hazard, so no per-
            # tensor block_until_ready either.
            metas, hosts = [], []
            qmetas, qpairs, qdevs = [], [], []
            err: Optional[native.RpcError] = None
            with view:
                man = json.loads(payload.decode())
                # b"" (not None): a group of only zero-size tensors ships
                # a manifest with no attachment, and b""[0:0] keeps the
                # slice-decode loop valid for their empty entries.
                buf = view.ndarray() if view.nbytes else b""
                off = 0
                for t in man["tensors"]:
                    if "error" in t:
                        # Surface like the per-tensor path would — after
                        # the groupmates decoded (a moved tensor must not
                        # poison them; the fleet retries it per name).
                        if err is None:
                            err = native.RpcError(t["code"], t["error"])
                        continue
                    nb = t["nbytes"]
                    sub = buf[off:off + nb]
                    off += nb
                    if "codec" in t:
                        # Decode side of the tensor_codec_* accounting:
                        # pull-only processes must still show their
                        # logical/wire bytes and ratio on /vars+/tensorz.
                        codec_mod.note(
                            t["name"], t["codec"],
                            int(np.prod(t["shape"], dtype=np.int64))
                            * np.dtype(t["dtype"]).itemsize, nb)
                    try:
                        with _stage("dequant"):
                            if on_accel and not to_host and "codec" in t:
                                # Real accelerator: collect the (4x
                                # smaller) codes+scales views; the single
                                # H2D below detaches the whole group.
                                q, s = codec_mod.split_wire(t, sub)
                                qmetas.append(t)
                                qpairs.append((q, s))
                                continue
                            if "codec" in t:
                                host = codec_mod.decode(t, sub)
                            else:
                                host = np.array(np.frombuffer(
                                    sub, dtype=np.dtype(t["dtype"])
                                ).reshape(tuple(t["shape"])))
                    except ValueError as ve:
                        # Corrupt entry: ride the same per-name error
                        # path as a manifest miss (groupmates survive
                        # into PartialPullError; a bare ValueError would
                        # bypass the salvage and the fleet re-route).
                        if err is None:
                            err = native.RpcError(
                                E_UNDECODABLE, "undecodable tensor "
                                f"payload for {t['name']}: {ve}")
                        continue
                    metas.append(t)
                    hosts.append(host)
                if qpairs:
                    with _stage("dequant"):
                        # Detach the whole group before the view releases
                        # (one put + one barrier — see the helper).
                        qdevs = _detach_device_put_batch(qpairs, device)
            if qmetas:
                with _stage("dequant"):
                    for i, t in enumerate(qmetas):
                        val = _dequant_widen(
                            qdevs[2 * i], qdevs[2 * i + 1], t["block"],
                            int(np.prod(t["shape"], dtype=np.int64)),
                            t["shape"], want=t["dtype"])
                        out[t["name"]] = (int(t["version"]), val)
                        m["pull_bytes"].add(
                            int(np.prod(t["shape"], dtype=np.int64))
                            * np.dtype(t["dtype"]).itemsize)
            if hosts:
                vals = hosts if to_host else jax.device_put(hosts, device)
                for t, val in zip(metas, vals):
                    m["pull_bytes"].add(
                        int(np.prod(t["shape"], dtype=np.int64))
                        * np.dtype(t["dtype"]).itemsize)
                    out[t["name"]] = (int(t["version"]), val)
            if err is not None:
                raise err

        def on_reply(tag, payload, view):
            if isinstance(tag, tuple):
                return on_group(tag, payload, view)
            # Predicted-ineligible per-tensor pull: raw reply, zero-copy
            # device_put straight from the view (the path the raw branch
            # above uses; the self-describing header keeps this correct
            # even if the server quantized after all).
            rest, dev, nbytes = consume_pull_reply(payload, view, device,
                                                   note_name=tag)
            m["pull_bytes"].add(nbytes)
            out[tag] = (int(rest.decode()), dev)

        try:
            with self._qos_bulk(), PipelineWindow(
                    self.channel, window, on_reply=on_reply) as win:
                for name in singles:
                    win.submit("ParamService/Pull",
                               request=self._pull_request(name), tag=name)
                for i in range(0, len(grouped), max(1, group)):
                    g = grouped[i:i + max(1, group)]
                    req = json.dumps({"names": g, "codec": c}).encode()
                    win.submit("ParamService/PullQ", request=req,
                               tag=tuple(g))
        except native.RpcError as e:
            self.pacer.note(e)
            if self._codec_pull_failed(e):
                # Pre-codec rollback (no PullQ method): renegotiated to
                # raw — re-pull the stragglers through the per-tensor
                # raw path and merge, keeping any decoded survivors.
                rem = [n for n in names if n not in out]
                try:
                    out.update(self.pull_all(rem, device=device,
                                             window=window, group=group,
                                             to_host=to_host))
                except PartialPullError as pe:
                    raise PartialPullError(pe, {**out, **pe.partial},
                                           pe.missing) from pe
                except native.RpcError as re2:
                    # The raw re-pull died before delivering anything new
                    # (e.g. the rolled-back server is still restarting).
                    # The survivors in `out` must still reach the caller.
                    if out:
                        raise PartialPullError(
                            re2, dict(out),
                            [n for n in rem if n not in out]) from re2
                    raise
                return out
            if out:
                raise PartialPullError(
                    e, dict(out),
                    [n for n in names if n not in out]) from e
            raise
        self.pacer.clear()
        return out

    def push_all(self, grads: Dict[str, object], window: int = 4,
                 group: int = 8) -> Dict[str, int]:
        """Push many gradients through one bounded pipeline window.

        -> ``{name: new_version}``. Staging (D2H + arena memcpy) of
        gradient k+1 overlaps the wire transfer of gradient k; the
        client arena never holds more than ``window`` staged gradients.

        Raw (no negotiated codec): one Push RPC per tensor —
        byte-identical to the pre-codec wire. Negotiated codec against a
        PushQ-advertising server: eligible gradients quantize (with
        error feedback) into groups of ``group`` per PushQ RPC — the
        codec cuts each ~4x, which leaves the per-RPC fixed cost
        dominating a per-tensor stream, the same second lever PullQ is
        on the read side (old host run, records deleted in PR 21). Per-name results ride the
        response manifest; a moved/undecodable name raises
        :class:`PartialPushError` with its groupmates' confirmed
        versions in ``applied``.
        """
        from brpc_tpu.runtime.tensor import _as_host_array, _metrics
        m = _metrics()
        versions: Dict[str, int] = {}
        per_name_err: Dict[str, native.RpcError] = {}
        c = self.negotiated_codec()
        use_group = c is not None and self._srv_pushq and group > 1

        def on_reply(tag, payload, view):
            view.release()  # push responses carry no tensor
            if isinstance(tag, tuple):
                doc = json.loads(payload.decode())
                for r in doc["results"]:
                    if "error" in r:
                        per_name_err[r["name"]] = native.RpcError(
                            int(r["code"]), r["error"])
                    else:
                        versions[r["name"]] = int(r["version"])
            else:
                versions[tag] = int(payload.decode())

        self.pacer.pace()
        try:
            with self._qos_bulk(), PipelineWindow(
                    self.channel, window, on_reply=on_reply) as win:
                if not use_group:
                    for name, grad in grads.items():
                        win.submit("ParamService/Push", array=grad,
                                   request=name.encode(), tag=name,
                                   encoder=self._grad_encoder(name))
                        m["push_bytes"].add(
                            int(getattr(grad, "nbytes", 0)))
                else:
                    # Split by METADATA (dtype/nbytes — no D2H needed),
                    # then materialize host copies one group slice at a
                    # time: an up-front copy of every gradient would
                    # hold a full host replica of the model where the
                    # per-tensor path never stages more than `window`
                    # tensors. Ineligible tensors ride per-tensor raw
                    # in the SAME window so they still pipeline (submit
                    # does their D2H, window-bounded).
                    names = list(grads)

                    def _predict(g) -> bool:
                        try:
                            return (np.dtype(getattr(g, "dtype", None))
                                    == np.float32
                                    and int(getattr(g, "nbytes", 0))
                                    >= codec_mod.MIN_QUANT_BYTES)
                        except TypeError:
                            return False

                    grouped = [n for n in names if _predict(grads[n])]
                    gset = set(grouped)
                    for name in names:
                        if name in gset:
                            continue
                        self._ef.clear(name)  # raw hop: nothing owed
                        win.submit("ParamService/Push",
                                   array=grads[name],
                                   request=name.encode(), tag=name)
                        m["push_bytes"].add(
                            int(getattr(grads[name], "nbytes", 0)))
                    for i in range(0, len(grouped), group):
                        gnames = grouped[i:i + group]
                        entries, blobs = [], []
                        for n in gnames:
                            host = _as_host_array(grads[n])
                            x = self._ef.compensate(n, host)
                            e = codec_mod.encode(x, c)
                            if e is None:  # raced ineligible: raw
                                self._ef.clear(n)
                                win.submit("ParamService/Push",
                                           array=host,
                                           request=n.encode(), tag=n)
                                m["push_bytes"].add(host.nbytes)
                                continue
                            self._ef.settle(n, x, e.dequantized())
                            codec_mod.note(n, c, e.logical_bytes,
                                           e.wire_bytes)
                            entries.append(
                                {"name": n, "dtype": host.dtype.str,
                                 "shape": list(host.shape),
                                 "codec": c, "block": e.block})
                            blobs.append(e.wire)
                            m["push_bytes"].add(host.nbytes)
                        if entries:
                            manifest, concat = groupwire.pack_group(
                                entries, blobs)
                            win.submit("ParamService/PushQ",
                                       array=concat, request=manifest,
                                       tag=tuple(e["name"]
                                                 for e in entries))
        except native.RpcError as e:
            self.pacer.note(e)
            self._codec_push_failed(e)
            group_tagged = isinstance(getattr(e, "pipeline_tag", None),
                                      tuple)
            if group_tagged and self._pushq_failed(e):
                # Pre-PushQ rollback: the method is gone, the names are
                # fine — re-push the unconfirmed stragglers per-tensor
                # (renegotiated; still quantized if the codec survived)
                # and merge, keeping every confirmed version.
                rem = {n: grads[n] for n in grads if n not in versions}
                try:
                    versions.update(self.push_all(rem, window=window,
                                                  group=group))
                except PartialPushError as pe:
                    raise PartialPushError(
                        pe, {**versions, **pe.applied},
                        pe.unpushed) from pe
                except native.RpcError as re2:
                    if versions:
                        raise PartialPushError(
                            re2, dict(versions),
                            [n for n in rem if n not in versions]
                        ) from re2
                    raise
                return versions
            if versions:
                raise PartialPushError(
                    e, dict(versions),
                    [n for n in grads if n not in versions]) from e
            raise
        if per_name_err:
            # Per-name refusals from the result manifest (moved mid-
            # reshard, undecodable): surface the PartialPush salvage —
            # and run the stale-advertisement heal for undecodable
            # answers exactly like a per-tensor push would.
            cause = next(iter(per_name_err.values()))
            for err in per_name_err.values():
                self._codec_push_failed(err)
            raise PartialPushError(
                cause, dict(versions),
                [n for n in grads if n not in versions])
        self.pacer.clear()
        return versions

    def close(self) -> None:
        if self._oneside_reader not in (None, False):
            self._oneside_reader.close()
        self._oneside_reader = False
        self.channel.close()
