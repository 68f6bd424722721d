"""Split the traced calls of one benchmark cell into the program's stages.

    python3 tools/call_split.py --workload <cell> --seed <n> --seconds <s> \
        [--out FILE] [--rehearse]

Runs one traced run of the cell through the benchmark's harness, as
``benchmark/run.py --trace 1`` does, and reads two more things from it:

  the rpcz ring   every span that shares a trace with a harness root
                  (``bench.<kind>/<tensor>``): the client leg and the server
                  leg. Per call, the client's ``rpc`` stage splits into
                  transport (``rpc`` minus the server leg: both wire
                  directions, parse, client wake) and the server leg, which
                  splits into pool_wait, gil_enter, handler, respond_stage
                  and a native tail; the root also carries gil_return.
  the profile     the program's ``brpc.<stage>`` host events: the share of
                  the slice's device-idle time during which a host<->HBM
                  stage was open on any thread, each long idle gap labelled
                  by the harness kind and the innermost program stages open
                  at its middle (``pull/respond_stage``), and the offset
                  that maps the trace's clock onto rpcz's.

Prints the harness's result line with these under ``"split"`` as one JSON
line (and writes it to ``--out``). Needs the chip, like the benchmark;
``--rehearse`` runs it on the CPU at the rehearsal's tiny size, where only
the rpcz side has something to read.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import devtrace, stats  # noqa: E402

SERVER_STAGES = ("pool_wait", "gil_enter", "handler", "respond_stage")
# Stages nested inside the handler (a push's H2D and update dispatch).
HANDLER_PARTS = ("device_put", "update_dispatch")
HOST_DEVICE_STAGES = ("device_put", "arena_stage", "respond_stage")
PREFIX = "brpc."

# (stage, start_ns, end_ns, thread): one brpc.* host event of the profile.
StageEvent = Tuple[str, float, float, str]


# ---- the rpcz side ----

def traces(spans: Sequence[dict]) -> Dict[str, List[dict]]:
    """Every span that shares a trace id with a harness root, by trace."""
    roots = {s["trace_id"] for s in spans
             if str(s.get("service_method", "")).startswith("bench.")}
    out: Dict[str, List[dict]] = defaultdict(list)
    for s in spans:
        if s["trace_id"] in roots:
            out[s["trace_id"]].append(s)
    return out


def call_splits(spans: Sequence[dict]) -> List[dict]:
    """One dict per harness call whose server leg is still in the ring
    (the others are skipped): its kind and tensor, the client's ``rpc``
    and ``gil_return``, the server leg's length and stages, and the
    derived transport (rpc - leg) and tail (leg - its four stages), all
    in microseconds."""
    out = []
    for legs in traces(spans).values():
        root = server = client = None
        for s in legs:
            name = str(s["service_method"])
            if name.startswith("bench."):
                root = s
            elif s["server_side"]:
                server = s
            else:
                client = s
        if root is None or server is None:
            continue
        kind, _, tensor = root["service_method"][len("bench."):].partition("/")
        rpc = stats.stage_us(root, "rpc")
        stages = {k: stats.stage_us(server, k) for k in SERVER_STAGES}
        if rpc is None or None in stages.values():
            continue
        leg = server["end_us"] - server["start_us"]
        split = dict(kind=kind, tensor=int(tensor), rpc=rpc, leg=leg,
                     gil_return=stats.stage_us(root, "gil_return"),
                     transport=rpc - leg,
                     tail=leg - sum(stages.values()), **stages)
        for k in HANDLER_PARTS:
            split[f"server_{k}"] = stats.stage_us(server, k)
        if client is not None:
            split["client_leg"] = client["end_us"] - client["start_us"]
        out.append(split)
    return out


def _mean_ms(values) -> Optional[float]:
    values = [v for v in values if v is not None]
    return sum(values) / len(values) / 1e3 if values else None


def summary(splits: Sequence[dict], nbytes: Sequence[int]) -> dict:
    """The six numbers of the split and the residual of each identity:
    rpc = transport + leg (transport >= 0), and leg = pool_wait +
    gil_enter + handler + respond_stage + tail (tail >= 0)."""
    pulls = [c for c in splits if c["kind"] == "pull"]
    respond_us = sum(c["respond_stage"] for c in pulls)
    n = len(splits)
    out = {
        "calls": n,
        "pool_wait_ms.mean": _mean_ms(c["pool_wait"] for c in splits),
        "gil_wait_ms.mean": _mean_ms(
            c["gil_enter"] + c["gil_return"] for c in splits
            if c["gil_return"] is not None),
        "handler_ms.mean": _mean_ms(c["handler"] for c in splits),
        "respond_stage_GBps.pull": (
            sum(nbytes[c["tensor"]] for c in pulls) / (respond_us * 1e-6)
            / 1e9 if respond_us > 0 else None),
        "transport_ms.mean": _mean_ms(c["transport"] for c in splits),
    }
    for key in ("rpc", "leg", "gil_enter", "gil_return", "respond_stage",
                "tail", "client_leg"):
        out[f"{key}_ms.mean"] = _mean_ms(c.get(key) for c in splits)
    # Each stage's mean per kind of call (pull, push), in ms.
    out["by_kind"] = {
        kind: {key: _mean_ms(c.get(key) for c in splits
                             if c["kind"] == kind)
               for key in ("rpc", "transport", "leg") + SERVER_STAGES + (
                   "tail", "gil_return") + tuple(
                       f"server_{k}" for k in HANDLER_PARTS)}
        for kind in sorted({c["kind"] for c in splits})}
    if n:
        out["transport_nonneg_share"] = sum(
            c["transport"] >= 0 for c in splits) / n
        out["stages_within_leg_share"] = sum(
            c["tail"] >= 0 for c in splits) / n
        # rpc - client leg: what Python and ctypes add around the native
        # client call (the part of transport outside the wire).
        around = [c["rpc"] - c["client_leg"] for c in splits
                  if "client_leg" in c]
        out["rpc_outside_client_leg_ms.mean"] = _mean_ms(around)
    return out


# ---- the profile side ----

def stage_events(path: str) -> List[StageEvent]:
    """The program's brpc.* events on every host plane of a profile, with
    the thread (plane and line) each ran on."""
    from jax.profiler import ProfileData

    out = []
    for p in ProfileData.from_file(path).planes:
        if not p.name.startswith("/host:"):
            continue
        for i, line in enumerate(p.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name[len(PREFIX):], e.start_ns, e.end_ns,
                                f"{p.name}#{i}"))
    return out


def idle_intervals(ev: devtrace.Events) -> List[Tuple[float, float]]:
    """The slice's device-idle intervals: the gaps between the union of
    its op intervals, clipped to the slice (as devtrace.reduce finds
    them)."""
    lo, hi = ev.slice
    busy = devtrace._union([(max(a, lo), min(b, hi)) for _, a, b in ev.ops
                            if min(b, hi) > max(a, lo)])
    gaps, edge = [], lo
    for a, b in busy + [[hi, hi]]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    return gaps


def innermost(stages: Sequence[StageEvent], t: float) -> List[str]:
    """The innermost program stage open at ``t`` on each thread (the one
    that started last), sorted and without repeats."""
    per_thread: Dict[str, Tuple[float, str]] = {}
    for name, a, b, thread in stages:
        if a <= t <= b and a >= per_thread.get(thread, (-1.0, ""))[0]:
            per_thread[thread] = (a, name)
    return sorted({name for _, name in per_thread.values()})


def gap_labels(ev: devtrace.Events, stages: Sequence[StageEvent],
               top: int = 10) -> List[Tuple[str, float]]:
    """The longest idle gaps, each as devtrace labels it, followed by the
    program stages open at its middle: 'pull' -> 'pull/respond_stage'.
    Without brpc.* events the labels are devtrace's own."""
    gaps = sorted(idle_intervals(ev), key=lambda g: g[1] - g[0],
                  reverse=True)[:top]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        label = devtrace.host_label(ev.host, mid)
        inner = innermost(stages, mid)
        out.append((label + "/" + "+".join(inner) if inner else label,
                    (b - a) * 1e-9))
    return out


def idle_in_copy_share(ev: devtrace.Events,
                       stages: Sequence[StageEvent]) -> Optional[float]:
    """% of the slice's device-idle time during which a host<->HBM stage
    (HOST_DEVICE_STAGES) was open on some thread."""
    gaps = idle_intervals(ev)
    idle = sum(b - a for a, b in gaps)
    if idle <= 0:
        return None
    copies = devtrace._union([(a, b) for name, a, b, _ in stages
                              if name in HOST_DEVICE_STAGES])
    both, j = 0.0, 0
    for a, b in gaps:
        while j < len(copies) and copies[j][1] <= a:
            j += 1
        k = j
        while k < len(copies) and copies[k][0] < b:
            both += min(b, copies[k][1]) - max(a, copies[k][0])
            k += 1
    return 100.0 * both / idle


def clock_offset(roots_us: Sequence[Tuple[float, float]],
                 annotations_ns: Sequence[Tuple[float, float]],
                 slack_us: float = 50.0, sample: int = 300
                 ) -> Optional[dict]:
    """The constant that maps the trace's clock onto rpcz's. Each root
    span (``bench.pull/*``, (start, end) in us) lies inside its harness
    annotation (``pull``, (start, end) in ns), opened a few microseconds
    earlier and closed a few later: the pair has the same length within
    ``slack_us``. Among such pairs, the start difference most of them
    share is the offset. -> offset_us (rpcz = trace + offset), the
    quartiles of each matched call's residual around it, and how many of
    the annotations matched a root within 100 us."""
    r = np.asarray(sorted(roots_us), np.float64).reshape(-1, 2)
    a = np.asarray(sorted(annotations_ns), np.float64).reshape(-1, 2) / 1e3
    if not len(r) or not len(a):
        return None
    r_len, a_len = r[:, 1] - r[:, 0], a[:, 1] - a[:, 0]
    picked = np.arange(0, len(a), max(1, len(a) // sample))
    same = np.abs(a_len[picked, None] - r_len[None, :]) < slack_us
    diffs = (r[None, :, 0] - a[picked, None, 0])[same]
    if not len(diffs):
        return None
    values, counts = np.unique(np.round(diffs / 10.0), return_counts=True)
    mode = 10.0 * values[counts.argmax()]
    res = []
    for (start, _), length in zip(a, a_len):
        lo, hi = np.searchsorted(r[:, 0], (start + mode - 100.0,
                                           start + mode + 100.0))
        near = [r[j, 0] - start - mode for j in range(lo, hi)
                if abs(r_len[j] - length) < slack_us]
        if near:
            res.append(min(near, key=abs))
    if len(res) < 2:
        return None
    q1, q2, q3 = statistics.quantiles(res, n=4)
    return {"offset_us": float(mode + q2), "residual_q1_us": q1 - q2,
            "residual_q3_us": q3 - q2, "matched": len(res),
            "annotations": int(len(a))}


# ---- one traced run ----

def main(argv=None) -> int:
    from benchmark import harness, run
    from brpc_tpu.observability.metrics import _snapshot_buf

    t_proc = harness.process_start_wall()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 1
    args.t_native = run.bootstrap()
    from brpc_tpu.runtime import native

    # The harness reads the profile and deletes it: read the brpc.* events
    # from the same file first.
    kept = {}
    extract = devtrace.extract

    def extract_and_keep(path: str, chip: int = 0) -> devtrace.Events:
        kept["events"] = extract(path, chip)
        kept["stages"] = stage_events(path)
        return kept["events"]

    devtrace.extract = extract_and_keep
    out = harness.execute(args, t_proc, rehearse=args.rehearse)
    if isinstance(out, int):
        return out
    line, result, _ = out
    raw = _snapshot_buf(native.lib().tbrpc_rpcz_dump_json, 0)
    spans = json.loads(raw.decode(errors="replace")) if raw else []
    split = summary(call_splits(spans), result.nbytes)
    ev, stages = kept.get("events"), kept.get("stages", [])
    split["stage_events"] = len(stages)
    if ev is not None and ev.slice is not None and ev.ops:
        split["device_idle_in_copy_share"] = idle_in_copy_share(ev, stages)
        split["idle_gaps"] = gap_labels(ev, stages)
        split["clock"] = clock_offset(
            [(s["start_us"], s["end_us"]) for s in spans
             if str(s.get("service_method", "")).startswith("bench.pull/")],
            [(a, b) for k, a, b in ev.host if k == "pull"])
    line["split"] = split
    line["workload"], line["seed"] = args.workload, args.seed
    text = json.dumps(line)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            fh.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
