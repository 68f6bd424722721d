"""tools/call_split.py: the split of a traced call into program stages and
the attribution of device-idle time to them, on synthetic spans and events
and on the recorded v5e slice (CPU only)."""

import os

import numpy as np
import pytest

from benchmark import devtrace
from tools import call_split as cs

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "benchmark_yardstick", "fixtures",
                       "resnet50_sync_slice.xplane.pb")


def _call(trace, kind, tensor, rpc, leg, stages, gil_return=40,
          client_leg=None, server=True):
    root_ann = [f"rpc={rpc}us"] + (
        [f"gil_return={gil_return}us"] if gil_return is not None else [])
    spans = [{"trace_id": trace, "service_method": f"bench.{kind}/{tensor}",
              "server_side": False, "start_us": 0, "end_us": rpc + 100,
              "annotations": root_ann}]
    if client_leg is not None:
        spans.append({"trace_id": trace, "service_method": "ParamService/X",
                      "server_side": False, "start_us": 10,
                      "end_us": 10 + client_leg, "annotations": []})
    if server:
        spans.append({"trace_id": trace, "service_method": "ParamService/X",
                      "server_side": True, "start_us": 50,
                      "end_us": 50 + leg,
                      "annotations": [f"{k}={v}us" for k, v in
                                      stages.items()]})
    return spans


STAGES = {"gil_enter": 100, "handler": 300, "respond_stage": 400,
          "pool_wait": 50}


def test_calls_split_into_transport_and_server_stages():
    spans = (_call("a", "pull", 1, rpc=2000, leg=1000, stages=STAGES,
                   client_leg=1500)
             + _call("b", "push", 0, rpc=1500, leg=900,
                     stages=dict(STAGES, update_dispatch=100),
                     gil_return=None)
             # the server leg fell out of the ring: skipped, not a zero
             + _call("c", "pull", 0, rpc=9000, leg=0, stages={},
                     server=False)
             + [{"trace_id": "z", "service_method": "other/Call",
                 "server_side": True, "start_us": 0, "end_us": 7,
                 "annotations": ["handler=5us"]}])
    splits = sorted(cs.call_splits(spans), key=lambda c: c["kind"])
    assert [c["kind"] for c in splits] == ["pull", "push"]
    pull, push = splits
    assert pull["transport"] == 1000 and pull["tail"] == 150
    assert push["transport"] == 600 and push["tail"] == 50
    s = cs.summary(splits, nbytes=[4, 1_000_000])
    assert s["calls"] == 2
    assert s["pool_wait_ms.mean"] == pytest.approx(0.05)
    assert s["handler_ms.mean"] == pytest.approx(0.3)
    assert s["transport_ms.mean"] == pytest.approx(0.8)
    # only the call with both GIL waits counts
    assert s["gil_wait_ms.mean"] == pytest.approx(0.14)
    assert s["respond_stage_GBps.pull"] == pytest.approx(
        1_000_000 / 400e-6 / 1e9)
    assert s["transport_nonneg_share"] == 1.0
    assert s["stages_within_leg_share"] == 1.0
    assert s["tail_ms.mean"] == pytest.approx(0.1)
    assert s["rpc_outside_client_leg_ms.mean"] == pytest.approx(0.5)
    assert s["by_kind"]["push"]["server_update_dispatch"] == \
        pytest.approx(0.1)
    assert s["by_kind"]["pull"]["server_update_dispatch"] is None
    assert s["by_kind"]["pull"]["transport"] == pytest.approx(1.0)


def test_an_empty_ring_reads_nothing():
    s = cs.summary(cs.call_splits([]), nbytes=[4])
    assert s["calls"] == 0
    for key in ("pool_wait_ms.mean", "gil_wait_ms.mean", "handler_ms.mean",
                "respond_stage_GBps.pull", "transport_ms.mean"):
        assert s[key] is None


def _events():
    ev = devtrace.Events(slice=(1000.0, 11000.0))
    ev.ops = [("%a = f32[4] fusion()", 2000.0, 4000.0),
              ("%b = f32[4] fusion()", 6000.0, 7000.0)]
    ev.host = [("push", 1500.0, 5000.0), ("pull", 7500.0, 10500.0),
               ("grad", 4800.0, 6500.0)]
    return ev


def test_idle_gaps_carry_the_innermost_program_stage():
    ev = _events()
    stages = [("rpc", 7600.0, 10400.0, "t1"),            # outer, thread 1
              ("respond_stage", 8500.0, 9500.0, "t2"),  # thread 2
              ("serve", 8000.0, 9800.0, "t2"),          # outer on thread 2
              ("device_put", 4500.0, 5600.0, "t3"),
              ("arena_stage", 1200.0, 1900.0, "t4")]
    labels = cs.gap_labels(ev, stages)
    # idle: [1000,2000] [4000,6000] [7000,11000]; middles 1500, 5000, 9000
    assert labels[0] == ("pull/respond_stage+rpc", pytest.approx(4000e-9))
    assert labels[1] == ("grad+push/device_put", pytest.approx(2000e-9))
    assert labels[2] == ("push/arena_stage", pytest.approx(1000e-9))
    # every label devtrace gives stays the prefix
    plain = devtrace.reduce(ev).idle_gaps
    assert [lab.split("/")[0] for lab, _ in labels] == [
        lab for lab, _ in plain]
    # host<->HBM stages cover [1200,1900] + [4500,5600] (respond_stage
    # [8500,9500]) of the 7000 ns idle
    share = cs.idle_in_copy_share(ev, stages)
    assert share == pytest.approx(100 * (700 + 1100 + 1000) / 7000)
    assert cs.idle_in_copy_share(ev, []) == 0.0


def test_without_program_stages_the_labels_are_devtraces():
    ev = _events()
    assert cs.gap_labels(ev, []) == devtrace.reduce(ev).idle_gaps


def test_the_recorded_chip_slice_reduces_as_before():
    """The recorded v5e slice has no brpc.* events: devtrace's numbers and
    labels stand, and the split's idle intervals add up to its idle time."""
    ev = devtrace.extract(FIXTURE)
    red = devtrace.reduce(ev)
    assert red.busy_s == pytest.approx(0.000804144)
    assert len(red.op_s) == 17
    assert sum(red.op_s.values()) == pytest.approx(0.000804144)
    assert red.idle_gaps == [
        (lab, pytest.approx(s)) for lab, s in (
            ("push", 0.00360152), ("grad+push", 0.002439348),
            ("push", 0.002020283), ("grad+push", 0.001911641),
            ("grad+push", 0.00160535), ("push", 0.001524111),
            ("push", 0.001048866), ("grad+push", 0.00101156),
            ("push", 0.001003818), ("push", 0.000979534))]
    assert cs.stage_events(FIXTURE) == []
    assert cs.gap_labels(ev, []) == red.idle_gaps
    idle = sum(b - a for a, b in cs.idle_intervals(ev)) * 1e-9
    assert idle == pytest.approx(red.window_s - red.busy_s)


def test_clock_offset_recovers_a_constant_shift():
    """Four closed-loop workers of back-to-back calls, each root span
    inside its annotation, on clocks that differ by a large constant."""
    rng = np.random.default_rng(7)
    offset_us = 1.76e15
    roots, anns = [], []
    for _ in range(4):
        t = rng.uniform(0, 5000)
        while t < 4e6:
            length = rng.uniform(500, 9000)
            lead = 6 + rng.normal(0, 1)
            anns.append((t * 1e3, (t + lead + length + 8) * 1e3))
            roots.append((t + lead + offset_us,
                          t + lead + length + offset_us))
            t += length + 20
    # roots whose annotations fell outside the profile's slice
    extra = [(offset_us + 4e6 + 10 * k, offset_us + 4e6 + 10 * k + 700)
             for k in range(3000)]
    got = cs.clock_offset(roots + extra, anns)
    assert got["offset_us"] == pytest.approx(offset_us + 6, abs=1)
    assert got["matched"] >= 0.99 * len(anns)
    assert got["residual_q1_us"] <= 0 <= got["residual_q3_us"]
    assert got["residual_q3_us"] - got["residual_q1_us"] < 3
    assert cs.clock_offset([], anns) is None
