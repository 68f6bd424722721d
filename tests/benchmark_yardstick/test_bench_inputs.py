"""What the accepted per-layer readers read, pinned: the traced run's
constants, the fields of ``Run`` they take, and each reader's value on one
fixed set of spans and one reduced trace (CPU only)."""

import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import devtrace, harness, spec  # noqa: E402


def test_the_traced_runs_inputs_are_unchanged():
    assert harness.RPCZ_SPANS == 1 << 16
    assert (harness.TRACE_LEAD_S, harness.TRACE_SLICE_S) == (1.0, 4.0)
    assert [f.name for f in dataclasses.fields(harness.Run)] == [
        "cell", "nbytes", "calls", "window_s", "setup_s", "peaks", "trace",
        "spans", "window_compiles", "extra"]


SPANS = [
    {"service_method": "bench.pull/0",
     "annotations": ["gil_return=30us", "rpc=2000us", "device_put=500us"]},
    {"service_method": "bench.pull/1",
     "annotations": ["rpc=4000us", "device_put=1500us"]},
    {"service_method": "bench.push/1",
     "annotations": ["arena_stage=2000us", "gil_return=10us", "rpc=3000us"]},
    # the program's other legs are not the harness's roots: not read
    {"service_method": "ParamService/Pull", "server_side": True,
     "annotations": ["pool_wait=5us", "handler=100us", "rpc=9999us"]},
]
NBYTES = [1_000_000, 3_000_000]


def _run():
    ev = devtrace.Events(slice=(0.0, 1e6))
    ev.modules = [("jit_fused_momentum_update(1)", 1000.0, 201000.0)]
    ev.ops = [("%fused_momentum_update.1 = (f32[1024,4096]) custom-call("
               "f32[1024,4096] %p, f32[1024,4096] %m, f32[1024,4096] %g)",
               1000.0, 201000.0)]
    calls = np.zeros((1, 5))
    calls[0, 4] = 1.0
    return harness.Run(cell=None, nbytes=NBYTES, calls=calls, window_s=1.0,
                       setup_s=1.0, peaks={"hbm_bytes_per_s": 819e9},
                       trace=devtrace.reduce(ev), spans=SPANS)


@pytest.mark.parametrize("name,want", [
    ("h2d_GBps.pull", 4_000_000 / 2000e-6 / 1e9),
    ("push_stage_GBps", 3_000_000 / 2000e-6 / 1e9),
    ("rpc_ms.mean", 3.0),
    ("device_idle_share", 80.0),
    ("fused_update_roofline",
     100 * (5 * 4 * 1024 * 4096 / 819e9) / 200000e-9),
])
def test_each_accepted_reader_reads_the_same_value(name, want):
    assert spec.load_reader(name)(_run()) == pytest.approx(want)
