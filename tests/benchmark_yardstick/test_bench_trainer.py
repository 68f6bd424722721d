"""The trainer cell (``moonlight_16b_a3b.train``) on the CPU at its tiny
size: the rehearsal that must pass, the control that must be refused, the
faults ``correct`` must catch, and each new reader's value on a fixed run.
No TPU and no libtpu topology is touched.

The rehearsal drives what a chip run does but the look for a chip: seeded
weights, a ParameterServer over tpu://, the OverlappedStepDriver over the
program's layered trainer, the two checked warm-up steps, the export after
them, the window, the final export, and the plain reference's two steps.
"""

import argparse
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from conftest import require_native_lib  # noqa: E402

from benchmark import devtrace, harness, spec  # noqa: E402

CELL = "moonlight_16b_a3b.train"
SEED = 3_000_000_019  # above 2**31, as the driver's seeds are


def _execute(seed=SEED, seconds=1.0, control=False, trace=0):
    require_native_lib()
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=trace)
    out = harness.execute(args, time.time(), rehearse=True, control=control)
    assert not isinstance(out, int)
    return out


def test_rehearsal_trains_correctly_with_nothing_compiled_in_the_window():
    from benchmark import traffic

    line, run, readings = _execute()
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and run.window_compiles == 0
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert list(line["checks"]) == [
        "failed_calls", "version_faults", "nonfinite_steps", "pull_mismatch",
        "loss_gap", "grad_gap", "state_gap", "route_flip_share"]
    steps = int(run.extra["steps"])
    assert steps >= 1 and run.extra["tokens"] == steps * 2 * 32
    # one pull and one push of every tensor per window step
    n = len(run.nbytes)
    assert len(run.calls) == 2 * n * steps
    kinds = run.calls[:, 0].astype(int)
    assert (kinds == traffic.PULL).sum() == (kinds == traffic.PUSH).sum()
    assert (run.calls[:, 4] >= run.calls[:, 3]).all()
    assert run.extra["routed_rows"] > 0 and run.extra["model_flops"] > 0
    assert 0 <= run.extra["exposed_s"] <= run.extra["wall_s"]
    assert sum(run.extra[f"expert_slots.{e}"] for e in range(4)) == \
        run.extra["routed_rows"] * steps * 2
    for name in ("tensor_GBps", "call_p50_ms", "call_p99_ms", "setup_s",
                 "train_tokens_per_s"):
        assert run.cell.readers[name](run) > 0
    assert 0 <= run.cell.readers["exposed_comm_share"](run) <= 100
    assert readings["losses"][0] > 0


def test_traced_calls_carry_the_wire_readers_spans():
    """In a traced run each pull and push of the trainer sits in its
    ``bench.<kind>/<tensor>`` span with the program's stages on it, so the
    accepted wire readers find something to read in this cell too."""
    line, run, _ = _execute(trace=1)
    assert line["correct"] is True, line["checks"]
    kinds = {s["service_method"].split("/")[0] for s in run.spans}
    assert kinds == {"bench.pull", "bench.push"}
    n = len(run.nbytes)
    pulls = [s for s in run.spans if s["service_method"].startswith(
        "bench.pull/")]
    assert len(pulls) >= n
    for name in ("h2d_GBps.pull", "push_stage_GBps", "rpc_ms.mean"):
        assert spec.load_reader(name)(run) > 0, name


def test_the_float8_control_is_refused():
    from benchmark import check

    line, run, readings = _execute(control=True)
    assert line["correct"] is True
    control = readings["control_checks"]
    assert check.passed(control) is False
    limits = run.cell.config["limits"]
    assert any(control[k]["value"] > limits[k]
               for k in ("loss_gap", "grad_gap", "state_gap",
                         "route_flip_share"))
    assert readings["control_grad_gap"] > readings["grad_gap"]


def _doubled_gradient(monkeypatch):
    from brpc_tpu.models import mla_moe

    orig = mla_moe.MlaMoeTrainer.backward

    def backward(self, ctx, name):
        g = orig(self, ctx, name)
        return 2 * g if name == "lm_head.weight" else g

    monkeypatch.setattr(mla_moe.MlaMoeTrainer, "backward", backward)


def _stale_update(monkeypatch):
    """Every push acknowledged while the state is left as it was."""
    from brpc_tpu.runtime.param_server import ParameterServer

    orig = ParameterServer._apply_update

    def apply(self, name, att, tracing):
        with self._mu:
            p, m = self._params[name], self._momenta[name]
        v = orig(self, name, att, tracing)
        with self._mu:
            self._params[name], self._momenta[name] = p, m
        return v

    monkeypatch.setattr(ParameterServer, "_apply_update", apply)


def _momentum_only(monkeypatch):
    """Every push's momentum written and acknowledged, its parameter
    left as it was: the write of p' = p - lr m' lost."""
    from brpc_tpu.runtime.param_server import ParameterServer

    orig = ParameterServer._apply_update

    def apply(self, name, att, tracing):
        with self._mu:
            p = self._params[name]
        v = orig(self, name, att, tracing)
        with self._mu:
            self._params[name] = p
        return v

    monkeypatch.setattr(ParameterServer, "_apply_update", apply)


def _skipped_version(monkeypatch):
    """A version number skipped: the acknowledged order has a hole."""
    from brpc_tpu.runtime.param_server import ParameterServer

    orig = ParameterServer._apply_update

    def apply(self, name, att, tracing):
        v = orig(self, name, att, tracing)
        if v == 3:
            with self._mu:
                self._version[name] += 1
            return v + 1
        return v

    monkeypatch.setattr(ParameterServer, "_apply_update", apply)


def _altered_pull(monkeypatch):
    """Every pull answer altered where the server produces it."""
    from brpc_tpu.runtime.param_server import ParameterServer

    orig = ParameterServer._encode_pull

    def altered(self, name, p, version, want):
        out = np.array(orig(self, name, p, version, want))
        out.reshape(-1)[-1] += 0.5
        return out

    monkeypatch.setattr(ParameterServer, "_encode_pull", altered)


@pytest.mark.parametrize("plant,caught_by", [
    (_doubled_gradient, "grad_gap"), (_stale_update, "state_gap"),
    (_momentum_only, "state_gap"), (_skipped_version, "version_faults"),
    (_altered_pull, "pull_mismatch")])
def test_a_planted_fault_makes_correct_false(monkeypatch, plant, caught_by):
    plant(monkeypatch)
    line, _, _ = _execute(seed=SEED + 2)
    assert line["correct"] is False
    c = line["checks"][caught_by]
    assert c["value"] > c["limit"], line["checks"]


# ---- the new readers on one fixed run ----

GROUPED = ("%ragged-dot-none.3 = f32[98304,2048]{1,0:T(8,128)} custom-call("
           "bf16[98304,1408]{1,0:T(8,128)(2,1)} %h, "
           "bf16[8,1408,2048]{2,1,0:T(8,128)(2,1)} %w), "
           "custom_call_target=\"tpu_custom_call\"")
WEIGHT_GRAD = ("%ragged-dot-none = f32[8,2048,1408]{2,1,0:T(8,128)} "
               "custom-call(bf16[98304,2048]{1,0} %x, bf16[98304,1408]{1,0}"
               " %d), custom_call_target=\"tpu_custom_call\"")
METADATA = ("%ragged-dot-metadata = (s32[9]{0}, s32[199]{0}) custom-call("
            "s32[8]{0} %sizes), custom_call_target=\"tpu_custom_call\"")


def _run():
    ev = devtrace.Events(slice=(0.0, 1e9))
    ev.modules = [("jit_grad(1)", 1000.0, 3_001_000.0)]
    ev.ops = [(METADATA, 1000.0, 2000.0),
              (GROUPED, 2000.0, 1_002_000.0),
              (WEIGHT_GRAD, 1_002_000.0, 3_001_000.0)]
    calls = np.zeros((1, 5))
    calls[0, 4] = 1.0
    extra = {"steps": 4.0, "tokens": 65536.0, "model_flops": 1.5e14,
             "wall_s": 10.0, "compute_s": 3.0, "exposed_s": 6.0,
             "routed_rows": 12288.0}
    return harness.Run(cell=None, nbytes=[4], calls=calls, window_s=8.0,
                       setup_s=1.0, peaks={"bf16_flops_per_s": 197e12},
                       trace=devtrace.reduce(ev), spans=[], extra=extra)


@pytest.mark.parametrize("name,want", [
    ("train_tokens_per_s", 65536 / 8.0),
    ("mfu.train", 100 * 1.5e14 / (8.0 * 197e12)),
    ("exposed_comm_share", 60.0),
    ("expert_matmul_roofline",
     100 * (2 * 2 * 12288 * 2048 * 1408 / 197e12) / (2_999_000e-9)),
])
def test_each_new_reader_reads_the_same_value(name, want):
    assert spec.load_reader(name)(_run()) == pytest.approx(want)


def test_new_readers_find_nothing_in_a_run_without_a_trainer():
    run = _run()
    run.extra, run.trace = {}, None
    for name in ("train_tokens_per_s", "mfu.train", "exposed_comm_share",
                 "expert_matmul_roofline"):
        assert spec.load_reader(name)(run) is None


def test_blocked_reference_matches_the_programs_plain_reference(monkeypatch):
    """The benchmark's reference, by query blocks, loss chunks and one layer
    at a time, gives the loss, gradients and routes of the program's plain
    reference (one softmax over all positions, one ``jax.grad``)."""
    import jax.numpy as jnp

    from benchmark import trainer_reference as R
    from brpc_tpu.models import mla_moe, mla_moe_reference

    c = spec.load_json(os.path.join(ROOT, "benchmark", "configs",
                                    "moonlight_16b_a3b.json"))
    c.update(c["tiny"])
    monkeypatch.setattr(R, "Q_BLOCK", 16)
    monkeypatch.setattr(R, "LOSS_CHUNK", 40)
    R._programs.cache_clear()
    try:
        m = R.Model.of(c)
        layout = R.layout(m)
        params = dict(zip([n for n, _ in layout], R.make_params(
            [s for _, s in layout], SEED, 0.02)))
        bias = R.make_bias(m, SEED, 0.02)
        ids, labels = R.tokens(SEED, 0, 2, 64, m.vocab)
        loss, grads, own = R.loss_and_grads(m, params, ids, labels, bias)
    finally:
        R._programs.cache_clear()
    want_loss, want, want_own = mla_moe_reference.loss_and_grads(
        mla_moe.Config.from_hf(c), params, ids, labels, bias)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert set(grads) == set(want)
    for n in want:
        scale = float(jnp.max(jnp.abs(want[n])))
        assert float(jnp.max(jnp.abs(grads[n] - want[n]))) <= 1e-5 * scale, n
    for a, b in zip(own, want_own):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_step_flops_count_the_published_block():
    """About 37 TFLOP a step at 2 x 8192 tokens with 3/4 of a slot a token
    routed here: 6 N T over 249.7M matrix parameters, the held experts'
    slots, and causal attention."""
    from benchmark import trainer_flops

    cfg = spec.load_json(os.path.join(ROOT, "benchmark", "configs",
                                      "moonlight_16b_a3b.json"))
    assert trainer_flops.dense_params_per_token(cfg) == 249_692_160
    slots = 16384 * 6 * 8 // 64 * 4
    total = trainer_flops.step_flops(cfg, 2, 8192, slots)
    assert total == (6 * 16384 * 249_692_160 + 6 * slots * 3 * 2048 * 1408
                     + 3 * 5 * 2 * (8192 * 8193 // 2) * 2 * 16 * 320)
    assert 37e12 < total < 38e12
