"""Whole runs of the benchmark on the CPU at a tiny size (its rehearsal):
the runs that must pass, the runs that must fail, and what ``correct``
must refuse. No TPU and no libtpu topology is touched.

The rehearsal drives everything a chip run does but the look for a chip:
seeded data, ParameterServer over tpu://, 4 closed-loop workers, warm-up,
window, handoff of the final state and the plain reference. It prints no
metric. The control (the plain reference replayed in bfloat16, the
precision below the configurations' fp32) is read by the same comparison,
and each fault a one-chip cell can have is planted under a whole run. A
one-chip cell has no exchange between chips to leave out and no batch
mean to take over half a batch.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from conftest import require_native_lib  # noqa: E402

CELLS = ("bert_large.sync", "resnet50.sync")
SEED = 3_000_000_017  # above 2**31, as the driver's seeds are


def _execute(cell, seed=SEED, seconds=1.0, control=False, root=ROOT):
    require_native_lib()
    from benchmark import harness

    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0)
    out = harness.execute(args, time.time(), rehearse=True, control=control,
                          root=str(root))
    assert not isinstance(out, int)
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_the_cell_correctly(cell):
    from benchmark import traffic

    # long enough that every worker reaches its pushes on a loaded host
    line, run, readings = _execute(cell, seconds=2.0)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] == len(run.calls) > 100
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert list(line)[-1] == "checks"
    assert readings["pulls_compared"] > 0
    assert run.window_compiles == 0  # warm-up left nothing to compile
    # every kind of the mix's step in the window, and no other
    kinds = set(run.calls[:, 0].astype(int))
    assert kinds == {traffic.KINDS[op] for op in run.cell.traffic["step"]}
    # The host-clock readers read the rehearsal (it just never prints them).
    for name in ("tensor_GBps", "call_p50_ms", "call_p99_ms", "setup_s"):
        assert run.cell.readers[name](run) > 0


def test_a_shape_left_cold_compiles_inside_the_window(monkeypatch):
    """The window's compile watch sees what warm-up missed."""
    from benchmark import data, traffic

    monkeypatch.setattr(traffic, "warm_up", lambda worker, shapes: None)
    data.scaler.cache_clear()  # a fresh program, traced on first use
    try:
        _, run, _ = _execute("resnet50.sync", seed=SEED + 1)
    finally:
        data.scaler.cache_clear()
    assert run.window_compiles > 0


def _run_cli(cwd, *extra, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "resnet50.sync",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def _result_lines(stdout):
    out = []
    for ln in stdout.splitlines():
        try:
            out.append(json.loads(ln))
        except ValueError:
            pass
    return out


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    require_native_lib()
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert _result_lines(p.stdout) == []
    assert "no TPU" in p.stderr


def test_without_the_program_the_run_fails(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's
    paths cannot run a cell."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(str(tmp_path), timeout=120)
    assert p.returncode != 0
    assert _result_lines(p.stdout) == []


# ---- what correct must refuse ----

@pytest.mark.parametrize("cell", CELLS)
def test_control_is_refused_and_the_program_passes(cell):
    """The bfloat16 replay in the program's place gets correct=false from
    the harness's own verdict; the program reads far below both limits
    and passes (tiny size, CPU)."""
    from benchmark import check

    line, run, r = _execute(cell, control=True)
    limits = run.cell.config["limits"]
    assert line["correct"] is True
    assert r["pull_gap"] <= limits["pull_gap"] / 100
    assert r["state_gap"] <= limits["state_gap"] / 100
    control = check.control_checks(r, line["failed"], limits)
    assert check.passed(control) is False
    assert (control["pull_gap"]["value"] > limits["pull_gap"]
            or control["state_gap"]["value"] > limits["state_gap"])


def test_calibrate_records_the_control_refused(tmp_path):
    """calibrate.py starts as run.py does (its bootstrap) and records, per
    seed, the program's verdict and the control's: true and false."""
    require_native_lib()
    out = tmp_path / "calib.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/calibrate.py", "--workload",
         "resnet50.sync", "--seeds", f"{SEED},{SEED + 2}", "--seconds", "1",
         "--rehearse", "--out", str(out)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(out.read_text())
    assert [r["seed"] for r in result["rows"]] == [SEED, SEED + 2]
    assert all(r["correct"] and not r["control_correct"]
               for r in result["rows"])
    assert result["summary"]["all_correct"] is True
    assert result["summary"]["any_control_correct"] is False
    assert result["summary"]["min_control_pull_gap"] > \
        result["summary"]["max_pull_gap"]


def _stale(orig):
    """A push acknowledged (version bumped) whose state is left as it was."""
    def apply(self, name, att, tracing):
        with self._mu:
            p, m = self._params[name], self._momenta[name]
        v = orig(self, name, att, tracing)
        with self._mu:
            self._params[name], self._momenta[name] = p, m
        return v
    return apply


def _twice(orig):
    """A push applied twice under one version."""
    def apply(self, name, att, tracing):
        v = orig(self, name, att, tracing)
        orig(self, name, att, tracing)
        with self._mu:
            self._version[name] = v
        return v
    return apply


def _skipped(orig):
    """A version number skipped: the acknowledged order has a hole."""
    def apply(self, name, att, tracing):
        v = orig(self, name, att, tracing)
        if v == 2:
            with self._mu:
                self._version[name] += 1
            return v + 1
        return v
    return apply


@pytest.mark.parametrize("fault,caught_by", [
    (_stale, "state_gap"), (_twice, "state_gap"),
    (_skipped, "version_faults")])
def test_faults_in_the_push_path_make_correct_false(monkeypatch, fault,
                                                    caught_by):
    from brpc_tpu.runtime.param_server import ParameterServer

    monkeypatch.setattr(ParameterServer, "_apply_update",
                        fault(ParameterServer._apply_update))
    line, _, _ = _execute("resnet50.sync")
    assert line["correct"] is False
    c = line["checks"][caught_by]
    assert c["value"] > c["limit"]


def _alter_pull_answers(monkeypatch):
    """Every pull answer altered where the server produces it."""
    from brpc_tpu.runtime.param_server import ParameterServer

    orig = ParameterServer._encode_pull

    def altered(self, name, p, version, want):
        out = np.array(orig(self, name, p, version, want))
        out.reshape(-1)[-1] += 0.5
        return out

    monkeypatch.setattr(ParameterServer, "_encode_pull", altered)


def test_an_altered_pull_answer_makes_correct_false(monkeypatch):
    _alter_pull_answers(monkeypatch)
    line, _, _ = _execute("resnet50.sync")
    assert line["correct"] is False
    assert line["checks"]["pull_gap"]["value"] > \
        line["checks"]["pull_gap"]["limit"]


def _pull_only_tree(tmp_path):
    """A copy of the benchmark with one more cell, ``resnet50.pull``: the
    sync mix with its step cut to pulls."""
    root = tmp_path / "tree"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    mix = json.loads((root / "benchmark" / "traffic" / "sync.json")
                     .read_text())
    mix.update(name="pull", step=["pull"])
    (root / "benchmark" / "traffic" / "pull.json").write_text(
        json.dumps(mix))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["workloads"].append({"name": "resnet50.pull", "config": "resnet50",
                               "traffic": "pull", "chips": 1,
                               "why": "pulls only"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_pull_only_step_warms_up_and_runs_pulls_alone(monkeypatch,
                                                         tmp_path):
    """Warm-up makes only the calls of the mix's step: a step of pulls
    pushes nothing, before the window or in it, and compiles nothing in
    the window; the check finds the seeded state."""
    from brpc_tpu.runtime.param_server import ParameterServer

    from benchmark import traffic

    applied = []
    orig = ParameterServer._apply_update

    def counted(self, name, att, tracing):
        applied.append(name)
        return orig(self, name, att, tracing)

    monkeypatch.setattr(ParameterServer, "_apply_update", counted)
    line, run, readings = _execute("resnet50.pull", seconds=2.0,
                                   root=_pull_only_tree(tmp_path))
    assert line["correct"] is True, line["checks"]
    assert applied == []
    assert set(run.calls[:, 0].astype(int)) == {traffic.PULL}
    assert run.window_compiles == 0
    assert readings["pulls_compared"] > 0
    assert readings["pull_gap"] == readings["state_gap"] == 0


def test_an_altered_pull_answer_fails_a_pull_only_step(monkeypatch,
                                                       tmp_path):
    """Nothing is pushed in a pull-only window, so a wrong pull answer is
    what its check has to catch."""
    _alter_pull_answers(monkeypatch)
    line, _, _ = _execute("resnet50.pull", root=_pull_only_tree(tmp_path))
    assert line["correct"] is False
    assert line["checks"]["pull_gap"]["value"] > \
        line["checks"]["pull_gap"]["limit"]
