"""The benchmark's yardstick (benchmark/): its files, its arithmetic and its
trace reduction, on the CPU. No TPU and no libtpu topology is touched."""

import json
import math
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import devtrace, reference, spec, stats  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
BENCH = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))


# ---- the configurations are the published parameter sets ----

def bert_tensors(c):
    h, ff = c["hidden_size"], c["intermediate_size"]
    t = [[c["vocab_size"], h], [c["max_position_embeddings"], h],
         [c["type_vocab_size"], h], [h], [h]]
    for _ in range(c["num_hidden_layers"]):
        t += [[h, h], [h]] * 4 + [[h], [h], [ff, h], [ff], [h, ff], [h],
                                  [h], [h]]
    return t + [[h, h], [h]]


def resnet_tensors(c):
    k = c["stem_kernel"]
    t = [[c["stem_channels"], 3 * k * k], [c["stem_channels"]],
         [c["stem_channels"]]]
    cin = c["stem_channels"]
    for n, w in zip(c["blocks"], c["widths"]):
        out = w * c["expansion"]
        for b in range(n):
            t += [[w, cin], [w], [w], [w, w * 9], [w], [w], [out, w], [out],
                  [out]]
            if b == 0:
                t += [[out, cin], [out], [out]]
            cin = out
    return t + [[c["num_classes"], cin], [c["num_classes"]]]


@pytest.mark.parametrize("name,build", [("bert_large", bert_tensors),
                                        ("resnet50", resnet_tensors)])
def test_config_tensors_follow_the_architecture(name, build):
    cfg = spec.load_json(os.path.join(ROOT, "benchmark", "configs",
                                      name + ".json"))
    shapes = [list(s) for _, s in cfg["tensors"]]
    assert shapes == build(cfg)
    assert len(shapes) == cfg["n_tensors"]
    assert sum(math.prod(s) for s in shapes) == cfg["n_parameters"]
    assert len({n for n, _ in cfg["tensors"]}) == len(shapes)
    assert cfg["reduced"] == [] and cfg["dtype"] == "float32"


def test_config_size_classes_match_the_issue():
    bert = spec.load_json(os.path.join(ROOT, "benchmark", "configs",
                                       "bert_large.json"))
    sizes = [4 * math.prod(s) for _, s in bert["tensors"]]
    assert sizes.count(4 << 20) == 97 and sizes.count(16 << 20) == 48
    assert sum(len(s) == 1 for _, s in bert["tensors"]) == 243
    assert max(sizes) == 30522 * 1024 * 4
    res = spec.load_json(os.path.join(ROOT, "benchmark", "configs",
                                      "resnet50.json"))
    vectors = [s[0] for _, s in res["tensors"] if len(s) == 1]
    assert len(vectors) == 106 + 1 and max(vectors[:-1]) == 2048


# ---- BENCHMARK.json and the files it names ----

def check_rules(bench, root):
    """The rules every BENCHMARK.json of this harness keeps: each piece a
    file found by name, 1 or 4 chips a cell (at most half the cells on 4,
    but one always may), every per-layer metric listing the cells it reads
    in, every mix naming a driver file that exists, or none."""
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(root, p))
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(root, c["file"]))
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(cells)) == len(cells)
    for w in bench["workloads"]:
        mix = spec.load_json(os.path.join(
            root, "benchmark", "traffic", w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(
            root, "benchmark", "drivers",
            mix.get("driver", spec.DEFAULT_DRIVER) + ".py"))
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(cells) // 2)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.isfile(os.path.join(root, "benchmark", "metrics",
                                           m["name"] + ".py"))
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["workloads"] and set(m["workloads"]) <= set(cells)
    names = {m["name"] for m in bench["end_to_end"]}
    assert {m["moves"] for m in bench["per_layer"]} <= names


def test_benchmark_json_names_files_that_exist():
    check_rules(BENCH, ROOT)


def test_full_check_fits_its_time_limit():
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def own_metrics(bench, cell):
    """The end-to-end metrics that list no cells or list ``cell``, and the
    per-layer metrics whose lists name it."""
    return ({m["name"] for m in bench["end_to_end"]
             if cell in m.get("workloads", [cell])},
            {m["name"] for m in bench["per_layer"] if cell in m["workloads"]})


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_with_all_its_metrics(cell):
    c = spec.load_cell(cell)
    e2e, per = own_metrics(BENCH, cell)
    assert e2e == {"tensor_GBps", "call_p50_ms", "call_p99_ms", "setup_s"}
    assert {m["name"] for m in c.end_to_end} == e2e
    assert {m["name"] for m in c.per_layer} == per
    if c.traffic["name"] == "sync":
        assert len(per) == 5
    assert set(c.readers) == {m["name"] for m in c.end_to_end + c.per_layer}
    assert c.driver.__name__.endswith(spec.DEFAULT_DRIVER)


def test_a_cell_is_added_by_files_and_entries_alone(tmp_path):
    """A new mix and a new cell: one new file, one new entry, and no
    existing file edited."""
    root = tmp_path / "tree"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    before = {p: (root / "benchmark" / p).read_bytes()
              for p in ("traffic/sync.json", "spec.py", "traffic.py")}
    mix = json.loads((root / "benchmark" / "traffic" / "sync.json")
                     .read_text())
    mix.update(name="pull", step=["pull"])
    (root / "benchmark" / "traffic" / "pull.json").write_text(
        json.dumps(mix))
    bench["workloads"].append({"name": "bert_large.pull",
                               "config": "bert_large", "traffic": "pull",
                               "chips": 1, "why": "pulls only"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("bert_large.pull", root=str(root))
    assert cell.traffic["step"] == ["pull"]
    assert cell.config["name"] == "bert_large"
    assert {p: (root / "benchmark" / p).read_bytes()
            for p in before} == before


# A cell that brings its own driver, mix keys, check, chip count and
# per-layer metric, each as a new file: seeded rows summed on every chip of
# the cell, compared with numpy's sum.
TOY_DRIVER = '''\
"""Seeded rows, summed again and again on each chip of the cell."""
import time

import numpy as np

from benchmark import harness

KEYS = {"rows"}


def check_mix(mix):
    if int(mix.get("rows", 0)) < 1:
        raise ValueError("toy: needs rows >= 1")


def run(cell, args, session, rehearse=False, control=False):
    import jax
    import jax.numpy as jnp

    rows = int(cell.traffic["rows"])
    host = np.random.default_rng(args.seed).standard_normal(
        (rows, 128)).astype(np.float32)
    xs = [jax.device_put(host, d) for d in session.devices]
    session.mark("data")
    total = jax.jit(jnp.sum)
    for x in xs:
        total(x).block_until_ready()
    session.mark("warm_up")
    calls, sums = [], {}

    def body(seconds, during):
        t_open = time.perf_counter()
        while time.perf_counter() < t_open + seconds:
            for i, x in enumerate(xs):
                t0 = time.perf_counter()
                sums[i] = float(total(x))
                calls.append((0, i, 0, t0, time.perf_counter()))
        return t_open, time.perf_counter()

    session.window(body, describe=f"{len(xs)} chips, {rows} rows",
                   detail=lambda: f"{len(calls)} sums")
    want = float(host.astype(np.float64).sum())
    gap = max(abs(s - want) for s in sums.values())
    limit = float(cell.config["limits"]["sum_gap"])
    return harness.Outcome(
        nbytes=[host.nbytes] * len(xs),
        calls=np.array(calls, np.float64).reshape(-1, 5),
        window_s=session.window_s, failed=0, errors=[],
        checks={"sum_gap": {"value": gap, "limit": limit}},
        readings={"sum_gap": gap}, extra={"sums": len(calls)})
'''

TOY_READER = '''\
"""Sums completed per second of the window (the driver's own count)."""


def read(run):
    n = run.extra.get("sums")
    return n / run.window_s if n and run.window_s > 0 else None
'''


def test_a_cell_with_its_own_driver_is_added_by_files_alone(tmp_path):
    """A driver, its mix, a configuration, a 4-chip cell and a per-layer
    metric of its own: new files and entries only, loaded with exactly its
    metrics and rehearsed to a correct line on the CPU."""
    import argparse
    import time

    from benchmark import harness

    root = tmp_path / "tree"
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), root / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    files = {
        "drivers/toy_sum.py": TOY_DRIVER,
        "traffic/toy.json": json.dumps({"name": "toy", "driver": "toy_sum",
                                        "rows": 64, "why": "a toy"}),
        "configs/toy.json": json.dumps({"name": "toy",
                                        "limits": {"sum_gap": 0.01}}),
        "metrics/toy_sums_per_s.py": TOY_READER,
    }
    for rel, text in files.items():
        assert not (root / "benchmark" / rel).exists()
        (root / "benchmark" / rel).write_text(text)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "toy", "source": "a test",
                             "file": "benchmark/configs/toy.json",
                             "reduced": [], "why": "a toy"})
    bench["workloads"].append({"name": "toy.sum", "config": "toy",
                               "traffic": "toy", "chips": 4,
                               "why": "sums on four chips"})
    bench["per_layer"].append({"name": "toy_sums_per_s", "unit": "1/s",
                               "better": "higher", "source": "program_counter",
                               "layer": "toy", "moves": "tensor_GBps",
                               "workloads": ["toy.sum"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert {p: p.read_bytes() for p in before} == before
    check_rules(bench, str(root))

    cell = spec.load_cell("toy.sum", root=str(root))
    e2e, per = own_metrics(bench, "toy.sum")
    assert per == {"toy_sums_per_s"} and cell.chips == 4
    assert {m["name"] for m in cell.end_to_end} == e2e
    assert {m["name"] for m in cell.per_layer} == per

    args = argparse.Namespace(workload="toy.sum", seed=3_000_000_019,
                              seconds=0.5, trace=0)
    line, run, readings = harness.execute(args, time.time(), rehearse=True,
                                          root=str(root))
    assert line["correct"] is True
    assert list(line["checks"]) == ["sum_gap"] and list(line)[-1] == "checks"
    assert line["checks"]["sum_gap"]["value"] <= 0.01
    assert line["checks"]["sum_gap"]["limit"] == 0.01
    assert line["device"]["count"] == 4 and run.extra["sums"] > 0
    assert len(run.nbytes) == 4  # the harness handed the driver four chips
    assert cell.readers["toy_sums_per_s"](run) == pytest.approx(
        run.extra["sums"] / run.window_s)

    for mix in ({"name": "x", "driver": "nonesuch"},
                {"name": "x", "driver": "../drivers/toy_sum"},
                {"name": "x", "driver": "toy_sum", "rows": 4, "rate_qps": 5},
                {"name": "x", "driver": "toy_sum", "rows": 0}):
        with pytest.raises(ValueError):
            spec.check_traffic(mix, root=str(root))


def test_a_mix_the_generator_cannot_run_is_refused():
    with pytest.raises(ValueError):
        spec.check_traffic({"name": "x", "loop": "open", "workers": 1,
                            "step": ["pull"]})
    with pytest.raises(ValueError):
        spec.check_traffic({"name": "x", "loop": "closed", "workers": 1,
                            "step": ["pull"], "rate_qps": 5})


def test_peaks_are_sourced_and_unknown_kinds_fail():
    p = spec.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in spec.load_json(
        os.path.join(ROOT, "benchmark", "peaks.json"))["source"]
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


# ---- metric arithmetic ----

def _calls(durations_ms, tensors):
    t0 = np.arange(len(durations_ms), dtype=np.float64)
    return np.stack([np.zeros_like(t0), np.asarray(tensors, np.float64),
                     np.zeros_like(t0), t0,
                     t0 + np.asarray(durations_ms) / 1e3], axis=1)


def test_percentiles_are_over_all_calls():
    calls = _calls(list(range(1, 101)), [0] * 100)
    lat = stats.latencies_ms(calls)
    assert stats.percentile(lat, 50) == pytest.approx(50.5)
    assert stats.percentile(lat, 99) == pytest.approx(99.01)
    assert stats.percentile([], 50) is None


def test_logical_bytes_and_rate():
    calls = _calls([1.0] * 4, [0, 1, 1, 2])
    nbytes = [4, 400, 4 << 20]
    assert stats.logical_bytes(calls, nbytes) == 4 + 800 + (4 << 20)
    from benchmark.harness import Run
    run = Run(cell=None, nbytes=nbytes, calls=calls, window_s=2.0,
              setup_s=1.0)
    read = spec.load_reader("tensor_GBps")
    assert read(run) == pytest.approx((4 + 800 + (4 << 20)) / 2 / 1e9)


def test_update_bytes_and_roofline_share():
    # A 1024 x 4096 fp32 update reads p, m, g and writes p', m'.
    assert stats.update_bytes(1024 * 4096) == 5 * 16 * 2 ** 20
    need = stats.update_bytes(1024 * 4096)
    least = need / 819e9
    assert stats.roofline_share(need, 4 * least, 819e9) == pytest.approx(25)
    assert stats.roofline_share(need, 0.0, 819e9) is None


def test_stage_rates_read_the_programs_annotations():
    spans = [{"service_method": "bench.pull/1",
              "annotations": ["rpc=300us", "device_put=500us"]},
             {"service_method": "bench.pull/0",
              "annotations": ["rpc=100us", "device_put=100us"]},
             {"service_method": "bench.push/1",
              "annotations": ["arena_stage=1000us", "rpc=700us"]},
             {"service_method": "ParamService/Pull", "annotations": []}]
    nbytes = [1000, 1_000_000]
    assert stats.stage_rate_GBps(spans, "pull", "device_put", nbytes) == \
        pytest.approx(1_001_000 / 600e-6 / 1e9)
    assert stats.stage_rate_GBps(spans, "push", "arena_stage", nbytes) == \
        pytest.approx(1.0)
    from benchmark.harness import Run
    run = Run(cell=None, nbytes=nbytes, calls=_calls([1], [0]),
              window_s=1.0, setup_s=1.0, spans=spans)
    assert spec.load_reader("rpc_ms.mean")(run) == pytest.approx(
        (300 + 100 + 700) / 3 / 1e3)
    assert stats.stage_rate_GBps([], "pull", "device_put", nbytes) is None


# ---- trace reduction ----

UPDATE = "jit_fused_momentum_update"
# Op events of a TPU trace are named by their HLO text (as recorded).
PAD = ("%pad.0 = f32[8,1024]{1,0:T(8,128)} pad(f32[1,1024]{1,0:T(1,128)} "
       "%bitcast.5, f32[]{:T(128)} %constant), padding=0_7x0_0")
KERNEL = ("%fused_momentum_update.1 = (f32[1024,4096]{1,0:T(8,128)}, "
          "f32[1024,4096]{1,0:T(8,128)}) custom-call(f32[1024,4096]"
          "{1,0:T(8,128)} %p.1, f32[1024,4096]{1,0:T(8,128)} %m.1, "
          "f32[1024,4096]{1,0:T(8,128)} %g.1), "
          "custom_call_target=\"tpu_custom_call\"")


def test_hlo_text_gives_op_names_and_update_sizes():
    assert devtrace.op_name(PAD) == "pad.0"
    assert devtrace.op_name(KERNEL) == "fused_momentum_update.1"
    assert devtrace.module_name(UPDATE + "(8273271)") == UPDATE
    # padded: the pad's input is the tensor; aligned: the kernel's operand
    assert devtrace.update_elements([PAD, KERNEL]) == 1024
    assert devtrace.update_elements([KERNEL]) == 1024 * 4096
    assert devtrace.update_elements(["%copy-done = f32[4] copy-done()"]) \
        is None


def _events():
    ev = devtrace.Events(slice=(1000.0, 11000.0))
    ev.modules = [(UPDATE + "(1)", 2000.0, 4000.0),
                  ("jit_other(2)", 6000.0, 7000.0),
                  ("jit_other(2)", 0.0, 1500.0)]
    ev.ops = [(KERNEL, 2000.0, 3500.0),   # in the update
              (PAD, 3500.0, 4000.0),      # in the update
              ("%fusion.2 = f32[4] fusion()", 6000.0, 7000.0),
              ("%early = f32[4] fusion()", 0.0, 1500.0)]  # clipped at 1000
    ev.host = [("push", 1500.0, 5000.0), ("pull", 7500.0, 10500.0),
               ("grad", 4800.0, 6500.0)]
    return ev


def test_reduce_unions_clips_and_names_idle_gaps():
    red = devtrace.reduce(_events())
    assert red.window_s == pytest.approx(10000e-9)
    # busy: [1000,1500] + [2000,4000] + [6000,7000] = 3500 ns
    assert red.busy_s == pytest.approx(3500e-9)
    assert red.module_s[UPDATE] == pytest.approx(2000e-9)
    # only runs wholly inside the slice count as executions
    assert [e.module for e in red.executions] == [UPDATE, "jit_other"]
    upd = red.executions[0]
    assert upd.op_s == pytest.approx(2000e-9) and len(upd.ops) == 2
    # gaps: [7000,11000] (pull at its middle), [4000,6000] (grad+push at
    # 5000), [1500,2000] (push at 1750)
    assert red.idle_gaps[0] == ("pull", pytest.approx(4000e-9))
    assert red.idle_gaps[1] == ("grad+push", pytest.approx(2000e-9))
    assert red.idle_gaps[2] == ("push", pytest.approx(500e-9))
    b = devtrace.breakdown(red)
    assert b["device_ops"][0][0] == UPDATE + "/fused_momentum_update.1"
    assert len(b["idle_gaps"]) == 3


def test_reduce_without_a_device_or_slice_is_none():
    ev = _events()
    ev.ops = []
    assert devtrace.reduce(ev) is None
    ev = _events()
    ev.slice = None
    assert devtrace.reduce(ev) is None


def _run(**kw):
    from benchmark.harness import Run
    base = dict(cell=None, nbytes=[4], calls=_calls([1], [0]), window_s=1.0,
                setup_s=1.0, peaks={"hbm_bytes_per_s": 819e9})
    base.update(kw)
    return Run(**base)


def test_idle_share_and_roofline_readers():
    red = devtrace.reduce(_events())
    run = _run(trace=red)
    assert spec.load_reader("device_idle_share")(run) == pytest.approx(65.0)
    # one update of 1024 elements (the pad's input) in 2000 ns of ops
    want = 100 * (5 * 4 * 1024 / 819e9) / 2000e-9
    assert spec.load_reader("fused_update_roofline")(run) == \
        pytest.approx(want)
    run.trace = None
    assert spec.load_reader("device_idle_share")(run) is None
    assert spec.load_reader("fused_update_roofline")(run) is None


def test_a_recorded_chip_trace_reduces():
    """25 ms cut from the traced slice of resnet50.sync on a v5e (my chip
    run, PR 22): the device plane's modules and ops, the host's call
    annotations and the slice marker, as the profiler wrote them."""
    ev = devtrace.extract(os.path.join(FIXTURES,
                                       "resnet50_sync_slice.xplane.pb"))
    assert (len(ev.ops), len(ev.modules), len(ev.host)) == (113, 24, 28)
    red = devtrace.reduce(ev)
    assert red.window_s == pytest.approx(0.025)
    assert red.busy_s == pytest.approx(0.000804144)
    updates = [e for e in red.executions if e.module == UPDATE]
    assert sorted(devtrace.update_elements(e.ops) for e in updates) == [
        64, 64, 1024, 1024, 2048, 2048, 2048, 2048, 4096, 262144, 1048576,
        2048000]
    share = spec.load_reader("fused_update_roofline")(_run(trace=red))
    assert share == pytest.approx(10.8688, abs=1e-3)
    assert 0 < share <= 100
    assert red.idle_gaps[0][0] == "push"


# ---- the plain reference ----

def test_worst_gap_uses_the_median_floor():
    pairs = [("a", 1e-7, 1.0), ("b", 1e-7, 1.0), ("zero", 1e-9, 0.0)]
    g, where = reference.worst(pairs)
    assert where in ("a", "b") and g == pytest.approx(1e-7)
    g, where = reference.worst([("z", 1.0, 0.0), ("a", 0.0, 2.0),
                                ("b", 0.0, 2.0)])
    assert (g, where) == (pytest.approx(0.5), "z")


def test_trajectory_matches_a_plain_loop():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    p0 = rng.standard_normal((3, 5)).astype(np.float32)
    base = rng.standard_normal((3, 5)).astype(np.float32)
    scales = [0.5, 1.25, 0.75]
    at, p_last, m_last = reference.trajectory(
        jnp.asarray(p0), jnp.asarray(base), scales, [0, 2], 0.01, 0.9,
        jnp.float32)
    p, m, want = p0.copy(), np.zeros_like(p0), {0: p0.copy()}
    for i, s in enumerate(scales, 1):
        m = np.float32(0.9) * m + base * np.float32(s)
        p = p - np.float32(0.01) * m
        want[i] = p.copy()
    n = p0.size
    np.testing.assert_allclose(np.asarray(at[0])[:n], want[0].ravel())
    np.testing.assert_allclose(np.asarray(at[2])[:n], want[2].ravel(),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p_last)[:n], p.ravel(), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(m_last)[:n], m.ravel(), rtol=1e-6)
    assert not np.asarray(p_last)[n:].any()


def test_seeds_span_64_bits_and_repeat():
    from benchmark import data

    assert list(data.key_data(2 ** 33 + 5)) == [2, 5]
    with pytest.raises(ValueError):
        data.key_data(-1)
    gen = data.generator([(4, 3), (7,)])
    a = data.make(gen, 3_000_000_001, data.PARAMS, 0.02)
    b = data.make(gen, 3_000_000_001, data.PARAMS, 0.02)
    c = data.make(gen, 3_000_000_001, data.GRADS, 0.02)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
