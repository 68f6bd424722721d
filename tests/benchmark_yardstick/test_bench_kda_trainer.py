"""The Kimi Linear trainer cell (``kimi_linear_48b_a3b.train_16k``) on the
CPU at its tiny size: the rehearsal that must pass, the control that must
be refused, the KDA fault ``correct`` must catch, the new reader's value
on a fixed run, the blocked reference against the program's plain one,
and the step's operation count. No TPU and no libtpu topology is touched.

The rehearsal drives what a chip run does but the look for a chip: seeded
weights and gates, a ParameterServer over tpu://, the OverlappedStepDriver
over the program's hybrid trainer, the two checked warm-up steps, the
export after them, the window, the final export, and the blocked
reference's two steps.
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

CELL = "kimi_linear_48b_a3b.train_16k"
SEED = 3_000_000_029  # above 2**31, as the driver's seeds are
CONFIG = os.path.join(ROOT, "benchmark", "configs", "kimi_linear_48b_a3b.json")


def _execute(seed=SEED, seconds=1.0, control=False):
    require_native_lib()
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=0)
    out = harness.execute(args, time.time(), rehearse=True, control=control)
    assert not isinstance(out, int)
    return out


def _kda_chunks_counted() -> int:
    """``trainer_kda_chunks`` on /vars (0 before the first trainer step)."""
    from brpc_tpu.observability import metrics

    for row in metrics.dump_vars("trainer_kda_chunks").splitlines():
        name, _, value = row.partition(":")
        if name.strip() == "trainer_kda_chunks":
            return int(value)
    return 0


def test_rehearsal_trains_correctly_with_nothing_compiled_in_the_window(
        monkeypatch):
    from brpc_tpu.models import mla_moe

    from benchmark import traffic

    require_native_lib()
    # the held experts in passes, as the cell's 16,384 tokens run them
    # (the tiny size's own plan is one pass): 6 divide the 36 tokens
    monkeypatch.setattr(mla_moe, "expert_passes", lambda cfg, tokens: 6)
    chunks0 = _kda_chunks_counted()
    line, run, readings = _execute()
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and run.window_compiles == 0
    assert line["metrics"] == {} and line["rehearsal"] is True
    steps = int(run.extra["steps"])
    seq_len = run.cell.config["tiny"]["seq_len"]
    assert steps >= 1 and run.extra["tokens"] == steps * seq_len
    # one pull and one push of every tensor per step: the published 193
    # less 3 for each of the 4 experts the tiny block holds fewer a layer
    assert len(run.nbytes) == 193 - 4 * 4 * 3
    assert len(run.calls) == 2 * len(run.nbytes) * steps
    kinds = run.calls[:, 0].astype(int)
    assert (kinds == traffic.PULL).sum() == (kinds == traffic.PUSH).sum()
    # routed rows per grouped matmul: 4 expert layers, each over the 36
    # tokens in 6 passes
    slots = sum(run.extra[f"expert_slots.{e}"] for e in range(4))
    assert slots == pytest.approx(run.extra["routed_rows"] * steps * 4 * 6)
    # the two checked steps and the window's: 4 KDA layers x 5 chunks of
    # 8 tokens (the last ragged) a step
    assert _kda_chunks_counted() - chunks0 == (2 + steps) * 4 * 5
    for k in ("fwd", "vjp"):
        assert run.extra[f"kda_core_flops.{k}"] > 0
        assert run.extra[f"kda_core_bytes.{k}"] > 0
    for name in ("tensor_GBps", "call_p50_ms", "call_p99_ms", "setup_s",
                 "train_tokens_per_s"):
        assert run.cell.readers[name](run) > 0
    assert 0 <= run.cell.readers["exposed_comm_share"](run) <= 100
    assert readings["losses"][0] > 0


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


def test_the_zeroed_carry_makes_correct_false(monkeypatch):
    """The KDA core with the state entering each chunk read as zero: no
    chunk sees the tokens before it."""
    import jax.numpy as jnp

    from brpc_tpu.models import kda

    orig = kda._chunk_step
    monkeypatch.setattr(kda, "_chunk_step", lambda s, *parts: (
        jnp.zeros_like(s), orig(s, *parts)[1]))
    line, _, _ = _execute(seed=SEED + 2)
    assert line["correct"] is False
    c = line["checks"]["grad_gap"]
    assert c["value"] > c["limit"], line["checks"]


# ---- the new reader on one fixed run ----

CORE_OP = ("%fusion.7 = f32[1,32,128,128]{3,2,1,0:T(8,128)} fusion("
           "f32[1,32,128,128]{3,2,1,0:T(8,128)} %s), kind=kLoop")
OTHER_OP = "%convolution.1 = f32[16384,4096]{1,0} convolution(%x, %w)"
FWD = (7.3e10, 1.34e9)
VJP = (1.46e11, 2.42e9)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _run(modules):
    ev = devtrace.Events(slice=(0.0, 1e9))
    ev.modules, ev.ops = [], []
    t = 1000.0
    for name, dur in modules:
        ev.modules.append((name, t, t + dur))
        ev.ops.append((CORE_OP if "kda_core" in name else OTHER_OP, t,
                       t + dur))
        t += dur + 1000.0
    calls = np.zeros((1, 5))
    extra = {"kda_core_flops.fwd": FWD[0], "kda_core_bytes.fwd": FWD[1],
             "kda_core_flops.vjp": VJP[0], "kda_core_bytes.vjp": VJP[1]}
    return harness.Run(cell=None, nbytes=[4], calls=calls, window_s=8.0,
                       setup_s=1.0, peaks=PEAKS, trace=devtrace.reduce(ev),
                       spans=[], extra=extra)


def test_the_kda_core_reader_reads_the_value_its_counts_give():
    """Two forward runs and one vjp run of the core inside the slice (a
    layer program beside them does not count): each adds its layer's
    least time, the larger of operations at the bf16 peak and bytes at
    HBM speed (both bytes-bound here)."""
    run = _run([("jit_kda_core_fwd(1)", 20e6), ("jit_grad(2)", 50e6),
                ("jit_kda_core_fwd(1)", 20e6), ("jit_kda_core_vjp(3)", 60e6)])
    need = 2 * max(FWD[0] / 197e12, FWD[1] / 819e9) + max(
        VJP[0] / 197e12, VJP[1] / 819e9)
    want = 100 * need / 100e-3
    assert spec.load_reader("kda_core_roofline")(run) == pytest.approx(want)
    assert want < 100


def test_the_kda_core_reader_finds_nothing_without_the_core():
    read = spec.load_reader("kda_core_roofline")
    assert read(_run([("jit_grad(2)", 50e6), ("jit__unknown(4)", 9e6)])) \
        is None
    run = _run([("jit_kda_core_fwd(1)", 20e6)])
    run.extra = {}
    assert read(run) is None
    run.trace = None
    assert read(run) is None


# ---- the blocked reference and the operation count ----

def test_blocked_reference_matches_the_programs_plain_reference(monkeypatch):
    """The benchmark's reference (the recurrence by 256-token blocks under
    ``jax.checkpoint``, MLA by query blocks, a layer's pieces at a time)
    gives the loss, gradients and routes of the program's plain reference
    (one token-by-token scan, one softmax, one ``jax.grad``)."""
    import jax.numpy as jnp

    from benchmark import kimi_linear_reference as R
    from benchmark import trainer_reference
    from brpc_tpu.models import kimi_linear_reference, mla_moe

    c = spec.load_json(CONFIG)
    c.update(c["tiny"])
    monkeypatch.setattr(R, "Q_BLOCK", 16)
    monkeypatch.setattr(R, "TOKEN_BLOCK", 8)
    monkeypatch.setattr(trainer_reference, "LOSS_CHUNK", 24)
    R._programs.cache_clear()
    try:
        m = R.Model.of(c)
        layout = R.layout(m)
        params = dict(zip([n for n, _ in layout], R.make_params(
            m, SEED, c["init"])))
        bias = trainer_reference.make_bias(m, SEED, 0.02)
        ids, labels = trainer_reference.tokens(SEED, 0, 1, c["seq_len"],
                                               m.vocab)
        loss, grads, own = R.loss_and_grads(m, params, ids, labels, bias)
    finally:
        R._programs.cache_clear()
    want_loss, want, want_own = kimi_linear_reference.loss_and_grads(
        mla_moe.Config.from_hf(c), params, ids, labels, bias)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert set(grads) == set(want) == {n for n, _ in layout}
    for n in want:
        scale = float(jnp.max(jnp.abs(want[n])))
        assert float(jnp.max(jnp.abs(grads[n] - want[n]))) <= 1e-5 * scale, n
    for a, b in zip(own, want_own):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the seeded gates are the configuration's: A in [1, 16], dt in
    # [0.001, 0.1] through softplus
    import jax

    a = np.exp(np.asarray(params["model.layers.0.self_attn.A_log.weight"]))
    dt = np.asarray(jax.nn.softplus(
        params["model.layers.0.self_attn.dt_bias.weight"]))
    assert 1 <= a.min() and a.max() <= 16
    assert 0.001 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001


def test_step_flops_count_the_published_block():
    """About 42 TFLOP a step at 16,384 tokens with 1/4 of a slot a token
    routed here: 6 N T over 328.7M matrix parameters, the held experts'
    slots, the MLA layer's causal attention and four KDA cores."""
    from benchmark import kimi_linear_flops as F

    cfg = spec.load_json(CONFIG)
    n = F.dense_params_per_token(cfg)
    kda_attn = 3 * 4096 * 2304 + 3 * 4096 * 4 + 2 * (128 * 2304 + 4096 * 128) \
        + 32 * 2304 + 2304 * 4096
    mla_attn = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 32 * 128 * 2304
    ffn = 3 * 2304 * 9216 + 4 * (256 * 2304 + 3 * 2304 * 1024)
    assert n == 4 * kda_attn + mla_attn + ffn + 20480 * 2304
    (fwd, fwd_b), (bwd, bwd_b) = F.kda_core_work(cfg, 1, 16384)
    per_chunk_head = 2 * (2016 * 128 + 2080 * 128 + 2016 * 256
                          + 3 * 64 * 128 * 128 + 2080 * 128)
    assert fwd == 256 * 32 * per_chunk_head and bwd == 2 * fwd
    assert fwd_b == 16384 * 32 * (5 * 128 + 1) * 4
    assert bwd_b == 16384 * 32 * (9 * 128 + 2) * 4
    slots = 16384 * 8 * 8 // 256 * 4
    total = F.step_flops(cfg, 1, 16384, slots)
    assert total == (6 * 16384 * n + 6 * slots * 3 * 2304 * 1024
                     + 3 * (16384 * 16385 // 2) * 2 * 32 * 320
                     + 4 * (fwd + bwd))
    assert n == 328_712_192 and 42e12 < total < 42.2e12
