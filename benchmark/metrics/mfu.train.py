"""Model FLOP/s utilization of the training window, in %: the operations
the window's completed steps require (benchmark/trainer_flops.py: 6 x
parameters passed x tokens, the routed slots' expert work, causal
attention; recomputation not counted) over the window's seconds times the
chip's bf16 peak."""


def read(run):
    flops = run.extra.get("model_flops")
    peak = run.peaks.get("bf16_flops_per_s")
    if not flops or not peak or run.window_s <= 0:
        return None
    return 100.0 * flops / (run.window_s * peak)
