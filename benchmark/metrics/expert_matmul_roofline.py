"""The grouped expert matmul's share of its compute roofline, in %.

Over the ``ragged-dot`` ops of the program runs that lie wholly inside the
traced slice: the operations they need (benchmark/trainer_flops.py: 2 x
rows x k x n, k and n from each op's own HLO shapes, rows the (token,
slot) pairs the window routed to the held experts per expert layer, from
``Run.extra``) at the chip's bf16 peak, over the device time of those ops
in the slice. Time of ops in runs cut by the slice's edges counts and
their operations do not, so the share reads low, never high. The bound
is compute: a held expert's rows do 2 x 2048 x 1408 operations each
against bf16 weights read once.
"""

from collections import Counter

from benchmark import devtrace, trainer_flops


def read(run):
    rows = run.extra.get("routed_rows")
    if run.trace is None or not rows:
        return None
    flops, ops = 0.0, Counter()
    for ex in run.trace.executions:
        for text in ex.ops:
            f = trainer_flops.grouped_matmul_flops(text, rows)
            if f is not None:
                flops += f
                ops[f"{ex.module}/{devtrace.op_name(text)}"] += 1
    seconds = sum(run.trace.op_s.get(k, 0.0) for k in ops)
    if seconds <= 0:
        return None
    return 100.0 * (flops / run.peaks["bf16_flops_per_s"]) / seconds
