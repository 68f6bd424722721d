"""The KDA core's share of its roofline, in %.

Over the runs of the core's own programs (``jit_kda_core_fwd``,
``jit_kda_core_vjp``: brpc_tpu/models/mla_moe.py runs each KDA layer's
chunked recurrence, and its vjp, as programs of their own) that lie wholly
inside the traced slice: each forward run adds one KDA layer's forward
core work, each vjp run its backward's (benchmark/kimi_linear_flops.py
``kda_core_work``, from ``Run.extra``); the least time for a run is the
larger of its operations at the chip's bf16 peak and its bytes at the
HBM peak. That least time over the summed device time of those runs'
ops. Recomputation is not counted, so the share reads low, never high.
The trace's op text carries no name scope, so the runs are told apart by
their programs' names.
"""

PROGRAMS = {"jit_kda_core_fwd": "fwd", "jit_kda_core_vjp": "vjp"}


def read(run):
    work = {k: (run.extra.get(f"kda_core_flops.{k}"),
                run.extra.get(f"kda_core_bytes.{k}")) for k in ("fwd", "vjp")}
    if run.trace is None or not all(f and b for f, b in work.values()):
        return None
    need = seconds = 0.0
    for ex in run.trace.executions:
        kind = PROGRAMS.get(ex.module)
        if kind is None:
            continue
        flops, nbytes = work[kind]
        need += max(flops / run.peaks["bf16_flops_per_s"],
                    nbytes / run.peaks["hbm_bytes_per_s"])
        seconds += ex.op_s
    if seconds <= 0:
        return None
    return 100.0 * need / seconds
