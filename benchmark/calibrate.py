"""Readings that the limits of ``correct`` are set from.

    python3 benchmark/calibrate.py --workload <cell> --seeds 11,12,... \
        --seconds <s> [--out FILE] [--rehearse]

One process (the chip belongs to one), one short window per seed at the
cell's own load and sizes, through the same harness and driver as a run,
asked for the control too. For each seed it records the driver's readings,
each number the run compared (``checks``), the same numbers with the
control in the program's place (the driver's ``control_checks``: for the
parameter-server driver the bfloat16 replay) and the harness's verdict on
each (``correct``, and ``control_correct``, which has to be false). The
lower reading of a limit is the largest the program gives over the seeds
(``max_<number>``); the upper one the smallest the control gives
(``min_control_<number>``). The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

# The sibling script: importing it puts the checkout's root on sys.path.
from run import bootstrap  # noqa: E402


def summarize(rows):
    """Largest program reading and smallest control reading per number."""
    out = {}
    for k in rows[0]["checks"]:
        out[f"max_{k}"] = max(r["checks"][k] for r in rows)
        out[f"min_control_{k}"] = min(r["control_checks"][k] for r in rows)
    out["all_correct"] = all(r["correct"] for r in rows)
    out["any_control_correct"] = any(r["control_correct"] for r in rows)
    return out


def calibrate(workload: str, seeds, seconds: float, rehearse: bool):
    from benchmark import check, harness

    rows = []
    for seed in seeds:
        args = argparse.Namespace(workload=workload, seed=seed,
                                  seconds=seconds, trace=0)
        t0 = time.time()
        out = harness.execute(args, t0, rehearse=rehearse, control=True)
        if isinstance(out, int):
            raise SystemExit(out)
        line, run, readings = out
        control = readings.pop("control_checks")
        row = {"seed": seed, "correct": line["correct"],
               "control_correct": check.passed(control),
               "attempted": line["attempted"],
               "failed_calls": line["failed"],
               "wall_s": time.time() - t0,
               "checks": {k: c["value"] for k, c in line["checks"].items()},
               "control_checks": {k: c["value"] for k, c in control.items()}}
        row.update(readings)
        rows.append(row)
        del out, line, run, readings
        gc.collect()
        print(json.dumps(row), flush=True)
    return {"workload": workload, "seconds": seconds, "rows": rows,
            "summary": summarize(rows)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    bootstrap()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    result = calibrate(args.workload, seeds, args.seconds, args.rehearse)
    print(json.dumps(result["summary"]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
