"""Share of the window's step time in which the step waited on the wire
with no compute left to run, in %: the step driver's exposed wait
(RunTrace.exposed_wait_s) summed over the completed steps, over their
summed wall time."""


def read(run):
    wall = run.extra.get("wall_s")
    if not wall or "exposed_s" not in run.extra:
        return None
    return 100.0 * run.extra["exposed_s"] / wall
