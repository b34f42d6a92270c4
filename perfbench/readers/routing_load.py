"""Expert layer: the largest load of a held expert over the mean load of
the held experts, per task (`load_max` / `load_mean` of the task's
`moe.routing` span), the median over the window's tasks.  A task whose
span counts a dropped pair makes the run not correct: the layer drops
none, whatever the imbalance."""

import statistics

from lib import journal


def read(run):
    tasks = [
        e for e in journal.spans(run.worker, "moe.routing")
        if run.t0 < e["ts"] <= run.t1
    ]
    dropped = sum(e["dropped"] for e in tasks)
    if dropped:
        run.faults.append(f"{dropped} routed pair(s) dropped in the window")
    ratios = [e["load_max"] / e["load_mean"] for e in tasks if e["load_mean"]]
    return statistics.median(ratios) if ratios else None
