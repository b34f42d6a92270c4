"""Master layer: the median gap between a task's acknowledgement and the
next task's dispatch inside the window, in ms: what the control plane adds
to every task."""

import statistics

from lib import journal


def read(run):
    tasks = journal.tasks(run.master)
    dispatches = sorted(ts for ts, _, _ in tasks["dispatch"])
    gaps, i = [], 0
    for done_ts, _ in sorted(tasks["done"]):
        if not run.t0 < done_ts <= run.t1:
            continue
        while i < len(dispatches) and dispatches[i] < done_ts:
            i += 1
        if i < len(dispatches):
            gaps.append(dispatches[i] - done_ts)
    return 1000.0 * statistics.median(gaps) if gaps else None
