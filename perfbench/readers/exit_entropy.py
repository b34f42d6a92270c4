"""Looped stack: the entropy of the exit distribution, in nats (at most
ln R for R exits), per task (`entropy` of the task's `loop.exits` span:
the mean over the task's tokens), the median over the window's tasks.  A
distribution that collapses onto one exit reads near 0.  Nothing where
the program writes no such span (a parent commit, a model without the
loop)."""

import statistics

from lib import journal


def read(run):
    tasks = [
        e["entropy"] for e in journal.spans(run.worker, "loop.exits")
        if run.t0 < e["ts"] <= run.t1
    ]
    return statistics.median(tasks) if tasks else None
