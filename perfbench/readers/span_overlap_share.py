"""A worker-journal span that is one real interval (`checkpoint.save`:
from `start_ts`, `duration_s` long) as a share of the window, in percent:
the part of each such span that lies inside the window, so a save still
being written when the window closes counts up to there.  Host clocks."""

from lib import journal


def read(run, span):
    seconds = 0.0
    for e in journal.spans(run.worker, span):
        start, end = e["start_ts"], e["start_ts"] + e["duration_s"]
        seconds += max(0.0, min(end, run.t1) - max(start, run.t0))
    return 100.0 * seconds / (run.t1 - run.t0)
