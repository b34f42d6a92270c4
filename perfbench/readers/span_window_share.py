"""A worker-journal span that is a real interval (`data.index_load`), as
a share of the window, in percent: the part of each that lies inside the
window.  Nothing where the journal has no span of that name at all (a
program without it).  Host clocks."""

from lib import journal


def read(run, span):
    spans = journal.spans(run.worker, span)
    if not spans:
        return None
    seconds = 0.0
    for e in spans:
        start, end = e["start_ts"], e["start_ts"] + e["duration_s"]
        seconds += max(0.0, min(end, run.t1) - max(start, run.t0))
    return 100.0 * seconds / (run.t1 - run.t0)
