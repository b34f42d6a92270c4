"""A worker-journal phase (`step.<phase>` spans of StepAnatomy: one span
per phase per flushed window, carrying the phase's summed seconds) as a
share of the window, in percent.  Host clocks."""

from lib import journal


def read(run, span):
    seconds = sum(
        e["duration_s"] for e in journal.spans(run.worker, span)
        if run.t0 < e["ts"] <= run.t1
    )
    return 100.0 * seconds / (run.t1 - run.t0)
