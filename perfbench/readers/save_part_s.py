"""Checkpoint: one part of the save (`checkpoint.save.gather`, `.write`,
`.crc`: real intervals, children of `checkpoint.save`), in seconds,
summed over the saves that STARTED inside the window.  Nothing where no
such save has that part (a program without the parts).  Host clocks."""

from lib import journal


def read(run, span):
    saves = [
        (e["start_ts"], e["start_ts"] + e["duration_s"])
        for e in journal.spans(run.worker, "checkpoint.save")
        if run.t0 < e["start_ts"] <= run.t1
    ]
    parts = [
        e["duration_s"] for e in journal.spans(run.worker, span)
        if any(lo <= e["start_ts"] <= hi for lo, hi in saves)
    ]
    return sum(parts) if parts else None
