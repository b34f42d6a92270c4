"""Compile layer: the `step.compile` spans before the window (tracing,
XLA compile or cache load, state init and the first window), in seconds."""

from lib import journal


def read(run):
    return sum(
        e["duration_s"] for e in journal.spans(run.worker, "step.compile")
        if e["ts"] <= run.t0
    )
