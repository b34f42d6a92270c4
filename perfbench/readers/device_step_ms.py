"""Trainers: device busy time (union of executed ops, from the trace) per
minibatch traced, in ms."""


def read(run):
    if not run.trace:
        return None
    return 1000.0 * run.trace["busy_s"] / run.trace_steps
