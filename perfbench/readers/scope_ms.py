"""Kernels: device time under one of the program's named scopes
(`jax.named_scope`: every op whose `op_name` path has the scope on it),
per traced minibatch, in ms (`lib/xscope.py`, in the window of whole
programs `lib/xplane.py` keeps).  Nothing where no op of the trace
carries a known scope (a program without them)."""

from lib import xscope


def read(run, scope):
    reduced = xscope.for_run(run)
    if not reduced or not reduced["scoped_ops"]:
        return None
    return 1000.0 * reduced["under"].get(scope, 0.0) / run.trace_steps
