"""Kernels: device time under a named scope the reader is given (any
`jax.named_scope` of the program, not only `lib/xscope.py`'s closed list),
per traced minibatch, in ms.  Nothing where no op carries the scope."""

from lib import named_scopes


def read(run, scope):
    seconds = named_scopes.under_s(run, scope)
    if seconds is None:
        return None
    return 1000.0 * seconds / run.trace_steps
