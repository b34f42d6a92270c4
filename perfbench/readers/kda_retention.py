"""Delta attention: how much of its state a layer keeps from one token to
the next, in percent: the mean retention exp(g) of the `kda.gates` spans
of the tasks acknowledged inside the window (each the mean over a task's
steps, layers, heads and key channels).  Higher is a longer memory; at
exp(bound), 0.67% under a bound of -5, the scan carries no state from a
token to the next and its time buys nothing.  A check on the model's
state, not a number to chase.  Nothing where the program writes no such
span (a parent commit, a model without such layers)."""

from lib import journal


def read(run):
    tasks = [
        e for e in journal.spans(run.worker, "kda.gates")
        if run.t0 < e["ts"] <= run.t1
    ]
    if not tasks:
        return None
    return 100.0 * sum(e["retention"] for e in tasks) / len(tasks)
