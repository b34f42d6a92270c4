"""Masked diffusion: the share of the window's tokens that the records'
noise masked, in percent (`masked` over `tokens` of the `diffusion.noise`
spans of the tasks acknowledged inside the window): 50% under one noise
level a record from U(0, 1].  A check on the traffic, not a number to
chase.  Nothing where the program writes no such span (a parent commit, a
model trained another way)."""

from lib import journal


def read(run):
    tasks = [
        e for e in journal.spans(run.worker, "diffusion.noise")
        if run.t0 < e["ts"] <= run.t1
    ]
    tokens = sum(e["tokens"] for e in tasks)
    if not tokens:
        return None
    return 100.0 * sum(e["masked"] for e in tasks) / tokens
