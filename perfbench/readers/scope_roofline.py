"""Kernels: the least time the chip could take for a named scope's work
in the traced steps (the larger of FLOPs over peak FLOP/s and bytes over
peak bytes/s, from the function `cost` beside the configuration's
reference) over the scope's device time in those steps, in percent.

A cost from shapes is `cost(model, minibatch)` for one step
(`gdn_scan_cost`).  A metric that says `counted` has a cost
`cost(model, pairs, steps)` (`moe_experts_cost`) of the pairs the program
COUNTED in the traced steps (`moe.routing` spans of the tasks that the
traced programs ran), not their expectation."""

from lib import journal, named_scopes


def _traced_pairs(run):
    opened = [
        e for e in run.worker
        if e.get("event") == "profile_window" and e.get("action") == "open"
    ]
    if not opened:
        return None
    first = opened[-1]["step_start"]
    tasks = [
        e for e in journal.spans(run.worker, "moe.routing")
        if first < e["step"] <= first + run.trace_steps
    ]
    if sum(e["steps"] for e in tasks) != run.trace_steps:
        return None  # the traced programs are not whole tasks
    return sum(e["pairs"] for e in tasks)


def read(run, scope, cost, counted=False):
    seconds = named_scopes.under_s(run, scope)
    function = getattr(run.reference, cost, None)
    if not seconds or function is None:
        return None
    if counted:
        pairs = _traced_pairs(run)
        if pairs is None:
            return None
        work = function(run.model, pairs, run.trace_steps)
    else:
        one = function(run.model, run.flag_int("minibatch_size"))
        work = {key: value * run.trace_steps for key, value in one.items()}
    peaks = run.peaks()
    least = max(
        work["flops"] / (peaks["flops_per_s"] * run.chips),
        work["bytes"] / (peaks["hbm_bytes_per_s"] * run.chips),
    )
    return 100.0 * least / seconds
