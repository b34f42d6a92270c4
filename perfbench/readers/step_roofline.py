"""Kernels: the least time the chip could take for one training step (the
larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, both from
the configuration's `step_cost`, beside its reference) over the device busy
time per traced step, in percent.  `run.roofline_bound` says which bound."""


def read(run):
    if not run.trace:
        return None
    cost = run.reference.step_cost(run.model, run.flag_int("minibatch_size"))
    peaks = run.peaks()
    by_flops = cost["flops"] / (peaks["flops_per_s"] * run.chips)
    by_bytes = cost["bytes"] / (peaks["hbm_bytes_per_s"] * run.chips)
    run.roofline_bound = "compute" if by_flops >= by_bytes else "memory"
    step_s = run.trace["busy_s"] / run.trace_steps
    return 100.0 * max(by_flops, by_bytes) / step_s
