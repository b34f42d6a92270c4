"""Worker loop: the share of the window that stalls took, 1 - (the
window's total rate) / (the median reading), in percent.  The cadence
save, collections and flushes land here, and so does any slow stretch."""

from lib import rates


def read(run):
    try:
        rate = rates.median_rate(
            run.done, run.t0, run.t1, int(run.traffic["group_tasks"])
        )
    except ValueError:
        return None
    return 100.0 * rate["stall_share"]
