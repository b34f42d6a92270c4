"""Worker loop: the pace between stalls, as the median of the window's
equal readings (`lib/rates.median_rate`), in the traffic's units per chip.
Nothing where the window holds too few readings for a median."""

from lib import rates


def read(run):
    try:
        rate = rates.median_rate(
            run.done, run.t0, run.t1, int(run.traffic["group_tasks"])
        )
    except ValueError:
        return None
    return rate["median"] * run.traffic["data"]["units_per_record"] / run.chips
