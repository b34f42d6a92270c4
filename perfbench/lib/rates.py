"""How a rate is read.

The end-to-end rate is the window's plain total: every record acknowledged
in (t0, t1] over t1 - t0, with the task in flight at t1 counted by the
share of its time that lies inside the window (`window_total`).  All the
work over all the time: a checkpoint save, a collection or a flush inside
the window lowers it.  The clock is the master journal's `task_done`
timestamps: the boundary a user feels, records trained AND acknowledged.

Beside it stands, as a per-layer statistic, the median of many readings
over equal work (`median_rate`): a reading is the rate over one group of G
consecutive acknowledged tasks, timed from the acknowledgement that closed
the previous group to the one that closes this group.  One save lands in
one reading and moves the median little, so the median is the pace between
stalls, and 1 - total / median is the share of the window the stalls took.
"""

from __future__ import annotations

import statistics

#: Fewer readings than this and the median is not reported.
MIN_READINGS = 20


def window_total(done, t0: float, t1: float) -> dict:
    """`done`: [(ts, records)] of acknowledged tasks, ascending in ts.
    -> the records of (t0, t1], their rate, and the whole tasks among
    them.  The first task acknowledged after t1 was in flight when the
    window ended: it counts by the share of its span (previous
    acknowledgement to its own) that lies inside the window, so the total
    has no step of a whole task at the window's edge, and a stall that
    straddles the edge is shared out by time."""
    if not t1 > t0:
        raise ValueError(f"empty window ({t0}, {t1}]")
    inside = [(ts, n) for ts, n in done if t0 < ts <= t1]
    records = float(sum(n for _, n in inside))
    last = inside[-1][0] if inside else t0
    after = [(ts, n) for ts, n in done if ts > t1]
    if after:
        ts, n = after[0]
        records += n * (t1 - last) / (ts - last)
    return {
        "records": records,
        "rate": records / (t1 - t0),
        "tasks": len(inside),
    }


def readings(done, t0: float, t1: float, group: int):
    """`done`: [(ts, records)] of acknowledged tasks, ascending in ts; t0 is
    the acknowledgement that ended warm-up.  -> (rates, records, seconds):
    one rate per whole group that ended inside (t0, t1], and the work and
    the time those groups cover together."""
    inside = [(ts, n) for ts, n in done if t0 < ts <= t1]
    rates, prev, total = [], t0, 0
    for i in range(group, len(inside) + 1, group):
        chunk = inside[i - group:i]
        end = chunk[-1][0]
        work = sum(n for _, n in chunk)
        if end <= prev:
            raise ValueError(f"task acknowledgements not ascending at {end}")
        rates.append(work / (end - prev))
        total += work
        prev = end
    return rates, total, prev - t0


def median_rate(done, t0: float, t1: float, group: int) -> dict:
    """The rate metric and what stands beside it."""
    rates, total, seconds = readings(done, t0, t1, group)
    if len(rates) < MIN_READINGS:
        raise ValueError(
            f"{len(rates)} readings of {group} task(s) in the window, "
            f"fewer than {MIN_READINGS}: too few for a median"
        )
    sizes = {n for ts, n in done if t0 < ts <= t1}
    if len(sizes) != 1:
        raise ValueError(f"tasks of unequal work in the window: {sizes}")
    median = statistics.median(rates)
    return {
        "median": median,
        "readings": len(rates),
        "records": total,
        "seconds": seconds,
        # The share of the window that stalls took: saves, collections,
        # flushes, whatever the pace between them does not show.
        "stall_share": 1.0 - window_total(done, t0, t1)["rate"] / median,
    }
