"""The end-to-end rate: all the records acknowledged in the window over
all the time of the window (`lib/rates.window_total`), in the traffic's
units (a record is `units_per_record` samples or tokens) per chip the cell
pays for.  Saves and every other stall inside the window are in it."""

from lib import rates


def read(run):
    total = rates.window_total(run.done, run.t0, run.t1)
    return total["rate"] * run.traffic["data"]["units_per_record"] / run.chips
