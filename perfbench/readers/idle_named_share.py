"""Device: the share of the device's idle time, in the traced window of
whole programs, that lies inside one of the program's own spans on the
profiler's host plane (`step.data_wait`, `data.index_load`,
`checkpoint.save.write`, ...; not `worker.task`, which wraps it all), in
percent.  High means the program's spans and the device's ops share a
clock and the spans name what the device waits for.  Nothing where the
host plane has no program span (a program that emits none)."""

from lib import xscope


def read(run):
    reduced = xscope.for_run(run)
    if not reduced or not reduced["spans"] or reduced["idle_s"] <= 0:
        return None
    return 100.0 * reduced["idle_named_s"] / reduced["idle_s"]
