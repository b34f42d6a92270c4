"""A named field of named spans of one journal (`master` or `worker`)
that ended before the window opened, summed, in seconds: what a
`compile.build` says it was made of (`trace_s`, `lower_s`, `backend_s`,
`cache_read_s`: JAX's own duration events inside the span, each the
union of its reported intervals).  Nothing where no such span carries the
field (a program from before the field).  Host clocks."""

from lib import journal


def read(run, journal_of, span, field):
    events = run.master if journal_of == "master" else run.worker
    found = [
        e[field] for e in journal.spans(events, span)
        if e["ts"] <= run.t0 and field in e
    ]
    return sum(found) if found else None
