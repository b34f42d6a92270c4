"""Set-up's parts: the named spans of one journal (`master` or `worker`)
that ended before the window opened, summed, in seconds: `proc.start`
(process creation to the first line of main), the master's
`master.tensorboard_init` and `master.serve_ready`, the worker's
`worker.backend_init` and `state.init`.  Nothing where the journal has
none of them.  Host clocks."""

from lib import journal


def read(run, journal_of, spans):
    events = run.master if journal_of == "master" else run.worker
    found = [
        e["duration_s"] for name in spans
        for e in journal.spans(events, name) if e["ts"] <= run.t0
    ]
    return sum(found) if found else None
