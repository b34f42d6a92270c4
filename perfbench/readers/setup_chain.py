"""Set-up as one chain: what of `setup_s` (the harness's start to the
acknowledgement that ends warm-up, `run.t0`) lies inside a NAMED interval
of the master's or the worker's journal, on the wall clock.

The named intervals are the leaf spans the metric file lists (`leaves`:
`proc.start` of both processes, the children of `master.boot` and
`worker.boot`, `master.launch_worker`, the task chain of warm-up), the
events it lists that carry a `duration_s` and end at their `ts`
(`events`: the `profile_window` close, where a cell's trace lies in
warm-up), and the harness's own part: its start to the creation of the
master process (the cell's data from the seed and the `Popen`), which is
not the program's.  Their UNION is taken, clipped to the interval, so
nothing is counted twice.  `part` selects the number:

  setup_named_share    % of `setup_s` inside the union
  setup_largest_gap_s  the longest stretch inside no named interval
  harness_prepare_s    harness start -> the master process was created
  worker_launch_s      end of `master.boot` -> the (first) worker
                       process was created

Nothing where the journals hold what the part needs none of (a program
from before the boot spans has no `master.boot`).  Host clocks.

    python3 perfbench/readers/setup_chain.py <tb dir> <setup_s> <warmup_tasks>

prints the chain of a finished run's journals (t0 is the acknowledgement
of warm-up's last task, as the scenario takes it), leaf by leaf, with
every gap over 0.1 s and the two leaves around it.
"""

import glob
import json
import os
import sys

HARNESS = "harness.prepare"


def _spans(events, name=None):
    return [
        e for e in events
        if e.get("event") == "span" and name in (None, e.get("name"))
    ]


def _created(events):
    """When the first process of these journals was created."""
    starts = [e["start_ts"] for e in _spans(events, "proc.start")]
    return min(starts) if starts else None


def named(master, worker, lo, hi, leaves, events=()):
    """[(start, end, name)] of the named intervals that touch (lo, hi),
    clipped to it, by start."""
    found = []
    for e in _spans(master + worker):
        if e["name"] in leaves:
            found.append(
                (e["start_ts"], e["start_ts"] + e["duration_s"], e["name"])
            )
    for e in master + worker:
        if e.get("event") in events and "duration_s" in e:
            found.append((e["ts"] - e["duration_s"], e["ts"], e["event"]))
    created = _created(master)
    if found and created is not None:
        found.append((lo, created, HARNESS))
    return sorted(
        (max(a, lo), min(b, hi), name) for a, b, name in found
        if b > lo and a < hi
    )


def gaps(intervals, lo, hi):
    """[(length, start, end, name before, name after)] of the stretches
    of (lo, hi) inside no interval, longest first."""
    out, reach, before = [], lo, "start"
    for a, b, name in intervals:
        if a > reach:
            out.append((a - reach, reach, a, before, name))
        if b > reach:
            reach, before = b, name
    if hi > reach:
        out.append((hi - reach, reach, hi, before, "t0"))
    return sorted(out, reverse=True)


def read(run, part, leaves=(), events=()):
    lo, hi = run.t0 - run.setup_s, run.t0
    if part == "harness_prepare_s":
        created = _created(run.master)
        return None if created is None else created - lo
    if part == "worker_launch_s":
        boots = _spans(run.master, "master.boot")
        created = _created(run.worker)
        if not boots or created is None:
            return None
        return created - (boots[0]["start_ts"] + boots[0]["duration_s"])
    intervals = named(run.master, run.worker, lo, hi, leaves, events)
    if not intervals:
        return None
    holes = gaps(intervals, lo, hi)
    if part == "setup_named_share":
        return 100.0 * (1.0 - sum(g[0] for g in holes) / (hi - lo))
    if part == "setup_largest_gap_s":
        if not holes:
            return 0.0
        length, start, _, before, after = holes[0]
        print(
            f"[perfbench] setup chain: largest gap {length:.3f}s, "
            f"{start - lo:.3f}s in, after {before}, before {after}",
            file=sys.stderr, flush=True,
        )
        return length
    raise ValueError(f"setup_chain has no part {part!r}")


def _load(path):
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.endswith("}\n")]


def main(argv):
    tb, setup_s, warmup = argv[0], float(argv[1]), int(argv[2])
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "metrics", "setup_named_share.json")) as f:
        args = json.load(f)["args"]
    master = _load(os.path.join(tb, "events.jsonl"))
    worker = [
        e for path in sorted(glob.glob(os.path.join(tb, "events_worker_*.jsonl")))
        for e in _load(path)
    ]
    hi = [
        e["ts"] for e in master
        if e.get("event") == "task_done" and e.get("type") == "TRAINING"
    ][warmup - 1]
    lo = hi - setup_s
    intervals = named(master, worker, lo, hi, args["leaves"], args["events"])
    for a, b, name in intervals:
        print(f"{a - lo:9.3f} {b - lo:9.3f} {b - a:8.3f}  {name}")
    holes = gaps(intervals, lo, hi)
    share = 100.0 * (1.0 - sum(g[0] for g in holes) / (hi - lo))
    print(f"setup_s {hi - lo:.3f}  named {share:.2f}%")
    for length, start, _, before, after in holes:
        if length > 0.1:
            print(f"gap {length:8.3f}s at {start - lo:8.3f}  "
                  f"after {before}, before {after}")


if __name__ == "__main__":
    main(sys.argv[1:])
