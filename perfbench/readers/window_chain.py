"""The measured window as one chain: what of (`run.t0`, `run.t1`], the
stretch every end-to-end rate is taken over, lies inside something the
worker's journal NAMES, on its wall clock.

Between tasks the timeline is the union of the real intervals the metric
file lists as `leaves` (`worker.get_task`, `worker.task`,
`worker.report_task`); what lies in none is a gap.  Inside a
`worker.task` the named seconds are

  - the union of its interval children that lie in no phase
    (`intervals`: `checkpoint.save`, `step.device_wait`; `events`: the
    `profile_window` close, which ends at its `ts` and is `duration_s`
    long), clipped to the window where it cuts the task, and
  - the SUM of its aggregate children (`phases`: `step.data_wait`,
    `step.stage`, ...: a flushed window's phase totals, sound as sums and
    not as positions), capped at what the task has left beside those
    intervals, and counted by the share of that rest that lies inside
    the window where t0 or t1 cuts the task.  The queue wait the run loop
    books as the next task's `data_wait` lies in `worker.get_task`,
    before the task: it is taken off the task's sum.

The rest of the task is its unnamed part.  Where every task the master
dispatched has been acknowledged, the stretch behind the worker's last
span is the work having run out (`after_last_task`), not a gap.  `part`
selects the number:

  window_named_share    % of the window outside every gap and remainder
  window_largest_gap_s  the longest single unnamed stretch: a gap between
                        two spans, or one task's remainder

Nothing where the journal holds no `step.device_wait` (a program from
before the span: most of each of its tasks is the unnamed wait).  One
worker's journal: the one that read the losses.  Host clocks.

    python3 perfbench/readers/window_chain.py <tb dir> <warmup_tasks> <seconds> [threshold_s]

prints the chain of a finished job's journals without the benchmark (t0
is the acknowledgement of warm-up's last task, as the scenario takes it):
the window's parts, then every gap and every task's remainder over the
threshold (0.01 s), longest first, a task with its own parts.
"""

import glob
import json
import os
import sys

FENCE = "step.device_wait"
QUEUE_WAIT, QUEUE_PHASE = "worker.get_task", "step.data_wait"
#: What the printed line calls a part that is not a phase.
LABELS = {
    "checkpoint.save": "save", "worker.get_task": "get_task",
    "worker.report_task": "report", "profile_window": "profile",
}
LINE = (
    "step.data_wait", "step.stage", "step.compile", "step.execute",
    "step.bookkeep", FENCE, "checkpoint.save", "worker.get_task",
    "worker.report_task", "profile_window", "after_last_task", "unnamed",
)


def _spans(events):
    return [e for e in events if e.get("event") == "span"]


def _end(e):
    return e["start_ts"] + e["duration_s"]


def _covered(intervals, lo, hi):
    """{name: seconds} of the union of [(start, end, name)] inside
    (lo, hi): where two overlap, the earlier one has the seconds."""
    seconds, reach = {}, lo
    for a, b, name in sorted(intervals):
        a, b = max(a, reach), min(b, hi)
        if b > a:
            seconds[name] = seconds.get(name, 0.0) + b - a
            reach = b
    return seconds


def _task(task, children, queue_wait, events, lo, hi, phases, intervals):
    """({part: seconds inside (lo, hi)}, the task's remainder there)."""
    a, b = task["start_ts"], _end(task)
    inner = [
        (e["start_ts"], _end(e), e["name"])
        for e in children if e["name"] in intervals
    ] + [
        (e["ts"] - e["duration_s"], e["ts"], e["event"])
        for e in events if a <= e["ts"] - e["duration_s"] / 2 <= b
    ]
    whole = _covered(inner, a, b)
    parts = _covered(inner, max(a, lo), min(b, hi))
    rest = (b - a) - sum(whole.values())
    rest_inside = (min(b, hi) - max(a, lo)) - sum(parts.values())
    sums = {}
    for e in children:
        if e["name"] in phases:
            sums[e["name"]] = sums.get(e["name"], 0.0) + e["duration_s"]
    if QUEUE_PHASE in sums:
        sums[QUEUE_PHASE] = max(0.0, sums[QUEUE_PHASE] - queue_wait)
    booked = sum(sums.values())
    if booked > 0 and rest > 0:
        scale = min(1.0, rest / booked) * rest_inside / rest
        for name, seconds in sums.items():
            parts[name] = seconds * scale
        rest_inside -= booked * scale
    return parts, max(0.0, rest_inside)


def chain(worker, lo, hi, drained, leaves, phases, intervals, events=()):
    """The window (lo, hi] of one worker's journal -> {"parts": {name:
    seconds}, "holes": [(seconds, start, what, the task's parts or None)]
    longest first}, or None where it holds no `step.device_wait`.
    `parts` has `unnamed` (the holes' sum) and `after_last_task`;
    `drained` says the master has no task out."""
    spans = _spans(worker)
    fences = [e for e in spans if e["name"] == FENCE]
    if not fences:
        return None
    spans = [e for e in spans if e.get("proc") == fences[0].get("proc")]
    closes = [
        e for e in worker if e.get("event") in events and "duration_s" in e
    ]
    children, queue_wait = {}, {}
    for e in spans:
        children.setdefault(e.get("parent_span_id"), []).append(e)
        if e["name"] == QUEUE_WAIT:
            queue_wait[e.get("trace_id")] = e["duration_s"]
    parts, holes = {}, []
    reach, before = lo, "t0"
    for e in sorted(spans, key=lambda e: e["start_ts"]):
        a, b = e["start_ts"], _end(e)
        if e["name"] not in leaves or b <= reach or a >= hi:
            continue
        if a > reach:
            holes.append(
                (a - reach, reach, f"after {before}, before {e['name']}", None)
            )
        a, b = max(a, reach), min(b, hi)
        if e["name"] == "worker.task":
            found, remainder = _task(
                e, children.get(e["span_id"], ()),
                queue_wait.get(e.get("trace_id"), 0.0), closes, a, b,
                phases, intervals,
            )
            holes.append((
                remainder, a,
                f"the remainder of task {e.get('task_id')} "
                f"({b - a:.3f}s of it inside)",
                dict(found, unnamed=remainder),
            ))
        else:
            found = {e["name"]: b - a}
        for name, seconds in found.items():
            parts[name] = parts.get(name, 0.0) + seconds
        reach, before = b, e["name"]
    if hi > reach and drained:
        parts["after_last_task"] = hi - reach
    elif hi > reach:
        holes.append((hi - reach, reach, f"after {before}, before t1", None))
    parts["unnamed"] = sum(h[0] for h in holes)
    holes.sort(key=lambda h: -h[0])
    return {"parts": parts, "holes": holes}


def _drained(master):
    """Whether every training task the master dispatched was acknowledged."""
    out = set()
    for e in master:
        if e.get("type") != "TRAINING":
            continue
        if e.get("event") == "task_dispatch":
            out.add(e["task_id"])
        elif e.get("event") == "task_done":
            out.discard(e["task_id"])
    return not out


def line(parts, window):
    """`data_wait a% stage b% ... unnamed j%`: the parts of the window."""
    return " ".join(
        f"{LABELS.get(name, name.split('.')[-1])} "
        f"{100.0 * parts.get(name, 0.0) / window:.2f}%"
        for name in LINE
        if name != "step.compile" or parts.get(name)
    )


def read(run, part, leaves, phases, intervals, events=()):
    found = chain(
        run.worker, run.t0, run.t1, _drained(run.master),
        leaves, phases, intervals, events,
    )
    if found is None:
        return None
    window = run.t1 - run.t0
    if part == "window_named_share":
        print(f"[perfbench] window chain: {line(found['parts'], window)}",
              file=sys.stderr, flush=True)
        return 100.0 * (1.0 - found["parts"]["unnamed"] / window)
    if part == "window_largest_gap_s":
        if not found["holes"]:
            return 0.0
        length, start, what, _ = found["holes"][0]
        print(
            f"[perfbench] window chain: largest gap {length:.3f}s, "
            f"{start - run.t0:.3f}s in, {what}",
            file=sys.stderr, flush=True,
        )
        return length
    raise ValueError(f"window_chain has no part {part!r}")


def _load(path):
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.endswith("}\n")]


def main(argv):
    tb, warmup, seconds = argv[0], int(argv[1]), float(argv[2])
    threshold = float(argv[3]) if len(argv) > 3 else 0.01
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "metrics", "window_named_share.json")) as f:
        args = json.load(f)["args"]
    args.pop("part")
    master = _load(os.path.join(tb, "events.jsonl"))
    worker = [
        e for path in sorted(glob.glob(os.path.join(tb, "events_worker_*.jsonl")))
        for e in _load(path)
    ]
    done = [
        e["ts"] for e in master
        if e.get("event") == "task_done" and e.get("type") == "TRAINING"
    ]
    lo = done[warmup - 1]
    hi, drained = lo + seconds, _drained(master)
    if drained:  # the work ran out: the window ends with it
        hi = min(hi, done[-1])
    found = chain(worker, lo, hi, drained, **args)
    if found is None:
        print(f"no {FENCE} span in {tb}: nothing to tile the window with")
        return 1
    print(f"window {hi - lo:.3f}s  {line(found['parts'], hi - lo)}")
    print(f"named {100.0 * (1.0 - found['parts']['unnamed'] / (hi - lo)):.2f}%")
    for length, start, what, parts in found["holes"]:
        if length > threshold:
            print(f"unnamed {length:8.3f}s at {start - lo:8.3f}  {what}")
            if parts:
                print(" " * 8 + line(parts, sum(parts.values())))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
