"""What a job's own records say, read from its journals.

The master journal (`<tb>/events.jsonl`) has `task_dispatch` / `task_done`
with host timestamps; the worker journal (`<tb>/events_worker_0.jsonl`) has
the `step.*` spans of `StepAnatomy`, one aggregate span per phase per
flushed window, the checkpoint spans and the profile window.  The parsing
is copied from `chip_smoke._read_job` (the program's reader may move).
"""

from __future__ import annotations

import json
import os


def load(path: str) -> list:
    """Every whole JSON line of a journal and of its rotated predecessor
    (`obs/journal.py` moves a file of 8 MB to `<path>.1`); a line being
    written is left."""
    events = []
    for part in (path + ".1", path):
        try:
            with open(part, encoding="utf-8") as f:
                for line in f:
                    if not line.endswith("\n"):
                        break
                    try:
                        events.append(json.loads(line))
                    except ValueError:
                        continue
        except FileNotFoundError:
            pass
    return events


def master_path(tb: str) -> str:
    return os.path.join(tb, "events.jsonl")


def tasks(master_events: list) -> dict:
    """Training tasks by the journal: `dispatch` [(ts, task_id, range)] and
    `done` [(ts, task_id)], in journal order."""
    dispatch, done = [], []
    for e in master_events:
        if e.get("type") != "TRAINING":
            continue
        if e["event"] == "task_dispatch":
            dispatch.append(
                (e["ts"], e["task_id"], (e["epoch"], e["start"], e["end"]))
            )
        elif e["event"] == "task_done":
            done.append((e["ts"], e["task_id"]))
    return {"dispatch": dispatch, "done": done}


def done_with_records(task_events: dict) -> list:
    """[(ts, records)] of acknowledged tasks, ascending."""
    size = {tid: rng[2] - rng[1] for _, tid, rng in task_events["dispatch"]}
    return sorted((ts, size[tid]) for ts, tid in task_events["done"])


def coverage_faults(task_events: dict) -> list:
    """Why the finished tasks do NOT cover what was dispatched exactly once
    (empty: they do).  Every acknowledged task was dispatched; no range of
    records is acknowledged or dispatched twice; and at most the one task
    in flight at the end is unacknowledged."""
    faults = []
    ranges = {}
    for _, tid, rng in task_events["dispatch"]:
        ranges.setdefault(tid, rng)
    seen = {}
    for _, tid in task_events["done"]:
        if tid not in ranges:
            faults.append(f"task {tid} acknowledged, never dispatched")
            continue
        if ranges[tid] in seen:
            faults.append(f"records {ranges[tid]} acknowledged twice")
        seen[ranges[tid]] = tid
    dispatched = [rng for _, _, rng in task_events["dispatch"]]
    if len(dispatched) != len(set(dispatched)):
        faults.append("a range of records was dispatched twice")
    if len(set(dispatched) - set(seen)) > 1:
        faults.append(
            f"{len(set(dispatched) - set(seen))} dispatched tasks unacknowledged"
        )
    return faults


def spans(worker_events: list, name: str) -> list:
    return [
        e for e in worker_events
        if e.get("event") == "span" and e.get("name") == name
    ]
