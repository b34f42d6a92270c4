"""From a profiler trace to device busy / idle, per-op time and the host
frame behind each idle gap.

Two halves.  `dump` (run as a child: `python xplane.py <dir> <out.json>`)
reads the newest `.xplane.pb` under a profile directory with
`jax.profiler.ProfileData` and writes the plain form below; the harness
parent never imports jax.  `reduce` works on the plain form alone, so it
runs, and is tested, without jax or a chip:

    {"planes": [{"name": str,
                 "lines": [{"name": str,
                            "events": [[name, start_ns, duration_ns], ..]}]}]}

Device planes are `/device:TPU:<n>`.  Their `XLA Ops` line holds one event
per executed HLO op, named by the op's whole HLO text (`%fusion.101 = f32[..`);
their `XLA Modules` line holds one event per executed program.  Busy time
is the UNION of the op intervals (ops on a chip can overlap, e.g. a copy
under a fusion), averaged over the chips.  The host plane `/host:CPU` holds
the Python tracer's frames (`$file.py:line function`) on the same clock.

The profiler is stopped from the host while the device still runs the last
dispatched program, so the trace ends inside a program.  The reduction
keeps whole executions of the training program only (the program with the
most device time): the window runs from its first execution's start to the
end of its last execution that is not cut short (shorter than half the
median one), and `programs` says how many those are, so that the caller
can count the steps they ran.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# Host events shorter than this explain no gap worth naming.
MIN_HOST_EVENT_NS = 20_000
MIN_GAP_NS = 20_000
#: Ops that only contain other ops, which the trace lists by themselves.
CONTAINERS = {"while", "conditional", "call"}
#: Collective HLO ops, by the stem of their names.
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute|collective-broadcast|ragged-all-to-all)"
)


def newest_xplane(profile_dir: str) -> str:
    found = glob.glob(
        os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return max(found, key=os.path.getmtime)


def dump(profile_dir: str, out_path: str) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(newest_xplane(profile_dir))
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            # An op's name is its whole HLO text: keep the result's name.
            events = [
                [e.name.split(" = ", 1)[0], int(e.start_ns), int(e.duration_ns)]
                for e in line.events
                if device or e.duration_ns >= MIN_HOST_EVENT_NS
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    with open(out_path, "w") as f:
        json.dump({"planes": planes}, f)


def op_stem(name: str) -> str:
    """`%fusion.123 = f32[8]{0} fusion(..)` -> `fusion`, `copy.4` -> `copy`:
    one row per kind of op however the compiler numbered it."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def union(intervals: list) -> list:
    """Sorted, disjoint [start, end) covering the same points."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def covered(merged: list) -> int:
    return sum(end - start for start, end in merged)


def _line_events(plane: dict, line_name: str) -> list:
    return [
        (name, start, start + dur)
        for line in plane["lines"] if line["name"] == line_name
        for name, start, dur in line["events"]
    ]


def _device_ops(trace: dict) -> tuple:
    """-> (per device plane [(name, start, end)] of executed ops inside the
    whole programs, the number of whole programs, window start, end)."""
    planes = [
        p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])
    ]
    chips = [ops for ops in (_line_events(p, OPS_LINE) for p in planes) if ops]
    if not chips:
        raise ValueError("the trace has no device plane with executed ops")
    lo = min(start for ops in chips for _, start, _ in ops)
    hi = max(end for ops in chips for _, _, end in ops)
    # The program that takes most of the time is the training program; the
    # one-op programs around it (a slice, a convert) ride inside the window.
    by_name = {}
    for name, start, end in _line_events(planes[0], MODULES_LINE):
        by_name.setdefault(name, []).append((start, end))
    whole = 0
    if by_name:
        runs = sorted(max(
            by_name.values(), key=lambda r: sum(e - s for s, e in r)
        ))
        typical = sorted(e - s for s, e in runs)[len(runs) // 2]
        kept = [r for r in runs if r[1] - r[0] >= typical / 2]
        whole, lo, hi = len(kept), kept[0][0], kept[-1][1]
        chips = [
            [op for op in ops if lo <= op[1] and op[2] <= hi] for ops in chips
        ]
    return chips, whole, lo, hi


def _main_host_frames(trace: dict, lo: int, hi: int) -> list:
    """The Python frames of the busiest host thread inside [lo, hi): the
    loop that feeds the device, not a heartbeat asleep in `wait`."""
    best = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            frames = [
                (name, start, start + dur)
                for name, start, dur in line["events"]
                if name.startswith("$") and start < hi and start + dur > lo
            ]
            if len(frames) > len(best):
                best = frames
    return best


def frame_label(name: str) -> str:
    """`$dir/file.py:233 fn` -> `file.py:233_fn`."""
    text = name.lstrip("$").strip()
    head, _, func = text.partition(" ")
    return re.sub(r"[^A-Za-z0-9_.:-]", "_", f"{os.path.basename(head)}_{func}")


def attribute_gaps(gaps: list, frames: list, top: int = 10) -> list:
    """Idle seconds by what the host thread itself was running: each frame
    is charged the idle time inside it that no frame nested in it covers
    (its SELF time during the gaps).  Frames of one thread nest properly,
    so a sweep with a stack finds each frame's children."""
    gaps = sorted(gaps)
    edges = [g[0] for g in gaps]
    before = [0]
    for start, end in gaps:
        before.append(before[-1] + end - start)

    def idle_until(x: int) -> int:
        i = bisect.bisect_right(edges, x)
        if i == 0:
            return 0
        start, end = gaps[i - 1]
        return before[i - 1] + min(x, end) - start

    seconds, stack = {}, []  # stack of [name, end, idle inside, children's]

    def close(frame):
        name, _, inside, nested = frame
        if inside - nested > 0:
            label = frame_label(name)
            seconds[label] = seconds.get(label, 0.0) + (inside - nested) / 1e9
        if stack:
            stack[-1][3] += inside

    for name, start, end in sorted(frames, key=lambda f: (f[1], -f[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        stack.append([name, end, idle_until(end) - idle_until(start), 0])
    while stack:
        close(stack.pop())
    charged = sum(seconds.values())
    rest = before[-1] / 1e9 - charged
    if rest > 1e-6:
        seconds["no_host_frame"] = rest
    ranked = sorted(seconds.items(), key=lambda kv: -kv[1])
    return [[name, secs] for name, secs in ranked[:top]]


def reduce(trace: dict, top: int = 10) -> dict:
    """-> busy_s, window_s, idle_share, chips, whole programs, per-op
    seconds, exposed collective seconds and the idle gaps by host frame."""
    chips, programs, lo, hi = _device_ops(trace)
    busy_ns, exposed_ns, op_ns = 0, 0, {}
    for ops in chips:
        busy = covered(union([(s, e) for _, s, e in ops]))
        compute = union(
            [(s, e) for n, s, e in ops if not COLLECTIVE.match(op_stem(n))]
        )
        busy_ns += busy
        # Collective time that no compute op on this chip hides.
        exposed_ns += busy - covered(compute)
        for name, start, end in ops:
            stem = op_stem(name)
            if stem not in CONTAINERS:
                op_ns[stem] = op_ns.get(stem, 0) + (end - start)
    n = len(chips)
    first = union([(s, e) for _, s, e in chips[0]])
    gaps = [
        (a[1], b[0]) for a, b in zip(first, first[1:])
        if b[0] - a[1] >= MIN_GAP_NS
    ]
    ranked = sorted(op_ns.items(), key=lambda kv: -kv[1])
    return {
        "chips": n,
        "programs": programs,
        "busy_s": busy_ns / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "idle_share": 1.0 - busy_ns / n / (hi - lo),
        "exposed_collective_s": exposed_ns / n / 1e9,
        "device_ops": [[name, ns / n / 1e9] for name, ns in ranked[:top]],
        "idle_gaps": attribute_gaps(
            gaps, _main_host_frames(trace, lo, hi), top
        ),
    }


if __name__ == "__main__":
    dump(sys.argv[1], sys.argv[2])
