"""Device time under ANY `jax.named_scope` a reader names.

`lib/xscope.py` reduces a trace by a closed list of scopes
(`KNOWN_SCOPES`); its plain form (`<work>/xscope.json`, written by
`xscope.for_run`) keeps every op's whole `op_name` path.  This file
reduces that form for a scope given as an argument, inside the same
window of whole training programs (`xplane._device_ops`): the union of
the intervals of every op whose path has the scope on it, mean over
chips.  None where the run has no trace or no op carries the scope (a
program without it, as a parent commit is).
"""

from __future__ import annotations

import json
import os
import re

from lib import xplane, xscope


def _window_ops(run):
    """[[(start, end, path)] per chip] inside the window, made once a run."""
    if not hasattr(run, "_named_scope_ops"):
        run._named_scope_ops = None
        path = os.path.join(run.work, "xscope.json")
        if xscope.for_run(run) is not None and os.path.exists(path):
            with open(path) as f:
                trace = json.load(f)
            _, _, lo, hi = xplane._device_ops(xscope._three(trace))
            paths = [text.split(";", 1)[0] for text in trace.get("scopes", [])]
            chips = [
                [
                    (start, start + dur, paths[scope])
                    for line in plane["lines"]
                    if line["name"] == xplane.OPS_LINE
                    for name, start, dur, scope in line["events"]
                    if scope >= 0 and lo <= start and start + dur <= hi
                    and xplane.op_stem(name) not in xplane.CONTAINERS
                ]
                for plane in trace["planes"]
                if xplane.DEVICE_PLANE.match(plane["name"])
            ]
            run._named_scope_ops = [ops for ops in chips if ops]
    return run._named_scope_ops


def under_s(run, scope: str):
    """Seconds of device time of the traced window under `scope`."""
    chips = _window_ops(run)
    if not chips:
        return None
    token = re.compile(
        r"(?<![A-Za-z0-9_.])" + re.escape(scope) + r"(?![A-Za-z0-9_])"
    )
    hit = {}
    total, found = 0, False
    for ops in chips:
        spans = []
        for start, end, path in ops:
            if path not in hit:
                hit[path] = bool(token.search(path))
            if hit[path]:
                spans.append((start, end))
        found = found or bool(spans)
        total += xplane.covered(xplane.union(spans))
    return total / len(chips) / 1e9 if found else None
