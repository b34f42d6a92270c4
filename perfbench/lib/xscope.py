"""From a profiler trace to device time by the program's named scopes
and to the program's own spans on the profiler's clock.

`lib/xplane.py` reads what `jax.profiler.ProfileData` exposes: an event's
name, start and length.  What the program adds for this file is not
there: the `jax.named_scope` path of a device op is a stat of the op's
*event metadata* (`tf_op`, e.g. `jit(f)/while/body/fwd_bwd/attn/dot`),
which `ProfileData` does not hand out.  So `dump` (run as a child:
`python xscope.py <profile_dir> <out.json>`) reads the newest
`.xplane.pb` itself, with `google.protobuf` and a description of the
XSpace messages built below (field numbers from tsl's `xplane.proto`; no
jax, no TensorFlow), and writes the plain form:

    {"scopes": [str, ..],      # op_name paths, interned
     "planes": [{"name": str,
                 "lines": [{"name": str,
                            "events": [[name, start_ns, dur_ns, scope], ..]}]}]}

`scope` is an index into `scopes`, or -1.  Device planes keep their
`XLA Ops` and `XLA Modules` lines (every op, with its scope); the host
plane keeps every event that is not a Python-tracer frame (`$...`),
whatever its length: the program's `TraceAnnotation`s (`step.data_wait`,
`checkpoint.save.write`, ...) are among them.

`reduce` works on the plain form alone (tested without jax or a chip),
inside the same window of whole training programs that `lib/xplane.py`
keeps:

    by_scope       seconds of device time (union of op intervals, mean
                   over chips) by the innermost KNOWN scope of each op
                   (a partition; ops under none are `unscoped`)
    under          the same by EVERY known scope on an op's path
                   (`sparse_apply` holds its `grad_accumulate` too)
    spans          the program spans on the host plane that overlap the
                   window: [[name, seconds inside the window], ..]
    idle_s         the first chip's idle time in the window
    idle_named_s   the part of it inside a program span other than
                   `worker.task` (the task span wraps everything, so it
                   explains nothing)
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

try:  # as `lib.xscope` (the harness, the tests) or as a script (the child)
    from . import xplane
except ImportError:  # pragma: no cover - the child process
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import xplane  # type: ignore

#: The device scopes the program documents (`DEVICE_SCOPES` in its
#: obs/tracing.py), copied: the yardstick does not import the program.
KNOWN_SCOPES = (
    "fwd_bwd", "dense_update", "sparse_apply", "grad_accumulate",
    "sparse_adam", "attn", "mlp", "lm_head_loss", "optimizer",
)
#: Program spans by the first part of their name (`SPAN_NAMES` there).
SPAN_PREFIXES = (
    "step.", "data.", "checkpoint.", "worker.", "compile.", "state.",
)
#: A span that wraps a whole task names no cause.
WRAPPER_SPANS = ("worker.task",)
#: Stats of an op's event metadata that hold its `op_name`, in order.
SCOPE_STATS = ("tf_op", "op_name", "long_name")

_SCOPE_TOKEN = re.compile(
    r"(?<![A-Za-z0-9_.])(" + "|".join(KNOWN_SCOPES) + r")(?![A-Za-z0-9_])"
)


# -- the child: .xplane.pb -> plain form --------------------------------


def _xspace_class():
    """The XSpace message class, from field numbers (tsl xplane.proto)."""
    from google.protobuf import (
        descriptor_pb2, descriptor_pool, message_factory,
    )

    F = descriptor_pb2.FieldDescriptorProto
    file = descriptor_pb2.FileDescriptorProto(
        name="perfbench_xplane.proto", package="perfbench.xplane",
        syntax="proto3",
    )

    def message(name, fields, maps=()):
        msg = file.message_type.add(name=name)
        for fname, number, ftype, label, type_name in fields:
            field = msg.field.add(
                name=fname, number=number, type=ftype, label=label
            )
            if type_name:
                field.type_name = ".perfbench.xplane." + type_name
        for fname, number, value_type in maps:
            entry = msg.nested_type.add(name="".join(
                part.capitalize() for part in fname.split("_")
            ) + "Entry")
            entry.options.map_entry = True
            entry.field.add(name="key", number=1, type=F.TYPE_INT64,
                            label=F.LABEL_OPTIONAL)
            entry.field.add(
                name="value", number=2, type=F.TYPE_MESSAGE,
                label=F.LABEL_OPTIONAL,
                type_name=".perfbench.xplane." + value_type,
            )
            msg.field.add(
                name=fname, number=number, type=F.TYPE_MESSAGE,
                label=F.LABEL_REPEATED,
                type_name=f".perfbench.xplane.{name}.{entry.name}",
            )
        return msg

    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    # XStat's values are a oneof on the wire; as plain optional fields
    # the parse is the same and an unset one reads 0 / "".
    message("XStat", [
        ("metadata_id", 1, F.TYPE_INT64, one, ""),
        ("double_value", 2, F.TYPE_DOUBLE, one, ""),
        ("uint64_value", 3, F.TYPE_UINT64, one, ""),
        ("int64_value", 4, F.TYPE_INT64, one, ""),
        ("str_value", 5, F.TYPE_STRING, one, ""),
        ("bytes_value", 6, F.TYPE_BYTES, one, ""),
        ("ref_value", 7, F.TYPE_UINT64, one, ""),
    ])
    message("XEvent", [
        ("metadata_id", 1, F.TYPE_INT64, one, ""),
        ("offset_ps", 2, F.TYPE_INT64, one, ""),
        ("duration_ps", 3, F.TYPE_INT64, one, ""),
        ("stats", 4, F.TYPE_MESSAGE, many, "XStat"),
        ("num_occurrences", 5, F.TYPE_INT64, one, ""),
    ])
    message("XLine", [
        ("id", 1, F.TYPE_INT64, one, ""),
        ("name", 2, F.TYPE_STRING, one, ""),
        ("timestamp_ns", 3, F.TYPE_INT64, one, ""),
        ("events", 4, F.TYPE_MESSAGE, many, "XEvent"),
        ("duration_ps", 9, F.TYPE_INT64, one, ""),
        ("display_id", 10, F.TYPE_INT64, one, ""),
        ("display_name", 11, F.TYPE_STRING, one, ""),
    ])
    message("XEventMetadata", [
        ("id", 1, F.TYPE_INT64, one, ""),
        ("name", 2, F.TYPE_STRING, one, ""),
        ("metadata", 3, F.TYPE_BYTES, one, ""),
        ("display_name", 4, F.TYPE_STRING, one, ""),
        ("stats", 5, F.TYPE_MESSAGE, many, "XStat"),
        ("child_id", 6, F.TYPE_INT64, many, ""),
    ])
    message("XStatMetadata", [
        ("id", 1, F.TYPE_INT64, one, ""),
        ("name", 2, F.TYPE_STRING, one, ""),
        ("description", 3, F.TYPE_STRING, one, ""),
    ])
    message("XPlane", [
        ("id", 1, F.TYPE_INT64, one, ""),
        ("name", 2, F.TYPE_STRING, one, ""),
        ("lines", 3, F.TYPE_MESSAGE, many, "XLine"),
        ("stats", 6, F.TYPE_MESSAGE, many, "XStat"),
    ], maps=[
        ("event_metadata", 4, "XEventMetadata"),
        ("stat_metadata", 5, "XStatMetadata"),
    ])
    message("XSpace", [
        ("planes", 1, F.TYPE_MESSAGE, many, "XPlane"),
    ])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("perfbench.xplane.XSpace")
    )


def _stat_text(stat, stat_names: dict) -> str:
    if stat.str_value:
        return stat.str_value
    if stat.ref_value:
        return stat_names.get(stat.ref_value, "")
    if stat.bytes_value:
        return stat.bytes_value.decode("utf-8", "replace")
    return ""


def dump(profile_dir: str, out_path: str) -> None:
    space = _xspace_class()()
    with open(xplane.newest_xplane(profile_dir), "rb") as f:
        space.ParseFromString(f.read())
    scopes, scope_index, planes = [], {}, []
    for plane in space.planes:
        device = bool(xplane.DEVICE_PLANE.match(plane.name))
        if not device and plane.name != xplane.HOST_PLANE:
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        scope_ids = [
            sid for name in SCOPE_STATS
            for sid, sname in stat_names.items() if sname == name
        ]
        # Per event metadata: (the op's short name, its scope index).
        resolved = {}

        def resolve(metadata_id):
            if metadata_id not in resolved:
                meta = plane.event_metadata.get(metadata_id)
                name = (meta.name if meta is not None else "") or ""
                scope = -1
                if device and meta is not None:
                    by_id = {s.metadata_id: s for s in meta.stats}
                    for sid in scope_ids:
                        text = (
                            _stat_text(by_id[sid], stat_names)
                            if sid in by_id else ""
                        )
                        if text:
                            if text not in scope_index:
                                scope_index[text] = len(scopes)
                                scopes.append(text)
                            scope = scope_index[text]
                            break
                # An op's name is its whole HLO text: keep the result's.
                resolved[metadata_id] = (name.split(" = ", 1)[0], scope)
            return resolved[metadata_id]

        lines = []
        for line in plane.lines:
            if device and line.name not in (
                xplane.OPS_LINE, xplane.MODULES_LINE
            ):
                continue
            events = []
            for event in line.events:
                name, scope = resolve(event.metadata_id)
                if not device and name.startswith("$"):
                    continue  # a Python-tracer frame
                start_ns = line.timestamp_ns + event.offset_ps // 1000
                events.append(
                    [name, start_ns, event.duration_ps // 1000, scope]
                )
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    with open(out_path, "w") as f:
        json.dump({"scopes": scopes, "planes": planes}, f)


# -- the reduction, on the plain form ----------------------------------------


def known_scopes(op_name: str) -> list:
    """The KNOWN scopes on an op_name path, outermost first
    (`a/fwd_bwd/jvp(M)/attn/dot` -> [fwd_bwd, attn];
    `x/jvp(vmap(lm_head_loss))/exp` -> [lm_head_loss]); a fused op lists
    several paths joined by `;`: the first decides."""
    return _SCOPE_TOKEN.findall(op_name.split(";", 1)[0])


def _three(trace: dict) -> dict:
    """The plain form as `lib/xplane.py` reads it (three-element events)."""
    return {"planes": [
        {"name": p["name"], "lines": [
            {"name": l["name"], "events": [e[:3] for e in l["events"]]}
            for l in p["lines"]
        ]} for p in trace["planes"]
    ]}


def program_spans(trace: dict) -> list:
    """[(name, start_ns, end_ns)] of the program's spans on the host plane."""
    return [
        (name, start, start + dur)
        for plane in trace["planes"] if plane["name"] == xplane.HOST_PLANE
        for line in plane["lines"]
        for name, start, dur, _ in line["events"]
        if name.startswith(SPAN_PREFIXES)
    ]


def reduce(trace: dict) -> dict:
    chips, programs, lo, hi = xplane._device_ops(_three(trace))
    scopes = trace.get("scopes", [])
    kept = [
        [
            (name, start, start + dur, scope)
            for line in plane["lines"] if line["name"] == xplane.OPS_LINE
            for name, start, dur, scope in line["events"]
            if lo <= start and start + dur <= hi
        ]
        for plane in trace["planes"]
        if xplane.DEVICE_PLANE.match(plane["name"])
    ]
    kept = [ops for ops in kept if ops]
    on_path = {i: known_scopes(text) for i, text in enumerate(scopes)}
    innermost, under, scoped_ops = {}, {}, 0
    for ops in kept:
        inner_chip, under_chip = {}, {}
        for name, start, end, scope in ops:
            if xplane.op_stem(name) in xplane.CONTAINERS:
                continue  # its body's ops are listed by themselves
            path = on_path.get(scope, [])
            scoped_ops += bool(path)
            inner_chip.setdefault(
                path[-1] if path else "unscoped", []
            ).append((start, end))
            for label in set(path):
                under_chip.setdefault(label, []).append((start, end))
        for total, chip in ((innermost, inner_chip), (under, under_chip)):
            for label, spans in chip.items():
                total[label] = total.get(label, 0) + xplane.covered(
                    xplane.union(spans)
                )
    n = len(kept) or 1
    by_scope = {label: ns / n / 1e9 for label, ns in innermost.items()}
    under = {label: ns / n / 1e9 for label, ns in under.items()}
    # Idle time of the first chip, and how much of it a program span names.
    first = xplane.union([(s, e) for _, s, e in chips[0]])
    gaps = [
        (a[1], b[0]) for a, b in zip(first, first[1:])
        if b[0] - a[1] >= xplane.MIN_GAP_NS
    ]
    spans = program_spans(trace)
    naming = xplane.union([
        (max(s, lo), min(e, hi)) for name, s, e in spans
        if name not in WRAPPER_SPANS and s < hi and e > lo
    ])
    idle_ns = sum(b - a for a, b in gaps)
    named_ns = 0
    for a, b in gaps:
        for s, e in naming:
            named_ns += max(0, min(b, e) - max(a, s))
    inside = {}
    for name, s, e in spans:
        part = min(e, hi) - max(s, lo)
        if part > 0:
            inside[name] = inside.get(name, 0) + part
    return {
        "programs": programs,
        "window_s": (hi - lo) / 1e9,
        "scoped_ops": scoped_ops,
        "by_scope": by_scope,
        "under": under,
        "spans": sorted(
            ([name, ns / 1e9] for name, ns in inside.items()),
            key=lambda kv: -kv[1],
        ),
        "idle_s": idle_ns / 1e9,
        "idle_named_s": named_ns / 1e9,
    }


def for_run(run):
    """The reduction of a traced run's newest profile, made once a run (a
    child process reads the file; the harness parent stays off jax and
    the readers share the result).  None where the run has no trace, the
    file cannot be read, or no op carries a scope: a program without the
    scopes, as the parent commit is, reads as nothing."""
    if not hasattr(run, "_xscope"):
        run._xscope = None
        if run.trace:
            out = os.path.join(run.work, "xscope.json")
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 os.path.join(run.job.tb, "profile"), out],
                capture_output=True, text=True, timeout=600,
            )
            if proc.returncode == 0:
                with open(out) as f:
                    run._xscope = reduce(json.load(f))
                # For the reader of a run's log: the partition of the
                # device's busy time and the host plane's program spans.
                print("[perfbench] xscope: " + json.dumps({
                    key: run._xscope[key] for key in (
                        "by_scope", "under", "spans", "idle_s",
                        "idle_named_s", "window_s",
                    )
                }), file=sys.stderr, flush=True)
            else:
                print(f"[perfbench] xscope: {proc.stderr[-1000:]}",
                      file=sys.stderr, flush=True)
    return run._xscope


if __name__ == "__main__":
    dump(sys.argv[1], sys.argv[2])
