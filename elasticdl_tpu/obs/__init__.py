"""Unified observability plane: metrics registry + event journal + spans.

The process-wide singletons live here; instrumented modules use the
module-level helpers:

    from elasticdl_tpu import obs

    REQUEUES = obs.counter(
        "elasticdl_task_requeues_total", "Task requeues by cause",
        labelnames=("reason",),
    )
    REQUEUES.inc(reason="timeout")

    with obs.span("task.dispatch", task_id=task_id):
        ...  # histogram observation + journal record on exit

Conventions (docs/observability.md):

- metric names: `elasticdl_<subsystem>_<what>_<unit?>_total|seconds|...`;
- labels are bounded enums only (task type, reason, RPC method, kind) —
  the `metric-label-cardinality` analysis rule rejects task-id/pod/host
  shaped labels at creation and increment sites;
- unbounded identifiers ride the JOURNAL as free-form fields (the span
  API's kwargs go to the journal, never to metric labels).

The exporter (obs/exporter.py, `--metrics_port` on the master) serves the
default registry and journal; `init_journal` points the journal at its
JSONL file (one per master, under the TensorBoard log dir).

The worker telemetry plane (obs/telemetry.py) builds on these pieces:
workers ship WorkerTelemetry snapshots on the liveness heartbeat, the
master's TelemetryAggregator folds fleet aggregates into this registry
(per-worker detail is journal-only per the cardinality rule), and
`python -m elasticdl_tpu.obs.top` renders the per-worker view from the
exporter's /metrics + /journal.  Imported lazily here to keep the base
obs import free of the telemetry module (analysis tooling imports obs).

The step-anatomy plane (obs/stepstats.py) decomposes each training
step's wall time into exclusive compute-plane sub-phases (data_wait /
stage / compile / execute / device_wait / bookkeep) with host-side
clocks, counts jit retraces per entrypoint, and turns measured rates
into MFU + a roofline `bound:` verdict; its windowed summaries ride the
telemetry heartbeat, journal as `step_anatomy` events, and upgrade
straggler evidence with the dominant phase.  Imported lazily for the
same reason as telemetry.

The goodput plane (obs/goodput.py) partitions job wall-clock into
exclusive phases (training / rendezvous / checkpoint / redo / ...)
driven by control-plane and worker step-loop hooks, exports
`elasticdl_goodput_ratio` + per-phase seconds + per-rescale cost
breakdowns, and journals every edge; `python -m elasticdl_tpu.obs.report`
replays the journal into a postmortem timeline + attribution report.
Also imported lazily, for the same reason as telemetry.
"""

from __future__ import annotations

import contextlib
import os
import time

from elasticdl_tpu.obs.journal import (
    DEFAULT_FILENAME,
    DEFAULT_MAX_BYTES,
    EventJournal,
)
from elasticdl_tpu.obs.metrics import (
    DURATION_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    RateTracker,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "RateTracker",
    "EventJournal",
    "DURATION_BUCKETS",
    "registry",
    "journal",
    "counter",
    "gauge",
    "histogram",
    "init_journal",
    "span",
]

_registry = MetricsRegistry()
_journal = EventJournal()


def registry() -> MetricsRegistry:
    """The process-wide default registry (what the exporter serves)."""
    return _registry


def journal() -> EventJournal:
    """The process-wide default event journal."""
    return _journal


def counter(name, help="", labelnames=()) -> Counter:
    return _registry.counter(name, help, labelnames)


def gauge(name, help="", labelnames=()) -> Gauge:
    return _registry.gauge(name, help, labelnames)


def histogram(name, help="", labelnames=(), buckets=DURATION_BUCKETS) -> Histogram:
    return _registry.histogram(name, help, labelnames, buckets=buckets)


def init_journal(
    directory: str,
    filename: str = DEFAULT_FILENAME,
    max_bytes: int = DEFAULT_MAX_BYTES,
) -> str:
    """Point the default journal at `<directory>/<filename>` (append
    mode, size-capped rotation).  Returns the journal path.  Never
    raises: an unusable directory (read-only mount, path component that
    is a file) degrades to the memory-only journal with a warning —
    observability must not take the control plane down."""
    path = os.path.join(directory, filename)
    try:
        os.makedirs(directory, exist_ok=True)
    except OSError:
        from elasticdl_tpu.obs.journal import logger

        logger.exception(
            "Journal directory %s unusable; events stay memory-only",
            directory,
        )
        return path
    _journal.configure(path, max_bytes)  # open failure degrades inside
    return path


def _span_metric_name(name: str) -> str:
    slug = name.replace(".", "_").replace("-", "_").replace("/", "_")
    return f"elasticdl_span_{slug}_seconds"


@contextlib.contextmanager
def span(name: str, labels=None, **fields):
    """Timer emitting BOTH halves of the observability plane: a histogram
    observation (`elasticdl_span_<name>_seconds`, bounded `labels` only)
    and — via the tracing plane (obs/tracing.py) — a journal `span`
    record carrying span/trace ids and parent context, so every obs.span
    call site is automatically a node in the distributed trace.  `fields`
    may carry unbounded ids (task_id, trace_id, pod name) — they ride the
    journal, never metric labels.  Yields the open tracing Span (callers
    propagate `span_id` over RPC metadata)."""
    from elasticdl_tpu.obs import tracing

    labels = dict(labels or {})
    hist = _registry.histogram(
        _span_metric_name(name),
        f"Duration of {name} spans",
        labelnames=tuple(sorted(labels)),
    )
    trace_id = fields.pop("trace_id", "")
    # Merge (fields win) rather than double-splat: a key present in both
    # must overwrite, not TypeError a worker's task loop.
    merged = {**labels, **fields}
    start = time.monotonic()
    try:
        with tracing.tracer().span(
            name, trace_id=trace_id, **merged
        ) as open_span:
            yield open_span
    finally:
        hist.observe(time.monotonic() - start, **labels)
