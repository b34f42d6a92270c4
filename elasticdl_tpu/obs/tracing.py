"""Distributed tracing plane: span trees over the event journal.

Every interesting latency in an elastic job is *cross-process*: a task's
life spans master dispatch, a gRPC hop, the worker's data_wait / stage /
execute phases, and the report back.  The metrics registry aggregates
those away and the journal records them as disconnected point events;
this module adds the missing structure — SPANS with parent/child
context — without any new storage plane: spans journal as
schema-registered ``span`` events in each process's durable journal
(master ``events.jsonl``, per-worker ``events_worker_<id>.jsonl``), and
``python -m elasticdl_tpu.obs.trace`` (obs/trace.py) merges the files,
aligns the clocks, and emits a Perfetto-loadable Chrome trace.

Model (stdlib only — contextvars + the journal):

- A ``Span`` is one timed operation: ``name``, ``trace_id`` (the
  dispatch-minted task trace id, or empty for non-task spans),
  ``span_id``, ``parent_span_id``, wall-clock ``start_ts`` plus a
  monotonic duration.  Span NAMES are a bounded enum (docs table);
  unbounded identifiers (task ids, trace ids) ride the journal record's
  free-form fields per the cardinality rule — span names never become
  metric labels beyond what ``obs.span`` already exports.
- ``Tracer.span()`` is a context manager: spans opened inside it become
  children automatically (a ``contextvars.ContextVar`` carries the
  current span, so thread pools and nested calls parent correctly).
- The ROOT span of a task trace has ``span_id == trace_id`` by
  convention: any process that knows the trace id can parent under the
  root without coordination (the master journals the root
  ``task.lifetime`` span at report time, after the fact).
- Cross-process propagation rides the existing gRPC metadata plane
  (``grpc_utils.TRACE_METADATA_KEY`` for the trace id plus
  ``SPAN_METADATA_KEY`` for the caller's span id), so the master's RPC
  handler spans nest under the worker's client spans.
- ``record_span`` journals after-the-fact spans (operations whose
  start was measured before a span was warranted — e.g. the task
  lifetime, known only at report time).

Clock discipline: ``start_ts`` is wall clock (``time.time``) — the
cross-process alignment in obs/trace.py needs a common timescale and
corrects per-worker offsets from heartbeat round-trips; durations come
from ``time.monotonic`` so an NTP step mid-span cannot produce negative
lengths.  All clock reads happen HERE, strictly outside traced code
(the instrumented sites are host-side control-plane code), keeping the
trace-purity analysis rule green.

Two sinks, one vocabulary: every span opened HERE (``Tracer.span``) and
every step phase opened in obs/stepstats.py also enters a
``jax.profiler.TraceAnnotation`` of the SAME name for the real interval
of the call (``annotate`` below), so a profile taken with
``--profile_steps`` carries the program's spans on the device ops' own
timeline.  The sink exists only in a process that has already imported
jax (``sys.modules``): the master and the serving supervisor stay off
jax, and with no profile running an annotation is an atomic load.
``SPAN_NAMES`` is the bounded list of training-path span names with what
each one times; ``DEVICE_SCOPES`` is the list of ``jax.named_scope``
names the trainers put on device ops.

Crash flight recorder: ``install_flight_recorder()`` registers an
atexit hook (reached from SIGTERM via the worker main's
SIGTERM->SystemExit conversion, the PR-3 shutdown path) that flushes
every still-open span (``flushed="shutdown"``, duration so-far) and a
final bounded ``registry_snapshot`` event — a preempted worker leaves a
complete trace tail instead of a cliff.
"""

from __future__ import annotations

import atexit
import contextlib
import contextvars
import itertools
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, Optional

from elasticdl_tpu.analysis.runtime import make_lock
from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("obs.tracing")

#: Tracer instances in one process must mint non-colliding span ids even
#: when tests rebuild them (same rule as the TaskManager trace prefix).
_TRACER_SEQ = itertools.count()

#: Ordered step-anatomy phases a dispatch window decomposes into
#: (stepstats.PHASES but `device_wait`, which is journaled as the real
#: interval it is; imported lazily there to avoid a cycle).
_WINDOW_PHASES = ("data_wait", "stage", "compile", "execute", "bookkeep")

#: The training path's span names (master, worker, checkpoint, data)
#: and what each one times.  "interval" spans are journaled with their
#: real start and length and, where jax is loaded, entered as a
#: TraceAnnotation of the same name; "aggregate" spans are a dispatch
#: window's phase totals laid out back to back (sound as sums, not as
#: positions on a timeline); "after" spans are journaled after the fact
#: from two clock reads and have no annotation.  PERF.md section 3 names
#: the reader of each (a benchmark metric or an obs command).
SPAN_NAMES: Dict[str, str] = {
    # task chain (obs.trace waterfall, obs.report slowest chains)
    "task.lifetime": "after: master, dispatch -> report (trace root)",
    "rpc.get_task": "after: master, dispatcher under the RPC handler",
    "rpc.report_task_result": "after: master, report handler",
    "worker.get_task": "after: worker, client half of dispatch (real "
                       "tasks only); annotation: every call's RPC, a "
                       "WAIT poll too",
    "worker.report_task": "interval: worker, result report RPC",
    "worker.task": "interval: worker, one task's execution",
    "worker.join_world": "interval: worker, the rank poll and, in a "
                         "world of more than one, the distributed init",
    "rendezvous.formation": "after: master, declaration -> all polled",
    # step anatomy: journal aggregates + annotation intervals
    "step.data_wait": "aggregate; annotation: each wait for a batch",
    "step.stage": "aggregate; annotation: each host->device staging",
    "step.compile": "aggregate: dispatches during which a jit compiled",
    "step.execute": "aggregate: dispatch clock of compiled programs",
    "step.dispatch": "annotation only: each device dispatch call "
                     "(what step.compile/step.execute clock)",
    "step.device_wait": "interval: the task's one wait for the device "
                        "(the leader's read of its last loss, where the "
                        "host catches up with what it dispatched; a child "
                        "of the task's own worker.task: `task_id`, `steps`)",
    "step.bookkeep": "aggregate; annotation: telemetry, version report "
                     "(and, annotation only, a task's counters and "
                     "reports behind its fence)",
    # host data plane (one set per task of a record-file reader)
    "data.index_load": "interval: first range_size after open (reads "
                       "the range's two index entries)",
    "data.read": "after: the task's read_range calls, summed",
    "data.decode": "interval: columnar concatenate + model transform",
    # checkpoint
    "checkpoint.save": "interval: one save, all ranks",
    "checkpoint.save.gather": "after: what a streamed save waited for "
                              "the device, summed (the program in flight, "
                              "then any leaf not yet on the host when the "
                              "writer asked); interval: a trainer's "
                              "collective gather (`state_to_host`)",
    "checkpoint.save.write": "after: the rest of the stream, the time "
                             "inside the writer: every file written once, "
                             "its CRC32 taken in flight (`copied_bytes`, "
                             "`streamed_bytes`, `lookahead_peak_bytes`, "
                             "`leaves`; `pieces`: the transfers, one a "
                             "whole leaf and one a piece of a cut one; "
                             "`recycled_bytes`: bytes taken from an "
                             "address range the file had taken bytes "
                             "from before)",
    "checkpoint.save.crc": "interval: the integrity manifest alone "
                           "(`reread_bytes`: 0 unless a file is read back)",
    "checkpoint.save.commit": "interval: rename + garbage-collect",
    "checkpoint.restore": "interval: newest step + its CRC check",
    "checkpoint.restore.load": "interval: read, unpickle, place",
    # start-up: one chain a process, `proc.start` then the boot span
    # whose children name its parts (its self time is what is unnamed)
    "proc.start": "after: process creation -> first line of main",
    "master.boot": "interval: first line of main (client main for "
                   "`elasticdl train`) -> serving (`heavy_imports`)",
    "master.imports": "after: client main, the import of the master's "
                      "modules (grpc, numpy, the services): it ends "
                      "before the journal exists",
    "master.build": "interval: build_master after the spec: readers, "
                    "create_shards, TaskManager, services (holds "
                    "master.tensorboard_init)",
    "master.tensorboard_init": "interval: the scalar service's event-"
                               "file writer (protos + open)",
    "master.serve_ready": "interval: gRPC server + exporter start",
    "master.build_fleet": "interval: serving -> manager.start(): policy "
                          "engine, worker manager, SLO plane",
    "master.launch_worker": "interval: one a worker process started, up "
                            "to and with its Popen / pod create "
                            "(`worker_id`, `cause`, `since_exit_s`)",
    "worker.boot": "interval: first line of the worker's main -> "
                   "worker.run() entered",
    "worker.imports": "interval: arguments, journal, compile cache and "
                      "the worker's imports (`jax_import_s`)",
    "worker.build_trainer": "interval: mesh, build_model, trainer, "
                            "saver, the worker object",
    "spec.load": "interval: load_model_spec, the zoo module's import "
                 "(`imported`: which frameworks it brought)",
    "worker.backend_init": "interval: first jax.devices()",
    "state.init": "interval: the state's shapes, then the jitted init "
                  "(`dp_init` / `ps_init`) or the restore in its place",
    "compile.build": "interval: first call of a jitted entrypoint "
                     "(`trace_s`, `lower_s`, `backend_s`, `cache_read_s`, "
                     "`programs`, `cache_hit`; the executable store: "
                     "`aot_hit`, `aot_load_s`, `aot_key`, `aot_skip`)",
    # expert routing (layers/moe.py): a task's counters ride on a span
    "moe.routing": "after: worker, one a task of a model with expert "
                   "layers: pairs routed to held experts, dropped (0), "
                   "the expert loop's trips (`blocks`) and the rows of "
                   "one (`block_rows`: pairs / (blocks x block_rows) is "
                   "the blocks' fill), largest and mean load of a held "
                   "expert, the mean balancing loss where the routers "
                   "have one (`balance_loss`), and where they select "
                   "within groups the mean number of distinct groups a "
                   "token's choices fell in (`groups_mean`, at most "
                   "`group_limit`)",
    # exits of a looped stack (layers/loop_exits.py): the same route
    "loop.exits": "after: worker, one a task of a model whose stack is "
                  "applied several times: the `tokens` its steps "
                  "counted, the mean exit distribution over them "
                  "(`p_exit_1` .. `p_exit_R`) and its mean `entropy`",
    # noise of a masked-diffusion step (layers/diffusion_noise.py): the
    # same route
    "diffusion.noise": "after: worker, one a task of a model trained by "
                       "masked diffusion: the `tokens` its steps saw, how "
                       "many of them the records' noise `masked`, and the "
                       "mean noise level `t_mean` of its sequences",
    # gates of a delta rule with a decay a key channel
    # (layers/delta_gates.py): the same route
    "kda.gates": "after: worker, one a task of a model with such "
                 "`layers`: the mean `retention` exp(g) of their state's "
                 "rows a token, the mean write strength `beta`, and the "
                 "share of gate values within 1% of the bound "
                 "(`at_bound_share`)",
}

#: ``jax.named_scope`` names on device ops (op metadata only; they show
#: as path components of an op's ``op_name`` in HLO and in the trace).
#: PS trainer: fwd_bwd, dense_update, sparse_apply > (grad_accumulate,
#: sparse_adam).  Dense trainer: fwd_bwd > (attn, mlp, lm_head_loss),
#: optimizer; with the hybrid expert model (model_zoo/qwen3_next) fwd_bwd
#: > (gdn > (gdn_mix, gdn_scan), attn, moe > (moe_route, moe_experts,
#: moe_shared), lm_head_loss); with the state-space hybrid (model_zoo/nemotron_h)
#: fwd_bwd > (ssm > ssm_scan, attn, moe > (...), lm_head_loss).  With the
#: latent-attention model (model_zoo/deepseek_v2): fwd_bwd > (attn >
#: (mla_latent, mla_core), mlp, moe > (...), lm_head_loss).  With the
#: window-and-full-attention model (model_zoo/laguna): fwd_bwd > (attn >
#: (attn_full | attn_window, attn_gate), mlp, moe > (...), lm_head_loss).
#: With the looped stack (model_zoo/ouro): fwd_bwd > (loop > (attn >
#: (attn_proj, attn_rotary, attn_full), mlp, block_norm), lm_head_loss,
#: exit_gate).  With the block-diffusion stack (model_zoo/sdar): fwd_bwd >
#: (attn > (attn_proj, attn_rotary, attn_blockdiff), moe > (...),
#: lm_head_loss).  With the delta-attention hybrid (model_zoo/ling):
#: fwd_bwd > (kda > (kda_mix, kda_gate, kda_scan), attn > (mla_latent,
#: mla_core, attn_gate), mlp, moe > (...), lm_head_loss).
DEVICE_SCOPES = (
    "fwd_bwd", "dense_update", "sparse_apply", "grad_accumulate",
    "sparse_adam", "attn", "mlp", "lm_head_loss", "optimizer",
    "gdn", "gdn_scan", "moe", "moe_route", "moe_experts", "moe_shared",
    "ssm", "ssm_scan", "gdn_mix", "mla_latent", "mla_core",
    "attn_full", "attn_window", "attn_gate", "attn_proj", "attn_rotary",
    "loop", "block_norm", "exit_gate", "attn_blockdiff",
    "kda", "kda_mix", "kda_gate", "kda_scan",
)

#: Size bound on the flight recorder's final registry snapshot: the
#: journal is size-capped, and a pathological registry must not spend
#: the whole budget on one exit record.
MAX_REGISTRY_SNAPSHOT_BYTES = 32 << 10


def covered_seconds(intervals) -> float:
    """The length of the UNION of (start, end) intervals: what spans
    that nest or overlap cover, nothing counted twice."""
    covered, reach = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        if hi > reach:
            covered += hi - max(lo, reach)
            reach = hi
    return covered


def annotate(name: str, **fields):
    """The profiler sink: a ``jax.profiler.TraceAnnotation(name)`` to
    enter around the real interval of the work, or a null context in a
    process that has not imported jax (never imports it).  Scalar
    fields ride along as the event's stats."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    try:
        return jax.profiler.TraceAnnotation(name, **fields)
    except Exception:  # a profiler quirk must never break the work
        return contextlib.nullcontext()


@dataclass
class Span:
    """One open (or closed) span.  Mutable fields accumulate while the
    context manager is open; closing journals the record."""

    name: str
    trace_id: str = ""
    span_id: str = ""
    parent_span_id: str = ""
    start_ts: float = 0.0
    start_monotonic: float = 0.0
    fields: dict = field(default_factory=dict)
    #: Restores the span that was current before this one (open_span).
    context_token: object = None


class Tracer:
    """Process-wide span factory + context carrier.

    One instance per process (module-level ``tracer()``); tests may
    build their own with an injected journal.  The current span lives
    in a ``ContextVar`` — each thread (and each ``contextvars`` context)
    sees its own ancestry, so the master's gRPC handler threads and the
    worker's task loop never cross-parent.
    """

    def __init__(self, journal=None, proc: str = ""):
        self._lock = make_lock("Tracer._lock")
        self._journal = journal
        # Pid + random salt + in-process seq: the pid alone is NOT a
        # process-unique discriminator on the k8s substrate (every pod's
        # main process is PID 1), and colliding span ids would cross-link
        # different workers' subtrees in the assembled trace.  The salt
        # is identity, not schedule — the determinism-replay rule (seeded
        # schedules) is untouched.
        self._prefix = (
            f"{os.getpid():x}{os.urandom(3).hex()}.{next(_TRACER_SEQ)}"
        )
        self._seq = itertools.count(1)
        self._proc = proc or f"pid-{os.getpid()}"
        self._current: contextvars.ContextVar = contextvars.ContextVar(
            f"elasticdl_span_{self._prefix}", default=None
        )
        # Open spans, for the crash flight recorder.  Keyed by span_id.
        self._open: Dict[str, Span] = {}  # guarded-by: _lock

    # -- identity -------------------------------------------------------

    @property
    def proc(self) -> str:
        return self._proc

    def set_process(self, label: str) -> None:
        """Name this process on the assembled trace (``master``,
        ``worker_3``); defaults to ``pid-<n>``."""
        if label:
            self._proc = str(label)

    def mint_span_id(self) -> str:
        """A fresh process-unique span id (callers that must send the id
        over the wire BEFORE the span's outcome is known — e.g. the
        get_task client span, whose trace id arrives in the response)."""
        return f"s-{self._prefix}-{next(self._seq)}"

    # -- context --------------------------------------------------------

    def current(self) -> Optional[Span]:
        return self._current.get()

    def current_span_id(self) -> str:
        span = self._current.get()
        return span.span_id if span is not None else ""

    def current_trace_id(self) -> str:
        span = self._current.get()
        return span.trace_id if span is not None else ""

    # -- span emission --------------------------------------------------

    def _journal_ref(self):
        if self._journal is not None:
            return self._journal
        from elasticdl_tpu import obs  # lazy: obs/__init__ imports us

        return obs.journal()

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        trace_id: str = "",
        parent_id: Optional[str] = None,
        root: bool = False,
        span_id: str = "",
        **fields,
    ):
        """Open a span; yields the ``Span`` (callers read ``span_id`` to
        propagate it over RPC metadata).  ``trace_id`` and parentage
        inherit from the enclosing span when not given; ``root=True``
        with a trace id makes this THE root span (span_id == trace_id,
        the cross-process parenting convention)."""
        span = self.open_span(
            name, trace_id=trace_id, parent_id=parent_id, root=root,
            span_id=span_id, **fields
        )
        try:
            with annotate(name):
                yield span
        except BaseException as exc:
            span.fields.setdefault("error", type(exc).__name__)
            raise
        finally:
            self.close_span(span)

    def open_span(
        self,
        name: str,
        trace_id: str = "",
        parent_id: Optional[str] = None,
        root: bool = False,
        span_id: str = "",
        **fields,
    ) -> Span:
        """The opening half of ``span()``, for an interval that no one
        ``with`` block holds (a process's boot span opens in ``main`` and
        closes where the process starts serving): the span becomes the
        current one until ``close_span``, which must run in the same
        thread."""
        parent = self._current.get()
        if not trace_id and parent is not None:
            trace_id = parent.trace_id
        if parent_id is None:
            parent_id = parent.span_id if parent is not None else ""
        if root and trace_id:
            span_id = trace_id
        if not parent_id and trace_id and span_id != trace_id:
            # Contextless span of a known trace: hang it off the trace
            # root (span_id == trace_id by convention) — the worker's
            # top-level task span has no enclosing span but is still a
            # child of the master's task.lifetime.
            parent_id = trace_id
        span = Span(
            name=name,
            trace_id=trace_id,
            span_id=span_id or self.mint_span_id(),
            parent_span_id=parent_id,
            start_ts=time.time(),
            start_monotonic=time.monotonic(),
            fields=dict(fields),
        )
        with self._lock:
            self._open[span.span_id] = span
        span.context_token = self._current.set(span)
        return span

    def close_span(self, span: Span) -> dict:
        """The closing half: journal the span with its length."""
        try:
            self._current.reset(span.context_token)
        except ValueError:
            pass  # closed in another thread than it was opened in
        duration_s = max(0.0, time.monotonic() - span.start_monotonic)
        with self._lock:
            self._open.pop(span.span_id, None)
        return self._emit(span, duration_s)

    def record_span(
        self,
        name: str,
        start_ts: float,
        duration_s: float,
        trace_id: str = "",
        parent_id: str = "",
        span_id: str = "",
        root: bool = False,
        **fields,
    ) -> dict:
        """Journal an after-the-fact span (start/duration measured by the
        caller — task lifetimes, rendezvous formation, phase windows).
        Does not touch the context; returns the journal record."""
        if root and trace_id:
            span_id = trace_id
        if not parent_id and trace_id and not root and span_id != trace_id:
            parent_id = trace_id  # same root convention as span()
        span = Span(
            name=name,
            trace_id=trace_id,
            span_id=span_id or self.mint_span_id(),
            parent_span_id=parent_id,
            start_ts=start_ts,
            fields=dict(fields),
        )
        return self._emit(span, max(0.0, duration_s))

    def _emit(self, span: Span, duration_s: float) -> dict:
        record = {
            "name": span.name,
            "duration_s": round(duration_s, 6),
            "start_ts": round(span.start_ts, 6),
            "span_id": span.span_id,
            "proc": self._proc,
        }
        if span.trace_id:
            record["trace_id"] = span.trace_id
        if span.parent_span_id:
            record["parent_span_id"] = span.parent_span_id
        record.update(span.fields)
        return self._journal_ref().record("span", **record)

    def record_window_spans(
        self, window: dict, end_ts: Optional[float] = None
    ) -> int:
        """Journal the step-anatomy phases of one sealed dispatch window
        as child spans of the CURRENT span (no-op outside a span — phase
        detail without a task context has no tree to hang from).

        The anatomy keeps exclusive per-phase totals, not raw intervals
        (a window can cover hundreds of batches; per-interval spans
        would swamp the journal), so the phases lay out sequentially in
        canonical order ending at ``end_ts`` — a faithful AGGREGATE
        waterfall: phases are exclusive by contract, so their sum is the
        window's accounted wall time.  Returns the number of spans."""
        parent = self._current.get()
        if parent is None or not isinstance(window, dict):
            return 0
        end = time.time() if end_ts is None else float(end_ts)
        phase_seconds = [
            (phase, float(window[phase]))
            for phase in _WINDOW_PHASES
            if isinstance(window.get(phase), (int, float))
            and window[phase] > 0
        ]
        cursor = end - sum(seconds for _, seconds in phase_seconds)
        emitted = 0
        for phase, seconds in phase_seconds:
            self.record_span(
                f"step.{phase}",
                start_ts=cursor,
                duration_s=seconds,
                trace_id=parent.trace_id,
                parent_id=parent.span_id,
                steps=window.get("steps"),
            )
            cursor += seconds
            emitted += 1
        return emitted

    # -- crash flight recorder -----------------------------------------

    def open_spans(self) -> Dict[str, Span]:
        with self._lock:
            return dict(self._open)

    def flush_open(self, reason: str = "shutdown") -> int:
        """Journal every still-open span with its duration so far and a
        ``flushed`` marker — the trace tail a preempted worker leaves
        behind.  Idempotent per span (flushed spans are dropped from the
        open set; the normal close at unwind would re-journal, but
        SIGTERM->SystemExit unwinding and atexit never both complete)."""
        with self._lock:
            open_spans = list(self._open.values())
            self._open.clear()
        now = time.monotonic()
        for span in open_spans:
            span.fields.setdefault("flushed", reason)
            self._emit(
                span,
                max(0.0, now - span.start_monotonic)
                if span.start_monotonic
                else 0.0,
            )
        return len(open_spans)


_tracer = Tracer()


def tracer() -> Tracer:
    """The process-wide default tracer (what ``obs.span`` journals
    through)."""
    return _tracer


def span(name: str, **kwargs):
    """Module-level shorthand for ``tracer().span(...)``."""
    return _tracer.span(name, **kwargs)


def record_span(name: str, start_ts: float, duration_s: float, **kwargs):
    return _tracer.record_span(name, start_ts, duration_s, **kwargs)


def record_child_span(name: str, start_ts: float, duration_s: float,
                      **fields):
    """An after-the-fact span as a child of the CURRENT span (a task's
    summed reads under its `worker.task`); a root where none is open."""
    return _tracer.record_span(
        name, start_ts, duration_s,
        trace_id=_tracer.current_trace_id(),
        parent_id=_tracer.current_span_id(), **fields
    )


def set_process(label: str) -> None:
    _tracer.set_process(label)


# ---------------------------------------------------------------------------
# Process start
# ---------------------------------------------------------------------------

_main_start_ts: Optional[float] = None
_proc_start_recorded = False


def note_main_start() -> None:
    """Call on the first line of a process's `main`: the end of
    `proc.start` (the first note wins)."""
    global _main_start_ts
    if _main_start_ts is None:
        _main_start_ts = time.time()


def _process_created_ts() -> Optional[float]:
    """Wall-clock time the kernel created this process: `starttime` of
    /proc/self/stat (clock ticks after boot) against /proc/uptime."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime_s = float(f.read().split()[0])
        age_s = uptime_s - ticks / os.sysconf("SC_CLK_TCK")
        return time.time() - max(0.0, age_s)
    except (OSError, ValueError, IndexError):
        return None


def record_proc_start() -> Optional[dict]:
    """Journal `proc.start` once the process has a journal: process
    creation -> the first line of main (interpreter start and the
    imports before main), and with it what main timed before there was
    a journal (`early_span`).  Nothing where main never noted its start
    or /proc cannot say when the process was created."""
    global _proc_start_recorded
    if _proc_start_recorded or _main_start_ts is None:
        return None
    _proc_start_recorded = True
    parent_id = _boot_span.span_id if _boot_span is not None else ""
    for name, start_ts, duration_s in _early_spans:
        _tracer.record_span(name, start_ts, duration_s, parent_id=parent_id)
    del _early_spans[:]
    created = _process_created_ts()
    if created is None:
        return None
    return _tracer.record_span(
        "proc.start", created, max(0.0, _main_start_ts - created)
    )


#: (name, start_ts, duration_s) of spans that closed before the process
#: had its journal; `record_proc_start` journals them.
_early_spans: list = []


@contextlib.contextmanager
def early_span(name: str):
    """A part of main that ends before the process has its journal (the
    master's imports, ahead of its arguments): timed here, journaled by
    `record_proc_start` as a child of the boot span."""
    start_ts, start = time.time(), time.monotonic()
    try:
        yield
    finally:
        _early_spans.append((name, start_ts, time.monotonic() - start))


_boot_span: Optional[Span] = None


def begin_boot(name: str) -> None:
    """Call on the first line of a process's `main`, in place of
    `note_main_start`: the end of `proc.start` and the start of the
    process's boot span (`master.boot` / `worker.boot`), which stays the
    current span of the main thread, so that what main opens until
    `end_boot` is its child.  The first call of a process wins (`client
    main` runs before the master's assembly)."""
    global _boot_span
    if _main_start_ts is None and _boot_span is None:
        note_main_start()
        _boot_span = _tracer.open_span(name)
        # One clock read for the end of proc.start and the boot's start.
        _boot_span.start_ts = _main_start_ts


def end_boot(**fields) -> Optional[dict]:
    """Close and journal the boot span (by now the process has its
    journal); nothing in a process whose main opened none, or twice."""
    global _boot_span
    span, _boot_span = _boot_span, None
    if span is None:
        return None
    span.fields.update(fields)
    return _tracer.close_span(span)


# ---------------------------------------------------------------------------
# Crash flight recorder
# ---------------------------------------------------------------------------

_flight_recorder_installed = False


def _registry_snapshot_record(reason: str) -> dict:
    """A bounded final-metrics record: the full registry dump when it
    fits, else a families-only summary (the journal's size cap must not
    be spent on one exit record)."""
    from elasticdl_tpu import obs

    record = {"reason": reason, "proc": _tracer.proc}
    try:
        metrics = obs.registry().to_dict()
        payload = json.dumps(metrics, default=str)
        if len(payload.encode("utf-8")) <= MAX_REGISTRY_SNAPSHOT_BYTES:
            record["metrics"] = metrics
        else:
            record["metrics_truncated"] = True
            record["families"] = sorted(metrics)
    except Exception:  # never let the recorder break process exit
        record["metrics_error"] = True
    return record


def flush_flight_record(reason: str = "shutdown") -> int:
    """Flush open spans + a final registry snapshot to the journal.
    Safe to call directly from fatal-error handlers; the atexit hook
    calls it too (flush_open is idempotent, the snapshot is not —
    repeated snapshots are harmless, just redundant)."""
    from elasticdl_tpu import obs

    flushed = _tracer.flush_open(reason)
    obs.journal().record(
        "registry_snapshot", **_registry_snapshot_record(reason)
    )
    return flushed


def install_flight_recorder() -> bool:
    """Register the atexit flush (once per process).  SIGTERM reaches it
    through the worker main's SIGTERM->SystemExit conversion; SIGKILL
    cannot be caught — the pod manager's grace period is the contract."""
    global _flight_recorder_installed
    if _flight_recorder_installed:
        return False
    _flight_recorder_installed = True
    atexit.register(_atexit_flush)
    return True


def _atexit_flush():
    try:
        flushed = flush_flight_record("shutdown")
        if flushed:
            logger.info(
                "Flight recorder flushed %d open span(s) at exit", flushed
            )
    except Exception:
        logger.exception("Flight-recorder flush failed at exit")
