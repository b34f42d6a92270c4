"""Step-time anatomy: the compute-plane profiling ledger.

The obs plane (telemetry/goodput) can say a worker is slow and price a
rescale to the second, but not *why a step is slow*: data-starved,
retrace-storming, or device-bound.  This module decomposes each step's
wall time into exclusive sub-phases using HOST-side clocks strictly
outside traced code (the trace-purity rule stays green — no journal,
registry, or lock call of this module ever executes under jit):

- ``data_wait``  — host waiting for records (reader/parse/batch, task
  queue wait on the lockstep broadcast);
- ``stage``      — host->device staging (``stage_batch``/``stage_window``);
- ``compile``    — dispatches during which a watched jitted entrypoint
  compiled (lowering/retrace; detected via the jit compile-cache size,
  polled per dispatch — never inside the traced region);
- ``execute``    — the dispatch of an already-compiled program: how
  long the call took to return, which under asynchronous dispatch is a
  dispatch clock and not the device's time;
- ``device_wait`` — the task's one wait for the device (the read of its
  last loss, `step.device_wait`): where the host catches up with what it
  dispatched.  It stands with ``execute`` on the device's side, and is
  booked after the fact in the window the task sealed last
  (``note_device_wait``), never in the next task's first;
- ``bookkeep``   — bookkeeping host work between dispatches (version
  reports, telemetry folds, counters).  A checkpoint save and the
  profiler's stop run OUTSIDE it: the save has its own
  ``checkpoint.save`` span and goodput phase, the dump its
  ``profile_window`` close event.

Every ``phase()`` / ``dispatch()`` call also enters a profiler
annotation of the span's name for its real interval (``step.<phase>``;
``step.dispatch`` for a dispatch, whichever of compile / execute it is
booked under afterwards) — obs/tracing.py ``annotate``, a no-op without
jax or without a running profile.

One more clock rides BESIDE the exclusive phases: ``overlap_s``, the
async staging engine's credit ledger (data/pipeline.py).  Host work
that ran CONCURRENTLY with device execution — parse/prefetch hidden
behind a dispatched window, ``stage_window`` issued while the previous
window was still executing — costs no step-loop latency, so booking it
as ``data_wait``/``stage`` would lie about the bottleneck, and dropping
it would hide that the pipeline is doing real work.  ``overlap_s`` is
deliberately NOT in ``PHASES``: the exclusive phase fractions still sum
to 1.0 over wall time actually serialized on the step loop, and the
overlap credit is reported alongside (windows, snapshot scalar,
``obs.top``'s OV% column, ``obs.report``'s worker lines).

On top of the phase clocks it keeps retrace counters keyed by jitted
function, the device-memory high-water mark, and a per-zoo-model
analytic FLOPs table (``MODEL_FLOPS``) that turns measured examples/s
into MFU and a roofline ``bound:`` verdict (compute / hbm / host /
sparse-row) — the same accounting BENCH_r04 derived by hand.

Windowed summaries ride the telemetry heartbeat: ``WorkerTelemetry``
embeds ``StepAnatomy.snapshot()`` under the ``anatomy`` key (bounded;
the snapshot serializer trims windows oldest-first near the 4 KiB
heartbeat budget), the master's ``TelemetryAggregator`` folds fleet
phase-fraction gauges (bounded ``phase`` label only — per-function
retrace names are journal-only per the cardinality rule), journals
``step_anatomy`` events, and upgrades straggler evidence from "slow" to
"slow because data_wait is Nx the fleet median".  ``obs.top`` renders
per-worker phase-fraction columns and ``obs.report`` a job-level
compute-phase attribution table (docs/observability.md "Step anatomy").
"""

from __future__ import annotations

import contextlib
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from elasticdl_tpu.analysis.runtime import make_lock
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.obs.tracing import annotate

logger = get_logger("obs.stepstats")

#: The exclusive sub-phases of a training step's wall time.
PHASES = (
    "data_wait", "stage", "compile", "execute", "device_wait", "bookkeep",
)

#: The exclusive sub-phases of one SERVING request's wall time (the
#: serving plane's twin of PHASES — serving/batcher.py stamps them,
#: serving/ledger.py accounts them, and obs.top's --serving mode
#: renders the per-replica fractions).  ``queue`` = admission to batch
#: formation, ``batch`` = stacking + bucket padding, ``execute`` = the
#: compiled inference dispatch, ``respond`` = result hand-off.
REQUEST_PHASES = ("queue", "batch", "execute", "respond")

#: Host-side phases: when these dominate, the accelerator is starved.
HOST_PHASES = ("data_wait", "stage", "bookkeep")

#: Roofline verdicts (bounded enum — safe for journal consumers).
BOUNDS = ("compute", "hbm", "host", "sparse-row")

#: Windows a snapshot carries (oldest trimmed first near the heartbeat
#: size budget — see WorkerTelemetry.snapshot_json).
MAX_SNAPSHOT_WINDOWS = 5

#: `jax.devices()[0].device_kind` of one TPU v5e chip.
V5E = "TPU v5 lite"

#: Chip ceilings, keyed by `device_kind` — the ONE table (bench.py
#: imports it).  bf16 peak and HBM bandwidth are the published peaks
#: (Google Cloud documentation, "TPU v5e"); the sparse floor is this
#: repo's own measured gather/scatter floor on that chip (BASELINE.md).
#: A device that is not listed has no ceilings: `roofline()` then emits
#: no mfu / floor_frac / bw_frac, and bench.py refuses to run.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    V5E: {
        "bf16_flops": 197e12,
        "hbm_bytes_per_sec": 819e9,
        "sparse_floor_ns_per_row": 25.0,
    },
}


def local_device_kind() -> Optional[str]:
    """`device_kind` of this process's first local device; None when jax
    is absent or the backend cannot say."""
    try:
        import jax

        return jax.local_devices()[0].device_kind
    except Exception:  # any backend quirk: anatomy must never crash a step
        return None

#: The transformer bench shape (mirrors bench.TRANSFORMER_BENCH — same
#: single-definition rule, cross-checked by the same tier-1 test).
TRANSFORMER_BENCH = dict(
    vocab=32768, d_model=512, num_heads=8, num_layers=4, seq_len=2048,
    mlp_ratio=4,
)


def transformer_flops_per_token(cfg: dict = TRANSFORMER_BENCH) -> float:
    """Analytic fwd FLOPs/token, causal (bench.py's formula verbatim)."""
    d, layers = cfg["d_model"], cfg["num_layers"]
    per_layer = (
        8 * d * d
        + 4 * cfg["mlp_ratio"] * d * d
        + 4 * d * (cfg["seq_len"] / 2)
    )
    return 2 * d * cfg["vocab"] + layers * per_layer


#: Per-zoo-model analytic cost table.  ``train_flops_per_example`` is
#: TRAIN flops (3x fwd); the optional resource keys drive the roofline
#: verdict the way BENCH_r04 derived it by hand:
#: ``sparse_rows_per_example`` -> the device's gather/scatter floor,
#: ``hbm_bytes_per_example`` -> its HBM bandwidth roofline
#: (DEVICE_PEAKS).
MODEL_FLOPS: Dict[str, dict] = {
    # Dense tower is ~50k params; sparse row traffic is the wall
    # (26 embedding rows/sample — BENCH_r04 `bound: sparse-row-count`).
    # The accounting is ENGINE-independent, so the verdict stays
    # truthful under --sparse_kernel=fused too: rows/example is a model
    # property and the 25 ns/row floor is the measured hardware bound
    # on random 512 B row traffic — the fused Pallas kernels
    # (ops/sparse_embedding.py) attack the engine's DISTANCE to that
    # floor (floor_frac rises toward 1.0), not the floor itself.
    "deepfm": {
        "train_flops_per_example": 3 * 2 * 49_856.0,
        "sparse_rows_per_example": 26,
    },
    # 12.3 GFLOP/image train; ~168 MB/image HBM traffic (BASELINE.md:
    # ~21.5 GB/step at batch 128 — the binding roofline).
    "resnet50": {
        "train_flops_per_example": 12.3e9,
        "hbm_bytes_per_example": 21.5e9 / 128,
    },
    # One example = one 2048-token sequence of the bench config.
    "transformer_lm": {
        "train_flops_per_example": (
            3 * transformer_flops_per_token()
            * TRANSFORMER_BENCH["seq_len"]
        ),
    },
}


def infer_model_key(name: str) -> Optional[str]:
    """Best-effort MODEL_FLOPS key from a model-zoo path or job name
    (``.../model_zoo/deepfm/deepfm_functional_api.py`` -> ``deepfm``)."""
    lowered = (name or "").lower()
    for key in MODEL_FLOPS:
        if key in lowered or key.replace("_", "") in lowered.replace("_", ""):
            return key
    return None


def roofline(examples_per_s: float, fractions: Dict[str, float],
             model_key: Optional[str], device_kind: Optional[str]) -> dict:
    """MFU + ``bound:`` verdict for a measured rate, the BENCH_r04 way.

    Priority: a host-starved step is host-bound no matter the model
    (the chip's ceilings are unreachable while it waits); then the
    model's named scarce resource (sparse row traffic / HBM bytes);
    compute is the default when the MXU is the binding engine.

    Every fraction is against `device_kind`'s row of DEVICE_PEAKS; a
    device without one (the CPU included) gets the host verdict only."""
    out: dict = {}
    host_frac = sum(fractions.get(p, 0.0) for p in HOST_PHASES)
    peaks = DEVICE_PEAKS.get(device_kind or "")
    spec = MODEL_FLOPS.get(model_key or "") if peaks else None
    if spec and examples_per_s > 0:
        out["mfu"] = round(
            examples_per_s * spec["train_flops_per_example"]
            / peaks["bf16_flops"],
            4,
        )
    if host_frac > 0.5:
        out["bound"] = "host"
        return out
    if spec and examples_per_s > 0:
        rows = spec.get("sparse_rows_per_example")
        if rows:
            ns_per_row = 1e9 / (examples_per_s * rows)
            out["floor_frac"] = round(
                peaks["sparse_floor_ns_per_row"] / ns_per_row, 3
            )
            if out["floor_frac"] > 0.5:
                out["bound"] = "sparse-row"
                return out
        hbm_bytes = spec.get("hbm_bytes_per_example")
        if hbm_bytes:
            out["bw_frac"] = round(
                examples_per_s * hbm_bytes / peaks["hbm_bytes_per_sec"], 3
            )
            if out["bw_frac"] > out.get("mfu", 0.0):
                out["bound"] = "hbm"
                return out
        out["bound"] = "compute"
    return out


def phase_fractions(seconds: Dict[str, float]) -> Dict[str, float]:
    """Normalize per-phase seconds to fractions of accounted time
    (sums to ~1.0 when any time is accounted; {} otherwise)."""
    total = sum(
        float(seconds.get(p, 0.0)) for p in PHASES
        if isinstance(seconds.get(p, 0.0), (int, float))
    )
    if total <= 0:
        return {}
    return {
        p: round(float(seconds.get(p, 0.0)) / total, 4)
        for p in PHASES
        if seconds.get(p)
    }


def device_memory_hwm_mb() -> Optional[float]:
    """Max ``peak_bytes_in_use`` over local devices, in MiB — None when
    the backend exposes no memory stats (CPU) or jax is absent."""
    try:
        import jax

        peaks = []
        for device in jax.local_devices():
            stats = device.memory_stats()
            if stats and "peak_bytes_in_use" in stats:
                peaks.append(float(stats["peak_bytes_in_use"]))
        if peaks:
            return round(max(peaks) / 2**20, 1)
    except Exception:  # any backend quirk: anatomy must never crash a step
        pass
    return None


class RetraceWatcher:
    """Compile/retrace detection per jitted entrypoint.

    Trainers register a PROVIDER (``() -> {name: jitted_fn}``; re-read
    every poll because trainers compile lazily and recompile on state
    changes).  ``poll()`` reads each function's jit compile-cache size —
    the jax lowering/compile counter — and returns the per-function
    delta since the last poll.  Polled on the HOST between dispatches,
    never under trace."""

    def __init__(self):
        # Own lock: poll() runs on the task-loop thread while the
        # heartbeat thread reads `compiles` for the snapshot — an
        # unlocked dict iteration there can raise mid-compile-storm,
        # exactly when the data matters most.
        self._lock = make_lock("RetraceWatcher._lock")
        self._providers: List[Callable[[], Optional[Dict[str, object]]]] = []  # guarded-by: _lock
        self._last: Dict[str, int] = {}  # guarded-by: _lock
        self._compiles: Dict[str, int] = {}  # guarded-by: _lock

    def watch(self, provider: Callable[[], Optional[Dict[str, object]]]):
        with self._lock:
            self._providers.append(provider)

    @staticmethod
    def _cache_size(fn) -> Optional[int]:
        try:
            return int(fn._cache_size())
        except Exception:
            return None

    def poll(self) -> Dict[str, int]:
        """{fn_name: new compiles} since the last poll (empty = no
        compile happened; the dispatch ran a cached executable)."""
        delta: Dict[str, int] = {}
        with self._lock:
            providers = list(self._providers)
            for provider in providers:
                try:
                    fns = provider() or {}
                except Exception:
                    continue
                for name, fn in fns.items():
                    if fn is None:
                        continue
                    size = self._cache_size(fn)
                    if size is None:
                        continue
                    prev = self._last.get(name, 0)
                    if size > prev:
                        delta[name] = delta.get(name, 0) + (size - prev)
                        self._compiles[name] = (
                            self._compiles.get(name, 0) + (size - prev)
                        )
                    self._last[name] = max(prev, size)
        return delta

    @property
    def compiles(self) -> Dict[str, int]:
        """Cumulative compiles per watched function (first compile
        included; retraces = compiles beyond the first)."""
        with self._lock:
            return dict(self._compiles)

    def retraces_total(self) -> int:
        with self._lock:
            return sum(max(0, c - 1) for c in self._compiles.values())


class StepAnatomy:
    """Per-worker accumulator decomposing step wall time into PHASES.

    Usage (one instance per worker process, driven from the task loop —
    all clocks are host-side, outside any traced region):

        anatomy = StepAnatomy(worker_id)
        anatomy.watch_jits(trainer.jitted_entrypoints)
        with anatomy.phase("data_wait"):
            batch = next(batches)
        with anatomy.phase("stage"):
            staged = trainer.stage_window(batches)
        with anatomy.dispatch(n_steps, n_examples):
            trainer.train_window(staged)   # books compile OR execute
        with anatomy.phase("bookkeep"):
            report_version()
        maybe_checkpoint()                 # its own span, not bookkeep
        anatomy.close_window()             # one window per dispatch flush

    ``snapshot()`` is called from the heartbeat thread; mutators run on
    the task-loop thread — the lock covers the hand-off."""

    def __init__(
        self,
        worker_id: int = 0,
        clock: Callable[[], float] = time.monotonic,
        max_windows: int = MAX_SNAPSHOT_WINDOWS,
        device_kind: Optional[str] = None,
    ):
        self._lock = make_lock("StepAnatomy._lock")
        self._worker_id = int(worker_id)
        self._clock = clock
        # The device the roofline fractions are judged against; None =
        # ask jax at the first snapshot (the worker's own device).
        self._device_kind = device_kind
        self._watcher = RetraceWatcher()
        self._model_key: Optional[str] = None
        self._open_phase: Optional[str] = None
        # Current-window accumulators.  # guarded-by: _lock
        self._acc = {p: 0.0 for p in PHASES}
        self._acc_steps = 0
        self._acc_examples = 0
        self._acc_compiles = 0
        self._acc_overlap = 0.0
        # Job-lifetime totals.  # guarded-by: _lock
        self._totals = {p: 0.0 for p in PHASES}
        self._overlap_total = 0.0
        self._steps_total = 0
        self._examples_total = 0
        self._windows: deque = deque(maxlen=int(max_windows))

    @property
    def worker_id(self) -> int:
        return self._worker_id

    def set_model(self, key_or_name: Optional[str]) -> Optional[str]:
        """Bind the analytic FLOPs row (exact MODEL_FLOPS key or a path
        to infer one from).  Returns the bound key (None = no row; MFU
        and the roofline verdict are simply omitted)."""
        key = (
            key_or_name
            if key_or_name in MODEL_FLOPS
            else infer_model_key(key_or_name or "")
        )
        with self._lock:
            self._model_key = key
        return key

    @property
    def model_key(self) -> Optional[str]:
        return self._model_key

    def watch_jits(self, provider) -> None:
        """Register a jitted-entrypoint provider (``() -> {name: fn}``)
        for compile/retrace detection — trainers expose
        ``jitted_entrypoints``."""
        self._watcher.watch(provider)

    # -- phase clocks ---------------------------------------------------

    @contextlib.contextmanager
    def phase(self, name: str):
        """Book host wall time under one exclusive sub-phase.  Nesting
        is a caller bug (phases are exclusive by contract) and raises
        immediately rather than silently double-counting."""
        if name not in PHASES:
            raise ValueError(f"unknown step phase {name!r} (not in {PHASES})")
        with self._lock:
            if self._open_phase is not None:
                raise RuntimeError(
                    f"step phase {name!r} opened inside open phase "
                    f"{self._open_phase!r} — sub-phases are exclusive"
                )
            self._open_phase = name
        start = self._clock()
        try:
            with annotate(f"step.{name}"):
                yield
        finally:
            elapsed = max(0.0, self._clock() - start)
            with self._lock:
                self._open_phase = None
                self._acc[name] += elapsed

    def note_phase_seconds(self, name: str, seconds: float) -> None:
        """Book already-measured host seconds under a phase — for
        callers that can only attribute AFTER the fact (e.g. the task
        queue wait, which may turn out to be a WAIT idle poll that must
        NOT count as data_wait)."""
        if name not in PHASES:
            raise ValueError(f"unknown step phase {name!r} (not in {PHASES})")
        with self._lock:
            self._acc[name] += max(0.0, float(seconds))

    def note_device_wait(self, seconds: float) -> None:
        """Book the task's one wait for the device, measured where the
        task's last window is already sealed: into that window and the
        totals, so the seconds stay in the task that dispatched the work
        (the accumulator would carry them into the next task's first
        window)."""
        seconds = max(0.0, float(seconds))
        with self._lock:
            self._totals["device_wait"] += seconds
            if self._windows:
                last = self._windows[-1]
                last["device_wait"] = round(
                    last.get("device_wait", 0.0) + seconds, 6
                )

    def note_overlap_seconds(self, seconds: float) -> None:
        """Book host seconds that ran CONCURRENTLY with device execution
        (async staging engine credit — parse/prefetch/stage hidden
        behind an outstanding dispatch).  Kept OUTSIDE the exclusive
        PHASES so phase fractions keep summing to 1.0 over time actually
        serialized on the step loop."""
        with self._lock:
            self._acc_overlap += max(0.0, float(seconds))

    @contextlib.contextmanager
    def dispatch(self, n_steps: int = 1, n_examples: int = 0):
        """Time one device dispatch; books ``compile`` when a watched
        jitted entrypoint compiled during it (cache-size delta), else
        ``execute``.  Also accumulates the window's step/example
        counts."""
        self._watcher.poll()  # absorb compiles that happened before us
        with self._lock:
            if self._open_phase is not None:
                raise RuntimeError(
                    f"dispatch opened inside open phase "
                    f"{self._open_phase!r} — sub-phases are exclusive"
                )
            self._open_phase = "execute"
        start = self._clock()
        try:
            with annotate("step.dispatch", steps=int(n_steps)):
                yield
        finally:
            elapsed = max(0.0, self._clock() - start)
            compiled = self._watcher.poll()
            phase = "compile" if compiled else "execute"
            with self._lock:
                self._open_phase = None
                self._acc[phase] += elapsed
                self._acc_steps += int(n_steps)
                self._acc_examples += int(n_examples)
                self._acc_compiles += sum(compiled.values())

    def close_window(self) -> Optional[dict]:
        """Seal the current accumulation as one summary window (rides
        the next heartbeat snapshot).  No-op when nothing accumulated."""
        with self._lock:
            accounted = sum(self._acc.values())
            if accounted <= 0 and self._acc_steps == 0 and self._acc_overlap <= 0:
                return None
            window = {"steps": self._acc_steps, "examples": self._acc_examples}
            for p in PHASES:
                if self._acc[p] > 0:
                    window[p] = round(self._acc[p], 6)
                self._totals[p] += self._acc[p]
            if self._acc_compiles:
                window["compiles"] = self._acc_compiles
            if self._acc_overlap > 0:
                window["overlap_s"] = round(self._acc_overlap, 6)
            self._overlap_total += self._acc_overlap
            self._steps_total += self._acc_steps
            self._examples_total += self._acc_examples
            self._windows.append(window)
            self._acc = {p: 0.0 for p in PHASES}
            self._acc_steps = 0
            self._acc_examples = 0
            self._acc_compiles = 0
            self._acc_overlap = 0.0
            return window

    # -- read side ------------------------------------------------------

    def totals(self) -> Dict[str, float]:
        with self._lock:
            return {p: round(s, 6) for p, s in self._totals.items() if s > 0}

    def snapshot(self) -> dict:
        """Bounded JSON-able anatomy summary (the ``anatomy`` sub-dict
        of the telemetry snapshot — docs/observability.md tabulates the
        fields).  Per-function compile counts are journal-only detail;
        they never become metric labels."""
        with self._lock:
            windows = [dict(w) for w in self._windows]
            totals = {
                p: round(s, 6) for p, s in self._totals.items() if s > 0
            }
            steps = self._steps_total
            examples = self._examples_total
            model_key = self._model_key
            overlap_total = self._overlap_total
        snap: dict = {
            "windows": windows,
            "totals": totals,
            "steps": steps,
            "examples": examples,
        }
        if overlap_total > 0:
            snap["overlap_s"] = round(overlap_total, 6)
        compiles = self._watcher.compiles
        if compiles:
            snap["compiles"] = {
                name[:48]: count
                for name, count in sorted(compiles.items())[:8]
            }
            snap["retraces"] = self._watcher.retraces_total()
        hwm = device_memory_hwm_mb()
        if hwm is not None:
            snap["mem_hwm_mb"] = hwm
        accounted = sum(totals.values())
        if accounted > 0 and examples > 0:
            fractions = phase_fractions(totals)
            kind = self._device_kind
            if kind is None:
                kind = self._device_kind = local_device_kind()
            snap.update(
                roofline(examples / accounted, fractions, model_key, kind)
            )
        return snap


# ---------------------------------------------------------------------------
# Wire-side sanitation (the master ingests anatomy off the heartbeat)
# ---------------------------------------------------------------------------

_WINDOW_INT_FIELDS = ("steps", "examples", "compiles")
_WINDOW_FLOAT_FIELDS = ("overlap_s",)  # beside the PHASES floats
_SCALAR_FLOAT_FIELDS = ("mem_hwm_mb", "mfu", "floor_frac", "bw_frac",
                        "overlap_s")
_SCALAR_INT_FIELDS = ("steps", "examples", "retraces")
MAX_WIRE_WINDOWS = 8


def _clean_number(value) -> Optional[float]:
    if isinstance(value, (int, float)) and not isinstance(value, bool):
        return float(value)
    return None


def sanitize_anatomy(anatomy) -> Optional[dict]:
    """Whitelist a wire anatomy sub-dict.  Unlike the snapshot's core
    liveness fields (where a wrong type rejects the whole snapshot), a
    malformed anatomy degrades to None — the heartbeat's liveness/step
    signal must survive a skewed worker's broken anatomy."""
    if not isinstance(anatomy, dict):
        return None
    clean: dict = {}
    windows = anatomy.get("windows")
    if isinstance(windows, list):
        clean_windows = []
        for window in windows[-MAX_WIRE_WINDOWS:]:
            if not isinstance(window, dict):
                return None
            clean_window = {}
            for key in _WINDOW_INT_FIELDS:
                value = _clean_number(window.get(key))
                if value is not None:
                    clean_window[key] = int(value)
            for phase in PHASES:
                value = _clean_number(window.get(phase))
                if value is not None:
                    clean_window[phase] = value
            for key in _WINDOW_FLOAT_FIELDS:
                value = _clean_number(window.get(key))
                if value is not None:
                    clean_window[key] = value
            clean_windows.append(clean_window)
        clean["windows"] = clean_windows
    totals = anatomy.get("totals")
    if isinstance(totals, dict):
        clean_totals = {
            phase: _clean_number(totals.get(phase))
            for phase in PHASES
            if _clean_number(totals.get(phase)) is not None
        }
        if clean_totals:
            clean["totals"] = clean_totals
    for key in _SCALAR_INT_FIELDS:
        value = _clean_number(anatomy.get(key))
        if value is not None:
            clean[key] = int(value)
    for key in _SCALAR_FLOAT_FIELDS:
        value = _clean_number(anatomy.get(key))
        if value is not None:
            clean[key] = value
    bound = anatomy.get("bound")
    if isinstance(bound, str) and bound in BOUNDS:
        clean["bound"] = bound
    compiles = anatomy.get("compiles")
    if isinstance(compiles, dict):
        clean_compiles = {}
        valid = sorted(
            (name, count)
            for name, count in compiles.items()
            if isinstance(name, str) and _clean_number(count) is not None
        )
        for name, count in valid[:8]:
            clean_compiles[name[:48]] = int(count)
        if clean_compiles:
            clean["compiles"] = clean_compiles
    return clean or None


def journal_anatomy(worker_id: int, anatomy: dict) -> Optional[dict]:
    """Record one ``step_anatomy`` journal event from an anatomy dict
    (cumulative totals — windows stay heartbeat-only).  Shared by the
    master's TelemetryAggregator (wire snapshots) and workers without a
    telemetry carrier (Local mode, which journals its own anatomy at
    task end into the process journal).  Returns the record, or None
    when there is nothing to attribute yet."""
    from elasticdl_tpu import obs

    fields = {
        key: value for key, value in anatomy.items() if key != "windows"
    }
    fractions = phase_fractions(anatomy.get("totals") or {})
    if fractions:
        fields["fractions"] = fractions
        fields["dominant_phase"] = max(fractions, key=fractions.get)
    elif not fields:
        return None
    return obs.journal().record(
        "step_anatomy", worker_id=worker_id, **fields
    )


def fleet_attribution(snapshots: Dict[int, dict]) -> dict:
    """Fold per-worker telemetry snapshots (with ``anatomy``) into the
    fleet view: summed per-phase seconds, normalized fractions, the
    bottleneck phase, and each worker's dominant phase.  Per-worker
    detail stays journal/report-side — only the bounded per-phase
    aggregates feed metrics."""
    fleet_seconds = {p: 0.0 for p in PHASES}
    workers: Dict[int, dict] = {}
    retraces = 0
    for wid, snapshot in snapshots.items():
        anatomy = snapshot.get("anatomy")
        if not isinstance(anatomy, dict):
            continue
        retraces += int(anatomy.get("retraces", 0) or 0)
        totals = anatomy.get("totals") or {}
        fractions = phase_fractions(totals)
        if not fractions:
            continue
        for phase in PHASES:
            fleet_seconds[phase] += float(totals.get(phase, 0.0))
        workers[wid] = {
            "fractions": fractions,
            "dominant_phase": max(fractions, key=fractions.get),
            "bound": anatomy.get("bound"),
        }
    fractions = phase_fractions(fleet_seconds)
    return {
        "seconds": {p: round(s, 6) for p, s in fleet_seconds.items() if s > 0},
        "fractions": fractions,
        "bottleneck": max(fractions, key=fractions.get) if fractions else None,
        "workers": workers,
        "retraces": retraces,
    }
