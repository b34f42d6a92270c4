"""Step-anatomy plane tests (obs/stepstats.py + PR-8 wiring).

Covers:

- StepAnatomy: phase exclusivity (nesting raises), compile-vs-execute
  booking via real jit retrace detection, retrace counters keyed by
  jitted function, MFU math against the analytic FLOPs table, roofline
  ``bound:`` verdicts, snapshot round-trip through the telemetry
  sanitizer;
- the roofline constants / FLOPs formulas staying in lockstep with
  bench.py (single-truth rule, enforced here);
- telemetry snapshot size budget: an oversized snapshot degrades by
  trimming anatomy windows OLDEST-first, never by dropping the core
  liveness/step fields;
- aggregator: ``step_anatomy`` journal events, fleet phase-fraction
  gauges, straggler evidence upgraded with the dominant phase;
- StepProfiler ``profile_window`` journal events (open/close with the
  trace dir obs.report points at);
- scripts/bench_regress.py: selftest, the synthetic beyond-spread
  regression exiting non-zero with a schema-valid ``bench_regress``
  journal event, untracked rows never gating;
- the check-invariants seeded-violation gate over the new
  instrumentation call sites (trace-purity + metric-label-cardinality);
- the ISSUE acceptance e2e: master + 3 heartbeating workers over real
  gRPC where one worker is artificially data-starved — the straggler
  journal evidence names ``data_wait`` as the dominant phase, and
  ``obs.report`` over that journal attributes it with phase fractions
  summing to ~1.0.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from elasticdl_tpu import obs
from elasticdl_tpu.obs import stepstats
from elasticdl_tpu.obs.stepstats import (
    PHASES,
    RetraceWatcher,
    StepAnatomy,
)
from elasticdl_tpu.obs.telemetry import (
    StragglerDetector,
    TelemetryAggregator,
    WorkerTelemetry,
    sanitize_snapshot,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, seconds):
        self.t += seconds


def _fed_anatomy(worker_id=0, data_wait=0.0, stage=0.0, execute=0.0,
                 bookkeep=0.0, examples=0, steps=1, windows=1):
    """A StepAnatomy with deterministic phase seconds via a fake clock."""
    clock = _Clock()
    anatomy = StepAnatomy(
        worker_id=worker_id, clock=clock, device_kind=stepstats.V5E
    )
    for _ in range(windows):
        if data_wait:
            with anatomy.phase("data_wait"):
                clock.advance(data_wait)
        if stage:
            with anatomy.phase("stage"):
                clock.advance(stage)
        with anatomy.dispatch(steps, examples):
            clock.advance(execute)
        if bookkeep:
            with anatomy.phase("bookkeep"):
                clock.advance(bookkeep)
        anatomy.close_window()
    return anatomy


# ---------------------------------------------------------------------------
# StepAnatomy core
# ---------------------------------------------------------------------------


def test_phase_exclusivity_and_accounting():
    clock = _Clock()
    anatomy = StepAnatomy(worker_id=1, clock=clock)
    with anatomy.phase("data_wait"):
        clock.advance(2.0)
    with anatomy.phase("stage"):
        clock.advance(0.5)
    with anatomy.dispatch(4, 256):
        clock.advance(1.5)
    window = anatomy.close_window()
    assert window["data_wait"] == pytest.approx(2.0)
    assert window["stage"] == pytest.approx(0.5)
    assert window["execute"] == pytest.approx(1.5)
    assert window["steps"] == 4 and window["examples"] == 256
    # Exclusive by contract: nesting is a caller bug and raises.
    with pytest.raises(RuntimeError, match="exclusive"):
        with anatomy.phase("data_wait"):
            with anatomy.phase("execute"):
                pass
    with pytest.raises(RuntimeError, match="exclusive"):
        with anatomy.phase("stage"):
            with anatomy.dispatch(1):
                pass
    with pytest.raises(ValueError):
        with anatomy.phase("no_such_phase"):
            pass
    # The failed opens above must not have corrupted the accounting.
    with anatomy.phase("bookkeep"):
        clock.advance(0.25)
    window = anatomy.close_window()
    assert window["bookkeep"] == pytest.approx(0.25)
    totals = anatomy.totals()
    assert sum(totals.values()) == pytest.approx(4.25)


def test_phase_fractions_sum_to_one():
    anatomy = _fed_anatomy(data_wait=6.0, execute=1.0, bookkeep=0.5,
                           examples=64)
    fractions = stepstats.phase_fractions(anatomy.totals())
    assert sum(fractions.values()) == pytest.approx(1.0, abs=0.01)
    assert max(fractions, key=fractions.get) == "data_wait"


def test_retrace_counting_books_compile_vs_execute():
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: x * 2)
    anatomy = StepAnatomy(worker_id=0)
    anatomy.watch_jits(lambda: {"train_step": fn})
    with anatomy.dispatch(1, 8):
        fn(jnp.ones((4,)))  # first compile
    first = anatomy.close_window()
    assert "compile" in first and "execute" not in first
    assert first["compiles"] == 1
    with anatomy.dispatch(1, 8):
        fn(jnp.ones((4,)))  # cached executable
    second = anatomy.close_window()
    assert "execute" in second and "compile" not in second
    with anatomy.dispatch(1, 8):
        fn(jnp.ones((8,)))  # new shape -> RETRACE
    third = anatomy.close_window()
    assert "compile" in third
    snap = anatomy.snapshot()
    assert snap["compiles"] == {"train_step": 2}
    assert snap["retraces"] == 1  # compiles beyond the first


def test_retrace_watcher_tolerates_lazy_and_broken_providers():
    watcher = RetraceWatcher()
    watcher.watch(lambda: None)
    watcher.watch(lambda: {"unbuilt": None, "odd": object()})

    def exploding():
        raise RuntimeError("trainer not initialized yet")

    watcher.watch(exploding)
    assert watcher.poll() == {}
    assert watcher.retraces_total() == 0


def test_mfu_math_matches_flops_table():
    # 4096 transformer examples in 2.0s of pure execute.
    anatomy = _fed_anatomy(execute=2.0, examples=4096, steps=4)
    anatomy.set_model("transformer_lm")
    snap = anatomy.snapshot()
    flops = stepstats.MODEL_FLOPS["transformer_lm"]["train_flops_per_example"]
    peak = stepstats.DEVICE_PEAKS[stepstats.V5E]["bf16_flops"]
    expected = (4096 / 2.0) * flops / peak
    assert snap["mfu"] == pytest.approx(expected, rel=1e-3)
    assert snap["bound"] == "compute"


def test_roofline_verdicts():
    # Host-starved: data_wait dominates regardless of model.
    v5e = stepstats.V5E
    host = stepstats.roofline(
        1000.0, {"data_wait": 0.7, "execute": 0.3}, "resnet50", v5e
    )
    assert host["bound"] == "host"
    # DeepFM at ~1M samples/s: the BENCH_r04 sparse-row-count wall.
    sparse = stepstats.roofline(975_000.0, {"execute": 1.0}, "deepfm", v5e)
    assert sparse["bound"] == "sparse-row"
    assert sparse["floor_frac"] == pytest.approx(0.634, abs=0.01)
    # ResNet-50 at its measured rate: bandwidth-bound, not MXU-bound.
    hbm = stepstats.roofline(2_665.0, {"execute": 1.0}, "resnet50", v5e)
    assert hbm["bound"] == "hbm"
    assert hbm["bw_frac"] > hbm["mfu"]
    # No FLOPs row -> no verdict invented.
    assert "bound" not in stepstats.roofline(
        10.0, {"execute": 1.0}, None, v5e
    )


@pytest.mark.parametrize("device_kind", ["cpu", None, "TPU v9 imaginary"])
def test_roofline_has_no_peaks_for_an_unlisted_device(device_kind):
    """A peak assumed for a device the table does not list is a number
    about nothing: such a device gets no mfu / floor_frac / bw_frac and
    no chip-side verdict — only the host verdict, which needs no peak."""
    for model in ("deepfm", "resnet50", "transformer_lm"):
        assert stepstats.roofline(
            975_000.0, {"execute": 1.0}, model, device_kind
        ) == {}
    assert stepstats.roofline(
        1000.0, {"data_wait": 0.7, "execute": 0.3}, "resnet50", device_kind
    ) == {"bound": "host"}


def test_roofline_constants_match_bench():
    """Single-truth rule: stepstats' chip ceilings and analytic FLOPs
    must never drift from bench.py's roofline accounting."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_for_test", os.path.join(REPO_ROOT, "bench.py")
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert bench.DEVICE_PEAKS is stepstats.DEVICE_PEAKS
    assert stepstats.TRANSFORMER_BENCH == bench.TRANSFORMER_BENCH
    assert stepstats.transformer_flops_per_token() == pytest.approx(
        bench._transformer_flops_per_token()
    )
    resnet = stepstats.MODEL_FLOPS["resnet50"]
    assert resnet["train_flops_per_example"] == pytest.approx(12.3e9)
    assert resnet["hbm_bytes_per_example"] == pytest.approx(21.5e9 / 128)
    assert stepstats.MODEL_FLOPS["deepfm"]["sparse_rows_per_example"] == 26


def test_infer_model_key():
    assert stepstats.infer_model_key(
        "model_zoo.deepfm.deepfm_functional_api.custom_model"
    ) == "deepfm"
    assert stepstats.infer_model_key("/mz/resnet50/resnet50_subclass.py") == (
        "resnet50"
    )
    assert stepstats.infer_model_key("transformer_lm.custom_model") == (
        "transformer_lm"
    )
    assert stepstats.infer_model_key("census_wide_deep") is None


def test_snapshot_round_trip_through_sanitizer():
    anatomy = _fed_anatomy(worker_id=7, data_wait=1.0, stage=0.25,
                           execute=3.0, examples=512, windows=3)
    telemetry = WorkerTelemetry(worker_id=7)
    telemetry.bind_anatomy(anatomy)
    telemetry.record_steps(4, duration_s=0.04, records=512)
    clean = sanitize_snapshot(json.loads(telemetry.snapshot_json()))
    assert clean is not None
    anatomy_clean = clean["anatomy"]
    assert anatomy_clean["totals"]["data_wait"] == pytest.approx(3.0)
    assert anatomy_clean["totals"]["execute"] == pytest.approx(9.0)
    assert len(anatomy_clean["windows"]) == 3
    assert anatomy_clean["steps"] == 3 and anatomy_clean["examples"] == 1536
    # Wire junk: unknown keys drop, wrong-typed anatomy degrades to
    # absent WITHOUT rejecting the snapshot's core fields.
    assert stepstats.sanitize_anatomy({"totals": {"data_wait": "NaN-ish"}}) \
        is None
    assert stepstats.sanitize_anatomy("not a dict") is None
    hostile = json.loads(telemetry.snapshot_json())
    hostile["anatomy"] = {"bound": "rm -rf /", "junk": 1}
    clean = sanitize_snapshot(hostile)
    assert clean is not None and "anatomy" not in clean
    assert "step_p50_s" in clean
    partial = stepstats.sanitize_anatomy(
        {"totals": {"execute": 1.0, "nonsense": 2.0}, "bound": "hbm",
         "retraces": 3, "compiles": {"train_step": 2, 5: "x"}}
    )
    assert partial == {
        "totals": {"execute": 1.0}, "bound": "hbm", "retraces": 3,
        "compiles": {"train_step": 2},
    }


def test_oversized_snapshot_trims_anatomy_oldest_first(monkeypatch):
    """Satellite: near the 4 KiB heartbeat bound the snapshot sheds
    anatomy windows oldest-first (then the whole sub-dict) — the core
    liveness/step fields always deliver."""
    from elasticdl_tpu.obs import telemetry as telemetry_mod

    anatomy = _fed_anatomy(worker_id=3, data_wait=0.5, execute=1.0,
                           examples=64, windows=5)
    telemetry = WorkerTelemetry(worker_id=3)
    telemetry.bind_anatomy(anatomy)
    telemetry.set_rendezvous(2)
    telemetry.record_steps(4, duration_s=0.04, records=64)
    full = telemetry.snapshot()
    assert len(full["anatomy"]["windows"]) == 5
    newest = full["anatomy"]["windows"][-1]
    # Budget that fits the core snapshot plus ~2 anatomy windows.
    core = dict(full)
    core.pop("anatomy")
    budget = len(json.dumps(core, separators=(",", ":")).encode()) + 220
    monkeypatch.setattr(telemetry_mod, "MAX_SNAPSHOT_BYTES", budget)
    payload = telemetry.snapshot_json()
    assert len(payload.encode()) <= budget
    degraded = json.loads(payload)
    # Core liveness/step fields survive intact.
    for field in ("worker_id", "ts", "steps_total", "step_p50_s",
                  "rendezvous_id", "examples_per_s"):
        assert field in degraded, field
    # Anatomy degraded window-wise, newest window retained first.
    kept = degraded["anatomy"]["windows"]
    assert 0 < len(kept) < 5
    assert kept[-1] == newest
    # An impossibly small budget still ships totals (windows dropped)
    # or, at worst, the core snapshot with no anatomy at all.
    monkeypatch.setattr(
        telemetry_mod, "MAX_SNAPSHOT_BYTES",
        len(json.dumps(core, separators=(",", ":")).encode()) + 10,
    )
    degraded = json.loads(telemetry.snapshot_json())
    assert "anatomy" not in degraded
    assert degraded["steps_total"] == 4
    # The sanitizer accepts every rung of the ladder.
    assert sanitize_snapshot(degraded) is not None


def test_fleet_attribution_unit():
    snapshots = {
        0: {"anatomy": {"totals": {"data_wait": 1.0, "execute": 9.0}}},
        1: {"anatomy": {"totals": {"data_wait": 1.2, "execute": 8.8}}},
        2: {"anatomy": {"totals": {"data_wait": 8.0, "execute": 2.0}}},
        3: {},  # no anatomy: excluded, not a crash
    }
    attribution = stepstats.fleet_attribution(snapshots)
    assert attribution["bottleneck"] == "execute"
    assert sum(attribution["fractions"].values()) == pytest.approx(
        1.0, abs=0.01
    )
    assert attribution["workers"][2]["dominant_phase"] == "data_wait"
    assert 3 not in attribution["workers"]
    empty = stepstats.fleet_attribution({0: {}})
    assert empty["bottleneck"] is None and empty["fractions"] == {}


# ---------------------------------------------------------------------------
# Aggregator wiring: journal events, gauges, straggler evidence
# ---------------------------------------------------------------------------


def _wire_snap(wid, p50, data_wait, execute, retraces=0):
    return json.dumps(
        {
            "v": 1, "worker_id": wid, "ts": time.time(),
            "step_p50_s": p50, "step_p95_s": p50 * 1.2,
            "anatomy": {
                "totals": {"data_wait": data_wait, "execute": execute},
                "steps": 32, "examples": 2048, "retraces": retraces,
                "windows": [
                    {"steps": 32, "data_wait": data_wait,
                     "execute": execute}
                ],
            },
        }
    )


def test_aggregator_journals_step_anatomy_and_phase_gauges(
    obs_registry_snapshot,
):
    aggregator = TelemetryAggregator(journal_interval_s=0.0)
    marker = time.time() - 1
    aggregator.ingest(0, _wire_snap(0, 0.01, 1.0, 9.0, retraces=2))
    aggregator.ingest(1, _wire_snap(1, 0.01, 2.0, 8.0))
    events = [
        e for e in obs.journal().tail(100)
        if e["event"] == "step_anatomy" and e["ts"] >= marker
    ]
    assert len(events) == 2
    event = events[0]
    assert event["worker_id"] == 0
    assert event["totals"] == {"data_wait": 1.0, "execute": 9.0}
    assert event["dominant_phase"] == "execute"
    assert sum(event["fractions"].values()) == pytest.approx(1.0, abs=0.01)
    assert "windows" not in event  # heartbeat-only bulk
    # worker_telemetry events stay lean (no anatomy duplicate).
    telem = [
        e for e in obs.journal().tail(100)
        if e["event"] == "worker_telemetry" and e["ts"] >= marker
    ]
    assert telem and all("anatomy" not in e for e in telem)
    # Fleet gauges: bounded phase label only.
    registry = obs.registry()
    fraction = registry.get("elasticdl_worker_phase_fraction")
    assert fraction.value(phase="execute") == pytest.approx(0.85, abs=0.01)
    assert fraction.value(phase="data_wait") == pytest.approx(0.15, abs=0.01)
    assert registry.get("elasticdl_worker_retraces").value() == 2


def test_straggler_evidence_names_dominant_phase(obs_registry_snapshot):
    aggregator = TelemetryAggregator(
        detector=StragglerDetector(flag_after=2, clear_after=2),
        journal_interval_s=1e9,
    )
    marker = time.time() - 1
    for wid in range(3):
        aggregator.ingest(wid, _wire_snap(wid, 0.01, 0.5, 9.5))
    for _ in range(3):
        aggregator.ingest(3, _wire_snap(3, 0.9, 9.0, 1.0))
    detected = [
        e for e in obs.journal().tail(100)
        if e["event"] == "straggler_detected" and e["ts"] >= marker
    ]
    assert detected and detected[-1]["worker_id"] == 3
    assert detected[-1]["dominant_phase"] == "data_wait"
    assert detected[-1]["phase_ratio"] > 5  # vs the fleet's ~5% median
    attribution = aggregator.fleet_attribution()
    assert attribution["workers"][3]["dominant_phase"] == "data_wait"


def test_note_phase_seconds_books_after_the_fact():
    anatomy = StepAnatomy(worker_id=0)
    anatomy.note_phase_seconds("data_wait", 2.5)
    anatomy.note_phase_seconds("data_wait", -1.0)  # clamped, not subtracted
    window = anatomy.close_window()
    assert window["data_wait"] == pytest.approx(2.5)
    with pytest.raises(ValueError):
        anatomy.note_phase_seconds("idle", 1.0)


def test_device_wait_is_booked_in_the_window_the_task_sealed_last():
    """The task's one wait for the device is measured after its last
    window was sealed: it joins THAT window and the totals, and nothing
    of it is carried into the next task's first window."""
    anatomy = _fed_anatomy(execute=0.1, examples=64, windows=2)
    anatomy.note_device_wait(3.0)
    anatomy.note_device_wait(-1.0)  # clamped, not subtracted
    first, last = anatomy.snapshot()["windows"]
    assert "device_wait" not in first
    assert last["device_wait"] == pytest.approx(3.0)
    assert anatomy.totals()["device_wait"] == pytest.approx(3.0)
    assert anatomy.close_window() is None  # nothing left to carry
    # The wire keeps it, and the fleet view folds it as a phase.
    clean = stepstats.sanitize_anatomy(anatomy.snapshot())
    assert clean["windows"][-1]["device_wait"] == pytest.approx(3.0)
    assert clean["totals"]["device_wait"] == pytest.approx(3.0)
    # A wait with no sealed window (nothing dispatched) still counts.
    empty = StepAnatomy(worker_id=0)
    empty.note_device_wait(1.0)
    assert empty.totals() == {"device_wait": 1.0}


def test_device_wait_stands_on_the_devices_side():
    """Not a host phase: a job whose wall time is the wait for the device
    is not host-bound, and the host fraction is what the host phases took
    of a total that now holds the wait."""
    assert "device_wait" in PHASES
    assert "device_wait" not in stepstats.HOST_PHASES
    anatomy = _fed_anatomy(data_wait=0.3, execute=0.1, examples=64)
    host_alone = stepstats.phase_fractions(anatomy.totals())
    assert host_alone["data_wait"] == pytest.approx(0.75)
    assert stepstats.roofline(1.0, host_alone, None, None)["bound"] == "host"
    anatomy.note_device_wait(3.6)
    fractions = stepstats.phase_fractions(anatomy.totals())
    assert fractions["device_wait"] == pytest.approx(0.9)
    assert fractions["data_wait"] == pytest.approx(0.075)
    assert "bound" not in stepstats.roofline(1.0, fractions, None, None)
    assert sum(fractions.values()) == pytest.approx(1.0)


def test_journal_anatomy_helper(obs_registry_snapshot):
    marker = time.time()
    record = stepstats.journal_anatomy(
        4, {"totals": {"data_wait": 3.0, "execute": 1.0}, "steps": 8,
            "windows": [{"steps": 8}]}
    )
    assert record["worker_id"] == 4
    assert record["dominant_phase"] == "data_wait"
    assert "windows" not in record
    assert stepstats.journal_anatomy(4, {}) is None
    events = [
        e for e in obs.journal().tail(20)
        if e["event"] == "step_anatomy" and e.get("worker_id") == 4
        and e["ts"] >= marker
    ]
    assert len(events) == 1


def test_fleet_attribution_cache_invalidates_on_ingest(
    obs_registry_snapshot,
):
    aggregator = TelemetryAggregator(journal_interval_s=1e9)
    aggregator.ingest(0, _wire_snap(0, 0.01, 1.0, 9.0))
    first = aggregator.fleet_attribution()
    assert aggregator.fleet_attribution() is first  # memoized per ingest
    aggregator.ingest(1, _wire_snap(1, 0.01, 9.0, 1.0))
    second = aggregator.fleet_attribution()
    assert second is not first
    assert second["fractions"]["data_wait"] == pytest.approx(0.5, abs=0.01)


def test_report_tolerates_degenerate_step_anatomy(tmp_path):
    """Forensics over arbitrary journals: zero-valued or garbage totals
    skip the worker instead of killing the whole postmortem CLI."""
    from elasticdl_tpu.obs import report

    events = [
        {"ts": 1.0, "event": "master_start", "job_name": "j"},
        {"ts": 2.0, "event": "step_anatomy", "worker_id": 0,
         "totals": {"data_wait": 0.0}},
        {"ts": 2.5, "event": "step_anatomy", "worker_id": 1,
         "totals": "garbage"},
        {"ts": 3.0, "event": "step_anatomy", "worker_id": 2,
         "totals": {"execute": 2.0}},
    ]
    summary = report.summarize(events)
    assert list(summary["compute"]["workers"]) == [2]
    report.render_report(summary)  # must not raise
    # All-degenerate journals simply have no compute section.
    summary = report.summarize(events[:3])
    assert "compute" not in summary
    report.render_report(summary)


# ---------------------------------------------------------------------------
# StepProfiler -> profile_window journal events
# ---------------------------------------------------------------------------


def test_profiler_journals_profile_window(tmp_path):
    from elasticdl_tpu.common.profiler import StepProfiler

    marker = time.time() - 1
    profiler = StepProfiler(str(tmp_path), "1,2", worker_id=5)
    profiler.before_steps(0)  # step 1 is in [1, 2): trace opens
    profiler.after_steps(1)   # last in-window step done: trace closes
    events = [
        e for e in obs.journal().tail(50)
        if e["event"] == "profile_window" and e["ts"] >= marker
    ]
    actions = [e["action"] for e in events]
    assert actions == ["open", "close"], events
    for event in events:
        assert event["worker_id"] == 5
        assert event["step_start"] == 1 and event["step_end"] == 2
        assert event["trace_dir"].endswith("worker_5")


# ---------------------------------------------------------------------------
# bench_regress gate
# ---------------------------------------------------------------------------


def _run_bench_regress(*argv, timeout=120):
    return subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "scripts", "bench_regress.py"), *argv],
        capture_output=True, text=True, timeout=timeout,
        cwd=REPO_ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_bench_regress_selftest():
    result = _run_bench_regress("--selftest")
    assert result.returncode == 0, result.stderr + result.stdout


def test_bench_regress_synthetic_regression_exits_nonzero(tmp_path):
    """ISSUE acceptance: a synthetic beyond-spread regression exits
    non-zero AND journals a schema-valid bench_regress event."""
    result = _run_bench_regress(
        "--synthetic", "regress", "--journal-dir", str(tmp_path)
    )
    assert result.returncode == 1, result.stderr + result.stdout
    assert "REGRESSED" in result.stdout
    journal_path = tmp_path / "events.jsonl"
    assert journal_path.exists()
    events = [
        json.loads(line)
        for line in journal_path.read_text().splitlines() if line
    ]
    regress = [e for e in events if e["event"] == "bench_regress"]
    assert len(regress) == 1
    assert regress[0]["verdict"] == "regressed"
    assert regress[0]["regressed"] == 1
    validator = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "scripts", "validate_journal.py"),
         str(journal_path)],
        capture_output=True, text=True, timeout=60,
    )
    assert validator.returncode == 0, validator.stderr


def test_bench_regress_synthetic_ok_passes():
    result = _run_bench_regress("--synthetic", "ok")
    assert result.returncode == 0, result.stderr + result.stdout
    assert "bench-regress: OK" in result.stdout


def test_bench_regress_fails_closed_on_crashed_bench():
    """A bench that emits rows then dies must NOT publish a passing
    claim — the gate fails on the bench's own exit code."""
    fake_bench = (
        f"{sys.executable} -c \"import json; "
        "print(json.dumps({'metric': "
        "'deepfm_train_samples_per_sec_per_chip', 'value': 87639.0})); "
        "raise SystemExit(3)\""
    )
    result = _run_bench_regress("--cmd", fake_bench)
    assert result.returncode == 1, result.stderr + result.stdout
    assert "BENCH_ERROR" in result.stdout


def test_bench_regress_fails_closed_on_dropped_metric(tmp_path):
    """A tracked baseline metric missing from the run gates — a metric
    that silently stops being emitted can never regress otherwise."""
    run = tmp_path / "partial.jsonl"
    run.write_text(json.dumps(
        {"metric": "deepfm_train_samples_per_sec_per_chip",
         "value": 87639.0}
    ) + "\n")
    result = _run_bench_regress("--input", str(run))
    assert result.returncode == 1, result.stderr + result.stdout
    assert "missing" in result.stdout


def test_bench_regress_judge_skips_untracked_rows():
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import bench_regress
    finally:
        sys.path.pop(0)

    baseline = {"m_tracked": 100.0, "m_untracked": 100.0}
    rows = [
        {"metric": "m_tracked", "value": 100.0},
        {"metric": "m_untracked", "value": 1.0, "tracked": False},
        {"metric": "m_unknown", "value": 5.0},
    ]
    result = bench_regress.judge(rows, baseline)
    assert result["verdict"] == "ok" and result["regressed"] == 0
    verdicts = {d["metric"]: d["verdict"] for d in result["details"]}
    assert verdicts == {"m_tracked": "ok", "m_untracked": "untracked"}
    rows[0]["value"] = 10.0
    assert bench_regress.judge(rows, baseline)["verdict"] == "regressed"


# ---------------------------------------------------------------------------
# Invariant-rule coverage of the new instrumentation call sites
# ---------------------------------------------------------------------------


def test_new_call_sites_pass_purity_and_cardinality_rules():
    """Satellite: the new instrumentation keeps (a) obs calls out of
    traced code and (b) per-worker/per-function names out of metric
    labels — and both rules still bite on seeded violations, so the
    clean pass is not vacuous."""
    from elasticdl_tpu.analysis.core import SourceFile, run_checks
    from elasticdl_tpu.analysis.jax_rules import check_trace_purity
    from elasticdl_tpu.analysis.rules import check_metric_label_cardinality

    new_call_sites = [
        os.path.join(REPO_ROOT, rel)
        for rel in (
            "elasticdl_tpu/obs/stepstats.py",
            "elasticdl_tpu/obs/telemetry.py",
            "elasticdl_tpu/obs/tracing.py",
            "elasticdl_tpu/obs/trace.py",
            "elasticdl_tpu/common/profiler.py",
            "elasticdl_tpu/worker/collective_worker.py",
            "elasticdl_tpu/worker/master_client.py",
            "elasticdl_tpu/master/servicer.py",
            "elasticdl_tpu/master/task_manager.py",
            "elasticdl_tpu/parallel/elastic.py",
            "elasticdl_tpu/serving/ledger.py",
            "elasticdl_tpu/serving/frontend.py",
            "elasticdl_tpu/serving/batcher.py",
            "elasticdl_tpu/serving/replica_main.py",
            "elasticdl_tpu/obs/slo.py",
            "elasticdl_tpu/obs/report.py",
            "elasticdl_tpu/obs/top.py",
            "scripts/bench_regress.py",
            "scripts/loadgen.py",
        )
    ]
    violations = run_checks(
        new_call_sites, [check_trace_purity, check_metric_label_cardinality]
    )
    assert violations == [], "\n".join(v.format() for v in violations)
    seeded_purity = SourceFile.parse(
        "seeded_purity.py",
        "import jax\n"
        "@jax.jit\n"
        "def step(x, anatomy):\n"
        "    anatomy.journal.record('step_anatomy', worker_id=1)\n"
        "    return x\n",
    )
    assert check_trace_purity(seeded_purity), (
        "trace-purity no longer catches journal calls under jit"
    )
    seeded_cardinality = SourceFile.parse(
        "seeded_card.py",
        "from elasticdl_tpu import obs\n"
        "obs.gauge('anatomy_phase_seconds', 'h',\n"
        "          labelnames=('worker_id',))\n",
    )
    assert check_metric_label_cardinality(seeded_cardinality), (
        "cardinality rule no longer catches worker_id labels"
    )


# ---------------------------------------------------------------------------
# Acceptance e2e: data-starved worker attributed end to end
# ---------------------------------------------------------------------------


def test_data_starved_straggler_attribution_end_to_end(
    obs_registry_snapshot, tmp_path
):
    """ISSUE acceptance: master + 3 heartbeating workers over real gRPC;
    one worker is artificially data-starved (slow steps, anatomy
    dominated by data_wait).  The straggler journal evidence names
    data_wait, and obs.report over the journal attributes it with
    phase fractions summing to ~1.0."""
    from elasticdl_tpu.common.grpc_utils import RetryPolicy
    from elasticdl_tpu.master.rendezvous_server import ElasticRendezvous
    from elasticdl_tpu.master.servicer import (
        MasterServicer,
        start_master_server,
    )
    from elasticdl_tpu.master.task_manager import TaskManager
    from elasticdl_tpu.obs import report
    from elasticdl_tpu.parallel.elastic import HeartbeatReporter, WorldInfo
    from elasticdl_tpu.worker.master_client import MasterClient

    test_start = time.time() - 1
    task_manager = TaskManager(
        training_shards={"shard": 64}, records_per_task=64
    )
    rendezvous = ElasticRendezvous(coordinator_port_fn=lambda host: 23456)
    rendezvous.set_worker_hosts(
        [(0, "127.0.0.1"), (1, "127.0.0.1"), (2, "127.0.0.1")]
    )
    aggregator = TelemetryAggregator(
        detector=StragglerDetector(flag_after=2, clear_after=2),
        current_workers_fn=lambda: [w for w, _h in rendezvous.world()],
    )
    servicer = MasterServicer(
        task_manager=task_manager,
        rendezvous_server=rendezvous,
        telemetry=aggregator,
    )
    server, port = start_master_server(servicer, port=0)
    policy = RetryPolicy(
        timeout_s=5.0, max_attempts=3, base_backoff_s=0.01,
        max_backoff_s=0.05, jitter=0.0, total_budget_s=30.0,
        wait_for_ready=True,
    )
    clients = [
        MasterClient(f"localhost:{port}", worker_id=wid, retry_policy=policy)
        for wid in range(3)
    ]
    # Worker 2 is DATA-STARVED: slow steps whose anatomy shows the time
    # going to data_wait, not the device.  Healthy workers are
    # execute-dominant.
    telemetries = {}
    for wid in range(3):
        starved = wid == 2
        telemetry = WorkerTelemetry(wid, step_window=4)
        anatomy = _fed_anatomy(
            worker_id=wid,
            data_wait=6.0 if starved else 0.1,
            stage=0.05,
            execute=0.5 if starved else 0.9,
            bookkeep=0.05,
            examples=256,
            windows=3,
        )
        telemetry.bind_anatomy(anatomy)
        per_step = 0.5 if starved else 0.01
        for _ in range(4):
            telemetry.record_steps(4, duration_s=4 * per_step, records=64)
        telemetries[wid] = telemetry
    reporters = [
        HeartbeatReporter(
            clients[wid],
            WorldInfo(rank=wid, world_size=3, rendezvous_id=1,
                      coordinator_addr=""),
            host="127.0.0.1",
            interval_s=0.05,
            telemetry=telemetries[wid],
        )
        for wid in range(3)
    ]
    try:
        for reporter in reporters:
            reporter.start()
        deadline = time.time() + 60
        while time.time() < deadline and 2 not in aggregator.stragglers():
            time.sleep(0.02)
        assert 2 in aggregator.stragglers(), "starved worker never flagged"

        detected = [
            e for e in obs.journal().tail(500)
            if e["event"] == "straggler_detected" and e["ts"] >= test_start
        ]
        assert detected and detected[-1]["worker_id"] == 2
        # The upgraded evidence: not just "slow" — slow because of
        # data_wait, quantified against the fleet.
        assert detected[-1]["dominant_phase"] == "data_wait"
        assert detected[-1]["phase_ratio"] > 2
        assert aggregator.fleet_attribution()["workers"][2][
            "dominant_phase"
        ] == "data_wait"
    finally:
        for reporter in reporters:
            reporter.stop()
        for client in clients:
            client.close()
        server.stop(grace=None)

    # ---- obs.report over the e2e's journal -----------------------------
    journal_path = tmp_path / "events.jsonl"
    with open(journal_path, "w", encoding="utf-8") as f:
        for event in obs.journal().tail(1000):
            if event["ts"] >= test_start:
                f.write(json.dumps(event) + "\n")
    summary = report.summarize(report.load_events(str(journal_path)))
    compute = summary["compute"]
    assert sum(compute["fractions"].values()) == pytest.approx(1.0, abs=0.02)
    worker = compute["workers"][2]
    assert worker["dominant_phase"] == "data_wait"
    assert sum(worker["fractions"].values()) == pytest.approx(1.0, abs=0.02)
    assert compute["workers"][0]["dominant_phase"] == "execute"
    attribution = summary["straggler_attribution"]
    assert attribution[-1]["worker_id"] == 2
    assert attribution[-1]["dominant_phase"] == "data_wait"
    rendered = report.render_report(summary)
    assert "compute-phase attribution" in rendered
    assert "straggler worker 2" in rendered
    assert "data_wait" in rendered
    # The e2e journal schema-validates (step_anatomy etc. registered).
    validator = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "scripts", "validate_journal.py"),
         str(journal_path)],
        capture_output=True, text=True, timeout=60,
    )
    assert validator.returncode == 0, validator.stderr
