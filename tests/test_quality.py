"""Model-quality observability plane tests (ISSUE 20).

Fast tier: the online-eval math (AUC/logloss/calibration), the
label-join ledger's bookkeeping (expiry, orphans, fault-injected drops
and duplicates), the canary gate's verdict lattice, the drift monitor's
edge discipline, and the graceful-degradation pins — `obs.top` and
`obs.report` must render journals from fleets predating the quality
plane without a single quality artifact.  An analyzer gate re-runs the
trace-purity and metric-cardinality rules over every file this plane
touched.

Slow tier (`make test-quality` / `make test-serving`): the ISSUE's
acceptance e2e — a 2-replica fleet under labeled load, a poisoned
(label-flipped) feed that both burns the quality SLO and produces a
regressed delta the canary gate HOLDS while the previous generation
serves on untouched, then a healthy recovery delta that passes — plus
the no-poison control that must fire nothing.  Everything runs on a
virtual clock, so the run replays bit-exactly.
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from elasticdl_tpu import obs
from elasticdl_tpu.common import faults
from elasticdl_tpu.data.stream import click_label_rule, feedback_labels
from elasticdl_tpu.obs import report as report_mod
from elasticdl_tpu.obs import top as top_mod
from elasticdl_tpu.obs.quality import (
    CanaryGate,
    DriftMonitor,
    QualityLedger,
    ReplayBuffer,
    binary_auc,
    binary_logloss,
)

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(TESTS_DIR)
GOLDEN = os.path.join(TESTS_DIR, "golden_journal.jsonl")


@pytest.fixture(autouse=True)
def _disarm():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture
def journal_file(tmp_path):
    path = obs.init_journal(str(tmp_path))
    try:
        yield path
    finally:
        obs.journal().configure(None)


def _events(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _load_validator():
    spec = importlib.util.spec_from_file_location(
        "validate_journal",
        os.path.join(REPO_ROOT, "scripts", "validate_journal.py"),
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules["validate_journal"] = module
    spec.loader.exec_module(module)
    return module


def _golden_events():
    return _events(GOLDEN)


def _pre_quality(events):
    """The same journal as seen by a fleet predating the quality plane."""
    return [
        e for e in events
        if not str(e.get("event", "")).startswith("quality")
    ]


# ---------------------------------------------------------------------------
# Online-eval math
# ---------------------------------------------------------------------------


def test_binary_auc_matches_bruteforce_pairwise():
    rng = np.random.RandomState(7)
    labels = (rng.rand(64) < 0.3).astype(np.float64)
    preds = rng.rand(64)
    wins = ties = 0
    for i in np.flatnonzero(labels == 1.0):
        for j in np.flatnonzero(labels == 0.0):
            if preds[i] > preds[j]:
                wins += 1
            elif preds[i] == preds[j]:
                ties += 1
    total = labels.sum() * (labels.size - labels.sum())
    expected = (wins + 0.5 * ties) / total
    assert binary_auc(labels, preds) == pytest.approx(expected, abs=1e-12)
    # Heavy ties resolve as half-wins, not as either extreme.
    tied = np.full(10, 0.5)
    tied_labels = np.array([1, 0] * 5, dtype=np.float64)
    assert binary_auc(tied_labels, tied) == pytest.approx(0.5)
    # A single-class window cannot define AUC: None, never a sentinel.
    assert binary_auc(np.ones(8), preds[:8]) is None
    assert binary_auc(np.zeros(8), preds[:8]) is None


# ---------------------------------------------------------------------------
# Label-join ledger bookkeeping
# ---------------------------------------------------------------------------


def test_ledger_expiry_orphans_and_window_eviction():
    ledger = QualityLedger(
        window_size=8, join_window_s=5.0, max_pending=64, origin="t"
    )
    preds = np.array([0.9, 0.1], dtype=np.float32)
    labels = np.array([1.0, 0.0], dtype=np.float32)
    ledger.note_prediction("a", preds, now=0.0)
    ledger.note_prediction("b", preds, now=1.0)
    # "a" expires at t=6 (outside the 5s join window); its label orphans.
    assert ledger.note_label("b", labels, now=4.0) is True
    assert ledger.note_label("a", labels, now=6.1) is False
    # A label with no sampled prediction orphans too.
    assert ledger.note_label("never-sampled", labels, now=6.2) is False
    snap = ledger.snapshot()
    assert snap["joined"] == 2
    assert snap["expired"] == 1
    assert snap["orphans"] == 2
    assert snap["pending"] == 0
    # The window is a ring: 5 more joined pairs of 2 evict the oldest.
    for i in range(5):
        tid = f"c{i}"
        ledger.note_prediction(tid, preds, now=7.0 + i)
        ledger.note_label(tid, labels, now=7.0 + i)
    snap = ledger.snapshot()
    assert snap["window"] == 8
    assert snap["joined"] == 12
    # Online metrics are recomputed from exactly the window pairs.
    window_labels, window_preds = ledger.pairs()
    assert snap["auc"] == pytest.approx(
        binary_auc(window_labels, window_preds), abs=1e-12
    )
    assert snap["logloss"] == pytest.approx(
        binary_logloss(window_labels, window_preds), abs=1e-12
    )


def test_ledger_label_join_fault_drop_and_duplicate():
    ledger = QualityLedger(window_size=64, join_window_s=60.0, origin="t")
    preds = np.array([0.8], dtype=np.float32)
    labels = np.array([1.0], dtype=np.float32)
    # Call 1 drops the label, call 2 delivers it twice (the second
    # delivery joins nothing — its prediction was consumed — and counts
    # as an orphan, the honest at-least-once bookkeeping).
    faults.install("quality.label_join:error@1, quality.label_join:truncate@2")
    ledger.note_prediction("x", preds, now=0.0)
    assert ledger.note_label("x", labels, now=1.0) is False  # dropped
    assert ledger.note_label("x", labels, now=2.0) is True  # + duplicate
    snap = ledger.snapshot()
    assert snap["dropped_injected"] == 1
    assert snap["duplicates_injected"] == 1
    assert snap["joined"] == 1
    assert snap["orphans"] == 1


def test_ledger_journal_silent_until_first_prediction(
    journal_file, obs_registry_snapshot
):
    ledger = QualityLedger(window_size=16, join_window_s=60.0, origin="r")
    # Pre-quality runs journal nothing new: no predictions sampled yet.
    assert ledger.journal_window(now=0.0) is None
    assert _events(journal_file) == []
    ledger.note_prediction("t0", np.array([0.7]), now=0.0)
    ledger.note_label("t0", np.array([1.0]), now=1.0)
    snap = ledger.journal_window(now=2.0)
    assert snap is not None
    events = _events(journal_file)
    assert [e["event"] for e in events] == ["quality_window"]
    event = events[0]
    assert event["joined"] == 1 and event["origin"] == "r"
    assert 0.0 <= event["auc"] <= 1.0 if "auc" in event else True
    validator = _load_validator()
    assert validator.validate_file(journal_file) == []


# ---------------------------------------------------------------------------
# Canary gate verdict lattice
# ---------------------------------------------------------------------------


def _labeled_replay(n_batches=8, rows=16):
    from elasticdl_tpu.data.stream import synthetic_click_batch

    replay = ReplayBuffer(max_batches=n_batches)
    for b in range(n_batches):
        feats = synthetic_click_batch(b * rows, (b + 1) * rows, 1000)
        replay.add(feats, click_label_rule(feats))
    return replay


def _scorer(offset):
    def predict(features):
        labels = click_label_rule(features)
        return np.clip(0.5 + offset * (2.0 * labels - 1.0), 0.01, 0.99)

    return predict


def test_gate_holds_regression_and_passes_parity():
    gate = CanaryGate(_labeled_replay(), min_rows=64)
    good, bad = _scorer(0.35), _scorer(-0.35)
    verdict = gate.evaluate(good, good)
    assert verdict["outcome"] == "passed"
    assert verdict["quality"] == "known"
    assert verdict["reason"] == "within_thresholds"
    verdict = gate.evaluate(good, bad)
    assert verdict["outcome"] == "held"
    assert "logloss_regress" in verdict["reason"]
    assert verdict["candidate_logloss"] > verdict["baseline_logloss"]
    # The escape hatch records the same evidence but never blocks.
    forced = CanaryGate(_labeled_replay(), min_rows=64, force=True)
    verdict = forced.evaluate(good, bad)
    assert verdict["outcome"] == "forced"
    assert verdict["quality"] == "known"


def test_gate_unknown_policy_and_shadow_faults():
    cold = ReplayBuffer(max_batches=4)  # no labeled rows at all
    assert CanaryGate(cold, min_rows=64).evaluate(
        _scorer(0.3), _scorer(0.3)
    )["outcome"] == "passed"  # open: a broken label pipe can't freeze swaps
    held = CanaryGate(cold, min_rows=64, unknown_policy="closed").evaluate(
        _scorer(0.3), _scorer(0.3)
    )
    assert held["outcome"] == "held"
    assert held["reason"] == "insufficient_labeled_rows"
    # A candidate that blows up mid-shadow degrades to unknown, never raises.
    def broken(_features):
        raise RuntimeError("shape mismatch")

    verdict = CanaryGate(_labeled_replay(), min_rows=64).evaluate(
        _scorer(0.3), broken
    )
    assert verdict["quality"] == "unknown"
    assert verdict["reason"].startswith("shadow_eval_error:")
    # The quality.shadow_eval fault site is the same unknown path.
    faults.install("quality.shadow_eval:error=injected@1")
    verdict = CanaryGate(
        _labeled_replay(), min_rows=64, unknown_policy="closed"
    ).evaluate(_scorer(0.3), _scorer(0.3))
    assert verdict["outcome"] == "held"
    assert verdict["reason"] == "shadow_eval_fault:injected"


# ---------------------------------------------------------------------------
# Drift monitor edge discipline
# ---------------------------------------------------------------------------


def test_drift_monitor_edge_triggered_events(
    journal_file, obs_registry_snapshot
):
    from elasticdl_tpu.data.stream import synthetic_click_batch

    monitor = DriftMonitor(threshold=0.25, bins=32, origin="replica_0")
    assert monitor.evaluate(0.0) is None  # incomparable: no serve sketch
    for b in range(16):
        monitor.observe_train(
            synthetic_click_batch(b * 64, (b + 1) * 64, 5000)
        )
    # Matched traffic: same generator, same range — no edge.
    for b in range(16):
        monitor.observe_serve(
            synthetic_click_batch(b * 64, (b + 1) * 64, 5000)
        )
    low = monitor.evaluate(1.0)
    assert low is not None and low < 0.25
    # Skewed serving traffic (one hot id) breaches — ONE event, not one
    # per tick.
    hot = {"user": np.full(4096, 17, dtype=np.int64),
           "item": np.full(4096, 23, dtype=np.int64)}
    monitor.observe_serve(hot)
    high = monitor.evaluate(2.0)
    assert high is not None and high > 0.25
    monitor.evaluate(3.0)  # still breached: no second event
    # Flooding matched traffic clears the breach: the second edge.
    for b in range(256):
        monitor.observe_serve(
            synthetic_click_batch(b * 64, (b + 1) * 64, 5000)
        )
    assert monitor.evaluate(4.0) < 0.25
    events = _events(journal_file)
    assert [e["event"] for e in events] == ["quality_drift"] * 2
    assert [e["state"] for e in events] == ["breach", "clear"]
    assert all(e["origin"] == "replica_0" for e in events)
    assert all(e["threshold"] == 0.25 for e in events)
    validator = _load_validator()
    assert validator.validate_file(journal_file) == []


# ---------------------------------------------------------------------------
# Graceful degradation: pre-quality journals render no quality artifact
# ---------------------------------------------------------------------------


def _synthetic_serving_events(with_quality):
    base = {
        "event": "serving_telemetry", "qps": 120.0, "p50_ms": 2.0,
        "p99_ms": 9.0, "queue_depth": 0, "inflight": 1,
        "availability_ratio": 1.0, "served": 1200, "shed": 0,
        "errors": 0, "generation": 2, "step": 640,
    }
    events = [dict(base, ts=100.0, replica_id=0),
              dict(base, ts=100.5, replica_id=1)]
    if with_quality:
        events += [
            {"event": "quality_window", "ts": 101.0, "origin": "replica_0",
             "joined": 512, "window": 256, "pending": 9, "expired": 3,
             "orphans": 1, "auc": 0.71, "logloss": 0.48,
             "calibration_error": 0.04},
            {"event": "quality_drift", "ts": 101.2, "origin": "replica_0",
             "state": "breach", "divergence": 0.41, "threshold": 0.25},
        ]
    return events


def test_top_serving_frame_is_byte_identical_without_quality_events():
    pre = _synthetic_serving_events(with_quality=False)
    rows = top_mod.serving_rows(pre, now=102.0)
    frame = top_mod.render_serving(rows, {}, addr="journal")
    # Pre-quality journal: no quality column, cell, or note — and the
    # frame is deterministic byte for byte.
    assert "AUC" not in frame and "CAL" not in frame
    assert "DRIFT" not in frame and "quality" not in frame
    assert frame == top_mod.render_serving(
        top_mod.serving_rows(pre, now=102.0), {}, addr="journal"
    )
    assert top_mod.quality_note(pre) == ""
    # The same telemetry WITH quality events grows the columns + note.
    full = _synthetic_serving_events(with_quality=True)
    frame = top_mod.render_serving(
        top_mod.serving_rows(full, now=102.0), {}, addr="journal"
    )
    assert "AUC" in frame and "CAL" in frame and "DRIFT" in frame
    assert "0.710" in frame and "0.040" in frame
    assert "0.41!" in frame  # breached drift cell carries the marker
    note = top_mod.quality_note(full)
    assert note.startswith("quality: joined=512 pending=9")
    # Replica 1 journaled no quality: its cells degrade to "-".
    replica_1 = [l for l in frame.splitlines() if l.startswith("1 ")]
    assert replica_1 and replica_1[0].split()[-3:] == ["-", "-", "-"]


def test_report_has_no_quality_section_on_pre_quality_journal():
    events = _golden_events()
    pre = _pre_quality(events)
    assert len(pre) < len(events), "golden journal must carry quality rows"
    summary = report_mod.summarize(pre)
    assert "quality" not in summary
    rendered = report_mod.render_report(summary)
    assert "model quality" not in rendered
    assert "quality_gate" not in rendered
    # The full golden journal reconstructs the plane: windows, the held
    # gate, the drift breach.
    summary = report_mod.summarize(events)
    quality = summary["quality"]
    assert quality["window_updates"] >= 1
    assert quality["holds"] >= 1
    assert quality["drift_breaches"] >= 1
    assert quality["gates"][-1]["outcome"] == "held"
    rendered = report_mod.render_report(summary)
    assert "model quality" in rendered and "HELD" in rendered


# ---------------------------------------------------------------------------
# Invariant-rule coverage of the quality plane's call sites
# ---------------------------------------------------------------------------


def test_quality_call_sites_pass_purity_and_cardinality_rules():
    """Satellite: every file the quality plane touched keeps (a) obs
    calls out of traced code and (b) unbounded names out of metric
    labels — and both rules still bite on seeded violations, so the
    clean pass is not vacuous."""
    from elasticdl_tpu.analysis.core import SourceFile, run_checks
    from elasticdl_tpu.analysis.jax_rules import check_trace_purity
    from elasticdl_tpu.analysis.rules import check_metric_label_cardinality

    call_sites = [
        os.path.join(REPO_ROOT, rel)
        for rel in (
            "elasticdl_tpu/obs/quality.py",
            "elasticdl_tpu/obs/slo.py",
            "elasticdl_tpu/obs/top.py",
            "elasticdl_tpu/obs/report.py",
            "elasticdl_tpu/serving/continuous.py",
            "elasticdl_tpu/serving/runtime.py",
            "elasticdl_tpu/serving/batcher.py",
            "elasticdl_tpu/serving/ledger.py",
            "elasticdl_tpu/serving/frontend.py",
            "elasticdl_tpu/serving/replica_main.py",
            "elasticdl_tpu/data/stream.py",
            "elasticdl_tpu/worker/collective_worker.py",
            "elasticdl_tpu/worker/main.py",
            "scripts/loadgen.py",
        )
    ]
    violations = run_checks(
        call_sites, [check_trace_purity, check_metric_label_cardinality]
    )
    assert violations == [], "\n".join(v.format() for v in violations)
    seeded_purity = SourceFile.parse(
        "seeded_purity.py",
        "import jax\n"
        "@jax.jit\n"
        "def step(x, ledger):\n"
        "    ledger.journal.record('quality_window', joined=1)\n"
        "    return x\n",
    )
    assert check_trace_purity(seeded_purity), (
        "trace-purity no longer catches journal calls under jit"
    )
    seeded_cardinality = SourceFile.parse(
        "seeded_card.py",
        "from elasticdl_tpu import obs\n"
        "obs.gauge('elasticdl_quality_auc', 'h',\n"
        "          labelnames=('worker_id',))\n",
    )
    assert check_metric_label_cardinality(seeded_cardinality), (
        "cardinality rule no longer catches worker_id labels"
    )


# ---------------------------------------------------------------------------
# Acceptance e2e: poisoned delta held, SLO burned, recovery passes
# ---------------------------------------------------------------------------


def _click_labels_like(feats, reference_labels):
    labels = feedback_labels(feats)
    if labels is None:
        return None
    return labels.astype(np.asarray(reference_labels).dtype).reshape(
        np.asarray(reference_labels).shape
    )


@pytest.mark.slow
@pytest.mark.e2e
def test_poisoned_delta_canary_gate_e2e(
    tmp_path, journal_file, obs_registry_snapshot
):
    """ISSUE 20 acceptance: a 2-replica fleet under labeled load.  A
    label-flipped feed (`stream.labels:error`) poisons BOTH the training
    shard (the retrained delta regresses) and the online joins (the
    windowed logloss burns the model_quality SLO).  The canary gate
    HOLDS the poisoned delta on every retry while the previous
    generation serves zero dropped requests; after the feed heals and a
    recovery retrain compacts past the quarantined link, the healthy
    artifact passes the same gate.  Virtual clock throughout."""
    from elasticdl_tpu.checkpoint.delta import DeltaExporter
    from elasticdl_tpu.obs.slo import SLOPlane, quality_slo
    from elasticdl_tpu.serving.continuous import DeltaWatcher
    from elasticdl_tpu.serving.runtime import ServingReplica
    from test_serving import _trained_deepfm

    zoo, trainer, batches = _trained_deepfm(steps=0)
    ref_labels = batches[0][1]

    def train_steps(count, start):
        for k in range(count):
            feats, _ = batches[(start + k) % len(batches)]
            labels = _click_labels_like(feats, ref_labels)
            assert labels is not None
            trainer.train_step(feats, labels)
            drift.observe_train(feats)

    drift = DriftMonitor(threshold=0.2, bins=64, origin="replica_0")

    # Ground truth everywhere is the stream's click_label_rule, so the
    # feed, the joins, and the offline audit agree element-wise.
    pub_dir = str(tmp_path / "pub")
    exporter = DeltaExporter(
        pub_dir,
        model_zoo="model_zoo",
        model_def="deepfm.deepfm_functional_api",
        model_params="vocab_size=100",
    )
    train_steps(24, start=0)
    full_dir = exporter.publish_full(trainer)

    replicas, ledgers, watchers = [], [], []
    for rid in range(2):
        replica = ServingReplica(full_dir, model_zoo="model_zoo")
        replay = ReplayBuffer(max_batches=16)
        ledger = QualityLedger(
            window_size=256, join_window_s=8.0,
            origin=f"replica_{rid}", replay=replay,
        )
        gate = CanaryGate(
            replay, max_logloss_regress=0.10, max_auc_drop=0.05,
            min_rows=64,
        )
        watcher = DeltaWatcher(
            replica, pub_dir, gate=gate, origin=f"replica_{rid}"
        )
        replicas.append(replica)
        ledgers.append(ledger)
        watchers.append(watcher)

    base_step = replicas[0].generation.step
    served = 0
    pending_feats = {}  # tick -> features awaiting their delayed label

    def serve_tick(tick, feats, attach_features):
        """One labeled-loadgen tick: both replicas predict, the label
        for tick-2 arrives 2 virtual seconds late, windows journal."""
        nonlocal served
        now = float(tick)
        for rid, (replica, ledger) in enumerate(zip(replicas, ledgers)):
            preds = np.asarray(replica.execute(feats, n_valid=16)).ravel()
            served += 1
            ledger.note_prediction(
                f"t{tick}-r{rid}", preds, now,
                features=feats if attach_features else None,
            )
        pending_feats[tick] = feats
        late = pending_feats.pop(tick - 2, None)
        if late is not None:
            labels = feedback_labels(late)  # the one shared label feed
            if labels is not None:
                for rid, ledger in enumerate(ledgers):
                    ledger.note_label(f"t{tick - 2}-r{rid}", labels, now)
        for ledger in ledgers:
            ledger.journal_window(now)
        drift.observe_serve(feats)
        drift.evaluate(now)

    # -- Phase A (t=0..29): clean labeled traffic fills the windows and
    # the gates' replay buffers with trusted evidence.
    for tick in range(30):
        serve_tick(tick, batches[(tick * 7) % len(batches)][0],
                   attach_features=True)
    baselines = []
    for ledger in ledgers:
        snap = ledger.snapshot()
        labels, preds = ledger.pairs()
        # Acceptance: the online AUC reproduces the offline audit of the
        # exact same joined set.
        assert snap["auc"] == pytest.approx(
            binary_auc(labels, preds), abs=1e-9
        )
        assert snap["logloss"] == pytest.approx(
            binary_logloss(labels, preds), abs=1e-9
        )
        assert snap["joined"] >= 256
        baselines.append(snap["logloss"])
    probe = batches[0][0]
    baseline_out = np.asarray(replicas[0].execute(probe, n_valid=16))

    plane = SLOPlane(
        specs=[quality_slo(
            max_logloss=max(baselines) + 0.15,
            compliance_window_s=7200.0, min_window_s=5.0,
        )],
        status_interval_s=1000.0, origin="replica_0",
    )

    # -- Poison: the upstream label shard flips.  The SAME fault feeds
    # the training loop (a poisoned retrain) and the online joins (the
    # quality windows).
    faults.install("stream.labels:errorx*")
    train_steps(30, start=30)
    poisoned_delta = exporter.publish_delta(trainer)
    assert poisoned_delta is not None

    held_polls = 0
    hot_feats = {
        "dense": batches[0][0]["dense"],
        "cat": np.full_like(np.asarray(batches[0][0]["cat"]), 17),
    }
    for tick in range(30, 50):
        # During the storm the sampler stops attaching features, so the
        # replay evidence stays the last known-good labeled set rather
        # than silently absorbing the poisoned feed.
        serve_tick(tick, batches[(tick * 7) % len(batches)][0],
                   attach_features=False)
        # A flash crowd on one hot key rides the same replicas — the
        # train-serve drift sketch must notice the traffic mix shifting
        # while the label feed burns.
        for replica in replicas:
            np.asarray(replica.execute(hot_feats, n_valid=16))
            served += 1
        drift.observe_serve(hot_feats)
        plane.tick(float(tick))
        if tick in (31, 45):  # the watcher retries a held link forever
            for watcher in watchers:
                summary = watcher.poll_once()
                assert summary["outcome"] == "held"
                assert summary["held"] == poisoned_delta
                assert "logloss_regress" in summary["reason"]
                held_polls += 1
    assert held_polls == 4
    assert "model_quality" in plane.slos.alerting(), (
        "poisoned joins must burn the quality SLO"
    )
    # The previous generation never stopped serving, bit-identically.
    for replica in replicas:
        assert replica.generation.step == base_step
    np.testing.assert_array_equal(
        baseline_out, np.asarray(replicas[0].execute(probe, n_valid=16))
    )

    # -- Recovery: the feed heals, and a clean retrain compacts past the
    # quarantined link.  Compaction folds into a fresh FULL artifact, so
    # catching up is the (ungated) quarantine-repair reload; the NEXT
    # clean delta then rides through the same canary gate and passes.
    faults.clear()
    train_steps(60, start=60)
    assert exporter.publish_delta(trainer) is not None
    assert exporter.compact() is not None
    for tick in range(50, 56):
        serve_tick(tick, batches[(tick * 7) % len(batches)][0],
                   attach_features=True)
    for watcher, replica in zip(watchers, replicas):
        summary = watcher.poll_once()
        assert summary["outcome"] == "applied", summary
        assert summary["reloaded_full"] is True
        assert replica.generation.step == exporter.head_step
    train_steps(12, start=120)
    healthy_delta = exporter.publish_delta(trainer)
    assert healthy_delta is not None
    for tick in range(56, 62):
        serve_tick(tick, batches[(tick * 7) % len(batches)][0],
                   attach_features=True)
    for watcher, replica in zip(watchers, replicas):
        summary = watcher.poll_once()
        assert summary["outcome"] == "applied", summary
        assert summary["applied_deltas"] == 1
        assert replica.generation.step == exporter.head_step
    assert served == 2 * 82  # zero dropped requests, every request served

    # -- Journal: the run's whole quality story, schema-valid.
    events = _events(journal_file)
    validator = _load_validator()
    assert validator.validate_file(journal_file) == []

    gates = [e for e in events if e["event"] == "quality_gate"]
    outcomes = [(e["origin"], e["outcome"]) for e in gates]
    assert outcomes.count(("replica_0", "held")) == 2
    assert outcomes.count(("replica_1", "held")) == 2
    assert outcomes[-2:] == [
        ("replica_0", "passed"), ("replica_1", "passed")
    ]
    for gate_event in gates:
        if gate_event["outcome"] == "held":
            assert "logloss_regress" in gate_event["reason"]
            assert gate_event["candidate_logloss"] > \
                gate_event["baseline_logloss"] + 0.10
            assert gate_event["step"] > base_step

    alerts = [e for e in events if e["event"] == "slo_alert"]
    fired = [a for a in alerts if a["state"] == "fire"]
    assert fired and fired[0]["slo"] == "model_quality"
    assert fired[0]["offending"] == "elasticdl_quality_logloss"

    drifts = [e for e in events if e["event"] == "quality_drift"]
    assert any(e["state"] == "breach" for e in drifts), (
        "hot-batch storm never tripped the train-serve drift sketch"
    )

    # The quality windows tell the poisoning story.  Windows journal in
    # tick order (one per tick from the first join at tick 2): the first
    # 28 are phase A's clean joins; by ticks 42..47 (indices 40..45) the
    # 256-pair window has fully churned onto flipped labels.
    lls = [e["logloss"] for e in events
           if e["event"] == "quality_window"
           and e["origin"] == "replica_0" and "logloss" in e]
    assert len(lls) == 60  # ticks 2..61, every tick journals its window
    assert max(lls[:28]) < min(lls[40:46]), (
        "poisoned joins must visibly degrade the windowed logloss"
    )

    # obs.report reconstructs the held-swap timeline from the journal.
    summary = report_mod.summarize(events)
    quality = summary["quality"]
    assert quality["holds"] == 4
    assert quality["gate_decisions"] == 6
    assert quality["drift_breaches"] >= 1
    gate_timeline = [g["outcome"] for g in quality["gates"]]
    assert gate_timeline[:4] == ["held"] * 4
    assert gate_timeline[-2:] == ["passed"] * 2
    rendered = report_mod.render_report(summary)
    assert "model quality" in rendered and "HELD" in rendered


@pytest.mark.slow
@pytest.mark.e2e
def test_no_poison_control_fires_nothing(
    tmp_path, journal_file, obs_registry_snapshot
):
    """The control run: same fleet, same labeled load, no fault.  The
    healthy delta passes the gate, the quality SLO never alerts, and no
    drift or hold appears anywhere in the journal."""
    from elasticdl_tpu.checkpoint.delta import DeltaExporter
    from elasticdl_tpu.obs.slo import SLOPlane, quality_slo
    from elasticdl_tpu.serving.continuous import DeltaWatcher
    from elasticdl_tpu.serving.runtime import ServingReplica
    from test_serving import _trained_deepfm

    zoo, trainer, batches = _trained_deepfm(steps=0)
    ref_labels = batches[0][1]
    for k in range(24):
        feats, _ = batches[k % len(batches)]
        trainer.train_step(feats, _click_labels_like(feats, ref_labels))

    pub_dir = str(tmp_path / "pub")
    exporter = DeltaExporter(
        pub_dir,
        model_zoo="model_zoo",
        model_def="deepfm.deepfm_functional_api",
        model_params="vocab_size=100",
    )
    full_dir = exporter.publish_full(trainer)
    replica = ServingReplica(full_dir, model_zoo="model_zoo")
    replay = ReplayBuffer(max_batches=16)
    ledger = QualityLedger(
        window_size=256, join_window_s=8.0, origin="replica_0",
        replay=replay,
    )
    gate = CanaryGate(replay, min_rows=64)
    watcher = DeltaWatcher(replica, pub_dir, gate=gate, origin="replica_0")

    pending = {}
    for tick in range(30):
        now = float(tick)
        feats = batches[(tick * 7) % len(batches)][0]
        preds = np.asarray(replica.execute(feats, n_valid=16)).ravel()
        ledger.note_prediction(f"t{tick}", preds, now, features=feats)
        pending[tick] = feats
        late = pending.pop(tick - 2, None)
        if late is not None:
            ledger.note_label(f"t{tick - 2}", feedback_labels(late), now)
        ledger.journal_window(now)

    plane = SLOPlane(
        specs=[quality_slo(
            max_logloss=ledger.snapshot()["logloss"] + 0.15,
            compliance_window_s=7200.0, min_window_s=5.0,
        )],
        status_interval_s=1000.0, origin="replica_0",
    )
    for k in range(24, 48):
        feats, _ = batches[k % len(batches)]
        trainer.train_step(feats, _click_labels_like(feats, ref_labels))
    assert exporter.publish_delta(trainer) is not None
    for tick in range(30, 50):
        now = float(tick)
        feats = batches[(tick * 7) % len(batches)][0]
        preds = np.asarray(replica.execute(feats, n_valid=16)).ravel()
        ledger.note_prediction(f"t{tick}", preds, now, features=feats)
        pending[tick] = feats
        late = pending.pop(tick - 2, None)
        if late is not None:
            ledger.note_label(f"t{tick - 2}", feedback_labels(late), now)
        ledger.journal_window(now)
        plane.tick(now)

    summary = watcher.poll_once()
    assert summary["outcome"] == "applied", summary
    assert replica.generation.step == exporter.head_step
    assert not plane.slos.alerting()

    events = _events(journal_file)
    validator = _load_validator()
    assert validator.validate_file(journal_file) == []
    gates = [e for e in events if e["event"] == "quality_gate"]
    assert [e["outcome"] for e in gates] == ["passed"]
    assert not any(e["event"] == "slo_alert" for e in events)
    assert not any(e["event"] == "quality_drift" for e in events)
