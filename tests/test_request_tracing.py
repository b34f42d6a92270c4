"""Request-level serving tracing: tail-based exemplars, shared batch
spans, and p99 phase attribution (docs/observability.md "Request
tracing & exemplars").

Covers:

- the `ExemplarSampler` decision: deterministic 1-in-N head samples,
  SLO-tied tail samples, every shed/dropped/error outcome captured, a
  hard-bounded ring, and O(sampled) journaling (unsampled requests
  write nothing; untraced requests are invisible);
- the shared `serve.batch` span: journaled ONCE per batch on the first
  sampled member, deduped by a bounded id ring;
- the frontend's span assembly through a fake gRPC context: the
  client-propagated trace id opens `rpc.predict` under the client span,
  phase spans nest per the settled parenting model, and a queue-full
  shed that never reaches the batcher still journals;
- `obs.trace.request_chain`: the full waterfall ordering including the
  trace-id-less shared batch span resolved via `batch_span_id`;
- `slo_alert` fire edges attaching exemplar trace ids from the
  registered provider (and surviving a broken provider);
- `obs.top --serving` phase columns + exemplar footer, degrading to the
  exact pre-tracing frame on old journals;
- `obs.report`'s tail-latency attribution section (and its absence on
  journals without `request_trace` rows);
- the loadgen client half: deterministic trace ids and journaled
  `client.predict` root spans;
- the `slow`-marked acceptance e2e: a 2-replica fleet under traced
  closed-loop load with an injected execute stall (queue backlog) must
  journal a schema-valid timeline from which the assembled trace yields
  a slow request's FULL waterfall with dominant phase queue, obs.report
  attributes p99 exemplars to the same phase, and the fired latency
  `slo_alert` carries exemplar trace ids resolvable in that trace —
  while the no-stall control run journals only head samples and fires
  nothing.
"""

import importlib.util
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from elasticdl_tpu import obs
from elasticdl_tpu.obs import report as report_mod
from elasticdl_tpu.obs import top
from elasticdl_tpu.obs import trace as trace_mod
from elasticdl_tpu.obs.metrics import MetricsRegistry
from elasticdl_tpu.obs.slo import SLOPlane, serving_latency_slo
from elasticdl_tpu.serving.batcher import BatcherConfig, MicroBatcher
from elasticdl_tpu.serving.frontend import PredictServicer, encode_features
from elasticdl_tpu.serving.ledger import ExemplarSampler

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(TESTS_DIR)
GOLDEN = os.path.join(TESTS_DIR, "golden_journal.jsonl")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO_ROOT, "scripts", f"{name}.py")
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


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


class _CapturingJournal:
    """Stand-in journal: records land in a list, nothing hits disk."""

    def __init__(self):
        self.records = []

    def record(self, event, **fields):
        self.records.append({"event": event, **fields})


# ---------------------------------------------------------------------------
# ExemplarSampler: the sampling decision
# ---------------------------------------------------------------------------


def _served(sampler, i, latency_ms=2.0):
    return sampler.observe(
        f"lg0-{i:08d}", {}, "served", latency_s=latency_ms / 1e3
    )


def test_head_sampling_is_deterministic():
    """The head sample is a counter, not a coin flip: two samplers fed
    the same traced stream journal the IDENTICAL request set."""
    journals = (_CapturingJournal(), _CapturingJournal())
    picks = []
    for journal in journals:
        sampler = ExemplarSampler(
            head_every=4, tail_threshold_ms=0.0, journal=journal
        )
        reasons = [_served(sampler, i) for i in range(13)]
        picks.append(reasons)
        assert sampler.counts() == {"observed": 13, "sampled": 4}
    assert picks[0] == picks[1]
    # 1-in-4 of the traced stream: requests 0, 4, 8, 12.
    ids = [[r["trace_id"] for r in j.records] for j in journals]
    assert ids[0] == ids[1] == [f"lg0-{i:08d}" for i in (0, 4, 8, 12)]
    assert all(r["sampled_by"] == "head" for r in journals[0].records)


def test_ring_is_bounded_and_journaling_is_o_sampled():
    journal = _CapturingJournal()
    sampler = ExemplarSampler(
        head_every=0, tail_threshold_ms=1.0, capacity=8, journal=journal
    )
    for i in range(100):
        assert _served(sampler, i, latency_ms=50.0) == "tail"
    assert sampler.counts() == {"observed": 100, "sampled": 100}
    assert len(sampler.exemplars()) == 8  # ring capacity, not 100
    assert len(journal.records) == 100  # every sample journaled once
    # Head off + sub-threshold latency: nothing journals at all.
    journal.records.clear()
    for i in range(100, 200):
        assert _served(sampler, i, latency_ms=0.5) == ""
    assert journal.records == []


def test_bad_outcomes_always_sampled():
    """Failures are always evidence — even with head sampling off and
    no tail threshold, every shed/dropped/error journals."""
    journal = _CapturingJournal()
    sampler = ExemplarSampler(
        head_every=0, tail_threshold_ms=0.0, journal=journal
    )
    for i, outcome in enumerate(("shed", "dropped", "error", "served")):
        sampler.observe(f"lg0-{i:08d}", {}, outcome, latency_s=0.001)
    sampled = [(r["outcome"], r["sampled_by"]) for r in journal.records]
    assert sampled == [
        ("shed", "outcome"), ("dropped", "outcome"), ("error", "outcome")
    ]


def test_untraced_requests_are_invisible():
    """No trace id -> no record AND no counter tick, so the head period
    stays pure in the traced stream."""
    journal = _CapturingJournal()
    sampler = ExemplarSampler(head_every=2, journal=journal)
    assert sampler.observe("", {}, "served", latency_s=0.001) == ""
    assert sampler.observe("", {}, "shed", latency_s=0.001) == ""
    assert sampler.counts() == {"observed": 0, "sampled": 0}
    assert journal.records == []


def test_dominant_phase_and_latency_from_phases():
    journal = _CapturingJournal()
    sampler = ExemplarSampler(head_every=1, journal=journal)
    phases = {"queue": 0.061, "batch": 0.002, "execute": 0.012,
              "respond": 0.003}
    assert sampler.observe("lg0-00000000", phases, "served") == "head"
    (rec,) = journal.records
    assert rec["dominant_phase"] == "queue"
    assert rec["latency_ms"] == pytest.approx(78.0, abs=0.01)
    assert rec["phases"]["queue"] == pytest.approx(61.0)
    assert sampler.slowest()["trace_id"] == "lg0-00000000"
    assert sampler.trace_ids() == ["lg0-00000000"]


def test_shared_batch_span_journaled_once(journal_file):
    """Two sampled members of the same batch journal ONE serve.batch
    span; the second member only links to it."""
    sampler = ExemplarSampler(head_every=1)
    batch = {"name": "serve.batch", "start_ts": 100.0, "duration_s": 0.01,
             "span_id": "b-shared", "batch_rows": 8, "bucket": 8,
             "requests": 2}
    for i in range(2):
        sampler.observe(
            f"lg0-{i:08d}", {"queue": 0.001}, "served",
            spans=[], batch=dict(batch),
        )
    batches = [e for e in _events(journal_file)
               if e["event"] == "span" and e["name"] == "serve.batch"]
    assert len(batches) == 1
    assert batches[0]["span_id"] == "b-shared"
    traces = [e for e in _events(journal_file)
              if e["event"] == "request_trace"]
    assert len(traces) == 2


# ---------------------------------------------------------------------------
# Frontend span assembly through a fake gRPC context
# ---------------------------------------------------------------------------


class _Ctx:
    """The slice of grpc.ServicerContext PredictServicer touches."""

    def __init__(self, metadata=None, remaining=5.0):
        self._metadata = metadata or ()
        self._remaining = remaining

    def invocation_metadata(self):
        return self._metadata

    def time_remaining(self):
        return self._remaining

    def abort(self, code, message):
        raise RuntimeError(f"abort {code}: {message}")


class _FakeReplica:
    class generation:
        gen_id = 3


def test_frontend_propagates_trace_to_phase_spans(journal_file):
    """A client-propagated trace id produces the settled span set:
    rpc.predict under the client span, serve.queue under rpc, the
    member serve.execute under the SHARED serve.batch span, and
    serve.respond back under rpc (the clamp-safety parent)."""
    from elasticdl_tpu.common import grpc_utils

    sampler = ExemplarSampler(head_every=1, replica_id=0)
    batcher = MicroBatcher(
        lambda features, n_valid: np.zeros(
            features["x"].shape[0], np.float32
        ),
        BatcherConfig(max_batch_size=4, max_wait_us=100, queue_limit=8),
    ).start()
    servicer = PredictServicer(_FakeReplica(), batcher, sampler=sampler)
    payload = encode_features({"x": np.zeros((2, 1), np.float32)})
    try:
        ctx = _Ctx(grpc_utils.trace_metadata("lg5-00000000",
                                             "lg5-00000000"))
        servicer.predict(payload, ctx)
        # An untraced request journals NOTHING (wire-compatible client).
        servicer.predict(payload, _Ctx())
    finally:
        batcher.stop()

    events = _events(journal_file)
    traces = [e for e in events if e["event"] == "request_trace"]
    assert len(traces) == 1
    (rec,) = traces
    assert rec["trace_id"] == "lg5-00000000"
    assert rec["outcome"] == "served" and rec["rows"] == 2
    assert rec["replica_id"] == 0 and rec["generation"] == 3
    assert set(rec["phases"]) == {"queue", "batch", "execute", "respond"}

    spans = {e["name"]: e for e in events if e["event"] == "span"}
    assert set(spans) == {"rpc.predict", "serve.queue", "serve.batch",
                          "serve.execute", "serve.respond"}
    batch_id = spans["serve.batch"]["span_id"]
    assert spans["rpc.predict"]["parent_span_id"] == "lg5-00000000"
    assert spans["rpc.predict"]["batch_span_id"] == batch_id
    rpc_id = spans["rpc.predict"]["span_id"]
    assert spans["serve.queue"]["parent_span_id"] == rpc_id
    assert spans["serve.execute"]["parent_span_id"] == batch_id
    assert spans["serve.respond"]["parent_span_id"] == rpc_id
    # The shared batch span belongs to every member equally: no trace id.
    assert spans["serve.batch"].get("trace_id", "") == ""
    assert spans["serve.batch"]["batch_rows"] == 2
    assert spans["serve.batch"]["generation"] == 3


def test_frontend_samples_queue_full_shed(journal_file):
    """A shed request never reaches the batcher, but it is still an
    outcome sample: request_trace + the rpc.predict span journal even
    though no phase stamps exist."""
    from elasticdl_tpu.common import grpc_utils

    gate = threading.Event()
    executing = threading.Event()

    def execute(features, n_valid):
        executing.set()
        gate.wait(timeout=30)
        return np.zeros(features["x"].shape[0], np.float32)

    sampler = ExemplarSampler(head_every=0, tail_threshold_ms=0.0)
    batcher = MicroBatcher(
        execute,
        BatcherConfig(max_batch_size=1, max_wait_us=100, queue_limit=1),
    ).start()
    servicer = PredictServicer(_FakeReplica(), batcher, sampler=sampler)
    payload = encode_features({"x": np.zeros((1, 1), np.float32)})
    try:
        first = batcher.submit({"x": np.zeros((1, 1), np.float32)})
        assert executing.wait(timeout=10)
        queued = batcher.submit({"x": np.zeros((1, 1), np.float32)})
        ctx = _Ctx(grpc_utils.trace_metadata("lg5-00000007",
                                             "lg5-00000007"))
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            servicer.predict(payload, ctx)
        gate.set()
        first.wait(timeout=30)
        queued.wait(timeout=30)
    finally:
        gate.set()
        batcher.stop()
    events = _events(journal_file)
    (rec,) = [e for e in events if e["event"] == "request_trace"]
    assert rec["trace_id"] == "lg5-00000007"
    assert rec["outcome"] == "shed" and rec["sampled_by"] == "outcome"
    names = [e["name"] for e in events if e["event"] == "span"]
    assert names == ["rpc.predict"]


# ---------------------------------------------------------------------------
# obs.trace: the waterfall chain
# ---------------------------------------------------------------------------


def test_request_chain_resolves_shared_batch_hop():
    def span(name, span_id, parent_id="", trace_id="t1", start=0.0,
             **args):
        return {"name": name, "span_id": span_id,
                "parent_span_id": parent_id, "trace_id": trace_id,
                "start": start, "end": start + 0.01, "args": args}

    spans = [
        span("serve.respond", "p1", "r1", start=0.040),
        span("client.predict", "t1", "", start=0.000),
        span("rpc.predict", "r1", "t1", start=0.001,
             batch_span_id="b1"),
        span("serve.batch", "b1", "", trace_id="", start=0.031),
        span("serve.execute", "x1", "b1", start=0.032,
             batch_span_id="b1"),
        span("serve.queue", "q1", "r1", start=0.001),
        # Noise from an unrelated trace must not leak in.
        span("rpc.predict", "r2", "t2", trace_id="t2", start=0.5),
    ]
    chain = trace_mod.request_chain(spans, "t1")
    assert [s["name"] for s in chain] == list(trace_mod.SERVING_SPAN_ORDER)
    assert trace_mod.request_chain(spans, "no-such-trace") == []


# ---------------------------------------------------------------------------
# slo_alert exemplars
# ---------------------------------------------------------------------------


def test_latency_alert_attaches_exemplar_trace_ids(journal_file):
    registry = MetricsRegistry()
    gauge = registry.gauge("elasticdl_serving_latency_p99_ms", "")
    plane = SLOPlane(
        registry=registry,
        specs=[serving_latency_slo(20.0, compliance_window_s=60.0)],
        origin="t",
    )
    plane.slos.set_exemplar_provider(
        lambda slo: ["lg0-00000102", "lg0-00000140"]
    )
    evidence_seen = []
    plane.slos.add_alert_callback(
        lambda slo, firing, ev: evidence_seen.append((firing, ev))
    )
    for tick in range(30):
        gauge.set(500.0)
        plane.tick(float(tick))
    # Recover so the clear edge journals too.
    for tick in range(30, 120):
        gauge.set(1.0)
        plane.tick(float(tick))
    alerts = [e for e in _events(journal_file) if e["event"] == "slo_alert"]
    fires = [a for a in alerts if a["state"] == "fire"]
    clears = [a for a in alerts if a["state"] == "clear"]
    assert fires and clears
    assert fires[0]["exemplars"] == ["lg0-00000102", "lg0-00000140"]
    # Clear edges carry no exemplars (nothing is offending anymore).
    assert all("exemplars" not in a for a in clears)
    fired = [ev for firing, ev in evidence_seen if firing]
    assert fired and fired[0]["exemplars"] == [
        "lg0-00000102", "lg0-00000140"
    ]


def test_broken_exemplar_provider_never_blocks_the_alert(journal_file):
    registry = MetricsRegistry()
    gauge = registry.gauge("elasticdl_serving_latency_p99_ms", "")
    plane = SLOPlane(
        registry=registry,
        specs=[serving_latency_slo(20.0, compliance_window_s=60.0)],
        origin="t",
    )

    def exploding(slo):
        raise RuntimeError("exemplar store unavailable")

    plane.slos.set_exemplar_provider(exploding)
    for tick in range(30):
        gauge.set(500.0)
        plane.tick(float(tick))
    fires = [e for e in _events(journal_file)
             if e["event"] == "slo_alert" and e["state"] == "fire"]
    assert fires, "alert must fire even when the provider is broken"
    assert all("exemplars" not in a for a in fires)


# ---------------------------------------------------------------------------
# obs.top: phase columns + exemplar footer, clean degradation
# ---------------------------------------------------------------------------


def _telemetry_row(**extra):
    row = {"event": "serving_telemetry", "replica_id": 1, "ts": 99.0,
           "generation": 2, "step": 7, "qps": 123.4, "p50_ms": 0.5,
           "p99_ms": 4.5, "queue_depth": 3, "inflight": 2,
           "availability_ratio": 0.98, "served": 700, "shed": 14,
           "errors": 0}
    row.update(extra)
    return row


def test_obs_top_phase_columns_and_exemplar_footer():
    events = [_telemetry_row(
        queue_p99_ms=61.0, batch_p99_ms=1.2, execute_p99_ms=9.4,
        respond_p99_ms=0.4,
        exemplar={"trace_id": "lg3-00000042", "latency_ms": 78.3,
                  "dominant_phase": "queue"},
    )]
    rows = top.serving_rows(events, now=101.0)
    assert rows[0]["queue_p99_ms"] == 61.0
    frame = top.render_serving(rows, {})
    for header in ("QU(ms)", "BA(ms)", "EX(ms)", "RE(ms)"):
        assert header in frame, frame
    assert "61.0" in frame
    assert "lg3-00000042" in frame and "dominant queue" in frame


def test_obs_top_degrades_without_phase_fields():
    """Pre-tracing journals must render the EXACT pre-tracing frame —
    no phantom columns, no exemplar footer."""
    events = [_telemetry_row()]
    frame = top.render_serving(top.serving_rows(events, now=101.0), {})
    assert "QU(ms)" not in frame and "dominant" not in frame
    assert "P99(ms)" in frame and "123.4" in frame


# ---------------------------------------------------------------------------
# obs.report: tail latency attribution
# ---------------------------------------------------------------------------


def _request_trace_rows():
    return [
        {"event": "request_trace", "ts": 1.0, "trace_id": "a",
         "outcome": "served", "sampled_by": "head", "latency_ms": 5.0,
         "phases": {"queue": 1.0, "batch": 0.5, "execute": 3.0,
                    "respond": 0.5},
         "dominant_phase": "execute", "rows": 8, "replica_id": 0},
        {"event": "request_trace", "ts": 2.0, "trace_id": "b",
         "outcome": "served", "sampled_by": "tail", "latency_ms": 80.0,
         "phases": {"queue": 70.0, "batch": 2.0, "execute": 6.0,
                    "respond": 2.0},
         "dominant_phase": "queue", "rows": 8, "replica_id": 1},
        {"event": "request_trace", "ts": 3.0, "trace_id": "c",
         "outcome": "shed", "sampled_by": "outcome", "latency_ms": 0.5,
         "phases": {}, "dominant_phase": "", "rows": 8, "replica_id": 1},
    ]


def test_report_tail_latency_attribution():
    tail = report_mod._tail_latency_summary(_request_trace_rows())
    assert tail["sampled"] == 3
    assert tail["by_reason"] == {"head": 1, "tail": 1, "outcome": 1}
    assert tail["exemplars"][0]["trace_id"] == "b"  # slowest first
    assert tail["dominant_phase"] == "queue"
    fractions = tail["phase_fractions"]
    assert max(fractions, key=fractions.get) == "queue"
    assert sum(fractions.values()) == pytest.approx(1.0)
    # Journals without request_trace rows render no section at all.
    assert report_mod._tail_latency_summary(
        [{"event": "job_start", "ts": 0.0}]
    ) is None


def test_report_renders_tail_section_from_golden_journal():
    summary = report_mod.summarize(report_mod.load_events(GOLDEN))
    assert "tail_latency" in summary
    text = report_mod.render_report(summary)
    assert "tail latency attribution" in text
    assert "lg7-00000102" in text and "dominant queue" in text


# ---------------------------------------------------------------------------
# loadgen: the client half
# ---------------------------------------------------------------------------


def test_loadgen_client_tracer_journals_root_spans(tmp_path):
    loadgen = _load_script("loadgen")
    assert loadgen.trace_id_for(7, 102) == "lg7-00000102"
    assert loadgen.trace_id_for(7, 102) == loadgen.trace_id_for(7, 102)
    tracer = loadgen.ClientTracer(seed=7, journal_dir=str(tmp_path))
    try:
        tracer.record(3, "served", 100.0, 0.0123)
        tracer.record(9, "shed", 101.0, 0.0007)
    finally:
        obs.journal().configure(None)
    events = _events(os.path.join(str(tmp_path), "events.jsonl"))
    spans = [e for e in events if e["event"] == "span"]
    assert [s["trace_id"] for s in spans] == [
        "lg7-00000003", "lg7-00000009"
    ]
    for span in spans:
        assert span["name"] == "client.predict"
        assert span["span_id"] == span["trace_id"]  # the trace ROOT
        assert span["proc"] == "loadgen"
    assert tracer.slowest(1)[0]["trace_id"] == "lg7-00000003"
    table = loadgen.render_slowest(
        tracer.slowest(2),
        events=[{"event": "request_trace", "trace_id": "lg7-00000003",
                 "latency_ms": 12.3, "dominant_phase": "queue",
                 "phases": {"queue": 10.0, "batch": 0.5, "execute": 1.5,
                            "respond": 0.3}}],
    )
    assert "lg7-00000003" in table and "queue" in table


# ---------------------------------------------------------------------------
# Acceptance e2e: stall -> tail exemplars -> alert evidence -> waterfall
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.e2e
def test_request_tracing_fleet_e2e(tmp_path, obs_registry_snapshot):
    """The ISSUE acceptance run.  A 2-replica fleet under traced load
    with an injected execute stall (ELASTICDL_FAULTS latency at the
    serving.execute site wedges the batcher thread, so requests pile up
    in the queue) must produce ONE shared journal from which:

    - a tail-sampled slow request resolves to the FULL waterfall
      client.predict -> rpc.predict -> serve.queue -> shared serve.batch
      -> serve.execute -> serve.respond with dominant phase queue;
    - obs.report's p99 exemplars name the same dominant phase;
    - the fired serving_latency slo_alert carries exemplar trace ids
      resolvable in the assembled trace.

    The control run (same fleet shape, no fault, SLO far above observed
    latency) journals ONLY head samples and fires nothing.
    """
    from test_serving import _exported_deepfm

    from elasticdl_tpu.serving.frontend import PredictClient
    from elasticdl_tpu.serving.supervisor import (
        start_serving_fleet,
        wait_for_replicas,
    )

    loadgen = _load_script("loadgen")
    validator = _load_script("validate_journal")
    _, _, gen1_dir, feats, _ = _exported_deepfm(tmp_path)
    warm = str(tmp_path / "warm.npz")
    with open(warm, "wb") as fh:
        fh.write(encode_features({k: v[:1] for k, v in feats.items()}))

    def run_fleet(serve_dir, env, num_requests, seed, slo_p99_ms):
        os.makedirs(serve_dir)
        # max_batch_size == the stream's batch_rows: ONE request per
        # dispatch, so a stalled dispatch leaves real queue depth behind
        # it (a 16-row budget would drain two waiters per stall and the
        # backlog — the queue phase under test — would never build).
        manager = start_serving_fleet(
            2, gen1_dir, serve_dir,
            worker_env=env,
            model_zoo="model_zoo",
            max_batch_size=8,
            max_wait_us=1000,
            telemetry_interval_s=0.5,
            warmup_features=warm,
            slo_p99_ms=slo_p99_ms,
            slo_compliance_window_s=60.0,
            trace_head_every=16,
        )
        clients = []
        journal_path = os.path.join(serve_dir, "events.jsonl")
        try:
            live = wait_for_replicas(serve_dir, 2, timeout_s=300)
            clients = [
                PredictClient(f"127.0.0.1:{r['port']}", deadline_s=60.0)
                for r in live
            ]
            tracer = loadgen.ClientTracer(seed=seed,
                                          journal_dir=serve_dir)
            stream = loadgen.RequestStream(loadgen.StreamConfig(seed=seed))
            result = loadgen.run_closed_loop(
                loadgen.round_robin_predict([c.predict for c in clients]),
                stream, num_requests=num_requests, concurrency=8,
                trace=tracer,
            )
            assert result.summary()["served"] == num_requests
            # Let telemetry/SLO ticks see the post-run ledger state; the
            # stall run needs the fire edge, which lands within a few
            # 0.5s ticks of the 5s-window burn going bad.
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline:
                alerts = [
                    e for e in _events(journal_path)
                    if e["event"] == "slo_alert" and e["state"] == "fire"
                    and e.get("slo") == "serving_latency"
                ]
                if not env.get("ELASTICDL_FAULTS") or alerts:
                    break
                time.sleep(0.5)
        finally:
            for client in clients:
                client.close()
            manager.stop()
            obs.journal().configure(None)
        assert validator.validate_file(journal_path) == []
        return _events(journal_path)

    base_env = {"JAX_PLATFORMS": "cpu"}

    # -- stall run: 0.35s execute stalls starting at the 5th dispatch ---
    events = run_fleet(
        str(tmp_path / "serve_stall"),
        dict(base_env,
             ELASTICDL_FAULTS="serving.execute:latency=0.35@4x20"),
        num_requests=120, seed=11, slo_p99_ms=50.0,
    )
    traces = [e for e in events if e["event"] == "request_trace"]
    tails = [e for e in traces if e["sampled_by"] == "tail"]
    assert tails, "stalled requests above the 50ms SLO must tail-sample"
    assert any(e["dominant_phase"] == "queue" for e in tails)

    asm = trace_mod.assemble([str(tmp_path / "serve_stall")])
    assert asm["invariant_problems"] == []
    assert trace_mod.validate_chrome_trace(asm["chrome"]) == []
    spans = asm["spans"]
    # At least one slow queue-dominated request resolves to the FULL
    # six-span waterfall (served requests have every phase stamp).
    full_chains = []
    for event in tails:
        # The request INSIDE a stalled dispatch is execute-dominated;
        # the ones queued behind it carry the stall as queue time — the
        # waterfall the acceptance run is after.
        if event["outcome"] != "served" or event["dominant_phase"] != "queue":
            continue
        chain = trace_mod.request_chain(spans, event["trace_id"])
        if [s["name"] for s in chain] == list(
            trace_mod.SERVING_SPAN_ORDER
        ):
            full_chains.append((event, chain))
    assert full_chains, (
        "no queue-dominated tail exemplar produced a complete waterfall"
    )
    event, chain = full_chains[0]
    by_name = {s["name"]: s for s in chain}
    assert (by_name["serve.queue"]["end"]
            - by_name["serve.queue"]["start"]) > (
        by_name["serve.execute"]["end"]
        - by_name["serve.execute"]["start"]
    )

    # obs.report attributes the p99 exemplars to the same phase.
    summary = report_mod.summarize(events)
    assert summary["tail_latency"]["dominant_phase"] == "queue"

    # The fired latency alert carries resolvable exemplar evidence.
    fires = [e for e in events if e["event"] == "slo_alert"
             and e["state"] == "fire" and e["slo"] == "serving_latency"]
    assert fires, "the injected stall must page the latency SLO"
    with_exemplars = [a for a in fires if a.get("exemplars")]
    assert with_exemplars, fires
    for trace_id in with_exemplars[0]["exemplars"]:
        assert trace_mod.request_chain(spans, trace_id), trace_id

    # -- control run: no stall, SLO far above observed latency ----------
    control = run_fleet(
        str(tmp_path / "serve_ok"), dict(base_env),
        num_requests=60, seed=12, slo_p99_ms=2000.0,
    )
    ctl_traces = [e for e in control if e["event"] == "request_trace"]
    assert ctl_traces, "head sampling must still journal exemplars"
    assert {e["sampled_by"] for e in ctl_traces} == {"head"}
    assert not [e for e in control if e["event"] == "slo_alert"
                and e["state"] == "fire"]
