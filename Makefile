# Build system (parity: the reference's Makefile — protoc gen, native
# build, packaging, tests).  Everything also happens automatically at
# first use (pb2 is checked in; the native .so builds lazily); these
# targets are the explicit developer entry points.

.PHONY: all proto native test test-fast test-sparse sparse-gates \
        test-compile compile-gates test-chaos test-obs test-serving \
        serving-gates test-pipeline test-stream stream-gates test-slo \
        slo-gates quality-gates test-quality e2e bench bench-regress \
        chip-smoke chip-smoke-cpu wheel clean lint \
        check-invariants

all: proto native test

proto:
	bash scripts/gen_protobuf.sh

native:
	python -c "from elasticdl_tpu import native; \
	           path = native.build_native(force=True); \
	           assert path, 'native build failed'; print(path)"

test:
	python -m pytest tests/ -q

# Invariant analyzer (docs/invariants.md): the control-plane rules, the
# hot-path compute-plane family (jit-host-sync, retrace-hazard,
# donation-discipline, trace-purity, sharding-coverage), and the
# whole-program protocol family (drain-discipline, blocking-under-lock,
# journal-schema — one cross-module call graph over the full scan) over
# both the package and the model zoo.  Exit 1 on any violation;
# suppress a deliberate exception with `# noqa-invariant: <rule>`.
check-invariants:
	python -m elasticdl_tpu.analysis elasticdl_tpu model_zoo

# Static gate: ruff (errors-only baseline, config in pyproject.toml) when
# available — the container may not ship it — then the invariant analyzer,
# with its JSON findings chased by the per-rule summary table (findings,
# suppressions, per-rule timing, cross-module graph size).
lint:
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check .; \
	else \
		echo "lint: ruff not installed; skipping style baseline" \
		     "(F821/F401/E722 — see [tool.ruff] in pyproject.toml)"; \
	fi
	@python -m elasticdl_tpu.analysis elasticdl_tpu model_zoo \
		--format json > .invariant_findings.json; rc=$$?; \
	python scripts/invariant_report.py .invariant_findings.json; \
	rm -f .invariant_findings.json; exit $$rc

# Tier-1 fast gate: lint + invariants first (cheap, seconds), then the
# correctness surface without the compile-heavy `slow`-marked tests
# (pyproject registers the markers) — what CI and a review session can
# finish on the 1-core box.  tests/test_analysis.py re-runs the invariant
# pass inside pytest, so the plain pytest tier-1 command gates on it too.
# The elastic policy-engine units (tests/test_policy.py: eviction
# hysteresis + kill budget, amortization math, thrash scale-down, the
# pod-manager scale-down regression) ride in tests/ here.
# sparse-gates / compile-gates (not the pytest files) chain into
# test-fast: the kernel and compile-layer test files already ride
# test-fast's own `pytest tests/` sweep, so chaining the full
# test-sparse / test-compile targets would run them twice per tier-1
# pass.
test-fast: lint sparse-gates compile-gates serving-gates stream-gates \
           slo-gates quality-gates
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow'

# Script gate of the model-quality plane, shared by test-quality and
# test-fast: the label-join ledger / drift-sketch / canary-gate
# selftest (online==offline AUC, fault-site degradation, gate
# held/passed/forced verdicts), plus the loadgen delayed-label replay
# half (pure label rule, broadcast join accounting, outage tolerance).
quality-gates:
	JAX_PLATFORMS=cpu python -m elasticdl_tpu.obs.quality --selftest
	JAX_PLATFORMS=cpu python scripts/loadgen.py --selftest --labels

# Standalone model-quality gate (docs/observability.md "Model
# quality"): ledger/sketch/gate units, the graceful-degradation pins
# (pre-quality journals render byte-identical top/report frames), and
# — without `-m 'not slow'` — the poisoned-delta canary acceptance e2e
# (label-flipped shard HELD with journaled evidence + quality SLO
# alert, healthy delta passes, zero dropped requests).
test-quality: quality-gates
	JAX_PLATFORMS=cpu python -m pytest tests/test_quality.py -q

# Script gate of the continuous train->serve loop, shared by
# test-stream and test-fast: the freshness SLO tracker's deterministic
# breach/clear transition selftest (one journal event per transition).
stream-gates:
	JAX_PLATFORMS=cpu python -m elasticdl_tpu.obs.freshness --selftest

# Script gate of the SLO plane, shared by test-slo and test-fast: the
# burn-rate alerting selftest — a deterministic virtual-clock run with
# an injected latency regression must page within the fast window,
# clear after it, journal schema-shaped slo_status/slo_alert events,
# and fire nothing on the no-fault control run.
slo-gates:
	JAX_PLATFORMS=cpu python -m elasticdl_tpu.obs.slo --selftest

# Standalone SLO-plane gate (docs/observability.md "SLO plane"): the
# metrics-history ring (eviction boundedness, clock-regression clamp,
# window queries), burn-rate math + fire/clear edges, the policy
# advisory wiring, the /slo endpoint, and — without `-m 'not slow'` —
# the 2-replica serving-fleet alerting acceptance e2e.
test-slo: slo-gates
	JAX_PLATFORMS=cpu python -m pytest tests/test_slo.py -q

# Standalone continuous-loop gate (docs/design.md "Continuous
# training"): the streaming dispatcher (watermark eviction, bounded
# lookahead, both crash-resume paths), the synthetic click stream's
# virtual-clock schedule math, and the delta-checkpoint chain
# (diff publish, torn-write quarantine, compaction repair, serving-side
# row-patch apply with atomic rollback).  The chaos acceptance e2e
# (tests/test_stream_e2e.py) is `slow`-marked and rides test-chaos.
test-stream: stream-gates
	JAX_PLATFORMS=cpu python -m pytest tests/test_stream.py \
	       tests/test_delta.py -q

# Script gate of the serving plane, shared by test-serving and
# test-fast: the load generator's no-server selftest (stream
# determinism + hot-key skew, outcome classification, closed/open-loop
# accounting against a fake backend), plus the client-tracing half
# (deterministic trace ids, the --slowest waterfall table joined from
# sampled request_trace events).
serving-gates:
	JAX_PLATFORMS=cpu python scripts/loadgen.py --selftest
	JAX_PLATFORMS=cpu python scripts/loadgen.py --selftest --slowest 3

# Standalone async-staging-engine gate (docs/design.md "Async staging
# engine"): parse-pool ordering/determinism under jitter, prefetcher
# backpressure + synchronous churn/checkpoint drain, overlap booking,
# the shared serving pad-and-stage, and the sync-vs-async bit-identical
# loss acceptance.  tests/test_pipeline.py also rides test-fast's own
# `pytest tests/` sweep — this target is the focused entry point.
test-pipeline:
	JAX_PLATFORMS=cpu python -m pytest tests/test_pipeline.py -q

# Standalone serving-plane gate (docs/serving.md): export round-trip,
# micro-batcher units (latency-budget vs batch-size race, shed-on-full,
# deadline drops), padded-bucket no-retrace under the RetraceWatcher,
# in-process hot-swap equivalence, and — without `-m 'not slow'` — the
# supervised-fleet acceptance e2es (live hot-swap with zero dropped
# in-flight, SIGKILL relaunch, journal schema validation; the traced
# stall run whose slow-request waterfall, report attribution, and
# alert exemplars must all name the queue phase).
test-serving: serving-gates
	JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py \
	       tests/test_request_tracing.py -q

# Script gates of the sparse path, shared by test-sparse and test-fast:
# the xla-vs-fused microbench's interpret-mode selftest and a tiny
# fused-vs-xla convergence A/B smoke (the full-scale fused A/B is chip
# work: `python scripts/convergence_ab.py --all --sparse-kernel fused`).
sparse-gates:
	JAX_PLATFORMS=cpu python scripts/exp_sparse_gather.py --selftest
	JAX_PLATFORMS=cpu python scripts/convergence_ab.py --smoke

# Script gate of the declarative compile layer's shard_map kernel
# dispatch, shared by test-compile and test-fast: the multi-device
# microbench's interpret-mode selftest on a forced 4-virtual-device
# mesh (sharded fused lookup bit-exact, sharded fused apply within the
# documented 1-ulp tolerance).
compile-gates:
	JAX_PLATFORMS=cpu python scripts/exp_sparse_gather.py --shard_map --selftest

# Standalone declarative-sharding gate (docs/design.md "Declarative
# sharding"): rule-table semantics over the zoo pytrees,
# pjit-vs-shard_map strategy selection + donation round-trip,
# per-trainer HLO-structure parity vs the pre-port hand-rolled steps,
# the no-direct-jit grep gate, the shard_map microbench selftest, and
# the multi-device fused-vs-xla equivalence + per-shard HLO tests.
test-compile: compile-gates
	JAX_PLATFORMS=cpu python -m pytest tests/test_compile.py \
	       tests/test_executable_store.py -q -m 'not slow'
	JAX_PLATFORMS=cpu python -m pytest tests/test_sparse_kernels.py \
	       -q -m 'not slow' -k 'multi_device or multichip or dispatch_route'

# Standalone sparse-path gate (docs/design.md "Fused sparse kernels"):
# the fused Pallas kernel family vs the XLA reference paths in
# interpret mode on CPU (bit-exactness / documented-tolerance contracts
# + the HLO no-row-batch-intermediates assertion), the packed-layout
# and stream/scatter/fused optimizer semantics they ride on, plus the
# script gates above.
test-sparse: sparse-gates
	JAX_PLATFORMS=cpu python -m pytest tests/test_sparse_kernels.py \
	       tests/test_sparse_optim_modes.py tests/test_packed.py \
	       -q -m 'not slow'

# Observability plane gate (docs/observability.md): registry semantics +
# lockcheck concurrency, exporter endpoint round-trip, journal rotation,
# the master end-to-end acceptance scrape, the worker telemetry plane
# (heartbeat snapshots, straggler detection, trace correlation, obs.top),
# the goodput ledger/report plane, and the distributed tracing plane
# (span trees, clock alignment, Perfetto export — tests/test_tracing.py
# + the obs.trace selftest) — then the journal schema validator's
# selftest + source-drift check, the postmortem report's selftest
# over the golden journal fixture, and the SLO plane (history ring +
# burn-rate alerting; test_slo.py's fleet e2e is `slow`-marked here —
# `make test-slo` runs it).
test-obs: slo-gates
	JAX_PLATFORMS=cpu python -m pytest tests/test_obs.py \
	       tests/test_telemetry.py tests/test_goodput.py \
	       tests/test_stepstats.py tests/test_tracing.py \
	       tests/test_span_vocabulary.py -q
	JAX_PLATFORMS=cpu python -m pytest tests/test_slo.py -q -m 'not slow'
	python scripts/validate_journal.py --selftest --check-sources
	python scripts/validate_journal.py tests/golden_journal.jsonl
	python -m elasticdl_tpu.obs.trace --selftest
	JAX_PLATFORMS=cpu python -m elasticdl_tpu.obs.report \
	       --selftest tests/golden_journal.jsonl
	JAX_PLATFORMS=cpu python scripts/bench_regress.py --selftest

# Transient-failure resilience gate: deterministic fault injection
# (common/faults.py, incl. the schedule-based @t storm triggers), the
# master-SIGKILL / torn-checkpoint chaos e2es, the preemption-storm
# two-baseline e2e (the policy engine must beat fixed-size AND naive
# always-rescale on the goodput ledger's own accounting), the
# policy-enforcement units, and the continuous train->serve chaos
# acceptance (stream spike + source stall + worker churn + master
# SIGKILL + torn delta + failed apply, under live loadgen traffic).
test-chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py tests/test_retry.py \
	       tests/test_faults.py tests/test_policy.py \
	       tests/test_stream_e2e.py -q

# The real multi-process end-to-end slices only (elasticity, PS, k8s).
e2e:
	python -m pytest tests/test_allreduce_e2e.py tests/test_ps_e2e.py \
	       tests/test_cluster_eval_e2e.py tests/test_k8s.py -q

# Needs the attached TPU chip (one process per chip): bench.py exits
# non-zero before any row when jax finds no TPU.
bench:
	python bench.py

# The quickest proof that the system still starts on the chip: train ->
# export -> serve at DeepFM's full width through the normal entry
# points, plus the Pallas kernels against their XLA twins.  From a
# sandbox without a chip: `chiprun -- python chip_smoke.py`.
chip-smoke:
	python chip_smoke.py

# The same script at tiny size on the CPU backend (rehearse before
# spending chip time); its last line says ok=false and names the cpu.
chip-smoke-cpu:
	python chip_smoke.py --cpu

# The canonical way to publish a perf claim (ROADMAP item 5): run the
# bench, gate every tracked metric against BASELINE.md's recorded
# value±spread (bench.SELF_BASELINE), journal a `bench_regress` event,
# and fail loud on beyond-spread regressions.  `--selftest` (in
# test-obs) proves the gate itself on CPU with no accelerator.
bench-regress:
	python scripts/bench_regress.py

wheel:
	python -m pip wheel --no-deps --wheel-dir dist .

clean:
	rm -rf dist build .elasticdl_build .jax_cache
	rm -f elasticdl_tpu/native/libedl_kernels.so
	find . -name __pycache__ -type d -prune -exec rm -rf {} +
