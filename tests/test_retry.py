"""Transient-failure RPC plane: retry wrapper + deadline + fault injection.

Covers ISSUE satellite "test coverage for the retry wrapper": a flaky fake
servicer that fails N times then succeeds, the exact (deterministic)
backoff schedule, deadline propagation to the server, and that
non-idempotent RPCs are never retried.
"""

import logging
import subprocess
import sys
import time

import grpc
import pytest

from elasticdl_tpu.common import faults
from elasticdl_tpu.common.grpc_utils import (
    RetryPolicy,
    build_server,
    expected_backoff_schedule,
)
from elasticdl_tpu.proto import elasticdl_pb2 as pb
from elasticdl_tpu.proto.service import (
    MasterServicer as BaseServicer,
    add_MasterServicer_to_server,
)
from elasticdl_tpu.worker.master_client import MasterClient

#: Fast-but-shaped policy for tests: real exponential backoff, tiny bases.
FAST_POLICY = RetryPolicy(
    timeout_s=5.0,
    max_attempts=6,
    base_backoff_s=0.01,
    max_backoff_s=0.04,
    jitter=0.25,
    total_budget_s=30.0,
)


class FlakyServicer(BaseServicer):
    """Fails the first `fail_get_task` get_task calls with UNAVAILABLE,
    then succeeds; report_task_result ALWAYS fails (the non-idempotent
    never-retried probe).  Records per-call deadlines as seen server-side."""

    def __init__(self, fail_get_task: int = 0):
        self.fail_get_task = fail_get_task
        self.get_task_calls = 0
        self.report_calls = 0
        self.deadlines = []

    def get_task(self, request, context):
        self.get_task_calls += 1
        self.deadlines.append(context.time_remaining())
        if self.get_task_calls <= self.fail_get_task:
            context.abort(grpc.StatusCode.UNAVAILABLE, "flaky (injected)")
        return pb.GetTaskResponse(
            task=pb.Task(task_id=7, type=pb.TRAINING, start=0, end=4)
        )

    def report_task_result(self, request, context):
        self.report_calls += 1
        self.deadlines.append(context.time_remaining())
        context.abort(grpc.StatusCode.UNAVAILABLE, "always down")


@pytest.fixture
def flaky_stack():
    """(servicer, make_client, sleeps) over a real localhost gRPC server.
    Backoff sleeps are recorded, not slept — the schedule is the assert."""
    created = []

    def build(fail_get_task=0, policy=FAST_POLICY):
        servicer = FlakyServicer(fail_get_task=fail_get_task)
        server = build_server(max_workers=4)
        add_MasterServicer_to_server(servicer, server)
        port = server.add_insecure_port("[::]:0")
        server.start()
        sleeps = []
        client = MasterClient(
            f"localhost:{port}", worker_id=0,
            retry_policy=policy, sleep=sleeps.append,
        )
        created.append((server, client))
        return servicer, client, sleeps

    yield build
    for server, client in created:
        client.close()
        server.stop(grace=None)


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.clear()


def test_flaky_rpc_retries_then_succeeds_with_exact_backoff(flaky_stack):
    servicer, client, sleeps = flaky_stack(fail_get_task=3)
    task = client.get_task()
    assert task.task_id == 7
    # 3 failures + 1 success, one backoff sleep per failure, and the
    # schedule is the policy's deterministic (seeded-jitter) exponential.
    assert servicer.get_task_calls == 4
    schedule = expected_backoff_schedule("get_task", FAST_POLICY, 3, seed="0")
    assert tuple(sleeps) == schedule
    # Exponential shape: each raw backoff at least ~doubles until the cap
    # (jitter <= 25% can't mask a 2x growth).
    assert sleeps[0] < sleeps[1] < sleeps[2]
    assert client.retry_stats.retries == 3
    assert client.retry_stats.calls == 1
    assert client.retry_stats.per_method_retries == {"get_task": 3}


def test_every_rpc_carries_an_explicit_deadline(flaky_stack):
    servicer, client, _sleeps = flaky_stack()
    client.get_task()
    with pytest.raises(grpc.RpcError):
        client.report_task_result(1, "")
    from elasticdl_tpu.common.constants import RPC

    assert len(servicer.deadlines) == 2
    # time_remaining() is None when the client set no deadline.
    get_task_remaining, report_remaining = servicer.deadlines
    assert get_task_remaining is not None
    assert 0 < get_task_remaining <= FAST_POLICY.timeout_s + 1.0
    assert report_remaining is not None
    assert 0 < report_remaining <= RPC.DEADLINE_S + 1.0


def test_non_idempotent_rpc_never_retried(flaky_stack):
    servicer, client, sleeps = flaky_stack()
    with pytest.raises(grpc.RpcError) as err:
        client.report_task_result(1, "")
    assert err.value.code() == grpc.StatusCode.UNAVAILABLE
    assert servicer.report_calls == 1  # exactly one attempt
    assert sleeps == []  # and no backoff
    assert client.retry_stats.retries == 0


def test_injected_rpc_fault_is_deterministic(flaky_stack):
    """Two identical runs against a HEALTHY server with a 2-failure
    injection produce byte-identical retry behavior."""
    runs = []
    for _ in range(2):
        servicer, client, sleeps = flaky_stack(fail_get_task=0)
        faults.install("rpc.get_task:error=UNAVAILABLE@1x2")
        task = client.get_task()
        assert task.task_id == 7
        runs.append(tuple(sleeps))
        # The injected failures never reached the wire.
        assert servicer.get_task_calls == 1
        assert client.retry_stats.retries == 2
        faults.clear()
    assert runs[0] == runs[1] == expected_backoff_schedule(
        "get_task", FAST_POLICY, 2, seed="0"
    )


def test_injected_latency_fault(flaky_stack):
    servicer, client, sleeps = flaky_stack()
    faults.install("rpc.get_task:latency=0.123@1")
    assert client.get_task().task_id == 7
    assert sleeps == [0.123]  # delayed, not failed: same attempt proceeds
    assert servicer.get_task_calls == 1
    assert client.retry_stats.retries == 0


def test_non_transient_code_propagates_immediately(flaky_stack):
    servicer, client, sleeps = flaky_stack()
    faults.install("rpc.get_task:error=INVALID_ARGUMENT@1")
    with pytest.raises(grpc.RpcError) as err:
        client.get_task()
    assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    assert servicer.get_task_calls == 0
    assert sleeps == []


def test_retry_budget_bounds_total_time(flaky_stack):
    budgetless = RetryPolicy(
        timeout_s=5.0, max_attempts=6, base_backoff_s=0.01,
        max_backoff_s=0.04, jitter=0.25, total_budget_s=0.0,
    )
    servicer, client, sleeps = flaky_stack(policy=budgetless)
    faults.install("rpc.get_task:error=UNAVAILABLE@1x*")
    with pytest.raises(grpc.RpcError):
        client.get_task()
    # Zero budget: the first backoff would overshoot, so exactly one
    # attempt and no sleep.
    assert sleeps == []
    assert client.retry_stats.attempts == 1
    assert client.retry_stats.give_ups == 1


def test_faults_disabled_is_default_and_counts_nothing():
    assert not faults.enabled()
    assert faults.fire("rpc.get_task") is None
    assert faults.call_count("rpc.get_task") == 0


def test_fault_crash_kills_the_process_like_sigkill():
    """`worker.*:crash` exits without cleanup, with the spec's code."""
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "from elasticdl_tpu.common import faults\n"
            "faults.install('worker.task:crash=7@2')\n"
            "for _ in range(5):\n"
            "    spec = faults.fire('worker.task')\n"
            "    if spec is not None and spec.kind == 'crash':\n"
            "        faults.crash_now(spec)\n"
            "raise SystemExit(99)  # unreachable when the fault fires\n",
        ],
        timeout=60,
    )
    assert proc.returncode == 7


def test_worker_task_loop_is_a_crash_injection_site(monkeypatch):
    """The worker loop fires the `worker.task` site before each task —
    crash_now intercepted so the test process survives."""
    from types import SimpleNamespace

    from elasticdl_tpu.parallel.elastic import WorldInfo
    from elasticdl_tpu.worker.collective_worker import CollectiveWorker

    class _Boom(Exception):
        pass

    fired = []
    monkeypatch.setattr(
        faults, "crash_now", lambda spec: (_ for _ in ()).throw(_Boom())
    )
    faults.install("worker.task:crash@1")

    class _OneTaskClient:
        worker_id = 0

        def get_task(self, task_type=pb.TRAINING):
            fired.append("get_task")
            return pb.Task(task_id=1, type=pb.TRAINING, start=0, end=4)

        def report_task_result(self, *a, **k):
            pass

        def report_version(self, *a, **k):
            pass

    worker = CollectiveWorker(
        master_client=_OneTaskClient(),
        model_spec=SimpleNamespace(dataset_fn=None, callbacks=None),
        data_reader=SimpleNamespace(
            metadata=None, shard_names=lambda: ["s"]
        ),
        minibatch_size=2,
        world=WorldInfo(
            rank=0, world_size=1, rendezvous_id=0, coordinator_addr=""
        ),
        trainer=SimpleNamespace(
            step=0, apply_every=1, local_block=lambda per_rank: per_rank
        ),
    )
    with pytest.raises(_Boom):
        worker.run()
    assert fired == ["get_task"]  # crashed before processing anything


def test_heartbeat_reporter_counts_failures_and_ratelimits_warnings():
    """Satellite: HeartbeatReporter._loop must not swallow errors silently
    — it counts them and warns with the error class, rate-limited."""
    from elasticdl_tpu.parallel.elastic import HeartbeatReporter, WorldInfo

    class _DownMaster:
        worker_id = 3

        def report_worker_liveness(self, host, rendezvous_id):
            raise ConnectionError("master is down")

    records = []
    handler = logging.Handler()
    handler.emit = records.append
    elastic_logger = logging.getLogger("elasticdl_tpu.parallel.elastic")
    elastic_logger.addHandler(handler)
    world = WorldInfo(
        rank=0, world_size=1, rendezvous_id=1, coordinator_addr=""
    )
    reporter = HeartbeatReporter(
        _DownMaster(), world, host="h", interval_s=0.01
    )
    try:
        reporter.start()
        deadline = time.time() + 10
        while reporter.error_count < 3 and time.time() < deadline:
            time.sleep(0.01)
    finally:
        reporter.stop()
        elastic_logger.removeHandler(handler)
    assert reporter.error_count >= 3
    warnings = [r for r in records if r.levelno == logging.WARNING]
    assert len(warnings) == 1  # rate-limited: one warning per interval
    assert "ConnectionError" in warnings[0].getMessage()
