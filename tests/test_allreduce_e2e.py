"""AllReduce-mode end-to-end tests: a real multi-process jax.distributed
world over localhost, driven by the master's process manager.

Parity surface: the reference's elasticity e2e (SURVEY.md §4) — run a job
across worker processes, kill one mid-job, assert the job still completes
with every record trained (at-least-once task semantics).
"""

import pytest

# Tier-1 fast gate runs `-m 'not slow'` (see Makefile test-fast).
pytestmark = [pytest.mark.slow, pytest.mark.e2e]

import os
import time

import pytest

from elasticdl_tpu.common.args import parse_master_args
from elasticdl_tpu.common.constants import Mode
from elasticdl_tpu.master.job_runner import run_allreduce_job
from elasticdl_tpu.master.main import start_master
from elasticdl_tpu.master.pod_manager import (
    LocalProcessManager,
    worker_argv_from_args,
)
from elasticdl_tpu.master.rendezvous_server import ElasticRendezvous

WORKER_ENV = {
    # Workers run single-CPU-device processes (override the test harness's
    # 8 virtual devices); the world then has one device per process.
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    "JAX_PLATFORMS": "cpu",
}


def job_args(tmp_path, n_records, records_per_task, minibatch, num_workers,
             max_restarts=3, extra=()):
    return parse_master_args([
        "--model_zoo=model_zoo",
        "--model_def=mnist.mnist_functional_api",
        f"--training_data=synthetic://mnist?n={n_records}",
        f"--records_per_task={records_per_task}",
        f"--minibatch_size={minibatch}",
        f"--num_workers={num_workers}",
        f"--max_worker_restarts={max_restarts}",
        "--distribution_strategy=AllreduceStrategy",
        f"--checkpoint_dir={tmp_path / 'ckpt'}",
        "--checkpoint_steps=5",
        *extra,
    ])


@pytest.fixture
def worker_env(monkeypatch):
    monkeypatch.setenv(
        "ELASTICDL_WORKER_ENV",
        ";".join(f"{k}={v}" for k, v in WORKER_ENV.items()),
    )


def test_allreduce_two_workers_end_to_end(tmp_path, worker_env):
    args = job_args(
        tmp_path, n_records=96, records_per_task=32, minibatch=8, num_workers=2,
        extra=("--validation_data=synthetic://mnist?n=32",),
    )
    rc = run_allreduce_job(args, Mode.TRAINING)
    assert rc == 0
    # A checkpoint was written by rank 0.
    assert any(p.startswith("step_") for p in os.listdir(tmp_path / "ckpt"))


def test_worker_kill_then_scale_up_when_capacity_returns(tmp_path, worker_env):
    """Elastic rejoin e2e (real processes): kill a worker with the restart
    budget exhausted — the world shrinks to 1 — then signal returned
    capacity through the capacity-file oracle; the world grows back to 2
    and every record still trains exactly-at-least-once."""
    n_records = 4096
    args = job_args(
        tmp_path, n_records=n_records, records_per_task=256, minibatch=4,
        num_workers=2, max_restarts=0,
    )
    capacity_file = tmp_path / "capacity"
    capacity_file.write_text("0")

    def capacity_check(needed):
        try:
            return max(0, min(needed, int(capacity_file.read_text() or 0)))
        except (OSError, ValueError):
            return 0

    rendezvous = ElasticRendezvous()
    master = start_master(args, rendezvous_server=rendezvous)
    manager = LocalProcessManager(
        num_workers=2,
        worker_argv_fn=worker_argv_from_args(args, master.addr),
        rendezvous=rendezvous,
        task_manager=master.task_manager,
        max_restarts=0,
        worker_env=WORKER_ENV,
        log_dir=str(tmp_path / "logs"),
        job_finished_fn=master.task_manager.finished,
        scale_up_check_fn=capacity_check,
    )
    try:
        manager.start()
        deadline = time.time() + 240
        while master.task_manager.finished_record_count < n_records // 16:
            assert time.time() < deadline, "no progress before kill"
            assert not master.task_manager.finished(), "job finished too fast"
            time.sleep(0.05)
        victims = manager.current_worker_ids()
        manager.kill_worker(victims[1])
        # Budget 0: the world shrinks to a single fresh worker.
        deadline = time.time() + 240
        while len(manager.current_worker_ids()) != 1 or (
            manager.current_worker_ids() == victims[:1]
        ):
            assert time.time() < deadline, "world never shrank"
            time.sleep(0.05)
        shrunk = manager.current_worker_ids()
        # Capacity returns: the manager must grow the world back to 2.
        capacity_file.write_text("1")
        deadline = time.time() + 240
        while len(manager.current_worker_ids()) != 2:
            assert time.time() < deadline, "world never grew back"
            assert not master.task_manager.finished(), (
                "job finished before scale-up could be observed"
            )
            time.sleep(0.05)
        grown = manager.current_worker_ids()
        assert len(grown) == 2 and not set(grown) & set(shrunk)
        assert manager.wait(timeout=480) is True
        assert master.task_manager.finished()
        assert master.task_manager.finished_record_count == n_records
    finally:
        manager.stop()
        master.stop()


def test_worker_kill_elastic_recovery(tmp_path, worker_env):
    """Kill a worker mid-job: world re-forms (restart budget 0 => shrink to
    one fresh worker), state restores from checkpoint, all records still
    train (asserted by the shared driver in conftest)."""
    from tests.conftest import run_kill_recovery_job

    n_records = 4096
    args = job_args(
        tmp_path, n_records=n_records, records_per_task=256, minibatch=4,
        num_workers=2, max_restarts=0,
        # Persistent compile cache: the re-formed world's compiles are
        # disk hits (the recovery-time shave measured in BASELINE.md).
        extra=(f"--jax_compilation_cache_dir={tmp_path / 'jaxcache'}",),
    )
    metrics = run_kill_recovery_job(
        args, n_records, WORKER_ENV, str(tmp_path / "logs")
    )
    assert metrics["replayed_records"] <= 2 * 256  # <= both workers' tasks
