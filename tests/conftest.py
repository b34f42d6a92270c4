"""Test harness configuration.

Emulates an 8-chip TPU slice on CPU (SURVEY.md §4: the fake-device layer) so
pjit/shard_map/psum and mesh re-formation logic are exercised without
hardware.  Must run before the first `import jax` anywhere in the test
process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Keep XLA compilation single-threaded-friendly on the 1-core CI host.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

# Tests (and the worker/replica processes they start, which inherit the
# environment) never write the persistent compile cache: every process
# that compiles points it at <repo>/.jax_cache (common/compile_cache.py),
# and a suite run must not fill the checkout.  tests/test_chip_smoke.py
# tests the placement itself in subprocesses with their own environment.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
# The TPU compiler (the `topo` cases) keeps no log directory.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest

# The language models' contract and descriptors are helper modules (the
# contract's cases are imported into each model's own test files): their
# `assert`s are rewritten as a test file's are, so that a failure shows the
# values compared.
pytest.register_assert_rewrite("lm_contract", *(
    name[:-3] for name in os.listdir(os.path.dirname(__file__))
    if name.startswith("spec_") and name.endswith(".py")
))


@pytest.fixture
def obs_registry_snapshot():
    """Save/restore the process-wide obs registry around a test so
    metrics registered inside it (telemetry aggregators, ad-hoc gauges)
    can't leak into another test's scrape.  RESTORE, not reset(): metric
    objects bound at import time (the RPC retry counters in
    common/grpc_utils) must keep their registry membership — clearing
    would orphan them for the rest of the session.  For the same reason,
    every import-time registrant is imported BEFORE the snapshot: if the
    test itself triggered that first import, restore would silently
    unregister the freshly-bound module constants.  Yields the registry.
    """
    import elasticdl_tpu.common.grpc_utils  # noqa: F401 — import-time metrics
    from elasticdl_tpu import obs

    registry = obs.registry()
    saved = registry.snapshot()
    try:
        yield registry
    finally:
        registry.restore(saved)


@pytest.fixture
def dense_step_losses(monkeypatch):
    """Every loss the dense trainer's steps produce, in step order, under
    whichever of the worker loop's two dispatch forms ran them (a fused
    window of K steps, or one staged step)."""
    import numpy as np

    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer

    losses = []
    window, step = (
        DataParallelTrainer.train_window,
        DataParallelTrainer.train_step_staged,
    )

    def spy_window(self, staged):
        out = window(self, staged)
        losses.extend(float(x) for x in np.asarray(out))
        return out

    def spy_step(self, staged):
        out = step(self, staged)
        losses.append(float(out))
        return out

    monkeypatch.setattr(DataParallelTrainer, "train_window", spy_window)
    monkeypatch.setattr(DataParallelTrainer, "train_step_staged", spy_step)
    return losses


def one_device_trainer(model, loss_fn, optimizer, seed=0):
    """The dense trainer as Local mode builds it: on a mesh of one device."""
    import jax

    from elasticdl_tpu.parallel import (
        DataParallelTrainer,
        MeshConfig,
        build_mesh,
    )

    return DataParallelTrainer(
        model, loss_fn, optimizer,
        mesh=build_mesh(MeshConfig(), devices=jax.devices()[:1]),
        seed=seed,
    )


def run_kill_recovery_job(
    args, n_records, worker_env, log_dir, progress_fraction=8,
    wait_timeout=480, recovery_bound_s=240.0,
):
    """Shared kill-a-worker elasticity driver (used by the AllReduce and
    context-parallel e2es): start a 2-worker job, wait for real progress,
    SIGKILL the rank-1 worker (restart budget 0), and assert the world
    shrank to ONE fresh worker while every record still trained.

    Quantifies the elasticity claim (BASELINE.md "Elasticity" section):
    returns {"recovery_s": SIGKILL -> first record finished by the
    re-formed world (process start + world re-formation + checkpoint
    restore + compile + first task), "replayed_records": at-least-once
    replay cost (task ranges requeued from the dead worker)} and asserts
    recovery under `recovery_bound_s` — the regression tripwire."""
    import time

    from elasticdl_tpu.master.main import start_master
    from elasticdl_tpu.master.pod_manager import (
        LocalProcessManager,
        worker_argv_from_args,
    )
    from elasticdl_tpu.master.rendezvous_server import ElasticRendezvous

    rendezvous = ElasticRendezvous()
    master = start_master(args, rendezvous_server=rendezvous)
    manager = LocalProcessManager(
        num_workers=2,
        worker_argv_fn=worker_argv_from_args(args, master.addr),
        rendezvous=rendezvous,
        task_manager=master.task_manager,
        max_restarts=0,
        worker_env=worker_env,
        log_dir=log_dir,
        job_finished_fn=master.task_manager.finished,
    )
    try:
        manager.start()
        deadline = time.time() + 300
        while (
            master.task_manager.finished_record_count
            < n_records // progress_fraction
        ):
            assert time.time() < deadline, "no progress before kill"
            assert not master.task_manager.finished(), "finished too fast"
            time.sleep(0.05)
        victims = manager.current_worker_ids()
        assert len(victims) == 2
        replayed_before = master.task_manager.recovered_record_count
        t_kill = time.monotonic()
        manager.kill_worker(victims[1])
        # Recovery clock: kill -> the re-formed world finishes its first
        # record.  The count baseline is read only AFTER the relaunch is
        # visible (fresh worker ids) — the dying world's stragglers can
        # still report for a few seconds after the SIGKILL, and counting
        # those as "recovery" would fake a ~0s number.  The re-formed
        # workers need seconds to boot, far above the 20 ms poll, so the
        # baseline is race-free in practice.
        probe_deadline = time.time() + wait_timeout
        while time.time() < probe_deadline:
            ids = manager.current_worker_ids()
            if ids and not set(ids) & set(victims):
                break  # all-fresh world: relaunch happened
            time.sleep(0.02)
        count_at_relaunch = master.task_manager.finished_record_count
        recovery_s = None
        while time.time() < probe_deadline:
            if master.task_manager.finished_record_count > count_at_relaunch:
                recovery_s = time.monotonic() - t_kill
                break
            time.sleep(0.02)
        assert recovery_s is not None, "no post-kill progress"
        assert manager.wait(timeout=wait_timeout) is True
        assert master.task_manager.finished()
        assert master.task_manager.finished_record_count == n_records
        # The world actually shrank: a relaunch happened with 1 FRESH
        # worker (not the survivor continuing unperturbed).
        assert manager.current_worker_ids() != victims
        assert len(manager.current_worker_ids()) == 1
        replayed = (
            master.task_manager.recovered_record_count - replayed_before
        )
        # Replay is task-granular (whole ranges requeue; the exact
        # accounting is unit-tested in test_task_manager) and bounded by
        # what the dead world could have held in flight.
        assert replayed % args.records_per_task == 0, replayed
        assert recovery_s < recovery_bound_s, (
            f"recovery took {recovery_s:.1f}s (bound {recovery_bound_s}s) — "
            "the restore path regressed"
        )
        metrics = {
            "recovery_s": recovery_s,
            "replayed_records": replayed,
            "records_done_at_relaunch": count_at_relaunch,
        }
        print(f"ELASTICITY_METRICS {metrics}", flush=True)
        return metrics
    finally:
        manager.stop()
        master.stop()


@pytest.fixture(scope="module")
def topo():
    """A DESCRIBED `v5e:2x2`: the TPU compiler compiles for it from shapes
    alone, no chip (tests/test_tpu_compile*.py, and a model's window
    program in its tests/test_<m>_program.py)."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no libtpu here: nothing to compile with
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc}")


@pytest.fixture
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip (the next one warns and
    recompiles): keep the cache out of these cases."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()
