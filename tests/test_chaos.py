"""Chaos suite: deterministic fault injection + a real master-outage e2e.

The e2e is the tentpole proof: SIGKILL the master mid-job while two real
workers hold in-flight tasks — the workers ride through the outage on the
RPC retry plane (no worker dies, no restart-the-world), the replacement
master (same port) resumes from the persisted shard-progress snapshot, and
the job completes with every record of every epoch processed at least
once.

The checkpoint-plane tests drive the `ckpt.write:truncate` injection
point: a torn write is detected by the CRC32 integrity manifest, the
snapshot is quarantined (with a logged reason), and restore falls back to
the previous step — it never crashes and never loads garbage.
"""

import contextlib
import json
import logging
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

from elasticdl_tpu.common import faults
from elasticdl_tpu.common.grpc_utils import RetryPolicy
from elasticdl_tpu.proto import elasticdl_pb2 as pb
from elasticdl_tpu.worker.master_client import MasterClient

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.clear()


@contextlib.contextmanager
def capture_logs(logger_name):
    """The framework root logger doesn't propagate (log_utils); attach a
    recording handler directly."""
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    logger = logging.getLogger(logger_name)
    logger.addHandler(handler)
    try:
        yield records
    finally:
        logger.removeHandler(handler)


# ---------------------------------------------------------------------------
# Tentpole e2e: master SIGKILL mid-job, workers ride through on retries.
# ---------------------------------------------------------------------------

#: Snappy retry plane for a localhost outage measured in seconds.
CHAOS_POLICY = RetryPolicy(
    timeout_s=3.0,
    max_attempts=400,
    base_backoff_s=0.05,
    max_backoff_s=0.25,
    jitter=0.25,
    total_budget_s=120.0,
    wait_for_ready=True,
)


def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("localhost", 0))
        return sock.getsockname()[1]


class RecordingClient(MasterClient):
    """MasterClient that records which (epoch, start, end) training ranges
    this worker COMPLETED (result report accepted by a master)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.completed = []
        self._inflight = {}

    def get_task(self, task_type=pb.TRAINING):
        task = super().get_task(task_type)
        if task.task_id >= 0 and task.type == pb.TRAINING:
            self._inflight[task.task_id] = (task.epoch, task.start, task.end)
        return task

    def report_task_result(self, task_id, err_message="", exec_counters=None,
                           trace_id=""):
        super().report_task_result(
            task_id, err_message, exec_counters, trace_id=trace_id
        )
        if not err_message and task_id in self._inflight:
            self.completed.append(self._inflight.pop(task_id))


def _start_master(ckpt_dir, port, shard_name, n_records, rpt, epochs, log_path):
    repo_root = os.path.dirname(TESTS_DIR)
    env = {**os.environ}
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo_root, env.get("PYTHONPATH", "")) if p
    )
    with open(log_path, "ab") as log_file:
        return subprocess.Popen(
            [
                sys.executable,
                os.path.join(TESTS_DIR, "chaos_master.py"),
                str(ckpt_dir), str(port), shard_name,
                str(n_records), str(rpt), str(epochs),
            ],
            stdout=log_file,
            stderr=subprocess.STDOUT,
            env=env,
        )


def test_master_sigkill_midjob_workers_ride_through(tmp_path):
    from elasticdl_tpu.common.args import parse_master_args
    from elasticdl_tpu.common.model_utils import load_model_spec
    from elasticdl_tpu.data.reader import build_data_reader
    from elasticdl_tpu.worker.main import _build_collective_worker

    # Long enough (256 tasks, ~10 s) that the kill lands mid-job even when a
    # loaded machine delays this thread or the snapshot by seconds.
    n_records, rpt, epochs = 4096, 32, 2
    port = _free_port()
    ckpt_dir = tmp_path / "ckpt"
    ckpt_dir.mkdir()
    master_log = str(tmp_path / "master.log")

    args = parse_master_args([
        "--model_zoo=model_zoo",
        "--model_def=mnist.mnist_functional_api",
        f"--training_data=synthetic://mnist?n={n_records}",
        f"--records_per_task={rpt}",
        "--minibatch_size=16",
        f"--num_epochs={epochs}",
    ])
    model_spec = load_model_spec(args)
    # The driver master serves the shard name the workers' reader expects.
    reader = build_data_reader(args, model_spec, args.training_data)
    (shard_name,) = reader.shard_names()

    proc = _start_master(
        ckpt_dir, port, shard_name, n_records, rpt, epochs, master_log
    )
    clients, workers, threads, errors = [], [], [], []
    try:
        for wid in range(2):
            client = RecordingClient(
                f"localhost:{port}", worker_id=wid, retry_policy=CHAOS_POLICY
            )
            clients.append(client)
            # Local mode's worker (the default strategy): a world of one
            # per client, no supervisor to relaunch it.
            workers.append(_build_collective_worker(
                args, model_spec,
                build_data_reader(args, model_spec, args.training_data),
                client,
            ))

        def run(worker):
            try:
                worker.run()
            except Exception as exc:  # noqa: BLE001 — the assert below
                errors.append(exc)

        for wid, worker in enumerate(workers):
            thread = threading.Thread(
                target=run, args=(worker,),
                name=f"chaos-worker-{wid}", daemon=True,
            )
            thread.start()
            threads.append(thread)

        # Let real progress land — tasks completed AND a progress
        # snapshot holding some of them persisted — with both workers
        # mid-job...
        def persisted_finished_records():
            try:
                with open(ckpt_dir / "task_progress.json") as f:
                    return json.load(f).get("finished_record_count", 0)
            except (OSError, ValueError):
                return 0

        deadline = time.time() + 300
        while (
            sum(len(c.completed) for c in clients) < 5
            or persisted_finished_records() == 0
        ):
            assert time.time() < deadline, "no progress before the kill"
            assert proc.poll() is None, "master died prematurely"
            time.sleep(0.01)

        # ... then SIGKILL the master.  Hold the outage open until both
        # facts are on record: the workers actually RETRIED (an in-flight
        # RPC died with UNAVAILABLE, or a wait_for_ready poll hit its
        # deadline — a too-short outage can be absorbed by a single
        # pending RPC with zero retries), and nothing died.
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        outage_deadline = time.time() + 30
        while (
            sum(c.retry_stats.retries for c in clients) == 0
            and time.time() < outage_deadline
        ):
            time.sleep(0.05)
        for thread in threads:
            assert thread.is_alive(), "a worker died during the outage"

        # Replacement master: same port, resumes the persisted snapshot.
        proc = _start_master(
            ckpt_dir, port, shard_name, n_records, rpt, epochs, master_log
        )
        for thread in threads:
            thread.join(timeout=420)
            assert not thread.is_alive(), "worker never finished after resume"
        assert not errors, f"worker(s) crashed: {errors!r}"
        assert proc.wait(timeout=120) == 0

        # The replacement really RESUMED (did not restart the epoch).
        with open(ckpt_dir / "MASTER_DONE") as f:
            done = json.load(f)
        assert done["resumed"] is True
        assert done["resumed_finished_records"] > 0

        # Workers rode through the outage on the retry plane.
        assert sum(c.retry_stats.retries for c in clients) > 0

        # The journal reconstructs the outage post-hoc: both master
        # generations appended to one timeline (events.jsonl survives the
        # SIGKILL), the resume and the training-epoch bump are on record.
        with open(ckpt_dir / "events.jsonl") as f:
            events = [json.loads(line) for line in f if line.strip()]
        assert sum(e["event"] == "master_start" for e in events) == 2
        assert any(e["event"] == "task_progress_resume" for e in events)
        assert any(e["event"] == "train_epoch_done" for e in events)

        # No lost records: every record of BOTH epochs completed at least
        # once across the two master generations (at-least-once).
        for epoch in range(epochs):
            covered = set()
            for client in clients:
                for ep, start, end in client.completed:
                    if ep == epoch:
                        covered.update(range(start, end))
            assert covered == set(range(n_records)), (
                f"gap in epoch {epoch}: "
                f"{sorted(set(range(n_records)) - covered)[:10]}..."
            )

        # Postmortem forensics: the goodput report replays the SAME
        # journal into a timeline whose phase durations cover wall-clock
        # and whose outage (the SIGKILL -> replacement gap) is attributed.
        from elasticdl_tpu.obs import report as report_mod

        summary = report_mod.summarize(
            report_mod.load_events(str(ckpt_dir / "events.jsonl"))
        )
        wall = summary["wall_s"]
        assert wall > 0
        assert abs(sum(summary["phases"].values()) - wall) <= 0.02 * wall
        assert summary["generations"] == 2
        assert summary["outages"], "master outage not attributed"
        assert summary["outage_s"] > 0
        assert 0.0 < summary["goodput_ratio"] <= 1.0
        assert summary["phases"].get("training", 0.0) > 0.0
        assert summary["ledger_summary"]["outcome"] == "job_complete"
        report_mod.render_report(summary)  # must not raise

        # And the journal — including the goodput event types — passes
        # the schema validator (the drift gate's runtime half).
        check = subprocess.run(
            [
                sys.executable,
                os.path.join(
                    os.path.dirname(TESTS_DIR), "scripts",
                    "validate_journal.py",
                ),
                str(ckpt_dir / "events.jsonl"),
            ],
            capture_output=True,
            text=True,
        )
        assert check.returncode == 0, check.stderr
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        for client in clients:
            client.close()
        if os.path.exists(master_log):
            sys.stderr.write(open(master_log).read()[-4000:])


# ---------------------------------------------------------------------------
# Preemption storm: the policy engine beats both baselines on goodput.
# ---------------------------------------------------------------------------

#: Deterministic spot-VM-style storm: at each scheduled time, every live
#: supervised worker except the lowest-id one (the "on-demand" slot) is
#: SIGKILLed.  Schedule-based fault specs (common/faults.py `@t`).
STORM_WAVES = (0.9, 2.3, 3.7, 5.1, 6.5, 7.9)
STORM_SITE = "storm.preempt"
STORM_SPEC = ",".join(f"{STORM_SITE}:crash@t{t}" for t in STORM_WAVES)
#: In-flight tasks assigned to each wave victim right before its kill —
#: the requeue/redo surface a preemption really has.
STORM_TASKS_PER_VICTIM = 2


def _drive_storm(manager, task_manager, stop_event):
    """Apply the armed storm schedule against a live LocalProcessManager:
    poll faults.due() on this thread's own monotonic timeline and turn
    each due spec into one preemption wave."""
    from elasticdl_tpu.common import faults as storm_faults

    t0 = time.monotonic()
    while not stop_event.is_set() and storm_faults.remaining_due(STORM_SITE):
        for _spec in storm_faults.due(STORM_SITE, time.monotonic() - t0):
            victims = sorted(manager.current_worker_ids())[1:]
            for wid in victims:
                for _ in range(STORM_TASKS_PER_VICTIM):
                    task_manager.get(wid)  # in-flight work dies with it
                try:
                    manager.kill_worker(wid, 9)
                except ValueError:
                    pass  # lost a race with churn; the wave moves on
        time.sleep(0.02)


def _run_storm_job(run_dir, *, max_restarts, elastic, policy_config=None,
                   n_tasks=320, task_s=0.035):
    """One full job under the deterministic preemption storm.  Returns
    (goodput_summary fields, full journal event list).

    Configurations compared by the e2e:
      fixed-size       elastic=False, big restart budget (every wave
                       pays a full same-size re-formation)
      always-rescale   elastic=True, restart budget 0 (every wave pays a
                       shrink-churn AND an immediate greedy regrow)
      policy           elastic=True + ElasticPolicyEngine (thrash parks
                       the fleet at the floor, restore + scale-up only
                       once the storm clears and the cost amortizes)
    """
    from elasticdl_tpu import obs
    from elasticdl_tpu.master.pod_manager import LocalProcessManager
    from elasticdl_tpu.master.rendezvous_server import ElasticRendezvous
    from elasticdl_tpu.master.task_manager import TaskManager
    from elasticdl_tpu.obs import goodput

    os.makedirs(run_dir, exist_ok=True)
    journal_path = obs.init_journal(str(run_dir))
    ledger = goodput.reset_ledger()
    faults.install(STORM_SPEC)
    sleeper = os.path.join(run_dir, "sleeper.py")
    with open(sleeper, "w") as f:
        f.write("import time\ntime.sleep(300)\n")
    manager = None
    engine = None
    storm_stop = threading.Event()
    storm_thread = None
    try:
        obs.journal().record("master_start", job_name="storm-e2e", port=0)
        ledger.transition("idle", cause="master_start")
        task_manager = TaskManager(
            training_shards={"shard": n_tasks * 8}, records_per_task=8
        )
        rendezvous = ElasticRendezvous(coordinator_port_fn=lambda host: 29321)
        if policy_config is not None:
            from elasticdl_tpu.master.policy import ElasticPolicyEngine

            engine = ElasticPolicyEngine(policy_config, ledger=ledger)
        oracle = None
        if elastic:
            oracle = (
                (lambda needed: engine.gate_scale_up(needed, needed))
                if engine is not None
                else (lambda needed: needed)
            )
        manager = LocalProcessManager(
            num_workers=3,
            worker_argv_fn=lambda wid: [sys.executable, sleeper],
            rendezvous=rendezvous,
            task_manager=task_manager,
            max_restarts=max_restarts,
            job_finished_fn=task_manager.finished,
            poll_interval_s=0.05,
            scale_up_check_fn=oracle,
        )
        if engine is not None:
            engine.bind(manager)
        manager.start()
        if engine is not None:
            engine.start()
        storm_thread = threading.Thread(
            target=_drive_storm, args=(manager, task_manager, storm_stop),
            name="storm-driver", daemon=True,
        )
        storm_thread.start()

        # The in-process trainer (worker 99 — never supervised, so churn
        # never requeues ITS tasks) works the queue at a fixed rate; the
        # supervised sleepers are the storm's preemption surface.
        from elasticdl_tpu.proto import elasticdl_pb2 as pb

        deadline = time.time() + 120
        while not task_manager.finished():
            assert time.time() < deadline, "storm job never finished"
            task = task_manager.get(99)
            if task.task_id == -1:
                if task.type == pb.WAIT:
                    time.sleep(0.01)
                    continue
                break
            time.sleep(task_s)
            task_manager.report(task.task_id, True, worker_id=99)
        assert task_manager.finished()
        storm_stop.set()
        storm_thread.join(timeout=10)
        if engine is not None:
            engine.stop()
        manager.stop()
        ledger.finish("job_complete")
        with open(journal_path) as f:
            events = [json.loads(line) for line in f if line.strip()]
        (summary,) = [
            e for e in events if e["event"] == "goodput_summary"
        ]
        return summary, events
    finally:
        storm_stop.set()
        if storm_thread is not None:
            storm_thread.join(timeout=10)
        if engine is not None:
            engine.stop()
        if manager is not None:
            manager.stop()
        faults.clear()
        obs.journal().configure(None)
        goodput.reset_ledger()


def test_preemption_storm_policy_beats_both_baselines(
    tmp_path, obs_registry_snapshot
):
    """Acceptance (ISSUE 7): under one deterministic preemption-storm
    schedule, the policy engine's end-of-job goodput_summary strictly
    beats the fixed-size AND the naive always-rescale baselines on the
    goodput ledger's own accounting, and every scale action it took has
    a matching policy_decision journal event with evidence."""
    from elasticdl_tpu.master.policy import PolicyConfig

    fixed, _fixed_events = _run_storm_job(
        str(tmp_path / "fixed"), max_restarts=30, elastic=False,
    )
    naive, naive_events = _run_storm_job(
        str(tmp_path / "naive"), max_restarts=0, elastic=True,
    )
    policy_config = PolicyConfig(
        tick_interval_s=0.1,
        amortize_horizon_s=600.0,
        min_workers=1,
        cooldown_factor=1.0,
        min_cooldown_s=1.6,
        thrash_window_s=6.0,
        thrash_rescales=2,
        thrash_overhead_frac=0.02,
        scale_down_after=2,
        hold_journal_interval_s=0.5,
    )
    policy, policy_events = _run_storm_job(
        str(tmp_path / "policy"), max_restarts=30, elastic=True,
        policy_config=policy_config,
    )

    # Both baselines paid the storm in full; the policy rode it out at
    # the floor.  Strict inequality on the ledger's own accounting is
    # the paper's claim: elasticity that pays for itself.
    assert policy["goodput_ratio"] > fixed["goodput_ratio"], (policy, fixed)
    assert policy["goodput_ratio"] > naive["goodput_ratio"], (policy, naive)
    # The policy avoided rescales instead of buying them: strictly fewer
    # than the always-rescale baseline, and less redone work than either.
    assert policy["rescales"] < naive["rescales"]
    assert policy["records_redone"] < fixed["records_redone"]
    assert policy["records_redone"] < naive["records_redone"]

    # Every scale/evict ACTION in the policy run has a matching
    # policy_decision with evidence; the baselines made none.
    decisions = [
        e for e in policy_events if e["event"] == "policy_decision"
    ]
    downs = [d for d in decisions if d["action"] == "scale_down"]
    ups = [d for d in decisions if d["action"] == "scale_up"]
    scale_events = [e for e in policy_events if e["event"] == "scale"]
    scale_up_events = [e for e in policy_events if e["event"] == "scale_up"]
    # The storm parked the fleet once, and the loop closed with an
    # approved, amortized regrow after the storm.
    assert len(scale_events) == 1 and scale_events[0]["direction"] == "down"
    assert len(downs) == len(scale_events)
    assert downs[0]["reason"] == "rescale_thrash"
    assert downs[0]["window_rescales"] >= 2
    assert len(scale_up_events) >= 1
    assert len(ups) >= len(scale_up_events)
    assert all(u["reason"] == "amortized" for u in ups)
    assert all("required_horizon_s" in u for u in ups)
    # Thrash holds were journaled while scale-ups were being denied.
    assert any(
        d["action"] == "hold" and d["reason"] == "rescale_thrash"
        for d in decisions
    )
    assert not any(
        e["event"] == "policy_decision" for e in naive_events
    )

    # The policy journal passes the schema validator (policy_decision is
    # a registered event type).
    check = subprocess.run(
        [
            sys.executable,
            os.path.join(
                os.path.dirname(TESTS_DIR), "scripts", "validate_journal.py"
            ),
            os.path.join(str(tmp_path / "policy"), "events.jsonl"),
        ],
        capture_output=True,
        text=True,
    )
    assert check.returncode == 0, check.stderr


# ---------------------------------------------------------------------------
# Event journal: a rescale is reconstructable from the JSONL timeline.
# ---------------------------------------------------------------------------


def test_journal_reconstructs_rescale(tmp_path):
    """Acceptance: a worker-death rescale leaves journal records that
    reconstruct it — the rendezvous epoch bump AND the churn requeues, in
    order — without consulting any log file."""
    from elasticdl_tpu import obs
    from elasticdl_tpu.master.rendezvous_server import ElasticRendezvous
    from elasticdl_tpu.master.task_manager import TaskManager

    journal_path = obs.init_journal(str(tmp_path))
    try:
        manager = TaskManager(
            training_shards={"shard": 256}, records_per_task=64
        )
        rendezvous = ElasticRendezvous(
            coordinator_port_fn=lambda host: 12345
        )
        rendezvous.set_worker_hosts([(0, "127.0.0.1"), (1, "127.0.0.1")])
        task0 = manager.get(0)
        task1 = manager.get(1)
        assert task0.task_id >= 0 and task1.task_id >= 0
        # Worker 1 dies: its in-flight task requeues and the world
        # re-forms one smaller under a fresh rendezvous id.
        manager.recover_tasks(1)
        rendezvous.set_worker_hosts([(0, "127.0.0.1")])

        with open(journal_path) as f:
            events = [json.loads(line) for line in f if line.strip()]
        declarations = [
            (i, e) for i, e in enumerate(events) if e["event"] == "rendezvous"
        ]
        assert [e["rendezvous_id"] for _, e in declarations] == [1, 2]
        assert [e["world_size"] for _, e in declarations] == [2, 1]
        requeues = [
            (i, e) for i, e in enumerate(events) if e["event"] == "task_requeue"
        ]
        assert len(requeues) == 1
        index, requeue = requeues[0]
        assert requeue["reason"] == "worker_churn"
        assert requeue["worker_id"] == 1
        assert requeue["task_ids"] == [task1.task_id]
        # Order on the timeline: world declared, worker died (requeue),
        # shrunk world declared.
        assert declarations[0][0] < index < declarations[1][0]
    finally:
        obs.journal().configure(None)


# ---------------------------------------------------------------------------
# Checkpoint plane: torn writes are quarantined, restore falls back.
# ---------------------------------------------------------------------------


def test_torn_checkpoint_write_quarantined_and_falls_back(tmp_path):
    from elasticdl_tpu.checkpoint.saver import CheckpointSaver

    saver = CheckpointSaver(str(tmp_path), keep_max=5)
    saver.save({"w": [1, 2, 3], "step": 1}, step=1)
    faults.install("ckpt.write:truncate@1")  # tear the NEXT save
    saver.save({"w": [4, 5, 6], "step": 2}, step=2)
    faults.clear()

    with capture_logs("elasticdl_tpu.checkpoint.saver") as records:
        state, step = saver.load_latest()
    # Fell back exactly one step; the torn snapshot never loaded.
    assert step == 1
    assert state == {"w": [1, 2, 3], "step": 1}
    quarantined = [
        n for n in os.listdir(tmp_path) if n.endswith(".quarantined")
    ]
    assert quarantined == ["step_000000000002.quarantined"]
    messages = [r.getMessage() for r in records]
    assert any("Quarantin" in m and "falling back" in m for m in messages)
    # The quarantined snapshot is invisible to future restores/GC.
    assert saver.steps() == [1]
    # And a fresh save at the same step works (the dir name is free).
    saver.save({"w": [7], "step": 2}, step=2)
    state, step = saver.load_latest()
    assert (step, state) == (2, {"w": [7], "step": 2})


def test_sharded_torn_write_falls_back_one_step(tmp_path):
    from elasticdl_tpu.checkpoint.sharded import ShardedCheckpointSaver

    saver = ShardedCheckpointSaver(str(tmp_path), keep_max=5)
    saver.save(1, {"dense": [1.0]}, sharded={})
    faults.install("ckpt.write:truncate@1")
    saver.save(2, {"dense": [2.0]}, sharded={})
    faults.clear()

    with capture_logs("elasticdl_tpu.checkpoint.saver") as records:
        assert saver.latest_step() == 1
    assert saver.load_dense(1) == {"dense": [1.0]}
    assert any(
        "Quarantin" in r.getMessage() for r in records
    )
    assert any(
        n.endswith(".quarantined") for n in os.listdir(tmp_path)
    )


def test_unreadable_and_empty_step_dirs_are_skipped(tmp_path):
    """Satellite: steps()/restore skip junk step dirs with a warning
    instead of raising mid-listing."""
    from elasticdl_tpu.checkpoint.saver import CheckpointSaver

    saver = CheckpointSaver(str(tmp_path), keep_max=5)
    saver.save({"ok": True}, step=3)
    os.makedirs(tmp_path / "step_000000000009")  # empty: no state file
    (tmp_path / "step_000000000010").mkdir()
    (tmp_path / "step_000000000010" / "state.pkl").write_bytes(b"")  # empty
    (tmp_path / "step_notanumber").mkdir()

    with capture_logs("elasticdl_tpu.checkpoint.saver") as records:
        assert saver.steps() == [3]
    assert sum(
        "incomplete/unreadable" in r.getMessage() for r in records
    ) == 2
    state, step = saver.load_latest()
    assert (step, state) == (3, {"ok": True})


def test_crashed_save_tmp_dir_swept_at_startup(tmp_path):
    """Satellite: stale .tmp dirs from crashed saves are garbage-collected
    by the startup sweep; fresh ones (a live peer's save) are kept."""
    from elasticdl_tpu.checkpoint.saver import CheckpointSaver

    stale = tmp_path / "step_000000000004.tmpabc"
    stale.mkdir()
    (stale / "state.pkl").write_bytes(b"partial")
    old = time.time() - 7200
    os.utime(stale, (old, old))
    fresh = tmp_path / "step_000000000005.tmpdef"
    fresh.mkdir()

    saver = CheckpointSaver(str(tmp_path), keep_max=5)
    assert not stale.exists(), "stale crashed-save tmp dir not swept"
    assert fresh.exists(), "in-flight peer save must not be swept"
    assert saver.steps() == []
