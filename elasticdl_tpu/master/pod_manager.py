"""Elastic worker-fleet management.

Parity: elasticdl/python/master/pod_manager.py (older
k8s_instance_manager.py) in the reference — create worker pods, watch
lifecycle events, relaunch failures within a restart budget, and drive task
recovery + rendezvous reset on churn (SURVEY.md §3.2).

TPU design — restart-the-world: when any member of a jax.distributed world
dies, the coordination service fatally terminates the surviving processes
(a dead host takes the slice down; verified empirically on jax 0.9).  So
churn recovery is not "patch the ring" but: recover all in-flight tasks,
tear the old world down, declare a new world (same size while the restart
budget lasts, shrunk otherwise) under a fresh rendezvous id, and relaunch
workers, which restore model state from the latest checkpoint.  Data
progress lives in the master's TaskManager, which survives — at-least-once
semantics mean no records are lost across re-formations.

That supervision policy is substrate-independent, so it lives in
`ElasticWorkerManager`; substrates plug in via five hooks (launch, poll,
terminate, kill, describe).  `LocalProcessManager` runs workers as
subprocesses (local mode, tests, single-host multi-process);
`KubernetesPodManager` (master/k8s_pod_manager.py) runs them as pods over
the same surface.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from elasticdl_tpu import obs
from elasticdl_tpu.analysis.runtime import make_lock
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.obs import goodput, tracing

logger = get_logger("master.pod_manager")


def _exit_reason(code) -> str:
    """Bounded relaunch-cause label from a worker exit code: 137 / -9 is
    the SIGKILL convention (preemption, OOM-kill, our own stale-worker
    kill); anything else nonzero is a crash."""
    return "preempted" if code in (137, -9) else "crash"


class ElasticWorkerManager:
    """Substrate-agnostic elastic supervision (restart-the-world policy).

    `worker_argv_fn(worker_id)` builds the worker command line;
    `on_world_change(worker_ids)` is told every new world before launch
    (wired to ElasticRendezvous.set_worker_hosts and
    TaskManager.recover_tasks by the caller).

    Subclasses implement:
      _substrate_start()                — one-time setup before first world
      _substrate_launch(worker_ids)    — start workers, return handles
                                         (objects with .worker_id)
      _substrate_poll(handle)          — None while alive, else exit code
      _substrate_terminate(handles)    — tear workers down, blocking
      _substrate_kill(handle, sig)     — hard-kill one worker
      _worker_host(worker_id)          — address advertised to rendezvous
    """

    def __init__(
        self,
        num_workers: int,
        worker_argv_fn: Callable[[int], List[str]],
        rendezvous=None,
        task_manager=None,
        max_restarts: int = 3,
        job_finished_fn: Optional[Callable[[], bool]] = None,
        poll_interval_s: float = 0.2,
        liveness_timeout_s: float = 0.0,
        startup_grace_s: Optional[float] = None,
        target_num_workers: Optional[int] = None,
        scale_up_check_fn: Optional[Callable[[int], int]] = None,
    ):
        self._num_workers = num_workers  # guarded-by: _lock
        self._worker_argv_fn = worker_argv_fn
        self._rendezvous = rendezvous
        self._task_manager = task_manager
        self._max_restarts = max_restarts
        self._job_finished_fn = job_finished_fn
        self._poll_interval_s = poll_interval_s
        self._liveness_timeout_s = liveness_timeout_s
        # Workers only heartbeat after spawn + imports + the distributed-init
        # barrier; judge never-heartbeated workers against a longer grace.
        self._startup_grace_s = (
            startup_grace_s
            if startup_grace_s is not None
            else 4 * liveness_timeout_s
        )
        # Elastic scale-up: the world may shrink under churn; when capacity
        # returns (scale_up_check_fn says so), grow back toward the target.
        self._target_num_workers = (  # guarded-by: _lock
            target_num_workers if target_num_workers is not None else num_workers
        )
        self._scale_up_check_fn = scale_up_check_fn

        self._lock = make_lock("ElasticWorkerManager._lock")
        # Serializes the world-REPLACING paths (scale(), churn
        # re-formation, elastic regrow): each is a long drain->relaunch
        # arc that releases _lock mid-flight, and two running
        # concurrently (the policy thread's scale() racing the monitor's
        # churn) would double-launch worlds and leak the loser's
        # processes.  Ordering: _resize_lock is always taken BEFORE
        # _lock, never the other way.
        self._resize_lock = make_lock("ElasticWorkerManager._resize_lock")
        self._handles: List = []  # guarded-by: _lock
        self._next_worker_id = 0  # guarded-by: _lock
        self._restarts_used = 0  # guarded-by: _lock
        self._stopped = False  # guarded-by: _lock
        self._failed_reason: Optional[str] = None  # guarded-by: _lock
        self._done_event = threading.Event()
        self._monitor_thread: Optional[threading.Thread] = None
        self._m_relaunches = obs.counter(
            "elasticdl_worker_relaunches_total",
            "Worker relaunches within world re-formations, by cause",
            labelnames=("reason",),
        )
        self._m_hung_kills = obs.counter(
            "elasticdl_hung_worker_kills_total",
            "Workers killed for silent heartbeats (hang -> churn)",
        )
        self._m_straggler_advisories = obs.counter(
            "elasticdl_straggler_advisories_total",
            "Straggler advisories received from the telemetry plane",
        )
        # Workers the telemetry plane currently flags as stragglers —
        # ADVISORY state for operators/schedulers (current_straggler_ids);
        # the liveness-timeout kill remains the only enforcement path.
        self._straggler_ids: set = set()  # guarded-by: _lock
        # Gauge callbacks read fields without the manager lock: a scrape
        # must never couple the exporter to the supervision lock, and the
        # len()/int reads are atomic enough for a monitoring sample.
        obs.gauge(
            "elasticdl_workers_target",
            "Worker count the elastic manager is trying to reach",
        ).set_function(lambda: self._target_num_workers)
        obs.gauge(
            "elasticdl_workers_actual", "Workers currently launched"
        ).set_function(lambda: len(self._handles))

    # ------------------------------------------------------------------
    # Substrate hooks
    # ------------------------------------------------------------------

    def _substrate_start(self):
        pass

    def _substrate_launch(self, worker_ids: List[int]) -> List:
        raise NotImplementedError

    def _substrate_poll(self, handle) -> Optional[int]:
        raise NotImplementedError

    def _substrate_terminate(self, handles: List):
        raise NotImplementedError

    def _substrate_kill(self, handle, sig: int = 9):
        raise NotImplementedError

    def _worker_host(self, worker_id: int) -> str:
        return "127.0.0.1"

    def _describe(self, handle) -> str:
        return f"worker {handle.worker_id}"

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------

    def start(self):
        self._substrate_start()
        self._launch_world(self._num_workers)
        self._monitor_thread = threading.Thread(
            target=self._monitor_loop, name="pod-manager-monitor", daemon=True
        )
        self._monitor_thread.start()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the job's worker fleet is done. True on success."""
        if not self._done_event.wait(timeout):
            raise TimeoutError("Worker fleet did not finish in time")
        return self._failed_reason is None

    @property
    def failed_reason(self) -> Optional[str]:
        return self._failed_reason

    @property
    def restarts_used(self) -> int:
        with self._lock:
            return self._restarts_used

    def stop(self):
        with self._lock:
            self._stopped = True
            handles = list(self._handles)
        self._substrate_terminate(handles)
        self._done_event.set()

    def current_worker_ids(self) -> List[int]:
        with self._lock:
            return [h.worker_id for h in self._handles]

    def note_straggler(self, worker_id: int, flagged: bool, evidence=None):
        """Advisory hook for the telemetry plane's straggler detector
        (obs/telemetry.TelemetryAggregator.add_straggler_callback).
        Deliberately does NOT kill: a straggler is making progress —
        killing it restarts the whole world and replays its in-flight
        work, usually worse than riding out the slowness.  The advisory
        is recorded (counter + log + `current_straggler_ids`); genuine
        hangs are still converted to churn by the liveness-timeout kill
        (_kill_stale_workers), and PERSISTENT stragglers are evicted by
        the policy engine (master/policy.py) through its own hysteresis
        and kill budget — `kill_worker` is the shared mechanism, the
        budget lives with the policy."""
        with self._lock:
            if flagged:
                self._straggler_ids.add(worker_id)
            else:
                self._straggler_ids.discard(worker_id)
        if flagged:
            self._m_straggler_advisories.inc()
            logger.warning(
                "Telemetry advisory: worker %d is straggling (%s); not "
                "killing — liveness timeout remains the enforcement path",
                worker_id, evidence or {},
            )
        else:
            logger.info(
                "Telemetry advisory: worker %d straggler cleared", worker_id
            )

    def current_straggler_ids(self) -> List[int]:
        with self._lock:
            return sorted(self._straggler_ids)

    def kill_worker(self, worker_id: int, sig: int = 9):
        """Fault injection / preemption simulation: kill one worker."""
        with self._lock:
            target = next(
                (h for h in self._handles if h.worker_id == worker_id), None
            )
        if target is None:
            raise ValueError(f"No live worker {worker_id}")
        # Kill outside the lock: on Kubernetes this is a blocking HTTP
        # DELETE that must not stall the monitor loop's lock acquisitions.
        self._substrate_kill(target, sig)

    def set_target_num_workers(self, num_workers: int):
        """Adjust the size the elastic manager is trying to reach WITHOUT
        forcing a rescale now: the monitor's `_maybe_scale_up` grows
        toward the new target as the capacity oracle (and the policy
        gate, when one is wired) allows.  The policy engine uses this to
        restore a storm-parked fleet once thrash clears."""
        with self._lock:
            self._target_num_workers = max(1, int(num_workers))

    def target_num_workers(self) -> int:
        with self._lock:
            return self._target_num_workers

    def scale(self, num_workers: int):
        """Explicit elastic resize: graceful drain (recover in-flight
        tasks, tear the old world down), then relaunch at the new size.
        Scale-DOWN lowers `_target_num_workers` too — the former
        `max()` clamp kept the old target, so `_maybe_scale_up` would
        immediately regrow and the shrink was silently a no-op."""
        if num_workers < 1:
            raise ValueError(f"scale() needs >= 1 worker, got {num_workers}")
        with self._resize_lock:
            with self._lock:
                if self._stopped:
                    return
                handles = list(self._handles)
                self._handles = []
            direction = (
                "up" if num_workers > len(handles)
                else "down" if num_workers < len(handles)
                else "flat"
            )
            logger.info(
                "Scaling world %d -> %d workers (%s)",
                len(handles), num_workers, direction,
            )
            goodput.ledger().on_rescale_detected("scale", len(handles))
            self._recover_world_tasks(handles)
            self._substrate_terminate(handles)
            goodput.ledger().on_drain_complete(num_workers)
            with self._lock:
                # scale() is an external-caller entry point racing the
                # monitor thread's churn/regrow writes to these fields.
                self._num_workers = num_workers
                self._target_num_workers = num_workers
            self._m_relaunches.inc(num_workers, reason="scale")
            obs.journal().record(
                "scale",
                old_size=len(handles),
                new_size=num_workers,
                direction=direction,
            )
            self._launch_world(num_workers)

    # ------------------------------------------------------------------
    # Internals
    # ------------------------------------------------------------------

    def _launch_each(
        self, worker_ids: List[int], exit_noticed: Optional[float] = None
    ) -> List:
        """Start the workers, each inside a `master.launch_worker` span
        (what leads to and includes its Popen / pod create).  `cause` is
        `start` for the job's first world (ids from 0, never reused) and
        `relaunch` for every later worker; where an exit brought the
        relaunch about, `since_exit_s` counts from the monitor's notice
        of it (`exit_noticed`, monotonic)."""
        handles = []
        for wid in worker_ids:
            fields = {"cause": "relaunch" if worker_ids[0] else "start"}
            if exit_noticed is not None:
                fields["since_exit_s"] = round(
                    time.monotonic() - exit_noticed, 6
                )
            with tracing.span(
                "master.launch_worker", worker_id=wid, **fields
            ):
                handles.extend(self._substrate_launch([wid]))
        return handles

    def _launch_world(self, n: int, exit_noticed: Optional[float] = None):
        with self._lock:
            if self._stopped:
                return
            worker_ids = list(range(self._next_worker_id, self._next_worker_id + n))
            self._next_worker_id += n
            # Straggler advisories die with the world: ids are never
            # reused, so a flagged worker that churned would otherwise
            # sit in the advisory set forever.
            self._straggler_ids.intersection_update(worker_ids)
        if self._rendezvous is not None:
            self._rendezvous.set_worker_hosts(
                [(wid, self._worker_host(wid)) for wid in worker_ids]
            )
        handles = self._launch_each(worker_ids, exit_noticed)
        with self._lock:
            if self._stopped:
                # stop() raced the launch; don't leak the new workers.
                stale = handles
                handles = []
            else:
                self._handles = handles
                stale = []
        self._substrate_terminate(stale)

    def _recover_world_tasks(self, handles: List):
        if self._task_manager is not None:
            for h in handles:
                self._task_manager.recover_tasks(h.worker_id)

    def _job_finished(self) -> bool:
        return bool(self._job_finished_fn and self._job_finished_fn())

    def _monitor_loop(self):
        try:
            self._monitor_loop_inner()
        except Exception as exc:  # never die silently: wait() must unblock
            logger.exception("Pod-manager monitor crashed")
            with self._lock:
                self._failed_reason = f"pod-manager monitor crashed: {exc}"
                self._stopped = True
                handles = list(self._handles)
            obs.journal().record(
                "job_failed", reason=f"pod-manager monitor crashed: {exc}"
            )
            goodput.ledger().finish("job_failed")
            self._substrate_terminate(handles)
            self._done_event.set()

    def _monitor_loop_inner(self):
        while True:
            time.sleep(self._poll_interval_s)
            with self._lock:
                if self._stopped:
                    return
                handles = list(self._handles)
            self._kill_stale_workers(handles)
            polled = [(h, self._substrate_poll(h)) for h in handles]
            exited = [(h, code) for h, code in polled if code is not None]
            if not exited:
                self._maybe_scale_up(handles)
                continue
            crashed = [(h, code) for h, code in exited if code != 0]
            if crashed and not self._job_finished():
                self._handle_churn(handles, crashed)
                with self._lock:
                    if self._stopped or not self._handles:
                        return
                continue
            if all(code is not None for _, code in polled):
                # Whole fleet exited cleanly (or job already done): finished.
                logger.info("All workers exited; job done")
                obs.journal().record(
                    "job_complete", restarts_used=self.restarts_used
                )
                goodput.ledger().finish(
                    "job_complete", restarts_used=self.restarts_used
                )
                self._done_event.set()
                return

    def _kill_stale_workers(self, handles: List):
        """Hung-worker detection: a worker whose heartbeat went silent is
        killed so the normal churn path re-forms the world (worker exit is
        the only signal the monitor reacts to; this converts 'wedged but
        alive' into it)."""
        if (
            self._liveness_timeout_s <= 0
            or self._rendezvous is None
            or self._job_finished()
        ):
            return
        stale = set(
            self._rendezvous.stale_workers(
                self._liveness_timeout_s, self._startup_grace_s
            )
        )
        for h in handles:
            if h.worker_id in stale and self._substrate_poll(h) is None:
                logger.warning(
                    "Worker %d heartbeat stale > %.0fs; killing it",
                    h.worker_id,
                    self._liveness_timeout_s,
                )
                self._m_hung_kills.inc()
                obs.journal().record(
                    "hung_worker_kill",
                    worker_id=h.worker_id,
                    silent_s=self._liveness_timeout_s,
                )
                self._substrate_kill(h, 9)

    def _maybe_scale_up(self, handles: List) -> bool:
        """Elastic rejoin: if the world shrank under churn and capacity has
        returned, re-form at a larger size (reference behavior: scavenge
        freed resources back up to the requested worker count, SURVEY §6).
        Growth is still restart-the-world — workers restore from the latest
        checkpoint and the TaskManager replays in-flight work."""
        current = len(handles)
        if current >= self._target_num_workers or self._scale_up_check_fn is None:
            return False
        if self._job_finished():
            return False
        with self._resize_lock:
            with self._lock:
                if self._stopped or self._handles != handles:
                    # The world was replaced (a concurrent scale() on the
                    # policy thread) since this snapshot was polled; the
                    # next monitor tick re-evaluates against the new one.
                    return False
            grant = self._scale_up_check_fn(self._target_num_workers - current)
            if grant <= 0:
                return False
            new_size = min(self._target_num_workers, current + grant)
            logger.info(
                "Capacity returned: growing world %d -> %d workers",
                current,
                new_size,
            )
            with self._lock:
                if self._stopped:
                    return True
                self._handles = []
                self._num_workers = new_size
            # Counted only once the regrow is actually committed (a stop()
            # racing the grant above must not journal a phantom rescale).
            self._m_relaunches.inc(new_size, reason="scale_up")
            obs.journal().record(
                "scale_up", old_size=current, new_size=new_size
            )
            goodput.ledger().on_rescale_detected("scale_up", current)
            self._recover_world_tasks(handles)
            self._substrate_terminate(handles)
            goodput.ledger().on_drain_complete(new_size)
            self._launch_world(new_size)
            return True

    def _handle_churn(self, handles: List, crashed):
        """One churn event: any worker death invalidates the whole world."""
        with self._resize_lock:
            with self._lock:
                if self._stopped or self._handles != handles:
                    # The world was replaced (a concurrent scale() on the
                    # policy thread already drained these processes);
                    # their exits are expected teardown, not churn.
                    return
            self._handle_churn_serialized(handles, crashed)

    def _handle_churn_serialized(self, handles: List, crashed):
        exit_noticed = time.monotonic()
        for h, code in crashed:
            logger.warning(
                "%s died (exit %s) — world re-formation",
                self._describe(h),
                code,
            )
            self._m_relaunches.inc(reason=_exit_reason(code))
        with self._lock:
            self._handles = []
            self._restarts_used += 1
            budget_left = self._restarts_used <= self._max_restarts
            old_size = len(handles)
        obs.journal().record(
            "worker_churn",
            workers=[h.worker_id for h, _ in crashed],
            exit_codes=[code for _, code in crashed],
            old_size=old_size,
            restarts_used=self._restarts_used,
            budget_left=budget_left,
        )
        # Rescale-cost clock starts at detection; churn requeues below
        # land inside the open record via TaskManager.recover_tasks.
        goodput.ledger().on_rescale_detected("worker_churn", old_size)
        self._recover_world_tasks(handles)
        self._substrate_terminate(handles)  # survivors die with the world
        new_size = old_size if budget_left else old_size - 1
        goodput.ledger().on_drain_complete(max(0, new_size))
        if new_size < 1:
            with self._lock:
                self._failed_reason = reason = (
                    f"restart budget exhausted ({self._restarts_used - 1} "
                    "used) and no workers left"
                )
                self._stopped = True
            logger.error("Job failed: %s", reason)
            obs.journal().record("job_failed", reason=reason)
            goodput.ledger().finish("job_failed")
            self._done_event.set()
            return
        logger.info(
            "Re-forming world: %d -> %d workers (restart %d/%d)",
            old_size,
            new_size,
            self._restarts_used,
            self._max_restarts,
        )
        self._launch_world(new_size, exit_noticed=exit_noticed)


class WorkerProcess:
    def __init__(self, worker_id: int, popen: subprocess.Popen, log_path: str):
        self.worker_id = worker_id
        self.popen = popen
        self.log_path = log_path


class LocalProcessManager(ElasticWorkerManager):
    """Subprocess substrate: workers are local child processes."""

    def __init__(
        self,
        num_workers: int,
        worker_argv_fn: Callable[[int], List[str]],
        worker_env: Optional[Dict[str, str]] = None,
        log_dir: str = "",
        **kwargs,
    ):
        super().__init__(num_workers, worker_argv_fn, **kwargs)
        self._worker_env = dict(worker_env or {})
        self._log_dir = log_dir

    def _substrate_start(self):
        if self._log_dir:
            os.makedirs(self._log_dir, exist_ok=True)

    def _substrate_launch(self, worker_ids: List[int]) -> List[WorkerProcess]:
        procs = []
        for wid in worker_ids:
            argv = self._worker_argv_fn(wid)
            log_path = (
                os.path.join(self._log_dir, f"worker_{wid}.log")
                if self._log_dir
                else os.devnull
            )
            log_file = open(log_path, "wb")
            env = {**os.environ, **self._worker_env}
            popen = subprocess.Popen(
                argv, stdout=log_file, stderr=subprocess.STDOUT, env=env
            )
            log_file.close()
            procs.append(WorkerProcess(wid, popen, log_path))
            logger.info("Launched worker %d (pid %d)", wid, popen.pid)
        return procs

    def _substrate_poll(self, handle: WorkerProcess) -> Optional[int]:
        return handle.popen.poll()

    def _substrate_terminate(self, handles: List[WorkerProcess]):
        for wp in handles:
            if wp.popen.poll() is None:
                try:
                    wp.popen.terminate()
                except ProcessLookupError:
                    pass
        deadline = time.time() + 5
        for wp in handles:
            try:
                wp.popen.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                wp.popen.kill()
                wp.popen.wait()

    def _substrate_kill(self, handle: WorkerProcess, sig: int = 9):
        try:
            handle.popen.send_signal(sig)
        except ProcessLookupError:
            pass

    def _describe(self, handle: WorkerProcess) -> str:
        return f"Worker {handle.worker_id} (log: {handle.log_path})"


def worker_argv_from_args(args, master_addr: str) -> Callable[[int], List[str]]:
    """Build the worker command line from parsed job args (flag round-trip,
    reference behavior: client flags forward to pods)."""
    from elasticdl_tpu.common.args import args_to_argv

    forwarded = args_to_argv(
        args,
        keys={
            "model_zoo", "model_def", "model_params", "dataset_fn", "loss",
            "optimizer", "eval_metrics_fn", "custom_data_reader", "callbacks",
            "training_data", "validation_data", "prediction_data",
            "records_per_task", "minibatch_size", "num_epochs",
            "data_reader_params", "distribution_strategy", "log_level",
            "checkpoint_dir", "checkpoint_steps", "keep_checkpoint_max",
            "output", "use_bf16", "tensorboard_log_dir", "profile_steps",
            "train_window_steps", "dense_sharding", "mesh_model_axis",
            "sparse_apply_every", "sparse_kernel",
            "pipeline", "parse_pool_workers", "pipeline_inflight",
            "dispatch_depth",
            "jax_compilation_cache_dir", "oov_diagnostics",
        },
    )

    def argv_fn(worker_id: int) -> List[str]:
        return [
            sys.executable,
            "-m",
            "elasticdl_tpu.worker.main",
            f"--worker_id={worker_id}",
            f"--master_addr={master_addr}",
            *forwarded,
        ]

    return argv_fn
