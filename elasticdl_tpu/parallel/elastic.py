"""Worker-side elastic world membership and collective task flow.

Parity: the reference's elastic-Horovod worker path
(worker/allreduce_trainer.py + master rendezvous, SURVEY.md §3.4): workers
ask the master `get_comm_rank`, join the communicator, and re-join when
membership changes.  TPU design: "the communicator" is a jax.distributed
world + Mesh; joining = `jax.distributed.initialize` with the assigned
(rank, world, coordinator).  A member death fatally kills the whole world
(see master/pod_manager.py), so re-join happens in a fresh process after
the pod manager re-forms the world — this module is what that fresh
process runs.

Task flow in a multi-process world: rank 0 pulls tasks from the master and
broadcasts them to all ranks as a tiny fixed-shape collective; every rank
processes its contiguous slice of each *global* minibatch, so all ranks
execute the same number of (collective) train steps per task — the lockstep
invariant jit-compiled SPMD requires.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Iterator, List, Optional, Tuple

import numpy as np

from elasticdl_tpu import obs
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.obs import goodput
from elasticdl_tpu.proto import elasticdl_pb2 as pb

logger = get_logger("parallel.elastic")


@dataclass
class WorldInfo:
    rank: int
    world_size: int
    rendezvous_id: int
    coordinator_addr: str

    @property
    def is_leader(self) -> bool:
        return self.rank == 0


def advertised_host() -> str:
    """The address this worker tells the rendezvous to reach it at.
    On Kubernetes the pod IP is injected as MY_POD_IP (k8s_client pod
    rendering); ELASTICDL_WORKER_HOST overrides for bespoke networks;
    single-host worlds fall back to loopback."""
    import os

    return (
        os.environ.get("ELASTICDL_WORKER_HOST", "")
        or os.environ.get("MY_POD_IP", "")
        or "127.0.0.1"
    )


def join_world(
    master_client,
    poll_interval_s: float = 0.5,
    timeout_s: float = 300.0,
    initialization_timeout_s: int = 120,
) -> WorldInfo:
    """Poll the master rendezvous until this worker has a rank AND the
    coordinator is resolved, then join the jax.distributed world (no-op
    for world_size == 1).

    Each poll carries this worker's advertised host: in deferred-host
    worlds (Kubernetes) the coordinator address can only resolve after
    rank 0 has advertised, and advertising must repeat because a world
    re-declaration discards previously reported hosts.  Advertising rides
    the rank poll, never the liveness channel — a heartbeat during world
    formation would collapse the rendezvous startup grace to the (much
    shorter) steady-state liveness timeout and get healthy workers killed
    while peers are still pulling images.
    """
    deadline = time.time() + timeout_s
    host = advertised_host()
    # Worker-side goodput accounting: everything from the first rank poll
    # to the coordination barrier completing is rendezvous time (this
    # process's ledger — the master accounts its own half).
    with goodput.ledger().phase("rendezvous", cause="join_world"):
        return _join_world_inner(
            master_client, poll_interval_s, deadline, host,
            initialization_timeout_s,
        )


def _join_world_inner(
    master_client, poll_interval_s, deadline, host,
    initialization_timeout_s,
) -> WorldInfo:
    while True:
        resp = master_client.get_comm_rank(host)
        if (
            resp.rank_id >= 0
            and resp.world_size > 0
            and (resp.world_size == 1 or resp.coordinator_addr)
        ):
            break
        if time.time() > deadline:
            raise TimeoutError(
                f"Worker {master_client.worker_id} never received a rank "
                f"(last world_size={resp.world_size}, "
                f"coordinator={resp.coordinator_addr!r})"
            )
        time.sleep(poll_interval_s)
    info = WorldInfo(
        rank=resp.rank_id,
        world_size=resp.world_size,
        rendezvous_id=resp.rendezvous_id,
        coordinator_addr=resp.coordinator_addr,
    )
    if info.world_size > 1:
        import jax

        logger.info(
            "Joining world %d: rank %d/%d via %s",
            info.rendezvous_id,
            info.rank,
            info.world_size,
            info.coordinator_addr,
        )
        jax.distributed.initialize(
            coordinator_address=info.coordinator_addr,
            num_processes=info.world_size,
            process_id=info.rank,
            initialization_timeout=initialization_timeout_s,
        )
    return info


class HeartbeatReporter:
    """Background liveness heartbeats to the master (failure-detection
    plane: the pod manager kills workers whose heartbeats go silent, which
    converts hangs into the process-exit signal churn handling reacts to).

    The heartbeat is also the TELEMETRY CARRIER: when a WorkerTelemetry
    collector (obs/telemetry.py) is attached, each beat ships its bounded
    snapshot in `ReportWorkerLivenessRequest.telemetry_json` — per-worker
    observability with zero new RPCs.  Intervals carry ±`JITTER` of
    deterministic per-worker jitter so a fleet that just re-formed (every
    worker's clock started at the same rendezvous barrier) doesn't
    heartbeat the master in lockstep."""

    WARN_INTERVAL_S = 60.0
    #: Fractional interval jitter (0.2 = ±20%).
    JITTER = 0.2

    def __init__(
        self,
        master_client,
        world: WorldInfo,
        host: str = "",
        interval_s: float = 5.0,
        telemetry=None,
        jitter: float = JITTER,
    ):
        import threading

        self._mc = master_client
        self._world = world
        self._host = host or advertised_host()
        self._interval_s = interval_s
        self._telemetry = telemetry
        self._jitter = float(jitter)
        self._stop = threading.Event()
        #: Consecutive/total failed heartbeats (tests and ops read these —
        #: a silently-dead liveness plane looks exactly like a healthy one
        #: from the worker side otherwise).
        self.error_count = 0
        self._last_warn_monotonic: Optional[float] = None
        self._thread = threading.Thread(
            target=self._loop, name="worker-heartbeat", daemon=True
        )

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()

    def jittered_interval_s(self, tick: int) -> float:
        """Interval for beat `tick`: uniform in [1-J, 1+J] x interval,
        seeded from (worker, tick) — deterministic per worker (replayable
        schedules, same rule as the RPC backoff jitter) yet decorrelated
        across the fleet."""
        if not self._jitter:
            return self._interval_s
        import random

        u = random.Random(f"hb:{self._mc.worker_id}:{tick}").random()
        return self._interval_s * (1.0 - self._jitter + 2.0 * self._jitter * u)

    def _loop(self):
        tick = 0
        while not self._stop.wait(self.jittered_interval_s(tick)):
            tick += 1
            payload = ""
            if self._telemetry is not None:
                try:
                    payload = self._telemetry.snapshot_json()
                except Exception:
                    payload = ""  # telemetry must never kill the liveness plane
            try:
                if payload:
                    # Clock probe around the carrying RPC: the send/recv
                    # wall stamps journal (this process's journal) as a
                    # `clock_probe`, paired by the trace assembler with
                    # the master's worker_telemetry event (same
                    # worker_ts) to estimate this worker's clock offset
                    # by the midpoint method — the heartbeat doubles as
                    # the time-sync plane with zero new RPCs.
                    t_send = time.time()
                    self._mc.report_worker_liveness(
                        self._host, self._world.rendezvous_id,
                        telemetry_json=payload,
                    )
                    t_recv = time.time()
                    probe_ts = getattr(
                        self._telemetry, "last_snapshot_ts", 0.0
                    )
                    if probe_ts:
                        obs.journal().record(
                            "clock_probe",
                            worker_id=self._mc.worker_id,
                            probe_ts=probe_ts,
                            t_send=round(t_send, 6),
                            t_recv=round(t_recv, 6),
                            rtt_s=round(t_recv - t_send, 6),
                        )
                else:
                    self._mc.report_worker_liveness(
                        self._host, self._world.rendezvous_id
                    )
            except Exception as exc:
                # Master unreachable: the process-manager side owns the
                # failure, but say so (rate-limited) — a heartbeat plane
                # that swallows every error is indistinguishable from one
                # that works, until the pod manager kills this "hung"
                # worker for silence.
                self.error_count += 1
                now = time.monotonic()
                if (
                    self._last_warn_monotonic is None
                    or now - self._last_warn_monotonic >= self.WARN_INTERVAL_S
                ):
                    self._last_warn_monotonic = now
                    logger.warning(
                        "Liveness heartbeat to master failed (%s: %s); "
                        "%d failure(s) so far — the pod manager may kill "
                        "this worker if heartbeats stay silent",
                        type(exc).__name__, exc, self.error_count,
                    )


# ---------------------------------------------------------------------------
# Task broadcast: rank 0 is the only master-facing rank for task dispatch.
# ---------------------------------------------------------------------------

_TASK_ENC_LEN = 7  # task_id, shard_idx, start, end, type, model_version, epoch


def _encode_task(task: Optional[pb.Task], shard_names: List[str]) -> np.ndarray:
    if task is None:
        return np.full((_TASK_ENC_LEN,), -1, np.int64)
    shard_idx = shard_names.index(task.shard_name) if task.shard_name else -1
    return np.asarray(
        [task.task_id, shard_idx, task.start, task.end, task.type,
         task.model_version, task.epoch],
        np.int64,
    )


def _decode_task(arr: np.ndarray, shard_names: List[str]) -> pb.Task:
    task_id, shard_idx, start, end, type_, version, epoch = (int(v) for v in arr)
    return pb.Task(
        task_id=task_id,
        shard_name=shard_names[shard_idx] if shard_idx >= 0 else "",
        start=start,
        end=end,
        type=type_,
        model_version=version,
        epoch=epoch,
    )


def broadcast_task(
    task: Optional[pb.Task], shard_names: List[str], world: WorldInfo,
    anatomy=None,
) -> pb.Task:
    """All ranks call this; rank 0 supplies the task, everyone returns it.

    `shard_names` must be identical (same order) on every rank — it comes
    from the deterministic data reader shard listing each rank builds.

    `anatomy` (obs/stepstats.StepAnatomy, optional) books the broadcast
    wall under `data_wait` on NON-leader ranks: for them this collective
    IS the task-queue wait (they block here while rank 0 talks to the
    master), and the step-anatomy ledger would otherwise blame the gap
    on whatever phase ran last.  Booked after the fact and only for real
    tasks — a WAIT poll is queue idleness (the goodput ledger's `idle`),
    not data starvation, and must not corrupt the anatomy.  The leader's
    wait (get_task + this broadcast) is booked by its own task loop.
    """
    if world.world_size == 1:
        assert task is not None
        return task
    from jax.experimental import multihost_utils

    start = time.monotonic()
    encoded = multihost_utils.broadcast_one_to_all(
        _encode_task(task, shard_names), is_source=world.is_leader
    )
    if world.is_leader and task is not None:
        # The leader keeps its ORIGINAL task object: the fixed-shape
        # encoding drops string fields (trace_id), and the leader is the
        # only rank that reports results — its trace id must survive the
        # broadcast round-trip.
        return task
    decoded = _decode_task(np.asarray(encoded), shard_names)
    if (
        anatomy is not None
        and not world.is_leader
        and decoded.task_id != -1
        and decoded.type != pb.WAIT
    ):
        anatomy.note_phase_seconds("data_wait", time.monotonic() - start)
    return decoded


# ---------------------------------------------------------------------------
# Lockstep global batching.
# ---------------------------------------------------------------------------

def iter_local_batch_ranges(
    task_start: int,
    task_end: int,
    per_rank_batch: int,
    world: WorldInfo,
) -> Iterator[Tuple[int, int, int]]:
    """Yield (lo, hi, global_real) for this rank, one tuple per global step.

    Global batch b covers records [task_start + b*W*B, ...); rank r's slice
    is the r-th contiguous B-record chunk of it.  Every rank yields the same
    number of tuples (possibly with empty [lo, lo) slices at the ragged
    tail), preserving the lockstep-collective invariant; `global_real` is
    the batch's real record count across all ranks (for masking/metrics).
    """
    total = task_end - task_start
    global_batch = per_rank_batch * world.world_size
    n_steps = max(1, -(-total // global_batch)) if total > 0 else 0
    for b in range(n_steps):
        g_lo = task_start + b * global_batch
        g_hi = min(g_lo + global_batch, task_end)
        lo = min(g_lo + world.rank * per_rank_batch, g_hi)
        hi = min(lo + per_rank_batch, g_hi)
        yield lo, hi, g_hi - g_lo


def per_rank_real_counts(
    global_real: int, per_rank_batch: int, world_size: int
) -> List[int]:
    """How many real (non-pad) rows each rank contributed to a global batch
    (deterministically reconstructible by any rank — used to strip padding
    from gathered eval outputs)."""
    counts = []
    remaining = global_real
    for _ in range(world_size):
        take = min(per_rank_batch, max(0, remaining))
        counts.append(take)
        remaining -= take
    return counts
