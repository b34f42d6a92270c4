"""Cluster-mode job orchestration (master side).

Parity: the master pod's role in elasticdl/python/master/main.py — start
the control-plane services and the pod manager, then supervise the worker
fleet until the job completes.  Substrate selection: local subprocesses
(single-host multi-process — also the test harness) now; the Kubernetes
pod manager plugs into the same flow.
"""

from __future__ import annotations

import os
import socket
import tempfile
import time

from elasticdl_tpu.common.constants import Mode
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.master.main import start_master
from elasticdl_tpu.master.pod_manager import (
    LocalProcessManager,
    worker_argv_from_args,
)
from elasticdl_tpu.master.rendezvous_server import ElasticRendezvous
from elasticdl_tpu.obs import tracing

logger = get_logger("master.job_runner")


def _capacity_oracle_from_env():
    """Elastic scale-up signal for the subprocess substrate: the file named
    by $ELASTICDL_CAPACITY_FILE holds an integer count of free worker slots
    (ops/tests write it when capacity returns).  Absent env -> no scale-up."""
    path = os.environ.get("ELASTICDL_CAPACITY_FILE", "")
    if not path:
        return None

    def check(needed: int) -> int:
        try:
            with open(path) as f:
                slots = int(f.read().strip() or 0)
        except (OSError, ValueError):
            return 0
        return max(0, min(needed, slots))

    return check


class _K8sCapacityProbe:
    """Scale-up oracle on Kubernetes: capacity is unknowable without a
    scheduler dry-run, so probe optimistically — grant a regrow attempt at
    most every `cooldown_s`; a cluster still out of capacity leaves the new
    pods Pending until the pod manager's startup timeout reads it as churn.
    An $ELASTICDL_CAPACITY_FILE override wins when present (explicit ops
    signal, no probing)."""

    def __init__(self, cooldown_s: float = 300.0):
        self._base_cooldown_s = cooldown_s
        self._cooldown_s = cooldown_s
        self._last_probe = time.time()

    def __call__(self, needed: int) -> int:
        explicit = _capacity_oracle_from_env()
        if explicit is not None:
            return explicit(needed)
        now = time.time()
        if now - self._last_probe < self._cooldown_s:
            return 0
        self._last_probe = now
        return needed

    def failed(self):
        """Probe pods never scheduled: exponential backoff (cap 1h)."""
        self._cooldown_s = min(self._cooldown_s * 2, 3600.0)

    def succeeded(self):
        self._cooldown_s = self._base_cooldown_s


def _running_on_k8s(args) -> bool:
    return bool(args.image_name) and bool(
        os.environ.get("KUBERNETES_SERVICE_HOST")
        or os.environ.get("ELASTICDL_K8S_HOST")
    )


def _build_policy_engine(args, master):
    """The goodput-driven policy engine (master/policy.py) — built when
    elasticity is on and --policy_enabled (the default).  It consumes
    the goodput ledger, the telemetry aggregator's straggler set, and
    pod-manager state; its decisions are enforced through the manager
    (gated scale-up, thrash scale-down, budgeted eviction)."""
    if not (args.need_elasticity and getattr(args, "policy_enabled", True)):
        return None
    from elasticdl_tpu.master.policy import ElasticPolicyEngine, PolicyConfig

    return ElasticPolicyEngine(
        PolicyConfig.from_args(args),
        stragglers_fn=(
            master.telemetry.stragglers if master.telemetry is not None
            else None
        ),
    )


def _build_slo_plane(args, master, policy_engine):
    """The master's SLO plane (obs/slo.py): a metrics-history sampler +
    burn-rate evaluator over the process registry.  The goodput SLO is
    registered only when --slo_goodput_target > 0; the sampler itself
    always runs (it feeds /slo sparklines and costs one registry scrape
    per tick).  Alert edges flow to the policy engine as advisories."""
    if not getattr(args, "slo_enabled", True):
        return None
    from elasticdl_tpu.obs.slo import SLOPlane, goodput_slo

    specs = []
    target = float(getattr(args, "slo_goodput_target", 0.0) or 0.0)
    if target > 0:
        specs.append(goodput_slo(
            target,
            compliance_window_s=float(
                getattr(args, "slo_compliance_window_s", 3600.0)
            ),
        ))
    plane = SLOPlane(
        specs=specs,
        tick_interval_s=float(getattr(args, "slo_tick_interval_s", 2.0)),
        origin="master",
    )
    if policy_engine is not None:
        plane.slos.add_alert_callback(policy_engine.note_slo_alert)
    if master.metrics_exporter is not None:
        master.metrics_exporter.set_slo_plane(plane)
    return plane


class _GatedScaleUp:
    """Chain policy and capacity: the policy says whether a rescale
    would pay (amortization, cooldown, thrash — every denial journals a
    `policy_decision`), and only THEN is the oracle asked whether
    workers can be had — the k8s probe consumes a once-per-cooldown
    token per call, which a policy denial must not burn.  Forwards the
    probe's `failed`/`succeeded` backoff feedback to the wrapped oracle
    when it has them."""

    def __init__(self, check_fn, policy_engine):
        self._check_fn = check_fn
        self._policy_engine = policy_engine

    def __call__(self, needed: int) -> int:
        return self._policy_engine.gate_scale_up(needed, self._check_fn)

    def failed(self):
        # The probe behind an APPROVED grant never proved capacity: the
        # policy retracts its scale_up (cooldown + audit trail) before
        # the oracle is told to back off.
        self._policy_engine.scale_up_aborted()
        if hasattr(self._check_fn, "failed"):
            self._check_fn.failed()

    def succeeded(self):
        if hasattr(self._check_fn, "succeeded"):
            self._check_fn.succeeded()


def _gated_scale_up(check_fn, policy_engine):
    if check_fn is None or policy_engine is None:
        return check_fn
    return _GatedScaleUp(check_fn, policy_engine)


def _build_worker_manager(args, master, rendezvous, worker_env,
                          policy_engine=None):
    """Substrate selection: worker pods when this master runs on Kubernetes
    (reference: the master pod creates worker pods through the API server),
    local subprocesses otherwise."""
    common = dict(
        num_workers=args.num_workers,
        rendezvous=rendezvous,
        task_manager=master.task_manager,
        max_restarts=args.max_worker_restarts,
        job_finished_fn=master.task_manager.finished,
        liveness_timeout_s=args.worker_liveness_timeout_s,
    )
    if _running_on_k8s(args):
        from elasticdl_tpu.master.k8s_client import (
            K8sClient,
            K8sConfig,
            parse_resource_spec,
        )
        from elasticdl_tpu.master.k8s_pod_manager import KubernetesPodManager

        if getattr(args, "tpu_slice", "") and args.need_elasticity:
            # Mirrors client/submit's terminal-time rejection for masters
            # launched without going through the client.
            raise ValueError(
                "--tpu_slice is incompatible with --need_elasticity "
                "(pod slices schedule all-or-nothing; see client/submit)"
            )
        client = K8sClient(K8sConfig.resolve(args.namespace))
        pod_ip = os.environ.get("MY_POD_IP", "") or socket.gethostbyname(
            socket.gethostname()
        )
        master_addr = f"{pod_ip}:{master.port}"
        owner = None
        own_name = os.environ.get("HOSTNAME", "")
        if own_name:
            owner = client.get_pod(own_name)
        return KubernetesPodManager(
            worker_argv_fn=worker_argv_from_args(args, master_addr),
            k8s_client=client,
            job_name=args.job_name,
            image=args.image_name,
            worker_env=worker_env,
            worker_resources=parse_resource_spec(args.worker_resource_request)
            or None,
            priority_class=args.worker_pod_priority,
            owner_pod=owner,
            volume_spec=args.volume,
            tpu_slice=getattr(args, "tpu_slice", ""),
            scale_up_check_fn=_gated_scale_up(
                _K8sCapacityProbe() if args.need_elasticity else None,
                policy_engine,
            ),
            **common,
        )
    _refuse_workers_sharing_a_chip(args.num_workers, worker_env)
    return LocalProcessManager(
        worker_argv_fn=worker_argv_from_args(args, master.addr),
        worker_env=worker_env,
        log_dir=os.path.join(
            args.checkpoint_dir or tempfile.gettempdir(),
            f"{args.job_name}_worker_logs",
        ),
        scale_up_check_fn=_gated_scale_up(
            _capacity_oracle_from_env() if args.need_elasticity else None,
            policy_engine,
        ),
        **common,
    )


def _local_tpu_chips() -> int:
    """TPU chips attached to this host, read from sysfs by jax's own
    scan.  No backend is initialized: the master stays off the
    accelerator, or its worker could not take the chip."""
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def _refuse_workers_sharing_a_chip(num_workers: int, worker_env) -> None:
    """A chip belongs to ONE process at a time, and every local worker
    builds its mesh over all of the host's devices (parallel/mesh.py).
    Seen on a one-chip v5e host with --num_workers=2: the second worker
    to reach the TPU dies in libtpu ("Internal error when accessing
    libtpu multi-process lockfile"), the first waits in world formation,
    and the job hangs.  Refuse the combination up front, with the
    reason."""
    platforms = worker_env.get(
        "JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", "")
    )
    if num_workers <= 1 or platforms == "cpu":
        return
    chips = _local_tpu_chips()
    if chips:
        raise ValueError(
            f"--num_workers={num_workers} cannot work on this host: it has "
            f"{chips} TPU chip(s), a chip belongs to one process at a time, "
            "and every local worker takes all of them. One worker process "
            "drives every chip of a host — use --num_workers=1 (and "
            "--mesh_model_axis to shape the mesh) — or set JAX_PLATFORMS=cpu "
            "for a multi-process CPU world."
        )


def _ensure_elastic_checkpointing(args, mode: str):
    """Churn recovery is restart-the-world + restore-latest: without a
    checkpoint, a re-formed world re-initializes weights while the
    TaskManager keeps finished tasks finished — silently discarding all
    learned state (reference keeps state alive on surviving Horovod
    workers, so it never has this failure mode).  Elastic training jobs
    therefore get checkpointing by default: a job-scoped temp dir when
    none is configured, and a sane save cadence when one is."""
    if mode != Mode.TRAINING or not args.need_elasticity:
        return
    if not args.checkpoint_dir:
        if _running_on_k8s(args):
            # A master-pod-local temp dir is invisible to worker pods:
            # workers would checkpoint into their own filesystems and a
            # re-formed world would restore nothing — exactly the silent
            # weight reset this guard exists to prevent.  Shared storage
            # is the operator's to provide; refuse rather than pretend.
            raise ValueError(
                "Elastic training on Kubernetes requires --checkpoint_dir "
                "on storage every pod shares — mount it with --volume "
                '(e.g. --volume "claim_name=ckpt-pvc,mount_path=/ckpt" '
                "--checkpoint_dir /ckpt/myjob); without it, worker churn "
                "silently resets model weights."
            )
        args.checkpoint_dir = tempfile.mkdtemp(
            prefix=f"{args.job_name}_ckpt_"
        )
        logger.warning(
            "Elastic job has no --checkpoint_dir; worker churn would "
            "silently reset model weights while task progress survives. "
            "Defaulting to %s — set --checkpoint_dir to keep snapshots.",
            args.checkpoint_dir,
        )
    if not args.checkpoint_steps:
        args.checkpoint_steps = 100
        logger.warning(
            "Elastic job has --checkpoint_steps=0; defaulting to %d so "
            "re-formed worlds restore recent state.",
            args.checkpoint_steps,
        )


def run_allreduce_job(args, mode: str = Mode.TRAINING) -> int:
    """AllReduce strategy: N worker processes form a jax.distributed world;
    gradients psum inside the compiled step; churn re-forms the world."""
    _ensure_elastic_checkpointing(args, mode)
    rendezvous = ElasticRendezvous()
    master = start_master(args, rendezvous_server=rendezvous)
    # Serving, and no worker yet: what stands between the two is timed
    # (`master.launch_worker` follows inside `manager.start()`).
    with tracing.span("master.build_fleet"):
        manager, policy_engine, slo_plane = _build_fleet(
            args, mode, master, rendezvous
        )
    progress_persister = master.progress_persister
    job_succeeded = False
    try:
        manager.start()
        if policy_engine is not None:
            policy_engine.start()
        if slo_plane is not None:
            slo_plane.start()
        ok = manager.wait()
        if master.evaluation_service is not None:
            master.evaluation_service.finalize()
            metrics = master.evaluation_service.latest_metrics
            if metrics:
                logger.info("Final metrics: %s", metrics)
        if not ok:
            logger.error("Job failed: %s", manager.failed_reason)
            return 1
        if not master.task_manager.finished():
            logger.error("Workers exited but tasks remain unfinished")
            return 1
        logger.info("AllReduce job complete")
        job_succeeded = True
        return 0
    finally:
        if slo_plane is not None:
            slo_plane.stop()
        if policy_engine is not None:
            policy_engine.stop()
        manager.stop()
        master.stop()
        if job_succeeded and progress_persister is not None:
            # Leaving a terminal snapshot behind would turn the next run
            # with this checkpoint_dir into a silent no-op.
            progress_persister.clear()


def _build_fleet(args, mode: str, master, rendezvous):
    """What a serving master still builds before its first worker: the
    policy engine, the worker manager and the SLO plane, wired to the
    master's services.  Returns (manager, policy_engine, slo_plane)."""
    if mode == Mode.EVALUATION:
        if master.evaluation_service is not None:
            master.evaluation_service.trigger_evaluation(model_version=0)
        else:
            master.task_manager.create_evaluation_tasks(model_version=0)

    worker_env = {}
    # Extra worker env as 'K=V;K2=V2' (e.g. XLA_FLAGS overrides in tests).
    for pair in os.environ.get("ELASTICDL_WORKER_ENV", "").split(";"):
        if "=" in pair:
            key, value = pair.split("=", 1)
            worker_env[key.strip()] = value
    policy_engine = _build_policy_engine(args, master)
    manager = _build_worker_manager(
        args, master, rendezvous, worker_env, policy_engine=policy_engine
    )
    master.pod_manager = manager  # type: ignore[attr-defined]
    if policy_engine is not None:
        policy_engine.bind(manager)
    if master.telemetry is not None:
        # Straggler advisories from the telemetry plane flow to the pod
        # manager (advisory — see ElasticWorkerManager.note_straggler)
        # and to the goodput ledger (training time while flagged is
        # accounted as degraded_straggler).  The policy engine consumes
        # the SAME detector state by polling the aggregator's flagged
        # set each tick (stragglers_fn, wired in _build_policy_engine) —
        # one mechanism, not a callback racing the poll — and enforces
        # eviction of PERSISTENT stragglers under its hysteresis + kill
        # budget.
        from elasticdl_tpu.obs import goodput

        master.telemetry.add_straggler_callback(manager.note_straggler)
        master.telemetry.add_straggler_callback(
            lambda wid, flagged, _evidence: goodput.ledger().on_straggler(
                wid, flagged
            )
        )
    if master.tensorboard_service is not None:
        master.tensorboard_service.bind(
            restarts_fn=lambda: manager.restarts_used
        )
    slo_plane = _build_slo_plane(args, master, policy_engine)
    return manager, policy_engine, slo_plane


def run_ps_job(args, mode: str = Mode.TRAINING) -> int:
    """ParameterServer strategy: on TPU the PS data plane dissolves into
    mesh-sharded embedding tables + replicated dense params inside the
    compiled step (SURVEY.md §5); the job topology is the same as
    AllReduce — workers + master, no separate PS processes to schedule."""
    return run_allreduce_job(args, mode)
