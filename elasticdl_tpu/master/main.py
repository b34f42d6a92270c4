"""Master assembly and entrypoint.

Parity: elasticdl/python/master/main.py in the reference — parse args, build
the data reader and shards, start the task manager + gRPC services, and (in
cluster mode) the pod manager.  `build_master` is the reusable in-process
assembly used by Local mode and by tests.
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass
from typing import Optional

from elasticdl_tpu import obs
from elasticdl_tpu.common.args import parse_master_args
from elasticdl_tpu.common.constants import DistributionStrategy
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.model_utils import load_model_spec
from elasticdl_tpu.data.reader import build_data_reader
from elasticdl_tpu.master.evaluation_service import EvaluationService
from elasticdl_tpu.master.servicer import MasterServicer, start_master_server
from elasticdl_tpu.master.task_manager import TaskManager, TaskProgressPersister
from elasticdl_tpu.obs import tracing

logger = get_logger("master.main")


@dataclass
class Master:
    args: object
    model_spec: object
    task_manager: TaskManager
    evaluation_service: Optional[EvaluationService]
    servicer: MasterServicer
    server: object = None
    port: int = 0
    rendezvous_server: object = None
    data_reader: object = None
    progress_persister: object = None
    tensorboard_service: object = None
    metrics_exporter: object = None
    telemetry: object = None

    @property
    def addr(self) -> str:
        return f"localhost:{self.port}"

    def stop(self):
        if self.metrics_exporter is not None:
            try:
                self.metrics_exporter.stop()
            except Exception:
                logger.exception("Metrics exporter stop failed")
            self.metrics_exporter = None
        if self.tensorboard_service is not None:
            try:
                self.tensorboard_service.close()
            except Exception:
                logger.exception("TensorBoard close failed")
            self.tensorboard_service = None
        if self.progress_persister is not None:
            try:
                self.progress_persister.stop()
            except Exception:
                logger.exception("Final task-progress persist failed")
            self.progress_persister = None
        if self.server is not None:
            self.server.stop(grace=None)


def build_master(args, model_spec=None, rendezvous_server=None) -> Master:
    # Event journal first: everything the assembly below does (task
    # creation, resume, rendezvous) should land on the timeline.  It
    # lives next to the TensorBoard events it complements; checkpoint_dir
    # is the fallback so cluster jobs without TensorBoard still journal.
    journal_dir = getattr(args, "tensorboard_log_dir", "") or getattr(
        args, "checkpoint_dir", ""
    )
    if journal_dir:
        from elasticdl_tpu.obs import goodput
        from elasticdl_tpu.obs.journal import DEFAULT_FILENAME

        resumed_journal = os.path.exists(
            os.path.join(journal_dir, DEFAULT_FILENAME)
        )
        journal_path = obs.init_journal(journal_dir)
        logger.info("Event journal -> %s", journal_path)
        if resumed_journal:
            # A predecessor's timeline exists: seed the goodput ledger's
            # cumulative phase seconds so elasticdl_goodput_ratio keeps
            # job-lifetime meaning across master restarts (the outage gap
            # itself is attributed by obs.report from the journal).
            goodput.ledger().seed_from_journal(journal_path)

    model_spec = model_spec or load_model_spec(args)  # `spec.load`
    with tracing.span("master.build"):
        return _assemble_master(args, model_spec, rendezvous_server)


def _assemble_master(args, model_spec, rendezvous_server) -> Master:
    """Readers and shards, the task manager (or a predecessor's
    progress), the services: `build_master` after the journal and the
    model spec."""
    training_reader = None
    training_shards = {}
    if args.training_data:
        training_reader = build_data_reader(args, model_spec, args.training_data)
        training_shards = training_reader.create_shards()
        if not training_shards:
            raise ValueError(
                f"--training_data={args.training_data!r} produced no shards "
                "(empty/missing path, or the model has no custom_data_reader "
                "for this scheme)"
            )
    evaluation_shards = {}
    if args.validation_data:
        eval_reader = build_data_reader(args, model_spec, args.validation_data)
        evaluation_shards = eval_reader.create_shards()
    prediction_shards = {}
    if getattr(args, "prediction_data", ""):
        pred_reader = build_data_reader(args, model_spec, args.prediction_data)
        prediction_shards = pred_reader.create_shards()

    # Master restart resume: a prior master's shard-progress snapshot (in
    # checkpoint_dir) takes precedence over fresh task creation, so a
    # restarted master continues the epoch instead of replaying it.
    # Cluster strategies only — in Local mode the "master" lives and dies
    # with the job, and resuming a *finished* run's snapshot would turn a
    # re-run into an instant no-op.
    task_manager = None
    progress_path = (
        TaskProgressPersister.progress_path(args.checkpoint_dir)
        if getattr(args, "checkpoint_dir", "")
        and args.distribution_strategy != DistributionStrategy.LOCAL
        else ""
    )
    if progress_path and os.path.exists(progress_path):
        try:
            with open(progress_path) as f:
                content = f.read()
            task_manager = TaskManager.from_checkpoint(
                content, task_timeout_s=args.task_timeout_s
            )
            counts = task_manager.counts()
            logger.info(
                "Resumed task progress from %s (epoch %d, %d tasks todo, "
                "%d records finished)",
                progress_path,
                counts["epoch"],
                counts["todo"],
                task_manager.finished_record_count,
            )
        except Exception:
            logger.exception(
                "Unreadable task-progress snapshot %s; starting fresh",
                progress_path,
            )
            task_manager = None
    if task_manager is None:
        task_manager = TaskManager(
            training_shards=training_shards,
            evaluation_shards=evaluation_shards,
            prediction_shards=prediction_shards,
            records_per_task=args.records_per_task,
            num_epochs=args.num_epochs,
            task_timeout_s=args.task_timeout_s,
        )

    tensorboard_service = None
    if getattr(args, "tensorboard_log_dir", ""):
        # The service writes its event files itself: this times the
        # `tensorboard` package's protos' import and one open().
        with tracing.span("master.tensorboard_init"):
            from elasticdl_tpu.master.tensorboard_service import (
                TensorBoardService,
            )

            tensorboard_service = TensorBoardService(
                args.tensorboard_log_dir, task_manager=task_manager
            )

    evaluation_service = None
    if model_spec.eval_metrics_fn is not None and evaluation_shards:
        evaluation_service = EvaluationService(
            task_manager,
            eval_metrics_fn=model_spec.eval_metrics_fn,
            evaluation_steps=args.evaluation_steps,
            tensorboard_service=tensorboard_service,
        )

    # Worker telemetry plane: snapshots arriving on liveness heartbeats
    # aggregate here (fleet gauges + straggler detection).  Scoped to the
    # current world when a rendezvous exists, so reports from torn-down
    # worlds neither skew aggregates nor read as infinitely stale.
    from elasticdl_tpu.obs.telemetry import TelemetryAggregator

    telemetry = TelemetryAggregator(
        current_workers_fn=(
            (lambda: [wid for wid, _h in rendezvous_server.world()])
            if rendezvous_server is not None
            else None
        )
    )

    servicer = MasterServicer(
        task_manager=task_manager,
        evaluation_service=evaluation_service,
        rendezvous_server=rendezvous_server,
        telemetry=telemetry,
    )
    if tensorboard_service is not None:
        tensorboard_service.bind(
            model_version_fn=lambda: servicer.model_version
        )
        tensorboard_service.start()
    if evaluation_service is not None and training_shards:
        # Always run a final evaluation when training tasks finish.
        task_manager.add_tasks_done_callback(
            lambda: evaluation_service.trigger_evaluation(servicer.model_version)
        )
        if args.evaluation_steps <= 0:
            # Default: evaluate at every epoch boundary.
            task_manager.add_epoch_done_callback(
                lambda epoch: evaluation_service.trigger_evaluation(
                    servicer.model_version
                )
            )
    if model_spec.callbacks is not None and training_shards:
        # Queue the TRAIN_END_CALLBACK task so zoo callbacks() actually run.
        task_manager.add_tasks_done_callback(task_manager.create_train_end_task)
    progress_persister = None
    if progress_path:
        progress_persister = TaskProgressPersister(
            task_manager, args.checkpoint_dir
        ).start()
    master = Master(
        args=args,
        model_spec=model_spec,
        task_manager=task_manager,
        evaluation_service=evaluation_service,
        servicer=servicer,
        rendezvous_server=rendezvous_server,
        data_reader=training_reader,
        progress_persister=progress_persister,
        tensorboard_service=tensorboard_service,
        telemetry=telemetry,
    )
    return master


def start_master(args, model_spec=None, rendezvous_server=None) -> Master:
    # Tracing plane identity + crash flight recorder: master spans label
    # as `master` on the assembled trace, and a SIGTERM'd/exiting master
    # flushes its open spans + a final registry snapshot to the journal.
    tracing.set_process("master")
    tracing.install_flight_recorder()
    master = build_master(args, model_spec, rendezvous_server)
    tracing.record_proc_start()  # the journal exists from here on
    with tracing.span("master.serve_ready"):
        _serve(master, args)
    # The boot that main's first line opened ends here.  What it paid
    # for in imports: none of these is the master's own to load (a zoo
    # module may bring jax; `spec.load` says so).
    tracing.end_boot(
        heavy_imports=sorted(
            {"torch", "tensorflow", "jax"} & set(sys.modules)
        )
    )
    # Phase accounting starts here: idle until the first dispatch or
    # world declaration opens a real phase.
    from elasticdl_tpu.obs import goodput

    goodput.ledger().transition("idle", cause="master_start")
    return master


def _serve(master: Master, args) -> None:
    """The gRPC server, the metrics exporter and the `master_start`
    record: from here workers can reach the master."""
    master.server, master.port = start_master_server(
        master.servicer, port=args.master_port
    )
    metrics_port = getattr(args, "metrics_port", None)
    if metrics_port is not None:
        from elasticdl_tpu.obs.exporter import MetricsExporter

        try:
            master.metrics_exporter = MetricsExporter(
                port=metrics_port
            ).start()
        except OSError:
            # Observability must never take the control plane down: a
            # taken port degrades to no exporter, not a dead master.
            logger.exception(
                "Metrics exporter could not bind port %d; continuing "
                "without /metrics", metrics_port,
            )
        if master.metrics_exporter is not None:
            # Discovery file next to the journal: `--metrics_port 0`
            # binds an ephemeral port, and scrapers/tests read the
            # chosen one from here instead of hardcoding it.
            port_dir = getattr(args, "tensorboard_log_dir", "") or getattr(
                args, "checkpoint_dir", ""
            )
            if port_dir:
                master.metrics_exporter.write_port_file(port_dir)
    obs.journal().record(
        "master_start",
        job_name=args.job_name,
        port=master.port,
        metrics_port=(
            master.metrics_exporter.port if master.metrics_exporter else None
        ),
    )


def mode_from_job_type(job_type: str) -> str:
    from elasticdl_tpu.common.constants import JobType, Mode

    return {
        JobType.TRAINING_ONLY: Mode.TRAINING,
        JobType.TRAINING_WITH_EVALUATION: Mode.TRAINING,
        JobType.EVALUATION_ONLY: Mode.EVALUATION,
        JobType.PREDICTION_ONLY: Mode.PREDICTION,
    }[job_type]


def main(argv=None):
    """`python -m elasticdl_tpu.master.main` — the master pod's command.

    Cluster strategies run the full job (control-plane services + worker
    fleet supervision, reference master-pod behavior); Local starts a bare
    master server for debugging.
    """
    # The end of `proc.start` and the start of `master.boot`.
    tracing.begin_boot("master.boot")
    from elasticdl_tpu.common import faults

    if faults.install_from_env():
        logger.warning(
            "Fault injection armed from %s=%r",
            faults.ENV_VAR, os.environ.get(faults.ENV_VAR),
        )
    args = parse_master_args(argv)
    if args.distribution_strategy != DistributionStrategy.LOCAL:
        from elasticdl_tpu.master.job_runner import run_allreduce_job, run_ps_job

        if args.need_elasticity and getattr(args, "policy_enabled", True):
            logger.info(
                "Elastic policy engine ON (amortize_horizon=%.0fs, "
                "min_workers=%d, evict_after=%d ticks, kill_budget=%d/"
                "%.0fs) — --policy_enabled=false for observe-only",
                args.policy_amortize_horizon_s, args.policy_min_workers,
                args.policy_evict_after, args.policy_kill_budget,
                args.policy_kill_budget_window_s,
            )

        runner = (
            run_ps_job
            if args.distribution_strategy
            == DistributionStrategy.PARAMETER_SERVER
            else run_allreduce_job
        )
        return runner(args, mode_from_job_type(args.job_type))
    master = start_master(args)
    logger.info("Master running on port %d", master.port)
    logger.warning(
        "Master started standalone in Local mode; use `elasticdl train` "
        "to run master+worker together."
    )
    master.server.wait_for_termination()


if __name__ == "__main__":
    sys.exit(main())
