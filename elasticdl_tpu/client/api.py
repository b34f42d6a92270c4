"""Client API: run/submit jobs.

Parity: elasticdl_client/api.py in the reference.  Local mode runs the
master and a world of one worker in this process, on the same worker loop
as the cluster modes (the reference's local-mode test harness, SURVEY.md
§4); cluster modes hand off to the pod/process manager.
"""

from __future__ import annotations

from elasticdl_tpu.common.args import parse_master_args
from elasticdl_tpu.common.constants import DistributionStrategy, Mode
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.model_utils import load_model_spec
from elasticdl_tpu.data.reader import build_data_reader
from elasticdl_tpu.master.main import start_master
from elasticdl_tpu.worker.main import _build_collective_worker, save_model
from elasticdl_tpu.worker.master_client import MasterClient

logger = get_logger("client.api")


def train(argv):
    args = parse_master_args(argv)
    return _run_job(args, mode=Mode.TRAINING)


def evaluate(argv):
    args = parse_master_args(argv)
    return _run_job(args, mode=Mode.EVALUATION)


def predict(argv):
    args = parse_master_args(argv)
    return _run_job(args, mode=Mode.PREDICTION)


def _run_job(args, mode: str):
    if args.image_name and args.distribution_strategy != DistributionStrategy.LOCAL:
        # Cluster submission: `--image_name` means "run on Kubernetes" —
        # create the master pod and return (reference client behavior).
        from elasticdl_tpu.client.submit import submit_job

        return submit_job(args, mode)
    if args.distribution_strategy == DistributionStrategy.LOCAL:
        return _run_local(args, mode)
    if args.distribution_strategy == DistributionStrategy.ALLREDUCE:
        from elasticdl_tpu.master.job_runner import run_allreduce_job

        return run_allreduce_job(args, mode)
    if args.distribution_strategy == DistributionStrategy.PARAMETER_SERVER:
        from elasticdl_tpu.master.job_runner import run_ps_job

        return run_ps_job(args, mode)
    raise ValueError(f"Unknown strategy {args.distribution_strategy}")


def _run_local(args, mode: str):
    """Master + one worker in this process, wired over localhost gRPC."""
    from elasticdl_tpu.common import compile_cache

    compile_cache.configure(
        getattr(args, "jax_compilation_cache_dir", ""), args=args
    )
    model_spec = load_model_spec(args)
    master = start_master(args, model_spec=model_spec)
    if mode == Mode.EVALUATION:
        # Evaluation-only job: queue an eval round immediately.
        if master.evaluation_service is not None:
            master.evaluation_service.trigger_evaluation(model_version=0)
        else:
            master.task_manager.create_evaluation_tasks(model_version=0)

    data_path = {
        Mode.TRAINING: args.training_data,
        Mode.EVALUATION: args.validation_data,
        Mode.PREDICTION: args.prediction_data,
    }[mode]
    data_reader = build_data_reader(args, model_spec, data_path)
    validation_reader = (
        build_data_reader(args, model_spec, args.validation_data)
        if args.validation_data and mode == Mode.TRAINING
        else None
    )

    client = MasterClient(master.addr, worker_id=0)
    worker = _build_collective_worker(
        args, model_spec, data_reader, client, validation_reader
    )
    try:
        worker.run()
        if mode == Mode.TRAINING and args.output:
            save_model(worker.trainer, args.output, args)
        metrics = {}
        if master.evaluation_service is not None:
            master.evaluation_service.finalize()
            metrics = master.evaluation_service.latest_metrics
        if metrics:
            logger.info("Final metrics: %s", metrics)
        return 0
    finally:
        client.close()
        master.stop()
