"""Client API: run/submit jobs.

Parity: elasticdl_client/api.py in the reference.  Local mode runs the
master and one worker in-process (the reference's local-mode test harness,
SURVEY.md §4); cluster modes hand off to the pod/process manager.
"""

from __future__ import annotations

import numpy as np

from elasticdl_tpu.common.args import parse_master_args
from elasticdl_tpu.common.constants import DistributionStrategy, Mode
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.model_utils import load_model_spec
from elasticdl_tpu.data.reader import build_data_reader
from elasticdl_tpu.master.main import start_master
from elasticdl_tpu.worker.master_client import MasterClient
from elasticdl_tpu.worker.worker import Worker

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

    compile_cache.configure(getattr(args, "jax_compilation_cache_dir", ""))
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

    from elasticdl_tpu.common.profiler import StepProfiler
    from elasticdl_tpu.data.pipeline import PipelineConfig

    client = MasterClient(master.addr, worker_id=0)
    worker = Worker(
        master_client=client,
        model_spec=model_spec,
        data_reader=data_reader,
        minibatch_size=args.minibatch_size,
        validation_data_reader=validation_reader,
        profiler=StepProfiler(
            args.tensorboard_log_dir, args.profile_steps, worker_id=0
        ),
        pipeline=PipelineConfig.from_args(args),
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


def save_model(trainer, output_path: str, args=None):
    """Export the trained model as a servable artifact directory (the
    reference's `get_model_to_export` analogue — serving/export.py).
    A legacy flat-variables `.npz` is still written when the path ends in
    `.npz` (external consumers of the round-1 format)."""
    if trainer.state is None:
        logger.warning("No variables to save (model never initialized)")
        return
    if output_path.endswith(".npz"):
        import jax

        variables = trainer.get_variables_numpy()  # collective (PS tables)
        if jax.process_index() == 0:
            np.savez(output_path, **variables)
            logger.info(
                "Saved %d variables to %s", len(variables), output_path
            )
        return
    from elasticdl_tpu.serving import export_model

    # Record the RESOLVED model params — job flags that model_utils
    # injects into model_params (sparse_apply_every, use_bf16) included
    # — not the raw --model_params string: a flag-dependent model
    # structure (DeepFM's per-mode table layout follows
    # sparse_apply_every at >10M rows) must rebuild identically at
    # serving load, where the job flags no longer exist.
    model_params = getattr(args, "model_params", "")
    if args is not None and getattr(args, "model_def", ""):
        from elasticdl_tpu.common.args import format_dict_params
        from elasticdl_tpu.common.model_utils import load_model_spec

        model_params = format_dict_params(load_model_spec(args).model_params)
    export_model(
        trainer,
        output_path,
        model_zoo=getattr(args, "model_zoo", ""),
        model_def=getattr(args, "model_def", ""),
        model_params=model_params,
    )
