"""Worker pod/process entrypoint.

Parity: elasticdl/python/worker/main.py in the reference.
"""

from __future__ import annotations

import sys

from elasticdl_tpu.common.args import parse_worker_args
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.model_utils import load_model_spec
from elasticdl_tpu.data.reader import build_data_reader
from elasticdl_tpu.worker.master_client import MasterClient

logger = get_logger("worker.main")


def _sigterm_to_systemexit(signum, frame):
    """Convert the pod manager's graceful terminate() (SIGTERM) into a
    normal interpreter exit so `finally` blocks and atexit hooks run —
    most importantly the StepProfiler flush: a preempted worker
    mid-profile-window ships a partial trace instead of losing it.
    The manager escalates to SIGKILL after its grace period, so a hung
    shutdown still dies."""
    raise SystemExit(128 + signum)


#: What `_build_collective_worker` imports (jax, flax and optax with the
#: trainers): `main` loads them inside `worker.imports`, so that the
#: seconds they take are named and not the builder's.
_WORKER_STACK = (
    "elasticdl_tpu.checkpoint", "elasticdl_tpu.common.profiler",
    "elasticdl_tpu.data.pipeline", "elasticdl_tpu.obs.stepstats",
    "elasticdl_tpu.obs.telemetry", "elasticdl_tpu.ops.sparse_embedding",
    "elasticdl_tpu.parallel", "elasticdl_tpu.parallel.dp_trainer",
    "elasticdl_tpu.parallel.ps_trainer", "elasticdl_tpu.parallel.elastic",
    "elasticdl_tpu.worker.collective_worker",
)


def _configure_process(argv, imports):
    """What `main` does before it loads the model: signals, faults,
    arguments, the process's name and journal, the compile cache, and
    the worker's imports.  `imports` is the open `worker.imports` span
    (`jax_import_s`: jax alone, of the span's seconds)."""
    import importlib
    import os
    import signal
    import time

    from elasticdl_tpu.obs import tracing

    try:
        signal.signal(signal.SIGTERM, _sigterm_to_systemexit)
    except ValueError:
        pass  # not the main thread (in-process test harnesses)

    from elasticdl_tpu.common import faults

    if faults.install_from_env():
        logger.warning(
            "Fault injection armed from %s=%r",
            faults.ENV_VAR, os.environ.get(faults.ENV_VAR),
        )
    args = parse_worker_args(argv)
    # Tracing plane identity + crash flight recorder: this worker's
    # spans label as `worker_<id>` on the assembled trace
    # (obs/trace.py), and process exit — including SIGTERM via the
    # SystemExit conversion above — flushes open spans + a final
    # registry snapshot, so a preempted worker leaves a complete trace
    # tail instead of a cliff.
    tracing.set_process(f"worker_{args.worker_id}")
    tracing.install_flight_recorder()
    if getattr(args, "tensorboard_log_dir", ""):
        # Each process owns its journal (obs scoping rule): give worker
        # processes a durable file so worker-side events — profile_window
        # trace pointers, step_anatomy in Local mode, worker spans —
        # survive the process instead of dying with the in-memory tail.
        # Distinct filename per worker: no collision with the master's
        # events.jsonl in the shared log dir.
        from elasticdl_tpu import obs

        obs.init_journal(
            args.tensorboard_log_dir,
            filename=f"events_worker_{args.worker_id}.jsonl",
        )
    tracing.record_proc_start()
    started = time.monotonic()
    import jax  # noqa: F401  (first import of the process)

    imports.fields["jax_import_s"] = round(time.monotonic() - started, 6)
    from elasticdl_tpu.common import compile_cache

    # Persistent compile cache: a re-formed world's jit compiles are
    # disk hits — the dominant recovery cost after process start.
    logger.info(
        "JAX compilation cache: %s",
        compile_cache.configure(
            getattr(args, "jax_compilation_cache_dir", ""), args=args
        ),
    )
    if getattr(args, "oov_diagnostics", False):
        from elasticdl_tpu.parallel import packed

        packed.set_oov_debug(True)
    if getattr(args, "quality_drift_bins", 0) > 0:
        # Train-side skew sketch (obs/quality.py): every train batch's
        # integer feature ids fold into a process-local DriftMonitor
        # for train-serve divergence (host-side numpy, O(bins) memory).
        from elasticdl_tpu.obs import quality

        quality.enable_train_sketch(quality.DriftMonitor(
            threshold=args.quality_drift_threshold,
            bins=args.quality_drift_bins,
            origin=f"worker_{args.worker_id}",
        ))
    for module in _WORKER_STACK:
        importlib.import_module(module)
    return args


def main(argv=None):
    from elasticdl_tpu.obs import tracing

    # The end of `proc.start` and the start of `worker.boot`, which
    # closes where `worker.run()` is entered.  A relaunched worker walks
    # the same path: its spans carry its own `proc`.
    tracing.begin_boot("worker.boot")
    with tracing.span("worker.imports") as imports:
        args = _configure_process(argv, imports)
    model_spec = load_model_spec(args)
    data_reader = build_data_reader(args, model_spec, args.training_data)
    validation_reader = (
        build_data_reader(args, model_spec, args.validation_data)
        if args.validation_data
        else None
    )
    prediction_reader = (
        build_data_reader(args, model_spec, args.prediction_data)
        if args.prediction_data
        else None
    )
    client = MasterClient(args.master_addr, worker_id=args.worker_id)
    worker = _build_collective_worker(
        args, model_spec, data_reader, client,
        validation_reader, prediction_reader,
    )
    tracing.end_boot()
    worker.run()
    if args.output and "training" in args.job_type:
        # Export the servable artifact at job end (reference: the master's
        # model handler exports after training).  ALL ranks call this in
        # lockstep — materializing process-spanning PS tables is a
        # collective row-gather — and only rank 0 writes; tables stream
        # out in bounded row chunks, so this works at any table size.
        save_model(worker.trainer, args.output, args)
    return 0


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
        import numpy as np

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

        model_params = format_dict_params(load_model_spec(args).model_params)
    export_model(
        trainer,
        output_path,
        model_zoo=getattr(args, "model_zoo", ""),
        model_def=getattr(args, "model_def", ""),
        model_params=model_params,
    )


#: Consecutive failed tasks a Local worker reports and rides through:
#: nothing would relaunch it.
LOCAL_TASK_FAILURES = 10


def _build_collective_worker(
    args, model_spec, data_reader, client,
    validation_reader=None, prediction_reader=None,
):
    """Build the worker for `args.distribution_strategy`: form the world,
    build the mesh-wide trainer, wire the loop.  The strategy decides
    three things, here and nowhere else: the world (joined through the
    master, or Local's world of one), the trainer and its devices, and
    how many failed tasks the loop rides through."""
    import jax

    from elasticdl_tpu import obs
    from elasticdl_tpu.checkpoint import (
        CheckpointSaver,
        ShardedCheckpointSaver,
    )
    from elasticdl_tpu.common.constants import DistributionStrategy
    from elasticdl_tpu.common.profiler import StepProfiler
    from elasticdl_tpu.data.pipeline import PipelineConfig
    from elasticdl_tpu.obs import tracing
    from elasticdl_tpu.obs.stepstats import StepAnatomy
    from elasticdl_tpu.obs.telemetry import WorkerTelemetry
    from elasticdl_tpu.ops import sparse_embedding as ske
    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer
    from elasticdl_tpu.parallel.elastic import WorldInfo, join_world
    from elasticdl_tpu.worker.collective_worker import CollectiveWorker

    strategy = args.distribution_strategy
    local = strategy == DistributionStrategy.LOCAL
    sharded_embeddings = strategy == DistributionStrategy.PARAMETER_SERVER
    # The worker-side half of world-formation cost: the rank poll and,
    # in a world of more than one, the distributed-init barrier (the
    # master-side half is
    # elasticdl_rendezvous_formation_duration_seconds).
    with obs.span("worker.join_world") as joined:
        if local:
            # A master and a world of one: no rendezvous to join, and no
            # supervisor that would re-form a world.
            world = WorldInfo(
                rank=0, world_size=1, rendezvous_id=0, coordinator_addr=""
            )
        else:
            world = join_world(client)
        joined.fields.update(
            rendezvous_id=world.rendezvous_id, rank=world.rank,
            world_size=world.world_size,
        )
    # Worker telemetry plane: step times / task progress / RPC retries
    # collected here ride the liveness heartbeat to the master's
    # aggregator (docs/observability.md "Worker telemetry plane").
    telemetry = WorkerTelemetry(client.worker_id)
    telemetry.bind_retry_stats(client.retry_stats)
    telemetry.set_rendezvous(world.rendezvous_id)
    # Step-anatomy ledger (docs/observability.md "Step anatomy"): the
    # phase decomposition rides the same heartbeat snapshot; the
    # CollectiveWorker reads it off the telemetry binding and registers
    # the trainer's jitted entrypoints for retrace detection.
    anatomy = StepAnatomy(client.worker_id)
    anatomy.set_model(args.model_def or args.model_zoo)
    telemetry.bind_anatomy(anatomy)
    # The first `jax.devices()` of the process brings the backend up
    # (on a TPU host: the runtime's start, seconds).
    with tracing.span("worker.backend_init") as backend:
        backend.fields["devices"] = len(jax.devices())
    # The mesh, the model, the trainer, the saver and the worker object.
    with tracing.span("worker.build_trainer"):
        if local:
            # Local trains on one device.
            mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
        else:
            # All devices of the joined world, shaped (data, model): the
            # model axis carries sharded embedding tables and — for
            # mesh-aware zoo models — ring-attention context parallelism.
            mesh = build_mesh(MeshConfig(model=args.mesh_model_axis))
        # --sparse_kernel resolution is STRATEGY-INDEPENDENT (the Embedding
        # layers run under every trainer).  Multi-device meshes run the
        # fused kernels through the shard_map dispatch
        # (ops/sparse_embedding.py "Sharded dispatch").  Register BOTH
        # process defaults BEFORE the model is built: the kernel default
        # (Embedding layers that did not thread sparse_kernel explicitly
        # resolve it at trace time; zoo models that declare the param get
        # the same value via model_params, common/model_utils.py) and the
        # dispatch mesh (layers that did not thread `mesh` still route
        # per-shard kernel bodies instead of tracing an unpartitionable
        # pallas_call into an SPMD program).
        sparse_kernel = args.sparse_kernel or "auto"
        ske.set_default_kernel(sparse_kernel)
        ske.set_dispatch_mesh(mesh)
        if sharded_embeddings:
            from elasticdl_tpu.parallel.ps_trainer import (
                ShardedEmbeddingTrainer,
            )

            trainer = ShardedEmbeddingTrainer(
                model=model_spec.build_model(mesh=mesh),
                loss_fn=model_spec.loss,
                optimizer=model_spec.optimizer(),
                mesh=mesh,
                embedding_optimizer=(
                    model_spec.embedding_optimizer()
                    if model_spec.embedding_optimizer is not None
                    else None
                ),
                sparse_apply_every=args.sparse_apply_every,
                sparse_kernel=sparse_kernel,
            )
        else:
            trainer = DataParallelTrainer(
                model=model_spec.build_model(mesh=mesh),
                loss_fn=model_spec.loss,
                optimizer=model_spec.optimizer(),
                mesh=mesh,
                dense_sharding=args.dense_sharding,
            )
        saver = None
        if args.checkpoint_dir:
            # Mesh-sharded state (PS tables / FSDP dense leaves): each
            # process writes its own shard files, so no host ever gathers
            # the full model (checkpoint/sharded.py).
            saver_cls = (
                ShardedCheckpointSaver
                if sharded_embeddings or args.dense_sharding == "fsdp"
                else CheckpointSaver
            )
            saver = saver_cls(
                args.checkpoint_dir, keep_max=args.keep_checkpoint_max
            )
        return CollectiveWorker(
            master_client=client,
            model_spec=model_spec,
            data_reader=data_reader,
            minibatch_size=args.minibatch_size,
            world=world,
            trainer=trainer,
            checkpoint_saver=saver,
            checkpoint_steps=args.checkpoint_steps,
            validation_data_reader=validation_reader,
            prediction_data_reader=prediction_reader,
            profiler=StepProfiler(
                args.tensorboard_log_dir, args.profile_steps, client.worker_id
            ),
            train_window_steps=args.train_window_steps,
            telemetry=telemetry,
            pipeline=PipelineConfig.from_args(args),
            max_task_failures=LOCAL_TASK_FAILURES if local else 0,
        )


if __name__ == "__main__":
    sys.exit(main())
