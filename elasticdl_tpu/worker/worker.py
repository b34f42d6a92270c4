"""The worker runtime: task loop around the jitted step.

Parity: elasticdl/python/worker/worker.py in the reference — `Worker.run()`
pulls tasks from the master, builds the per-task dataset, runs the
minibatch loop, and reports results; evaluation tasks run forward-only and
ship outputs/labels to the master for aggregation.
"""

from __future__ import annotations

import contextlib
import time
import traceback
from typing import Optional

import numpy as np

from elasticdl_tpu import obs
from elasticdl_tpu.common import faults
from elasticdl_tpu.common.constants import Mode, TaskExecCounterKey
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.model_utils import ModelSpec
from elasticdl_tpu.data.pipeline import PipelineConfig, Prefetcher
from elasticdl_tpu.data.task_data_service import TaskDataService
from elasticdl_tpu.obs import goodput, quality
from elasticdl_tpu.proto import elasticdl_pb2 as pb
from elasticdl_tpu.worker.trainer import Trainer

logger = get_logger("worker.worker")


class Worker:
    def __init__(
        self,
        master_client,
        model_spec: ModelSpec,
        data_reader,
        minibatch_size: int,
        trainer: Optional[Trainer] = None,
        report_version_every_steps: int = 20,
        wait_sleep_s: float = 0.5,
        max_consecutive_task_failures: int = 10,
        validation_data_reader=None,
        prediction_data_reader=None,
        profiler=None,
        anatomy=None,
        pipeline: Optional[PipelineConfig] = None,
    ):
        self._mc = master_client
        self._spec = model_spec
        self._minibatch_size = minibatch_size
        self._task_data_service = TaskDataService(
            data_reader, model_spec.dataset_fn
        )
        # Evaluation/prediction tasks read from their own data source when
        # one is configured (shard names address a different dataset).
        self._eval_data_service = (
            TaskDataService(validation_data_reader, model_spec.dataset_fn)
            if validation_data_reader is not None
            else self._task_data_service
        )
        self._predict_data_service = (
            TaskDataService(prediction_data_reader, model_spec.dataset_fn)
            if prediction_data_reader is not None
            else self._task_data_service
        )
        self._trainer = trainer or Trainer(
            model=model_spec.build_model(),
            loss_fn=model_spec.loss,
            optimizer=model_spec.optimizer(),
        )
        self._report_every = report_version_every_steps
        self._wait_sleep_s = wait_sleep_s
        self._max_consecutive_failures = max_consecutive_task_failures
        self._last_reported_version = 0
        self._profiler = profiler
        # Step-anatomy ledger (obs/stepstats.StepAnatomy, optional):
        # host-clock decomposition of the train loop into data_wait /
        # compile / execute / bookkeep sub-phases.
        self._anatomy = anatomy
        if anatomy is not None and hasattr(
            self._trainer, "jitted_entrypoints"
        ):
            anatomy.watch_jits(self._trainer.jitted_entrypoints)
        # Async staging engine (data/pipeline.py): Local mode fuses
        # staging into train_step, so async here means bounded
        # background prefetch — parse/batching for item N+1 runs while
        # step N dispatches, with the hidden producer time credited as
        # anatomy overlap.  Sync (default) is the classic serial loop.
        self._pipeline = pipeline or PipelineConfig()

    def _anat_phase(self, name: str):
        if self._anatomy is None:
            return contextlib.nullcontext()
        return self._anatomy.phase(name)

    @property
    def trainer(self) -> Trainer:
        return self._trainer

    # ------------------------------------------------------------------

    def run(self):
        """Main loop: pull tasks until the master says the job is done."""
        try:
            self._run_inner()
        finally:
            # In finally: an aborting worker must still flush an in-flight
            # profiler trace — it's most needed exactly then.
            if self._profiler is not None:
                self._profiler.stop()

    def _run_inner(self):
        consecutive_failures = 0
        while True:
            task = self._mc.get_task()
            if task.task_id == -1 and task.type != pb.WAIT:
                logger.info("Job complete; worker %d exiting", self._mc.worker_id)
                break
            if task.type == pb.WAIT:
                # Ledger: nothing to do right now — idle, not training
                # (in Local mode this is the same process-wide ledger the
                # master hooks feed; the phases agree by construction).
                goodput.ledger().transition("idle", cause="wait_task")
                time.sleep(self._wait_sleep_s)
                continue
            spec = faults.fire("worker.task")
            if spec is not None and spec.kind == "crash":
                faults.crash_now(spec)
            try:
                counters = self._process_task(task)
            except Exception as exc:
                logger.error("Task %d failed:\n%s", task.task_id, traceback.format_exc())
                self._mc.report_task_result_best_effort(
                    task.task_id, str(exc) or repr(exc),
                    trace_id=task.trace_id,
                )
                consecutive_failures += 1
                if consecutive_failures >= self._max_consecutive_failures:
                    raise RuntimeError(
                        f"{consecutive_failures} consecutive task failures; "
                        "worker aborting"
                    ) from exc
            else:
                # The task itself succeeded — a lost SUCCESS report must
                # not morph into a failure report (it would requeue
                # already-trained records AND double-charge the task's
                # retry budget).
                self._mc.report_task_result_best_effort(
                    task.task_id, "", counters, trace_id=task.trace_id
                )
                consecutive_failures = 0
        # Final version report so master-side services see the last step.
        self._report_version(force=True)

    # ------------------------------------------------------------------

    def _process_task(self, task) -> dict:
        try:
            type_name = pb.TaskType.Name(task.type)
        except ValueError:
            type_name = "UNKNOWN"
        # Span: per-task worker-side latency histogram (bounded `type`
        # label) + a journal record carrying the unbounded task id and the
        # dispatch-minted trace id (the worker half of the trace chain).
        span_fields = dict(task_id=task.task_id)
        if task.trace_id:
            span_fields["trace_id"] = task.trace_id
        with obs.span(
            "worker.task", labels={"type": type_name}, **span_fields
        ):
            if task.type == pb.TRAINING:
                return self._process_train_task(task)
            if task.type == pb.EVALUATION:
                return self._process_eval_task(task)
            if task.type == pb.PREDICTION:
                return self._process_predict_task(task)
            if task.type == pb.TRAIN_END_CALLBACK:
                return self._process_train_end(task)
            raise ValueError(f"Unknown task type {task.type}")

    def _get_batches(self, task, mode: str):
        # The user's dataset_fn parses/shuffles records; the worker applies
        # the job-level minibatch batching (reference worker behavior).
        service = {
            Mode.TRAINING: self._task_data_service,
            Mode.EVALUATION: self._eval_data_service,
            Mode.PREDICTION: self._predict_data_service,
        }[mode]
        dataset = service.get_dataset(task, mode)
        return dataset.batch(self._minibatch_size)

    def _process_train_task(self, task) -> dict:
        batch_count = 0
        record_count = 0
        last_loss = None
        prefetcher = None
        if self._pipeline.is_async:
            batches = self._task_data_service.get_batches(
                task, Mode.TRAINING, self._minibatch_size,
                lookahead=self._pipeline.max_inflight,
            )
            if isinstance(batches, Prefetcher):
                prefetcher = batches
        else:
            batches = iter(self._get_batches(task, Mode.TRAINING))
        try:
            while True:
                # Host data wait: record parse + batching live in the
                # iterator (step anatomy's starvation signal); behind a
                # prefetcher this measures only true blocked time.
                with self._anat_phase("data_wait"):
                    batch = next(batches, None)
                if batch is None:
                    break
                features, labels = batch
                spec = faults.fire("worker.step")
                if spec is not None and spec.kind == "crash":
                    faults.crash_now(spec)
                # Train-side skew sketch (host-side, pre-staging host
                # arrays — never a device read): no-op until
                # --quality_drift_bins enables a monitor.
                quality.note_train_batch(features)
                if self._profiler is not None:
                    self._profiler.before_steps(self._trainer.step)
                n = _batch_size_of(features)
                if self._anatomy is not None:
                    # One dispatch per batch in Local mode (staging is
                    # fused into train_step; compile-vs-execute split
                    # comes from the trainer's watched jit cache).
                    with self._anatomy.dispatch(1, n):
                        last_loss = self._trainer.train_step(features, labels)
                else:
                    last_loss = self._trainer.train_step(features, labels)
                batch_count += 1
                record_count += n
                with self._anat_phase("bookkeep"):
                    if self._trainer.step % self._report_every == 0:
                        self._report_version()
                # Outside bookkeep: a profile's stop waits for the
                # device and writes the trace.
                if self._profiler is not None:
                    self._profiler.after_steps(
                        self._trainer.step, wait_for=last_loss
                    )
        finally:
            # Task boundary (or an exception): drain the read-ahead so
            # no stale in-flight batch survives into the next task.
            if prefetcher is not None:
                if self._anatomy is not None:
                    self._anatomy.note_overlap_seconds(prefetcher.overlap_s)
                prefetcher.close()
        if self._anatomy is not None:
            # One anatomy window per task in Local mode — and since this
            # path has no telemetry heartbeat to carry it, journal the
            # cumulative anatomy here (the process journal: shared with
            # the master in-process in Local mode, the worker's own
            # events_worker_N.jsonl in subprocess runs).  The window's
            # phases also become aggregate child spans of the open
            # worker.task span (obs/tracing.py).
            from elasticdl_tpu.obs import stepstats, tracing

            window = self._anatomy.close_window()
            if window:
                tracing.tracer().record_window_spans(window)
            stepstats.journal_anatomy(
                self._anatomy.worker_id, self._anatomy.snapshot()
            )
        if last_loss is not None:
            logger.info(
                "task %d done: step=%d loss=%.5f (%d batches)",
                task.task_id,
                self._trainer.step,
                float(last_loss),
                batch_count,
            )
        self._report_version()
        return {
            TaskExecCounterKey.BATCH_COUNT: batch_count,
            TaskExecCounterKey.RECORD_COUNT: record_count,
        }

    def _process_eval_task(self, task) -> dict:
        dataset = self._get_batches(task, Mode.EVALUATION)
        outputs_list = []
        labels_list = []
        batch_count = 0
        for features, labels in dataset:
            outputs = self._trainer.eval_step(features)
            outputs_list.append(named_arrays(outputs, "output"))
            labels_list.append(named_arrays(labels, ""))
            batch_count += 1
        if outputs_list:
            # Report under the round's version so the master aggregates all
            # of a round's tasks together regardless of worker step skew.
            self._mc.report_evaluation_metrics(
                model_version=task.model_version,
                model_outputs=concat_named(outputs_list),
                labels=concat_named(labels_list),
                # Reports stage per task on the master and promote when
                # the task completes (retry-safe chunked-report protocol).
                task_id=task.task_id,
            )
        return {TaskExecCounterKey.BATCH_COUNT: batch_count}

    def _process_predict_task(self, task) -> dict:
        dataset = self._get_batches(task, Mode.PREDICTION)
        batch_count = 0
        for batch in dataset:
            features = batch[0] if isinstance(batch, tuple) else batch
            self._trainer.eval_step(features)
            batch_count += 1
        return {TaskExecCounterKey.BATCH_COUNT: batch_count}

    def _process_train_end(self, task) -> dict:
        if self._spec.callbacks is not None:
            for callback in self._spec.callbacks() or []:
                callback(self)
        return {}

    def _report_version(self, force: bool = False):
        step = self._trainer.step
        if force or step > self._last_reported_version:
            self._mc.report_version(step)
            self._last_reported_version = step


def named_arrays(tree, default_name: str = "output") -> dict:
    """Flatten a model-output/label pytree into {name: np.ndarray}.

    Dicts (the multi-output contract) keep their keys, nesting joined with
    '/'; a bare tensor maps to `default_name`.  The reference aggregates
    arbitrary named outputs/labels through Keras metrics (SURVEY.md §3.5).
    """
    if isinstance(tree, dict):
        flat = {}
        for key, value in tree.items():
            if isinstance(value, dict):
                for sub, arr in named_arrays(value, default_name).items():
                    flat[f"{key}/{sub}"] = arr
            else:
                flat[str(key)] = np.asarray(value)
        return flat
    return {default_name: np.asarray(tree)}


def concat_named(batches: list) -> dict:
    """Concatenate a list of {name: array} dicts along axis 0."""
    names = batches[0].keys()
    return {name: np.concatenate([b[name] for b in batches]) for name in names}


def _batch_size_of(features) -> int:
    if isinstance(features, dict):
        features = next(iter(features.values()))
    if isinstance(features, (tuple, list)):
        features = features[0]
    return int(np.asarray(features).shape[0])
