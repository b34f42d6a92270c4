"""Worker-side wrapper around the Master gRPC stub.

Parity: elasticdl/python/worker/master_client.py in the reference, plus the
transient-failure plane: every RPC carries an explicit deadline, and
idempotent RPCs (reads and naturally-deduplicated reports) retry transient
failures with backoff so workers ride through a master restart instead of
dying and triggering a slice-wide world re-formation.

Idempotency per RPC (the retry wrapper never guesses — see
common/grpc_utils.py):

- `get_task`           retried: a popped-but-unacked task is recovered by
                       the master's timeout/churn paths (at-least-once).
- `get_comm_rank`, `report_worker_liveness`, `get_shard_checkpoint`
                       retried: pure reads / latest-wins liveness.
- `report_version`     retried: the master folds it with max().
- `report_task_result` NOT retried: a duplicate success report for a
                       task id the master already closed logs as
                       unknown-task; a duplicate *failure* report would
                       double-charge the task's retry budget.
- `report_evaluation_metrics`
                       NOT retried: reports append to the round's staged
                       chunks — a duplicate would double-count rows.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional

import numpy as np

from elasticdl_tpu.common import tensor_utils
from elasticdl_tpu.common.constants import RPC
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.grpc_utils import (
    IDEMPOTENT_POLICY,
    NON_IDEMPOTENT_POLICY,
    RetryPolicy,
    RetryStats,
    build_channel,
    call_with_retry,
    trace_metadata,
)
from elasticdl_tpu.proto import elasticdl_pb2 as pb
from elasticdl_tpu.proto.service import MasterStub

logger = get_logger("worker.master_client")


class MasterClient:
    def __init__(
        self,
        addr: str,
        worker_id: int,
        retry_policy: Optional[RetryPolicy] = None,
        no_retry_policy: Optional[RetryPolicy] = None,
        sleep: Callable[[float], None] = time.sleep,
    ):
        self._channel = build_channel(addr)
        self._stub = MasterStub(self._channel)
        self._worker_id = worker_id
        self._retry_policy = retry_policy or IDEMPOTENT_POLICY
        self._no_retry_policy = no_retry_policy or NON_IDEMPOTENT_POLICY
        self._sleep = sleep
        #: Transient-failure observability: how often this worker had to
        #: retry (chaos tests assert workers actually rode through the
        #: outage instead of never noticing it).
        self.retry_stats = RetryStats()

    @property
    def worker_id(self) -> int:
        return self._worker_id

    # ------------------------------------------------------------------

    def _call(self, method: str, request, policy: RetryPolicy, metadata=None):
        return call_with_retry(
            getattr(self._stub, method),
            request,
            method=method,
            policy=policy,
            stats=self.retry_stats,
            sleep=self._sleep,
            # Per-worker jitter salt: deterministic per worker, but the
            # fleet's backoff schedules are decorrelated.
            seed=str(self._worker_id),
            metadata=metadata,
        )

    def _call_idempotent(self, method: str, request):
        return self._call(method, request, self._retry_policy)

    def _call_once(self, method: str, request, timeout_s: Optional[float] = None,
                   metadata=None):
        policy = self._no_retry_policy
        if timeout_s is not None and timeout_s != policy.timeout_s:
            # Override only the deadline; an injected no_retry_policy
            # keeps its other fields.
            policy = dataclasses.replace(policy, timeout_s=timeout_s)
        return self._call(method, request, policy, metadata=metadata)

    # ------------------------------------------------------------------

    def get_task(self, task_type: int = pb.TRAINING) -> pb.Task:
        """The client half of dispatch is a trace span: the span id is
        minted BEFORE the call and rides gRPC metadata (the servicer's
        `rpc.get_task` span parents under it), and the span journals
        after the fact once the response reveals the trace id — WAIT
        polls and job-complete answers carry no trace and journal no
        span (a poll loop must not flood the journal).  The RPC itself is
        a `TraceAnnotation` of the same name, for every call."""
        from elasticdl_tpu.obs import tracing

        request = pb.GetTaskRequest(worker_id=self._worker_id, task_type=task_type)
        span_id = tracing.tracer().mint_span_id()
        start_ts = time.time()
        start = time.monotonic()
        # On the profiler's host plane every call is named, a WAIT poll
        # too: a device idle behind the queue is what a trace wants said.
        with tracing.annotate("worker.get_task"):
            task = self._call(
                "get_task",
                request,
                self._retry_policy,
                metadata=trace_metadata("", span_id=span_id),
            ).task
        if task.trace_id:
            tracing.tracer().record_span(
                "worker.get_task",
                start_ts=start_ts,
                duration_s=time.monotonic() - start,
                trace_id=task.trace_id,
                # Root convention: the task root's span id IS the trace
                # id, so the client can parent under it without ever
                # having seen the root span.
                parent_id=task.trace_id,
                span_id=span_id,
                worker_id=self._worker_id,
            )
        return task

    def report_task_result(
        self, task_id: int, err_message: str = "",
        exec_counters: Optional[Dict[str, int]] = None, trace_id: str = "",
    ):
        """`trace_id` (the dispatch-minted id from Task.trace_id) rides
        gRPC metadata back to the master, closing the cross-process
        journal chain (grpc_utils.TRACE_METADATA_KEY)."""
        from elasticdl_tpu.obs import tracing

        request = pb.ReportTaskResultRequest(
            task_id=task_id, err_message=err_message, worker_id=self._worker_id
        )
        if exec_counters:
            for key, value in exec_counters.items():
                request.exec_counters[key] = int(value)
        if not trace_id:
            self._call_once("report_task_result", request)
            return
        # Traced report: the client span parents under the task root
        # (the worker.task span has already closed by report time), and
        # its span id rides the metadata so the master's
        # rpc.report_task_result handler span nests under it.
        with tracing.tracer().span(
            "worker.report_task",
            trace_id=trace_id,
            parent_id=trace_id,
            worker_id=self._worker_id,
            task_id=task_id,
        ) as report_span:
            self._call_once(
                "report_task_result",
                request,
                metadata=trace_metadata(trace_id, span_id=report_span.span_id),
            )

    def report_task_result_best_effort(
        self, task_id: int, err_message: str = "",
        exec_counters: Optional[Dict[str, int]] = None, trace_id: str = "",
    ) -> bool:
        """Result report where delivery failure is data, not an error:
        result reports are non-idempotent and never retried, and an
        unreported task is recovered by the master's timeout/churn paths
        (at-least-once) — so a report lost to a master outage must not
        crash the worker or poison the world.  True when delivered."""
        try:
            self.report_task_result(
                task_id, err_message, exec_counters, trace_id=trace_id
            )
            return True
        except Exception:
            logger.warning(
                "Could not report task %d %s (master unreachable?); the "
                "master will requeue the task (at-least-once)",
                task_id, "failure" if err_message else "success",
            )
            return False

    def report_evaluation_metrics(self, model_version: int, model_outputs,
                                  labels, task_id: int = 0):
        """`model_outputs` is {name: array}; `labels` is an array or a
        {name: array} dict (multi-label models).  `task_id` scopes the
        chunked reports to their EVALUATION task (see the proto note)."""
        request = pb.ReportEvaluationMetricsRequest(
            worker_id=self._worker_id, model_version=model_version,
            task_id=task_id,
        )
        for name, array in model_outputs.items():
            request.model_outputs.append(tensor_utils.ndarray_to_pb(array, name=name))
        if not isinstance(labels, dict):
            labels = {"": np.asarray(labels)}
        for name, array in labels.items():
            request.labels.append(
                tensor_utils.ndarray_to_pb(np.asarray(array), name=name)
            )
        self._call_once(
            "report_evaluation_metrics",
            request,
            timeout_s=RPC.EVAL_REPORT_DEADLINE_S,
        )

    def report_version(self, model_version: int):
        self._call_idempotent(
            "report_version",
            pb.ReportVersionRequest(
                model_version=model_version, worker_id=self._worker_id
            ),
        )

    def get_comm_rank(self, host: str = "") -> pb.GetCommRankResponse:
        return self._call_idempotent(
            "get_comm_rank",
            pb.GetCommRankRequest(worker_id=self._worker_id, host=host),
        )

    def report_worker_liveness(
        self, host: str, rendezvous_id: int, telemetry_json: str = ""
    ) -> bool:
        """`telemetry_json` is the worker's bounded telemetry snapshot
        (obs/telemetry.py) — the heartbeat doubles as the telemetry
        carrier, so per-worker observability costs zero new RPCs."""
        response = self._call_idempotent(
            "report_worker_liveness",
            pb.ReportWorkerLivenessRequest(
                worker_id=self._worker_id, host=host,
                rendezvous_id=rendezvous_id, telemetry_json=telemetry_json,
            ),
        )
        return response.should_reset

    def get_shard_checkpoint(self) -> str:
        return self._call_idempotent(
            "get_shard_checkpoint", pb.ShardCheckpointRequest()
        ).content

    def close(self):
        self._channel.close()
