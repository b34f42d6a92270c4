"""Master-side TensorBoard scalar service.

Parity: elasticdl/python/master/tensorboard_service.py in the reference —
the master owns one event-file writer and streams job-level scalars:
evaluation metrics per model version (pushed by EvaluationService through
`write_dict_to_summary`, the reference's method name) and training
progress (model version, records/tasks finished, worker-restart count)
sampled on a background cadence, since the master — not any worker — is
the single stable observer of an elastic job.

Writer backend: `_EventFileWriter` below appends TFRecord-framed `Event`
protos to one `events.out.tfevents.*` file itself.  It needs only the
`tensorboard` package's protos (a tenth of a second to import): a
master never imports a deep-learning framework to write a handful of
60-byte scalars.  A writer that cannot be built (no `tensorboard`
package, an unwritable `log_dir`) degrades to one warning and dropped
scalars, never a job failure — observability must not take training
down.

Worker-side profiling (jax.profiler traces viewable in the same
TensorBoard under the Profile plugin) lives in common/profiler.py; this
module is only the master's scalar plane.
"""

from __future__ import annotations

import os
import socket
import struct
import threading
import time
from typing import Callable, Dict, Optional

from elasticdl_tpu.analysis.runtime import make_lock
from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("master.tensorboard")


def _crc32c_table():
    table = []
    for n in range(256):
        for _ in range(8):
            n = (n >> 1) ^ (0x82F63B78 if n & 1 else 0)
        table.append(n)
    return table


_CRC32C_TABLE = _crc32c_table()


def crc32c(data: bytes) -> int:
    """CRC-32C (Castagnoli), the checksum of the TFRecord framing."""
    crc = 0xFFFFFFFF
    for byte in data:
        crc = _CRC32C_TABLE[(crc ^ byte) & 0xFF] ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF


def masked_crc32c(data: bytes) -> int:
    crc = crc32c(data)
    return (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF


def frame_record(payload: bytes) -> bytes:
    """One TFRecord: little-endian length, its masked CRC, the payload,
    the payload's masked CRC."""
    header = struct.pack("<Q", len(payload))
    return b"".join((
        header,
        struct.pack("<I", masked_crc32c(header)),
        payload,
        struct.pack("<I", masked_crc32c(payload)),
    ))


class _EventFileWriter:
    """Appends scalar `Event`s to one TensorBoard event file.  Not
    thread-safe: the service's lock serializes its callers."""

    def __init__(self, log_dir: str):
        from tensorboard.compat.proto import event_pb2, summary_pb2

        self._event_pb2 = event_pb2
        self._summary_pb2 = summary_pb2
        os.makedirs(log_dir, exist_ok=True)
        path = os.path.join(
            log_dir,
            "events.out.tfevents.%010d.%s.%d.0"
            % (time.time(), socket.gethostname(), os.getpid()),
        )
        self._file = open(path, "ab")
        self._write(
            event_pb2.Event(
                wall_time=time.time(), file_version="brain.Event:2"
            )
        )
        self.flush()

    def _write(self, event):
        self._file.write(frame_record(event.SerializeToString()))

    def add_scalar(self, tag: str, value: float, step: int):
        summary = self._summary_pb2.Summary(
            value=[
                self._summary_pb2.Summary.Value(tag=tag, simple_value=value)
            ]
        )
        self._write(
            self._event_pb2.Event(
                wall_time=time.time(), step=step, summary=summary
            )
        )

    def flush(self):
        self._file.flush()

    def close(self):
        self._file.close()


class TensorBoardService:
    def __init__(
        self,
        log_dir: str,
        task_manager=None,
        model_version_fn: Optional[Callable[[], int]] = None,
        restarts_fn: Optional[Callable[[], int]] = None,
        sample_interval_s: float = 10.0,
    ):
        self._log_dir = log_dir
        self._task_manager = task_manager
        self._model_version_fn = model_version_fn
        self._restarts_fn = restarts_fn
        self._sample_interval_s = sample_interval_s
        # Guards the (not thread-safe) event-file writer: scalars arrive
        # from servicer threads, the sampler thread, and close().
        self._lock = make_lock("TensorBoardService._lock")
        self._stop_event = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._writer = None  # guarded-by: _lock
        try:
            self._writer = _EventFileWriter(log_dir)
            logger.info("TensorBoard events -> %s", log_dir)
        except Exception:
            logger.exception(
                "TensorBoard writer unavailable; scalars will be dropped"
            )

    # -- write paths ----------------------------------------------------

    def write_dict_to_summary(
        self, metrics: Dict[str, float], version: int, prefix: str = "eval"
    ):
        """EvaluationService pushes each finalized round's metrics here
        (reference method name/contract)."""
        with self._lock:
            for name, value in metrics.items():
                self._add_scalar_locked(f"{prefix}/{name}", value, version)
            self._flush_locked()

    def write_scalar(self, tag: str, value: float, step: int):
        with self._lock:
            self._add_scalar_locked(tag, value, step)

    def _add_scalar_locked(self, tag: str, value, step):
        if self._writer is None:
            return
        try:
            self._writer.add_scalar(tag, float(value), int(step))
        except Exception:
            logger.exception("Dropping scalar %s", tag)

    def _flush_locked(self):
        if self._writer is None:
            return
        try:
            self._writer.flush()
        except OSError:
            logger.exception("TensorBoard flush failed")

    def bind(
        self,
        model_version_fn: Optional[Callable[[], int]] = None,
        restarts_fn: Optional[Callable[[], int]] = None,
    ):
        """Late-bind progress sources that exist only after this service
        is constructed (servicer's model version, the pod manager's
        restart counter)."""
        if model_version_fn is not None:
            self._model_version_fn = model_version_fn
        if restarts_fn is not None:
            self._restarts_fn = restarts_fn

    # -- progress sampling ----------------------------------------------

    def start(self) -> "TensorBoardService":
        if self._writer is not None:
            self._thread = threading.Thread(
                target=self._sample_loop, name="tensorboard-sampler",
                daemon=True,
            )
            self._thread.start()
        return self

    def _sample_progress(self):
        version = (
            int(self._model_version_fn()) if self._model_version_fn else 0
        )
        if self._task_manager is not None:
            counts = self._task_manager.counts()
            self.write_scalar(
                "train/records_finished",
                self._task_manager.finished_record_count,
                version,
            )
            self.write_scalar("train/tasks_todo", counts["todo"], version)
            self.write_scalar("train/epoch", counts["epoch"], version)
            from elasticdl_tpu.common.constants import TaskExecCounterKey

            counters_fn = getattr(self._task_manager, "exec_counters", None)
            if counters_fn is not None:
                self.write_scalar(
                    "train/oov_lookup_count",
                    counters_fn().get(
                        TaskExecCounterKey.OOV_LOOKUP_COUNT, 0
                    ),
                    version,
                )
        if self._model_version_fn is not None:
            self.write_scalar("train/model_version", version, version)
        if self._restarts_fn is not None:
            self.write_scalar(
                "train/worker_restarts", self._restarts_fn(), version
            )
        # No queue thread stands behind the writer: what a sample wrote
        # is on disk when the sample returns.
        with self._lock:
            self._flush_locked()

    def _sample_loop(self):
        while not self._stop_event.wait(self._sample_interval_s):
            try:
                self._sample_progress()
            except Exception:
                logger.exception("TensorBoard progress sample failed")

    def close(self):
        self._stop_event.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        if self._writer is None:
            return
        try:
            self._sample_progress()  # final datapoint at job end
        except Exception:
            logger.exception("TensorBoard final sample failed")
        with self._lock:
            writer, self._writer = self._writer, None
            if writer is None:
                return
            try:
                writer.close()
            except OSError:
                logger.exception("TensorBoard close failed")
