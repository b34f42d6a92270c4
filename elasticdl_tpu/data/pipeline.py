"""Async staging engine — the host-bound killer (ROADMAP item 4).

BENCH_r04's e2e decomposition shows the strict-mode DeepFM chip number
(~973k samples/s/chip) collapsing to ~276k end to end with `bound:
host-core` and `host_parse_frac 0.685`: parse, stage, and H2D all
serialize with device compute.  This module is the shared machinery that
breaks the serialization, used by both the training step loop
(worker/collective_worker.py) and the serving micro-batcher
(serving/batcher.py):

  ParsePool        multi-core host parse: `parse_buffer` (and any other
                   pure chunk->columns fn) runs on worker threads off the
                   step loop's critical path.  numpy releases the GIL for
                   the big copies/casts, so threads scale with cores
                   without the pickling tax of processes.  Ordering is
                   deterministic (results reassemble by submission index)
                   and errors propagate in submission order, so a
                   jittered pool is indistinguishable from serial `map`.

  Prefetcher       bounded background readahead over any batch iterator:
                   the producer thread runs parse + batch slicing for
                   item N+1..N+k while the step loop dispatches N.  The
                   queue bound is the backpressure contract — a slow
                   device stalls the producer instead of growing host
                   memory without limit.  Per-item production time and
                   consumer blocked time are both clocked so step anatomy
                   can book the *hidden* portion as overlap credit
                   instead of silently vanishing it.

  StagingPipeline  double-buffered device staging: while window N's
                   dispatch is outstanding on the device queue, window
                   N+1's `stage_window`/`stage_batch` (non-blocking
                   `device_put` under JAX async dispatch) books as
                   `overlap_s`, not `stage` — the ledger tells the truth
                   about what actually serialized with compute.

  pad_and_stage    the serving pad-to-bucket + optional stage step, so
                   training and serving share one staging implementation
                   (`bucket_for`/`pad_features` live here now; the
                   batcher re-exports them).

Elastic discipline: pipelines are scoped to ONE task.  Churn, rescale,
and checkpoint all happen at task/rendezvous boundaries in this
codebase, and `Prefetcher.close()` / `ParsePool.close()` drain
synchronously — no stale in-flight batch ever crosses a rendezvous
generation (tests/test_pipeline.py exercises the churn path).

Donation note: staged buffers feed `train_window`/`train_step_staged`,
which donate only the STATE argument (position 0); batches are never
donated, so read-ahead staging cannot alias a donated buffer.  The
analyzer's `async-staging-discipline` rule (analysis/jax_rules.py)
machine-checks the hazard for code that *does* stage into a donated
position.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import (
    Any, Callable, Dict, Iterable, Iterator, Optional, Sequence, Tuple,
)

import numpy as np

PIPELINE_MODES = ("sync", "async")


class PipelineConfig:
    """Knobs for the async staging engine, threadable from CLI args.

    mode            "sync" keeps the reference-parity serial step loop;
                    "async" turns on parse pool + prefetch + overlap
                    booking.
    parse_workers   host parse pool size (0 = parse inline on the
                    producer thread; the pool is still bypassed
                    entirely in sync mode).
    max_inflight    bounded lookahead: max batches buffered between the
                    producer and the step loop (backpressure bound).
    dispatch_depth  how many windows may be in flight on the device
                    queue before staging stops earning overlap credit.
    """

    def __init__(
        self,
        mode: str = "sync",
        parse_workers: int = 0,
        max_inflight: int = 2,
        dispatch_depth: int = 2,
    ):
        if mode not in PIPELINE_MODES:
            raise ValueError(
                f"pipeline mode {mode!r} not in {PIPELINE_MODES}"
            )
        self.mode = mode
        self.parse_workers = max(0, int(parse_workers))
        self.max_inflight = max(1, int(max_inflight))
        self.dispatch_depth = max(1, int(dispatch_depth))

    @property
    def is_async(self) -> bool:
        return self.mode == "async"

    @classmethod
    def from_args(cls, args) -> "PipelineConfig":
        return cls(
            mode=getattr(args, "pipeline", "sync"),
            parse_workers=getattr(args, "parse_pool_workers", 0),
            max_inflight=getattr(args, "pipeline_inflight", 2),
            dispatch_depth=getattr(args, "dispatch_depth", 2),
        )

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (
            f"PipelineConfig(mode={self.mode!r}, "
            f"parse_workers={self.parse_workers}, "
            f"max_inflight={self.max_inflight}, "
            f"dispatch_depth={self.dispatch_depth})"
        )


class _ImapState:
    """Per-imap reassembly buffer shared between submitter and workers."""

    __slots__ = ("cond", "results")

    def __init__(self):
        self.cond = threading.Condition()
        self.results: Dict[int, Any] = {}


class ParsePool:
    """Ordered, bounded thread-pool map for host parse work.

    `imap(fn, iterable)` yields `fn(item)` in submission order while up
    to `lookahead` items execute concurrently on `workers` threads.
    Exceptions re-raise at the yield position of the item that failed —
    exactly where serial `map` would have raised — so downstream code
    cannot observe reordering even under failure.  With `workers == 0`
    the pool degrades to plain serial `map` (no threads at all).
    """

    _CLOSE = object()

    def __init__(self, workers: int):
        self.workers = max(0, int(workers))
        self._tasks: "queue.Queue" = queue.Queue()
        self._threads = [
            threading.Thread(
                target=self._worker, name=f"parse-pool-{i}", daemon=True
            )
            for i in range(self.workers)
        ]
        for t in self._threads:
            t.start()
        self._closed = False

    def _worker(self) -> None:
        while True:
            task = self._tasks.get()
            if task is self._CLOSE:
                return
            seq, fn, item, state = task
            try:
                out = (True, fn(item))
            except BaseException as exc:  # propagated to the consumer
                out = (False, exc)
            with state.cond:
                state.results[seq] = out
                state.cond.notify_all()

    def imap(
        self,
        fn: Callable[[Any], Any],
        iterable: Iterable[Any],
        lookahead: Optional[int] = None,
    ) -> Iterator[Any]:
        if self.workers == 0:
            yield from map(fn, iterable)
            return
        if self._closed:
            raise RuntimeError("ParsePool is closed")
        if lookahead is None:
            lookahead = 2 * self.workers
        lookahead = max(1, int(lookahead))
        state = _ImapState()
        it = iter(iterable)
        submitted = 0
        next_yield = 0
        exhausted = False
        while True:
            # Keep the pool fed up to the lookahead bound; the bound is
            # what keeps host memory flat when the consumer is slow.
            while not exhausted and submitted - next_yield < lookahead:
                try:
                    item = next(it)
                except StopIteration:
                    exhausted = True
                    break
                self._tasks.put((submitted, fn, item, state))
                submitted += 1
            if next_yield >= submitted and exhausted:
                return
            with state.cond:
                while next_yield not in state.results:
                    state.cond.wait()
                ok, value = state.results.pop(next_yield)
            next_yield += 1
            if not ok:
                raise value
            yield value

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for _ in self._threads:
            self._tasks.put(self._CLOSE)
        for t in self._threads:
            t.join()

    def __enter__(self) -> "ParsePool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class Prefetcher:
    """Bounded background readahead over an iterator.

    The producer thread pulls from `source` and buffers up to
    `max_inflight` items; `__next__` hands them out in order.  The
    consumer's blocked time (`wait_s`) and the producer's total
    production time (`prod_s`) are both clocked: the step loop books
    `wait_s` as `data_wait` (it really stalled) and
    `max(0, prod_s - wait_s)` as overlap credit (host work that hid
    behind device execution).  `close()` drains synchronously — after it
    returns no producer thread is running and no buffered item will
    ever be observed, which is what lets a churn/rescale/checkpoint
    boundary guarantee no stale batch crosses a rendezvous generation.
    """

    _DONE = object()

    def __init__(self, source: Iterable[Any], max_inflight: int = 2):
        self._queue: "queue.Queue" = queue.Queue(
            maxsize=max(1, int(max_inflight))
        )
        self._source = iter(source)
        self._stop = threading.Event()
        self._exc: Optional[BaseException] = None
        self.prod_s = 0.0
        self.wait_s = 0.0
        self.produced = 0
        self.consumed = 0
        self._finished = False
        self._thread = threading.Thread(
            target=self._produce, name="prefetcher", daemon=True
        )
        self._thread.start()

    def _put(self, item: Any) -> bool:
        """Queue.put that aborts promptly when close() is racing us."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self) -> None:
        try:
            while not self._stop.is_set():
                t0 = time.perf_counter()
                try:
                    item = next(self._source)
                except StopIteration:
                    break
                self.prod_s += time.perf_counter() - t0
                self.produced += 1
                if not self._put(item):
                    return
        except BaseException as exc:  # re-raised at the consumer
            self._exc = exc
        self._put(self._DONE)

    def __iter__(self) -> "Prefetcher":
        return self

    def __next__(self) -> Any:
        if self._finished:
            raise StopIteration
        t0 = time.perf_counter()
        item = self._queue.get()
        self.wait_s += time.perf_counter() - t0
        if item is self._DONE:
            self._finished = True
            if self._exc is not None:
                exc, self._exc = self._exc, None
                raise exc
            raise StopIteration
        self.consumed += 1
        return item

    @property
    def overlap_s(self) -> float:
        """Producer time hidden behind the consumer's own work."""
        return max(0.0, self.prod_s - self.wait_s)

    def close(self) -> None:
        """Synchronous drain: stop the producer, discard buffered items,
        join.  Safe to call multiple times and mid-iteration."""
        self._stop.set()
        # Unblock a producer stuck on a full queue / a consumer racing.
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        self._thread.join()
        # Drop anything the producer flushed while we were joining.
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        self._finished = True

    def __enter__(self) -> "Prefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class StagingPipeline:
    """Double-buffered device staging with honest anatomy booking.

    Under JAX async dispatch, `stage_window`/`stage_batch` issued while
    a previous window is still executing on the device costs no
    step-loop latency — it overlaps.  This wrapper books such staging
    time as overlap credit (`StepAnatomy.note_overlap_seconds`) instead
    of the exclusive `stage` phase whenever at least one dispatch is
    outstanding.  The outstanding count is CAPPED at `dispatch_depth`:
    JAX's own dispatch queue bounds host runahead (a dispatch past the
    queue bound blocks inside the jit call, which the `execute` phase
    clock already books), so older windows beyond the depth are assumed
    retired rather than tracked — `note_synced()` resets the count at
    real host/device sync points (blocking readbacks, task boundaries).
    """

    def __init__(self, anatomy=None, dispatch_depth: int = 2):
        self._anatomy = anatomy
        self._depth = max(1, int(dispatch_depth))
        self._outstanding = 0

    @property
    def outstanding(self) -> int:
        return self._outstanding

    def stage(self, fn: Callable[..., Any], *args: Any) -> Any:
        """Run a trainer staging fn, booking its host time truthfully."""
        t0 = time.perf_counter()
        out = fn(*args)
        dt = time.perf_counter() - t0
        if self._anatomy is not None:
            if self._outstanding > 0:
                self._anatomy.note_overlap_seconds(dt)
            else:
                self._anatomy.note_phase_seconds("stage", dt)
        return out

    def note_dispatched(self) -> None:
        """A window/step was dispatched to the device queue."""
        self._outstanding = min(self._outstanding + 1, self._depth)

    def note_synced(self) -> None:
        """The host observed a device result (blocking readback): the
        device queue is drained, nothing is outstanding."""
        self._outstanding = 0

    def drain(self) -> None:
        """Task/rendezvous boundary: forget in-flight accounting."""
        self._outstanding = 0


# ---------------------------------------------------------------------------
# Shared pad-and-stage step (serving's bucket padding lives here so the
# training and serving planes use one implementation — the batcher
# re-exports these names for its existing callers).


def bucket_sizes(max_batch_size: int) -> Tuple[int, ...]:
    """Power-of-two padding buckets up to (and including) the max batch
    size — the fixed shape set the compiled step may see."""
    if max_batch_size < 1:
        raise ValueError(f"max_batch_size must be >= 1, got {max_batch_size}")
    sizes = []
    size = 1
    while size < max_batch_size:
        sizes.append(size)
        size *= 2
    sizes.append(max_batch_size)
    return tuple(sizes)


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """The smallest bucket holding n rows."""
    for size in buckets:
        if n <= size:
            return size
    return buckets[-1]


def pad_features(features: Dict[str, np.ndarray], rows: int) -> Dict[str, np.ndarray]:
    """Zero-pad every array of a features dict to `rows` along axis 0.
    Id 0 is a valid embedding row, but pad rows' outputs are sliced off
    before any request sees them and model rows are independent."""
    out = {}
    for key, array in features.items():
        array = np.asarray(array)
        if array.shape[0] == rows:
            out[key] = array
            continue
        pad = np.zeros((rows - array.shape[0],) + array.shape[1:], array.dtype)
        out[key] = np.concatenate([array, pad], axis=0)
    return out


def pad_and_stage(
    features: Dict[str, np.ndarray],
    rows: int,
    buckets: Sequence[int],
    stage_fn: Optional[Callable[[Dict[str, np.ndarray]], Any]] = None,
):
    """Serving's pad-to-bucket + optional non-blocking stage step.

    Pads `features` (stacked live rows) to the smallest admitting
    bucket, then — when `stage_fn` is given (typically a partial of
    `jax.device_put` or a trainer/replica stage method) — hands the
    padded batch to it so the H2D transfer is already in flight when
    the execute fn runs.  Returns (staged_or_padded, bucket).
    """
    bucket = bucket_for(rows, buckets)
    padded = pad_features(features, bucket)
    if stage_fn is not None:
        padded = stage_fn(padded)
    return padded, bucket
