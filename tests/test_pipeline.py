"""Async staging engine units (data/pipeline.py — ROADMAP item 4).

The contracts the step loops and the serving batcher lean on:

- ParsePool.imap is indistinguishable from serial `map` under thread
  jitter: submission-order yields, submission-order error positions,
  bounded read-ahead from the source iterator.
- Prefetcher is a bounded readahead: the queue bound is a backpressure
  contract (a slow consumer stalls the producer, host memory stays
  flat), close() is a synchronous drain (the churn/rescale/checkpoint
  boundary guarantee: no stale in-flight batch crosses a rendezvous
  generation), and the wait/overlap clocks feed step anatomy.
- StagingPipeline books staging time as the exclusive `stage` phase
  only when nothing is outstanding on the device queue, overlap credit
  otherwise.
- The async Local-mode train loop produces a BIT-IDENTICAL loss curve
  to the sync loop on CPU — the pipeline reorders work in time, never
  in effect.
"""

import threading
import time

import numpy as np
import pytest

from elasticdl_tpu.data.pipeline import (
    ParsePool,
    PipelineConfig,
    Prefetcher,
    StagingPipeline,
    bucket_for,
    bucket_sizes,
    pad_and_stage,
    pad_features,
)

# ---------------------------------------------------------------------------
# ParsePool
# ---------------------------------------------------------------------------


def _jittered_square(x):
    # Deterministic per-item jitter: later items often finish FIRST on a
    # multi-worker pool, so ordered reassembly is actually exercised.
    time.sleep(((x * 7919) % 5) / 1000.0)
    return x * x


def test_parse_pool_jittered_ordering_matches_serial_map():
    items = list(range(48))
    expect = [x * x for x in items]
    with ParsePool(workers=4) as pool:
        assert list(pool.imap(_jittered_square, items)) == expect
        # Determinism: a second pass over the same (still-jittered) pool
        # reproduces the same sequence.
        assert list(pool.imap(_jittered_square, items)) == expect


def test_parse_pool_workers_zero_is_serial_map():
    pool = ParsePool(workers=0)
    assert list(pool.imap(_jittered_square, range(8))) == [
        x * x for x in range(8)
    ]
    pool.close()  # no threads to join; must still be a no-op


def test_parse_pool_error_raises_at_failing_item_position():
    def boom_at_7(x):
        time.sleep(((x * 31) % 3) / 1000.0)
        if x == 7:
            raise ValueError("chunk 7 corrupt")
        return x

    with ParsePool(workers=3) as pool:
        out = []
        with pytest.raises(ValueError, match="chunk 7 corrupt"):
            for value in pool.imap(boom_at_7, range(16)):
                out.append(value)
        # Everything BEFORE the failing item was yielded, in order —
        # exactly where serial map would have stopped.
        assert out == list(range(7))


def test_parse_pool_lookahead_bounds_source_readahead():
    pulled = [0]

    def counting_source():
        for i in range(32):
            pulled[0] += 1
            yield i

    with ParsePool(workers=2) as pool:
        it = pool.imap(lambda x: x, counting_source(), lookahead=3)
        consumed = 0
        for value in it:
            assert value == consumed
            consumed += 1
            # The submitter never runs more than `lookahead` items past
            # the consumer (+1 for the iterator's own refill turn) —
            # this bound is what keeps host memory flat on a slow
            # device.
            assert pulled[0] <= consumed + 3 + 1
        assert consumed == 32


# ---------------------------------------------------------------------------
# Prefetcher
# ---------------------------------------------------------------------------


def test_prefetcher_yields_in_order_and_counts():
    with Prefetcher(iter(range(20)), max_inflight=4) as prefetcher:
        assert list(prefetcher) == list(range(20))
        assert prefetcher.produced == 20
        assert prefetcher.consumed == 20
        assert prefetcher.overlap_s >= 0.0
    # Exhausted + closed: further next() is a clean StopIteration.
    assert next(iter(prefetcher), None) is None


def test_prefetcher_backpressure_bounds_producer_runahead():
    produced_log = []

    def slow_to_consume_source():
        for i in range(24):
            produced_log.append(i)
            yield i

    prefetcher = Prefetcher(slow_to_consume_source(), max_inflight=2)
    try:
        for consumed, value in enumerate(prefetcher, start=1):
            assert value == consumed - 1
            time.sleep(0.002)  # consumer is the slow side
            # Queue bound 2 + one item in the producer's hand: the
            # producer may never run further ahead than that.
            assert len(produced_log) <= consumed + 2 + 1
    finally:
        prefetcher.close()
    assert prefetcher.consumed == 24


def test_prefetcher_close_mid_iteration_is_synchronous_drain():
    """Simulated churn: the worker loop dies mid-task; the finally-close
    must leave no producer thread and no observable stale batch."""

    def endless():
        i = 0
        while True:
            yield i
            i += 1

    prefetcher = Prefetcher(endless(), max_inflight=2)
    seen = []
    with pytest.raises(RuntimeError, match="simulated churn"):
        try:
            for value in prefetcher:
                seen.append(value)
                if len(seen) == 3:
                    raise RuntimeError("simulated churn")
        finally:
            prefetcher.close()
    assert seen == [0, 1, 2]
    assert not prefetcher._thread.is_alive()
    # After the drain the iterator is terminally finished — a stale
    # buffered batch can never surface in the next generation.
    assert next(iter(prefetcher), None) is None


def test_prefetcher_drain_then_fresh_generation_sees_fresh_data():
    """Checkpoint/rescale boundary: drain the old pipeline, build a new
    one for the re-formed world — the new generation must see exactly
    its own source from the start, nothing carried over."""
    first = Prefetcher(iter(range(100)), max_inflight=4)
    for _ in range(5):
        next(first)
    first.close()
    second = Prefetcher(iter(range(100, 108)), max_inflight=4)
    try:
        assert list(second) == list(range(100, 108))
    finally:
        second.close()


def test_prefetcher_propagates_source_exception_at_consume_point():
    def poisoned():
        yield 1
        yield 2
        raise OSError("read failed")

    prefetcher = Prefetcher(poisoned(), max_inflight=2)
    try:
        assert next(prefetcher) == 1
        assert next(prefetcher) == 2
        with pytest.raises(OSError, match="read failed"):
            next(prefetcher)
    finally:
        prefetcher.close()


def test_prefetcher_close_unblocks_stuck_producer():
    """close() while the producer is blocked on a full queue must not
    deadlock (the 0.05 s put poll re-checks the stop flag)."""
    prefetcher = Prefetcher(iter(range(1000)), max_inflight=1)
    time.sleep(0.02)  # let the producer fill the queue and block
    done = threading.Event()

    def closer():
        prefetcher.close()
        done.set()

    t = threading.Thread(target=closer)
    t.start()
    t.join(timeout=5.0)
    assert done.is_set(), "close() deadlocked against a blocked producer"


# ---------------------------------------------------------------------------
# StagingPipeline (overlap booking)
# ---------------------------------------------------------------------------


def test_staging_pipeline_books_stage_then_overlap():
    from elasticdl_tpu.obs.stepstats import StepAnatomy

    anatomy = StepAnatomy(worker_id=0)
    staging = StagingPipeline(anatomy, dispatch_depth=2)

    def fake_stage():
        time.sleep(0.002)
        return "staged"

    # Nothing outstanding: staging really serializes -> `stage` phase.
    assert staging.stage(fake_stage) == "staged"
    staging.note_dispatched()
    assert staging.outstanding == 1
    # A dispatch is in flight: the same staging call is hidden work.
    staging.stage(fake_stage)
    with anatomy.dispatch(1, 8):
        pass
    window = anatomy.close_window()
    assert window is not None
    assert window.get("stage", 0.0) > 0.0
    assert window.get("overlap_s", 0.0) > 0.0
    # Exclusive fractions still sum to 1 — overlap rides BESIDE them.
    from elasticdl_tpu.obs.stepstats import phase_fractions

    fractions = phase_fractions(anatomy.totals())
    assert "overlap_s" not in fractions
    assert abs(sum(fractions.values()) - 1.0) < 1e-6


def test_staging_pipeline_depth_cap_and_sync_reset():
    staging = StagingPipeline(anatomy=None, dispatch_depth=2)
    for _ in range(5):
        staging.note_dispatched()
    assert staging.outstanding == 2  # capped at dispatch_depth
    staging.note_synced()
    assert staging.outstanding == 0
    staging.note_dispatched()
    staging.drain()  # task/rendezvous boundary forgets in-flight state
    assert staging.outstanding == 0


# ---------------------------------------------------------------------------
# Shared pad-and-stage (serving reuse)
# ---------------------------------------------------------------------------


def test_bucket_helpers_are_shared_with_serving_batcher():
    from elasticdl_tpu.serving import batcher

    assert batcher.bucket_sizes is bucket_sizes
    assert batcher.bucket_for is bucket_for
    assert batcher.pad_features is pad_features
    assert bucket_sizes(8) == (1, 2, 4, 8)
    assert bucket_for(3, (1, 2, 4, 8)) == 4


def test_pad_and_stage_pads_to_bucket_and_stages():
    features = {"x": np.arange(6, dtype=np.float32).reshape(3, 2)}
    staged_calls = []

    def stage_fn(padded):
        staged_calls.append(padded)
        return ("on-device", padded)

    out, bucket = pad_and_stage(features, 3, bucket_sizes(8), stage_fn)
    assert bucket == 4
    assert out[0] == "on-device"
    padded = staged_calls[0]["x"]
    assert padded.shape == (4, 2)
    np.testing.assert_array_equal(padded[:3], features["x"])
    np.testing.assert_array_equal(padded[3:], 0.0)
    # Without a stage_fn the padded host batch comes back directly.
    out, bucket = pad_and_stage(features, 3, bucket_sizes(8))
    assert bucket == 4 and out["x"].shape == (4, 2)


# ---------------------------------------------------------------------------
# Config plumbing
# ---------------------------------------------------------------------------


def test_pipeline_config_from_parsed_args():
    from elasticdl_tpu.common.args import parse_worker_args

    args = parse_worker_args(
        [
            "--master_addr", "localhost:0",
            "--worker_id", "0",
            "--model_zoo", "model_zoo",
            "--model_def", "mnist.mnist_functional_api",
            "--pipeline", "async",
            "--parse_pool_workers", "3",
            "--pipeline_inflight", "5",
            "--dispatch_depth", "4",
        ]
    )
    config = PipelineConfig.from_args(args)
    assert config.is_async
    assert config.parse_workers == 3
    assert config.max_inflight == 5
    assert config.dispatch_depth == 4
    # Defaults: sync, no pool — the reference-parity serial loop.
    default = PipelineConfig()
    assert not default.is_async and default.parse_workers == 0
    with pytest.raises(ValueError):
        PipelineConfig(mode="turbo")


# ---------------------------------------------------------------------------
# Sync-vs-async equivalence (the acceptance gate)
# ---------------------------------------------------------------------------


def _local_losses(losses, pipeline_mode):
    from elasticdl_tpu.client import api
    from elasticdl_tpu.common.args import parse_master_args

    args = parse_master_args(
        [
            "--model_zoo", "model_zoo",
            "--model_def", "mnist.mnist_functional_api",
            "--distribution_strategy", "Local",
            "--training_data", "synthetic://mnist?n=320",
            "--records_per_task", "160",
            "--minibatch_size", "32",
            "--num_epochs", "1",
            "--pipeline", pipeline_mode,
            "--pipeline_inflight", "3",
        ]
    )
    del losses[:]
    assert api._run_local(args, mode="training") == 0
    return list(losses)


def test_async_pipeline_loss_curve_bit_identical_to_sync(dense_step_losses):
    """The pipeline moves host work in TIME, never in EFFECT: the same
    job through the async prefetch path must reproduce the sync loss
    sequence bit for bit on CPU."""
    sync_losses = _local_losses(dense_step_losses, "sync")
    async_losses = _local_losses(dense_step_losses, "async")
    assert len(sync_losses) == 10  # 320 records / 32 batch
    assert async_losses == sync_losses  # exact float equality, per step
