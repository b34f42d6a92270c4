"""Columnar task materialization — the no-per-record-Python data path.

Parity: the reference's worker materializes each dynamic-sharding task as
a tf.data pipeline of per-record parses (†worker/worker.py task loop over
†data/reader/).  On a 1-core TPU host that per-record interpreter layer
caps the whole job: the device consumes ~1M samples/s (BASELINE.md) while
a Python `for record in task` loop tops out at a few hundred k/s.

This module keeps the task contract (same [task.start, task.end) range,
deterministic per (task, mode) on every rank — the lockstep requirement
of the collective worker) but carries the data as COLUMN arrays end to
end: readers that implement `read_columns(task)` hand back columnar
chunks straight from the file codec (e.g. ETRF parse_buffer output), the
model's `columnar_dataset_fn` transforms whole columns (vectorized
shuffle included), and batches are row-range VIEWS — zero per-record
work anywhere on the hot path.

Both layers are optional: a reader without `read_columns` or a model
without `columnar_dataset_fn` falls back to the per-record path
unchanged (reference-parity behaviour).
"""

from __future__ import annotations

import inspect
import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from elasticdl_tpu.obs import tracing

Tree = Any  # nested dict/tuple of np.ndarray, all sharing axis-0 length


def _tree_len(tree: Tree) -> int:
    if isinstance(tree, dict):
        return _tree_len(next(iter(tree.values())))
    if isinstance(tree, (tuple, list)):
        return _tree_len(tree[0])
    return len(tree)


def _tree_slice(tree: Tree, lo: int, hi: int) -> Tree:
    if isinstance(tree, dict):
        return {k: _tree_slice(v, lo, hi) for k, v in tree.items()}
    if isinstance(tree, (tuple, list)):
        return type(tree)(_tree_slice(v, lo, hi) for v in tree)
    return tree[lo:hi]


class ColumnarTask:
    """One task's records as (features_tree, labels_or_None), columnar."""

    def __init__(self, features: Tree, labels: Optional[np.ndarray]):
        self.features = features
        self.labels = labels
        self.n = _tree_len(features)
        if labels is not None and len(labels) != self.n:
            raise ValueError(
                f"labels length {len(labels)} != features length {self.n}"
            )

    def slice(self, lo: int, hi: int) -> Tuple[Tree, Optional[np.ndarray]]:
        """Row-range views [lo, hi) (no copies)."""
        return (
            _tree_slice(self.features, lo, hi),
            None if self.labels is None else self.labels[lo:hi],
        )


def materialize_columnar_task(
    reader,
    task,
    columnar_dataset_fn: Optional[Callable],
    mode: str,
    metadata,
    parse_pool=None,
) -> Optional[ColumnarTask]:
    """Build a ColumnarTask, or None when either side lacks the columnar
    surface (caller falls back to the per-record dataset path).  A
    `parse_pool` (data/pipeline.ParsePool) fans chunk parsing across
    host cores for readers that accept it — older readers without the
    parameter are called the classic way."""
    read_columns = getattr(reader, "read_columns", None)
    if read_columns is None or columnar_dataset_fn is None:
        return None
    read_start = time.monotonic()
    if (
        parse_pool is not None
        and "parse_pool" in inspect.signature(read_columns).parameters
    ):
        chunks = list(read_columns(task, parse_pool=parse_pool))
    else:
        chunks = list(read_columns(task))
    if not chunks:
        return None
    # `data.decode`: the task's chunks to one set of columns, then the
    # model's columnar transform (its permutation included).  What the
    # reader spent before it (index load, reads, the chunks' parse) is
    # `read_columns_s`; a record-file reader journals the first two as
    # `data.index_load` / `data.read`.
    with tracing.span(
        "data.decode",
        read_columns_s=round(time.monotonic() - read_start, 6),
    ) as span:
        columnar = _decode(chunks, task, columnar_dataset_fn, mode, metadata)
        span.fields["records"] = columnar.n
    return columnar


def _decode(chunks, task, columnar_dataset_fn, mode, metadata):
    if len(chunks) == 1:
        columns: Dict[str, np.ndarray] = chunks[0]
    else:
        columns = {
            k: np.concatenate([c[k] for c in chunks]) for k in chunks[0]
        }
    kwargs = {}
    if "seed" in inspect.signature(columnar_dataset_fn).parameters:
        # Task-identity-derived randomness for transforms that opt in
        # (shuffle order, image crop/flip): deterministic across ranks
        # (every rank sees identical task fields — lockstep collectives
        # require it) but VARIES across tasks and epochs — a fixed seed
        # would replay bit-identical augmentation every epoch.
        kwargs["seed"] = (
            1_000_003 * int(getattr(task, "epoch", 0))
            + 31 * int(getattr(task, "start", 0))
            + int(getattr(task, "end", 0))
        ) % (2**31)
    features, labels = columnar_dataset_fn(columns, mode, metadata, **kwargs)
    return ColumnarTask(features, labels)


def training_permutation(n: int, seed: int = 0) -> np.ndarray:
    """Deterministic full-range shuffle for columnar training transforms
    (the per-record path's buffered dataset.shuffle equivalent) — same
    permutation on every rank, which lockstep collectives require."""
    return np.random.RandomState(seed).permutation(n)
