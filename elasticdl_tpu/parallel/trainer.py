"""What the trainers share: the dense state, the model-apply helpers, and
the surface the worker loop drives.

`TrainState` is the dense trainer's state and the tree a `state.pkl`
pickles by reference (checkpoint/saver.py resolves the name this class
had before it lived here).  `Trainer` lists what
worker/collective_worker.py calls on whichever trainer it was given;
`DataParallelTrainer` and `ShardedEmbeddingTrainer` both provide all of
it, so the loop never asks a trainer what kind it is.
"""

from __future__ import annotations

import inspect
from typing import Any, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp


class TrainState(NamedTuple):
    step: jnp.ndarray
    params: Any
    opt_state: Any
    model_state: Any  # non-trainable collections, e.g. batch_stats


def unbox_partitioned(tree):
    """Strip flax partitioning metadata boxes (the trainers place state
    from their own rule tables, parallel/compile.py)."""
    import flax.linen as nn

    return jax.tree.map(
        lambda x: x.unbox() if isinstance(x, nn.Partitioned) else x,
        tree,
        is_leaf=lambda x: isinstance(x, nn.Partitioned),
    )


def model_apply(model, variables, features, train: bool, mutable):
    """Call a flax module, passing `train` only if the model accepts it."""
    call_params = inspect.signature(model.__call__).parameters
    kwargs = {}
    if "train" in call_params:
        kwargs["train"] = train
    if mutable:
        return model.apply(variables, features, mutable=mutable, **kwargs)
    return model.apply(variables, features, **kwargs), {}


@runtime_checkable
class Trainer(Protocol):
    """The trainer as the worker loop sees it.  Batches are this
    process's equal-size slice of the global batch, already padded to
    `local_block` rows; every process of the world calls in lockstep."""

    #: Train steps between sparse-table applies (1: every step).  Settled
    #: by the end of `ensure_initialized`; the loop sizes its dispatch
    #: window to a multiple of it.
    apply_every: int

    #: The programs that cut the state's large leaves into pieces for a
    #: streamed save (`checkpoint/saver.py` `LeafCutter`); None until
    #: the step programs are built.
    leaf_cutter: Any

    @property
    def state(self) -> Any: ...

    @state.setter
    def state(self, value: Any) -> None: ...

    @property
    def step(self) -> int: ...

    @property
    def mesh(self) -> Any: ...

    def local_block(self, per_rank_batch: int) -> int: ...

    def jitted_entrypoints(self) -> dict: ...

    def ensure_initialized(self, features) -> Any: ...

    def stage_batch(self, features, labels, mask) -> Any: ...

    def train_step_staged(self, staged) -> Any: ...

    def stage_window(self, batches) -> Any: ...

    def train_window(self, window) -> Any: ...

    def train_step_local(self, features, labels, mask) -> Any: ...

    def eval_step_local(self, features) -> Any: ...

    def consume_oov_count(self) -> int: ...

    def state_to_host(self) -> Any: ...

    def save_checkpoint(self, saver, step: int) -> None: ...

    def set_sharded_restore(self, saver, step: int) -> None: ...

    def get_variables_numpy(self) -> dict: ...
