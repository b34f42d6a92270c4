"""The noise of a masked-diffusion training step, counted.

A model trained by masked (absorbing-state) diffusion sees each record
with some of its tokens replaced by a mask token: a noise level `t` in
(0, 1] a sequence and a mask `m`, each position masked with probability
`t`.  Here the noise is part of the record's FEATURES (the stack's
`dataset_fn` draws it on the host, keyed by the record: `model_zoo/sdar`),
so the step stays a pure function of what the task's records hold, and a
task re-run by another worker after a kill trains on the same noise.

Counters (the ``noise`` collection, cumulative, updated only where the
collection is mutable, i.e. in training; `count_noise`): the sequences and
tokens a step saw and the positions it masked (uint32: differences are
right across a wrap), and the sum of the sequences' `t` (float32).  The
worker journals their per-task differences as ``diffusion.noise``
(`NoiseLedger`).  Rows the trainer pads a minibatch with are counted with
the rest (their loss is masked; the model cannot tell them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax.traverse_util import flatten_dict

from elasticdl_tpu.layers.ledger import TaskLedger

NOISE_COLLECTION = "noise"


def count_noise(module, mask, t) -> None:
    """Add a step's records to `module`'s ``noise`` counters: `mask`
    [B, T] (true where a position was masked) and `t` [B]."""
    if not module.is_mutable_collection(NOISE_COLLECTION):
        return  # evaluation, serving: nothing is read, nothing counted

    def counter(name, dtype):
        return module.variable(
            NOISE_COLLECTION, name, lambda: jnp.zeros((), dtype)
        )

    sequences = counter("sequences", jnp.uint32)
    tokens = counter("tokens", jnp.uint32)
    masked = counter("masked", jnp.uint32)
    t_sum = counter("t_sum", jnp.float32)
    if module.is_initializing():
        return
    sequences.value = sequences.value + np.uint32(mask.shape[0])
    tokens.value = tokens.value + np.uint32(mask.size)
    masked.value = masked.value + jnp.sum(mask, dtype=jnp.uint32)
    t_sum.value = t_sum.value + jnp.sum(t.astype(jnp.float32))


class NoiseLedger(TaskLedger):
    """``diffusion.noise``: the `tokens` a task's steps saw, how many of
    them were `masked`, and the mean noise level `t_mean` of its
    sequences."""

    span = "diffusion.noise"

    def _read(self, model_state) -> dict:
        # one module of a model counts; its counters by their own names
        flat = flatten_dict(dict(model_state.get(NOISE_COLLECTION, {})))
        return {
            path[-1]: np.asarray(value)
            for path, value in jax.device_get(flat).items()
        }

    def _fields(self, now, seen, steps):
        sequences, tokens, masked = (
            int((now[key] - seen[key]).astype(np.uint32))
            for key in ("sequences", "tokens", "masked")
        )
        return {
            "tokens": tokens,
            "masked": masked,
            "t_mean": float(now["t_sum"] - seen["t_sum"]) / max(sequences, 1),
        }
