"""The exits of a stack that is applied several times over shared weights.

After each of its R passes such a model has an exit: the head's logits at
that pass's state and a gate `lambda_r = sigmoid(g_r)`, the probability of
leaving there having come that far.  The EXIT DISTRIBUTION of a token::

    p_1 = lambda_1
    p_r = lambda_r prod_{j<r} (1 - lambda_j)        1 < r < R
    p_R = prod_{j<R} (1 - lambda_j)                 what remains

sums to 1 whatever the gates are (`exit_log_probs`, in logarithms: a
product of R small numbers is a sum here; the last pass's gate value is
read by nothing).  Its entropy `H(p) = -sum_r p_r ln p_r` is what a
training objective rewards so that the distribution does not collapse
onto one exit (`exit_entropy`).

Counters (the ``exits`` collection, cumulative float32, updated only where
the collection is mutable, i.e. in training; `count_exits`): the sum over
tokens of each `p_r`, the sum of `H(p)` and the tokens counted.  The
worker journals their per-task means as ``loop.exits`` (`ExitLedger`).  A
float32 sum resolves 2^-24 of its total, so a task's difference is good
to one part in a thousand for the first ~16,000 tasks of its size and
coarser after: what a job restored from a checkpoint keeps counting from.
Rows the trainer pads a minibatch with are counted with the rest (their
loss is masked; the model cannot tell them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax.traverse_util import flatten_dict

from elasticdl_tpu.layers.ledger import TaskLedger

EXITS_COLLECTION = "exits"


def exit_log_probs(gate):
    """gate [..., R, T], the gates' values BEFORE the sigmoid, float32 ->
    ln p [..., R, T] of the exit distribution."""
    stay = jax.nn.log_sigmoid(-gate)               # ln (1 - lambda_r)
    came = jnp.concatenate(                        # sum over j < r
        [jnp.zeros_like(stay[..., :1, :]),
         jnp.cumsum(stay[..., :-1, :], axis=-2)], axis=-2,
    )
    leave = jnp.concatenate(                       # the last takes the rest
        [jax.nn.log_sigmoid(gate[..., :-1, :]),
         jnp.zeros_like(gate[..., :1, :])], axis=-2,
    )
    return came + leave


def exit_entropy(log_probs):
    """ln p [..., R, T] -> H(p) [..., T], in nats (at most ln R)."""
    return -jnp.sum(jnp.exp(log_probs) * log_probs, axis=-2)


def count_exits(module, log_probs, entropy) -> None:
    """Add a step's tokens to `module`'s ``exits`` counters: ln p
    [B, R, T] and H(p) [B, T] of that step."""
    if not module.is_mutable_collection(EXITS_COLLECTION):
        return  # evaluation, serving: nothing is read, nothing counted
    passes = log_probs.shape[-2]
    zeros = lambda *shape: jnp.zeros(shape, jnp.float32)  # noqa: E731
    p_sum = module.variable(EXITS_COLLECTION, "p_sum", zeros, passes)
    entropy_sum = module.variable(EXITS_COLLECTION, "entropy_sum", zeros)
    tokens = module.variable(EXITS_COLLECTION, "tokens", zeros)
    if module.is_initializing():
        return
    p = jax.lax.stop_gradient(jnp.exp(log_probs))
    p_sum.value = p_sum.value + jnp.sum(p, axis=(0, 2))
    entropy_sum.value = entropy_sum.value + jnp.sum(
        jax.lax.stop_gradient(entropy)
    )
    tokens.value = tokens.value + np.float32(entropy.size)


class ExitLedger(TaskLedger):
    """``loop.exits``: the task's mean exit distribution (`p_exit_1` ..
    `p_exit_R`) and mean entropy over the `tokens` its steps counted."""

    span = "loop.exits"

    def _read(self, model_state) -> dict:
        # one module of a model counts; its counters by their own names
        flat = flatten_dict(dict(model_state.get(EXITS_COLLECTION, {})))
        return {
            path[-1]: np.asarray(value, np.float64)
            for path, value in jax.device_get(flat).items()
        }

    def _fields(self, now, seen, steps):
        tokens = float(now["tokens"] - seen["tokens"])
        share = (now["p_sum"] - seen["p_sum"]) / max(tokens, 1.0)
        fields = {"tokens": int(tokens)}
        fields.update(
            (f"p_exit_{r + 1}", float(p)) for r, p in enumerate(share)
        )
        fields["entropy"] = float(
            (now["entropy_sum"] - seen["entropy_sum"]) / max(tokens, 1.0)
        )
        return fields
