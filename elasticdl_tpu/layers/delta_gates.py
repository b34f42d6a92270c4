"""The gates of a delta rule whose decay is one rate a key channel,
counted.

Such a layer (`model_zoo/ling` `KimiDeltaAttention`) multiplies each row
of its state by `exp(g)` a token, `g` bounded below (`ops/gdn_passes.py`
`decay_gate`), and writes with a strength `beta`.  How much of the state a
token keeps says whether the scan carries anything at all: a retention at
`exp(bound)` everywhere is a layer that forgets a token after one step,
and a gate that sits at its bound has no gradient left to leave it.

Counters (the ``gates`` collection, one set a layer, cumulative, updated
only where the collection is mutable, i.e. in training; `count_gates`): the
steps counted, the sum over them of a step's mean `exp(g)` and mean `beta`
(float32: a mean a step, so that no sum runs over 10^7 values), the gate
values seen and how many of them lay within 1% of the bound (uint32:
differences are right across a wrap).  The worker journals their per-task
differences as ``kda.gates`` (`GateLedger`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax.traverse_util import flatten_dict

from elasticdl_tpu.layers.ledger import TaskLedger

GATES_COLLECTION = "gates"


def count_gates(module, g, beta, bound: float) -> None:
    """Add a step to `module`'s ``gates`` counters: the log-decays `g`
    (any shape, in (bound, 0)) and the write strengths `beta`."""
    if not module.is_mutable_collection(GATES_COLLECTION):
        return  # evaluation, serving: nothing is read, nothing counted

    def counter(name, dtype):
        return module.variable(
            GATES_COLLECTION, name, lambda: jnp.zeros((), dtype)
        )

    steps = counter("steps", jnp.uint32)
    retention = counter("retention", jnp.float32)
    write = counter("beta", jnp.float32)
    values = counter("values", jnp.uint32)
    at_bound = counter("at_bound", jnp.uint32)
    if module.is_initializing():
        return
    g, beta = jax.lax.stop_gradient((g, beta))
    steps.value = steps.value + np.uint32(1)
    retention.value = retention.value + jnp.mean(jnp.exp(g))
    write.value = write.value + jnp.mean(beta)
    values.value = values.value + np.uint32(g.size)
    at_bound.value = at_bound.value + jnp.sum(
        g < 0.99 * bound, dtype=jnp.uint32
    )


class GateLedger(TaskLedger):
    """``kda.gates``: over the `layers` that count, a task's mean
    `retention` exp(g), mean `beta`, and the share of gate values within
    1% of the bound (`at_bound_share`)."""

    span = "kda.gates"

    def _read(self, model_state) -> dict:
        """{counter: [layers]} of every counting layer's counters."""
        flat = flatten_dict(dict(model_state.get(GATES_COLLECTION, {})))
        if not flat:
            return {}
        flat = jax.device_get(flat)
        layers = sorted({path[:-1] for path in flat})
        return {
            key: np.stack([np.asarray(flat[layer + (key,)]) for layer in layers])
            for key in ("steps", "retention", "beta", "values", "at_bound")
        }

    def _fields(self, now, seen, steps):
        counted, values, at_bound = (
            (now[key] - seen[key]).astype(np.uint32).astype(np.float64)
            for key in ("steps", "values", "at_bound")
        )
        counted = np.maximum(counted, 1.0)
        return {
            "layers": int(now["steps"].shape[0]),
            "retention": float(np.mean(
                (now["retention"] - seen["retention"]) / counted
            )),
            "beta": float(np.mean((now["beta"] - seen["beta"]) / counted)),
            "at_bound_share": float(at_bound.sum() / max(values.sum(), 1.0)),
        }
