"""A task's share of a model's cumulative counters.

A layer that counts keeps cumulative counters in a mutable collection of
the model's state (`layers/moe.py`: ``routing``; `layers/loop_exits.py`:
``exits``; `layers/diffusion_noise.py`: ``noise``; `layers/delta_gates.py`:
``gates``), updated inside the step program.  The worker reads them where
it has already fetched the task's loss (no device sync of its own inside
a step) and journals the difference to the last reading as ONE span a
task.  A `TaskLedger` is that reading for one collection: `span` names
the span, `_read` fetches the counters, `_fields` turns two readings into
the span's fields, `refuse` says what in them must stop the job.
`task_ledgers()` is every ledger a model can have; one whose collection
the model lacks reads nothing and writes nothing.
"""

from __future__ import annotations


class TaskLedger:
    span: str  # the span's name, one of `obs/tracing.py` SPAN_NAMES

    def __init__(self):
        self._seen = None  # None: not seeded yet

    def _read(self, model_state) -> dict:
        """{counter: array} on the host, or {} where the model has none."""
        raise NotImplementedError

    def _fields(self, now: dict, seen: dict, steps: int) -> dict:
        raise NotImplementedError

    def refuse(self, fields: dict):
        """Why a task with these fields must not go on, or None."""
        return None

    def seed_once(self, model_state) -> None:
        """Before the first task: counters restored from a checkpoint are
        not this job's tasks' (no state yet: they will start at zero)."""
        if self._seen is None:
            self._seen = self._read(model_state or {})

    def task_delta(self, model_state, steps: int = 1):
        """-> the span's fields, or None for a model that counts nothing.
        `steps`: the task's steps (a mean over them divides by it)."""
        now = self._read(model_state)
        if not now:
            return None
        seen = self._seen or {key: 0 * value for key, value in now.items()}
        self._seen = now
        return self._fields(now, seen, steps)


def task_ledgers() -> list:
    """A fresh ledger of every kind, in the order their spans are
    written."""
    from elasticdl_tpu.layers.delta_gates import GateLedger
    from elasticdl_tpu.layers.diffusion_noise import NoiseLedger
    from elasticdl_tpu.layers.loop_exits import ExitLedger
    from elasticdl_tpu.layers.moe import RoutingLedger

    return [RoutingLedger(), ExitLedger(), NoiseLedger(), GateLedger()]
