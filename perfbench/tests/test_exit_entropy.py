"""`readers/exit_entropy.py` on a RECORDED journal: the `loop.exits` spans
of one traced run of `ouro.train-synth-8k` on a TPU v5e (PR 45, seed
3000004511: fifteen tasks of two steps, the window opening where step 6
ended), beside `test_named_scopes.py`'s case for `routing_load`.  No jax,
no chip; a journal without the span (a parent commit, a model without the
loop) reads as None.
"""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from lib import load_module  # noqa: E402

T0 = 1791021808.9  # just after the acknowledgement of warm-up's last task


def recorded():
    with open(os.path.join(HERE, "data", "ouro_loop_exits.jsonl")) as f:
        return [json.loads(line) for line in f]


def read(worker, t0=T0, seconds=30.0):
    with open(os.path.join(BENCH, "metrics", "exit_entropy.lm.json")) as f:
        decl = json.load(f)
    reader = load_module(
        os.path.join(BENCH, "readers", decl["reader"] + ".py")
    )
    run = types.SimpleNamespace(worker=worker, t0=t0, t1=t0 + seconds)
    return reader.read(run, **decl.get("args", {}))


def test_the_windows_median_entropy_of_a_recorded_run():
    spans = recorded()
    assert len(spans) == 15 and spans[0]["step"] == 2
    # a zero gate's distribution at the first task, to the span's digits
    first = [spans[0][f"p_exit_{r}"] for r in (1, 2, 3, 4)]
    assert first == pytest.approx([0.5, 0.25, 0.125, 0.125], abs=1e-4)
    assert spans[0]["entropy"] == pytest.approx(1.2130, abs=1e-4)
    for span in spans:
        assert sum(span[f"p_exit_{r}"] for r in (1, 2, 3, 4)) == (
            pytest.approx(1.0, abs=1e-5))
        assert span["tokens"] == 2 * 8192
    # the eleven tasks acknowledged inside the window: warm-up's three
    # (steps 2-6) and the one that ended past it (step 30) are not read
    inside = sorted(s["entropy"] for s in spans if 6 < s["step"] < 30)
    assert len(inside) == 11
    assert read(spans) == inside[5]
    assert 1.2130 < read(spans) < 1.2180
    # a window that ends before the first task does: nothing
    assert read(spans, seconds=1.0) is None


def test_nothing_to_read_without_the_span():
    routing = {"event": "span", "name": "moe.routing", "ts": T0 + 1.0,
               "step": 8, "steps": 2, "pairs": 10}
    assert read([routing]) is None
    assert read([]) is None
