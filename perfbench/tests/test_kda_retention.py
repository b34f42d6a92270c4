"""`readers/kda_retention.py` on a RECORDED journal: the `kda.gates` spans
of one untraced run of `ling3-flash-vl.train-synth-8k` on a TPU v5e (PR 53,
seed 3000005307 at a cadence of 14: sixteen tasks of two steps, the window
opening where step 6 ended), beside `test_masked_share.py`'s case.  No
jax, no chip; a journal without the span (a parent commit, a model without
such layers) reads as None.
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

T0 = 1791179099.698403  # the acknowledgement of warm-up's last task


def recorded():
    with open(os.path.join(HERE, "data", "ling_kda_gates.jsonl")) as f:
        return [json.loads(line) for line in f]


def read(worker, t0=T0, seconds=30.0):
    with open(os.path.join(BENCH, "metrics", "kda_retention.lm.json")) as f:
        decl = json.load(f)
    reader = load_module(
        os.path.join(BENCH, "readers", decl["reader"] + ".py")
    )
    run = types.SimpleNamespace(worker=worker, t0=t0, t1=t0 + seconds)
    return reader.read(run, **decl.get("args", {}))


def test_the_windows_retention_of_a_recorded_run():
    spans = recorded()
    assert len(spans) == 16 and spans[0]["step"] == 2
    for span in spans:
        assert span["steps"] == 2 and span["layers"] == 6
        # seeded near full retention, far above exp(-5), the bound's
        assert 0.0067 < span["retention"] < 1.0
        assert 0.0 < span["beta"] < 1.0
        assert 0.0 <= span["at_bound_share"] < 0.01
    # the twelve tasks acknowledged inside the window: warm-up's three
    # (steps 2-6) and the one that ended past it (step 32) are not read
    inside = [s for s in spans if 6 < s["step"] < 32]
    assert len(inside) == 12
    mean = sum(s["retention"] for s in inside) / 12
    assert read(spans) == pytest.approx(100.0 * mean)
    assert read(spans) == pytest.approx(99.25014, abs=1e-4)
    # a window that ends before the first task does: nothing
    assert read(spans, seconds=1.0) is None


def test_nothing_to_read_without_the_span():
    routing = {"event": "span", "name": "moe.routing", "ts": T0 + 1.0,
               "step": 8, "steps": 2, "pairs": 10}
    assert read([routing]) is None
    assert read([]) is None
