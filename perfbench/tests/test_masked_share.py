"""`readers/masked_share.py` on a RECORDED journal: the `diffusion.noise`
spans of one traced run of `sdar.train-synth-8k` on a TPU v5e (PR 51, seed
3000005101: eleven tasks of two steps, the window opening where step 6
ended), beside `test_exit_entropy.py`'s case.  No jax, no chip; a journal
without the span (a parent commit, a model trained another way) reads as
None.
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

T0 = 1791135094.45  # just after the acknowledgement of warm-up's last task


def recorded():
    with open(os.path.join(HERE, "data", "sdar_diffusion_noise.jsonl")) as f:
        return [json.loads(line) for line in f]


def read(worker, t0=T0, seconds=30.0):
    with open(os.path.join(BENCH, "metrics", "masked_share.lm.json")) as f:
        decl = json.load(f)
    reader = load_module(
        os.path.join(BENCH, "readers", decl["reader"] + ".py")
    )
    run = types.SimpleNamespace(worker=worker, t0=t0, t1=t0 + seconds)
    return reader.read(run, **decl.get("args", {}))


def test_the_windows_masked_share_of_a_recorded_run():
    spans = recorded()
    assert len(spans) == 11 and spans[0]["step"] == 2
    for span in spans:
        assert span["tokens"] == 2 * 8192 and span["steps"] == 2
        assert 0 < span["masked"] < span["tokens"]
        # a task is two records: its masked share follows their mean t
        assert abs(span["masked"] / span["tokens"] - span["t_mean"]) < 0.02
        assert 1e-3 < span["t_mean"] <= 1.0
    # the seven tasks acknowledged inside the window: warm-up's three
    # (steps 2-6) and the one that ended past it (step 22) are not read
    inside = [s for s in spans if 6 < s["step"] < 22]
    assert len(inside) == 7
    masked = sum(s["masked"] for s in inside)
    assert read(spans) == pytest.approx(100.0 * masked / (7 * 16384))
    assert read(spans) == pytest.approx(52.837, abs=1e-3)
    # a window that ends before the first task does: nothing
    assert read(spans, seconds=1.0) is None


def test_nothing_to_read_without_the_span():
    routing = {"event": "span", "name": "moe.routing", "ts": T0 + 1.0,
               "step": 8, "steps": 2, "pairs": 10}
    assert read([routing]) is None
    assert read([]) is None
