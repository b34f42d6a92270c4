"""The readers PR 26 adds: device time under a scope given as an argument
(`lib/named_scopes.py`, `readers/named_scope_ms.py`), a scope's roofline
share from the program's COUNTED work (`readers/scope_roofline.py`) and
the expert load from `moe.routing` spans (`readers/routing_load.py`), on
made-up traces and journals.  No jax, no chip; and nothing to read from a
program without the scopes or the spans (a parent commit) reads as None.
"""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from lib import load_module, named_scopes, xscope  # noqa: E402

PEAKS = {"flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}


def reader(name):
    return load_module(os.path.join(BENCH, "readers", name + ".py"))


def metric(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        decl = json.load(f)
    return reader(decl["reader"]), decl.get("args", {})


def trace(scopes):
    """Two programs of 400 us; op i of each runs 100 us under scopes[i]."""
    ops, programs = [], []
    for base in (0, 1_000_000):
        programs.append(["jit_f", base, 400_000, -1])
        for i in range(len(scopes)):
            ops.append([f"%fusion.{i} = f32[8] fusion(..)",
                        base + 100_000 * i, 100_000, i])
    return {"scopes": list(scopes), "planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": programs}]},
        {"name": "/host:CPU", "lines": []},
    ]}


def run_on(tmp_path, scopes, worker=(), steps=4, reference=None):
    plain = trace(scopes)
    with open(tmp_path / "xscope.json", "w") as f:
        json.dump(plain, f)
    run = types.SimpleNamespace(
        work=str(tmp_path), trace={"programs": 2}, trace_steps=steps,
        worker=list(worker), chips=1, model={}, reference=reference,
        peaks=lambda: PEAKS, flag_int=lambda name: 2, faults=[],
        t0=0.0, t1=100.0,
    )
    run._xscope = xscope.reduce(plain)  # what `xscope.for_run` would give
    return run


HYBRID = (
    "jit(w)/while/body/fwd_bwd/jvp(M)/layers_0/gdn/linear_attn/gdn_scan/dot:",
    "jit(w)/while/body/fwd_bwd/transpose(jvp(M))/layers_0/gdn/linear_attn/mul:",
    "jit(w)/while/body/fwd_bwd/jvp(M)/layers_0/moe/mlp/moe_experts/while/body/dot:",
    "jit(w)/while/body/optimizer/add:",
)


def test_time_under_a_scope_given_as_an_argument(tmp_path):
    run = run_on(tmp_path, HYBRID)
    assert named_scopes.under_s(run, "gdn") == pytest.approx(400e-6)
    assert named_scopes.under_s(run, "gdn_scan") == pytest.approx(200e-6)
    assert named_scopes.under_s(run, "moe_experts") == pytest.approx(200e-6)
    # a module or a longer scope that merely contains the name is not it
    assert named_scopes.under_s(run, "attn") is None
    assert named_scopes.under_s(run, "scan") is None
    read, args = metric("gdn_scan_ms.lm")
    assert read.read(run, **args) == pytest.approx(0.05)  # 200 us / 4 steps
    read, args = metric("moe_route_ms.lm")
    assert read.read(run, **args) is None  # no op carries it


def test_nothing_to_read_from_a_program_without_the_scopes(tmp_path):
    bare = run_on(tmp_path, ("jit(w)/dot:", "jit(w)/copy:"))
    bare._xscope = None  # `for_run` of a trace whose ops carry no scope
    for name in ("gdn_ms.lm", "gdn_scan_roofline.lm", "moe_experts_roofline.lm"):
        read, args = metric(name)
        assert read.read(bare, **args) is None
    untraced = types.SimpleNamespace(work=str(tmp_path), trace=None)
    assert named_scopes.under_s(untraced, "gdn") is None


def routing(step, pairs, load_max=30, load_mean=20.0, dropped=0, ts=10.0):
    return {"event": "span", "name": "moe.routing", "ts": ts, "step": step,
            "steps": 2, "pairs": pairs, "dropped": dropped,
            "load_max": load_max, "load_mean": load_mean}


def test_roofline_share_uses_the_counted_pairs_of_the_traced_tasks(tmp_path):
    reference = types.SimpleNamespace(
        gdn_scan_cost=lambda model, minibatch: {"flops": 1e9, "bytes": 1e7},
        moe_experts_cost=lambda model, pairs, steps: {
            "flops": 1e5 * pairs, "bytes": 10.0 * steps},
    )
    worker = [
        {"event": "profile_window", "action": "open", "step_start": 14},
        routing(14, 999), routing(16, 100), routing(18, 300), routing(20, 999),
    ]
    run = run_on(tmp_path, HYBRID, worker, steps=4, reference=reference)
    read, args = metric("gdn_scan_roofline.lm")
    # 4 steps x 1e9 FLOP / 100e12 = 40 us of 200 us (bytes: 0.04 us)
    assert read.read(run, **args) == pytest.approx(20.0)
    read, args = metric("moe_experts_roofline.lm")
    # the tasks that ended at steps 16 and 18: 400 pairs -> 4e7 FLOP -> 0.4 us
    assert read.read(run, **args) == pytest.approx(0.2)
    # traced programs that are not whole tasks: nothing, not a guess
    run.trace_steps = 3
    assert read.read(run, **args) is None


def test_expert_load_and_a_dropped_pair(tmp_path):
    read, _ = metric("expert_load_max_over_mean.lm")
    run = run_on(tmp_path, HYBRID, [
        routing(2, 10, 30, 20.0), routing(4, 10, 50, 20.0),
        routing(6, 10, 44, 20.0), routing(8, 10, 99, 20.0, ts=200.0),
    ])
    assert read.read(run) == pytest.approx(2.2)  # median of 1.5, 2.5, 2.2
    assert run.faults == []
    run.worker.append(routing(10, 10, dropped=3))
    read.read(run)
    assert run.faults == ["3 routed pair(s) dropped in the window"]
    assert read.read(run_on(tmp_path, HYBRID)) is None  # no such span
