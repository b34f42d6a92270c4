"""The scope and span reduction (`lib/xscope.py`) and the readers that
use it, on a small plain-form trace recorded on the chip (my chip run,
PR 24: three executions of a scoped toy program on one TPU v5e, with the
program's `TraceAnnotation`s on the host plane) and on made-up traces
and journals.  No jax, no chip.
"""

import gzip
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from lib import load_module, xplane, xscope  # noqa: E402


def recorded():
    with gzip.open(os.path.join(HERE, "data", "scoped_trace.json.gz"), "rt") as f:
        return json.load(f)


def reader(name):
    return load_module(os.path.join(BENCH, "readers", name + ".py"))


def metric(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        decl = json.load(f)
    return reader(decl["reader"]), decl.get("args", {})


def test_known_scopes_outermost_first():
    assert xscope.known_scopes(
        "jit(f)/while/body/closed_call/fwd_bwd/jvp(TransformerLM)/block_3/attn/attn/dot_general:"
    ) == ["fwd_bwd", "attn", "attn"]
    assert xscope.known_scopes(
        "jit(f)/fwd_bwd/jvp(vmap(lm_head_loss))/exp;jit(f)/optimizer/mul"
    ) == ["fwd_bwd", "lm_head_loss"]
    assert xscope.known_scopes(
        "jit(f)/sparse_apply/sparse_adam/grad_accumulate/scatter-add:"
    ) == ["sparse_apply", "sparse_adam", "grad_accumulate"]
    # a module named like a scope's prefix is not the scope
    assert xscope.known_scopes("jit(f)/attn_proj/optimizer_state.mul") == []


def test_recorded_trace_partitions_busy_time_and_names_the_idle_time():
    trace = recorded()
    got = xscope.reduce(trace)
    busy = xplane.reduce(xscope._three(trace))
    assert got["programs"] == busy["programs"] == 3
    assert got["window_s"] == pytest.approx(busy["window_s"])
    # the partition by innermost scope adds up to the busy time
    assert sum(got["by_scope"].values()) == pytest.approx(
        busy["busy_s"], rel=0.02)
    assert got["by_scope"]["attn"] == pytest.approx(272.7e-6, rel=0.01)
    assert got["by_scope"]["mlp"] == pytest.approx(269.7e-6, rel=0.01)
    # `sparse_apply` holds its `grad_accumulate`
    assert got["under"]["sparse_apply"] == pytest.approx(
        got["under"]["grad_accumulate"])
    assert got["under"]["fwd_bwd"] == pytest.approx(
        got["by_scope"]["attn"] + got["by_scope"]["mlp"], rel=0.01)
    # the device idles while the host sleeps inside `step.data_wait`
    names = [name for name, _ in got["spans"]]
    assert names[:2] == ["step.data_wait", "step.dispatch"]
    assert got["idle_named_s"] / got["idle_s"] > 0.99


def made_up(spans=(), scopes=("jit(f)/fwd_bwd/dot:", "jit(f)/copy:")):
    ops = [
        ["%fusion.1 = f32[8] fusion(..)", 0, 100_000, 0],
        ["%copy.2", 300_000, 100_000, 1],
        ["%fusion.1 = f32[8] fusion(..)", 1_000_000, 100_000, 0],
        ["%copy.2", 1_300_000, 100_000, 1],
    ]
    programs = [["jit_f", 0, 400_000, -1], ["jit_f", 1_000_000, 400_000, -1]]
    host = [[name, start, dur, -1] for name, start, dur in spans]
    return {"scopes": list(scopes), "planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": programs}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
    ]}


def test_idle_time_is_named_only_by_program_spans_not_the_task_wrapper():
    got = xscope.reduce(made_up(spans=[
        ("worker.task", 0, 2_000_000),
        ("step.data_wait", 400_000, 300_000),      # half of the long gap
        ("PjitFunction(f)", 100_000, 1_000_000),   # not a program span
    ]))
    assert got["idle_s"] == pytest.approx(1000e-6)  # 200 + 600 + 200 us
    assert got["idle_named_s"] == pytest.approx(300e-6)
    assert got["by_scope"] == {
        "fwd_bwd": pytest.approx(200e-6), "unscoped": pytest.approx(200e-6)}


def run_with(reduced, steps=2):
    run = types.SimpleNamespace(trace={"programs": 2}, trace_steps=steps)
    run._xscope = reduced
    return run


def test_scope_ms_and_idle_named_share_read_nothing_from_a_bare_program():
    scope_ms, args = metric("sparse_apply_ms")
    idle, _ = metric("idle_named_share")
    bare = xscope.reduce(made_up(scopes=("jit(f)/dot:", "jit(f)/copy:")))
    assert bare["scoped_ops"] == 0 and bare["spans"] == []
    assert scope_ms.read(run_with(bare), **args) is None
    assert idle.read(run_with(bare)) is None
    assert idle.read(run_with(None)) is None
    scoped = xscope.reduce(made_up(
        spans=[("step.data_wait", 400_000, 600_000)],
        scopes=("jit(f)/sparse_apply/sparse_adam/mul:", "jit(f)/copy:")))
    assert scope_ms.read(run_with(scoped), **args) == pytest.approx(0.1)
    assert idle.read(run_with(scoped)) == pytest.approx(60.0)
    attn, attn_args = metric("attn_ms.lm")
    assert attn.read(run_with(scoped), **attn_args) == 0.0


# -- the journal readers, on lines of a journal recorded on the chip ---------


def journal_lines(name):
    with gzip.open(os.path.join(HERE, "data", name), "rt") as f:
        return [json.loads(line) for line in f]


def test_readers_on_a_recorded_worker_and_master_journal():
    worker = journal_lines("deepfm_worker_spans.jsonl.gz")
    master = journal_lines("deepfm_master_spans.jsonl.gz")
    save = next(e for e in worker if e.get("name") == "checkpoint.save")
    t0 = save["start_ts"] - 10.0
    run = types.SimpleNamespace(worker=worker, master=master, t0=t0,
                                t1=t0 + 30.0)
    parts = {}
    for part in ("gather", "write", "crc"):
        r, args = metric(f"save_{part}_s")
        parts[part] = r.read(run, **args)
        assert parts[part] > 0
    # the three parts are the save, but for its rename
    assert sum(parts.values()) == pytest.approx(save["duration_s"], rel=0.05)
    share, args = metric("index_load_share")
    assert 0 < share.read(run, **args) < 100
    amp, args = metric("read_amplification")
    # every task re-reads the 13,107,200-record file's whole index
    # (8 B a record) for its 65,536 records of 157 B
    assert amp.read(run, **args) == pytest.approx(
        (104857600 + 10289152) / 10289152, rel=1e-6)
    # a window that closed before the save started has none of its parts
    early = types.SimpleNamespace(worker=worker, master=master,
                                  t0=t0 - 100, t1=t0 - 70)
    r, args = metric("save_write_s")
    assert r.read(early, **args) is None
    # set-up's parts end before the window opens
    late = types.SimpleNamespace(worker=worker, master=master,
                                 t0=save["start_ts"], t1=save["start_ts"] + 30)
    for name in ("proc_start_s", "master_boot_s", "backend_init_s",
                 "state_init_s"):
        r, args = metric(name)
        assert r.read(late, **args) > 0, name
    boot, args = metric("master_boot_s")
    assert boot.read(late, **args) == pytest.approx(sum(
        e["duration_s"] for e in master if e.get("name") in args["spans"]))


def test_readers_read_nothing_from_a_program_without_the_spans():
    bare = types.SimpleNamespace(
        worker=[{"ts": 5.0, "event": "span", "name": "checkpoint.save",
                 "start_ts": 4.0, "duration_s": 1.0}],
        master=[], t0=0.0, t1=30.0)
    for name in ("index_load_share", "read_amplification", "save_gather_s",
                 "save_write_s.lm", "save_crc_s", "proc_start_s",
                 "master_boot_s", "backend_init_s", "state_init_s"):
        r, args = metric(name)
        assert r.read(bare, **args) is None, name


def test_every_new_metric_is_declared_with_a_reader_file():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    for entry in bench["per_layer"]:
        r, _ = metric(entry["name"])
        assert callable(r.read)
        assert set(entry.get("workloads", cells)) <= cells
