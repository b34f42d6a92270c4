"""The benchmark's readers of the start-up chain (ISSUE 34), loaded by
path and held on hand-written journals: `perfbench/readers/setup_chain.py`
(what of `setup_s` lies inside a named interval, and the longest stretch
that does not) and `span_field_s.py` (a field of named spans, summed).
"""

import importlib.util
import json
import os
import sys
import types

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO_ROOT, "perfbench")


@pytest.fixture(scope="module")
def readers():
    """(setup_chain, span_field_s); `span_field_s` imports the benchmark's
    `lib` as the harness does, from `perfbench/`."""
    sys.path.insert(0, BENCH)
    try:
        loaded = []
        for name in ("setup_chain", "span_field_s"):
            spec = importlib.util.spec_from_file_location(
                "perfbench_" + name,
                os.path.join(BENCH, "readers", name + ".py"),
            )
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            loaded.append(module)
        return loaded
    finally:
        sys.path.remove(BENCH)
        for name in [m for m in sys.modules if m.split(".")[0] == "lib"]:
            del sys.modules[name]


with open(os.path.join(BENCH, "metrics", "setup_named_share.json")) as f:
    CHAIN_ARGS = {
        k: v for k, v in json.load(f)["args"].items() if k != "part"
    }

T0 = 1100.0  # the acknowledgement that ends warm-up
SETUP_S = 100.0  # so the harness started at 1000


def span(name, start, length, proc="master", incarnation="a", n=1, **fields):
    return {
        "event": "span", "name": name, "start_ts": 1000.0 + start,
        "duration_s": length, "ts": 1000.0 + start + length, "proc": proc,
        "span_id": f"s-{incarnation}-{n}", **fields,
    }


def run_of(master, worker):
    return types.SimpleNamespace(
        master=master, worker=worker, t0=T0, setup_s=SETUP_S)


def full_chain():
    """A run whose every second but 36..40 is named: the harness to 10,
    the master to 20, the worker's boot to 36, warm-up from 40."""
    master = [
        span("proc.start", 10, 1),
        span("master.boot", 11, 9),
        span("master.imports", 11, 1),
        span("spec.load", 12, 5),
        span("master.build", 17, 2),
        span("master.tensorboard_init", 17.5, 1),  # inside master.build
        span("master.serve_ready", 19, 1),
        span("master.build_fleet", 20, 0.5),
        span("master.launch_worker", 20.5, 0.5, worker_id=0, cause="start"),
    ]
    worker = [
        span("proc.start", 21, 1, proc="worker_0", incarnation="w"),
        span("worker.boot", 22, 14, proc="worker_0", incarnation="w"),
        span("worker.imports", 22, 5, proc="worker_0", incarnation="w"),
        span("spec.load", 27, 1, proc="worker_0", incarnation="w"),
        span("worker.join_world", 28, 1, proc="worker_0", incarnation="w"),
        span("worker.backend_init", 29, 6, proc="worker_0", incarnation="w"),
        span("worker.build_trainer", 35, 1, proc="worker_0", incarnation="w"),
        span("worker.task", 40, 60, proc="worker_0", incarnation="w"),
        span("state.init", 41, 5, proc="worker_0", incarnation="w"),
        span("compile.build", 46, 20, proc="worker_0", incarnation="w",
             entrypoint="dp_train_window", trace_s=4.0, lower_s=2.0,
             backend_s=12.0, cache_read_s=9.0),
    ]
    return master, worker


def nested_counted_once():
    return full_chain() + ({
        "setup_named_share": 96.0, "setup_largest_gap_s": 4.0,
        "harness_prepare_s": 10.0, "worker_launch_s": 1.0,
    },)


def straddling_t0_is_clipped():
    master, worker = full_chain()
    worker[-3] = span("worker.task", 40, 500, proc="worker_0", incarnation="w")
    # ...and a task wholly after t0 adds nothing.
    worker.append(span("worker.task", 600, 5, proc="worker_0", incarnation="w"))
    return master, worker, {
        "setup_named_share": 96.0, "setup_largest_gap_s": 4.0,
    }


def gap_reported_with_its_length():
    master, worker = full_chain()
    # No backend span: 29..35 is a second, longer hole; the profiler's
    # stop (an event that ends at its ts) names 36..38 of the first.
    worker = [e for e in worker if e["name"] != "worker.backend_init"]
    worker.append({"event": "profile_window", "action": "close",
                   "ts": 1038.0, "duration_s": 2.0})
    worker.append({"event": "profile_window", "action": "open", "ts": 1036.0})
    return master, worker, {
        "setup_named_share": 92.0, "setup_largest_gap_s": 6.0,
    }


def two_incarnations_of_one_worker():
    master, worker = full_chain()
    # worker_0 dies 30 s in and comes again: the same names, the same
    # proc, another process.  Its chain fills 36..40; the first's task
    # never happened.
    first = [e for e in worker if e["start_ts"] < 1030.0]
    second = [
        span("proc.start", 30, 1, proc="worker_0", incarnation="x"),
        span("worker.imports", 31, 4, proc="worker_0", incarnation="x"),
        span("worker.backend_init", 35, 5, proc="worker_0", incarnation="x"),
        span("worker.task", 40, 60, proc="worker_0", incarnation="x"),
    ]
    master.append(span("master.launch_worker", 29.5, 0.5, worker_id=1,
                       cause="relaunch", since_exit_s=0.3))
    return master, first + second, {
        "setup_named_share": 100.0, "setup_largest_gap_s": 0.0,
        # The FIRST worker process's creation, whatever came later.
        "worker_launch_s": 1.0,
    }


def none_of_the_spans():
    other = [span("checkpoint.save", 50, 5), {"event": "task_done", "ts": T0}]
    return other, other, {
        "setup_named_share": None, "setup_largest_gap_s": None,
        "harness_prepare_s": None, "worker_launch_s": None,
    }


def a_program_from_before_the_boot_spans():
    """The parent's journals: some leaves, no `master.boot`."""
    master = [span("proc.start", 10, 1), span("master.serve_ready", 19, 1)]
    worker = [span("proc.start", 21, 1, proc="worker_0"),
              span("worker.task", 40, 60, proc="worker_0")]
    return master, worker, {
        "setup_named_share": 73.0, "setup_largest_gap_s": 18.0,
        "harness_prepare_s": 10.0, "worker_launch_s": None,
    }


@pytest.mark.parametrize("case", [
    nested_counted_once, straddling_t0_is_clipped,
    gap_reported_with_its_length, two_incarnations_of_one_worker,
    none_of_the_spans, a_program_from_before_the_boot_spans,
], ids=lambda case: case.__name__)
def test_setup_chain_reads(readers, case, capsys):
    setup_chain, _ = readers
    master, worker, expected = case()
    run = run_of(master, worker)
    for part, value in expected.items():
        args = CHAIN_ARGS if part.startswith("setup_") else {}
        got = setup_chain.read(run, part=part, **args)
        if value is None:
            assert got is None, part
        else:
            assert got == pytest.approx(value, abs=1e-6), part
    if expected.get("setup_largest_gap_s"):
        # The gap's neighbours go to the run's stderr, for PERF.md.
        assert "largest gap" in capsys.readouterr().err


def test_largest_gap_names_its_neighbours(readers):
    setup_chain, _ = readers
    master, worker = full_chain()
    intervals = setup_chain.named(
        master, worker, 1000.0, T0, CHAIN_ARGS["leaves"], CHAIN_ARGS["events"])
    assert intervals[0] == (1000.0, 1010.0, setup_chain.HARNESS)
    ((length, start, end, before, after),) = setup_chain.gaps(
        intervals, 1000.0, T0)
    assert (length, start, end) == (4.0, 1036.0, 1040.0)
    assert (before, after) == ("worker.build_trainer", "worker.task")


@pytest.mark.parametrize("field,value", [
    ("trace_s", 4.5), ("backend_s", 12.0), ("cache_read_s", 9.0),
    ("no_such_field", None),
])
def test_span_field_sums_the_builds_before_the_window(readers, field, value):
    _, span_field_s = readers
    _, worker = full_chain()
    worker.append(span("compile.build", 70, 1, proc="worker_0",
                       entrypoint="dp_init", trace_s=0.5))
    # A build that ends after t0 is the window's, not set-up's.
    worker.append(span("compile.build", 99.5, 2, proc="worker_0",
                       trace_s=100.0, backend_s=100.0, cache_read_s=100.0))
    got = span_field_s.read(
        run_of([], worker), journal_of="worker", span="compile.build",
        field=field)
    assert got == value


def test_every_leaf_of_the_chain_is_a_span_of_the_program():
    from elasticdl_tpu.obs import tracing

    assert set(CHAIN_ARGS["leaves"]) <= set(tracing.SPAN_NAMES)
    # The two parents are not leaves: their self time is what is unnamed.
    assert not {"master.boot", "worker.boot"} & set(CHAIN_ARGS["leaves"])
