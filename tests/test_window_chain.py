"""The benchmark's reader of the measured window (ISSUE 48), loaded by
path: `perfbench/readers/window_chain.py` over hand-written journals
(what of the window lies inside something the worker's journal names, and
the longest stretch that does not), and over the journals of a real job
on the CPU, which holds the contract the reader rests on: a task's
phases and its interval children fit inside the task, and
`step.device_wait` is the task's own child.
"""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO_ROOT, "perfbench")


@pytest.fixture(scope="module")
def window_chain():
    spec = importlib.util.spec_from_file_location(
        "perfbench_window_chain",
        os.path.join(BENCH, "readers", "window_chain.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


with open(os.path.join(BENCH, "metrics", "window_named_share.json")) as f:
    CHAIN_ARGS = {
        k: v for k, v in json.load(f)["args"].items() if k != "part"
    }

T0, T1 = 1000.0, 1030.0


def span(name, start, length, n, parent="", trace="", **fields):
    return {
        "event": "span", "name": name, "start_ts": T0 + start,
        "duration_s": length, "ts": T0 + start + length, "proc": "worker_0",
        "span_id": f"s-{n}", "parent_span_id": parent, "trace_id": trace,
        **fields,
    }


def task(n, start, length, wait=None, queue=0.5, save=None, phases=None):
    """Task n of one journal: `worker.get_task` 0.5 s long before it, the
    task, its phases (2 s of `step.execute`, the queue wait booked as
    `step.data_wait`), the wait for the device at its end and 0.5 s of
    `worker.report_task` behind it."""
    me, trace = f"s-{n}0", f"t-{n}"
    wait = length - 2.0 if wait is None else wait
    events = [
        span("worker.get_task", start - queue, queue, f"{n}1", trace, trace),
        span("step.data_wait", start, queue, f"{n}2", me, trace),
        span("step.execute", start + queue, 2.0, f"{n}3", me, trace),
        *(span(name, start, seconds, f"{n}{i}", me, trace)
          for i, (name, seconds) in enumerate((phases or {}).items(), 7)),
        span("step.device_wait", start + length - wait, wait, f"{n}4", me,
             trace, task_id=n, steps=2),
        span("worker.task", start, length, f"{n}0", trace, trace, task_id=n),
        span("worker.report_task", start + length, 0.5, f"{n}5", trace, trace),
    ]
    if save is not None:
        events.append(span("checkpoint.save", save[0], save[1], f"{n}6", me, trace))
    return events


def master_of(dispatched, done):
    return [
        {"event": "task_dispatch", "type": "TRAINING", "task_id": n, "ts": T0}
        for n in dispatched
    ] + [
        {"event": "task_done", "type": "TRAINING", "task_id": n, "ts": T0}
        for n in done
    ]


def back_to_back():
    """Three tasks of 9 s, each with its queue wait before it and its
    report behind it: every second of the 30 is named."""
    worker = [e for n in range(3) for e in task(n + 1, 0.5 + 10 * n, 9.0)]
    return master_of([1, 2, 3, 4], [1, 2, 3]), worker, {
        "window_named_share": 100.0, "window_largest_gap_s": 0.0,
    }, "device_wait 70.00%"


def a_task_cut_by_t1():
    """The third task runs 26..38: 4 s of it lie inside.  Nothing of its
    12 s is a child interval but the fence (10 s, from 28 on), so its
    phases (2 s in 2 s of rest) count by the share of that rest inside:
    all of it, and the fence counts 2 s."""
    worker = (
        task(1, 0.5, 9.0) + task(2, 10.5, 14.5)
        + task(3, 26.0, 12.0, queue=0.5)
    )
    return master_of([1, 2, 3], [1, 2]), worker, {
        "window_named_share": 100.0, "window_largest_gap_s": 0.0,
    }, "device_wait 71.67%"


def a_save_inside_a_task():
    """Task 2 holds a 10 s save between its dispatch and its fence, and
    1.5 s that nothing names: the task's remainder, reported by its id."""
    worker = (
        task(1, 0.5, 9.0)
        + task(2, 10.5, 15.0, wait=1.5, save=(13.0, 10.0))
        + task(3, 26.5, 9.0)
    )
    return master_of([1, 2, 3], [1, 2]), worker, {
        "window_named_share": 95.0, "window_largest_gap_s": 1.5,
    }, "save 33.33%"


def a_gap_between_report_and_get_task():
    """After task 1's report the worker journals nothing for 3 s (a WAIT
    poll journals no span): a gap, with its neighbours."""
    worker = task(1, 0.5, 9.0) + task(2, 13.5, 20.0)
    return master_of([1, 2, 3], [1]), worker, {
        "window_named_share": 90.0, "window_largest_gap_s": 3.0,
    }, "unnamed 10.00%"


def work_that_runs_out():
    """The job's last task is acknowledged 20 s in and nothing is out:
    the last 10 s are the work having run out, not the program's gap."""
    worker = task(1, 0.5, 9.0) + task(2, 10.5, 9.0)
    return master_of([1, 2], [1, 2]), worker, {
        "window_named_share": 100.0, "window_largest_gap_s": 0.0,
    }, "after_last_task 33.33%"


def a_task_still_out_is_a_gap():
    """The same journal, but the master has task 3 out: a worker that
    says nothing for 10 s with a task in hand is a gap."""
    master, worker, _, _ = work_that_runs_out()
    return master + master_of([3], []), worker, {
        "window_named_share": 200.0 / 3, "window_largest_gap_s": 10.0,
    }, "after_last_task 0.00% unnamed 33.33%"


def phases_over_the_tasks_length_are_capped():
    """Aggregates are sums: where they say more than the task has left
    beside its intervals, the task is full and no more."""
    worker = task(1, 0.5, 9.0, phases={"step.stage": 5.0}) + task(2, 10.5, 20.0)
    return master_of([1, 2, 3], [1]), worker, {
        "window_named_share": 100.0, "window_largest_gap_s": 0.0,
    }, "unnamed 0.00%"


def the_profilers_stop_is_named():
    """A `profile_window` close inside a task (an event that ends at its
    `ts`) names its seconds; without it they are the task's remainder."""
    worker = task(1, 0.5, 9.0, wait=4.0) + task(2, 10.5, 20.0)
    worker.append({"event": "profile_window", "action": "close",
                   "ts": T0 + 5.5, "duration_s": 3.0})
    worker.append({"event": "profile_window", "action": "open", "ts": T0 + 1})
    return master_of([1, 2, 3], [1]), worker, {
        "window_named_share": 100.0, "window_largest_gap_s": 0.0,
    }, "profile 10.00%"


def a_journal_without_the_fence():
    """The parent's journal: tasks and phases, no `step.device_wait`."""
    master, worker, _, _ = back_to_back()
    worker = [e for e in worker if e["name"] != "step.device_wait"]
    return master, worker, {
        "window_named_share": None, "window_largest_gap_s": None,
    }, None


@pytest.mark.parametrize("case", [
    back_to_back, a_task_cut_by_t1, a_save_inside_a_task,
    a_gap_between_report_and_get_task, work_that_runs_out,
    a_task_still_out_is_a_gap, phases_over_the_tasks_length_are_capped,
    the_profilers_stop_is_named, a_journal_without_the_fence,
], ids=lambda case: case.__name__)
def test_window_chain_reads(window_chain, case, capsys):
    master, worker, expected, in_line = case()
    run = types.SimpleNamespace(master=master, worker=worker, t0=T0, t1=T1)
    for part, value in expected.items():
        got = window_chain.read(run, part=part, **CHAIN_ARGS)
        if value is None:
            assert got is None, part
        else:
            assert got == pytest.approx(value, abs=1e-6), part
    err = capsys.readouterr().err
    if in_line is None:
        assert "window chain" not in err
    else:
        # The window's parts go to the run's stderr, for PERF.md.
        (line,) = [l for l in err.splitlines() if "data_wait" in l]
        assert line.startswith("[perfbench] window chain: data_wait ")
        assert in_line in line
    if expected["window_largest_gap_s"]:
        assert "window chain: largest gap" in err


def test_largest_gap_names_its_neighbours_or_its_task(window_chain):
    master, worker, _, _ = a_gap_between_report_and_get_task()
    found = window_chain.chain(worker, T0, T1, False, **CHAIN_ARGS)
    length, start, what, parts = found["holes"][0]
    assert (length, start - T0) == pytest.approx((3.0, 10.0))
    assert what == "after worker.report_task, before worker.get_task"
    assert parts is None
    master, worker, _, _ = a_save_inside_a_task()
    found = window_chain.chain(worker, T0, T1, False, **CHAIN_ARGS)
    length, _, what, parts = found["holes"][0]
    assert what.startswith("the remainder of task 2 ")
    assert parts["checkpoint.save"] == pytest.approx(10.0)
    assert parts["unnamed"] == pytest.approx(1.5)
    # The parts tile the window: nothing is counted twice or left out.
    assert sum(found["parts"].values()) == pytest.approx(T1 - T0)


def test_every_name_of_the_chain_is_a_span_of_the_program():
    from elasticdl_tpu.obs import stepstats, tracing

    for key in ("leaves", "phases", "intervals"):
        assert set(CHAIN_ARGS[key]) <= set(tracing.SPAN_NAMES), key
    # Every phase the anatomy journals as an aggregate is summed, and
    # the one it journals as the interval it is, is an interval here.
    assert set(CHAIN_ARGS["phases"]) == {
        f"step.{phase}" for phase in tracing._WINDOW_PHASES}
    assert (set(stepstats.PHASES) - set(tracing._WINDOW_PHASES)
            == {"device_wait"})
    assert "step.device_wait" in CHAIN_ARGS["intervals"]
    assert "device_wait" not in stepstats.HOST_PHASES
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    lm_cells = declared["data_wait_share.lm"]["workloads"]
    for name in ("window_named_share", "window_largest_gap_s",
                 "device_wait_share"):
        assert declared[name]["workloads"] == ["deepfm-dac.train-file"]
        assert declared[name]["moves"] == "train_samples_per_s"
        assert declared[name + ".lm"]["workloads"] == lm_cells
        assert declared[name + ".lm"]["moves"] == "train_tokens_per_s"


# ---------------------------------------------------------------------------
# A real job on the CPU
# ---------------------------------------------------------------------------


def _load(path):
    with open(path) as f:
        return [json.loads(line) for line in f]


@pytest.fixture(scope="module")
def real_job(tmp_path_factory):
    """`elasticdl train` as a user runs it, one worker process, on the
    CPU: 10 tasks of 16 steps (a tenth of a second each: what a task
    leaves unnamed is 2-3 ms of RPC and journal lines, whatever its
    length), a save every 48 steps."""
    tmp = tmp_path_factory.mktemp("window_chain")
    proc = subprocess.run(
        [sys.executable, "-m", "elasticdl_tpu.client.main", "train",
         "--distribution_strategy=AllreduceStrategy", "--num_workers=1",
         "--model_zoo=model_zoo", "--model_def=mnist.mnist_functional_api",
         "--training_data=synthetic://mnist?n=81920",
         "--records_per_task=8192", "--minibatch_size=512",
         "--checkpoint_steps=48", "--job_name=window_chain",
         f"--tensorboard_log_dir={tmp / 'tb'}",
         f"--checkpoint_dir={tmp / 'ckpt'}"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=1"),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    (log,) = (tmp / "ckpt" / "window_chain_worker_logs").glob("worker_*.log")
    return (
        _load(tmp / "tb" / "events.jsonl"),
        _load(tmp / "tb" / "events_worker_0.jsonl"),
        str(tmp / "tb"), log.read_text(),
    )


def test_a_tasks_phases_and_intervals_fit_inside_it(real_job):
    """The contract the reader rests on, for every `worker.task`: the
    sum of its phases (less the queue wait the run loop books into them,
    which lies in `worker.get_task`) and its interval children is at
    most the task's length + 1 ms; its one `step.device_wait` is its own
    child, lies inside it, and carries its id and its steps."""
    _, worker, _, log = real_job
    spans = [e for e in worker if e.get("event") == "span"]
    tasks = [e for e in spans if e["name"] == "worker.task"]
    assert len(tasks) == 10
    saves = 0
    for t in tasks:
        children = [e for e in spans if e.get("parent_span_id") == t["span_id"]]
        (queue,) = [e for e in spans if e["name"] == "worker.get_task"
                    and e["trace_id"] == t["trace_id"]]
        phases = sum(e["duration_s"] for e in children
                     if e["name"] in CHAIN_ARGS["phases"])
        inner = [e for e in children if e["name"] in CHAIN_ARGS["intervals"]]
        saves += sum(e["name"] == "checkpoint.save" for e in inner)
        named = phases - queue["duration_s"] + sum(
            e["duration_s"] for e in inner)
        assert named <= t["duration_s"] + 1e-3, t["task_id"]
        (fence,) = [e for e in inner if e["name"] == "step.device_wait"]
        assert fence["trace_id"] == t["trace_id"]
        assert (fence["task_id"], fence["steps"]) == (t["task_id"], 16)
        assert t["start_ts"] <= fence["start_ts"]
        assert (fence["start_ts"] + fence["duration_s"]
                <= t["start_ts"] + t["duration_s"] + 1e-3)
    assert saves == 3
    # The fence wraps the read that was there: one loss a task, printed.
    assert log.count(" done: step=") == 10


def test_the_middle_of_a_real_job_is_named(window_chain, real_job):
    """From the acknowledgement of the second task to that of the ninth:
    at least 95% of it is named, the parts tile it, and the queue wait
    has its span in every task."""
    master, worker, _, _ = real_job
    done = [e["ts"] for e in master if e.get("event") == "task_done"
            and e.get("type") == "TRAINING"]
    run = types.SimpleNamespace(
        master=master, worker=worker, t0=done[1], t1=done[8])
    share = window_chain.read(run, part="window_named_share", **CHAIN_ARGS)
    gap = window_chain.read(run, part="window_largest_gap_s", **CHAIN_ARGS)
    assert 95.0 <= share <= 100.0
    assert 0.0 <= gap <= 0.05 * (run.t1 - run.t0)
    found = window_chain.chain(worker, run.t0, run.t1, False, **CHAIN_ARGS)
    assert sum(found["parts"].values()) == pytest.approx(run.t1 - run.t0)
    assert found["parts"]["step.device_wait"] > 0
    assert found["parts"]["checkpoint.save"] > 0


def test_main_prints_a_finished_jobs_chain(window_chain, real_job, capsys):
    _, _, tb, _ = real_job
    assert window_chain.main([tb, "2", "3600", "0.0"]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("window ") and " device_wait " in out[0]
    assert out[1].startswith("named ")
    assert any(l.startswith("unnamed ") and "the remainder of task" in l
               for l in out)


def test_main_says_so_where_the_journal_has_no_fence(
        window_chain, tmp_path, capsys):
    master, worker, _, _ = a_journal_without_the_fence()
    for name, events in (("events.jsonl", master),
                         ("events_worker_0.jsonl", worker)):
        with open(tmp_path / name, "w") as f:
            f.writelines(json.dumps(e) + "\n" for e in events)
    assert window_chain.main([str(tmp_path), "1", "30"]) == 1
    assert "no step.device_wait" in capsys.readouterr().out
