"""The window total and the median-of-readings arithmetic, on a master journal recorded on the
chip (`deepfm-dac.train-file`, seed 2147483659, my chip run, PR 23: the
`task_dispatch` / `task_done` lines of `events.jsonl`) and on made-up cases.

    python -m pytest perfbench/tests -q
"""

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from lib import journal, rates  # noqa: E402


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(HERE, "data", "deepfm_master_journal.jsonl.gz")
    with gzip.open(path, "rt") as f:
        events = [json.loads(line) for line in f]
    tasks = journal.tasks(events)
    return tasks, journal.done_with_records(tasks)


def test_recorded_window_reads_as_on_the_chip(recorded):
    tasks, done = recorded
    t0 = done[15][0]  # 16 warm-up tasks
    rate = rates.median_rate(done, t0, t0 + 30.0, group=2)
    assert rate["readings"] == 100
    assert rate["records"] == 100 * 2 * 65536
    assert rate["median"] == pytest.approx(439754, rel=1e-5)
    total = rates.window_total(done, t0, t0 + 30.0)
    assert total["tasks"] == 201
    # 201 whole tasks and the share of the 202nd that lies in the window.
    assert 201 * 65536 < total["records"] < 202 * 65536
    assert total["rate"] == pytest.approx(440300, rel=2e-3)
    # No save and no stall in that window: the median hides nothing.
    assert abs(rate["stall_share"]) < 0.005
    assert journal.coverage_faults(tasks) == []


@pytest.mark.parametrize("group", [1, 2, 4])
def test_group_size_moves_the_recorded_median_little(recorded, group):
    _, done = recorded
    t0 = done[15][0]
    rate = rates.median_rate(done, t0, t0 + 30.0, group=group)
    assert rate["median"] == pytest.approx(440000, rel=0.005)


def steady(n, period=0.5, records=100, start=10.0):
    return [(start + period * (i + 1), records) for i in range(n)]


def test_readings_are_equal_work_over_time():
    done = steady(80)
    got, total, seconds = rates.readings(done, 10.0, 10.0 + 30.0, group=2)
    assert len(got) == 30 and total == 6000 and seconds == pytest.approx(30.0)
    assert all(r == pytest.approx(200.0) for r in got)


def test_a_stall_moves_the_total_and_not_the_median():
    done = steady(40) + [(t + 5.0, n) for t, n in steady(40, start=30.0)]
    rate = rates.median_rate(done, 10.0, 10.0 + 45.0, group=1)
    assert rate["median"] == pytest.approx(200.0)
    total = rates.window_total(done, 10.0, 10.0 + 45.0)
    assert total["rate"] == pytest.approx(8000 / 45.0)
    assert rate["stall_share"] == pytest.approx(1 - (8000 / 45.0) / 200.0)


@pytest.mark.parametrize("t1, records", [
    (20.0, 2000.0),        # the edge falls on an acknowledgement
    (20.25, 2050.0),       # half of the task in flight lies inside
    (20.49, 2098.0),
])
def test_the_task_in_flight_counts_by_its_share_of_time(t1, records):
    total = rates.window_total(steady(80), 10.0, t1)
    assert total["records"] == pytest.approx(records)
    assert total["rate"] == pytest.approx(200.0)


def test_a_stall_across_the_edge_is_shared_out_by_time():
    # 20 tasks, then one that took 10 s (a save) and ended after the window.
    done = steady(20) + [(30.0, 100)]
    total = rates.window_total(done, 10.0, 25.0)
    assert total["records"] == pytest.approx(2000 + 100 * 5.0 / 10.0)
    # After the job's last acknowledgement nothing is in flight.
    assert rates.window_total(done, 10.0, 30.0)["records"] == 2100


def test_too_few_readings_is_an_error():
    with pytest.raises(ValueError, match="fewer than 20"):
        rates.median_rate(steady(30), 10.0, 40.0, group=2)


def test_unequal_tasks_are_an_error():
    done = steady(40) + [(31.0, 50)]
    with pytest.raises(ValueError, match="unequal work"):
        rates.median_rate(sorted(done), 10.0, 40.0, group=1)


def _events(dispatch, done):
    events = [
        {"ts": ts, "event": "task_dispatch", "type": "TRAINING", "task_id": tid,
         "epoch": rng[0], "start": rng[1], "end": rng[2]}
        for ts, tid, rng in dispatch
    ]
    events += [
        {"ts": ts, "event": "task_done", "type": "TRAINING", "task_id": tid}
        for ts, tid in done
    ]
    return journal.tasks(sorted(events, key=lambda e: e["ts"]))


@pytest.mark.parametrize("dispatch, done, fault", [
    ([(1, 1, (0, 0, 8)), (2, 2, (0, 8, 16))], [(1.5, 1)], None),
    ([(1, 1, (0, 0, 8))], [(1.5, 1), (1.6, 7)], "never dispatched"),
    ([(1, 1, (0, 0, 8)), (2, 2, (0, 0, 8))], [(1.5, 1), (2.5, 2)],
     "acknowledged twice"),
    ([(1, 1, (0, 0, 8)), (2, 2, (0, 0, 8))], [(2.5, 2)], "dispatched twice"),
    ([(1, 1, (0, 0, 8)), (2, 2, (0, 8, 16)), (3, 3, (0, 16, 24))],
     [(1.5, 1)], "unacknowledged"),
])
def test_coverage(dispatch, done, fault):
    faults = journal.coverage_faults(_events(dispatch, done))
    if fault is None:
        assert faults == []
    else:
        assert any(fault in f for f in faults)


def test_save_watch_follows_the_worker_journals(tmp_path):
    """The harness never ends a job in the middle of a save: it reads the
    workers' `phase_transition` lines as they are written."""
    from lib import load_module

    scenario = load_module(os.path.join(
        os.path.dirname(HERE), "scenarios", "train_window.py"))
    watch = scenario.SaveWatch(str(tmp_path))
    watch.poll()
    assert not watch.saving
    path = tmp_path / "events_worker_0.jsonl"

    def write(**event):
        with open(path, "a") as f:
            f.write(json.dumps(event) + "\n")

    write(ts=10.0, event="phase_transition", to="training", **{"from": "idle"})
    write(ts=11.0, event="phase_transition", to="checkpoint_save",
          **{"from": "training"})
    watch.poll()
    assert watch.saving
    write(ts=12.0, event="checkpoint_saved", step=40)
    watch.poll()
    assert watch.saving
    write(ts=12.5, event="phase_transition", to="training",
          **{"from": "checkpoint_save"})
    watch.poll()
    assert not watch.saving and watch.last_end == 12.5


def test_a_save_counts_by_its_part_inside_the_window():
    from types import SimpleNamespace
    from lib import load_module

    reader = load_module(os.path.join(
        os.path.dirname(HERE), "readers", "span_overlap_share.py"))

    def span(start, seconds, name="checkpoint.save"):
        return {"event": "span", "name": name, "start_ts": start,
                "duration_s": seconds, "ts": start + seconds}

    run = SimpleNamespace(t0=100.0, t1=130.0, worker=[
        span(90.0, 5.0),                 # before the window
        span(112.0, 15.0),               # whole, inside: 15 s
        span(127.0, 9.0),                # cut by the window's end: 3 s
        span(110.0, 4.0, "step.stage"),  # another span
    ])
    assert reader.read(run, "checkpoint.save") == pytest.approx(60.0)
