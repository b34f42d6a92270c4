"""A training job measured over a window of `--seconds`.

Set-up: data from the seed, the job launched as a user launches it, and
`warmup_tasks` tasks acknowledged (state init, every compile or cache load,
the first windows).  The acknowledgement that ends warm-up is t0; set-up is
process start to t0.  The window is (t0, t0 + seconds].

The job is given a FIXED amount of work from the traffic file: `epochs`
passes over the seed's data, more than the system trains in the window, so
that a faster program still fills it.  The job saves at the cadence its
configuration states (`guarantees`), and as many saves as stated there
have to START inside the window and be committed before the job is ended:
the rate is over all of the window, saves included, and a save that is
still being written when the window closes costs the window all its time
up to there.  When the window is over, no save is being written and the
task then in flight has been acknowledged, the harness ends the job as a
preemption would (SIGKILL), and the comparison with the reference restores
the newest checkpoint the job committed: the cadence save.  A save is
never cut off: how long one takes depends on the machine (10-19 s for the
same 6.7 GB on one chip machine), and a run whose save was slow is a slow
run, not a failed one.  If a program became so fast that the work ran out
early, the job ends by itself and the window ends with the work.
"""

from __future__ import annotations

import glob
import os
import time

from lib import job as joblib, journal

POLL_S = 0.05
#: How long after the window the task in flight may take to be
#: acknowledged (it gives the window's last, partial, task its share).
IN_FLIGHT_S = 5.0
#: How long after the window a save that is being written may still take.
SAVE_GRACE_S = 120.0


class SaveWatch:
    """Whether a worker is writing a checkpoint now, by the workers'
    journals (`phase_transition` into and out of `checkpoint_save`; the
    journal flushes every line)."""

    def __init__(self, tb: str):
        self._tb, self._followers = tb, {}
        self.saving, self.last_end = False, 0.0

    def poll(self) -> None:
        for path in glob.glob(os.path.join(self._tb, "events_worker_*.jsonl")):
            if path not in self._followers:
                self._followers[path] = joblib.Follower(path)
        for follower in self._followers.values():
            for event in follower.new_events():
                if event.get("event") != "phase_transition":
                    continue
                if event.get("to") == "checkpoint_save":
                    self.saving = True
                elif event.get("from") == "checkpoint_save":
                    self.saving, self.last_end = False, event["ts"]


def job_argv(run, training_data: str) -> list:
    traffic = run.traffic
    per_task = run.flag_int("records_per_task")
    argv = run.job_flags() + [
        f"--training_data={training_data}",
        f"--num_epochs={int(traffic['epochs'])}",
    ]
    if run.trace_on:
        first = (
            traffic["warmup_tasks"] * per_task // run.flag_int("minibatch_size")
            + traffic["trace"]["after_warmup_steps"]
        )
        argv.append(
            f"--profile_steps={first},{first + traffic['trace']['steps']}"
        )
    return argv


def drive(run, t_start: float) -> None:
    training_data = run.prepare()
    run.job = joblib.Job(
        run.root, run.work, job_argv(run, training_data), run.env(),
    )
    run.job.start()
    follower = joblib.Follower(journal.master_path(run.job.tb))
    saves = SaveWatch(run.job.tb)
    warmup = run.traffic["warmup_tasks"]
    acknowledged, t0, last_done = 0, None, 0.0
    # Until the window is over, no save is being written and the task in
    # flight at the window's end has been acknowledged, or the job has ended.
    while True:
        for event in follower.new_events():
            if event.get("event") == "task_done" and event.get("type") == "TRAINING":
                acknowledged += 1
                last_done = event["ts"]
                if acknowledged == warmup:
                    t0 = event["ts"]
        run.guard_device()
        rc = run.job.returncode()
        if rc is not None:
            break
        saves.poll()
        if t0 is not None:
            t1, now = t0 + run.seconds, time.time()
            if saves.saving:
                if now > t1 + SAVE_GRACE_S:
                    break
            elif last_done > t1 or now > max(t1, saves.last_end) + IN_FLIGHT_S:
                break
        time.sleep(POLL_S)
    if t0 is None or rc not in (None, 0):
        raise joblib.JobError(
            f"the job ended (exit {rc}), {acknowledged} task(s) "
            f"acknowledged, {warmup} are warm-up\n"
            + joblib.tail(run.job.log) + "\n" + run.job.worker_tail()
        )
    stopped = time.time()
    run.job.stop()
    run.collect()
    # What the dying processes still wrote is not the job's doing.
    run.master = [e for e in run.master if e["ts"] < stopped]
    tasks = journal.tasks(run.master)
    run.done = journal.done_with_records(tasks)
    run.t0 = t0
    # A job that ran out of work ended its window with its last task.
    run.t1 = t0 + run.seconds if rc is None else min(
        t0 + run.seconds, run.done[-1][0])
    run.setup_s = t0 - t_start
    run.faults += journal.coverage_faults(tasks)

    def inside(event) -> bool:
        return run.t0 < event["ts"] <= run.t1

    run.attempted = sum(1 for ts, _ in run.done if run.t0 < ts <= run.t1)
    run.failed = sum(
        1 for e in run.master
        if e.get("event") in ("task_requeue", "task_failed_permanently")
        and inside(e)
    )
    if run.failed:
        run.faults.append(f"{run.failed} task(s) failed or were requeued")
    compiles = [e for e in journal.spans(run.worker, "step.compile") if inside(e)]
    if compiles:
        run.faults.append(
            f"{len(compiles)} compile(s) inside the window, "
            f"{sum(e['duration_s'] for e in compiles):.1f}s"
        )
    # Saves that started inside the window and committed before the end.
    committed = [
        e["ts"] for e in run.worker
        if e.get("event") == "checkpoint_saved" and e["ts"] < stopped
    ]
    saved = [
        e for e in journal.spans(run.worker, "checkpoint.save")
        if run.t0 < e["start_ts"] <= run.t1
        and any(e["start_ts"] <= ts <= e["ts"] for ts in committed)
    ]
    stated = run.config["guarantees"]
    if len(saved) < stated["saves_in_window"]:
        run.faults.append(
            f"{len(saved)} save(s) started inside the window and were "
            f"committed before the job was ended; the configuration states "
            f"{stated['saves_in_window']} "
            f"(--checkpoint_steps={stated['checkpoint_steps']})"
        )
    if run.flag_int("checkpoint_steps") != stated["checkpoint_steps"]:
        run.faults.append("the job's --checkpoint_steps is not the stated one")
    if run.trace_on:
        run.reduce_trace()
    run.run_check()

