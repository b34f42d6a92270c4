#!/usr/bin/env python3
"""One run of one benchmark cell of elasticdl_tpu.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (`BENCHMARK.json` `workloads`) names a configuration and a traffic
mix.  Everything that belongs to one of them is a file found by that name:

    configs/<file>               sizes, the job's own flags, and beside it
                                 the plain reference (`reference`)
    traffic/<traffic>.json       parameters of the load; its `kind` names
    scenarios/<kind>.py          how such a run goes (warm up, window, end)
                                 and its `data.generator` names
    generators/<generator>.py    what the job reads, made from the seed
    metrics/<metric>.json        which reader gives the metric, with what
    readers/<reader>.py          `read(run, **args)` -> number, or None

so a later PR adds a cell, a model, a traffic mix or a metric by adding
files and `BENCHMARK.json` entries, and edits nothing here.

This process is only the parent of `elasticdl train`: it never imports jax
(a chip belongs to one process, and the job's worker holds it).  The last
line of stdout is the result object; without a TPU, or in a directory
without the program, the run ends non-zero and prints no result.
`--rehearse` shrinks everything for a CPU rehearsal of the control flow and
also never prints a result.
"""

from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from lib import job as joblib, journal, load_module, xplane  # noqa: E402

#: Data files and work directories live here, inside the checkout
#: (`.gitignore` lists it).  The compile cache is the program's own:
#: `JAX_COMPILATION_CACHE_DIR` if set, else `<checkout>/.jax_cache`.
STATE = os.path.join(ROOT, ".perfbench")


class BenchError(Exception):
    """The run cannot produce a result; the message says why."""


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


class Run:
    """What a scenario fills in and the readers read."""

    def __init__(self, args, bench: dict):
        self.root = ROOT
        self.bench = bench
        cells = {w["name"]: w for w in bench["workloads"]}
        if args.workload not in cells:
            raise BenchError(f"no workload {args.workload!r} in BENCHMARK.json")
        self.cell = cells[args.workload]
        self.chips = int(self.cell["chips"])
        config_entry = next(
            c for c in bench["configs"] if c["name"] == self.cell["config"]
        )
        self.rehearse = bool(args.rehearse)
        self.config = self._sized(
            read_json(os.path.join(ROOT, config_entry["file"]))
        )
        self.config_dir = os.path.dirname(
            os.path.join(ROOT, config_entry["file"])
        )
        self.traffic = self._sized(read_json(
            os.path.join(BENCH, "traffic", self.cell["traffic"] + ".json")
        ))
        self.model = self.config["model"]
        self.seconds = float(args.seconds)
        self.seed = int(args.seed)
        self.trace_on = bool(int(args.trace))
        self.work = os.path.join(STATE, "work", args.workload)
        self.peaks_table = read_json(os.path.join(BENCH, "peaks.json"))
        self.reference = load_module(
            os.path.join(self.config_dir, self.config["reference"])
        )
        self.generator = load_module(os.path.join(
            BENCH, "generators", self.traffic["data"]["generator"] + ".py"
        ))
        # Filled by the scenario:
        self.job = None
        self.master, self.worker = [], []   # journal events
        self.t0 = self.t1 = None            # the measured window, wall clock
        self.setup_s = None
        self.done = []                      # [(ts, records)] acknowledged
        self.attempted = self.failed = 0
        self.faults = []                    # why `correct` is false
        self.trace = None                   # xplane.reduce(...)
        self.trace_steps = 0
        self.roofline_bound = None          # "compute" | "memory"
        self.facts = {}
        self.check = {}
        self._device_seen = False

    # -- sizes ----------------------------------------------------------

    def _sized(self, spec: dict) -> dict:
        """A rehearsal takes the file's own tiny sizes (`rehearse`)."""
        if self.rehearse:
            spec = {**spec, **spec.get("rehearse", {})}
        return spec

    def job_flags(self) -> list:
        return list(self.config["job"]) + list(self.traffic["job"])

    def flag_int(self, name: str) -> int:
        """The whole number the job's own flags give `--<name>`."""
        for flag in self.job_flags():
            if flag.startswith(f"--{name}="):
                return int(flag.split("=", 1)[1])
        raise BenchError(f"the cell's job flags have no --{name}")

    def peaks(self) -> dict:
        return self.peaks_table[self.facts["device"]["kind"]]

    # -- pieces every scenario uses ------------------------------------------

    def prepare(self):
        """Fresh work directory, and the cell's data from the seed."""
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)
        return self.generator.training_data(
            os.path.join(STATE, "data"), self.traffic["data"], self.model,
            self.seed,
        )

    def env(self) -> dict:
        env = dict(os.environ)
        env.pop("BENCH_RUN", None)
        if self.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={self.chips}"
            )
        return env

    def guard_device(self, device=None):
        """Fail as soon as the worker's log names a device that is not what
        the cell asks for (called from the scenarios' waiting loops: without
        a TPU the run ends seconds after the worker starts)."""
        if device is None:
            if self._device_seen:
                return
            device = self.job.device_now()
            if device is None:
                return
        self._device_seen = True
        want = "cpu" if self.rehearse else "tpu"
        if device["platform"] != want or device["count"] != self.chips:
            raise BenchError(
                f"the job runs on {device}, the cell asks for {self.chips} "
                f"{want} chip(s)"
            )
        if not self.rehearse and device["kind"] not in self.peaks_table:
            raise BenchError(
                f"device kind {device['kind']!r} is not in "
                "perfbench/peaks.json: no peaks, so no result"
            )

    def collect(self):
        """After the job's end: its journals, its facts, the device."""
        self.master = journal.load(journal.master_path(self.job.tb))
        self.worker = self.job.worker_events()
        self.facts = self.job.facts()
        self.guard_device(self.facts["device"])
        if not self.facts["losses_finite"]:
            self.faults.append("a task's loss is missing or not finite")
        codec = self.generator.CODEC
        if codec is not None and self.facts["codec"] != codec:
            self.faults.append(
                f"the file was read with the {self.facts['codec']!r} codec, "
                f"not {codec!r}"
            )

    def reduce_trace(self):
        """The traced steps of the newest profile the job wrote."""
        out = os.path.join(self.work, "trace.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "lib", "xplane.py"),
             os.path.join(self.job.tb, "profile"), out],
            env=env, capture_output=True, text=True, timeout=300,
        )
        if proc.returncode != 0:
            raise BenchError(f"no trace to reduce: {proc.stderr[-2000:]}")
        if self.rehearse:
            return  # a CPU trace has no device plane
        self.trace = xplane.reduce(read_json(out))
        windows = [e for e in self.worker if e.get("event") == "profile_window"]
        opened = [e for e in windows if e["action"] == "open"]
        closed = [e for e in windows if e["action"] == "close"]
        if not opened or not closed:
            raise BenchError("the worker journal has no closed profile window")
        lo, hi = opened[-1]["ts"], closed[-1]["ts"]
        # One dispatch span per executed program, in order; the trace keeps
        # the first `programs` of them whole.
        dispatched = [
            e.get("steps", 0)
            for e in journal.spans(self.worker, "step.execute")
            if lo <= e["start_ts"] <= hi
        ]
        whole = self.trace["programs"] or len(dispatched)
        self.trace_steps = sum(dispatched[:whole])
        if self.trace_steps <= 0:
            raise BenchError("no step.execute span inside the profile window")

    def run_check(self):
        """The program against the plain reference, at the job's weights."""
        spec_path = os.path.join(self.work, "check.json")
        check = self.config["check"]
        with open(spec_path, "w") as f:
            json.dump({
                "root": ROOT,
                "reference": os.path.join(
                    self.config_dir, self.config["reference"]),
                "model": self.model,
                "job_argv": self.job.argv,
                "seed": self.seed + 1,
                "rows": check["rows"],
                "precisions": (
                    list(check["tolerance_rel_rms"])
                    + check.get("also_report", [])
                ),
                "rehearse": self.rehearse,
            }, f)
        log = os.path.join(self.work, "check.log")
        with open(log, "wb") as err:
            proc = subprocess.run(
                [sys.executable, os.path.join(BENCH, "lib", "check.py"),
                 spec_path],
                env=self.env(), cwd=ROOT, stdout=subprocess.PIPE, stderr=err,
                timeout=900,
            )
        if proc.returncode != 0:
            raise BenchError(
                f"the check exited {proc.returncode}\n" + joblib.tail(log)
            )
        self.check = json.loads(proc.stdout.decode().strip().splitlines()[-1])
        ran_on, checked_on = self.facts["device"], self.check["device"]
        if ran_on != checked_on:
            raise BenchError(f"job on {ran_on}, check on {checked_on}")
        if not self.check["finite"]:
            self.faults.append("outputs not finite")
        for precision, tolerance in check["tolerance_rel_rms"].items():
            differ = self.check["rel_rms_diff"][precision]
            if not differ <= tolerance:
                self.faults.append(
                    f"program and reference ({precision}) differ by "
                    f"{differ:.3g} of the outputs' rms; tolerance {tolerance}"
                )

    def memory_peak_bytes(self) -> int:
        """The worker's own high-water mark (`mem_hwm_mb` of the
        `step_anatomy` events its heartbeats carry to the master journal):
        the peak while TRAINING, on the fullest chip."""
        marks = [
            e["mem_hwm_mb"] for e in self.master
            if e.get("event") == "step_anatomy" and e.get("mem_hwm_mb")
        ]
        if not marks:
            if self.rehearse:
                return 0  # the CPU backend reports no memory
            raise BenchError("no mem_hwm_mb in the master journal")
        return int(max(marks) * 2**20)


def metric_values(run: Run, declared: list) -> dict:
    values = {}
    for metric in declared:
        if "workloads" in metric and run.cell["name"] not in metric["workloads"]:
            continue
        decl = read_json(os.path.join(BENCH, "metrics", metric["name"] + ".json"))
        reader = load_module(os.path.join(BENCH, "readers", decl["reader"] + ".py"))
        value = reader.read(run, **decl.get("args", {}))
        if value is not None:
            values[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return values


def result_line(run: Run) -> dict:
    declared = run.bench["per_layer" if run.trace_on else "end_to_end"]
    device = dict(run.facts["device"])
    device["memory_peak_bytes"] = run.memory_peak_bytes()
    metrics = metric_values(run, declared)  # a reader may find a fault
    line = {
        "correct": not run.faults,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
        "faults": run.faults,
        "check": run.check,
    }
    if run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["roofline_bound"] = run.roofline_bound
        line["trace_steps"] = run.trace_steps
        line["breakdown"] = {
            "device_ops": run.trace["device_ops"],
            "idle_gaps": run.trace["idle_gaps"],
        }
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny sizes on the CPU; never prints a result")
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "elasticdl_tpu")):
        print("perfbench runs from a checkout of elasticdl_tpu; there is "
              "none beside it", file=sys.stderr)
        return 2
    print(f"[perfbench] host cores: {os.cpu_count()}", flush=True)
    run = None
    try:
        run = Run(args, read_json(os.path.join(ROOT, "BENCHMARK.json")))
        scenario = load_module(
            os.path.join(BENCH, "scenarios", run.traffic["kind"] + ".py")
        )
        scenario.drive(run, T_START)
        line = result_line(run)
    except (BenchError, joblib.JobError) as exc:
        print(f"[perfbench] FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    finally:
        if run is not None and run.job is not None:
            run.job.stop()
        if run is not None:
            # Gigabytes of checkpoints; the logs beside them stay.
            for step_dir in glob.glob(os.path.join(run.work, "ckpt", "step_*")):
                shutil.rmtree(step_dir, ignore_errors=True)
    if run.rehearse:
        print(f"[perfbench] rehearsal passed (no result is printed): "
              f"{json.dumps(line)[:2000]}", file=sys.stderr, flush=True)
        return 3
    for fault in run.faults:
        print(f"[perfbench] not correct: {fault}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
