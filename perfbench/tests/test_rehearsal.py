"""A CPU rehearsal of each cell's command at tiny sizes goes through the
whole control flow (data, job, window with its cadence save, trace dump,
the comparison with the reference) and ends NON-ZERO with no result line:
a number from a CPU can never be read as a device metric.  So does the
command in a directory that holds only `BENCHMARK.json` and the benchmark.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def run_cell(root, workload, trace, seconds="4"):
    cmd = BENCH["command"] + [
        "--workload", workload, "--seed", str(2**31 + 11),
        "--seconds", seconds, "--trace", str(trace), "--rehearse",
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    return subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True, timeout=900)


def assert_no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        assert not line.lstrip().startswith("{"), line


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_rehearses_and_prints_no_result(workload, trace):
    proc = run_cell(ROOT, workload, trace)
    assert_no_result(proc)
    assert "rehearsal passed" in proc.stderr, proc.stderr[-3000:]
    assert '"correct": true' in proc.stderr, proc.stderr[-3000:]


def test_no_result_without_the_program(tmp_path):
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    cmd = BENCH["command"] + ["--workload", BENCH["workloads"][0]["name"],
                              "--seed", "1", "--seconds", "4", "--trace", "0"]
    proc = subprocess.run(cmd, cwd=tmp_path, capture_output=True, text=True,
                          timeout=120)
    assert_no_result(proc)


def test_no_result_without_a_tpu():
    """The real command (no --rehearse) here, where jax is held to the CPU:
    the job runs on the wrong platform, the run fails."""
    cmd = BENCH["command"] + ["--workload", "deepfm-dac.train-file",
                              "--seed", "1", "--seconds", "1", "--trace", "0"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert_no_result(proc)
