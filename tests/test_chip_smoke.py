"""chip_smoke.py's own contract, as far as a machine without a chip can
hold it: the CPU rehearsal runs every phase and never claims the chip, a
run without an accelerator (or without the repo) fails and prints no
result, one failing phase fails the run — and the compile-cache helper
every compiling process calls places the cache where the contract says.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(argv, cwd=REPO, env=None, timeout=900):
    return subprocess.run(
        [sys.executable, *argv], cwd=cwd, env=env or dict(os.environ),
        capture_output=True, text=True, timeout=timeout,
    )


def _result_lines(stdout):
    """Every stdout line that parses as a result object with "ok"."""
    found = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "ok" in obj:
            found.append(obj)
    return found


@pytest.mark.slow
@pytest.mark.e2e
def test_cpu_rehearsal_runs_every_phase_and_never_claims_the_chip():
    """The whole script at tiny size on the CPU backend: ~130 s (three
    jobs, a fleet, five children), so it is marked slow — the tier-1
    gate's time limit has no room for it — and runs under `make test`;
    `make chip-smoke-cpu` is the same run by hand."""
    proc = _run([SCRIPT, "--cpu"])
    assert proc.returncode == 0, proc.stderr[-4000:]
    for phase in chip_smoke.ONE_CHIP_PHASES:
        assert f"[{phase}] ok in " in proc.stdout, phase
    # The progress lines a reader needs are there before the result.
    for needle in (
        "codec native", "sparse kernel {'kernel': 'xla'",
        "compile seconds:", "loadgen closed loop: 12/12 served",
        "attention engine (worker log): xla blockwise_attention",
        "fused_dedup_apply[adam,", "flash_attention bwd D64",
    ):
        assert needle in proc.stdout, needle
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["rehearsal"] == "cpu"
    assert last["phases_passed"] is True
    assert last["device"]["platform"] == "cpu"
    assert all(r["ok"] is not True for r in _result_lines(proc.stdout))


def test_without_an_accelerator_the_smoke_fails_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = _run([SCRIPT], env=env)
    assert proc.returncode != 0
    assert _result_lines(proc.stdout) == []
    assert "not on 'tpu'" in proc.stderr


def test_the_script_alone_fails_and_prints_no_result(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    for argv in ([], ["--cpu"]):
        proc = _run([str(tmp_path / "chip_smoke.py"), *argv],
                    cwd=str(tmp_path))
        assert proc.returncode != 0
        assert _result_lines(proc.stdout) == []


@pytest.mark.parametrize("failing", range(len(chip_smoke.ONE_CHIP_PHASES)))
def test_one_failing_phase_fails_the_run(tmp_path, failing):
    """Whichever phase fails, the run fails there: later phases do not
    start, and no result is assembled from the ones that passed."""
    phases = chip_smoke.ONE_CHIP_PHASES
    args = chip_smoke.parse_args(["--cpu", "--work_dir", str(tmp_path)])
    device = {"platform": "cpu", "kind": "cpu", "count": 1}

    def phase_cmd(phase, args):
        if phases.index(phase) == failing:
            return [sys.executable, "-c", "import sys; sys.exit(3)"]
        write = (
            "import json, sys; json.dump({'device': %r}, "
            "open(sys.argv[1], 'w'))" % (device,)
        )
        return [sys.executable, "-c", write,
                chip_smoke._result_path(args.work_dir, phase)]

    with pytest.raises(chip_smoke.SmokeError, match=phases[failing]):
        chip_smoke.run_phases(phases, args, phase_cmd)
    ran = sorted(p for p in phases if os.path.exists(
        chip_smoke._result_path(str(tmp_path), p)))
    assert ran == sorted(phases[:failing])


def test_final_line_is_honest_about_the_device():
    phases = chip_smoke.ONE_CHIP_PHASES
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    on = lambda device: {p: {"device": device} for p in phases}  # noqa: E731
    chip = chip_smoke.parse_args([])
    assert chip_smoke._final_line(chip, phases, on(tpu)) == {
        "ok": True, "device": tpu,
    }
    # No flag, no chip: no result at all.
    with pytest.raises(chip_smoke.SmokeError):
        chip_smoke._final_line(chip, phases, on(cpu))
    # One phase on another device than the train steps: not a pass.
    mixed = on(tpu)
    mixed["serve"] = {"device": cpu}
    with pytest.raises(chip_smoke.SmokeError, match="serve"):
        chip_smoke._final_line(chip, phases, mixed)
    # The rehearsal names the cpu and never says ok.
    rehearsal = chip_smoke._final_line(
        chip_smoke.parse_args(["--cpu"]), phases, on(cpu)
    )
    assert rehearsal["ok"] is False and rehearsal["device"] == cpu
    # --chips 4 carries the count the worker saw.
    four = dict(tpu, count=4)
    assert chip_smoke._final_line(
        chip_smoke.parse_args(["--chips", "4"]),
        chip_smoke.FOUR_CHIP_PHASES, {"train4": {"device": four}},
    ) == {"ok": True, "device": four}


# ---------------------------------------------------------------------------
# the compile-cache helper
# ---------------------------------------------------------------------------

_PROBE = """
import json, sys
import jax
updated = []
real_update = jax.config.update
def recording_update(name, value):
    updated.append(name)
    return real_update(name, value)
jax.config.update = recording_update
from elasticdl_tpu.common import compile_cache
first = compile_cache.configure(*sys.argv[1:])
second = compile_cache.configure(*sys.argv[1:])
print(json.dumps({
    "returned": [first, second],
    "config_dir": jax.config.jax_compilation_cache_dir,
    "updated": updated,
    "min_compile_time": jax.config.jax_persistent_cache_min_compile_time_secs,
}))
"""


def _probe_cache(tmp_path, env_dir=None, flag_dir=None, cwd=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = _run(["-c", _PROBE, *([flag_dir] if flag_dir else [])],
                cwd=cwd or str(tmp_path), env=env, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cache_dir_set_from_outside_is_left_to_jax(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax's own reading of it stands and
    the code sets NO directory — not even the job's flag."""
    outside = str(tmp_path / "placed_from_outside")
    for flag_dir in (None, str(tmp_path / "flag")):
        got = _probe_cache(tmp_path, env_dir=outside, flag_dir=flag_dir)
        assert got["returned"] == [outside, outside]
        assert got["config_dir"] == outside
        assert "jax_compilation_cache_dir" not in got["updated"]
        assert got["min_compile_time"] == 0.0  # thresholds still lowered


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout(tmp_path):
    """Unset: <repo>/.jax_cache — the same string across two calls and
    two processes started from different directories (the path is part
    of the cache key, so it may never carry a pid, a time or a temp
    name); the job's flag wins over it."""
    fixed = os.path.join(REPO, ".jax_cache")
    a = _probe_cache(tmp_path)
    b = _probe_cache(tmp_path, cwd=REPO)
    assert a["returned"] == b["returned"] == [fixed, fixed]
    assert a["config_dir"] == b["config_dir"] == fixed
    flag = str(tmp_path / "flag")
    assert _probe_cache(tmp_path, flag_dir=flag)["config_dir"] == flag
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ---------------------------------------------------------------------------
# one process per chip: the master never takes it
# ---------------------------------------------------------------------------

_MASTER_PROBE = """
import sys
from elasticdl_tpu.common.args import parse_master_args
from elasticdl_tpu.master.main import start_master
args = parse_master_args([
    "--distribution_strategy=ParameterServerStrategy", "--num_workers=1",
    "--model_zoo=model_zoo", "--model_def=deepfm.deepfm_functional_api",
    "--model_params=vocab_size=64", "--minibatch_size=32",
    "--training_data=synthetic://criteo?n=64&vocab=64",
])
master = start_master(args)
master.stop()
from jax._src import xla_bridge
print("BACKENDS_INITIALIZED", xla_bridge.backends_are_initialized())
"""


def test_master_process_initializes_no_backend():
    """The master imports model code (load_model_spec) — flax, jax and
    the zoo module — but must initialize NO backend: on a chip host the
    process that touches jax holds the chip, and the worker it starts
    could not take it."""
    proc = _run(["-c", _MASTER_PROBE], env={**os.environ, "PYTHONPATH": REPO})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "BACKENDS_INITIALIZED False" in proc.stdout


@pytest.mark.parametrize(
    "num_workers,chips,platforms,refused",
    [
        (2, 1, "", True),       # what hung on the one-chip host
        (2, 4, "tpu", True),    # four chips: still ONE process for all
        (1, 1, "", False),      # one worker drives every chip
        (2, 1, "cpu", False),   # a multi-process CPU world
        (2, 0, "", False),      # no TPU on this host
    ],
)
def test_local_substrate_refuses_workers_sharing_a_chip(
    monkeypatch, num_workers, chips, platforms, refused
):
    from elasticdl_tpu.master import job_runner

    monkeypatch.setattr(job_runner, "_local_tpu_chips", lambda: chips)
    monkeypatch.setenv("JAX_PLATFORMS", "")
    env = {"JAX_PLATFORMS": platforms} if platforms else {}
    if refused:
        with pytest.raises(ValueError, match="one process at a time"):
            job_runner._refuse_workers_sharing_a_chip(num_workers, env)
    else:
        job_runner._refuse_workers_sharing_a_chip(num_workers, env)


def test_tpu_chip_count_comes_from_sysfs_without_a_backend():
    from jax._src import xla_bridge

    from elasticdl_tpu.master import job_runner

    before = xla_bridge.backends_are_initialized()
    assert job_runner._local_tpu_chips() == 0  # this sandbox has no chip
    assert xla_bridge.backends_are_initialized() == before
