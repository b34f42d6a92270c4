"""TensorBoard service + profiler-hook tests (reference:
master/tensorboard_service.py; SURVEY.md §5 names jax.profiler the cheap
observability win)."""

import glob
import json
import os
import struct
import subprocess
import sys
import threading

import numpy as np
import pytest

from elasticdl_tpu.common.profiler import StepProfiler, parse_profile_steps
from elasticdl_tpu.master.tensorboard_service import TensorBoardService


def _read_scalars(log_dir):
    from tensorboard.backend.event_processing.event_accumulator import (
        EventAccumulator,
    )

    acc = EventAccumulator(log_dir)
    acc.Reload()
    return {
        tag: [(e.step, e.value) for e in acc.Scalars(tag)]
        for tag in acc.Tags()["scalars"]
    }


class FakeTaskManager:
    finished_record_count = 128

    def counts(self):
        return {"todo": 3, "doing": 1, "epoch": 2}


def test_scalar_service_writes_event_files(tmp_path):
    log_dir = str(tmp_path / "tb")
    service = TensorBoardService(
        log_dir,
        task_manager=FakeTaskManager(),
        model_version_fn=lambda: 40,
        restarts_fn=lambda: 1,
        sample_interval_s=3600,  # sampling driven manually below
    )
    service.write_dict_to_summary({"auc": 0.75, "accuracy": 0.9}, version=40)
    service._sample_progress()
    service.close()

    assert glob.glob(os.path.join(log_dir, "events.out.tfevents.*"))
    scalars = _read_scalars(log_dir)
    assert scalars["eval/auc"][0] == (40, pytest.approx(0.75))
    assert scalars["eval/accuracy"][0] == (40, pytest.approx(0.9))
    assert scalars["train/records_finished"][0][1] == 128
    assert scalars["train/epoch"][0][1] == 2
    assert scalars["train/worker_restarts"][0][1] == 1


def test_local_job_honors_tensorboard_flag(tmp_path):
    """`--tensorboard_log_dir` end-to-end: a Local training job with
    evaluation writes eval-metric scalars the TB event reader can load."""
    from elasticdl_tpu.client import api

    log_dir = str(tmp_path / "tb")
    rc = api.train(
        [
            "--model_zoo", "model_zoo",
            "--model_def", "mnist.mnist_functional_api",
            "--training_data", "synthetic://mnist?n=256",
            "--validation_data", "synthetic://mnist?n=64&seed=1",
            "--minibatch_size", "32",
            "--num_epochs", "1",
            "--records_per_task", "128",
            "--distribution_strategy", "Local",
            "--tensorboard_log_dir", log_dir,
        ]
    )
    assert rc == 0
    scalars = _read_scalars(log_dir)
    assert any(tag.startswith("eval/") for tag in scalars), scalars.keys()
    assert "train/records_finished" in scalars
    # The final sample (flushed at close) saw the whole dataset trained.
    assert scalars["train/records_finished"][-1][1] == 256


# ---------------------------------------------------------------------------
# The service writes its event files itself
# ---------------------------------------------------------------------------

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_probe(code, *argv):
    proc = subprocess.run(
        [sys.executable, "-c", code, *argv], cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": REPO_ROOT, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


_SERVICE_PROBE = """
import glob, json, os, sys
from elasticdl_tpu.master.tensorboard_service import TensorBoardService

class FakeTaskManager:
    finished_record_count = 128
    def counts(self):
        return {"todo": 3, "doing": 1, "epoch": 2}
    def exec_counters(self):
        return {}

log_dir = sys.argv[1]
service = TensorBoardService(
    log_dir, task_manager=FakeTaskManager(), sample_interval_s=0.01
)
service.bind(model_version_fn=lambda: 7, restarts_fn=lambda: 0)
service.start()
service.write_dict_to_summary({"auc": 0.75}, version=7)
service.write_scalar("train/extra", 1.5, 7)
service._sample_progress()
service.close()
service.write_scalar("train/after_close", 1.0, 8)  # dropped, no raise
print(json.dumps({
    "heavy": sorted({"torch", "tensorflow", "jax"} & set(sys.modules)),
    "files": len(glob.glob(os.path.join(log_dir, "events.out.tfevents.*"))),
}))
"""


def test_service_writes_without_torch_or_tensorflow(tmp_path):
    """The whole point of the service's own writer: every write path,
    sampler thread included, in a process that imports neither torch nor
    TensorFlow (nor jax: the master's own code never does)."""
    log_dir = str(tmp_path / "nested" / "tb")  # created if missing
    out, _ = _run_probe(_SERVICE_PROBE, log_dir)
    assert out == {"heavy": [], "files": 1}
    scalars = _read_scalars(log_dir)
    assert scalars["eval/auc"] == [(7, pytest.approx(0.75))]
    assert scalars["train/extra"] == [(7, pytest.approx(1.5))]
    assert scalars["train/model_version"][-1] == (7, 7.0)
    assert "train/after_close" not in scalars


def _records(path):
    """A TFRecord file parsed by hand: every length and both masked
    CRC32Cs checked, nothing left over."""
    from elasticdl_tpu.master.tensorboard_service import masked_crc32c

    with open(path, "rb") as f:
        data = f.read()
    payloads, at = [], 0
    while at < len(data):
        header = data[at:at + 8]
        (length,) = struct.unpack("<Q", header)
        (header_crc,) = struct.unpack("<I", data[at + 8:at + 12])
        payload = data[at + 12:at + 12 + length]
        assert len(payload) == length, "torn record"
        (payload_crc,) = struct.unpack(
            "<I", data[at + 12 + length:at + 16 + length]
        )
        assert header_crc == masked_crc32c(header)
        assert payload_crc == masked_crc32c(payload)
        payloads.append(payload)
        at += 16 + length
    assert at == len(data)
    return payloads


def _events(path):
    from tensorboard.compat.proto import event_pb2

    return [event_pb2.Event.FromString(p) for p in _records(path)]


def test_crc32c_known_vectors():
    from tensorboard.compat.tensorflow_stub.pywrap_tensorflow import (
        masked_crc32c as tensorboard_masked_crc32c,
    )

    from elasticdl_tpu.master import tensorboard_service as tbs

    # RFC 3720 B.4 check values.
    assert tbs.crc32c(b"123456789") == 0xE3069283
    assert tbs.crc32c(b"") == 0
    assert tbs.crc32c(bytes(32)) == 0x8A9136AA
    assert tbs.crc32c(bytes([0xFF] * 32)) == 0x62A8AB43
    for data in (b"", b"123456789", bytes(range(256)) * 3):
        assert tbs.masked_crc32c(data) == tensorboard_masked_crc32c(data)


def test_event_file_framing(tmp_path):
    log_dir = str(tmp_path / "tb")
    service = TensorBoardService(log_dir, sample_interval_s=3600)
    service.write_dict_to_summary({"auc": 0.75, "loss": 2}, version=40)
    service.write_scalar("train/x", 3, 41)
    service.close()
    (path,) = glob.glob(os.path.join(log_dir, "events.out.tfevents.*"))
    name = os.path.basename(path).split(".")
    assert name[3].isdigit() and name[-2] == str(os.getpid())
    assert name[-1] == "0"
    events = _events(path)
    assert events[0].file_version == "brain.Event:2"
    assert not events[0].HasField("summary")
    got = [
        (e.step, e.summary.value[0].tag, e.summary.value[0].simple_value)
        for e in events[1:]
    ]
    assert got == [
        (40, "eval/auc", 0.75), (40, "eval/loss", 2.0), (41, "train/x", 3.0),
    ]
    assert all(e.wall_time > 0 and len(e.summary.value) == 1
               for e in events[1:])


def test_concurrent_writers_tear_no_record(tmp_path):
    """Servicer threads and the sampler meet under the service's lock:
    four threads x 200 scalars while the sampler runs, all 800 read back
    in each thread's own order."""
    log_dir = str(tmp_path / "tb")
    service = TensorBoardService(
        log_dir, task_manager=FakeTaskManager(), sample_interval_s=0.001
    )
    service.bind(model_version_fn=lambda: 1)
    service.start()

    def write(thread):
        for i in range(200):
            service.write_scalar(f"thread/{thread}", i, i)

    threads = [threading.Thread(target=write, args=(t,)) for t in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)  # switch threads inside every write
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    service.close()
    (path,) = glob.glob(os.path.join(log_dir, "events.out.tfevents.*"))
    by_tag = {}
    for event in _events(path)[1:]:
        value = event.summary.value[0]
        by_tag.setdefault(value.tag, []).append(
            (event.step, value.simple_value)
        )
    for thread in range(4):
        assert by_tag[f"thread/{thread}"] == [(i, float(i)) for i in range(200)]
    assert by_tag["train/records_finished"], "the sampler never ran"
    # And TensorBoard's own reader sees the same 800.
    scalars = _read_scalars(log_dir)
    assert sum(len(scalars[f"thread/{t}"]) for t in range(4)) == 800


def test_unbuildable_writer_drops_scalars_with_one_warning(
    tmp_path, monkeypatch
):
    from elasticdl_tpu.master import tensorboard_service as tbs

    logged = []
    monkeypatch.setattr(
        tbs.logger, "exception", lambda msg, *a: logged.append(msg % a)
    )
    blocker = tmp_path / "a_file"
    blocker.write_text("not a directory")
    service = TensorBoardService(
        str(blocker / "tb"), task_manager=FakeTaskManager(),
        model_version_fn=lambda: 1, sample_interval_s=0.001,
    )
    service.start()
    assert service._thread is None  # nothing to sample for
    service.write_dict_to_summary({"auc": 0.5}, version=1)
    service.write_scalar("train/x", 1.0, 1)
    service._sample_progress()
    service.close()
    assert logged == ["TensorBoard writer unavailable; scalars will be dropped"]


_MASTER_PROBE = """
import json, sys
from elasticdl_tpu import obs
from elasticdl_tpu.common.args import parse_master_args
from elasticdl_tpu.common.model_utils import ModelSpec
from elasticdl_tpu.master.main import start_master
from elasticdl_tpu.obs import tracing
from elasticdl_tpu.worker.master_client import MasterClient

tracing.begin_boot("master.boot")  # as the first line of a main does
log_dir, training_data = sys.argv[1], sys.argv[2]
args = parse_master_args([
    "--distribution_strategy=AllreduceStrategy", "--num_workers=1",
    "--model_zoo=model_zoo", "--model_def=mnist.mnist_functional_api",
    "--training_data=" + training_data, "--records_per_task=32",
    "--tensorboard_log_dir=" + log_dir,
])
spec = None
if not training_data.startswith("synthetic:"):
    # A model module that brings no framework along, on a plain file.
    spec = ModelSpec(module=None, custom_model=None, loss=None,
                     optimizer=None, dataset_fn=None)
master = start_master(args, model_spec=spec)
client = MasterClient(master.addr, worker_id=0)
task = client.get_task()
client.close()
(boot,) = [
    e for e in obs.journal().tail(2000)
    if e.get("event") == "span" and e["name"] == "master.boot"
]
writer = master.tensorboard_service._writer
master.stop()
print(json.dumps({
    "served": [task.start, task.end],
    "heavy_imports": boot["heavy_imports"],
    "writer": writer is not None,
}))
"""


@pytest.mark.parametrize(
    "model,writable,heavy_imports",
    [
        # The master's own boot imports none of the three...
        ("plain", True, []),
        # ...a zoo module of flax layers brings jax, and only jax...
        ("zoo", True, ["jax"]),
        # ...and a log_dir nothing can be written under costs the
        # scalars and the journal's file, never the control plane.
        ("plain", False, []),
    ],
)
def test_master_boots_without_heavy_imports(
    tmp_path, model, writable, heavy_imports
):
    if writable:
        log_dir = tmp_path / "tb"
    else:
        blocker = tmp_path / "a_file"
        blocker.write_text("not a directory")
        log_dir = blocker / "tb"
    training_data = "synthetic://mnist?n=64"  # the zoo module's reader
    if model == "plain":
        training_data = str(tmp_path / "train.csv")
        with open(training_data, "w") as f:
            f.write("1,2\n" * 64)
    out, stderr = _run_probe(_MASTER_PROBE, str(log_dir), training_data)
    assert out["served"] == [0, 32]
    assert out["heavy_imports"] == heavy_imports
    assert out["writer"] is writable
    assert stderr.count("TensorBoard writer unavailable") == (not writable)
    if writable:
        # The journal's own line carries the field, as obs.trace reads it.
        with open(log_dir / "events.jsonl") as f:
            (boot,) = [
                e for e in map(json.loads, f)
                if e.get("name") == "master.boot"
            ]
        assert boot["heavy_imports"] == heavy_imports
        assert glob.glob(str(log_dir / "events.out.tfevents.*"))


class TestProfiler:
    def test_parse(self):
        assert parse_profile_steps("") is None
        assert parse_profile_steps("5,8") == (5, 8)
        with pytest.raises(ValueError):
            parse_profile_steps("8,5")
        with pytest.raises(ValueError):
            parse_profile_steps("abc")

    def test_inactive_without_steps(self, tmp_path):
        profiler = StepProfiler(str(tmp_path), "")
        profiler.before_steps(1)
        profiler.after_steps(1)

    def test_profile_steps_without_log_dir_rejected(self):
        # The silently-dangling-flag failure mode: must be loud.
        with pytest.raises(ValueError, match="tensorboard_log_dir"):
            StepProfiler("", "1,2")
        from elasticdl_tpu.common.args import parse_master_args

        with pytest.raises(ValueError, match="tensorboard_log_dir"):
            parse_master_args(
                ["--model_zoo", "z", "--model_def", "m.f",
                 "--training_data", "t", "--profile_steps", "1,2"]
            )

    def test_malformed_spec_fails_at_parse_time(self):
        """A bad spec must fail the submission, not crash-loop workers."""
        from elasticdl_tpu.common.args import parse_master_args

        with pytest.raises(SystemExit):
            parse_master_args(
                ["--model_zoo", "z", "--model_def", "m.f",
                 "--training_data", "t", "--tensorboard_log_dir", "/tb",
                 "--profile_steps", "20,10"]
            )

    def test_traces_window(self, tmp_path):
        import jax
        import jax.numpy as jnp

        profiler = StepProfiler(str(tmp_path), "2,4", worker_id=0)
        f = jax.jit(lambda x: x * 2 + 1)
        step = 0
        for _ in range(6):
            profiler.before_steps(step)
            f(jnp.ones((8,))).block_until_ready()
            step += 1
            profiler.after_steps(step)
        profiler.stop()  # idempotent (already stopped after step 3)
        trace_dir = os.path.join(str(tmp_path), "profile", "worker_0")
        files = [
            p
            for p in glob.glob(os.path.join(trace_dir, "**"), recursive=True)
            if os.path.isfile(p)
        ]
        assert files, "no trace files written"

    def test_fused_window_rounds_outward(self, tmp_path):
        """A trainer running 8 steps per device call with a 2-step profile
        window traces the whole enclosing window instead of skipping."""
        profiler = StepProfiler(str(tmp_path), "11,13", worker_id=0)
        profiler.before_steps(0, n=8)   # steps 1..8: before window
        assert not profiler._tracing
        profiler.after_steps(8)
        profiler.before_steps(8, n=8)   # steps 9..16: overlaps [11, 13)
        assert profiler._tracing
        profiler.after_steps(16)
        assert not profiler._tracing and profiler._done

    def test_missed_window_warns_not_silent(self, tmp_path, monkeypatch):
        from elasticdl_tpu.common import profiler as profiler_mod

        warnings = []
        monkeypatch.setattr(
            profiler_mod.logger,
            "warning",
            lambda msg, *a: warnings.append(msg % a),
        )
        profiler = StepProfiler(str(tmp_path), "2,3", worker_id=0)
        profiler.before_steps(10, n=8)  # window long gone
        assert profiler._done and not profiler._tracing
        assert any("already passed" in w for w in warnings)


def test_observability_flags_forward_to_workers():
    """The flags must round-trip to worker pods or cluster jobs silently
    lose profiling (the round-1 dangling-flag failure mode)."""
    from elasticdl_tpu.common.args import parse_master_args
    from elasticdl_tpu.master.pod_manager import worker_argv_from_args

    args = parse_master_args(
        [
            "--model_zoo", "z", "--model_def", "m.f",
            "--training_data", "t",
            "--tensorboard_log_dir", "/tb",
            "--profile_steps", "10,20",
        ]
    )
    argv = worker_argv_from_args(args, "localhost:1")(0)
    joined = " ".join(argv)
    assert "--tensorboard_log_dir /tb" in joined
    assert "--profile_steps 10,20" in joined
