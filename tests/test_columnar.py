"""Columnar task materialization (data/columnar.py).

The no-per-record-Python data path: reader.read_columns chunks ->
columnar_dataset_fn whole-column transform -> row-view batches.  Pinned
against the per-record dataset path it replaces (same records, same
lockstep determinism), plus a real 2-worker PS cluster job over an ETRF
file proving the worker engages it end to end.
"""

import os
import time

import numpy as np
import pytest

from elasticdl_tpu.data import recordfile
from elasticdl_tpu.data.columnar import (
    ColumnarTask,
    materialize_columnar_task,
    training_permutation,
)
from elasticdl_tpu.data.dataset import Dataset
from model_zoo.deepfm import deepfm_functional_api as zoo


class _Task:
    type = 1  # pb.TRAINING

    def __init__(self, start, end, task_id=0):
        self.start, self.end, self.task_id = start, end, task_id


def _write_criteo(tmp_path, n=200, seed=0):
    layout = zoo.criteo_record_layout()
    rng = np.random.RandomState(seed)
    recs = []
    for _ in range(n):
        recs.append(
            layout.pack(
                dense=rng.rand(zoo.NUM_DENSE).astype(np.float32),
                cat=rng.randint(0, 100, size=zoo.NUM_CAT).astype(np.int32),
                label=[int(rng.rand() > 0.5)],
            )
        )
    path = str(tmp_path / "criteo.etrf")
    recordfile.write_records(path, recs)
    return path


def test_columnar_matches_per_record_eval(tmp_path):
    """Evaluation mode (no shuffle): columnar rows == the per-record
    dataset path rows, in order."""
    path = _write_criteo(tmp_path)
    reader = zoo.CriteoRecordReader(path)
    task = _Task(30, 170)

    columnar = materialize_columnar_task(
        reader, task, zoo.columnar_dataset_fn, "evaluation", None
    )
    assert columnar is not None and columnar.n == 140

    dataset = zoo.dataset_fn(
        Dataset.from_generator(lambda: reader.read_records(task)),
        "evaluation",
        None,
    )
    records = list(dataset)
    assert len(records) == columnar.n
    feats, labels = columnar.slice(0, columnar.n)
    for i, (rf, rl) in enumerate(records):
        np.testing.assert_array_equal(feats["dense"][i], rf["dense"])
        np.testing.assert_array_equal(feats["cat"][i], rf["cat"])
        assert labels[i] == rl


def test_columnar_training_is_deterministic_permutation(tmp_path):
    """Training mode shuffles with a deterministic permutation — identical
    on every call (the lockstep requirement), rows a permutation of the
    eval-order rows."""
    path = _write_criteo(tmp_path)
    reader = zoo.CriteoRecordReader(path)
    task = _Task(0, 200)

    a = materialize_columnar_task(
        reader, task, zoo.columnar_dataset_fn, "training", None
    )
    b = materialize_columnar_task(
        reader, task, zoo.columnar_dataset_fn, "training", None
    )
    np.testing.assert_array_equal(a.features["cat"], b.features["cat"])
    np.testing.assert_array_equal(a.labels, b.labels)

    ordered = materialize_columnar_task(
        reader, task, zoo.columnar_dataset_fn, "evaluation", None
    )
    # The shuffle seed is TASK-DERIVED (identical on every rank, but
    # varying across tasks/epochs — round-5 review fix: a fixed seed
    # replayed the same order every epoch).
    seed = (31 * task.start + task.end) % (2**31)
    perm = training_permutation(200, seed=seed)
    np.testing.assert_array_equal(
        a.features["cat"], ordered.features["cat"][perm]
    )
    np.testing.assert_array_equal(a.labels, ordered.labels[perm])

    # A later epoch of the same range shuffles DIFFERENTLY.
    class _EpochTask:
        start, end, epoch = 0, 200, 1

    later = materialize_columnar_task(
        reader, _EpochTask, zoo.columnar_dataset_fn, "training", None
    )
    assert not np.array_equal(a.features["cat"], later.features["cat"])


def test_columnar_falls_back_without_surface(tmp_path):
    path = _write_criteo(tmp_path, n=10)
    reader = zoo.CriteoRecordReader(path)
    task = _Task(0, 10)
    # No columnar_dataset_fn -> per-record path.
    assert materialize_columnar_task(reader, task, None, "training", None) is None

    class NoColumns:
        pass

    assert (
        materialize_columnar_task(
            NoColumns(), task, zoo.columnar_dataset_fn, "training", None
        )
        is None
    )


def test_columnar_task_slices_are_views():
    feats = {"x": np.arange(20).reshape(10, 2)}
    labels = np.arange(10)
    ct = ColumnarTask(feats, labels)
    f, l = ct.slice(3, 7)
    assert f["x"].base is not None  # view, not copy
    np.testing.assert_array_equal(f["x"], feats["x"][3:7])
    np.testing.assert_array_equal(l, [3, 4, 5, 6])
    with pytest.raises(ValueError):
        ColumnarTask({"x": np.zeros((5, 2))}, np.zeros((4,)))


def test_ps_cluster_job_uses_columnar_path(tmp_path):
    """Real 2-worker PS job over an ETRF file: completes, and both the
    flag-forwarding and the columnar engagement log prove the production
    worker ran the vectorized path."""
    from elasticdl_tpu.common.args import parse_master_args
    from elasticdl_tpu.master.main import start_master
    from elasticdl_tpu.master.pod_manager import (
        LocalProcessManager,
        worker_argv_from_args,
    )
    from elasticdl_tpu.master.rendezvous_server import ElasticRendezvous

    path = _write_criteo(tmp_path, n=256)
    args = parse_master_args([
        "--model_zoo=model_zoo",
        "--model_def=deepfm.deepfm_functional_api",
        f"--training_data={path}",
        "--model_params=vocab_size=100",
        "--records_per_task=64",
        "--minibatch_size=8",
        "--num_workers=2",
        "--distribution_strategy=ParameterServerStrategy",
    ])
    rendezvous = ElasticRendezvous()
    master = start_master(args, rendezvous_server=rendezvous)
    manager = LocalProcessManager(
        num_workers=2,
        worker_argv_fn=worker_argv_from_args(args, master.addr),
        rendezvous=rendezvous,
        task_manager=master.task_manager,
        max_restarts=0,
        worker_env={
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "JAX_PLATFORMS": "cpu",
        },
        log_dir=str(tmp_path / "logs"),
        job_finished_fn=master.task_manager.finished,
    )
    try:
        manager.start()
        deadline = time.time() + 420
        while time.time() < deadline and not master.task_manager.finished():
            time.sleep(0.5)
        assert master.task_manager.finished(), "ETRF PS job did not finish"
    finally:
        manager.stop()
        master.stop()

    logs = ""
    logdir = tmp_path / "logs"
    for f in os.listdir(logdir):
        logs += (logdir / f).read_text()
    assert "Columnar task path engaged" in logs
