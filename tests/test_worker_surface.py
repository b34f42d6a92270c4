"""One worker loop over one trainer surface.

- the layering gate: nothing at or under parallel/ imports worker/, and
  worker/ has exactly one train-task loop;
- every strategy's worker is what worker/main's one builder builds: the
  same loop over a trainer that has the whole `Trainer` surface, with the
  `worker.step` fault site and the train-side drift sketch in it;
- how many failed tasks the loop rides through is the builder's decision;
- a `state.pkl` written before `TrainState` moved restores to it.
"""

import ast
import os
import pickle
import sys
import types
from typing import Any, NamedTuple

import numpy as np
import pytest

from elasticdl_tpu.common import faults
from elasticdl_tpu.common.args import parse_master_args
from elasticdl_tpu.common.model_utils import load_model_spec
from elasticdl_tpu.data.reader import build_data_reader
from elasticdl_tpu.proto import elasticdl_pb2 as pb

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO_ROOT, "elasticdl_tpu")
STRATEGIES = ("Local", "AllreduceStrategy", "ParameterServerStrategy")


# ---------------------------------------------------------------------------
# Layering
# ---------------------------------------------------------------------------


def _modules_under(*subpackages):
    for sub in subpackages:
        for root, _dirs, files in os.walk(os.path.join(PACKAGE, sub)):
            for name in sorted(files):
                if name.endswith(".py"):
                    path = os.path.join(root, name)
                    with open(path, encoding="utf-8") as f:
                        yield path, ast.parse(f.read(), filename=path)


def _imported_modules(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            for alias in node.names:
                yield f"{node.module}.{alias.name}"


def test_nothing_below_the_worker_imports_it():
    offenders = [
        f"{os.path.relpath(path, REPO_ROOT)} imports {module}"
        for path, tree in _modules_under(
            "parallel", "ops", "layers", "checkpoint", "data", "serving"
        )
        for module in _imported_modules(tree)
        if module.split(".")[:2] == ["elasticdl_tpu", "worker"]
    ]
    assert offenders == []


def test_the_worker_has_one_train_task_loop():
    loops = [
        f"{os.path.relpath(path, REPO_ROOT)}:{node.name}"
        for path, tree in _modules_under("worker")
        for node in ast.walk(tree)
        if isinstance(node, ast.ClassDef)
        and any(
            isinstance(member, ast.FunctionDef)
            and member.name == "_process_train_task"
            for member in node.body
        )
    ]
    assert loops == ["elasticdl_tpu/worker/collective_worker.py:CollectiveWorker"]


# ---------------------------------------------------------------------------
# The one builder, under every strategy
# ---------------------------------------------------------------------------


class _ScriptedMaster:
    """A master client that hands out `tasks`, then says the job is done,
    and grants a world of one to whoever asks for a rank."""

    worker_id = 0

    def __init__(self, tasks):
        from elasticdl_tpu.common.grpc_utils import RetryStats

        self.retry_stats = RetryStats()
        self._tasks = list(tasks)
        self.results = []  # (task_id, err_message) as reported

    def get_comm_rank(self, host=""):
        return pb.GetCommRankResponse(
            rank_id=0, world_size=1, rendezvous_id=1, coordinator_addr=""
        )

    def get_task(self, task_type=pb.TRAINING):
        return self._tasks.pop(0) if self._tasks else pb.Task(task_id=-1)

    def report_task_result_best_effort(
        self, task_id, err_message="", exec_counters=None, trace_id=""
    ):
        self.results.append((task_id, err_message))
        return True

    def report_version(self, model_version):
        pass

    def report_worker_liveness(self, host, rendezvous_id, telemetry_json=""):
        return False


def _build(strategy, tasks):
    from elasticdl_tpu.worker.main import _build_collective_worker

    args = parse_master_args([
        "--model_zoo=model_zoo",
        "--model_def=deepfm.deepfm_functional_api",
        "--training_data=synthetic://criteo?n=64&vocab=64",
        "--model_params=vocab_size=64",
        "--minibatch_size=16",
        f"--distribution_strategy={strategy}",
    ])
    spec = load_model_spec(args)
    reader = build_data_reader(args, spec, args.training_data)
    (shard,) = reader.shard_names()
    master = _ScriptedMaster(
        pb.Task(task_id=i + 1, type=pb.TRAINING, shard_name=shard,
                start=start, end=end)
        for i, (start, end) in enumerate(tasks)
    )
    return _build_collective_worker(args, spec, reader, master), master


@pytest.fixture
def armed():
    """Arms the fault registry (with a spec that never fires here, so the
    sites only count their calls) and a train-side drift monitor."""
    from elasticdl_tpu.obs import quality

    monitor = quality.DriftMonitor(bins=16, origin="test")
    faults.install("worker.step:latency=0@1000000")
    quality.enable_train_sketch(monitor)
    try:
        yield monitor
    finally:
        quality.enable_train_sketch(None)
        faults.clear()


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_every_strategy_builds_the_one_loop(strategy, armed):
    import jax

    from elasticdl_tpu.parallel.trainer import Trainer
    from elasticdl_tpu.worker.collective_worker import CollectiveWorker

    worker, master = _build(strategy, [(0, 48)])
    assert type(worker) is CollectiveWorker
    assert isinstance(worker.trainer, Trainer)
    assert worker.trainer.mesh.devices.size == (
        1 if strategy == "Local" else len(jax.devices())
    )
    worker.run()
    assert master.results == [(1, "")]
    assert worker.trainer.step == 3  # 48 records / 16
    # once a minibatch, in the loop and not in a trainer
    assert faults.call_count("worker.step") == 3
    assert armed._train.total_ids > 0


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_failed_tasks_ridden_through_are_the_builders_decision(strategy):
    worker, master = _build(strategy, [(0, 16), (16, 32)])

    def process(task):
        if task.task_id == 1:
            raise RuntimeError("task 1 broke")
        return {}

    worker._process_task = process
    if strategy == "Local":
        # nothing would relaunch it: report the failure, take the next
        worker.run()
        assert master.results == [(1, "task 1 broke"), (2, "")]
    else:
        # a supervisor re-forms the world: report, then die
        with pytest.raises(RuntimeError, match="task 1 broke"):
            worker.run()
        assert master.results == [(1, "task 1 broke")]


# ---------------------------------------------------------------------------
# A checkpoint from before TrainState moved
# ---------------------------------------------------------------------------

_OLD_MODULE = "elasticdl_tpu.worker.trainer"


@pytest.fixture
def old_train_state():
    """`TrainState` as the tree before this one defined it: importable
    under the old module's name only while a checkpoint is being written."""

    class TrainState(NamedTuple):
        step: Any
        params: Any
        opt_state: Any
        model_state: Any

    TrainState.__module__ = _OLD_MODULE
    TrainState.__qualname__ = "TrainState"
    module = types.ModuleType(_OLD_MODULE)
    module.TrainState = TrainState
    sys.modules[_OLD_MODULE] = module
    try:
        yield TrainState
    finally:
        sys.modules.pop(_OLD_MODULE, None)


@pytest.mark.parametrize("layout", ["raw", "plain"])
def test_state_saved_under_the_old_name_restores(
    layout, old_train_state, tmp_path
):
    from elasticdl_tpu.checkpoint.saver import (
        CheckpointSaver,
        write_integrity_manifest,
    )
    from elasticdl_tpu.parallel.trainer import TrainState

    params = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    old = old_train_state(
        np.int32(7), params, ({"mu": params["w"] * 2},),
        {"batch_stats": {"mean": np.ones(3, np.float32)}},
    )
    saver = CheckpointSaver(str(tmp_path))
    if layout == "raw":
        step_dir = saver.save(old, 7)
    else:  # one pickle.dump of the tree, as the saver wrote it before
        step_dir = os.path.join(str(tmp_path), f"step_{7:012d}")
        os.makedirs(step_dir)
        with open(os.path.join(step_dir, "state.pkl"), "wb") as f:
            pickle.dump(old, f)
        write_integrity_manifest(step_dir, ["state.pkl"])
    with open(os.path.join(step_dir, "state.pkl"), "rb") as f:
        assert _OLD_MODULE.encode() in f.read()
    del sys.modules[_OLD_MODULE]  # the old module is gone, as in this tree
    restored, step = saver.load_latest()
    assert step == 7 and type(restored) is TrainState
    assert int(restored.step) == 7
    np.testing.assert_array_equal(restored.params["w"], params["w"])
    np.testing.assert_array_equal(
        restored.opt_state[0]["mu"], params["w"] * 2
    )
    np.testing.assert_array_equal(
        restored.model_state["batch_stats"]["mean"], np.ones(3, np.float32)
    )
