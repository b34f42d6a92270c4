"""Local-mode end-to-end: `elasticdl train` on MNIST DNN (BASELINE config 1).

Parity: the reference's local-mode CI smoke test (SURVEY.md §4) — master +
worker in one process, real gRPC, loss must decrease and eval must report.
"""

import numpy as np
import pytest

from elasticdl_tpu.client import api
from elasticdl_tpu.common.args import parse_master_args
from elasticdl_tpu.common.model_utils import load_model_spec


def _train_args(tmp_path, extra=()):
    return parse_master_args(
        [
            "--model_zoo", "model_zoo",
            "--model_def", "mnist.mnist_functional_api",
            "--distribution_strategy", "Local",
            "--training_data", "synthetic://mnist?n=640",
            "--validation_data", "synthetic://mnist?n=256&seed=9",
            "--records_per_task", "320",
            "--minibatch_size", "32",
            "--num_epochs", "1",
            "--output", str(tmp_path / "model"),
            *extra,
        ]
    )


def test_local_train_end_to_end(tmp_path, dense_step_losses):
    args = _train_args(tmp_path)
    losses = dense_step_losses  # the loss trajectory, step by step
    assert api._run_local(args, mode="training") == 0

    assert len(losses) == 20  # 640 records / 32 batch
    # Loss decreases substantially on the learnable synthetic task.
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.7

    # --output produced a servable artifact a fresh loader can predict from.
    from elasticdl_tpu.serving import load_for_serving

    served = load_for_serving(str(tmp_path / "model"))
    out = np.asarray(served.predict(np.zeros((2, 28, 28, 1), np.float32)))
    assert out.shape == (2, 10) and np.isfinite(out).all()


def test_mnist_subclass_variant_trains(tmp_path):
    """The setup()-style CNN variant (reference: mnist_subclass) runs the
    same contract end to end."""
    args = parse_master_args(
        [
            "--model_zoo", "model_zoo",
            "--model_def", "mnist.mnist_subclass",
            "--distribution_strategy", "Local",
            "--training_data", "synthetic://mnist?n=256",
            "--validation_data", "synthetic://mnist?n=64&seed=1",
            "--records_per_task", "128",
            "--minibatch_size", "32",
            "--num_epochs", "1",
        ]
    )
    assert api._run_local(args, mode="training") == 0


def test_local_evaluate_only(tmp_path):
    args = parse_master_args(
        [
            "--model_zoo", "model_zoo",
            "--model_def", "mnist.mnist_functional_api",
            "--distribution_strategy", "Local",
            "--validation_data", "synthetic://mnist?n=128",
            "--records_per_task", "64",
            "--minibatch_size", "32",
        ]
    )
    assert api._run_local(args, mode="evaluation") == 0


def test_model_spec_loading():
    args = parse_master_args(
        ["--model_zoo", "model_zoo", "--model_def", "mnist.mnist_functional_api"]
    )
    spec = load_model_spec(args)
    model = spec.build_model()
    assert model.hidden_dim == 128
    assert spec.eval_metrics_fn is not None
    assert spec.custom_data_reader is not None


def test_model_params_passthrough():
    args = parse_master_args(
        [
            "--model_zoo", "model_zoo",
            "--model_def", "mnist.mnist_functional_api",
            "--model_params", "hidden_dim=32",
        ]
    )
    spec = load_model_spec(args)
    assert spec.build_model().hidden_dim == 32


def test_per_epoch_eval_and_train_end_callbacks(tmp_path, monkeypatch):
    """evaluation_steps=0 evaluates at each epoch boundary; zoo callbacks()
    run via the TRAIN_END_CALLBACK task."""
    from model_zoo.mnist import mnist_functional_api as zoo

    ran = []
    monkeypatch.setattr(
        zoo, "callbacks", lambda: [lambda worker: ran.append(worker)], raising=False
    )
    from elasticdl_tpu.master import evaluation_service as es_mod

    rounds = []
    original = es_mod.EvaluationService.trigger_evaluation

    def spy(self, model_version):
        rounds.append(model_version)
        return original(self, model_version)

    monkeypatch.setattr(es_mod.EvaluationService, "trigger_evaluation", spy)

    args = parse_master_args(
        [
            "--model_zoo", "model_zoo",
            "--model_def", "mnist.mnist_functional_api",
            "--distribution_strategy", "Local",
            "--training_data", "synthetic://mnist?n=256",
            "--validation_data", "synthetic://mnist?n=64&seed=9",
            "--records_per_task", "128",
            "--minibatch_size", "32",
            "--num_epochs", "3",
        ]
    )
    assert api._run_local(args, mode="training") == 0
    # 2 epoch boundaries (after epochs 0 and 1) + 1 final round.
    assert len(rounds) == 3
    assert len(ran) == 1  # train-end callback ran exactly once


def test_eval_tasks_read_from_validation_reader():
    """EVALUATION tasks must read the validation dataset, not re-read the
    training shards that happen to share names."""
    import numpy as np

    from elasticdl_tpu.data.reader import NumpyDataReader
    from elasticdl_tpu.parallel.elastic import WorldInfo
    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.worker.collective_worker import CollectiveWorker

    train_reader = NumpyDataReader(
        np.zeros((8, 2), np.float32), np.zeros(8, np.int32), shard_name="d"
    )
    val_reader = NumpyDataReader(
        np.ones((8, 2), np.float32), np.ones(8, np.int32), shard_name="d"
    )

    class Spec:
        dataset_fn = staticmethod(lambda ds, mode, meta: ds)

    class Trainer:  # only what the loop asks of it before a step
        apply_every = 1

        def local_block(self, per_rank_batch):
            return per_rank_batch

    worker = CollectiveWorker(
        master_client=None,
        model_spec=Spec(),
        data_reader=train_reader,
        minibatch_size=4,
        world=WorldInfo(
            rank=0, world_size=1, rendezvous_id=0, coordinator_addr=""
        ),
        trainer=Trainer(),
        validation_data_reader=val_reader,
    )
    task = pb.Task(task_id=1, shard_name="d", start=0, end=8, type=pb.EVALUATION)
    from elasticdl_tpu.common.constants import Mode

    batches = list(worker._local_batches(task, Mode.EVALUATION))
    assert len(batches) == 2
    assert all(np.all(f == 1.0) for f, _l, _mask, _real in batches)
    task.type = pb.TRAINING
    train_batches = list(worker._local_batches(task, Mode.TRAINING))
    assert all(np.all(f == 0.0) for f, _l, _mask, _real in train_batches)
