"""CIFAR-10 ResNet-20 zoo model tests (BASELINE config 2).

Covers the batch-norm (mutable model_state) path through both trainers —
the mnist DNN has no non-trainable state, so this is the coverage for it.
"""

import numpy as np

from elasticdl_tpu.parallel import DataParallelTrainer, MeshConfig, build_mesh
from tests.conftest import one_device_trainer
from model_zoo.cifar10 import cifar10_functional_api as zoo
from model_zoo import datasets


def _batch(n=16, seed=0):
    reader = datasets.synthetic_cifar10_reader(n=n, seed=seed)
    records = [
        r
        for r in zoo.dataset_fn(
            _as_dataset(reader), "training", reader.metadata
        )
    ]
    feats = np.stack([r[0] for r in records])
    labels = np.stack([r[1] for r in records])
    return feats, labels


def _as_dataset(reader):
    from elasticdl_tpu.data.dataset import Dataset
    from elasticdl_tpu.proto import elasticdl_pb2 as pb

    task = pb.Task(task_id=1, shard_name="cifar-synth", start=0, end=1 << 30)
    return Dataset.from_generator(lambda: reader.read_records(task))


def test_resnet20_trains_and_updates_batch_stats():
    trainer = one_device_trainer(
        zoo.custom_model(use_bf16=False), zoo.loss, zoo.optimizer(lr=0.05)
    )
    feats, labels = _batch(16)
    losses = [float(trainer.train_step(feats, labels)) for _ in range(8)]
    assert losses[-1] < losses[0]
    state = trainer.state
    assert "batch_stats" in state.model_state
    # Running stats actually moved away from init.
    leaves = [np.asarray(x) for x in __import__("jax").tree.leaves(
        state.model_state["batch_stats"])]
    assert any(np.abs(leaf).sum() > 0 for leaf in leaves)


def test_resnet20_dp_matches_single_device():
    mesh = build_mesh(MeshConfig())
    dp = DataParallelTrainer(
        zoo.custom_model(use_bf16=False), zoo.loss, zoo.optimizer(), mesh, seed=0
    )
    single = one_device_trainer(
        zoo.custom_model(use_bf16=False), zoo.loss, zoo.optimizer(), seed=0
    )
    feats, labels = _batch(16, seed=1)
    # Reduction-order differences through batch-norm rsqrt amplify float
    # drift step over step; the first step must agree tightly, later steps
    # within growing slack.
    for step, rtol in enumerate((1e-3, 8e-3, 3e-2)):
        dp_loss = dp.train_step(feats, labels)
        s_loss = single.train_step(feats, labels)
        np.testing.assert_allclose(
            float(dp_loss), float(s_loss), rtol=rtol, atol=1e-4,
            err_msg=f"step {step}",
        )


def test_resnet20_bf16_forward_finite():
    trainer = one_device_trainer(
        zoo.custom_model(use_bf16=True), zoo.loss, zoo.optimizer()
    )
    feats, labels = _batch(8)
    loss = trainer.train_step(feats, labels)
    assert np.isfinite(float(loss))
    outputs = trainer.eval_step(feats)
    assert outputs.dtype == np.float32 and outputs.shape == (8, 10)
