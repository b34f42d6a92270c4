"""ResNet-50 model-zoo config (BASELINE config 5).

Parity surface: model_zoo/resnet50_subclass in the reference.  CPU tests
use small images/classes (the architecture is size-agnostic past the
stem); the bench exercises the real 224x1000 shape on the chip.
"""

import numpy as np
import optax
import pytest

from tests.conftest import one_device_trainer
from model_zoo import datasets
from model_zoo.resnet50 import resnet50_subclass as zoo


def test_architecture_shapes():
    """50 layers: 1 stem conv + 3*(3+4+6+3) bottleneck convs + fc, with
    4x filter expansion per stage."""
    import jax
    import jax.numpy as jnp

    model = zoo.custom_model(num_classes=10, use_bf16=False)
    x = jnp.zeros((2, 64, 64, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x)
    out = model.apply(variables, x)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32
    n_conv = sum(1 for k in _flat_keys(variables["params"]) if "Conv" in k)
    # stem + 16 blocks x 3 convs + projection shortcuts (4 stages)
    assert n_conv == 1 + 16 * 3 + 4
    n_params = sum(
        int(np.prod(p.shape)) for p in jax.tree.leaves(variables["params"])
    )
    assert 23_000_000 < n_params < 24_500_000  # ~23.5M at 10 classes


def _flat_keys(tree, prefix=""):
    keys = []
    for name, value in tree.items():
        path = f"{prefix}/{name}"
        if isinstance(value, dict):
            keys.extend(_flat_keys(value, path))
        else:
            keys.append(path)
    return keys


def test_trains_and_bn_state_updates():
    model = zoo.custom_model(num_classes=4, use_bf16=True)
    trainer = one_device_trainer(
        model, zoo.loss, optax.sgd(0.05, momentum=0.9), seed=0
    )
    rng = np.random.RandomState(0)
    # Raw uint8 pixels: the input contract since round 5 — the model
    # normalizes (0-255 scale) on device.
    images = rng.randint(0, 256, size=(8, 32, 32, 3)).astype(np.uint8)
    labels = rng.randint(0, 4, size=8).astype(np.int32)
    trainer.ensure_initialized(images)
    bn_before = {
        k: v.copy()
        for k, v in trainer.get_variables_numpy().items()
        if "batch_stats" in k
    }
    assert bn_before, "BatchNorm state must live in model_state"
    losses = [float(trainer.train_step(images, labels)) for _ in range(5)]
    assert losses[-1] < losses[0], losses
    bn_after = trainer.get_variables_numpy()
    assert any(
        np.abs(bn_after[k] - v).max() > 0 for k, v in bn_before.items()
    ), "BN running stats never updated"


def test_synthetic_imagenet_reader_learnable():
    reader = datasets.synthetic_imagenet_reader(
        n=32, image_size=64, num_classes=8, seed=1
    )
    assert reader.create_shards() == {"imagenet-synth": 32}

    class _Task:
        shard_name, start, end = "imagenet-synth", 0, 32

    records = list(reader.read_records(_Task()))
    assert len(records) == 32
    image, label = records[0]
    assert image.shape == (64, 64, 3) and image.dtype == np.uint8
    # Deterministic across readers with the same seed.
    again = list(
        datasets.synthetic_imagenet_reader(
            n=32, image_size=64, num_classes=8, seed=1
        ).read_records(_Task())
    )
    np.testing.assert_array_equal(records[5][0], again[5][0])


def test_custom_data_reader_path_roundtrip():
    reader = zoo.custom_data_reader("synthetic://imagenet?n=16&size=64&classes=8")
    assert reader is not None
    assert reader.create_shards() == {"imagenet-synth": 16}
    assert zoo.custom_data_reader("/real/path.csv") is None
