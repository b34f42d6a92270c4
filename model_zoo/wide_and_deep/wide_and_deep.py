"""Wide-and-Deep (census) — model-zoo contract, JAX/flax body.

Parity: the reference's census wide-and-deep
(model_zoo/census_model_sqlflow / wide_and_deep; BASELINE config 3).  The
categorical path uses the framework's sharded Embedding layer
(elasticdl_tpu.layers.Embedding — the `elasticdl.layers.Embedding`
equivalent), so in ParameterServerStrategy the tables shard across every
chip's HBM and updates run through the sparse row-wise optimizers.

Wide part: per-field dim-1 embeddings (a sharded linear-in-one-hot, the
feature-column 'wide' column); deep part: per-field dim-8 embeddings
concatenated with the dense features into an MLP.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.layers import Embedding
from elasticdl_tpu.parallel import sparse_optim
from model_zoo import datasets
from model_zoo.metrics import auc

NUM_DENSE = 13
NUM_CAT = 26
VOCAB = 1000


class WideAndDeep(nn.Module):
    vocab_size: int = VOCAB
    embedding_dim: int = 8
    hidden: int = 64

    @nn.compact
    def __call__(self, features, train: bool = False):
        dense = jnp.asarray(features["dense"], jnp.float32)
        # Offset each field into a disjoint id range of one shared table
        # (the reference's embedding_column with one table per feature
        # group; a single offset table keeps lookups to one gather).
        cats = jnp.asarray(features["cat"], jnp.int32)
        offsets = jnp.arange(cats.shape[-1], dtype=jnp.int32) * self.vocab_size
        flat_ids = cats + offsets[None, :]
        total_vocab = self.vocab_size * cats.shape[-1]

        wide = Embedding(
            total_vocab, 1, combiner="sum", name="wide_embedding"
        )(flat_ids)[..., 0]

        deep_emb = Embedding(
            total_vocab, self.embedding_dim, name="deep_embedding"
        )(flat_ids)
        deep_in = jnp.concatenate(
            [deep_emb.reshape((deep_emb.shape[0], -1)), dense], axis=-1
        )
        x = nn.relu(nn.Dense(self.hidden)(deep_in))
        x = nn.relu(nn.Dense(self.hidden // 2)(x))
        deep = nn.Dense(1)(x)[..., 0]
        return wide + deep  # logit


def custom_model(vocab_size: int = VOCAB, embedding_dim: int = 8, hidden: int = 64):
    return WideAndDeep(
        vocab_size=vocab_size, embedding_dim=embedding_dim, hidden=hidden
    )


def loss(labels, predictions):
    return optax.sigmoid_binary_cross_entropy(
        predictions, labels.astype(jnp.float32)
    ).mean()


def optimizer(lr: float = 0.005):
    return optax.adam(lr)


def embedding_optimizer(lr: float = 0.005):
    return sparse_optim.adam(lr)


def dataset_fn(dataset, mode, metadata):
    def parse(record):
        features, label = record
        return (
            {
                "dense": np.asarray(features["dense"], np.float32),
                "cat": np.asarray(features["cat"], np.int32),
            },
            np.int32(label),
        )

    dataset = dataset.map(parse)
    if mode == "training":
        dataset = dataset.shuffle(2048, seed=0)
    return dataset


def eval_metrics_fn():
    return {
        "accuracy": lambda outputs, labels: np.mean(
            (outputs > 0).astype(np.int64) == labels.astype(np.int64)
        ),
        "auc": auc,
    }


def custom_data_reader(data_path: str, **kwargs):
    name, params = datasets.parse_synthetic_path(data_path)
    if name is None:
        return None
    return datasets.synthetic_ctr_reader(
        n=params.get("n", 4096),
        num_dense=NUM_DENSE,
        num_categorical=NUM_CAT,
        vocab_size=params.get("vocab", VOCAB),
        seed=params.get("seed", 0),
        shard_name="census-synth",
    )
