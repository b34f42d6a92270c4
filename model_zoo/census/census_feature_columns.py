"""Census Wide&Deep declared through the feature-column glue.

Parity: the reference's census_model_sqlflow variant, which builds the
same model from feature columns (numeric_column / bucketized_column /
categorical_column_with_* / crossed_column / embedding_column) instead of
hand-wired preprocessing calls — the schema is declared ONCE and both the
input pipeline and the embedding-table sizes fall out of it.

The sibling `census_wide_deep.py` is the hand-wired version of the same
model; this module is the declarative one.  Both consume the same raw
synthetic census records.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.layers import Embedding
from elasticdl_tpu.parallel import sparse_optim
from elasticdl_tpu.preprocessing import Normalizer
from elasticdl_tpu.preprocessing.feature_column import (
    FeatureLayer,
    bucketized_column,
    categorical_column_with_hash_bucket,
    categorical_column_with_vocabulary_list,
    crossed_column,
    embedding_column,
    numeric_column,
)
from model_zoo import datasets

# ---- the schema, declared once ----------------------------------------

AGE = numeric_column("age", Normalizer.from_stats(40.0, 15.0))
GAIN = numeric_column("capital_gain", Normalizer.from_stats(3000.0, 8000.0))
HOURS = numeric_column("hours_per_week", Normalizer.from_stats(40.0, 12.0))

EDUCATION = categorical_column_with_vocabulary_list(
    "education", datasets.CENSUS_EDUCATION, num_oov_indices=1
)
WORKCLASS = categorical_column_with_vocabulary_list(
    "workclass", datasets.CENSUS_WORKCLASS, num_oov_indices=1
)
OCCUPATION = categorical_column_with_hash_bucket("occupation", 64)
AGE_BUCKETS = bucketized_column(
    AGE, [18, 25, 30, 35, 40, 45, 50, 55, 60, 65]
)
EDU_X_OCC = crossed_column(["education", "occupation"], 128)

FEATURES = FeatureLayer(
    [
        AGE,
        GAIN,
        HOURS,
        embedding_column(EDUCATION, 8),
        embedding_column(WORKCLASS, 8),
        embedding_column(OCCUPATION, 8),
        embedding_column(AGE_BUCKETS, 8),
        embedding_column(EDU_X_OCC, 8),
    ]
)


class CensusFeatureColumnModel(nn.Module):
    hidden: int = 32

    @nn.compact
    def __call__(self, features, train: bool = False):
        vocab, dim = FEATURES.embedding_specs()["default"]
        wide = Embedding(vocab, 1, combiner="sum", name="wide_embedding")(
            features["cat"]
        )[..., 0]
        deep_emb = Embedding(vocab, dim, name="deep_embedding")(
            features["cat"]
        )
        deep_in = jnp.concatenate(
            [deep_emb.reshape((deep_emb.shape[0], -1)), features["dense"]],
            axis=-1,
        )
        x = nn.relu(nn.Dense(self.hidden)(deep_in))
        return wide + nn.Dense(1)(x)[..., 0]  # logit


def custom_model(hidden: int = 32):
    return CensusFeatureColumnModel(hidden=hidden)


def loss(labels, predictions):
    return optax.sigmoid_binary_cross_entropy(
        predictions, labels.astype(jnp.float32)
    ).mean()


def optimizer(lr: float = 0.01):
    return optax.adam(lr)


def embedding_optimizer(lr: float = 0.01):
    return sparse_optim.adam(lr)


def dataset_fn(dataset, mode, metadata):
    def parse(record):
        raw, label = record
        batch = {k: np.asarray([v]) for k, v in raw.items()}
        inputs = FEATURES(batch)
        return (
            {k: v[0] for k, v in inputs.items()},
            np.int32(label),
        )

    dataset = dataset.map(parse)
    if mode == "training":
        dataset = dataset.shuffle(2048, seed=0)
    return dataset


def eval_metrics_fn():
    from model_zoo.metrics import auc

    return {
        "accuracy": lambda outputs, labels: np.mean(
            (outputs > 0).astype(np.int64) == labels.astype(np.int64)
        ),
        "auc": auc,
    }


def custom_data_reader(data_path: str, **kwargs):
    name, params = datasets.parse_synthetic_path(data_path)
    if name != "census":
        return None
    return datasets.synthetic_census_reader(
        n=params.get("n", 4096), seed=params.get("seed", 0)
    )
