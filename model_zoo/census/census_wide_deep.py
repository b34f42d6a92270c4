"""Census Wide&Deep over RAW features — the preprocessing-layer showcase.

Parity: the reference's census model built on elasticdl_preprocessing
(model_zoo/census_model_sqlflow: feature-column glue over Hashing /
IndexLookup / Discretization / Normalizer / ConcatenateWithOffset /
RoundIdentity).  Records arrive as raw strings + unscaled floats
(datasets.synthetic_census_reader) and every transform the reference
library provides runs on the way in:

HOST (dataset_fn — strings can't enter a TPU program):
  education -> IndexLookup(vocab)      workclass -> IndexLookup(vocab)
  occupation -> Hashing(64 bins)
DEVICE (inside the jitted model — pure jnp, fuses with the matmuls):
  age -> Discretization(bins)          hours -> RoundIdentity(100)
  capital_gain -> Normalizer           all ids -> ConcatenateWithOffset
                                       -> ONE shared sharded Embedding

The same transform objects serve both training's dataset_fn and serving
(train==serve consistency — asserted in tests/test_preprocessing.py).
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.layers import Embedding
from elasticdl_tpu.parallel import sparse_optim
from elasticdl_tpu.preprocessing import (
    ConcatenateWithOffset,
    Discretization,
    Hashing,
    IndexLookup,
    Normalizer,
    RoundIdentity,
)
from model_zoo import datasets

# ---- HOST transforms (module-level singletons: one source of truth for
# training AND serving) ------------------------------------------------

EDUCATION_LOOKUP = IndexLookup(datasets.CENSUS_EDUCATION, num_oov_indices=1)
WORKCLASS_LOOKUP = IndexLookup(datasets.CENSUS_WORKCLASS, num_oov_indices=1)
OCCUPATION_HASH = Hashing(num_bins=64)

# ---- DEVICE transforms ------------------------------------------------

AGE_BUCKETS = Discretization(
    [18, 25, 30, 35, 40, 45, 50, 55, 60, 65]
)
HOURS_ID = RoundIdentity(max_value=100)
GAIN_NORM = Normalizer.from_stats(mean=3000.0, std=8000.0)

# One shared table: each feature family offset into a disjoint id range.
ID_SPACES = ConcatenateWithOffset(
    [
        EDUCATION_LOOKUP.vocab_size,
        WORKCLASS_LOOKUP.vocab_size,
        OCCUPATION_HASH.num_bins,
        AGE_BUCKETS.num_bins,
        HOURS_ID.max_value,
    ]
)


class CensusWideDeep(nn.Module):
    embedding_dim: int = 8
    hidden: int = 32

    @nn.compact
    def __call__(self, features, train: bool = False):
        # Device-side preprocessing: traced into the same XLA program as
        # the model body.
        age_ids = AGE_BUCKETS(features["age"])
        hour_ids = HOURS_ID(features["hours_per_week"])
        gain = GAIN_NORM(features["capital_gain"])[:, None]
        ids = ID_SPACES(
            [
                features["edu_id"],
                features["work_id"],
                features["occ_id"],
                age_ids,
                hour_ids,
            ]
        )
        total = ID_SPACES.total_id_space

        wide = Embedding(total, 1, combiner="sum", name="wide_embedding")(
            ids
        )[..., 0]
        deep_emb = Embedding(
            total, self.embedding_dim, name="deep_embedding"
        )(ids)
        deep_in = jnp.concatenate(
            [deep_emb.reshape((deep_emb.shape[0], -1)), gain], axis=-1
        )
        x = nn.relu(nn.Dense(self.hidden)(deep_in))
        deep = nn.Dense(1)(x)[..., 0]
        return wide + deep  # logit


def custom_model(embedding_dim: int = 8, hidden: int = 32):
    return CensusWideDeep(embedding_dim=embedding_dim, hidden=hidden)


def preprocess_record(raw: dict) -> dict:
    """Raw census dict -> model features (host transforms applied).  Used
    by dataset_fn for training and directly by serving callers — the SAME
    code path, which is the whole point of the preprocessing library."""
    return {
        "edu_id": EDUCATION_LOOKUP(np.asarray([raw["education"]]))[0],
        "work_id": WORKCLASS_LOOKUP(np.asarray([raw["workclass"]]))[0],
        "occ_id": OCCUPATION_HASH(np.asarray([raw["occupation"]], object))[0],
        "age": np.float32(raw["age"]),
        "hours_per_week": np.float32(raw["hours_per_week"]),
        "capital_gain": np.float32(raw["capital_gain"]),
    }


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
        return preprocess_record(raw), np.int32(label)

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
