"""DeepFM (Criteo/DAC click-through) — model-zoo contract, JAX/flax body.

Parity: model_zoo/deepfm_functional_api in the reference (BASELINE config
4, the north-star workload).  TPU-first body:

- 26 categorical fields share one offset embedding table through the
  framework's sharded Embedding layer — in ParameterServerStrategy the
  table (vocab 26M+ at Criteo scale) spreads over every chip's HBM and is
  updated sparsely, never materializing a dense gradient.
- FM second-order term uses the sum-square trick (one elementwise fuse, no
  pairwise blowup); all matmuls are MXU-shaped.
- Numeric features get per-field linear + embedding projections.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.data.reader import FixedWidthEtrfReader
from elasticdl_tpu.layers import Embedding
from elasticdl_tpu.parallel import sparse_optim
from model_zoo import datasets

NUM_DENSE = 13
NUM_CAT = 26
VOCAB = 1000


# Auto table-layout crossover (see DeepFM.split_tables): measured on the
# v5e at the 26M-row probe (BASELINE.md "table-scale probe").  Strict
# per-step mode pays table-sized streaming passes whose cost scales with
# DESTINATION BLOCKS; merging the dim-1 linear into a dim-9 (pad 16)
# table doubled those blocks (1.83M -> 3.25M) and strict throughput fell
# 192k -> 157k.  Windowed mode (sparse_apply_every > 1) amortizes the
# passes, so the merged table's halved count-bound cost wins there.
SPLIT_TABLE_ROWS = 10_000_000


class DeepFM(nn.Module):
    vocab_size: int = VOCAB
    embedding_dim: int = 8
    hidden: int = 128
    # Table layout: the combined 1+dim table is the default — one
    # lookup gather + one grad scatter per step where the reference's
    # split linear+fm layout paid two (the dual-lookup waste the old
    # comment documented).  `split_tables` stays as the COMPAT FLAG:
    # checkpoints saved under the split layout restore only into a
    # split build (ps_trainer's manifest check names this flag), so
    # pass --model_params split_tables=true to keep reading them.
    # None = auto: merged everywhere EXCEPT the one measured exception
    # — strict per-step apply at >SPLIT_TABLE_ROWS rows under the XLA
    # sparse path, where the per-step table-sized streaming pass
    # charges by destination blocks (merged doubles them; 192k->157k,
    # BASELINE.md table-scale probe).  The fused kernel path
    # (--sparse_kernel=fused) is touched-row-bound with no streaming
    # pass, so it keeps the merged layout at every scale.
    split_tables: bool | None = None
    sparse_apply_every: int = 1
    # 'xla' | 'fused' | 'auto' | None (process default) — threaded into
    # the Embedding layers (lookup/FM kernels) and the auto layout rule.
    sparse_kernel: str | None = None
    # The job mesh (model_utils forwards it to mesh-aware models):
    # under the fused kernel on a multi-device mesh the Embedding ops
    # dispatch per-shard bodies through shard_map (tables over the
    # `model` axis — ops/sparse_embedding.py "Sharded dispatch").
    mesh: Any = None

    def _resolved_kernel(self) -> str:
        from elasticdl_tpu.ops import sparse_embedding as ske

        return ske.resolve_kernel(self.sparse_kernel)

    def _split(self, total_vocab: int) -> bool:
        if self.split_tables is not None:
            return self.split_tables
        return (
            self.sparse_apply_every <= 1
            and total_vocab > SPLIT_TABLE_ROWS
            and self._resolved_kernel() != "fused"
        )

    @nn.compact
    def __call__(self, features, train: bool = False):
        dense = jnp.asarray(features["dense"], jnp.float32)  # [B, 13]
        cats = jnp.asarray(features["cat"], jnp.int32)       # [B, 26]
        batch = cats.shape[0]
        offsets = jnp.arange(cats.shape[-1], dtype=jnp.int32) * self.vocab_size
        flat_ids = cats + offsets[None, :]
        total_vocab = self.vocab_size * cats.shape[-1]

        first_dense = nn.Dense(1, name="linear_dense")(dense)[..., 0]
        dense_emb = nn.DenseGeneral(
            (NUM_DENSE, self.embedding_dim), axis=-1, name="dense_projection"
        )(dense[:, None, :])[:, 0]                           # [B, 13, d]
        if self._split(total_vocab):
            # TWO tables (the reference's layout: linear + fm) — the
            # xla-strict->10M-row exception only (see split_tables):
            # a second lookup gather + grad scatter (~25 ns/row each)
            # buys halved destination blocks for the per-step streaming
            # passes (1.83M vs 3.25M at the 26M probe).
            linear = Embedding(
                total_vocab, 1, name="linear_embedding",
                sparse_kernel=self.sparse_kernel, mesh=self.mesh,
            )(flat_ids)                                      # [B, 26, 1]
            first_cat = jnp.sum(linear[..., 0], axis=-1)     # [B]
            cat_emb = Embedding(
                total_vocab, self.embedding_dim, name="fm_embedding",
                sparse_kernel=self.sparse_kernel, mesh=self.mesh,
            )(flat_ids)                                      # [B, 26, d]
            # FM second order: 0.5 * (sum^2 - sum-of-squares) over all
            # 39 fields at once.
            fields = jnp.concatenate([cat_emb, dense_emb], axis=1)
            sum_fields = jnp.sum(fields, axis=1)
            second = 0.5 * jnp.sum(
                sum_fields * sum_fields
                - jnp.sum(fields * fields, axis=1),
                axis=-1,
            )
        else:
            # ONE merged table of dim 1+d (the default layout): lane 0
            # is the first-order (linear) weight, lanes 1..d the
            # FM/deep field vector — one gather + one scatter per step
            # instead of two.  fm_interaction returns the activations
            # (deep tower input) AND the categorical FM partial sums
            # from the same pass — under the fused kernel those sums
            # accumulate in VMEM during the lookup, so the FM term
            # never re-reads [B, 26, 1+d] from HBM.  The dense fields'
            # sums compose algebraically:
            #   (S_cat + S_dense)^2 - (SS_cat + SS_dense)
            cat_acts, first_cat, sum_v, sum_sq = Embedding(
                total_vocab, 1 + self.embedding_dim, name="fm_embedding",
                sparse_kernel=self.sparse_kernel, fm_interaction=True,
                mesh=self.mesh,
            )(flat_ids)                                      # [B, 26, 1+d]
            cat_emb = cat_acts[..., 1:]                      # [B, 26, d]
            fields = jnp.concatenate([cat_emb, dense_emb], axis=1)
            sum_dense = jnp.sum(dense_emb, axis=1)           # [B, d]
            sumsq_dense = jnp.sum(dense_emb * dense_emb, axis=1)
            total_sum = sum_v + sum_dense
            second = 0.5 * jnp.sum(
                total_sum * total_sum - (sum_sq + sumsq_dense), axis=-1
            )

        # Deep tower over the flattened field embeddings.
        x = fields.reshape((batch, -1))
        x = nn.relu(nn.Dense(self.hidden)(x))
        x = nn.relu(nn.Dense(self.hidden // 2)(x))
        deep = nn.Dense(1)(x)[..., 0]

        return first_cat + first_dense + second + deep  # logit


def custom_model(
    vocab_size: int = VOCAB,
    embedding_dim: int = 8,
    hidden: int = 128,
    split_tables: bool | None = None,
    sparse_apply_every: "int | str" = 1,
    sparse_kernel: "str | None" = None,
    mesh: Any = None,
):
    """`sparse_apply_every` arrives from the job flag (model_utils
    forwards it to models declaring the parameter) and drives the auto
    table layout; `--model_params split_tables=...` overrides.  The
    flag's 'auto' resolves here from the model's own vocabulary using
    the SAME threshold the trainer resolves with at init
    (ps_trainer.AUTO_APPLY_TABLE_ROWS == SPLIT_TABLE_ROWS), so layout
    and apply mode can't diverge: auto at <=10M rows is strict+merged,
    above it windowed+merged — auto never reaches the strict-large
    regime the split layout exists for."""
    if sparse_apply_every == "auto":
        from elasticdl_tpu.parallel.ps_trainer import (
            AUTO_APPLY_TABLE_ROWS,
            AUTO_APPLY_W,
        )

        # Count the rows the TRAINER will count: it sums rows over the
        # actual tables at init, so a forced split layout
        # (--model_params split_tables=true) holds 2x total_vocab rows
        # (linear + fm).  Resolving from the same count keeps layout
        # and apply mode consistent in every configuration.
        total_rows = vocab_size * NUM_CAT * (2 if split_tables else 1)
        sparse_apply_every = (
            1 if total_rows <= AUTO_APPLY_TABLE_ROWS else AUTO_APPLY_W
        )
    return DeepFM(
        vocab_size=vocab_size,
        embedding_dim=embedding_dim,
        hidden=hidden,
        split_tables=split_tables,
        sparse_apply_every=sparse_apply_every,
        sparse_kernel=sparse_kernel,
        mesh=mesh,
    )


def loss(labels, predictions):
    return optax.sigmoid_binary_cross_entropy(
        predictions, labels.astype(jnp.float32)
    ).mean()


def optimizer(lr: float = 0.001):
    return optax.adam(lr)


def embedding_optimizer(lr: float = 0.001):
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
        dataset = dataset.shuffle(4096, seed=0)
    return dataset


def columnar_dataset_fn(columns, mode, metadata, seed: int = 0):
    """Vectorized counterpart of dataset_fn for the columnar task path
    (data/columnar.py): whole-column casts + one deterministic
    permutation instead of per-record map + buffered shuffle.  `seed`
    arrives task/epoch-derived (same on every rank) so the shuffle
    order varies across epochs instead of replaying."""
    from elasticdl_tpu.data.columnar import training_permutation

    features = {
        "dense": np.ascontiguousarray(columns["dense"], np.float32),
        "cat": np.ascontiguousarray(columns["cat"], np.int32),
    }
    labels = columns["label"][:, 0].astype(np.int32)
    if mode == "training":
        perm = training_permutation(len(labels), seed=seed)
        features = {k: v[perm] for k, v in features.items()}
        labels = labels[perm]
    return features, labels


def eval_metrics_fn():
    from model_zoo.metrics import auc

    return {
        "accuracy": lambda outputs, labels: np.mean(
            (outputs > 0).astype(np.int64) == labels.astype(np.int64)
        ),
        "auc": auc,
    }


# Fixed-width binary layout of one Criteo record in an ETRF file —
# written by `pack`, parsed by the vectorized columnar path (~1.9M rec/s
# per host; BASELINE.md data-plane section).
def criteo_record_layout():
    from elasticdl_tpu.data.vectorized import RecordLayout

    return RecordLayout([
        ("dense", np.float32, NUM_DENSE),
        ("cat", np.int32, NUM_CAT),
        ("label", np.uint8, 1),
    ])


class CriteoRecordReader(FixedWidthEtrfReader):
    """Shard-addressable reader over Criteo-layout ETRF (one file or a
    directory of shard files — the reference's RecordIO-dir layout)
    using the vectorized buffer path: whole chunks parse into columnar
    numpy in one pass, records yield as cheap row views — no per-record
    byte objects or struct unpacking."""

    def __init__(self, path: str, **kwargs):
        super().__init__(path, **kwargs)
        self._layout = criteo_record_layout()

    def layout(self):
        return self._layout

    def _row(self, cols, i):
        return (
            {"dense": cols["dense"][i], "cat": cols["cat"][i]},
            np.int32(cols["label"][i, 0]),
        )


def custom_data_reader(data_path: str, **kwargs):
    name, params = datasets.parse_synthetic_path(data_path)
    if name is not None:
        return datasets.synthetic_ctr_reader(
            n=params.get("n", 4096),
            num_dense=NUM_DENSE,
            num_categorical=NUM_CAT,
            vocab_size=params.get("vocab", VOCAB),
            seed=params.get("seed", 0),
            shard_name="criteo-synth",
        )
    from elasticdl_tpu.data.reader import is_etrf_dir

    path = data_path.removeprefix("recordio:")
    if path.endswith(".etrf") or is_etrf_dir(path):
        return CriteoRecordReader(path)
    return None
