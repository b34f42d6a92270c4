"""Plain reference of DeepFM (Guo et al. 2017) as `model_zoo/deepfm` builds
it: float32 `jax.numpy`, no kernels, no packed tables; the step's least
work; and how the program's own outputs are had from a job's checkpoint.

logit = first_order(cat) + linear(dense) + FM_second_order + deep_tower
  - each categorical id owns one row of 1+d floats: lane 0 the first-order
    weight, lanes 1..d the field vector (ids are offset by field x vocab);
  - each dense feature projects to a field vector: [13] -> [13, d];
  - FM: 0.5 * sum_d((sum_f v)^2 - sum_f v^2) over the 26+13 field vectors;
  - tower: flatten [39, d] -> h1 -> h2 -> 1 with ReLU (`hidden`).

The weights come straight from the job's checkpoint files, read with numpy:
`dense.pkl` (the flax params) and the table rows the sample touches from
`shards_p*.npz`.  Tables are stored packed: a row of `dim` floats padded to
the next power of two, 128 // padded rows to a 128-lane block, row-major,
so logical row r is `packed.reshape(-1, padded)[r, :dim]`.  Both table
layouts the program can choose are read: one merged table of 1+d, or a
`linear_embedding` of 1 and an `fm_embedding` of d.

`forward(..., precision=)` computes at `highest` (every product in float32)
or as the configuration states the program computes (`stated`: matmul
operands rounded to bfloat16, float32 accumulation, everything else
float32); `stated_bf16_rows` is `stated` with the table rows rounded to
bfloat16 too: what a table kept in a lower precision than stated reads.
"""

from __future__ import annotations

import glob
import os
import pickle

import numpy as np


def sample(seed: int, rows: int, model: dict) -> dict:
    """Seeded features: half the ids of a row are small (rows that training
    has surely touched), half are anywhere in the vocabulary."""
    rng = np.random.default_rng(seed)
    vocab, fields = model["vocab_size"], model["num_categorical"]
    hot = rng.integers(0, min(vocab, 64), size=(rows, fields))
    cold = rng.integers(0, vocab, size=(rows, fields))
    ids = np.where(rng.random((rows, fields)) < 0.5, hot, cold)
    return {
        "dense": rng.random((rows, model["num_dense"]), dtype=np.float32),
        "cat": ids.astype(np.int32),
    }


def _padded(dim: int) -> int:
    p = 1
    while p < dim:
        p *= 2
    return p


def _table_rows(step_dir: str, table: str, dim: int, rows: np.ndarray):
    """Logical rows `rows` of packed table `table`, from the shard files."""
    padded = _padded(dim)
    per_block = 128 // padded
    out = np.zeros((len(rows), dim), np.float32)
    found = np.zeros(len(rows), bool)
    prefix = f"table|{table}|"
    for path in sorted(glob.glob(os.path.join(step_dir, "shards_p*.npz"))):
        with np.load(path) as shards:
            for key in shards.files:
                if not key.startswith(prefix):
                    continue
                lo, hi = (int(x) for x in key[len(prefix):].split("|"))
                block = rows // per_block
                here = (block >= lo) & (block < hi) & ~found
                if not here.any():
                    continue
                if shards[key].dtype != np.float32:
                    raise ValueError(f"{key} is stored as {shards[key].dtype}")
                logical = shards[key].reshape(-1, padded)
                local = rows[here] - lo * per_block
                out[here] = logical[local, :dim]
                found |= here
    if not found.all():
        raise ValueError(f"{(~found).sum()} rows of {table} not in {step_dir}")
    return out


def weights(step_dir: str, features: dict, model: dict, program_state=None):
    with open(os.path.join(step_dir, "dense.pkl"), "rb") as f:
        params = pickle.load(f)["params"]
    vocab, d = model["vocab_size"], model["embedding_dim"]
    ids = features["cat"].astype(np.int64) + (
        np.arange(features["cat"].shape[1], dtype=np.int64) * vocab
    )
    flat = ids.reshape(-1)
    if "linear_embedding" in params:
        first = _table_rows(step_dir, "linear_embedding/embedding", 1, flat)
        vec = _table_rows(step_dir, "fm_embedding/embedding", d, flat)
        rows = np.concatenate([first, vec], axis=1)
    else:
        rows = _table_rows(step_dir, "fm_embedding/embedding", 1 + d, flat)
    plain = {
        k: {n: np.asarray(a, np.float32) for n, a in v.items()}
        for k, v in params.items()
        if k not in ("fm_embedding", "linear_embedding")
    }
    # The configuration states a float32 table.  Outputs cannot tell it
    # from a bfloat16 one (the difference is under the matmuls' rounding),
    # the stored bits can: a float32 value is exact in bfloat16 once in 65536.
    coarse = np.mean((rows.view(np.uint32) & 0xFFFF) == 0)
    if coarse > 0.5:
        raise ValueError(
            f"{100 * coarse:.0f}% of the table's values are exact in "
            "bfloat16: the table is kept in a lower precision than stated"
        )
    plain["rows"] = rows.reshape(ids.shape + (1 + d,))
    return plain


def program(args, features):
    """The program's own logits for `features` at the job's last committed
    checkpoint, restored the way a relaunched worker restores it (the
    trainer is built as `worker/main._build_collective_worker` builds it).
    -> (outputs, step, program_state)."""
    from elasticdl_tpu.checkpoint import ShardedCheckpointSaver
    from elasticdl_tpu.common.model_utils import load_model_spec
    from elasticdl_tpu.ops import sparse_embedding as ske
    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.ps_trainer import ShardedEmbeddingTrainer

    spec = load_model_spec(args)
    mesh = build_mesh(MeshConfig(model=args.mesh_model_axis))
    ske.set_default_kernel(args.sparse_kernel or "auto")
    ske.set_dispatch_mesh(mesh)
    trainer = ShardedEmbeddingTrainer(
        model=spec.build_model(mesh=mesh),
        loss_fn=spec.loss,
        optimizer=spec.optimizer(),
        mesh=mesh,
        embedding_optimizer=spec.embedding_optimizer(),
        sparse_apply_every=args.sparse_apply_every,
        sparse_kernel=args.sparse_kernel,
    )
    saver = ShardedCheckpointSaver(args.checkpoint_dir)
    step = saver.latest_step()
    if step is None:
        return None, None, None
    trainer.set_sharded_restore(saver, step)
    return trainer.eval_step(features), step, None


def forward(w: dict, features: dict, model: dict, precision: str = "highest"):
    import jax
    import jax.numpy as jnp

    rows = jnp.asarray(w["rows"])                           # [B, 26, 1+d]
    if precision == "highest":
        def dot(x, kernel, spec=None):
            return jnp.einsum(spec, x, kernel) if spec else x @ kernel
    else:
        def dot(x, kernel, spec=None):
            x = x.astype(jnp.bfloat16)
            kernel = jnp.asarray(kernel).astype(jnp.bfloat16)
            if spec:
                return jnp.einsum(
                    spec, x, kernel, preferred_element_type=jnp.float32
                )
            return jnp.dot(x, kernel, preferred_element_type=jnp.float32)
        if precision == "stated_bf16_rows":
            rows = rows.astype(jnp.bfloat16).astype(jnp.float32)
        elif precision != "stated":
            raise ValueError(f"no precision {precision!r}")

    with jax.default_matmul_precision("highest"):
        dense = jnp.asarray(features["dense"], jnp.float32)
        first_cat = rows[..., 0].sum(axis=1)
        first_dense = (
            dot(dense, w["linear_dense"]["kernel"])
            + w["linear_dense"]["bias"]
        )[:, 0]
        dense_vec = (
            dot(dense, w["dense_projection"]["kernel"], "bi,ifd->bfd")
            + w["dense_projection"]["bias"]
        )
        fields = jnp.concatenate([rows[..., 1:], dense_vec], axis=1)
        total = fields.sum(axis=1)
        second = 0.5 * (total * total - (fields * fields).sum(axis=1)).sum(-1)
        x = fields.reshape(fields.shape[0], -1)
        x = jax.nn.relu(dot(x, w["Dense_0"]["kernel"]) + w["Dense_0"]["bias"])
        x = jax.nn.relu(dot(x, w["Dense_1"]["kernel"]) + w["Dense_1"]["bias"])
        deep = (dot(x, w["Dense_2"]["kernel"]) + w["Dense_2"]["bias"])[:, 0]
        return first_cat + first_dense + second + deep


def step_cost(model: dict, minibatch: int) -> dict:
    """The least a training step needs, from shapes.

    FLOPs: the tower and the projections, forward and backward (3 x 2 x
    multiply-adds).  Bytes: each of the minibatch's B x 26 ids needs its row
    of 1+d floats read for the forward pass, its gradient written and read
    back, and sparse Adam's read and write of the row and its two moments:
    9 passes over B x 26 x (1+d) x 4 bytes.  Duplicate ids are counted once
    per occurrence (an upper bound on what dedup could save is not the
    algorithm's need); the dense parameters (0.05M) are left out."""
    d, fields = model["embedding_dim"], model["num_categorical"]
    wide = (fields + model["num_dense"]) * d
    h1, h2 = model["hidden"]
    macs = wide * h1 + h1 * h2 + h2 + model["num_dense"] * (
        model["num_dense"] * d + 1
    )
    return {
        "flops": 3 * 2 * macs * minibatch,
        "bytes": 9 * minibatch * fields * (1 + d) * 4,
    }
