"""Sharded (per-process) checkpointing tests.

Parity surface: the reference's per-PS-pod partition snapshots
(pkg/ps/checkpoint.go).  Here each process writes only its local table
rows; restore reassembles arbitrary row intervals under the NEW world's
sharding — including worlds of a different size than the one that saved
(the shrink/grow restore path of elastic re-formation).
"""

import json
import os

import numpy as np
import optax
import pytest

from elasticdl_tpu.checkpoint import RowReader, ShardedCheckpointSaver
from elasticdl_tpu.parallel import MeshConfig, build_mesh, sparse_optim
from elasticdl_tpu.parallel.ps_trainer import ShardedEmbeddingTrainer

from test_embedding import SparseModel, _loss, VOCAB


def _write_parts(step_dir, name, parts):
    """Simulate a multi-process save: one npz per (fake) process."""
    os.makedirs(step_dir, exist_ok=True)
    for i, (lo, hi, data) in enumerate(parts):
        np.savez(
            os.path.join(step_dir, f"shards_p{i}of{len(parts)}.npz"),
            **{f"{name}|{lo}|{hi}": data},
        )


class TestRowReader:
    def test_reassembles_across_files(self, tmp_path):
        data = np.arange(160, dtype=np.float32).reshape(16, 10)
        step_dir = str(tmp_path / "step_000000000001")
        _write_parts(
            step_dir, "table|emb", [(0, 8, data[0:8]), (8, 16, data[8:16])]
        )
        reader = RowReader(step_dir, "table|emb")
        np.testing.assert_array_equal(reader.read(0, 16), data)
        np.testing.assert_array_equal(reader.read(3, 12), data[3:12])
        np.testing.assert_array_equal(reader.read(8, 9), data[8:9])

    def test_missing_rows_raise(self, tmp_path):
        data = np.zeros((4, 2), np.float32)
        step_dir = str(tmp_path / "step_000000000001")
        _write_parts(step_dir, "t", [(0, 4, data), (8, 12, data)])
        reader = RowReader(step_dir, "t")
        with pytest.raises(ValueError, match="missing"):
            reader.read(2, 10)

    def test_name_isolation(self, tmp_path):
        """Entries of other arrays (names that themselves contain '|')
        are never mixed in."""
        step_dir = str(tmp_path / "step_000000000001")
        os.makedirs(step_dir)
        np.savez(
            os.path.join(step_dir, "shards_p0of1.npz"),
            **{
                "slot|emb|m|0|4": np.ones((4, 2), np.float32),
                "slot|emb|v|0|4": np.full((4, 2), 7, np.float32),
            },
        )
        np.testing.assert_array_equal(
            RowReader(step_dir, "slot|emb|v").read(0, 4),
            np.full((4, 2), 7, np.float32),
        )


def _make_trainer(mesh):
    return ShardedEmbeddingTrainer(
        SparseModel(), _loss, optax.sgd(0.1), mesh,
        embedding_optimizer=sparse_optim.adam(0.05), seed=0,
    )


def _train_batches():
    rng = np.random.RandomState(7)
    ids = rng.randint(0, VOCAB, size=(8, 3)).astype(np.int32)
    labels = rng.randint(0, 4, size=8).astype(np.int32)
    return ids, labels


def test_sharded_save_restore_roundtrip(tmp_path):
    mesh = build_mesh(MeshConfig())
    saver = ShardedCheckpointSaver(str(tmp_path))
    t1 = _make_trainer(mesh)
    ids, labels = _train_batches()
    for _ in range(3):
        t1.train_step(ids, labels)
    t1.save_checkpoint(saver, t1.step)

    # Layout: manifest + dense pickle + this process's shard file; no
    # host-complete state pickle anywhere.
    assert saver.latest_step() == 3
    step_dir = tmp_path / "step_000000000003"
    files = sorted(os.listdir(step_dir))
    assert "manifest.json" in files and "dense.pkl" in files
    assert any(f.startswith("shards_p0of") for f in files)
    assert "state.pkl" not in files
    manifest = json.loads((step_dir / "manifest.json").read_text())
    assert any(k.startswith("table|") for k in manifest["arrays"])
    assert any(k.startswith("slot|") for k in manifest["arrays"])

    # Restore at worker boot (structure unknown yet -> deferred).
    t2 = _make_trainer(mesh)
    t2.set_sharded_restore(saver, 3)
    assert t2.step == 3
    l1 = float(t1.train_step(ids, labels))
    l2 = float(t2.train_step(ids, labels))
    np.testing.assert_allclose(l1, l2, rtol=1e-6)


def test_sharded_restore_from_differently_split_files(tmp_path):
    """A world of a different size saved this checkpoint: the shard rows
    arrive split across several files with arbitrary intervals.  Restore
    must reassemble them bit-identically."""
    mesh = build_mesh(MeshConfig())
    saver = ShardedCheckpointSaver(str(tmp_path))
    t1 = _make_trainer(mesh)
    ids, labels = _train_batches()
    for _ in range(2):
        t1.train_step(ids, labels)
    t1.save_checkpoint(saver, t1.step)

    # Rewrite the single-process shard file as if 2 processes had saved:
    # every entry split at an uneven row boundary.
    step_dir = str(tmp_path / "step_000000000002")
    src = next(
        f for f in os.listdir(step_dir) if f.startswith("shards_p0of1")
    )
    npz = np.load(os.path.join(step_dir, src))
    part0, part1 = {}, {}
    for key in npz.files:
        name, lo, hi = key.rsplit("|", 2)
        lo, hi = int(lo), int(hi)
        cut = lo + max(1, (hi - lo) // 3)
        part0[f"{name}|{lo}|{cut}"] = npz[key][: cut - lo]
        part1[f"{name}|{cut}|{hi}"] = npz[key][cut - lo :]
    os.unlink(os.path.join(step_dir, src))
    np.savez(os.path.join(step_dir, "shards_p0of2.npz"), **part0)
    np.savez(os.path.join(step_dir, "shards_p1of2.npz"), **part1)
    manifest_path = os.path.join(step_dir, "manifest.json")
    manifest = json.loads(open(manifest_path).read())
    manifest["n_processes"] = 2
    manifest["shard_files"] = ["shards_p0of2.npz", "shards_p1of2.npz"]
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)
    saver = ShardedCheckpointSaver(str(tmp_path))  # fresh index cache

    t2 = _make_trainer(mesh)
    t2.set_sharded_restore(saver, 2)
    l1 = float(t1.train_step(ids, labels))
    l2 = float(t2.train_step(ids, labels))
    np.testing.assert_allclose(l1, l2, rtol=1e-6)


def test_stale_shard_files_are_ignored(tmp_path):
    """A file left behind by a world that died mid-save (different process
    count, stale weights) must never leak rows into a restore: only the
    manifest-inventoried files are read."""
    mesh = build_mesh(MeshConfig())
    saver = ShardedCheckpointSaver(str(tmp_path))
    t1 = _make_trainer(mesh)
    ids, labels = _train_batches()
    t1.train_step(ids, labels)
    t1.save_checkpoint(saver, 1)
    step_dir = str(tmp_path / "step_000000000001")
    # Forge a stale shard covering the same rows with garbage.
    src = next(f for f in os.listdir(step_dir) if f.startswith("shards_"))
    npz = np.load(os.path.join(step_dir, src))
    garbage = {k: np.full_like(npz[k], 1e9) for k in npz.files}
    np.savez(os.path.join(step_dir, "shards_p1of3.npz"), **garbage)

    t2 = _make_trainer(mesh)
    t2.set_sharded_restore(ShardedCheckpointSaver(str(tmp_path)), 1)
    l1 = float(t1.train_step(ids, labels))
    l2 = float(t2.train_step(ids, labels))
    np.testing.assert_allclose(l1, l2, rtol=1e-6)


def test_garbage_collection_keeps_newest(tmp_path):
    mesh = build_mesh(MeshConfig())
    saver = ShardedCheckpointSaver(str(tmp_path), keep_max=2)
    trainer = _make_trainer(mesh)
    ids, labels = _train_batches()
    for step in (1, 2, 3, 4):
        trainer.train_step(ids, labels)
        trainer.save_checkpoint(saver, step)
    assert saver.steps() == [3, 4]


def test_table_layout_mismatch_raises_with_cause(tmp_path):
    """A checkpoint written under one table layout must refuse restore
    into a build with a different table set — naming the per-mode
    layout cause, not a bare KeyError.  The real-world trigger: DeepFM
    merges linear+fm tables under windowed sparse apply but splits them
    under strict mode at >10M rows, so flipping --sparse_apply_every
    across a restart silently changes the model's table structure."""
    from model_zoo.deepfm import deepfm_functional_api as zoo

    mesh = build_mesh(MeshConfig())
    saver = ShardedCheckpointSaver(str(tmp_path))
    merged = ShardedEmbeddingTrainer(
        zoo.custom_model(vocab_size=100, split_tables=False),
        zoo.loss, zoo.optimizer(), mesh,
        embedding_optimizer=zoo.embedding_optimizer(), seed=0,
    )
    rng = np.random.RandomState(0)
    feats = {
        "dense": rng.rand(8, zoo.NUM_DENSE).astype(np.float32),
        "cat": rng.randint(0, 100, size=(8, zoo.NUM_CAT)).astype(np.int32),
    }
    labels = rng.randint(0, 2, size=8).astype(np.int32)
    merged.train_step(feats, labels)
    merged.save_checkpoint(saver, merged.step)

    split = ShardedEmbeddingTrainer(
        zoo.custom_model(vocab_size=100, split_tables=True),
        zoo.loss, zoo.optimizer(), mesh,
        embedding_optimizer=zoo.embedding_optimizer(), seed=0,
    )
    split.set_sharded_restore(saver, 1)
    with pytest.raises(ValueError, match="table layout changed"):
        split.ensure_initialized(feats)


def _gather_then_write(step_dir, trainer):
    """The parent commit's order (sharded.py:250-297 at 2cbfc0d): every
    shard and the dense state to the host, then the files."""
    import pickle

    import jax

    from elasticdl_tpu.checkpoint.saver import ChecksumWriter
    from elasticdl_tpu.checkpoint.sharded import _interval, write_npz

    state = trainer.state
    entries = {}
    for name, array in trainer._sharded_arrays(state).items():
        seen = set()
        for shard in array.addressable_shards:
            lo, hi = _interval(shard, array.shape[0])
            if (lo, hi) not in seen:
                seen.add((lo, hi))
                entries[f"{name}|{lo}|{hi}"] = np.asarray(shard.data)
    dense = jax.device_get({
        "step": state.step, "params": state.params,
        "opt_state": state.opt_state, "model_state": state.model_state,
        "scalar_slots": trainer._scalar_slots(state),
    })
    os.makedirs(step_dir)
    with ChecksumWriter(os.path.join(step_dir, "shards_p0of1.npz")) as writer:
        write_npz(writer, entries)
    with ChecksumWriter(os.path.join(step_dir, "dense.pkl")) as writer:
        pickle.dump(dense, writer)


@pytest.mark.parametrize("lookahead", [1 << 29, 64])
@pytest.mark.parametrize("name", ["shards_p0of1.npz", "dense.pkl"])
def test_streamed_files_are_the_gathered_files_byte_for_byte(
    tmp_path, monkeypatch, name, lookahead
):
    """`perfbench/configs/deepfm_reference.py` opens both files itself:
    the stream changes the order of the work, not one byte of a file."""
    import time

    from elasticdl_tpu.checkpoint import saver as saver_mod
    from elasticdl_tpu.checkpoint.saver import save_span
    from test_checkpoint_single_pass import _spans_since

    monkeypatch.setattr(saver_mod, "_LOOKAHEAD_BYTES", lookahead)
    mesh = build_mesh(MeshConfig())
    trainer = _make_trainer(mesh)
    ids, labels = _train_batches()
    for _ in range(2):
        trainer.train_step(ids, labels)
    marker = time.time()
    saver = ShardedCheckpointSaver(str(tmp_path / "streamed"))
    with save_span(rank=0, step=2):
        trainer.save_checkpoint(saver, 2)
    _gather_then_write(str(tmp_path / "gathered"), trainer)
    streamed = tmp_path / "streamed" / "step_000000000002" / name
    assert streamed.read_bytes() == (tmp_path / "gathered" / name).read_bytes()
    (gather,) = _spans_since(marker, "checkpoint.save.gather")
    (write,) = _spans_since(marker, "checkpoint.save.write")
    assert write["leaves"] > 2 and gather["bytes"] > 0
    assert 0 < write["streamed_bytes"] < gather["bytes"]
    assert 0 < write["lookahead_peak_bytes"] <= gather["bytes"]
    if lookahead == 64:  # one leaf ahead, whatever its size
        assert write["lookahead_peak_bytes"] < gather["bytes"]
