"""Embedding engine tests: layer semantics, sparse optimizers vs dense
golden math, and sparse-path training equivalence with dense autodiff.

Parity surface: elasticdl/python/tests/embedding_layer_test.py and the Go
kernel tests in elasticdl/pkg/kernel (golden-value sparse-apply parity).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.layers import Embedding
from elasticdl_tpu.parallel import MeshConfig, build_mesh, sparse_optim
from elasticdl_tpu.parallel.ps_trainer import ShardedEmbeddingTrainer
from elasticdl_tpu.parallel.trainer import TrainState
from tests.conftest import one_device_trainer

VOCAB, DIM = 32, 8


# ---------------------------------------------------------------------------
# Sparse optimizers vs dense golden math.
# ---------------------------------------------------------------------------

def _golden_rows(ids, grads):
    """Per-unique-row summed grads (numpy reference)."""
    out = {}
    for i, g in zip(ids, grads):
        out.setdefault(int(i), np.zeros(grads.shape[1], np.float32))
        out[int(i)] += g
    return out


class TestSparseOptimizers:
    def setup_method(self, method):
        rng = np.random.RandomState(0)
        self.table = rng.rand(VOCAB, DIM).astype(np.float32)
        self.ids = np.array([3, 7, 3, 0], np.int32)  # duplicate id 3
        self.grads = rng.rand(4, DIM).astype(np.float32)

    def test_sgd_matches_segment_summed_update(self):
        opt = sparse_optim.sgd(0.1)
        new_table, _ = opt.apply_logical(
            jnp.asarray(self.table), opt.init_slots_logical(jnp.asarray(self.table)),
            jnp.asarray(self.ids), jnp.asarray(self.grads),
        )
        expected = self.table.copy()
        for row, g in _golden_rows(self.ids, self.grads).items():
            expected[row] -= 0.1 * g
        np.testing.assert_allclose(np.asarray(new_table), expected, rtol=1e-6)

    def test_adagrad_matches_golden(self):
        opt = sparse_optim.adagrad(0.1, epsilon=1e-7)
        slots = opt.init_slots_logical(jnp.asarray(self.table))
        new_table, new_slots = opt.apply_logical(
            jnp.asarray(self.table), slots,
            jnp.asarray(self.ids), jnp.asarray(self.grads),
        )
        expected = self.table.copy()
        acc = np.zeros_like(self.table)
        for row, g in _golden_rows(self.ids, self.grads).items():
            acc[row] += g * g
            expected[row] -= 0.1 * g / (np.sqrt(acc[row]) + 1e-7)
        np.testing.assert_allclose(np.asarray(new_table), expected, rtol=1e-5)
        from elasticdl_tpu.parallel.packed import PackedSpec
        from elasticdl_tpu.parallel import packed as pk

        spec = PackedSpec(VOCAB, DIM)
        np.testing.assert_allclose(
            np.asarray(pk.unpack(spec, new_slots["accumulator"])), acc, rtol=1e-6
        )

    def test_momentum_matches_golden(self):
        opt = sparse_optim.momentum(0.1, mu=0.9)
        slots = opt.init_slots_logical(jnp.asarray(self.table))
        table, slots = opt.apply_logical(
            jnp.asarray(self.table), slots,
            jnp.asarray(self.ids), jnp.asarray(self.grads),
        )
        # Second apply exercises existing momentum.
        table, slots = opt.apply_logical(
            table, slots, jnp.asarray(self.ids), jnp.asarray(self.grads)
        )
        expected = self.table.copy()
        v = np.zeros_like(self.table)
        for _ in range(2):
            for row, g in _golden_rows(self.ids, self.grads).items():
                v[row] = 0.9 * v[row] + g
                expected[row] -= 0.1 * v[row]
        np.testing.assert_allclose(np.asarray(table), expected, rtol=1e-5)

    def test_adam_matches_golden(self):
        opt = sparse_optim.adam(0.01, 0.9, 0.999, 1e-8)
        slots = opt.init_slots_logical(jnp.asarray(self.table))
        table, slots = opt.apply_logical(
            jnp.asarray(self.table), slots,
            jnp.asarray(self.ids), jnp.asarray(self.grads),
        )
        expected = self.table.copy()
        m = np.zeros_like(self.table)
        v = np.zeros_like(self.table)
        for row, g in _golden_rows(self.ids, self.grads).items():
            m[row] = 0.9 * m[row] + 0.1 * g
            v[row] = 0.999 * v[row] + 0.001 * g * g
            m_hat = m[row] / (1 - 0.9)
            v_hat = v[row] / (1 - 0.999)
            expected[row] -= 0.01 * m_hat / (np.sqrt(v_hat) + 1e-8)
        np.testing.assert_allclose(np.asarray(table), expected, rtol=1e-5)


# ---------------------------------------------------------------------------
# Layer semantics.
# ---------------------------------------------------------------------------

class TestEmbeddingLayer:
    def _apply(self, layer, ids):
        from elasticdl_tpu.parallel import packed as pk

        variables = layer.init(jax.random.PRNGKey(0), ids)
        packed_table = variables["params"]["embedding"].unbox()
        table = pk.unpack(layer.spec, packed_table)  # logical [vocab, dim]
        out = layer.apply(variables, ids)
        return np.asarray(table), np.asarray(out)

    def test_plain_lookup(self):
        ids = jnp.asarray([[1, 2], [3, 1]], jnp.int32)
        table, out = self._apply(Embedding(VOCAB, DIM), ids)
        np.testing.assert_allclose(out, table[np.asarray(ids)], rtol=1e-6)

    def test_combiner_mean_with_padding(self):
        ids = jnp.asarray([[1, 2, -1], [3, -1, -1]], jnp.int32)
        table, out = self._apply(Embedding(VOCAB, DIM, combiner="mean"), ids)
        np.testing.assert_allclose(
            out[0], (table[1] + table[2]) / 2.0, rtol=1e-5
        )
        np.testing.assert_allclose(out[1], table[3], rtol=1e-5)

    def test_combiner_sum(self):
        ids = jnp.asarray([[1, 2, -1]], jnp.int32)
        table, out = self._apply(Embedding(VOCAB, DIM, combiner="sum"), ids)
        np.testing.assert_allclose(out[0], table[1] + table[2], rtol=1e-5)

    def test_high_oov_ids_read_zeros(self):
        """The fixed-vocab contract (docs/design.md): ids >= vocab_size
        contribute zeros, exactly like negative padding — NOT a clamped
        read of the last row (what the raw gather would do)."""
        ids = jnp.asarray([[1, VOCAB, VOCAB + 7], [2 * VOCAB, 3, -1]],
                          jnp.int32)
        table, out = self._apply(Embedding(VOCAB, DIM), ids)
        np.testing.assert_allclose(out[0, 0], table[1], rtol=1e-6)
        np.testing.assert_allclose(out[0, 1], np.zeros(DIM), atol=0)
        np.testing.assert_allclose(out[0, 2], np.zeros(DIM), atol=0)
        np.testing.assert_allclose(out[1, 0], np.zeros(DIM), atol=0)
        np.testing.assert_allclose(out[1, 1], table[3], rtol=1e-6)
        np.testing.assert_allclose(out[1, 2], np.zeros(DIM), atol=0)

    def test_oov_diagnostics_prints_count(self, capfd):
        from elasticdl_tpu.parallel import packed as pk

        pk.set_oov_debug(True)
        try:
            ids = jnp.asarray([[1, VOCAB + 5, VOCAB]], jnp.int32)
            self._apply(Embedding(VOCAB, DIM, name="probe"), ids)
            jax.effects_barrier()
        finally:
            pk.set_oov_debug(False)
        captured = capfd.readouterr()
        assert "OOV diagnostics [probe]" in captured.out, captured
        assert "2 ids >= vocab_size" in captured.out, captured

    def test_oov_diagnostics_silent_when_in_range(self, capfd):
        from elasticdl_tpu.parallel import packed as pk

        pk.set_oov_debug(True)
        try:
            ids = jnp.asarray([[1, 2, -1]], jnp.int32)
            self._apply(Embedding(VOCAB, DIM), ids)
            jax.effects_barrier()
        finally:
            pk.set_oov_debug(False)
        assert "OOV diagnostics" not in capfd.readouterr().out


# ---------------------------------------------------------------------------
# Training equivalence: the sparse path (stop_gradient + perturbation +
# scatter apply) must produce EXACTLY the dense-autodiff updates under SGD.
# ---------------------------------------------------------------------------

class SparseModel(nn.Module):
    @nn.compact
    def __call__(self, ids):
        x = Embedding(VOCAB, DIM, combiner="sum", name="emb")(ids)
        return nn.Dense(4, name="head")(x)


class DenseModel(nn.Module):
    @nn.compact
    def __call__(self, ids):
        table = self.param(
            "table", nn.initializers.uniform(0.05), (VOCAB, DIM)
        )
        ids = jnp.asarray(ids, jnp.int32)
        valid = ids >= 0
        acts = jnp.take(table, jnp.where(valid, ids, 0), axis=0)
        acts = acts * valid[..., None].astype(acts.dtype)
        return nn.Dense(4, name="head")(jnp.sum(acts, axis=-2))


def _loss(labels, outputs):
    return optax.softmax_cross_entropy_with_integer_labels(
        outputs, labels.astype(jnp.int32)
    ).mean()


def test_sparse_path_matches_dense_autodiff_sgd():
    mesh = build_mesh(MeshConfig())
    sparse_trainer = ShardedEmbeddingTrainer(
        SparseModel(), _loss, optax.sgd(0.2), mesh,
        embedding_optimizer=sparse_optim.sgd(0.2), seed=0,
    )
    dense_trainer = one_device_trainer(
        DenseModel(), _loss, optax.sgd(0.2), seed=0
    )

    rng = np.random.RandomState(0)
    ids = rng.randint(0, VOCAB, size=(16, 3)).astype(np.int32)
    ids[rng.rand(16, 3) < 0.2] = -1  # padding positions
    labels = rng.randint(0, 4, size=16).astype(np.int32)

    # Sync initial params: copy the sparse trainer's init into the dense one.
    sparse_trainer.ensure_initialized(ids)
    dense_trainer.ensure_initialized(ids)
    sv = sparse_trainer.get_variables_numpy()
    dense_params = {
        "table": jnp.asarray(sv["params/emb/embedding"]),
        "head": {
            "kernel": jnp.asarray(sv["params/head/kernel"]),
            "bias": jnp.asarray(sv["params/head/bias"]),
        },
    }
    dense_trainer.state = TrainState(
        jnp.zeros((), jnp.int32), dense_params,
        optax.sgd(0.2).init(dense_params), {},
    )

    for step in range(5):
        s_loss = sparse_trainer.train_step(ids, labels)
        d_loss = dense_trainer.train_step(ids, labels)
        np.testing.assert_allclose(
            float(s_loss), float(d_loss), rtol=1e-5, atol=1e-6,
            err_msg=f"loss diverged at step {step}",
        )
    sv = sparse_trainer.get_variables_numpy()
    dv = dense_trainer.get_variables_numpy()
    np.testing.assert_allclose(
        sv["params/emb/embedding"], dv["params/table"], rtol=1e-4, atol=1e-6
    )
    np.testing.assert_allclose(
        sv["params/head/kernel"], dv["params/head/kernel"], rtol=1e-4, atol=1e-6
    )


def test_sharded_trainer_eval_step():
    mesh = build_mesh(MeshConfig())
    trainer = ShardedEmbeddingTrainer(
        SparseModel(), _loss, optax.sgd(0.1), mesh,
        embedding_optimizer=sparse_optim.sgd(0.1),
    )
    rng = np.random.RandomState(1)
    ids = rng.randint(0, VOCAB, size=(16, 3)).astype(np.int32)
    labels = rng.randint(0, 4, size=16).astype(np.int32)
    trainer.train_step(ids, labels)
    out = trainer.eval_step(ids)
    assert out.shape == (16, 4) and np.isfinite(out).all()


def test_checkpoint_restore_roundtrip():
    import jax as _jax

    mesh = build_mesh(MeshConfig())

    def make():
        return ShardedEmbeddingTrainer(
            SparseModel(), _loss, optax.sgd(0.1), mesh,
            embedding_optimizer=sparse_optim.adagrad(0.1), seed=0,
        )

    rng = np.random.RandomState(2)
    ids = rng.randint(0, VOCAB, size=(8, 3)).astype(np.int32)
    labels = rng.randint(0, 4, size=8).astype(np.int32)
    t1 = make()
    for _ in range(3):
        t1.train_step(ids, labels)
    snapshot = _jax.device_get(t1.state)

    t2 = make()
    t2.state = snapshot  # restore BEFORE first batch (worker boot path)
    assert t2.step == 3
    l1 = float(t1.train_step(ids, labels))
    l2 = float(t2.train_step(ids, labels))
    np.testing.assert_allclose(l1, l2, rtol=1e-6)


def test_embedding_trains_densely_under_local_trainer():
    """Outside PS mode the table is a normal param: dense autodiff must
    train it (no silent freeze)."""
    trainer = one_device_trainer(SparseModel(), _loss, optax.sgd(0.2), seed=0)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, VOCAB, size=(16, 3)).astype(np.int32)
    labels = rng.randint(0, 4, size=16).astype(np.int32)
    trainer.ensure_initialized(ids)
    before = trainer.get_variables_numpy()["params/emb/embedding"].copy()
    for _ in range(3):
        trainer.train_step(ids, labels)
    after = trainer.get_variables_numpy()["params/emb/embedding"]
    assert np.abs(after - before).max() > 0, "embedding table never trained"


def test_dense_trainer_handles_ragged_batches():
    """The capture collections (perturbations/ids) must NOT live in
    model_state: they'd freeze the init batch's shape (crash on a ragged
    final batch) and grow the sow tuple every step (recompile per step)."""
    trainer = one_device_trainer(SparseModel(), _loss, optax.sgd(0.2), seed=0)
    rng = np.random.RandomState(0)
    for batch in (16, 16, 7, 16, 3):  # ragged sizes interleaved
        ids = rng.randint(0, VOCAB, size=(batch, 3)).astype(np.int32)
        labels = rng.randint(0, 4, size=batch).astype(np.int32)
        trainer.train_step(ids, labels)
    state = trainer.state
    assert "perturbations" not in state.model_state
    assert "embedding_ids" not in state.model_state


def test_dp_trainer_handles_ragged_batches():
    """Same invariant for the AllReduce trainer (padded final batch)."""
    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer

    mesh = build_mesh(MeshConfig())
    trainer = DataParallelTrainer(SparseModel(), _loss, optax.sgd(0.2), mesh)
    rng = np.random.RandomState(0)
    for batch in (16, 5):
        ids = rng.randint(0, VOCAB, size=(batch, 3)).astype(np.int32)
        labels = rng.randint(0, 4, size=batch).astype(np.int32)
        trainer.train_step(ids, labels)
    assert "perturbations" not in trainer.state.model_state
    assert "embedding_ids" not in trainer.state.model_state


def test_masked_batch_does_not_touch_adam_slots():
    """A fully-masked (all-zero-grad) step must leave tables and moments
    untouched (padding rows must not drift)."""
    opt = sparse_optim.adam(0.01)
    table = jnp.asarray(np.random.RandomState(0).rand(8, 4).astype(np.float32))
    slots = opt.init_slots_logical(table)
    # Prime row 2 with a real update.
    ids = jnp.asarray([2], jnp.int32)
    g = jnp.ones((1, 4), jnp.float32)
    table1, slots1 = opt.apply_logical(table, slots, ids, g)
    # Zero-grad (masked) step touching rows 2 and 0.
    table2, slots2 = opt.apply_logical(
        table1, slots1, jnp.asarray([2, 0], jnp.int32),
        jnp.zeros((2, 4), jnp.float32),
    )
    np.testing.assert_array_equal(np.asarray(table2), np.asarray(table1))
    np.testing.assert_array_equal(np.asarray(slots2["m"]), np.asarray(slots1["m"]))
    np.testing.assert_array_equal(np.asarray(slots2["t"]), np.asarray(slots1["t"]))


def test_dense_trainer_exports_logical_table_shape():
    """Export from the Local/AllReduce path must show [vocab, dim], not the
    packed storage shape (same contract as the PS trainer)."""
    trainer = one_device_trainer(SparseModel(), _loss, optax.sgd(0.1), seed=0)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, VOCAB, size=(8, 3)).astype(np.int32)
    labels = rng.randint(0, 4, size=8).astype(np.int32)
    trainer.train_step(ids, labels)
    assert trainer.get_variables_numpy()["params/emb/embedding"].shape == (VOCAB, DIM)

    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer

    mesh = build_mesh(MeshConfig())
    dp = DataParallelTrainer(SparseModel(), _loss, optax.sgd(0.1), mesh)
    dp.train_step(ids, labels)
    assert dp.get_variables_numpy()["params/emb/embedding"].shape == (VOCAB, DIM)
