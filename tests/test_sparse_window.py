"""Windowed sparse apply (ps_trainer sparse_apply_every > 1).

The relaxation: within a W-step chunk, embedding grads accumulate and the
sparse optimizer applies ONCE from the sum (forwards read chunk-start
tables; dense params still update per step) — the async-PS staleness of
the reference traded for amortizing the streaming moment update (see
_train_chunk_impl).  These tests pin the plumbing and the exactness cases.
"""

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.layers import Embedding
from elasticdl_tpu.parallel import MeshConfig, build_mesh, sparse_optim
from elasticdl_tpu.parallel.ps_trainer import ShardedEmbeddingTrainer
from tests.test_embedding import DIM, VOCAB, SparseModel, _loss


def _batches(k, rng, batch=16):
    out = []
    for _ in range(k):
        ids = rng.randint(0, VOCAB, size=(batch, 3)).astype(np.int32)
        labels = rng.randint(0, 4, size=batch).astype(np.int32)
        out.append((ids, labels, np.ones((batch,), np.float32)))
    return out


def _make(sparse_apply_every=1, emb_opt=None, dense_lr=0.1):
    return ShardedEmbeddingTrainer(
        SparseModel(), _loss, optax.sgd(dense_lr), build_mesh(MeshConfig()),
        embedding_optimizer=emb_opt or sparse_optim.adam(0.01),
        seed=0,
        sparse_apply_every=sparse_apply_every,
    )


def test_windowed_runs_with_remainder_chunk():
    """K=7, W=3 -> chunks of 3,3,1; losses come back per step and the step
    counter advances by K."""
    rng = np.random.RandomState(0)
    batches = _batches(7, rng)
    t = _make(sparse_apply_every=3)
    t.ensure_initialized(batches[0][0])
    losses = np.asarray(t.train_window(t.stage_window(batches)))
    assert losses.shape == (7,)
    assert np.isfinite(losses).all()
    assert t.step == 7


def test_windowed_first_chunk_first_loss_matches_strict():
    """Chunk 1 step 1 sees identical state in both modes -> identical loss."""
    rng = np.random.RandomState(1)
    batches = _batches(4, rng)

    t_strict = _make(1)
    t_strict.ensure_initialized(batches[0][0])
    strict_losses = np.asarray(t_strict.train_window(t_strict.stage_window(batches)))

    t_win = _make(4)
    t_win.ensure_initialized(batches[0][0])
    win_losses = np.asarray(t_win.train_window(t_win.stage_window(batches)))

    np.testing.assert_allclose(win_losses[0], strict_losses[0], rtol=1e-6)
    # Later losses DIFFER (stale tables within the chunk) — that's the
    # documented trade, not a bug; assert they still train sanely.
    assert np.isfinite(win_losses).all()


class LinearSparseModel(nn.Module):
    """Output linear in the embedding rows with a CONSTANT readout, so
    d loss/d row is independent of the table values: strict and windowed
    training produce bit-equal gradients, making windowed == strict
    exactly when the sparse optimizer is linear too (SGD)."""

    @nn.compact
    def __call__(self, ids):
        x = Embedding(VOCAB, DIM, combiner="sum", name="emb")(ids)
        return jnp.sum(x, axis=-1, keepdims=True) * jnp.ones((1, 4))


def _linear_loss(labels, outputs):
    # Linear in outputs -> constant gradient.
    return outputs.mean(axis=-1) * (labels.astype(jnp.float32) * 0 + 1.0)


def test_windowed_sgd_linear_model_exact():
    rng = np.random.RandomState(2)
    batches = _batches(6, rng)

    def make(w):
        return ShardedEmbeddingTrainer(
            LinearSparseModel(), _linear_loss, optax.sgd(0.0),
            build_mesh(MeshConfig()),
            embedding_optimizer=sparse_optim.sgd(0.05),
            seed=0,
            sparse_apply_every=w,
        )

    t1 = make(1)
    t1.ensure_initialized(batches[0][0])
    np.asarray(t1.train_window(t1.stage_window(batches)))

    t3 = make(3)
    t3.ensure_initialized(batches[0][0])
    np.asarray(t3.train_window(t3.stage_window(batches)))

    v1, v3 = t1.get_variables_numpy(), t3.get_variables_numpy()
    for key in v1:
        np.testing.assert_allclose(
            v3[key], v1[key], rtol=1e-6, atol=1e-7, err_msg=key
        )


def test_windowed_checkpoint_state_roundtrips():
    rng = np.random.RandomState(3)
    batches = _batches(4, rng)
    t = _make(2)
    t.ensure_initialized(batches[0][0])
    np.asarray(t.train_window(t.stage_window(batches)))
    state = t.state

    t2 = _make(2)
    t2.ensure_initialized(batches[0][0])
    t2.state = state
    more = _batches(2, rng)
    losses = np.asarray(t2.train_window(t2.stage_window(more)))
    assert np.isfinite(losses).all()
    assert t2.step == 6


def test_windowed_single_apply_per_chunk():
    """The chunk's sparse apply consumes the CONCATENATED (ids, grads) of
    all W steps through the normal optimizer apply — one moment update
    per chunk with summed duplicates (== apply_acc of the summed acc, by
    the dedup contract pinned in test_sparse_optim_modes)."""
    calls = []
    base = sparse_optim.adam(0.01)

    def counting_apply(spec, table, slots, ids, grads):
        calls.append(int(ids.shape[0]))
        return base.apply(spec, table, slots, ids, grads)

    spy = sparse_optim.SparseOptimizer(
        base.name, base.init_slots, counting_apply, base.hyperparams,
        base.apply_acc,
    )
    rng = np.random.RandomState(4)
    batches = _batches(6, rng)
    t = _make(3, emb_opt=spy)
    t.ensure_initialized(batches[0][0])
    np.asarray(t.train_window(t.stage_window(batches)))
    # 6 steps at W=3 -> 2 chunk applies, each over 3 stacked batches
    # (16 examples x 3 ids x 3 steps = 144 ids per apply).  Tracing may
    # record extra entries; the executed structure is what the loss shape
    # and step counter already pin — here we check each traced apply saw
    # the 3-step concatenation.
    assert all(n == 16 * 3 * 3 for n in calls)


def test_windowed_apply_convergence_parity():
    """Convergence tripwire for the windowed-apply semantics trade (the
    r04 A/B, scripts/convergence_ab.py + BASELINE.md "Windowed-apply
    convergence"): on the same learnable Zipf CTR stream, W=8 windowed
    apply must reach the same best held-out AUC as strict W=1 within a
    generous tolerance (measured diff at this scale: ~0.0006; on the
    chip-scale A/B, peak AUC at W=16/32 matched strict within 0.003).
    A real staleness bug — dropped window grads, mis-concatenated chunk
    ids, double-applied chunks — moves AUC far beyond 0.03."""
    from model_zoo import datasets
    from model_zoo.deepfm import deepfm_functional_api as zoo
    from model_zoo.metrics import auc

    vocab, batch, spe, epochs = 200, 256, 16, 3
    dense, cats, labels = datasets.synthetic_ctr_columns(
        batch * spe, vocab_size=vocab, weights_seed=0, draw_seed=1,
        zipf_s=1.1,
    )
    e_dense, e_cats, e_labels = datasets.synthetic_ctr_columns(
        2048, vocab_size=vocab, weights_seed=0, draw_seed=2, zipf_s=1.1
    )

    def run(w: int) -> float:
        mesh = build_mesh(MeshConfig())
        trainer = ShardedEmbeddingTrainer(
            zoo.custom_model(vocab_size=vocab),
            zoo.loss,
            zoo.optimizer(),
            mesh,
            embedding_optimizer=sparse_optim.adam(
                0.001, bias_correction="global"
            ),
            sparse_apply_every=w,
            seed=0,
        )
        mask = np.ones((batch,), np.float32)

        def make_batch(i):
            lo, hi = i * batch, (i + 1) * batch
            return (
                {"dense": dense[lo:hi], "cat": cats[lo:hi]},
                labels[lo:hi],
                mask,
            )

        trainer.ensure_initialized(make_batch(0)[0])
        window = trainer.stage_window([make_batch(i) for i in range(spe)])
        best = 0.0
        for _ in range(epochs):
            losses = trainer.train_window(window)
            assert np.isfinite(np.asarray(losses)).all()
            outs = [
                np.asarray(
                    trainer.eval_step(
                        {
                            "dense": e_dense[lo : lo + batch],
                            "cat": e_cats[lo : lo + batch],
                        }
                    )
                )
                for lo in range(0, 2048, batch)
            ]
            best = max(best, auc(np.concatenate(outs), e_labels))
        return best

    strict, windowed = run(1), run(8)
    assert strict > 0.58, f"strict run failed to learn (AUC {strict})"
    assert windowed > 0.58, f"windowed run failed to learn (AUC {windowed})"
    assert abs(strict - windowed) < 0.03, (strict, windowed)


def test_oov_counts_aggregate_across_windows():
    """OOV ids (>= vocab) are counted device-side per dispatch and
    drained by consume_oov_count(); negative ids are padding, NOT OOV
    (round-5 VERDICT weak #5).  Covers both the strict scan and the
    windowed chunk path."""
    for w in (1, 3):
        rng = np.random.RandomState(2)
        batches = _batches(6, rng)
        # Plant a known OOV pattern: 2 OOV ids in batch 0, 3 in batch 4,
        # plus a padding id that must NOT count.
        batches[0][0][0, 0] = VOCAB
        batches[0][0][1, 2] = VOCAB + 7
        batches[4][0][:3, 1] = VOCAB + 1
        batches[2][0][0, 0] = -1  # padding
        t = _make(sparse_apply_every=w)
        t.ensure_initialized(batches[0][0])
        t.train_window(t.stage_window(batches))
        assert t.consume_oov_count() == 5, f"W={w}"
        assert t.consume_oov_count() == 0  # drained
        # Per-step path counts too.
        t.train_step(batches[0][0], batches[0][1])
        assert t.consume_oov_count() == 2


def test_auto_apply_resolves_from_table_rows(monkeypatch):
    """--sparse_apply_every=auto: strict at <= AUTO_APPLY_TABLE_ROWS
    resident rows, AUTO_APPLY_W above — resolved at init, when the
    trainer first knows its table sizes (round-5 VERDICT #5)."""
    from elasticdl_tpu.parallel import ps_trainer as ps

    rng = np.random.RandomState(0)
    batches = _batches(4, rng)

    t = _make(sparse_apply_every="auto")
    assert t._sparse_apply_every is None  # unresolved until init
    t.ensure_initialized(batches[0][0])
    assert t._sparse_apply_every == 1  # tiny table -> strict

    # Same tiny model over a lowered threshold -> the windowed branch,
    # without building a real >10M-row table in the CPU suite.
    monkeypatch.setattr(ps, "AUTO_APPLY_TABLE_ROWS", 8)
    t2 = _make(sparse_apply_every="auto")
    t2.ensure_initialized(batches[0][0])
    assert t2._sparse_apply_every == ps.AUTO_APPLY_W
    # The windowed path actually runs: W=32 over a 4-step window is one
    # short chunk, applied once.
    losses = np.asarray(t2.train_window(t2.stage_window(batches)))
    assert losses.shape == (4,) and np.isfinite(losses).all()


def test_strict_mode_large_table_logs_perf_advice():
    """Strict per-step apply past 10M resident rows logs the windowed-
    apply recommendation (the measured ~3x + convergence-validated
    config); windowed runs stay quiet."""
    import contextlib
    import io
    import logging

    class BigModel(nn.Module):
        @nn.compact
        def __call__(self, ids, train: bool = False):
            return Embedding(10_000_064, 1)(ids)[..., 0]

    def loss(labels, out):
        return jnp.mean((out - labels) ** 2)

    @contextlib.contextmanager
    def capture():
        buf = io.StringIO()
        handler = logging.StreamHandler(buf)
        lg = logging.getLogger("elasticdl_tpu.parallel.ps_trainer")
        lg.addHandler(handler)
        try:
            yield buf
        finally:
            lg.removeHandler(handler)

    ids = np.zeros((8,), np.int32)
    labels = np.zeros((8,), np.float32)
    for apply_every, expect in ((1, True), (16, False)):
        mesh = build_mesh(MeshConfig())
        trainer = ShardedEmbeddingTrainer(
            BigModel(), loss, optax.sgd(0.1), mesh,
            embedding_optimizer=sparse_optim.sgd(0.1),
            sparse_apply_every=apply_every,
        )
        with capture() as buf:
            trainer.ensure_initialized(ids)
        trainer.train_step(ids, labels)
        advised = "sparse_apply_every=16" in buf.getvalue()
        assert advised is expect, (apply_every, buf.getvalue())
