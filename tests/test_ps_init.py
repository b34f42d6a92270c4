"""The PS trainer's state is born on the device by ONE compiled program
(`ps_init`, through `CompilePlan.compile` and so through the executable
store), and a restore never builds or runs it.

Beside tests/test_ps_e2e.py, whose worlds of several processes are slow
tests: these run in one process, on the suite's eight virtual devices.
"""

import hashlib
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu import obs
from elasticdl_tpu.checkpoint import ShardedCheckpointSaver
from elasticdl_tpu.common import compile_cache
from elasticdl_tpu.layers import Embedding
from elasticdl_tpu.layers.embedding import default_embedding_init
from elasticdl_tpu.parallel import MeshConfig, build_mesh, sparse_optim
from elasticdl_tpu.parallel.ps_trainer import (
    ShardedEmbeddingTrainer,
    _path_key,
)

VOCAB, DIM = 32, 8


# ---------------------------------------------------------------------------
# (a) A fixed seed gives the state the eager init gave.
# ---------------------------------------------------------------------------

#: sha256 (16 hex digits) over every leaf's path, shape, dtype and BYTES
#: of the initial state at seed 7, taken on the parent commit (c576e55),
#: whose `_init_state` ran `model.init`, the slots and `tx.init` eagerly
#: and placed a host copy: (tables and slots, the dense rest).  Every
#: leaf is bit-equal on the CPU.  (On a v5e the tables, the slots and
#: the optimizer state are bit-equal too, and the dense kernels, whose
#: truncated normal the TPU compiler fuses, move by at most 1.5e-7:
#: PERF.md section 6, PR 37.)
PARENT_STATE = {
    "deepfm": ("71fd1e925b0afa5f", "b7c81fe477992082"),
    "deepfm_split": ("554cd7a9150c9e82", "dfb4cd3b359489ba"),
    "wide_and_deep": ("11c14c777bbe10c6", "a45ac7a7f3ec1a41"),
}


def _zoo_trainer(name, seed=7):
    if name == "wide_and_deep":
        from model_zoo.wide_and_deep import wide_and_deep as zoo

        model = zoo.custom_model(vocab_size=100)
    else:
        from model_zoo.deepfm import deepfm_functional_api as zoo

        model = zoo.custom_model(
            vocab_size=100, split_tables=(name == "deepfm_split")
        )
    trainer = ShardedEmbeddingTrainer(
        model, zoo.loss, zoo.optimizer(lr=0.01), build_mesh(MeshConfig()),
        embedding_optimizer=zoo.embedding_optimizer(lr=0.01), seed=seed,
    )
    features = {
        "dense": np.zeros((16, zoo.NUM_DENSE), np.float32),
        "cat": np.zeros((16, zoo.NUM_CAT), np.int32),
    }
    return trainer, features


def _digest(tree) -> str:
    digest = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        value = np.asarray(leaf)
        digest.update(
            f"{_path_key(path)} {value.shape} {value.dtype}\n".encode()
        )
        digest.update(value.tobytes())
    return digest.hexdigest()[:16]


def _state_digests(state):
    return (
        _digest((state.tables, state.slots)),
        _digest(
            (state.step, state.params, state.opt_state, state.model_state)
        ),
    )


@pytest.mark.parametrize("name", sorted(PARENT_STATE))
def test_a_fixed_seed_gives_the_parents_initial_state(name):
    trainer, features = _zoo_trainer(name)
    state = trainer.ensure_initialized(features)
    assert _state_digests(state) == PARENT_STATE[name]


# ---------------------------------------------------------------------------
# A model whose initializers refuse to run outside a trace.
# ---------------------------------------------------------------------------


def _traced_only(initializer):
    def init(key, shape, dtype=jnp.float32):
        if not isinstance(key, jax.core.Tracer):
            raise AssertionError(
                "an initializer ran eagerly, on a concrete key"
            )
        return initializer(key, shape, dtype)

    init.packed_iid_safe = getattr(initializer, "packed_iid_safe", False)
    return init


class TracedOnlyModel(nn.Module):
    @nn.compact
    def __call__(self, ids):
        x = Embedding(
            VOCAB, DIM, combiner="sum", name="emb",
            embeddings_initializer=_traced_only(default_embedding_init),
        )(ids)
        return nn.Dense(
            4, name="head",
            kernel_init=_traced_only(nn.initializers.lecun_normal()),
        )(x)


def _loss(labels, outputs):
    return optax.softmax_cross_entropy_with_integer_labels(
        outputs, labels.astype(jnp.int32)
    ).mean()


def _trainer():
    return ShardedEmbeddingTrainer(
        TracedOnlyModel(), _loss, optax.adam(0.1), build_mesh(MeshConfig()),
        embedding_optimizer=sparse_optim.adam(0.05), seed=0,
    )


def _batch():
    rng = np.random.RandomState(7)
    ids = rng.randint(0, VOCAB, size=(8, 3)).astype(np.int32)
    return ids, rng.randint(0, 4, size=8).astype(np.int32)


def _spans_since(marker):
    return [
        e for e in obs.journal().tail(400)
        if e.get("event") == "span" and e["ts"] >= marker
    ]


def _assert_states_equal(got, want):
    got_leaves, got_tree = jax.tree_util.tree_flatten(got)
    want_leaves, want_tree = jax.tree_util.tree_flatten(want)
    assert got_tree == want_tree
    for a, b in zip(got_leaves, want_leaves):
        assert a.dtype == b.dtype and a.sharding == b.sharding
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_init_is_one_build_inside_state_init():
    compile_cache._count_events()  # configure()'s listeners, once a process
    trainer, (ids, _) = _trainer(), _batch()
    marker = time.time()
    trainer.ensure_initialized(ids)
    spans = _spans_since(marker)
    (state_init,) = [e for e in spans if e["name"] == "state.init"]
    builds = [e for e in spans if e["name"] == "compile.build"]
    assert [b["entrypoint"] for b in builds] == ["ps_init"]
    assert builds[0]["parent_span_id"] == state_init["span_id"]
    assert builds[0]["programs"] == 1  # one XLA compile, not dozens


# ---------------------------------------------------------------------------
# (b), (d) With a restore pending the init is neither built nor run.
# ---------------------------------------------------------------------------


def _trained(steps=3):
    trainer, (ids, labels) = _trainer(), _batch()
    for _ in range(steps):
        trainer.train_step(ids, labels)
    return trainer


def test_a_pending_sharded_restore_builds_no_init(tmp_path):
    saved = _trained()
    saver = ShardedCheckpointSaver(str(tmp_path))
    saved.save_checkpoint(saver, saved.step)

    fresh, (ids, labels) = _trainer(), _batch()
    fresh.set_sharded_restore(saver, saved.step)
    marker = time.time()
    fresh.ensure_initialized(ids)
    spans = _spans_since(marker)
    names = [e["name"] for e in spans]
    assert "checkpoint.restore.load" in names
    (state_init,) = [e for e in spans if e["name"] == "state.init"]
    (load,) = [e for e in spans if e["name"] == "checkpoint.restore.load"]
    assert load["parent_span_id"] == state_init["span_id"]
    assert "ps_init" not in [
        e.get("entrypoint") for e in spans if e["name"] == "compile.build"
    ]
    assert fresh.step == saved.step
    _assert_states_equal(fresh.state, saved.state)
    assert float(fresh.train_step(ids, labels)) == float(
        saved.train_step(ids, labels)
    )


def test_a_pending_host_state_is_placed_and_builds_no_init():
    saved = _trained()
    snapshot = saved.state_to_host()

    fresh, (ids, labels) = _trainer(), _batch()
    fresh.state = snapshot  # before the first batch: the worker's boot
    marker = time.time()
    fresh.ensure_initialized(ids)
    assert "ps_init" not in [
        e.get("entrypoint") for e in _spans_since(marker)
        if e["name"] == "compile.build"
    ]
    assert fresh.step == saved.step
    _assert_states_equal(fresh.state, saved.state)
    assert float(fresh.train_step(ids, labels)) == float(
        saved.train_step(ids, labels)
    )


# ---------------------------------------------------------------------------
# (c) `state.init` makes no host copy of a table.
# ---------------------------------------------------------------------------


def test_state_init_fetches_no_table_to_the_host(monkeypatch):
    trainer, features = _zoo_trainer("deepfm")
    fetched = []
    device_get = jax.device_get

    def counting(tree):
        fetched.append(sum(
            int(np.prod(np.shape(leaf))) * np.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves(tree) if hasattr(leaf, "dtype")
        ))
        return device_get(tree)

    monkeypatch.setattr(jax, "device_get", counting)
    trainer._init_state(features)
    monkeypatch.undo()
    table_bytes = sum(t.nbytes for t in trainer.state.tables.values())
    assert table_bytes > 150_000  # what the parent fetched and put back
    assert sum(fetched) < min(1_000_000, table_bytes)
    # ... and what the host keeps of the model came from the trace.
    (spec,) = trainer._table_specs.values()
    assert (spec.vocab_size, spec.dim) == (100 * 26, 9)
