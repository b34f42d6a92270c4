"""Ring attention (sequence/context parallelism) tests.

Golden parity: ring attention over the 8-device mesh must match plain
single-device softmax attention — full and causal — to fp tolerance,
including through the backward pass (grads flow through ppermute).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.parallel import MeshConfig, build_mesh
from elasticdl_tpu.parallel.mesh import MODEL_AXIS
from elasticdl_tpu.parallel.ring_attention import (
    blockwise_attention,
    ring_attention,
    ring_self_attention,
)


def dense_attention(q, k, v, causal=False):
    """O(T^2)-materialized reference numerics."""
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d).astype(q.dtype)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.arange(tk)[None, :] > jnp.arange(tq)[:, None]
        scores = jnp.where(mask[None, None], -jnp.inf, scores)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )


def _qkv(b=2, t=32, h=4, d=16, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(
        rng.standard_normal((b, t, h, d)).astype(np.float32), dtype
    )
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_dense(causal):
    q, k, v = _qkv()
    out = blockwise_attention(q, k, v, causal=causal)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_dense_on_mesh(causal):
    mesh = build_mesh(MeshConfig(data=2, model=4))
    q, k, v = _qkv(b=4, t=64)
    out = ring_self_attention(mesh, q, k, v, causal=causal)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5
    )


def test_ring_full_context_axis():
    """Sequence over ALL 8 devices (data=1): the deepest ring."""
    mesh = build_mesh(MeshConfig(data=1, model=8))
    q, k, v = _qkv(b=1, t=64, seed=3)
    out = ring_self_attention(mesh, q, k, v, causal=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_gradients_match_dense():
    """Backward through the ring (ppermute transposes to the reverse
    rotation) must produce the same input grads as dense attention."""
    from functools import partial

    from jax.sharding import NamedSharding, PartitionSpec as P

    from elasticdl_tpu.parallel import compile as pc
    from elasticdl_tpu.parallel.mesh import DATA_AXIS

    mesh = build_mesh(MeshConfig(data=2, model=4))
    q, k, v = _qkv(b=2, t=32, seed=7)
    spec = P(DATA_AXIS, MODEL_AXIS, None, None)
    ring = pc.shard_map_call(
        partial(ring_attention, axis_name=MODEL_AXIS, causal=True),
        mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    sharding = NamedSharding(mesh, spec)
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))

    def ring_loss(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(qs, ks, vs)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_ring, g_dense):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=5e-4
        )


def test_ring_bf16_inputs():
    """bf16 q/k/v accumulate in f32 (flash numerics) — outputs stay
    close to the f32 dense reference."""
    mesh = build_mesh(MeshConfig(data=2, model=4))
    q, k, v = _qkv(b=2, t=32, seed=5, dtype=jnp.bfloat16)
    out = ring_self_attention(mesh, q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=0.05, rtol=0.05
    )


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_kv_chunking_matches_dense(causal):
    """T > kv_chunk exercises the chunked scan path; parity must hold."""
    q, k, v = _qkv(t=64, seed=11)
    out = blockwise_attention(q, k, v, causal=causal, kv_chunk=16)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_zigzag_layout_matches_dense(causal):
    """Balanced causal layout: shard i holds chunks (i, 2N-1-i); the
    wrapper permutes in/out, so results must equal dense attention in
    natural order."""
    mesh = build_mesh(MeshConfig(data=2, model=4))
    q, k, v = _qkv(b=2, t=64, seed=13)
    out = ring_self_attention(mesh, q, k, v, causal=causal, layout="zigzag")
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_zigzag_order_roundtrip():
    from elasticdl_tpu.parallel.ring_attention import (
        inverse_order,
        zigzag_order,
    )

    order = zigzag_order(32, 4)
    inv = inverse_order(order)
    np.testing.assert_array_equal(np.sort(order), np.arange(32))
    np.testing.assert_array_equal(order[inv], np.arange(32))
    # Shard 0 of 4 holds chunks 0 and 7 (of 8).
    assert list(order[:4]) == [0, 1, 2, 3]
    assert list(order[4:8]) == [28, 29, 30, 31]
    with pytest.raises(ValueError, match="chunks"):
        zigzag_order(30, 4)


def test_zigzag_gradients_match_dense():
    """Zigzag changes the differentiated graph (no cond skip, plus the
    in/out permutation gathers) — backward must still match dense."""
    mesh = build_mesh(MeshConfig(data=2, model=4))
    q, k, v = _qkv(b=2, t=32, seed=17)

    def zig_loss(q, k, v):
        out = ring_self_attention(mesh, q, k, v, causal=True,
                                  layout="zigzag")
        return jnp.sum(out ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    g_zig = jax.grad(zig_loss, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_zig, g_dense):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=5e-4
        )


def test_zigzag_rejects_cross_attention_lengths():
    mesh = build_mesh(MeshConfig(data=2, model=4))
    q, _, _ = _qkv(b=2, t=32, seed=1)
    k, _, _ = _qkv(b=2, t=64, seed=2)
    with pytest.raises(ValueError, match="equal q/k/v sequence lengths"):
        ring_self_attention(mesh, q, k, k, causal=True, layout="zigzag")
