"""Attention with two head sizes and a given scale, and YaRN's rotary
tables: what latent attention (model_zoo/deepseek_v2) asks of
`ops/gqa.py` and `ops/flash_attention.py`.  Small sizes, float32, CPU.
"""

import hashlib
import importlib
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import gqa

fa = importlib.import_module("elasticdl_tpu.ops.flash_attention")


def _plain(q, k, v, scale):
    """A full masked softmax, grouped-query heads, float32."""
    t, n_rep = q.shape[1], q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, n_rep, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.tril(jnp.ones((t, t), bool))
    weights = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _qkv(seed, t, hq, hkv, d, dv, b=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (
        jax.random.normal(keys[0], (b, t, hq, d), jnp.float32),
        jax.random.normal(keys[1], (b, t, hkv, d), jnp.float32),
        jax.random.normal(keys[2], (b, t, hkv, dv), jnp.float32),
        jax.random.normal(keys[3], (b, t, hq, dv), jnp.float32),
    )


@pytest.mark.parametrize("impl,hq,hkv", [
    ("xla", 4, 4), ("xla", 4, 2), ("pallas", 4, 4), ("pallas", 4, 2),
])
@pytest.mark.parametrize("d,dv,scale", [
    (48, 32, 0.3),       # two head sizes, a scale that is not 1/sqrt(48)
    (32, 48, None),      # values wider than keys, the default scale
])
def test_two_head_sizes_and_a_scale_match_a_plain_softmax(
    impl, hq, hkv, d, dv, scale
):
    """Outputs and the three gradients, both engines (the Pallas kernel
    interpreted).  The wrong scale, or v's size taken for q's, fails."""
    q, k, v, weight = _qkv(0, 256, hq, hkv, d, dv)
    used = d ** -0.5 if scale is None else scale

    def engine(q, k, v):
        return gqa.causal_attention(
            q, k, v, scale=scale, impl=impl, block=64
        )

    out = engine(q, k, v)
    want = _plain(q, k, v, used)
    assert out.shape == (2, 256, hq, dv)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(engine(*a) * weight), (0, 1, 2))(q, k, v)
    ref = jax.grad(
        lambda *a: jnp.sum(_plain(*a, used) * weight), (0, 1, 2)
    )(q, k, v)
    for name, a, b in zip("qkv", got, ref):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4, err_msg=name)
    # The scale is in the result: without it the outputs differ.
    if scale is not None:
        bare = gqa.causal_attention(q, k, v, impl=impl, block=64)
        assert float(jnp.max(jnp.abs(bare - want))) > 1e-2


def _digest(fn, *args) -> str:
    return hashlib.sha256(
        str(jax.make_jaxpr(fn)(*args)).encode()
    ).hexdigest()[:16]


def test_equal_head_sizes_trace_the_program_they_traced_before():
    """Callers with one head size and no scale (qwen3_next, nemotron_h)
    get ONE jaxpr, forward and backward, whatever else the engines learn:
    the digest is of PR 43's tree (jax 0.9.0), where the XLA engine took
    its operands heads first and its blocks in place (before it, PR 31's
    `330e9ce25b3e684e`) with, since PR 52, the forward rule's two
    `checkpoint_name` equations on `out` and `lse` (PR 43's own digest
    was `4d5ff8ac58522df5`; without those two lines the jaxprs have the
    same primitives, parameters and result types, line for line);
    plain-theta `rotary_tables` still traces what it did at PR 31.  A
    scale given as 1/sqrt(D) traces it too."""
    q = jnp.zeros((2, 256, 4, 32), jnp.float32)
    k = jnp.zeros((2, 256, 2, 32), jnp.float32)

    def total(scale):
        return jax.grad(
            lambda q, k, v: jnp.sum(gqa.causal_attention(
                q, k, v, scale=scale, impl="xla", block=64
            )), (0, 1, 2),
        )

    if jax.__version__ == "0.9.0":
        assert _digest(total(None), q, k, k) == "75851b953aa597d0"
        assert _digest(
            lambda p: gqa.rotary_tables(p, 64, 1e4), jnp.arange(128)
        ) == "e5b29948198f66e1"
    assert _digest(total(None), q, k, k) == _digest(
        total(1.0 / 32 ** 0.5), q, k, k
    )


def test_supports_counts_both_head_sizes(monkeypatch):
    """K [T, 192] and V [T, 128] of a head in float32: 5 MiB at T 4096
    (inside the 8 MiB the forward kernel may keep), 10 MiB at 8192."""
    monkeypatch.delenv("LIBTPU_INIT_ARGS", raising=False)
    assert fa.kv_vmem_bytes(8192, 192, 128) == (192 + 128) * 8192 * 4
    assert fa.supports(4096, 192, d_v=128)
    assert not fa.supports(8192, 192, d_v=128)
    assert fa.supports(8192, 128) and fa.supports(8192, 128, d_v=128)
    assert not fa.supports(8192, 128, d_v=192)
    assert fa.kv_vmem_exceeded(8192, 192, 128)
    assert not fa.shape_aligned(4096, 192, d_v=100)


@pytest.mark.parametrize("t,engine", [
    (4096, "pallas flash_attention"), (8192, "xla causal_gqa_attention"),
])
def test_auto_chooses_by_both_sizes_and_the_log_names_them(
    t, engine, monkeypatch
):
    """`impl="auto"` on a TPU with heads of 192 and 128: the kernel at
    T 4096, the XLA engine at T 8192 (not padded, not refused), and the
    worker's log says which with both sizes."""
    monkeypatch.delenv("LIBTPU_INIT_ARGS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((1, t, 2, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, t, 2, 128), jnp.bfloat16)
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    gqa.logger.addHandler(handler)
    try:
        out = jax.eval_shape(
            lambda q, k, v: gqa.causal_attention(q, k, v, scale=0.1), q, q, v
        )
    finally:
        gqa.logger.removeHandler(handler)
    assert out.shape == (1, t, 2, 128)
    assert any(
        line.startswith(f"attention engine: {engine} T={t} Dqk=192 Dv=128")
        for line in lines
    ), lines


# -- YaRN ------------------------------------------------------------------

YARN = dict(factor=40, original=4096, beta_fast=32, beta_slow=1,
            mscale=0.707, mscale_all_dim=0.707)


def test_yarn_tables_follow_the_formulas():
    """DeepSeek-V2-Lite's rotary part: 32 pairs, base 10,000, trained on
    4096 positions, factor 40.  The formulas are written out here."""
    dim, base = 64, 10000.0
    low, high = gqa.yarn_correction_range(dim, base, 4096, 32, 1)
    d = lambda beta: dim * math.log(4096 / (2 * math.pi * beta)) / (  # noqa: E731
        2 * math.log(base)
    )
    assert (low, high) == (math.floor(d(32)), math.ceil(d(1))) == (10, 23)
    want = []
    for i in range(dim // 2):
        extra = base ** (-2 * i / dim)
        ramp = min(max((i - 10) / (23 - 10), 0.0), 1.0)
        want.append(extra / 40 * ramp + extra * (1 - ramp))
    positions = np.arange(0, 8192, 37)
    cos, sin = gqa.yarn_rotary_tables(jnp.asarray(positions), dim, base, **YARN)
    angles = positions[:, None] * np.asarray(want)[None, :]
    angles = np.concatenate([angles, angles], axis=-1)
    # float32 angles of up to 8192 rad: 5e-4 of rounding in the tables
    np.testing.assert_allclose(cos, np.cos(angles), atol=2e-3)
    np.testing.assert_allclose(sin, np.sin(angles), atol=2e-3)
    plain_cos, _ = gqa.rotary_tables(jnp.asarray(positions), dim, base)
    half = dim // 2
    # pair 0..9 unchanged; pair 31 (and every pair past 23) divided by 40
    np.testing.assert_array_equal(cos[:, :10], plain_cos[:, :10])
    np.testing.assert_allclose(
        cos[:, half - 1],
        np.cos(positions * base ** (-2 * 31 / dim) / 40), atol=1e-5,
    )
    assert float(jnp.max(jnp.abs(cos[:, 15] - plain_cos[:, 15]))) > 0.5
    # mscale == mscale_all_dim: the tables' magnitude is 1
    np.testing.assert_allclose(cos ** 2 + sin ** 2, 1.0, atol=1e-5)
    scaled, _ = gqa.yarn_rotary_tables(
        jnp.asarray(positions), dim, base, **{**YARN, "mscale_all_dim": 0.0}
    )
    np.testing.assert_allclose(
        scaled, cos * (0.1 * 0.707 * math.log(40) + 1), rtol=1e-5, atol=1e-6
    )


def test_softmax_scale_carries_mscale_squared():
    """s = 192^-0.5 m(40, 0.707)^2, both numbers written out."""
    m = gqa.yarn_mscale(40, 0.707)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert m == pytest.approx(1.2608, abs=5e-5)
    assert 192 ** -0.5 == pytest.approx(0.07217, abs=5e-6)
    assert m * m == pytest.approx(1.5896, abs=5e-5)
    assert gqa.yarn_mscale(1.0, 0.707) == 1.0
    from model_zoo.deepseek_v2 import deepseek_v2_lm as zoo

    cfg = zoo.DeepseekV2Config(
        qk_nope_head_dim=128, qk_rope_head_dim=64, rope_scaling_factor=40,
        rope_scaling_mscale=0.707, rope_scaling_mscale_all_dim=0.707,
    )
    assert zoo.softmax_scale(cfg) == pytest.approx(0.07217 * 1.5896, rel=1e-4)
    assert zoo.softmax_scale(
        zoo.DeepseekV2Config(qk_nope_head_dim=128, qk_rope_head_dim=64)
    ) == pytest.approx(192 ** -0.5)
