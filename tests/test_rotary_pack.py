"""`ops/rotary_pack.py` (ISSUE 43): one pass from a projection's float32
result to the attention engine's operand, heads in front of tokens, and
its transpose.  The definition (`rotary_pack_xla`) IS the chain the
models ran before, `apply_rotary` after `RMSNorm`, `astype`, to the bit;
the kernels (interpreted here) are held to it: in float32 to the last
places (the CPU's compiler contracts the definition's multiply-adds
where the interpreter's ops stand alone, so not to the bit here; on the
chip `chip_smoke.py`'s kernels phase compares the two and read
max|diff| = 0 for the operand in float32 and in bfloat16, with and
without the norm, at PR 43), and in bfloat16 to one rounding.  Small
sizes, CPU.
"""

import logging

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import gqa, rotary_pack as rp
from model_zoo.lm_common import NormWeight, RMSNorm

B, T, H = 2, 64, 3


def _tables(rotary_dim, yarn):
    positions = jnp.arange(T)
    if yarn:
        return gqa.yarn_rotary_tables(
            positions, rotary_dim, 5e5, factor=16, original=16, mscale=0.707,
        )
    return gqa.rotary_tables(positions, rotary_dim, 1e4)


def _inputs(d, normed, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (B, T, H, d), jnp.float32)
    weight = (
        1.0 + 0.1 * jax.random.normal(keys[1], (d,), jnp.float32)
        if normed else None
    )
    d_out = jax.random.normal(keys[2], (B, H, T, d), jnp.float32)
    return x, weight, d_out.astype(dtype)


CASES = pytest.mark.parametrize("d,rotary_dim", [
    (128, 128), (128, 64), (256, 64),
], ids=["full", "half", "quarter-of-256"])


@CASES
@pytest.mark.parametrize("yarn", [False, True], ids=["plain", "yarn"])
@pytest.mark.parametrize("normed", [False, True], ids=["bare", "normed"])
def test_definition_is_the_chain_the_models_ran_to_the_bit(
    d, rotary_dim, yarn, normed
):
    """`apply_rotary` o `RMSNorm` o `astype`, then heads first, and the
    same under `jax.vjp`; float32 (before the rounding) and bfloat16."""
    cos, sin = _tables(rotary_dim, yarn)
    norm = RMSNorm(1e-6)
    for dtype in (jnp.float32, jnp.bfloat16):
        x, weight, d_out = _inputs(d, normed, dtype)

        def chain(x, weight):
            if weight is not None:
                x = norm.apply({"params": {"weight": weight}}, x)
            return jnp.swapaxes(
                gqa.apply_rotary(x, cos, sin).astype(dtype), 1, 2
            )

        def definition(x, weight):
            return rp.rotary_pack(x, cos, sin, dtype, weight, 1e-6)

        want, back = jax.vjp(chain, x, weight)
        got, transposed = jax.vjp(definition, x, weight)
        assert got.dtype == dtype and got.shape == (B, H, T, d)
        np.testing.assert_array_equal(got, want)
        for a, b in zip(transposed(d_out), back(d_out)):
            np.testing.assert_array_equal(a, b)


@CASES
@pytest.mark.parametrize("yarn", [False, True], ids=["plain", "yarn"])
@pytest.mark.parametrize("normed", [False, True], ids=["bare", "normed"])
def test_kernels_match_the_definition(d, rotary_dim, yarn, normed):
    cos, sin = _tables(rotary_dim, yarn)
    for dtype in (jnp.float32, jnp.bfloat16):
        x, weight, d_out = _inputs(d, normed, dtype, seed=d + rotary_dim)
        want, back = jax.vjp(
            lambda x, w: rp.rotary_pack_xla(x, cos, sin, dtype, w), x, weight
        )
        got, transposed = jax.vjp(
            lambda x, w: rp.rotary_pack(
                x, cos, sin, dtype, w, interpret=True
            ), x, weight,
        )
        assert got.dtype == dtype and got.shape == (B, H, T, d)
        if dtype == jnp.float32:
            np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
        else:  # one rounding: equal but where float32's last place tips it
            got32, want32 = (np.asarray(a, np.float32) for a in (got, want))
            np.testing.assert_allclose(got32, want32, rtol=2 ** -7, atol=1e-6)
            assert np.mean(got32 != want32) < 0.01
        d_x, d_weight = transposed(d_out)
        np.testing.assert_allclose(d_x, back(d_out)[0], rtol=1e-5, atol=1e-5)
        if normed:
            np.testing.assert_allclose(
                d_weight, back(d_out)[1], rtol=1e-5, atol=1e-4
            )
        else:
            assert d_weight is None


def test_a_block_of_tokens_smaller_than_the_sequence():
    """Several blocks of tokens a head (the cells': 8 of 1024): each block
    reads its own rows of the tables."""
    cos, sin = _tables(64, False)
    x, weight, _ = _inputs(128, True, jnp.float32, seed=5)
    want = rp.rotary_pack_xla(x, cos, sin, jnp.float32, weight)
    got = rp._pack(
        x.reshape(B, T, H * 128), rp._kernel_tables(cos, sin, 128),
        weight.reshape(1, 128), (H, 32, 1e-6, jnp.dtype(jnp.float32), 16, True),
    )
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("backend,devices,d,engine,why", [
    ("cpu", 1, 128, "xla", "(backend cpu)"),
    ("tpu", 1, 128, "pallas", "(one device)"),
    ("tpu", 4, 128, "xla", "(4 devices and no mesh given)"),
    ("tpu", 1, 192, "xla", "(a head size or a length the kernels do not take)"),
])
def test_engine_is_chosen_from_what_the_trace_sees(
    monkeypatch, caplog, backend, devices, d, engine, why
):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    rp.logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=rp.logger.name):
            assert rp._engine(8192, 64, d, 64, True) == engine
    finally:
        rp.logger.removeHandler(caplog.handler)
    assert caplog.records[-1].getMessage() == (
        f"rotary_pack engine: {engine} T=8192 H=64 D={d} rotary_dim=64 "
        f"head norm {why}"
    )


def test_supports():
    assert rp.supports(8192, 128, 128) and rp.supports(8192, 256, 64)
    assert rp.supports(64, 128, 64)
    assert not rp.supports(8192, 192, 64)     # no whole lane tiles
    assert not rp.supports(8192, 128, 63)     # no whole pairs
    assert not rp.supports(8192 + 512, 128, 128)  # no whole blocks
    assert not rp.supports(8, 128, 128)


def test_norm_weight_is_the_norms_parameter():
    """`NormWeight` under a norm's name holds what `RMSNorm` would: a
    checkpoint of either restores into the other."""
    class Both(nn.Module):
        @nn.compact
        def __call__(self, x):
            return RMSNorm(1e-6, name="a")(x), NormWeight(16, name="b")()

    variables = Both().init(jax.random.PRNGKey(0), jnp.ones((2, 16)))
    a, b = variables["params"]["a"], variables["params"]["b"]
    assert jax.tree.structure(a) == jax.tree.structure(b)
    np.testing.assert_array_equal(a["weight"], b["weight"])
