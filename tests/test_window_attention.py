"""A causal BAND in the attention layer (ISSUE 36): with `window=W` a
query at t reads the keys `t - W < s <= t`, and the XLA block engine
SKIPS the key blocks outside that band instead of masking them.  The
Pallas kernel has no band (PR 36's review: a streaming one measured
slower and went), so it refuses a window; without one both engines are
the parent's to the bit.  Small sizes, float32, CPU.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import gqa

fa = importlib.import_module("elasticdl_tpu.ops.flash_attention")

T, BLOCK = 256, 64


def _plain(q, k, v, window=None):
    """A full masked softmax, grouped-query heads, float32."""
    t, n_rep = q.shape[1], q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, n_rep, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
    at = jnp.arange(t)
    mask = at[None, :] <= at[:, None]
    if window is not None:
        mask = mask & (at[None, :] > at[:, None] - window)
    weights = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _qkv(seed, hq, hkv, t=T, d=32, b=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (
        jax.random.normal(keys[0], (b, t, hq, d), jnp.float32),
        jax.random.normal(keys[1], (b, t, hkv, d), jnp.float32),
        jax.random.normal(keys[2], (b, t, hkv, d), jnp.float32),
        jax.random.normal(keys[3], (b, t, hq, d), jnp.float32),
    )


def _engine(window, block=BLOCK):
    """The XLA engine in blocks of 64 (under a band `causal_attention`
    gives it blocks of half the window, 128 at the least)."""
    return lambda q, k, v: gqa.heads_first(gqa.causal_gqa_attention(
        *map(gqa.heads_first, (q, k, v)), block, None,
        window if window and window < q.shape[1] else None,
    ))


# W smaller than, equal to and larger than a block of 64; one key; one
# short of and one past a block's edge; the whole sequence and beyond.
WINDOWS = [1, 17, 63, 64, 65, 100, 128, 129, 255, 256, 1000]
#: `_qkv`'s shape for the band's other published shape (Mellum 2's: a
#: window of 1024 under 8 query heads a key-value head), where the front
#: door gives the engine blocks of 512.
MELLUM_BAND = dict(hq=8, hkv=1, t=2048, d=16, b=1)


@pytest.mark.parametrize("impl", ["xla", "auto"])
@pytest.mark.parametrize("window,shape", [
    pytest.param(window, dict(hq=4, hkv=2), id=str(window))
    for window in WINDOWS
] + [pytest.param(1024, MELLUM_BAND, id="1024-of-2048-x8")])
def test_band_matches_a_masked_plain_softmax(impl, window, shape):
    """`impl` is the front door's; the engine beneath is the XLA one."""
    q, k, v, weight = _qkv(window, **shape)
    engine = _engine(window)
    want = _plain(q, k, v, window)
    # the front door, with its own choice of blocks, gives the same
    front = jax.grad(lambda *a: jnp.sum(gqa.causal_attention(
        *a, window=window, impl=impl
    ) * weight), (0, 1, 2))
    np.testing.assert_allclose(
        gqa.causal_attention(q, k, v, window=window, impl=impl), want,
        rtol=2e-4, atol=2e-5,
    )
    np.testing.assert_allclose(engine(q, k, v), want, rtol=2e-4, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(engine(*a) * weight), (0, 1, 2))(q, k, v)
    ref = jax.grad(
        lambda *a: jnp.sum(_plain(*a, window) * weight), (0, 1, 2)
    )(q, k, v)
    for name, a, b, c in zip("qkv", got, ref, front(q, k, v)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4, err_msg=name)
        np.testing.assert_allclose(c, b, rtol=2e-3, atol=2e-4, err_msg=name)
    if window < q.shape[1]:  # the band is in the result: full causal differs
        assert float(jnp.max(jnp.abs(engine(q, k, v) - _plain(q, k, v)))) > 1e-2


@pytest.mark.parametrize("block", [32, 64])
@pytest.mark.parametrize("hq,hkv", [(12, 2), (16, 2)], ids=["x6", "x8"])
def test_band_with_six_and_eight_query_heads_a_key_value_head(block, hq, hkv):
    """Laguna's groups: 48 / 8 in a full layer, 64 / 8 in a sliding one."""
    q, k, v, weight = _qkv(hq, hq, hkv, t=128, d=16, b=1)
    for window in (40, None):
        engine = _engine(window, block)
        np.testing.assert_allclose(
            engine(q, k, v), _plain(q, k, v, window), rtol=2e-4, atol=2e-5
        )
        got = jax.grad(lambda *a: jnp.sum(engine(*a) * weight), (1, 2))(q, k, v)
        ref = jax.grad(
            lambda *a: jnp.sum(_plain(*a, window) * weight), (1, 2)
        )(q, k, v)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_no_window_and_a_window_of_the_whole_sequence_are_todays_output(impl):
    """`window=None` is the code path the other cells run, and a band that
    holds every key a causal mask leaves takes it too: bit-equal."""
    q, k, v, weight = _qkv(7, 4, 2)

    def out(window, **more):
        return gqa.causal_attention(
            q, k, v, impl=impl, block=BLOCK, **more,
            **({} if window == "absent" else {"window": window}),
        )

    base = out("absent")
    for window in (None, T, T + 1):
        np.testing.assert_array_equal(out(window), base)
    if impl == "xla":
        np.testing.assert_array_equal(base, _engine(None)(q, k, v))
    else:
        np.testing.assert_array_equal(
            base, fa.flash_attention(
                q, gqa.repeat_kv(k, 2), gqa.repeat_kv(v, 2), causal=True,
            ),
        )

    def total(**more):
        return jax.grad(lambda q, k, v: jnp.sum(gqa.causal_attention(
            q, k, v, impl=impl, block=BLOCK, **more
        ) * weight), (0, 1, 2))

    for a, b in zip(total()(q, k, v), total(window=None)(q, k, v)):
        np.testing.assert_array_equal(a, b)
    assert str(jax.make_jaxpr(total())(q, k, v)) == str(
        jax.make_jaxpr(total(window=T))(q, k, v)
    )


@pytest.mark.parametrize("window,reads", [(1, 1), (BLOCK, 2), (BLOCK + 2, 3)])
def test_blocks_outside_the_band_are_never_read(window, reads):
    """SKIPPED, not masked: with K and V poisoned (NaN) in every key block
    the last query block's band does not touch, its outputs and its
    queries' gradients are those of clean inputs.  An engine that computed
    those blocks and masked them would carry 0 x NaN into both."""
    q, k, v, weight = _qkv(3, 4, 2)
    dead = T - reads * BLOCK  # the last query block reads `reads` key blocks
    poison = jnp.where(jnp.arange(T)[None, :, None, None] < dead, jnp.nan, 0.0)
    last = jnp.arange(T)[None, :, None, None] >= T - BLOCK

    def rows(q, k, v):
        return jnp.where(last, _engine(window)(q, k, v), 0.0)

    got = rows(q, k + poison, v + poison)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(
        got, jnp.where(last, _plain(q, k, v, window), 0.0),
        rtol=2e-4, atol=2e-5,
    )
    dq = jax.grad(lambda q: jnp.sum(rows(q, k + poison, v + poison) * weight))(q)
    clean = jax.grad(lambda q: jnp.sum(rows(q, k, v) * weight))(q)
    assert bool(jnp.isfinite(dq[:, T - BLOCK:]).all())
    np.testing.assert_allclose(
        dq[:, T - BLOCK:], clean[:, T - BLOCK:], rtol=2e-3, atol=2e-4
    )
    # full causal attention does read them
    full = _engine(None)(q, k + poison, v + poison)
    assert not bool(jnp.isfinite(full[:, T - BLOCK:]).all())


def test_band_visits_a_fraction_of_the_causal_block_pairs():
    """The key loop's trip count, from the bounds the XLA engine gives it
    (`_first_block` .. i): at T 4096 and W 512 in blocks of 512, 15 block
    pairs against full causal attention's 36, under a third with the
    diagonal's own share counted; at the cell's T 8192, 31 of 136."""
    def pairs(t, window, block=512):
        return sum(
            i + 1 - int(gqa._first_block(i, block, window))
            for i in range(t // block)
        )

    assert pairs(4096, None) == 36 and pairs(4096, 512) == 15
    assert pairs(8192, None) == 136 and pairs(8192, 512) == 31
    assert pairs(4096, 513) == 15 and pairs(4096, 514) == 21
    assert pairs(4096, 1, block=512) == 8  # the diagonal alone


def test_xla_engines_loop_starts_at_the_bands_first_block():
    """The trip count read from the jaxpr: the key loop's lower bound is
    the literal 0 under a causal mask alone and `max(i - 1, 0)` under a
    band of one block (W = 64, blocks of 64: two blocks a query block)."""
    q, k, v, _ = _qkv(0, 4, 2)

    def loops(window):
        jaxpr = jax.make_jaxpr(lambda q, k, v: gqa.causal_attention(
            q, k, v, window=window, impl="xla", block=BLOCK
        ))(q, k, v)
        return str(jaxpr)

    assert "max" not in loops(None).split("while")[1].split("body_jaxpr")[0]
    assert loops(None) != loops(64)
    # run it: outputs of a band of 64 equal the masked softmax, so the
    # blocks it left out held nothing the mask keeps
    out = gqa.causal_attention(q, k, v, window=64, impl="xla", block=BLOCK)
    np.testing.assert_allclose(out, _plain(q, k, v, 64), rtol=2e-4, atol=2e-5)


def test_the_pallas_kernel_refuses_a_window():
    """No band in the Pallas kernel: the front door says so before any
    trace, and the kernel's own signature has no such argument; a window
    that holds the whole sequence is no window, and passes."""
    q, k, v, _ = _qkv(5, 4, 2)
    with pytest.raises(ValueError, match="no band"):
        gqa.causal_attention(q, k, v, window=64, impl="pallas")
    with pytest.raises(ValueError, match="at least 1"):
        gqa.causal_attention(q, k, v, window=0)
    with pytest.raises(TypeError):
        fa.flash_attention(q, q, q, causal=True, window=64)
    np.testing.assert_array_equal(
        gqa.causal_attention(q, k, v, window=T, impl="pallas"),
        gqa.causal_attention(q, k, v, impl="pallas"),
    )


@pytest.mark.parametrize("window,block,blocks_of", [
    (512, 512, 256), (1024, 512, 512), (4096, 512, 512), (256, 512, 128),
    (64, 512, 128), (512, 128, 128),
])
def test_front_door_gives_a_band_blocks_of_half_the_window(
    window, block, blocks_of
):
    """Half the window (a query block then reads three key blocks, 1.5
    windows of keys), 128 at the least, the caller's `block` at the most;
    read from the slab the key loop carries."""
    q = jax.ShapeDtypeStruct((1, 8192, 16, 32), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8192, 2, 32), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda q, k, v: gqa.causal_attention(
        q, k, v, window=window, impl="xla", block=block
    ))(q, k, k))
    assert f"f32[1,2,8,{blocks_of},{blocks_of}]" in text


def test_band_with_another_head_size_for_v_and_a_scale():
    """Latent attention's shapes (q and k wider than v) under a band."""
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(keys[0], (1, 128, 4, 24), jnp.float32)
    k = jax.random.normal(keys[1], (1, 128, 2, 24), jnp.float32)
    v = jax.random.normal(keys[2], (1, 128, 2, 16), jnp.float32)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, axis=2)
    ) * 0.3
    at = jnp.arange(128)
    mask = (at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - 40)
    want = jnp.einsum(
        "bhqk,bkhd->bqhd",
        jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1),
        jnp.repeat(v, 2, axis=2),
    )
    got = gqa.causal_attention(
        q, k, v, scale=0.3, window=40, impl="xla", block=32
    )
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window,impl,engine,ending", [
    (512, "auto", "xla causal_gqa_attention",
     "(blocks of 256; operands: as given, tokens first)"),
    (512, "xla", "xla causal_gqa_attention",
     "(blocks of 256; operands: as given, tokens first)"),
    (None, "auto", "pallas flash_attention", "repeat_kv x8)"),
    (None, "xla", "xla causal_gqa_attention",
     "(blocks of 512; operands: as given, tokens first)"),
])
def test_log_line_names_the_window_and_the_repeats(
    window, impl, engine, ending, monkeypatch, caplog
):
    """`attention engine: ... T=8192 D=128 window=512 ...`; under a band
    every `impl` that runs is the XLA engine in blocks of half the window;
    the Pallas branch says how many times it repeats K and V (x8 in a sliding layer
    of Laguna, x6 in a full one, x16 in Nemotron's cell)."""
    import logging

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("LIBTPU_INIT_ARGS", raising=False)
    q = jax.ShapeDtypeStruct((1, 8192, 64, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)
    logger = logging.getLogger("elasticdl_tpu.ops.gqa")
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="elasticdl_tpu.ops.gqa"):
            jax.eval_shape(
                lambda q, k, v: gqa.causal_attention(
                    q, k, v, window=window, impl=impl
                ), q, k, k,
            )
    finally:
        logger.removeHandler(caplog.handler)
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("attention engine:")]
    want = f"attention engine: {engine} T=8192 D=128"
    if window:
        want += f" window={window}"
    assert any(
        line.startswith(want + " (") and line.endswith(ending)
        for line in lines
    ), lines


@pytest.mark.parametrize("window", [None, 40], ids=["causal", "band-40"])
@pytest.mark.parametrize("d,dv", [(128, 128), (192, 128), (128, 256)],
                         ids=["128", "192-128", "128-256"])
@pytest.mark.parametrize("group", [1, 6, 8])
@pytest.mark.parametrize("sequences", [1, 2])
def test_heads_first_engine_at_one_and_two_sequences(
    sequences, group, d, dv, window
):
    """The engine as the packed callers reach it (ISSUE 43): operands and
    results [B, H, T, D], blocks taken in place by a dynamic slice of the
    token axis, at one sequence (where the old block-major layout was
    free) and at two (where it was a physical transpose), with one, six
    and eight query heads a key-value head and the zoo's head sizes, v's
    other than q's among them."""
    keys = jax.random.split(jax.random.PRNGKey(group + d + dv), 4)
    t, hkv, block = 128, 2, 32
    q = jax.random.normal(keys[0], (sequences, t, hkv * group, d)) * 0.3
    k = jax.random.normal(keys[1], (sequences, t, hkv, d)) * 0.3
    v = jax.random.normal(keys[2], (sequences, t, hkv, dv))
    weight = jax.random.normal(keys[3], (sequences, t, hkv * group, dv))

    def engine(q, k, v):  # tokens first in, tokens first out
        return gqa.heads_first(gqa.causal_gqa_attention(
            *map(gqa.heads_first, (q, k, v)), block, None, window
        ))

    want = _plain(q, k, v, window)
    assert want.shape == (sequences, t, hkv * group, dv)
    np.testing.assert_allclose(engine(q, k, v), want, rtol=2e-4, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(engine(*a) * weight), (0, 1, 2))(q, k, v)
    ref = jax.grad(
        lambda *a: jnp.sum(_plain(*a, window) * weight), (0, 1, 2)
    )(q, k, v)
    for name, a, b in zip("qkv", got, ref):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("window", [None, 100])
def test_packed_front_door_is_the_engine_without_the_transposes(
    window, caplog
):
    """`packed=True`: the operands arrive heads first and the result
    leaves so, to the bit what the tokens-first door gives, and the log
    line says which door it was."""
    import logging

    q, k, v, _ = _qkv(13, 4, 2)
    logger = logging.getLogger("elasticdl_tpu.ops.gqa")
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="elasticdl_tpu.ops.gqa"):
            packed = gqa.causal_attention(
                *map(gqa.heads_first, (q, k, v)), window=window, impl="xla",
                block=BLOCK, packed=True,
            )
            given = gqa.causal_attention(
                q, k, v, window=window, impl="xla", block=BLOCK
            )
    finally:
        logger.removeHandler(caplog.handler)
    assert packed.shape == gqa.heads_first(given).shape
    np.testing.assert_array_equal(gqa.heads_first(packed), given)
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("attention engine:")]
    assert lines[0].endswith("operands: heads first, packed by the caller)")
    assert lines[1].endswith("operands: as given, tokens first)")
    # the Pallas kernel behind the same door, heads first in and out
    if window is None:
        np.testing.assert_array_equal(
            gqa.heads_first(gqa.causal_attention(
                *map(gqa.heads_first, (q, k, v)), impl="pallas", packed=True
            )),
            gqa.causal_attention(q, k, v, impl="pallas"),
        )
