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


def _allowed(tokens, length):
    """The block-diffusion mask over [noised | clean], dense, from its
    three lines: [2 tokens, 2 tokens], rows the queries."""
    at = np.arange(2 * tokens)
    noised, blk = at < tokens, at % tokens // length
    q_noised, k_noised = noised[:, None], noised[None, :]
    return jnp.asarray(
        (q_noised & k_noised & (blk[None, :] == blk[:, None]))
        | (q_noised & ~k_noised & (blk[None, :] < blk[:, None]))
        | (~q_noised & ~k_noised & (blk[None, :] <= blk[:, None]))
    )


def _plain(q, k, v, window=None, block_diffusion=None):
    """A full masked softmax, grouped-query heads, float32: causal, a
    band of `window` keys, or `block_diffusion=(tokens, length)`."""
    t, n_rep = q.shape[1], q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, n_rep, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
    at = jnp.arange(t)
    mask = at[None, :] <= at[:, None]
    if window is not None:
        mask = mask & (at[None, :] > at[:, None] - window)
    if block_diffusion is not None:
        mask = _allowed(*block_diffusion)
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
    gives it blocks of half the window, 128 at the least); `window` may
    be a mask rule instead."""
    def rule(t):
        if isinstance(window, gqa.BlockDiffusion):
            return window
        return gqa.Causal(window if window and window < t else None)

    return lambda q, k, v: gqa.heads_first(gqa.causal_gqa_attention(
        *map(gqa.heads_first, (q, k, v)), block, None, rule(q.shape[1]),
    ))


# W smaller than, equal to and larger than a block of 64; one key; one
# short of and one past a block's edge; the whole sequence and beyond.
WINDOWS = [1, 17, 63, 64, 65, 100, 128, 129, 255, 256, 1000]
#: `_qkv`'s shape for the band's other published shape (Mellum 2's: a
#: window of 1024 under 8 query heads a key-value head), where the front
#: door gives the engine blocks of 512.
MELLUM_BAND = dict(hq=8, hkv=1, t=2048, d=16, b=1)


#: The block-diffusion rule (ISSUE 51) as cases of the same test: a
#: noised and a clean copy of 256 / 512 tokens in blocks of 4 and 16,
#: 8 query heads over 2, the engine in tiles of 64 and 128.
BLOCK_DIFFUSION = [
    pytest.param(
        gqa.BlockDiffusion(tokens, length),
        dict(hq=8, hkv=2, t=2 * tokens, d=16, b=1, block=tile),
        id=f"block-diffusion-{tokens}-by-{length}-tiles-of-{tile}",
    )
    for tokens in (256, 512) for length in (4, 16) for tile in (64, 128)
]


@pytest.mark.parametrize("impl", ["xla", "auto"])
@pytest.mark.parametrize("window,shape", [
    pytest.param(window, dict(hq=4, hkv=2), id=str(window))
    for window in WINDOWS
] + [pytest.param(1024, MELLUM_BAND, id="1024-of-2048-x8")]
  + BLOCK_DIFFUSION)
def test_band_matches_a_masked_plain_softmax(impl, window, shape):
    """`impl` is the front door's; the engine beneath is the XLA one.
    `window`: a band's keys, or the block-diffusion rule."""
    shape = dict(shape)
    block = shape.pop("block", BLOCK)
    if isinstance(window, gqa.BlockDiffusion):
        mask = dict(block_diffusion=tuple(window))
        door = dict(mask, block=block)
        seed = window.tokens + window.length
    else:
        mask = door = dict(window=window)
        seed = window
    q, k, v, weight = _qkv(seed, **shape)
    engine = _engine(window, block)
    want = _plain(q, k, v, **mask)
    # the front door, with its own choice of blocks, gives the same
    front = jax.grad(lambda *a: jnp.sum(gqa.causal_attention(
        *a, impl=impl, **door
    ) * weight), (0, 1, 2))
    np.testing.assert_allclose(
        gqa.causal_attention(q, k, v, impl=impl, **door), want,
        rtol=2e-4, atol=2e-5,
    )
    np.testing.assert_allclose(engine(q, k, v), want, rtol=2e-4, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(engine(*a) * weight), (0, 1, 2))(q, k, v)
    ref = jax.grad(
        lambda *a: jnp.sum(_plain(*a, **mask) * weight), (0, 1, 2)
    )(q, k, v)
    for name, a, b, c in zip("qkv", got, ref, front(q, k, v)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4, err_msg=name)
        np.testing.assert_allclose(c, b, rtol=2e-3, atol=2e-4, err_msg=name)
    if "block_diffusion" in mask or window < q.shape[1]:
        # the mask is in the result: full causal attention differs
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
            *map(gqa.heads_first, (q, k, v)), block, None,
            gqa.Causal(window),
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


# ---------------------------------------------------------------------------
# The mask as a rule (ISSUE 51): the block-diffusion rule's visits, and the
# two rules there were, lowered as at the parent
# ---------------------------------------------------------------------------


def _count_scored_tiles(monkeypatch, run):
    """How often `run()` scores a tile, with the loops run in Python
    (`jax.disable_jit`: a `fori_loop` is then a `for`), so that every
    visit of the engine, forward or backward, is one call of `_scores`."""
    visits = []
    scores = gqa._scores

    def counted(q_i, k_j, i, j, *rest):
        visits.append((int(i), int(j)))
        return scores(q_i, k_j, i, j, *rest)

    monkeypatch.setattr(gqa, "_scores", counted)
    with jax.disable_jit():
        run()
    return visits


@pytest.mark.parametrize("tokens,tile", [(256, 64), (256, 128), (512, 64)])
def test_block_diffusion_visits_n_n_plus_1_plus_n_tiles(
    tokens, tile, monkeypatch
):
    """For the 2 n query tiles of a noised and a clean copy the engine
    scores n (n + 1) + n key tiles, forward, and as many again backward:
    the clean tiles 0..i for query tile i of either half and the noised
    tile i for the noised one; a plain mask over the 2 n tiles would
    score n (2 n + 1).  Counted from the loops as they run."""
    n = tokens // tile
    q, k, v, weight = _qkv(1, 4, 2, t=2 * tokens, d=8, b=1)
    engine = _engine(gqa.BlockDiffusion(tokens, 4), tile)
    forward = _count_scored_tiles(monkeypatch, lambda: engine(q, k, v))
    assert len(forward) == n * (n + 1) + n < n * (2 * n + 1)
    want = (
        [(i, n + j) for i in range(n) for j in range(i + 1)]      # noised,
        + [(i, i) for i in range(n)]                              # its own,
        + [(n + i, n + j) for i in range(n) for j in range(i + 1)]  # clean
    )
    assert sorted(forward) == sorted(want)
    # a noised query tile's own tile is its last
    assert [j for i, j in forward if i == n - 1][-1] == n - 1
    both = _count_scored_tiles(monkeypatch, lambda: jax.grad(
        lambda q: jnp.sum(engine(q, k, v) * weight)
    )(q))
    assert sorted(both) == sorted(2 * want)  # the forward, then the backward
    # the causal rule over the same 2 n tiles, for scale
    causal = _count_scored_tiles(
        monkeypatch, lambda: _engine(None, tile)(q, k, v)
    )
    assert len(causal) == n * (2 * n + 1)
    # at the cell's shape: 288 of 528
    rule, cell = gqa.BlockDiffusion(8192, 4), 8192 // 512
    assert sum(
        int(hi) - int(lo)
        for lo, hi in (rule.visits(i, 512) for i in range(2 * cell))
    ) == 288 == cell * (cell + 1) + cell
    assert cell * (2 * cell + 1) == 528


def test_block_diffusion_reads_no_tile_outside_its_rule():
    """SKIPPED, not masked, forward and backward: with K and V poisoned
    (NaN) in every tile that query tile 1 of the noised half does not
    visit (the noised tiles but its own, the clean tiles after its own),
    its rows and its queries' gradients are those of clean inputs."""
    tokens, tile = 256, 64
    rule = gqa.BlockDiffusion(tokens, 4)
    q, k, v, weight = _qkv(2, 4, 2, t=2 * tokens, d=16, b=1)
    at = jnp.arange(2 * tokens)[None, :, None, None]
    read = ((at >= tile) & (at < 2 * tile)) | (
        (at >= tokens) & (at < tokens + 2 * tile)
    )
    poison = jnp.where(read, 0.0, jnp.nan)
    rows = (at >= tile) & (at < 2 * tile)

    def own(q, k, v):
        return jnp.where(rows, _engine(rule, tile)(q, k, v), 0.0)

    got = own(q, k + poison, v + poison)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(
        got, jnp.where(rows, _plain(q, k, v, block_diffusion=rule), 0.0),
        rtol=2e-4, atol=2e-5,
    )
    dq = jax.grad(lambda q: jnp.sum(own(q, k + poison, v + poison) * weight))(q)
    clean = jax.grad(lambda q: jnp.sum(own(q, k, v) * weight))(q)
    assert bool(jnp.isfinite(dq[:, tile:2 * tile]).all())
    np.testing.assert_allclose(
        dq[:, tile:2 * tile], clean[:, tile:2 * tile], rtol=2e-3, atol=2e-4
    )
    # the causal rule over the same positions does read the noised tile 0
    full = _engine(None, tile)(q, k + poison, v + poison)
    assert not bool(jnp.isfinite(full[:, tile:2 * tile]).all())


def test_front_door_says_what_a_block_diffusion_mask_needs():
    q, k, v, _ = _qkv(5, 4, 2, t=512, d=8, b=1)
    door = gqa.causal_attention
    with pytest.raises(ValueError, match="no block-diffusion mask"):
        door(q, k, v, block_diffusion=(256, 4), impl="pallas")
    with pytest.raises(ValueError, match="takes no window"):
        door(q, k, v, block_diffusion=(256, 4), window=64)
    with pytest.raises(ValueError, match="1024 positions: q has 512"):
        door(q, k, v, block_diffusion=(512, 4))
    with pytest.raises(ValueError, match="does not divide the tile of 64"):
        door(q[:, :384], k[:, :384], v[:, :384], block_diffusion=(192, 48),
             block=64)
    with pytest.raises(ValueError, match="does not divide its 256 tokens"):
        door(q, k, v, block_diffusion=(256, 3), block=64)
    # a block of the whole copy: the noised half attends to itself in
    # both directions and to nothing else
    whole = door(q, k, v, block_diffusion=(256, 256), impl="auto")
    scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, :256], jnp.repeat(
        k[:, :256], 2, axis=2)) / 8 ** 0.5
    dense = jnp.einsum(
        "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
        jnp.repeat(v[:, :256], 2, axis=2),
    )
    np.testing.assert_allclose(whole[:, :256], dense, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window,golden", [
    (None, "gqa_causal_packed.hlo.gz"), (512, "gqa_band_packed.hlo.gz"),
], ids=["causal", "band-512"])
def test_causal_and_banded_callers_lower_to_the_parents_program(
    window, golden
):
    """The two rules the engine had before it took its mask as a rule
    lower to the text they lowered to then: forward and backward of the
    packed front door at 2 x 1024 tokens, 8 heads over 2 of 64, blocks
    of 256, against the text recorded at the commit before ISSUE 51
    (`tests/data/`: `jax.jit(...).lower(...).as_text()`, gzipped), so
    that no cell's program moved.  Equal but for the NUMBERS of the
    private functions (`@closed_call_39`, `@_where_42`): they come from
    one counter of the trace, and since PR 52 the forward rule's two
    `checkpoint_name`s (`ATTN_OUT`, `ATTN_LSE`: equations that lower to
    nothing) move it on by one, so each function is numbered here by
    its first appearance."""
    import difflib
    import gzip
    import os
    import re

    def numbered_by_appearance(text):
        seen = {}
        return re.sub(
            r"@(\w+?)_\d+\b",
            lambda m: seen.setdefault(m.group(0), f"@{m.group(1)}.{len(seen)}"),
            text,
        )

    q = jax.ShapeDtypeStruct((2, 8, 1024, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 2, 1024, 64), jnp.bfloat16)
    text = jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(gqa.causal_attention(
            q, k, v, window=window, impl="xla", block=256, packed=True,
        ).astype(jnp.float32) ** 2), (0, 1, 2),
    )).lower(q, k, k).as_text()
    path = os.path.join(os.path.dirname(__file__), "data", golden)
    with gzip.open(path, "rt") as f:
        recorded = numbered_by_appearance(f.read())
    text = numbered_by_appearance(text)
    assert text == recorded, "".join(list(difflib.unified_diff(
        recorded.splitlines(True), text.splitlines(True), "parent", "now",
    ))[:60])
