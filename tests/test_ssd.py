"""`ops/ssd.py`: the chunked state-space-dual form of the Mamba-2
recurrence against the token-by-token recurrence, at the two published
shapes that run it (Nemotron-H's eight groups in chunks of 128, Granite
4.0-H's ONE group in chunks of 256).  Tiny sizes, seeded inputs, float32 on
the CPU: 1e-5 of the outputs' size, gradients 5e-5 of each one's largest
entry.  `ssd_recurrent` has its only callers here: it is the written
recurrence the chunked form is held to.  The Pallas engine (interpret
mode here) takes bfloat16 products only: it is held to the recurrence as
far as those allow and to the XLA form at the same products, which
rounds at the same places.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import ssd
from elasticdl_tpu.ops.ssd import (
    ssd_chunked, ssd_chunked_pallas, ssd_chunked_rows, ssd_chunked_xla,
    ssd_recurrent,
)
from lm_contract import _cpu_mesh, _dot_precisions, _log_lines, _reference


def _ssd_inputs(t, seed, b=2, h=4, p=8, g=2, n=16, dt_max=0.5):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, t, h, p))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(dt_max), size=(b, t, h)))
    a = -rng.uniform(1.0, 16.0, size=(h,))
    bm = rng.normal(size=(b, t, g, n))
    cm = rng.normal(size=(b, t, g, n))
    return [jnp.asarray(v, jnp.float32) for v in (x, dt, a, bm, cm)]


# One chunk; several chunks; two T that are no multiple of 128 (one of
# them shorter than a chunk); many chunks, the last one padded; and the
# family's other published shape (Granite 4.0-H): ONE group that every
# head reads, in chunks of 256, two whole and a padded one.  There dt goes
# up to 0.1, the largest step a model starts from, where the others go to
# 0.5: a chunk's running sum of dt A is twice as long at 256, and float32
# resolves a decay no finer than that sum (see the strong-decay test).
@pytest.mark.parametrize("t,g,chunk,dt_max", [
    (128, 2, 128, 0.5), (512, 2, 128, 0.5), (200, 2, 128, 0.5),
    (50, 2, 128, 0.5), (1100, 2, 128, 0.5), (600, 1, 256, 0.1),
])
def test_chunked_ssd_matches_the_recurrence(t, g, chunk, dt_max):
    inputs = _ssd_inputs(t, seed=t, g=g, dt_max=dt_max)
    want, want_state = ssd_recurrent(*inputs)
    got, got_state = ssd_chunked(*inputs, chunk=chunk)
    assert got.shape == want.shape and got.dtype == jnp.float32
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 1e-5 * max(scale, 1.0)
    assert float(jnp.abs(got_state - want_state).max()) < 1e-5 * max(
        float(jnp.abs(want_state).max()), 1.0)


@pytest.mark.parametrize("t,chunk,g,dt_max", [
    (128, 128, 2, 0.5), (384, 128, 2, 0.5), (200, 128, 2, 0.5),
    (150, 32, 2, 0.5), (600, 256, 1, 0.1),
])
def test_chunked_ssd_gradients_match_the_recurrence(t, chunk, g, dt_max):
    """All five gradients, through the outputs and the final state."""
    inputs = _ssd_inputs(t, seed=100 + t, g=g, dt_max=dt_max)
    rng = np.random.default_rng(t)
    weight = jnp.asarray(rng.normal(size=inputs[0].shape), jnp.float32)
    state_weight = jnp.asarray(rng.normal(size=(2, 4, 8, 16)), jnp.float32)

    def grads(rule):
        def total(*a):
            out, state = rule(*a)
            return jnp.sum(out * weight) + jnp.sum(state * state_weight)

        return jax.grad(total, argnums=range(5))(*inputs)

    want = grads(ssd_recurrent)
    got = grads(lambda *a: ssd_chunked(*a, chunk=chunk))
    for name, g, w in zip("x dt a b c".split(), got, want):
        assert float(jnp.abs(g - w).max()) < 5e-5 * float(jnp.abs(w).max()), name


def _rows(x, b, c):
    """[B, T, H, P] and [B, T, G, N] twice -> the rows the kernels read."""
    bsz, t = x.shape[:2]
    return x.reshape(bsz, t, -1), jnp.concatenate(
        [b.reshape(bsz, t, -1), c.reshape(bsz, t, -1)], axis=-1
    )


def _kernels(x, dt, a, b, c, chunk=128, mesh=None):
    """The Pallas engine in interpret mode, of the 4-D tensors."""
    rows, bc = _rows(x, b, c)
    y, state = ssd_chunked_pallas(
        rows, dt, a, bc, groups=b.shape[2], chunk=chunk, interpret=True,
        mesh=mesh,
    )
    return y.reshape(x.shape), state


@pytest.mark.parametrize("rule,shape,limit", [
    pytest.param(ssd_chunked, {}, 1e-4, id="xla"),
    # bfloat16 products: a decayed state reads 0 all the same
    pytest.param(_kernels, dict(h=8, p=64, g=1, n=128), 2e-2, id="kernels"),
])
def test_chunked_ssd_stays_finite_under_strong_decay(rule, shape, limit):
    """dt A down to -80 a token: the decays are differences of running
    sums that never leave (-inf, 0], so nothing overflows and a fully
    decayed state reads 0, forward and backward.  The running sum reaches
    -10,000 inside a chunk here, where float32 resolves 1e-3, so a decay
    is right to 1e-3 of itself (1e-5 at the steps the model starts from:
    the source's kernels take the same differences in float32)."""
    x, dt, a, b, c = _ssd_inputs(256, seed=9, **shape)
    dt = dt * 10.0
    got, state = rule(x, dt, a, b, c)
    want, _ = ssd_recurrent(x, dt, a, b, c)
    assert bool(jnp.isfinite(got).all()) and bool(jnp.isfinite(state).all())
    assert float(jnp.abs(got - want).max()) < limit * float(jnp.abs(want).max())
    grads = jax.grad(
        lambda *v: jnp.sum(rule(*v)[0]), argnums=range(5)
    )(x, dt, a, b, c)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


# The kernels at both groupings (Nemotron-H's eight groups of eight
# heads; ONE group, of sixteen heads so that two blocks of heads add up
# in d B and d C) and both chunk sizes: two chunks in one grid step, the
# second padded; three chunks of 256, a step each, the last padded; eight
# whole chunks, four a step, of two sequences.
_KERNEL_CASES = [
    pytest.param(200, dict(b=1, h=64, g=8), 128, 0.5, id="8x8-chunks-of-128"),
    pytest.param(600, dict(b=1, h=16, g=1), 256, 0.1, id="1x16-chunks-of-256"),
    pytest.param(1024, dict(b=2, h=8, g=1), 128, 0.5, id="1x8-two-steps"),
]


def _of_largest(got, want):
    return float(jnp.abs(got - want).max()) / float(jnp.abs(want).max())


@pytest.mark.parametrize("t,shape,chunk,dt_max", _KERNEL_CASES)
def test_ssd_kernels_match_the_recurrence(t, shape, chunk, dt_max):
    """Forward: within the bfloat16 products' 1% of the recurrence, as
    the XLA form with the same products is, and within 0.3% of that form
    (the same roundings at the same places; what differs is the order of
    the float32 sums, which moves a rounding here and there)."""
    inputs = _ssd_inputs(t, seed=t, p=64, n=128, dt_max=dt_max, **shape)
    want, want_state = ssd_recurrent(*inputs)
    xla, xla_state = ssd_chunked_xla(*inputs, chunk=chunk, dtype=jnp.bfloat16)
    got, got_state = jax.jit(lambda *a: _kernels(*a, chunk=chunk))(*inputs)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert _of_largest(got, want) < 1e-2
    assert _of_largest(got_state, want_state) < 1e-2
    assert _of_largest(got, xla) < 3e-3
    assert _of_largest(got_state, xla_state) < 3e-3


@pytest.mark.parametrize("t,shape,chunk,dt_max", _KERNEL_CASES)
def test_ssd_kernels_gradients_match_the_xla_form(t, shape, chunk, dt_max):
    """All five gradients, through the outputs and the final state:
    within 1% of each one's largest entry of the XLA form's at bfloat16
    products and of the recurrence's; d a, a sum over every token of a
    running sum's gradient, within 3%.  The kernels round d y to bfloat16
    for their products, as a TPU's default precision does in the XLA
    form's backward pass; a CPU keeps it float32 there, so the XLA form
    reads closer to the recurrence here than on the chip, where the two
    engines' d a differ by 0.15% at most (PERF.md, PR 46)."""
    inputs = _ssd_inputs(t, seed=100 + t, p=64, n=128, dt_max=dt_max, **shape)
    rng = np.random.default_rng(t)
    weight = jnp.asarray(rng.normal(size=inputs[0].shape), jnp.float32)
    state_weight = jnp.asarray(
        rng.normal(size=(shape["b"], shape["h"], 64, 128)), jnp.float32
    )

    def grads(rule):
        def total(*a):
            out, state = rule(*a)
            return jnp.sum(out * weight) + jnp.sum(state * state_weight)

        return jax.jit(jax.grad(total, argnums=range(5)))(*inputs)

    want = grads(ssd_recurrent)
    xla = grads(
        lambda *a: ssd_chunked_xla(*a, chunk=chunk, dtype=jnp.bfloat16)
    )
    got = grads(lambda *a: _kernels(*a, chunk=chunk))
    for name, g, x, w in zip("x dt a b c".split(), got, xla, want):
        limit = 3e-2 if name == "a" else 1e-2
        assert _of_largest(g, x) < limit and _of_largest(g, w) < limit, name


@pytest.mark.parametrize("b,mesh", [(2, (2, 2)), (1, (2, 1))])
def test_ssd_kernels_under_a_mesh_are_the_kernels(b, mesh):
    """Under a mesh of several devices each pass runs inside a shard_map
    over the data axis (a sequence a device; all of them on every device
    where the axis does not divide the batch), `a` whole on every device:
    outputs, final state and all five gradients are the unmapped
    kernels' own."""
    inputs = _ssd_inputs(200, seed=7 + b, b=b, h=8, p=64, g=1, n=128)
    rng = np.random.default_rng(b)
    weight = jnp.asarray(rng.normal(size=inputs[0].shape), jnp.float32)

    def run(mesh):
        def total(*a):
            out, state = _kernels(*a, mesh=mesh)
            return jnp.sum(out * weight) + jnp.sum(state), (out, state)

        return jax.jit(
            jax.value_and_grad(total, argnums=range(5), has_aux=True)
        )(*inputs)

    (_, want), want_grads = run(None)
    (_, got), got_grads = run(_cpu_mesh(*mesh))
    for g, w in zip(got + got_grads, want + want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("engine", ["xla", "pallas"])
def test_rows_in_and_out_are_the_four_dimensional_form(engine, monkeypatch):
    """`ssd_chunked_rows`, what the layer calls, takes x [B, T, H P] and
    [B | C] [B, T, 2 G N] and returns y as rows: the same numbers as the
    engine's own door gives the [B, T, H, P] tensors, by either engine."""
    monkeypatch.setattr(ssd, "_engine", lambda *a: (engine, "dictated"))
    monkeypatch.setattr(ssd, "_use_interpret", lambda: True)
    x, dt, a, b, c = _ssd_inputs(300, seed=3, h=16, p=64, g=2, n=128)
    rows, bc = _rows(x, b, c)
    got, got_state = ssd_chunked_rows(
        rows, dt, a, bc, groups=2, dtype=jnp.bfloat16
    )
    same, same_state = ssd_chunked(x, dt, a, b, c, dtype=jnp.bfloat16)
    want, want_state = (
        _kernels(x, dt, a, b, c) if engine == "pallas"
        else ssd_chunked_xla(x, dt, a, b, c, dtype=jnp.bfloat16)
    )
    assert got.shape == rows.shape
    for g, w in ((got.reshape(x.shape), want), (same, want),
                 (got_state, want_state), (same_state, want_state)):
        np.testing.assert_array_equal(g, w)


def test_reference_scan_is_the_written_recurrence():
    """The reference's own token-by-token scan against the program's
    recurrent form: two independent writings of the same equations."""
    x, dt, a, b, c = _ssd_inputs(96, seed=5, b=1)
    want, _ = ssd_recurrent(x, dt, a, b, c)
    got = _reference("nemotron_h_reference.py")._selective_scan(
        x[0], dt[0], a, jnp.repeat(b[0], 2, axis=1), jnp.repeat(c[0], 2, axis=1)
    )
    np.testing.assert_allclose(got, want[0], atol=1e-5)


def test_ssd_engine_line_names_the_trace(monkeypatch):
    lines, handler = _log_lines(ssd.logger)
    try:
        shapes = [
            jax.ShapeDtypeStruct(s, jnp.float32) for s in (
                (1, 8192, 64, 64), (1, 8192, 64), (64,), (1, 8192, 8, 128),
                (1, 8192, 8, 128),
            )
        ]
        out, state = jax.eval_shape(
            lambda *a: ssd_chunked(*a, dtype=jnp.bfloat16), *shapes
        )
    finally:
        ssd.logger.removeHandler(handler)
    assert out.shape == (1, 8192, 64, 64) and state.shape == (1, 64, 64, 128)
    assert lines == [
        "ssd engine: xla ssd_chunked T=8192 H=64 P=64 N=128 "
        "chunks of 128, products in bfloat16 (backend cpu)"
    ]


@pytest.mark.parametrize("shape,chunk", [
    pytest.param(dict(t=200, b=2, g=2), 128, id="groups-of-heads-chunks-of-128"),
    pytest.param(dict(t=600, b=1, g=1), 256, id="one-group-chunks-of-256"),
])
def test_four_products_ask_for_highest_where_they_are_float32(shape, chunk):
    """In the float32 model the form's four products ask for `HIGHEST`
    themselves (a product left to a TPU's default would round its float32
    operands to bfloat16); in the bfloat16 model they take bfloat16
    operands and ask for nothing: what the whole models' traces show of
    them (test_float32_products_ask_for_their_precision)."""
    highest = jax.lax.Precision.HIGHEST
    for dtype, count in ((jnp.float32, 4), (jnp.bfloat16, 0)):
        rule = _dot_precisions(
            jax.make_jaxpr(lambda *a: ssd_chunked(*a, chunk=chunk, dtype=dtype))(
                *_ssd_inputs(seed=0, **shape)
            ).jaxpr
        )
        assert len(rule) == 4
        assert sum(p == (highest, highest) for _, p in rule) == count
        assert all(d == dtype for d, _ in rule)
