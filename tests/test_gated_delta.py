"""`ops/gated_delta.py`: the chunked gated delta rule in its XLA form and
in its Pallas kernels (interpret mode here), each against the token-by-token
recurrence, and the engine's choice between them.  Tiny sizes, seeded
inputs, float32 on the CPU: 1e-5 of the outputs' size, gradients 2e-5 to
5e-5 of each one's largest entry.  `gated_delta_rule_recurrent` has its
only callers here: it is the written recurrence the engines are held to.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import gated_delta
from elasticdl_tpu.ops.gated_delta import (
    chunk_gated_delta_rule, chunk_gated_delta_rule_pallas,
    chunk_gated_delta_rule_xla, gated_delta_rule_recurrent,
)
from lm_contract import (
    _cpu_mesh, _dot_precisions, _dots, _log_lines, _reference,
)


def _delta_inputs(t, seed, b=2, hk=2, hv=4, dk=16, dv=8):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(b, t, hk, dk))
    k = rng.normal(size=(b, t, hk, dk))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(b, t, hv, dv))
    g = -0.3 * np.exp(rng.normal(size=(b, t, hv)))
    beta = 1.0 / (1.0 + np.exp(-rng.normal(size=(b, t, hv))))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


def _recurrent(q, k, v, g, beta):
    repeat = v.shape[2] // k.shape[2]
    return gated_delta_rule_recurrent(
        jnp.repeat(q, repeat, axis=2), jnp.repeat(k, repeat, axis=2),
        v, g, beta,
    )



@pytest.mark.parametrize("passes", ["forward", "backward"])
def test_kernel_products_are_three_bfloat16_passes(passes):
    """The Pallas engine's own jaxprs (interpret mode on a CPU computes
    in float32 whatever is asked, so no number here can say it): Mosaic
    takes no `Precision.HIGH`, so every product in the kernels is HIGH
    written out: bfloat16 operands, float32 accumulation, and the three
    terms hi hi + hi lo + lo hi as one contraction of [hi | hi | lo]
    with [hi | lo | hi], three times the product's own length; no
    float32 operand reaches a product at any precision."""
    inputs = _delta_inputs(200, seed=0, hk=1, hv=2, dk=128, dv=128)

    def forward(*a):
        return chunk_gated_delta_rule_pallas(*a, interpret=False)[0]

    fn = forward if passes == "forward" else jax.grad(
        lambda *a: jnp.sum(forward(*a)), argnums=range(5)
    )
    dots = list(_dots(jax.make_jaxpr(fn)(*inputs).jaxpr))
    # 17 products a chunk forward; the backward kernel walks forward too
    assert len(dots) >= (17 if passes == "forward" else 60)
    for eqn, in_kernel in dots:
        assert in_kernel  # outside its kernels the engine multiplies nothing
        lhs, rhs = (var.aval for var in eqn.invars)
        assert lhs.dtype == rhs.dtype == jnp.bfloat16, (lhs, rhs)
        assert eqn.outvars[0].aval.dtype == jnp.float32
        assert eqn.params["precision"] is None
        (lhs_axes, rhs_axes), _ = eqn.params["dimension_numbers"]
        contracted = lhs.shape[lhs_axes[0]]
        assert contracted == rhs.shape[rhs_axes[0]]
        assert contracted in (3 * 64, 3 * 128, 3 * 256), contracted



def test_rule_products_ask_for_high_precision():
    """Every product of the XLA form has float32 operands, the state among
    them, and asks for `Precision.HIGH`: a product left to a TPU's default
    would round them to bfloat16 (what the whole model's trace shows of
    them: tests/test_qwen3_next.py's
    test_float32_products_ask_for_their_precision)."""
    high = jax.lax.Precision.HIGH
    rule = _dot_precisions(
        jax.make_jaxpr(lambda *a: chunk_gated_delta_rule(*a))(
            *_delta_inputs(200, seed=0)
        ).jaxpr
    )
    assert len(rule) > 10
    assert all(p == (high, high) for _, p in rule)
    assert all(dtype == jnp.float32 for dtype, _ in rule)


# One chunk; several chunks in one group; a T that is no multiple of 64;
# several groups, the last one padded.
@pytest.mark.parametrize("t", [64, 256, 200, 1100])
def test_chunked_delta_rule_matches_the_recurrence(t):
    inputs = _delta_inputs(t, seed=t)
    want, want_state = _recurrent(*inputs)
    got, got_state = chunk_gated_delta_rule(*inputs)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 1e-5 * max(scale, 1.0)
    assert float(jnp.abs(got_state - want_state).max()) < 1e-5


@pytest.mark.parametrize("t", [64, 256, 200])
def test_chunked_delta_rule_gradients_match_the_recurrence(t):
    inputs = _delta_inputs(t, seed=100 + t)
    weight = jnp.asarray(
        np.random.default_rng(t).normal(size=inputs[2].shape), jnp.float32
    )
    want = jax.grad(
        lambda *a: jnp.sum(_recurrent(*a)[0] * weight), argnums=range(5)
    )(*inputs)
    got = jax.grad(
        lambda *a: jnp.sum(chunk_gated_delta_rule(*a)[0] * weight),
        argnums=range(5),
    )(*inputs)
    for name, g, w in zip("q k v g beta".split(), got, want):
        assert float(jnp.abs(g - w).max()) < 2e-5 * float(jnp.abs(w).max()), name


# The Pallas engine (interpret mode here) at head sizes it takes: one
# chunk, one group, a padded tail, several groups; one pair of value
# heads a key head, two pairs of one key head (their q and k gradients
# add up in the kernel), two key heads.  Its products are three bfloat16
# passes, float32 to about 1e-5 of a product where the XLA engine on a
# CPU is float32 itself.
_KERNEL_CASES = [
    (64, dict(b=1, hk=1, hv=2)),
    (128, dict(b=2, hk=1, hv=2)),
    (200, dict(b=2, hk=1, hv=2)),
    (1100, dict(b=1, hk=1, hv=2)),
    (200, dict(b=1, hk=1, hv=4)),
    (200, dict(b=1, hk=2, hv=4)),
]


def _kernel(*inputs):
    return chunk_gated_delta_rule_pallas(*inputs, interpret=True)


@pytest.mark.parametrize("t,shape", _KERNEL_CASES)
def test_delta_rule_kernel_matches_the_recurrence(t, shape):
    inputs = _delta_inputs(t, seed=t, dk=128, dv=128, **shape)
    want, want_state = _recurrent(*inputs)
    xla, xla_state = chunk_gated_delta_rule_xla(*inputs)
    got, got_state = jax.jit(_kernel)(*inputs)
    scale = max(float(jnp.abs(want).max()), 1.0)
    for other, other_state in ((want, want_state), (xla, xla_state)):
        assert float(jnp.abs(got - other).max()) < 1e-5 * scale
        assert float(jnp.abs(got_state - other_state).max()) < 2e-5


@pytest.mark.parametrize("t,shape", _KERNEL_CASES)
def test_delta_rule_kernel_gradients_match_the_recurrence(t, shape):
    """All five gradients, through the outputs and the final state."""
    inputs = _delta_inputs(t, seed=100 + t, dk=128, dv=128, **shape)
    rng = np.random.default_rng(t)
    weight = jnp.asarray(rng.normal(size=inputs[2].shape), jnp.float32)
    state_weight = jnp.asarray(
        rng.normal(size=(inputs[2].shape[0], inputs[2].shape[2], 128, 128)),
        jnp.float32,
    )

    def grads(rule):
        def total(*a):
            out, state = rule(*a)
            return jnp.sum(out * weight) + jnp.sum(state * state_weight)

        return jax.jit(jax.grad(total, argnums=range(5)))(*inputs)

    got = grads(_kernel)
    for other in (grads(_recurrent), grads(chunk_gated_delta_rule_xla)):
        for name, g, w in zip("q k v g beta".split(), got, other):
            assert (
                float(jnp.abs(g - w).max()) < 5e-5 * float(jnp.abs(w).max())
            ), name


@pytest.mark.parametrize("backend,devices,mesh,hk,hv,dk,engine,why", [
    # the published shapes on one chip, the cell's case
    ("tpu", 1, None, 16, 32, 128, "pallas", "one device"),
    ("tpu", 4, (1, 1), 16, 32, 128, "pallas", "one device"),
    # a mesh of several chips: the kernels a data shard a device
    ("tpu", 4, (2, 2), 16, 32, 128, "pallas",
     "under shard_map over {'data': 2, 'model': 2}"),
    # several chips and no mesh named: the trace may be for all of them
    ("tpu", 4, None, 16, 32, 128, "xla", "4 devices and no mesh given"),
    # heads of 256: fewer of them a grid step, for VMEM
    ("tpu", 1, None, 16, 32, 256, "pallas", "one device"),
    # one key head's sixteen value heads of 256 do not fit in VMEM
    ("tpu", 1, None, 1, 16, 256, "xla",
     "head sizes or counts the kernels do not take"),
    # a head is no whole lane tile
    ("tpu", 1, None, 2, 4, 16, "xla",
     "head sizes or counts the kernels do not take"),
    # no two value heads a key head
    ("tpu", 1, None, 2, 2, 128, "xla",
     "head sizes or counts the kernels do not take"),
    # interpret mode is for tests
    ("cpu", 1, None, 16, 32, 128, "xla", "backend cpu"),
])
def test_delta_rule_engine_choice(backend, devices, mesh, hk, hv, dk, engine,
                                  why, monkeypatch):
    """On a TPU the kernels where `supports` holds and the trace is for
    one device or names its mesh, the XLA form for every other shape,
    for a trace that may be for several devices and off the TPU; the
    worker's log line says which and why (traced only: shapes, no
    device)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    mesh = mesh and _cpu_mesh(*mesh)
    t = 8192
    qk = jax.ShapeDtypeStruct((2, t, hk, dk), jnp.float32)
    v = jax.ShapeDtypeStruct((2, t, hv, dk), jnp.float32)
    gate = jax.ShapeDtypeStruct((2, t, hv), jnp.float32)
    lines, handler = _log_lines(gated_delta.logger)
    try:
        jaxpr = jax.make_jaxpr(  # a new function: no cached trace
            lambda *a: chunk_gated_delta_rule(*a, mesh=mesh)
        )(qk, qk, v, gate, gate)
    finally:
        gated_delta.logger.removeHandler(handler)
    assert [aval.shape for aval in jaxpr.out_avals] == [
        v.shape, (2, hv, dk, dk)
    ]
    assert lines == [
        f"delta rule engine: {engine} chunk_gated_delta_rule "
        f"T={t} Dk={dk} Dv={dk} ({why})"
    ]
    assert ("pallas_call" in str(jaxpr)) == (engine == "pallas")
    assert ("shard_map" in str(jaxpr)) == why.startswith("under shard_map")


@pytest.mark.parametrize("b,mesh", [(2, (2, 2)), (4, (4, 1)), (1, (2, 1))])
def test_delta_rule_kernel_under_a_mesh_is_the_kernel(b, mesh):
    """Under a mesh of several devices each pass runs inside a shard_map
    over the data axis (a sequence or two a device; all of them on every
    device where the axis does not divide the batch): outputs, final
    state and all five gradients are the unmapped kernels' own."""
    inputs = _delta_inputs(200, seed=7 + b, dk=128, dv=128, b=b, hk=1, hv=2)
    rng = np.random.default_rng(b)
    weight = jnp.asarray(rng.normal(size=inputs[2].shape), jnp.float32)

    def run(mesh):
        def total(*a):
            out, state = chunk_gated_delta_rule_pallas(
                *a, interpret=True, mesh=mesh
            )
            return jnp.sum(out * weight) + jnp.sum(state), (out, state)

        return jax.jit(
            jax.value_and_grad(total, argnums=range(5), has_aux=True)
        )(*inputs)

    (_, want), want_grads = run(None)
    (_, got), got_grads = run(_cpu_mesh(*mesh))
    for g, w in zip(got + got_grads, want + want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)



def test_reference_delta_rule_is_the_written_recurrence():
    """The reference's own token-by-token rule against the program's
    recurrent form: two independent writings of the same equations."""
    q, k, v, g, beta = _delta_inputs(96, seed=5, b=1)
    want, _ = _recurrent(q, k, v, g, beta)
    got = _reference("qwen3_next_reference.py")._delta_rule(
        jnp.repeat(q[0], 2, axis=1), jnp.repeat(k[0], 2, axis=1),
        v[0], g[0], beta[0],
    )
    np.testing.assert_allclose(got, want[0], atol=1e-6)

