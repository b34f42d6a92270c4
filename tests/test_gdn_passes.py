"""`ops/gdn_passes.py`: what surrounds the delta rule in a Gated DeltaNet
layer, one pass each way over head-major rows (the causal convolution with
silu and the l2-norm by head; the gated RMSNorm), the Pallas kernels
(interpret mode here) against the plain `jax.numpy` chain, and the engine's
choice.  The layer that calls them is `model_zoo/qwen3_next`'s
`GatedDeltaNet`, which the engine-choice case traces.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import gated_delta, gdn_passes
from lm_contract import _close, _cpu_mesh, _log_lines


# T = 64 and 200 are one block of as many rows; with blocks of 256 rows
# (the cell's 8192 are four of 2048) 1100 is four blocks and a ragged
# fifth, each edge inside the convolution's reach.  One and two value
# heads a key head, two key heads.
_PASS_CASES = [
    (t, hk, hv) for t in (64, 200, 1100)
    for hk, hv in ((1, 1), (1, 2), (2, 4))
]


@pytest.fixture
def blocks_of_256_rows(monkeypatch):
    monkeypatch.setattr(gdn_passes, "ROWS", 256)



@pytest.mark.parametrize("t,hk,hv", _PASS_CASES)
@pytest.mark.parametrize("bias", [False, True])
def test_conv_silu_kernels_match_the_plain_chain(t, hk, hv, bias,
                                                 blocks_of_256_rows):
    """q's pass (the l2-norm by head, scaled), v's (none) and, with a
    bias, a state-space layer's: outputs and every gradient (rows, taps,
    bias) against the `jax.numpy` chain, which pads and shifts."""
    rng = np.random.default_rng(t + hk + hv)
    for width, head, scale in ((hk * 128, 128, 128 ** -0.5),
                               (hv * 128, 0, 1.0)):
        rows, weight = (
            jnp.asarray(rng.normal(size=(2, t, width)), jnp.float32)
            for _ in range(2)
        )
        taps = jnp.asarray(rng.normal(size=(4, width)), jnp.float32)
        offset = bias and jnp.asarray(rng.normal(size=(width,)), jnp.float32)

        def run(pallas):
            def total(rows, taps, offset):
                out = gdn_passes.conv_silu(
                    rows, taps, offset if bias else None, head=head,
                    scale=scale, pallas=pallas, interpret=True,
                )
                return jnp.sum(out * weight), out

            return jax.jit(jax.value_and_grad(
                total, argnums=(0, 1, 2) if bias else (0, 1), has_aux=True
            ))(rows, taps, offset)

        ((_, got), got_grads), ((_, want), want_grads) = run(True), run(False)
        _close(got, want, 1e-6, (width, "out"))
        for g, w, name in zip(got_grads, want_grads, ("rows", "taps", "bias")):
            assert g.shape == w.shape
            _close(g, w, 2e-6, (width, name))


@pytest.mark.parametrize("t,hk,hv", _PASS_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gated_norm_kernels_match_the_plain_chain(t, hk, hv, dtype,
                                                  blocks_of_256_rows):
    """Outputs (in the out-projection's operand type) and the gradients
    of o, z and the norm's weight."""
    rng = np.random.default_rng(t + hv)
    out, gate, weight = (
        jnp.asarray(rng.normal(size=(2, t, hv * 128)), jnp.float32)
        for _ in range(3)
    )
    norm_weight = jnp.asarray(rng.normal(size=(128,)), jnp.float32)

    def run(pallas):
        def total(out, gate, norm_weight):
            y = gdn_passes.gated_rms_norm(
                out, gate, norm_weight, eps=1e-6, dtype=dtype,
                pallas=pallas, interpret=True,
            )
            return jnp.sum(y.astype(jnp.float32) * weight), y

        return jax.jit(jax.value_and_grad(
            total, argnums=(0, 1, 2), has_aux=True
        ))(out, gate, norm_weight)

    ((_, got), got_grads), ((_, want), want_grads) = run(True), run(False)
    assert got.dtype == want.dtype == dtype
    # a bfloat16 result may round the last float32 bit the other way
    _close(got.astype(jnp.float32), want.astype(jnp.float32),
           1e-6 if dtype == jnp.float32 else 1e-3, "out")
    for g, w, name in zip(got_grads, want_grads, ("o", "z", "weight")):
        assert g.shape == w.shape
        _close(g, w, 2e-6, name)



@pytest.mark.parametrize("backend,devices,mesh,t,dk,taps,engine,why", [
    # the published shapes on one chip, the cell's case
    ("tpu", 1, None, 8192, 128, 4, "pallas", "one device"),
    ("tpu", 4, (1, 1), 8192, 128, 4, "pallas", "one device"),
    ("tpu", 4, (2, 2), 8192, 128, 4, "pallas",
     "under shard_map over {'data': 2, 'model': 2}"),
    ("tpu", 4, None, 8192, 128, 4, "xla", "4 devices and no mesh given"),
    ("tpu", 1, None, 8192, 256, 4, "pallas", "one device"),
    # a head is no whole lane tile; rows that are no whole tiles; taps
    # that reach past the tile before a block
    ("tpu", 1, None, 8192, 16, 4, "xla",
     "head sizes, a length or taps the kernels do not take"),
    ("tpu", 1, None, 150, 128, 4, "xla",
     "head sizes, a length or taps the kernels do not take"),
    ("tpu", 1, None, 8192, 128, 10, "xla",
     "head sizes, a length or taps the kernels do not take"),
    # a block of one head of 1024 is 8 MiB, a dozen of them past VMEM
    ("tpu", 1, None, 8192, 1024, 4, "xla",
     "head sizes, a length or taps the kernels do not take"),
    ("cpu", 1, None, 8192, 128, 4, "xla", "backend cpu"),
])
def test_gdn_passes_engine_choice(backend, devices, mesh, t, dk, taps, engine,
                                  why, monkeypatch):
    """The passes' engine by the rule's own rule (backend, what the
    trace is for) and their `supports`; the layer's trace logs it beside
    the rule's line and holds the kernels, mapped where a mesh of
    several devices is named (traced only: shapes, no device)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    from model_zoo.qwen3_next import qwen3_next_lm as zoo  # the layer

    monkeypatch.setattr(jax, "device_count", lambda: devices)
    mesh = mesh and _cpu_mesh(*mesh)
    module = zoo.GatedDeltaNet(2, 4, dk, dk, taps, 1e-6, jnp.bfloat16, mesh)
    x = jax.ShapeDtypeStruct((2, t, 64), jnp.float32)
    variables = jax.eval_shape(
        zoo.GatedDeltaNet(2, 4, dk, dk, taps, 1e-6, jnp.float32).init,
        jax.random.PRNGKey(0), x,
    )
    lines, handler = _log_lines(gated_delta.logger)
    try:
        jaxpr = str(jax.make_jaxpr(
            jax.grad(lambda v, x: jnp.sum(module.apply(v, x)))
        )(variables, x))
    finally:
        gated_delta.logger.removeHandler(handler)
    assert lines[0] == (
        f"gdn passes engine: {engine} T={t} Hk=2 Hv=4 D={dk} ({why})"
    )
    assert lines[1].startswith("delta rule engine: ")
    kernels = ("conv_silu_fwd", "conv_silu_bwd", "gated_norm_fwd",
               "gated_norm_bwd")
    for name in kernels:
        assert (name in jaxpr) == (engine == "pallas"), name
    if why.startswith("under shard_map"):
        # the three conv passes, the rule, the norm, each forward and
        # backward, each mapped on its own
        assert jaxpr.count("shard_map") >= 10


@pytest.mark.parametrize("b,mesh", [(2, (2, 2)), (1, (2, 1))])
def test_gdn_passes_under_a_mesh_are_the_kernels(b, mesh):
    """Under a mesh of several devices each pass runs inside a shard_map
    over the data axis, the taps and the norm's weight whole on every
    device: outputs and every gradient are the unmapped kernels' own."""
    rng = np.random.default_rng(b)
    rows, gate, weight = (
        jnp.asarray(rng.normal(size=(b, 200, 256)), jnp.float32)
        for _ in range(3)
    )
    taps = jnp.asarray(rng.normal(size=(4, 256)), jnp.float32)
    norm_weight = jnp.asarray(rng.normal(size=(128,)), jnp.float32)

    def run(mesh):
        def total(rows, taps, gate, norm_weight):
            mixed = gdn_passes.conv_silu(
                rows, taps, head=128, pallas=True, interpret=True, mesh=mesh
            )
            out = gdn_passes.gated_rms_norm(
                mixed, gate, norm_weight, pallas=True, interpret=True,
                mesh=mesh,
            )
            return jnp.sum(out * weight), (mixed, out)

        return jax.jit(jax.value_and_grad(
            total, argnums=range(4), has_aux=True
        ))(rows, taps, gate, norm_weight)

    (_, want), want_grads = run(None)
    (_, got), got_grads = run(_cpu_mesh(*mesh))
    for g, w in zip(got + got_grads, want + want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)

