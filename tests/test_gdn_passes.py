"""`ops/gdn_passes.py`: what surrounds the delta rule in a Gated DeltaNet
layer and the scan in a Mamba-2 layer, one pass each way over rows (the
causal convolution with silu and, for the rule, the l2-norm by head; the
rule's RMSNorm by head, gated; the scan's skip, gate and RMSNorm by
group), the Pallas kernels (interpret mode here) against the plain
`jax.numpy` chain, and the engine's choice.  The layers that call them are
`model_zoo/qwen3_next`'s `GatedDeltaNet` and `model_zoo/lm_common.py`'s
`Mamba2Mixer`, which the engine-choice cases trace.
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



# A state-space layer's two passes, reduced: x (Nemotron-H's and Granite's
# 4096 columns) and [B | C] (2 x 8 x 128 and 2 x 128), neither a whole
# count of heads, each with its share of the taps and the bias.
@pytest.mark.parametrize("t,width", [
    (200, 512), (1100, 512), (200, 256), (1100, 384),
])
def test_conv_silu_with_a_bias_at_a_state_space_layers_widths(
    t, width, blocks_of_256_rows
):
    """The pass with no norm by head and with the bias: outputs and every
    gradient against the `jax.numpy` chain."""
    rng = np.random.default_rng(t + width)
    rows, weight = (
        jnp.asarray(rng.normal(size=(2, t, width)), jnp.float32)
        for _ in range(2)
    )
    taps = jnp.asarray(rng.normal(size=(4, width)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(width,)), jnp.float32)

    def run(pallas):
        def total(rows, taps, bias):
            out = gdn_passes.conv_silu(
                rows, taps, bias, pallas=pallas, interpret=True
            )
            return jnp.sum(out * weight), out

        return jax.jit(jax.value_and_grad(
            total, argnums=(0, 1, 2), has_aux=True
        ))(rows, taps, bias)

    ((_, got), got_grads), ((_, want), want_grads) = run(True), run(False)
    _close(got, want, 1e-6, "out")
    for g, w, name in zip(got_grads, want_grads, ("rows", "taps", "bias")):
        assert g.shape == w.shape
        _close(g, w, 2e-6, name)


# At blocks of 256 rows of a lane tile a group of 128 columns has blocks
# of 256 rows and one of 1024 has blocks of 32 (the cells: 512 rows of
# 512 columns, 64 of 4096): T = 1100 ends in a block that is not whole.
@pytest.mark.parametrize("t", [64, 200, 1100])
@pytest.mark.parametrize("groups", [1, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gated_group_norm_kernels_match_the_plain_chain(t, groups, dtype,
                                                        blocks_of_256_rows):
    """Outputs (in the out-projection's operand type) and the gradients
    of y, x, z, the skip and the norm's weight."""
    rng = np.random.default_rng(t + groups)
    inner, heads = 1024, 16
    y, x, z, weight = (
        jnp.asarray(rng.normal(size=(2, t, inner)), jnp.float32)
        for _ in range(4)
    )
    skip = jnp.asarray(rng.normal(size=(heads,)), jnp.float32)
    norm_weight = jnp.asarray(rng.normal(size=(inner,)), jnp.float32)

    def run(pallas):
        def total(y, x, z, skip, norm_weight):
            out = gdn_passes.gated_group_norm(
                y, x, z, skip, norm_weight, groups=groups, eps=1e-5,
                dtype=dtype, pallas=pallas, interpret=True,
            )
            return jnp.sum(out.astype(jnp.float32) * weight), out

        return jax.jit(jax.value_and_grad(
            total, argnums=range(5), has_aux=True
        ))(y, x, z, skip, norm_weight)

    ((_, got), got_grads), ((_, want), want_grads) = run(True), run(False)
    assert got.dtype == want.dtype == dtype
    # a bfloat16 result may round the last float32 bit the other way
    _close(got.astype(jnp.float32), want.astype(jnp.float32),
           1e-6 if dtype == jnp.float32 else 1e-3, "out")
    for g, w, name in zip(got_grads, want_grads,
                          ("y", "x", "z", "skip", "weight")):
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


_NO_WIDTHS = "widths, a length or taps the kernels do not take"


@pytest.mark.parametrize(
    "backend,devices,mesh,t,groups,state,taps,engine,why", [
        # the published shapes on one chip, the cells' case: Nemotron-H's
        # 8 groups of 512 over rows of 6144, Granite's one of 4096 over 4352
        ("tpu", 1, None, 8192, 8, 128, 4, "pallas", "one device"),
        ("tpu", 1, None, 8192, 1, 128, 4, "pallas", "one device"),
        ("tpu", 4, (2, 2), 8192, 8, 128, 4, "pallas",
         "under shard_map over {'data': 2, 'model': 2}"),
        ("tpu", 4, None, 8192, 1, 128, 4, "xla",
         "4 devices and no mesh given"),
        # groups, and so rows, that are no whole lane tiles; rows that are
        # no whole float32 tiles; taps that reach past the tile before a
        # block
        ("tpu", 1, None, 8192, 64, 128, 4, "xla", _NO_WIDTHS),
        ("tpu", 1, None, 8192, 1, 96, 4, "xla", _NO_WIDTHS),
        ("tpu", 1, None, 150, 8, 128, 4, "xla", _NO_WIDTHS),
        ("tpu", 1, None, 8192, 8, 128, 10, "xla", _NO_WIDTHS),
        ("cpu", 1, None, 8192, 8, 128, 4, "xla", "backend cpu"),
    ],
)
def test_gdn_passes_engine_choice_of_a_state_space_layer(
    backend, devices, mesh, t, groups, state, taps, engine, why, monkeypatch
):
    """`Mamba2Mixer` at 64 heads of 64, in the layer's own terms (the
    rows' width, the groups and their width): the trace logs the choice
    once, before the scan's line, and holds the four kernels, mapped
    where a mesh of several devices is named (traced only)."""
    from elasticdl_tpu.ops import ssd
    from model_zoo.lm_common import Mamba2Mixer

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    mesh = mesh and _cpu_mesh(*mesh)
    module = Mamba2Mixer(
        64, 64, groups, state, taps, 128, 1e-5, jnp.bfloat16, mesh=mesh
    )
    x = jax.ShapeDtypeStruct((2, t, 64), jnp.float32)
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)
    lines, handler = _log_lines(gated_delta.logger)
    ssd.logger.addHandler(handler)
    try:
        jaxpr = str(jax.make_jaxpr(
            jax.grad(lambda v, x: jnp.sum(module.apply(v, x)))
        )(variables, x))
    finally:
        gated_delta.logger.removeHandler(handler)
        ssd.logger.removeHandler(handler)
    width = 4096 + 2 * groups * state
    assert lines[0] == (
        f"gdn passes engine: {engine} T={t} W={width} "
        f"G={groups}x{4096 // groups} ({why})"
    )
    assert lines[1].startswith("ssd engine: ") and len(lines) == 2
    for name in ("conv_silu_fwd", "conv_silu_bwd", "gated_group_norm_fwd",
                 "gated_group_norm_bwd"):
        assert (name in jaxpr) == (engine == "pallas"), name
    if why.startswith("under shard_map"):
        assert jaxpr.count("shard_map") >= 4  # each pass mapped on its own


_NO_SHAPES = "shapes the kernels do not take"


@pytest.mark.parametrize(
    "backend,devices,mesh,groups,chunk,head,dtype,engine,why", [
        # the published shapes on one chip, the cells' case: Nemotron-H's
        # 8 groups of 8 heads in chunks of 128, Granite's one group of 64
        # heads in chunks of 256
        ("tpu", 1, None, 8, 128, 64, "bfloat16", "pallas", "one device"),
        ("tpu", 1, None, 1, 256, 64, "bfloat16", "pallas", "one device"),
        ("tpu", 4, (1, 1), 8, 128, 64, "bfloat16", "pallas", "one device"),
        ("tpu", 4, (2, 2), 1, 256, 64, "bfloat16", "pallas",
         "under shard_map over {'data': 2, 'model': 2}"),
        ("tpu", 4, None, 8, 128, 64, "bfloat16", "xla",
         "4 devices and no mesh given"),
        # a group of four heads is no whole block of heads; a head of 32
        # is no half of a lane tile; a chunk of 64 no whole one
        ("tpu", 1, None, 16, 128, 64, "bfloat16", "xla", _NO_SHAPES),
        ("tpu", 1, None, 8, 128, 32, "bfloat16", "xla", _NO_SHAPES),
        ("tpu", 1, None, 8, 64, 64, "bfloat16", "xla", _NO_SHAPES),
        # a float32 model's products ask for HIGHEST, which is XLA's
        ("tpu", 1, None, 8, 128, 64, "float32", "xla",
         "float32 products at HIGHEST"),
        ("cpu", 1, None, 8, 128, 64, "bfloat16", "xla", "backend cpu"),
    ],
)
def test_ssd_engine_choice(backend, devices, mesh, groups, chunk, head, dtype,
                           engine, why, monkeypatch):
    """The state-space scan's kernel pair where the backend is a TPU,
    `ssd.supports` holds, the products are bfloat16 and the trace is for
    one device or names its mesh; the XLA form everywhere else.  The
    worker's log line says which and why, and the trace holds `ssd_fwd`
    and `ssd_bwd` exactly then (traced only: shapes, no device)."""
    from elasticdl_tpu.ops import ssd

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    mesh = mesh and _cpu_mesh(*mesh)
    t, heads, n = 8192, 64, 128
    shapes = [
        jax.ShapeDtypeStruct(shape, jnp.float32) for shape in (
            (2, t, heads * head), (2, t, heads), (heads,),
            (2, t, 2 * groups * n),
        )
    ]
    lines, handler = _log_lines(ssd.logger)
    try:
        jaxpr = str(jax.make_jaxpr(jax.grad(  # a new function: no cached trace
            lambda *a: jnp.sum(ssd.ssd_chunked_rows(
                *a, groups=groups, chunk=chunk, dtype=jnp.dtype(dtype),
                mesh=mesh,
            )[0]),
            argnums=range(4),
        ))(*shapes))
    finally:
        ssd.logger.removeHandler(handler)
    assert lines == [
        f"ssd engine: {engine} ssd_chunked T={t} H={heads} P={head} N={n} "
        f"chunks of {chunk}, products in {dtype} ({why})"
    ]
    for name in ("ssd_fwd", "ssd_bwd"):
        assert (name in jaxpr) == (engine == "pallas"), name
    assert ("shard_map" in jaxpr) == why.startswith("under shard_map")


@pytest.mark.parametrize("b,mesh", [(2, (2, 2)), (1, (2, 1))])
def test_state_space_passes_under_a_mesh_are_the_kernels(b, mesh):
    """As the next case, for the pair a Mamba-2 layer calls: the taps,
    the bias, the skip and the norm's weight whole on every device."""
    rng = np.random.default_rng(b)
    rows, y, z, weight = (
        jnp.asarray(rng.normal(size=(b, 200, 256)), jnp.float32)
        for _ in range(4)
    )
    taps = jnp.asarray(rng.normal(size=(4, 256)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    skip = jnp.asarray(rng.normal(size=(4,)), jnp.float32)
    norm_weight = jnp.asarray(rng.normal(size=(256,)), jnp.float32)

    def run(mesh):
        def total(rows, taps, bias, y, z, skip, norm_weight):
            mixed = gdn_passes.conv_silu(
                rows, taps, bias, pallas=True, interpret=True, mesh=mesh
            )
            out = gdn_passes.gated_group_norm(
                y, mixed, z, skip, norm_weight, groups=2, pallas=True,
                interpret=True, mesh=mesh,
            )
            return jnp.sum(out * weight), (mixed, out)

        return jax.jit(jax.value_and_grad(
            total, argnums=range(7), has_aux=True
        ))(rows, taps, bias, y, z, skip, norm_weight)

    (_, want), want_grads = run(None)
    (_, got), got_grads = run(_cpu_mesh(*mesh))
    for g, w in zip(got + got_grads, want + want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)


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

