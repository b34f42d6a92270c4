"""The float32 passes around a recurrence, each as ONE pass over
[B, T, W] rows, forward and backward.  Two families of layer call them:

- a Gated DeltaNet layer (`model_zoo/qwen3_next` `GatedDeltaNet`), around
  the gated delta rule, on head-major [B, T, H D] rows, the layout the
  rule's kernels read and write (`ops/gated_delta.py`): `conv_silu` once
  for each of q (with the l2-norm by head, ``scale = 1 / sqrt(Dk)``), k
  and v, and `gated_rms_norm`;
- a Mamba-2 layer (`model_zoo/lm_common.py` `Mamba2Mixer`: Nemotron-H,
  Granite 4.0-H), around the state-space scan (`ops/ssd.py`): `conv_silu`
  with the bias and no norm, for x and for [B | C], and
  `gated_group_norm`.  A kernel takes an ARRAY: a slice of a wider one
  handed to it is a copy in HBM first, and the slice's gradient a pad, so
  that layer makes z, x, [B | C] and dt one product each.

The passes:

- `conv_silu`: rows in float32 -> causal depthwise convolution of
  `taps.shape[0]` taps accumulated in float32 (+ `bias`) -> silu -> where
  `head` is given, the l2-norm of each head of `head` columns,
  ``x rsqrt(sum x^2 + eps) scale`` -> rows in float32.  It knows nothing
  of either layer (rows, taps, bias).
- `gated_rms_norm` (DeltaNet): o rows and gate rows z in float32 -> per
  HEAD ``weight (o rsqrt(mean o^2 + eps)) silu(z)`` -> rows in `dtype`;
  the weight is one head's, [D].  The gate's form follows its shape and
  `activation`: a column's silu (Qwen3-Next; the kernels') or ONE value a
  head through a sigmoid (`model_zoo/ling` `KimiDeltaAttention`; the XLA
  chain).
- `decay_gate` (a delta rule whose decay is one rate a key channel): the
  projection's rows -> ``bound sigmoid(exp(A_log) (rows + dt_bias))``, a
  log-decay in (bound, 0); float32, the XLA chain alone.
- `gated_group_norm` (Mamba-2): y, x, z rows in float32, a skip a head
  and a weight a column -> ``weight GroupRMS((y + skip x) silu(z))`` over
  G GROUPS of W / G columns -> rows in `dtype`.  Not the norm above with
  other arguments: that one norms and then gates, this one gates and then
  norms, so the gate is inside the mean and its derivative goes through
  the norm's; a group is 512 or 4096 columns where a head is 128, so a
  block's rows follow from the group's width (`_group_rows`); and the
  weight is the whole width's.  Two equations, two pairs of kernels.

Two engines, chosen by the caller from what `engine` (`engine_groups` in
a state-space layer's terms) can see; the log says which and why, once a
trace, beside the recurrence's line:

- Pallas kernels on a TPU where `supports` (`supports_groups`) holds and
  the trace is for one device or names its mesh (then a shard's sequences
  a device under the rule's `_over_batch`).  Grid (sequence, block of
  rows, block of columns), every step independent of every other; a
  block is `ROWS` rows of one head (or one lane tile), or the same bytes
  of one group.  The convolution:
  the `taps - 1` rows before a block come from a second, 8-row block of
  the same array (the rows a float32 tile holds), and the backward pass,
  one kernel too, reads 8 rows after the block as well, recomputes the
  convolution and silu there from the input it already has, and writes
  d(rows) and one partial sum a block of d(taps) (and d(bias)); XLA adds
  the partial sums up.  The norms' backward kernels recompute the forward
  from their inputs likewise and write a block's partial sums of
  d(weight) (and d(skip), by column).  Everything is float32, in and out
  and between; the one cast is a norm's result to `dtype`, which is what
  the out-projection's product takes.
- the plain `jax.numpy` chain (`conv_silu_xla`, `gated_rms_norm_xla`,
  `gated_group_norm_xla`) everywhere else: the definition the tests hold
  the kernels to, and the engine of the CPU's tests and of widths that
  are no whole lane tiles.

Neither engine names a scope: the caller's `jax.named_scope` (the
models' `gdn_mix`, `ssm`) reaches the `custom_vjp`s' backward kernels too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops import gated_delta
from elasticdl_tpu.ops.flash_attention import _use_interpret

ROWS = 2048  # rows a block; its columns are one head (or one lane tile)
LANE_TILE = 128
HALO = 8     # rows of the block before (and after): one float32 tile's
# What a pass may hold in VMEM: a block is 1 MiB at heads of 128, and a
# dozen of them are live in the backward kernel, the pipeline's second
# buffers among them (`supports`).
VMEM_LIMIT = 64 << 20


# ----------------------------------------------------------------------
# The definition: plain jax.numpy
# ----------------------------------------------------------------------


def conv_silu_xla(rows, taps, bias=None, *, head=0, scale=1.0, eps=1e-6):
    """`conv_silu` in XLA ops: the taps over shifted slices of the padded
    rows, accumulated in float32."""
    b, t, width = rows.shape
    k = taps.shape[0]
    padded = jnp.pad(rows, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    mixed = sum(padded[:, j:j + t] * taps[j] for j in range(k))
    if bias is not None:
        mixed = mixed + bias
    mixed = jax.nn.silu(mixed)
    if not head:
        return mixed
    heads = mixed.reshape(b, t, width // head, head)
    heads = heads * jax.lax.rsqrt(
        jnp.sum(heads * heads, axis=-1, keepdims=True) + eps
    )
    return (heads * scale).reshape(b, t, width)


#: What a norm's gate passes through before it multiplies.
GATE_ACTIVATIONS = {"silu": jax.nn.silu, "sigmoid": jax.nn.sigmoid}


def gated_rms_norm_xla(rows, gate, weight, *, eps=1e-6, dtype=jnp.float32,
                       activation="silu"):
    """`gated_rms_norm` in XLA ops; the gate one value a column
    [B, T, H D] or one a head [B, T, H]."""
    b, t, width = rows.shape
    head = weight.shape[0]
    heads = rows.astype(jnp.float32).reshape(b, t, width // head, head)
    heads = heads * jax.lax.rsqrt(
        jnp.mean(heads * heads, axis=-1, keepdims=True) + eps
    )
    gate = gate.astype(jnp.float32).reshape(b, t, width // head, -1)
    return (
        weight * heads * GATE_ACTIVATIONS[activation](gate)
    ).reshape(b, t, width).astype(dtype)


def gated_group_norm_xla(y, x, z, skip, weight, *, groups, eps=1e-6,
                         dtype=jnp.float32):
    """`gated_group_norm` in XLA ops: the skip a head, the gate, the
    RMSNorm over each of the `groups` groups of the width, float32."""
    b, t, inner = y.shape
    heads = skip.shape[0]
    y = (
        y.reshape(b, t, heads, -1) + skip[:, None] * x.reshape(b, t, heads, -1)
    ).reshape(b, t, inner) * jax.nn.silu(z)
    y = y.reshape(b, t, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return (weight * y.reshape(b, t, inner)).astype(dtype)


# ----------------------------------------------------------------------
# The choice of engine
# ----------------------------------------------------------------------


def supports(t: int, dk: int, dv: int, taps: int) -> bool:
    """Whether the kernels take these shapes: a head has to be whole
    lane tiles of the rows, a block's rows whole float32 tiles, the rows
    before a block that a tap reaches have to lie in one tile, and the
    backward kernel's dozen live blocks of `ROWS` rows of one head have
    to fit in `VMEM_LIMIT` (heads of 512 compile for a described v5e,
    heads of 1024 do not)."""
    return (
        dk % LANE_TILE == 0 and dv % LANE_TILE == 0 and t % HALO == 0
        and 1 <= taps <= HALO + 1
        and 12 * ROWS * max(dk, dv) * 4 <= VMEM_LIMIT
    )


def supports_groups(t: int, width: int, group: int, taps: int) -> bool:
    """`supports` in a state-space layer's terms: rows `width` wide
    through `conv_silu` with no norm by head (blocks of one lane tile),
    and `gated_group_norm` over groups of `group` columns, a block's
    columns one group and its rows what `_group_rows` gives, whole
    float32 tiles."""
    return (
        width % LANE_TILE == 0 and group % LANE_TILE == 0 and t % HALO == 0
        and 1 <= taps <= HALO + 1 and _group_rows(group, ROWS) >= HALO
    )


def _chosen(supported, mesh, unsupported, shapes):
    """-> "pallas" or "xla" by the rule's own `_engine` (backend, what
    the trace is for) and what a `supports` found; the worker's log says
    which and why, once a trace."""
    found, why = gated_delta._engine(supported, mesh, unsupported)
    gated_delta.logger.info(
        "gdn passes engine: %s %s (%s)", found, shapes, why
    )
    return found


def engine(t, hk, hv, dk, dv, taps, mesh=None):
    """-> "pallas" or "xla" for both passes of a Gated DeltaNet layer
    with these shapes."""
    return _chosen(
        supports(t, dk, dv, taps), mesh,
        "head sizes, a length or taps the kernels do not take",
        f"T={t} Hk={hk} Hv={hv} D={dk if dk == dv else f'{dk}/{dv}'}",
    )


def engine_groups(t, width, groups, group, taps, mesh=None):
    """-> "pallas" or "xla" for both passes of a state-space layer: the
    convolution over rows `width` wide, the norm over `groups` groups of
    `group` columns."""
    return _chosen(
        supports_groups(t, width, group, taps), mesh,
        "widths, a length or taps the kernels do not take",
        f"T={t} W={width} G={groups}x{group}",
    )


def _blocks(t: int, width: int, head: int, rows: int):
    """-> (rows a block, columns a block, grid of one sequence).  A
    block's columns are ONE head, so that a kernel's body is written
    once and not once a head (what a body traces it traces at every
    start of a worker), or one lane tile where no norm goes by head."""
    rows = min(t, rows)
    columns = head or LANE_TILE
    return rows, columns, (pl.cdiv(t, rows), width // columns)


def _group_rows(group: int, rows: int) -> int:
    """Rows of a block whose columns are one group of `group`: as many
    as give it the bytes of `rows` rows of one lane tile, in whole
    float32 tiles (512 at groups of 512, 64 at one group of 4096)."""
    return rows * LANE_TILE // group // HALO * HALO


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"),
        vmem_limit_bytes=VMEM_LIMIT,
    )


# ----------------------------------------------------------------------
# conv + silu (+ l2-norm by head)
# ----------------------------------------------------------------------


def _window(refs, first_row, t):
    """`refs`' rows (halos and the block, in order) one under the other,
    zero where the sequence has no such row: before its start, and past
    its end in a last block that is not whole."""
    window = jnp.concatenate([ref[0] for ref in refs], axis=0)
    rows = first_row + jax.lax.broadcasted_iota(
        jnp.int32, (window.shape[0], 1), 0
    )
    return jnp.where((rows >= 0) & (rows < t), window, 0.0)


def _conv(window, taps_ref, bias_ref, n):
    """`n` rows of the causal convolution, the first of them the
    window's row `HALO` (the window starts `HALO` rows before)."""
    k = taps_ref.shape[0]
    first = HALO - (k - 1)
    mixed = sum(
        window[first + j:first + j + n] * taps_ref[j:j + 1]
        for j in range(k)
    )
    return mixed if bias_ref is None else mixed + bias_ref[...]


def _conv_fwd_kernel(*refs, t, head, scale, eps, has_bias):
    x_ref, before_ref, taps_ref = refs[:3]
    bias_ref = refs[3] if has_bias else None
    o_ref = refs[-1]
    rows = x_ref.shape[1]
    window = _window(
        (before_ref, x_ref), pl.program_id(1) * rows - HALO, t
    )
    mixed = _conv(window, taps_ref, bias_ref, rows)
    mixed = mixed * jax.nn.sigmoid(mixed)
    if head:  # the block is one head
        mixed = mixed * jax.lax.rsqrt(
            jnp.sum(mixed * mixed, axis=-1, keepdims=True) + eps
        ) * scale
    o_ref[0] = mixed


def _conv_bwd_kernel(*refs, t, head, scale, eps, has_bias):
    """d(rows) of a block and the block's share of d(taps) (and, in the
    row after them, of d(bias)): the convolution and silu once more over
    the block and the `HALO` rows after it, whose d(conv) a tap carries
    back into the block."""
    x_ref, before_ref, after_ref, taps_ref = refs[:4]
    bias_ref = refs[4] if has_bias else None
    do_ref, do_after_ref, dx_ref, dtaps_ref = refs[-4:]
    rows, k = x_ref.shape[1], taps_ref.shape[0]
    block_row = pl.program_id(1) * rows
    window = _window((before_ref, x_ref, after_ref), block_row - HALO, t)
    conv = _conv(window, taps_ref, bias_ref, rows + HALO)
    gate = jax.nn.sigmoid(conv)
    d_mixed = _window((do_ref, do_after_ref), block_row, t)
    if head:
        mixed = conv * gate
        norm = jax.lax.rsqrt(
            jnp.sum(mixed * mixed, axis=-1, keepdims=True) + eps
        )
        d_mixed = (scale * norm) * (d_mixed - mixed * (norm * norm) * (
            jnp.sum(d_mixed * mixed, axis=-1, keepdims=True)
        ))
    d_conv = d_mixed * (gate * (1.0 + conv * (1.0 - gate)))
    dx_ref[0] = sum(
        d_conv[k - 1 - j:k - 1 - j + rows] * taps_ref[j:j + 1]
        for j in range(k)
    )
    first = HALO - (k - 1)
    for j in range(k):
        dtaps_ref[0, 0, j:j + 1] = jnp.sum(
            d_conv[:rows] * window[first + j:first + j + rows],
            axis=0, keepdims=True,
        )
    if has_bias:
        dtaps_ref[0, 0, k:k + 1] = jnp.sum(
            d_conv[:rows], axis=0, keepdims=True
        )


def _row_specs(t, rows, columns):
    """Block specs of (a block, the `HALO` rows before it, the `HALO`
    rows after it) of [B, T, W] rows, each clamped into the array: what
    lies outside the sequence is zeroed in the kernel."""
    tiles, last = rows // HALO, pl.cdiv(t, HALO) - 1
    return (
        pl.BlockSpec((1, rows, columns), lambda s, i, j: (s, i, j)),
        pl.BlockSpec(
            (1, HALO, columns),
            lambda s, i, j: (s, jnp.maximum(i * tiles - 1, 0), j),
        ),
        pl.BlockSpec(
            (1, HALO, columns),
            lambda s, i, j: (s, jnp.minimum((i + 1) * tiles, last), j),
        ),
    )


def _column_spec(rows, columns):
    """A [rows, W] parameter's columns of a block."""
    return pl.BlockSpec((rows, columns), lambda s, i, j: (0, j))


def _conv_plan(static, rows, taps, bias):
    """What both conv calls are built from -> (the kernel's keywords,
    grid of one sequence, the rows' three specs, the parameters'
    specs)."""
    head, scale, eps, block_rows, _, _ = static
    _, t, width = rows.shape
    block_rows, columns, grid = _blocks(t, width, head, block_rows)
    parameters = [_column_spec(taps.shape[0], columns)] + (
        [] if bias is None else [_column_spec(1, columns)]
    )
    keywords = dict(
        t=t, head=head, scale=scale, eps=eps, has_bias=bias is not None
    )
    return keywords, grid, _row_specs(t, block_rows, columns), parameters


# Every kernel call is jitted, as the rule's are: a program's layers share
# one trace and one lowering of each (a layer's q, k and v each have their own).
@functools.partial(jax.jit, static_argnums=0)
def _conv_forward_call(static, rows, taps, bias):
    keywords, grid, (block, before, _), parameters = _conv_plan(
        static, rows, taps, bias
    )
    *_, interpret, mesh = static

    def call_for(b):
        return pl.pallas_call(
            functools.partial(_conv_fwd_kernel, **keywords),
            grid=(b,) + grid,
            in_specs=[block, before] + parameters,
            out_specs=block,
            out_shape=jax.ShapeDtypeStruct((b,) + rows.shape[1:], jnp.float32),
            compiler_params=_params(),
            name="conv_silu_fwd",
            interpret=interpret,
        )

    return gated_delta._over_batch(
        mesh, rows.shape[0], call_for, whole=range(2, 2 + len(parameters))
    )(rows, rows, taps, *([] if bias is None else [bias]))


@functools.partial(jax.jit, static_argnums=0)
def _conv_backward_call(static, rows, taps, bias, d_out):
    """-> (d rows, [B, blocks, taps (+ 1), W] partial sums of d taps
    (and d bias))."""
    keywords, grid, (block, before, after), parameters = _conv_plan(
        static, rows, taps, bias
    )
    *_, interpret, mesh = static
    _, t, width = rows.shape
    sums = taps.shape[0] + (bias is not None)

    def call_for(b):
        return pl.pallas_call(
            functools.partial(_conv_bwd_kernel, **keywords),
            grid=(b,) + grid,
            in_specs=[block, before, after] + parameters + [block, after],
            out_specs=[
                block,
                pl.BlockSpec(
                    (1, 1, sums, block.block_shape[2]),
                    lambda s, i, j: (s, i, 0, j),
                ),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, t, width), jnp.float32),
                jax.ShapeDtypeStruct((b, grid[0], sums, width), jnp.float32),
            ],
            compiler_params=_params(),
            name="conv_silu_bwd",
            interpret=interpret,
        )

    return gated_delta._over_batch(
        mesh, rows.shape[0], call_for,
        whole=range(3, 3 + len(parameters)),
    )(rows, rows, rows, taps, *([] if bias is None else [bias]), d_out, d_out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_silu(rows, taps, bias, static):
    return _conv_forward_call(static, rows, taps, bias)


def _conv_silu_fwd(rows, taps, bias, static):
    return _conv_forward_call(static, rows, taps, bias), (rows, taps, bias)


def _conv_silu_bwd(static, residuals, d_out):
    rows, taps, bias = residuals
    d_rows, sums = _conv_backward_call(static, rows, taps, bias, d_out)
    sums = jnp.sum(sums, axis=(0, 1))
    k = taps.shape[0]
    return d_rows, sums[:k], None if bias is None else sums[k:]


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def conv_silu(rows, taps, bias=None, *, head=0, scale=1.0, eps=1e-6,
              pallas=False, interpret=None, mesh=None):
    """rows [B, T, W] -> silu(causal depthwise conv(rows, taps [K, W])
    (+ bias [W])), and with `head` the l2-norm of each `head` columns
    times `scale`; float32.  `pallas`: the kernels (`engine` says where
    they run; interpret mode off the TPU), else the XLA chain."""
    if not pallas:
        return conv_silu_xla(
            rows, taps, bias, head=head, scale=scale, eps=eps
        )
    return _conv_silu(
        rows.astype(jnp.float32), taps.astype(jnp.float32),
        None if bias is None else bias.astype(jnp.float32).reshape(1, -1),
        (head, float(scale), eps, ROWS,  # noqa-invariant: jit-host-sync (`scale` is a Python number, a static of the kernel, never a traced array)
         _use_interpret() if interpret is None else interpret,
         gated_delta._several(mesh)),
    )


# ----------------------------------------------------------------------
# RMS norm by head, gated
# ----------------------------------------------------------------------


def _norm_fwd_kernel(o_ref, z_ref, w_ref, y_ref, *, eps):
    out, gate = o_ref[0], z_ref[0]  # one head's columns
    out = out * jax.lax.rsqrt(
        jnp.mean(out * out, axis=-1, keepdims=True) + eps
    )
    y_ref[0] = (w_ref[...] * out * (gate * jax.nn.sigmoid(gate))).astype(
        y_ref.dtype
    )


def _norm_bwd_kernel(o_ref, z_ref, w_ref, dy_ref, do_ref, dz_ref, dw_ref, *,
                     t, eps):
    weight = w_ref[...]
    rows = o_ref.shape[1]
    # past the sequence's end a block holds anything: no share of it may
    # reach the sum over the block's rows
    inside = pl.program_id(1) * rows + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0
    ) < t
    out = jnp.where(inside, o_ref[0], 0.0)
    gate = jnp.where(inside, z_ref[0], 0.0)
    d_y = jnp.where(inside, dy_ref[0].astype(jnp.float32), 0.0)
    norm = jax.lax.rsqrt(jnp.mean(out * out, axis=-1, keepdims=True) + eps)
    normed = out * norm
    sig = jax.nn.sigmoid(gate)
    silu = gate * sig
    dw_ref[0, 0, 0] = jnp.sum(d_y * normed * silu, axis=0, keepdims=True)
    dz_ref[0] = (d_y * weight * normed) * (sig * (1.0 + gate * (1.0 - sig)))
    d_normed = d_y * weight * silu
    do_ref[0] = norm * (d_normed - normed * jnp.mean(
        d_normed * normed, axis=-1, keepdims=True
    ))


def _norm_specs(t, width, head, rows):
    block_rows, columns, grid = _blocks(t, width, head, rows)
    return (
        grid, pl.BlockSpec((1, block_rows, columns), lambda s, i, j: (s, i, j)),
        pl.BlockSpec((1, head), lambda s, i, j: (0, 0)),
    )


@functools.partial(jax.jit, static_argnums=0)
def _norm_forward_call(static, rows, gate, weight):
    eps, dtype, block_rows, interpret, mesh = static
    _, t, width = rows.shape
    head = weight.shape[1]
    grid, block, whole = _norm_specs(t, width, head, block_rows)

    def call_for(b):
        return pl.pallas_call(
            functools.partial(_norm_fwd_kernel, eps=eps),
            grid=(b,) + grid,
            in_specs=[block, block, whole],
            out_specs=block,
            out_shape=jax.ShapeDtypeStruct((b, t, width), dtype),
            compiler_params=_params(),
            name="gated_norm_fwd",
            interpret=interpret,
        )

    return gated_delta._over_batch(mesh, rows.shape[0], call_for, whole=(2,))(
        rows, gate, weight
    )


@functools.partial(jax.jit, static_argnums=0)
def _norm_backward_call(static, rows, gate, weight, d_out):
    """-> (d rows, d gate, [B, blocks, heads, 1, head] partial sums of
    d weight)."""
    eps, _, block_rows, interpret, mesh = static
    _, t, width = rows.shape
    head = weight.shape[1]
    grid, block, whole = _norm_specs(t, width, head, block_rows)

    def call_for(b):
        return pl.pallas_call(
            functools.partial(_norm_bwd_kernel, t=t, eps=eps),
            grid=(b,) + grid,
            in_specs=[block, block, whole, block],
            out_specs=[
                block, block,
                pl.BlockSpec(
                    (1, 1, 1, 1, head), lambda s, i, j: (s, i, j, 0, 0)
                ),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, t, width), jnp.float32),
                jax.ShapeDtypeStruct((b, t, width), jnp.float32),
                jax.ShapeDtypeStruct((b,) + grid + (1, head), jnp.float32),
            ],
            compiler_params=_params(),
            name="gated_norm_bwd",
            interpret=interpret,
        )

    return gated_delta._over_batch(mesh, rows.shape[0], call_for, whole=(2,))(
        rows, gate, weight, d_out
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gated_rms_norm(rows, gate, weight, static):
    return _norm_forward_call(static, rows, gate, weight)


def _gated_rms_norm_fwd(rows, gate, weight, static):
    return _norm_forward_call(static, rows, gate, weight), (rows, gate, weight)


def _gated_rms_norm_bwd(static, residuals, d_out):
    d_rows, d_gate, sums = _norm_backward_call(static, *residuals, d_out)
    return d_rows, d_gate, jnp.sum(sums, axis=(0, 1, 2))


_gated_rms_norm.defvjp(_gated_rms_norm_fwd, _gated_rms_norm_bwd)


def gated_rms_norm(rows, gate, weight, *, eps=1e-6, dtype=jnp.float32,
                   activation="silu", pallas=False, interpret=None,
                   mesh=None):
    """rows [B, T, H D] float32, weight [D] -> per head
    weight (rows rsqrt(mean rows^2 + eps)) act(gate), in `dtype`.  The
    gate's form is its shape's and `activation`'s: one value a column
    [B, T, H D] or one a head [B, T, H]; "silu" or "sigmoid".  The
    kernels compute silu of a column's gate; any other form is the XLA
    chain whatever `pallas` says."""
    if not pallas or activation != "silu" or gate.shape != rows.shape:
        return gated_rms_norm_xla(
            rows, gate, weight, eps=eps, dtype=dtype, activation=activation
        )
    return _gated_rms_norm(
        rows.astype(jnp.float32), gate.astype(jnp.float32),
        weight.astype(jnp.float32).reshape(1, -1),
        (eps, jnp.dtype(dtype), ROWS,
         _use_interpret() if interpret is None else interpret,
         gated_delta._several(mesh)),
    )


def decay_gate(rows, a_log, dt_bias, *, bound):
    """A delta rule's log-decay, one a key channel, BOUNDED below: rows
    [B, T, H Dk] (the decay's projection), a_log [H], dt_bias [H Dk] ->
    ``bound sigmoid(exp(a_log)[h] (rows + dt_bias))`` in (bound, 0) for a
    `bound` < 0, float32 rows.  The bound is what lets the chunked rule
    take a sub-chunk's decays apart (`ops/gated_delta._decayed_inside`).
    One elementwise chain, which XLA fuses into the projection's
    consumer: the plain `jax.numpy` chain is all there is of it."""
    b, t, width = rows.shape
    rate = jnp.exp(a_log.astype(jnp.float32))[:, None]
    pre = (rows.astype(jnp.float32) + dt_bias).reshape(
        b, t, a_log.shape[0], -1
    )
    return (bound * jax.nn.sigmoid(rate * pre)).reshape(b, t, width)


# ----------------------------------------------------------------------
# Skip, gate, RMS norm by group
# ----------------------------------------------------------------------


def _group_fwd_kernel(y_ref, x_ref, z_ref, skip_ref, w_ref, o_ref, *, eps):
    gate = z_ref[0]  # one group's columns
    gated = (y_ref[0] + skip_ref[...] * x_ref[0]) * (
        gate * jax.nn.sigmoid(gate)
    )
    o_ref[0] = (w_ref[...] * (gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + eps
    ))).astype(o_ref.dtype)


def _group_bwd_kernel(y_ref, x_ref, z_ref, skip_ref, w_ref, do_ref, dy_ref,
                      dx_ref, dz_ref, sums_ref, *, t, eps):
    """d y, d x, d z of a block and the block's share of d weight and,
    in the row after it, of d skip by column: the skip, the gate and the
    norm once more from the inputs."""
    skip = skip_ref[...]
    rows = y_ref.shape[1]
    # past the sequence's end a block holds anything: no share of it may
    # reach the sums over the block's rows
    inside = pl.program_id(1) * rows + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0
    ) < t
    x = jnp.where(inside, x_ref[0], 0.0)
    gate = jnp.where(inside, z_ref[0], 0.0)
    summed = jnp.where(inside, y_ref[0], 0.0) + skip * x
    d_out = jnp.where(inside, do_ref[0].astype(jnp.float32), 0.0)
    sig = jax.nn.sigmoid(gate)
    silu = gate * sig
    gated = summed * silu
    norm = jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + eps
    )
    normed = gated * norm
    sums_ref[0, 0, 0:1] = jnp.sum(d_out * normed, axis=0, keepdims=True)
    d_normed = d_out * w_ref[...]
    d_gated = norm * (d_normed - normed * jnp.mean(
        d_normed * normed, axis=-1, keepdims=True
    ))
    d_summed = d_gated * silu
    dy_ref[0] = d_summed
    dx_ref[0] = d_summed * skip
    sums_ref[0, 0, 1:2] = jnp.sum(d_summed * x, axis=0, keepdims=True)
    dz_ref[0] = (d_gated * summed) * (sig * (1.0 + gate * (1.0 - sig)))


def _group_specs(t, width, groups, rows):
    """-> (grid of one sequence, a block of rows, a [1, W] parameter's
    columns of a block)."""
    group = width // groups
    block_rows = min(t, _group_rows(group, rows))
    return (
        (pl.cdiv(t, block_rows), groups),
        pl.BlockSpec((1, block_rows, group), lambda s, i, j: (s, i, j)),
        _column_spec(1, group),
    )


@functools.partial(jax.jit, static_argnums=0)
def _group_forward_call(static, y, x, z, skip, weight):
    groups, eps, dtype, block_rows, interpret, mesh = static
    _, t, width = y.shape
    grid, block, columns = _group_specs(t, width, groups, block_rows)

    def call_for(b):
        return pl.pallas_call(
            functools.partial(_group_fwd_kernel, eps=eps),
            grid=(b,) + grid,
            in_specs=[block, block, block, columns, columns],
            out_specs=block,
            out_shape=jax.ShapeDtypeStruct((b, t, width), dtype),
            compiler_params=_params(),
            name="gated_group_norm_fwd",
            interpret=interpret,
        )

    return gated_delta._over_batch(
        mesh, y.shape[0], call_for, whole=(3, 4)
    )(y, x, z, skip, weight)


@functools.partial(jax.jit, static_argnums=0)
def _group_backward_call(static, y, x, z, skip, weight, d_out):
    """-> (d y, d x, d z, [B, blocks, 2, W] partial sums of d weight and
    of d skip by column)."""
    groups, eps, _, block_rows, interpret, mesh = static
    _, t, width = y.shape
    grid, block, columns = _group_specs(t, width, groups, block_rows)

    def call_for(b):
        rows = jax.ShapeDtypeStruct((b, t, width), jnp.float32)
        return pl.pallas_call(
            functools.partial(_group_bwd_kernel, t=t, eps=eps),
            grid=(b,) + grid,
            in_specs=[block, block, block, columns, columns, block],
            out_specs=[
                block, block, block,
                pl.BlockSpec(
                    (1, 1, 2, block.block_shape[2]),
                    lambda s, i, j: (s, i, 0, j),
                ),
            ],
            out_shape=[
                rows, rows, rows,
                jax.ShapeDtypeStruct((b, grid[0], 2, width), jnp.float32),
            ],
            compiler_params=_params(),
            name="gated_group_norm_bwd",
            interpret=interpret,
        )

    return gated_delta._over_batch(
        mesh, y.shape[0], call_for, whole=(3, 4)
    )(y, x, z, skip, weight, d_out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gated_group_norm(y, x, z, skip, weight, static):
    return _group_forward_call(static, y, x, z, skip, weight)


def _gated_group_norm_fwd(y, x, z, skip, weight, static):
    return (
        _group_forward_call(static, y, x, z, skip, weight),
        (y, x, z, skip, weight),
    )


def _gated_group_norm_bwd(static, residuals, d_out):
    d_y, d_x, d_z, sums = _group_backward_call(static, *residuals, d_out)
    sums = jnp.sum(sums, axis=(0, 1))
    return d_y, d_x, d_z, sums[1:], sums[:1]


_gated_group_norm.defvjp(_gated_group_norm_fwd, _gated_group_norm_bwd)


def gated_group_norm(y, x, z, skip, weight, *, groups, eps=1e-6,
                     dtype=jnp.float32, pallas=False, interpret=None,
                     mesh=None):
    """y, x, z [B, T, W] float32, skip [H] (a head is W / H columns),
    weight [W] -> weight GroupRMS((y + skip x) silu(z)) over `groups`
    groups of W / groups columns, in `dtype`."""
    if not pallas:
        return gated_group_norm_xla(
            y, x, z, skip, weight, groups=groups, eps=eps, dtype=dtype
        )
    width = y.shape[-1]
    return _gated_group_norm(
        y.astype(jnp.float32), x.astype(jnp.float32), z.astype(jnp.float32),
        jnp.repeat(skip.astype(jnp.float32), width // skip.shape[0]).reshape(
            1, -1
        ),
        weight.astype(jnp.float32).reshape(1, -1),
        (groups, eps, jnp.dtype(dtype), ROWS,
         _use_interpret() if interpret is None else interpret,
         gated_delta._several(mesh)),
    )
