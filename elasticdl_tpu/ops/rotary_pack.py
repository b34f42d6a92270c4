"""From a projection's result to the attention engine's operand in ONE
pass: `rotary_pack` reads the float32 [B, T, H, D] result of `q_proj` or
`k_proj` once, norms each head where the model has a head norm
(``w x rsqrt(mean x^2 + eps)``), rotates the first `rotary_dim` of a
head's dimensions (`ops/gqa.py`'s rotate-half form, plain or YaRN
tables), rounds to `dtype` ONCE, after the rotation, and writes
[B, H, T, D], heads in front of tokens: the layout
`gqa.causal_gqa_attention` reads its blocks from where they lie.  Its
transpose takes d(operand) back to d(result) the same way.

Two engines, chosen from what the trace can see (the log says which and
why, once a trace):

- Pallas kernels where the backend is a TPU, the trace is for one device
  and `supports` holds.  Grid (sequence, block of `ROWS` tokens, head),
  every step independent: a step reads one head's [ROWS, D] columns of
  the result where the projection left them and writes the same tile
  into the head's own plane, so the transposition costs nothing beyond
  the one read and the one write.  The rotation is lane rolls of the tile
  against tables as wide as the head (`_kernel_tables`): 1 and 0 past
  `rotary_dim`, the sign of the rotated half folded into the sines.  A
  block's tables are fetched once for all of its heads (the head is the
  grid's innermost axis and the tables' block does not depend on it).
- `rotary_pack_xla`, the definition (`apply_rotary` after the norm, the
  cast, `heads_first`), everywhere else: the CPU's tests, a trace that
  may be for several devices (XLA partitions it; a Mosaic kernel it
  cannot), heads that are no whole lane tiles.

Neither engine names a scope: the caller's `jax.named_scope` reaches the
`custom_vjp`'s backward kernel too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.ops import gated_delta, gqa
from elasticdl_tpu.ops.flash_attention import _use_interpret

logger = get_logger("ops.rotary_pack")

ROWS = 1024  # tokens a block; its columns are one head
LANE_TILE = 128
VMEM_LIMIT = 64 << 20


def _head_norm(x, weight, eps):
    """`lm_common.RMSNorm` over a head's columns."""
    x = x.astype(jnp.float32)
    return weight * x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    )


def rotary_pack_xla(x, cos, sin, dtype, weight=None, eps=1e-6):
    """`rotary_pack` in XLA ops: the definition."""
    if weight is not None:
        x = _head_norm(x, weight, eps)
    return gqa.heads_first(gqa.apply_rotary(x, cos, sin).astype(dtype))


def supports(t: int, d: int, rotary_dim: int) -> bool:
    """Whether the kernels take these shapes: a head is whole lane tiles,
    the rotated part whole pairs, and the tokens whole blocks."""
    return (
        d % LANE_TILE == 0 and rotary_dim % 2 == 0 and 0 < rotary_dim <= d
        and t % 16 == 0 and t % min(t, ROWS) == 0
    )


def _engine(t, h, d, rotary_dim, normed):
    """-> "pallas" or "xla", by the delta rule's `_engine` for a trace
    that names no mesh (backend, shapes, one device); the worker's log
    says which and why."""
    found, why = gated_delta._engine(
        supports(t, d, rotary_dim), None,
        "a head size or a length the kernels do not take",
    )
    logger.info(
        "rotary_pack engine: %s T=%d H=%d D=%d rotary_dim=%d%s (%s)", found,
        t, h, d, rotary_dim, " head norm" if normed else "", why,
    )
    return found


def rotary_pack(x, cos, sin, dtype, weight=None, eps=1e-6, interpret=None):
    """x [B, T, H, D], a projection's float32 result; cos, sin
    [T, rotary_dim] (`gqa.rotary_tables` / `yarn_rotary_tables`); `weight`
    [D], a head norm's, or None -> [B, H, T, D] in `dtype`.  `interpret`:
    None leaves the engine to `_engine`; True or False takes the kernels,
    interpreted or compiled (the tests')."""
    b, t, h, d = x.shape
    if interpret is None:
        if _engine(t, h, d, cos.shape[-1], weight is not None) == "xla":
            return rotary_pack_xla(x, cos, sin, dtype, weight, eps)
        interpret = _use_interpret()
    return _pack(
        x.astype(jnp.float32).reshape(b, t, h * d),
        _kernel_tables(cos, sin, d),
        None if weight is None else weight.astype(jnp.float32).reshape(1, d),
        (h, cos.shape[-1] // 2, eps, jnp.dtype(dtype), ROWS, interpret),
    )


# ----------------------------------------------------------------------
# The kernels
# ----------------------------------------------------------------------


def _kernel_tables(cos, sin, d):
    """Tables as wide as a head for `_rotate`: (cos, sin) where the
    rotation takes the whole head, one roll by half of it serving both
    halves, else (cos, sin_a, sin_b), a roll for each half; the cosine is
    1 and the sines 0 past `rotary_dim`, and the first half's sine
    carries rotate-half's minus sign."""
    rotary_dim = cos.shape[-1]
    pad = ((0, 0), (0, d - rotary_dim))
    first = jnp.arange(d) < rotary_dim // 2
    cos = jnp.pad(cos.astype(jnp.float32), pad, constant_values=1.0)
    sin = jnp.pad(sin.astype(jnp.float32), pad)
    if rotary_dim == d:
        return cos, jnp.where(first, -sin, sin)
    return cos, jnp.where(first, -sin, 0.0), jnp.where(first, 0.0, sin)


def _rotate(x, tables, half, transposed=False):
    """`apply_rotary` of a [rows, D] tile, float32:
    ``y[c] = x[c] cos[c] + x[c + half] sin_a[c] + x[c - half] sin_b[c]``
    (what the tables zero adds an exact 0), or with `transposed` its
    transpose, the same rolls the other way round."""
    d = x.shape[-1]
    cos = tables[0][...]
    if len(tables) == 2:  # half of the head: the roll is its own inverse
        sin = tables[1][...]
        if transposed:
            return x * cos + pltpu.roll(x * sin, d // 2, 1)
        return x * cos + pltpu.roll(x, d // 2, 1) * sin
    sin_a, sin_b = tables[1][...], tables[2][...]
    if transposed:
        return (x * cos + pltpu.roll(x * sin_a, half, 1)
                + pltpu.roll(x * sin_b, d - half, 1))
    return (x * cos + pltpu.roll(x, d - half, 1) * sin_a
            + pltpu.roll(x, half, 1) * sin_b)


def _pack_kernel(*refs, tables, half, eps, normed):
    x_ref, o_ref = refs[0], refs[-1]
    x = x_ref[0]  # [rows, D]: one head's columns
    if normed:
        x = _head_norm(x, refs[1 + tables][...], eps)
    o_ref[0, 0] = _rotate(x, refs[1:1 + tables], half).astype(o_ref.dtype)


def _unpack_kernel(*refs, tables, half, eps, normed):
    """d(result) of a block from d(operand), and with a head norm the
    block's share of d(weight): the norm once more from the result the
    forward pass read."""
    dy_ref = refs[0]
    d_normed = _rotate(
        dy_ref[0, 0].astype(jnp.float32), refs[1:1 + tables], half,
        transposed=True,
    )
    if not normed:
        refs[-1][0] = d_normed
        return
    weight, x = refs[1 + tables][...], refs[2 + tables][0]
    dx_ref, dw_ref = refs[-2:]
    norm = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    unit = x * norm
    dw_ref[0, 0, 0] = jnp.sum(d_normed * unit, axis=0, keepdims=True)
    d_unit = d_normed * weight
    dx_ref[0] = norm * (
        d_unit - unit * jnp.mean(d_unit * unit, axis=-1, keepdims=True)
    )


def _specs(t, d, block_rows, tables, normed):
    """-> (blocks of a sequence, a head's columns of a block of the
    [B, T, H D] result, the same tile in [B, H, T, D], the tables' and
    the weight's specs)."""
    rows = min(t, block_rows)
    return (
        t // rows,
        pl.BlockSpec((1, rows, d), lambda s, i, j: (s, i, j)),
        pl.BlockSpec((1, 1, rows, d), lambda s, i, j: (s, j, i, 0)),
        [pl.BlockSpec((rows, d), lambda s, i, j: (i, 0))] * tables
        + [pl.BlockSpec((1, d), lambda s, i, j: (0, 0))] * normed,
    )


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT,
    )


# Both kernel calls are jitted, as the delta rule's are: a program's
# layers share one trace and one lowering of each.
@functools.partial(jax.jit, static_argnums=0)
def _pack_call(static, x, tables, weight):
    h, half, eps, dtype, block_rows, interpret = static
    b, t, width = x.shape
    d, normed = width // h, weight is not None
    blocks, result, operand, parameters = _specs(
        t, d, block_rows, len(tables), normed
    )
    return pl.pallas_call(
        functools.partial(
            _pack_kernel, tables=len(tables), half=half, eps=eps,
            normed=normed,
        ),
        grid=(b, blocks, h),
        in_specs=[result] + parameters,
        out_specs=operand,
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), dtype),
        compiler_params=_params(),
        name="rotary_pack_fwd",
        interpret=interpret,
    )(x, *tables, *([weight] if normed else []))


@functools.partial(jax.jit, static_argnums=0)
def _unpack_call(static, x, tables, weight, d_out):
    """-> (d result [B, T, H D], [B, blocks, H, 1, D] partial sums of
    d weight or None)."""
    h, half, eps, _, block_rows, interpret = static
    b, _, t, d = d_out.shape
    normed = weight is not None
    blocks, result, operand, parameters = _specs(
        t, d, block_rows, len(tables), normed
    )
    d_result = jax.ShapeDtypeStruct((b, t, h * d), jnp.float32)
    out = pl.pallas_call(
        functools.partial(
            _unpack_kernel, tables=len(tables), half=half, eps=eps,
            normed=normed,
        ),
        grid=(b, blocks, h),
        in_specs=[operand] + parameters + [result] * normed,
        out_specs=[result, pl.BlockSpec(
            (1, 1, 1, 1, d), lambda s, i, j: (s, i, j, 0, 0)
        )] if normed else result,
        out_shape=[d_result, jax.ShapeDtypeStruct(
            (b, blocks, h, 1, d), jnp.float32
        )] if normed else d_result,
        compiler_params=_params(),
        name="rotary_pack_bwd",
        interpret=interpret,
    )(d_out, *tables, *([weight, x] if normed else []))
    return out if normed else (out, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pack(x, tables, weight, static):
    return _pack_call(static, x, tables, weight)


def _pack_fwd(x, tables, weight, static):
    # the result is read again only where a norm has to be taken again
    kept = None if weight is None else x
    return _pack_call(static, x, tables, weight), (kept, tables, weight)


def _pack_bwd(static, residuals, d_out):
    x, tables, weight = residuals
    d_x, sums = _unpack_call(static, x, tables, weight, d_out)
    return (
        d_x, jax.tree.map(jnp.zeros_like, tables),
        None if weight is None else jnp.sum(sums, axis=(0, 1, 2)),
    )


_pack.defvjp(_pack_fwd, _pack_bwd)
