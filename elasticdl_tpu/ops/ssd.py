"""The Mamba-2 selective state-space recurrence, and its chunked
state-space-dual form.

Per head ``h`` of group ``g = h // (H / G)``, with a state ``S`` of shape
[P, N] that starts at zero, a step ``dt_t > 0`` and a scalar ``A_h < 0``::

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t

(B and C are a GROUP's, shared by its heads; the caller adds the skip
``D_h x_t``.)  `ssd_recurrent` is that recurrence token by token: the
definition, which the tests hold the chunked form to.  `ssd_chunked` is
the state-space-dual form of the architecture's source (Dao and Gu,
arXiv:2405.21060, section 6; `mamba_chunk_scan_combined`) at the chunk
the configuration publishes: with ``a_t = dt_t A_h``, ``cum`` its running
sum inside a chunk of ``Q`` tokens and ``L[i, j] = exp(cum_i - cum_j)``
for ``j <= i`` (0 above the diagonal)::

    y      = (L o C B^T) (dt x)                       inside the chunk
           + exp(cum) o (C S_in)                      from the chunks before
    S_out  = exp(cum_Q) S_in + ((dt x) o exp(cum_Q - cum))^T B

Precision, of both engines: ``dt``, ``cum``, the decays ``exp(.)``, ``L``
and the state are float32 always.  The four products (C B^T, the chunk's
scores with dt x, the chunk states, C S_in) take operands in `dtype` and
accumulate in float32, as the source's kernels do (they hand `tl.dot` the
input's dtype, the state cast to it, and keep the state in float32); with
`dtype` float32 they ask for `Precision.HIGHEST`, so that no backend's
default rounds a float32 operand.  The exp is taken only of what the mask
keeps, so a strong decay stays finite.

Two engines compute the chunked form, chosen from what the code can see
(`gated_delta._engine`, the delta rule's own; the log says which and why,
once a trace: `ssd engine: pallas|xla ... (why)`):

- the Pallas kernel pair `ssd_fwd` / `ssd_bwd` under one `custom_vjp`,
  where the backend is a TPU, the products are bfloat16 (Mosaic has no
  `HIGH`, and a float32 model keeps XLA's `HIGHEST`), `supports` holds and
  the trace is for one device or names its mesh (then each kernel call
  runs inside a `shard_map` over the data axis, `gated_delta._over_batch`).
  `supports`: heads of 64, two a 128-lane tile of the rows; a group's heads
  in whole blocks of `HEADS` = 8; N and the chunk whole lane tiles; any T
  (padded to whole grid steps, a padded token neither decays nor writes);
  the blocks within `VMEM_LIMIT` by `_vmem_bytes`.  Nemotron-H's 64 heads
  in 8 groups at chunk 128 and Granite 4.0-H's 64 heads in ONE group at
  chunk 256 are the same kernels at other parameters.
  LAYOUT CONTRACT: the kernels read x as the row-major [B, T, H P] float32
  rows a layer's convolution wrote, B and C as two column ranges of the
  ONE [B, T, 2 G N] array the second convolution wrote (two BlockSpecs of
  the same operand), and write y as [B, T, H P] rows; the backward takes
  d y so and writes d x and d[B | C] in their operands' layouts: nothing
  is laid out again between a caller that holds rows
  (`ssd_chunked_rows`) and the kernels.  dt [B, T, H], 2 MB, is the one
  operand laid out for them (a chunk an [H, Q] matrix, a head a row), and
  its gradient comes back so.
  The grid is (sequence, step of `ROWS` = 512 tokens, block of 8 heads),
  the steps in order and the blocks of a step one after the other, with
  the state of EVERY block in a VMEM scratch ([H / 8, N, 8 P] float32, 2
  MB: the state transposed, so that C S_in and the chunk states are ONE
  product a block, not one a head) from the sequence's first step to its
  last.  B and C of a group are fetched once a step for all its blocks,
  and d[B | C] of a step is ONE resident block that the blocks of a group
  add into.  Inside a step a `fori_loop` walks its chunks; a chunk of a
  block is: the gates as rows [8, Q] (`cum` by a product with a triangle
  of ones, float32 at HIGHEST, the `cumsum` XLA computed in 1.9 ms a
  call), transposed ONCE into columns; C B^T; per PAIR of heads, whose x is
  one lane tile [Q, 128], [M1 | M2] (dt x as a block diagonal) for both
  heads' scores-times-values in one product; then C S_in, the state's
  update.  Only those last two depend on the carry.  NOTHING of size
  Q x Q reaches HBM: `cum`, L, C B^T, the masked scores, exp(cum_Q - cum)
  and exp(cum) are made in VMEM a chunk a head and die there.  What
  crosses HBM is x, B, C, dt in and y out, the chunk-start states
  [B, Z, H / 8, N, 8 P] float32 the forward writes as the backward's
  residual (134 MB a layer at chunk 128, 67 MB at 256; the primal call,
  which a rematerialised layer's first pass is, does not write them), and
  the gradients.
  The backward kernel walks steps and chunks last first with dS of every
  block in VMEM, and rounds where a TPU's default rounds the XLA form's
  backward products: d y, dS and the decays' gradient to bfloat16,
  float32 accumulation.  The running sum's gradient is taken where it
  does not cancel: d cum_i = sum_j D_ij - D_ji over the SAME [Q, Q] entries
  D = dL o L under the diagonal (a transpose and a column sum a head; the
  diagonal, where cum_i - cum_i is 0 whatever cum is, would otherwise
  drown what a strong decay leaves beside it in two sums' roundings),
  plus three sums a token a head (<d y, what the chunks before gave>,
  <d(dt x), x>, <d weighted, weighted>) that ride the MXU as products
  with a 0/1 matrix (`_head_sums`), transposed once into rows; its reverse
  running sum is the other triangle's product.  d a is summed outside
  from the kernel's d cum o dt.
- the XLA form (`ssd_chunked_xla`), everywhere else: the definition the
  CPU's tests hold to `ssd_recurrent` at any head size, the engine of a
  float32 model, of shapes the kernels do not take and of a trace over
  several devices that names no mesh.  Its products inside a chunk are
  batched over ALL chunks of the sequence at once (at T = 8192 the decays
  ``L`` of 64 heads are 268 MB in float32 in chunks of 128 and 537 MB in
  chunks of 256, the chunk states 134 and 67 MB), and only
  ``S_in -> S_out`` walks the chunks in order, a `lax.scan` of T / Q
  elementwise steps on [B, H, P, N].  The backward pass is the one JAX
  derives; the model rematerialises the layer around it.

Neither engine names a scope: the caller's `jax.named_scope` (the model's
`ssm_scan`) reaches both passes of both engines, the `custom_vjp`'s
backward kernel too (`.../ssm/ssm_scan/jit(_backward_call)/ssd_bwd` in the
compiled program's metadata).

Shapes: x [B, T, H, P]; dt [B, T, H]; a [H]; b, c [B, T, G, N] with G a
divisor of H -> (y [B, T, H, P] float32, final S [B, H, P, N]); or,
through `ssd_chunked_rows`, x [B, T, H P] and [B | C] [B, T, 2 G N] as
rows -> (y [B, T, H P], final S).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.ops.flash_attention import _use_interpret
from elasticdl_tpu.ops.gated_delta import (
    _NN, _NT, _TN, _engine, _over_batch, _several,
)

logger = get_logger("ops.ssd")

CHUNK = 128  # Nemotron-H's `chunk_size`; Granite 4.0-H hands `chunk=256`


def ssd_recurrent(x, dt, a, b, c):
    """The recurrence itself, one token a step, float32."""
    x, dt, a, b, c = (v.astype(jnp.float32) for v in (x, dt, a, b, c))
    bsz, _, h, p = x.shape
    repeat = h // b.shape[2]
    b, c = (jnp.repeat(v, repeat, axis=2) for v in (b, c))

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs                     # [B,H,P] [B,H] [B,H,N]
        state = state * jnp.exp(dt_t * a)[..., None, None] + (
            (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        )
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    state0 = jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32)
    state, y = jax.lax.scan(
        step, state0, tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c))
    )
    return jnp.moveaxis(y, 0, 1), state


def ssd_chunked(x, dt, a, b, c, *, chunk: int = CHUNK, dtype=jnp.float32,
                mesh=None):
    """The same outputs as `ssd_recurrent`, in chunks of `chunk` tokens;
    `dtype`: the operands of the four products (float32 accumulation);
    `mesh`: the mesh the caller's program is compiled for, where it knows
    one.  The engine as `ssd_chunked_rows` finds it."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    y, final = ssd_chunked_rows(
        x.reshape(bsz, t, h * p), dt, a,
        jnp.concatenate(
            [b.reshape(bsz, t, g * n), c.reshape(bsz, t, g * n)], axis=-1
        ),
        groups=g, chunk=chunk, dtype=dtype, mesh=mesh,
    )
    return y.reshape(bsz, t, h, p), final


def ssd_chunked_rows(x, dt, a, bc, *, groups: int, chunk: int = CHUNK,
                     dtype=jnp.float32, mesh=None):
    """`ssd_chunked` of row-major rows, the layout the kernels read and
    write: x [B, T, H P], bc [B, T, 2 G N] with B in the first G N columns
    and C in the rest (dt [B, T, H] says how many heads) -> (y [B, T, H P]
    float32, final S [B, H, P, N]).  A layer that holds its tensors as
    rows goes through no [B, T, H, P] on the way in or out; the XLA
    engine's views are taken at its own front door."""
    bsz, t, h = dt.shape
    p, n = x.shape[-1] // h, bc.shape[-1] // (2 * groups)
    bfloat16 = jnp.dtype(dtype) == jnp.bfloat16
    engine, why = _engine(
        bfloat16 and supports(t, h, p, groups, n, chunk), mesh,
        "shapes the kernels do not take" if bfloat16
        else f"{jnp.dtype(dtype).name} products at HIGHEST",
    )
    logger.info(
        "ssd engine: %s ssd_chunked T=%d H=%d P=%d N=%d chunks of %d, "
        "products in %s (%s)",
        engine, t, h, p, n, chunk, jnp.dtype(dtype).name, why,
    )
    if engine == "pallas":
        return ssd_chunked_pallas(
            x, dt, a, bc, groups=groups, chunk=chunk, mesh=mesh
        )
    b, c = jnp.split(bc.reshape(bsz, t, 2 * groups, n), 2, axis=2)
    y, final = ssd_chunked_xla(
        x.reshape(bsz, t, h, p), dt, a, b, c, chunk=chunk, dtype=dtype
    )
    return y.reshape(bsz, t, h * p), final


def ssd_chunked_xla(x, dt, a, b, c, *, chunk: int = CHUNK,
                    dtype=jnp.float32):
    """`ssd_chunked` in XLA ops: the definition the tests hold to
    `ssd_recurrent`, and the engine of every trace the kernels do not
    take."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    precision = (
        jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 else None
    )
    product = dict(preferred_element_type=jnp.float32, precision=precision)
    z = -(-t // chunk)
    pad = z * chunk - t
    if pad:  # a padded token neither decays (dt = 0) nor writes
        x, dt, b, c = (
            jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            for v in (x, dt, b, c)
        )
    dt = dt.astype(jnp.float32).reshape(bsz, z, chunk, g, r)
    x = x.astype(jnp.float32).reshape(bsz, z, chunk, g, r, p)
    b = b.reshape(bsz, z, chunk, g, n).astype(dtype)
    c = c.reshape(bsz, z, chunk, g, n).astype(dtype)
    cum = jnp.cumsum(dt * a.astype(jnp.float32).reshape(g, r), axis=2)
    cum = jnp.moveaxis(cum, 2, -1)                   # [B,Z,G,R,Q]
    rows = jnp.arange(chunk)
    # exp only of what the mask keeps: cum_i - cum_j > 0 above the diagonal.
    decay = jnp.exp(jnp.where(
        rows[:, None] >= rows[None, :],
        cum[..., :, None] - cum[..., None, :], -jnp.inf,
    ))                                               # L [B,Z,G,R,Q,Q]
    x_dt = x * dt[..., None]                         # [B,Z,Q,G,R,P]
    scores = jnp.einsum("bzign,bzjgn->bzgij", c, b, **product)
    y = jnp.einsum(
        "bzgrij,bzjgrp->bzigrp",
        (scores[:, :, :, None] * decay).astype(dtype), x_dt.astype(dtype),
        **product,
    )
    to_end = jnp.exp(cum[..., -1:] - cum)            # [B,Z,G,R,Q]
    chunk_state = jnp.einsum(
        "bzjgrp,bzjgn->bzgrpn",
        (x_dt * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype), b,
        **product,
    )                                                # [B,Z,G,R,P,N]
    from_start = jnp.exp(cum)                        # [B,Z,G,R,Q]
    chunk_decay = from_start[..., -1]                # [B,Z,G,R]

    def carry(state, xs):
        decay_z, state_z = xs
        return state * decay_z[..., None, None] + state_z, state

    final, state_in = jax.lax.scan(
        carry, jnp.zeros((bsz, g, r, p, n), jnp.float32),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(chunk_state, 1, 0)),
    )
    state_in = jnp.moveaxis(state_in, 0, 1)          # [B,Z,G,R,P,N]
    y = y + jnp.einsum(
        "bzign,bzgrpn->bzigrp", c, state_in.astype(dtype), **product
    ) * jnp.moveaxis(from_start, -1, 2)[..., None]
    return (
        y.reshape(bsz, z * chunk, h, p)[:, :t],
        final.reshape(bsz, h, p, n),
    )


# ----------------------------------------------------------------------
# The Pallas engine
# ----------------------------------------------------------------------

HEADS = 8     # heads a grid step: one float32 sublane tile of dt's rows
HEAD = 64     # the head size the kernels take: two heads a lane tile
LANES = 128
ROWS = 512    # tokens a grid step, in whole chunks
VMEM_LIMIT = 64 << 20
HIGHEST = jax.lax.Precision.HIGHEST

def _dot(a, b, dims=_NN, precision=None):
    """a b, a b^T or a^T b (`_NN`, `_NT`, `_TN`), float32 accumulation."""
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=precision,
        preferred_element_type=jnp.float32,
    )


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _chunks_a_step(t: int, chunk: int) -> int:
    """Chunks a grid step: `ROWS` tokens' worth where the sequence's
    chunks divide so, fewer where they do not."""
    chunks = -(-t // chunk)
    return next(
        k for k in (4, 2, 1) if k * chunk <= max(ROWS, chunk)
        and chunks % k == 0
    )


def _vmem_bytes(h: int, g: int, n: int, chunk: int) -> int:
    """What the backward kernel, the larger of the two, holds in VMEM:
    its blocks (x, d y, d x; B, C and d[B | C]; dt and its two
    gradients; a step's chunk states), each twice for the pipeline, the
    states' gradient of every head, d final twice, and a chunk's live
    values at eight [Q, HEADS x HEAD] and eight [Q, Q] arrays."""
    rows = max(ROWS, chunk)
    width = HEADS * HEAD
    blocks = (
        3 * rows * width + rows * (2 * n + 2 * g * n) + 3 * rows * h
        + rows // chunk * n * width
    )
    states = 3 * h * HEAD * n
    live = 8 * chunk * (width + chunk)
    return 4 * (2 * blocks + states + live)


def supports(t: int, h: int, p: int, g: int, n: int, chunk: int) -> bool:
    """Whether the kernels take these shapes: heads of `HEAD`, two a lane
    tile of the [B, T, H P] rows; a group's heads in whole blocks of
    `HEADS`; B and C of a group whole lane tiles of the [B, T, 2 G N]
    rows; chunks of whole lane tiles (a chunk's decays are [Q, Q]); any T
    (padded to whole grid steps); and the blocks within `VMEM_LIMIT`."""
    return (
        p == HEAD and h % g == 0 and (h // g) % HEADS == 0
        and n % LANES == 0 and chunk % LANES == 0
        and _vmem_bytes(h, g, n, chunk) <= VMEM_LIMIT
    )


class _Masks(NamedTuple):
    """What a kernel makes once of iotas: whether [i, j] of a chunk lies
    on or under the diagonal, and whether a lane of a pair's tile is the
    first head's."""

    lower: Any   # [Q, Q]
    first: Any   # [1, 128]

    @classmethod
    def of(cls, q):
        return cls(
            _iota((q, q), 0) >= _iota((q, q), 1),
            _iota((1, LANES), 1) < HEAD,
        )


def _gates(dt_ref, a_ref, c, heads, masks):
    """A chunk's gates of a block's heads, a head a row [HEADS, Q]: dt,
    `cum` (the running sum of dt A inside the chunk: a product with the
    upper triangle of ones, float32 at HIGHEST), exp(cum_Q - cum) and
    exp(cum), all at most 1."""
    dt = dt_ref[0, c, heads, :]
    cum = _dot(
        dt * a_ref[heads, :], masks.lower.astype(jnp.float32), _NT,
        precision=HIGHEST,
    )
    return dt, cum, jnp.exp(cum[:, -1:] - cum), jnp.exp(cum)


def _columns(parts, q):
    """Rows [HEADS, Q] -> ONE [Q, 128]: part k of head h is lane
    HEADS k + h (a transpose of all of them together)."""
    rows = HEADS * len(parts)
    return jnp.concatenate(
        list(parts) + [jnp.zeros((LANES - rows, q), jnp.float32)], axis=0
    ).T


def _pair_lanes(columns, k, pair, masks):
    """Part `k` of a pair's two heads along its lane tile [Q, 128]."""
    lane = HEADS * k + 2 * pair
    return jnp.where(
        masks.first, columns[:, lane:lane + 1], columns[:, lane + 1:lane + 2]
    )


def _block_diagonal(u, masks):
    """A pair's [Q, 128] -> [2Q, 128] in bfloat16: the first head's lanes
    over the second's, zero elsewhere, so that [M1 | M2] times it is both
    heads' product in one."""
    return jnp.concatenate(
        [jnp.where(masks.first, u, 0.0), jnp.where(masks.first, 0.0, u)],
        axis=0,
    ).astype(jnp.bfloat16)


def _chunk_operands(x_ref, b_ref, c_ref, dt_ref, a_ref, c, j, masks):
    """What both passes make of a chunk first: its rows, the gates as
    rows and as columns, B and C in bfloat16, C B^T, and per pair of
    heads (x, dt, exp(cum_Q - cum), exp(cum)) along the lanes."""
    q = masks.lower.shape[0]
    rows = pl.ds(pl.multiple_of(c * q, q), q)
    heads = pl.ds(pl.multiple_of(j * HEADS, HEADS), HEADS)
    gates = _gates(dt_ref, a_ref, c, heads, masks)
    columns = _columns(gates, q)
    b = b_ref[0, rows, :].astype(jnp.bfloat16)
    c_ = c_ref[0, rows, :].astype(jnp.bfloat16)
    pairs = [
        (x_ref[0, rows, pair * LANES:(pair + 1) * LANES],)
        + tuple(_pair_lanes(columns, k, pair, masks) for k in (0, 2, 3))
        for pair in range(HEADS // 2)
    ]
    return rows, heads, gates, columns, b, c_, _dot(c_, b, _NT), pairs


def _masked_scores(scores, columns, cum, pair, masks):
    """-> ([M1 | M2] in bfloat16 [Q, 2Q], (L1, L2)): L of a head is
    exp(cum_i - cum_j) on and under the diagonal, the exp only of what
    the mask keeps."""
    decays = [
        jnp.exp(jnp.where(
            masks.lower,
            columns[:, HEADS + head:HEADS + head + 1] - cum[head:head + 1],
            -jnp.inf,
        ))
        for head in (2 * pair, 2 * pair + 1)
    ]
    return jnp.concatenate(
        [(scores * decay).astype(jnp.bfloat16) for decay in decays], axis=1
    ), decays


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, final_ref, *rest,
                q, chunks, save):
    """One block of heads over one step's chunks, the state of every
    block in VMEM from the sequence's first step to its last."""
    saved_ref, state = rest if save else (None,) + rest
    i, j = pl.program_id(1), pl.program_id(2)
    masks = _Masks.of(q)

    @pl.when(i == 0)
    def _():
        state[j] = jnp.zeros_like(state[j])

    def chunk(c, carry):
        rows, _, (_, cum, _, _), columns, b, c_, scores, pairs = (
            _chunk_operands(x_ref, b_ref, c_ref, dt_ref, a_ref, c, j, masks)
        )
        state_in = state[j]
        if save:
            saved_ref[0, c, 0] = state_in
        inside, to_end, from_start = [], [], []
        for pair, (x, dt, end, start) in enumerate(pairs):
            x_dt = x * dt
            masked, _ = _masked_scores(scores, columns, cum, pair, masks)
            inside.append(_dot(masked, _block_diagonal(x_dt, masks)))
            to_end.append((x_dt * end).astype(jnp.bfloat16))
            from_start.append(start)
        from_start = jnp.concatenate(from_start, axis=1)
        y_ref[0, rows, :] = jnp.concatenate(inside, axis=1) + (
            _dot(c_, state_in.astype(jnp.bfloat16)) * from_start
        )
        state[j] = state_in * from_start[-1:] + _dot(
            b, jnp.concatenate(to_end, axis=1), _TN
        )
        return carry

    jax.lax.fori_loop(0, chunks, chunk, None)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        final_ref[0, j] = state[j]


def _head_sums(parts, select):
    """Each [Q, HEADS x HEAD] part summed over each head's lanes -> ONE
    [Q, 128], part k of head h in lane HEADS k + h: products of the
    parts' bfloat16 halves (hi + lo, float32 to 16 bits) with the 0/1
    matrix `select`, so that the MXU adds what the XLU would take a
    butterfly a head for."""
    both = jnp.concatenate(parts, axis=1)
    hi = both.astype(jnp.bfloat16)
    lo = (both - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return _dot(hi, select) + _dot(lo, select)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, select_ref, saved_ref,
                dy_ref, dfinal_ref,
                dx_ref, dbc_ref, ddt_ref, dadt_ref, dstate,
                *, q, chunks, n, groups, blocks_a_group):
    """The same block and step, the steps and their chunks last first,
    with dS of every block in VMEM.  The running sums' gradients come
    from four sums a token a head (`_head_sums`): the decays' rows add up
    to <d y, y>, their columns to dt <d(dt x), x>."""
    i, j = pl.program_id(1), pl.program_id(2)
    width = HEADS * HEAD

    @pl.when(i == 0)
    def _():
        dstate[j] = dfinal_ref[0, j]

    group = j // blocks_a_group
    b_columns = pl.ds(pl.multiple_of(group * n, LANES), n)
    c_columns = pl.ds(pl.multiple_of((groups + group) * n, LANES), n)
    masks = _Masks.of(q)
    strictly_lower = _iota((q, q), 0) > _iota((q, q), 1)
    last = _iota((1, q), 1) == q - 1

    def chunk(step, carry):
        c = chunks - 1 - step
        rows, heads, (dt_row, cum, _, _), columns, b, c_, \
            scores, pairs = _chunk_operands(
                x_ref, b_ref, c_ref, dt_ref, a_ref, c, j, masks
            )
        state_in = saved_ref[0, c, 0]
        d_state = dstate[j]
        d_y = dy_ref[0, rows, :]
        x = jnp.concatenate([pair[0] for pair in pairs], axis=1)
        dt, to_end, from_start = (
            jnp.concatenate([pair[k] for pair in pairs], axis=1)
            for k in (1, 2, 3)
        )
        x_dt = x * dt
        state_b, d_state_b = (
            v.astype(jnp.bfloat16) for v in (state_in, d_state)
        )
        d_inter = (d_y * from_start).astype(jnp.bfloat16)
        weighted = (x_dt * to_end).astype(jnp.bfloat16)
        d_c = _dot(d_inter, state_b, _NT)
        d_b = _dot(weighted, d_state_b, _NT)
        d_weighted = _dot(b, d_state_b)
        dstate[j] = d_state * from_start[-1:] + _dot(c_, d_inter, _TN)
        d_y_b = d_y.astype(jnp.bfloat16)
        d_scores = jnp.zeros((q, q), jnp.float32)
        d_cum = jnp.zeros((HEADS, q), jnp.float32)
        d_inside = []
        for pair in range(HEADS // 2):
            lanes = slice(pair * LANES, (pair + 1) * LANES)
            masked, decays = _masked_scores(
                scores, columns, cum, pair, masks
            )
            d_masked = _dot(
                d_y_b[:, lanes], _block_diagonal(x_dt[:, lanes], masks), _NT
            )
            for i, decay in enumerate(decays):
                d_score = d_masked[:, i * q:(i + 1) * q] * decay
                d_scores = d_scores + d_score
                # d L o L = D under the diagonal (on it cum_i - cum_i is
                # 0 whatever cum is): d cum_i = sum_j D_ij - D_ji, the
                # same entries added and taken away, so that what a strong
                # decay leaves of them is not lost in two sums' roundings
                d_decay = jnp.where(strictly_lower, d_score * scores, 0.0)
                d_cum = d_cum + jnp.where(
                    _iota((HEADS, 1), 0) == 2 * pair + i,
                    jnp.sum(d_decay.T - d_decay, axis=0, keepdims=True), 0.0,
                )
            both = _dot(masked, d_y_b[:, lanes], _TN)        # [2Q, 128]
            d_inside.append(jnp.where(masks.first, both[:q], both[q:]))
        d_inside = jnp.concatenate(d_inside, axis=1)
        d_scores = d_scores.astype(jnp.bfloat16)
        d_c = d_c + _dot(d_scores, b)
        d_b = d_b + _dot(d_scores, c_, _TN)
        d_x_dt = d_inside + d_weighted * to_end
        dx_ref[0, rows, :] = d_x_dt * dt

        if blocks_a_group == 1:
            dbc_ref[0, rows, b_columns] = d_b
            dbc_ref[0, rows, c_columns] = d_c
        else:
            first_block = j % blocks_a_group == 0

            @pl.when(first_block)
            def _():
                dbc_ref[0, rows, b_columns] = d_b
                dbc_ref[0, rows, c_columns] = d_c

            @pl.when(jnp.logical_not(first_block))
            def _():
                dbc_ref[0, rows, b_columns] += d_b
                dbc_ref[0, rows, c_columns] += d_c

        # Three sums a token a head.  exp(cum) scales what the chunks
        # before give: <d y, that>; the chunk's decay exp(cum_Q) reads cum
        # at the last token, and its gradient exp(cum_Q) <dS, S_in> joins
        # there.  <d(dt x), x> is dt's own gradient.  exp(cum_Q - cum)
        # weighs what the chunk gives its state: <d weighted, weighted>,
        # taken from each token and given to the last.
        state_row = jnp.sum(d_state * state_in, axis=0, keepdims=True)
        sums = _head_sums([
            d_y * from_start * _dot(c_, state_b) + jnp.where(
                _iota((q, 1), 0) == q - 1, state_row * from_start[-1:], 0.0,
            ),
            d_x_dt * x, d_weighted * x_dt * to_end,
        ], select_ref[...]).T                                # [128, Q]
        d_start, d_dt, d_end = (
            sums[k * HEADS:(k + 1) * HEADS] for k in range(3)
        )
        d_cum = d_cum + d_start - d_end + jnp.where(
            last, jnp.sum(d_end, axis=1, keepdims=True), 0.0
        )
        d_a = _dot(
            d_cum, masks.lower.astype(jnp.float32), precision=HIGHEST
        )
        ddt_ref[0, c, heads, :] = d_dt + d_a * a_ref[heads, :]
        dadt_ref[0, c, heads, :] = d_a * dt_row
        return carry

    jax.lax.fori_loop(0, chunks, chunk, None)


def _specs(h, g, n, q, chunks, step_of):
    """Block specs of (x or y, B, C, dt's rows, a, the step's chunk
    states, every block's state, [B | C] whole) for the grid (sequence,
    step, block of heads): the `step_of(i)`-th step of `chunks` chunks."""
    rows, width = chunks * q, HEADS * HEAD
    blocks_a_group = h // g // HEADS
    return (
        pl.BlockSpec((1, rows, width), lambda s, i, j: (s, step_of(i), j)),
        pl.BlockSpec(
            (1, rows, n), lambda s, i, j: (s, step_of(i), j // blocks_a_group)
        ),
        pl.BlockSpec(
            (1, rows, n),
            lambda s, i, j: (s, step_of(i), g + j // blocks_a_group),
        ),
        pl.BlockSpec(
            (1, chunks, h, q), lambda s, i, j: (s, step_of(i), 0, 0)
        ),
        pl.BlockSpec((h, 1), lambda s, i, j: (0, 0)),
        pl.BlockSpec(
            (1, chunks, 1, n, width),
            lambda s, i, j: (s, step_of(i), j, 0, 0),
        ),
        pl.BlockSpec(
            (1, h // HEADS, n, width), lambda s, i, j: (s, 0, 0, 0)
        ),
        pl.BlockSpec(
            (1, rows, 2 * g * n), lambda s, i, j: (s, step_of(i), 0)
        ),
    )


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT,
    )


# Both kernel calls are jitted, as the delta rule's: a program's layers
# share ONE trace of each kernel.
@functools.partial(jax.jit, static_argnums=(0, 1))
def _forward_call(static, save, x, bc, dt, a):
    """-> (y, the final states[, with `save` the states at each chunk's
    start: the backward's residual])."""
    g, q, interpret, mesh = static
    _, z, h, _ = dt.shape
    n = bc.shape[-1] // (2 * g)
    chunks = _chunks_a_step(z * q, q)
    width, blocks = HEADS * HEAD, h // HEADS
    x_spec, b_spec, c_spec, dt_spec, a_spec, saved_spec, states_spec, _ = (
        _specs(h, g, n, q, chunks, lambda i: i)
    )

    def call_for(b):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, q=q, chunks=chunks, save=save),
            grid=(b, z // chunks, blocks),
            in_specs=[x_spec, b_spec, c_spec, dt_spec, a_spec],
            out_specs=[x_spec, states_spec] + [saved_spec] * save,
            out_shape=[
                jax.ShapeDtypeStruct((b, z * q, h * HEAD), jnp.float32),
                jax.ShapeDtypeStruct((b, blocks, n, width), jnp.float32),
            ] + [
                jax.ShapeDtypeStruct((b, z, blocks, n, width), jnp.float32)
            ] * save,
            scratch_shapes=[pltpu.VMEM((blocks, n, width), jnp.float32)],
            compiler_params=_compiler_params(),
            name="ssd_fwd",
            interpret=interpret,
        )

    return _over_batch(mesh, x.shape[0], call_for, whole=(4,))(
        x, bc, bc, dt, a
    )


@functools.partial(jax.jit, static_argnums=0)
def _backward_call(static, x, bc, dt, a, saved, d_y, d_final):
    """-> (d x, d[B | C], d dt in dt's rows, d cum's share of d a there)."""
    g, q, interpret, mesh = static
    _, z, h, _ = dt.shape
    n = bc.shape[-1] // (2 * g)
    chunks = _chunks_a_step(z * q, q)
    steps, width, blocks = z // chunks, HEADS * HEAD, h // HEADS
    x_spec, b_spec, c_spec, dt_spec, a_spec, saved_spec, states_spec, \
        bc_spec = _specs(h, g, n, q, chunks, lambda i: steps - 1 - i)
    # part k of head h, lane p -> lane HEADS k + h
    select = (
        _iota((3 * width, LANES), 0) // HEAD == _iota((3 * width, LANES), 1)
    ).astype(jnp.bfloat16)

    def call_for(b):
        return pl.pallas_call(
            functools.partial(
                _bwd_kernel, q=q, chunks=chunks, n=n, groups=g,
                blocks_a_group=h // g // HEADS,
            ),
            grid=(b, steps, blocks),
            in_specs=[
                x_spec, b_spec, c_spec, dt_spec, a_spec,
                pl.BlockSpec(select.shape, lambda s, i, j: (0, 0)),
                saved_spec, x_spec, states_spec,
            ],
            out_specs=[x_spec, bc_spec, dt_spec, dt_spec],
            out_shape=[
                jax.ShapeDtypeStruct((b,) + v.shape[1:], jnp.float32)
                for v in (x, bc, dt, dt)
            ],
            scratch_shapes=[pltpu.VMEM((blocks, n, width), jnp.float32)],
            compiler_params=_compiler_params(),
            name="ssd_bwd",
            interpret=interpret,
        )

    return _over_batch(mesh, x.shape[0], call_for, whole=(4, 5))(
        x, bc, bc, dt, a, select, saved, d_y, d_final
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ssd_walk(x, bc, dt, a, static):
    return tuple(_forward_call(static, False, x, bc, dt, a))


def _ssd_walk_fwd(x, bc, dt, a, static):
    y, final, saved = _forward_call(static, True, x, bc, dt, a)
    return (y, final), (x, bc, dt, a, saved)


def _ssd_walk_bwd(static, residuals, cts):
    d_x, d_bc, d_dt, d_a_dt = _backward_call(static, *residuals, *cts)
    return d_x, d_bc, d_dt, jnp.sum(d_a_dt, axis=(0, 1, 3))[:, None]


_ssd_walk.defvjp(_ssd_walk_fwd, _ssd_walk_bwd)


def ssd_chunked_pallas(x, dt, a, bc, *, groups: int, chunk: int = CHUNK,
                       interpret=None, mesh=None):
    """`ssd_chunked_rows` in the Pallas kernels (interpret mode off the
    TPU; under a multi-device `mesh`, a shard's sequences a device), the
    products in bfloat16.  x and [B | C] go in as they lie; dt, small, is
    laid out here a chunk a [H, Q] matrix, a head a row, and the states
    come back a block of heads a [N, HEADS x HEAD] matrix."""
    bsz, t, h = dt.shape
    n = bc.shape[-1] // (2 * groups)
    x, dt, a, bc = (v.astype(jnp.float32) for v in (x, dt, a, bc))
    z = -(-t // chunk)
    pad = z * chunk - t
    if pad:  # a padded token neither decays (dt = 0) nor writes
        x, dt, bc = (
            jnp.pad(v, [(0, 0), (0, pad), (0, 0)]) for v in (x, dt, bc)
        )
    y, final = _ssd_walk(
        x, bc, jnp.swapaxes(dt.reshape(bsz, z, chunk, h), 2, 3),
        a.reshape(h, 1),
        (groups, chunk,
         _use_interpret() if interpret is None else interpret,
         _several(mesh)),
    )
    final = final.reshape(bsz, h // HEADS, n, HEADS, HEAD)
    return (
        y if not pad else y[:, :t],
        jnp.transpose(final, (0, 1, 3, 4, 2)).reshape(bsz, h, HEAD, n),
    )
