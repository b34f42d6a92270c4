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

The products inside a chunk are batched over ALL chunks of the sequence
at once (at T = 8192 the decays ``L`` of 64 heads are 268 MB in float32
in chunks of 128 and 537 MB in chunks of 256, the chunk states 134 and
67 MB), and only ``S_in -> S_out`` walks the chunks in
order, a `lax.scan` of T / Q elementwise steps on [B, H, P, N].  The
backward pass is the one JAX derives (the scan's reverse keeps the state
at each chunk's start, which the forward has anyway); the model
rematerialises the layer around it.

Precision: ``dt``, the decays ``exp(.)``, ``L`` and the state are float32
always.  The four products (C B^T, the chunk's scores with dt x, the
chunk states, C S_in) take operands in `dtype` and accumulate in
float32, as the source's kernels do (they hand `tl.dot` the input's
dtype, the state cast to it, and keep the state in float32); with
`dtype` float32 they ask for `Precision.HIGHEST`, so that no backend's
default rounds a float32 operand.

One engine, XLA ops; the log says so once a trace (`ssd engine: ...`), as
the delta rule's does, so that a job's log tells which engine its trace
held when a kernel joins.  It names no scope: the caller's
`jax.named_scope` (the model's `ssm_scan`) reaches both passes.

Shapes: x [B, T, H, P]; dt [B, T, H]; a [H]; b, c [B, T, G, N] with G a
divisor of H.  -> (y [B, T, H, P] float32, final S [B, H, P, N]).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from elasticdl_tpu.common.log_utils import get_logger

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


def ssd_chunked(x, dt, a, b, c, *, chunk: int = CHUNK, dtype=jnp.float32):
    """The same outputs as `ssd_recurrent`, in chunks of `chunk` tokens;
    `dtype`: the operands of the four products (float32 accumulation)."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    logger.info(
        "ssd engine: xla ssd_chunked T=%d H=%d P=%d N=%d "
        "(chunks of %d, products in %s)",
        t, h, p, n, chunk, jnp.dtype(dtype).name,
    )
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
