"""The gated delta rule over a sequence, computed in chunks.

Per head, with a state ``S`` of shape [Dk, Dv] that starts at zero, a
log-decay ``g_t <= 0`` and a write strength ``beta_t``::

    S   <- S * exp(g_t)
    d_t  = beta_t * (v_t - S^T k_t)
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

`gated_delta_rule_recurrent` is that recurrence token by token (the
definition; tests hold the chunked form to it).  `chunk_gated_delta_rule`
is the WY form the architecture's source computes (its
``chunk_gated_delta_rule``): inside a chunk of ``C`` tokens the rank-one
writes are folded into one triangular system, and only the
chunk-to-chunk state is carried by a ``lax.scan``.

With ``G`` the cumulative sum of ``g`` inside a chunk and
``M[i, j] = beta_i (k_i . k_j) exp(G_i - G_j)`` for ``j < i`` (0
elsewhere), ``Tm = (I + M)^-1`` gives ``u = Tm (beta v)`` and
``w = Tm (beta k exp(G))``; then, chunk by chunk::

    v'  = u - w S
    o   = (q exp(G)) S + tril(q k^T exp(G_i - G_j)) v'
    S  <- S exp(G_C) + (k exp(G_C - G))^T v'

``I + M`` is unit lower triangular, so ``M`` is nilpotent and the inverse
is the finite product ``(I - M)(I + M^2)(I + M^4)...`` up to ``M^(C/2)``:
six [C, C] products for C = 64 instead of C rows solved in sequence.

One scan step takes a GROUP of `GROUP` consecutive chunks: their
triangular systems are solved together (batched over heads and the
group's chunks, which is what keeps the MXU fed), then the state walks
through them one by one.  Nothing the size of the sequence is built
besides the inputs and the output: at T = 8192, 32 heads and 2 sequences
every [B, T, H, D] float32 tensor is 268 MB, and the whole-sequence form
of the source (all chunks' systems first, then the scan) keeps some
twenty of them alive through its backward pass.

Everything here is float32 (the decay, the state and the triangular
system need it), and every product asks for `PRECISION`, `Precision.HIGH`
(three bfloat16 passes, float32 to about 1e-6): a TPU's default would
round their float32 operands to bfloat16, the state among them, at every
chunk.  Measured on a v5e at the published widths (PERF.md, PR 26):
against the float32 reference the model's logits differ by the same
2.0% with HIGH as with HIGHEST (six passes, twice the scan's time), and
by 2.1% at the default, which a benchmark's check therefore has to tell
from HIGH by a comparison at the stated precision.

Two engines compute the chunked form, chosen from what the code can see
(`_engine`; the log says which and why, once a trace):

- the Pallas kernels below, where the backend is a TPU, `supports`
  holds, and the trace is for one device or names its mesh.  `supports`:
  Dk and Dv multiples of 128, so that a head is whole lane tiles of the
  [B, T, H D] rows the model already has; an even number of value heads
  a key head, because the kernels take them two by two; and a block of
  at least one key head's value heads within `VMEM_LIMIT` by
  `_vmem_bytes` (`_heads_a_block` takes as many key heads a grid step
  as fit: 8 at the published heads of 128, 4 at heads of 256); any T
  (padded to whole groups).  A Mosaic kernel cannot be partitioned
  automatically, so under a `mesh` of several devices each kernel call
  runs inside a `shard_map` over the data axis, a shard's sequences a
  device (`_over_batch`), and a trace that may be for several devices
  (`jax.device_count() > 1`) and names no mesh keeps the XLA engine.
  The grid is (sequence, block of heads, chunk), the chunks in order,
  and the block's states stay in a VMEM
  scratch from the first chunk to the last: a chunk's triangular
  systems, its products with the state and the state's update are one
  grid step, and nothing but q, k, v, g, beta, o and the state at each
  group's start (a group here: `EVERY` chunks) ever touches HBM.
  Mosaic takes `DEFAULT` and `HIGHEST` for a product and not `HIGH`, so
  `_mm` writes HIGH out by hand
  (a = hi + lo in bfloat16; hi hi + hi lo + lo hi, float32
  accumulation), forward and backward.  The backward kernel walks the
  groups in reverse with dS in VMEM: per group it walks the states
  forward once more from the saved group start, keeping each chunk's
  inverses, then takes each chunk's vector-Jacobian product last chunk
  first (JAX's own, traced inside the kernel, of the same
  `_chunk_pairs` the forward runs; the inverse's is the closed form
  -T^T dT T^T around the kept T).  What a v5e taught about its form
  (PERF.md, PR 29) is written where it applies: the MXU's time goes by
  weight tiles and dependent products, not by FLOPs.
- the XLA form (`_chunk_group` under a `lax.scan`), everywhere else.  It
  stays because it is the definition the tests hold to the recurrence
  on a CPU at any head size, because interpret mode is far too slow for
  a training job off the TPU (the tiny end-to-end jobs of the tests run
  this engine), and because it is the fallback for head sizes the
  kernels do not take and for a trace over several devices that names
  no mesh.  Its backward pass is the reverse scan JAX
  derives, with the scan step rematerialised so that only the state at
  each group's start is kept (T / (C GROUP) x [Dk, Dv] a head, not
  every intermediate of every chunk).

Neither engine names a scope: the caller's `jax.named_scope` around the
rule (the model's `gdn_scan`) reaches both passes of both engines, the
`custom_vjp`'s backward kernel too (`.../transpose(jvp(gdn_scan))/
jit(_backward_call)/delta_rule_bwd` in the compiled program's metadata).

**A decay a KEY CHANNEL.**  `g` of [B, T, H, Dk] in place of [B, T, H]
(the shape decides; no flag) is one rate a row of the state,
``S <- diag(exp(g_t)) S``: Kimi Delta Attention's rule
(arXiv:2510.26692; `model_zoo/ling`).  The WY form stands, with the decay
INSIDE the contractions, ``M[i, j] = beta_i sum_d k_i[d] k_j[d]
exp(G_i[d] - G_j[d])``, which no [C, C] factor laid over a finished
product gives: `_decayed_inside` factors it around a reference row a
sub-chunk of `SUB` rows and relies, on the diagonal sub-blocks, on the
caller's gate being BOUNDED below (`ops/gdn_passes.decay_gate`).  The
XLA engine alone computes it (`_chunk_group_channels`, `GROUP_CHANNELS`
chunks a scan step, the state's walk one product a chunk and everything
else a product over the group); the kernels walk
a head's scalar, so `_engine` is told that they do not take it.  Its
triangular systems are inverted BY BLOCKS (`_unit_lower_inverse_by_blocks`):
the finite product over a whole chunk loses every digit in float32 where
a chunk's keys are alike, which seeded layers behind a common stream
are (PERF.md section 6, PR 53: gradients of 1e1 at single records, then
NaN at step 19 on the chip).  The scalar path keeps the product it had,
bit for bit (a test holds its programs to recorded texts), and with it
that weakness (PERF.md section 7).

Shapes: q, k [B, T, Hk, Dk]; v [B, T, H, Dv]; g, beta [B, T, H], with
Hk = H or a divisor of it (key head i then serves value heads
i H / Hk .. (i + 1) H / Hk - 1); or, through
`chunk_gated_delta_rule_rows`, the same tensors as head-major rows
[B, T, Hk Dk] and [B, T, H Dv], which is what the kernels read and
write: a layer that holds them so (`ops/gdn_passes.py` computes what
surrounds the rule in that layout) moves no tensor on the way in or out.
q and k arrive normalised and scaled as the caller's layer defines.
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

logger = get_logger("ops.gated_delta")

CHUNK = 64
SUB = 16   # rows of a chunk's sub-chunk under a decay a key channel
BASE = 8   # rows of the blocks a system's inverse is joined from
GROUP = 8  # chunks a step of the XLA engine's scan
GROUP_CHANNELS = 16  # the same under a decay a key channel
EVERY = 8  # chunks between two states the Pallas forward saves
PRECISION = jax.lax.Precision.HIGH


def gated_delta_rule_recurrent(q, k, v, g, beta):
    """The recurrence itself, one token a step.  -> (o [B,T,H,Dv], S).
    `g` [B,T,H], one log-decay a head, or [B,T,H,Dk], one a key channel
    (a row of the state each)."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    b, _, h, dk = k.shape
    # a head's decay over [Dk, Dv], or a key channel's over its row [Dv]
    over = (None,) * (2 if g.ndim == beta.ndim else 1)

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs          # [B,H,D] / [B,H]
        state = state * jnp.exp(g_t)[(...,) + over]
        delta = beta_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
        )
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    state, out = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(out, 0, 1), state


def _unit_lower_inverse(m, nilpotent=None):
    """(I + M)^-1 for strictly lower triangular M [..., C, C]; `nilpotent`:
    a power of two at which M's powers are known to vanish, where that is
    less than C (a block-diagonal M)."""
    c = m.shape[-1]
    eye = jnp.eye(c, dtype=m.dtype)
    power = -m
    inverse = eye + power
    span = 2
    while span < (nilpotent or c):
        power = jnp.matmul(power, power, precision=PRECISION)
        inverse = jnp.matmul(inverse, eye + power, precision=PRECISION)
        span *= 2
    return inverse


def _unit_lower_inverse_by_blocks(m):
    """(I + M)^-1 for strictly lower triangular M [..., C, C], taken BY
    BLOCKS: the finite product of `_unit_lower_inverse` on the diagonal
    blocks of `BASE` rows alone, then pairs of blocks joined,
    [[A, 0], [X, B]]^-1 = [[A^-1, 0], [-B^-1 X A^-1, B^-1]], from `BASE`
    rows to C.  The product over the WHOLE chunk goes through M^32, whose
    entries reach C(62, 31) ~ 5e17 where a chunk's keys are alike
    (M near beta times all ones under the diagonal), and the inverse's
    entries of size 1 are what is left when those cancel: in float32
    nothing is.  By blocks no intermediate exceeds the inverse's own
    entries by more than C(6, 3) = 20.  The same ten [C, C] products: the
    blocks are masks of whole matrices."""
    c = m.shape[-1]
    block = jnp.arange(c)

    def same(size):
        return (block[:, None] // size) == (block[None, :] // size)

    inverse = _unit_lower_inverse(jnp.where(same(BASE), m, 0.0), BASE)
    size = BASE
    while size < c:
        joined = jnp.where(same(2 * size) & ~same(size), m, 0.0)
        inverse = inverse - jnp.matmul(
            inverse, jnp.matmul(joined, inverse, precision=PRECISION),
            precision=PRECISION,
        )
        size *= 2
    return inverse


@jax.checkpoint
def _chunk_group(state, xs):
    """`G` consecutive chunks of every head: the triangular systems of all
    G at once, then the state through them one after the other.
    q, k [B, Hk, G, C, Dk]; v [B, H, G, C, Dv]; g, beta [B, H, G, C]."""
    q, k, v, g, beta = xs
    repeat = v.shape[1] // k.shape[1]
    if repeat > 1:  # each key head serves `repeat` value heads
        q, k = (jnp.repeat(x, repeat, axis=1) for x in (q, k))
    g_cum = jnp.cumsum(g, axis=-1)
    rows = jnp.arange(CHUNK)
    lower = rows[:, None] >= rows[None, :]
    # exp only of what the mask keeps: G_i - G_j > 0 above the diagonal.
    decay = jnp.exp(jnp.where(
        lower, g_cum[..., :, None] - g_cum[..., None, :], -jnp.inf
    ))
    k_beta = k * beta[..., None]
    m = jnp.einsum("...ik,...jk->...ij", k_beta, k, precision=PRECISION)
    m = jnp.where(rows[:, None] > rows[None, :], m * decay, 0.0)
    t_m = _unit_lower_inverse(m)
    u = jnp.matmul(t_m, v * beta[..., None], precision=PRECISION)
    w = jnp.matmul(
        t_m, k_beta * jnp.exp(g_cum)[..., None], precision=PRECISION
    )
    qk = jnp.einsum("...ik,...jk->...ij", q, k, precision=PRECISION) * decay
    q_decayed = q * jnp.exp(g_cum)[..., None]
    g_last = g_cum[..., -1:]
    k_tail = k * jnp.exp(g_last - g_cum)[..., None]
    carry_decay = jnp.exp(g_last)[..., None]              # [B,H,G,1,1]
    outs = []
    for i in range(q.shape[2]):
        v_new = u[:, :, i] - jnp.matmul(w[:, :, i], state, precision=PRECISION)
        outs.append(
            jnp.matmul(q_decayed[:, :, i], state, precision=PRECISION)
            + jnp.matmul(qk[:, :, i], v_new, precision=PRECISION)
        )
        state = state * carry_decay[:, :, i] + jnp.einsum(
            "...ck,...cv->...kv", k_tail[:, :, i], v_new,
            precision=PRECISION,
        )
    return state, jnp.stack(outs, axis=2)                 # [B,H,G,C,Dv]


def _decayed_inside(rows, k, g_cum):
    """P[i, j] = sum_d x_i[d] k_j[d] exp(G_i[d] - G_j[d]) for j <= i and
    0 above the diagonal, for each x of `rows` (k itself, then q): the
    decay of a KEY CHANNEL sits inside the contraction, so it cannot be
    laid over a finished [C, C] product as `_chunk_group` lays a head's.
    It factors as (x_i e^(G_i - R)) . (k_j e^(R - G_j)) for any reference
    row R, and the second factor grows without bound unless R is near j,
    so the chunk's ROWS are taken in sub-chunks of `SUB`, R the first row
    of the sub-chunk the row i lies in: a row's factor is then at most 1, a
    column's before the sub-chunk at most 1 too, and a column's inside it
    at most e^((SUB - 1) |bound|) where the caller's gate keeps g above
    `bound` (16 rows at -5 a token: e^75, a float32; a whole chunk of 64
    would not be).  The sub-chunks are one batched product against ALL of
    the chunk's columns (a [C, C] product's worth of work, in one op and
    not C / SUB of growing width); a column behind a row's sub-chunk lies
    above the diagonal, so its factor is set to 1, which keeps it finite
    in both passes, and the product there is masked.
    rows, k, g_cum [..., C, Dk] -> [..., C, C] each."""
    lead, dk = k.shape[:-2], k.shape[-1]
    subs = CHUNK // SUB
    by_sub = g_cum.reshape(lead + (subs, SUB, dk))
    reference = by_sub[..., :1, :]                        # [..., S, 1, Dk]
    into = jnp.exp(by_sub - reference)                    # [..., S, SUB, Dk]
    index = jnp.arange(CHUNK)
    behind = index[None, :, None] >= (
        (jnp.arange(subs)[:, None, None] + 1) * SUB
    )                                                     # [S, C, 1]
    out_of = k[..., None, :, :] * jnp.exp(jnp.where(
        behind, 0.0, reference - g_cum[..., None, :, :]
    ))                                                    # [..., S, C, Dk]
    lower = index[:, None] >= index[None, :]
    return [
        jnp.where(
            lower,
            jnp.einsum(
                "...sik,...sjk->...sij",
                x.reshape(lead + (subs, SUB, dk)) * into, out_of,
                precision=PRECISION,
            ).reshape(lead + (CHUNK, CHUNK)),
            0.0,
        )
        for x in rows
    ]


@jax.checkpoint
def _chunk_group_channels(state, xs):
    """`_chunk_group` where the decay is one rate a KEY CHANNEL: g
    [B, H, G, C, Dk].  The triangular systems are `_chunk_group`'s; what
    differs is where the decay sits: inside the two [C, C] products
    (`_decayed_inside`), and as a factor a channel on q, k and the state's
    rows; the system's inverse is taken by blocks
    (`_unit_lower_inverse_by_blocks`); and the state's walk is ONE product
    a chunk: ``S <- S exp(G_C) + k_tail^T (u - w S)`` is
    ``S <- (diag(exp(G_C)) - k_tail^T w) S + k_tail^T u``, whose two
    coefficients are products over the whole group at once, as are the
    outputs once every chunk's starting state is known.  A step of the
    scan is then some sixty ops whatever the group's size and two more a
    chunk, where the walk of `_chunk_group` is six a chunk.  That, the
    sub-chunks' products as one and `GROUP_CHANNELS` 16 are the form that
    is CHEAP TO TRACE, not the fast one: at 1 x 8192 tokens and 32 heads a
    layer's forward and backward are 5.2k device ops where 8 chunks a
    step with the six-op walk are 14.4k, and 57 ms where those are 41
    (PERF.md section 6, PR 53 (4): a profiler's stop goes by device
    events, and the cell that runs this traces inside its window)."""
    q, k, v, g, beta = xs
    repeat = v.shape[1] // k.shape[1]
    if repeat > 1:  # each key head serves `repeat` value heads
        q, k = (jnp.repeat(x, repeat, axis=1) for x in (q, k))
    g_cum = jnp.cumsum(g, axis=-2)
    kk, qk = _decayed_inside((k, q), k, g_cum)
    index = jnp.arange(CHUNK)
    m = jnp.where(
        index[:, None] > index[None, :], kk * beta[..., None], 0.0
    )
    t_m = _unit_lower_inverse_by_blocks(m)
    since_start = jnp.exp(g_cum)
    u = jnp.matmul(t_m, v * beta[..., None], precision=PRECISION)
    w = jnp.matmul(
        t_m, k * beta[..., None] * since_start, precision=PRECISION
    )
    g_last = g_cum[..., -1:, :]
    k_tail = k * jnp.exp(g_last - g_cum)
    carry_decay = jnp.exp(jnp.swapaxes(g_last, -1, -2))   # [B,H,G,Dk,1]
    keep = carry_decay * jnp.eye(k.shape[-1], dtype=k.dtype) - jnp.einsum(
        "...ck,...cj->...kj", k_tail, w, precision=PRECISION
    )                                                     # [B,H,G,Dk,Dk]
    write = jnp.einsum(
        "...ck,...cv->...kv", k_tail, u, precision=PRECISION
    )                                                     # [B,H,G,Dk,Dv]
    starts = []
    for i in range(q.shape[2]):
        starts.append(state)
        state = jnp.matmul(
            keep[:, :, i], state, precision=PRECISION
        ) + write[:, :, i]
    starts = jnp.stack(starts, axis=2)                    # [B,H,G,Dk,Dv]
    v_new = u - jnp.matmul(w, starts, precision=PRECISION)
    out = jnp.matmul(
        q * since_start, starts, precision=PRECISION
    ) + jnp.matmul(qk, v_new, precision=PRECISION)
    return state, out                                     # [B,H,G,C,Dv]


def _engine(supported, mesh,
            unsupported="head sizes or counts the kernels do not take"):
    """-> ("pallas" or "xla", why, for the log).  The kernels run where
    the backend is a TPU, they take the shapes (`supported`) and the
    trace is known to be
    for one device or comes with the `mesh` to map them over: a Mosaic
    kernel cannot be partitioned automatically, so a trace that may be
    for several devices and names no mesh keeps the engine that can."""
    if jax.default_backend() != "tpu":
        return "xla", f"backend {jax.default_backend()}"
    if not supported:
        return "xla", unsupported
    if mesh is None and jax.device_count() > 1:
        return "xla", f"{jax.device_count()} devices and no mesh given"
    if mesh is not None and mesh.devices.size > 1:
        return "pallas", f"under shard_map over {dict(mesh.shape)}"
    return "pallas", "one device"


def chunk_gated_delta_rule(q, k, v, g, beta, mesh=None):
    """The same outputs as `gated_delta_rule_recurrent`, in chunks of
    `CHUNK` tokens: the Pallas engine or the XLA engine, as `_engine`
    finds (`mesh`: the mesh the caller's program is compiled for, where
    it knows one).

    q and k may have fewer heads than v (each then serves
    `Hv / Hk` consecutive value heads).
    -> (o [B,T,Hv,Dv], final S [B,Hv,Dk,Dv])."""
    b, t, h, dv = v.shape
    out, state = chunk_gated_delta_rule_rows(
        q.reshape(b, t, -1), k.reshape(b, t, -1), v.reshape(b, t, -1),
        g, beta, k.shape[2], mesh=mesh,
    )
    return out.reshape(b, t, h, dv), state


def chunk_gated_delta_rule_rows(q, k, v, g, beta, hk, mesh=None):
    """`chunk_gated_delta_rule` of head-major rows, the layout the
    kernels read and write: q, k [B, T, Hk Dk], v [B, T, Hv Dv] (beta
    [B, T, Hv] says how many value heads; g [B, T, Hv], or
    [B, T, Hv, Dk] for one rate a key channel, which the XLA engine
    alone computes) -> (o [B, T, Hv Dv], final S).  A layer that holds
    its tensors as rows goes through no [B, T, H, D] on the way in or
    out."""
    b, t, h = beta.shape
    dk, dv = k.shape[-1] // hk, v.shape[-1] // h
    if g.ndim == beta.ndim:
        engine, why = _engine(supports(dk, dv, hk, h), mesh)
    else:  # one rate a key channel: the kernels walk a head's scalar
        engine, why = _engine(False, mesh, "a decay a key channel")
    logger.info(
        "delta rule engine: %s chunk_gated_delta_rule T=%d Dk=%d Dv=%d (%s)",
        engine, t, dk, dv, why,
    )
    if engine == "pallas":
        return _rows_pallas(q, k, v, g, beta, hk, None, mesh)
    out, state = chunk_gated_delta_rule_xla(
        q.reshape(b, t, hk, dk), k.reshape(b, t, hk, dk),
        v.reshape(b, t, h, dv), g, beta,
    )
    return out.reshape(b, t, h * dv), state


def _padded(xs, t, chunks):
    """Zero k, v, beta and g up to `chunks` whole chunks: a padded token
    neither decays nor writes."""
    pad = chunks * CHUNK - t
    if not pad:
        return xs
    return tuple(
        jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2)) for x in xs
    )


def chunk_gated_delta_rule_xla(q, k, v, g, beta):
    """`chunk_gated_delta_rule` in XLA ops: a `lax.scan` over groups; g
    [B, T, H], or [B, T, H, Dk] for one rate a key channel (the shape
    decides: `_chunk_group_channels`)."""
    out_dtype = v.dtype
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    b, t, h, dv = v.shape
    dk = k.shape[-1]
    n = -(-t // CHUNK)
    channels = g.ndim > beta.ndim
    group = min(GROUP_CHANNELS if channels else GROUP, n)
    steps = -(-n // group)
    q, k, v, g, beta = _padded((q, k, v, g, beta), t, steps * group)

    def grouped(x):  # [B, T, H, ...] -> [steps, B, H, G, C, ...]
        x = x.reshape((b, steps, group, CHUNK) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 4, 2), 1, 0)

    state0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    state, out = jax.lax.scan(
        _chunk_group_channels if channels else _chunk_group,
        state0, tuple(map(grouped, (q, k, v, g, beta))),
    )
    # [steps,B,H,G,C,Dv] -> [B,T,H,Dv]
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 2, 4).reshape(
        b, steps * group * CHUNK, h, dv
    )
    return out[:, :t].astype(out_dtype), state


# ----------------------------------------------------------------------
# The Pallas engine
# ----------------------------------------------------------------------

PAIR = 2 * CHUNK  # rows of two heads' chunks, one under the other

# How a product's two operands are laid out: a b, a b^T, a^T b.
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
# (layout, operands) of (da, db) for each: C = A B gives dA = ct B^T and
# dB = A^T ct, and so on; the three layouts are closed under it.
_GRADS = {
    _NN: ((_NT, "ct,b"), (_TN, "a,ct")),
    _NT: ((_NN, "ct,b"), (_TN, "ct,a")),
    _TN: ((_NT, "b,ct"), (_NN, "a,ct")),
}


def _split(x):
    """float32 -> (hi, lo) in bfloat16 with hi + lo = x to 16 bits."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mm(a, b, dims):
    """A float32 product at `Precision.HIGH` written out (Mosaic takes
    DEFAULT or HIGHEST): a = hi + lo in bfloat16 and hi hi + hi lo +
    lo hi with float32 accumulation; the lo lo term, 2^-16 of the
    product, is what HIGH drops too.  The three terms are ONE product of
    [hi | hi | lo] with [hi | lo | hi] along the contracted axis, so
    that they add up where the MXU accumulates and one result comes
    back, not three.  Backward the same, of the same operands."""
    (a_axis,), (b_axis,) = dims
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    return jax.lax.dot_general(
        jnp.concatenate([a_hi, a_hi, a_lo], axis=a_axis),
        jnp.concatenate([b_hi, b_lo, b_hi], axis=b_axis),
        (dims, ((), ())), preferred_element_type=jnp.float32,
    )


def _mm_fwd(a, b, dims):
    return _mm(a, b, dims), (a, b)


def _mm_bwd(dims, residuals, ct):
    named = dict(zip("ab", residuals), ct=ct)
    return tuple(
        _mm(*(named[name] for name in operands.split(",")), layout)
        for layout, operands in _GRADS[dims]
    )


_mm.defvjp(_mm_fwd, _mm_bwd)


def _halves():
    """(whether a lane of a [C, 2C] tile is the first head's, a [2C, 2C]
    tile's block-diagonal mask)."""
    first = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, PAIR), 1) < CHUNK
    rows = jax.lax.broadcasted_iota(jnp.int32, (PAIR, PAIR), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (PAIR, PAIR), 1)
    return first, (rows >= CHUNK) == (cols >= CHUNK)


def _block_diagonal(x):
    """Two heads' [C, C] side by side [C, 2C] -> one [2C, 2C], the
    second's under and right of the first's, zero elsewhere."""
    return jnp.where(_halves()[1], jnp.concatenate([x, x], axis=0), 0.0)


def _side_by_side(x):
    """The diagonal blocks of [2C, 2C] -> [C, 2C]."""
    return jnp.where(_halves()[0], x[:CHUNK], x[CHUNK:])


# Two heads' matrices lie side by side, [A1 | A2]: a product FROM THE
# RIGHT with both heads' other matrix is then [A1 | A2] blockdiag(B1, B2),
# 64 rows through the MXU where the block-diagonal form of both pushes
# 128.
#
# Everything below takes a LIST, one entry a pair of heads, and walks it
# stage by stage: a pair's products each wait for the last one (a chain
# of some sixteen, ten of them the inverse's), and the compiler schedules
# what it finds near by, so the pairs of a block are written out side by
# side, each stage for all of them, for another pair's product to fill
# the MXU while this one's drains.
@jax.custom_vjp
def _unit_lower_inverses(ms):
    """(I + M)^-1 of two strictly lower triangular M side by side, for
    each entry of the list."""
    first, _ = _halves()
    rows = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, PAIR), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, PAIR), 1)
    eye = (rows == jnp.where(first, cols, cols - CHUNK)).astype(jnp.float32)
    powers = [-m for m in ms]
    inverses = [eye + power for power in powers]
    span = 2
    while span < CHUNK:
        powers = [
            _mm(power, _block_diagonal(power), _NN) for power in powers
        ]
        inverses = [
            _mm(inverse, _block_diagonal(eye + power), _NN)
            for inverse, power in zip(inverses, powers)
        ]
        span *= 2
    return inverses


def _inverses_grad(inverses, cts):
    """dM = -T^T dT T^T: two products, not the ten's transposes."""
    ts = [_block_diagonal(inverse) for inverse in inverses]
    left = [_mm(t, _block_diagonal(ct), _TN) for t, ct in zip(ts, cts)]
    return [_side_by_side(-_mm(x, t, _NT)) for x, t in zip(left, ts)]


_unit_lower_inverses.defvjp(
    lambda ms: (_unit_lower_inverses(ms),) * 2,
    lambda inverses, cts: (_inverses_grad(inverses, cts),),
)


@jax.custom_vjp
def _known_inverses(ms, inverses):
    """`_unit_lower_inverses(ms)` where a caller has kept them."""
    return inverses


_known_inverses.defvjp(
    lambda ms, inverses: (inverses, inverses),
    lambda inverses, cts: (
        _inverses_grad(inverses, cts), [jnp.zeros_like(ct) for ct in cts],
    ),
)


def _pair_systems(k, g_col, g_row, beta):
    """-> (decay, M), both heads' side by side [C, 2C]: exp(G_i - G_j)
    on and under each diagonal, beta_i (k_i . k_j) exp(G_i - G_j) under
    it."""
    first, _ = _halves()
    rows = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, PAIR), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, PAIR), 1)
    cols = jnp.where(first, cols, cols - CHUNK)

    def each(column):  # [2C, 1] -> its head's column in each half
        return jnp.where(first, column[:CHUNK], column[CHUNK:])

    # exp only of what the mask keeps, as in `_chunk_group`.
    decay = jnp.exp(jnp.where(rows >= cols, each(g_col) - g_row, -jnp.inf))
    kk = _mm(k, jnp.concatenate([k, k], axis=0), _NT)
    return decay, jnp.where(rows > cols, kk * each(beta) * decay, 0.0)


class _Pair(NamedTuple):
    """One chunk of the TWO value heads of one key head, as values in
    VMEM (a pytree: `jax.vjp` hands its gradients back in this form)."""

    q: Any        # [C, Dk], the key head's
    k: Any
    v: Any        # [2C, Dv], the second head's rows under the first's
    g_col: Any    # G, the chunk's cumulative log-decay: a column [2C, 1]
    g_row: Any    # ... and a row [1, 2C]
    tail: Any     # G_C - G, a column
    beta: Any     # a column
    g_last: Any   # per head G_C along the lanes [1, Dv] (Mosaic does not
    # broadcast one element along sublanes and lanes at once)
    states: Any   # per head [Dk, Dv]


def _chunk_pairs(pairs, inverses=None, with_out=True):
    """One chunk of a block's heads: `pairs` a list of `_Pair`,
    `inverses` the systems' inverses where the caller has kept them.
    -> [(o [2C, Dv] or None, the two next states)]."""
    heads = [
        (i, slice(j * CHUNK, (j + 1) * CHUNK), pair.states[j], pair.g_last[j])
        for i, pair in enumerate(pairs) for j in range(2)
    ]  # (its pair, its rows there, its state, its G_C)
    dv = pairs[0].v.shape[-1]
    systems = [
        _pair_systems(pair.k, pair.g_col, pair.g_row, pair.beta)
        for pair in pairs
    ]
    ms = [m for _, m in systems]
    if inverses is None:
        inverses = _unit_lower_inverses(ms)
    else:
        inverses = _known_inverses(ms, inverses)
    k2s = [jnp.concatenate([pair.k, pair.k], axis=0) for pair in pairs]
    e_gs = [jnp.exp(pair.g_col) for pair in pairs]
    # u and w of both heads in one product: T [v beta | k beta e^G]
    uws = [
        _mm(
            _block_diagonal(inverse),
            jnp.concatenate(
                [pair.v * pair.beta, k2 * (pair.beta * e_g)], axis=1
            ),
            _NN,
        )
        for inverse, k2, e_g, pair in zip(inverses, k2s, e_gs, pairs)
    ]
    if with_out:
        # w S and (q e^G) S in one product
        ws = [
            _mm(
                jnp.concatenate(
                    [uws[i][rows, dv:], pairs[i].q * e_gs[i][rows]], axis=0
                ),
                state, _NN,
            )
            for i, rows, state, _ in heads
        ]
        v_new = [
            uws[i][rows, :dv] - w_s[:CHUNK]
            for (i, rows, _, _), w_s in zip(heads, ws)
        ]
    else:
        v_new = [
            uws[i][rows, :dv] - _mm(uws[i][rows, dv:], state, _NN)
            for i, rows, state, _ in heads
        ]
    k_tails = [k2 * jnp.exp(pair.tail) for k2, pair in zip(k2s, pairs)]
    new_states = [
        state * jnp.exp(last) + _mm(k_tails[i][rows], new, _TN)
        for (i, rows, state, last), new in zip(heads, v_new)
    ]
    if not with_out:
        return [(None, new_states[2 * i:2 * i + 2]) for i in range(len(pairs))]
    scores = [
        _mm(pair.q, k2, _NT) * decay
        for pair, k2, (decay, _) in zip(pairs, k2s, systems)
    ]
    outs = [
        jnp.concatenate([ws[2 * i][CHUNK:], ws[2 * i + 1][CHUNK:]], axis=0)
        + _mm(
            _block_diagonal(scores[i]),
            jnp.concatenate(v_new[2 * i:2 * i + 2], axis=0), _NN,
        )
        for i in range(len(pairs))
    ]
    return [
        (out, new_states[2 * i:2 * i + 2]) for i, out in enumerate(outs)
    ]


def _block_operands(refs, gates, state_of, rows, dims):
    """A block's `_Pair`s of value heads (heads 2p and 2p + 1, of one
    key head); `gates` the block's (columns,
    G row, G_C along the lanes) tiles of this chunk, the columns being
    G, G_C - G and beta of each pair; `rows` the chunk's rows of the
    refs."""
    q_ref, k_ref, v_ref = refs
    r, dk, dv = dims
    columns, g_row, g_last = gates
    pairs = g_row.shape[0]

    def pair(p):
        g_col, tail, beta = (
            columns[:, i * pairs + p:i * pairs + p + 1] for i in range(3)
        )
        heads = (2 * p, 2 * p + 1)
        j = 2 * p // r
        return _Pair(
            q_ref[0, rows, j * dk:(j + 1) * dk],
            k_ref[0, rows, j * dk:(j + 1) * dk],
            jnp.concatenate(
                [v_ref[0, rows, h * dv:(h + 1) * dv] for h in heads], axis=0
            ),
            g_col, g_row[p:p + 1], tail, beta,
            tuple(g_last[h:h + 1] for h in heads),
            tuple(state_of(h) for h in heads),
        )

    return [pair(p) for p in range(pairs)]


def _fwd_kernel(q_ref, k_ref, v_ref, col_ref, gr_ref, gl_ref,
                o_ref, final_ref, saved_ref, state, *, r, dk, dv, every):
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    @pl.when(i % every == 0)
    def _():
        saved_ref[0, :, 0] = state[...]

    results = _chunk_pairs(_block_operands(
        (q_ref, k_ref, v_ref),
        (col_ref[0, 0], gr_ref[0, 0, 0], gl_ref[0, 0, 0]),
        lambda h: state[h], slice(None), (r, dk, dv),
    ))
    for p, (out, new) in enumerate(results):
        for i_head, h in enumerate((2 * p, 2 * p + 1)):
            o_ref[0, :, h * dv:(h + 1) * dv] = (
                out[i_head * CHUNK:(i_head + 1) * CHUNK]
            )
            state[h] = new[i_head]

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        final_ref[0] = state[...]


def _bwd_kernel(q_ref, k_ref, v_ref, col_ref, gr_ref, gl_ref,
                saved_ref, do_ref, dfinal_ref,
                dq_ref, dk_ref, dv_ref, dcol_ref, dgr_ref, dgl_ref,
                dstate, states, inverses, *, hb, r, dk, dv, every):
    """One group of `every` chunks, the groups last first: the states at
    each chunk's start walked forward from the saved one, each chunk's
    inverses kept, then each chunk's vector-Jacobian product (JAX's, of
    `_chunk_pairs` around the kept inverses), last chunk first."""
    pairs = hb // 2

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = dfinal_ref[0]

    states[0] = saved_ref[0, :, 0]

    def chunk(c):
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        pair_rows = pl.ds(pl.multiple_of(c * PAIR, PAIR), PAIR)
        return rows, pair_rows, _block_operands(
            (q_ref, k_ref, v_ref),
            (col_ref[0, 0, pair_rows], gr_ref[0, 0, c], gl_ref[0, 0, c]),
            lambda h: states[c, h], rows, (r, dk, dv),
        )

    def forward(c, carry):
        _, _, operands = chunk(c)
        kept = _unit_lower_inverses([
            _pair_systems(pair.k, pair.g_col, pair.g_row, pair.beta)[1]
            for pair in operands
        ])
        for p, inverse in enumerate(kept):
            inverses[c, p] = inverse

        @pl.when(c < every - 1)
        def _():
            for p, (_, new) in enumerate(
                _chunk_pairs(operands, inverses=kept, with_out=False)
            ):
                states[c + 1, 2 * p], states[c + 1, 2 * p + 1] = new

        return carry

    jax.lax.fori_loop(0, every, forward, None)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 3 * pairs), 1)
    pair_sublane = jax.lax.broadcasted_iota(jnp.int32, (pairs, 1), 0)
    head_sublane = jax.lax.broadcasted_iota(jnp.int32, (hb, 1), 0)

    def backward(step, carry):
        c = every - 1 - step
        rows, pair_rows, operands = chunk(c)
        _, pullback = jax.vjp(
            functools.partial(
                _chunk_pairs, inverses=[inverses[c, p] for p in range(pairs)]
            ),
            operands,
        )
        (grads,) = pullback([
            (
                jnp.concatenate([
                    do_ref[0, rows, h * dv:(h + 1) * dv]
                    for h in (2 * p, 2 * p + 1)
                ], axis=0),
                [dstate[2 * p], dstate[2 * p + 1]],
            )
            for p in range(pairs)
        ])
        d_cols = jnp.zeros((PAIR, 3 * pairs), jnp.float32)
        d_row = jnp.zeros((pairs, PAIR), jnp.float32)
        d_last = jnp.zeros((hb, dv), jnp.float32)
        d_qk = {}
        for p, d in enumerate(grads):
            for i, part in enumerate((d.g_col, d.tail, d.beta)):
                d_cols = d_cols + jnp.where(lane == i * pairs + p, part, 0.0)
            d_row = d_row + jnp.where(pair_sublane == p, d.g_row, 0.0)
            for i_head, h in enumerate((2 * p, 2 * p + 1)):
                head = slice(i_head * CHUNK, (i_head + 1) * CHUNK)
                dv_ref[0, rows, h * dv:(h + 1) * dv] = d.v[head]
                dstate[h] = d.states[i_head]
                d_last = d_last + jnp.where(
                    head_sublane == h, d.g_last[i_head], 0.0
                )
            # the pairs of one key head add up in its q and k
            j = 2 * p // r
            d_qk[j] = (
                (d_qk[j][0] + d.q, d_qk[j][1] + d.k) if j in d_qk
                else (d.q, d.k)
            )
        for j, (dq, dk_) in d_qk.items():
            dq_ref[0, rows, j * dk:(j + 1) * dk] = dq
            dk_ref[0, rows, j * dk:(j + 1) * dk] = dk_
        dcol_ref[0, 0, pair_rows] = d_cols
        dgr_ref[0, 0, c] = d_row
        dgl_ref[0, 0, c] = d_last
        return carry

    jax.lax.fori_loop(0, every, backward, None)


# What a kernel may hold in VMEM: of the 128 MiB a v5e's or a v6e's core
# has, with room left for what Mosaic keeps beside the kernel's own.
VMEM_LIMIT = 96 << 20


def _vmem_bytes(key_heads: int, r: int, dk: int, dv: int) -> int:
    """What the backward kernel, the larger of the two, holds in VMEM
    with `key_heads` key heads of `r` value heads each a grid step: its
    scratch (dS, a group's states and inverses) and its blocks, each
    twice because the pipeline fetches the next while this one is worked
    on (q, k and their gradients; v, do and dv; the saved state and
    d final), then 8 states' worth for the live values of a chunk's
    vector-Jacobian product.  The one reading there is: sixteen heads of
    256 a step used 144 MB where the rest adds up to 134, 2.5 states'
    worth; every choice of 93 to 96 MB by this count that was tried
    compiled (both for a described v5e, PR 29)."""
    hb = key_heads * r
    state = hb * dk * dv * 4
    rows = EVERY * CHUNK
    scratch = (EVERY + 1) * state + EVERY * (hb // 2) * CHUNK * PAIR * 4
    blocks = (4 * key_heads * dk + 3 * hb * dv) * rows * 4 + 2 * state
    return scratch + 2 * blocks + 8 * state


def _heads_a_block(hk: int, h: int, dk: int, dv: int) -> int:
    """Value heads a grid step, 0 if none fits: whole key heads, and as
    many pairs as the counts and `VMEM_LIMIT` allow (one pair's products
    each wait for the last; another pair's fill the MXU's pipeline
    meanwhile)."""
    r = h // hk
    return r * next(
        (
            n for n in (8, 4, 2, 1)
            if hk % n == 0 and _vmem_bytes(n, r, dk, dv) <= VMEM_LIMIT
        ),
        0,
    )


def supports(dk: int, dv: int, hk: int, h: int) -> bool:
    """Whether the kernels take these head sizes and counts: a head of
    the [B, T, H D] rows has to be whole lane tiles, the value heads go
    two by two, each two of one key head, and one key head's block at
    least has to fit in VMEM."""
    return (
        dk % 128 == 0 and dv % 128 == 0 and h % (2 * hk) == 0
        and _heads_a_block(hk, h, dk, dv) > 0
    )


def _specs(hk, h, dk, dv, rows_of, chunk_block):
    """Block specs of (q or k, v or o, the gates' columns, G's rows, G_C
    along the lanes, a block's states) for a grid (sequence, block of
    heads, step); a step covers `chunk_block` chunks, the
    `rows_of(step)`-th such block."""
    hb = _heads_a_block(hk, h, dk, dv)
    rows = chunk_block * CHUNK
    return (
        pl.BlockSpec(
            (1, rows, hb * hk // h * dk), lambda s, j, i: (s, rows_of(i), j)
        ),
        pl.BlockSpec((1, rows, hb * dv), lambda s, j, i: (s, rows_of(i), j)),
        pl.BlockSpec(
            (1, 1, chunk_block * PAIR, 3 * hb // 2),
            lambda s, j, i: (s, j, rows_of(i), 0),
        ),
        pl.BlockSpec(
            (1, 1, chunk_block, hb // 2, PAIR),
            lambda s, j, i: (s, j, rows_of(i), 0, 0),
        ),
        pl.BlockSpec(
            (1, 1, chunk_block, hb, dv),
            lambda s, j, i: (s, j, rows_of(i), 0, 0),
        ),
        pl.BlockSpec((1, hb, dk, dv), lambda s, j, i: (s, j, 0, 0)),
    )


def _saved_spec(hb, dk, dv, group_of):
    return pl.BlockSpec(
        (1, hb, 1, dk, dv), lambda s, j, i: (s, j, group_of(i), 0, 0)
    )


def _compiler_params(vmem_limit_bytes):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes,
    )


def _several(mesh):
    """The mesh where a kernel call has to be mapped over it, else None."""
    return mesh if mesh is not None and mesh.devices.size > 1 else None


def _over_batch(mesh, b, call_for, whole=()):
    """The kernel call for `b` sequences: `call_for(b)` as it is for one
    device.  Under a multi-device `mesh` (a Mosaic kernel cannot be
    partitioned automatically, and the sequences do not depend on each
    other) the call for one shard's sequences inside a `shard_map`,
    every operand and result split over the data axis along its leading,
    batch, axis, or whole on every device where that axis does not
    divide `b`; the operands at the positions `whole` have no batch axis
    (a layer's parameters) and are whole on every device.  The
    `custom_vjp` stays outside: each pass is mapped on
    its own, and no derivative is taken through the `shard_map`."""
    if mesh is None:
        return call_for(b)
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.parallel import compile as pc
    from elasticdl_tpu.parallel.mesh import DATA_AXIS

    shards = mesh.shape.get(DATA_AXIS, 1)
    split = b % shards == 0
    spec = P(DATA_AXIS) if split else P()
    # check_vma=False: the kernel interpreter trips the checker off the
    # TPU, as under the ring's shard_map (parallel/ring_attention.py).
    call = call_for(b // shards if split else b)

    def mapped(*operands):
        return pc.shard_map_call(
            call, mesh,
            in_specs=tuple(
                P() if i in whole else spec for i in range(len(operands))
            ),
            out_specs=spec, check_vma=False,
        )(*operands)

    return mapped


# Both kernel calls are jitted: a program's layers (and a process's
# programs) then share ONE trace of each kernel and a program one
# lowering, where each layer and pass would pay Python a second or two
# for them at every start of a worker, whatever the compile cache holds.
@functools.partial(jax.jit, static_argnums=0)
def _forward_call(static, q, k, v, columns, g_row, g_last):
    """-> (o, the final states, the states at each group's start)."""
    hk, h, dk, dv, every, interpret, mesh = static
    t = q.shape[1]
    n = t // CHUNK
    hb = _heads_a_block(hk, h, dk, dv)
    qk_spec, v_spec, col_spec, row_spec, last_spec, state_spec = _specs(
        hk, h, dk, dv, lambda i: i, 1
    )

    def call_for(b):
        return pl.pallas_call(
            functools.partial(
                _fwd_kernel, r=h // hk, dk=dk, dv=dv, every=every
            ),
            grid=(b, h // hb, n),
            in_specs=[qk_spec, qk_spec, v_spec, col_spec, row_spec, last_spec],
            out_specs=[
                v_spec, state_spec,
                _saved_spec(hb, dk, dv, lambda i: i // every),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, t, h * dv), jnp.float32),
                jax.ShapeDtypeStruct((b, h, dk, dv), jnp.float32),
                jax.ShapeDtypeStruct((b, h, n // every, dk, dv), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
            compiler_params=_compiler_params(VMEM_LIMIT // 3),
            name="delta_rule_fwd",
            interpret=interpret,
        )

    return _over_batch(mesh, q.shape[0], call_for)(
        q, k, v, columns, g_row, g_last
    )


@functools.partial(jax.jit, static_argnums=0)
def _backward_call(static, operands, saved, d_out, d_final):
    """-> the gradients of `operands` (what `_forward_call` took)."""
    hk, h, dk, dv, every, interpret, mesh = static
    t = operands[0].shape[1]
    groups = t // CHUNK // every
    hb = _heads_a_block(hk, h, dk, dv)
    qk_spec, v_spec, col_spec, row_spec, last_spec, state_spec = _specs(
        hk, h, dk, dv, lambda i: groups - 1 - i, every
    )
    operand_specs = [qk_spec, qk_spec, v_spec, col_spec, row_spec, last_spec]

    def call_for(b):
        return pl.pallas_call(
            functools.partial(
                _bwd_kernel, hb=hb, r=h // hk, dk=dk, dv=dv, every=every
            ),
            grid=(b, h // hb, groups),
            in_specs=operand_specs + [
                _saved_spec(hb, dk, dv, lambda i: groups - 1 - i),
                v_spec, state_spec,
            ],
            out_specs=operand_specs,
            out_shape=[
                jax.ShapeDtypeStruct((b,) + x.shape[1:], jnp.float32)
                for x in operands
            ],
            scratch_shapes=[
                pltpu.VMEM((hb, dk, dv), jnp.float32),
                pltpu.VMEM((every, hb, dk, dv), jnp.float32),
                pltpu.VMEM((every, hb // 2, CHUNK, PAIR), jnp.float32),
            ],
            compiler_params=_compiler_params(VMEM_LIMIT),
            name="delta_rule_bwd",
            interpret=interpret,
        )

    return _over_batch(mesh, operands[0].shape[0], call_for)(
        *operands, saved, d_out, d_final
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _delta_walk(q, k, v, columns, g_row, g_last, static):
    return _delta_walk_fwd(q, k, v, columns, g_row, g_last, static)[0]


def _delta_walk_fwd(*operands_and_static):
    *operands, static = operands_and_static
    out, final, saved = _forward_call(static, *operands)
    return (out, final), (*operands, saved)


def _delta_walk_bwd(static, residuals, cts):
    *operands, saved = residuals
    return tuple(_backward_call(static, tuple(operands), saved, *cts))


_delta_walk.defvjp(_delta_walk_fwd, _delta_walk_bwd)


def chunk_gated_delta_rule_pallas(q, k, v, g, beta, interpret=None,
                                  mesh=None):
    """`chunk_gated_delta_rule` in the Pallas kernels (interpret mode off
    the TPU; under a multi-device `mesh`, a shard's sequences a device:
    `_over_batch`), of [B, T, H, D] tensors: the kernels take them as the
    [B, T, H D] rows they are (`_rows_pallas`)."""
    b, t, h, dv = v.shape
    out, state = _rows_pallas(
        q.reshape(b, t, -1), k.reshape(b, t, -1), v.reshape(b, t, -1),
        g, beta, k.shape[2], interpret, mesh,
    )
    return out.reshape(b, t, h, dv), state


def _rows_pallas(q, k, v, g, beta, hk, interpret, mesh):
    """The kernels read q, k, v as the [B, T, H D] rows they are
    and write o the same way.  g and beta, small, are laid out for them
    here, a block's heads two by two: G (the chunk's cumulative sum),
    G_C - G and beta as columns [B, blocks, chunks x 2C, 3 x pairs], G
    as rows [B, blocks, chunks, pairs, 2C], and G_C of each head along
    the lanes [B, blocks, chunks, heads, lanes]."""
    out_dtype = v.dtype
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    b, t, h = g.shape
    dk, dv = k.shape[-1] // hk, v.shape[-1] // h
    hb = _heads_a_block(hk, h, dk, dv)
    blocks, pairs = h // hb, hb // 2
    n = -(-t // CHUNK)
    every = min(EVERY, n)
    n = -(-n // every) * every
    q, k, v, g, beta = _padded((q, k, v, g, beta), t, n)

    def chunked(x):  # [B, T, H] -> [B, blocks, chunks, pairs, 2, C]
        return jnp.transpose(
            x.reshape(b, n, CHUNK, blocks, pairs, 2), (0, 3, 1, 4, 5, 2)
        )

    def column(x):  # -> [B, blocks, chunks x 2C, pairs]
        return jnp.moveaxis(x, 3, 5).reshape(b, blocks, n * PAIR, pairs)

    g_cum = jnp.cumsum(chunked(g), axis=-1)
    g_last = g_cum[..., -1:]
    out, state = _delta_walk(
        q, k, v,
        jnp.concatenate([
            column(g_cum), column(g_last - g_cum), column(chunked(beta)),
        ], axis=-1),
        g_cum.reshape(b, blocks, n, pairs, PAIR),
        jnp.broadcast_to(
            g_last.reshape(b, blocks, n, hb, 1), (b, blocks, n, hb, dv)
        ),
        (hk, h, dk, dv, every,
         _use_interpret() if interpret is None else interpret,
         _several(mesh)),
    )
    return (out if n * CHUNK == t else out[:, :t]).astype(out_dtype), state
