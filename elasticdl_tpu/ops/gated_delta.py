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
from HIGH by a comparison at the stated precision.  The backward pass
is the reverse scan JAX derives, with the scan step rematerialised so
that only the state at each group's start is kept (T / (C GROUP) x
[Dk, Dv] a head, not every intermediate of every chunk).

Shapes: q, k [B, T, Hk, Dk]; v [B, T, H, Dv]; g, beta [B, T, H], with
Hk = H or a divisor of it (key head i then serves value heads
i H / Hk .. (i + 1) H / Hk - 1).
q and k arrive normalised and scaled as the caller's layer defines.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64
GROUP = 8  # chunks a scan step
PRECISION = jax.lax.Precision.HIGH


def gated_delta_rule_recurrent(q, k, v, g, beta):
    """The recurrence itself, one token a step.  -> (o [B,T,H,Dv], S)."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    b, _, h, dk = k.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs          # [B,H,D] / [B,H]
        state = state * jnp.exp(g_t)[..., None, None]
        delta = beta_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
        )
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    state, out = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(out, 0, 1), state


def _unit_lower_inverse(m):
    """(I + M)^-1 for strictly lower triangular M [..., C, C]."""
    c = m.shape[-1]
    eye = jnp.eye(c, dtype=m.dtype)
    power = -m
    inverse = eye + power
    span = 2
    while span < c:
        power = jnp.matmul(power, power, precision=PRECISION)
        inverse = jnp.matmul(inverse, eye + power, precision=PRECISION)
        span *= 2
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


def chunk_gated_delta_rule(q, k, v, g, beta):
    """The same outputs as `gated_delta_rule_recurrent`, in chunks of
    `CHUNK` tokens, `GROUP` chunks a scan step.

    q and k may have fewer heads than v (each then serves
    `Hv / Hk` consecutive value heads).
    -> (o [B,T,Hv,Dv], final S [B,Hv,Dk,Dv])."""
    out_dtype = v.dtype
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    b, t, h, dv = v.shape
    dk = k.shape[-1]
    n = -(-t // CHUNK)
    group = min(GROUP, n)
    steps = -(-n // group)
    pad = steps * group * CHUNK - t
    if pad:
        # Zero k, v, beta and g: a padded token neither decays nor writes.
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta)
        )

    def grouped(x):  # [B, T, H, ...] -> [steps, B, H, G, C, ...]
        x = x.reshape((b, steps, group, CHUNK) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 4, 2), 1, 0)

    state0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    state, out = jax.lax.scan(
        _chunk_group, state0, tuple(map(grouped, (q, k, v, g, beta)))
    )
    # [steps,B,H,G,C,Dv] -> [B,T,H,Dv]
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 2, 4).reshape(
        b, steps * group * CHUNK, h, dv
    )
    return out[:, :t].astype(out_dtype), state
