"""Rotary position embedding and grouped-query attention under a mask rule.

Rotary is the rotate-half form on the first `rotary_dim` of a head's
dimensions (a partial rotary factor leaves the rest untouched), with
plain frequencies (`rotary_tables`) or YaRN's blend of interpolated and
extrapolated ones (`yarn_rotary_tables`).

Grouped-query attention gives every key-value head to `Hq / Hkv` query
heads.  q and k share a head size `Dqk`; v may have another, `Dv` (latent
attention: scores over a head's position-free and rotary parts, values
of the position-free size), and the output has v's.  The scores are
scaled by `scale`, 1/sqrt(Dqk) where none is given.  Two engines:

- `ops.flash_attention`, the Pallas kernel, where the backend is a TPU and
  `supports(T, Dqk, d_v=Dv)` holds (K and V of a head resident in VMEM:
  T (Dqk + Dv) 4 B within 8 MiB, so T <= 4096 at head sizes of 256 or of
  192 and 128).  It takes equal head counts, so K and V are repeated to
  the query heads for it.  `auto` takes it wherever it fits, and at
  T 8192, heads of 128, the three shapes measured say it should not (a
  v5e, forward, rematerialised forward and backward): K and V repeated 8
  times, 51.35 ms a layer against 35.10 in the XLA engine (32 heads over
  4, 2 sequences, PR 42), repeated 6 and 8 times in Laguna's two layer
  types (PR 36: its job says `xla` too), and with NOTHING repeated, 16
  heads over 16, 1 sequence: 13.74 ms a layer application against
  12.54 (PR 45; that cell keeps `auto`, for its traced runs' sake).  The rule is still left to a PR that measures every
  cell whose stack calls it (PERF.md section 7).
- `causal_gqa_attention`, flash numerics in XLA ops, for everything else.
  `parallel.ring_attention.blockwise_attention` scores ALL queries against
  one key chunk at a time, and the reverse pass JAX derives for its scan
  keeps every chunk's probabilities ([B, H, T, chunk] each: 8 GiB at
  T = 8192, 16 heads, 2 sequences); cutting its queries into independent
  rematerialised blocks leaves XLA free to hold several blocks' slabs at
  once (11 GB, compiled for a v5e).  So this engine walks the query blocks
  in a loop and, for each, only the key blocks its MASK RULE lets it
  see (a device-side loop with a trip count of its own, so under a causal
  mask the half of the score matrix above the diagonal is never
  computed), keeps the output and the log-sum-exp, and has its own
  backward pass that recomputes a block's probabilities from them (its
  forward rule NAMES the two, `ATTN_OUT` and `ATTN_LSE`, as the Pallas
  kernel's does: a rematerialised layer whose policy saves those names
  hands them to this backward pass and runs no forward loop again).  One
  [B, Hq, block, block] slab is alive at a time.  Query heads of one
  key-value head are batched into one product, so K and V are never
  repeated.

  The mask is a RULE the engine is handed (ISSUE 51), not a case it
  knows: for query tile i, `visits(i, block)` gives the bounds of its
  key loop, `tile(i, step, block)` the key tile a step of that loop
  reads, and `masked(rows, cols)` a tile's own mask from its positions;
  the tile a row visits LAST holds the row's own key (`_scores`).  Three
  rules exist, as two classes: `Causal()` (tiles 0..i; the mask `cols >
  rows`), `Causal(window)` (the band: from `_first_block` to i) and
  `BlockDiffusion(tokens, length)` (a noised copy of `tokens` tokens in
  front of their clean copy, blocks of `length`: the clean tiles up to
  and with the query tile's own, then, for a noised query tile, its own
  noised tile; n (n + 1) + n visits for 2 n query tiles, forward and
  backward, where a plain mask over them would make n (2 n + 1)).  The
  first two trace to the programs they traced to before the rule was an
  argument (a test holds the lowered text).

  Its layout contract (ISSUE 43): operands, results and their gradients
  are [B, H, T, D], HEADS IN FRONT OF TOKENS, so that a block of one
  head is a contiguous [block, D] matrix, and both loops take block `i`
  by a dynamic slice of the token axis and write their results the same
  way.  Nothing is laid out again between the caller and the loops: the
  old layout, the block axis moved in front of the batch for a
  `lax.scan`, was free at one sequence and a physical transpose at two,
  and the compiler pulled it back through whatever produced q and k, at
  a float32 copy a crossing (8-15 GB a step in four cells, PERF.md
  section 6, PR 43).  A caller that holds a projection's result packs q
  and k with `ops/rotary_pack.py` (norm, rotation, one rounding, this
  layout, one pass) and says `packed=True`; for the others the front
  door swaps the axes (`heads_first`) on the way in and out.

`causal_attention(window=W)` is a causal BAND: a query at t reads the keys
`t - W < s <= t`.  The XLA engine alone runs it, and skips what lies
outside the band, forward and backward: its key loop starts at the band's
first block (`_first_block`).  The Pallas kernel has no band (a streaming
one, tried in PR 36, was slower: PERF.md section 6, ROADMAP queue 1
item 5), so `impl="pallas"` with a window raises.

`causal_attention(block_diffusion=(T, B))` is the block-diffusion mask
over q, k, v of 2 T positions: the XLA engine alone, in tiles that hold
whole blocks of ONE copy (T a multiple of the tile, the tile of B:
checked, with a message that says which); `impl="pallas"` raises, as
with a window (the kernel's `causal` is a bool, and K + V of a head over
both copies of 8192 tokens are 16 MiB against its 8).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("ops.gqa")


def _plain_inv_freq(rotary_dim: int, theta: float):
    return 1.0 / (
        theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    )


def _angles(positions, inv_freq):
    """[T, rotary_dim]: position x frequency, the pairs' halves side by
    side (rotate-half form)."""
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.concatenate([angles, angles], axis=-1)


def rotary_tables(positions, rotary_dim: int, theta: float):
    """-> (cos, sin), each [T, rotary_dim], float32."""
    angles = _angles(positions, _plain_inv_freq(rotary_dim, theta))
    return jnp.cos(angles), jnp.sin(angles)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature term m(f, a) = 0.1 a ln f + 1 (1 for
    f <= 1)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(rotary_dim: int, theta: float, original: int,
                          beta_fast: float, beta_slow: float):
    """-> (low, high): the rotary PAIRS between which YaRN blends.  Pair
    i turns `original theta^(-2i/dim) / 2 pi` times over the positions
    the model was first trained on; d(beta) is the pair that turns beta
    times.  Pairs below floor(d(beta_fast)) keep their frequency, pairs
    above ceil(d(beta_slow)) are interpolated."""
    def pair(turns):
        return rotary_dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), rotary_dim - 1)
    return low, high


def yarn_rotary_tables(positions, rotary_dim: int, theta: float, *,
                       factor: float, original: int, beta_fast: float = 32,
                       beta_slow: float = 1, mscale: float = 1.0,
                       mscale_all_dim: float = 0.0):
    """-> (cos, sin), each [T, rotary_dim], float32, with YaRN's
    frequencies (arXiv:2309.00071, as `deepseek_v2` computes them):
    pair i of `rotary_dim / 2` has the extrapolated frequency
    `theta^(-2i/dim)` and the interpolated one, that over `factor`;
    `ramp_i = clip((i - low) / (high - low), 0, 1)` blends them,
    `inv_freq_i = interpolated ramp_i + extrapolated (1 - ramp_i)`.  Both
    tables are multiplied by m(factor, mscale) / m(factor, mscale_all_dim)
    (`yarn_mscale`); the softmax scale's own factor is the caller's."""
    extrapolated = _plain_inv_freq(rotary_dim, theta)
    low, high = yarn_correction_range(
        rotary_dim, theta, original, beta_fast, beta_slow
    )
    ramp = jnp.clip(
        (jnp.arange(rotary_dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 1e-3), 0.0, 1.0,
    )
    angles = _angles(
        positions, extrapolated / factor * ramp + extrapolated * (1.0 - ramp)
    )
    magnitude = yarn_mscale(factor, mscale) / yarn_mscale(
        factor, mscale_all_dim
    )
    return jnp.cos(angles) * magnitude, jnp.sin(angles) * magnitude


def apply_rotary(x, cos, sin):
    """x [B, T, H, D]; rotates the first `cos.shape[-1]` dimensions."""
    rotary_dim = cos.shape[-1]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    rotated = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    rot = (
        rot.astype(jnp.float32) * cos[None, :, None, :]
        + rotated.astype(jnp.float32) * sin[None, :, None, :]
    )
    return jnp.concatenate([rot.astype(x.dtype), rest], axis=-1)


def repeat_kv(x, n_rep: int):
    """[B, T, Hkv, D] -> [B, T, Hkv * n_rep, D]; query head h reads
    key-value head h // n_rep."""
    return x if n_rep == 1 else jnp.repeat(x, n_rep, axis=2)


NEG_INF = -1e30

# The names both engines' forward rules give the two results that are
# also residuals of their backward passes (`_gqa_fwd` here, `_flash_fwd`
# in `ops/flash_attention.py`).  A layer rematerialised under a policy
# that saves these names (`model_zoo/lm_common.KEEP_ATTENTION_RESULTS`)
# keeps them, and its recomputed forward holds no engine; anywhere else a
# name is the identity.
ATTN_OUT = "attn_out"
ATTN_LSE = "attn_lse"


def _block_size(t: int, block: int) -> int:
    """The largest power-of-two fraction of `block` that divides t, or t."""
    while block >= 64:
        if t % block == 0:
            return block
        block //= 2
    return t


def _first_block(i, block: int, window):
    """The first key block query block `i` reads: 0 under a causal mask
    alone (a literal, so that the loop traces as it did before bands),
    else the block that holds key `i block - window + 1`, the first of
    the `window - 1` keys before the block's first row."""
    if window is None:
        return 0
    return jnp.maximum(i - (window + block - 2) // block, 0)


class Causal(NamedTuple):
    """The causal mask, alone or as a band of `window` keys up to the
    query's own.  A mask RULE is what the engine's loops ask: `visits`,
    how many key tiles query tile `i` reads, as the bounds of its key
    loop; `tile`, which tile a step of that loop is; `masked`, a tile's
    own mask from its rows' and columns' positions.  Here the steps are
    the tiles themselves, from the band's first to the diagonal."""

    window: Any = None

    def visits(self, i, block):
        return _first_block(i, block, self.window), i + 1

    def tile(self, i, step, block):
        return step

    def masked(self, rows, cols):
        masked = cols[None, :] > rows[:, None]
        if self.window is not None:
            masked = masked | (cols[None, :] <= rows[:, None] - self.window)
        return masked


class BlockDiffusion(NamedTuple):
    """The block-diffusion mask (BD3-LM, arXiv:2503.09573) over 2 `tokens`
    positions, a NOISED copy of a sequence in front of its CLEAN copy, in
    blocks of `length` tokens: a noised query reads its own noised block,
    both directions, and the clean blocks strictly before it; a clean
    query reads the clean blocks up to and with its own.  With n tiles a
    half, query tile i of either half reads the clean tiles n .. n + i
    mod n, and a noised one then its OWN noised tile, last (where every
    row has a key, its own: `_scores`): n (n + 1) + n visits for the 2 n
    query tiles, where a plain mask over the 2 n tiles would make
    n (2 n + 1)."""

    tokens: int
    length: int

    def visits(self, i, block):
        n = self.tokens // block
        return 0, i % n + 1 + (i < n)

    def tile(self, i, step, block):
        n = self.tokens // block
        return jnp.where(step <= i % n, n + step, i)

    def masked(self, rows, cols):
        t, length = self.tokens, self.length
        noised_q, noised_k = rows[:, None] < t, cols[None, :] < t
        block_q = (rows % t // length)[:, None]
        block_k = (cols % t // length)[None, :]
        allowed = jnp.where(
            noised_q,
            jnp.where(noised_k, block_k == block_q, block_k < block_q),
            ~noised_k & (block_k <= block_q),
        )
        return ~allowed


def _scores(q_i, k_j, i, j, block, scale, rule):
    """[B,N,G,Bq,D] x [B,N,Bk,D] -> masked scores [B,N,G,Bq,Bk], float32,
    of query tile `i` against key tile `j` under `rule`.  A row with no
    key in tile j (a band's first block, a noised block's rows against
    its own clean block) reads NEG_INF throughout; the tile visited last
    holds the row's own key, and the running maximum then wipes what such
    a row gathered."""
    s = jnp.einsum(
        "bngqd,bnkd->bngqk", q_i, k_j, preferred_element_type=jnp.float32
    ) * scale
    rows = i * block + jnp.arange(block)
    cols = j * block + jnp.arange(block)
    return jnp.where(rule.masked(rows, cols), NEG_INF, s)


def _take(x, i, block, axis=-2):
    """Block `i` of the token axis (the one before the last; the last of
    a row statistic): read where it lies."""
    return jax.lax.dynamic_slice_in_dim(x, i * block, block, axis % x.ndim)


def _put(x, x_i, i, block, axis=-2, add=False):
    """`x` with block `i` of its token axis set to `x_i`, or raised by
    it."""
    if add:
        x_i = x_i + _take(x, i, block, axis)
    return jax.lax.dynamic_update_slice_in_dim(
        x, x_i, i * block, axis % x.ndim
    )


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def causal_gqa_attention(q, k, v, block: int, scale=None, rule=Causal()):
    """HEADS IN FRONT OF TOKENS: q [B, Hq, T, D]; k [B, Hkv, T, D];
    v [B, Hkv, T, Dv] -> [B, Hq, T, Dv] (`heads_first` packs and unpacks;
    `rotary_pack` makes q and k so); softmax of q k^T scale (1/sqrt(D)
    where none is given) under the mask `rule` says (`Causal`, alone or
    as a band; `BlockDiffusion`, where T counts both copies), float32
    accumulation.  A block of one head is a contiguous [block, D] matrix
    in every operand and result, forward and backward, and the loops
    take block `i` by a dynamic slice of the token axis: nothing is laid
    out again for them.  A query block visits only the key blocks its
    rule names (`visits`, `tile`), forward and backward."""
    return _gqa_fwd(q, k, v, block, scale, rule)[0]


def _gqa_fwd(q, k, v, block, scale, rule):
    b, hq, t, d = q.shape
    n, dv = k.shape[1], v.shape[3]
    g = hq // n
    scale = 1.0 / (d ** 0.5) if scale is None else scale
    grouped = q.reshape(b, n, g, t, d)

    def q_step(i, results):
        out, lse = results
        q_i = _take(grouped, i, block)

        def kv_step(step, carry):
            m, l, acc = carry
            j = rule.tile(i, step, block)
            s = _scores(q_i, _take(k, j, block), i, j, block, scale, rule)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            fix = jnp.exp(m - m_new)
            acc = acc * fix[..., None] + jnp.einsum(
                "bngqk,bnkd->bngqd", p.astype(v.dtype), _take(v, j, block),
                preferred_element_type=jnp.float32,
            )
            return m_new, l * fix + jnp.sum(p, axis=-1), acc

        m, l, acc = jax.lax.fori_loop(
            *rule.visits(i, block), kv_step,
            (
                jnp.full((b, n, g, block), NEG_INF, jnp.float32),
                jnp.zeros((b, n, g, block), jnp.float32),
                jnp.zeros((b, n, g, block, dv), jnp.float32),
            ),
        )
        return (
            _put(out, (acc / l[..., None]).astype(q.dtype), i, block),
            _put(lse, m + jnp.log(l), i, block, axis=-1),
        )

    out, lse = jax.lax.fori_loop(0, t // block, q_step, (
        jnp.zeros((b, n, g, t, dv), q.dtype),
        jnp.zeros((b, n, g, t), jnp.float32),
    ))
    out = checkpoint_name(out.reshape(b, hq, t, dv), ATTN_OUT)
    lse = checkpoint_name(lse, ATTN_LSE)
    return out, (q, k, v, out, lse)


def _gqa_bwd(block, scale, rule, residuals, dout):
    q, k, v, out, lse = residuals
    b, hq, t, d = q.shape
    n, dv = k.shape[1], v.shape[3]
    g = hq // n
    scale = 1.0 / (d ** 0.5) if scale is None else scale
    grouped = q.reshape(b, n, g, t, d)
    dout = dout.reshape(b, n, g, t, dv)
    delta = jnp.sum(
        dout.astype(jnp.float32)
        * out.reshape(dout.shape).astype(jnp.float32), axis=-1
    )                                                    # [B,N,G,T]

    def q_step(i, grads):
        dq, dk, dv = grads
        q_i, do_i = _take(grouped, i, block), _take(dout, i, block)
        delta_i, lse_i = _take(delta, i, block, -1), _take(lse, i, block, -1)

        def kv_step(step, inner):
            dq_i, dk, dv = inner
            j = rule.tile(i, step, block)
            k_j, v_j = _take(k, j, block), _take(v, j, block)
            s = _scores(q_i, k_j, i, j, block, scale, rule)
            p = jnp.exp(s - lse_i[..., None])
            dv_j = jnp.einsum(
                "bngqk,bngqd->bnkd", p.astype(q.dtype), do_i,
                preferred_element_type=jnp.float32,
            )
            dp = jnp.einsum(
                "bngqd,bnkd->bngqk", do_i, v_j,
                preferred_element_type=jnp.float32,
            )
            ds = (p * (dp - delta_i[..., None]) * scale).astype(q.dtype)
            dq_i = dq_i + jnp.einsum(
                "bngqk,bnkd->bngqd", ds, k_j,
                preferred_element_type=jnp.float32,
            )
            dk_j = jnp.einsum(
                "bngqk,bngqd->bnkd", ds, q_i,
                preferred_element_type=jnp.float32,
            )
            return (dq_i, _put(dk, dk_j, j, block, add=True),
                    _put(dv, dv_j, j, block, add=True))

        dq_i, dk, dv = jax.lax.fori_loop(
            *rule.visits(i, block), kv_step,
            (jnp.zeros(q_i.shape, jnp.float32), dk, dv),
        )
        return _put(dq, dq_i.astype(q.dtype), i, block), dk, dv

    dq, dk, dv = jax.lax.fori_loop(0, t // block, q_step, (
        jnp.zeros(grouped.shape, q.dtype),
        jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32),
    ))
    return dq.reshape(q.shape), dk.astype(k.dtype), dv.astype(v.dtype)


causal_gqa_attention.defvjp(_gqa_fwd, _gqa_bwd)


def _head_sizes(d: int, dv: int) -> str:
    return f"D={d}" if d == dv else f"Dqk={d} Dv={dv}"


def heads_first(x):
    """[B, T, H, D] <-> [B, H, T, D]."""
    return jnp.swapaxes(x, 1, 2)


def causal_attention(q, k, v, *, scale=None, window=None,
                     block_diffusion=None, impl: str = "auto",
                     block: int = 512, packed: bool = False):
    """Causal softmax attention, grouped-query heads; the scores are
    scaled by `scale`, 1/sqrt(Dqk) where none is given.  With `window` a
    query at t reads the keys `t - window < s <= t` (its own among them):
    the XLA engine's work, which skips the key blocks outside that band.
    With `block_diffusion=(T, B)` q, k and v hold 2 T positions, a noised
    copy of T tokens in front of their clean copy, and the mask is
    `BlockDiffusion`'s in blocks of B tokens (not causal: a block reads
    itself in both directions): the XLA engine's work too.
    q [B, T, Hq, Dqk]; k [B, T, Hkv, Dqk]; v [B, T, Hkv, Dv] (Dv may
    differ from Dqk) -> [B, T, Hq, Dv]."""
    # (`elasticdl_tpu.ops` exports the FUNCTION under the module's name)
    from elasticdl_tpu.ops.flash_attention import (
        _use_interpret, flash_attention, supports,
    )

    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"impl must be auto, pallas or xla, got {impl!r}")
    _, t, hq, d = q.shape
    if packed:
        t, hq = hq, t
    dv = v.shape[3]
    if window is not None and window >= t:
        window = None  # the band holds every key a causal mask leaves
    sizes = _head_sizes(d, dv)
    if window is not None:
        if window < 1:
            raise ValueError(f"window must be at least 1 key, got {window}")
        if impl == "pallas":
            raise ValueError(
                "the Pallas kernel has no band: a window needs impl auto "
                "or xla"
            )
        sizes += f" window={window}"
    if block_diffusion is not None:
        return _block_diffusion_attention(
            q, k, v, t, sizes, BlockDiffusion(*block_diffusion), scale,
            window, impl, block, packed,
        )
    use_pallas = impl == "pallas" or (
        impl == "auto" and window is None
        and jax.default_backend() == "tpu" and supports(t, d, d_v=dv)
    )
    if use_pallas:
        if packed:  # the kernel's own transposes meet these and go
            return heads_first(causal_attention(
                *map(heads_first, (q, k, v)), scale=scale, impl="pallas",
            ))
        n_rep = hq // k.shape[2]
        logger.info(
            "attention engine: pallas flash_attention T=%d %s "
            "(interpret=%s, repeat_kv x%d)", t, sizes, _use_interpret(),
            n_rep,
        )
        return flash_attention(
            q, repeat_kv(k, n_rep), repeat_kv(v, n_rep), causal=True,
            scale=scale,
        )
    if window is not None:
        # Blocks of half the window: a query block then reads three key
        # blocks, 1.5 windows of keys, where blocks of the window's size
        # read two, 2 windows.  Measured forward + backward on a v5e at
        # T 8192, heads of 128: window 512, 64 heads over 8, 1 sequence:
        # 8.0 ms in blocks of 256 against 10.4 in blocks of 512 (PR 36);
        # window 1024, 32 heads over 4, 2 sequences: 13.9 ms in blocks of
        # 512 against 49.9 in blocks of 1024, and 11.5 in blocks of 256, a
        # QUARTER of the window (1 sequence: 5.8, 21.0, 5.7; PR 42).  The
        # rule is left as it was: the quarter's gain is 1% of that step.
        block = min(block, max(window // 2, 128))
    block = _block_size(t, block)
    return _xla_engine(q, k, v, t, sizes, block, scale, Causal(window), packed)


def _xla_engine(q, k, v, t, sizes, block, scale, rule, packed):
    logger.info(
        "attention engine: xla causal_gqa_attention T=%d %s "
        "(blocks of %d; operands: %s)", t, sizes, block,
        "heads first, packed by the caller" if packed
        else "as given, tokens first",
    )
    with jax.named_scope("attn"):
        if packed:
            return causal_gqa_attention(q, k, v, block, scale, rule)
        return heads_first(causal_gqa_attention(
            *map(heads_first, (q, k, v)), block, scale, rule
        ))


def _block_diffusion_attention(q, k, v, positions, sizes, rule, scale,
                               window, impl, block, packed):
    """The front door's checks under the block-diffusion mask, then the
    XLA engine in tiles that hold whole blocks of ONE copy."""
    tokens, length = rule
    if window is not None:
        raise ValueError("a block-diffusion mask takes no window")
    if impl == "pallas":
        raise ValueError(
            "the Pallas kernel has no block-diffusion mask (its `causal` "
            "is a bool, and K and V of a head over both copies pass its "
            "cap at 8192 tokens): block_diffusion needs impl auto or xla"
        )
    if positions != 2 * tokens:
        raise ValueError(
            f"block_diffusion=({tokens}, {length}) is a noised and a clean "
            f"copy of {tokens} tokens, {2 * tokens} positions: q has "
            f"{positions}"
        )
    if tokens % length:
        raise ValueError(
            f"a copy holds whole blocks: the block length {length} does not "
            f"divide its {tokens} tokens"
        )
    block = _block_size(tokens, max(block, length))  # a block at the least
    if block % length:
        raise ValueError(
            f"a tile holds whole blocks: the block length {length} does "
            f"not divide the tile of {block} tokens (the largest power-of-"
            f"two fraction of the tile asked for that divides {tokens})"
        )
    return _xla_engine(
        q, k, v, positions,
        sizes + f" block_diffusion=({tokens}, {length})", block, scale, rule,
        packed,
    )
