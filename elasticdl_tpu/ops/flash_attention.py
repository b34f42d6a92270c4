"""Pallas TPU flash-attention kernel (forward + backward).

The hot op of the long-context path — single-chip (`flash_attention`)
AND per-ring-step inside the cross-chip ring (`flash_ring_step_carry` /
`flash_ring_step_bwd`, consumed by parallel/ring_attention's pallas
impl; measured 1.25x-3x over the ring's XLA block math as T_local grows
2048 -> 16384, BASELINE.md).  A hand-scheduled Pallas
kernel instead of the XLA-fused blockwise einsum
because attention's online-softmax recurrence is exactly the pattern XLA
can't restructure itself: the [T, T] score slab must never exist, scores
must stay resident in VMEM between the two matmuls, and the causal
upper-triangle must be SKIPPED (not computed-then-masked).  Standard
flash-attention scheme (grid over (batch, heads, q-blocks), K/V streamed
block-wise from VMEM, f32 running max/denominator carried in registers),
with the standard two-kernel backward (dq pass over q-blocks, dk/dv pass
over k-blocks, recomputing probabilities from the saved logsumexp).

`flash_attention` is a drop-in for `blockwise_attention`'s self-attention
case: [B, T, H, D] in, [B, T, H, D] out, differentiable via custom_vjp.
Its forward rule NAMES the two results that are also the backward pass's
residuals, the output and the log-sum-exp (`ops/gqa.py` `ATTN_OUT`,
`ATTN_LSE`), so that a rematerialised layer can keep them and run no
forward kernel again (`model_zoo/lm_common.KEEP_ATTENTION_RESULTS`).
Off-TPU (tests, CPU meshes) the kernels run in Pallas interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from elasticdl_tpu.ops.gqa import ATTN_LSE, ATTN_OUT

NEG_INF = -1e30
# Measured on the v5e (B4 T2048 H8 D128, causal): fwd 256->4.18ms,
# 512->3.88ms, 1024/512->4.01ms; XLA blockwise 5.88ms.  512 wins.
DEFAULT_BLOCK = 512


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pos(block: int, index, dim: int):
    """Global positions of a block's rows as a 2-D iota (TPU needs >=2D)."""
    return index * block + jax.lax.broadcasted_iota(
        jnp.int32, (block, 1) if dim == 0 else (1, block), dim
    )


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q, block_k):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # [block_q, D]
    t_k = k_ref.shape[2]
    n_k = t_k // block_k
    if causal:
        # K blocks strictly above the diagonal are never touched.
        n_k = jnp.minimum(n_k, ((qi + 1) * block_q + block_k - 1) // block_k)
    q_pos = _pos(block_q, qi, 0)  # [block_q, 1]

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        if causal:
            k_pos = _pos(block_k, j, 1)  # [1, block_k]
            s = jnp.where(k_pos > q_pos, NEG_INF, s)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc * correction + pv

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, v_ref.shape[3]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_k, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l_safe)  # [block_q, 1]


def _fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    b, h, t, d = q.shape
    dv = v.shape[3]  # the values' head size may differ from q's and k's
    grid = (b, h, t // block_q)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, t, d), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, t, dv), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ----------------------------------------------------------------------
# backward: dq pass (grid over q-blocks), dk/dv pass (grid over k-blocks)
# ----------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale, causal, block_q, block_k):
    """Grid (B, H, n_q, n_k), k innermost: each step adds one KV block's
    contribution to this q-block's gradient.  The f32 accumulator lives
    in VMEM scratch across the inner grid steps (TPU grids are
    sequential), and only the final [block_q, D] block is written out —
    no full-[T, D] buffer ever sits in VMEM, so T scales past the
    scoped-VMEM ceiling the fori-loop-over-full-KV formulation hit."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    # Fully-masked (q-block entirely before k-block): skip the matmuls.
    live = (qi + 1) * block_q > kj * block_k if causal else True

    @pl.when(live)
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32)  # [block_q, D]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]  # [block_q, 1]
        delta = delta_ref[0, 0]
        k_blk = k_ref[0, 0].astype(jnp.float32)  # [block_k, D]
        v_blk = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q * scale, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            mask = _pos(block_k, kj, 1) > _pos(block_q, qi, 0)
            s = jnp.where(mask, NEG_INF, s)
        p = jnp.exp(s - lse)  # [block_q, block_k]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        dq_acc[...] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == n_k - 1)
    def _emit():
        dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale, causal, block_q, block_k):
    """Grid (B, H, n_k, n_q), q innermost; mirror of _dq_kernel with the
    roles swapped — see its docstring for the accumulation scheme."""
    kj = pl.program_id(2)
    qi = pl.program_id(3)
    n_q = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = (qi + 1) * block_q > kj * block_k if causal else True

    @pl.when(live)
    def _accumulate():
        k_blk = k_ref[0, 0].astype(jnp.float32)  # [block_k, D]
        v_blk = v_ref[0, 0].astype(jnp.float32)
        q = q_ref[0, 0].astype(jnp.float32)  # [block_q, D]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, 0][None, :]  # [1, block_q]
        delta = delta_ref[0, 0][:, 0][None, :]
        # Transposed layout: s_t [block_k, block_q].
        s_t = jax.lax.dot_general(
            k_blk, q * scale, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            mask = _pos(block_k, kj, 0) > _pos(block_q, qi, 1)
            s_t = jnp.where(mask, NEG_INF, s_t)
        p_t = jnp.exp(s_t - lse)  # [block_k, block_q]
        dv_acc[...] += jax.lax.dot_general(
            p_t, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp_t = jax.lax.dot_general(
            v_blk, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds_t = p_t * (dp_t - delta)
        dk_acc[...] += jax.lax.dot_general(
            ds_t, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == n_q - 1)
    def _emit():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    b, h, t, d = q.shape
    dv = v.shape[3]
    do = g.astype(jnp.float32)
    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian diagonal term.
    delta = jnp.sum(
        do * out.astype(jnp.float32), axis=-1, keepdims=True
    )  # [B, H, T, 1]

    from jax.experimental.pallas import tpu as pltpu

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        grid=(b, h, t // block_q, t // block_k),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda b, h, i, j: (b, h, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, dv), lambda b, h, i, j: (b, h, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, dv), lambda b, h, i, j: (b, h, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        grid=(b, h, t // block_k, t // block_q),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d), lambda b, h, j, i: (b, h, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda b, h, j, i: (b, h, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, dv), lambda b, h, j, i: (b, h, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, dv), lambda b, h, j, i: (b, h, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, 1), lambda b, h, j, i: (b, h, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, 1), lambda b, h, j, i: (b, h, i, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_k, d), lambda b, h, j, i: (b, h, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, dv), lambda b, h, j, i: (b, h, j, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, t, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


# ----------------------------------------------------------------------
# ring-step kernels (parallel/ring_attention.py's per-step engine)
#
# Same math as the kernels above with two ring-specific twists:
# - causal masking uses EXPLICIT position arrays (q_pos [Tq], k_pos [Tk])
#   instead of block-index arithmetic — a rotating KV block's global
#   positions depend on its source shard, and the zigzag layout's are not
#   even affine;
# - the forward RETURNS (out_i, lse_i) unnormalized-combinable partials:
#   the ring recombines steps exactly via
#       lse = logaddexp(lse_c, lse_i)
#       acc = acc * exp(lse_c - lse) + out_i * exp(lse_i - lse)
#   so no m/l state ever crosses the kernel boundary (a fully-masked
#   step's lse_i = NEG_INF contributes exp(-inf) = 0 automatically).
# The backward reuses the flash identity P = exp(S - lse_final): each
# ring step's (dq contribution, dk/dv of the rotating block) needs only
# the FINAL lse + delta, so the step kernels stay stateless.
# ----------------------------------------------------------------------


def _fwd_ring_carry_kernel(q_ref, k_ref, v_ref, acc_ref, lsec_ref,
                           qpos_ref, kpos_ref, acc_out, lse_out, *,
                           scale, causal, block_k):
    """_fwd_ring_kernel with the lse-space COMBINE fused in: takes the
    running (acc, lse) carry as inputs (aliased to the outputs — no
    fresh HBM buffers) and emits the updated carry directly, saving the
    separate [B,H,T,D]-sized combine pass per ring step."""
    q = q_ref[0, 0].astype(jnp.float32) * scale  # [block_q, D]
    t_k = k_ref.shape[2]
    n_k = t_k // block_k
    block_q = q.shape[0]

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            q_pos = qpos_ref[0, 0]  # [block_q, 1]
            k_pos = kpos_ref[0, 0, :, pl.ds(j * block_k, block_k)]
            s = jnp.where(k_pos > q_pos, NEG_INF, s)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - safe_m)
        if causal:
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        correction = jnp.where(
            m <= NEG_INF / 2, 0.0, jnp.exp(m - safe_m)
        )
        l_new = l * correction + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc * correction + pv

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, q.shape[1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_k, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_i = acc / l_safe
    lse_i = jnp.where(
        l == 0.0, NEG_INF,
        jnp.where(m <= NEG_INF / 2, 0.0, m) + jnp.log(l_safe),
    )
    # Fused lse-space combine with the incoming carry.
    lse_c = lsec_ref[0, 0]  # [block_q, 1]
    lse_new = jnp.logaddexp(lse_c, lse_i)
    safe = jnp.where(lse_new <= NEG_INF / 2, 0.0, lse_new)
    alpha = jnp.exp(jnp.where(lse_c <= NEG_INF / 2, NEG_INF, lse_c) - safe)
    beta = jnp.exp(jnp.where(lse_i <= NEG_INF / 2, NEG_INF, lse_i) - safe)
    acc_out[0, 0] = acc_ref[0, 0] * alpha + o_i * beta
    lse_out[0, 0] = lse_new


def flash_ring_step_carry(q, k_blk, v_blk, acc, lse, q_pos, k_pos, *,
                          causal, scale, block_q=DEFAULT_BLOCK,
                          block_k=DEFAULT_BLOCK, interpret=None):
    """One ring step, combine fused: (acc [B,H,Tq,D] f32, lse [B,H,Tq,1]
    f32) in -> updated (acc, lse) out, buffers aliased in place."""
    b, h, tq, d = q.shape
    tk = k_blk.shape[2]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    if tq % block_q or tk % block_k:
        raise ValueError(
            f"ring-step kernel needs block-divisible shard lengths; got "
            f"Tq={tq} (block {block_q}), Tk={tk} (block {block_k})"
        )
    interpret = _use_interpret() if interpret is None else interpret
    qp = _match_vma(q_pos.astype(jnp.int32).reshape(1, 1, tq, 1), q)
    kp = _match_vma(k_pos.astype(jnp.int32).reshape(1, 1, 1, tk), q)
    acc_new, lse_new = pl.pallas_call(
        functools.partial(
            _fwd_ring_carry_kernel, scale=scale, causal=causal,
            block_k=block_k,
        ),
        grid=(b, h, tq // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, tk, d), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, tk, d), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (0, 0, i, 0)),
            pl.BlockSpec((1, 1, 1, tk), lambda b, h, i: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            _out_struct((b, h, tq, d), jnp.float32, q),
            _out_struct((b, h, tq, 1), jnp.float32, q),
        ],
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
    )(q, k_blk, v_blk, acc, lse, qp, kp)
    return acc_new, lse_new


def _vma_of(x):
    """`x`'s varying-mesh-axes type (empty outside shard_map)."""
    return getattr(jax.typeof(x), "vma", None)


def _out_struct(shape, dtype, like):
    """ShapeDtypeStruct that inherits `like`'s varying-mesh-axes type —
    required when these kernels run inside shard_map (the ring), where
    check_vma demands explicit output vma."""
    vma = _vma_of(like)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _match_vma(x, like):
    """Give `x` at least `like`'s varying-mesh-axes type (shard_map's
    check_vma requires all kernel operands to agree; position arrays are
    only `model`-varying while q varies over the data axis too)."""
    want = _vma_of(like)
    if not want:
        return x
    have = _vma_of(x) or frozenset()
    missing = tuple(set(want) - set(have))
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def _dq_ring_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    qpos_ref, kpos_ref, dq_ref, *,
                    scale, causal, block_k):
    """dq contribution of ONE ring step's KV block (grid over q-blocks,
    inner fori over this block's KV): P = exp(S - lse_final)."""
    q = q_ref[0, 0].astype(jnp.float32)  # [block_q, D]
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]  # [block_q, 1]
    delta = delta_ref[0, 0]
    t_k = k_ref.shape[2]
    n_k = t_k // block_k

    def body(j, acc):
        k_blk = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(
            jnp.float32
        )
        v_blk = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(
            jnp.float32
        )
        s = jax.lax.dot_general(
            q * scale, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            q_pos = qpos_ref[0, 0]  # [block_q, 1]
            k_pos = kpos_ref[0, 0, :, pl.ds(j * block_k, block_k)]  # [1, block_k]
            s = jnp.where(k_pos > q_pos, NEG_INF, s)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        return acc + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    acc0 = jnp.zeros((q.shape[0], q.shape[1]), jnp.float32)
    dq_ref[0, 0] = (
        jax.lax.fori_loop(0, n_k, body, acc0) * scale
    ).astype(dq_ref.dtype)


def _dkv_ring_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     qpos_ref, kpos_ref, dk_ref, dv_ref,
                     *, scale, causal, block_q):
    """dk/dv of ONE ring step's KV block vs the local q shard (grid over
    k-blocks, inner fori over q-blocks)."""
    k_blk = k_ref[0, 0].astype(jnp.float32)  # [block_kk, D]
    v_blk = v_ref[0, 0].astype(jnp.float32)
    t_q = q_ref.shape[2]
    n_q = t_q // block_q

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, 0, pl.ds(i * block_q, block_q), :].astype(
            jnp.float32
        )
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q), 0][None, :]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q), 0][None, :]
        s_t = jax.lax.dot_general(
            k_blk, q * scale, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_kk, block_q]
        if causal:
            q_pos = qpos_ref[0, 0, :, pl.ds(i * block_q, block_q)]  # [1, block_q]
            k_pos = kpos_ref[0, 0]  # [block_kk, 1]
            s_t = jnp.where(k_pos > q_pos, NEG_INF, s_t)
        p_t = jnp.exp(s_t - lse)
        dv = dv + jax.lax.dot_general(
            p_t, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp_t = jax.lax.dot_general(
            v_blk, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds_t = p_t * (dp_t - delta)
        dk = dk + jax.lax.dot_general(
            ds_t, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    z = jnp.zeros((k_blk.shape[0], k_blk.shape[1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, n_q, body, (z, z))
    dk_ref[0, 0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def flash_ring_step_bwd(q, k_blk, v_blk, do, lse, delta, q_pos, k_pos, *,
                        causal, scale, block_q=DEFAULT_BLOCK,
                        block_k=DEFAULT_BLOCK, interpret=None):
    """One ring step's backward: (dq contribution [B,H,Tq,D] f32,
    dk [B,H,Tk,D] f32, dv [B,H,Tk,D] f32).  `lse`/`delta` are the FINAL
    ring-combined stats [B,H,Tq,1]."""
    b, h, tq, d = q.shape
    tk = k_blk.shape[2]
    block_q_ = min(block_q, tq)
    block_k_ = min(block_k, tk)
    if tq % block_q_ or tk % block_k_:
        raise ValueError(
            f"ring-step backward needs block-divisible shard lengths; got "
            f"Tq={tq} (block {block_q_}), Tk={tk} (block {block_k_})"
        )
    interpret = _use_interpret() if interpret is None else interpret
    qp = _match_vma(q_pos.astype(jnp.int32).reshape(1, 1, tq, 1), q)
    kp_lanes = _match_vma(k_pos.astype(jnp.int32).reshape(1, 1, 1, tk), q)
    qp_lanes = _match_vma(q_pos.astype(jnp.int32).reshape(1, 1, 1, tq), q)
    kp = _match_vma(k_pos.astype(jnp.int32).reshape(1, 1, tk, 1), q)

    from jax.experimental.pallas import tpu as pltpu

    dq = pl.pallas_call(
        functools.partial(
            _dq_ring_kernel, scale=scale, causal=causal, block_k=block_k_
        ),
        grid=(b, h, tq // block_q_),
        in_specs=[
            pl.BlockSpec((1, 1, block_q_, d), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, tk, d), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, tk, d), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_q_, d), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q_, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q_, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q_, 1), lambda b, h, i: (0, 0, i, 0)),
            pl.BlockSpec((1, 1, 1, tk), lambda b, h, i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q_, d), lambda b, h, i: (b, h, i, 0)
        ),
        out_shape=_out_struct((b, h, tq, d), jnp.float32, q),
        interpret=interpret,
    )(q, k_blk, v_blk, do, lse, delta, qp, kp_lanes)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_ring_kernel, scale=scale, causal=causal, block_q=block_q_
        ),
        grid=(b, h, tk // block_k_),
        in_specs=[
            pl.BlockSpec((1, 1, tq, d), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k_, d), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k_, d), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, tq, d), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, tq, 1), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, tq, 1), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, tq), lambda b, h, j: (0, 0, 0, 0)),
            pl.BlockSpec((1, 1, block_k_, 1), lambda b, h, j: (0, 0, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_k_, d), lambda b, h, j: (b, h, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k_, d), lambda b, h, j: (b, h, j, 0)
            ),
        ],
        out_shape=[
            _out_struct((b, h, tk, d), jnp.float32, k_blk),
            _out_struct((b, h, tk, d), jnp.float32, k_blk),
        ],
        interpret=interpret,
    )(q, k_blk, v_blk, do, lse, delta, qp_lanes, kp)
    return dq, dk, dv


# ----------------------------------------------------------------------
# public entry
# ----------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    out = checkpoint_name(out, ATTN_OUT)
    lse = checkpoint_name(lse, ATTN_LSE)
    return out, (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


# The forward kernel keeps each (batch, head)'s FULL [T, D] K and V
# resident in VMEM (the backward kernels stream block-wise).  Cap the K+V
# footprint auto-mode will accept: half the scoped-VMEM budget leaves
# room for the Q/output blocks and the f32 accumulators (at the default
# ~16 MiB budget that is 8 MiB: T=16384 x D=64 sits exactly at the cap
# and is measured to work).  Explicit flash_attention() calls are not
# bounded — only supports(), which attention_impl='auto' consults before
# preferring the kernel over blockwise_attention.
_DEFAULT_SCOPED_VMEM_KIB = 16 * 1024


def _configured_scoped_vmem_kib() -> int:
    """The scoped-VMEM budget the operator actually configured: parse
    --xla_tpu_scoped_vmem_limit_kib out of LIBTPU_INIT_ARGS (round 4 —
    previously auto mode capped at the FLAG-FREE bound even when the
    operator had raised the limit, so the kernel silently fell back at
    exactly the long-T shapes the flag exists for)."""
    import os
    import re

    match = re.search(
        r"--xla_tpu_scoped_vmem_limit_kib=(\d+)",
        os.environ.get("LIBTPU_INIT_ARGS", ""),
    )
    return int(match.group(1)) if match else _DEFAULT_SCOPED_VMEM_KIB


def _kv_vmem_bytes_max() -> int:
    return _configured_scoped_vmem_kib() * 1024 // 2


def shape_aligned(t: int, d: int, block: int = DEFAULT_BLOCK,
                  d_v: Optional[int] = None) -> bool:
    """The pure shape-capability half of `supports()` (block/sublane
    alignment), independent of the VMEM budget.  `d` is the head size of
    q and k, `d_v` that of v (d where not given)."""
    block = min(block, t)
    d_v = d if d_v is None else d_v
    return t % block == 0 and t % 8 == 0 and d % 8 == 0 and d_v % 8 == 0


def supports(t: int, d: int, block: int = DEFAULT_BLOCK,
             d_v: Optional[int] = None) -> bool:
    """Whether the kernel handles this (seq_len, head sizes) shape within
    the CONFIGURED scoped-VMEM budget (LIBTPU_INIT_ARGS-aware)."""
    return shape_aligned(t, d, block, d_v) and not kv_vmem_exceeded(
        t, d, d_v
    )


def kv_vmem_bytes(t: int, d: int, d_v: Optional[int] = None) -> int:
    """K [T, d] and V [T, d_v] of one head, float32: what the forward
    kernel keeps resident."""
    return t * (d + (d if d_v is None else d_v)) * 4


def kv_vmem_exceeded(t: int, d: int, d_v: Optional[int] = None) -> bool:
    """True when the KV block exceeds the configured scoped-VMEM budget —
    the operator can raise it with
    LIBTPU_INIT_ARGS=--xla_tpu_scoped_vmem_limit_kib (65536 is the
    measured-working value at T=16384; BASELINE.md ring table), and auto
    mode then accepts the shape without forcing attn_impl.  Auto-mode
    callers warn when this is the SOLE blocker (check `shape_aligned`
    too — advising the flag on a misaligned shape would point at a
    kernel that still cannot run)."""
    return kv_vmem_bytes(t, d, d_v) > _kv_vmem_bytes_max()


# The measured-working scoped-VMEM limit for the long-T kernel shapes
# (T=16384 D=64 and up; BASELINE.md ring table).
VMEM_FLAG_ADVICE = "LIBTPU_INIT_ARGS=--xla_tpu_scoped_vmem_limit_kib=65536"


def warn_if_vmem_is_sole_blocker(logger_name: str, t: int, d: int,
                                 d_v: Optional[int] = None) -> bool:
    """Auto-mode honesty contract: when the Pallas kernel is rejected
    ONLY by the VMEM budget (shape alignment fine), log the flag that
    unlocks it — a silent fallback at long T leaves up to ~3x on the
    table exactly where the kernel matters most.  Returns whether the
    warning fired (trace-time, so once per compile)."""
    if not (shape_aligned(t, d, d_v=d_v) and kv_vmem_exceeded(t, d, d_v)):
        return False
    from elasticdl_tpu.common.log_utils import get_logger

    get_logger(logger_name).warning(
        "attn impl=auto fell back to the XLA block engine at T=%d Dqk=%d "
        "Dv=%d: the KV block (%.1f MiB f32) exceeds the flag-free "
        "scoped-VMEM budget. Set %s and force attn_impl=pallas to unlock "
        "the Pallas kernel (up to ~3x at long T; BASELINE.md "
        "ring-attention table).",
        t, d, d if d_v is None else d_v,
        kv_vmem_bytes(t, d, d_v) / 2**20, VMEM_FLAG_ADVICE,
    )
    return True


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
    interpret: Optional[bool] = None,
):
    """Self-attention q, k [B, T, H, D], v [B, T, H, Dv] -> [B, T, H, Dv],
    Pallas kernels; scores scaled by `scale` (1/sqrt(D) where not given).

    T must be a multiple of block_q/block_k (`supports()` checks); use
    parallel.ring_attention.blockwise_attention for irregular shapes.
    """
    b, t, h, d = q.shape
    # Short sequences: shrink blocks to the sequence (T itself is a valid
    # single block when sublane-aligned).
    block_q, block_k = min(block_q, t), min(block_k, t)
    if t % block_q or t % block_k:
        raise ValueError(
            f"seq len {t} must be a multiple of block sizes "
            f"({block_q}, {block_k})"
        )
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    interpret = _use_interpret() if interpret is None else interpret
    # Kernels run in [B, H, T, D].  `attn` is the device scope of
    # attention (obs/tracing.py DEVICE_SCOPES): the transposes and both
    # passes of the kernel carry it whoever calls.
    with jax.named_scope("attn"):
        qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        out = _flash(qt, kt, vt, scale, causal, block_q, block_k, interpret)
        return out.transpose(0, 2, 1, 3)
