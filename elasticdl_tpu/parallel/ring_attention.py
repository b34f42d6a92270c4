"""Ring attention: sequence/context parallelism over a mesh axis.

Net-new TPU scope beyond the reference (SURVEY.md §5 records the
reference has no long-context machinery; the rebuild treats long-context
as first-class).  Design follows the public ring-attention recipe
(Liu et al., blockwise parallel transformers): shard the sequence over a
mesh axis, keep Q local, rotate K/V blocks around the ring with
`lax.ppermute`, and accumulate attention with the flash-attention online
softmax (running max + running denominator) so the full [T, T] score
matrix never materializes — memory is O(T_local^2) per device and the
KV transfer rides ICI overlapped with each block's compute.

Public surface:

- `blockwise_attention(q, k, v, causal=)` — single-device reference
  numerics (also the per-block kernel), f32 accumulation.
- `ring_attention(q, k, v, axis_name=, causal=, q_offset/k_offset)` —
  the SPMD collective form; call inside `shard_map` with the sequence
  dim sharded over `axis_name`.
- `ring_self_attention(mesh, q, k, v, axis=, causal=)` — host-level
  wrapper: shard_maps over the mesh's `model` axis (the context axis in
  this framework's 2-D mesh; see parallel/mesh.py).

Shapes follow the JAX convention [batch, seq, heads, head_dim].
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticdl_tpu.parallel import compile as pc
from elasticdl_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

NEG_INF = -1e30


def _attn_block(q, k, v, scale, q_pos, k_pos, causal, m, l, acc):
    """One (q-block, kv-block) flash update.  q:[B,Tq,H,D] k,v:[B,Tk,H,D];
    m,l:[B,H,Tq]; acc:[B,Tq,H,D].  f32 throughout (inputs may be bf16)."""
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk",
        q.astype(jnp.float32),
        k.astype(jnp.float32),
    ) * scale
    if causal:
        mask = k_pos[None, None, None, :] > q_pos[None, None, :, None]
        scores = jnp.where(mask, NEG_INF, scores)
    block_max = jnp.max(scores, axis=-1)  # [B,H,Tq]
    m_new = jnp.maximum(m, block_max)
    # exp of a fully-masked row's NEG_INF max would overflow: clamp.
    safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(scores - safe_m[..., None])  # [B,H,Tq,Tk]
    if causal:
        p = jnp.where(mask, 0.0, p)
    correction = jnp.exp(jnp.where(m <= NEG_INF / 2, NEG_INF, m) - safe_m)
    l_new = l * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    acc_new = acc * correction.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, acc_new


def _finalize(m, l, acc, dtype):
    # Rows that attended to nothing (can't happen for causal self-attn
    # with q_pos >= 0, but keep the division safe) return zeros.
    denom = jnp.where(l == 0.0, 1.0, l)
    out = acc / denom.transpose(0, 2, 1)[..., None]
    return out.astype(dtype)


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    q_offset: int = 0,
    k_offset: int = 0,
    scale: Optional[float] = None,
    kv_chunk: int = 1024,
):
    """Single-device attention with flash numerics — the reference
    semantics ring_attention must match, and the per-ring-step kernel.

    K/V are processed in `kv_chunk`-sized blocks (when the chunk divides
    the KV length) so the materialized score slab is [B, H, Tq, kv_chunk]
    rather than the full [Tq, Tk] — the flash-attention memory shape.
    `q_offset`/`k_offset` give the global position of the first local row
    (needed for causal masking when the sequence is sharded)."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    q_pos = q_offset + jnp.arange(tq)
    m = jnp.full((b, h, tq), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, tq), jnp.float32)
    acc = jnp.zeros((b, tq, h, d), jnp.float32)
    if kv_chunk and tk > kv_chunk and tk % kv_chunk == 0:
        n_chunks = tk // kv_chunk
        k_blocks = k.reshape(b, n_chunks, kv_chunk, h, d).transpose(
            1, 0, 2, 3, 4
        )
        v_blocks = v.reshape(b, n_chunks, kv_chunk, h, d).transpose(
            1, 0, 2, 3, 4
        )

        def body(carry, xs):
            m, l, acc = carry
            k_blk, v_blk, chunk = xs
            k_pos = k_offset + chunk * kv_chunk + jnp.arange(kv_chunk)
            return (
                _attn_block(
                    q, k_blk, v_blk, scale, q_pos, k_pos, causal, m, l, acc
                ),
                None,
            )

        (m, l, acc), _ = jax.lax.scan(
            body, (m, l, acc), (k_blocks, v_blocks, jnp.arange(n_chunks))
        )
    else:
        k_pos = k_offset + jnp.arange(tk)
        m, l, acc = _attn_block(
            q, k, v, scale, q_pos, k_pos, causal, m, l, acc
        )
    return _finalize(m, l, acc, q.dtype)


def zigzag_order(t: int, n_shards: int):
    """Global-position permutation for `layout="zigzag"`: applying it to
    the sequence dim and then sharding contiguously gives shard i the
    position chunks (i, 2N-1-i) — every shard then carries one early and
    one late chunk, so causal ring work is BALANCED across shards
    instead of piling onto the last one.  `t % (2 * n_shards) == 0`.
    Invert with `inverse_order`."""
    import numpy as np

    if t % (2 * n_shards):
        raise ValueError(f"t={t} must divide into 2*{n_shards} chunks")
    h = t // (2 * n_shards)
    idx = []
    for i in range(n_shards):
        idx.extend(range(i * h, (i + 1) * h))
        j = 2 * n_shards - 1 - i
        idx.extend(range(j * h, (j + 1) * h))
    return np.asarray(idx)


def inverse_order(order):
    import numpy as np

    inv = np.empty_like(order)
    inv[order] = np.arange(len(order))
    return inv


def zigzag_orders(t: int, n_shards: int):
    """(order, inverse) pair for `layout="zigzag"` — the one helper both
    ring_self_attention and mesh-aware models use, so the permute-around-
    attend contract lives in one place."""
    order = zigzag_order(t, n_shards)
    return order, inverse_order(order)


def _shard_positions(index, t_local, axis_size, layout):
    """Global positions of shard `index`'s local rows under `layout`."""
    if layout == "contiguous":
        return index * t_local + jnp.arange(t_local)
    half = t_local // 2
    late = 2 * axis_size - 1 - index
    return jnp.concatenate(
        [
            index * half + jnp.arange(half),
            late * half + jnp.arange(half),
        ]
    )


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    layout: str = "contiguous",
):
    """Collective attention over sequence shards; call under shard_map.

    Local shapes [B, T_local, H, D]; the global sequence is the
    concatenation over `axis_name` in axis-index order.  Each of the
    `axis_size` ring steps attends Q against one rotating KV block, then
    ppermutes KV to the next device — the transfer and the next block's
    compute overlap under XLA's scheduler.

    `layout` declares how global positions map to shards:

    - "contiguous": shard i holds positions [i*T_local, (i+1)*T_local).
      Causal fully-masked blocks are lax.cond-skipped — reclaiming FLOPs
      but NOT wall-clock (the ring is lockstep; the last shard attends
      at every step, so the critical path still runs N full blocks).
    - "zigzag": shard i holds chunks (i, 2N-1-i) of 2N chunks (pre-
      permute the global sequence with `zigzag_order`).  Every shard
      does the same ~half-masked work at every causal step, cutting the
      causal critical path toward N/2 block-attends — the standard
      balanced causal ring.
    """
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown layout {layout!r}")
    axis_size = jax.lax.psum(1, axis_name)
    my_index = jax.lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    q_pos = _shard_positions(my_index, tq, axis_size, layout)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def body(carry, step):
        m, l, acc, k_blk, v_blk = carry
        # KV block currently held arrived from `my_index - step`.
        src = (my_index - step) % axis_size
        k_pos = _shard_positions(src, tk, axis_size, layout)

        def attend(operands):
            m, l, acc = operands
            return _attn_block(
                q, k_blk, v_blk, scale, q_pos, k_pos, causal, m, l, acc
            )

        if causal and layout == "contiguous":
            # A KV block from a strictly-later shard (src > my_index) is
            # fully masked — skip its matmuls (FLOPs, not wall-clock;
            # see the layout note above — "zigzag" is the wall-clock fix).
            m, l, acc = jax.lax.cond(
                src > my_index, lambda ops: ops, attend, (m, l, acc)
            )
        else:
            # Zigzag blocks are never fully masked (every shard holds an
            # early chunk): always attend — that uniformity IS the
            # balance.
            m, l, acc = attend((m, l, acc))
        # Rotate for the next step (skipped result on the last step).
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (m, l, acc, k_blk, v_blk), None

    # Derive the initial accumulators FROM q (zeros_like) rather than
    # fresh jnp.zeros: under shard_map's typed-varying-axes model the
    # scan carry must vary over the same mesh axes as the body output,
    # and zeros born of q inherit q's varying type.
    acc = jnp.zeros_like(q, jnp.float32)  # [B,Tq,H,D]
    l = acc[..., 0].transpose(0, 2, 1)  # [B,H,Tq] zeros
    m = NEG_INF + l
    (m, l, acc, _, _), _ = jax.lax.scan(
        body, (m, l, acc, k, v), jnp.arange(axis_size)
    )
    return _finalize(m, l, acc, q.dtype)


def _to_kernel(x):  # [B, T, H, D] -> [B, H, T, D]
    return x.transpose(0, 2, 1, 3)


def _ring_axis_geometry(cfg, tq, tk):
    """(axis_size, my_index, q_pos, perm) — recomputed inside EVERY side
    of the custom VJP below: closing over these (they are tracers under
    shard_map) leaks tracers across the custom_vjp boundary when the
    ring runs under jit+scan."""
    axis_name, causal, scale, layout, interpret = cfg
    axis_size = jax.lax.psum(1, axis_name)
    my_index = jax.lax.axis_index(axis_name)
    q_pos = _shard_positions(my_index, tq, axis_size, layout)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    return axis_size, my_index, q_pos, perm


def _ring_pallas_forward(cfg, q, k, v):
    """Forward ring: each step runs the flash kernel on the rotating KV
    block with the lse-space combine FUSED into the kernel epilogue
    (flash_ring_step_carry — the (acc, lse) carry buffers alias in
    place, so no per-step [B,H,T,D] combine pass ever touches HBM; a
    fully-masked step's lse_i = NEG_INF contributes exp(-inf) = 0)."""
    from elasticdl_tpu.ops.flash_attention import flash_ring_step_carry

    axis_name, causal, scale, layout, interpret = cfg
    tq, tk = q.shape[1], k.shape[1]
    axis_size, my_index, q_pos, perm = _ring_axis_geometry(cfg, tq, tk)
    qk = _to_kernel(q)
    acc0 = jnp.zeros_like(qk, jnp.float32)
    lse0 = jnp.full(qk.shape[:3] + (1,), NEG_INF, jnp.float32) + (
        0.0 * qk[..., :1].astype(jnp.float32)
    )  # inherit q's varying mesh axes (shard_map typed-axes rule)

    def body(carry, step):
        acc, lse_c, k_blk, v_blk = carry
        src = (my_index - step) % axis_size
        k_pos = _shard_positions(src, tk, axis_size, layout)
        acc, lse_c = flash_ring_step_carry(
            qk, k_blk, v_blk, acc, lse_c,
            q_pos, k_pos, causal=causal, scale=scale, interpret=interpret,
        )
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (acc, lse_c, k_blk, v_blk), None

    # KV rotate in KERNEL layout [B,H,T,D]: one transpose before the
    # ring instead of two per step (measured ~10% of the per-step device
    # time at T_local=2048; ppermute cost is layout-independent).
    (acc, lse, _, _), _ = jax.lax.scan(
        body, (acc0, lse0, _to_kernel(k), _to_kernel(v)),
        jnp.arange(axis_size),
    )
    out = _to_kernel(acc).astype(q.dtype)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring_pallas(cfg, q, k, v):
    """Pallas-engined ring attention core (per-shard; under shard_map).
    `cfg` = (axis_name, causal, scale, layout, interpret), all static.
    The round-2 'fuse the kernel into the ring' gap (VERDICT #3)."""
    return _ring_pallas_forward(cfg, q, k, v)[0]


def _ring_pallas_fwd(cfg, q, k, v):
    out, lse = _ring_pallas_forward(cfg, q, k, v)
    return out, (q, k, v, out, lse)


def _ring_pallas_bwd(cfg, res, g):
    """Ring-aware backward: re-rotate KV (and the dk/dv accumulators
    with them) for axis_size steps; every step reuses the flash backward
    identity P = exp(S - lse_final) via stateless step kernels, so after
    the full rotation each KV block's gradient arrives home."""
    from elasticdl_tpu.ops.flash_attention import flash_ring_step_bwd

    axis_name, causal, scale, layout, interpret = cfg
    q, k, v, out, lse = res
    tq, tk = q.shape[1], k.shape[1]
    axis_size, my_index, q_pos, perm = _ring_axis_geometry(cfg, tq, tk)
    qk = _to_kernel(q)
    do = _to_kernel(g).astype(jnp.float32)
    outk = _to_kernel(out).astype(jnp.float32)
    delta = jnp.sum(do * outk, axis=-1, keepdims=True)  # [B,H,Tq,1]
    kk, vk = _to_kernel(k), _to_kernel(v)
    dq0 = jnp.zeros_like(qk, jnp.float32)
    dk0 = jnp.zeros_like(kk, jnp.float32)
    dv0 = jnp.zeros_like(dk0)

    def body(carry, step):
        dq_acc, k_blk, v_blk, dk_blk, dv_blk = carry
        src = (my_index - step) % axis_size
        k_pos = _shard_positions(src, tk, axis_size, layout)
        dq_i, dk_i, dv_i = flash_ring_step_bwd(
            qk, k_blk, v_blk, do, lse, delta,
            q_pos, k_pos, causal=causal, scale=scale,
            interpret=interpret,
        )
        dq_acc = dq_acc + dq_i
        dk_blk = dk_blk + dk_i
        dv_blk = dv_blk + dv_i
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        dk_blk = jax.lax.ppermute(dk_blk, axis_name, perm)
        dv_blk = jax.lax.ppermute(dv_blk, axis_name, perm)
        return (dq_acc, k_blk, v_blk, dk_blk, dv_blk), None

    # KV (and their gradient accumulators, which ride the same rotation)
    # in KERNEL layout across the ring — transposes once outside the
    # scan, not per step (same trade as the forward).
    (dq_acc, _, _, dk_acc, dv_acc), _ = jax.lax.scan(
        body, (dq0, kk, vk, dk0, dv0), jnp.arange(axis_size)
    )
    return (
        _to_kernel(dq_acc).astype(q.dtype),
        _to_kernel(dk_acc).astype(k.dtype),
        _to_kernel(dv_acc).astype(v.dtype),
    )


_ring_pallas.defvjp(_ring_pallas_fwd, _ring_pallas_bwd)


def ring_attention_pallas(
    q, k, v, *, axis_name, causal=False, scale=None,
    layout="contiguous", interpret=None,
):
    """Ring attention with the Pallas flash kernel as the per-step block
    engine.  Same contract as `ring_attention` (call under shard_map,
    local [B, T_local, H, D] shards); `interpret=None` auto-selects
    interpret mode off-TPU."""
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown layout {layout!r}")
    from elasticdl_tpu.ops.flash_attention import _use_interpret

    interpret = _use_interpret() if interpret is None else interpret
    scale_ = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    return _ring_pallas(
        (axis_name, causal, scale_, layout, interpret), q, k, v
    )


def _ring_dispatch(q, k, v, *, axis_name, causal, scale=None,
                   layout="contiguous", impl="auto"):
    """Per-shard impl selection (shapes are static at trace time):
    'pallas' = flash kernels per ring step (2.4x the XLA block engine on
    the chip, BASELINE.md), 'xla' = the blockwise einsum engine, 'auto' =
    pallas whenever the kernel supports the local shard shape."""
    if impl == "auto":
        from elasticdl_tpu.ops.flash_attention import (
            supports,
            warn_if_vmem_is_sole_blocker,
        )

        t, d = q.shape[1], q.shape[3]
        tk = k.shape[1]
        ok = supports(t, d) and supports(tk, d)
        impl = "pallas" if ok else "xla"
        if not ok:
            from elasticdl_tpu.ops.flash_attention import shape_aligned

            # BOTH operand shapes must be kernel-alignable before the
            # flag advice is honest — a misaligned q shard would still
            # block attn_impl=pallas after the operator sets the flag.
            if shape_aligned(t, d) and shape_aligned(tk, d):
                warn_if_vmem_is_sole_blocker(
                    "parallel.ring_attention", max(t, tk), d
                )
    if impl == "pallas":
        return ring_attention_pallas(
            q, k, v, axis_name=axis_name, causal=causal, scale=scale,
            layout=layout,
        )
    if impl != "xla":
        raise ValueError(f"impl must be auto|pallas|xla, got {impl!r}")
    return ring_attention(
        q, k, v, axis_name=axis_name, causal=causal, scale=scale,
        layout=layout,
    )


def make_ring_attention(mesh, *, axis: str = MODEL_AXIS,
                        causal: bool = False, layout: str = "contiguous",
                        impl: str = "auto"):
    """Build the shard_mapped ring-attention callable for `mesh`: batch
    sharded over `data`, sequence over `axis`.  The ONE place the
    sharding specs live — both ring_self_attention and mesh-aware models
    (model_zoo/transformer) call this.  With `layout="zigzag"` the
    caller is responsible for feeding sequences permuted by
    `zigzag_order` (and un-permuting outputs with `inverse_order`).
    `impl` selects the per-step block engine (see _ring_dispatch)."""
    spec = P(DATA_AXIS, axis, None, None)
    fn = partial(
        _ring_dispatch, axis_name=axis, causal=causal, layout=layout,
        impl=impl,
    )
    # check_vma stays ON wherever the kernels are compiled (the flash
    # kernels carry their varying-axes types: tests/test_tpu_compile.py
    # compiles this ring for a 2x2 v5e mesh with the check on) and for
    # the pure-XLA engine.  It is off only where the Pallas engine runs
    # in INTERPRET mode (CPU tests/dryruns), which trips a jax limitation
    # inside the kernel interpreter ("Primitive dynamic_slice requires
    # varying manual axes to match ... as a temporary workaround pass
    # check_vma=False"); collective placement there is pinned by the
    # parity+HLO-structure tests instead.
    from elasticdl_tpu.ops.flash_attention import _use_interpret

    interpreted = impl != "xla" and _use_interpret()
    return pc.shard_map_call(
        fn, mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False if interpreted else None,
    )


def ring_self_attention(
    mesh,
    q: jax.Array,
    k: jax.Array = None,
    v: jax.Array = None,
    *,
    axis: str = MODEL_AXIS,
    causal: bool = False,
    layout: str = "contiguous",
    impl: str = "auto",
):
    """Host-level entry: global [B, T, H, D] arrays in, attention out,
    computed ring-wise with batch sharded over `data` and sequence over
    `axis`.  (Inside a jitted step prefer calling `make_ring_attention`'s
    result from your own code so it fuses with the rest of the program.)

    `layout="zigzag"` handles the permutation here: inputs/outputs stay
    in natural sequence order, the balanced layout is internal."""
    k = q if k is None else k
    v = q if v is None else v
    fn = make_ring_attention(
        mesh, axis=axis, causal=causal, layout=layout, impl=impl
    )
    sharding = NamedSharding(mesh, P(DATA_AXIS, axis, None, None))
    if layout == "zigzag":
        if k.shape[1] != q.shape[1] or v.shape[1] != q.shape[1]:
            raise ValueError(
                "layout='zigzag' requires equal q/k/v sequence lengths "
                f"(got q={q.shape[1]}, k={k.shape[1]}, v={v.shape[1]}); "
                "the balanced layout is a self-attention arrangement"
            )
        order, inv = zigzag_orders(q.shape[1], mesh.shape[axis])
        q, k, v = (x[:, order] for x in (q, k, v))
        q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
        return fn(q, k, v)[:, inv]
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    return fn(q, k, v)
