"""Packed (lane-tiled) storage for narrow embedding tables.

Why this exists — the TPU memory-layout problem for embedding tables:
XLA tiles 2-D f32 arrays as T(8,128) (8 sublanes x 128 lanes).  A logical
[vocab, dim] table with small dim (CTR models use 1..32) is hostile to
that tiling either way:

- row-major {1,0}: the minor (lane) dimension `dim` pads to 128 ->
  128/dim x HBM blow-up (16x for dim=8).  XLA refuses.
- column-major {0,1} (what XLA picks): one embedding row's `dim` floats
  sit `vocab` elements apart, so every row gather/scatter touches `dim`
  far-apart tiles.  Measured on the DeepFM step (SURVEY §2.5 config 4):
  the three [2.6M, 8] scatter-adds of the sparse-Adam update ran ~6.3 ms
  EACH — 19 ms of a 30 ms step.

The fix is to make the physical shape lane-shaped: store the table as
[vocab/R, 128] where R = 128/dim_padded rows pack into one 128-lane
storage row.  Then:

- lookup  = gather of full 512-byte storage rows (fast path) + a tiny
  one-hot einsum to select the packed slot (MXU work, no per-element
  gather — `take_along_axis` on lanes lowers to a serialized gather and
  measured 250 ms for a batch; the einsum is ~0).
- scatter = tile the update to 128 lanes, mask to the right slot, and
  scatter-add full storage rows.
- optimizer slot updates stream over the whole (sharded) table with a
  touched-row mask instead of gather/update/scatter of individual rows
  (see parallel/sparse_optim.py).

Parity note: this module replaces the row-partitioned embedding storage
of the reference's Go parameter server (elasticdl/pkg/ps/parameters.go,
embedding.go — a hash map of vocab-row slices per PS pod).  The sharding
story is unchanged (dim 0, now storage blocks, spreads over the mesh);
only the per-device physical layout is TPU-shaped.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128

# Opt-in out-of-vocabulary diagnostics (--oov_diagnostics / env
# ELASTICDL_OOV_DEBUG).  The fixed-vocab contract (docs/design.md
# "Fixed-vocabulary embedding tables"): ids outside [0, vocab) contribute
# zeros and receive no update — the reference's Go PS instead lazily
# GREW a row on first lookup, so a ported open-vocabulary model loses
# updates silently here.  With diagnostics on, the Embedding layer
# reports per-step OOV counts (jax.debug.print host callback) so that
# migration gap is visible instead of silent.
import os as _os

_OOV_DEBUG = _os.environ.get("ELASTICDL_OOV_DEBUG", "").strip().lower() in (
    "1", "true", "yes", "on",
)


def set_oov_debug(enabled: bool) -> None:
    global _OOV_DEBUG
    _OOV_DEBUG = bool(enabled)


def oov_debug_enabled() -> bool:
    return _OOV_DEBUG


def _pad_dim(dim: int) -> int:
    """Smallest power-of-two >= dim that divides 128, or a multiple of 128
    for wide rows (which need no packing).

    Power-of-two is a measured requirement, not cosmetics: a round-3
    experiment packed dim 9 at its own stride (block_width 126) to save
    the 78% pad HBM, and the per-step grad scatter went 4.1 ms -> 15.8 ms
    at the 26M-row probe — non-tile-aligned storage rows make every
    scatter straddle 128-lane tiles.  Pad waste is the cheaper poison."""
    if dim >= LANES:
        return -(-dim // LANES) * LANES
    p = 1
    while p < dim:
        p *= 2
    return p


@dataclass(frozen=True)
class PackedSpec:
    """Static description of one packed table."""

    vocab_size: int
    dim: int

    @property
    def dim_padded(self) -> int:
        return _pad_dim(self.dim)

    @property
    def rows_per_block(self) -> int:
        return max(1, LANES // self.dim_padded)

    @property
    def vocab_padded(self) -> int:
        r = self.rows_per_block
        return -(-self.vocab_size // r) * r

    @property
    def num_blocks(self) -> int:
        return self.vocab_padded // self.rows_per_block

    @property
    def block_width(self) -> int:
        return self.rows_per_block * self.dim_padded  # == LANES for dim<128

    @property
    def packed_shape(self) -> tuple:
        return (self.num_blocks, self.block_width)


def pack(spec: PackedSpec, table):
    """[vocab, dim] -> packed [num_blocks, block_width]."""
    table = jnp.asarray(table)
    v_pad = spec.vocab_padded - table.shape[0]
    d_pad = spec.dim_padded - table.shape[1]
    if v_pad or d_pad:
        table = jnp.pad(table, ((0, v_pad), (0, d_pad)))
    return table.reshape(spec.packed_shape)


def unpack(spec: PackedSpec, packed):
    """packed [num_blocks, block_width] -> logical [vocab, dim]."""
    logical = jnp.asarray(packed).reshape(spec.vocab_padded, spec.dim_padded)
    return logical[: spec.vocab_size, : spec.dim]


def mark_iid(initializer):
    """Tag an initializer as elementwise-i.i.d. (its distribution does not
    depend on the shape argument — uniform/normal with fixed scale), which
    lets packed_init generate DIRECTLY in packed storage shape.  That
    matters at scale: the logical [vocab, dim] array pads its minor dim
    to 128 lanes on the chip, so the logical->packed relayout of a
    [26M, 9] init needs 24.8 GB of a v5e's 15.75 GB HBM and the
    compiler refuses it (RESOURCE_EXHAUSTED; compiled for a described
    v5e with jax 0.9.0 — at [2.6M, 9] it compiles with 2.5 GiB of
    temporaries)."""
    initializer.packed_iid_safe = True
    return initializer


def packed_init(spec: PackedSpec, initializer):
    """Wrap an initializer so it produces the packed storage shape (flax
    param init shim).

    Initializers tagged with `mark_iid` generate directly in the packed
    shape (distribution-identical for i.i.d. draws) with pad cells zeroed.
    Untagged initializers may be shape-DEPENDENT (fan-scaled variance,
    row-indexed conventions), so they are invoked with the logical
    (vocab, dim) shape and repacked — correct for any initializer, but the
    relayout's lane-padded temporaries outgrow a 16 GB chip somewhere
    past 10M rows (see mark_iid); tag large-table initializers i.i.d.
    or initialize on host.
    """

    def init(key, shape, dtype=jnp.float32):
        assert tuple(shape) == spec.packed_shape, (shape, spec)
        if not getattr(initializer, "packed_iid_safe", False):
            return pack(spec, initializer(key, (spec.vocab_size, spec.dim), dtype))
        packed = initializer(key, spec.packed_shape, dtype)
        r = spec.rows_per_block
        d = spec.dim_padded
        # Zero pad rows/lanes so the packed invariant (pad cells == 0)
        # holds from the start.
        row = (
            jnp.arange(spec.num_blocks, dtype=jnp.int32)[:, None] * r
            + jnp.arange(spec.block_width, dtype=jnp.int32)[None, :] // d
        )
        mask = row < spec.vocab_size
        if spec.dim != d:
            mask = mask & (
                jnp.arange(spec.block_width, dtype=jnp.int32)[None, :] % d
                < spec.dim
            )
        return jnp.where(mask, packed, jnp.zeros((), dtype))

    return init


def lookup(spec: PackedSpec, packed, ids):
    """Gather logical rows: ids [n] int32 -> [n, dim].

    Storage-row gather (contiguous 512B rows) + one-hot einsum slot
    select.  NEVER use take_along_axis here: lane-indexed gathers
    serialize on TPU (measured 250 ms vs ~0 for the einsum).
    """
    r = spec.rows_per_block
    d = spec.dim_padded
    rows = jnp.take(packed, ids // r, axis=0)  # [n, block_width]
    if r == 1:
        return rows[:, : spec.dim]
    rows = rows.reshape((-1, r, d))
    sel = jax.nn.one_hot(ids % r, r, dtype=packed.dtype)  # [n, r]
    # precision=HIGHEST: at default MXU precision this matmul would round
    # the f32 table values to bf16 on every lookup (and its gradient).
    # The selector contraction is tiny, so exactness costs nothing.
    out = jnp.einsum(
        "nrd,nr->nd", rows, sel, precision=jax.lax.Precision.HIGHEST
    )
    return out[:, : spec.dim]


def expand_updates(spec: PackedSpec, ids, updates):
    """(ids [n], updates [n, dim]) -> (block_ids [n], rows [n, block_width])
    where each output row holds the update in its packed slot and zeros
    elsewhere.  `scatter-add(packed, block_ids, rows)` then applies the
    update with full-storage-row writes (duplicates sum, as scatter-add
    must).

    Negative ids (padding) are routed to an out-of-bounds-HIGH block so
    the scatter DROPS them: JAX scatters drop positive out-of-bounds
    indices but WRAP negative ones numpy-style, which would silently add
    padding grads into the last storage block."""
    r = spec.rows_per_block
    d = spec.dim_padded
    n = ids.shape[0]
    if spec.dim != d:
        updates = jnp.pad(updates, ((0, 0), (0, d - spec.dim)))
    dropped = jnp.asarray(spec.num_blocks, ids.dtype)
    if r == 1:
        return jnp.where(ids >= 0, ids, dropped), updates
    tiled = jnp.tile(updates, (1, r))  # [n, block_width]; lane l holds updates[:, l % d]
    lane_row = jnp.arange(spec.block_width, dtype=ids.dtype) // d  # [bw]
    mask = (lane_row[None, :] == (ids % r)[:, None]).astype(updates.dtype)
    return jnp.where(ids >= 0, ids // r, dropped), tiled * mask


def scatter_add(spec: PackedSpec, packed, ids, updates):
    """packed[ids] += updates, packed-layout fast path."""
    block_ids, rows = expand_updates(spec, ids, updates)
    return packed.at[block_ids].add(rows)


def grad_accumulate(spec: PackedSpec, packed_like, ids, grads):
    """Segment-sum grads by row, in packed layout: returns acc with
    acc[row] = sum of grads over every occurrence of that row in `ids`
    (zeros elsewhere).  This IS the dedup: duplicate ids sum, exactly like
    the reference's IndexedSlices -> unsorted_segment_sum before its Eigen
    sparse-apply kernels (elasticdl/pkg/kernel/capi)."""
    with jax.named_scope("grad_accumulate"):
        block_ids, rows = expand_updates(spec, ids, grads)
        return jnp.zeros_like(packed_like).at[block_ids].add(rows)


def touched_mask(spec: PackedSpec, acc):
    """[num_blocks, rows_per_block] bool: rows whose summed gradient is
    nonzero.  Zero-summed rows (padding ids, fully-masked batches, exact
    cancellation) must not decay optimizer moments — same contract as the
    sorted-dedup implementation this replaced."""
    r = spec.rows_per_block
    d = spec.dim_padded
    return jnp.any(acc.reshape((-1, r, d)) != 0, axis=-1)


def real_lane_mask(spec: PackedSpec, dtype=jnp.float32):
    """[block_width] mask: 1 on lanes holding real dims, 0 on pad lanes.
    Keeps the invariant that pad lanes of every packed array stay zero
    (scatter-side expand_updates zero-pads; streaming updates must mask)."""
    lane = jnp.arange(spec.block_width)
    return ((lane % spec.dim_padded) < spec.dim).astype(dtype)


def broadcast_rows(spec: PackedSpec, per_row):
    """[num_blocks, rows_per_block] -> [num_blocks, block_width] by
    repeating each row value across its dim lanes (elementwise-streaming
    friendly; no gathers)."""
    return jnp.repeat(per_row, spec.dim_padded, axis=1, total_repeat_length=spec.block_width)


# -- touched-rows (lazy) support ----------------------------------------
#
# The streaming optimizer path above costs O(local-table) HBM traffic per
# step; at the north-star table scale (26M rows resident) that pass
# dominates the whole train step (measured 839k -> 192k samples/s).  The
# helpers below give the O(touched-rows) alternative: dedup the batch ids
# WITHOUT a sort (`jnp.unique` lowers to an O(n log n) TPU sort; this is
# a pair of O(n) scatters plus one O(vocab) i32 buffer — 64x less traffic
# than one full f32 table pass), then gather/update/scatter just the
# touched rows.


def _slot_mask(spec: PackedSpec, ids):
    """[n, rows_per_block] bool: one-hot of each id's slot in its block."""
    r = spec.rows_per_block
    return jnp.arange(r, dtype=ids.dtype)[None, :] == (ids % r)[:, None]


def dedup_representatives(spec: PackedSpec, ids, grads):
    """Sort-free dedup of (ids, grads) for lazy row-wise optimizers —
    ALSO the segment-combine prologue of the fused Pallas apply
    (ops/sparse_embedding.fused_dedup_apply), which consumes
    (safe, gsum, touched) directly so both engines see identical
    summed-gradient bits.

    Returns (safe_ids [n] int32, gsum [n, dim], touched [n] bool) where
    exactly ONE position per distinct in-bounds id — its last occurrence,
    the "representative" — is marked touched, `gsum` at that position
    holds the SUMMED grads of all occurrences (the IndexedSlices dedup
    contract of the reference's sparse-apply kernels), and rows whose sum
    is exactly zero are untouched (no moment decay — same contract as
    `touched_mask`).  Out-of-bounds ids (negative padding, >= vocab_padded)
    are dropped, matching the scatter-bounds behaviour of the streaming
    path.

    Mechanism: scatter-max each position's index into a per-logical-row
    i32 buffer (last write wins = max), gather it back to find every
    occurrence's representative, then scatter-add grads onto the
    representative position.
    """
    n = ids.shape[0]
    r = spec.rows_per_block
    ids = ids.astype(jnp.int32)
    valid = (ids >= 0) & (ids < spec.vocab_padded)
    safe = jnp.where(valid, ids, 0)
    pos = jnp.arange(n, dtype=jnp.int32)
    mask = _slot_mask(spec, safe)  # [n, r]
    # last-occurrence index per logical row (-1 = never written).
    buf = jnp.full((spec.num_blocks, r), -1, jnp.int32)
    block_ids = jnp.where(valid, safe // r, spec.num_blocks)  # OOB -> dropped
    buf = buf.at[block_ids].max(jnp.where(mask, pos[:, None], -1))
    got = jnp.take(buf, safe // r, axis=0)  # [n, r] (gather clamps; masked below)
    last = jnp.max(jnp.where(mask, got, -1), axis=1)  # [n]
    # Sum every occurrence's grad onto its representative position.
    tgt = jnp.where(valid, last, n)  # invalid -> out of bounds -> dropped
    gsum = jnp.zeros_like(grads).at[tgt].add(grads)
    is_repr = valid & (pos == last)
    touched = is_repr & jnp.any(gsum != 0, axis=-1)
    return safe, gsum, touched


