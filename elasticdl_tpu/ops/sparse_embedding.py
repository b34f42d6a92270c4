"""Fused Pallas TPU kernels for the sparse embedding hot path.

BENCH_r04 named the perf ceiling: DeepFM trains at 972.9k samples/s/chip
with ``bound: sparse-row-count`` (ns_per_row 39.5, floor_frac 0.632) —
the lookup gather + one-hot select and the dedup+scatter optimizer
update, not matmul, are the wall.  The XLA formulation of that path
(parallel/packed.py + parallel/sparse_optim.py) round-trips several
``[n, block_width]`` (512 B/row) intermediates through HBM per step:

- ``pk.lookup``: gather full storage rows to an HBM ``[n, 128]`` buffer,
  re-read it for the one-hot slot-select einsum, write ``[n, dim_pad]``;
- ``scatter_apply`` (per optimizer): 2-4 such lookups for the slot rows
  PLUS 3-4 ``expand_updates`` scatters, each materializing a tiled+
  masked ``[n, 128]`` update operand before the full-row scatter-add.

The kernels here keep the touched rows in VMEM between those steps
instead (the same treatment ``ops/flash_attention.py`` gave the dense
side — 2.4x on the transformer):

``fused_lookup``       gather-and-lane-select in one kernel: each
                       storage row is DMA'd HBM->VMEM once, the packed
                       slot's lanes are selected EXACTLY (a lane rotate
                       by the slot's dynamic offset plus a static slice
                       — what Mosaic lowers; no MXU contraction, so no
                       precision= escape hatch needed), and only the
                       compact ``[n, dim_pad]`` result is written back.
``fused_dedup_apply``  the optimizer update in one pass: the sort-free
                       segment-combine (scatter-max representatives —
                       the same mechanism as
                       ``packed.dedup_representatives``, pinned
                       bit-exact by tests) runs as a cheap O(n)
                       prologue, then ONE kernel walks the touched
                       representatives, DMAs table+slot rows into VMEM,
                       applies sgd/momentum/adagrad/adam slot math in
                       delta form (the scatter path's read-modify-write
                       adds, <= 1 ulp — see its docstring), and DMAs
                       the rows back — zero ``[n, 128]`` HBM
                       intermediates.
``fused_lookup_fm``    the DeepFM combined ``1+dim`` lookup feeding the
                       FM second-order term: one pass emits the field
                       activations (the deep tower needs them) AND the
                       first-order sum + FM partial sums, so the FM
                       term never re-reads the ``[batch, fields, dim]``
                       tensor from HBM.  Differentiable via custom_vjp
                       (the perturbation-capture input ``bet`` carries
                       the sparse gradient, exactly like the unfused
                       Embedding layer's capture point).

Mode selection: the kernels are wired as a third ``fused`` mode behind
``sparse_optim``'s stream/scatter switch and the ``--sparse_kernel
{xla,fused,auto}`` job flag (threaded through ps_trainer, the Embedding
layer, and the DeepFM zoo model).  ``auto`` currently resolves to
``xla``: the fused path's chip numbers are queued driver work
(BASELINE.md "queued chip work") and auto must not move the headline on
unmeasured code — flip AUTO_FUSED_READY once the evidence lands.

Every kernel runs in Pallas interpret mode off-TPU (same
``_use_interpret()`` pattern as flash_attention), so tier-1 CPU tests
exercise the real kernel bodies, and ``scripts/convergence_ab.py
--sparse-kernel fused`` gates end-to-end training quality.  What the
interpreter cannot see — Mosaic's lowering, the chip's 1 MB of SMEM,
the tiling — is held by tests/test_tpu_compile.py (each kernel compiled
with ``interpret=False`` for a described v5e at DeepFM's widths) and by
chip_smoke.py's kernels phase (each kernel against its XLA twin on the
chip: bit-equal at PR 21).

Sharded dispatch (round 7): ``pl.pallas_call`` is not
SPMD-partitionable the way the XLA gather/scatter ops are, so on a
multi-device mesh every fused kernel routes through ``shard_map``
(built via the parallel/compile.py shim) instead of the SPMD
partitioner: embedding tables shard their storage blocks over the
mesh's ``model`` axis (``table_partition_axis``), each shard runs the
SAME kernel body over its resident blocks with ids routed to their
owning shard (out-of-shard ids contribute exact zeros / are dropped by
the dedup prologue), and the cross-shard combine is a ``psum`` for
lookups and nothing at all for the apply (each shard owns its rows'
writes; the batch gradient all-gathers over ``data`` first so every
replica applies the identical update).  ``dispatch_route(mesh)``
selects ``single_device`` (plain pallas_call) vs ``shard_map``;
trainers journal the decision in ``sparse_kernel_selected``.  Tables
whose blocks don't divide the model axis replicate (each shard then
runs the full-table body — still inside shard_map, because manual
sharding is what makes a pallas body legal on a multi-device mesh).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.parallel import packed as pk
from elasticdl_tpu.parallel.packed import PackedSpec

#: Ids processed per grid step.  VMEM per step, in TILED bytes (a block's
#: lane dim pads to 128 and its sublane dim to 8): the gsum / output
#: tile is TILE x 128 lanes x 4 B = 64 KB, double-buffered by the
#: pipeline = 128 KB, plus the row scratches (one (8, 128) f32 tile =
#: 4 KB per storage row held) — ~140 KB at the default, far under the
#: 16 MB scoped-VMEM budget the compile for a v5e accepts.  The per-id
#: scalars (block, lane offset, touched) ride in SMEM one TILE at a
#: time: the whole id arrays do not fit its 1 MB (`_smem_tile`).
DEFAULT_IDS_PER_TILE = 128
#: Batch rows per grid step of the FM kernel.  Its bet and acts blocks
#: are (8, fields, dim) -> tiled (8, 32, 128) f32 = 128 KB each at
#: DeepFM's 26 fields x dim 9, double-buffered = 512 KB for the pair.
DEFAULT_FM_BATCH_TILE = 8

KERNELS = ("xla", "fused", "auto")

#: Gate for auto mode: the fused kernels' chip numbers are queued driver
#: work (BASELINE.md).  Until a driver bench verifies them, `auto`
#: resolves to the measured xla path so the headline never silently
#: moves onto unmeasured code.  Flip to True WITH the chip evidence.
AUTO_FUSED_READY = False

_DEFAULT_KERNEL = "xla"


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def set_default_kernel(kernel: str) -> None:
    """Process-wide default consulted by Embedding layers whose model
    did not thread ``sparse_kernel`` explicitly (worker main sets this
    from ``--sparse_kernel`` before the model is built)."""
    global _DEFAULT_KERNEL
    if kernel not in KERNELS:
        raise ValueError(f"sparse_kernel must be one of {KERNELS}, got {kernel!r}")
    _DEFAULT_KERNEL = kernel


def default_kernel() -> str:
    return _DEFAULT_KERNEL


def resolve_kernel(requested: Optional[str] = None) -> str:
    """'xla' or 'fused' from a requested mode (None = process default).

    ``auto`` prefers the fused kernels only once AUTO_FUSED_READY is
    flipped by chip evidence (see module docstring); until then it IS
    the xla path, logged once by ps_trainer at init.
    """
    kernel = requested or _DEFAULT_KERNEL
    if kernel not in KERNELS:
        raise ValueError(f"sparse_kernel must be one of {KERNELS}, got {kernel!r}")
    if kernel == "auto":
        return "fused" if AUTO_FUSED_READY else "xla"
    return kernel


# ----------------------------------------------------------------------
# sharded dispatch (multi-device meshes; see the module docstring)
# ----------------------------------------------------------------------

#: Process-default dispatch mesh: worker/main registers the job's mesh
#: so Embedding layers that did not thread `mesh` explicitly still take
#: the shard_map route on multi-device worlds (an unpartitionable
#: pallas_call traced into an SPMD program is the failure mode this
#: replaces).  Ops-level functions consult ONLY their explicit `mesh`
#: argument; the layer resolves None against this default.
_DISPATCH_MESH = None


def set_dispatch_mesh(mesh) -> None:
    global _DISPATCH_MESH
    _DISPATCH_MESH = mesh


def dispatch_mesh():
    return _DISPATCH_MESH


def dispatch_route(mesh=None) -> str:
    """'single_device' (plain pallas_call) or 'shard_map' (per-shard
    kernel bodies inside shard_map) for a given mesh."""
    if mesh is not None and int(mesh.devices.size) > 1:
        return "shard_map"
    return "single_device"


def table_partition_axis(num_blocks: int, mesh) -> Optional[str]:
    """Mesh axis the fused engine shards a table's storage blocks over:
    the `model` axis when it divides them (the one table-placement
    decision — ps_trainer's rule table and the shard_map in_specs here
    both read it), else None (replicate — the table is tiny)."""
    from elasticdl_tpu.parallel.mesh import MODEL_AXIS

    if mesh is None:
        return None
    # Host ints throughout (mesh shape and PackedSpec fields are static
    # Python values — no tracer ever reaches this decision).
    msize = mesh.shape.get(MODEL_AXIS, 1)
    if msize > 1 and num_blocks % msize == 0:
        return MODEL_AXIS
    return None


def _shard_local_spec(spec: PackedSpec, mesh) -> PackedSpec:
    """The per-shard PackedSpec under model-axis block sharding: same
    dim/packing, 1/msize of the storage blocks (exact because
    table_partition_axis demanded divisibility)."""
    from elasticdl_tpu.parallel.mesh import MODEL_AXIS

    msize = int(mesh.shape[MODEL_AXIS])
    return PackedSpec(spec.vocab_padded // msize, spec.dim)


def _batch_spec(n: int, mesh):
    """PartitionSpec for a batch-derived dim0 of static size `n`: shard
    over `data` when it divides (the trainers' padded batches always
    do), else replicate — either split is CORRECT (routing/combine
    never depend on which ids land on which data shard), sharding just
    avoids redundant per-device work."""
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.parallel.mesh import DATA_AXIS

    dp = int(mesh.shape.get(DATA_AXIS, 1))
    return P(DATA_AXIS) if n % dp == 0 else P()


# ----------------------------------------------------------------------
# shared host-side prologue helpers
# ----------------------------------------------------------------------


def _pad_to_tile(n: int, tile: int) -> int:
    return -(-n // tile) * tile


def _block_and_lane(spec: PackedSpec, ids):
    """(block_ids, lane0) int32 for the kernels' row DMA: storage block
    CLAMPED to [0, num_blocks) — a deliberate choice for out-of-range
    ids (every DMA must target a real row), NOT pk.lookup's semantics
    there (its jnp.take default fill-mode reads NaN for OOB-high and
    wraps negatives).  Bit-equivalence with pk.lookup therefore holds
    for ids in [0, vocab_padded); out-of-range ids are the Embedding
    layer's job (safe ids + validity mask), behind which the engines
    are bit-identical — see fused_lookup's docstring and
    tests/test_sparse_kernels.py.  The slot lane comes from floor-mod,
    matching the one-hot select for every id."""
    ids = ids.astype(jnp.int32)
    r = spec.rows_per_block
    blocks = jnp.clip(ids // r, 0, spec.num_blocks - 1)
    lane0 = (ids % r) * spec.dim_padded
    return blocks, lane0


def _slot_to_lane0(row, lane0):
    """Rotate a ``[1, block_width]`` storage row so the packed slot that
    starts at lane `lane0` starts at lane 0.  Mosaic has no value-level
    dynamic lane slice; a lane rotate by a dynamic amount plus a STATIC
    slice (or an iota mask) is what it lowers, and it moves bits
    unchanged, so the exactness contracts hold."""
    width = row.shape[-1]
    return pltpu.roll(row, jax.lax.rem(width - lane0, width), 1)


# ----------------------------------------------------------------------
# fused lookup: gather + lane select in one kernel
# ----------------------------------------------------------------------


def _lookup_kernel(blocks_ref, lane0_ref, table_ref, out_ref, rows, sem,
                   *, tile, dim_padded):
    """One grid step: `tile` ids.  Per id, DMA its 512 B storage row
    HBM->VMEM (double-buffered: row i+1's fetch overlaps row i's
    select) and write only the slot's dim_padded lanes to the compact
    output block."""

    def fetch(i, slot):
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(blocks_ref[0, i], 1), :],
            rows.at[slot],
            sem.at[slot],
        )

    fetch(0, 0).start()

    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < tile)
        def _prefetch():
            fetch(i + 1, 1 - slot).start()

        fetch(i, slot).wait()
        sel = _slot_to_lane0(rows[slot], lane0_ref[0, i])
        out_ref[pl.ds(i, 1), :] = sel[:, :dim_padded]
        return 0

    jax.lax.fori_loop(0, tile, body, 0)


def _smem_tile(tile):
    """BlockSpec handing each grid step its own `tile` scalars in SMEM.
    (Scalar-prefetching the WHOLE id arrays does not fit: the chip has
    1 MB of SMEM and DeepFM's 8192 x 26 ids are 832 KB per array.)"""
    return pl.BlockSpec(
        (None, 1, tile), lambda g: (g, 0, 0), memory_space=pltpu.SMEM
    )


def _scalar_tiles(x, tile):
    """[n_pad] per-id scalars -> the [n_pad // tile, 1, tile] array
    `_smem_tile` blocks (SMEM blocks must span the array's last two
    dims, so the tile index gets a leading dim of its own)."""
    return x.reshape((-1, 1, tile))


def _lookup_impl(spec: PackedSpec, interpret: bool, tile: int, packed, ids):
    n = ids.shape[0]
    if n == 0:
        return jnp.zeros((0, spec.dim), packed.dtype)
    tile = min(tile, _pad_to_tile(n, 8))
    n_pad = _pad_to_tile(n, tile)
    ids_pad = jnp.pad(ids.astype(jnp.int32), (0, n_pad - n))
    blocks, lane0 = _block_and_lane(spec, ids_pad)
    out = pl.pallas_call(
        functools.partial(
            _lookup_kernel, tile=tile, dim_padded=spec.dim_padded
        ),
        grid=(n_pad // tile,),
        in_specs=[
            _smem_tile(tile),
            _smem_tile(tile),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tile, spec.dim_padded), lambda g: (g, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, 1, spec.block_width), packed.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        out_shape=jax.ShapeDtypeStruct((n_pad, spec.dim_padded), packed.dtype),
        interpret=interpret,
    )(_scalar_tiles(blocks, tile), _scalar_tiles(lane0, tile), packed)
    return out[:n, : spec.dim]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _lookup_diff(spec, interpret, tile, packed, ids):
    return _lookup_impl(spec, interpret, tile, packed, ids)


def _lookup_fwd(spec, interpret, tile, packed, ids):
    out = _lookup_impl(spec, interpret, tile, packed, ids)
    return out, ids


def _lookup_bwd(spec, interpret, tile, ids, g):
    # Same cotangent the packed scatter path owns: duplicate ids sum,
    # out-of-range ids drop.  (pk.lookup's fill-mode backward would
    # drop/wrap OOV cotangents differently, but every caller masks
    # invalid positions to zero gradient first — the Embedding layer's
    # validity mask — so the two backwards agree where gradients are
    # nonzero.)
    d_packed = pk.grad_accumulate(
        spec, jnp.zeros(spec.packed_shape, g.dtype), ids, g
    )
    return d_packed, jnp.zeros(ids.shape, jax.dtypes.float0)


_lookup_diff.defvjp(_lookup_fwd, _lookup_bwd)


def _sharded_lookup_impl(spec, interpret, tile, mesh, packed, ids):
    """shard_map route of the lookup: table blocks P(model), ids
    routed to their owning shard, per-shard kernel bodies, psum
    combine.  Out-of-range ids read ZEROS here (no shard owns them)
    where the single-device kernel clamp-reads a real row — identical
    through the Embedding layer's validity mask, which is the only
    sanctioned consumer of out-of-range ids."""
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.parallel import compile as pc
    from elasticdl_tpu.parallel.mesh import MODEL_AXIS

    axis = table_partition_axis(spec.num_blocks, mesh)
    local_spec = _shard_local_spec(spec, mesh) if axis else spec
    data = _batch_spec(ids.shape[0], mesh)

    def body(packed_l, ids_l):
        if axis is None:
            return _lookup_impl(spec, interpret, tile, packed_l, ids_l)
        rows_local = local_spec.vocab_padded
        start = jax.lax.axis_index(MODEL_AXIS) * rows_local
        local = ids_l.astype(jnp.int32) - start
        inshard = (local >= 0) & (local < rows_local)
        rows = _lookup_impl(
            local_spec, interpret, tile, packed_l,
            jnp.where(inshard, local, 0),
        )
        rows = rows * inshard[:, None].astype(rows.dtype)
        # Each valid id is owned by exactly one shard; the psum adds
        # exact zeros elsewhere, so owner bits pass through untouched.
        return jax.lax.psum(rows, MODEL_AXIS)

    return pc.shard_map_call(
        body, mesh,
        in_specs=(P(axis), data),
        out_specs=data,
        check_vma=False,
    )(packed, ids)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _sharded_lookup_diff(spec, interpret, tile, mesh, packed, ids):
    return _sharded_lookup_impl(spec, interpret, tile, mesh, packed, ids)


def _sharded_lookup_fwd(spec, interpret, tile, mesh, packed, ids):
    out = _sharded_lookup_impl(spec, interpret, tile, mesh, packed, ids)
    return out, ids


def _sharded_lookup_bwd(spec, interpret, tile, mesh, ids, g):
    # Same global segment-sum cotangent as the single-device route —
    # plain XLA scatters, which the SPMD partitioner shards fine (the
    # custom_vjp keeps the backward OUTSIDE shard_map on purpose).
    d_packed = pk.grad_accumulate(
        spec, jnp.zeros(spec.packed_shape, g.dtype), ids, g
    )
    return d_packed, jnp.zeros(ids.shape, jax.dtypes.float0)


_sharded_lookup_diff.defvjp(_sharded_lookup_fwd, _sharded_lookup_bwd)


def fused_lookup(
    spec: PackedSpec,
    packed,
    ids,
    *,
    mesh=None,
    interpret: Optional[bool] = None,
    tile: int = DEFAULT_IDS_PER_TILE,
):
    """Drop-in for ``packed.lookup``: ids [n] int32 -> [n, dim].

    Bit-exact vs pk.lookup for every id in ``[0, vocab_padded)`` (the
    one-hot einsum at precision=HIGHEST is an exact f32 select; so is
    the kernel's lane slice).  Out-of-range ids — which every caller
    masks BEFORE the lookup (the Embedding layer's safe-id contract) —
    read a clamped storage row here, where pk.lookup's jnp.take
    fill-mode reads NaN (OOB-high) or wraps (negative); through the
    Embedding layer the two paths are bit-identical because the
    validity mask zeroes those positions either way (pinned by
    tests/test_sparse_kernels.py).  Differentiable in the table
    (sparse segment-sum cotangent).

    `mesh`: a multi-device mesh routes through shard_map (per-shard
    kernel bodies over model-axis table shards, psum combine — module
    docstring "Sharded dispatch"); None / single device keeps the
    plain pallas_call.
    """
    interpret = _use_interpret() if interpret is None else interpret
    if dispatch_route(mesh) == "shard_map":
        return _sharded_lookup_diff(spec, interpret, tile, mesh, packed, ids)
    return _lookup_diff(spec, interpret, tile, packed, ids)


# ----------------------------------------------------------------------
# fused dedup + optimizer apply
# ----------------------------------------------------------------------

#: Table-shaped operands per optimizer kind, in kernel-operand order.
#: The table itself is always first; the rest are the slot names.
_KIND_SLOTS: Dict[str, Tuple[str, ...]] = {
    "sgd": (),
    "momentum": ("momentum",),
    "adagrad": ("accumulator",),
    "adam": ("m", "v", "t"),
    "adam_global": ("m", "v"),
}


def _apply_math(kind, hyper, lane_mask, g, subs, tr):
    """Per-representative optimizer math on dim_padded lane vectors.

    Returns the DELTAS to add to each operand's lanes (table first, then
    slots in _KIND_SLOTS order) — delta form so the written values are
    bit-identical to the scatter path's read-modify-write adds.  `g` is
    the summed gradient (pad lanes zero), `subs` the current lane
    vectors, `tr` the adam bias-correction step count (scalar).
    """
    lr = hyper["learning_rate"]
    if kind == "sgd":
        return (-lr * g,)
    if kind == "momentum":
        mu = hyper["momentum"]
        v = subs[1]
        v_new = mu * v + g
        step = (mu * v_new + g) if hyper["nesterov"] else v_new
        return (-lr * step, v_new - v)
    if kind == "adagrad":
        eps = hyper["epsilon"]
        acc = subs[1]
        gg = g * g
        new_acc = acc + gg
        update = -lr * g / (jnp.sqrt(new_acc) + eps)
        return (update, gg)
    # adam / adam_global
    b1, b2, eps = hyper["beta_1"], hyper["beta_2"], hyper["epsilon"]
    m, v = subs[1], subs[2]
    m_new = b1 * m + (1 - b1) * g
    v_new = b2 * v + (1 - b2) * g * g
    m_hat = m_new / (1 - b1 ** tr)
    v_hat = v_new / (1 - b2 ** tr)
    update = -lr * m_hat / (jnp.sqrt(v_hat) + eps)
    if kind == "adam":
        # Per-row t increments by 1 on REAL lanes only (pad lanes stay
        # zero — the packed-invariant the scatter path keeps too).
        return (update, m_new - m, v_new - v, lane_mask)
    return (update, m_new - m, v_new - v)


def _dedup_apply_kernel(blocks_ref, lane0_ref, touched_ref, gsum_ref,
                        tr_ref, *refs, kind, hyper, tile, dim_padded,
                        dim, n_tables):
    """Grid step over `tile` representatives.  For each touched one:
    DMA the table row + slot rows HBM->VMEM (all fetches in flight
    together), apply the optimizer math to the slot's lanes, DMA the
    updated rows back.  The TPU grid is sequential, so two
    representatives sharing a storage row serialize correctly.

    The math runs on whole block_width-lane rows rotated so the slot
    sits at lane 0 (`_slot_to_lane0`), and only the slot's dim_padded
    lanes are selected into the written row: elementwise ops see the
    same per-lane inputs as a dim_padded-wide slice would, so the bits
    are those of the narrow formulation, and a vreg is 128 lanes wide
    either way."""
    # refs layout: n_tables ANY-space input refs, n_tables output refs
    # (input_output_aliases makes each pair one buffer — read and write
    # through the OUTPUT ref), then scratch: rows VMEM
    # [n_tables, 1, block_width], the widened-gradient row and the
    # in/out DMA semaphores.
    tables = refs[n_tables : 2 * n_tables]
    rows, gwide, sem_in, sem_out = refs[2 * n_tables :]
    width = rows.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    slot_lanes = lane < dim_padded
    lane_mask = (lane < dim).astype(gsum_ref.dtype)
    # Lanes past the slot never reach a written row (slot_lanes), but
    # they do go through the math: keep them finite.
    gwide[...] = jnp.zeros(gwide.shape, gwide.dtype)

    def body(i, _):
        @pl.when(touched_ref[0, i] != 0)
        def _apply():
            block = blocks_ref[0, i]
            lane0 = lane0_ref[0, i]
            for t in range(n_tables):
                pltpu.make_async_copy(
                    tables[t].at[pl.ds(block, 1), :],
                    rows.at[t],
                    sem_in.at[t],
                ).start()
            for t in range(n_tables):
                pltpu.make_async_copy(
                    tables[t].at[pl.ds(block, 1), :],
                    rows.at[t],
                    sem_in.at[t],
                ).wait()
            subs = tuple(
                _slot_to_lane0(rows[t], lane0) for t in range(n_tables)
            )
            gwide[:, :dim_padded] = gsum_ref[pl.ds(i, 1), :]
            gvec = gwide[...]
            if kind == "adam":
                # Scatter-path contract: tr = max(t_before + 1, 1) read
                # from the count slot's first real lane.
                tr = jnp.maximum(
                    jnp.broadcast_to(subs[3][:, 0:1], (1, width)) + 1.0, 1.0
                )
            else:
                tr = jnp.full((1, width), tr_ref[0, 0], gvec.dtype)
            deltas = _apply_math(kind, hyper, lane_mask, gvec, subs, tr)
            for t in range(n_tables):
                updated = jnp.where(slot_lanes, subs[t] + deltas[t], subs[t])
                rows[t] = pltpu.roll(updated, lane0, 1)  # back to its slot
                pltpu.make_async_copy(
                    rows.at[t],
                    tables[t].at[pl.ds(block, 1), :],
                    sem_out.at[t],
                ).start()
            for t in range(n_tables):
                pltpu.make_async_copy(
                    rows.at[t],
                    tables[t].at[pl.ds(block, 1), :],
                    sem_out.at[t],
                ).wait()

        return 0

    jax.lax.fori_loop(0, tile, body, 0)


def _dedup_apply_core(spec, kind, hyper, tables, ids, grads, tr,
                      interpret, tile):
    """The dedup prologue + ONE kernel pass over `tables` (packed table
    first, then slot arrays in _KIND_SLOTS order), all in the given
    spec's (possibly per-shard) coordinate space.  Returns the updated
    arrays in operand order."""
    safe, gsum, touched = pk.dedup_representatives(spec, ids, grads)
    tch = touched.astype(tables[0].dtype)[:, None]
    gsum = gsum * tch  # the scatter path's masking, same bits
    return _apply_representatives(
        spec, kind, hyper, tables, safe, gsum, touched, tr, interpret, tile
    )


def _apply_representatives(spec, kind, hyper, tables, safe, gsum, touched,
                           tr, interpret, tile):
    """The kernel pass alone, over an already segment-combined batch
    (`pk.dedup_representatives`' triple).  Split from the prologue so
    tests/test_tpu_compile.py compiles the Pallas call at DeepFM's real
    id count without the prologue's XLA scatters."""
    n = safe.shape[0]
    tile = min(tile, _pad_to_tile(max(n, 1), 8))
    n_pad = _pad_to_tile(max(n, 1), tile)
    pad = n_pad - n
    safe_pad = jnp.pad(safe, (0, pad))
    touched_pad = jnp.pad(touched.astype(jnp.int32), (0, pad))
    blocks, lane0 = _block_and_lane(spec, safe_pad)
    if spec.dim != spec.dim_padded:
        gsum = jnp.pad(gsum, ((0, 0), (0, spec.dim_padded - spec.dim)))
    gsum_pad = jnp.pad(gsum, ((0, pad), (0, 0)))

    n_tables = len(tables)
    # Operand order: 3 per-tile scalar arrays, gsum tile, tr scalar,
    # then the aliased table refs.
    aliases = {5 + t: t for t in range(n_tables)}
    outs = pl.pallas_call(
        functools.partial(
            _dedup_apply_kernel,
            kind=kind,
            hyper=hyper,
            tile=tile,
            dim_padded=spec.dim_padded,
            dim=spec.dim,
            n_tables=n_tables,
        ),
        grid=(n_pad // tile,),
        in_specs=[
            _smem_tile(tile),
            _smem_tile(tile),
            _smem_tile(tile),
            pl.BlockSpec((tile, spec.dim_padded), lambda g: (g, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ]
        + [pl.BlockSpec(memory_space=pl.ANY)] * n_tables,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_tables,
        scratch_shapes=[
            pltpu.VMEM((n_tables, 1, spec.block_width), tables[0].dtype),
            pltpu.VMEM((1, spec.block_width), tables[0].dtype),
            pltpu.SemaphoreType.DMA((n_tables,)),
            pltpu.SemaphoreType.DMA((n_tables,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tables
        ],
        input_output_aliases=aliases,
        interpret=interpret,
    )(
        _scalar_tiles(blocks, tile),
        _scalar_tiles(lane0, tile),
        _scalar_tiles(touched_pad, tile),
        gsum_pad,
        tr,
        *tables,
    )
    return tuple(outs)


def _sharded_dedup_apply(spec, kind, hyper, tables, ids, grads, tr, mesh,
                         interpret, tile):
    """shard_map route of the optimizer apply: table + slot blocks
    P(model), the batch (ids, grads) all-gathered over `data` so every
    replica of a table shard applies the IDENTICAL update (the dedup
    sees the same global occurrence order as single-device — same
    summed-gradient bits), ids routed to their owning shard (-1 =
    dropped by the dedup prologue, exactly like padding ids).  No
    cross-shard combine: each shard owns its rows' writes."""
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.parallel import compile as pc
    from elasticdl_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    axis = table_partition_axis(spec.num_blocks, mesh)
    local_spec = _shard_local_spec(spec, mesh) if axis else spec
    data = _batch_spec(ids.shape[0], mesh)
    data_sharded = data != P()

    def body(ids_l, grads_l, tr_l, *tables_l):
        if data_sharded:
            ids_g = jax.lax.all_gather(ids_l, DATA_AXIS, tiled=True)
            grads_g = jax.lax.all_gather(grads_l, DATA_AXIS, tiled=True)
        else:
            ids_g, grads_g = ids_l, grads_l
        if axis is None:
            return _dedup_apply_core(
                spec, kind, hyper, tables_l, ids_g, grads_g, tr_l,
                interpret, tile,
            )
        rows_local = local_spec.vocab_padded
        start = jax.lax.axis_index(MODEL_AXIS) * rows_local
        local = ids_g.astype(jnp.int32) - start
        inshard = (local >= 0) & (local < rows_local)
        routed = jnp.where(inshard, local, -1)
        return _dedup_apply_core(
            local_spec, kind, hyper, tables_l, routed, grads_g, tr_l,
            interpret, tile,
        )

    table_p = P(axis) if axis else P()
    return pc.shard_map_call(
        body, mesh,
        in_specs=(data, data, P()) + (table_p,) * len(tables),
        out_specs=(table_p,) * len(tables),
        check_vma=False,
    )(ids, grads, tr, *tables)


def fused_dedup_apply(
    spec: PackedSpec,
    kind: str,
    hyper: dict,
    packed_table,
    slots: dict,
    ids,
    grads,
    *,
    mesh=None,
    interpret: Optional[bool] = None,
    tile: int = DEFAULT_IDS_PER_TILE,
):
    """One-pass sparse optimizer step: ``(ids, grads)`` in,
    ``(new_table, new_slots)`` out, matching
    ``dedup_representatives + scatter_apply``.

    Exactness contract (pinned by tests/test_sparse_kernels.py): the
    kernel replays the scatter path's arithmetic operation-for-
    operation — the same segment-combined gradients (identical bits:
    the dedup prologue IS the scatter path's), the same elementwise
    slot math, and delta-form writes (``old + fl(new - old)``, the
    scatter path's read-modify-write adds).  In exact arithmetic the
    two are identical; in compiled f32 they agree to <= 1 ulp, because
    XLA is free to fuse any multiply-feeding-an-add into an FMA (one
    rounding) on either side of the comparison and no kernel
    formulation can pin which.  Documented tolerance: rtol 3e-7
    (observed diffs: 0 on most elements, 1 ulp on the rest — e.g.
    adagrad's ``acc + g*g`` inside the update chain).

    The sort-free segment-combine (two O(n) scatters; the SAME
    scatter-max mechanism the scatter path uses, so the summed
    gradients carry identical bits) runs as an XLA prologue; the
    gather/update/scatter trips it used to feed — 2-4 packed lookups
    plus 3-4 expand_updates scatters, each an ``[n, 128]`` HBM
    intermediate — collapse into one kernel that round-trips only the
    touched rows' 512 B storage rows through VMEM.

    `mesh`: a multi-device mesh routes the whole pass through shard_map
    (module docstring "Sharded dispatch") — same arithmetic per shard,
    identical update on every replica of a table shard.
    """
    if kind == "adam" and "t" not in slots:
        kind = "adam_global"
    if kind not in _KIND_SLOTS:
        raise ValueError(f"unknown sparse optimizer kind {kind!r}")
    interpret = _use_interpret() if interpret is None else interpret
    slot_names = _KIND_SLOTS[kind]
    new_slots = dict(slots)

    if kind == "adam_global":
        # Global bias correction: one shared apply counter, incremented
        # unconditionally per apply (the reference Go Adam's contract).
        # Replicated scalar — updated OUTSIDE any shard_map.
        t_global = slots["t_global"] + 1.0
        new_slots["t_global"] = t_global
        tr = jnp.reshape(t_global.astype(jnp.float32), (1, 1))
    else:
        tr = jnp.zeros((1, 1), jnp.float32)  # per-row tr reads in-kernel

    tables = (packed_table,) + tuple(slots[name] for name in slot_names)
    if dispatch_route(mesh) == "shard_map":
        outs = _sharded_dedup_apply(
            spec, kind, hyper, tables, ids, grads, tr, mesh, interpret,
            tile,
        )
    else:
        outs = _dedup_apply_core(
            spec, kind, hyper, tables, ids, grads, tr, interpret, tile
        )
    new_table = outs[0]
    for name, arr in zip(slot_names, outs[1:]):
        new_slots[name] = arr
    return new_table, new_slots


# ----------------------------------------------------------------------
# fused lookup -> FM interaction (DeepFM's combined 1+dim table)
# ----------------------------------------------------------------------


def _fm_kernel(blocks_ref, lane0_ref, valid_ref, bet_ref, table_ref,
               acts_ref, first_ref, sumv_ref, sumsq_ref, rows, sem,
               *, batch_tile, fields, dim):
    """Grid step over `batch_tile` examples x `fields` ids: DMA each
    field's storage row once, add the perturbation capture, mask
    validity, and accumulate the first-order sum + FM partial sums in
    VMEM registers while the activations stream to their output block —
    the FM term never re-reads [batch, fields, dim] from HBM."""

    def fetch(pos, slot):
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(blocks_ref[0, pos], 1), :],
            rows.at[slot],
            sem.at[slot],
        )

    def example(b, _):
        base = b * fields
        fetch(base, 0).start()

        def field(f, carry):
            s, ss = carry
            slot = jax.lax.rem(f, 2)

            @pl.when(f + 1 < fields)
            def _prefetch():
                fetch(base + f + 1, 1 - slot).start()

            fetch(base + f, slot).wait()
            sel = _slot_to_lane0(rows[slot], lane0_ref[0, base + f])
            a = (sel[:, :dim] + bet_ref[b, pl.ds(f, 1), :]) * valid_ref[
                0, base + f
            ]
            acts_ref[b, pl.ds(f, 1), :] = a
            # Lane 0 of `s` is the first-order sum, lanes 1.. the FM
            # vector sums; lane 0 of `ss` is unused.
            return s + a, ss + a * a

        zero = jnp.zeros((1, dim), acts_ref.dtype)
        s, ss = jax.lax.fori_loop(0, fields, field, (zero, zero))
        first_ref[pl.ds(b, 1), :] = s[:, :1]
        sumv_ref[pl.ds(b, 1), :] = s[:, 1:]
        sumsq_ref[pl.ds(b, 1), :] = ss[:, 1:]
        return 0

    jax.lax.fori_loop(0, batch_tile, example, 0)


def _fm_impl(spec, interpret, batch_tile, packed, bet, ids, valid):
    batch, fields = ids.shape
    dim = spec.dim
    batch_tile = min(batch_tile, max(batch, 1))
    b_pad = _pad_to_tile(max(batch, 1), batch_tile)
    pad = b_pad - batch
    ids_pad = jnp.pad(ids.astype(jnp.int32), ((0, pad), (0, 0)))
    blocks, lane0 = _block_and_lane(spec, ids_pad.reshape((-1,)))
    bet_pad = jnp.pad(
        bet.astype(packed.dtype), ((0, pad), (0, 0), (0, 0))
    )
    valid_pad = jnp.pad(
        valid.astype(packed.dtype), ((0, pad), (0, 0))
    ).reshape((-1,))
    ids_tile = batch_tile * fields
    acts, first, sumv, sumsq = pl.pallas_call(
        functools.partial(
            _fm_kernel, batch_tile=batch_tile, fields=fields, dim=dim
        ),
        grid=(b_pad // batch_tile,),
        in_specs=[
            _smem_tile(ids_tile),
            _smem_tile(ids_tile),
            _smem_tile(ids_tile),
            pl.BlockSpec((batch_tile, fields, dim), lambda g: (g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((batch_tile, fields, dim), lambda g: (g, 0, 0)),
            pl.BlockSpec((batch_tile, 1), lambda g: (g, 0)),
            pl.BlockSpec((batch_tile, dim - 1), lambda g: (g, 0)),
            pl.BlockSpec((batch_tile, dim - 1), lambda g: (g, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, 1, spec.block_width), packed.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, fields, dim), packed.dtype),
            jax.ShapeDtypeStruct((b_pad, 1), packed.dtype),
            jax.ShapeDtypeStruct((b_pad, dim - 1), packed.dtype),
            jax.ShapeDtypeStruct((b_pad, dim - 1), packed.dtype),
        ],
        interpret=interpret,
    )(
        _scalar_tiles(blocks, ids_tile),
        _scalar_tiles(lane0, ids_tile),
        _scalar_tiles(valid_pad, ids_tile),
        bet_pad,
        packed,
    )
    return (
        acts[:batch],
        first[:batch, 0],
        sumv[:batch],
        sumsq[:batch],
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _fm_diff(spec, interpret, batch_tile, packed, bet, ids, valid):
    return _fm_impl(spec, interpret, batch_tile, packed, bet, ids, valid)


def _fm_fwd(spec, interpret, batch_tile, packed, bet, ids, valid):
    out = _fm_impl(spec, interpret, batch_tile, packed, bet, ids, valid)
    acts = out[0]
    return out, (acts, ids, valid)


def _fm_bwd_math(spec, res, cots):
    """Shared backward of both FM routes (single-device and sharded):
    pure XLA ops over the GLOBAL residuals, so the custom_vjp never
    transposes through shard_map."""
    acts, ids, valid = res
    dtype = acts.dtype
    d_acts, d_first, d_sumv, d_sumsq = cots
    # acts = (row + bet) * valid; first/sum_v/sum_sq are plain sums of
    # acts components, so every cotangent folds into one per-field
    # activation cotangent (the 2*v term is the sum-of-squares
    # jacobian) — the same quantity the unfused layer's perturbation
    # capture would receive.
    d_field = d_acts.astype(dtype)
    d_field = d_field.at[..., 0].add(d_first.astype(dtype)[:, None])
    d_field = d_field.at[..., 1:].add(
        d_sumv.astype(dtype)[:, None, :]
        + 2.0 * acts[..., 1:] * d_sumsq.astype(dtype)[:, None, :]
    )
    d_field = d_field * valid.astype(dtype)[..., None]
    d_packed = pk.grad_accumulate(
        spec,
        jnp.zeros(spec.packed_shape, dtype),
        ids.reshape((-1,)),
        d_field.reshape((-1, spec.dim)),
    )
    return (
        d_packed,
        d_field,
        jnp.zeros(ids.shape, jax.dtypes.float0),
        jnp.zeros(valid.shape, jax.dtypes.float0),
    )


def _fm_bwd(spec, interpret, batch_tile, res, cots):
    return _fm_bwd_math(spec, res, cots)


_fm_diff.defvjp(_fm_fwd, _fm_bwd)


def _sharded_fm_impl(spec, interpret, batch_tile, mesh, packed, bet, ids,
                     valid):
    """shard_map route of the FM kernel: table blocks P(model), batch
    P(data), per-shard validity = valid AND owned-here, psum combine.
    Field sums are additive with one owning shard per field, so the
    combined quadruple matches single-device up to the documented
    reduction-order tolerance (psum adds exact zeros for acts)."""
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.parallel import compile as pc
    from elasticdl_tpu.parallel.mesh import MODEL_AXIS

    axis = table_partition_axis(spec.num_blocks, mesh)
    local_spec = _shard_local_spec(spec, mesh) if axis else spec
    data = _batch_spec(ids.shape[0], mesh)

    def body(packed_l, bet_l, ids_l, valid_l):
        if axis is None:
            return _fm_impl(
                spec, interpret, batch_tile, packed_l, bet_l, ids_l,
                valid_l,
            )
        rows_local = local_spec.vocab_padded
        start = jax.lax.axis_index(MODEL_AXIS) * rows_local
        local = ids_l.astype(jnp.int32) - start
        inshard = valid_l & (local >= 0) & (local < rows_local)
        out = _fm_impl(
            local_spec, interpret, batch_tile, packed_l, bet_l,
            jnp.where(inshard, local, 0), inshard,
        )
        return tuple(jax.lax.psum(x, MODEL_AXIS) for x in out)

    return pc.shard_map_call(
        body, mesh,
        in_specs=(P(axis), data, data, data),
        out_specs=(data, data, data, data),
        check_vma=False,
    )(packed, bet, ids, valid)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _sharded_fm_diff(spec, interpret, batch_tile, mesh, packed, bet, ids,
                     valid):
    return _sharded_fm_impl(
        spec, interpret, batch_tile, mesh, packed, bet, ids, valid
    )


def _sharded_fm_fwd(spec, interpret, batch_tile, mesh, packed, bet, ids,
                    valid):
    out = _sharded_fm_impl(
        spec, interpret, batch_tile, mesh, packed, bet, ids, valid
    )
    return out, (out[0], ids, valid)


def _sharded_fm_bwd(spec, interpret, batch_tile, mesh, res, cots):
    return _fm_bwd_math(spec, res, cots)


_sharded_fm_diff.defvjp(_sharded_fm_fwd, _sharded_fm_bwd)


def fused_lookup_fm(
    spec: PackedSpec,
    packed,
    bet,
    ids,
    valid,
    *,
    mesh=None,
    interpret: Optional[bool] = None,
    batch_tile: int = DEFAULT_FM_BATCH_TILE,
):
    """Combined ``1+dim`` lookup + FM partial sums in one pass.

    ids [batch, fields] int32 (already offset), valid [batch, fields]
    bool, bet [batch, fields, dim] — the perturbation-capture variable
    (zeros at runtime; its cotangent IS the sparse gradient).  Returns
    ``(acts [batch, fields, dim], first [batch], sum_v [batch, dim-1],
    sum_sq [batch, dim-1])`` where acts lane 0 is the first-order
    weight and lanes 1..dim the FM field vector:

        second_order = 0.5 * sum_d(sum_v^2 - sum_sq)

    composable with dense-field sums (DeepFM adds its 13 projected
    numeric fields before squaring).  The activations are emitted for
    the deep tower; the FM sums accumulate in VMEM during the same
    pass, so the ``[batch, fields, dim]`` tensor is written once and
    never re-read on the FM path.  ``fm_stats_xla`` is the reference
    twin (same contract, XLA ops) — the two agree on acts bit-for-bit
    and on the sums to reduction-order tolerance (documented in
    docs/design.md).
    """
    if spec.dim < 2:
        raise ValueError(
            f"fused_lookup_fm needs a combined table of dim >= 2 "
            f"(1 linear lane + FM lanes), got dim={spec.dim}"
        )
    interpret = _use_interpret() if interpret is None else interpret
    if dispatch_route(mesh) == "shard_map":
        return _sharded_fm_diff(
            spec, interpret, batch_tile, mesh, packed, bet, ids, valid
        )
    return _fm_diff(spec, interpret, batch_tile, packed, bet, ids, valid)


def fm_stats_xla(acts):
    """The XLA twin of fused_lookup_fm's statistics: acts
    [batch, fields, dim] -> (first, sum_v, sum_sq).  Same contract;
    jnp reductions instead of the kernel's sequential field loop (the
    documented reduction-order tolerance between the two)."""
    first = jnp.sum(acts[..., 0], axis=-1)
    v = acts[..., 1:]
    return first, jnp.sum(v, axis=1), jnp.sum(v * v, axis=1)
