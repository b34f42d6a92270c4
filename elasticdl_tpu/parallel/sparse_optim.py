"""Sparse row-wise optimizers for sharded, packed embedding tables.

Parity: the reference's native optimizer kernels
(elasticdl/pkg/kernel/capi/kernel_api.cc via elasticdl/pkg/optimizer — the
Eigen-backed SGD/Adam/Momentum/AdaGrad `*SparseApply` paths the Go PS runs
on pushed IndexedSlices).  elasticdl_tpu/native/kernel_api.cc mirrors the
same math in C++ for host-side parity testing (golden values shared by
both suites).

TPU design (round 2 rewrite — the round-1 version cost 2.9x):

- Tables and slot variables live in PACKED layout (parallel/packed.py):
  [vocab/R, 128] so every memory op is full-lane.  The round-1 layout let
  XLA choose column-major [vocab, dim], making each of sparse-Adam's
  three table-sized scatters ~6.3 ms on the DeepFM step.
- Duplicate-id handling is a packed scatter-add segment-sum
  (`grad_accumulate`) — no argsort, no per-row gather/update/scatter.
- Moment/accumulator updates have TWO paths, selected per table at trace
  time (mode="auto"):
  * STREAM: one elementwise pass over the whole table with a touched-row
    mask (perfectly tiled, sharded with the table — zero communication).
    Per-step cost is O(table_size / n_devices) sequential HBM traffic,
    which for lane-packed tables beats random-access row updates by >10x
    at small table sizes; the measured DeepFM-Adam step went 30 ms ->
    2 ms on one chip (2.6M rows).
  * SCATTER (lazy, round 3): sort-free dedup of the batch ids
    (packed.dedup_representatives — two O(n) scatters plus one O(vocab)
    i32 buffer), then gather/update/scatter ONLY the touched rows.
    O(batch) instead of O(table): at the north-star 26M resident rows the
    streaming pass had collapsed DeepFM from 839k to 192k samples/s; this
    path removes the table-size term entirely.
  * FUSED (round 6, opt-in via --sparse_kernel): the scatter path's
    gather/update/scatter trips collapsed into one Pallas kernel
    (ops/sparse_embedding.fused_dedup_apply) that keeps each touched
    row in VMEM between the dedup, the slot math, and the write-back —
    none of the [n, 128] HBM intermediates the XLA formulation
    materializes.  Bit-exact vs the scatter path for adagrad/adam
    (1-ulp documented tolerance on sgd/momentum table writes — see the
    kernel docstring).
  The auto crossover (streaming below ~8 batch-sized table passes,
  scatter above) is set from measurements on the v5e chip; see
  _use_scatter below.  `auto` never selects FUSED on its own until its
  chip numbers land (BASELINE.md queued chip work).

Semantics (identical to round 1 and to the TF sparse-apply contract):
- Duplicate ids within a step contribute their SUMMED gradient and cause
  exactly one slot/row update (the reference dedups IndexedSlices the
  same way).
- Rows whose summed gradient is exactly zero (padding ids, fully-masked
  batches, cancellation) are untouched: no moment decay, no step count.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from elasticdl_tpu.parallel import packed as pk
from elasticdl_tpu.parallel.packed import PackedSpec


@dataclass(frozen=True)
class SparseOptimizer:
    """A row-wise optimizer over packed tables.

    init_slots(spec, packed_table) -> slots dict (packed layouts);
    apply(spec, packed_table, slots, ids, grads)
        -> (new_packed_table, new_slots).

    ids: int32 [n] LOGICAL row ids; grads: [n, dim] (flattened by the
    trainer).  Helpers `init_slots_logical`/`apply_logical` operate on
    [vocab, dim] arrays for tests and host-side use.
    """

    name: str
    init_slots: Callable[..., Dict[str, jnp.ndarray]]
    apply: Callable[..., Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]]
    hyperparams: dict = field(default_factory=dict)
    # apply_acc(spec, packed_table, slots, acc) -> (table, slots): one
    # optimizer step from an ALREADY-ACCUMULATED packed gradient table
    # (grad_accumulate output).  Semantically identical to apply() on the
    # batch that produced the acc — the dedup contract makes the two
    # interchangeable (pinned by test_apply_acc_matches_apply).  NOTE the
    # trainer's windowed path (ps_trainer sparse_apply_every > 1) calls
    # apply() on the chunk's CONCATENATED (ids, grads), not this — an acc
    # table carried through the step scan costs a full table copy per
    # step (BASELINE.md).  apply_acc serves host-side/offline applies and
    # callers that already hold an accumulated gradient table.
    apply_acc: Optional[Callable] = None
    # remake(mode, mesh=None) -> SparseOptimizer: this optimizer rebuilt
    # with a different apply-mode but identical hyperparameters.  The
    # trainer uses it to honor --sparse_kernel=fused on an optimizer the
    # model spec constructed with the default mode (ps_trainer can't
    # mutate a frozen dataclass whose apply closures captured the mode).
    # `mesh` selects the fused kernels' dispatch route: a multi-device
    # mesh routes fused_dedup_apply through shard_map
    # (ops/sparse_embedding.py "Sharded dispatch").
    remake: Optional[Callable[..., "SparseOptimizer"]] = None

    # -- logical-shape conveniences (tests, host tools) -----------------

    def init_slots_logical(self, table):
        spec = PackedSpec(table.shape[0], table.shape[1])
        return self.init_slots(spec, pk.pack(spec, table))

    def apply_logical(self, table, slots, ids, grads):
        """table [vocab, dim] in/out; slots must come from
        init_slots_logical (packed layouts)."""
        spec = PackedSpec(table.shape[0], table.shape[1])
        new_packed, new_slots = self.apply(
            spec, pk.pack(spec, table), slots, ids, grads
        )
        return pk.unpack(spec, new_packed), new_slots


def _t_slot_shape(spec: PackedSpec) -> tuple:
    # Per-row step counts stored as f32 BROADCAST LANES: same packed shape
    # as the table, each row's count repeated across its dim lanes.  The
    # round-2 flat [vocab_padded] i32 layout was 8x smaller but cost two
    # physical reshape copies per step (measured 3.1 ms/step/table at the
    # 26M-row probe: XLA materializes [vocab] <-> [blocks, R] relayouts)
    # and kept the t update out of the fused m/v/table pass.  Lane-shaped
    # t joins that multi-output fusion and needs no reshapes; f32 counts
    # are exact to 2^24 steps.
    return (spec.num_blocks, spec.block_width)


# Auto mode: measured on the v5e chip at the 26M-row probe (BASELINE.md):
# the streaming pass costs ~27 ns per storage block per step; the scatter
# path is count-bound at ~0.4 us per batch id (dedup buffer RMW + row
# gathers/scatters) — at num_blocks = 15 x n_ids it still measured 2x
# SLOWER than streaming (91 ms vs ~45 ms per step).  Require a wide
# margin before switching: scatter only pays for huge-vocab/small-batch
# regimes (e.g. online-style batches against Criteo-scale tables).
_SCATTER_CROSSOVER = 64


def _use_scatter(spec: PackedSpec, n_ids: int, mode: str) -> bool:
    if mode == "scatter":
        return True
    if mode == "stream":
        return False
    if mode != "auto":
        raise ValueError(f"mode must be auto|stream|scatter, got {mode!r}")
    return spec.num_blocks > _SCATTER_CROSSOVER * n_ids


def select_mode(spec: PackedSpec, n_ids: int, mode: str) -> str:
    """'stream' | 'scatter' | 'fused' for one apply.  `fused` routes the
    whole update through the Pallas dedup+apply kernel
    (ops/sparse_embedding.py); `auto` keeps the measured stream/scatter
    crossover and never picks fused on its own — the fused kernels'
    chip numbers are queued driver work (BASELINE.md), so fused stays
    opt-in (--sparse_kernel) until the evidence lands."""
    if mode == "fused":
        return "fused"
    return (
        "scatter" if _use_scatter(spec, n_ids, mode) else "stream"
    )


def _scoped(name: str, fn):
    """`fn` traced under `jax.named_scope(name)`: the device ops it
    emits carry the name in their `op_name` (metadata only)."""

    def scoped(*args):
        with jax.named_scope(name):
            return fn(*args)

    return scoped


def _fused_apply(kind: str, hyper: dict, mesh=None):
    """apply() via the fused Pallas dedup+apply kernel.  Import at
    construction time (host), not trace time.  `mesh` routes the
    kernel's dispatch (single-device pallas_call vs shard_map over a
    multi-device mesh)."""
    from elasticdl_tpu.ops import sparse_embedding as ske

    def apply(spec, packed_table, slots, ids, grads):
        return ske.fused_dedup_apply(
            spec, kind, hyper, packed_table, slots, ids, grads, mesh=mesh
        )

    return apply


def _dual_apply(mode: str, stream_apply_acc, scatter_apply,
                fused_apply=None):
    """The apply dispatcher shared by every slotted optimizer: streaming
    (grad_accumulate + the acc-consuming core), touched-rows scatter, or
    the fused Pallas kernel — chosen per select_mode."""

    def stream_apply(spec, packed_table, slots, ids, grads):
        acc = pk.grad_accumulate(spec, packed_table, ids, grads)
        return stream_apply_acc(spec, packed_table, slots, acc)

    impls = {
        "stream": stream_apply,
        "scatter": scatter_apply,
        "fused": fused_apply,
    }

    def apply(spec, packed_table, slots, ids, grads):
        impl = impls[select_mode(spec, ids.shape[0], mode)]
        if impl is None:
            raise ValueError("this optimizer has no fused kernel path")
        return impl(spec, packed_table, slots, ids, grads)

    return apply


def sgd(learning_rate: float = 0.01, mode: str = "auto",
        mesh=None) -> SparseOptimizer:
    lr = learning_rate
    hyper = {"learning_rate": lr}

    def init_slots(spec, packed_table):
        return {}

    def scatter_or_stream_apply(spec, packed_table, slots, ids, grads):
        # SGD is linear in the gradient, so one scatter-add IS both the
        # stream and the scatter path — no dedup needed.
        return pk.scatter_add(spec, packed_table, ids, -lr * grads), slots

    fused = _fused_apply("sgd", hyper, mesh)

    def apply(spec, packed_table, slots, ids, grads):
        if select_mode(spec, ids.shape[0], mode) == "fused":
            return fused(spec, packed_table, slots, ids, grads)
        return scatter_or_stream_apply(spec, packed_table, slots, ids, grads)

    def apply_acc(spec, packed_table, slots, acc):
        # SGD is linear in the gradient, so the windowed apply is EXACTLY
        # the sum of the per-step applies.
        return packed_table - lr * acc, slots

    return SparseOptimizer(
        "sgd", init_slots, apply, hyper, apply_acc,
        remake=lambda m, mesh=None: sgd(learning_rate, mode=m, mesh=mesh),
    )


def momentum(
    learning_rate: float = 0.01,
    mu: float = 0.9,
    nesterov: bool = False,
    mode: str = "auto",
    mesh=None,
) -> SparseOptimizer:
    lr = learning_rate

    def init_slots(spec, packed_table):
        return {"momentum": jnp.zeros_like(packed_table)}

    def stream_apply_acc(spec, packed_table, slots, acc):
        touched = pk.broadcast_rows(spec, pk.touched_mask(spec, acc)).astype(
            packed_table.dtype
        )
        v_new = touched * (mu * slots["momentum"] + acc) + (1 - touched) * slots[
            "momentum"
        ]
        step = (mu * v_new + acc) if nesterov else v_new
        new_table = packed_table - lr * touched * step
        return new_table, {"momentum": v_new}

    def scatter_apply(spec, packed_table, slots, ids, grads):
        uids, gsum, touched = pk.dedup_representatives(spec, ids, grads)
        tch = touched.astype(packed_table.dtype)[:, None]  # [n, 1]
        gsum = gsum * tch
        v_rows = pk.lookup(spec, slots["momentum"], uids)
        v_new_rows = mu * v_rows + gsum
        step = (mu * v_new_rows + gsum) if nesterov else v_new_rows
        new_v = pk.scatter_add(spec, slots["momentum"], uids,
                               (v_new_rows - v_rows) * tch)
        new_table = pk.scatter_add(spec, packed_table, uids, -lr * tch * step)
        return new_table, {"momentum": new_v}

    hyper = {"learning_rate": lr, "momentum": mu, "nesterov": nesterov}
    return SparseOptimizer(
        "momentum", init_slots,
        _dual_apply(mode, stream_apply_acc, scatter_apply,
                    _fused_apply("momentum", hyper, mesh)),
        hyper,
        stream_apply_acc,
        remake=lambda m, mesh=None: momentum(
            learning_rate, mu, nesterov, mode=m, mesh=mesh
        ),
    )


def adagrad(
    learning_rate: float = 0.01, epsilon: float = 1e-7, mode: str = "auto",
    mesh=None,
) -> SparseOptimizer:
    lr = learning_rate

    def init_slots(spec, packed_table):
        return {"accumulator": jnp.zeros_like(packed_table)}

    def stream_apply_acc(spec, packed_table, slots, acc):
        new_acc = slots["accumulator"] + acc * acc
        update = -lr * acc / (jnp.sqrt(new_acc) + epsilon)
        return packed_table + update, {"accumulator": new_acc}

    def scatter_apply(spec, packed_table, slots, ids, grads):
        uids, gsum, touched = pk.dedup_representatives(spec, ids, grads)
        tch = touched.astype(packed_table.dtype)[:, None]
        gsum = gsum * tch
        acc_rows = pk.lookup(spec, slots["accumulator"], uids)
        new_acc_rows = acc_rows + gsum * gsum
        update = -lr * gsum / (jnp.sqrt(new_acc_rows) + epsilon)
        new_acc = pk.scatter_add(spec, slots["accumulator"], uids, gsum * gsum)
        new_table = pk.scatter_add(spec, packed_table, uids, update)
        return new_table, {"accumulator": new_acc}

    hyper = {"learning_rate": lr, "epsilon": epsilon}
    return SparseOptimizer(
        "adagrad", init_slots,
        _dual_apply(mode, stream_apply_acc, scatter_apply,
                    _fused_apply("adagrad", hyper, mesh)),
        hyper,
        stream_apply_acc,
        remake=lambda m, mesh=None: adagrad(
            learning_rate, epsilon, mode=m, mesh=mesh
        ),
    )


def adam(
    learning_rate: float = 0.001,
    beta_1: float = 0.9,
    beta_2: float = 0.999,
    epsilon: float = 1e-8,
    mode: str = "auto",
    bias_correction: str = "per_row",
    mesh=None,
) -> SparseOptimizer:
    """Sparse Adam.

    bias_correction:
    - "per_row" (default): each row's correction uses ITS OWN touch count
      (lazy semantics; matches the golden native-kernel contract).  Costs
      a table-sized `t` slot plus its share of the streaming pass.
    - "global": correction uses one shared apply counter — what the
      reference's Go Adam actually does (†pkg/optimizer adam with a global
      step; TF's Adam on sparse grads behaves the same).  Rows first
      touched late are slightly over-corrected, and the table-sized `t`
      slot disappears — at the 26M-row probe that is 1.66 GB of HBM and
      ~3 ms/step of streaming traffic.
    """
    lr = learning_rate
    if bias_correction not in ("per_row", "global"):
        raise ValueError(
            f"bias_correction must be per_row|global, got {bias_correction!r}"
        )
    per_row = bias_correction == "per_row"

    def init_slots(spec, packed_table):
        slots = {
            "m": jnp.zeros_like(packed_table),
            "v": jnp.zeros_like(packed_table),
        }
        if per_row:
            # Lane-broadcast f32 layout — see _t_slot_shape.
            slots["t"] = jnp.zeros(_t_slot_shape(spec), jnp.float32)
        else:
            slots["t_global"] = jnp.zeros((), jnp.float32)
        return slots

    def stream_apply_acc(spec, packed_table, slots, acc):
        touched = pk.broadcast_rows(spec, pk.touched_mask(spec, acc)).astype(
            packed_table.dtype
        )
        new_slots = {}
        if per_row:
            # Pad lanes stay zero (scatter mode's expand_updates zero-pads).
            t_new = slots["t"] + touched * pk.real_lane_mask(
                spec, packed_table.dtype
            )
            t_rows = jnp.maximum(t_new, 1.0)
            new_slots["t"] = t_new
        else:
            t_rows = slots["t_global"] + 1.0
            new_slots["t_global"] = t_rows
        m_new = touched * (beta_1 * slots["m"] + (1 - beta_1) * acc) + (
            1 - touched
        ) * slots["m"]
        v_new = touched * (beta_2 * slots["v"] + (1 - beta_2) * acc * acc) + (
            1 - touched
        ) * slots["v"]
        m_hat = m_new / (1 - beta_1 ** t_rows)
        v_hat = v_new / (1 - beta_2 ** t_rows)
        update = -lr * touched * m_hat / (jnp.sqrt(v_hat) + epsilon)
        new_slots["m"] = m_new
        new_slots["v"] = v_new
        return packed_table + update, new_slots

    def scatter_apply(spec, packed_table, slots, ids, grads):
        uids, gsum, touched = pk.dedup_representatives(spec, ids, grads)
        tch = touched.astype(packed_table.dtype)[:, None]
        gsum = gsum * tch
        m_rows = pk.lookup(spec, slots["m"], uids)
        v_rows = pk.lookup(spec, slots["v"], uids)
        new_slots = {}
        if per_row:
            t_rows = pk.lookup(spec, slots["t"], uids)[:, :1]  # [n, 1]
            tr = jnp.maximum(t_rows + tch, 1.0)
            new_slots["t"] = pk.scatter_add(
                spec, slots["t"], uids,
                jnp.broadcast_to(tch, (tch.shape[0], spec.dim)),
            )
        else:
            t_global = slots["t_global"] + 1.0
            tr = t_global
            new_slots["t_global"] = t_global
        m_new_rows = beta_1 * m_rows + (1 - beta_1) * gsum
        v_new_rows = beta_2 * v_rows + (1 - beta_2) * gsum * gsum
        m_hat = m_new_rows / (1 - beta_1 ** tr)
        v_hat = v_new_rows / (1 - beta_2 ** tr)
        update = -lr * tch * m_hat / (jnp.sqrt(v_hat) + epsilon)
        new_slots["m"] = pk.scatter_add(spec, slots["m"], uids,
                                        (m_new_rows - m_rows) * tch)
        new_slots["v"] = pk.scatter_add(spec, slots["v"], uids,
                                        (v_new_rows - v_rows) * tch)
        new_table = pk.scatter_add(spec, packed_table, uids, update)
        return new_table, new_slots

    hyper = {"learning_rate": lr, "beta_1": beta_1, "beta_2": beta_2,
             "epsilon": epsilon, "bias_correction": bias_correction}
    stream_apply_acc = _scoped("sparse_adam", stream_apply_acc)
    scatter_apply = _scoped("sparse_adam", scatter_apply)
    return SparseOptimizer(
        "adam", init_slots,
        _dual_apply(mode, stream_apply_acc, scatter_apply,
                    _fused_apply("adam", hyper, mesh)),
        hyper,
        stream_apply_acc,
        remake=lambda m, mesh=None: adam(
            learning_rate, beta_1, beta_2, epsilon, mode=m,
            bias_correction=bias_correction, mesh=mesh,
        ),
    )


_BY_NAME = {"sgd": sgd, "momentum": momentum, "adagrad": adagrad, "adam": adam}


def by_name(name: str, **hyperparams) -> SparseOptimizer:
    if name not in _BY_NAME:
        raise ValueError(f"Unknown sparse optimizer {name!r}; have {sorted(_BY_NAME)}")
    return _BY_NAME[name](**hyperparams)
