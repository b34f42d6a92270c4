"""Sharded-embedding trainer: ParameterServerStrategy, compiled.

Parity: the reference's PS-mode training stack (SURVEY.md §3.3) — worker
pulls dense params + embedding rows from Go PS pods, computes grads, and
pushes dense grads + IndexedSlices back for the PS's Eigen sparse kernels.
TPU-native: the PS dissolves into the step function.

- Dense params: replicated over the mesh, optax-updated (the PS's dense
  optimizer path).
- Embedding tables: ONE array per table, vocab-sharded across ALL mesh
  devices' HBM (the PS-pod partitioning, minus the gRPC hop).  Lookups are
  gathers on the sharded operand; XLA lowers them to local gathers + ICI
  collectives inside the same program as the matmuls.
- Sparse gradients: captured at each Embedding layer's perturbation point
  (layers/embedding.py) — never a dense [vocab, dim] cotangent — and
  scatter-applied by the sparse row-wise optimizers (parallel/sparse_optim).

Same public surface as DataParallelTrainer, so the worker runtimes drive
either interchangeably.
"""

from __future__ import annotations

import json
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.checkpoint.saver import tree_nbytes
from elasticdl_tpu.checkpoint.sharded import own_shards
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.obs import tracing
from elasticdl_tpu.layers.embedding import (
    IDS_COLLECTION,
    OOV_COLLECTION,
    PERTURBATIONS,
    SPECS_COLLECTION,
    VOCAB_AXIS,
)
from elasticdl_tpu.parallel import compile as pc
from elasticdl_tpu.parallel import packed as pk
from elasticdl_tpu.parallel.packed import PackedSpec
from elasticdl_tpu.parallel import sharding as shd
from elasticdl_tpu.parallel.dp_trainer import per_example_loss_fn
from elasticdl_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from elasticdl_tpu.parallel.sparse_optim import SparseOptimizer, sgd
from elasticdl_tpu.parallel.trainer import model_apply, unbox_partitioned

logger = get_logger("parallel.ps_trainer")

# --sparse_apply_every=auto resolution (round-5 VERDICT #5): strict
# per-step apply up to this many resident embedding rows, the windowed
# W below above it.  Threshold = where strict mode's per-step
# table-streaming pass starts dominating (the BASELINE.md table-scale
# probe: ~3.5x at 26M rows) — deliberately the same number as
# model_zoo/deepfm's SPLIT_TABLE_ROWS so a layout-aware model and the
# trainer resolve `auto` consistently from the same row count.  W=32 is
# the round-4 "largest safe W" (convergence within noise of strict at
# both tested scales, BASELINE.md "Windowed-apply convergence").
AUTO_APPLY_TABLE_ROWS = 10_000_000
AUTO_APPLY_W = 32


class PSTrainState(NamedTuple):
    step: jnp.ndarray
    params: Any           # dense params; table leaves hold scalar placeholders
    opt_state: Any
    model_state: Any      # batch_stats etc.
    tables: Dict[str, jnp.ndarray]          # path-key -> [vocab, dim]
    slots: Dict[str, Dict[str, jnp.ndarray]]  # path-key -> optimizer slots


def _path_key(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


class ShardedEmbeddingTrainer:
    """PS-mode trainer over an N-device (data, model) mesh."""

    def __init__(
        self,
        model,
        loss_fn: Callable,
        optimizer: optax.GradientTransformation,
        mesh,
        embedding_optimizer: Optional[SparseOptimizer] = None,
        seed: int = 0,
        sparse_apply_every=1,
        sparse_kernel: Optional[str] = None,
    ):
        self._model = model
        self._loss_fn = loss_fn
        self._per_example_loss = per_example_loss_fn(loss_fn)
        self._tx = optimizer
        if embedding_optimizer is None:
            logger.warning(
                "No embedding_optimizer in the model spec; defaulting to "
                "sparse SGD(0.01) for embedding tables"
            )
            embedding_optimizer = sgd(0.01)
        self._emb_tx = embedding_optimizer
        # --sparse_kernel: 'fused' swaps the optimizer's apply onto the
        # Pallas dedup+apply kernel (ops/sparse_embedding.py); the
        # LOOKUP side rides the model's own Embedding layers (the zoo
        # threads the flag via model_params; worker main also sets the
        # process default so un-threaded models follow).  None = the
        # process default; 'auto' resolves there (xla until the fused
        # chip numbers land — BASELINE.md queued chip work).
        from elasticdl_tpu.ops import sparse_embedding as ske

        self._sparse_kernel_requested = sparse_kernel or ske.default_kernel()
        resolved = ske.resolve_kernel(sparse_kernel)
        # Fused dispatch route: single_device keeps the plain pallas_call
        # path; a multi-device mesh routes every fused kernel through
        # shard_map (ops/sparse_embedding.py "Sharded dispatch") —
        # tables shard over the `model` axis, ids route to their owning
        # shard, and the combine is a psum.  The v1 multi-device config
        # ERROR (pallas_call has no SPMD partitioning rule) is gone:
        # shard_map IS the partitioning rule.
        self._sparse_route = ske.dispatch_route(mesh)
        if resolved == "fused":
            if self._emb_tx.remake is None:
                logger.warning(
                    "sparse_kernel=fused but embedding optimizer %r has "
                    "no remake hook; its apply keeps its constructed "
                    "mode (lookups still run fused)",
                    self._emb_tx.name,
                )
            else:
                self._emb_tx = self._remake_fused(self._emb_tx, mesh)
            if (
                self._sparse_route == "shard_map"
                and ske.dispatch_mesh() is not mesh
            ):
                # The trainer cannot introspect the MODEL's Embedding
                # layers (created inside @nn.compact), so it cannot
                # verify they carry this mesh.  A layer left at
                # mesh=None in a multi-device job would trace an
                # unpartitionable pallas_call into the SPMD step — the
                # failure the old config error guarded.  worker/main
                # registers the process default; direct constructions
                # must thread mesh= into the model.  Leave the
                # breadcrumb the eventual compile error won't.
                logger.warning(
                    "sparse_kernel=fused on a %d-device mesh: the fused "
                    "kernels dispatch through shard_map ONLY where the "
                    "model's Embedding layers were built with this mesh "
                    "(mesh= field, or ske.set_dispatch_mesh as "
                    "worker/main does).  If a layer was built without "
                    "it, the step will fail to compile — docs/design.md "
                    "'Declarative sharding'.",
                    int(mesh.devices.size),
                )
        self._sparse_kernel = resolved
        if sparse_apply_every == "auto":
            # Resolved at ensure_initialized, the first point the
            # resident table row count is known (AUTO_APPLY_TABLE_ROWS
            # below).  None means "unresolved": `apply_every` reads 1
            # until then, and the worker loop reads it again right after
            # ensure_initialized.
            self._sparse_apply_every = None
        else:
            self._sparse_apply_every = max(1, int(sparse_apply_every))
        self._mesh = mesh
        self._seed = seed
        self._dp = shd.data_axis_size(mesh)
        self._state: Optional[PSTrainState] = None
        self._host_step = 0
        # Device-side OOV scalars, one per dispatched step/window; summed
        # and drained host-side by consume_oov_count().
        self._pending_oov: list = []
        self._perturb_shapes: Dict[str, Any] = {}
        self._pending_restore: Optional[PSTrainState] = None
        self._pending_sharded_restore: Optional[Tuple[Any, int]] = None
        self._train_step = None  # jitted lazily once shardings are known
        self._eval_step = None
        self.leaf_cutter = None  # (with the step programs)

    def _remake_fused(self, emb_tx: SparseOptimizer, mesh):
        """Rebuild the optimizer in fused mode, threading the dispatch
        mesh when its remake hook accepts one (signature-inspected — no
        exception swallowing).  A pre-mesh hook is fine on a single
        device but a hard ERROR on a multi-device mesh: a mesh-less
        fused apply over model-sharded tables would trace an
        unpartitionable pallas_call into the SPMD step while the
        journal reports route=shard_map — the misattribution the
        journal event exists to prevent."""
        import inspect

        try:
            params = inspect.signature(emb_tx.remake).parameters
            accepts_mesh = "mesh" in params or any(
                p.kind == p.VAR_KEYWORD for p in params.values()
            )
        except (TypeError, ValueError):
            accepts_mesh = False
        if accepts_mesh:
            return emb_tx.remake("fused", mesh=mesh)
        if self._sparse_route == "shard_map":
            raise ValueError(
                f"sparse_kernel=fused on a {int(mesh.devices.size)}-"
                f"device mesh needs an embedding optimizer whose remake "
                f"hook accepts mesh= (got {emb_tx.name!r} with a "
                "mode-only hook) — the fused apply must dispatch "
                "through shard_map to run over model-sharded tables "
                "(docs/design.md 'Declarative sharding')"
            )
        return emb_tx.remake("fused")

    # -- public surface (mirrors DataParallelTrainer) -------------------

    @property
    def mesh(self):
        return self._mesh

    @property
    def apply_every(self) -> int:
        """Train steps between sparse-table applies; 1 while `auto` is
        still unresolved (before ensure_initialized)."""
        return self._sparse_apply_every or 1

    def jitted_entrypoints(self) -> dict:
        """Current jitted entrypoints by name for the step-anatomy
        retrace watcher (obs/stepstats.py; see DataParallelTrainer)."""
        return {
            "ps_train_step": self._train_step,
            "ps_train_window": getattr(self, "_train_window", None),
            "ps_eval_step": self._eval_step,
        }

    def local_block(self, per_rank_batch: int) -> int:
        local_devices = max(1, self._dp // jax.process_count())
        return -(-per_rank_batch // local_devices) * local_devices

    @property
    def state(self) -> Optional[PSTrainState]:
        return self._state

    @state.setter
    def state(self, value: PSTrainState):
        value = PSTrainState(*value)
        if self._state is None:
            # Restore before the first batch (checkpoint restore at worker
            # boot): applied inside ensure_initialized once the model's
            # structure/shardings exist.
            self._pending_restore = value
            self._host_step = int(np.asarray(jax.device_get(value.step)))
            return
        self._state = self._place_state(jax.device_get(value))
        self._host_step = int(np.asarray(jax.device_get(value.step)))

    @property
    def step(self) -> int:
        return self._host_step

    # -- sharding layout (declarative rule table, parallel/compile.py) --

    def _partition_rules(self) -> pc.RuleTable:
        """PS-mode placement policy as a rule table: dense state (step,
        params, opt_state, model_state) replicates; embedding tables
        and their table-shaped optimizer slots shard on dim0 (storage
        blocks).  The block placement is the ONE shape-aware entry:

        - xla engine: blocks across the WHOLE mesh (`data` x `model`) —
          maximum HBM capacity, the analogue of partitioning one table
          over every PS pod; tables too small to split evenly replicate
          (they are by definition tiny).
        - fused engine: blocks over the `model` axis only (replicated
          across `data`) — the layout the shard_map'd kernel dispatch
          declares (ops/sparse_embedding.table_partition_axis), so the
          per-shard pallas bodies see exactly their resident blocks
          with no per-step resharding.

        Scalar slots (adam's global-bias counter) replicate via the
        table's scalar default."""
        from jax.sharding import PartitionSpec as P

        from elasticdl_tpu.ops import sparse_embedding as ske

        fused = self._sparse_kernel == "fused"
        mesh = self._mesh
        total = int(mesh.devices.size)

        def table_blocks(path, shape):
            if fused:
                axis = ske.table_partition_axis(shape[0], mesh)
                if axis is None:
                    return P()
                return P(axis, *([None] * (len(shape) - 1)))
            if shape[0] % total != 0:
                return P()
            return P((DATA_AXIS, MODEL_AXIS), *([None] * (len(shape) - 1)))

        return pc.RuleTable(
            [
                pc.Rule(r"^(tables|slots)(/|$)", table_blocks),
                pc.Rule(".*", P()),
            ],
            name="ps-fused" if fused else "ps-xla",
        )

    def _plan(self) -> pc.CompilePlan:
        return pc.CompilePlan(
            self._mesh, self._partition_rules(), trainer="ps_trainer"
        )

    def _state_shardings(self, state: PSTrainState, plan=None):
        plan = plan or self._plan()
        tree = plan.state_shardings({
            "step": state.step,
            "params": state.params,
            "opt_state": state.opt_state,
            "model_state": state.model_state,
            "tables": state.tables,
            "slots": state.slots,
        })
        return PSTrainState(
            step=tree["step"],
            params=tree["params"],
            opt_state=tree["opt_state"],
            model_state=tree["model_state"],
            tables=tree["tables"],
            slots=tree["slots"],
        )

    @staticmethod
    def _place_leaf(x, s):
        return shd.put(x, s)

    def _place_state(self, state: PSTrainState) -> PSTrainState:
        return shd.put(state, self._state_shardings(state))

    # -- initialization -------------------------------------------------

    def ensure_initialized(self, features) -> PSTrainState:
        if self._state is not None:
            return self._state
        # `state.init`: the shapes, then the one program that births the
        # state in its layout (or the restore in its place:
        # `checkpoint.restore.load` nests inside).
        with tracing.span("state.init", trainer="ps_trainer"):
            self._init_state(features)
        self._compile_steps()
        return self._state

    def _make_state(self, rng, features) -> PSTrainState:
        """State constructor, pure in its arrays: it runs under jit, so
        that the state is BORN in its layout (`out_shardings`) by one
        program, and under `jax.eval_shape`, which gives the state's
        tree without a FLOP.

        What the host keeps of the model besides arrays is static (the
        tables' paths, their packed specs, the perturbations' shapes)
        and is noted on the trainer as a trace passes here: the
        `eval_shape` of `_init_state` is the first, so a program loaded
        from the store, or a restore, needs no other."""
        variables = dict(self._model.init(rng, features))
        params_boxed = variables.pop("params")
        variables.pop(IDS_COLLECTION, None)
        variables.pop(OOV_COLLECTION, None)
        perturbs = variables.pop(PERTURBATIONS, {})
        specs_tree = variables.pop(SPECS_COLLECTION, {})

        # Split tables (VOCAB_AXIS-marked Partitioned leaves) from dense.
        tables: Dict[str, jnp.ndarray] = {}
        table_paths = {}

        def split(path, leaf):
            if (
                isinstance(leaf, nn.Partitioned)
                and leaf.names
                and leaf.names[0] == VOCAB_AXIS
            ):
                key = _path_key(path)
                tables[key] = leaf.unbox()
                table_paths[key] = tuple(getattr(p, "key", p) for p in path)
                return jnp.zeros((), jnp.float32)  # structure placeholder
            return leaf.unbox() if isinstance(leaf, nn.Partitioned) else leaf

        flat = jax.tree_util.tree_flatten_with_path(
            params_boxed,
            is_leaf=lambda x: isinstance(x, nn.Partitioned),
        )
        params = jax.tree_util.tree_unflatten(
            flat[1], [split(p, v) for p, v in flat[0]]
        )
        table_specs: Dict[str, PackedSpec] = {}
        for key, module_path in table_paths.items():
            # Sown as a NumPy constant (layers/embedding.py): concrete
            # here, whatever traces this function.
            spec = _collection_get(specs_tree, module_path[:-1], "spec")
            table_specs[key] = PackedSpec(*spec.tolist())  # noqa-invariant: jit-host-sync (a NumPy constant of the module, never a tracer: nothing to sync)
            assert tables[key].shape == table_specs[key].packed_shape, (
                key, tables[key].shape, table_specs[key],
            )
        self._table_paths = table_paths
        self._table_specs = table_specs
        self._perturb_shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            unbox_partitioned(perturbs),
        )
        return PSTrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=self._tx.init(params),
            model_state=unbox_partitioned(variables),
            tables=tables,
            slots={
                key: self._emb_tx.init_slots(table_specs[key], table)
                for key, table in tables.items()
            },
        )

    def _init_state(self, features) -> None:
        rng = jax.random.PRNGKey(self._seed)
        # Init with the GLOBAL batch shape (local rows x process count):
        # perturbation variables take their shape from init, and apply runs
        # on the assembled global batch.  Zeros keep init identical on
        # every rank (param init only consumes shapes + rng); host
        # zeros, so that making them compiles nothing.
        procs = jax.process_count()
        features = jax.tree.map(
            lambda x: np.zeros(
                (np.shape(x)[0] * procs,) + tuple(np.shape(x)[1:]),
                np.asarray(x).dtype,
            ),
            features,
        )
        # Structure first (no FLOPs, no memory), the shardings from it,
        # then ONE program whose out_shardings birth the state placed:
        # no table is computed on the way, fetched to the host or put
        # back.  Whatever restores supplies every value, so it takes the
        # shape tree as its template and the init is neither run nor
        # compiled.
        shapes = jax.eval_shape(self._make_state, rng, features)
        plan = self._plan()
        shardings = self._state_shardings(shapes, plan)
        if self._pending_sharded_restore is not None:
            self._state = self._restore_sharded(shapes, shardings)
        elif self._pending_restore is not None:
            self._state = shd.put(self._pending_restore, shardings)
            self._pending_restore = None
        else:
            self._state = plan.compile(
                self._make_state, name="ps_init", out_shardings=shardings
            )(rng, features)
        n_dense = sum(
            int(np.prod(p.shape)) for p in jax.tree.leaves(shapes.params)
        )
        n_table = sum(int(np.prod(t.shape)) for t in shapes.tables.values())
        total_rows = sum(
            spec.vocab_size for spec in self._table_specs.values()
        )
        if self._sparse_apply_every is None:
            self._sparse_apply_every = (
                1 if total_rows <= AUTO_APPLY_TABLE_ROWS else AUTO_APPLY_W
            )
            logger.info(
                "sparse_apply_every=auto -> %d (%.1fM resident embedding "
                "rows %s the %dM strict/windowed threshold)",
                self._sparse_apply_every,
                total_rows / 1e6,
                "<=" if total_rows <= AUTO_APPLY_TABLE_ROWS else ">",
                AUTO_APPLY_TABLE_ROWS // 1_000_000,
            )
        if self._sparse_apply_every == 1 and total_rows > AUTO_APPLY_TABLE_ROWS:
            # Same honesty contract as the attention VMEM advice: strict
            # per-step apply at this scale pays table-sized streaming
            # passes every step — measured ~3x slower than the windowed
            # config at the 26M-row probe, and the windowed semantics
            # are convergence-validated (BASELINE.md "Windowed-apply
            # convergence": peak held-out AUC at W=16 within 0.003 of
            # strict).  Say so instead of silently running slow.
            logger.warning(
                "Strict per-step sparse apply with %.1fM embedding rows "
                "resident: --sparse_apply_every=16 runs ~3x faster at "
                "this scale with convergence measured equal at peak "
                "(docs/tutorial.md 'Large embedding tables'); strict "
                "mode stays exact-per-step if that is what you need",
                total_rows / 1e6,
            )
        logger.info(
            "Initialized PS-mode model: %d dense params (replicated), "
            "%d embedding-table params in %d table(s) sharded over %d "
            "device(s) [%s, sparse_kernel=%s]",
            n_dense,
            n_table,
            len(shapes.tables),
            self._mesh.devices.size,
            self._emb_tx.name,
            self._sparse_kernel,
        )
        # What the placement DID, not what the mesh promises: the bytes
        # of table storage each local device holds (chip_smoke.py's
        # four-chip run reads this line).
        table_bytes: Dict[int, int] = {}
        for table in self._state.tables.values():
            for shard in table.addressable_shards:
                table_bytes[shard.device.id] = (
                    table_bytes.get(shard.device.id, 0) + shard.data.nbytes
                )
        logger.info(
            "Embedding-table bytes per local device: %s",
            json.dumps({str(k): v for k, v in sorted(table_bytes.items())}),
        )
        # Journal the kernel decision (host-side, init-time — the obs
        # plane never rides the traced step): postmortems and the
        # bench-regress audit trail need to know WHICH engine a number
        # was measured on (schema: scripts/validate_journal.py).
        from elasticdl_tpu import obs

        # `route` replaces the removed multi-device downgrade warning:
        # for the fused engine it names the dispatch the kernels take
        # (single_device pallas_call vs shard_map over the mesh); the
        # xla engine always runs the SPMD partitioner ('xla').
        obs.journal().record(
            "sparse_kernel_selected",
            kernel=self._sparse_kernel,
            requested=self._sparse_kernel_requested,
            route=(
                self._sparse_route if self._sparse_kernel == "fused"
                else "xla"
            ),
            optimizer=self._emb_tx.name,
            tables=len(shapes.tables),
            table_rows=total_rows,
        )

    def _compile_steps(self):
        plan = self._plan()
        repl = plan.replicated()
        batch = shd.batch_sharded(self._mesh)
        window = shd.window_sharded(self._mesh)
        state_shardings = self._state_shardings(self._state, plan)
        self._train_step = plan.compile(
            self._train_step_impl,
            name="ps_train_step",
            in_shardings=(state_shardings, batch, batch, batch),
            out_shardings=(state_shardings, (repl, repl)),
            donate_argnums=(0,),
        )
        self._train_window = plan.compile(
            self._train_window_impl,
            name="ps_train_window",
            in_shardings=(state_shardings, window, window, window),
            out_shardings=(state_shardings, (repl, repl)),
            donate_argnums=(0,),
        )
        self._eval_step = plan.compile(
            self._eval_step_impl,
            name="ps_eval_step",
            in_shardings=(state_shardings, batch),
            out_shardings=batch,
        )
        self.leaf_cutter = pc.leaf_cutter(
            plan, own_shards(self._sharded_arrays(self._state))[1]
        )

    # -- compiled steps -------------------------------------------------

    def _merge_params(self, params, tables):
        flat = jax.tree_util.tree_flatten_with_path(params)
        merged = [
            tables.get(_path_key(path), leaf) for path, leaf in flat[0]
        ]
        return jax.tree_util.tree_unflatten(flat[1], merged)

    def _zero_perturbations(self):
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), self._perturb_shapes
        )

    def _forward_backward(self, state: PSTrainState, features, labels, mask):
        """One fwd/bwd: loss, mutated collections, dense + perturbation
        (sparse embedding) gradients."""
        mutable_keys = list(state.model_state.keys()) + [
            IDS_COLLECTION, OOV_COLLECTION,
        ]

        def compute_loss(params, perturbs):
            full_params = self._merge_params(params, state.tables)
            variables = {
                "params": full_params,
                PERTURBATIONS: perturbs,
                **state.model_state,
            }
            outputs, muts = model_apply(
                self._model, variables, features, train=True,
                mutable=mutable_keys,
            )
            losses = self._per_example_loss(labels, outputs)
            loss = jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            return loss, muts

        with jax.named_scope("fwd_bwd"):
            (loss, muts), (dense_grads, perturb_grads) = jax.value_and_grad(
                compute_loss, argnums=(0, 1), has_aux=True
            )(state.params, self._zero_perturbations())
        return loss, muts, dense_grads, perturb_grads

    @staticmethod
    def _oov_total(muts) -> jnp.ndarray:
        """Sum of the per-Embedding OOV counts sown this apply (scalar
        int32; zero when the model has no Embedding layers)."""
        total = jnp.zeros((), jnp.int32)
        for leaf in jax.tree.leaves(muts.get(OOV_COLLECTION, {})):
            total = total + jnp.sum(jnp.asarray(leaf))
        return total

    def _sparse_batches(self, muts, perturb_grads, tables):
        """Per table: (spec, flat ids, flat grads) from the sown id
        collection + perturbation cotangents."""
        ids_tree = muts.get(IDS_COLLECTION, {})
        for key, module_path in self._table_paths.items():
            prefix = module_path[:-1]  # drop the 'embedding' param name
            spec = self._table_specs[key]
            ids = _collection_get(ids_tree, prefix, "ids")
            grad = _collection_get(perturb_grads, prefix, "bet")
            flat_ids = ids.reshape((-1,))
            flat_grads = grad.reshape((-1, spec.dim)).astype(tables[key].dtype)
            yield key, spec, flat_ids, flat_grads

    def _dense_and_state(self, state, muts, dense_grads):
        with jax.named_scope("dense_update"):
            updates, new_opt_state = self._tx.update(
                dense_grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
        new_model_state = (
            {k: muts[k] for k in state.model_state.keys() if k in muts}
            or state.model_state
        )
        return new_params, new_opt_state, new_model_state

    def _train_step_impl(self, state: PSTrainState, features, labels, mask):
        loss, muts, dense_grads, perturb_grads = self._forward_backward(
            state, features, labels, mask
        )
        new_params, new_opt_state, new_model_state = self._dense_and_state(
            state, muts, dense_grads
        )
        # Sparse apply per table: pair sown ids with perturbation grads.
        new_tables = dict(state.tables)
        new_slots = dict(state.slots)
        for key, spec, flat_ids, flat_grads in self._sparse_batches(
            muts, perturb_grads, new_tables
        ):
            with jax.named_scope("sparse_apply"):
                new_tables[key], new_slots[key] = self._emb_tx.apply(
                    spec, new_tables[key], new_slots[key],
                    flat_ids, flat_grads,
                )
        return (
            PSTrainState(
                state.step + 1,
                new_params,
                new_opt_state,
                new_model_state,
                new_tables,
                new_slots,
            ),
            (loss, self._oov_total(muts)),
        )

    def _train_chunk_impl(self, state: PSTrainState, feats, labels, masks):
        """W steps with per-step dense updates and ONE deferred sparse
        apply (sparse_apply_every > 1).

        The windowed relaxation: embedding grads accumulate into a packed
        acc table across the chunk (duplicates sum, exactly the per-step
        dedup contract) and the sparse optimizer applies ONCE per chunk
        from the sum — so forwards within a chunk read the tables as of
        the chunk start.  This is the reference's ASYNC-PS staleness
        (workers there train on pulled snapshots while pushed grads land;
        SURVEY §3.3), traded deliberately: the full-table streaming
        moment update amortizes W-fold, which at the 26M-row north-star
        probe is the difference between 184k and >500k samples/s/chip.
        Dense params, batch stats, and the step counter still update
        every step; strict per-step semantics remain the default (W=1).

        Mechanically the chunk's (ids, grads) stream OUT of the scan and
        feed one optimizer `apply` on the concatenated W-step batch —
        NOT an accumulator table carried through the scan: XLA never
        scatters into a loop carry in place, so a carried acc paid a
        full table copy every step (measured 15.9 ms/step at the 26M
        probe, worse than what the window was saving).  The scan outputs
        cost W x batch-sized buffers instead (a few hundred MB at W=64).
        """

        def body(st, xs):
            features, labels_, mask = xs
            loss, muts, dense_grads, perturb_grads = self._forward_backward(
                st, features, labels_, mask
            )
            new_params, new_opt_state, new_model_state = self._dense_and_state(
                st, muts, dense_grads
            )
            sparse = {
                key: (flat_ids, flat_grads)
                for key, _, flat_ids, flat_grads in self._sparse_batches(
                    muts, perturb_grads, st.tables
                )
            }
            new_st = PSTrainState(
                st.step + 1, new_params, new_opt_state, new_model_state,
                st.tables, st.slots,
            )
            return new_st, (loss, self._oov_total(muts), sparse)

        state, (losses, oovs, sparse) = jax.lax.scan(
            body, state, (feats, labels, masks)
        )
        new_tables = dict(state.tables)
        new_slots = dict(state.slots)
        for key in self._table_paths:
            spec = self._table_specs[key]
            ids_w, grads_w = sparse[key]  # [W, n], [W, n, dim]
            with jax.named_scope("sparse_apply"):
                new_tables[key], new_slots[key] = self._emb_tx.apply(
                    spec, new_tables[key], new_slots[key],
                    ids_w.reshape((-1,)),
                    grads_w.reshape((-1, spec.dim)),
                )
        return (
            state._replace(tables=new_tables, slots=new_slots),
            (losses, jnp.sum(oovs)),
        )

    def _train_window_impl(self, state, feat_win, label_win, mask_win):
        """K train steps in ONE device program (lax.scan over the stacked
        window).  One dispatch + one transfer amortize per-call overheads
        K-fold — the TPU-idiomatic device-side training loop.  With
        sparse_apply_every=W > 1 the window runs as ceil(K/W) chunks (see
        _train_chunk_impl)."""
        W = self._sparse_apply_every or 1  # auto resolves at init

        if W <= 1:
            def body(st, xs):
                features, labels, mask = xs
                new_state, (loss, oov) = self._train_step_impl(
                    st, features, labels, mask
                )
                return new_state, (loss, oov)

            state, (losses, oovs) = jax.lax.scan(
                body, state, (feat_win, label_win, mask_win)
            )
            return state, (losses, jnp.sum(oovs))

        K = jax.tree.leaves(feat_win)[0].shape[0]
        n_full, rem = divmod(K, W)
        losses_parts = []
        oov_parts = []
        if n_full:
            chunked = jax.tree.map(
                lambda x: x[: n_full * W].reshape(
                    (n_full, W) + x.shape[1:]
                ),
                (feat_win, label_win, mask_win),
            )

            def chunk_body(st, xs):
                return self._train_chunk_impl(st, *xs)

            state, (losses_full, oov_full) = jax.lax.scan(
                chunk_body, state, chunked
            )
            losses_parts.append(losses_full.reshape((-1,)))
            oov_parts.append(jnp.sum(oov_full))
        if rem:
            tail = jax.tree.map(
                lambda x: x[n_full * W:], (feat_win, label_win, mask_win)
            )
            state, (losses_tail, oov_tail) = self._train_chunk_impl(
                state, *tail
            )
            losses_parts.append(losses_tail)
            oov_parts.append(oov_tail)
        losses = (
            jnp.concatenate(losses_parts)
            if len(losses_parts) > 1
            else losses_parts[0]
        )
        return state, (losses, sum(oov_parts))

    def _eval_step_impl(self, state: PSTrainState, features):
        variables = {
            "params": self._merge_params(state.params, state.tables),
            PERTURBATIONS: self._zero_perturbations(),
            **state.model_state,
        }
        outputs, _ = model_apply(
            self._model, variables, features, train=False,
            mutable=[IDS_COLLECTION],
        )
        return outputs

    # -- host-side entry points (same shapes contract as DP trainer) ----

    def train_step(self, features, labels):
        block = self.local_block(
            jax.tree.leaves(features)[0].shape[0]
        )
        features, mask = shd.pad_batch(features, block)
        labels, _ = shd.pad_batch(labels, block)
        return self.train_step_local(features, labels, mask)

    def train_step_local(self, features, labels, mask):
        self.ensure_initialized(features)
        return self.train_step_staged(self.stage_batch(features, labels, mask))

    def stage_batch(self, features, labels, mask):
        """Asynchronously place one lockstep batch on the mesh.  Staging
        returns immediately (device transfers are async), so staging batch
        k+1 BEFORE stepping batch k overlaps host->device traffic with
        compute — on hosts where the transfer is the bottleneck this is
        the difference between step-time and transfer-time throughput."""
        return (
            shd.assemble_global_batch(features, self._mesh),
            shd.assemble_global_batch(labels, self._mesh),
            shd.assemble_global_batch(np.asarray(mask, np.float32), self._mesh),
        )

    def train_step_staged(self, staged):
        if self._state is None:
            # Init derives perturbation shapes from LOCAL batch shapes;
            # staged batches are already global, so init must happen first
            # (train_step_local does this; direct stagers call
            # ensure_initialized themselves).
            raise RuntimeError(
                "train_step_staged requires ensure_initialized(features) first"
            )
        self._state, (loss, oov) = self._train_step(self._state, *staged)
        self._host_step += 1
        self._pending_oov.append(oov)
        return loss

    def stage_window(self, batches):
        """Stage K lockstep (features, labels, mask) batches in ONE
        host->device transfer: [K, batch, ...] stacks, batch dim sharded.
        Per-transfer overhead (dominant on thin hosts) amortizes K-fold;
        `train_window(window)` then runs all K steps in one device
        program.  All K batches must share shapes (callers route ragged
        tails through `train_step_staged`)."""
        stacked_f, stacked_l, stacked_m = shd.stack_window(batches)
        return (
            shd.assemble_window(stacked_f, self._mesh),
            shd.assemble_window(stacked_l, self._mesh),
            shd.assemble_window(stacked_m, self._mesh),
        )

    def train_window(self, window):
        """Run every batch of a staged window; returns the [K] losses
        (device array — don't block on it in the hot loop)."""
        if self._state is None:
            raise RuntimeError(
                "train_window requires ensure_initialized(features) first"
            )
        k = jax.tree.leaves(window[1])[0].shape[0]
        self._state, (losses, oov) = self._train_window(self._state, *window)
        self._host_step += k
        self._pending_oov.append(oov)
        return losses

    def consume_oov_count(self) -> int:
        """Total out-of-vocabulary ids seen by train steps since the last
        call.  BLOCKS on the pending device scalars — call at task
        boundaries (the worker does, folding the count into the task's
        exec counters), not in the dispatch hot loop."""
        if not self._pending_oov:
            return 0
        total = sum(int(np.asarray(x)) for x in self._pending_oov)
        self._pending_oov = []
        return total

    def eval_step(self, features):
        n = jax.tree.leaves(features)[0].shape[0]
        block = self.local_block(n)
        features, _ = shd.pad_batch(features, block)
        outputs = self.eval_step_local(features)
        return jax.tree.map(lambda x: np.asarray(x)[:n], outputs)

    def eval_step_local(self, features):
        # The gather is ONE GLOBAL BATCH of outputs to every host (a
        # collective, so all ranks call it) — memory is batch-bounded;
        # task/dataset-scale bounding lives in the worker's streaming
        # eval loop (collective_worker EVAL_REPORT_BATCHES +
        # data/dataset.SequentialRecords).
        state = self.ensure_initialized(features)
        features = shd.assemble_global_batch(features, self._mesh)
        outputs = self._eval_step(state, features)
        return shd.gather_to_host(outputs)

    # -- sharded checkpointing -------------------------------------------

    def _sharded_arrays(self, state: PSTrainState) -> Dict[str, jax.Array]:
        """The mesh-sharded leaves, under stable checkpoint names.  '|' is
        the name separator (path keys use '/'); row intervals append two
        more '|' fields in the shard files (checkpoint/sharded.py)."""
        out = {f"table|{k}": v for k, v in state.tables.items()}
        for key, group in state.slots.items():
            for name, v in group.items():
                if np.ndim(v):  # scalar slots ride the dense pickle instead
                    out[f"slot|{key}|{name}"] = v
        return out

    def _scalar_slots(self, state: PSTrainState) -> dict:
        """Replicated 0-d slots (e.g. adam's global-bias counter): row-
        interval sharding is meaningless for them, so they checkpoint with
        the dense state."""
        return {
            key: {
                name: jax.device_get(v)
                for name, v in group.items()
                if not np.ndim(v)
            }
            for key, group in state.slots.items()
        }

    def save_checkpoint(self, saver, step: int) -> None:
        """COLLECTIVE sharded checkpoint (checkpoint/sharded.py): every
        process calls this and writes only its local table/slot rows — no
        host ever materializes a full table, unlike `state_to_host` (whose
        full gather OOMs by construction at Criteo scale)."""
        if self._state is None:
            return
        state = self._state
        # Dense state is replicated and only rank 0 writes it — don't pay
        # the device->host transfer on the other N-1 ranks' hot path.
        # The saver's stream brings it to the host behind the table
        # rows, leaf by leaf.
        dense = None
        if jax.process_index() == 0:
            dense = {
                "step": state.step,
                "params": state.params,
                "opt_state": state.opt_state,
                "model_state": state.model_state,
                "scalar_slots": self._scalar_slots(state),
            }
        saver.save(
            step, dense, self._sharded_arrays(state), cutter=self.leaf_cutter
        )

    def set_sharded_restore(self, saver, step: int) -> None:
        """Defer restore until ensure_initialized has built the model's
        structure and shardings (worker-boot restore, same contract as the
        `state` setter's pending path)."""
        self._pending_sharded_restore = (saver, step)
        self._host_step = step

    def _restore_sharded(
        self, template: PSTrainState, shardings: PSTrainState
    ) -> PSTrainState:
        """Materialize the checkpoint under the CURRENT world's shardings:
        dense state replicates from rank 0's pickle; each table/slot row
        interval is read by whichever process now owns it — world-size
        agnostic, which is what restart-the-world shrink/grow needs.
        `template` is the state's shape tree (`jax.ShapeDtypeStruct`
        leaves: nothing was initialised for the checkpoint to overwrite)
        and `shardings` the tree of its leaves' shardings."""
        saver, step = self._pending_sharded_restore
        with tracing.span("checkpoint.restore.load", step=step) as span:
            restored = self._restore_sharded_inner(template, shardings)
            span.fields["bytes"] = tree_nbytes(restored)
        return restored

    def _restore_sharded_inner(
        self, template: PSTrainState, shardings: PSTrainState
    ) -> PSTrainState:
        saver, step = self._pending_sharded_restore
        self._pending_sharded_restore = None
        dense = saver.load_dense(step)
        if hasattr(saver, "manifest"):
            # Fail with the CAUSE when the checkpoint's table set differs
            # from this build's (a bare KeyError on 'table|...' is
            # undiagnosable).  The usual way to get here: a per-mode
            # table layout changed between runs — e.g. DeepFM merges its
            # linear+fm tables under windowed sparse apply but splits
            # them under strict mode at >10M rows, so changing
            # --sparse_apply_every across a restart changes the model's
            # table structure.
            have = {
                name[len("table|"):]
                for name in saver.manifest(step).get("arrays", {})
                if name.startswith("table|")
            }
            want = set(template.tables)
            if have != want:
                raise ValueError(
                    f"Checkpoint at step {step} holds embedding tables "
                    f"{sorted(have)} but this build expects "
                    f"{sorted(want)} — the model's table layout changed "
                    "between save and restore (e.g. DeepFM's per-mode "
                    "layout splits/merges tables when "
                    "--sparse_apply_every crosses the strict/windowed "
                    "boundary at >10M rows). Restore with the same "
                    "sparse_apply_every, or pin the layout with "
                    "--model_params split_tables=true|false"
                )
        tables = {
            k: saver.load_array(step, f"table|{k}", shardings.tables[k])
            for k in template.tables
        }
        scalar_slots = dense.get("scalar_slots", {})

        def load_scalar_slot(k, n, tmpl):
            # Fail LOUDLY if the checkpoint predates this slot (e.g. a
            # per_row-bias adam checkpoint restored into a global-bias
            # build): silently defaulting the counter to 0 would reset
            # bias correction on a converged model.
            if n not in scalar_slots.get(k, {}):
                raise ValueError(
                    f"Checkpoint at step {step} has no scalar slot "
                    f"{k}/{n} — it was written by a build with a "
                    "different optimizer configuration (e.g. adam "
                    "bias_correction='per_row' vs 'global'); restore "
                    "with the matching configuration"
                )
            return self._place_leaf(
                np.asarray(scalar_slots[k][n], dtype=tmpl.dtype),
                shardings.slots[k][n],
            )

        slots = {
            k: {
                n: (
                    load_scalar_slot(k, n, group[n])
                    if not group[n].ndim
                    else saver.load_array(
                        step, f"slot|{k}|{n}", shardings.slots[k][n]
                    )
                )
                for n in group
            }
            for k, group in template.slots.items()
        }
        for k, v in tables.items():
            assert v.shape == template.tables[k].shape, (
                f"Checkpoint table {k} shape {v.shape} != model "
                f"{template.tables[k].shape} (vocab/dim changed?)"
            )
        for k, group in slots.items():
            for n, v in group.items():
                tmpl = template.slots[k][n]
                # .shape/.dtype only — never np.asarray a sharded slot
                # (that would gather the full table to host).
                got = (tuple(np.shape(v)), np.dtype(v.dtype))
                want = (tuple(np.shape(tmpl)), np.dtype(tmpl.dtype))
                assert got == want, (
                    f"Checkpoint slot {k}/{n} is {got} but this build "
                    f"expects {want} — slot layouts changed (e.g. adam "
                    "'t' moved from flat i32 to packed lane f32 in round "
                    "3); re-train or migrate the checkpoint"
                )
        if hasattr(saver, "release"):
            saver.release(step)  # close shard-file handles; restore done
        self._host_step = int(np.asarray(dense["step"]))
        logger.info(
            "Restored sharded checkpoint at step %d (%d tables)",
            self._host_step,
            len(tables),
        )
        return PSTrainState(
            step=self._place_leaf(np.asarray(dense["step"]), shardings.step),
            params=jax.tree.map(
                self._place_leaf, dense["params"], shardings.params
            ),
            opt_state=jax.tree.map(
                self._place_leaf, dense["opt_state"], shardings.opt_state
            ),
            model_state=jax.tree.map(
                self._place_leaf, dense["model_state"], shardings.model_state
            ),
            tables=tables,
            slots=slots,
        )

    def state_to_host(self) -> Optional[PSTrainState]:
        """Host-complete snapshot for checkpointing.  Tables/slots are
        sharded across processes, so this is a COLLECTIVE (allgather) —
        every process must call it, even though only rank 0 writes."""
        if self._state is None:
            return None
        state = self._state
        with tracing.span(
            "checkpoint.save.gather", bytes=tree_nbytes(state)
        ):
            return PSTrainState(
                step=jax.device_get(state.step),
                params=jax.device_get(state.params),
                opt_state=jax.device_get(state.opt_state),
                model_state=jax.device_get(state.model_state),
                tables={
                    k: shd.gather_to_host(v)
                    for k, v in state.tables.items()
                },
                slots={
                    k: {n: shd.gather_to_host(v) for n, v in group.items()}
                    for k, group in state.slots.items()
                },
            )

    def get_variables_numpy(self) -> dict:
        """Flat {path: logical np.ndarray} — packed tables are unpacked to
        their [vocab, dim] shape (the export/serving view).  COLLECTIVE in
        a multi-process world: tables span processes, so materializing
        them is an allgather every rank must join (device_get alone raises
        on non-addressable shards)."""
        if self._state is None:
            return {}
        state = self._state
        flat = {}
        merged = self._merge_params(
            jax.device_get(state.params),
            {
                k: np.asarray(
                    pk.unpack(self._table_specs[k], shd.gather_to_host(v))
                )
                for k, v in state.tables.items()
            },
        )
        tree = {"params": merged, **jax.device_get(state.model_state)}
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            flat[_path_key(path)] = np.asarray(leaf)
        return flat


def _collection_get(tree, module_path: Tuple, name: str):
    """Fetch collection value at tree[module_path...][name], unwrapping
    flax's sow tuple."""
    node = tree
    for part in module_path:
        node = node[part]
    value = node[name]
    if isinstance(value, tuple):  # sow appends into a tuple
        value = value[0]
    return value
