"""Data-parallel trainer: the AllReduce mode, compiled.

Parity: the reference's AllReduce path (worker/allreduce_trainer.py +
collective_ops/communicator.py — per-step gradient allreduce over
NCCL/Gloo, SURVEY.md §3.4).  TPU-native design: parameters are replicated
over the mesh's `data` axis, the batch is sharded over it, and the gradient
all-reduce is *not a library call* — XLA inserts `psum` when it lowers the
replicated-out gradient of a data-sharded loss, and schedules it onto ICI
overlapped with the backward pass.  One compiled program per step; no
Horovod, no ring management.

Ragged final batches are padded and masked (see parallel/sharding.py) so
shapes stay static across the whole epoch — one compilation, every batch.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.checkpoint.saver import tree_nbytes
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.layers.moe import ROUTING_COLLECTION, with_absent_counters
from elasticdl_tpu.obs import tracing
from elasticdl_tpu.parallel import compile as pc
from elasticdl_tpu.parallel import sharding as shd
from elasticdl_tpu.parallel.trainer import (
    TrainState,
    model_apply,
    unbox_partitioned,
)

logger = get_logger("parallel.dp_trainer")


def per_example_loss_fn(loss_fn: Callable) -> Callable:
    """Lift a batch-mean loss into a per-example loss via vmap.

    The model-zoo contract's `loss(labels, outputs)` returns the batch mean
    (reference contract).  Applying it to singleton batches under vmap
    recovers the per-example loss for any mean-of-per-example loss, which
    lets the trainer mask padded rows exactly.  Labels and outputs may
    each be a tree of arrays with the batch in front (a prediction that
    is several named arrays: `model_zoo/ouro`'s four exits' logits and
    their exit distribution; tests/test_ouro_program.py trains it through
    here): every leaf is mapped.
    """

    def singleton(label, output):
        return loss_fn(
            jax.tree.map(lambda x: x[None], label),
            jax.tree.map(lambda x: x[None], output),
        )

    return jax.vmap(singleton)


class DataParallelTrainer:
    """The dense trainer (parallel/trainer.Trainer) over an N-device mesh.

    Batch sharded over `data`; loss is a mask-weighted mean so padded
    rows contribute zero gradient.  Dense state placement is selectable
    (SURVEY.md §5 "dense: replicated or FSDP-sharded"):

    - `dense_sharding="replicated"` (default): params/opt-state replicated;
      XLA reduces gradients with a psum.
    - `dense_sharding="fsdp"`: params/opt-state sharded on dim0 over the
      `data` axis — each chip holds 1/N of the model+optimizer memory.
      No hand-written gather/scatter: the jit's in/out shardings declare
      the layout and XLA's SPMD partitioner inserts the all-gathers
      (weights, before use) and reduce-scatters (gradients) itself,
      scheduled onto ICI overlapped with compute.  Leaves too small or
      not divisible by the axis stay replicated.
    """

    FSDP_MIN_LEAF = 1024  # elements; below this, sharding buys nothing
    apply_every = 1  # no sparse tables: every step applies everything

    def __init__(
        self,
        model,
        loss_fn: Callable,
        optimizer: optax.GradientTransformation,
        mesh,
        seed: int = 0,
        dense_sharding: str = "replicated",
    ):
        if dense_sharding not in ("replicated", "fsdp"):
            raise ValueError(
                f"dense_sharding must be 'replicated' or 'fsdp', "
                f"got {dense_sharding!r}"
            )
        self._model = model
        self._loss_fn = loss_fn
        self._per_example_loss = per_example_loss_fn(loss_fn)
        self._tx = optimizer
        self._mesh = mesh
        self._seed = seed
        self._dense_sharding = dense_sharding
        self._state: Optional[TrainState] = None
        # Host-side mirror of state.step (avoids a per-batch device sync).
        self._host_step = 0
        self._dp = shd.data_axis_size(mesh)
        self._pending_sharded_restore = None

        # FSDP needs per-leaf state shardings, which need the state's
        # STRUCTURE — compile lazily at first state (ps_trainer pattern).
        self._train_step = None
        self._train_window_jit = None
        self._eval_step = None
        self.leaf_cutter = None  # (with the step programs)

    # -- sharding layout (declarative rule table, parallel/compile.py) --

    def _partition_rules(self) -> pc.RuleTable:
        """The dense trainer's placement policy as a rule table.
        Replicated mode is one catch-all entry; FSDP shards
        params/opt_state dim0 over `data` when the leaf divides the
        axis and is worth sharding (shape-aware callable rule — the
        FSDP_MIN_LEAF/divisibility policy reads as ONE table entry).
        Scalars and everything else (step counter, batch stats)
        replicate."""
        from jax.sharding import PartitionSpec as P

        from elasticdl_tpu.parallel.mesh import DATA_AXIS

        if self._dense_sharding == "replicated":
            return pc.RuleTable([pc.Rule(".*", P())], name="dp-replicated")
        dp = self._dp
        min_leaf = self.FSDP_MIN_LEAF

        def fsdp_leaf(path, shape):
            if shape[0] % dp == 0 and int(np.prod(shape)) >= min_leaf:
                return P(DATA_AXIS, *([None] * (len(shape) - 1)))
            return P()

        return pc.RuleTable(
            [
                pc.Rule(r"^(params|opt_state)(/|$)", fsdp_leaf),
                pc.Rule(".*", P()),
            ],
            name="dp-fsdp",
        )

    def _plan(self) -> pc.CompilePlan:
        return pc.CompilePlan(
            self._mesh, self._partition_rules(), trainer="dp_trainer"
        )

    def _state_shardings(self, state: TrainState, plan=None):
        # Works on concrete arrays AND jax.eval_shape's ShapeDtypeStructs
        # (the sharded-init path computes shardings from shapes alone).
        plan = plan or self._plan()
        tree = plan.state_shardings({
            "step": state.step,
            "params": state.params,
            "opt_state": state.opt_state,
            "model_state": state.model_state,
        })
        return TrainState(
            tree["step"], tree["params"], tree["opt_state"],
            tree["model_state"],
        )

    def _place_state(self, state: TrainState) -> TrainState:
        return shd.put(state, self._state_shardings(state))

    def _compile_steps(self, state: TrainState):
        plan = self._plan()
        repl = plan.replicated()
        batch = shd.batch_sharded(self._mesh)
        window = shd.window_sharded(self._mesh)
        state_shardings = self._state_shardings(state, plan)
        self._train_step = plan.compile(
            self._train_step_impl,
            name="dp_train_step",
            in_shardings=(state_shardings, batch, batch, batch),
            out_shardings=(state_shardings, repl),
            donate_argnums=(0,),
        )
        self._train_window_jit = plan.compile(
            self._train_window_impl,
            name="dp_train_window",
            in_shardings=(state_shardings, window, window, window),
            out_shardings=(state_shardings, repl),
            donate_argnums=(0,),
        )
        self._eval_step = plan.compile(
            self._eval_step_impl,
            name="dp_eval_step",
            in_shardings=(state_shardings, batch),
            out_shardings=batch,
        )
        # (an FSDP world's shards have no program and cross whole)
        self.leaf_cutter = pc.leaf_cutter(plan, jax.tree.leaves(state))

    def jitted_entrypoints(self) -> dict:
        """Current jitted entrypoints by name — the step-anatomy
        retrace watcher (obs/stepstats.py) polls their compile-cache
        sizes between dispatches.  Empty until first compile; re-read
        per poll because compilation is lazy."""
        return {
            "dp_train_step": self._train_step,
            "dp_train_window": self._train_window_jit,
            "dp_eval_step": self._eval_step,
        }

    # -- state ----------------------------------------------------------

    @property
    def mesh(self):
        return self._mesh

    def local_block(self, per_rank_batch: int) -> int:
        """Rows each process must supply per collective step: the requested
        per-rank batch rounded up to a multiple of the process's local
        device count (the global batch must divide the `data` axis)."""
        local_devices = max(1, self._dp // jax.process_count())
        return -(-per_rank_batch // local_devices) * local_devices

    @property
    def state(self) -> Optional[TrainState]:
        return self._state

    @state.setter
    def state(self, value: TrainState):
        value = TrainState(*value)
        # A checkpoint older than one of the expert layers' counters
        # restores with it at zero: the tree the step program writes.
        value = value._replace(
            model_state=with_absent_counters(value.model_state)
        )
        self._state = self._place_state(jax.device_get(value))
        self._host_step = int(np.asarray(jax.device_get(value.step)))
        if self._train_step is None:
            self._compile_steps(self._state)

    @property
    def step(self) -> int:
        return self._host_step

    def _make_state(self, rng, features):
        """Pure state constructor — runs under jit so FSDP state is BORN
        sharded (out_shardings), never materialized whole on one device.
        Returns (state, specs_collection) — the tiny packed-table specs
        ride out for host-side export mapping."""
        from elasticdl_tpu.layers.embedding import (
            SPECS_COLLECTION,
            strip_capture_collections,
        )

        variables = dict(self._model.init(rng, features))
        specs = variables.get(SPECS_COLLECTION, {})
        variables = strip_capture_collections(variables)
        variables = unbox_partitioned(variables)
        params = variables.pop("params")
        state = TrainState(
            jnp.zeros((), jnp.int32),
            params,
            self._tx.init(params),
            variables,
        )
        return state, specs

    def ensure_initialized(self, features) -> TrainState:
        if self._state is None:
            # `state.init`: shapes, then the jitted init that births the
            # state in its layout (or the restore in its place).
            with tracing.span("state.init", trainer="dp_trainer"):
                self._init_state(features)
        if self._pending_sharded_restore is not None:
            # State arrived via the setter (or was already live) after a
            # deferred restore was registered: apply it now.
            self._state = self._restore_sharded(self._state)
        if self._train_step is None:
            self._compile_steps(self._state)
        return self._state

    def _init_state(self, features) -> None:
        from elasticdl_tpu.layers.embedding import (
            SPECS_COLLECTION,
            export_spec_map,
        )

        rng = jax.random.PRNGKey(self._seed)
        features = jax.tree.map(jnp.asarray, features)
        # Structure first (no FLOPs, no memory), shardings from it,
        # then a jitted init whose out_shardings birth the state in
        # its final layout — under FSDP no device ever holds the
        # full params+opt_state (the point of sharding them).
        state_shapes, _specs_shapes = jax.eval_shape(
            self._make_state, rng, features
        )
        if self._pending_sharded_restore is not None:
            # Restore path: the checkpoint supplies every value, so
            # never run (or even compile) the full init — the shape
            # tree is template enough, and the tiny export specs come
            # from a specs-only jit whose unused param computations
            # XLA dead-code-eliminates.
            # Specs-only jit: the outputs are a handful of [2] int32
            # packed-table specs (host-bound, layout-irrelevant) and
            # the param computations feeding them are dead-code-
            # eliminated — declaring shardings here would force the
            # full init to compile (jit_utility is the compile
            # layer's sanctioned non-step passthrough).
            specs = pc.jit_utility(
                lambda r, f: self._make_state(r, f)[1]
            )(rng, features)
            self._state = self._restore_sharded(state_shapes)
        else:
            plan = self._plan()
            repl = plan.replicated()
            init = plan.compile(
                self._make_state,
                name="dp_init",
                out_shardings=(
                    self._state_shardings(state_shapes, plan),
                    jax.tree.map(lambda _: repl, _specs_shapes),
                ),
            )
            self._state, specs = init(rng, features)
        self._export_specs = export_spec_map(
            {SPECS_COLLECTION: jax.device_get(specs)}
        )
        logger.info(
            "Initialized %s model over %d-way data parallel: "
            "%d parameters",
            self._dense_sharding,
            self._dp,
            sum(
                int(np.prod(p.shape))
                for p in jax.tree.leaves(state_shapes.params)
            ),
        )

    # -- compiled steps -------------------------------------------------

    def _train_step_impl(self, state: TrainState, features, labels, mask):
        mutable_keys = list(state.model_state.keys())

        def compute_loss(params):
            variables = {"params": params, **state.model_state}
            outputs, new_model_state = model_apply(
                self._model, variables, features, train=True, mutable=mutable_keys
            )
            losses = self._per_example_loss(labels, outputs)
            loss = jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            return loss, new_model_state

        with jax.named_scope("fwd_bwd"):
            (loss, new_model_state), grads = jax.value_and_grad(
                compute_loss, has_aux=True
            )(state.params)
        with jax.named_scope("optimizer"):
            updates, new_opt_state = self._tx.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
        if not mutable_keys:
            new_model_state = state.model_state
        return (
            TrainState(state.step + 1, new_params, new_opt_state, new_model_state),
            loss,
        )

    def _train_window_impl(self, state, feat_win, label_win, mask_win):
        """K train steps in one device program (see ps_trainer)."""

        def body(st, xs):
            features, labels, mask = xs
            new_state, loss = self._train_step_impl(st, features, labels, mask)
            return new_state, loss

        return jax.lax.scan(body, state, (feat_win, label_win, mask_win))

    def _eval_step_impl(self, state: TrainState, features):
        variables = {"params": state.params, **state.model_state}
        outputs, _ = model_apply(
            self._model, variables, features, train=False, mutable=False
        )
        return outputs

    # -- host-side entry points ----------------------------------------

    def _place_batch(self, features, labels=None):
        features, mask = shd.pad_batch(features, self._dp)
        if labels is not None:
            labels, _ = shd.pad_batch(labels, self._dp)
            labels = shd.shard_batch(labels, self._mesh)
        features = shd.shard_batch(features, self._mesh)
        mask = shd.shard_batch(mask, self._mesh)
        return features, labels, mask

    def train_step(self, features, labels):
        state = self.ensure_initialized(features)
        features, labels, mask = self._place_batch(features, labels)
        self._state, loss = self._train_step(state, features, labels, mask)
        self._host_step += 1
        return loss

    def train_step_local(self, features, labels, mask):
        """Collective-mode entry: `features`/`labels`/`mask` are this
        process's equal-size slice of the global batch (pre-padded by the
        caller); all processes must call this in lockstep."""
        self.ensure_initialized(features)
        return self.train_step_staged(self.stage_batch(features, labels, mask))

    def stage_batch(self, features, labels, mask):
        """Async device placement of one lockstep batch (stage k+1 before
        stepping k to overlap H2D with compute; see ps_trainer)."""
        return (
            shd.assemble_global_batch(features, self._mesh),
            shd.assemble_global_batch(labels, self._mesh),
            shd.assemble_global_batch(np.asarray(mask, np.float32), self._mesh),
        )

    def train_step_staged(self, staged):
        state = self.ensure_initialized(staged[0])
        self._state, loss = self._train_step(state, *staged)
        self._host_step += 1
        return loss

    def stage_window(self, batches):
        """Stage K same-shape (features, labels, mask) batches as one
        stacked transfer (see ps_trainer.stage_window)."""
        stacked_f, stacked_l, stacked_m = shd.stack_window(batches)
        return (
            shd.assemble_window(stacked_f, self._mesh),
            shd.assemble_window(stacked_l, self._mesh),
            shd.assemble_window(stacked_m, self._mesh),
        )

    def train_window(self, window):
        """Run every batch of a staged window; returns the [K] losses."""
        if self._state is None:
            self.ensure_initialized(jax.tree.map(lambda x: x[0], window[0]))
        k = jax.tree.leaves(window[1])[0].shape[0]
        self._state, losses = self._train_window_jit(self._state, *window)
        self._host_step += k
        return losses

    def consume_oov_count(self) -> int:
        """Out-of-vocabulary ids since the last call: the dense path
        counts none (parallel/trainer.Trainer)."""
        return 0

    def eval_step_local(self, features):
        """Collective-mode eval: local slice in, FULL global outputs out
        (host numpy, identical on every process)."""
        state = self.ensure_initialized(features)
        features = shd.assemble_global_batch(features, self._mesh)
        outputs = self._eval_step(state, features)
        return shd.gather_to_host(outputs)

    def eval_step(self, features):
        state = self.ensure_initialized(features)
        n = jax.tree.leaves(features)[0].shape[0]
        features, _, _ = self._place_batch(features)
        outputs = self._eval_step(state, features)
        # Strip padding rows before returning to the host.
        return jax.tree.map(lambda x: np.asarray(x)[:n], outputs)

    def state_to_host(self) -> Optional[TrainState]:
        """Host-complete snapshot for checkpointing.  Replicated state
        materializes locally; FSDP-sharded leaves allgather — a COLLECTIVE
        in multi-process worlds (every process must call this).  FSDP jobs
        normally checkpoint via save_checkpoint (shard-wise, no gather);
        this full-gather remains for export/debug paths."""
        if self._state is None:
            return None
        with tracing.span(
            "checkpoint.save.gather", bytes=tree_nbytes(self._state)
        ):
            if self._dense_sharding == "replicated":
                return jax.device_get(self._state)
            return shd.gather_to_host(self._state)

    # -- sharded checkpointing (FSDP) -----------------------------------

    @staticmethod
    def _leaf_key(path) -> str:
        return "dense|" + "/".join(str(getattr(p, "key", p)) for p in path)

    def save_checkpoint(self, saver, step: int) -> None:
        """COLLECTIVE shard-wise checkpoint (checkpoint/sharded.py):
        each process writes only its local rows of FSDP-sharded leaves —
        no host ever gathers the full model+optimizer state (which is
        the thing FSDP exists to avoid holding)."""
        if self._state is None:
            return
        state = self._state
        shardings = self._state_shardings(state)
        flat_state = jax.tree_util.tree_flatten_with_path(state)[0]
        flat_shard = jax.tree.leaves(
            shardings, is_leaf=lambda x: hasattr(x, "is_fully_replicated")
        )
        sharded = {}
        dense_leaves = {}
        for (path, leaf), sharding in zip(flat_state, flat_shard):
            key = self._leaf_key(path)
            if sharding.is_fully_replicated:
                if jax.process_index() == 0:
                    # The saver's stream brings it to the host, behind
                    # the shards.
                    dense_leaves[key] = leaf
            else:
                sharded[key] = leaf
        dense = None
        if jax.process_index() == 0:
            dense = {
                "step": int(self._host_step),
                "leaves": dense_leaves,
            }
        saver.save(step, dense, sharded, cutter=self.leaf_cutter)

    def set_sharded_restore(self, saver, step: int) -> None:
        self._pending_sharded_restore = (saver, step)
        self._host_step = step

    def _restore_sharded(self, template: TrainState) -> TrainState:
        saver, step = self._pending_sharded_restore
        with tracing.span("checkpoint.restore.load", step=step) as span:
            restored = self._restore_sharded_inner(template)
            span.fields["bytes"] = tree_nbytes(restored)
        return restored

    def _restore_sharded_inner(self, template: TrainState) -> TrainState:
        saver, step = self._pending_sharded_restore
        self._pending_sharded_restore = None
        shardings = self._state_shardings(template)
        manifest_arrays = saver.manifest(step)["arrays"]
        dense = saver.load_dense(step)
        flat_template, treedef = jax.tree_util.tree_flatten_with_path(
            template
        )
        flat_shard = jax.tree.leaves(
            shardings, is_leaf=lambda x: hasattr(x, "is_fully_replicated")
        )
        leaves = []
        for (path, leaf), sharding in zip(flat_template, flat_shard):
            key = self._leaf_key(path)
            if key in manifest_arrays:
                leaves.append(saver.load_array(step, key, sharding))
            elif key in dense["leaves"]:
                leaves.append(shd.put(dense["leaves"][key], sharding))
            elif f"/{ROUTING_COLLECTION}/" in key:
                # A counter younger than the checkpoint (layers/moe.py).
                leaves.append(
                    shd.put(np.zeros(leaf.shape, leaf.dtype), sharding)
                )
            else:
                raise KeyError(
                    f"Checkpoint at step {step} missing leaf {key} "
                    "(model structure changed?)"
                )
        if hasattr(saver, "release"):
            saver.release(step)
        self._host_step = int(dense["step"])
        logger.info("Restored sharded checkpoint at step %d", self._host_step)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def get_variables_numpy(self) -> dict:
        """Flat {path: np.ndarray} view of all variables (for export);
        packed embedding tables unpacked to their logical [vocab, dim].
        COLLECTIVE under FSDP in multi-process worlds (see state_to_host)."""
        from elasticdl_tpu.parallel import packed as pk

        if self._state is None:
            return {}
        specs = getattr(self, "_export_specs", {})
        flat = {}
        tree = {"params": self._state.params, **self._state.model_state}
        if self._dense_sharding == "fsdp":
            tree = shd.gather_to_host(tree)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            key = "/".join(str(getattr(p, "key", p)) for p in path)
            if key in specs:
                leaf = pk.unpack(specs[key], leaf)
            flat[key] = np.asarray(leaf)
        return flat
