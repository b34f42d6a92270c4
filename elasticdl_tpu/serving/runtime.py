"""Serving replica runtime: the compiled batched inference step with
hot-swap model generations.

A `ServingReplica` owns the device side of the serving plane:

- **Loading.**  Each model generation is an `export.py` artifact loaded
  with `load_for_serving`, its variables placed on the replica's mesh by
  a serving `RuleTable` (embedding tables block-shard on dim0 when their
  storage rows divide the mesh — HBM capacity, same policy as the PS
  trainer's table placement; everything else replicates).
- **Compiling.**  The inference step is compiled ONCE per generation
  through `CompilePlan` (parallel/compile.py), so its placement is
  declared and journaled (`compile_plan` event, trainer="serving") like
  every training entry point.  The step is the model's eval path
  (`model_apply(train=False, mutable=False)`) — under
  `--sparse_kernel fused` the Embedding layers route lookups through
  `fused_lookup_fm`'s forward (single-device Pallas or the shard_map
  dispatch when a multi-device dispatch mesh is registered); no backward
  is ever traced.
- **Hot-swap.**  `reload(model_dir)` builds the NEW generation fully
  (load, place, compile) before an atomic pointer swap; dispatches
  already riding the old generation drain on its in-flight counter
  before it is released, so a swap drops zero in-flight requests.  The
  swap is journaled as a schema-registered `model_swap` event.

Trace purity: the compiled step body touches only the model apply —
journaling, locks, and clocks all live on the host side of the
dispatch boundary (`make check-invariants` gates this).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from elasticdl_tpu import obs
from elasticdl_tpu.analysis.runtime import make_lock
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.parallel import compile as pc
from elasticdl_tpu.serving.batcher import pad_features
from elasticdl_tpu.serving.export import ServingModel, load_for_serving

logger = get_logger("serving.runtime")


def serving_rules(mesh, sparse_kernel: str = "xla") -> pc.RuleTable:
    """Placement policy for serving variables as a rule table: dense
    params and batch stats replicate (they are small and every device
    reads them each step); embedding tables — the leaves the Embedding
    layer names ``embedding`` — are the one shape-aware entry:

    - xla engine: storage blocks across the WHOLE mesh when dim0
      divides it (maximum HBM capacity; the partitioner turns the
      lookup gather into collectives), else replicate — a table too
      small to split evenly is by definition tiny.
    - fused engine: blocks over the ``model`` axis only, the layout the
      shard_map'd kernel dispatch declares
      (ops/sparse_embedding.table_partition_axis), so the per-shard
      pallas bodies see exactly their resident blocks.
    """
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.ops import sparse_embedding as ske
    from elasticdl_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    fused = sparse_kernel == "fused"
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
            pc.Rule(r"(^|/)embedding$", table_blocks),
            pc.Rule(".*", P()),
        ],
        name="serving-fused" if fused else "serving-xla",
    )


class Generation:
    """One loaded model generation: the artifact, its device-placed
    variables, and the compiled step — plus an in-flight dispatch count
    so hot-swap can drain it before release."""

    def __init__(
        self,
        gen_id: int,
        model_dir: str,
        served: ServingModel,
        variables,
        serve_fn,
        shardings=None,
        event_time: float = 0.0,
    ):
        self.gen_id = gen_id
        self.model_dir = model_dir
        self.served = served
        self.variables = variables
        self.serve_fn = serve_fn
        # Placement tree + event-time frontier: what delta apply needs to
        # re-place patched variables and what the freshness SLO reads.
        self.shardings = shardings
        self.event_time = float(event_time)
        self._lock = make_lock("Generation._lock")
        self._inflight = 0  # guarded-by: _lock
        self._idle = threading.Condition(self._lock)

    @property
    def step(self) -> int:
        return int(self.served.signature.get("step", 0))

    def begin(self):
        with self._lock:
            self._inflight += 1

    def end(self):
        with self._lock:
            self._inflight -= 1
            self._idle.notify_all()

    def inflight(self) -> int:
        with self._lock:
            return self._inflight

    def drain(self, timeout_s: float = 30.0) -> int:
        """Block until in-flight dispatches finish (or timeout); returns
        the count still in flight (0 = fully drained)."""
        deadline = time.monotonic() + timeout_s
        with self._lock:
            while self._inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._idle.wait(timeout=remaining)
            return self._inflight


class ServingReplica:
    """The device half of one serving replica process.

    `execute(features, n_valid)` is the MicroBatcher's execute callable:
    it rides the CURRENT generation (acquired under the swap lock, so a
    concurrent `reload` can never free variables out from under a
    dispatch).  `reload(model_dir)` performs the hot swap.
    """

    def __init__(
        self,
        model_dir: str,
        mesh=None,
        sparse_kernel: Optional[str] = None,
        model_zoo: str = "",
        mmap: bool = True,
        drain_timeout_s: float = 30.0,
    ):
        from elasticdl_tpu.ops import sparse_embedding as ske
        from elasticdl_tpu.parallel.mesh import MeshConfig, build_mesh

        self._mesh = mesh if mesh is not None else build_mesh(MeshConfig())
        self._kernel = ske.resolve_kernel(sparse_kernel)
        self._model_zoo = model_zoo
        self._mmap = mmap
        self._drain_timeout_s = drain_timeout_s
        if self._kernel == "fused" and int(self._mesh.devices.size) > 1:
            # The Embedding layer consults the process dispatch mesh for
            # its shard_map'd fused route (worker/main.py does the same
            # registration on the training side).
            ske.set_dispatch_mesh(self._mesh)
        self._lock = make_lock("ServingReplica._lock")
        self._next_gen_id = 1  # guarded-by: _lock
        self._generation: Optional[Generation] = None  # guarded-by: _lock
        self._generation = self._load_generation(model_dir)
        logger.info(
            "Serving replica up: generation %d (step %d) from %s, "
            "kernel=%s, %d device(s)",
            self._generation.gen_id,
            self._generation.step,
            model_dir,
            self._kernel,
            int(self._mesh.devices.size),
        )

    # -- loading / compiling --------------------------------------------

    def _load_generation(self, model_dir: str) -> Generation:
        import jax

        served = load_for_serving(
            model_dir, model_zoo=self._model_zoo, mmap=self._mmap
        )
        rules = serving_rules(self._mesh, self._kernel)
        plan = pc.CompilePlan(self._mesh, rules, trainer="serving")
        shardings = plan.state_shardings(served.variables)
        variables = jax.device_put(served.variables, shardings)
        model = served.model

        def _serve_step(variables, features):
            from elasticdl_tpu.parallel.trainer import model_apply

            outputs, _ = model_apply(
                model, variables, features, train=False, mutable=False
            )
            return outputs

        serve_fn = plan.compile(
            _serve_step,
            name="serve_step",
            in_shardings=(shardings, plan.replicated()),
            out_shardings=plan.replicated(),
        )
        with self._lock:
            gen_id = self._next_gen_id
            self._next_gen_id += 1
        return Generation(
            gen_id,
            model_dir,
            served,
            variables,
            serve_fn,
            shardings=shardings,
            event_time=float(served.signature.get("event_time", 0.0)),
        )

    # -- the dispatch path ----------------------------------------------

    def _acquire(self) -> Generation:
        with self._lock:
            gen = self._generation
            gen.begin()
            return gen

    def execute(self, features: Dict[str, np.ndarray], n_valid: int):
        """Run the compiled step on one (padded) batch — the
        MicroBatcher's execute_fn.  Returns host outputs (the asarray is
        the device sync, outside every lock)."""
        gen = self._acquire()
        try:
            return np.asarray(gen.serve_fn(gen.variables, features))
        finally:
            gen.end()

    def warmup(self, features: Dict[str, np.ndarray], buckets: Sequence[int]):
        """Pre-trace every padded-bucket shape so live traffic never
        waits on a compile (and the RetraceWatcher baseline is clean)."""
        for size in buckets:
            self.execute(pad_features(features, size), n_valid=0)

    # -- hot swap --------------------------------------------------------

    def reload(self, model_dir: str) -> Generation:
        """Atomic generation swap: the new generation is fully built
        (loaded, placed, compiled) BEFORE the pointer moves, then the
        old generation drains its in-flight dispatches — zero in-flight
        requests are dropped by a swap.

        A failed build — corrupt artifact, bad pickle, compile error —
        never touches the generation pointer: the old generation keeps
        serving (stale, ledger-visible, never down) and the rollback is
        journaled as a `model_swap` with ``outcome=rolled_back``."""
        try:
            new_gen = self._load_generation(model_dir)
        except Exception as exc:
            old_gen = self.generation
            obs.journal().record(
                "model_swap",
                kind="full",
                outcome="rolled_back",
                generation=old_gen.gen_id,
                step=old_gen.step,
                old_generation=old_gen.gen_id,
                old_step=old_gen.step,
                model_dir=model_dir,
                reason=repr(exc),
            )
            logger.exception(
                "Reload from %s failed; generation %d (step %d) keeps "
                "serving", model_dir, old_gen.gen_id, old_gen.step,
            )
            raise
        return self._swap(new_gen, model_dir, kind="full")

    def _swap(self, new_gen: Generation, model_dir: str, kind: str) -> Generation:
        with self._lock:
            old_gen = self._generation
            self._generation = new_gen
        inflight_at_swap = old_gen.inflight()
        leftover = old_gen.drain(self._drain_timeout_s)
        if leftover:
            logger.warning(
                "Generation %d still has %d dispatch(es) in flight after "
                "%.1fs drain", old_gen.gen_id, leftover, self._drain_timeout_s
            )
        obs.journal().record(
            "model_swap",
            kind=kind,
            outcome="applied",
            generation=new_gen.gen_id,
            step=new_gen.step,
            old_generation=old_gen.gen_id,
            old_step=old_gen.step,
            model_dir=model_dir,
            drained_inflight=inflight_at_swap,
            undrained=leftover,
            event_time=new_gen.event_time,
        )
        logger.info(
            "Hot-swapped (%s) generation %d (step %d) -> %d (step %d); "
            "drained %d in-flight dispatch(es)",
            kind, old_gen.gen_id, old_gen.step, new_gen.gen_id,
            new_gen.step, inflight_at_swap,
        )
        return new_gen

    def shadow_execute(self, features: Dict[str, np.ndarray],
                       generation: Optional[Generation] = None):
        """Run the compiled step against an EXPLICIT generation without
        touching the serving pointer — the canary gate's evaluation
        path: a built-but-uncommitted candidate generation answers the
        replay batches while the live one keeps serving.  Defaults to
        the current generation (the gate's baseline side)."""
        gen = generation if generation is not None else self.generation
        gen.begin()
        try:
            return np.asarray(gen.serve_fn(gen.variables, features))
        finally:
            gen.end()

    def build_delta_generation(self, delta_dir: str) -> Generation:
        """Build (but do NOT serve) the generation a delta checkpoint
        would produce: patch the current generation's host tables
        row-wise, re-place them with the generation's own shardings,
        and reuse its compiled step (shapes and placement are unchanged
        by construction — no recompile, no retrace).  The serving
        pointer is untouched; `commit_generation` performs the swap.
        Splitting build from commit is what lets the canary gate
        shadow-evaluate the candidate BEFORE any traffic sees it — a
        held candidate is simply dropped (its gen id burns; ids are
        monotone, not dense).

        Any failure — injected `serving.delta_apply` fault, integrity
        mismatch (the delta is quarantined), a chain gap (base_step !=
        the serving step) — leaves the old generation serving and
        journals a `model_swap` with ``outcome=rolled_back``, then
        re-raises."""
        from elasticdl_tpu.common import faults
        from elasticdl_tpu.checkpoint import delta as deltas
        from elasticdl_tpu.checkpoint.saver import verify_integrity
        import jax

        old_gen = self.generation
        try:
            spec = faults.fire("serving.delta_apply")
            if spec is not None and spec.kind == "error":
                raise RuntimeError(
                    f"FAULT INJECTION: delta apply failed ({spec.arg or 'error'})"
                )
            reason = verify_integrity(delta_dir)
            if reason is not None:
                deltas.quarantine_artifact(delta_dir, reason)
                raise ValueError(f"corrupt delta {delta_dir}: {reason}")
            loaded = deltas.load_delta(delta_dir)
            manifest = loaded["manifest"]
            if int(manifest["base_step"]) != old_gen.step:
                raise ValueError(
                    f"delta {delta_dir} chains from step "
                    f"{manifest['base_step']} but generation "
                    f"{old_gen.gen_id} serves step {old_gen.step}"
                )
            # Patch copies of the current host tables row-wise.
            new_tables = {}
            for key, (rows, vals, _meta) in loaded["tables"].items():
                base = old_gen.served.tables.get(key)
                if base is None:
                    raise ValueError(
                        f"delta {delta_dir} patches unknown table {key!r}"
                    )
                patched = np.array(base)
                if rows.size:
                    patched[rows] = vals
                new_tables[key] = patched
            # Resolve the delta's dense ref-tree against the patched
            # tables (refs are "tables/<i>.npy" paths; index -> key via
            # the manifest).
            key_by_file = {
                f"tables/{meta['index']}.npy": key
                for key, (_r, _v, meta) in loaded["tables"].items()
            }

            def resolve(leaf):
                if isinstance(leaf, dict) and "__table__" in leaf:
                    key = key_by_file.get(leaf["__table__"])
                    if key is None or key not in new_tables:
                        raise ValueError(
                            f"delta dense tree references unknown table "
                            f"file {leaf['__table__']!r}"
                        )
                    return new_tables[key]
                return leaf

            from elasticdl_tpu.serving.export import _map_tree_with_refs

            host_variables = _map_tree_with_refs(loaded["dense"], resolve)
            variables = jax.device_put(host_variables, old_gen.shardings)
            signature = dict(old_gen.served.signature)
            signature["step"] = int(manifest["step"])
            signature["event_time"] = float(manifest.get("event_time", 0.0))
            served = ServingModel(
                old_gen.served.model,
                host_variables,
                signature,
                old_gen.served.base_dir,
                tables=new_tables,
            )
            with self._lock:
                if self._generation is not old_gen:
                    raise RuntimeError(
                        "generation changed under delta apply; re-resolve "
                        "the chain"
                    )
                gen_id = self._next_gen_id
                self._next_gen_id += 1
            new_gen = Generation(
                gen_id,
                delta_dir,
                served,
                variables,
                old_gen.serve_fn,  # same shapes+placement: reuse the compile
                shardings=old_gen.shardings,
                event_time=float(manifest.get("event_time", 0.0)),
            )
        except Exception as exc:
            obs.journal().record(
                "model_swap",
                kind="delta",
                outcome="rolled_back",
                generation=old_gen.gen_id,
                step=old_gen.step,
                old_generation=old_gen.gen_id,
                old_step=old_gen.step,
                model_dir=delta_dir,
                reason=repr(exc),
            )
            logger.exception(
                "Delta apply from %s failed; generation %d (step %d) "
                "keeps serving", delta_dir, old_gen.gen_id, old_gen.step,
            )
            raise
        return new_gen

    def commit_generation(self, new_gen: Generation,
                          model_dir: str) -> Generation:
        """Serve a generation built by `build_delta_generation`: the
        same pointer-swap + drain protocol as `reload` (journaled
        `model_swap` kind="delta" outcome="applied")."""
        return self._swap(new_gen, model_dir, kind="delta")

    def apply_delta(self, delta_dir: str) -> Generation:
        """Build + commit in one step — the ungated path (and the
        original API).  See `build_delta_generation` for the failure
        contract."""
        return self.commit_generation(
            self.build_delta_generation(delta_dir), delta_dir)

    # -- readouts --------------------------------------------------------

    @property
    def mesh(self):
        return self._mesh

    @property
    def sparse_kernel(self) -> str:
        return self._kernel

    @property
    def generation(self) -> Generation:
        """The currently-serving generation.  Besides the hot-swap
        plane, request tracing reads ``generation.gen_id`` per sampled
        request (frontend.py) so exemplars journaled across a swap
        attribute their latency to the model that actually served
        them."""
        with self._lock:
            return self._generation

    def jitted_entrypoints(self) -> Dict[str, Any]:
        """Provider for the step-anatomy RetraceWatcher: the current
        generation's compiled step (a fresh generation starts a fresh
        jit cache, so watch baselines reset at swap)."""
        with self._lock:
            gen = self._generation
        return {"serve_step": gen.serve_fn}

    def stats(self) -> dict:
        """Bounded host-side snapshot for the frontend's Stats RPC and
        the serving_telemetry journal event."""
        with self._lock:
            gen = self._generation
        return {
            "generation": gen.gen_id,
            "step": gen.step,
            "model_dir": gen.model_dir,
            "inflight": gen.inflight(),
            "sparse_kernel": self._kernel,
            "devices": int(self._mesh.devices.size),
            # Event-time frontier of the servable model: the freshness
            # SLO's serving-side input (0.0 for pre-delta artifacts).
            "model_event_time": gen.event_time,
        }
