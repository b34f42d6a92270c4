# sharding-compile-layer — the one sanctioned mesh context (see
# analysis/jax_rules.py sharding-coverage): every jit/shard_map this
# module builds applies placements from a rule table or explicit specs,
# and tests/test_compile.py gates each (trainer, rule-table) config with
# HLO-structure parity, so per-call-site sharding checks are owned here.
"""Declarative sharding compile layer: one place that turns (step fn,
param pytree, partition-rule table, mesh) into the compiled step.

ROADMAP item 3 named the problem: `dp_trainer.py`, `ps_trainer.py`, and
`ring_attention.py` each hand-rolled their mesh/sharding decisions —
three private copies of "which leaf lives where", each with its own
`jax.jit(in_shardings=...)` plumbing, donation flags, and (for the
ring) `shard_map` fallback shims.  New parallelism forms meant new
trainers.  This module centralizes the decision the way Titanax's
compile module and fmengine's `match_partition_rules` do (SNIPPETS
[2]/[3]):

- **Rule tables** (`Rule`, `RuleTable.shardings`): an ordered list of
  (regex over the '/'-joined leaf path, PartitionSpec-or-callable)
  entries matched over a param/state pytree, first match wins.  Scalars
  replicate without consulting the table (partitioning a 0-d leaf is
  meaningless); a non-scalar leaf no rule matches is an ERROR — silent
  XLA layout guessing is exactly what the table exists to prevent.
  Size-aware placements (FSDP's min-leaf/divisibility tests, the PS
  table's block-divisibility test) are callable rules: they receive
  (path, shape) and return the spec, so the *policy* still reads as one
  table entry.
- **Strategy selection** (`select_strategy`, `CompilePlan.compile`):
  jit-with-shardings ("pjit") when explicit per-leaf shardings cover
  the argument pytrees; `shard_map` for map-style bodies that need
  per-device rank-local views (ring attention's ppermute ring, the
  fused Pallas sparse kernels — `pallas_call` has no SPMD partitioning
  rule, so manual sharding is the only way a kernel body runs on a
  multi-device mesh).
- **One plumbing point**: donation (`donate_argnums`) and
  `in/out_shardings` are applied here, and every compile journals a
  `compile_plan` event (trainer, strategy, rule hits/misses, donated
  argnums — scripts/validate_journal.py) so a postmortem can always
  answer "what placement did this job actually compile?".

The trainers now build every compiled entry point through this module
(gated by a grep test in tests/test_compile.py), so a new parallelism
form is a rule-table entry, not a new trainer.
"""

from __future__ import annotations

import logging
import re
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("parallel.compile")

#: Sentinel distinguishing "not passed" from an explicit None (jax gives
#: None meaning in sharding kwargs).
_UNSET = object()


# ---------------------------------------------------------------------------
# Partition-rule tables
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Rule:
    """One partition rule: `pattern` is a regex searched against the
    '/'-joined leaf path (dict keys, attr names, sequence indices);
    `spec` is a `PartitionSpec`, or a callable `(path, shape) ->
    PartitionSpec` for size/shape-aware placements (FSDP min-leaf,
    table block divisibility)."""

    pattern: str
    spec: Any


def _key_str(key) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(key, attr):
            return str(getattr(key, attr))
    return str(key)


def tree_paths(tree) -> List[Tuple[str, Any]]:
    """[(path string, leaf)] over a pytree, '/'-joined keys."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [
        ("/".join(_key_str(k) for k in path), leaf) for path, leaf in flat
    ]


def _leaf_shape(leaf) -> tuple:
    shape = getattr(leaf, "shape", None)
    if shape is None:
        shape = np.shape(leaf)
    return tuple(shape)


class RuleTable:
    """Ordered partition rules over a pytree (fmengine's
    `match_partition_rules`, shape-aware).  First match wins; scalar
    leaves (ndim 0 or one element) replicate without consulting the
    table; an unmatched non-scalar leaf raises."""

    def __init__(self, rules: Sequence[Rule], name: str = ""):
        self.name = name
        self.rules = tuple(rules)
        self._compiled = [re.compile(rule.pattern) for rule in self.rules]

    def match(self, tree):
        """(specs pytree, stats) — stats carries per-rule hit counts and
        the total leaves that fell to the scalar default."""
        import jax
        from jax.sharding import PartitionSpec as P

        hits = [0] * len(self.rules)
        scalars = 0
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs = []
        for path, leaf in flat:
            path_str = "/".join(_key_str(k) for k in path)
            shape = _leaf_shape(leaf)
            if len(shape) == 0 or int(np.prod(shape)) == 1:
                scalars += 1
                specs.append(P())
                continue
            for i, regex in enumerate(self._compiled):
                if regex.search(path_str) is not None:
                    hits[i] += 1
                    spec = self.rules[i].spec
                    if callable(spec):
                        spec = spec(path_str, shape)
                    specs.append(spec)
                    break
            else:
                raise ValueError(
                    f"partition rule table {self.name!r} has no rule for "
                    f"leaf {path_str!r} (shape {shape}) — every non-scalar "
                    "leaf must be covered; add a rule (or a catch-all "
                    "'.*' replicate entry) so the placement is declared, "
                    "not guessed"
                )
        stats = {
            "rule_hits": int(sum(hits)),
            # Unmatched non-scalar leaves raise above, so a SUCCESSFUL
            # match always reports 0 — the journaled invariant witness
            # that nothing fell through to a guessed layout.
            "rule_misses": 0,
            # Rules that matched nothing (e.g. a catch-all behind a
            # fully-covering specific rule) — dead-table-entry hygiene,
            # NOT a coverage hole.
            "unused_rules": int(sum(1 for h in hits if h == 0)),
            "scalars": scalars,
            "per_rule": {
                rule.pattern: hit for rule, hit in zip(self.rules, hits)
            },
        }
        return jax.tree_util.tree_unflatten(treedef, specs), stats

    def shardings(self, mesh, tree):
        """(NamedSharding pytree, stats) for `tree` on `mesh`."""
        import jax
        from jax.sharding import NamedSharding

        specs, stats = self.match(tree)
        return (
            jax.tree.map(lambda s: NamedSharding(mesh, s), specs),
            stats,
        )


def match_partition_rules(rules: Sequence[Rule], tree):
    """Functional form (SNIPPETS [3] parity): specs pytree only."""
    return RuleTable(rules).match(tree)[0]


# ---------------------------------------------------------------------------
# Strategy selection + the raw shard_map shim
# ---------------------------------------------------------------------------


def select_strategy(
    *, in_shardings=_UNSET, out_shardings=_UNSET, in_specs=None,
    out_specs=None,
) -> str:
    """'shard_map' for map-style bodies (per-shard specs given), 'pjit'
    when explicit shardings cover the pytree (or the body is a plain
    whole-array program the partitioner owns)."""
    if in_specs is not None or out_specs is not None:
        if (in_specs is None) != (out_specs is None):
            raise ValueError(
                "shard_map strategy needs BOTH in_specs and out_specs "
                "(a map-style body's input and output rank-local views "
                "must both be declared)"
            )
        return "shard_map"
    return "pjit"


def shard_map_call(
    fn: Callable,
    mesh,
    *,
    in_specs,
    out_specs,
    check_vma: Optional[bool] = None,
):
    """`jax.shard_map` over `mesh`.  Trace-safe (no journaling): model
    bodies build shard_mapped callables under trace (ring attention
    inside a zoo model's `__call__`).

    `check_vma=False` is the documented escape hatch for Pallas bodies
    in interpret mode (CPU tests/dryruns trip a jax limitation inside
    the kernel interpreter: "Primitive dynamic_slice requires varying
    manual axes to match"); collective placement for those paths is
    pinned by HLO-structure tests instead.
    """
    import jax

    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(fn, **kwargs)


def jit_utility(fn: Callable, **jit_kwargs):
    """Sanctioned passthrough for NON-step compiles whose outputs are
    layout-irrelevant (e.g. a specs-only init jit whose dead param
    computations XLA eliminates).  Step functions go through
    `CompilePlan.compile` so their placement is declared and journaled.
    """
    import jax

    return jax.jit(fn, **jit_kwargs)


# ---------------------------------------------------------------------------
# The compile plan
# ---------------------------------------------------------------------------


class _EngineLines(logging.Handler):
    """Collects the `... engine: pallas|xla ... (why)` lines the kernels
    log while a program is traced, so that a build served from the store
    can still say which kernels its program holds."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.lines: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        line = record.getMessage()
        if " engine: " in line and line not in self.lines:
            self.lines.append(line)

    def __enter__(self) -> List[str]:
        logging.getLogger("elasticdl_tpu").addHandler(self)
        return self.lines

    def __exit__(self, *exc) -> None:
        logging.getLogger("elasticdl_tpu").removeHandler(self)


class _BuildSpan:
    """A jitted entrypoint whose FIRST call runs inside a `compile.build`
    span and goes through the executable store
    (`common/executable_store.py`) where the process has one:

    - hit: the stored executable is loaded and called; nothing is traced
      or lowered (`aot_hit` true, `aot_load_s`; `trace_s` / `lower_s` /
      `backend_s` read 0.0, the persistent cache is not consulted);
    - miss: `lower().compile()` through the persistent cache as before,
      and the result is written to the store;
    - wherever the store cannot be sound or cannot work (`aot_skip` says
      why: no store, more than one process, a program that does not
      serialise, ...) the jitted function is called exactly as before.

    The span carries `entrypoint`, `aot_hit`, `aot_load_s`, `aot_key`, and
    what JAX's own events say the call was made of
    (`common/compile_cache.BuildParts`): `trace_s`, `lower_s`,
    `backend_s` (XLA's compile or the persistent cache's load),
    `cache_read_s` (the part of `backend_s` that was retrieval),
    `programs` and `cache_hit`.  What is left of the span is its own:
    the key, argument handling, `device_put`s, the dispatch.

    Later calls run the same executable, whose own argument check (the
    C++ one a jitted call makes: treedef, each leaf's shape and dtype,
    committed shardings; nothing per call is added in Python) refuses
    any other arguments before anything runs or is donated: such a call
    (a short last task) falls through to the jitted function.
    `_cache_size()` counts the stored or ahead-of-time build as one
    compile and adds the jitted function's own
    (`obs/stepstats.RetraceWatcher` reads it); other attributes
    (`lower`, ...) are the jitted function's."""

    def __init__(self, jitted, name: str, mesh=None,
                 donate_argnums: Tuple[int, ...] = (), static: bool = False):
        self._jitted, self._name, self._built = jitted, name, False
        self._mesh, self._donated, self._static = mesh, donate_argnums, static
        # The executable the first call loaded or built ahead of time.
        self._compiled = None

    def __call__(self, *args, **kwargs):
        if self._built:
            if self._compiled is not None:
                try:
                    return self._compiled(*args, **kwargs)
                except (TypeError, ValueError):
                    # Not the arguments it was compiled for (`TypeError`:
                    # tree, shapes, dtypes; `ValueError`: shardings),
                    # found before it ran: the jitted function's call,
                    # which raises what is raised today if they are
                    # wrong for it too.
                    pass
            return self._jitted(*args, **kwargs)
        from elasticdl_tpu.common import compile_cache
        from elasticdl_tpu.obs import tracing

        self._built = True
        with tracing.span("compile.build", entrypoint=self._name) as span:
            with compile_cache.measuring() as parts:
                out = self._first_call(span.fields, args, kwargs)
            span.fields.update(parts.fields())
        return out

    def _cache_size(self) -> int:
        return int(self._compiled is not None) + self._jitted._cache_size()

    def __getattr__(self, attr):
        return getattr(self._jitted, attr)

    def _key(self, store, args, kwargs) -> str:
        import jax

        from elasticdl_tpu.common import executable_store
        from elasticdl_tpu.parallel import packed

        if store is None:
            raise executable_store.Skip("the process has no executable store")
        if self._static:
            raise executable_store.Skip("static arguments")
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        return store.key(
            entrypoint=self._name, donate_argnums=self._donated,
            trace_state={"oov_debug": packed.oov_debug_enabled()},
            mesh=self._mesh, treedef=treedef,
            signature=[
                executable_store.leaf_signature(leaf) for leaf in leaves
            ],
        )

    def _first_call(self, fields: dict, args, kwargs):
        from elasticdl_tpu.common import compile_cache, executable_store

        fields.update(aot_hit=False, aot_load_s=0.0)
        store = compile_cache.executable_store()
        try:
            key = self._key(store, args, kwargs)
        except executable_store.Skip as skip:
            fields["aot_skip"] = str(skip)
            return self._jitted(*args, **kwargs)
        fields["aot_key"] = key[:12]
        started = time.monotonic()
        try:
            found = store.load(key, self._mesh)
        except executable_store.Skip as skip:
            fields["aot_skip"], found = str(skip), None
        if found is not None:
            self._compiled, record = found
            fields.update(
                aot_hit=True,
                aot_load_s=round(time.monotonic() - started, 6),
            )
            for line in record["engine_lines"]:
                logger.info("%s (stored build)", line)
            logger.info(
                "%s: loaded the stored build %s (%d bytes) in %.2fs",
                self._name, key[:12], record["bytes"], fields["aot_load_s"],
            )
            return self._compiled(*args, **kwargs)
        with _EngineLines() as lines:
            self._compiled = self._jitted.lower(*args, **kwargs).compile()
        started = time.monotonic()
        try:
            written = store.save(
                key, self._compiled,
                {"entrypoint": self._name, "engine_lines": lines},
            )
        except executable_store.Skip as skip:
            fields["aot_skip"] = str(skip)
        else:
            logger.info(
                "%s: stored the build as %s (%d bytes) in %.2fs",
                self._name, key[:12], written, time.monotonic() - started,
            )
        return self._compiled(*args, **kwargs)


def leaf_cutter(plan: "CompilePlan", leaves, **cutter_kwargs):
    """The programs that cut the large ones of `leaves` into pieces for a
    streamed save (`checkpoint/saver.py` `LeafCutter`), built now and
    through `plan`: each is a `compile.build` of its own, which the
    compile cache and the executable store keep, so that a worker that
    starts again reads them and a save compiles nothing.  They are
    programs of ONE device (a leaf that every device holds is cut on
    the first): under a mesh of several the store, which loads a
    program for the whole mesh, is not theirs, and they are plain jits
    that the compile cache alone keeps."""
    from elasticdl_tpu.checkpoint.saver import LeafCutter

    build = None
    if plan.mesh.devices.size == 1:
        def build(fn, name):
            return plan.compile(fn, name=name, journal=False)

    cutter = LeafCutter(build=build, **cutter_kwargs)
    cutter.warm(leaves)
    return cutter


def _journal_plan(record: Dict[str, Any]) -> None:
    # Host-side only (trainer init / _compile_steps time); the obs
    # plane never rides a traced step (trace-purity rule).
    from elasticdl_tpu import obs

    obs.journal().record("compile_plan", **record)


class CompilePlan:
    """The declarative compile context for one trainer: a mesh, an
    optional partition-rule table, and the journaling identity.

    `state_shardings(tree)` resolves the rule table over a state pytree
    (recording hits/misses for the next `compile_plan` event);
    `compile(fn, ...)` produces the compiled step — jit-with-shardings
    or shard_map per `select_strategy` — applying donation and
    in/out_shardings in this one place.
    """

    def __init__(self, mesh, rules: Optional[RuleTable] = None,
                 trainer: str = ""):
        self.mesh = mesh
        self.rules = rules
        self.trainer = trainer
        self._last_stats: Dict[str, int] = {}

    # -- rule resolution -------------------------------------------------

    def state_shardings(self, tree):
        """NamedSharding pytree for `tree` from this plan's rule table."""
        if self.rules is None:
            raise ValueError(
                f"CompilePlan for {self.trainer!r} has no rule table; "
                "pass explicit shardings to compile() instead"
            )
        shardings, stats = self.rules.shardings(self.mesh, tree)
        self._last_stats = stats
        return shardings

    def replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    # -- the compile entry ----------------------------------------------

    def compile(
        self,
        fn: Callable,
        *,
        name: str,
        in_shardings=_UNSET,
        out_shardings=_UNSET,
        in_specs=None,
        out_specs=None,
        donate_argnums: Tuple[int, ...] = (),
        static_argnums=None,
        check_vma: Optional[bool] = None,
        journal: bool = True,
    ):
        """The compiled callable for `fn` under this plan.

        pjit strategy: `jax.jit` with the given shardings + donation.
        shard_map strategy: the shard_mapped body wrapped in `jax.jit`
        (out_shardings derived from out_specs; donation still applies),
        so callers get one compiled program either way.
        """
        import jax

        strategy = select_strategy(
            in_shardings=in_shardings, out_shardings=out_shardings,
            in_specs=in_specs, out_specs=out_specs,
        )
        if strategy == "shard_map":
            body = shard_map_call(
                fn, self.mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=check_vma,
            )
            # The shard_map's own specs pin every operand's rank-local
            # layout; the jit wrapper only owns donation + caching.
            compiled = jax.jit(
                body,
                donate_argnums=donate_argnums,
                static_argnums=static_argnums,
            )
        else:
            kwargs: Dict[str, Any] = {}
            if in_shardings is not _UNSET:
                kwargs["in_shardings"] = in_shardings
            if out_shardings is not _UNSET:
                kwargs["out_shardings"] = out_shardings
            if static_argnums is not None:
                kwargs["static_argnums"] = static_argnums
            compiled = jax.jit(
                fn, donate_argnums=donate_argnums, **kwargs
            )
        if journal:
            stats = self._last_stats
            _journal_plan({
                "trainer": self.trainer,
                "name": name,
                "strategy": strategy,
                "rule_table": self.rules.name if self.rules else "",
                "rule_hits": stats.get("rule_hits", 0),
                "rule_misses": stats.get("rule_misses", 0),
                "unused_rules": stats.get("unused_rules", 0),
                "donated_argnums": list(donate_argnums),
                "devices": int(self.mesh.devices.size),
            })
        return _BuildSpan(
            compiled, name, mesh=self.mesh, donate_argnums=donate_argnums,
            static=static_argnums not in (None, ()),
        )
