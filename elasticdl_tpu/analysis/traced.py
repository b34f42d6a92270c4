"""Tracedness: which functions execute under a JAX trace.

The control-plane rules in `rules.py` are per-line syntactic checks.
The hot-path rules in `jax_rules.py` need a stronger question answered:
*does this statement run inside `jax.jit` / `pjit` / `shard_map` /
`lax.scan` / a Pallas kernel?* — because `np.asarray(x)` is a harmless
host conversion in a data-loader and a device sync (or a tracer leak)
inside a compiled step.

This module computes that property per file, stdlib-only:

1. **Function index** — every `def` / `async def` / `lambda` in the
   module, with its qualified name, enclosing class, and enclosing
   function scopes.
2. **Trace roots** — functions that enter a trace directly:
   decorated with a jit-like decorator (`@jax.jit`, `@pjit`,
   `@functools.partial(jax.jit, ...)`, `@jax.custom_vjp`, or
   `@nn.compact` — flax module bodies run under the caller's jit in
   this codebase), or passed to a trace-entry call (`jax.jit(fn, ...)`,
   `shard_map(fn, ...)`, `jax.lax.scan(body, ...)`,
   `pl.pallas_call(kernel, ...)`, `f.defvjp(fwd, bwd)`, ...), including
   through `functools.partial`.  Local aliases of trace entries
   (``sm = _shard_map()``) are tracked per scope.
3. **Transitive closure** — a function referenced (called or passed)
   from a traced function's body is itself traced: the helper a jitted
   step calls runs under the same trace.  The closure is per-file
   here; when the analyzer scans more than one file, the whole-program
   index (`program.py`) resolves imports and receiver classes and
   extends it ACROSS modules via `TracedIndex.mark_traced`, so a
   helper imported from another package module is traced too.

On top of the call graph sits a small **intraprocedural symbol pass**:
`array_tainted_names` marks the names in a traced function that hold
traced arrays (parameters, results of `jnp.*`/`jax.*` calls, results of
calls to other traced functions, and anything assigned from those),
while *de-tainting* static accessors (`x.shape`, `x.dtype`, `x.ndim`,
`x.size`, `len(...)`) so shape arithmetic — the bread and butter of
kernel code — never trips a host-sync rule.

Everything is per-module: cross-module tracedness (a model's
`__call__` jitted by a trainer in another file) is approximated by the
`nn.compact` root above, which is exactly how the model zoo runs.
Stdlib-only, like the rest of the analyzer.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass
from typing import Dict, Iterable, Iterator, List, Optional, Set, Tuple

from elasticdl_tpu.analysis.core import SourceFile

#: Call names (last dotted segment, leading underscores ignored) that
#: trace their function-valued arguments.
TRACE_ENTRY_NAMES = frozenset(
    {
        "jit",
        "pjit",
        "shard_map",
        # parallel/compile.py entry points: `shard_map_call(fn, ...)`
        # and `CompilePlan.compile(fn, ...)` trace their function
        # argument exactly like the jax primitives they wrap — ported
        # trainers build every step through them, and the hot-path
        # rules must keep seeing those bodies as traced.  ("compile"
        # exact-matches the last segment; `re.compile("...")` is
        # harmless — a string argument marks nothing.)
        "shard_map_call",
        "compile",
        "pallas_call",
        "scan",
        "associative_scan",
        "fori_loop",
        "while_loop",
        "cond",
        "switch",
        "vmap",
        "pmap",
        "grad",
        "value_and_grad",
        "custom_vjp",
        "custom_jvp",
        "defvjp",
        "defjvp",
        "remat",
    }
)

#: Entry names that are jit *compilation* sites specifically (the rules
#: about donation / sharding / retracing only apply to these).
#: `compile` = CompilePlan.compile, the declarative layer's jit-building
#: entry (parallel/compile.py) — its sites carry the same
#: donation/sharding kwargs jax.jit does.
JIT_ENTRY_NAMES = frozenset({"jit", "pjit", "compile"})

#: Decorator name segments that make the decorated function a trace root.
TRACED_DECORATOR_NAMES = frozenset(
    {"jit", "pjit", "compact", "custom_vjp", "custom_jvp", "remat",
     "checkpoint"}
)

#: Attribute accesses that yield static (host) values even on tracers.
STATIC_ATTRS = frozenset(
    {"shape", "dtype", "ndim", "size", "aval", "sharding"}
)

#: Call roots whose results are traced arrays (for the taint pass).
ARRAY_NAMESPACES = ("jnp", "jax", "lax", "pl", "pltpu")

#: Trace-entry keyword arguments that carry *specifications* (shardings,
#: static/donate argnums, block specs), not traced callables — a helper
#: referenced inside `out_shardings=self._state_shardings(...)` does NOT
#: run under the trace.
_SPEC_KWARGS = frozenset(
    {
        "in_shardings",
        "out_shardings",
        "in_axis_resources",
        "out_axis_resources",
        "static_argnums",
        "static_argnames",
        "donate_argnums",
        "donate_argnames",
        "device",
        "backend",
        "mesh",
        "in_specs",
        "out_specs",
        "grid",
        "grid_spec",
        "out_shape",
        "scratch_shapes",
        "input_output_aliases",
        "interpret",
        "check_vma",
        "axis_name",
        "axis_size",
        "nondiff_argnums",
        "length",
        "unroll",
        "compiler_params",
        "cost_estimate",
        "name",
    }
)


#: Constructor names whose arguments are *specifications*, not traced
#: callables, even when the constructed object rides a POSITIONAL
#: trace-entry argument (``pl.pallas_call(kernel, pl.GridSpec(...))``).
#: Index-map lambdas inside BlockSpec/GridSpec run at trace SETUP on
#: the host (shape math, np, closures all legal) — marking them traced
#: false-positives every hot-path rule on kernel call sites.
_SPEC_CONSTRUCTOR_NAMES = frozenset(
    {
        "BlockSpec",
        "GridSpec",
        "PrefetchScalarGridSpec",
        "CompilerParams",
        "InterpretParams",
        "CostEstimate",
        "ShapeDtypeStruct",
    }
)


def _walk_skipping_spec_constructors(expr: ast.AST) -> Iterator[ast.AST]:
    """ast.walk that prunes spec-constructor call subtrees (and their
    index-map lambdas) out of trace-root marking."""
    stack = [expr]
    while stack:
        node = stack.pop()
        if (
            isinstance(node, ast.Call)
            and _last_segment(node.func) in _SPEC_CONSTRUCTOR_NAMES
        ):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _last_segment(node: ast.AST) -> Optional[str]:
    """Last dotted segment of a Name/Attribute chain ('jax.lax.scan' ->
    'scan'), with leading underscores stripped ('_shard_map' ->
    'shard_map')."""
    if isinstance(node, ast.Attribute):
        name = node.attr
    elif isinstance(node, ast.Name):
        name = node.id
    else:
        return None
    return name.lstrip("_") or name


def _is_compile_plan_call(call: ast.Call) -> bool:
    """Only `CompilePlan.compile(fn, ...)` — a method call taking a
    FUNCTION-REFERENCE first argument — is the compile-layer entry.
    `re.compile(...)` (any argument shape: literal, f-string,
    concatenation, variable) and `lowered.compile()` are not."""
    func = call.func
    if isinstance(func, ast.Attribute):
        receiver = func.value
        if isinstance(receiver, ast.Name) and receiver.id == "re":
            return False
    if not call.args:
        return False
    return isinstance(call.args[0], (ast.Name, ast.Attribute, ast.Lambda))


def _entry_name_of(segment: Optional[str]) -> Optional[str]:
    if not segment:
        return None
    if segment in TRACE_ENTRY_NAMES:
        return segment
    # Suffix matching only for distinctive multi-word entries: a local
    # `_shard_map()` wrapper is a trace entry, but a compiled callable
    # named `train_window_jit` is NOT a jit construction site.
    for entry in TRACE_ENTRY_NAMES:
        if "_" in entry and segment.endswith("_" + entry):
            return entry
    return None


@dataclass
class FunctionInfo:
    """One def/lambda plus enough context to resolve its references."""

    qualname: str
    name: str
    node: ast.AST  # FunctionDef | AsyncFunctionDef | Lambda
    lineno: int
    params: Tuple[str, ...]
    self_class: Optional[str]  # class providing `self` inside the body
    is_method: bool  # defined directly in a class body
    parent_function: Optional[str]  # nearest enclosing function qualname
    decorators: Tuple[ast.AST, ...] = ()

    @property
    def data_params(self) -> Tuple[str, ...]:
        """Parameters excluding the self/cls receiver."""
        if self.is_method and self.params and self.params[0] in ("self", "cls"):
            return self.params[1:]
        return self.params


@dataclass
class JitSite:
    """One `jax.jit(...)` / `pjit(...)` compilation site (call form or
    decorator form)."""

    node: ast.AST  # the Call (or the decorated def for bare decorators)
    entry: str  # 'jit' or 'pjit'
    target: Optional[str]  # resolved FunctionInfo qualname, if any
    keywords: Dict[str, ast.AST]
    bound_name: Optional[str]  # '_train_step' from self._train_step = jit(..)
    enclosing_function: Optional[str]  # qualname of the fn holding the call
    in_loop: bool
    in_mesh_context: bool  # lexically inside `with ...mesh...:`
    is_decorator: bool = False

    def donate_positions(self) -> Optional[Tuple[int, ...]]:
        """Static donate_argnums positions, or None if absent/dynamic."""
        arg = self.keywords.get("donate_argnums")
        if arg is None:
            return None
        if isinstance(arg, ast.Constant) and isinstance(arg.value, int):
            return (arg.value,)
        if isinstance(arg, (ast.Tuple, ast.List)):
            out = []
            for elt in arg.elts:
                if not (
                    isinstance(elt, ast.Constant)
                    and isinstance(elt.value, int)
                ):
                    return None
                out.append(elt.value)
            return tuple(out)
        return None

    def static_positions(self) -> Tuple[int, ...]:
        arg = self.keywords.get("static_argnums")
        if isinstance(arg, ast.Constant) and isinstance(arg.value, int):
            return (arg.value,)
        if isinstance(arg, (ast.Tuple, ast.List)):
            return tuple(
                elt.value
                for elt in arg.elts
                if isinstance(elt, ast.Constant)
                and isinstance(elt.value, int)
            )
        return ()


class _Scope:
    __slots__ = ("qualname", "functions", "entry_aliases")

    def __init__(self, qualname: str):
        self.qualname = qualname
        self.functions: Dict[str, str] = {}  # local name -> func qualname
        self.entry_aliases: Set[str] = set()  # names bound to trace entries


@dataclass
class _Ctx:
    """Lexical context threaded through the walk."""

    scopes: List[_Scope]
    class_qualname: Optional[str]  # non-None only directly inside a class
    self_class: Optional[str]  # nearest method-owning class (for self.X)
    function: Optional[str]  # enclosing function qualname
    loop_depth: int = 0
    mesh_depth: int = 0

    def replace(self, **kw) -> "_Ctx":
        data = dict(
            scopes=self.scopes,
            class_qualname=self.class_qualname,
            self_class=self.self_class,
            function=self.function,
            loop_depth=self.loop_depth,
            mesh_depth=self.mesh_depth,
        )
        data.update(kw)
        return _Ctx(**data)


class TracedIndex:
    """Per-file tracedness database.  Build with `traced_index(source)`."""

    def __init__(self, source: SourceFile):
        self.source = source
        self.functions: Dict[str, FunctionInfo] = {}
        self.by_node: Dict[int, FunctionInfo] = {}  # id(node) -> info
        self.traced: Dict[str, str] = {}  # qualname -> reason
        self.jit_sites: List[JitSite] = []
        self._refs: Dict[str, Set[str]] = {}  # qualname -> referenced fns
        self._class_methods: Dict[str, Dict[str, str]] = {}
        self._pending_entry_calls: List[Tuple[ast.Call, _Ctx]] = []
        self._pending_refs: List[Tuple[str, _Ctx]] = []
        #: jit-site targets resolve AFTER the walk: `__init__` may jit a
        #: method defined later in the class body.
        self._pending_jit_targets: List[Tuple[JitSite, ast.AST, _Ctx]] = []
        self._module_scope = _Scope("")
        self._build()

    # -- public API ----------------------------------------------------

    def is_traced(self, fn) -> bool:
        info = fn if isinstance(fn, FunctionInfo) else self.by_node.get(id(fn))
        return bool(info) and info.qualname in self.traced

    def mark_traced(self, qualname: str, reason: str) -> bool:
        """Mark `qualname` traced with `reason`; True when newly marked.

        The per-file walk marks same-file tracedness; the whole-program
        index (program.py) calls this to extend the closure across
        module boundaries — a helper that only a jitted fn in ANOTHER
        module calls is traced too, and the per-file jax rules see it
        because the index is shared (memoized via traced_index())."""
        if qualname in self.traced:
            return False
        self.traced[qualname] = reason
        return True

    def traced_infos(self) -> Iterator[FunctionInfo]:
        for qualname, info in self.functions.items():
            if qualname in self.traced:
                yield info

    def reason(self, qualname: str) -> str:
        return self.traced.get(qualname, "")

    def own_body(self, info: FunctionInfo) -> Iterator[ast.AST]:
        """Walk a function's body, NOT descending into nested defs or
        lambdas (those are separate FunctionInfos, traced or not)."""
        body = info.node.body
        if not isinstance(body, list):  # Lambda
            body = [body]
        stack: List[ast.AST] = list(body)
        while stack:
            node = stack.pop()
            yield node
            for child in ast.iter_child_nodes(node):
                if isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
                ):
                    continue
                stack.append(child)

    def donated_callables(self) -> Dict[str, Tuple[int, ...]]:
        """bound name -> donated argument positions, for every jit site
        assigned to a name (`self._train_step = jax.jit(..,
        donate_argnums=(0,))` -> {'_train_step': (0,)})."""
        out: Dict[str, Tuple[int, ...]] = {}
        for site in self.jit_sites:
            positions = site.donate_positions()
            if site.bound_name and positions:
                out[site.bound_name] = positions
        return out

    # -- taint (intraprocedural symbol pass) ---------------------------

    def array_tainted_names(self, info: FunctionInfo) -> Set[str]:
        """Names in `info`'s body that (likely) hold traced arrays."""
        tainted: Set[str] = set(info.data_params)
        # Two passes reach a fixpoint for straight-line code and the
        # simple re-assignment chains that occur in step functions.
        for _ in range(2):
            for node in self.own_body(info):
                targets: List[ast.AST] = []
                value: Optional[ast.AST] = None
                if isinstance(node, ast.Assign):
                    targets, value = node.targets, node.value
                elif isinstance(node, ast.AnnAssign) and node.value is not None:
                    targets, value = [node.target], node.value
                elif isinstance(node, ast.AugAssign):
                    targets, value = [node.target], node.value
                elif isinstance(node, ast.For):
                    targets, value = [node.target], node.iter
                if value is None or not self.expr_tainted(value, tainted):
                    continue
                for target in targets:
                    for name_node in ast.walk(target):
                        if isinstance(name_node, ast.Name):
                            tainted.add(name_node.id)
        return tainted

    def expr_tainted(self, expr: ast.AST, tainted: Set[str]) -> bool:
        """True when `expr` (likely) evaluates to a traced array: it
        mentions a tainted name or an array-producing call, outside of
        static accessors (`x.shape`, `len(x)`, ...)."""
        for node in self._walk_non_static(expr):
            if isinstance(node, ast.Name) and node.id in tainted:
                return True
            if isinstance(node, ast.Call):
                root = _dotted_root(node.func)
                if root in ARRAY_NAMESPACES:
                    return True
                resolved = self._resolve_loose(node.func)
                if resolved is not None and resolved in self.traced:
                    return True
        return False

    @staticmethod
    def _walk_non_static(expr: ast.AST) -> Iterator[ast.AST]:
        """Walk an expression, pruning static-accessor subtrees
        (`x.shape[0]` contributes nothing to array taint)."""
        stack = [expr]
        while stack:
            node = stack.pop()
            if isinstance(node, ast.Attribute) and node.attr in STATIC_ATTRS:
                continue
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                if node.func.id == "len":
                    continue
                if (
                    node.func.id == "getattr"
                    and len(node.args) >= 2
                    and isinstance(node.args[1], ast.Constant)
                    and node.args[1].value in STATIC_ATTRS
                ):
                    continue
            yield node
            stack.extend(ast.iter_child_nodes(node))

    # -- construction --------------------------------------------------

    def _build(self):
        ctx = _Ctx(
            scopes=[self._module_scope],
            class_qualname=None,
            self_class=None,
            function=None,
        )
        for stmt in self.source.tree.body:
            self._visit(stmt, ctx)
        for site, expr, site_ctx in self._pending_jit_targets:
            site.target = self._resolve_ref(expr, site_ctx)
        self._mark_decorator_roots()
        self._mark_entry_call_roots()
        self._close_transitively()

    def _visit(self, node: ast.AST, ctx: _Ctx):
        if isinstance(node, ast.ClassDef):
            qualname = self._child_qualname(ctx, node.name)
            self._class_methods.setdefault(qualname, {})
            for deco in node.decorator_list:
                self._visit(deco, ctx)
            inner = ctx.replace(class_qualname=qualname, self_class=qualname)
            for stmt in node.body:
                self._visit(stmt, inner)
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            self._register_function(node, ctx)
            return
        if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            body_nodes = set(map(id, list(node.body) + list(node.orelse)))
            loop_ctx = ctx.replace(loop_depth=ctx.loop_depth + 1)
            for child in ast.iter_child_nodes(node):
                self._visit(child, loop_ctx if id(child) in body_nodes else ctx)
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            meshy = any(
                _mentions_mesh(item.context_expr) for item in node.items
            )
            for item in node.items:
                self._visit(item.context_expr, ctx)
                if item.optional_vars is not None:
                    self._visit(item.optional_vars, ctx)
            body_ctx = (
                ctx.replace(mesh_depth=ctx.mesh_depth + 1) if meshy else ctx
            )
            for stmt in node.body:
                self._visit(stmt, body_ctx)
            return
        if isinstance(node, ast.Assign):
            self._note_alias(node, ctx)
            if isinstance(node.value, ast.Call):
                entry = self._entry_of(node.value, ctx)
                if entry in JIT_ENTRY_NAMES:
                    self._record_jit_site(
                        node.value, entry, ctx,
                        bound_name=_bound_name(node.targets),
                    )
            for child in ast.iter_child_nodes(node):
                self._visit(child, ctx)
            return
        if isinstance(node, ast.Call):
            entry = self._entry_of(node, ctx)
            if entry is not None:
                self._pending_entry_calls.append((node, ctx))
                if entry in JIT_ENTRY_NAMES and not any(
                    site.node is node for site in self.jit_sites
                ):
                    self._record_jit_site(node, entry, ctx, bound_name=None)
            for child in ast.iter_child_nodes(node):
                self._visit(child, ctx)
            return
        for child in ast.iter_child_nodes(node):
            self._visit(child, ctx)

    def _child_qualname(self, ctx: _Ctx, name: str) -> str:
        parent = ctx.class_qualname or ctx.scopes[-1].qualname
        return f"{parent}.{name}" if parent else name

    def _register_function(self, node, ctx: _Ctx):
        if isinstance(node, ast.Lambda):
            name = f"<lambda:{node.lineno}:{node.col_offset}>"
            decorators: Tuple[ast.AST, ...] = ()
            params = tuple(a.arg for a in node.args.args)
        else:
            name = node.name
            decorators = tuple(node.decorator_list)
            params = tuple(
                a.arg
                for a in (
                    node.args.posonlyargs
                    + node.args.args
                    + node.args.kwonlyargs
                )
            )
        qualname = self._child_qualname(ctx, name)
        if qualname in self.functions:  # redefinition / lambda collision
            qualname = f"{qualname}@{node.lineno}"
        is_method = ctx.class_qualname is not None and not isinstance(
            node, ast.Lambda
        )
        info = FunctionInfo(
            qualname=qualname,
            name=name,
            node=node,
            lineno=node.lineno,
            params=params,
            self_class=ctx.self_class,
            is_method=is_method,
            parent_function=ctx.function,
            decorators=decorators,
        )
        self.functions[qualname] = info
        self.by_node[id(node)] = info
        if not isinstance(node, ast.Lambda):
            if is_method:
                # Methods are visible as `self.<name>`, NOT as bare names
                # in enclosing scopes (class bodies are not a scope for
                # name resolution inside methods).
                self._class_methods[ctx.class_qualname].setdefault(
                    name, qualname
                )
            else:
                ctx.scopes[-1].functions.setdefault(name, qualname)
        # Decorators and default values evaluate in the ENCLOSING scope.
        for deco in decorators:
            self._visit(deco, ctx)
        if not isinstance(node, ast.Lambda):
            for default in node.args.defaults + [
                d for d in node.args.kw_defaults if d is not None
            ]:
                self._visit(default, ctx)
        # The body runs in a fresh function scope; `self` still resolves
        # against the owning class, but nested defs are not methods.
        inner_scope = _Scope(qualname)
        body_ctx = _Ctx(
            scopes=ctx.scopes + [inner_scope],
            class_qualname=None,
            self_class=ctx.self_class,
            function=qualname,
        )
        body = node.body if isinstance(node.body, list) else [node.body]
        for stmt in body:
            self._visit(stmt, body_ctx)
        self._refs[qualname] = set()
        self._pending_refs.append((qualname, body_ctx))

    def _note_alias(self, node: ast.Assign, ctx: _Ctx):
        """Track two alias forms: `sm = _shard_map()` (trace-entry alias)
        and `fn = partial(step_fn, ...)` / `fn = step_fn` (function
        alias, so `sm(fn, ...)` resolves to the real step)."""
        if len(node.targets) != 1 or not isinstance(node.targets[0], ast.Name):
            return
        target_name = node.targets[0].id
        value = node.value
        segment = None
        if isinstance(value, ast.Call):
            segment = _last_segment(value.func)
        elif isinstance(value, (ast.Name, ast.Attribute)):
            segment = _last_segment(value)
        if _entry_name_of(segment):
            ctx.scopes[-1].entry_aliases.add(target_name)
            return
        aliased = None
        if (
            isinstance(value, ast.Call)
            and _last_segment(value.func) == "partial"
            and value.args
        ):
            aliased = self._resolve_ref(value.args[0], ctx)
        elif isinstance(value, (ast.Name, ast.Attribute)):
            aliased = self._resolve_ref(value, ctx)
        if aliased:
            ctx.scopes[-1].functions[target_name] = aliased

    def _entry_of(self, call: ast.Call, ctx: _Ctx) -> Optional[str]:
        entry = _entry_name_of(_last_segment(call.func))
        if entry == "compile" and not _is_compile_plan_call(call):
            entry = None
        if entry:
            return entry
        if isinstance(call.func, ast.Name):
            for scope in reversed(ctx.scopes):
                if call.func.id in scope.entry_aliases:
                    return "shard_map"  # aliases here are shard_map-shaped
        return None

    def _record_jit_site(self, call: ast.Call, entry: str, ctx: _Ctx,
                         bound_name: Optional[str]):
        site = JitSite(
            node=call,
            entry=entry,
            target=None,
            keywords={kw.arg: kw.value for kw in call.keywords if kw.arg},
            bound_name=bound_name,
            enclosing_function=ctx.function,
            in_loop=ctx.loop_depth > 0,
            in_mesh_context=ctx.mesh_depth > 0,
        )
        self.jit_sites.append(site)
        if call.args:
            self._pending_jit_targets.append((site, call.args[0], ctx))

    # -- resolution ----------------------------------------------------

    def _resolve_ref(self, node: ast.AST, ctx: _Ctx) -> Optional[str]:
        """Resolve a Name / self.X / lambda reference to a known function."""
        if isinstance(node, ast.Lambda):
            info = self.by_node.get(id(node))
            return info.qualname if info else None
        if isinstance(node, ast.Name):
            for scope in reversed(ctx.scopes):
                if node.id in scope.functions:
                    return scope.functions[node.id]
            return None
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id in ("self", "cls")
            and ctx.self_class is not None
        ):
            return self._class_methods.get(ctx.self_class, {}).get(node.attr)
        return None

    def _resolve_loose(self, node: ast.AST) -> Optional[str]:
        """Best-effort resolution without lexical context (module scope +
        any class) — used only by the taint pass."""
        if isinstance(node, ast.Name):
            return self._module_scope.functions.get(node.id)
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id in ("self", "cls")
        ):
            for methods in self._class_methods.values():
                if node.attr in methods:
                    return methods[node.attr]
        return None

    # -- root marking + closure ----------------------------------------

    def _mark_decorator_roots(self):
        for info in self.functions.values():
            for deco in info.decorators:
                jit_entry = None
                for sub in ast.walk(deco):
                    segment = _last_segment(sub)
                    if segment in TRACED_DECORATOR_NAMES:
                        self.traced.setdefault(
                            info.qualname,
                            f"decorated @{segment} (line {info.lineno})",
                        )
                    if segment in JIT_ENTRY_NAMES:
                        jit_entry = segment
                if jit_entry:
                    # Decorator-form jit site: @jax.jit or
                    # @functools.partial(jax.jit, donate_argnums=...).
                    keywords = {}
                    if isinstance(deco, ast.Call):
                        keywords = {
                            kw.arg: kw.value
                            for kw in deco.keywords
                            if kw.arg
                        }
                    self.jit_sites.append(
                        JitSite(
                            node=deco,
                            entry=jit_entry,
                            target=info.qualname,
                            keywords=keywords,
                            bound_name=info.name,
                            enclosing_function=info.parent_function,
                            in_loop=False,
                            in_mesh_context=False,
                            is_decorator=True,
                        )
                    )

    def _mark_entry_call_roots(self):
        for call, ctx in self._pending_entry_calls:
            entry = self._entry_of(call, ctx) or "trace-entry"
            arg_exprs: List[ast.AST] = list(call.args) + [
                kw.value
                for kw in call.keywords
                if kw.arg not in _SPEC_KWARGS
            ]
            for expr in arg_exprs:
                for sub in _walk_skipping_spec_constructors(expr):
                    if isinstance(sub, (ast.Name, ast.Attribute, ast.Lambda)):
                        resolved = self._resolve_ref(sub, ctx)
                        if resolved:
                            self.traced.setdefault(
                                resolved,
                                f"passed to {entry}() at line {call.lineno}",
                            )

    def _close_transitively(self):
        # Resolve each function's outgoing references now that every
        # function (including later-defined siblings) is indexed.
        for qualname, body_ctx in self._pending_refs:
            refs = self._refs[qualname]
            info = self.functions[qualname]
            for sub in self.own_body(info):
                if isinstance(sub, (ast.Name, ast.Attribute, ast.Lambda)):
                    resolved = self._resolve_ref(sub, body_ctx)
                    if resolved and resolved != qualname:
                        refs.add(resolved)
        worklist = list(self.traced)
        while worklist:
            current = worklist.pop()
            for ref in self._refs.get(current, ()):
                if ref not in self.traced:
                    self.traced[ref] = (
                        f"called from traced {current or '<module>'}"
                    )
                    worklist.append(ref)


def _bound_name(targets: Iterable[ast.AST]) -> Optional[str]:
    targets = list(targets)
    if len(targets) != 1:
        return None
    target = targets[0]
    if isinstance(target, ast.Name):
        return target.id
    if isinstance(target, ast.Attribute):
        return target.attr
    return None


def _dotted_root(node: ast.AST) -> Optional[str]:
    while isinstance(node, ast.Attribute):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return None


def _mentions_mesh(expr: ast.AST) -> bool:
    for node in ast.walk(expr):
        name = None
        if isinstance(node, ast.Name):
            name = node.id
        elif isinstance(node, ast.Attribute):
            name = node.attr
        if name and "mesh" in name.lower():
            return True
    return False


def traced_index(source: SourceFile) -> TracedIndex:
    """The (memoized) TracedIndex for a SourceFile — every jax rule
    shares one index per file."""
    index = getattr(source, "_traced_index", None)
    if index is None or index.source is not source:
        index = TracedIndex(source)
        source._traced_index = index
    return index
