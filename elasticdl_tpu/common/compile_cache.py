"""Where JAX's persistent compilation cache lives.

Every process that compiles (worker main, Local mode, the serving
replica, bench.py, __graft_entry__, chip_smoke's children) calls
`configure()` before its first compile, so a machine that starts with
no compiled code pays for each program once and not once per process.

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads the variable itself and
  this code sets NO directory — whoever placed the cache from outside
  owns its location.  Child processes inherit the variable.
- unset: `--jax_compilation_cache_dir` if the job gave one, else one
  fixed path inside the checkout (`<repo>/.jax_cache`, gitignored).
  Never a temp dir, a pid or a timestamp: the directory is part of the
  cache key, so a path that moves never hits.

A process that also hands `configure()` its parsed arguments gets the
executable store (`common/executable_store.py`) in that directory's
`executables/`: `parallel/compile.py` loads a build from it without
tracing, by a key those arguments are part of.
"""

from __future__ import annotations

import contextlib
import os
import time

from elasticdl_tpu.obs.tracing import covered_seconds

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: <repo>/.jax_cache — the parent of the `elasticdl_tpu` package.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


#: The process's executable store: `configure(args=...)` opens it,
#: `parallel/compile.py` reads it at an entrypoint's first call.
_store = None


def configure(flag_dir: str = "", args=None) -> str:
    """Point this process at the persistent compile cache and return
    the directory in use.  Touches `jax.config` only — no backend is
    initialized.  `args` (the process's parsed arguments) opens the
    executable store beside the cache, unless the process was told to
    keep no compiled programs (`jax_enable_compilation_cache` off); a
    later call without them leaves it as it is."""
    global _store
    import jax

    cache_dir = os.environ.get(ENV_VAR, "")
    if not cache_dir:
        cache_dir = flag_dir or REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Cache every program: a re-formed world's (or a fresh machine's
    # second process's) small jits are disk hits too.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _count_events()
    if args is not None:
        _store = None
        if jax.config.jax_enable_compilation_cache:
            from elasticdl_tpu.common.executable_store import ExecutableStore

            _store = ExecutableStore(
                os.path.join(cache_dir, "executables"), args
            )
    return cache_dir


def executable_store():
    """The store `configure()` opened for this process, or None."""
    return _store


_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
#: JAX's duration events of one compile -> the field of `compile.build`
#: each is summed into, innermost first: a program compiled while
#: another is being traced (an op run eagerly on a constant) counts as
#: `backend_s`, not twice.  `backend_s` is `compile_or_get_cached` as a
#: whole, XLA's compile OR the persistent cache's load; `cache_read_s`
#: is the part of it that was retrieval (fetch, deserialise, load).
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_PARTS = (
    (_BACKEND_EVENT, "backend_s"),
    ("/jax/core/compile/jaxpr_to_mlir_module_duration", "lower_s"),
    ("/jax/core/compile/jaxpr_trace_duration", "trace_s"),
)
_listening = False
_open_builds: list = []


class BuildParts:
    """What JAX reported while one build was open (`measuring()`)."""

    def __init__(self):
        self._opened = time.monotonic()
        self._intervals = {event: [] for event, _ in _PARTS}
        self._intervals[_READ_EVENT] = []
        self.hits = self.misses = 0

    def note(self, event: str, duration_s: float) -> None:
        """A duration event: it ended now, so it began `duration_s` ago
        (clipped to the build's own start).  Other events pass."""
        if event in self._intervals:
            now = time.monotonic()
            self._intervals[event].append(
                (max(self._opened, now - duration_s), now)
            )

    def fields(self) -> dict:
        """The `compile.build` span's fields.  A jit traced inside
        another reports its own trace inside its caller's, so each part
        is the UNION of its reported intervals, less what a part before
        it in `_PARTS` holds: the three sum to no more than the span."""
        fields, taken, before = {}, [], 0.0
        for event, name in _PARTS:
            taken = taken + self._intervals[event]
            covered = covered_seconds(taken)
            fields[name] = round(covered - before, 6)
            before = covered
        fields["cache_read_s"] = round(
            covered_seconds(self._intervals[_READ_EVENT]), 6
        )
        fields["programs"] = len(self._intervals[_BACKEND_EVENT])
        # Every program the build asked for came from the cache.
        fields["cache_hit"] = self.hits > 0 and self.misses == 0
        return fields


@contextlib.contextmanager
def measuring():
    """Collect JAX's compile events while the block runs (a
    `compile.build` span's first call).  The events are the PROCESS's: a
    compile on another thread during the block lands in it (no thread
    compiles during a worker's boot today).  Empty in a process that
    never called `configure()`."""
    parts = BuildParts()
    _open_builds.append(parts)
    try:
        yield parts
    finally:
        _open_builds.remove(parts)


def _count_events() -> None:
    """Listen to JAX's own cache-hit / cache-miss events and compile
    durations (once a process), so that a `compile.build` span can say
    which its build was and what it was made of.  With no build open a
    listener call is one look at an empty list."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax

    def on_event(event: str, **_kwargs) -> None:
        for parts in list(_open_builds):
            if event == _HIT_EVENT:
                parts.hits += 1
            elif event == _MISS_EVENT:
                parts.misses += 1

    def on_duration(event: str, duration_s: float, **_kwargs) -> None:
        for parts in list(_open_builds):
            parts.note(event, duration_s)

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
