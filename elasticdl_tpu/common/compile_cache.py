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
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: <repo>/.jax_cache — the parent of the `elasticdl_tpu` package.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def configure(flag_dir: str = "") -> str:
    """Point this process at the persistent compile cache and return
    the directory in use.  Touches `jax.config` only — no backend is
    initialized."""
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
    return cache_dir


_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_events = {_HIT_EVENT: 0, _MISS_EVENT: 0}
_listening = False


def _count_events() -> None:
    """Count JAX's own cache-hit / cache-miss events (once a process),
    so that a `compile.build` span can say which its build was."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax

    def on_event(event: str, **_kwargs) -> None:
        if event in _events:
            _events[event] += 1

    jax.monitoring.register_event_listener(on_event)


def hits_and_misses() -> tuple:
    """(persistent-cache hits, misses) this process has seen since
    `configure()`; (0, 0) in a process that never configured."""
    return _events[_HIT_EVENT], _events[_MISS_EVENT]
