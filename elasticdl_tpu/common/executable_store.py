"""Compiled programs kept under a key that needs no trace.

JAX's persistent cache finds a finished executable by a hash of the
LOWERED module, so a worker that starts again traces its model in Python
and lowers it only to compute the name of a file the disk already holds
(11-14 s of a language model's set-up, PERF.md §5).  This store keeps
the same executable (`jax.experimental.serialize_executable`, the PjRt
serialisation the persistent cache uses) under a digest of what a trace
could read:

- the bytes of every `*.py` of `elasticdl_tpu/` and of the model zoo,
  by path inside the package: any edit is a miss, a move of the
  checkout is not;
- every parsed argument of the process except `EXCLUDED_ARGS`;
- environment and module state a trace reads, and the `jax.config`
  values that change a program;
- jax, jaxlib, the backend's `platform_version`, the device kind, the
  mesh and the process count;
- the entrypoint, its donated argnums and the call's flattened
  arguments (treedef; each leaf's shape, dtype, weak type, sharding).

A stale hit would run old code in silence, so the key errs towards
missing.  The store lives in `<compile cache dir>/executables/`
(`compile_cache.configure()` opens it): whoever places the cache places
the store, and a process whose compilation cache is switched off has
none.  Nothing evicts it yet (PERF.md §7).
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import pickle
import struct
import sys
import zlib
from typing import Any, Dict, Iterable, List, Optional, Tuple

#: Parsed arguments (`common/args.py`) that are NOT part of the key, each
#: with the reason no trace can read it.  Everything else the parsers
#: define is in the key; `tests/test_executable_store.py` holds this list
#: against them, so a flag added later is in the key unless it is argued
#: out here.
EXCLUDED_ARGS: Dict[str, str] = {
    "worker_id": "names this process",
    "job_name": "names the job: pod names and log directories",
    "master_addr": "an address",
    "master_port": "a port",
    "metrics_port": "a port",
    "model_zoo": "a place: the zoo's bytes are in the key, its path is not",
    "training_data": "a place: records reach a program as arguments, "
                     "whose shapes and dtypes are in the key",
    "validation_data": "a place, as training_data",
    "prediction_data": "a place, as training_data",
    "checkpoint_dir": "a directory",
    "output": "a directory",
    "tensorboard_log_dir": "a directory",
    "jax_compilation_cache_dir": "a directory: where this store lives",
    "profile_steps": "when the profiler watches, not what runs",
}

#: Libraries whose code a trace runs through, beside jax and jaxlib: the
#: version of each that the process has loaded is in the key.
_LIBRARIES = ("flax", "optax", "numpy", "ml_dtypes")
#: Environment a trace or the compiler reads.
_ENVIRONMENT = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
#: `jax.config` values that change the program a trace gives.
_JAX_CONFIG = (
    "jax_enable_x64", "jax_default_matmul_precision",
    "jax_default_prng_impl", "jax_threefry_partitionable",
    "jax_numpy_dtype_promotion",
)

try:  # as JAX's persistent cache: zstd where there is one, else zlib
    import zstandard
except ImportError:
    zstandard = None

_MAGIC = b"EDLEXEC1"
#: A file: the magic, the two lengths, the pickled record (entrypoint,
#: versions, engine lines, the two treedefs, the codec), the serialised
#: executable, compressed (GPT-2's window program is 257 MB raw).
_HEADER = struct.Struct("<8sQQ")


#: codec -> (compress, decompress); a file names the one it was written
#: with, and one this process lacks is a file that does not load.
_CODECS = {"zlib": (lambda data: zlib.compress(data, 1), zlib.decompress)}
if zstandard:
    _CODECS["zstd"] = (
        lambda data: zstandard.ZstdCompressor().compress(data),
        lambda data: zstandard.ZstdDecompressor().decompress(data),
    )


class Skip(Exception):
    """The store cannot serve or keep this build; the message is the
    reason the `compile.build` span carries as `aot_skip`."""


def source_digest(roots: Iterable[str]) -> str:
    """sha256 over every `*.py` under each root, as (path from the
    root's parent, bytes), sorted: the checkout's own path is not in it."""
    digest = hashlib.sha256()
    for root in roots:
        root = os.path.abspath(root)
        base = os.path.dirname(root)
        found = []
        for directory, _dirs, names in os.walk(root):
            found.extend(
                os.path.join(directory, name)
                for name in names if name.endswith(".py")
            )
        if not found:
            raise Skip(f"no source files under {root}")
        for path in sorted(found):
            with open(path, "rb") as f:
                data = f.read()
            name = os.path.relpath(path, base).replace(os.sep, "/")
            digest.update(f"{name}\0{len(data)}\0".encode())
            digest.update(data)
    return digest.hexdigest()


def _zoo_root(model_zoo: str) -> str:
    """The directory `--model_zoo` names (a directory, or a package)."""
    if os.path.isdir(model_zoo):
        return model_zoo
    spec = importlib.util.find_spec(model_zoo)
    places = list(getattr(spec, "submodule_search_locations", None) or ())
    if not places:
        raise Skip(f"cannot find the sources of model zoo {model_zoo!r}")
    return places[0]


def leaf_signature(leaf) -> Tuple[Any, Any, bool, Any]:
    """(shape, dtype, weak type, sharding) of one flattened argument, as
    jit would abstract it; a value that is no `jax.Array` has no sharding."""
    import jax

    if isinstance(leaf, jax.Array):
        return (leaf.shape, leaf.dtype, leaf.weak_type, leaf.sharding)
    from jax.api_util import shaped_abstractify

    aval = shaped_abstractify(leaf)
    return (aval.shape, aval.dtype, aval.weak_type, None)


class ExecutableStore:
    """One process's view of `<compile cache dir>/executables/`."""

    def __init__(self, directory: str, args):
        self.directory = directory
        self._args = args
        self._process_part: Optional[List[str]] = None

    # -- the key ----------------------------------------------------------

    def _process(self) -> List[str]:
        """What every key of this process shares (computed once: the
        source bytes are 1.7 MB)."""
        if self._process_part is None:
            import jax
            import jaxlib

            import elasticdl_tpu

            if self._args is None:
                raise Skip("the process gave no parsed arguments to key on")
            arguments = vars(self._args)
            roots = [os.path.dirname(os.path.abspath(elasticdl_tpu.__file__))]
            if arguments.get("model_zoo"):
                roots.append(_zoo_root(arguments["model_zoo"]))
            self._process_part = [
                "sources " + source_digest(roots),
                *(
                    f"arg {name}={value!r}"
                    for name, value in sorted(arguments.items())
                    if name not in EXCLUDED_ARGS
                ),
                *(
                    f"env {name}={os.environ.get(name, '')}"
                    for name in _ENVIRONMENT
                ),
                *(
                    f"config {name}={getattr(jax.config, name)!r}"
                    for name in _JAX_CONFIG
                ),
                f"jax {jax.__version__} jaxlib {jaxlib.__version__}",
                *(
                    f"{name} {getattr(sys.modules[name], '__version__', '?')}"
                    for name in _LIBRARIES if name in sys.modules
                ),
                "python %d.%d.%d" % sys.version_info[:3],
            ]
        return self._process_part

    def key(self, *, entrypoint: str, donate_argnums, trace_state: dict,
            mesh, treedef, signature) -> str:
        """The digest a build of `entrypoint` for this call is kept under."""
        import jax

        processes = jax.process_count()
        if processes > 1:
            raise Skip("a world of more than one process")
        devices = list(mesh.devices.flat)
        tree = str(treedef)
        if " at 0x" in tree:
            raise Skip("the arguments' treedef names an object by address")
        parts = [
            *self._process(),
            *(f"state {k}={v!r}" for k, v in sorted(trace_state.items())),
            f"backend {devices[0].platform} "
            f"{devices[0].client.platform_version}",
            f"device kind {devices[0].device_kind}",
            f"mesh {tuple(mesh.shape.items())} {mesh.axis_names} "
            f"ids {[d.id for d in devices]} processes {processes}",
            f"entrypoint {entrypoint} donated {tuple(donate_argnums)}",
            f"treedef {tree}",
            *(
                f"leaf {tuple(shape)} {dtype} {weak} {sharding!r}"
                for shape, dtype, weak, sharding in signature
            ),
        ]
        return hashlib.sha256("\n".join(parts).encode()).hexdigest()

    # -- the files --------------------------------------------------------

    def path(self, key: str) -> str:
        return os.path.join(self.directory, key)

    def load(self, key: str, mesh):
        """(the loaded `jax.stages.Compiled`, its record), or None where
        the store holds no such key.  A file that is short, does not
        unpickle or does not load on this backend raises `Skip`: the
        caller builds, and `save` replaces it."""
        from jax.experimental import serialize_executable

        try:
            with open(self.path(key), "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            return None
        except OSError as exc:
            raise Skip(f"cannot read the stored build: {exc}")
        if len(blob) < _HEADER.size:
            raise Skip("the stored build is short")
        magic, record_bytes, payload_bytes = _HEADER.unpack_from(blob)
        if magic != _MAGIC:
            raise Skip("the stored build is not one of this store's files")
        if len(blob) != _HEADER.size + record_bytes + payload_bytes:
            raise Skip("the stored build is short")
        # Only bytes this program wrote are unpickled (the magic and the
        # lengths above); whatever still fails to load is a miss.
        payload_at, view = _HEADER.size + record_bytes, memoryview(blob)
        try:
            record = pickle.loads(view[_HEADER.size:payload_at])
            compiled = serialize_executable.deserialize_and_load(
                _CODECS[record["codec"]][1](view[payload_at:]),
                record.pop("in_tree"),
                record.pop("out_tree"),
                backend=mesh.devices.flat[0].client,
                execution_devices=list(mesh.devices.flat),
            )
        except Exception as exc:  # noqa: BLE001 - any load failure is a miss
            raise Skip(
                f"the stored build does not load: "
                f"{type(exc).__name__}: {exc}"[:300]
            )
        record["bytes"] = len(blob)
        return compiled, record

    def save(self, key: str, compiled, record: dict) -> int:
        """Serialise `compiled` and put it under `key` (temporary name,
        atomic rename); returns the bytes written.  `Skip` where the
        program cannot be serialised (a host callback, `const_args`) or
        the directory cannot be written."""
        import jax
        import jaxlib
        from jax.experimental import serialize_executable

        try:
            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled
            )
            codec = "zstd" if zstandard else "zlib"
            payload = _CODECS[codec][0](payload)
            described = pickle.dumps({
                **record, "codec": codec,
                "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "in_tree": in_tree, "out_tree": out_tree,
            })
        except Exception as exc:  # noqa: BLE001 - serialize raises many kinds
            raise Skip(
                f"the program does not serialise: "
                f"{type(exc).__name__}: {exc}"[:300]
            )
        temporary = f"{self.path(key)}.{os.getpid()}.tmp"
        try:
            os.makedirs(self.directory, exist_ok=True)
            with open(temporary, "wb") as f:
                f.write(_HEADER.pack(_MAGIC, len(described), len(payload)))
                f.write(described)
                f.write(payload)
            os.replace(temporary, self.path(key))
        except OSError as exc:
            try:
                os.remove(temporary)
            except OSError:
                pass
            raise Skip(f"cannot write the stored build: {exc}")
        return _HEADER.size + len(described) + len(payload)
