"""The executable store (common/executable_store.py, ISSUE 35): a worker
that starts again loads its compiled programs by a key that needs no
trace, through the first call of every `CompilePlan.compile` entrypoint
(`parallel/compile._BuildSpan`).

What a stale hit would cost is old code run in silence, so most of this
file is about the key: what changes it, what must not, and that the list
of arguments left out of it is held against the parsers.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticdl_tpu import obs
from elasticdl_tpu.common import args as args_lib
from elasticdl_tpu.common import compile_cache, executable_store
from elasticdl_tpu.obs.stepstats import RetraceWatcher
from elasticdl_tpu.parallel import MeshConfig, build_mesh
from elasticdl_tpu.parallel import compile as pc

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ZOO = os.path.join(REPO_ROOT, "model_zoo")


def _namespace(**changes):
    """The worker parser's defaults, as a job's worker would hold them."""
    args = args_lib.build_worker_parser().parse_args([
        f"--model_zoo={ZOO}", "--model_def=mnist.mnist_functional_api",
        "--worker_id=0", "--master_addr=localhost:1",
    ])
    for name, value in changes.items():
        setattr(args, name, value)
    return args


@pytest.fixture
def store(tmp_path, monkeypatch):
    """This process's store, as `compile_cache.configure(args=...)` opens
    it where the compilation cache is on (the suite switches it off)."""
    opened = executable_store.ExecutableStore(
        str(tmp_path / "executables"), _namespace()
    )
    monkeypatch.setattr(compile_cache, "_store", opened)
    compile_cache._count_events()  # configure()'s listeners, once a process
    return opened


def _plan(devices=1):
    return pc.CompilePlan(
        build_mesh(MeshConfig(), devices=jax.devices()[:devices]),
        trainer="test",
    )


def _builds_since(marker):
    return [
        e for e in obs.journal().tail(400)
        if e.get("name") == "compile.build" and e["ts"] >= marker
    ]


def _entry(plan, name="affine", fn=None):
    return plan.compile(
        fn or (lambda w, x: (w + x.sum(), w * 2.0)), name=name,
        journal=False, donate_argnums=(0,),
    )


# ---------------------------------------------------------------------------
# The key
# ---------------------------------------------------------------------------


def _key(store=None, *, args=None, directory="/nowhere", mesh=None,
         leaf=None, entrypoint="step", donated=(0,), oov=False):
    store = store or executable_store.ExecutableStore(
        directory, args or _namespace()
    )
    mesh = mesh or build_mesh(MeshConfig(), devices=jax.devices()[:1])
    leaves, treedef = jax.tree_util.tree_flatten(
        (({"w": leaf if leaf is not None else np.zeros((2, 3), np.float32)},),
         {})
    )
    return store.key(
        entrypoint=entrypoint, donate_argnums=donated,
        trace_state={"oov_debug": oov}, mesh=mesh, treedef=treedef,
        signature=[executable_store.leaf_signature(x) for x in leaves],
    )


def _sharded(spec, devices=2):
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:devices])
    return jax.device_put(
        np.zeros((2, 3), np.float32), NamedSharding(mesh, spec)
    )


def test_key_is_stable_and_needs_no_trace():
    assert _key() == _key()
    assert len(_key()) == 64


KEY_CHANGES = {
    "argument": lambda: _key(args=_namespace(minibatch_size=65)),
    "model_def": lambda: _key(
        args=_namespace(model_def="mnist.mnist_subclass")),
    "leaf_shape": lambda: _key(leaf=np.zeros((2, 4), np.float32)),
    "leaf_dtype": lambda: _key(leaf=np.zeros((2, 3), np.int32)),
    "leaf_weak_type": lambda: _key(leaf=1.0),
    "leaf_sharding": lambda: (
        _key(leaf=_sharded(P())), _key(leaf=_sharded(P("data")))),
    "mesh_devices": lambda: _key(
        mesh=build_mesh(MeshConfig(), devices=jax.devices()[:2])),
    "mesh_device_ids": lambda: _key(
        mesh=build_mesh(MeshConfig(), devices=jax.devices()[1:2])),
    "mesh_axes": lambda: _key(mesh=build_mesh(
        MeshConfig(model=2), devices=jax.devices()[:2])),
    "entrypoint": lambda: _key(entrypoint="other"),
    "donation": lambda: _key(donated=()),
    "oov_debug": lambda: _key(oov=True),
}


@pytest.mark.parametrize("what", sorted(KEY_CHANGES))
def test_key_changes_with(what):
    changed = KEY_CHANGES[what]()
    if isinstance(changed, tuple):
        one, other = changed
        assert one != other
    else:
        assert changed != _key()


PROCESS_CHANGES = {
    "jax_version": lambda m: m.setattr(jax, "__version__", "0.0.1"),
    "xla_flags_device_count": lambda m: m.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4"),
    "libtpu_init_args": lambda m: m.setenv("LIBTPU_INIT_ARGS", "--x=1"),
    "x64": lambda m: jax.config.update("jax_enable_x64", True),
    "matmul_precision": lambda m: jax.config.update(
        "jax_default_matmul_precision", "highest"),
}


@pytest.mark.parametrize("what", sorted(PROCESS_CHANGES))
def test_key_changes_with_the_process(what, monkeypatch):
    before = _key()
    try:
        PROCESS_CHANGES[what](monkeypatch)
        assert _key() != before
    finally:
        jax.config.update("jax_enable_x64", False)
        jax.config.update("jax_default_matmul_precision", None)


def _copy_of_zoo(parent):
    """A small zoo package under `parent`, byte for byte the same each
    time."""
    root = os.path.join(str(parent), "zoo")
    os.makedirs(os.path.join(root, "model"))
    for name, text in (("__init__.py", ""), ("model/net.py", "WIDTH = 8\n")):
        with open(os.path.join(root, name), "w") as f:
            f.write(text)
    return root


def test_key_changes_with_one_byte_of_a_source_file(tmp_path):
    root = _copy_of_zoo(tmp_path)
    before = _key(args=_namespace(model_zoo=root))
    with open(os.path.join(root, "model", "net.py"), "w") as f:
        f.write("WIDTH = 9\n")
    assert _key(args=_namespace(model_zoo=root)) != before
    # The package's own bytes are in it too.
    package = os.path.join(REPO_ROOT, "elasticdl_tpu")
    assert executable_store.source_digest([package, root]) != (
        executable_store.source_digest([root]))


def test_key_does_not_change_with_the_checkouts_path(tmp_path):
    here = _copy_of_zoo(tmp_path / "a")
    there = _copy_of_zoo(tmp_path / "b" / "deeper")
    assert executable_store.source_digest([here]) == (
        executable_store.source_digest([there]))
    assert _key(args=_namespace(model_zoo=here), directory="/x") == _key(
        args=_namespace(model_zoo=there), directory="/y")
    # A file that moves inside the package is another program.
    shutil.move(os.path.join(there, "model", "net.py"),
                os.path.join(there, "net.py"))
    assert executable_store.source_digest([here]) != (
        executable_store.source_digest([there]))


def _parser_dests():
    dests = set()
    for parser in (args_lib.build_master_parser(),
                   args_lib.build_worker_parser()):
        dests |= {a.dest for a in parser._actions if a.dest != "help"}
    return dests


def _another(value):
    if isinstance(value, bool):
        return not value
    if isinstance(value, (int, float)):
        return value + 1
    return f"{value}x"


def test_exclusion_list_is_held_against_the_parsers():
    """Every parsed argument is in the key unless `EXCLUDED_ARGS` argues
    it out: a flag added to `common/args.py` later changes the key by
    default, and a name on the list that no parser defines is stale."""
    dests = _parser_dests()
    assert set(executable_store.EXCLUDED_ARGS) <= dests
    assert all(executable_store.EXCLUDED_ARGS.values())  # each says why
    # The list is short and written out: places, names, ports, and when
    # the profiler watches.
    assert sorted(executable_store.EXCLUDED_ARGS) == [
        "checkpoint_dir", "jax_compilation_cache_dir", "job_name",
        "master_addr", "master_port", "metrics_port", "model_zoo",
        "output", "prediction_data", "profile_steps",
        "tensorboard_log_dir", "training_data", "validation_data",
        "worker_id",
    ]
    master = args_lib.build_master_parser().parse_args([
        f"--model_zoo={ZOO}", "--model_def=mnist.mnist_functional_api",
    ])
    master.worker_id, before = 0, None
    before = _key(args=master)
    for name in sorted(dests):
        changed = argparse.Namespace(**vars(master))
        if name == "model_zoo":
            # Another PLACE with the same bytes (its bytes are the key's).
            setattr(changed, name, os.path.join(ZOO, os.pardir, "model_zoo"))
        else:
            setattr(changed, name, _another(getattr(master, name, "")))
        same = _key(args=changed) == before
        assert same == (name in executable_store.EXCLUDED_ARGS), name


def test_a_process_without_parsed_arguments_has_no_key():
    with pytest.raises(executable_store.Skip, match="no parsed arguments"):
        _key(store=executable_store.ExecutableStore("/nowhere", None))


def test_configure_opens_the_store_beside_the_cache(tmp_path, monkeypatch):
    """Whoever places the cache places the store; a process told to keep
    no compiled programs (this suite) has none."""
    monkeypatch.setattr(compile_cache, "_store", None)
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        assert not jax.config.jax_enable_compilation_cache
        compile_cache.configure(str(tmp_path), args=_namespace())
        assert compile_cache.executable_store() is None
        jax.config.update("jax_enable_compilation_cache", True)
        compile_cache.configure(str(tmp_path))
        assert compile_cache.executable_store() is None  # no arguments
        compile_cache.configure(str(tmp_path), args=_namespace())
        opened = compile_cache.executable_store()
        assert opened.directory == str(tmp_path / "executables")
        compile_cache.configure(str(tmp_path))  # a later call keeps it
        assert compile_cache.executable_store() is opened
    finally:
        jax.config.update("jax_enable_compilation_cache", False)
        jax.config.update("jax_compilation_cache_dir", None)


# ---------------------------------------------------------------------------
# The first call, in this process
# ---------------------------------------------------------------------------


def test_without_a_store_the_first_call_is_the_jitted_functions(monkeypatch):
    monkeypatch.setattr(compile_cache, "_store", None)
    entry, marker = _entry(_plan()), time.time()
    out, _ = entry(jnp.float32(1), jnp.ones((3,)))
    (build,) = _builds_since(marker)
    assert float(out) == 4.0 and entry._cache_size() == 1
    assert build["aot_hit"] is False and build["aot_load_s"] == 0.0
    assert build["aot_skip"] == "the process has no executable store"
    assert "aot_key" not in build


def test_miss_writes_and_a_second_build_loads(store):
    plan, marker = _plan(), time.time()
    w, x = jnp.float32(1), jnp.ones((3,))
    first = _entry(plan)
    out = first(w, x)
    assert w.is_deleted()
    (path,) = [os.path.join(store.directory, n)
               for n in os.listdir(store.directory)]
    second = _entry(plan)
    w2 = jnp.float32(1)
    again = second(w2, x)
    assert w2.is_deleted()  # the donation is the stored program's own
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(again[0]))
    miss, hit = _builds_since(marker)
    assert miss["aot_hit"] is False and miss["trace_s"] > 0
    assert "aot_skip" not in miss
    assert hit["aot_hit"] is True and hit["aot_load_s"] > 0
    assert hit["aot_key"] == miss["aot_key"] == os.path.basename(path)[:12]
    # What JAX reports on a hit is nothing: numbers, not gaps.
    assert (hit["trace_s"], hit["lower_s"], hit["backend_s"],
            hit["cache_read_s"], hit["programs"], hit["cache_hit"]) == (
        0.0, 0.0, 0.0, 0.0, 0, False)
    assert not [n for n in os.listdir(store.directory) if ".tmp" in n]


def test_other_shapes_fall_through_to_the_jitted_function(store):
    plan = _plan()
    _entry(plan)(jnp.float32(1), jnp.ones((3,)))
    entry = _entry(plan)
    assert entry._cache_size() == 0
    entry(jnp.float32(1), jnp.ones((3,)))          # the stored build
    entry(jnp.float32(2), jnp.ones((3,)))          # the same executable
    assert entry._cache_size() == 1
    # A short last task.  The executable refuses it BEFORE it runs: the
    # donated argument is still there for the jitted function to take.
    w = jnp.float32(1)
    out, _ = entry(w, jnp.ones((5,)))
    assert float(out) == 6.0 and w.is_deleted()
    assert entry._cache_size() == 2
    entry(jnp.float32(1), jnp.ones((3,)))
    entry(jnp.float32(1), jnp.ones((5,)))
    assert entry._cache_size() == 2
    assert entry.lower(jnp.float32(1), jnp.ones((3,))).compile() is not None


@pytest.mark.parametrize("other", ["dtype", "tree", "device"])
def test_other_arguments_fall_through_to_the_jitted_function(store, other):
    plan = _plan()
    _entry(plan)(jnp.float32(1), jnp.ones((3,)))
    entry = _entry(plan)
    entry(jnp.float32(1), jnp.ones((3,)))
    w, x = {
        "dtype": lambda: (jnp.float32(1), jnp.ones((3,), jnp.int32)),
        "tree": lambda: (jnp.float32(1), {"x": jnp.ones((3,))}),
        # Committed to a device the stored program was not compiled for.
        "device": lambda: (
            jax.device_put(jnp.float32(1), jax.devices()[1]),
            jax.device_put(jnp.ones((3,)), jax.devices()[1])),
    }[other]()
    if other == "tree":
        with pytest.raises(AttributeError):  # the function's own error
            entry(w, x)
        return
    out, _ = entry(w, x)
    assert float(out) == 4.0 and w.is_deleted()
    assert entry._cache_size() == 2
    if other == "device":
        assert out.devices() == {jax.devices()[1]}


def test_retrace_watcher_reports_a_stored_build_as_one_compile(store):
    plan = _plan()
    _entry(plan)(jnp.float32(1), jnp.ones((3,)))
    entry, watcher = _entry(plan), RetraceWatcher()
    watcher.watch(lambda: {"affine": entry})
    assert watcher.poll() == {}
    entry(jnp.float32(1), jnp.ones((3,)))
    assert watcher.poll() == {"affine": 1}
    entry(jnp.float32(1), jnp.ones((3,)))
    assert watcher.poll() == {}
    entry(jnp.float32(1), jnp.ones((5,)))
    assert watcher.poll() == {"affine": 1}
    assert watcher.retraces_total() == 1


@pytest.mark.parametrize("damage", ["truncated", "garbage", "foreign"])
def test_a_damaged_store_file_falls_through_and_is_replaced(store, damage):
    plan = _plan()
    _entry(plan)(jnp.float32(1), jnp.ones((3,)))
    (name,) = os.listdir(store.directory)
    path = os.path.join(store.directory, name)
    with open(path, "rb") as f:
        whole = f.read()
    with open(path, "wb") as f:
        f.write({
            "truncated": whole[: len(whole) // 2],
            # The right magic and lengths around bytes that do not unpickle.
            "garbage": whole[:24] + bytes(len(whole) - 24),
            "foreign": b"not one of this store's files",
        }[damage])
    marker = time.time()
    out, _ = _entry(plan)(jnp.float32(1), jnp.ones((3,)))
    (build,) = _builds_since(marker)
    assert float(out) == 4.0
    assert build["aot_hit"] is False and build["trace_s"] > 0
    assert build["aot_skip"].startswith({
        "truncated": "the stored build is short",
        "garbage": "the stored build does not load",
        "foreign": "the stored build is not one of this store's files",
    }[damage])
    with open(path, "rb") as f:  # replaced by a whole file
        assert f.read(8) == whole[:8]
    marker = time.time()
    _entry(plan)(jnp.float32(1), jnp.ones((3,)))
    assert _builds_since(marker)[0]["aot_hit"] is True


def test_a_program_with_a_host_callback_falls_through(store, capfd):
    def noisy(w, x):
        jax.debug.print("sum {}", x.sum())
        return w + x.sum(), w * 2.0

    marker = time.time()
    entry = _entry(_plan(), name="noisy", fn=noisy)
    out, _ = entry(jnp.float32(1), jnp.ones((3,)))
    jax.effects_barrier()
    assert float(out) == 4.0 and "sum 3.0" in capfd.readouterr().out
    (build,) = _builds_since(marker)
    assert build["aot_hit"] is False
    assert build["aot_skip"].startswith("the program does not serialise")
    assert not os.path.exists(store.directory) or not os.listdir(
        store.directory)
    out, _ = entry(jnp.float32(2), jnp.ones((3,)))
    assert float(out) == 5.0 and entry._cache_size() == 1


def test_a_world_of_more_than_one_process_falls_through(store, monkeypatch):
    monkeypatch.setattr(jax, "process_count", lambda *a: 2)
    marker = time.time()
    _entry(_plan())(jnp.float32(1), jnp.ones((3,)))
    (build,) = _builds_since(marker)
    assert build["aot_skip"] == "a world of more than one process"
    assert not os.path.exists(store.directory)


def test_static_arguments_fall_through(store):
    entry = _plan().compile(
        lambda n, x: x * n, name="scaled", journal=False, static_argnums=0)
    marker = time.time()
    assert float(entry(3, jnp.float32(2))) == 6.0
    assert _builds_since(marker)[0]["aot_skip"] == "static arguments"


def test_a_mesh_of_some_devices_loads_onto_those_devices(store):
    """A stored program for devices 2-3 of 8 loads onto devices 2-3."""
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[2:4])
    plan = pc.CompilePlan(mesh, trainer="test")
    sharded = NamedSharding(mesh, P("data"))

    def build():
        return plan.compile(
            lambda x: (x * 2.0).sum(axis=1), name="rows", journal=False,
            in_shardings=(sharded,), out_shardings=sharded,
        )

    x = jax.device_put(np.arange(8, dtype=np.float32).reshape(4, 2), sharded)
    want = np.asarray(build()(x))
    marker = time.time()
    got = build()(x)
    assert _builds_since(marker)[0]["aot_hit"] is True
    assert {d.id for d in got.sharding.device_set} == {2, 3}
    np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# A fresh process
# ---------------------------------------------------------------------------

_PROCESS_PROBE = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
from elasticdl_tpu.common import args as args_lib, compile_cache
args = args_lib.build_worker_parser().parse_args(sys.argv[2:])
compile_cache.configure(args=args)
import jax, jax.numpy as jnp, numpy as np
from elasticdl_tpu import obs
from elasticdl_tpu.parallel import MeshConfig, build_mesh, compile as pc

traced = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, duration, **kw: traced.append(event))
plan = pc.CompilePlan(
    build_mesh(MeshConfig(), devices=jax.devices()[:1]), trainer="test")

def step(state, x):
    carry, ys = jax.lax.scan(
        lambda c, row: (c * 0.5 + row.sum(), c), state["step"], x)
    return {"step": carry, "w": state["w"] + carry}, ys

entry = plan.compile(step, name="probe_step", journal=False,
                     donate_argnums=(0,))
state = {"step": jnp.float32(1), "w": jnp.ones((4, 4))}
x = np.arange(12, dtype=np.float32).reshape(3, 4)
before = len(traced)
new, ys = entry(state, x)
inside = traced[before:]
(build,) = [
    e for e in obs.journal().tail(100) if e.get("name") == "compile.build"]
print(json.dumps({
    "build": build,
    "traced": [e for e in inside if e.endswith("jaxpr_trace_duration")],
    "donated_deleted": bool(state["w"].is_deleted()),
    "w": np.asarray(new["w"]).tolist(), "ys": np.asarray(ys).tolist(),
    "cache_size": entry._cache_size(),
}))
"""

_ZOO_PROBE = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
from elasticdl_tpu.common import args as args_lib, compile_cache
args = args_lib.build_worker_parser().parse_args(sys.argv[2:])
compile_cache.configure(args=args)
import jax, numpy as np
from elasticdl_tpu import obs
from elasticdl_tpu.parallel import MeshConfig, build_mesh

mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
rng = np.random.RandomState(0)
if args.model_def == "transformer.transformer_lm":
    from model_zoo.transformer import transformer_lm as zoo
    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer

    trainer = DataParallelTrainer(
        model=zoo.custom_model(vocab=64, d_model=32, num_heads=2,
                               num_layers=1, max_len=16),
        loss_fn=zoo.loss, optimizer=zoo.optimizer(), mesh=mesh,
    )
    tokens = rng.randint(0, 64, size=(8, 16)).astype(np.int32)
    features, labels, window_name = tokens, tokens, "dp_train_window"
else:
    from model_zoo.deepfm import deepfm_functional_api as zoo
    from elasticdl_tpu.parallel.ps_trainer import ShardedEmbeddingTrainer

    trainer = ShardedEmbeddingTrainer(
        model=zoo.custom_model(vocab_size=32, embedding_dim=4, hidden=16),
        loss_fn=zoo.loss, optimizer=zoo.optimizer(lr=0.01), mesh=mesh,
        embedding_optimizer=zoo.embedding_optimizer(lr=0.01),
        sparse_apply_every=2,
    )
    features = {
        "dense": rng.rand(8, 13).astype(np.float32),
        "cat": rng.randint(0, 32, size=(8, 26)).astype(np.int32),
    }
    labels = rng.randint(0, 2, size=(8,)).astype(np.int32)
    trainer.ensure_initialized(features)
    window_name = "ps_train_window"
batch = (features, labels, np.ones((8,), np.float32))
losses = []
for _ in range(3):
    window = trainer.stage_window([batch, batch])
    losses += np.asarray(trainer.train_window(window)).tolist()
print(json.dumps({
    "losses": losses,
    "builds": [
        {k: e.get(k) for k in ("entrypoint", "aot_hit", "aot_skip", "trace_s")}
        for e in obs.journal().tail(400) if e.get("name") == "compile.build"],
    "cache_size": trainer.jitted_entrypoints()[window_name]._cache_size(),
}))
"""


def _run_probe(probe, cache_dir, *argv, root=REPO_ROOT, cache="true",
               model_def="transformer.transformer_lm"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE=cache,
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    proc = subprocess.run(
        [sys.executable, "-c", probe, root,
         f"--model_zoo={os.path.join(root, 'model_zoo')}",
         f"--model_def={model_def}", "--master_addr=x:1",
         *(argv or ("--worker_id=0",))],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def test_a_fresh_process_loads_the_build_and_traces_nothing(tmp_path):
    cache = tmp_path / "cache"
    cold, _ = _run_probe(_PROCESS_PROBE, cache)
    warm, _ = _run_probe(
        _PROCESS_PROBE, cache, "--worker_id=7", "--master_addr=y:2",
        "--training_data=synthetic://lm?seed=9", "--profile_steps=3,7",
        f"--tensorboard_log_dir={tmp_path / 'tb'}",
    )
    assert cold["build"]["aot_hit"] is False and cold["traced"]
    assert warm["build"]["aot_hit"] is True
    assert warm["traced"] == []  # no jaxpr_trace_duration inside the span
    assert warm["build"]["aot_key"] == cold["build"]["aot_key"]
    assert warm["build"]["trace_s"] == warm["build"]["lower_s"] == 0.0
    assert (warm["w"], warm["ys"]) == (cold["w"], cold["ys"])  # bit-equal
    assert warm["donated_deleted"] and cold["donated_deleted"]
    assert warm["cache_size"] == cold["cache_size"] == 1
    # One non-excluded argument more: another key, a miss.
    other, _ = _run_probe(_PROCESS_PROBE, cache, "--worker_id=0",
                          "--minibatch_size=65")
    assert other["build"]["aot_hit"] is False
    assert other["build"]["aot_key"] != cold["build"]["aot_key"]


@pytest.mark.parametrize("model_def,names,engine_line", [
    ("transformer.transformer_lm", ["dp_init", "dp_train_window"],
     "attention engine: xla blockwise_attention T=16 D=16"),
    ("deepfm.deepfm_functional_api", ["ps_init", "ps_train_window"], None),
])
def test_a_zoo_models_window_gives_the_same_loss_from_a_stored_build(
    tmp_path, model_def, names, engine_line
):
    """The init and the window program of the smallest language model the
    tests build on the dp trainer, and of the smallest CTR model on the PS
    trainer: traced with no store, traced and stored, then loaded in a
    fresh process; three windows each, losses equal to the last bit,
    every later window on the loaded executable, and the log of a hit
    names the kernels' engines."""
    cache = tmp_path / "cache"
    plain, _ = _run_probe(
        _ZOO_PROBE, cache, cache="false", model_def=model_def)
    cold, cold_log = _run_probe(_ZOO_PROBE, cache, model_def=model_def)
    warm, warm_log = _run_probe(_ZOO_PROBE, cache, model_def=model_def)
    assert [b["entrypoint"] for b in warm["builds"]] == names
    assert [b["aot_hit"] for b in plain["builds"]] == [False, False]
    assert {b["aot_skip"] for b in plain["builds"]} == {
        "the process has no executable store"}
    assert [b["aot_hit"] for b in cold["builds"]] == [False, False]
    assert [b["aot_hit"] for b in warm["builds"]] == [True, True]
    assert [b["trace_s"] for b in warm["builds"]] == [0.0, 0.0]
    assert plain["losses"] == cold["losses"] == warm["losses"]
    assert len(warm["losses"]) == 6 and warm["losses"][-1] < warm["losses"][0]
    assert plain["cache_size"] == cold["cache_size"] == warm["cache_size"] == 1
    if engine_line:
        assert engine_line in cold_log and "(stored build)" not in cold_log
        assert warm_log.count(f"{engine_line} (stored build)") == 2
    assert f"{names[1]}: loaded the stored build" in warm_log
    assert f"{names[0]}: loaded the stored build" in warm_log


def test_a_checkout_under_another_path_hits(tmp_path):
    """The persistent cache keys a Pallas program by its checkout's path
    (PERF.md §6 PR 28); this store keys it by the bytes."""
    elsewhere = tmp_path / "elsewhere"
    for name in ("elasticdl_tpu", "model_zoo"):
        shutil.copytree(
            os.path.join(REPO_ROOT, name), elsewhere / name,
            ignore=shutil.ignore_patterns("__pycache__", "*.so", "*.pyc"),
        )
    cache = tmp_path / "cache"
    cold, _ = _run_probe(_PROCESS_PROBE, cache)
    moved, _ = _run_probe(_PROCESS_PROBE, cache, root=str(elsewhere))
    assert moved["build"]["aot_hit"] is True
    assert moved["build"]["aot_key"] == cold["build"]["aot_key"]
    with open(elsewhere / "elasticdl_tpu" / "ops" / "__init__.py", "a") as f:
        f.write("\n")
    edited, _ = _run_probe(_PROCESS_PROBE, cache, root=str(elsewhere))
    assert edited["build"]["aot_hit"] is False


@pytest.mark.parametrize("devices", [1, 4])
def test_a_restart_reads_the_piece_programs_where_the_mesh_is_one_device(
    store, tmp_path, devices
):
    """`pc.leaf_cutter`'s programs cut a leaf on ONE device: the store,
    which loads a program for a plan's whole mesh, keeps them for a mesh
    of one and leaves them to the compile cache under a mesh of several
    (a stored build loaded for four devices refuses a one-device call)."""
    from elasticdl_tpu.checkpoint.saver import CheckpointSaver

    plan = _plan(devices)
    leaf = jax.device_put(
        jnp.arange(64 * 16, dtype=jnp.float32).reshape(64, 16),
        NamedSharding(plan.mesh, P()),
    )
    hits = []
    for start in range(2):  # a worker's first start, then its second
        marker = time.time()
        cutter = pc.leaf_cutter(plan, [leaf], piece_bytes=1024)
        builds = _builds_since(marker)
        hits.append([b["aot_hit"] for b in builds])
        saver = CheckpointSaver(str(tmp_path / f"start{start}"))
        saver.save({"w": leaf}, 1, cutter=cutter)
        restored, _step = saver.load_latest()
        assert np.array_equal(restored["w"], np.asarray(leaf))
    assert hits == ([[False], [True]] if devices == 1 else [[], []])
