"""The one span vocabulary and its two sinks (ISSUE 24).

- the profiler sink is a no-op, and imports nothing, in a process
  without jax (the master), and enters a ``TraceAnnotation`` of the
  span's own name where jax is loaded;
- ``step.bookkeep`` no longer holds a checkpoint save or the profiler's
  stop;
- the save's four children lie inside ``checkpoint.save``, in order,
  with the bytes of the files they wrote; the parent carries the host's
  I/O marks;
- ``data.index_load`` / ``data.read`` / ``data.decode`` carry the
  task's counters;
- start-up spans (``proc.start``, ``compile.build``, ``state.init``);
- the device scopes change op metadata only: outputs are bit-equal with
  and without them, and the names are on the compiled HLO's ``op_name``s.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from elasticdl_tpu import obs
from elasticdl_tpu.obs import tracing
from elasticdl_tpu.obs.journal import EventJournal
from elasticdl_tpu.obs.stepstats import StepAnatomy
from lm_contract import scopes_are_metadata

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ZOO = os.path.join(REPO_ROOT, "model_zoo")


def _spans_since(marker, name=None):
    return [
        e for e in obs.journal().tail(2000)
        if e.get("event") == "span" and e["ts"] >= marker
        and (name is None or e["name"] == name)
    ]


# ---------------------------------------------------------------------------
# The second sink
# ---------------------------------------------------------------------------


def test_master_opens_spans_without_importing_jax():
    """PR 21's property, held by a test: the master process never
    imports jax, and the annotation sink does not change that."""
    code = (
        "import sys\n"
        "import elasticdl_tpu.master.main\n"
        "from elasticdl_tpu.obs import tracing, stepstats\n"
        "assert tracing.annotate('x').__class__.__name__ == 'nullcontext'\n"
        "with tracing.span('master.serve_ready'):\n"
        "    pass\n"
        "anatomy = stepstats.StepAnatomy(0)\n"
        "with anatomy.phase('bookkeep'):\n"
        "    pass\n"
        "with anatomy.dispatch(1, 1):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'the master imported jax'\n"
        "print('off-jax')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO_ROOT, capture_output=True,
        text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "off-jax" in proc.stdout


class _Recorder:
    """Stands in for jax.profiler.TraceAnnotation."""

    entered = []

    def __init__(self, name, **fields):
        self.name, self.fields = name, fields

    def __enter__(self):
        _Recorder.entered.append((self.name, self.fields))
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    import jax

    _Recorder.entered = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    return _Recorder.entered


def test_span_and_phases_enter_an_annotation_of_the_same_name(annotations):
    tracer = tracing.Tracer(journal=EventJournal(), proc="t")
    with tracer.span("checkpoint.save.write", bytes=1):
        pass
    anatomy = StepAnatomy(0)
    for phase in ("data_wait", "stage", "bookkeep"):
        with anatomy.phase(phase):
            pass
    with anatomy.dispatch(8, 64):
        pass
    assert [name for name, _ in annotations] == [
        "checkpoint.save.write", "step.data_wait", "step.stage",
        "step.bookkeep", "step.dispatch",
    ]
    assert annotations[-1][1] == {"steps": 8}


def test_every_emitted_name_is_in_the_vocabulary():
    """SPAN_NAMES is the bounded list: the names this PR's sites emit."""
    for name in (
        "step.dispatch", "data.index_load", "data.read", "data.decode",
        "checkpoint.save.gather", "checkpoint.save.write",
        "checkpoint.save.crc", "checkpoint.save.commit",
        "checkpoint.restore.load", "proc.start",
        "master.tensorboard_init", "master.serve_ready",
        "worker.backend_init", "state.init", "compile.build",
        "moe.routing", "loop.exits", "diffusion.noise", "kda.gates",
        *BOOT_CHAIN_SPANS,
    ):
        assert name in tracing.SPAN_NAMES
    assert set(tracing.DEVICE_SCOPES) >= {
        "fwd_bwd", "dense_update", "sparse_apply", "grad_accumulate",
        "sparse_adam", "attn", "mlp", "lm_head_loss", "optimizer",
        "gdn", "gdn_scan", "moe", "moe_route", "moe_experts", "moe_shared",
        "ssm", "ssm_scan", "gdn_mix", "mla_latent", "mla_core",
        "attn_full", "attn_window", "attn_gate", "attn_proj", "attn_rotary",
        "loop", "block_norm", "exit_gate", "attn_blockdiff",
        "kda", "kda_mix", "kda_gate", "kda_scan",
    }
    # every ledger a model's counters can have writes a span of the list
    from elasticdl_tpu.layers.ledger import task_ledgers

    assert [ledger.span for ledger in task_ledgers()] == [
        "moe.routing", "loop.exits", "diffusion.noise", "kda.gates",
    ]


#: The start-up chain (ISSUE 34): the two boot spans, their children,
#: and what the master does between serving and its worker's creation.
BOOT_CHAIN_SPANS = (
    "master.boot", "master.imports", "spec.load", "master.build",
    "master.build_fleet", "master.launch_worker", "worker.boot",
    "worker.imports", "worker.join_world", "worker.build_trainer",
)
#: The fields those spans and `compile.build` carry, and the metric
#: files that read the chain.
BOOT_CHAIN_FIELDS = (
    "heavy_imports", "imported", "jax_import_s", "cause", "since_exit_s",
    "trace_s", "lower_s", "backend_s", "cache_read_s", "programs",
    "aot_hit", "aot_load_s", "aot_key", "aot_skip",
)
BOOT_CHAIN_METRICS = (
    "setup_named_share", "setup_largest_gap_s", "harness_prepare_s",
    "worker_launch_s", "build_trace_s", "build_lower_s", "build_backend_s",
    "build_cache_read_s", "master_main_to_serving_s", "master_spec_load_s",
    "worker_boot_s", "worker_imports_s", "worker_spec_load_s",
    "trainer_build_s", "build_aot_load_s", "build_aot_hits",
)


def test_every_device_scope_and_start_up_span_names_its_reader():
    """`docs/observability.md` and PERF.md's span table name every device
    scope, the `moe.routing`, `loop.exits`, `diffusion.noise` and
    `kda.gates` spans and every span and field of the
    start-up chain, with the reader of each: nothing on the lists is
    without one, and `since_main_s` is gone from both."""
    with open(os.path.join(REPO_ROOT, "docs", "observability.md")) as f:
        docs = f.read()
    with open(os.path.join(REPO_ROOT, "PERF.md")) as f:
        perf = f.read()
    for name in (
        tracing.DEVICE_SCOPES
        + ("moe.routing", "loop.exits", "diffusion.noise", "kda.gates")
        + BOOT_CHAIN_SPANS
        + BOOT_CHAIN_FIELDS
    ):
        assert f"`{name}`" in docs, name
        assert f"`{name}`" in perf, name
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in BOOT_CHAIN_METRICS:
        assert f"`{name}`" in perf, name
        assert declared[name]["moves"] == "setup_s"
        assert os.path.exists(os.path.join(
            REPO_ROOT, "perfbench", "metrics", name + ".json"))
    span_table = perf[perf.index("| span / family |"):perf.index("## 4. Cells")]
    # the expert loop's trips and the rows of one (ISSUE 40)
    for field in ("blocks", "block_rows"):
        assert f"`{field}`" in tracing.SPAN_NAMES["moe.routing"]
        assert f"`{field}`" in docs[docs.index("`moe.routing` is journaled"):]
        assert f"`{field}`" in span_table[span_table.index("| `moe.routing`"):]
    # group-limited selection's two fields (ISSUE 53)
    for field in ("groups_mean", "group_limit"):
        assert f"`{field}`" in tracing.SPAN_NAMES["moe.routing"]
        assert f"`{field}`" in docs[docs.index("`moe.routing` is journaled"):]
    assert "since_main_s" not in span_table.replace(
        "`since_main_s` gone", "")
    assert "`since_main_s`" not in docs


# ---------------------------------------------------------------------------
# step.bookkeep is bookkeeping
# ---------------------------------------------------------------------------


def _collective_worker(saver, profiler=None, anatomy=None):
    import flax.linen as nn
    import jax.numpy as jnp
    import optax

    from elasticdl_tpu.parallel.elastic import WorldInfo
    from tests.conftest import one_device_trainer
    from elasticdl_tpu.worker.collective_worker import CollectiveWorker

    class Reader:
        metadata = None

        def shard_names(self):
            return ["s"]

        def read_records(self, task):
            for i in range(task.start, task.end):
                yield np.full((2,), i, np.float32), np.int32(i)

    class Model(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(1)(x)[:, 0]

    trainer = one_device_trainer(
        Model(), lambda labels, out: jnp.mean((out - labels) ** 2),
        optax.sgd(0.0),
    )

    class Spec:
        columnar_dataset_fn = None

        @staticmethod
        def dataset_fn(dataset, mode, metadata):
            return dataset

    class Client:
        def report_version(self, step):
            pass

    return CollectiveWorker(
        master_client=Client(), model_spec=Spec(), data_reader=Reader(),
        minibatch_size=4,
        world=WorldInfo(rank=0, world_size=1, rendezvous_id=1,
                        coordinator_addr=""),
        trainer=trainer, checkpoint_saver=saver, checkpoint_steps=2,
        profiler=profiler, anatomy=anatomy,
    )


def test_bookkeep_holds_neither_the_save_nor_the_profilers_stop():
    from elasticdl_tpu.proto import elasticdl_pb2 as pb

    class SleepySaver:
        saves = 0

        def save(self, state, step, cutter=None):
            time.sleep(0.2)
            SleepySaver.saves += 1

    class SleepyProfiler:
        stops = 0

        def before_steps(self, step, n=1):
            pass

        def after_steps(self, step, wait_for=None):
            assert wait_for is not None  # the last program's loss
            time.sleep(0.1)
            SleepyProfiler.stops += 1

        def stop(self):
            pass

    anatomy = StepAnatomy(0)
    worker = _collective_worker(SleepySaver(), SleepyProfiler(), anatomy)
    marker = time.time()
    task = pb.Task(task_id=1, type=pb.TRAINING, shard_name="s",
                   start=0, end=16)
    worker._process_train_task(task)
    assert SleepySaver.saves >= 1 and SleepyProfiler.stops >= 1
    totals = anatomy.totals()
    assert totals.get("bookkeep", 0.0) < 0.05, totals
    saves = _spans_since(marker, "checkpoint.save")
    assert saves and sum(e["duration_s"] for e in saves) >= 0.2
    for key in ("majflt", "oublock", "nivcsw"):
        assert isinstance(saves[0][key], int)


# ---------------------------------------------------------------------------
# The task's one wait for the device, and the queue wait (ISSUE 48)
# ---------------------------------------------------------------------------

#: The metric files that read the window's chain and the fence.
WINDOW_CHAIN_METRICS = (
    "window_named_share", "window_largest_gap_s", "device_wait_share",
)


def test_the_fence_is_one_span_a_task_under_the_tasks_own_span(annotations):
    """`step.device_wait` wraps the leader's read of the task's last loss:
    one span a task, journal and annotation, a child of the span that is
    open around the task, with the task's id and steps; the anatomy books
    its seconds in the window the task sealed last, not in the next."""
    from elasticdl_tpu.proto import elasticdl_pb2 as pb

    anatomy = StepAnatomy(0)
    worker = _collective_worker(None, anatomy=anatomy)
    marker = time.time()
    with tracing.span("worker.task", task_id=7) as task_span:
        worker._process_train_task(pb.Task(
            task_id=7, type=pb.TRAINING, shard_name="s", start=0, end=16))
    (fence,) = _spans_since(marker, "step.device_wait")
    assert fence["parent_span_id"] == task_span.span_id
    assert (fence["task_id"], fence["steps"]) == (7, 4)
    entered = [name for name, _ in annotations]
    assert entered.count("step.device_wait") == 1
    # Behind the fence, the task's counters and reports are bookkeeping
    # on the profiler's plane; the journal's aggregates are the flush's.
    assert entered[-2:] == ["step.device_wait", "step.bookkeep"]
    assert len(_spans_since(marker, "step.bookkeep")) <= 1
    # Nothing is left in the accumulator for the next task's first window.
    assert anatomy.totals()["device_wait"] == pytest.approx(
        fence["duration_s"], abs=5e-3)
    assert "device_wait" in anatomy.snapshot()["windows"][-1]
    assert "device_wait" not in (anatomy.close_window() or {})
    # The aggregates the flush journals are the five they were.
    names = {e["name"] for e in _spans_since(marker)}
    assert "step.device_wait" not in {
        f"step.{phase}" for phase in tracing._WINDOW_PHASES}
    assert names >= {"step.data_wait", "step.stage", "step.device_wait"}


def test_a_rank_that_reads_no_loss_has_no_fence():
    from elasticdl_tpu.proto import elasticdl_pb2 as pb

    worker = _collective_worker(None)
    worker._world = worker._world.__class__(
        rank=1, world_size=1, rendezvous_id=1, coordinator_addr="")
    assert not worker._world.is_leader
    marker = time.time()
    worker._process_train_task(pb.Task(
        task_id=8, type=pb.TRAINING, shard_name="s", start=0, end=8))
    assert not _spans_since(marker, "step.device_wait")


@pytest.mark.parametrize("trace_id,journaled", [("t-1", 1), ("", 0)],
                         ids=["a_task", "a_wait_poll"])
def test_every_queue_rpc_is_an_annotation_real_tasks_a_span(
        annotations, trace_id, journaled):
    """`worker.get_task` journals after the fact and for real tasks only;
    its RPC is an annotation for every call, a WAIT poll too."""
    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.worker.master_client import MasterClient

    client = MasterClient.__new__(MasterClient)
    client._worker_id, client._retry_policy = 0, None
    client._call = lambda *a, **k: pb.GetTaskResponse(task=pb.Task(
        task_id=3 if trace_id else -1,
        type=pb.TRAINING if trace_id else pb.WAIT, trace_id=trace_id))
    marker = time.time()
    client.get_task()
    assert [name for name, _ in annotations] == ["worker.get_task"]
    assert len(_spans_since(marker, "worker.get_task")) == journaled


def test_the_fence_and_the_windows_chain_name_their_readers():
    """SPAN_NAMES, `docs/observability.md` and PERF.md's span table name
    `step.device_wait`, the queue wait's annotation and the three metric
    pairs over them, and every metric has its file."""
    assert tracing.SPAN_NAMES["step.device_wait"].startswith(
        "interval: the task's one wait for the device")
    assert "annotation" in tracing.SPAN_NAMES["worker.get_task"]
    with open(os.path.join(REPO_ROOT, "docs", "observability.md")) as f:
        docs = f.read()
    with open(os.path.join(REPO_ROOT, "PERF.md")) as f:
        perf = f.read()
    span_table = perf[perf.index("| span / family |"):perf.index("## 4. Cells")]
    assert "`step.device_wait`" in docs and "window_chain.py" in docs
    assert "| `step.device_wait`" in span_table
    assert "readers/window_chain.py" in perf
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in WINDOW_CHAIN_METRICS:
        for metric in (name, name + ".lm"):
            assert f"`{name}`" in span_table, name
            assert declared[metric]["source"] == "program_span"
            with open(os.path.join(
                    REPO_ROOT, "perfbench", "metrics", metric + ".json")) as f:
                reader = json.load(f)["reader"]
            assert reader == ("span_share" if name == "device_wait_share"
                              else "window_chain")
            assert os.path.exists(os.path.join(
                REPO_ROOT, "perfbench", "readers", reader + ".py"))


def test_profiler_stop_waits_and_journals_its_own_duration(tmp_path):
    import jax.numpy as jnp

    from elasticdl_tpu.common.profiler import StepProfiler

    marker = time.time() - 1
    profiler = StepProfiler(str(tmp_path), "1,2", worker_id=3)
    profiler.before_steps(0)
    profiler.after_steps(1, wait_for=jnp.ones(4) * 2)
    close = [
        e for e in obs.journal().tail(50)
        if e["event"] == "profile_window" and e["ts"] >= marker
        and e["action"] == "close"
    ]
    assert len(close) == 1 and close[0]["duration_s"] > 0


# ---------------------------------------------------------------------------
# The save's parts
# ---------------------------------------------------------------------------


def _assert_parts_inside(parent, parts):
    order = ["checkpoint.save.gather", "checkpoint.save.write",
             "checkpoint.save.crc", "checkpoint.save.commit"]
    assert [p["name"] for p in parts] == order
    lo, hi = parent["start_ts"], parent["start_ts"] + parent["duration_s"]
    cursor = lo
    for part in parts:
        assert part["parent_span_id"] == parent["span_id"]
        assert part["start_ts"] >= cursor - 1e-3
        cursor = part["start_ts"] + part["duration_s"]
        assert cursor <= hi + 1e-3


def test_full_save_has_four_children_in_order_with_the_files_bytes(tmp_path):
    import jax.numpy as jnp

    from elasticdl_tpu.checkpoint.saver import CheckpointSaver, save_span

    saver = CheckpointSaver(str(tmp_path))
    state = {"w": jnp.arange(4096, dtype=jnp.float32)}
    marker = time.time()
    with save_span(rank=0, step=7):
        final = saver.save(state, 7)
    spans = _spans_since(marker)
    parent = [e for e in spans if e["name"] == "checkpoint.save"][0]
    parts = sorted(
        (e for e in spans if e["name"].startswith("checkpoint.save.")),
        key=lambda e: e["start_ts"],
    )
    _assert_parts_inside(parent, parts)
    size = os.path.getsize(os.path.join(final, "state.pkl"))
    by_name = {p["name"]: p for p in parts}
    assert by_name["checkpoint.save.gather"]["bytes"] == 4096 * 4
    assert by_name["checkpoint.save.write"]["bytes"] == size
    assert by_name["checkpoint.save.crc"]["bytes"] == size
    assert "dirty_kb_start" in parent and "writeback_kb_end" in parent
    # and the restore's real part has a span of its own
    marker = time.time()
    restored, step = saver.load_latest()
    assert step == 7
    load = _spans_since(marker, "checkpoint.restore.load")
    assert len(load) == 1 and load[0]["bytes"] == size


def test_sharded_save_has_four_children_in_order_with_the_files_bytes(
    tmp_path,
):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elasticdl_tpu.checkpoint import ShardedCheckpointSaver
    from elasticdl_tpu.checkpoint.saver import save_span
    from elasticdl_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig())
    table = jax.device_put(
        jnp.arange(64 * 16, dtype=jnp.float32).reshape(64, 16),
        NamedSharding(mesh, P(("data", "model"))),
    )
    saver = ShardedCheckpointSaver(str(tmp_path))
    marker = time.time()
    with save_span(rank=0, step=3):
        final = saver.save(3, {"step": jnp.int32(3)}, {"table|t": table})
    spans = _spans_since(marker)
    parent = [e for e in spans if e["name"] == "checkpoint.save"][0]
    parts = sorted(
        (e for e in spans if e["name"].startswith("checkpoint.save.")),
        key=lambda e: e["start_ts"],
    )
    _assert_parts_inside(parent, parts)
    by_name = {p["name"]: p for p in parts}
    written = sum(
        os.path.getsize(os.path.join(final, name))
        for name in ("shards_p0of1.npz", "dense.pkl")
    )
    assert by_name["checkpoint.save.gather"]["bytes"] >= 64 * 16 * 4
    assert by_name["checkpoint.save.write"]["bytes"] == written
    assert by_name["checkpoint.save.crc"]["bytes"] == written + os.path.getsize(
        os.path.join(final, "manifest.json")
    )


@pytest.mark.parametrize("kind", ["full", "sharded"])
def test_the_write_span_carries_the_streams_counters(tmp_path, kind):
    """`pieces` and `recycled_bytes` (PR 50) beside the four of PR 39;
    no file under `perfbench/` reads any of them yet (`PERF.md` section 7)."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.checkpoint import ShardedCheckpointSaver
    from elasticdl_tpu.checkpoint.saver import (
        CheckpointSaver, LeafCutter, save_span,
    )
    from elasticdl_tpu.obs.tracing import SPAN_NAMES

    leaf = jnp.arange(64 * 16, dtype=jnp.float32).reshape(64, 16)
    cutter = LeafCutter(piece_bytes=1024)
    cutter.warm([leaf])
    marker = time.time()
    with save_span(rank=0, step=1):
        if kind == "full":
            CheckpointSaver(str(tmp_path)).save(
                {"w": leaf, "n": jnp.int32(1)}, 1, cutter=cutter
            )
        else:
            ShardedCheckpointSaver(str(tmp_path)).save(
                1, {"n": jnp.int32(1)}, {"table|t": leaf}, cutter=cutter
            )
    (write,) = _spans_since(marker, "checkpoint.save.write")
    assert write["pieces"] == 4 + 1 and write["leaves"] == 2
    assert write["recycled_bytes"] == 0  # (pieces under 1 MiB: not followed)
    assert write["lookahead_peak_bytes"] == 4096 + 4
    assert write["copied_bytes"] == (0 if kind == "full" else 4)
    for field in ("pieces", "recycled_bytes", "streamed_bytes", "leaves",
                  "lookahead_peak_bytes", "copied_bytes"):
        assert field in write
        assert f"`{field}`" in SPAN_NAMES["checkpoint.save.write"]
    reads = " ".join(
        open(os.path.join(REPO_ROOT, "perfbench", "metrics", name)).read()
        for name in os.listdir(os.path.join(REPO_ROOT, "perfbench", "metrics"))
    )
    assert "recycled_bytes" not in reads and '"pieces"' not in reads


# ---------------------------------------------------------------------------
# The host data plane
# ---------------------------------------------------------------------------


@pytest.fixture
def etrf_file(tmp_path):
    from elasticdl_tpu.data import recordfile

    path = str(tmp_path / "d.etrf")
    recordfile.write_records(path, (bytes([i % 251]) * 24 for i in range(9000)))
    return path


def test_index_load_carries_the_index_bytes_once_per_open(etrf_file):
    """`index_bytes` is what the handle really read of the index: the
    entries its ranges needed, 8 bytes each, not the index's size."""
    from elasticdl_tpu import native

    codec = native.record_file()
    if codec is None:
        pytest.skip("no native record codec here")
    marker = time.time()
    # 9000 records in chunks of 4096: three ranges of ONE open handle.
    chunks = list(codec.read_range_buffers(etrf_file, 0, 9000))
    assert len(chunks) == 3
    loads = _spans_since(marker, "data.index_load")
    reads = _spans_since(marker, "data.read")
    # One interval per open, around the first range's size query: it
    # read entry 0 and entry 4096.
    assert len(loads) == 1 and len(reads) == 1
    assert loads[0]["index_bytes"] == 16 and loads[0]["opens"] == 1
    assert reads[0]["records"] == 9000
    assert reads[0]["payload_bytes"] == 9000 * 24
    # The task: entry 8192 more (4096 was in the memo, and the file's
    # end is the footer's index_offset), of an index of 72,000 bytes.
    assert reads[0]["index_bytes"] == 24 and reads[0]["opens"] == 1
    # A second range of the open handle adds its own few bytes and no
    # second span.
    task = {"opens": 1, "index_loaded": True}
    handle = codec._lib.edl_rf_open(etrf_file.encode())
    try:
        codec._lib.edl_rf_range_size(handle, 0, 10)
        assert codec._lib.edl_rf_index_bytes_read(handle) == 16
        marker = time.time()
        assert codec._range_size(handle, 10, 20, task) == 10 * 24
        assert codec._lib.edl_rf_index_bytes_read(handle) == 24
    finally:
        codec._lib.edl_rf_close(handle)
    assert not _spans_since(marker, "data.index_load")


def test_python_codec_journals_the_same_read_span(etrf_file, monkeypatch):
    from elasticdl_tpu.data import recordfile

    monkeypatch.setenv("ELASTICDL_DISABLE_NATIVE", "1")
    marker = time.time()
    chunks = list(recordfile.read_range_buffers(etrf_file, 100, 300))
    assert sum(len(lengths) for _, lengths in chunks) == 200
    reads = _spans_since(marker, "data.read")
    assert len(reads) == 1
    assert reads[0]["records"] == 200 and reads[0]["index_bytes"] == 0
    assert reads[0]["payload_bytes"] == 200 * 24


def test_decode_span_counts_the_tasks_records():
    from elasticdl_tpu.data.columnar import materialize_columnar_task

    class Reader:
        def read_columns(self, task):
            yield {"x": np.zeros((5, 2), np.float32)}
            yield {"x": np.ones((3, 2), np.float32)}

    def columnar_fn(columns, mode, metadata):
        return columns, None

    marker = time.time()
    task = materialize_columnar_task(Reader(), object(), columnar_fn,
                                     "training", None)
    assert task.n == 8
    decode = _spans_since(marker, "data.decode")
    assert len(decode) == 1 and decode[0]["records"] == 8
    assert decode[0]["read_columns_s"] >= 0


# ---------------------------------------------------------------------------
# Start-up
# ---------------------------------------------------------------------------


def test_proc_start_spans_creation_to_main(monkeypatch):
    monkeypatch.setattr(tracing, "_main_start_ts", None)
    monkeypatch.setattr(tracing, "_proc_start_recorded", False)
    assert tracing.record_proc_start() is None  # main never noted
    tracing.note_main_start()
    record = tracing.record_proc_start()
    assert record["name"] == "proc.start"
    # This test process was created well before "main" was noted here.
    assert 0 < record["duration_s"] < 24 * 3600
    assert abs(record["start_ts"] + record["duration_s"] - time.time()) < 5
    assert tracing.record_proc_start() is None  # once a process


def test_first_call_of_a_compiled_entrypoint_is_a_build_span():
    import jax.numpy as jnp

    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel import compile as pc

    plan = pc.CompilePlan(build_mesh(MeshConfig()), trainer="test")
    double = plan.compile(lambda x: x * 2, name="double", journal=False)
    marker = time.time()
    assert float(double(jnp.float32(2))) == 4.0
    assert float(double(jnp.float32(3))) == 6.0
    builds = _spans_since(marker, "compile.build")
    assert len(builds) == 1
    assert builds[0]["entrypoint"] == "double"
    assert builds[0]["cache_hit"] in (True, False)
    # No executable store in this process: the jitted function's own call.
    assert builds[0]["aot_hit"] is False and builds[0]["aot_load_s"] == 0.0
    assert builds[0]["aot_skip"] == "the process has no executable store"
    assert double._cache_size() == 1  # the jitted function's own


def _journal_spans(path):
    with open(path) as f:
        return [
            e for e in map(json.loads, f) if e.get("event") == "span"
        ]


def _covered_share(parent, children):
    """The share of `parent`'s interval inside the union of `children`."""
    lo = parent["start_ts"]
    hi, reach, covered = lo + parent["duration_s"], lo, 0.0
    for child in sorted(children, key=lambda e: e["start_ts"]):
        end = min(hi, child["start_ts"] + child["duration_s"])
        covered += max(0.0, end - max(reach, child["start_ts"]))
        reach = max(reach, end)
    return covered / parent["duration_s"]


@pytest.fixture(scope="module")
def tiny_job(tmp_path_factory):
    """`elasticdl train` as a user runs it, one worker process, on the
    CPU: (master spans, worker spans) of its journals."""
    tmp = tmp_path_factory.mktemp("boot_chain")
    proc = subprocess.run(
        [sys.executable, "-m", "elasticdl_tpu.client.main", "train",
         "--distribution_strategy=AllreduceStrategy", "--num_workers=1",
         "--model_zoo=model_zoo", "--model_def=mnist.mnist_functional_api",
         "--training_data=synthetic://mnist?n=384", "--records_per_task=64",
         "--minibatch_size=32", "--job_name=boot_chain",
         f"--tensorboard_log_dir={tmp / 'tb'}",
         f"--checkpoint_dir={tmp / 'ckpt'}"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=1"),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return (
        _journal_spans(tmp / "tb" / "events.jsonl"),
        _journal_spans(tmp / "tb" / "events_worker_0.jsonl"),
        str(tmp / "tb" / "events.jsonl"),
    )


def test_master_boot_names_nine_tenths_of_itself_and_brings_no_jax(tiny_job):
    master, _, _ = tiny_job
    (boot,) = [e for e in master if e["name"] == "master.boot"]
    (proc_start,) = [e for e in master if e["name"] == "proc.start"]
    # The boot starts where proc.start ends: one clock read.
    assert proc_start["start_ts"] + proc_start["duration_s"] == pytest.approx(
        boot["start_ts"], abs=1e-3)
    children = [e for e in master if e.get("parent_span_id") == boot["span_id"]]
    assert {e["name"] for e in children} == {
        "master.imports", "spec.load", "master.build", "master.serve_ready",
    }
    assert _covered_share(boot, children) >= 0.9
    (build,) = [e for e in children if e["name"] == "master.build"]
    (tb_init,) = [e for e in master if e["name"] == "master.tensorboard_init"]
    assert tb_init["parent_span_id"] == build["span_id"]
    # What the master holds of the heavy frameworks, its zoo module
    # brought: none is its own import.
    (spec,) = [e for e in children if e["name"] == "spec.load"]
    assert boot["heavy_imports"] == ["jax"]
    assert set(boot["heavy_imports"]) <= set(spec["imported"])
    assert not any("since_main_s" in e for e in master)
    # Serving -> the worker's creation is named too.
    (fleet,) = [e for e in master if e["name"] == "master.build_fleet"]
    (launch,) = [e for e in master if e["name"] == "master.launch_worker"]
    assert (launch["worker_id"], launch["cause"]) == (0, "start")
    assert "since_exit_s" not in launch
    boot_end = boot["start_ts"] + boot["duration_s"]
    assert boot_end <= fleet["start_ts"] <= launch["start_ts"]


def test_worker_boot_has_its_five_children_in_order(tiny_job):
    _, worker, _ = tiny_job
    (boot,) = [e for e in worker if e["name"] == "worker.boot"]
    children = sorted(
        (e for e in worker if e.get("parent_span_id") == boot["span_id"]),
        key=lambda e: e["start_ts"],
    )
    assert [e["name"] for e in children] == [
        "worker.imports", "spec.load", "worker.join_world",
        "worker.backend_init", "worker.build_trainer",
    ]
    for before, after in zip(children, children[1:]):
        assert before["start_ts"] + before["duration_s"] <= after["start_ts"]
    assert _covered_share(boot, children) >= 0.9
    imports = children[0]
    assert 0 < imports["jax_import_s"] <= imports["duration_s"]
    assert children[2]["world_size"] == 1
    assert {e["proc"] for e in children + [boot]} == {"worker_0"}
    # The first task opens after the boot closed.
    first_task = min(
        e["start_ts"] for e in worker if e["name"] == "worker.task")
    assert boot["start_ts"] + boot["duration_s"] <= first_task


def test_a_task_after_warm_up_journals_the_spans_it_did(tiny_job):
    """The start-up chain adds nothing inside a task: after the first,
    every task journals the seven spans it journaled before ISSUE 34,
    and since ISSUE 48 its one wait for the device."""
    _, worker, _ = tiny_job
    by_task = {}
    for e in worker:
        if e.get("trace_id"):
            by_task.setdefault(e["trace_id"], []).append(e["name"])
    steady = list(by_task.values())[1:]
    assert len(steady) >= 4
    for names in steady:
        assert sorted(names) == sorted([
            "worker.get_task", "step.data_wait", "step.stage",
            "step.execute", "step.device_wait", "step.bookkeep",
            "worker.task", "worker.report_task",
        ])


def test_report_prints_a_boot_row_for_each_process(tiny_job, capsys):
    from elasticdl_tpu.obs import report

    master, worker, journal_path = tiny_job
    assert report.main([journal_path, "--json", "-"]) == 0
    out = capsys.readouterr().out
    summary = json.loads(out[out.index('{\n  "wall_s"'):])
    rows = {row["proc"]: row for row in summary["boot"]}
    assert list(rows) == ["master", "worker_0"]  # by creation
    for proc, spans, boot_name in (
        ("master", master, "master.boot"),
        ("worker_0", worker, "worker.boot"),
    ):
        row = rows[proc]
        (boot,) = [e for e in spans if e["name"] == boot_name]
        assert row["boot"] == boot_name
        assert row["boot_s"] == pytest.approx(boot["duration_s"])
        durations = [child["duration_s"] for child in row["children"]]
        assert durations == sorted(durations, reverse=True)
        assert 0 <= row["self_s"] <= 0.1 * row["boot_s"]
        assert row["first_ack_s"] > row["proc_start_s"] + row["boot_s"]
    assert rows["master"]["heavy_imports"] == ["jax"]
    assert [b["entrypoint"] for b in rows["worker_0"]["builds"]] == [
        "dp_init", "dp_train_window",
    ]
    assert rows["master"]["builds"] == []
    text = out[:out.index('{\n  "wall_s"')]
    assert "boot (a row a process" in text
    assert "compile.build dp_train_window: trace " in text
    assert "aot_load " in text and "aot_hit False, aot_skip " in text


def test_report_shows_the_two_builds_of_a_ps_job(tmp_path, capsys):
    """A ParameterServerStrategy worker builds two programs before its
    first window: `ps_init` inside `state.init` (the state born in its
    layout, as `dp_init`'s is), then the window program."""
    from elasticdl_tpu.obs import report

    proc = subprocess.run(
        [sys.executable, "-m", "elasticdl_tpu.client.main", "train",
         "--distribution_strategy=ParameterServerStrategy", "--num_workers=1",
         "--model_zoo=model_zoo", "--model_def=deepfm.deepfm_functional_api",
         "--training_data=synthetic://criteo?n=256&vocab=64",
         "--model_params=vocab_size=64", "--records_per_task=64",
         "--minibatch_size=16", "--job_name=ps_boot",
         f"--tensorboard_log_dir={tmp_path / 'tb'}"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=1"),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    worker = _journal_spans(tmp_path / "tb" / "events_worker_0.jsonl")
    (state_init,) = [e for e in worker if e["name"] == "state.init"]
    builds = [e for e in worker if e["name"] == "compile.build"]
    assert [b["entrypoint"] for b in builds] == ["ps_init", "ps_train_window"]
    assert builds[0]["parent_span_id"] == state_init["span_id"]
    assert builds[1]["parent_span_id"] != state_init["span_id"]
    journal = str(tmp_path / "tb" / "events.jsonl")
    assert report.main([journal, "--json", "-"]) == 0
    out = capsys.readouterr().out
    summary = json.loads(out[out.index('{\n  "wall_s"'):])
    rows = {row["proc"]: row for row in summary["boot"]}
    assert [b["entrypoint"] for b in rows["worker_0"]["builds"]] == [
        "ps_init", "ps_train_window",
    ]
    assert "compile.build ps_init: trace " in out


_BUILD_PROBE = """
import json
from elasticdl_tpu.common import compile_cache
compile_cache.configure()
import jax, jax.numpy as jnp
from elasticdl_tpu import obs
from elasticdl_tpu.parallel import MeshConfig, build_mesh, compile as pc

inner = jax.jit(lambda x: jnp.tanh(x) @ x)
plan = pc.CompilePlan(build_mesh(MeshConfig()), trainer="test")
outer = plan.compile(
    lambda x: inner(x) + inner(x * 2), name="outer", journal=False)
x = jnp.ones((64, 64))
outer(x).block_until_ready()
(build,) = [
    e for e in obs.journal().tail(100) if e.get("name") == "compile.build"]
print(json.dumps(build))
"""


def test_build_span_says_what_it_was_made_of_and_reads_the_cache(tmp_path):
    """A jit that calls a jit reports the inner trace inside the outer:
    the parts are unions, so they sum to no more than the span.  A second
    process finds the program in the persistent cache."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    builds = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-c", _BUILD_PROBE], cwd=REPO_ROOT, env=env,
            capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        builds.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    for build in builds:
        assert build["entrypoint"] == "outer" and build["programs"] == 1
        parts = build["trace_s"] + build["lower_s"] + build["backend_s"]
        assert 0 < parts <= build["duration_s"] + 1e-6
        assert min(build["trace_s"], build["lower_s"], build["backend_s"]) > 0
        assert build["cache_read_s"] <= build["backend_s"] + 1e-6
    cold, warm = builds
    assert cold["cache_hit"] is False and cold["cache_read_s"] == 0
    assert warm["cache_hit"] is True and warm["cache_read_s"] > 0


def test_build_parts_take_the_union_of_what_jax_reports(monkeypatch):
    """Durations that overlap (a jit traced inside another; a program
    compiled while another is traced) are not summed."""
    from elasticdl_tpu.common import compile_cache

    clock = [100.0]
    monkeypatch.setattr(compile_cache.time, "monotonic", lambda: clock[0])
    parts = compile_cache.BuildParts()
    trace, lower, backend = (event for event, _ in reversed(compile_cache._PARTS))
    clock[0] = 103.0
    parts.note(trace, 1.0)            # the inner jit: 102..103
    parts.note(backend, 0.5)          # an eager op inside the trace
    clock[0] = 104.0
    parts.note(trace, 4.0)            # the outer: 100..104, holds both
    clock[0] = 105.0
    parts.note(lower, 1.0)
    clock[0] = 108.0
    parts.note(compile_cache._READ_EVENT, 2.0)
    parts.note(backend, 3.0)
    parts.note(trace, 50.0)           # began before the build: clipped
    parts.hits = 2
    fields = parts.fields()
    assert fields == {
        "backend_s": 3.5, "lower_s": 1.0, "trace_s": 3.5,
        "cache_read_s": 2.0, "programs": 2, "cache_hit": True,
    }


class _FakeFleet:
    """A test double of the substrate: handles whose exit codes the test
    sets."""

    def __new__(cls, **kwargs):
        from elasticdl_tpu.master.pod_manager import ElasticWorkerManager

        class Handle:
            def __init__(self, worker_id):
                self.worker_id, self.code = worker_id, None

        class Fleet(ElasticWorkerManager):
            launched = []

            def _substrate_launch(self, worker_ids):
                handles = [Handle(wid) for wid in worker_ids]
                self.launched.extend(handles)
                return handles

            def _substrate_poll(self, handle):
                return handle.code

            def _substrate_terminate(self, handles):
                pass

        return Fleet(worker_argv_fn=lambda wid: [], **kwargs)


def test_a_relaunch_says_its_cause_and_how_long_after_the_exit():
    done = []
    fleet = _FakeFleet(
        num_workers=1, max_restarts=1, poll_interval_s=0.02,
        job_finished_fn=lambda: bool(done),
    )
    marker = time.time()
    try:
        fleet.start()
        fleet.launched[0].code = 137  # preempted
        deadline = time.time() + 20
        while len(fleet.launched) < 2 and time.time() < deadline:
            time.sleep(0.02)
        assert len(fleet.launched) == 2
        done.append(True)
        fleet.launched[1].code = 0
        assert fleet.wait(timeout=20)
    finally:
        fleet.stop()
    first, second = _spans_since(marker, "master.launch_worker")
    assert (first["worker_id"], first["cause"]) == (0, "start")
    assert "since_exit_s" not in first
    assert (second["worker_id"], second["cause"]) == (1, "relaunch")
    assert 0 <= second["since_exit_s"] < 5


# ---------------------------------------------------------------------------
# Device scopes: metadata only
# ---------------------------------------------------------------------------


def _sparse_window(seed=0):
    """(trainer, staged window) of a tiny DeepFM on the PS trainer."""
    sys.path.insert(0, ZOO)
    from deepfm import deepfm_functional_api as zoo

    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.ps_trainer import ShardedEmbeddingTrainer

    mesh = build_mesh(MeshConfig())
    trainer = ShardedEmbeddingTrainer(
        model=zoo.custom_model(vocab_size=32, embedding_dim=4, hidden=16),
        loss_fn=zoo.loss, optimizer=zoo.optimizer(), mesh=mesh,
        embedding_optimizer=zoo.embedding_optimizer(),
        sparse_apply_every=2,
    )
    rng = np.random.RandomState(seed)
    features = {
        "dense": rng.rand(8, 13).astype(np.float32),
        "cat": rng.randint(0, 32, size=(8, 26)).astype(np.int32),
    }
    labels = rng.randint(0, 2, size=(8,)).astype(np.int32)
    trainer.ensure_initialized(features)
    batch = (features, labels, np.ones((8,), np.float32))
    return trainer, trainer.stage_window([batch, batch])


@pytest.mark.parametrize("build,jit_attr,scopes", [pytest.param(
    _sparse_window, "_train_window",
    ("fwd_bwd", "dense_update", "sparse_apply", "grad_accumulate",
     "sparse_adam"), id="deepfm",
)])
def test_scopes_are_on_the_op_names_and_leave_outputs_bit_equal(
    build, jit_attr, scopes, monkeypatch,
):
    """The PS trainer's window; each language model's is this check in its
    own `tests/test_<m>_program.py` (`tests/lm_contract.py`)."""
    scopes_are_metadata(build, jit_attr, scopes, monkeypatch)


def test_every_literal_span_name_in_the_training_path_is_in_the_list():
    """The bounded list is a gate: a span opened under a new name in the
    master, worker, checkpoint, data or parallel code fails here until
    SPAN_NAMES says what it times (the serving plane keeps its own
    names; obs/trace.py and obs/report.py only build synthetic
    journals for their selftests)."""
    import ast

    pkg = os.path.join(REPO_ROOT, "elasticdl_tpu")
    skip = (os.path.join(pkg, "serving"), os.path.join(pkg, "obs", "trace.py"),
            os.path.join(pkg, "obs", "report.py"),
            os.path.join(pkg, "obs", "slo.py"))
    seen = set()
    for folder, _, files in os.walk(pkg):
        for fname in files:
            path = os.path.join(folder, fname)
            if not fname.endswith(".py") or path.startswith(skip):
                continue
            with open(path) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                called = getattr(func, "attr", getattr(func, "id", ""))
                if called not in ("span", "record_span", "annotate",
                                  "early_span", "open_span", "begin_boot"):
                    continue
                first = node.args[0] if node.args else next(
                    (kw.value for kw in node.keywords if kw.arg == "name"),
                    None,
                )
                if isinstance(first, ast.Constant) and isinstance(
                    first.value, str
                ):
                    seen.add((first.value, os.path.relpath(path, pkg)))
    unknown = sorted(
        (name, where) for name, where in seen
        if name not in tracing.SPAN_NAMES
    )
    assert not unknown, unknown
    assert len({name for name, _ in seen}) >= 20
