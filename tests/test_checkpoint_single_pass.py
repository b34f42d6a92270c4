"""The single-pass checkpoint write (PR 27).

A save hands every byte to the file once, from the host array's own
memory, and the size and CRC32 that `integrity.json` records are taken
from those bytes as they pass: no `tobytes()` of array data, no pickle
framing around it, no second read of a file this process has just
written.  The guarantees around it (manifest before the rename,
quarantine and fallback on a torn file, the parent commit's checkpoints
still restore) are held here too.
"""

import importlib.util
import json
import os
import pickle
import struct
import time
import zipfile
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticdl_tpu import obs
from elasticdl_tpu.checkpoint import ShardedCheckpointSaver, saver as saver_mod
from elasticdl_tpu.checkpoint.saver import (
    CheckpointSaver,
    ChecksumWriter,
    crc32_combine,
    file_crc32,
    read_state,
    save_span,
    write_integrity_manifest,
    write_state,
)
from elasticdl_tpu.checkpoint.sharded import write_npz
from elasticdl_tpu.common import faults
from elasticdl_tpu.parallel import MeshConfig, build_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spans_since(marker, name):
    return [
        e for e in obs.journal().tail(2000)
        if e.get("event") == "span" and e["ts"] >= marker
        and e["name"] == name
    ]


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return (
        a.dtype == b.dtype and a.shape == b.shape
        and np.ascontiguousarray(a).tobytes()
        == np.ascontiguousarray(b).tobytes()
    )


# ---------------------------------------------------------------------------
# The shared writer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("len_b", [0, 1, 7, 4096, 77777, (1 << 20) + 3])
def test_crc32_combine_is_the_crc_of_the_concatenation(len_b):
    rng = np.random.default_rng(len_b)
    a = rng.integers(0, 256, 1234, dtype=np.uint8).tobytes()
    b = rng.integers(0, 256, len_b, dtype=np.uint8).tobytes()
    assert crc32_combine(zlib.crc32(a), zlib.crc32(b), len_b) == zlib.crc32(
        a + b
    )


@pytest.mark.parametrize("piece,beside", [(16 << 20, 1 << 20), (4096, 1024)])
def test_checksum_writer_takes_crc_and_size_from_the_bytes_in_flight(
    tmp_path, monkeypatch, piece, beside
):
    """Whatever the piece size and whichever thread folds a piece, the
    running CRC32 is that of the file; a member's own CRC32 comes out of
    the same pass."""
    monkeypatch.setattr(saver_mod, "_PIECE_BYTES", piece)
    monkeypatch.setattr(saver_mod, "_BESIDE_BYTES", beside)
    rng = np.random.default_rng(0)
    head = b"header"
    body = rng.normal(size=(700, 301)).astype(np.float32)  # read-only too
    body.flags.writeable = False
    tail = rng.integers(0, 256, 999, dtype=np.uint8)
    path = str(tmp_path / "f.bin")
    with ChecksumWriter(path) as writer:
        writer.write(head)
        writer.begin_member()
        writer.write(body.reshape(-1).view(np.uint8))
        writer.write(pickle.PickleBuffer(tail))
        member = writer.end_member()
        writer.write(b"")
        writer.write(bytearray(b"end"))
    data = open(path, "rb").read()
    assert data == head + body.tobytes() + tail.tobytes() + b"end"
    assert (writer.size, writer.crc32) == (len(data), zlib.crc32(data))
    assert member == zlib.crc32(body.tobytes() + tail.tobytes())
    assert file_crc32(path) == writer.crc32


def test_manifest_reads_back_only_what_it_was_not_given(tmp_path):
    (tmp_path / "a.bin").write_bytes(b"a" * 100)
    (tmp_path / "b.bin").write_bytes(b"b" * 50)
    reread = write_integrity_manifest(
        str(tmp_path), ["a.bin", "b.bin"], known={"a.bin": (123, 100)}
    )
    assert reread == 50
    files = json.loads((tmp_path / "integrity.json").read_text())["files"]
    assert files["a.bin"] == {"crc32": 123, "size": 100}
    assert files["b.bin"] == {"crc32": zlib.crc32(b"b" * 50), "size": 50}
    # `delta.py`'s callers give nothing and have everything read back.
    assert write_integrity_manifest(str(tmp_path), ["a.bin", "b.bin"]) == 150


# ---------------------------------------------------------------------------
# (a) CheckpointSaver: every leaf kind, bit for bit, writable
# ---------------------------------------------------------------------------


def _leaf(kind):
    rng = np.random.default_rng(1)
    return {
        "float32": lambda: rng.normal(size=(37, 5)).astype(np.float32),
        "bfloat16": lambda: jnp.asarray(
            rng.normal(size=(9, 3)), jnp.bfloat16
        ),
        "zero_d": lambda: np.asarray(7, np.int32),
        "non_contiguous": lambda: rng.normal(size=(8, 10))[:, ::3],
        "fortran": lambda: np.asfortranarray(rng.normal(size=(4, 6))),
        "permuted_axes": lambda: rng.normal(size=(3, 4, 5)).transpose(1, 2, 0),
        "int64": lambda: rng.integers(-9, 9, size=11),
        "empty": lambda: np.zeros((0, 4), np.float32),
        "device_array": lambda: jnp.arange(12.0).reshape(3, 4),
        "read_only_host": lambda: jax.device_get(jnp.arange(5, dtype=jnp.uint8)),
    }[kind]()


@pytest.mark.parametrize("kind", [
    "float32", "bfloat16", "zero_d", "non_contiguous", "fortran",
    "permuted_axes", "int64", "empty", "device_array", "read_only_host",
])
def test_array_leaf_round_trips_bit_for_bit_and_writable(tmp_path, kind):
    leaf = _leaf(kind)
    saver = CheckpointSaver(str(tmp_path))
    saver.save({"layer": {"w": leaf}, "step": 3}, 3)
    restored, step = saver.load_latest()
    got = restored["layer"]["w"]
    assert step == 3 and restored["step"] == 3
    assert type(got) is np.ndarray and _same_bits(got, leaf)
    assert got.flags.writeable
    if kind in ("fortran", "permuted_axes"):
        assert got.strides == leaf.strides  # the layout it was saved from
    got[...] = 0  # a restored state may be updated in place


def test_whole_tree_round_trips_with_scalars_and_a_typed_key(tmp_path):
    shared = np.arange(6, dtype=np.float32)
    state = {
        "params": {"w": _leaf("float32"), "b": _leaf("bfloat16")},
        "count": _leaf("zero_d"),
        "key": jax.random.key(11),
        "lr": 3.5, "epoch": 2, "name": "adam", "none": None,
        "tuple": (1, _leaf("int64")),
        "twice": [shared, shared],
    }
    saver = CheckpointSaver(str(tmp_path))
    saver.save(state, 5)
    restored, step = saver.load_latest()
    assert step == 5
    assert jax.tree.structure(restored) == jax.tree.structure(state)
    for got, want in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
        if isinstance(want, jax.Array) and jax.dtypes.issubdtype(
            want.dtype, jax.dtypes.prng_key
        ):
            assert _same_bits(
                jax.random.key_data(got), jax.random.key_data(want)
            )
        elif hasattr(want, "dtype"):
            assert _same_bits(got, want)
        else:
            assert got == want and type(got) is type(want)
    # One leaf held twice is written once and still shares its memory.
    assert np.shares_memory(restored["twice"][0], restored["twice"][1])


def test_raw_layout_copies_nothing_but_what_is_not_dense(tmp_path):
    """A transposed array (how a TPU's weights may come back from
    `jax.device_get`) goes out from its own memory too."""
    tree = {
        "w": _leaf("float32"), "b": np.asarray(_leaf("bfloat16")),
        "n": _leaf("zero_d"), "py": 1.5, "f": _leaf("fortran"),
        "p": _leaf("permuted_axes"),
    }
    with ChecksumWriter(str(tmp_path / "a")) as writer:
        assert write_state(writer, tree) == 0
    strided = _leaf("non_contiguous")
    with ChecksumWriter(str(tmp_path / "b")) as writer:
        assert write_state(writer, {"s": strided, **tree}) == strided.nbytes
    # The arrays' bytes are in the file as they are in memory.
    data = (tmp_path / "a").read_bytes()
    assert data.startswith(b"EDLRAW01")
    assert tree["w"].tobytes() in data and tree["b"].tobytes() in data
    assert tree["f"].T.tobytes() in data


# ---------------------------------------------------------------------------
# (b) the manifest's numbers are the committed files' own
# ---------------------------------------------------------------------------


def _sharded_save(tmp_path, step=3, rows=64):
    mesh = build_mesh(MeshConfig())
    table = jax.device_put(
        jnp.arange(rows * 16, dtype=jnp.float32).reshape(rows, 16),
        NamedSharding(mesh, P(("data", "model"))),
    )
    saver = ShardedCheckpointSaver(str(tmp_path))
    dense = {"step": jnp.int32(step), "params": {"k": jnp.ones((4, 3))}}
    with save_span(rank=0, step=step):
        final = saver.save(step, dense, {"table|t": table})
    return saver, final, table


@pytest.mark.parametrize(
    "name", ["state.pkl", "shards_p0of1.npz", "dense.pkl", "manifest.json"]
)
def test_manifest_matches_the_committed_file(tmp_path, name):
    if name == "state.pkl":
        final = CheckpointSaver(str(tmp_path)).save(
            {"w": _leaf("float32"), "k": jax.random.key(0)}, 1
        )
    else:
        _saver, final, _table = _sharded_save(tmp_path)
    files = json.load(open(os.path.join(final, "integrity.json")))["files"]
    path = os.path.join(final, name)
    assert files[name] == {
        "crc32": file_crc32(path), "size": os.path.getsize(path),
    }
    assert saver_mod.verify_integrity(final) is None


# ---------------------------------------------------------------------------
# (c) no read-back
# ---------------------------------------------------------------------------


@pytest.fixture
def no_big_rereads(monkeypatch):
    real = file_crc32

    def guarded(path, *args, **kwargs):
        assert os.path.getsize(path) <= 1 << 20, f"{path} was read back"
        return real(path, *args, **kwargs)

    monkeypatch.setattr(saver_mod, "file_crc32", guarded)


@pytest.mark.parametrize("kind", ["full", "sharded"])
def test_save_reads_nothing_back(tmp_path, no_big_rereads, kind):
    marker = time.time()
    if kind == "full":
        state = {"w": np.ones((600, 1000), np.float32), "n": 3}
        with save_span(rank=0, step=2):
            final = CheckpointSaver(str(tmp_path)).save(state, 2)
        big = os.path.join(final, "state.pkl")
    else:
        _saver, final, _table = _sharded_save(tmp_path, step=2, rows=32768)
        big = os.path.join(final, "shards_p0of1.npz")
    assert os.path.getsize(big) > 1 << 20 and os.path.isdir(final)
    (crc,) = _spans_since(marker, "checkpoint.save.crc")
    (write,) = _spans_since(marker, "checkpoint.save.write")
    assert crc["reread_bytes"] == 0
    files = json.load(open(os.path.join(final, "integrity.json")))["files"]
    assert crc["bytes"] == sum(meta["size"] for meta in files.values())
    # What was copied on the way: nothing of a contiguous tree; of a
    # sharded save only the dense part, which stays a plain pickle.
    assert write["copied_bytes"] == (0 if kind == "full" else 4 + 4 * 3 * 4)


# ---------------------------------------------------------------------------
# (d) the parent commit's checkpoints still restore
# ---------------------------------------------------------------------------


def test_plain_pickle_state_file_of_the_parent_still_restores(tmp_path):
    state = {"w": _leaf("float32"), "step": 4, "k": jax.random.key(2)}
    step_dir = tmp_path / "step_000000000004"
    step_dir.mkdir()
    with open(step_dir / "state.pkl", "wb") as f:
        pickle.dump(jax.device_get(state), f)  # saver.py:307-310 at PR 26
    write_integrity_manifest(str(step_dir), ["state.pkl"])
    saver = CheckpointSaver(str(tmp_path))
    restored, step = saver.load_latest()
    assert step == 4 and _same_bits(restored["w"], state["w"])
    # The two layouts live side by side in one directory; the newest wins.
    saver.save({"w": state["w"] + 1, "step": 5}, 5)
    restored, step = saver.load_latest()
    assert step == 5 and _same_bits(restored["w"], state["w"] + 1)
    with open(tmp_path / "step_000000000005" / "state.pkl", "rb") as f:
        assert f.read(8) == b"EDLRAW01"
    with open(step_dir / "state.pkl", "rb") as f:
        assert f.read(1) == b"\x80"  # a pickle stream's PROTO opcode


# ---------------------------------------------------------------------------
# (e) a torn file of the new layout
# ---------------------------------------------------------------------------


def test_torn_raw_state_file_is_quarantined_and_restore_falls_back(tmp_path):
    saver = CheckpointSaver(str(tmp_path), keep_max=5)
    old = {"w": np.full((300, 300), 1.0, np.float32), "step": 1}
    saver.save(old, 1)
    faults.install("ckpt.write:truncate@1")  # tears after the checksum
    try:
        saver.save({"w": np.full((300, 300), 2.0, np.float32), "step": 2}, 2)
    finally:
        faults.clear()
    torn = tmp_path / "step_000000000002" / "state.pkl"
    files = json.load(open(torn.parent / "integrity.json"))["files"]
    assert os.path.getsize(torn) < files["state.pkl"]["size"]
    restored, step = saver.load_latest()
    assert step == 1 and _same_bits(restored["w"], old["w"])
    assert "step_000000000002.quarantined" in os.listdir(tmp_path)
    assert saver.steps() == [1]


@pytest.mark.parametrize("damage", ["cut_buffers", "cut_header", "lengths"])
def test_damaged_raw_state_file_without_a_manifest_is_quarantined(
    tmp_path, damage
):
    """A snapshot with no manifest passes verification vacuously; the
    raw layout's own header then has to add up to the file's size."""
    saver = CheckpointSaver(str(tmp_path), keep_max=5)
    saver.save({"w": _leaf("float32"), "step": 1}, 1)
    final = saver.save({"w": _leaf("float32") * 2, "step": 2}, 2)
    os.unlink(os.path.join(final, "integrity.json"))
    path = os.path.join(final, "state.pkl")
    data = open(path, "rb").read()
    if damage == "cut_buffers":
        data = data[:-100]
    elif damage == "cut_header":
        data = data[:20]
    else:
        data = data[:8] + (1 << 40).to_bytes(8, "little") + data[16:]
    open(path, "wb").write(data)
    with pytest.raises((ValueError, EOFError)):
        read_state(path)
    restored, step = saver.load_latest()
    assert step == 1
    assert any(n.endswith(".quarantined") for n in os.listdir(tmp_path))


# ---------------------------------------------------------------------------
# (f) the shard file is still what numpy reads
# ---------------------------------------------------------------------------


def _entries():
    rng = np.random.default_rng(2)
    return {
        "table|fm_embedding/embedding|0|64": rng.normal(size=(64, 128)).astype(
            np.float32
        ),
        "slot|fm_embedding/embedding|m|0|64": rng.normal(size=(64, 128)).astype(
            np.float32
        ),
        "slot|emb|step|0|3": np.arange(3, dtype=np.int32),
        "strided|0|4": rng.normal(size=(4, 10))[:, ::2],
        "fortran|0|5": np.asfortranarray(rng.normal(size=(5, 7))),
        "empty|0|0": np.zeros((0, 16), np.float32),
        "tablé|0|2": np.ones((2, 2), np.float16),
    }


def test_written_npz_holds_the_members_np_savez_writes(tmp_path):
    entries = _entries()
    mine, theirs = str(tmp_path / "mine.npz"), str(tmp_path / "theirs.npz")
    with ChecksumWriter(mine) as writer:
        copied = write_npz(writer, entries)
    np.savez(theirs, **entries)
    assert copied == entries["strided|0|4"].nbytes
    assert (writer.size, writer.crc32) == (
        os.path.getsize(mine), file_crc32(mine)
    )
    with zipfile.ZipFile(mine) as a, zipfile.ZipFile(theirs) as b:
        assert a.testzip() is None  # every member's own CRC32 holds
        assert a.namelist() == b.namelist()
        for name in b.namelist():
            assert a.read(name) == b.read(name), name
            assert a.getinfo(name).compress_type == zipfile.ZIP_STORED
    with np.load(mine) as shards:  # plain numpy, no pickle
        assert set(shards.files) == set(entries)
        for key, want in entries.items():
            assert _same_bits(shards[key], want)


def test_an_npz_of_nothing_opens(tmp_path):
    path = str(tmp_path / "none.npz")
    with ChecksumWriter(path) as writer:
        assert write_npz(writer, {}) == 0
    with np.load(path) as shards:
        assert shards.files == []
    assert file_crc32(path) == writer.crc32


def test_every_member_carries_zip64_sizes_and_offset_whatever_its_size(
    tmp_path,
):
    """One code path for a 100-byte and a 7 GB shard file: the directory
    gives every member's sizes and offset in the ZIP64 extra field (the
    32-bit fields say "look there"), and `zipfile` reads them from it."""
    path = str(tmp_path / "a.npz")
    with ChecksumWriter(path) as writer:
        write_npz(writer, {"a|0|2": np.ones((2, 2), np.float32),
                           "b|0|1": np.zeros(1, np.int8)})
    with zipfile.ZipFile(path) as z:
        first, second = z.infolist()
    assert first.header_offset == 0 and second.header_offset > 0
    assert first.file_size == first.compress_size == 128 + 16
    for info in (first, second):
        assert struct.unpack("<2H3Q", info.extra) == (
            1, 24, info.file_size, info.compress_size, info.header_offset,
        )
        assert info.flag_bits & 0x08 and info.extract_version == 45


def test_sharded_checkpoint_is_read_the_way_the_reference_reads_it(tmp_path):
    """`perfbench/configs/deepfm_reference.py` opens `shards_p*.npz` with
    numpy and `dense.pkl` with pickle, by hand."""
    spec = importlib.util.spec_from_file_location(
        "deepfm_reference",
        os.path.join(REPO, "perfbench", "configs", "deepfm_reference.py"),
    )
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    mesh = build_mesh(MeshConfig())
    dim, padded = 11, 16
    logical = np.random.default_rng(3).normal(size=(512, padded)).astype(
        np.float32
    )
    packed = logical.reshape(-1, 128)  # 8 rows of 16 lanes to a block
    table = jax.device_put(
        jnp.asarray(packed), NamedSharding(mesh, P(("data", "model")))
    )
    saver = ShardedCheckpointSaver(str(tmp_path))
    dense = {"step": jnp.int32(9), "params": {"dnn": {"kernel": jnp.ones((3, 2))}}}
    final = saver.save(9, dense, {"table|fm_embedding/embedding": table})
    rows = np.array([0, 7, 8, 100, 511, 100])
    got = reference._table_rows(final, "fm_embedding/embedding", dim, rows)
    assert _same_bits(got, logical[rows, :dim])
    with open(os.path.join(final, "dense.pkl"), "rb") as f:
        params = pickle.load(f)["params"]
    assert _same_bits(params["dnn"]["kernel"], np.ones((3, 2), np.float32))
    # and the program's own restore reads the same rows
    reader = saver.row_reader(9, "table|fm_embedding/embedding")
    assert _same_bits(reader.read(0, packed.shape[0]), packed)
    saver.release(9)


# ---------------------------------------------------------------------------
# (g) two processes: a peer's checksum reaches rank 0's manifest
# ---------------------------------------------------------------------------


def _save_as_rank(monkeypatch, tmp_path, rank, table, step=6):
    from jax.experimental import multihost_utils

    monkeypatch.setattr(jax, "process_index", lambda: rank)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(
        multihost_utils, "sync_global_devices", lambda name: None
    )
    saver = ShardedCheckpointSaver(str(tmp_path))
    dense = {"step": jnp.int32(step)} if rank == 0 else None
    with save_span(rank=rank, step=step):
        return saver.save(step, dense, {"table|t": table})


@pytest.mark.parametrize("sidecar", ["present", "missing", "stale"])
def test_peer_ranks_checksum_reaches_rank_zeros_manifest(
    tmp_path, monkeypatch, sidecar
):
    mesh = build_mesh(MeshConfig())
    table = jax.device_put(
        jnp.arange(64 * 16, dtype=jnp.float32).reshape(64, 16),
        NamedSharding(mesh, P(("data", "model"))),
    )
    # Rank 1 writes its file and leaves its checksum beside it ...
    _save_as_rank(monkeypatch, tmp_path, 1, table)
    tmp_dir = tmp_path / "step_000000000006.shared.tmp"
    peer = tmp_dir / "shards_p1of2.npz"
    note = tmp_dir / "shards_p1of2.npz.crc"
    assert json.loads(note.read_text()) == {
        "crc32": file_crc32(str(peer)), "size": os.path.getsize(peer),
    }
    if sidecar == "missing":
        note.unlink()
    elif sidecar == "stale":  # of a file that has since been rewritten
        note.write_text(json.dumps({"crc32": 1, "size": 1}))
    # ... and rank 0, past the barrier, folds it into the manifest.
    rereads = []
    real = file_crc32
    monkeypatch.setattr(
        saver_mod, "file_crc32",
        lambda path, *a, **k: rereads.append(os.path.basename(path))
        or real(path, *a, **k),
    )
    marker = time.time()
    final = _save_as_rank(monkeypatch, tmp_path, 0, table)
    monkeypatch.setattr(saver_mod, "file_crc32", real)
    (crc,) = _spans_since(marker, "checkpoint.save.crc")
    peer = os.path.join(final, "shards_p1of2.npz")
    if sidecar == "present":
        assert rereads == [] and crc["reread_bytes"] == 0
    else:
        assert rereads == ["shards_p1of2.npz"]
        assert crc["reread_bytes"] == os.path.getsize(peer)
    files = json.load(open(os.path.join(final, "integrity.json")))["files"]
    assert files["shards_p1of2.npz"] == {
        "crc32": real(peer), "size": os.path.getsize(peer),
    }
    # The sidecars are not part of the checkpoint.
    assert sorted(os.listdir(final)) == [
        "dense.pkl", "integrity.json", "manifest.json",
        "shards_p0of2.npz", "shards_p1of2.npz",
    ]
    assert saver_mod.verify_integrity(final) is None


# ---------------------------------------------------------------------------
# (h) a save is a stream of leaves (PR 39): the file takes leaf i while
# leaves i+1 ... i+k cross from the device; the bytes are the same bytes
# ---------------------------------------------------------------------------


def _device_tree():
    rng = np.random.default_rng(5)
    # (keys in sorted order, the order `jax.device_get` hands a dict
    # back in: the file of the tree and of its host copy list the
    # leaves alike)
    return {
        "count": jnp.asarray(7, jnp.int32),
        "ids": jnp.arange(11),
        "key": jax.random.key(3),
        "lr": 0.5,
        "params": {
            "b": jnp.asarray(rng.normal(size=(9, 3)), jnp.bfloat16),
            "big": jnp.asarray(rng.normal(size=(64, 48)), jnp.float32),
            "w": jnp.asarray(rng.normal(size=(37, 5)), jnp.float32),
        },
    }


def _assert_same_tree(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if isinstance(b, jax.Array) and jax.dtypes.issubdtype(
            b.dtype, jax.dtypes.prng_key
        ):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        elif not hasattr(b, "dtype"):
            assert a == b
            continue
        assert _same_bits(a, b)


@pytest.fixture
def transposed_transfers(monkeypatch):
    """Every 2-d leaf comes to the host with its axes swapped in memory,
    as a weight that a TPU keeps transposed does."""
    real = saver_mod.LeafStream._fetch

    def fetch(handle):
        host = real(handle)
        return np.asfortranarray(host) if host.ndim == 2 else host

    monkeypatch.setattr(saver_mod.LeafStream, "_fetch", staticmethod(fetch))


@pytest.mark.parametrize("case", [
    "as_it_lies", "transposed_as_the_layout_said", "transposed_unannounced",
    "leaves_larger_than_the_lookahead",
])
def test_streamed_device_tree_restores_bit_for_bit(
    tmp_path, monkeypatch, request, case
):
    tree = _device_tree()
    copied = 0
    if case.startswith("transposed"):
        request.getfixturevalue("transposed_transfers")
        if case == "transposed_as_the_layout_said":
            monkeypatch.setattr(
                saver_mod, "_device_axes",
                lambda a: (1, 0) if a.ndim == 2 else None,
            )
        else:  # the axes were settled before the transfer: one copy each
            copied = sum(
                x.nbytes for x in jax.tree.leaves(tree["params"])
            )
    elif case == "leaves_larger_than_the_lookahead":
        monkeypatch.setattr(saver_mod, "_LOOKAHEAD_BYTES", 64)
    marker = time.time()
    saver = CheckpointSaver(str(tmp_path))
    with save_span(rank=0, step=4):
        final = saver.save(tree, 4)
    restored, step = saver.load_latest()
    assert step == 4
    _assert_same_tree(restored, tree)
    for leaf in jax.tree.leaves(restored["params"]) + [restored["count"]]:
        assert type(leaf) is np.ndarray and leaf.flags.writeable
    (write,) = _spans_since(marker, "checkpoint.save.write")
    (gather,) = _spans_since(marker, "checkpoint.save.gather")
    on_device = [x for x in jax.tree.leaves(tree) if saver_mod._on_device(x)]
    total = sum(x.nbytes for x in on_device)
    assert write["copied_bytes"] == copied
    assert write["leaves"] == len(on_device) == 5
    assert gather["bytes"] == total
    assert write["bytes"] == os.path.getsize(os.path.join(final, "state.pkl"))
    # Every leaf but the first was on its way before the writer asked.
    assert write["streamed_bytes"] == total - tree["count"].nbytes
    if case == "leaves_larger_than_the_lookahead":
        assert write["lookahead_peak_bytes"] < total
    if case == "transposed_as_the_layout_said":
        # (a transposed leaf comes back in the layout it was saved from)
        assert restored["params"]["w"].flags.f_contiguous


def _parent_write_state(path, state):
    """`write_state` as the parent commit (2cbfc0d) had it, for a host
    tree: the bytes an older tree left behind."""

    class Pickler(pickle.Pickler):
        def __init__(self, file):
            super().__init__(file, protocol=5)
            self.buffers = []

        def persistent_id(self, obj):
            if type(obj) is not np.ndarray or obj.dtype.hasobject:
                return None
            view, axes = saver_mod._dense_view(obj)
            self.buffers.append(view.reshape(-1).view(np.uint8))
            return (len(self.buffers) - 1, obj.dtype, view.shape, axes)

    import io

    skeleton = io.BytesIO()
    pickler = Pickler(skeleton)
    pickler.dump(state)
    with open(path, "wb") as f:
        f.write(b"EDLRAW01" + struct.pack(
            f"<{2 + len(pickler.buffers)}Q", skeleton.tell(),
            len(pickler.buffers), *(b.nbytes for b in pickler.buffers),
        ))
        f.write(skeleton.getbuffer())
        for buffer in pickler.buffers:
            f.write(buffer)


@pytest.mark.parametrize("layout", ["EDLRAW01", "plain_pickle"])
def test_read_state_reads_what_the_parents_code_wrote(tmp_path, layout):
    host = jax.device_get(_device_tree())
    host["f"] = _leaf("fortran")
    path = str(tmp_path / "state.pkl")
    if layout == "EDLRAW01":
        _parent_write_state(path, host)
        assert open(path, "rb").read(8) == b"EDLRAW01"
    else:
        with open(path, "wb") as f:
            pickle.dump(host, f)
    _assert_same_tree(read_state(path), host)


def test_the_stream_writes_the_layout_the_parent_reads(tmp_path):
    """No second layout came with the stream: a device tree's file is
    the `EDLRAW01` file of the same tree on the host, to the byte after
    the skeleton (whose pickle memoises an equal shape tuple or not),
    so a tree older than this PR reads it."""
    tree = _device_tree()
    with ChecksumWriter(str(tmp_path / "device")) as writer:
        assert write_state(writer, tree) == 0
    _parent_write_state(str(tmp_path / "host"), jax.device_get(tree))
    device = (tmp_path / "device").read_bytes()
    host = (tmp_path / "host").read_bytes()
    n_buffers = struct.unpack_from("<Q", device, 16)[0]
    assert device[:8] == host[:8] == b"EDLRAW01"
    assert device[16:24 + 8 * n_buffers] == host[16:24 + 8 * n_buffers]
    payload = sum(struct.unpack_from(f"<{n_buffers}Q", device, 24))
    assert device[-payload:] == host[-payload:]
    _assert_same_tree(read_state(str(tmp_path / "device")), tree)


class _CountingStream(saver_mod.LeafStream):
    """A leaf source that counts what is on the host: a handle's bytes
    from its start until the stream lets go of it."""

    outstanding = 0
    peak = 0
    order = []

    class Handle:
        def __init__(self, leaf):
            self.leaf = leaf
            cls = _CountingStream
            cls.outstanding += leaf.nbytes
            cls.peak = max(cls.peak, cls.outstanding)

        def __del__(self):
            _CountingStream.outstanding -= self.leaf.nbytes

    @staticmethod
    def _start(leaf):
        _CountingStream.order.append(("start", id(leaf)))
        return _CountingStream.Handle(leaf)

    @staticmethod
    def _fetch(handle):
        _CountingStream.order.append(("fetch", id(handle.leaf)))
        return np.asarray(handle.leaf)


@pytest.mark.parametrize("sizes,bound", [
    ([100] * 12, 256), ([64, 8, 8, 200, 8, 300, 8, 8], 128),
    ([4096, 4096, 4096, 16], 1024), ([10], 1 << 20),
])
def test_the_host_never_holds_more_than_the_lookahead_and_one_leaf(
    monkeypatch, sizes, bound
):
    monkeypatch.setattr(saver_mod, "_LOOKAHEAD_BYTES", bound)
    _CountingStream.outstanding = _CountingStream.peak = 0
    _CountingStream.order = []
    leaves = [jnp.arange(n, dtype=jnp.uint8) for n in sizes]
    stream = _CountingStream()
    held_by_writer = []
    for leaf, host in zip(leaves, stream.host_arrays(leaves)):
        assert _same_bits(host, leaf)
        # (the writer has the leaf it was handed, and what is ahead)
        held_by_writer.append(_CountingStream.outstanding)
    assert _CountingStream.outstanding == 0  # all let go of
    largest = max(sizes)
    # The leaf being written, and ahead of it the bound or, where one
    # leaf is larger than the bound, that one leaf.
    assert _CountingStream.peak <= largest + max(bound, largest)
    if largest <= bound:
        assert _CountingStream.peak <= bound + largest
    assert stream.lookahead_peak_bytes == _CountingStream.peak
    assert max(held_by_writer) <= _CountingStream.peak
    # Transfers start in the order the file holds the leaves, each
    # before its fetch, and (but for the first) before the fetch of the
    # leaf ahead of it: the write of leaf i runs beside i+1's transfer.
    starts = [i for kind, i in _CountingStream.order if kind == "start"]
    assert starts == [id(leaf) for leaf in leaves]
    for n, leaf in enumerate(leaves[1:]):
        assert _CountingStream.order.index(("start", id(leaf))) < (
            _CountingStream.order.index(("fetch", id(leaves[n])))
        )
    assert stream.leaves == len(sizes) and stream.bytes == sum(sizes)
    assert stream.streamed_bytes == sum(sizes[1:])


def test_a_host_leaf_among_device_leaves_keeps_its_place():
    host = np.arange(5)
    leaves = [jnp.ones(3), host, "text", jnp.zeros((2, 2))]
    stream = saver_mod.LeafStream()
    got = list(stream.host_arrays(leaves))
    assert got[1] is host and got[2] == "text"
    assert _same_bits(got[0], leaves[0]) and _same_bits(got[3], leaves[3])
    assert stream.leaves == 2 and stream.streamed_bytes == leaves[3].nbytes


@pytest.mark.parametrize("kind", ["full", "sharded"])
def test_a_transfer_that_fails_mid_stream_commits_nothing(
    tmp_path, monkeypatch, kind
):
    real = saver_mod.LeafStream._fetch
    fetched = []

    def fetch(handle):
        fetched.append(handle)
        if len(fetched) == 3:
            raise RuntimeError("the device went away")
        return real(handle)

    if kind == "full":
        saver = CheckpointSaver(str(tmp_path), keep_max=5)
        saver.save(_device_tree(), 1)
        monkeypatch.setattr(saver_mod.LeafStream, "_fetch", staticmethod(fetch))
        with pytest.raises(RuntimeError, match="went away"):
            saver.save(_device_tree(), 2)
    else:
        saver, _final, table = _sharded_save(tmp_path, step=1)
        dense = {"step": jnp.int32(2), "a": jnp.ones(3), "b": jnp.ones(4)}
        monkeypatch.setattr(saver_mod.LeafStream, "_fetch", staticmethod(fetch))
        with pytest.raises(RuntimeError, match="went away"):
            saver.save(2, dense, {"table|t": table})
    monkeypatch.setattr(saver_mod.LeafStream, "_fetch", staticmethod(real))
    # The previous checkpoint is still the newest committed one ...
    assert saver.steps() == [1]
    if kind == "full":
        restored, step = saver.load_latest()
        assert step == 1
        _assert_same_tree(restored, _device_tree())
    else:
        assert saver.latest_step() == 1
    # ... and what the failed save left is a tmp dir, which the next
    # saver sweeps once it is stale.
    (tmp,) = [n for n in os.listdir(tmp_path) if ".tmp" in n]
    old = time.time() - saver_mod.STALE_TMP_GRACE_S - 10
    os.utime(tmp_path / tmp, (old, old))
    type(saver)(str(tmp_path))
    assert not [n for n in os.listdir(tmp_path) if ".tmp" in n]
    assert saver.steps() == [1]


def test_a_streamed_file_torn_after_its_checksum_falls_back(tmp_path):
    """The manifest's CRC32 and size are those of the bytes the stream
    handed to the file, whatever became of the file afterwards."""
    saver = CheckpointSaver(str(tmp_path), keep_max=5)
    saver.save(_device_tree(), 1)
    tree = jax.tree.map(
        lambda x: x + 1 if saver_mod._on_device(x) else x, _device_tree()
    )
    faults.install("ckpt.write:truncate@1")  # tears after the checksum
    try:
        final = saver.save(tree, 2)
    finally:
        faults.clear()
    with ChecksumWriter(str(tmp_path / "whole")) as writer:
        write_state(writer, tree)
    files = json.load(open(os.path.join(final, "integrity.json")))["files"]
    assert files["state.pkl"] == {
        "crc32": file_crc32(str(tmp_path / "whole")),
        "size": os.path.getsize(tmp_path / "whole"),
    }
    assert os.path.getsize(os.path.join(final, "state.pkl")) < writer.size
    restored, step = saver.load_latest()
    assert step == 1
    _assert_same_tree(restored, _device_tree())
    assert "step_000000000002.quarantined" in os.listdir(tmp_path)


def test_a_host_tree_is_saved_as_before(tmp_path):
    """`save(host_state)`: no device to wait for, so no gather span of
    the saver's; the file is the one the parent wrote."""
    host = jax.device_get(_device_tree())
    marker = time.time()
    with save_span(rank=0, step=3):
        final = CheckpointSaver(str(tmp_path)).save(host, 3)
    assert not _spans_since(marker, "checkpoint.save.gather")
    (write,) = _spans_since(marker, "checkpoint.save.write")
    assert write["leaves"] == write["streamed_bytes"] == 0
    assert write["lookahead_peak_bytes"] == write["copied_bytes"] == 0
    _parent_write_state(str(tmp_path / "parent"), host)
    assert open(os.path.join(final, "state.pkl"), "rb").read() == (
        tmp_path / "parent"
    ).read_bytes()
    assert not saver_mod.streams(host)


def _worker_with(saver):
    from test_span_vocabulary import _collective_worker

    return _collective_worker(saver)


def test_state_to_host_gathers_as_before():
    worker = _worker_with(None)
    trainer = worker._trainer
    trainer.train_step(np.ones((4, 2), np.float32), np.ones(4, np.float32))
    assert saver_mod.streams(trainer.state)
    marker = time.time()
    host = trainer.state_to_host()
    (gather,) = _spans_since(marker, "checkpoint.save.gather")
    assert gather["bytes"] == saver_mod.tree_nbytes(trainer.state)
    assert all(
        type(x) is np.ndarray for x in jax.tree.leaves(host)
    ) and not saver_mod.streams(host)
    _assert_same_tree(host, trainer.state)


def test_the_worker_hands_the_saver_the_state_as_it_lies_on_the_device(
    tmp_path, monkeypatch
):
    """... and the save stays synchronous: `checkpoint_saved` is
    journaled inside `checkpoint.save`, whose `.write` child says how
    much of the state was streamed."""
    from elasticdl_tpu.proto import elasticdl_pb2 as pb

    saver = CheckpointSaver(str(tmp_path))
    worker = _worker_with(saver)
    monkeypatch.setattr(
        type(worker._trainer), "state_to_host",
        lambda self: pytest.fail("a state that streams needs no gather"),
    )
    marker = time.time()
    worker._process_train_task(
        pb.Task(task_id=1, type=pb.TRAINING, shard_name="s", start=0, end=8)
    )
    saves = _spans_since(marker, "checkpoint.save")
    writes = _spans_since(marker, "checkpoint.save.write")
    gathers = _spans_since(marker, "checkpoint.save.gather")
    assert saves and len(saves) == len(writes) == len(gathers)
    committed = [
        e for e in obs.journal().tail(2000)
        if e.get("event") == "checkpoint_saved" and e["ts"] >= marker
    ]
    assert len(committed) == len(saves)
    state_bytes = saver_mod.tree_nbytes(worker._trainer.state)
    for save, write, gather, event in zip(saves, writes, gathers, committed):
        lo, hi = save["start_ts"], save["start_ts"] + save["duration_s"]
        assert lo <= event["ts"] <= hi + 1e-3
        for child in (write, gather):
            assert child["parent_span_id"] == save["span_id"]
            assert lo <= child["start_ts"] <= hi
        assert write["leaves"] == len(jax.tree.leaves(worker._trainer.state))
        assert gather["bytes"] == state_bytes
        assert 0 < write["lookahead_peak_bytes"] <= state_bytes
        assert 0 < write["streamed_bytes"] < state_bytes
        assert gather["duration_s"] + write["duration_s"] <= (
            save["duration_s"] + 1e-3
        )
    restored, step = saver.load_latest()
    assert step == worker._trainer.step
    _assert_same_tree(restored, worker._trainer.state)


def test_a_state_sharded_over_devices_takes_the_host_path():
    mesh = build_mesh(MeshConfig())
    if mesh.devices.size < 2:
        pytest.skip("one device: nothing is sharded")
    table = jax.device_put(
        jnp.zeros((64, 4)), NamedSharding(mesh, P(("data", "model")))
    )
    assert not saver_mod.streams({"t": table, "w": jnp.ones(3)})
    assert saver_mod.streams({"w": jnp.ones(3), "n": 2})
    assert not saver_mod.streams({"n": 2})


# ---------------------------------------------------------------------------
# Leaves cut into pieces on the device (PR 50)
# ---------------------------------------------------------------------------


def _cuttable_tree():
    """Leaves under a piece of 1 KiB, exactly one, one and a remainder,
    many (along a second axis too: a row of `deep` is 2112 bytes),
    0-d, bfloat16, and what is no plain array."""
    rng = np.random.default_rng(11)

    def normal(*shape, dtype=jnp.float32):
        return jnp.asarray(rng.normal(size=shape), dtype)

    return {
        "deep": normal(7, 33, 16),
        "exact": normal(32, 8),
        "half": normal(100, 24, dtype=jnp.bfloat16),
        "key": jax.random.key(3),
        "lr": 0.5,
        "many": normal(640, 8),
        "remainder": normal(40, 8),
        "under": normal(10, 8),
        "zero": jnp.float32(3.0),
    }


def _integrity(step_dir):
    with open(os.path.join(step_dir, "integrity.json")) as f:
        return json.load(f)["files"]


@pytest.mark.parametrize("piece_bytes", [256, 1024, 4096, 1 << 20])
def test_a_state_cut_into_pieces_saves_the_bytes_the_whole_leaves_do(
    tmp_path, piece_bytes
):
    tree = _cuttable_tree()
    whole = CheckpointSaver(str(tmp_path / "whole")).save(tree, 3)
    cutter = saver_mod.LeafCutter(piece_bytes=piece_bytes)
    large = [
        x for x in jax.tree.leaves(tree)
        if saver_mod._on_device(x) and x.nbytes > piece_bytes
    ]
    # One program a (shape, dtype); warming again builds nothing.
    assert cutter.warm(jax.tree.leaves(tree)) == len(large)
    assert cutter.warm(jax.tree.leaves(tree)) == 0
    marker = time.time()
    saver = CheckpointSaver(str(tmp_path / "cut"))
    with save_span(rank=0, step=3):
        cut = saver.save(tree, 3, cutter=cutter)
    assert _integrity(cut) == _integrity(whole)
    restored, step = saver.load_latest()
    assert step == 3
    _assert_same_tree(restored, tree)
    (write,) = _spans_since(marker, "checkpoint.save.write")
    pieces = sum(
        sum(1 for _ in saver_mod._Cut.of(
            x.shape, x.dtype, None, piece_bytes
        ).pieces())
        for x in large
    )
    on_device = [x for x in jax.tree.leaves(tree) if saver_mod._on_device(x)]
    assert write["pieces"] == pieces + len(on_device) - len(large)
    assert write["leaves"] == len(on_device)
    assert write["copied_bytes"] == 0
    assert 0 <= write["recycled_bytes"] <= write["bytes"]


@pytest.mark.parametrize("shape,dtype,axes,piece_bytes,axis,rows,count", [
    ((32, 8), np.float32, None, 1024, 0, 32, 1),
    ((40, 8), np.float32, None, 1024, 0, 32, 2),
    ((640, 8), np.float32, None, 1024, 0, 32, 20),
    ((7, 33, 16), np.float32, None, 1024, 1, 16, 7 * 3),
    ((100, 24), jnp.bfloat16, None, 1024, 0, 21, 5),
    ((24, 100), np.float32, (1, 0), 1024, 0, 10, 10),
    ((3, 5, 64, 4), np.float32, (1, 2, 3, 0), 512, 1, 10, 5 * 7),
    ((12544, 2048), np.float32, None, 16 << 20, 0, 2048, 7),
    ((32, 2048, 512), np.float32, None, 16 << 20, 0, 4, 8),
])
def test_a_leafs_pieces_are_its_bytes_in_the_files_order(
    shape, dtype, axes, piece_bytes, axis, rows, count
):
    cut = saver_mod._Cut.of(shape, dtype, axes, piece_bytes)
    assert (cut.axis, cut.rows) == (axis, rows)
    itemsize = np.dtype(dtype).itemsize
    assert int(np.prod(cut.sizes)) * itemsize <= piece_bytes
    pieces = list(cut.pieces())
    assert len(pieces) == count
    if int(np.prod(shape)) > 1 << 20:
        return  # (the published shapes: the arithmetic alone)
    leaf = np.arange(int(np.prod(shape)), dtype=np.int64).reshape(shape)
    stored = leaf if axes is None else leaf.transpose(axes)
    runs = []
    for starts, skip in pieces:  # what the program and the stream do
        piece = stored[tuple(
            slice(int(at), int(at) + size)
            for at, size in zip(starts, cut.sizes)
        )]
        assert piece.shape == cut.sizes
        runs.append(piece.reshape(cut.rows, -1)[skip:].reshape(-1))
    assert np.array_equal(np.concatenate(runs), stored.reshape(-1))


def test_a_leaf_the_device_keeps_transposed_is_cut_along_its_major_axis(
    tmp_path, monkeypatch, transposed_transfers
):
    monkeypatch.setattr(
        saver_mod, "_device_axes", lambda a: (1, 0) if a.ndim == 2 else None
    )
    tree = _cuttable_tree()
    whole = CheckpointSaver(str(tmp_path / "whole")).save(tree, 1)
    cutter = saver_mod.LeafCutter(piece_bytes=1024)
    cutter.warm(jax.tree.leaves(tree))
    saver = CheckpointSaver(str(tmp_path / "cut"))
    cut = saver.save(tree, 1, cutter=cutter)
    assert _integrity(cut) == _integrity(whole)
    restored, _step = saver.load_latest()
    _assert_same_tree(restored, tree)
    assert restored["many"].flags.f_contiguous


def test_a_leaf_of_several_lookaheads_is_never_on_the_host_whole(
    monkeypatch,
):
    lookahead, piece_bytes = 8192, 1024
    monkeypatch.setattr(saver_mod, "_LOOKAHEAD_BYTES", lookahead)
    leaves = [
        jnp.arange(5 * lookahead, dtype=jnp.uint8).reshape(-1, 64),
        jnp.arange(100, dtype=jnp.uint8),
        jnp.arange(3 * lookahead + 320, dtype=jnp.uint8).reshape(-1, 64),
    ]
    cutter = saver_mod.LeafCutter(piece_bytes=piece_bytes)
    assert cutter.warm(leaves) == 2
    stream = saver_mod.LeafStream(cutter)
    got = []
    for host in stream.host_arrays(leaves):
        if isinstance(host, saver_mod.LeafPieces):
            runs = list(host)
            assert all(run.nbytes <= piece_bytes for run in runs)
            host = np.concatenate(runs).reshape(host.shape)
        got.append(host)
    for host, leaf in zip(got, leaves):
        assert _same_bits(host, leaf)
    assert stream.lookahead_peak_bytes <= lookahead + piece_bytes
    assert stream.leaves == 3
    assert stream.bytes == sum(x.nbytes for x in leaves)
    assert stream.pieces == 40 + 1 + 25
    # All but the first piece was on its way before the writer asked; the
    # rows a last piece shares with the one before it count once.
    assert stream.streamed_bytes == stream.bytes - piece_bytes
    # Without the programs the first leaf alone is five look-aheads.
    whole = saver_mod.LeafStream()
    list(whole.host_arrays(leaves))
    assert whole.lookahead_peak_bytes >= leaves[0].nbytes
    assert whole.pieces == 3


def test_pieces_in_flight_are_bounded_in_device_bytes_too(monkeypatch):
    monkeypatch.setattr(saver_mod, "_PIECES_AHEAD_BYTES", 4 * 1024)
    leaf = jnp.arange(64 * 1024, dtype=jnp.uint8).reshape(-1, 64)
    cutter = saver_mod.LeafCutter(piece_bytes=1024)
    cutter.warm([leaf])
    in_flight, most = [], [0]

    class Counting(saver_mod.LeafStream):
        def _start_piece(self, leaf, program, starts):
            in_flight.append(starts)
            most[0] = max(most[0], len(in_flight))
            return super()._start_piece(leaf, program, starts)

        @staticmethod
        def _fetch(handle):
            in_flight.pop(0)
            return saver_mod.LeafStream._fetch(handle)

    stream = Counting(cutter)
    pieces = next(stream.host_arrays([leaf]))
    assert _same_bits(np.concatenate(list(pieces)).reshape(leaf.shape), leaf)
    # Four pieces ahead and the one the writer has asked for.
    assert most[0] == 5
    assert stream.lookahead_peak_bytes == 5 * 1024


def test_pieces_not_walked_to_their_end_fail_the_stream():
    leaf = jnp.arange(4096, dtype=jnp.uint8).reshape(-1, 64)
    cutter = saver_mod.LeafCutter(piece_bytes=1024)
    cutter.warm([leaf])
    arrays = saver_mod.LeafStream(cutter).host_arrays([leaf, jnp.ones(3)])
    next(iter(next(arrays)))
    with pytest.raises(RuntimeError, match="not taken to their end"):
        next(arrays)


def test_a_leaf_no_program_was_warmed_for_crosses_whole_and_compiles_nothing(
    tmp_path,
):
    warmed = {"a": jnp.ones((64, 16)), "n": 1}
    cutter = saver_mod.LeafCutter(piece_bytes=1024)
    assert cutter.warm(jax.tree.leaves(warmed)) == 1
    tree = dict(warmed, b=jnp.ones((48, 16)), c=jnp.ones((64, 16), jnp.int32))
    (program,) = [p for _cut, p in cutter._programs.values()]
    compiled = program._cache_size()
    marker = time.time()
    saver = CheckpointSaver(str(tmp_path))
    with save_span(rank=0, step=1):
        saver.save(tree, 1, cutter=cutter)
    (write,) = _spans_since(marker, "checkpoint.save.write")
    assert write["pieces"] == 4 + 2  # `a` cut, `b` and `c` whole
    assert program._cache_size() == compiled
    restored, _step = saver.load_latest()
    _assert_same_tree(restored, tree)


def test_a_second_save_through_the_plans_programs_compiles_nothing(tmp_path):
    from elasticdl_tpu.parallel import compile as pc

    tree = _cuttable_tree()
    plan = pc.CompilePlan(
        build_mesh(MeshConfig(), devices=jax.devices()[:1]), trainer="test"
    )
    marker = time.time()
    cutter = pc.leaf_cutter(plan, jax.tree.leaves(tree), piece_bytes=1024)
    builds = _spans_since(marker, "compile.build")
    # One build a (shape, dtype) over a piece, each a named entrypoint.
    assert len(builds) == 4
    assert all(b["entrypoint"].startswith("ckpt_piece.") for b in builds)
    saver = CheckpointSaver(str(tmp_path))
    saver.save(tree, 1, cutter=cutter)
    sizes = [p._cache_size() for _cut, p in cutter._programs.values()]
    marker = time.time()
    with save_span(rank=0, step=2):
        saver.save(tree, 2, cutter=cutter)
    assert not _spans_since(marker, "compile.build")
    assert [
        p._cache_size() for _cut, p in cutter._programs.values()
    ] == sizes
    (write,) = _spans_since(marker, "checkpoint.save.write")
    assert write["pieces"] > write["leaves"]
    restored, step = saver.load_latest()
    assert step == 2
    _assert_same_tree(restored, tree)


@pytest.mark.parametrize("kind", ["full", "sharded"])
def test_a_failure_on_the_helper_thread_fails_the_save(
    tmp_path, monkeypatch, kind
):
    rows = (2 << 20) // 64  # pieces of 1 MiB: folded on the helper thread
    leaf = jnp.zeros((rows, 16), jnp.float32)
    cutter = saver_mod.LeafCutter(piece_bytes=1 << 20)
    cutter.warm([leaf])

    def fold(self, piece):
        raise RuntimeError("the helper thread failed")

    monkeypatch.setattr(ChecksumWriter, "_fold", fold)
    with pytest.raises(RuntimeError, match="helper thread failed"):
        if kind == "full":
            saver = CheckpointSaver(str(tmp_path))
            saver.save({"w": leaf}, 1, cutter=cutter)
        else:
            saver = ShardedCheckpointSaver(str(tmp_path))
            saver.save(1, {"step": 1}, {"table|t": leaf}, cutter=cutter)
    monkeypatch.undo()
    assert saver.steps() == []
    assert not [
        name for name in os.listdir(tmp_path)
        if name.startswith("step_") and ".tmp" not in name
    ]


def test_sharded_rows_cut_into_pieces_are_the_same_files(tmp_path):
    mesh = build_mesh(MeshConfig())
    table = jax.device_put(
        jnp.arange(256 * 16, dtype=jnp.float32).reshape(256, 16),
        NamedSharding(mesh, P(("data", "model"))),
    )
    # (a dense leaf of a shard's own shape: it is pickled whole)
    rows = table.addressable_shards[0].data.shape[0]
    dense = {"step": jnp.int32(5), "like_a_shard": jnp.ones((rows, 16))}
    from elasticdl_tpu.checkpoint.sharded import own_shards

    whole = ShardedCheckpointSaver(str(tmp_path / "whole")).save(
        5, dense, {"table|t": table}
    )
    cutter = saver_mod.LeafCutter(piece_bytes=256)
    assert cutter.warm(own_shards({"table|t": table})[1]) == 1
    marker = time.time()
    saver = ShardedCheckpointSaver(str(tmp_path / "cut"))
    with save_span(rank=0, step=5):
        cut = saver.save(5, dense, {"table|t": table}, cutter=cutter)
    assert _integrity(cut) == _integrity(whole)
    (write,) = _spans_since(marker, "checkpoint.save.write")
    shards = len(own_shards({"table|t": table})[1])
    assert write["pieces"] == shards * (rows * 64 // 256) + 2
    restored = saver.load_array(5, "table|t", table.sharding)
    saver.release(5)
    assert _same_bits(restored, table)
    assert _same_bits(
        saver.load_dense(5)["like_a_shard"], np.ones((rows, 16), np.float32)
    )


@pytest.mark.parametrize("spans,new,had", [
    ([], (0, 10), 0),
    ([(0, 10)], (0, 10), 10),
    ([(0, 10)], (10, 20), 0),
    ([(0, 10)], (5, 15), 5),
    ([(0, 10), (20, 30)], (5, 25), 10),
    ([(0, 10), (20, 30)], (12, 18), 0),
    ([(0, 10), (10, 20)], (0, 20), 20),
])
def test_address_ranges_count_the_bytes_they_already_had(spans, new, had):
    ranges = saver_mod._Ranges()
    for lo, hi in spans:
        ranges.add(lo, hi)
    assert ranges.add(*new) == had
    assert ranges.add(*new) == new[1] - new[0]  # all of it, by now


def test_bytes_written_from_memory_written_from_before_are_recycled(tmp_path):
    first = np.ones(3 << 20, np.uint8)
    other = np.ones(2 << 20, np.uint8)
    with ChecksumWriter(str(tmp_path / "f")) as writer:
        writer.write(first)
        assert writer.recycled_bytes == 0
        writer.write(other)
        assert writer.recycled_bytes == 0
        writer.write(first[1 << 20:])
        assert writer.recycled_bytes == 2 << 20
        writer.write(b"small writes are not followed")
        assert writer.recycled_bytes == 2 << 20
    assert writer.size == (7 << 20) + 29
