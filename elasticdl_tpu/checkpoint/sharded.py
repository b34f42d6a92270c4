"""Per-process sharded checkpointing for mesh-sharded state.

Parity: the reference's Go parameter servers each snapshot their own
partition of the embedding tables (pkg/ps/checkpoint.go); no single host
ever holds the full model.  Here the "PS partitions" are the vocab-sharded
table rows living in each process's local devices, so the same property
is kept by having every process write only its addressable shard rows —
the collective `state_to_host` full-gather (which OOMs by construction at
Criteo scale) never runs.

Layout of one checkpoint (directory per step, committed atomically by a
rank-0 rename after a cross-process barrier):

    step_000000000042/
      manifest.json        - step, process count, array shapes/dtypes, and
                             the EXACT shard-file inventory (restores read
                             only inventoried files: a file left behind in
                             the tmp dir by a world that died mid-save can
                             never leak stale rows into a later commit)
      dense.pkl            - replicated state (dense params, opt state,
                             batch stats, step counter); rank 0 writes it
      shards_p0of2.npz     - process 0's rows: entries named
                             "<array>|<row_lo>|<row_hi>" (a zip of stored
                             `.npy` members, as `np.savez` writes and
                             `np.load` reads; `write_npz` hands each
                             array's bytes to the file from its own
                             memory)
      shards_p1of2.npz     - process 1's rows

A save is a stream (`saver.LeafStream`): each rank's shards, then rank
0's dense leaves, cross from the device up to a bounded look-ahead
before the file takes them, so no rank's host holds more than a few
shards at once; the files are byte for byte what gather-then-write made.
Every file goes out through `saver.ChecksumWriter`, so the CRC32 and size
`integrity.json` records come from the bytes in flight; a rank other
than 0 leaves its file's pair in a sidecar (`<file>.crc`) in the shared
tmp dir before the barrier, which rank 0 folds into the manifest and
removes before the rename.

Restore is world-size agnostic: a re-formed world of ANY process/device
count reads the row intervals its new sharding assigns it, reassembled
from whichever inventoried files cover them.  This is what makes
checkpoints the backbone of elastic re-formation — shrink and grow both
restore from the same files.  Requires checkpoint_dir on storage every
process shares, same as elasticity itself.
"""

from __future__ import annotations

import io
import json
import os
import pickle
import struct
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from elasticdl_tpu import obs
from elasticdl_tpu.checkpoint.saver import (
    CheckpointSaver,
    ChecksumWriter,
    LeafCutter,
    LeafPieces,
    LeafStream,
    _apply_write_fault,
    _ckpt_metrics,
    _device_axes,
    tree_nbytes,
    verify_integrity,
    write_integrity_manifest,
)
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.obs import tracing

logger = get_logger("checkpoint.sharded")

_MANIFEST = "manifest.json"
_DENSE = "dense.pkl"
_SIDECAR_SUFFIX = ".crc"

# The zip container of an `.npz`, written member by member to a file
# that is never sought: every member is stored (method 0) with bit 3 set
# (its CRC32 and sizes follow its data in a descriptor) and carries
# ZIP64 sizes and offsets whatever its size, so one code path serves a
# 100-byte and a 100 GB shard file.  This is what `zipfile` itself
# writes to an unseekable stream with `force_zip64`.
_ZIP_VERSION = 45  # 4.5: ZIP64
_ZIP_DESCRIPTOR_FLAG = 0x08
_ZIP_UTF8_FLAG = 0x800
_ZIP_DOS_DATE = (1 << 5) | 1  # 1980-01-01: the files carry no clock
_ZIP_LOCAL = struct.Struct("<4s5H3L2H")
_ZIP_DESCRIPTOR = struct.Struct("<4sL2Q")
_ZIP_CENTRAL = struct.Struct("<4s6H3L5H2L")
_ZIP_END64 = struct.Struct("<4sQ2H2L4Q")
_ZIP_END64_LOCATOR = struct.Struct("<4sLQL")
_ZIP_END = struct.Struct("<4s4H2LH")
_ZIP_MAX32 = 0xFFFFFFFF


def write_npz(writer: ChecksumWriter, entries) -> int:
    """Write what `np.savez(file, **entries)` would, through `writer`:
    each member is the array's `.npy` header and then its bytes, handed
    over from the array's own memory, with the member's CRC32 and the
    file's taken in the same one pass.  `entries` is a dict or any
    iterable of (key, array) pairs, asked for one pair at a time (a
    save hands over pairs whose arrays are still crossing from the
    device).  -> `copied_bytes` (arrays that were neither C- nor
    Fortran-contiguous and had to be copied once)."""
    copied = 0
    directory = []
    pairs = entries.items() if isinstance(entries, dict) else entries
    for key, array in pairs:
        if array.dtype.hasobject:
            raise ValueError(f"{key}: object arrays are not checkpointed")
        cut = isinstance(array, LeafPieces)  # (rows in their own order)
        if not (cut or array.flags.c_contiguous or array.flags.f_contiguous):
            array = np.ascontiguousarray(array)
            copied += array.nbytes
        name = (key + ".npy").encode("utf-8")
        flags = _ZIP_DESCRIPTOR_FLAG | (
            0 if name.isascii() else _ZIP_UTF8_FLAG
        )
        npy_header = io.BytesIO()
        np.lib.format.write_array_header_1_0(npy_header, {
            "shape": array.shape, "fortran_order": False,
            "descr": np.lib.format.dtype_to_descr(array.dtype),
        } if cut else np.lib.format.header_data_from_array_1_0(array))
        offset = writer.size
        writer.write(_ZIP_LOCAL.pack(
            b"PK\x03\x04", _ZIP_VERSION, flags, 0, 0, _ZIP_DOS_DATE,
            0, _ZIP_MAX32, _ZIP_MAX32, len(name), 20,
        ) + name + struct.pack("<2H2Q", 1, 16, 0, 0))
        writer.begin_member()
        writer.write(npy_header.getbuffer())
        # (the header says `fortran_order` for a Fortran-ordered array,
        # whose bytes are its transpose's)
        if cut:
            for stored in array:
                writer.write(stored)
        else:
            stored = array if array.flags.c_contiguous else array.T
            writer.write(stored.reshape(-1).view(np.uint8))
        size = npy_header.tell() + array.nbytes
        crc = writer.end_member()
        writer.write(_ZIP_DESCRIPTOR.pack(b"PK\x07\x08", crc, size, size))
        directory.append((name, flags, crc, size, offset))
        array = stored = None  # let go of before asking for the next
    directory_offset = writer.size
    for name, flags, crc, size, offset in directory:
        writer.write(_ZIP_CENTRAL.pack(
            b"PK\x01\x02", _ZIP_VERSION, _ZIP_VERSION, flags, 0, 0,
            _ZIP_DOS_DATE, crc, _ZIP_MAX32, _ZIP_MAX32, len(name), 28,
            0, 0, 0, 0, _ZIP_MAX32,
        ) + name + struct.pack("<2H3Q", 1, 24, size, size, offset))
    directory_bytes = writer.size - directory_offset
    end = _ZIP_END.pack(
        b"PK\x05\x06", 0, 0, min(len(directory), 0xFFFF),
        min(len(directory), 0xFFFF), min(directory_bytes, _ZIP_MAX32),
        min(directory_offset, _ZIP_MAX32), 0,
    )
    if directory:
        # (`np.load` knows an EMPTY archive by its plain end record.)
        end = _ZIP_END64.pack(
            b"PK\x06\x06", _ZIP_END64.size - 12, _ZIP_VERSION,
            _ZIP_VERSION, 0, 0, len(directory), len(directory),
            directory_bytes, directory_offset,
        ) + _ZIP_END64_LOCATOR.pack(b"PK\x06\x07", 0, writer.size, 1) + end
    writer.write(end)
    return copied


def _take_sidecar(tmp_dir: str, name: str) -> Optional[Tuple[int, int]]:
    """The (crc32, size) a peer rank left for its file `name`, if it
    left one that fits the file; the sidecar itself is removed (it is
    not part of the checkpoint)."""
    path = os.path.join(tmp_dir, name + _SIDECAR_SUFFIX)
    try:
        with open(path) as f:
            noted = json.load(f)
        os.unlink(path)
        pair = int(noted["crc32"]), int(noted["size"])
    except (OSError, ValueError, KeyError, TypeError):
        return None
    if pair[1] != os.path.getsize(os.path.join(tmp_dir, name)):
        return None
    return pair


def _interval(shard, dim0: int) -> Tuple[int, int]:
    index = shard.index[0] if shard.index else slice(None)
    lo = index.start if index.start is not None else 0
    hi = index.stop if index.stop is not None else dim0
    return int(lo), int(hi)


def own_shards(
    sharded: Dict[str, jax.Array]
) -> Tuple[List[str], List[jax.Array]]:
    """-> (the members' names, the rows still on the device) of what
    this process writes of `sharded`, in the order its file holds them:
    each interval of rows once, and rows that every process holds on
    rank 0 alone."""
    process = jax.process_index()
    keys, on_device = [], []
    for name, array in sharded.items():
        dim0 = array.shape[0]
        seen: set = set()
        for shard in array.addressable_shards:
            lo, hi = _interval(shard, dim0)
            if (lo, hi) in seen:
                continue  # replicas of these rows on other devices
            seen.add((lo, hi))
            if (lo, hi) == (0, dim0) and process != 0:
                continue  # fully replicated: rank 0 writes it
            keys.append(f"{name}|{lo}|{hi}")
            on_device.append(shard.data)
    return keys, on_device


class ShardedCheckpointSaver(CheckpointSaver):
    """Collective sharded save / world-size-agnostic restore.

    Shares CheckpointSaver's directory layout and GC; a step only counts
    as committed once its manifest exists (the rank-0 rename writes it
    last).  All save coordination assumes every process calls `save` with
    the same (step, array names); the internal barrier keeps the rank-0
    commit from racing slower writers.
    """

    def __init__(self, checkpoint_dir: str, keep_max: int = 3):
        super().__init__(checkpoint_dir, keep_max=keep_max)
        # step -> {array name -> [(lo, hi, npz, entry key)]}; one scan of
        # the inventoried files serves every load_array of that step.
        self._index_cache: Dict[int, Dict[str, List]] = {}

    def _is_committed(self, step_dir: str) -> bool:
        return os.path.exists(os.path.join(step_dir, _MANIFEST))

    def latest_step(self) -> Optional[int]:
        """Newest step that passes its CRC32 integrity inventory.  A torn
        snapshot (crashed writer, truncated shard file) is quarantined and
        the previous step wins — restores never touch corrupt state.
        Transient I/O errors skip the step without quarantining it.

        Only rank 0 pays the full CRC pass; other ranks check
        existence+size (metadata-only), so re-formation cost does not
        scale with process count.  In the rare case rank 0 quarantines a
        bit-rotted snapshot that size-checks clean elsewhere, the ranks
        pick different steps, the restore-consistency broadcast
        (collective_worker._verify_restore_consistency) aborts the world,
        and the re-formed world agrees on the already-quarantined view."""
        check_crc = jax.process_index() == 0
        for step in reversed(self.steps()):
            step_dir = self._step_dir(step)
            try:
                reason = verify_integrity(step_dir, check_crc=check_crc)
            except OSError:
                logger.exception(
                    "Could not verify checkpoint %s (transient I/O "
                    "error?); skipping it this restore", step_dir,
                )
                continue
            if reason is None:
                return step
            self._quarantine(step_dir, reason)
        return None

    # -- save (collective) ----------------------------------------------

    def save(
        self,
        step: int,
        dense_state: Any,
        sharded: Dict[str, jax.Array],
        cutter: Optional[LeafCutter] = None,
    ) -> str:
        """Every process calls this with the same arguments; each writes
        only its own addressable rows of each `sharded` array.  Replicated
        arrays (tables too small to split) are written by rank 0 alone.
        `dense_state` may be None on ranks != 0 (only rank 0 writes it).
        Shards that `cutter` cuts cross and are written piece by piece."""
        start = time.monotonic()
        process = jax.process_index()
        n_processes = jax.process_count()
        final_dir = self._step_dir(step)
        tmp_dir = final_dir + ".shared.tmp"
        if os.path.exists(final_dir):
            return final_dir
        os.makedirs(tmp_dir, exist_ok=True)

        # This rank's rows, then (rank 0) the dense leaves, in the order
        # the files hold them and still on the device: `LeafStream`
        # brings each over while the file takes the one before it.
        keys, on_device = own_shards(sharded)
        dense_leaves, dense_tree = jax.tree.flatten(
            dense_state if process == 0 else None
        )
        shard_files = [
            f"shards_p{i}of{n_processes}.npz" for i in range(n_processes)
        ]
        # {file: (crc32, size)} as this rank's writers took them.
        known: Dict[str, Tuple[int, int]] = {}
        stream = LeafStream(cutter)
        # Whole: the dense leaves (their pickle needs them so), and a
        # shard the device keeps in another order than its rows (a
        # `.npy` member says C or Fortran order, no third).
        leaves = on_device + dense_leaves
        arrays = stream.host_arrays(leaves, whole=[
            at for at, leaf in enumerate(leaves)
            if at >= len(on_device) or _device_axes(leaf) is not None
        ])
        mine = shard_files[process]
        with ChecksumWriter(os.path.join(tmp_dir, mine)) as writer:
            copied = write_npz(
                writer, ((key, next(arrays)) for key in keys)
            )
        known[mine] = (writer.crc32, writer.size)
        recycled = writer.recycled_bytes
        # Keep the shared tmp dir's mtime fresh while the save is
        # live so a restarting peer's stale-tmp sweep
        # (saver.sweep_stale_tmp) never mistakes an in-flight save
        # for crashed-save garbage.
        os.utime(tmp_dir)
        if process == 0:
            # A plain pickle stream (readers outside this package
            # read it with `pickle.load`): it needs its tree whole on
            # the host, and its arrays pass through pickle's own copy.
            dense_state = jax.tree.unflatten(dense_tree, list(arrays))
            with ChecksumWriter(os.path.join(tmp_dir, _DENSE)) as writer:
                pickle.dump(dense_state, writer)
            copied += tree_nbytes(dense_state)
            known[_DENSE] = (writer.crc32, writer.size)
            recycled += writer.recycled_bytes
            os.utime(tmp_dir)
        else:
            sidecar = os.path.join(tmp_dir, mine + _SIDECAR_SUFFIX)
            with open(sidecar, "w") as f:
                json.dump({"crc32": writer.crc32, "size": writer.size}, f)
        stream.journal(
            copied_bytes=copied + stream.copied_bytes,
            bytes=sum(size for _crc, size in known.values()),
            recycled_bytes=recycled,
        )

        if n_processes > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(f"edl_sharded_ckpt_{step}")

        if process == 0:
            for name in shard_files[1:]:
                noted = _take_sidecar(tmp_dir, name)
                if noted is not None:
                    known[name] = noted
            # Stale files from a previous world that died mid-save in this
            # same tmp dir (different process count -> different names)
            # are swept, its sidecars too; the manifest inventories
            # exactly this world's files, and restores read nothing else.
            for fname in os.listdir(tmp_dir):
                if fname.startswith("shards_p") and fname not in shard_files:
                    os.unlink(os.path.join(tmp_dir, fname))
            manifest = {
                "step": step,
                "n_processes": n_processes,
                "shard_files": shard_files,
                "arrays": {
                    name: {
                        "shape": list(array.shape),
                        "dtype": str(array.dtype),
                    }
                    for name, array in sharded.items()
                },
            }
            with ChecksumWriter(os.path.join(tmp_dir, _MANIFEST)) as writer:
                writer.write(json.dumps(manifest).encode())
            known[_MANIFEST] = (writer.crc32, writer.size)
            # Integrity inventory: every file a restore may read —
            # INCLUDING manifest.json itself (a torn metadata manifest
            # would otherwise pass verification and crash restore) — is
            # inventoried post-barrier (all writers are done), before the
            # commit rename publishes anything.  A peer's file whose
            # sidecar is missing is read back.
            inventory = shard_files + [_DENSE, _MANIFEST]
            with tracing.span("checkpoint.save.crc") as span:
                span.fields["reread_bytes"] = write_integrity_manifest(
                    tmp_dir, inventory, known=known
                )
                span.fields["bytes"] = sum(
                    os.path.getsize(os.path.join(tmp_dir, name))
                    for name in inventory
                )
            with tracing.span("checkpoint.save.commit", bytes=0):
                _apply_write_fault(os.path.join(tmp_dir, _DENSE))
                try:
                    os.rename(tmp_dir, final_dir)
                except OSError:
                    if not os.path.exists(final_dir):
                        raise
                save_hist, _restore, saves, _q = _ckpt_metrics()
                save_hist.observe(time.monotonic() - start, kind="sharded")
                saves.inc(kind="sharded")
                obs.journal().record(
                    "checkpoint_saved",
                    step=step,
                    kind="sharded",
                    n_processes=n_processes,
                )
                logger.info(
                    "Saved sharded checkpoint at step %d (%d arrays, "
                    "%d procs)",
                    step,
                    len(sharded),
                    n_processes,
                )
                self._garbage_collect()
        return final_dir

    # -- restore ----------------------------------------------------------

    def manifest(self, step: int) -> dict:
        with open(os.path.join(self._step_dir(step), _MANIFEST)) as f:
            return json.load(f)

    def load_dense(self, step: int) -> Any:
        with open(os.path.join(self._step_dir(step), _DENSE), "rb") as f:
            return pickle.load(f)

    def _entry_index(self, step: int) -> Dict[str, List]:
        if step not in self._index_cache:
            self._index_cache[step] = build_entry_index(
                self._step_dir(step),
                self.manifest(step).get("shard_files"),
            )
        return self._index_cache[step]

    def row_reader(self, step: int, name: str) -> "RowReader":
        return RowReader.from_entries(
            self._entry_index(step).get(name, [])
        )

    def release(self, step: int):
        """Drop the cached entry index (and close its npz handles) once a
        restore is complete — the saver object outlives the restore."""
        index = self._index_cache.pop(step, None)
        if not index:
            return
        closed = set()
        for entries in index.values():
            for _lo, _hi, npz, _key in entries:
                if id(npz) not in closed:
                    closed.add(id(npz))
                    try:
                        npz.close()
                    except Exception:
                        pass

    def load_array(self, step: int, name: str, sharding) -> jax.Array:
        """Materialize one sharded array under the CURRENT world's
        `sharding` — each process reads only the row intervals its local
        devices need, regardless of the world size that saved them."""
        meta = self.manifest(step)["arrays"][name]
        shape = tuple(meta["shape"])
        dtype = np.dtype(meta["dtype"])
        reader = self.row_reader(step, name)

        def fetch(index):
            dim0 = shape[0]
            lo, hi = (
                index[0].start or 0,
                index[0].stop if index[0].stop is not None else dim0,
            )
            rows = reader.read(int(lo), int(hi)).astype(dtype, copy=False)
            rest = index[1:]
            return rows[(slice(None),) + tuple(rest)] if rest else rows

        return jax.make_array_from_callback(shape, sharding, fetch)


def build_entry_index(
    step_dir: str, shard_files: Optional[List[str]] = None
) -> Dict[str, List]:
    """One pass over a checkpoint's shard files: {array name -> sorted
    [(lo, hi, npz, entry key)]}.  `shard_files` (the manifest inventory)
    bounds what is read; None falls back to globbing (pre-inventory
    checkpoints, unit tests)."""
    if shard_files is None:
        shard_files = [
            f
            for f in sorted(os.listdir(step_dir))
            if f.startswith("shards_p") and f.endswith(".npz")
        ]
    index: Dict[str, List] = {}
    for fname in shard_files:
        npz = np.load(os.path.join(step_dir, fname), allow_pickle=False)
        for key in npz.files:
            arr_name, lo, hi = key.rsplit("|", 2)
            index.setdefault(arr_name, []).append(
                (int(lo), int(hi), npz, key)
            )
    for entries in index.values():
        entries.sort(key=lambda e: (e[0], e[1]))
    return index


class RowReader:
    """Reassembles arbitrary [lo, hi) row ranges of one named array from
    the shard files of a checkpoint (the files were written under a
    different — possibly larger, possibly smaller — world)."""

    def __init__(self, step_dir: str, name: str):
        self._entries = build_entry_index(step_dir).get(name, [])
        self._decoded: Dict[Tuple[int, str], np.ndarray] = {}

    @classmethod
    def from_entries(cls, entries: List) -> "RowReader":
        reader = cls.__new__(cls)
        reader._entries = entries
        reader._decoded = {}
        return reader

    def _entry_data(self, npz, key: str) -> np.ndarray:
        # npz[key] re-reads the full stored entry from disk every time;
        # one restore calls read() once per local device, so cache the
        # decoded entry for this reader's lifetime (one load_array call).
        cache_key = (id(npz), key)
        if cache_key not in self._decoded:
            self._decoded[cache_key] = npz[key]
        return self._decoded[cache_key]

    def read(self, lo: int, hi: int) -> np.ndarray:
        parts = []
        cursor = lo
        for e_lo, e_hi, npz, key in self._entries:
            if e_hi <= cursor or e_lo >= hi:
                continue
            if e_lo > cursor:
                raise ValueError(
                    f"Checkpoint rows [{cursor}, {e_lo}) missing "
                    f"(requested [{lo}, {hi}))"
                )
            data = self._entry_data(npz, key)
            parts.append(data[cursor - e_lo : min(hi, e_hi) - e_lo])
            cursor = min(hi, e_hi)
            if cursor >= hi:
                break
        if cursor < hi:
            raise ValueError(
                f"Checkpoint rows [{cursor}, {hi}) missing "
                f"(requested [{lo}, {hi}))"
            )
        return parts[0] if len(parts) == 1 else np.concatenate(parts, axis=0)
