"""Training-state checkpointing.

Parity: the reference checkpoints PS-side parameters every
`--checkpoint_steps` versions (pkg/ps/checkpoint.go + the python
CheckpointSaver, SURVEY.md §5) and resumes from the latest snapshot.

TPU design: checkpoints are the *backbone of elasticity*, not just crash
insurance — worker churn kills the whole jax.distributed world (a dead host
takes the slice's coordination service down), so re-formation is
restart-the-world + restore-latest.  In data-parallel mode the state is
replicated, so any rank-0 host snapshot is complete; the sharded-embedding
engine layers orbax sharded save/restore on top of this interface.

Format: one directory per step, written atomically (tmp + rename), holding
the host pytree in `state.pkl` plus a CRC32 integrity manifest
(`integrity.json`, written before the commit rename).  `state.pkl` is

    b"EDLRAW01" | u64 skeleton bytes | u64 buffer count | u64 x count
    buffer lengths | skeleton | the buffers, back to back

(little-endian).  The skeleton is a protocol-5 pickle of the tree in which
every plain array leaf is a persistent id (buffer index, dtype, shape and
axis order as stored); the leaves' bytes are the buffers, handed to the
file from the host arrays' own memory (no `tobytes()`, no pickle
framing), and restore reads each buffer into an array of its own, which
the restored leaf then views.  Everything that is not a plain array
(Python scalars, typed PRNG keys, array subclasses) is pickled into the
skeleton as ever.  A `state.pkl` that starts with anything but the magic
is what this module wrote before: one plain `pickle.dump` of the tree,
which `load_latest` still reads.

A save is a stream of leaves: `save` takes the state as it lies on the
device, starts the device-to-host transfers of the leaves up to
`_LOOKAHEAD_BYTES` ahead of the one the file is taking (`LeafStream`),
and lets go of each leaf's host copy once it is written, so the write
of leaf i runs beside the transfers of leaves i+1 ... i+k and the host
never holds the whole state.  A leaf of more than 16 MiB is cut on the
device, by programs its trainer built with its step programs
(`LeafCutter`), and crosses piece by piece: host copies of that size
come from memory the allocator keeps mapped and hands out again, which
these machines take into a file more than twice as fast as pages just
faulted in (`PERF.md` §6 PR 50), and no leaf is on the host whole.  The
file's bytes are the same either way.  It is still synchronous: `save`
returns after the rename.  A host tree (a trainer's collective
`state_to_host()`, export, debug) goes through the same writer with
nothing to wait for.

A save writes every byte once and reads none back: the size and CRC32
the manifest records are taken from the bytes on their way to the file
(`ChecksumWriter`).  Restore verifies every inventoried file against its
checksum: a torn write — power loss mid-flush, a dying NFS client, an
injected `ckpt.write:truncate` fault — is detected, the snapshot is
QUARANTINED (renamed aside, never deleted: it is forensic evidence), and
restore falls back to the next-newest good step instead of crashing or
silently loading garbage.  `keep_max` old checkpoints are retained.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import io
import json
import os
import pickle
import resource
import shutil
import struct
import tempfile
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import (
    Any, Dict, Iterable, Iterator, List, NamedTuple, Optional, Tuple,
)

import numpy as np

from elasticdl_tpu import obs
from elasticdl_tpu.common import faults
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.obs import tracing

logger = get_logger("checkpoint.saver")


def _ckpt_metrics():
    """Checkpoint-plane registry handles (get-or-create; shared with the
    sharded saver and the master's task-progress persister)."""
    return (
        obs.histogram(
            "elasticdl_checkpoint_save_duration_seconds",
            "Checkpoint write latency, by checkpoint kind",
            labelnames=("kind",),
        ),
        obs.histogram(
            "elasticdl_checkpoint_restore_duration_seconds",
            "Checkpoint restore latency, by checkpoint kind",
            labelnames=("kind",),
        ),
        obs.counter(
            "elasticdl_checkpoint_saves_total",
            "Checkpoints committed, by checkpoint kind",
            labelnames=("kind",),
        ),
        obs.counter(
            "elasticdl_checkpoint_quarantines_total",
            "Corrupt checkpoints quarantined (integrity failures)",
        ),
    )

def tree_nbytes(tree) -> int:
    """Bytes of a pytree's array leaves (host or device)."""
    import jax

    return int(sum(
        getattr(leaf, "nbytes", 0) for leaf in jax.tree.leaves(tree)
    ))


def _host_io_marks() -> dict:
    """What the host's page cache and this process's I/O look like now:
    `Dirty` / `Writeback` kB of /proc/meminfo and the getrusage
    counters a slow save moves (major faults, blocks written,
    involuntary context switches).  Two small reads."""
    marks = {}
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                key, _, rest = line.partition(":")
                if key in ("Dirty", "Writeback"):
                    marks[key.lower() + "_kb"] = int(rest.split()[0])
    except (OSError, ValueError, IndexError):
        pass
    usage = resource.getrusage(resource.RUSAGE_SELF)
    marks.update(
        majflt=usage.ru_majflt, oublock=usage.ru_oublock,
        nivcsw=usage.ru_nivcsw,
    )
    return marks


@contextlib.contextmanager
def save_span(**fields):
    """The parent `checkpoint.save` span of one save (every rank opens
    it around its gather and write; the children are
    `checkpoint.save.gather` / `.write` / `.crc` / `.commit`).  It
    closes with the host's dirty and write-back kB at its start and
    end and the process's rusage deltas over it — what tells a save
    slowed by piled-up dirty pages from one slowed by the device."""
    before = _host_io_marks()
    with tracing.span("checkpoint.save", **fields) as span:
        try:
            yield span
        finally:
            after = _host_io_marks()
            for key in ("dirty_kb", "writeback_kb"):
                if key in before and key in after:
                    span.fields[key + "_start"] = before[key]
                    span.fields[key + "_end"] = after[key]
            for key in ("majflt", "oublock", "nivcsw"):
                span.fields[key] = after[key] - before[key]


_STATE_FILE = "state.pkl"
_INTEGRITY_FILE = "integrity.json"
_QUARANTINE_SUFFIX = ".quarantined"

#: Tmp dirs untouched for this long are garbage from a crashed save.
#: Deliberately generous: the sweep runs at every saver CONSTRUCTION
#: (worker restarts coincide with in-flight peer saves during elastic
#: churn), directory mtime only advances on entry creation — writers
#: os.utime() their tmp dir after each large file write to stay fresh —
#: and deleting a live save costs a checkpoint while a leaked tmp dir
#: costs only disk for an hour.
STALE_TMP_GRACE_S = 3600.0


def file_crc32(path: str, chunk_bytes: int = 1 << 20) -> int:
    # Note: verification streams the file once and the restore then
    # re-reads it (2x restore I/O, page-cache-warm on local disk).
    # Folding the CRC into the load read would save the second pass on
    # NFS-scale states; measure before taking that complexity.
    crc = 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(chunk_bytes)
            if not chunk:
                return crc
            crc = zlib.crc32(chunk, crc)


def _gf2_times(matrix: List[int], vector: int) -> int:
    total = 0
    for row in matrix:
        if not vector:
            break
        if vector & 1:
            total ^= row
        vector >>= 1
    return total


#: `_APPEND_ZEROS[k]` is the GF(2) operator on a CRC32 register that
#: appending 2**k zero bytes applies (each the square of the one before;
#: grown on demand, a few hundred integers in all).
_APPEND_ZEROS: List[List[int]] = []


def crc32_combine(crc_a: int, crc_b: int, len_b: int) -> int:
    """crc32(A + B) from crc32(A), crc32(B) and len(B): zlib's
    `crc32_combine`, which Python's `zlib` does not export.  Applies the
    operator "append len(B) zero bytes" to crc32(A), one factor for each
    set bit of len(B)."""
    if not _APPEND_ZEROS:
        operator = [0xEDB88320] + [1 << n for n in range(31)]  # one bit
        for _ in range(3):  # two, four, eight zero bits: one byte
            operator = [_gf2_times(operator, row) for row in operator]
        _APPEND_ZEROS.append(operator)
    k = 0
    while len_b > 0:
        if k == len(_APPEND_ZEROS):
            last = _APPEND_ZEROS[-1]
            _APPEND_ZEROS.append([_gf2_times(last, row) for row in last])
        if len_b & 1:
            crc_a = _gf2_times(_APPEND_ZEROS[k], crc_a)
        len_b >>= 1
        k += 1
    return crc_a ^ crc_b


#: Writes are handed to the file in pieces of this size (16 MB pieces
#: went out a tenth faster than 64 MB to 1 GB ones where it was
#: measured, `PERF.md` §6 PR 27); a piece of at least `_BESIDE_BYTES`
#: has its checksum taken on the helper thread while the file takes it
#: (`zlib.crc32` and `file.write` both release the GIL), smaller ones
#: are folded in line.
_PIECE_BYTES = 16 << 20
_BESIDE_BYTES = 1 << 20


class _Ranges:
    """Address ranges, merged: `add` takes one more and says how many of
    its bytes lay in those it already had."""

    def __init__(self):
        self._lo: List[int] = []
        self._hi: List[int] = []

    def add(self, lo: int, hi: int) -> int:
        first = bisect.bisect_left(self._hi, lo)
        last = bisect.bisect_right(self._lo, hi)
        had = sum(
            max(0, min(hi, self._hi[n]) - max(lo, self._lo[n]))
            for n in range(first, last)
        )
        if first < last:
            lo, hi = min(lo, self._lo[first]), max(hi, self._hi[last - 1])
        self._lo[first:last], self._hi[first:last] = [lo], [hi]
        return had


class ChecksumWriter:
    """The write-only file both savers write through: `crc32` and `size`
    are those of the bytes it was handed, taken as they pass, so the
    integrity manifest never reads a file this process has just
    written.  `write` takes any contiguous buffer (an array's own
    memory, a `PickleBuffer`, bytes) and copies none of it.
    `recycled_bytes` counts the bytes of pieces of `_BESIDE_BYTES` and
    more that came from an address range this file had already taken
    bytes from: where the allocator hands memory out again while it is
    still mapped, the file takes it more than twice as fast as pages
    that were just faulted in (`PERF.md` §6 PR 50; a range unmapped and
    mapped anew at the same address counts too)."""

    def __init__(self, path: str):
        self.crc32 = 0
        self.size = 0
        self.recycled_bytes = 0
        self._taken = _Ranges()
        self._file = open(path, "wb")
        self._helper = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="ckpt-crc"
        )
        self._member: Optional[Tuple[int, int]] = None

    def __enter__(self) -> "ChecksumWriter":
        return self

    def __exit__(self, *exc_info) -> None:
        self.close()

    def _fold(self, piece) -> None:
        self.crc32 = zlib.crc32(piece, self.crc32)

    def write(self, data) -> int:
        view = memoryview(data)
        if view.ndim != 1 or view.itemsize != 1:
            view = view.cast("B")
        for lo in range(0, view.nbytes, _PIECE_BYTES):
            piece = view[lo:lo + _PIECE_BYTES]
            if piece.nbytes < _BESIDE_BYTES:
                self._fold(piece)
                self._file.write(piece)
                continue
            folding = self._helper.submit(self._fold, piece)
            try:
                self._file.write(piece)
            finally:
                folding.result()
            at = np.frombuffer(piece, np.uint8).ctypes.data
            self.recycled_bytes += self._taken.add(at, at + piece.nbytes)
        self.size += view.nbytes
        return view.nbytes

    def begin_member(self) -> None:
        """Start a stretch whose own CRC32 the caller needs too (a zip
        member's data): one pass over its bytes serves both."""
        self._member = (self.crc32, self.size)
        self.crc32 = 0

    def end_member(self) -> int:
        """-> the CRC32 of the bytes written since `begin_member`; the
        file's running CRC32 goes on as if it had never been reset."""
        before, start = self._member
        self._member = None
        member = self.crc32
        self.crc32 = crc32_combine(before, member, self.size - start)
        return member

    def close(self) -> None:
        self._helper.shutdown()
        self._file.close()


#: How far ahead of the leaf being written a save starts the transfers
#: of the leaves behind it, in bytes, and so, with that leaf, all that
#: the host holds of a device state at once.  The link brings a leaf
#: over two to three times as fast as the file takes it (`PERF.md` §6
#: PR 39), so any bound of a few leaves keeps the writer fed; one leaf
#: is always ahead, whatever its size.
_LOOKAHEAD_BYTES = 512 << 20


def _on_device(leaf) -> bool:
    """Whether `leaf` is an array a save has to bring to the host (a
    typed PRNG key is not: it pickles itself)."""
    import jax

    return isinstance(leaf, jax.Array) and not jax.dtypes.issubdtype(
        leaf.dtype, jax.dtypes.extended
    )


def streams(tree) -> bool:
    """Whether a save can take `tree` as it lies on the device: every
    array of it is whole on this process's devices, so bringing it to
    the host is no collective."""
    import jax

    leaves = [x for x in jax.tree.leaves(tree) if _on_device(x)]
    return bool(leaves) and all(
        x.is_fully_addressable and x.is_fully_replicated for x in leaves
    )


#: What the pieces cut from large leaves and not yet on the host may
#: hold of the DEVICE's memory: a piece is a buffer there until it has
#: crossed.  Half a second of the file's appetite, which a link five
#: times as fast refills with room to spare.
_PIECES_AHEAD_BYTES = 128 << 20


class _Cut(NamedTuple):
    """How a leaf larger than a piece is cut: in the order the device
    keeps its axes (the order its bytes have in the file), into ranges
    of `rows` indices of the first axis one index of which fits a
    piece, one index at a time of every axis before it."""

    axes: Optional[tuple]  # the device's order of the axes; None: their own
    shape: tuple  # the leaf's shape in that order
    axis: int
    rows: int

    @classmethod
    def of(cls, shape, dtype, axes, piece_bytes: int) -> "_Cut":
        stored = tuple(shape) if axes is None else tuple(
            shape[axis] for axis in axes
        )
        inner = np.dtype(dtype).itemsize * int(np.prod(stored, dtype=np.int64))
        for axis, length in enumerate(stored):
            inner //= length  # the bytes of one index of this axis
            if inner <= piece_bytes:
                break
        rows = min(length, max(1, piece_bytes // inner))
        return cls(axes, stored, axis, rows)

    @property
    def sizes(self) -> tuple:
        """A piece's shape."""
        return (
            (1,) * self.axis + (self.rows,) + self.shape[self.axis + 1:]
        )

    def pieces(self) -> Iterator[Tuple[np.ndarray, int]]:
        """-> (where a piece starts, the rows at its head that the piece
        before it already held), in the file's order.  Every piece has
        `rows` rows, so one program cuts them all: where the lengths do
        not divide, the last starts early and its head is skipped."""
        length = self.shape[self.axis]
        starts = np.zeros(len(self.shape), np.int32)
        for outer in np.ndindex(*self.shape[:self.axis]):
            starts[:self.axis] = outer
            for row in range(0, length, self.rows):
                starts[self.axis] = min(row, length - self.rows)
                yield starts.copy(), row - int(starts[self.axis])


def _on_one_device(leaf):
    """A leaf that every device holds whole, as the first one's array."""
    if len(leaf.sharding.device_set) > 1:
        return leaf.addressable_shards[0].data
    return leaf


class LeafCutter:
    """The device programs that cut a leaf of more than `piece_bytes`
    into pieces of at most that, so that a `LeafStream` brings it to
    the host piece by piece: a piece's host copy is small enough for
    the allocator to hand the same memory out again for the next, and
    no leaf is ever on the host whole.  One program a (shape, dtype,
    order of axes), the piece's place its argument.  `warm` builds
    them (through `build`, the compile layer's door, where a trainer
    gives one: then the compile cache and the executable store keep
    them); a leaf that no program was warmed for crosses whole, so a
    save compiles nothing."""

    def __init__(self, build=None, piece_bytes: int = _PIECE_BYTES):
        self.piece_bytes = piece_bytes
        self._build = build
        self._programs: Dict[tuple, Tuple[_Cut, Any]] = {}

    def _program(self, cut: _Cut):
        import jax

        def ckpt_piece(leaf, starts):
            stored = leaf if cut.axes is None else leaf.transpose(cut.axes)
            return jax.lax.dynamic_slice(
                stored, tuple(starts[n] for n in range(stored.ndim)),
                cut.sizes,
            )

        if self._build is None:
            return jax.jit(ckpt_piece)
        order = "".join(map(str, cut.axes or ()))
        return self._build(
            ckpt_piece, f"ckpt_piece.{cut.axis}x{cut.rows}.{order or 'rows'}"
        )

    def warm(self, leaves: Iterable) -> int:
        """Build the program of every leaf of `leaves` that is to be
        cut (one that is whole on this process's devices and larger
        than a piece), each by one call.  -> how many were built."""
        import jax

        built = 0
        for leaf in leaves:
            if not (
                _on_device(leaf) and leaf.nbytes > self.piece_bytes
                and leaf.is_fully_addressable and leaf.is_fully_replicated
            ):
                continue
            axes = _device_axes(leaf)
            key = (leaf.shape, leaf.dtype, axes)
            if key in self._programs:
                continue
            cut = _Cut.of(leaf.shape, leaf.dtype, axes, self.piece_bytes)
            program = self._program(cut)
            jax.block_until_ready(program(
                _on_one_device(leaf), np.zeros(leaf.ndim, np.int32)
            ))
            self._programs[key] = (cut, program)
            built += 1
        return built

    def cut_of(self, leaf) -> Optional[Tuple[_Cut, Any]]:
        """(how `leaf` is cut, the program that cuts it), or None for a
        leaf that crosses whole."""
        if leaf.nbytes <= self.piece_bytes or not self._programs:
            return None
        return self._programs.get(
            (leaf.shape, leaf.dtype, _device_axes(leaf))
        )


class LeafPieces:
    """A cut leaf on its way to the host: the leaf's `shape`, `dtype`
    and `nbytes`, and, walked once, the `np.uint8` runs of its pieces,
    which back to back are the bytes `_DeviceLeaf.bytes_of` gives for
    the leaf whole."""

    def __init__(self, leaf, runs: Iterator):
        self.shape, self.dtype = leaf.shape, leaf.dtype
        self.nbytes = leaf.nbytes
        self._runs = runs

    def __iter__(self) -> Iterator[np.ndarray]:
        return self._runs


class LeafStream:
    """Device arrays to the host in the order a file holds them: each
    transfer is started up to `_LOOKAHEAD_BYTES` before the writer asks
    for it, so the file takes leaf i while leaves i+1 ... i+k cross the
    link, and a host copy goes once the writer asks for the next.  A
    leaf that `cutter` has a program for crosses piece by piece
    (`LeafCutter`).  Counts what the save's spans report."""

    def __init__(self, cutter: Optional[LeafCutter] = None):
        self._started_ts, self._started = time.time(), time.monotonic()
        self._cutter = cutter
        self._device_idle = False  # the program in flight has been waited for
        self.wait_s = 0.0  # the caller, blocked on the device
        self.leaves = 0  # device leaves handed over
        self.bytes = 0
        self.pieces = 0  # transfers: one a whole leaf, one a piece
        self.copied_bytes = 0  # pieces that came in an order of their own
        self.streamed_bytes = 0  # ... whose transfer was started ahead
        self.lookahead_peak_bytes = 0  # most held on the host at once

    @staticmethod
    def _start(leaf):
        """Start `leaf`'s transfer.  -> a handle of this stream's own on
        the same device buffer: the host copy a transfer leaves cached
        on a `jax.Array` goes with the handle, not with the trainer's
        array."""
        import jax

        if leaf.is_fully_replicated:  # (else: assembled on the leaf)
            piece = leaf.addressable_shards[0].data
            leaf = jax.make_array_from_single_device_arrays(
                piece.shape, piece.sharding, [piece]
            )
        leaf.copy_to_host_async()
        return leaf

    def _start_piece(self, leaf, program, starts):
        """Cut the piece of `leaf` at `starts` and start its transfer.
        A piece is taken from the device's memory when it is
        dispatched, so none is before the program in flight is over
        (the train programs leave the chip little room)."""
        if not self._device_idle:
            waited = time.monotonic()
            leaf.block_until_ready()
            self.wait_s += time.monotonic() - waited
            self._device_idle = True
        piece = program(_on_one_device(leaf), starts)
        piece.copy_to_host_async()
        return piece

    @staticmethod
    def _fetch(handle) -> np.ndarray:
        return np.asarray(handle)

    def host_arrays(self, leaves: Iterable, whole: Iterable[int] = ()):
        """Yield each of `leaves` as the host has it: a device array as
        the `np.ndarray` its transfer made, or, where it is cut (never
        one whose place in `leaves` is in `whole`), as `LeafPieces`,
        to be walked to their end before the next leaf is asked for;
        anything else as it is.  A caller that keeps no reference to
        an array past its turn holds what `lookahead_peak_bytes` says
        and no more."""
        leaves, whole = list(leaves), set(whole)
        # The transfers to make, in the file's order: (leaf's place,
        # bytes, bytes of them the file takes, None or the piece's
        # (program, start)); and of each cut leaf (how it is cut, the
        # rows to skip at the head of each of its pieces).
        units: List[tuple] = []
        cuts: Dict[int, Tuple[_Cut, List[int]]] = {}
        for at, leaf in enumerate(leaves):
            if not _on_device(leaf):
                continue
            found = None
            if self._cutter is not None and at not in whole:
                found = self._cutter.cut_of(leaf)
            if found is None:
                units.append((at, leaf.nbytes, leaf.nbytes, None))
                continue
            cut, program = found
            row = leaf.dtype.itemsize * int(np.prod(cut.sizes)) // cut.rows
            cuts[at] = (cut, [])
            for starts, skip in cut.pieces():
                cuts[at][1].append(skip)
                units.append((
                    at, row * cut.rows, row * (cut.rows - skip),
                    (program, starts),
                ))
        ahead: collections.deque = collections.deque()  # started, in order
        started = ahead_bytes = ahead_device = held = in_hand = 0

        def top_up():
            """Start transfers as far ahead as the bounds allow; one is
            always ahead, whatever its size."""
            nonlocal started, ahead_bytes, ahead_device, held
            while started < len(units):
                at, nbytes, kept, piece = units[started]
                device = nbytes if piece else 0
                if ahead and (
                    ahead_bytes + nbytes > _LOOKAHEAD_BYTES
                    or ahead_device + device > _PIECES_AHEAD_BYTES
                ):
                    break
                ahead.append((
                    self._start(leaves[at]) if piece is None
                    else self._start_piece(leaves[at], *piece),
                    nbytes, kept, device,
                ))
                ahead_bytes += nbytes
                ahead_device += device
                held += nbytes
                started += 1

        def take() -> np.ndarray:
            """-> the next transfer's host copy; what the one before it
            left on the host is let go of."""
            nonlocal ahead_bytes, ahead_device, held, in_hand
            held -= in_hand
            if ahead:
                self.streamed_bytes += ahead[0][2]
            else:
                top_up()
            handle, in_hand, _kept, device = ahead.popleft()
            ahead_bytes -= in_hand
            ahead_device -= device
            top_up()
            self.lookahead_peak_bytes = max(self.lookahead_peak_bytes, held)
            waited = time.monotonic()
            host = self._fetch(handle)
            self.wait_s += time.monotonic() - waited
            self.pieces += 1
            return host

        def runs(cut: _Cut, skips: List[int]) -> Iterator[np.ndarray]:
            for skip in skips:
                host = take()
                if not host.flags.c_contiguous:  # (a layout of its own)
                    host = np.ascontiguousarray(host)
                    self.copied_bytes += host.nbytes
                yield host.reshape(cut.rows, -1)[skip:].reshape(-1).view(
                    np.uint8
                )

        try:
            for at, leaf in enumerate(leaves):
                if not _on_device(leaf):
                    yield leaf
                    continue
                self.leaves += 1
                self.bytes += leaf.nbytes
                if at in cuts:
                    pieces = runs(*cuts[at])
                    yield LeafPieces(leaf, pieces)
                    if next(pieces, None) is not None:
                        raise RuntimeError(
                            "a cut leaf's pieces were not taken to their end"
                        )
                else:
                    yield take()
        finally:
            ahead.clear()

    def journal(self, **write_fields):
        """Journal the save this stream was made for, from its making
        until now, as the two spans its reader sums:
        `checkpoint.save.gather`, the time the save waited for the
        device (the program in flight, then any leaf not yet on the
        host when the writer asked for it; none where no leaf came
        from a device), and `checkpoint.save.write`, the rest: the
        time inside the writer."""
        elapsed_s = time.monotonic() - self._started
        if self.leaves:
            tracing.record_child_span(
                "checkpoint.save.gather", self._started_ts, self.wait_s,
                bytes=self.bytes,
            )
        tracing.record_child_span(
            "checkpoint.save.write", self._started_ts + self.wait_s,
            elapsed_s - self.wait_s, streamed_bytes=self.streamed_bytes,
            lookahead_peak_bytes=self.lookahead_peak_bytes,
            leaves=self.leaves, pieces=self.pieces, **write_fields,
        )


def write_integrity_manifest(
    step_dir: str,
    filenames: Iterable[str],
    known: Optional[Dict[str, Tuple[int, int]]] = None,
) -> int:
    """Inventory `filenames` (relative to `step_dir`) into integrity.json.
    Called while the checkpoint is still a tmp dir, BEFORE the atomic
    commit rename — the manifest is part of what the rename publishes.
    `known` is {name: (crc32, size)} as the writers took them from the
    bytes in flight (`ChecksumWriter`); only a file it does not name is
    read back.  -> the bytes read back (0 when every file was known)."""
    known = known or {}
    files = {}
    reread = 0
    for name in filenames:
        if name in known:
            crc, size = known[name]
        else:
            path = os.path.join(step_dir, name)
            crc, size = file_crc32(path), os.path.getsize(path)
            reread += size
        files[name] = {"crc32": crc, "size": size}
    with open(os.path.join(step_dir, _INTEGRITY_FILE), "w") as f:
        json.dump({"files": files}, f)
    return reread


def verify_integrity(step_dir: str, check_crc: bool = True) -> Optional[str]:
    """None if `step_dir` passes its integrity manifest, else a reason
    string — returned ONLY for proven corruption (checksum/size
    mismatch, garbage manifest, inventoried file missing from a
    committed dir), which callers may quarantine.  Transient I/O errors
    (NFS blip, ESTALE) raise OSError instead: the snapshot may be
    perfectly good, so callers skip it for this attempt, never
    quarantine.  A checkpoint without a manifest (pre-integrity
    snapshots) passes vacuously — the pickle/npz load remains its only
    guard.

    `check_crc=False` verifies existence+size only (metadata ops, no
    data reads) — catches truncation/torn writes but not bit rot; used
    by non-zero ranks of a sharded restore so a world re-formation does
    not multiply full-checkpoint reads by the process count."""
    manifest_path = os.path.join(step_dir, _INTEGRITY_FILE)
    if not os.path.exists(manifest_path):
        return None
    with open(manifest_path) as f:
        try:
            inventory: Dict[str, dict] = json.load(f)["files"]
        except (ValueError, KeyError) as exc:
            return f"garbage integrity manifest (torn write?): {exc!r}"
    for name, meta in inventory.items():
        path = os.path.join(step_dir, name)
        try:
            size = os.path.getsize(path)
        except FileNotFoundError:
            return f"{name}: missing from committed checkpoint"
        if size != meta["size"]:
            return (
                f"{name}: size {size} != manifest {meta['size']} "
                "(torn write)"
            )
        if check_crc:
            crc = file_crc32(path)
            if crc != meta["crc32"]:
                return (
                    f"{name}: crc32 {crc:#010x} != manifest "
                    f"{meta['crc32']:#010x}"
                )
    return None


#: First bytes of a `state.pkl` in the raw layout (module docstring).  A
#: pickle stream starts with its PROTO opcode, 0x80.
_RAW_MAGIC = b"EDLRAW01"


def _dense_view(array: np.ndarray):
    """-> (`array`'s elements as a C-contiguous view, the order of
    `array`'s axes in that view or None for their own order), without a
    copy, whatever dense layout the array has: C, Fortran, or any other
    order of its axes (weights that a TPU keeps transposed come back
    from `jax.device_get` that way).  None for an array with gaps or
    overlaps (a slice with a step, a broadcast)."""
    if array.flags.c_contiguous:
        return array, None
    axes = tuple(
        int(axis) for axis in np.argsort(
            [-stride for stride in array.strides], kind="stable"
        )
    )
    view = array.transpose(axes)
    return (view, axes) if view.flags.c_contiguous else None


def _device_axes(array) -> Optional[tuple]:
    """The order in which the device keeps `array`'s axes, major first,
    which is the order its transfer brings them to the host in: None
    for their own order, and where the runtime does not say."""
    try:
        axes = tuple(int(a) for a in array.format.layout.major_to_minor)
    except Exception:  # no layout to ask for: a leaf that is off then
        return None  # costs one copy (`_DeviceLeaf.bytes_of`)
    return None if axes == tuple(range(array.ndim)) else axes


class _DeviceLeaf:
    """A leaf still on the device, in a pickler's `buffers`: its length
    is known from its shape and the axes it is stored by are settled
    before its transfer, because the skeleton that names them goes to
    the file before any buffer."""

    def __init__(self, array):
        self.array = array  # (and keeps its id alive)
        self.axes = _device_axes(array)
        self.nbytes = array.nbytes

    def bytes_of(self, host: np.ndarray) -> Tuple[np.ndarray, int]:
        """-> (`host`'s elements in the stored order as one run of
        bytes, the bytes copied for it: none unless the transfer
        brought another order than the device's layout said)."""
        stored = host if self.axes is None else host.transpose(self.axes)
        copied = 0
        if not stored.flags.c_contiguous:
            stored, copied = np.ascontiguousarray(stored), stored.nbytes
        return stored.reshape(-1).view(np.uint8), copied


class _RawLeafPickler(pickle.Pickler):
    """Pickles a tree's skeleton and sets its plain array leaves aside
    (`buffers`), each named in the skeleton by a persistent id (index,
    dtype, shape as stored, axes as stored).  A host array's buffer is
    a raw byte view of it: float32 as much as bfloat16, 0-d, read-only
    (what `jax.device_get` returns) and transposed arrays, none of
    them copied; an array that is not dense is copied once into one
    that is (`copied_bytes`).  A device array's is a `_DeviceLeaf`,
    whose bytes the writer asks a `LeafStream` for."""

    def __init__(self, file, device_leaves: Iterable = ()):
        super().__init__(file, protocol=5)
        self.buffers: List[Any] = []
        self.copied_bytes = 0
        self._pid_of: Dict[int, tuple] = {}
        # The tree's own leaves, by id: an array INSIDE another leaf's
        # pickle (a typed key's data) is that leaf's to restore.
        self._on_device = {id(leaf) for leaf in device_leaves}

    def persistent_id(self, obj):
        on_host = type(obj) is np.ndarray
        if obj.dtype.hasobject if on_host else (
            id(obj) not in self._on_device
        ):
            return None
        pid = self._pid_of.get(id(obj))
        if pid is not None:
            return pid
        if on_host:
            dense = _dense_view(obj)
            if dense is None:
                dense = np.ascontiguousarray(obj), None
                self.copied_bytes += obj.nbytes
            view, axes = dense
            shape = view.shape
            # The buffer's base keeps `obj` (and so its id) alive.
            buffer = view.reshape(-1).view(np.uint8)
        else:
            buffer = _DeviceLeaf(obj)
            axes = buffer.axes
            shape = obj.shape if axes is None else tuple(
                obj.shape[axis] for axis in axes
            )
        pid = (len(self.buffers), obj.dtype, shape, axes)
        self.buffers.append(buffer)
        self._pid_of[id(obj)] = pid
        return pid


#: A `state.pkl` names its tree's classes by the module they lived in when
#: it was written.  Those that have moved since, as (module, name) then
#: -> module now, all relative to this package.
_PACKAGE = __name__.partition(".")[0]
_MOVED_CLASSES = {("worker.trainer", "TrainState"): "parallel.trainer"}


class _StateUnpickler(pickle.Unpickler):
    """Unpickles a saved tree of either layout, whichever tree wrote it."""

    def find_class(self, module, name):
        package, _, rest = module.partition(".")
        if package == _PACKAGE and (rest, name) in _MOVED_CLASSES:
            module = f"{_PACKAGE}.{_MOVED_CLASSES[rest, name]}"
        return super().find_class(module, name)


class _RawLeafUnpickler(_StateUnpickler):
    def __init__(self, file, buffers: List[np.ndarray]):
        super().__init__(file)
        self._buffers = buffers

    def persistent_load(self, pid):
        index, dtype, shape, axes = pid
        array = self._buffers[index].view(dtype).reshape(shape)
        # (a transposed leaf comes back in the layout it was saved from)
        return array if axes is None else array.transpose(np.argsort(axes))


def write_state(
    writer: ChecksumWriter, state: Any, stream: Optional[LeafStream] = None
) -> int:
    """Write a tree in the raw layout, its device leaves (if any) as
    `stream` brings them over.  -> `copied_bytes`: array bytes that did
    not go to the file from the memory the leaf came to the host in."""
    import jax

    skeleton = io.BytesIO()
    pickler = _RawLeafPickler(
        skeleton, filter(_on_device, jax.tree.leaves(state))
    )
    pickler.dump(state)
    buffers = pickler.buffers
    writer.write(_RAW_MAGIC + struct.pack(
        f"<{2 + len(buffers)}Q", skeleton.tell(), len(buffers),
        *(buffer.nbytes for buffer in buffers),
    ))
    writer.write(skeleton.getbuffer())
    copied = pickler.copied_bytes
    stream = stream or LeafStream()
    arrays = stream.host_arrays(
        getattr(buffer, "array", buffer) for buffer in buffers
    )
    for buffer in buffers:
        host = next(arrays)
        if isinstance(host, LeafPieces):
            for run in host:
                writer.write(run)
            run = None
            continue
        if isinstance(buffer, _DeviceLeaf):
            host, extra = buffer.bytes_of(host)
            copied += extra
        writer.write(host)
        host = None  # let go of before the stream brings the next
    return copied + stream.copied_bytes


def _read_exact(f, into) -> None:
    view = memoryview(into)
    if view.nbytes:
        view = view.cast("B")
    while view.nbytes:
        n = f.readinto(view)
        if not n:
            raise EOFError("state file ends inside what its header lists")
        view = view[n:]


def read_state(path: str) -> Any:
    """Read a `state.pkl` in either layout, told apart by its first
    bytes: the raw one (each buffer read into an array of its own, which
    the restored leaves then view: writable, no second copy) or the
    plain pickle stream this module wrote before."""
    size = os.path.getsize(path)
    with open(path, "rb") as f:
        if f.read(len(_RAW_MAGIC)) != _RAW_MAGIC:
            f.seek(0)
            return _StateUnpickler(f).load()
        counts = np.empty(2, "<u8")
        _read_exact(f, counts)
        skeleton_bytes, n_buffers = (int(x) for x in counts)
        head = len(_RAW_MAGIC) + 8 * (2 + n_buffers)
        if head + skeleton_bytes > size:
            raise ValueError("state file's header lists more than the file")
        lengths = np.empty(n_buffers, "<u8")
        _read_exact(f, lengths)
        if head + skeleton_bytes + int(lengths.sum()) != size:
            raise ValueError(
                "state file's header does not add up to its size"
            )
        skeleton = bytearray(skeleton_bytes)
        _read_exact(f, skeleton)
        buffers = [np.empty(int(n), np.uint8) for n in lengths]
        for buffer in buffers:
            _read_exact(f, buffer)
    return _RawLeafUnpickler(io.BytesIO(skeleton), buffers).load()


def _apply_write_fault(state_path: str) -> None:
    """The `ckpt.write` injection site: a `truncate` fault tears the
    just-written state file AFTER its checksum was recorded — exactly the
    corruption a crashed flush produces."""
    spec = faults.fire("ckpt.write")
    if spec is None or spec.kind != "truncate":
        return
    size = os.path.getsize(state_path)
    keep = int(spec.arg) if spec.arg else size // 2
    with open(state_path, "r+b") as f:
        f.truncate(keep)
    logger.warning(
        "FAULT INJECTION: truncated %s to %d of %d bytes",
        state_path, keep, size,
    )


class CheckpointSaver:
    def __init__(self, checkpoint_dir: str, keep_max: int = 3):
        self._dir = checkpoint_dir
        self._keep_max = keep_max
        os.makedirs(checkpoint_dir, exist_ok=True)
        self.sweep_stale_tmp()

    # ------------------------------------------------------------------

    def _step_dir(self, step: int) -> str:
        return os.path.join(self._dir, f"step_{step:012d}")

    def _is_committed(self, step_dir: str) -> bool:
        """Validity hook: a complete snapshot has a non-empty state file
        (subclasses narrow further, e.g. sharded saves require their
        manifest).  An empty/stateless step dir — a crashed save that got
        as far as the rename, or a stray mkdir — is skipped with a
        warning instead of surfacing later as a restore crash."""
        try:
            state_path = os.path.join(step_dir, _STATE_FILE)
            return os.path.getsize(state_path) > 0
        except (FileNotFoundError, NotADirectoryError):
            # Proven incomplete (no state file / not a dir).  Other
            # OSErrors are transient I/O and must propagate — reporting a
            # good checkpoint as uncommitted on an NFS blip would
            # silently restart training from an older step.
            return False

    def steps(self):
        # An unlistable checkpoint dir raises: pretending it is empty
        # would turn one transient I/O error into a silent fresh start.
        steps = []
        for name in os.listdir(self._dir):
            if (
                not name.startswith("step_")
                or ".tmp" in name
                or name.endswith(_QUARANTINE_SUFFIX)
            ):
                continue
            try:
                step = int(name[len("step_"):])
            except ValueError:
                continue
            if not self._is_committed(os.path.join(self._dir, name)):
                logger.warning(
                    "Skipping incomplete/unreadable checkpoint %s",
                    os.path.join(self._dir, name),
                )
                continue
            steps.append(step)
        return sorted(steps)

    # ------------------------------------------------------------------

    def save(
        self, state: Any, step: int, cutter: Optional[LeafCutter] = None
    ) -> str:
        """Snapshot a pytree at `step`, atomically, with a CRC32
        integrity manifest covering the state file.  Leaves that are
        still on the device (`streams`) reach the file through a
        `LeafStream`: the host never holds the whole of them, and of a
        leaf that `cutter` cuts never more than a few pieces."""
        start = time.monotonic()
        final_dir = self._step_dir(step)
        if os.path.exists(final_dir):
            return final_dir
        tmp_dir = tempfile.mkdtemp(
            prefix=f"step_{step:012d}.tmp", dir=self._dir
        )
        state_path = os.path.join(tmp_dir, _STATE_FILE)
        stream = LeafStream(cutter)
        with ChecksumWriter(state_path) as writer:
            copied = write_state(writer, state, stream)
        stream.journal(
            copied_bytes=copied, bytes=writer.size,
            recycled_bytes=writer.recycled_bytes,
        )
        with tracing.span(
            "checkpoint.save.crc", bytes=writer.size
        ) as span:
            span.fields["reread_bytes"] = write_integrity_manifest(
                tmp_dir, [_STATE_FILE],
                known={_STATE_FILE: (writer.crc32, writer.size)},
            )
        with tracing.span("checkpoint.save.commit", bytes=0):
            _apply_write_fault(state_path)
            os.rename(tmp_dir, final_dir)
            save_hist, _restore, saves, _quarantines = _ckpt_metrics()
            save_hist.observe(time.monotonic() - start, kind="full")
            saves.inc(kind="full")
            obs.journal().record("checkpoint_saved", step=step, kind="full")
            logger.info(
                "Saved checkpoint at step %d -> %s", step, final_dir
            )
            self._garbage_collect()
        return final_dir

    def load_latest(self) -> Tuple[Optional[Any], int]:
        """Returns (state, step); (None, 0) when no checkpoint exists.
        Corrupt snapshots (checksum mismatch or unreadable pickle) are
        quarantined and the next-newest good one wins."""
        start = time.monotonic()
        for step in reversed(self.steps()):
            step_dir = self._step_dir(step)
            try:
                reason = verify_integrity(step_dir)
            except OSError:
                # Transient I/O — the snapshot may be intact; skip it for
                # THIS restore, never destroy evidence on a read blip.
                logger.exception(
                    "Could not verify checkpoint %s (transient I/O "
                    "error?); skipping it this restore", step_dir,
                )
                continue
            if reason is not None:
                self._quarantine(step_dir, reason)
                continue
            path = os.path.join(step_dir, _STATE_FILE)
            try:
                # The real restore (read + unpickle); the caller places
                # the state on the device inside `checkpoint.restore`.
                with tracing.span(
                    "checkpoint.restore.load", step=step,
                    bytes=os.path.getsize(path),
                ):
                    state = read_state(path)
                _save, restore_hist, _saves, _q = _ckpt_metrics()
                restore_hist.observe(
                    time.monotonic() - start, kind="full"
                )
                obs.journal().record(
                    "checkpoint_restored", step=step, kind="full"
                )
                logger.info("Restored checkpoint from step %d", step)
                return state, step
            except OSError:
                logger.exception(
                    "Could not read checkpoint %s (transient I/O "
                    "error?); skipping it this restore", step_dir,
                )
            except (pickle.UnpicklingError, EOFError, ValueError) as exc:
                # The file read fine but is not a valid pickle stream:
                # corruption the (vacuously-passing, pre-integrity)
                # manifest could not catch.
                self._quarantine(step_dir, f"unloadable state: {exc!r}")
            except Exception:
                # Environment-shaped load failures (ImportError after a
                # bad deploy, MemoryError on a constrained restart) are
                # NOT corruption — quarantining here would eat every
                # snapshot in the dir, newest first.  Skip; the snapshot
                # stays restorable once the environment is fixed.
                logger.exception(
                    "Could not load checkpoint %s (environment error, "
                    "not corruption); skipping it this restore", step_dir,
                )
        return None, 0

    def _quarantine(self, step_dir: str, reason: str):
        """Move a corrupt snapshot aside (never delete: it is the evidence
        for the postmortem) so no future restore can pick it again."""
        target = step_dir + _QUARANTINE_SUFFIX
        # A previous incident at the same step keeps ITS evidence: pick
        # the next free suffix rather than deleting it.
        n = 2
        while os.path.exists(target):
            target = f"{step_dir}{_QUARANTINE_SUFFIX}.{n}"
            n += 1
        logger.error(
            "Quarantining corrupt checkpoint %s -> %s (%s); falling back "
            "to the previous step",
            step_dir, target, reason,
        )
        _save, _restore, _saves, quarantines = _ckpt_metrics()
        quarantines.inc()
        obs.journal().record(
            "checkpoint_quarantined", path=step_dir, reason=reason
        )
        try:
            os.rename(step_dir, target)
        except OSError:
            logger.exception("Quarantine rename failed for %s", step_dir)

    def sweep_stale_tmp(self, grace_s: float = STALE_TMP_GRACE_S):
        """Startup sweep: tmp dirs left by crashed saves (the very
        scenario checkpoints exist for) would otherwise pile up forever.
        Age-guarded — in a multi-process world a peer may be mid-save."""
        try:
            names = os.listdir(self._dir)
        except OSError:
            return
        for name in names:
            if not (name.startswith("step_") and ".tmp" in name):
                continue
            path = os.path.join(self._dir, name)
            try:
                stale = time.time() - os.path.getmtime(path) > grace_s
            except OSError:
                continue  # a peer committed (renamed) it mid-sweep
            if stale:
                logger.warning(
                    "Sweeping stale checkpoint tmp dir %s (crashed save)",
                    path,
                )
                shutil.rmtree(path, ignore_errors=True)

    def _garbage_collect(self):
        # Best-effort: by the time GC runs the new checkpoint is already
        # durable, so a transient I/O blip here must not crash the save
        # (the raise-on-transient policy in steps() protects RESTORES).
        try:
            steps = self.steps()
            for step in steps[: -self._keep_max]:
                shutil.rmtree(self._step_dir(step), ignore_errors=True)
        except OSError:
            logger.exception(
                "Checkpoint GC failed (transient I/O error?); old "
                "snapshots will be collected on a later save"
            )
        self.sweep_stale_tmp()
