"""Shardable binary record file format ("ETRF") — pure-Python codec.

Parity: the reference depends on RecordIO (external C++/Go, pyrecordio) as
its shard-addressable record format.  ETRF is this framework's equivalent:

    header:  magic b"ETRF" + u32 version (little-endian)
    record:  u32 payload_length + u32 crc32(payload) + payload bytes
    footer:  u64 record_count + u64 index_offset + magic b"FTRE"
             where index (at index_offset) is record_count u64 file offsets

The index footer makes `count_records` and `read_range` O(1) seeks instead
of scans — that is what makes dynamic sharding cheap for the master.  Both
codecs read only the index entries a range needs (its first record's, and
for the native codec's size query the end boundary's), never the whole
index: a task's cost does not grow with the file.  The native C++
implementation (elasticdl_tpu/native/recordfile.cc) reads and
writes the same format and is preferred automatically when the toolchain
built it (`read_range`/`count_records` dispatch below); this module is the
always-available fallback and the reference implementation for parity
tests (tests/test_native_recordfile.py).  Set ELASTICDL_DISABLE_NATIVE=1
to force the Python codec.
"""

from __future__ import annotations

import os
import struct
import zlib
from typing import Iterator, List


def _native():
    if os.environ.get("ELASTICDL_DISABLE_NATIVE"):
        return None
    from elasticdl_tpu import native as native_mod

    return native_mod.record_file()


MAGIC = b"ETRF"
FOOTER_MAGIC = b"FTRE"
VERSION = 1

_HEADER = struct.Struct("<4sI")       # magic, version
_RECORD_HEAD = struct.Struct("<II")   # length, crc32
_FOOTER = struct.Struct("<QQ4s")      # record_count, index_offset, magic


class RecordFileError(IOError):
    pass


class Writer:
    def __init__(self, path: str):
        self._file = open(path, "wb")
        self._file.write(_HEADER.pack(MAGIC, VERSION))
        self._offsets: List[int] = []

    def write(self, payload: bytes):
        if not isinstance(payload, (bytes, bytearray, memoryview)):
            raise TypeError("record payload must be bytes")
        payload = bytes(payload)
        self._offsets.append(self._file.tell())
        self._file.write(_RECORD_HEAD.pack(len(payload), zlib.crc32(payload)))
        self._file.write(payload)

    def close(self):
        index_offset = self._file.tell()
        for offset in self._offsets:
            self._file.write(struct.pack("<Q", offset))
        self._file.write(_FOOTER.pack(len(self._offsets), index_offset, FOOTER_MAGIC))
        self._file.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def write_records(path: str, records) -> int:
    with Writer(path) as writer:
        count = 0
        for record in records:
            writer.write(record)
            count += 1
    return count


def _read_footer(f) -> tuple:
    f.seek(0, os.SEEK_END)
    size = f.tell()
    if size < _HEADER.size + _FOOTER.size:
        raise RecordFileError("File too small to be an ETRF record file")
    f.seek(size - _FOOTER.size)
    count, index_offset, magic = _FOOTER.unpack(f.read(_FOOTER.size))
    if magic != FOOTER_MAGIC:
        raise RecordFileError("Bad footer magic (truncated or not an ETRF file)")
    return count, index_offset


def count_records(path: str) -> int:
    native = _native()
    if native is not None:
        try:
            return native.count_records(path)
        except RecordFileError:
            raise
        except OSError as e:
            raise RecordFileError(str(e)) from e
    return _count_records_py(path)


def _count_records_py(path: str) -> int:
    with open(path, "rb") as f:
        header = f.read(_HEADER.size)
        magic, _version = _HEADER.unpack(header)
        if magic != MAGIC:
            raise RecordFileError(f"Bad magic in {path}")
        count, _ = _read_footer(f)
        return count


def read_range(path: str, start: int, end: int) -> Iterator[bytes]:
    """Yield records [start, end) using the index footer to seek directly.
    Dispatches to the native C++ codec when built (one C call per range)."""
    native = _native()
    if native is not None:
        try:
            yield from native.read_range(path, start, end)
        except RecordFileError:
            raise
        except OSError as e:
            raise RecordFileError(str(e)) from e
        return
    yield from _read_range_py(path, start, end)


def _read_range_py(path: str, start: int, end: int) -> Iterator[bytes]:
    with open(path, "rb") as f:
        magic, _version = _HEADER.unpack(f.read(_HEADER.size))
        if magic != MAGIC:
            raise RecordFileError(f"Bad magic in {path}")
        count, index_offset = _read_footer(f)
        start = max(0, start)
        end = min(end, count)
        if start >= end:
            return
        f.seek(index_offset + 8 * start)
        first_offset = struct.unpack("<Q", f.read(8))[0]
        f.seek(first_offset)
        for _ in range(end - start):
            length, crc = _RECORD_HEAD.unpack(f.read(_RECORD_HEAD.size))
            payload = f.read(length)
            if len(payload) != length:
                raise RecordFileError("Truncated record")
            if zlib.crc32(payload) != crc:
                raise RecordFileError("CRC mismatch (corrupt record)")
            yield payload


def read_all(path: str) -> Iterator[bytes]:
    yield from read_range(path, 0, count_records(path))


def read_range_buffers(path: str, start: int, end: int,
                       max_bytes: int = 0):
    """Yield (payload_buffer np.uint8, lengths np.uint32) chunks of
    records [start, end) — the vectorized data-plane path: payloads ride
    one contiguous buffer per chunk with NO per-record Python objects,
    feeding data/vectorized.py's RecordLayout.parse_buffer directly.
    Native codec when built; Python fallback assembles equivalent
    chunks.

    `max_bytes` overrides the default per-chunk payload bound.
    Consumers that concatenate the chunks anyway (the columnar task
    path) pass their whole-task budget: one chunk instead of N both
    skips the concatenate pass and HALVES peak memory (no chunks+copy
    coexistence) — at image record sizes that pass was ~20% of the
    host pipeline."""
    import numpy as np

    native = _native()
    if native is not None:
        try:
            yield from native.read_range_buffers(
                path, start, end, max_bytes=max_bytes
            )
        except RecordFileError:
            raise
        except OSError as e:
            raise RecordFileError(str(e)) from e
        return
    # Same chunk bounds as the native codec (one source of truth).
    from elasticdl_tpu.native import NativeRecordFile

    # The fallback IGNORES a larger max_bytes: it accumulates per-record
    # bytes objects before the join, so honoring a 1 GiB budget would
    # hold the object list AND the joined copy simultaneously (~2x task
    # bytes + object overhead) — the opposite of the memory win the
    # budget buys on the native path.  Downstream columnar consumers
    # already handle multi-chunk results (they concatenate), so a
    # smaller-than-requested chunking is always correct.
    max_records = NativeRecordFile.CHUNK_RECORDS
    max_bytes = min(max_bytes or NativeRecordFile.CHUNK_BYTES,
                    NativeRecordFile.CHUNK_BYTES)

    def emit(records):
        buf = np.frombuffer(b"".join(records), np.uint8)
        return buf, np.asarray([len(r) for r in records], np.uint32)

    # The same `data.read` span the native codec journals, one a task:
    # the seconds spent in here between the consumer's pulls, summed
    # (this codec seeks to one index entry: no index load to name).
    import time

    from elasticdl_tpu.obs import tracing

    task = {"records": 0, "payload_bytes": 0}

    def chunks():
        chunk_records: list = []
        chunk_bytes = 0
        for payload in _read_range_py(path, start, end):
            chunk_records.append(payload)
            chunk_bytes += len(payload)
            task["records"] += 1
            task["payload_bytes"] += len(payload)
            if len(chunk_records) >= max_records or chunk_bytes >= max_bytes:
                yield emit(chunk_records)
                chunk_records, chunk_bytes = [], 0
        if chunk_records:
            yield emit(chunk_records)

    start_ts, read_s, made = time.time(), 0.0, chunks()
    try:
        while True:
            resumed = time.monotonic()
            chunk = next(made, None)
            read_s += time.monotonic() - resumed
            if chunk is None:
                return
            yield chunk
    finally:
        tracing.record_child_span(
            "data.read", start_ts, read_s, index_bytes=0, opens=1, **task
        )
