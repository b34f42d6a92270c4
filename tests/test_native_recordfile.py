"""Native <-> Python ETRF codec parity tests.

The C++ codec (native/recordfile.cc) must be byte-identical with the
pure-Python reference implementation (data/recordfile.py) in both
directions: files written by either are read by both, CRC corruption is
detected by both, and range semantics (clamping, empty) match.
"""

import os

import numpy as np
import pytest

from elasticdl_tpu import native
from elasticdl_tpu.data import recordfile

pytestmark = pytest.mark.skipif(
    native.record_file() is None,
    reason="no C++ toolchain; native codec unavailable",
)

RECORDS = [
    b"hello",
    b"",
    b"x" * 5000,
    np.arange(64, dtype=np.int32).tobytes(),
    b"\x00\xff" * 33,
]


def test_python_written_native_read(tmp_path):
    path = str(tmp_path / "py.etrf")
    recordfile.write_records(path, RECORDS)  # pure-Python writer
    codec = native.record_file()
    assert codec.count_records(path) == len(RECORDS)
    assert list(codec.read_range(path, 0, len(RECORDS))) == RECORDS
    # Range semantics: clamping + interior slice + empty.
    assert list(codec.read_range(path, 2, 4)) == RECORDS[2:4]
    assert list(codec.read_range(path, -3, 99)) == RECORDS
    assert list(codec.read_range(path, 4, 4)) == []


def test_native_written_python_read(tmp_path):
    path = str(tmp_path / "native.etrf")
    codec = native.record_file()
    assert codec.write_records(path, RECORDS) == len(RECORDS)
    # Force the pure-Python read path for the parity check.
    assert recordfile._count_records_py(path) == len(RECORDS)
    assert list(recordfile._read_range_py(path, 0, len(RECORDS))) == RECORDS


def test_native_written_byte_identical_to_python(tmp_path):
    py_path = str(tmp_path / "py.etrf")
    native_path = str(tmp_path / "native.etrf")
    recordfile.write_records(py_path, RECORDS)
    native.record_file().write_records(native_path, RECORDS)
    with open(py_path, "rb") as a, open(native_path, "rb") as b:
        assert a.read() == b.read()


def test_crc_corruption_detected_by_both(tmp_path):
    path = str(tmp_path / "corrupt.etrf")
    recordfile.write_records(path, [b"payload-one", b"payload-two"])
    # Flip one payload byte of record 0 (after 8B header + 8B record head).
    with open(path, "r+b") as f:
        f.seek(8 + 8 + 2)
        byte = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([byte[0] ^ 0xFF]))
    with pytest.raises(IOError, match="CRC"):
        list(native.record_file().read_range(path, 0, 2))
    with pytest.raises(recordfile.RecordFileError, match="CRC"):
        list(recordfile._read_range_py(path, 0, 2))


def test_corrupt_length_field_is_an_error_not_an_overflow(tmp_path):
    """A flipped bit in a record's LENGTH field must surface as a clean
    error: the native reader bounds every record against the caller's
    buffer before writing (a naive implementation heap-overflows here)."""
    path = str(tmp_path / "len.etrf")
    recordfile.write_records(path, [b"abcdef", b"ghijkl"])
    with open(path, "r+b") as f:
        f.seek(8)  # record 0's u32 length field
        f.write((6 | 0x40000000).to_bytes(4, "little"))
    with pytest.raises(IOError, match="length|truncated"):
        list(native.record_file().read_range(path, 0, 2))


def test_bad_files_rejected(tmp_path):
    codec = native.record_file()
    garbage = tmp_path / "garbage.bin"
    garbage.write_bytes(b"not a record file at all")
    with pytest.raises(IOError):
        codec.count_records(str(garbage))
    with pytest.raises(IOError):
        codec.count_records(str(tmp_path / "missing.etrf"))


def test_reader_dispatches_to_native(tmp_path, monkeypatch):
    """data/recordfile.py's public functions use the native codec when
    built — the docstring's promise, previously unimplemented."""
    path = str(tmp_path / "dispatch.etrf")
    recordfile.write_records(path, RECORDS)
    calls = []
    codec = native.record_file()
    real = codec.read_range

    def spy(path, start, end):
        calls.append((start, end))
        return real(path, start, end)

    monkeypatch.setattr(codec, "read_range", spy)
    assert list(recordfile.read_range(path, 1, 3)) == RECORDS[1:3]
    assert calls == [(1, 3)]
    # Escape hatch: the env var forces the Python codec.
    monkeypatch.setenv("ELASTICDL_DISABLE_NATIVE", "1")
    assert list(recordfile.read_range(path, 1, 3)) == RECORDS[1:3]
    assert calls == [(1, 3)]


# ---------------------------------------------------------------------------
# Index access is O(range): the entries a call needs, checked as read
# ---------------------------------------------------------------------------

N_INDEXED = 9000


def _varied(count):
    # Lengths 0..36, so that offsets are uneven and some payloads empty.
    return (bytes([i % 251]) * (i % 37) for i in range(count))


@pytest.fixture(scope="module")
def indexed_file(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("etrf") / "indexed.etrf")
    recordfile.write_records(path, _varied(N_INDEXED))
    return path


@pytest.mark.parametrize("start, end", [
    pytest.param(0, 10, id="start-0"),
    pytest.param(N_INDEXED - 10, N_INDEXED, id="end-is-count"),
    pytest.param(4321, 4322, id="single-record"),
    pytest.param(N_INDEXED - 1, N_INDEXED, id="last-record"),
    pytest.param(500, 500, id="empty"),
    pytest.param(700, 600, id="inverted"),
    pytest.param(-5, 3, id="clamped-start"),
    pytest.param(N_INDEXED - 5, 10**6, id="clamped-end"),
    pytest.param(N_INDEXED, N_INDEXED + 100, id="past-the-end"),
    pytest.param(100, 300, id="interior"),
    pytest.param(0, N_INDEXED, id="whole-file-three-chunks"),
])
def test_native_range_is_byte_identical_to_python_codec(
    indexed_file, start, end,
):
    expected = list(recordfile._read_range_py(indexed_file, start, end))
    assert list(
        native.record_file().read_range(indexed_file, start, end)
    ) == expected
    assert len(expected) == max(
        0, min(end, N_INDEXED) - max(start, 0))


@pytest.mark.parametrize("bytes_cap", [64, 1000, 20000])
def test_range_split_by_chunk_bytes_halving_matches_python_codec(
    indexed_file, monkeypatch, bytes_cap,
):
    """A chunk over CHUNK_BYTES is halved until it fits (down to one
    record, which may exceed a tiny cap): more, smaller chunks, the
    same records."""
    codec = native.record_file()
    monkeypatch.setattr(codec, "CHUNK_BYTES", bytes_cap)
    chunks = list(codec.read_range_buffers(indexed_file, 4000, 8200))
    assert len(chunks) > 2
    assert all(
        buf.size <= bytes_cap or len(lengths) == 1
        for buf, lengths in chunks
    )
    got = [
        bytes(buf[offset - int(length):offset])
        for buf, lengths in chunks
        for offset, length in zip(np.cumsum(lengths, dtype=np.int64), lengths)
    ]
    assert got == list(recordfile._read_range_py(indexed_file, 4000, 8200))


def _index_bytes_of(codec, monkeypatch, path, start, end):
    """`index_bytes` of the task's `data.read` span."""
    spans = []
    monkeypatch.setattr(
        native.tracing, "record_child_span",
        lambda name, ts, dur, **fields: spans.append((name, fields)),
    )
    records = sum(
        len(lengths)
        for _, lengths in codec.read_range_buffers(path, start, end)
    )
    assert records == end - start
    (name, fields), = spans
    assert name == "data.read" and fields["records"] == records
    return fields["index_bytes"]


@pytest.mark.parametrize("count", [1000, N_INDEXED, 120000])
def test_index_bytes_read_do_not_grow_with_the_file(
    tmp_path, monkeypatch, count,
):
    """[100, 300) needs entry 100 and entry 300: 16 bytes (the issue
    allows 64) whatever the file's size (the whole-index load read 8 x count: 72,000 at 9,000
    records, 105 MB at the benchmark's 13.1M)."""
    path = str(tmp_path / "grow.etrf")
    recordfile.write_records(path, (b"r" * 24 for _ in range(count)))
    assert _index_bytes_of(
        native.record_file(), monkeypatch, path, 100, 300) == 16


def test_index_entries_are_not_read_twice_across_chunks(
    indexed_file, monkeypatch,
):
    """Three chunks of one handle: a chunk's start is the chunk
    before's end (in the memo), and the file's end is the footer's
    index_offset (no entry)."""
    read = _index_bytes_of(
        native.record_file(), monkeypatch, indexed_file, 0, N_INDEXED)
    assert read == 8 * 3


def _footer(path):
    size = os.path.getsize(path)
    with open(path, "rb") as f:
        f.seek(size - 20)
        raw = f.read(20)
    return (int.from_bytes(raw[:8], "little"),
            int.from_bytes(raw[8:16], "little"), size)


def _set_index_entry(path, i, value):
    _, index_offset, _ = _footer(path)
    with open(path, "r+b") as f:
        f.seek(index_offset + 8 * i)
        f.write(int(value).to_bytes(8, "little"))


def _entry(path, i):
    _, index_offset, _ = _footer(path)
    with open(path, "rb") as f:
        f.seek(index_offset + 8 * i)
        return int.from_bytes(f.read(8), "little")


@pytest.mark.parametrize("entry, value", [
    # Non-monotonic: the range's end boundary lies before its start.
    pytest.param(300, lambda path: _entry(path, 50), id="non-monotonic"),
    # Monotonic, but too close to hold 200 record heads.
    pytest.param(300, lambda path: _entry(path, 100) + 8,
                 id="too-close-for-the-heads"),
    pytest.param(100, lambda path: 0, id="inside-the-header"),
    pytest.param(100, lambda path: _footer(path)[1] + 16,
                 id="inside-the-index"),
    pytest.param(300, lambda path: _footer(path)[2] + 4096,
                 id="past-the-file"),
    pytest.param(100, lambda path: 2**63 + 5, id="huge"),
])
def test_corrupt_index_entry_is_an_ioerror_never_a_seek_into_garbage(
    tmp_path, entry, value,
):
    path = str(tmp_path / "badindex.etrf")
    recordfile.write_records(path, _varied(1000))
    _set_index_entry(path, entry, value(path))
    codec = native.record_file()
    with pytest.raises(IOError, match="corrupt index"):
        list(codec.read_range(path, 100, 300))
    # Through the dispatching reader it is the codec's own error type,
    # and ranges that do not touch the entry still read.
    with pytest.raises(recordfile.RecordFileError, match="corrupt index"):
        list(recordfile.read_range(path, 100, 300))
    assert list(codec.read_range(path, 400, 410)) == list(
        recordfile._read_range_py(path, 400, 410))


def _drop_an_index_entry(data, count, index_offset):
    return data[:index_offset] + data[index_offset + 8:]


def _claim_one_more_record(data, count, index_offset):
    return data[:-20] + (count + 1).to_bytes(8, "little") + data[-12:]


def _claim_a_huge_count(data, count, index_offset):
    return data[:-20] + (2**61 + 7).to_bytes(8, "little") + data[-12:]


def _index_offset_past_the_file(data, count, index_offset):
    return data[:-12] + (len(data) + 8).to_bytes(8, "little") + data[-4:]


def _index_offset_inside_the_header(data, count, index_offset):
    return data[:-12] + (4).to_bytes(8, "little") + data[-4:]


@pytest.mark.parametrize("damage", [
    _drop_an_index_entry, _claim_one_more_record, _claim_a_huge_count,
    _index_offset_past_the_file, _index_offset_inside_the_header,
], ids=lambda f: f.__name__.strip("_"))
def test_file_size_that_contradicts_its_footer_fails_at_open(
    tmp_path, damage,
):
    """What the whole-index load gave for free: an index that is not
    all there is found out before any entry is trusted."""
    path = str(tmp_path / "short.etrf")
    recordfile.write_records(path, _varied(1000))
    count, index_offset, _ = _footer(path)
    with open(path, "rb") as f:
        data = f.read()
    with open(path, "wb") as f:
        f.write(damage(data, count, index_offset))
    codec = native.record_file()
    with pytest.raises(IOError, match="truncated index"):
        codec.count_records(path)
    with pytest.raises(IOError, match="truncated index"):
        list(codec.read_range(path, 0, 10))
    with pytest.raises(recordfile.RecordFileError, match="truncated index"):
        list(recordfile.read_range_buffers(path, 0, 10))


def test_abi_version_is_three_on_both_sides():
    assert native._ABI_VERSION == 3
    assert native.load().edl_abi_version() == 3


def test_stale_abi2_library_with_a_fresher_mtime_is_rebuilt_not_bound(
    tmp_path, monkeypatch,
):
    """A parent checkout's `libedl_kernels.so` left beside the sources
    (it is git-ignored, and rebuilt by mtime only) answers ABI 2 and
    lacks `edl_rf_index_bytes_read`: `load()` must rebuild, not bind."""
    import subprocess

    stale = tmp_path / "libedl_kernels.so"
    source = tmp_path / "stale.cc"
    source.write_text(
        'extern "C" long long edl_abi_version() { return 2; }\n')
    subprocess.run(
        ["g++", "-shared", "-fPIC", str(source), "-o", str(stale)],
        check=True, capture_output=True, timeout=120,
    )
    assert os.path.getmtime(stale) >= max(
        os.path.getmtime(src) for src in native._SOURCES)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SO_PATH", str(stale))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_failed", False)
    builds = []
    build_native = native.build_native

    def spy(force=False):
        builds.append(force)
        return build_native(force)

    monkeypatch.setattr(native, "build_native", spy)
    lib = native.load()
    # Kept by mtime, refused by its ABI, rebuilt over the same path.
    assert builds == [False, True]
    assert lib is not None and lib.edl_abi_version() == 3
    assert hasattr(lib, "edl_rf_index_bytes_read")
