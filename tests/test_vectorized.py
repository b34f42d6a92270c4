"""Vectorized data-plane tests: RecordLayout round-trips and the
buffer-level ETRF read path (native codec and Python fallback produce
identical chunks; parse_buffer matches per-record parsing)."""

import pytest

# Tier-1 fast gate runs `-m 'not slow'` (see Makefile test-fast).
pytestmark = pytest.mark.slow

import numpy as np
import pytest

from elasticdl_tpu.data import recordfile
from elasticdl_tpu.data.vectorized import RecordLayout

LAYOUT = RecordLayout([
    ("dense", np.float32, 13),
    ("cat", np.int32, 26),
    ("label", np.uint8, 1),
])


def _records(n, seed=0):
    rng = np.random.RandomState(seed)
    return [
        LAYOUT.pack(
            dense=rng.rand(13).astype(np.float32),
            cat=rng.randint(0, 1 << 20, size=26),
            label=[i % 2],
        )
        for i in range(n)
    ]


def test_pack_parse_roundtrip():
    recs = _records(32, seed=1)
    cols = LAYOUT.parse_batch(recs)
    assert cols["dense"].shape == (32, 13)
    assert cols["cat"].shape == (32, 26)
    np.testing.assert_array_equal(cols["label"][:, 0], np.arange(32) % 2)
    # Field values survive bit-exactly.
    one = LAYOUT.parse_batch([recs[7]])
    np.testing.assert_array_equal(one["cat"][0], cols["cat"][7])
    np.testing.assert_array_equal(one["dense"][0], cols["dense"][7])


def test_parse_batch_rejects_ragged():
    with pytest.raises(ValueError, match="fixed-width"):
        LAYOUT.parse_batch([b"short"])


def test_read_range_buffers_matches_per_record(tmp_path):
    recs = _records(300, seed=2)
    path = str(tmp_path / "v.etrf")
    recordfile.write_records(path, recs)

    per_record = list(recordfile.read_range(path, 25, 275))
    chunks = list(recordfile.read_range_buffers(path, 25, 275))
    assert sum(len(lengths) for _, lengths in chunks) == 250
    joined = b"".join(bytes(buf) for buf, _ in chunks)
    assert joined == b"".join(per_record)

    # Columnar parse over the buffer chunks == per-record parse.
    cols = [LAYOUT.parse_buffer(buf, lengths) for buf, lengths in chunks]
    cat = np.concatenate([c["cat"] for c in cols])
    ref = LAYOUT.parse_batch(per_record)
    np.testing.assert_array_equal(cat, ref["cat"])


def test_read_range_buffers_python_fallback(tmp_path, monkeypatch):
    recs = _records(100, seed=3)
    path = str(tmp_path / "f.etrf")
    recordfile.write_records(path, recs)
    native = list(recordfile.read_range_buffers(path, 0, 100))
    monkeypatch.setattr(recordfile, "_native", lambda: None)
    fallback = list(recordfile.read_range_buffers(path, 0, 100))
    assert b"".join(bytes(b) for b, _ in native) == b"".join(
        bytes(b) for b, _ in fallback
    )
    assert np.concatenate([l for _, l in native]).tolist() == (
        np.concatenate([l for _, l in fallback]).tolist()
    )


def test_read_range_buffers_max_bytes_budget(tmp_path, monkeypatch):
    """`max_bytes` (round 5): the native codec honors a whole-task
    budget (one chunk) and splits under a small one; the Python
    fallback deliberately caps at its default streaming bound (memory —
    see recordfile.read_range_buffers) — both yield identical DATA at
    any budget."""
    recs = _records(100, seed=5)
    path = str(tmp_path / "g.etrf")
    recordfile.write_records(path, recs)
    rec_bytes = len(recs[0])

    whole = list(recordfile.read_range_buffers(path, 0, 100,
                                               max_bytes=1 << 30))
    assert len(whole) == 1  # native: whole task, one chunk
    small = list(recordfile.read_range_buffers(path, 0, 100,
                                               max_bytes=10 * rec_bytes))
    assert len(small) > 1  # budget smaller than the task splits

    def payload(chunks):
        return b"".join(bytes(b) for b, _ in chunks)

    assert payload(whole) == payload(small)
    monkeypatch.setattr(recordfile, "_native", lambda: None)
    for budget in (1 << 30, 10 * rec_bytes, 0):
        fallback = list(recordfile.read_range_buffers(path, 0, 100,
                                                      max_bytes=budget))
        assert payload(fallback) == payload(whole)
        assert np.concatenate([l for _, l in fallback]).tolist() == (
            np.concatenate([l for _, l in whole]).tolist()
        )


def test_parse_buffer_length_validation():
    recs = _records(4)
    buf = np.frombuffer(b"".join(recs), np.uint8)
    with pytest.raises(ValueError, match="fixed-width"):
        LAYOUT.parse_buffer(buf, lengths=[1, 2, 3, 4])
    with pytest.raises(ValueError, match="multiple"):
        LAYOUT.parse_buffer(buf[:-1])


def test_deepfm_trains_from_criteo_etrf_file(tmp_path):
    """Binary-file ingestion e2e: a Criteo-layout ETRF file trains the
    DeepFM config through the real CLI Local path via the vectorized
    reader (loss decreases => parsing wired features correctly)."""
    import subprocess
    import sys

    from model_zoo.deepfm.deepfm_functional_api import (
        NUM_CAT,
        NUM_DENSE,
        criteo_record_layout,
    )

    layout = criteo_record_layout()
    rng = np.random.RandomState(0)
    n = 512
    # Learnable structure: label depends on dense[0] and cat[0] parity.
    recs = []
    for _ in range(n):
        dense = rng.rand(NUM_DENSE).astype(np.float32)
        cat = rng.randint(0, 100, size=NUM_CAT).astype(np.int32)
        label = int(dense[0] + 0.3 * (cat[0] % 2) > 0.65)
        recs.append(layout.pack(dense=dense, cat=cat, label=[label]))
    path = str(tmp_path / "criteo.etrf")
    recordfile.write_records(path, recs)

    proc = subprocess.run(
        [
            sys.executable, "-m", "elasticdl_tpu.client.main", "train",
            "--distribution_strategy=Local",
            "--model_zoo=model_zoo",
            "--model_def=deepfm.deepfm_functional_api",
            "--model_params=vocab_size=100",
            f"--training_data={path}",
            "--records_per_task=128",
            "--num_epochs=4",
            "--minibatch_size=32",
        ],
        capture_output=True,
        text=True,
        timeout=600,
        env={
            **__import__("os").environ,
            "JAX_PLATFORMS": "cpu",
        },
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    import re

    losses = [
        float(m) for m in re.findall(r"loss=([0-9.]+)", proc.stderr)
    ]
    assert len(losses) >= 8
    assert losses[-1] < losses[0] * 0.9, (losses[:2], losses[-2:])


def test_criteo_reader_implements_reader_surface(tmp_path):
    """The collective worker needs shard_names()/metadata (AbstractDataReader
    surface) — the reader must not be Local-only."""
    from model_zoo.deepfm.deepfm_functional_api import CriteoRecordReader

    path = str(tmp_path / "s.etrf")
    recordfile.write_records(path, _records(10))
    reader = CriteoRecordReader(path)
    assert reader.shard_names() == [path]
    assert reader.create_shards() == {path: 10}
    assert hasattr(reader, "metadata")
