"""Traffic generator `criteo-etrf`: a traffic file's `data` block -> one
ETRF file of fixed-width Criteo records, made from the seed.

A record is 13 f32 dense, 26 i32 ids, 1 u8 label (`model_zoo/deepfm`
`criteo_record_layout`).  The ETRF framing is copied from
`elasticdl_tpu/data/recordfile.py` (header `ETRF`+u32 version; per record
u32 length + u32 crc32 + payload; index of u64 offsets; footer u64 count +
u64 index offset + `FTRE`) so that the yardstick does not move with the
program.  Ids are truncated-Zipf(s) per field, the distribution that
`model_zoo/datasets.synthetic_ctr_columns` draws by inverse CDF (id 0
hottest; the table offsets fields apart, so hot sets are disjoint rows).

The file is made in blocks of `BLOCK` records, block b from the seed
(seed, b), by `data["workers"]` processes that each write their blocks at
their own offsets (records are of one width): the same seed gives the same
file whatever the number of workers, which the traffic file states and
which is never taken from the machine's core count.
"""

from __future__ import annotations

import glob
import os
import struct
import zlib
import multiprocessing

import numpy as np

NUM_DENSE, NUM_CAT = 13, 26
WIDTH = 4 * NUM_DENSE + 4 * NUM_CAT + 1
FRAMED = 8 + WIDTH
HEADER = 8
BLOCK = 262144
#: The codec the worker's log has to name for this file.
CODEC = "native"
#: Data files kept in the cache directory (2.2 GB each at the cell's
#: size): the newest, so a repeated seed comes back and the disk stays small.
KEEP_FILES = 2


def criteo_columns(n: int, vocab: int, zipf_s: float, seed):
    """(dense [n,13] f32, ids [n,26] i32, label [n] u8) from the seed.  The
    label follows a rule the model can learn (one dense feature and the
    parity of one id), so a falling loss means something."""
    rng = np.random.default_rng(seed)
    dense = rng.random((n, NUM_DENSE), dtype=np.float32)
    if zipf_s > 0.0:
        # n x 26 independent draws, made as what they are once sorted: a
        # multinomial count for every id, laid out in a uniformly random
        # order.  The same distribution as one inverse-CDF lookup a draw,
        # at a fifth of the time.
        pmf = 1.0 / np.power(np.arange(1, vocab + 1, dtype=np.float64), zipf_s)
        counts = rng.multinomial(n * NUM_CAT, pmf / pmf.sum())
        ids = rng.permutation(
            np.repeat(np.arange(vocab, dtype=np.int32), counts)
        ).reshape(n, NUM_CAT)
    else:
        ids = rng.integers(0, vocab, size=(n, NUM_CAT), dtype=np.int32)
    label = ((dense[:, 0] + 0.5 * (ids[:, 0] % 2)) > 0.75).astype(np.uint8)
    return dense, ids, label


def framed_block(n: int, vocab: int, zipf_s: float, seed) -> np.ndarray:
    """[n, FRAMED] u8: each record's length, crc32 and payload."""
    dense, ids, label = criteo_columns(n, vocab, zipf_s, seed)
    framed = np.empty((n, FRAMED), np.uint8)
    framed[:, :4] = np.frombuffer(struct.pack("<I", WIDTH), np.uint8)
    payload = framed[:, 8:]
    payload[:, :4 * NUM_DENSE] = dense.view(np.uint8)
    payload[:, 4 * NUM_DENSE:-1] = ids.view(np.uint8)
    payload[:, -1] = label
    crcs = np.fromiter(
        (zlib.crc32(row) for row in payload), np.uint32, count=n
    )
    framed[:, 4:8] = crcs.astype("<u4").view(np.uint8).reshape(n, 4)
    return framed


def _write_blocks(path: str, blocks: list, vocab, zipf_s, seed) -> None:
    with open(path, "r+b") as f:
        for first, n in blocks:
            framed = framed_block(n, vocab, zipf_s, (seed, first // BLOCK))
            f.seek(HEADER + first * FRAMED)
            f.write(framed.tobytes())


def write_file(path: str, n: int, vocab: int, zipf_s: float, seed: int,
               workers: int) -> None:
    """An ETRF file of n records, atomically."""
    tmp = f"{path}.{os.getpid()}.tmp"
    index_offset = HEADER + n * FRAMED
    with open(tmp, "wb") as f:
        f.write(struct.pack("<4sI", b"ETRF", 1))
        f.seek(index_offset)
        f.write((HEADER + np.arange(n, dtype="<u8") * FRAMED).tobytes())
        f.write(struct.pack("<QQ4s", n, index_offset, b"FTRE"))
    blocks = [(first, min(BLOCK, n - first)) for first in range(0, n, BLOCK)]
    # Forked, so that this file need not be importable by a name.
    fork = multiprocessing.get_context("fork")
    procs = [
        fork.Process(
            target=_write_blocks,
            args=(tmp, blocks[i::workers], vocab, zipf_s, seed),
        )
        for i in range(min(workers, len(blocks)))
    ]
    for proc in procs:
        proc.start()
    for proc in procs:
        proc.join()
    if any(proc.exitcode != 0 for proc in procs):
        os.remove(tmp)
        raise RuntimeError("a process writing the data file failed")
    os.replace(tmp, path)


def training_data(cache_dir: str, data: dict, model: dict, seed: int):
    """-> the job's `--training_data` value.  The file is
    written once per (seed, sizes) under the benchmark's cache directory and
    read through once, so that the window reads the page cache either way."""
    n, zipf_s = int(data["records"]), float(data["zipf_s"])
    vocab = int(model["vocab_size"])
    path = os.path.join(
        cache_dir, f"criteo_n{n}_v{vocab}_s{zipf_s:g}_seed{seed}.etrf"
    )
    if not os.path.exists(path):
        os.makedirs(cache_dir, exist_ok=True)
        write_file(path, n, vocab, zipf_s, seed, int(data["workers"]))
        cached = sorted(
            glob.glob(os.path.join(cache_dir, "criteo_*.etrf")),
            key=os.path.getmtime,
        )
        for old in cached[:-KEEP_FILES]:
            os.remove(old)
    with open(path, "rb") as f:
        while f.read(1 << 24):
            pass
    return f"recordio:{path}"
