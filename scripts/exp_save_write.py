"""Chip experiment: what the host memory a save writes from is worth.

Builds the training state of a benchmark cell as its job does (the
cell's `job` flags from `perfbench/configs/<configuration>.json` through
the worker's own parser, `load_model_spec` and `DataParallelTrainer`;
weights from `--seed`; with `--steps n`, n real train steps first, so
that every leaf lies as the train program leaves it) and writes it to a
fresh file on the machine's disk by each way, interleaved, `--repeats`
times:

- `whole`: a leaf crosses whole (`LeafStream()`), its host copy as large
  as the leaf and, over the allocator's mmap threshold, mapped anew;
- `pieces`: a leaf over 16 MiB is cut on the device (`LeafCutter`) and
  crosses piece by piece, each host copy small enough for the allocator
  to hand the same memory out again;
- `ring`: leaves cross whole, and the writer's helper thread copies each
  piece of a large one into one of two staging buffers that live as long
  as the writer; the file takes its bytes from those (`RingWriter`,
  this script's own: measured, not built into the saver).

One JSON line a save on stdout: the wall time, the time the stream
waited for the device (`gather_s`) and the rest (`write_s`), the write's
GB/s, `lookahead_peak_bytes`, `pieces`, `recycled_bytes / bytes` (the
share of the file's bytes taken from an address range it had taken bytes
from before), `copied_bytes`, and the file's CRC32, which has to be the
same by every way.  The table also goes to `chiprun_out/`.

Between two saves the script waits for the host to write the last one
back (`os.sync()`): a guest holds 15-20 GB of dirty pages and then
takes writes at the disk's 0.35-0.47 GB/s whatever the way (this
script's first call, `PERF.md` section 6 PR 50), and a cell's save
starts from a clean page cache.  A `chiprun` call may write 45 GiB in
all, deleted files included: the script stops before `--budget_gb`.

A machine's third save of 8 GB within a minute reads the disk whatever
the way and whatever was synced: give a way a first or second place in
a call of its own (`--ways ring whole`) before believing its number.

Usage (the cells first: `--ways` takes what follows it):
       chiprun -- python scripts/exp_save_write.py \\
           gpt2-medium.train-synth laguna-xs2.train-synth-8k --steps 1
       python scripts/exp_save_write.py laguna-xs2.train-synth-8k --tiny
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import zlib

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

WAYS = ("whole", "pieces", "ring")


def cell_config(cell: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = next(w["config"] for w in bench["workloads"] if w["name"] == cell)
    path = next(c["file"] for c in bench["configs"] if c["name"] == config)
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def build_trainer(config: dict, seed: int):
    """The cell's trainer with its state on the device, and one batch."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.common.args import parse_worker_args
    from elasticdl_tpu.common.model_utils import load_model_spec
    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer

    args = parse_worker_args([
        "--model_zoo=" + os.path.join(ROOT, "model_zoo"), "--worker_id=0",
        "--master_addr=localhost:0",
        *(f for f in config["job"] if not f.startswith("--checkpoint_steps")),
    ])
    if args.distribution_strategy != "AllreduceStrategy":
        raise SystemExit("this script writes a data-parallel job's state")
    spec = load_model_spec(args)
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    trainer = DataParallelTrainer(
        model=spec.build_model(mesh=mesh), loss_fn=spec.loss,
        optimizer=spec.optimizer(), mesh=mesh,
        dense_sharding=args.dense_sharding, seed=seed,
    )
    model = config["model"]
    length = model.get("sample_tokens") or model["n_positions"]
    vocab = model.get("vocab_size") or 64
    tokens = jnp.asarray(
        np.random.default_rng(seed).integers(
            0, vocab, (args.minibatch_size, length)
        ), jnp.int32,
    )
    trainer.ensure_initialized(tokens)
    return trainer, tokens


def ring_writer_class():
    from elasticdl_tpu.checkpoint import saver

    class RingWriter(saver.ChecksumWriter):
        """`ChecksumWriter` whose large writes go through two staging
        buffers: the helper thread copies piece i+1 into one and folds
        its CRC32 there while the file takes piece i from the other."""

        def __init__(self, path):
            super().__init__(path)
            self._ring = None
            self.ring_bytes = 0

        def write(self, data):
            view = memoryview(data)
            if view.ndim != 1 or view.itemsize != 1:
                view = view.cast("B")
            piece_bytes = saver._PIECE_BYTES
            if view.nbytes <= piece_bytes:
                return super().write(view)
            if self._ring is None:
                self._ring = [
                    np.empty(piece_bytes, np.uint8) for _ in range(2)
                ]

            def fill(n):
                piece = view[n * piece_bytes:(n + 1) * piece_bytes]
                staged = self._ring[n % 2][:piece.nbytes]
                staged[:] = np.frombuffer(piece, np.uint8)
                self.crc32 = zlib.crc32(staged, self.crc32)
                return staged

            count = -(-view.nbytes // piece_bytes)
            filling = self._helper.submit(fill, 0)
            for n in range(count):
                staged = filling.result()
                if n + 1 < count:
                    filling = self._helper.submit(fill, n + 1)
                self._file.write(staged)
                at = staged.ctypes.data
                self.recycled_bytes += self._taken.add(
                    at, at + staged.nbytes
                )
            self.size += view.nbytes
            self.ring_bytes += view.nbytes
            return view.nbytes

    return RingWriter


def save_once(way: str, state, directory: str, cutter) -> dict:
    from elasticdl_tpu.checkpoint import saver

    writer_class = ring_writer_class() if way == "ring" else (
        saver.ChecksumWriter
    )
    path = os.path.join(directory, f"{way}.state")
    started = time.monotonic()
    stream = saver.LeafStream(cutter if way == "pieces" else None)
    with writer_class(path) as writer:
        copied = saver.write_state(writer, state, stream)
    wall = time.monotonic() - started
    os.remove(path)
    write_s = wall - stream.wait_s
    return {
        "way": way, "bytes": writer.size, "wall_s": round(wall, 3),
        "gather_s": round(stream.wait_s, 3), "write_s": round(write_s, 3),
        "write_GB_per_s": round(writer.size / write_s / 1e9, 3),
        "lookahead_peak_bytes": stream.lookahead_peak_bytes,
        "leaves": stream.leaves, "pieces": stream.pieces,
        "recycled_share": round(writer.recycled_bytes / writer.size, 4),
        "copied_bytes": copied + getattr(writer, "ring_bytes", 0),
        "crc32": writer.crc32,
    }


def leaf_sizes(state, piece_bytes: int) -> dict:
    import jax

    sizes = [x.nbytes for x in jax.tree.leaves(state) if hasattr(x, "nbytes")]
    large = [n for n in sizes if n > piece_bytes]
    return {
        "leaves": len(sizes), "bytes": sum(sizes), "largest": max(sizes),
        "over_a_piece": len(large), "bytes_over_a_piece": sum(large),
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("cells", nargs="+", help="BENCHMARK.json workloads")
    parser.add_argument("--ways", nargs="+", default=list(WAYS), choices=WAYS)
    parser.add_argument("--repeats", type=int, default=2)
    parser.add_argument("--steps", type=int, default=0,
                        help="real train steps before the saves")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--budget_gb", type=float, default=40.0,
                        help="stop before the call has written this much")
    parser.add_argument("--tiny", action="store_true",
                        help="the cells' rehearsal sizes and pieces of 4 KiB")
    parser.add_argument("--dir", default=os.path.join(ROOT, ".perfbench"),
                        help="where the files are written (and removed)")
    args = parser.parse_args(argv)

    import jax

    from elasticdl_tpu.checkpoint import saver

    device = jax.devices()[0]
    machine = {
        "platform": device.platform, "device_kind": device.device_kind,
        "cpus": os.cpu_count(),
    }
    print(json.dumps({"machine": machine}), flush=True)
    rows = []
    os.makedirs(args.dir, exist_ok=True)
    if args.tiny:  # (the writer's pieces too, so that the ring turns)
        saver._PIECE_BYTES, saver._BESIDE_BYTES = 4096, 1024
    for cell in args.cells:
        config = cell_config(cell)
        if args.tiny:
            config = {**config, **config["rehearse"]}
        trainer, tokens = build_trainer(config, args.seed)
        for _ in range(args.steps):
            trainer.train_step(tokens, tokens)
        state = trainer.state
        jax.block_until_ready(state)
        cutter = saver.LeafCutter(piece_bytes=saver._PIECE_BYTES)
        started = time.monotonic()
        built = cutter.warm(jax.tree.leaves(state))
        shape = {
            "cell": cell, "steps": args.steps, **machine,
            **leaf_sizes(state, cutter.piece_bytes),
            "cut_programs": built,
            "cut_programs_build_s": round(time.monotonic() - started, 3),
            "layouts": sorted({
                str(saver._device_axes(x)) for x in jax.tree.leaves(state)
                if saver._on_device(x)
            }),
        }
        print(json.dumps(shape), flush=True)
        directory = tempfile.mkdtemp(prefix="exp_save_write.", dir=args.dir)
        try:
            for repeat in range(args.repeats):
                for way in args.ways:
                    written = sum(row["bytes"] for row in rows)
                    if written + shape["bytes"] > args.budget_gb * 1e9:
                        print(json.dumps({"stopped": "budget_gb",
                                          "written": written}), flush=True)
                        break
                    os.sync()
                    row = {"cell": cell, "repeat": repeat,
                           **save_once(way, state, directory, cutter)}
                    rows.append(row)
                    print(json.dumps(row), flush=True)
        finally:
            shutil.rmtree(directory, ignore_errors=True)
        crcs = {row["crc32"] for row in rows if row["cell"] == cell}
        if len(crcs) != 1:
            raise SystemExit(f"{cell}: the ways wrote different bytes: {crcs}")
        trainer = state = cutter = None
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "exp_save_write.json"), "w") as f:
        json.dump({"machine": machine, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
