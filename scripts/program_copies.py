"""What a cell's compiled program lays out again in HBM.

Compiles a language model's two-step window program (or, with `--step`,
its one step) for a DESCRIBED v5e at the cell's widths, as
`tests/lm_contract.py` `test_window_program_compiles_and_fits_for_v5e`
does (no chip), and prints the top-level `copy` and
`dynamic-update-slice` ops of 16 MB and more by shape, direction
(forward, rematerialised forward, backward) and `op_name`, their sum,
and that sum read and written once at the chip's HBM rate beside the
`copy` the ledger's newest traced run measured for the cell.  The sum of
the `copy` ops is what the descriptor's `CompileSpec.copy_bytes` bounds.
Its second line counts the attention engines by the same direction
(`lm_contract.attention_engine_runs`: the XLA engine's loops, the
flash-attention kernels): a stack whose rematerialised layers keep the
engine's results (`model_zoo/lm_common.KEEP_ATTENTION_RESULTS`) reads 0
under `remat`, which the cell's compile test holds.

Usage (35-60 s a cell; `<cell>` is a `tests/spec_<cell>.py`, or the
name of a `BENCHMARK.json` workload whose configuration one describes):
    python scripts/program_copies.py laguna [--sequences n] [--step]
        [--text out.hlo]
    python scripts/program_copies.py nemotron3-nano.train-synth-8k
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
from collections import defaultdict

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES_PER_S = 819e9  # a v5e chip's


def short(op_name: str) -> str:
    """The scopes of an `op_name` without the transformations' wrappers:
    the last three path entries."""
    return "/".join(op_name.split("/")[-3:]) or "-"


def ledger_copy_ms(cell: str):
    """-> (ms of `copy` a traced step, the PR) on the newest ledger line
    of the cell that has a breakdown, or None."""
    path = os.path.join(REPO_ROOT, "PERF_LEDGER.jsonl")
    if not os.path.exists(path):
        return None
    found = None
    with open(path) as f:
        for line in f:
            entry = json.loads(line)
            ops = dict((entry.get("breakdown") or {}).get("device_ops", ()))
            if entry.get("workload") == cell and "copy" in ops:
                found = (ops["copy"] / 4 * 1e3, entry["pr"])  # 4 traced steps
    return found


def descriptor(name: str):
    """The `SPEC` of `tests/spec_<name>.py`, or of the descriptor whose
    cell file is the configuration of the `BENCHMARK.json` workload
    `name`."""
    try:
        return importlib.import_module("spec_" + name).SPEC
    except ModuleNotFoundError:
        pass
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = next(w["config"] for w in bench["workloads"] if w["name"] == name)
    cell = os.path.basename(
        next(c["file"] for c in bench["configs"] if c["name"] == config)
    )
    for path in sorted(os.listdir(os.path.join(REPO_ROOT, "tests"))):
        if path.startswith("spec_") and path.endswith(".py"):
            spec = importlib.import_module(path[:-len(".py")]).SPEC
            if spec.cell == cell:
                return spec
    raise SystemExit(f"no tests/spec_*.py describes {cell}")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "cell", help="a descriptor, tests/spec_<cell>.py, or a workload"
    )
    parser.add_argument("--sequences", type=int, default=None,
                        help="sequences a step (the cell's own)")
    parser.add_argument("--step", action="store_true",
                        help="the one-step program, not the window")
    parser.add_argument("--text", default=None,
                        help="also write the compiled text here")
    args = parser.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    sys.path[:0] = [REPO_ROOT, os.path.join(REPO_ROOT, "tests")]
    from jax.experimental import topologies

    import lm_contract

    spec = descriptor(args.cell)
    sequences = args.sequences or spec.job_flags[1]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    compiled = lm_contract.compile_program(
        spec, topo, sequences, one_step=args.step
    )
    text = compiled.as_text()
    if args.text:
        with open(args.text, "w") as f:
            f.write(text)
    memory = compiled.memory_analysis()
    cell = spec.cell[:-len(".json")]
    print(f"{cell}: {'one step' if args.step else 'two-step window'}, "
          f"{sequences} x {spec.config['model']['sample_tokens']} tokens; "
          f"arguments {memory.argument_size_in_bytes:,} B "
          f"(aliased {memory.alias_size_in_bytes:,}), "
          f"temporaries {memory.temp_size_in_bytes:,} B")

    runs = lm_contract.attention_engine_runs(text)
    print("attention engines, fwd / remat / bwd: " + "; ".join(
        f"{kind} {n['fwd']} / {n['remat']} / {n['bwd']}"
        for kind, n in runs.items()
    ))

    moves = lm_contract.program_moves(
        text, opcodes=("copy", "dynamic-update-slice")
    )
    for opcode in ("copy", "dynamic-update-slice"):
        rows = defaultdict(lambda: [0, 0])
        for _, shape, size, name in (m for m in moves if m[0] == opcode):
            way = lm_contract.op_direction(name) if name else "-"
            row = rows[(shape, way, short(name))]
            row[0] += size
            row[1] += 1
        total = sum(size for size, _ in rows.values())
        named = sum(size for (_, way, _), (size, _) in rows.items()
                    if way != "-")
        print(f"\n{opcode}: {total / 1e9:.2f} GB a step in "
              f"{sum(n for _, n in rows.values())} ops of 16 MB and more "
              f"({named / 1e9:.2f} GB carry an op_name, "
              f"{(total - named) / 1e9:.2f} GB none)")
        for (shape, way, name), (size, n) in sorted(
            rows.items(), key=lambda kv: -kv[1][0]
        ):
            print(f"  {size / 1e9:6.3f} GB x{n:<3d} {shape:<28s} "
                  f"{way:<5s} {name}")
        if opcode == "copy":
            line = (f"  read + written once at {HBM_BYTES_PER_S / 1e9:.0f} "
                    f"GB/s: {2 * total / HBM_BYTES_PER_S * 1e3:.1f} ms a step")
            measured = ledger_copy_ms(cell)
            if measured:
                line += (f"; measured `copy` {measured[0]:.1f} ms a step "
                         f"(ledger, PR {measured[1]})")
            print(line)


if __name__ == "__main__":
    main()
