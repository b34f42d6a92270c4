#!/usr/bin/env python3
"""Runs of one cell, one after another in one call, and their spread.

    python3 perfbench/sets.py --workload <cell> --seeds 11,12,13 \
        [--seconds N] [--trace 0|1] [--out chiprun_out/<name>]

Each run is `BENCHMARK.json`'s command in a process of its own, as the
driver makes it.  Prints every result line, then for each metric the
median and the spread the contract measures bounds by: the distance
between the first and third quartile (`statistics.quantiles(v, n=4)`) as a
share of the median.  With `--out`, the result lines and each run's small
files (journals, logs, the reduced trace) are kept there.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values: list) -> float:
    if len(values) < 2:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=int, default=None)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--out", default="")
    args = parser.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    lines, failed = [], 0
    for seed in args.seeds.split(","):
        cmd = bench["command"] + [
            "--workload", args.workload, "--seed", seed,
            "--seconds", str(seconds), "--trace", str(args.trace),
        ]
        start = time.monotonic()
        proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE, text=True)
        wall = time.monotonic() - start
        last = proc.stdout.strip().splitlines()[-1:] or [""]
        print(f"[sets] seed {seed}: exit {proc.returncode} in {wall:.1f}s: "
              f"{last[0][:3000]}", flush=True)
        if args.out:
            keep = os.path.join(ROOT, args.out, f"{args.workload}.s{seed}.t{args.trace}")
            os.makedirs(keep, exist_ok=True)
            work = os.path.join(ROOT, ".perfbench", "work", args.workload)
            for path in glob.glob(os.path.join(work, "**", "*"), recursive=True):
                small = os.path.isfile(path) and os.path.getsize(path) < 24 << 20
                if small and "/profile/" not in path:
                    dest = os.path.join(keep, os.path.relpath(path, work))
                    os.makedirs(os.path.dirname(dest), exist_ok=True)
                    shutil.copy(path, dest)
        if proc.returncode != 0:
            failed += 1
            continue
        line = json.loads(last[0])
        line["wall_s"] = wall
        lines.append(line)
    if args.out:
        with open(os.path.join(ROOT, args.out,
                               f"{args.workload}.t{args.trace}.jsonl"), "a") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    names = sorted({n for line in lines for n in line["metrics"]})
    for name in names:
        values = [l["metrics"][name]["value"] for l in lines if name in l["metrics"]]
        shown = values[1:] if name == "setup_s" and len(values) > 2 else values
        print(f"[sets] {name}: median {statistics.median(shown):.6g} "
              f"spread {100 * spread(shown):.2f}% of {len(shown)} "
              f"(all: {', '.join(f'{v:.6g}' for v in values)})", flush=True)
    print(f"[sets] correct: {[l['correct'] for l in lines]}, "
          f"{failed} run(s) failed", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
