"""Diagnose the ring tracked metric's -3% drift (round-5 VERDICT #4).

BENCH_r04 recorded `ring_attention_tokens_per_sec_per_chip` at
vs_baseline 0.97 with 0.3% within-run spread — ten times its own noise.
The kernel did not change between the baseline recording and the driver
run; what DID differ is process context: in `bench.py main()` the ring
bench runs THIRD, after the transformer and ResNet-50 trainers have
initialized, allocated, and stepped on the same chip, while the
baseline was recorded by calling bench_ring_engine in a fresh process.

This script measures exactly that variable on one chip:

  A. bench_ring_engine in a FRESH process (subprocess), nothing else
     has touched the chip;
  B. bench_ring_engine after bench_transformer() + bench_resnet50()
     in the same process (the driver's execution context).

Each arm repeats `--arms` times (alternating) so run-to-run weather
shows up as within-arm scatter rather than between-arm bias.  Each arm
is a child process and this parent stays off jax: a chip belongs to one
process at a time.  If B sits ~3%
below A, the drift is predecessor-state (HBM layout/fragmentation or
residual allocations), not a kernel regression — re-baseline with the
reason recorded in BASELINE.md, or report the ring row from a fresh
subprocess in main().

Usage: python scripts/exp_ring_drift.py [--arms 3]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run_arm(predecessors: bool) -> dict:
    """One subprocess measurement of bench_ring_engine.  Arm A
    (predecessors=False): the chip is untouched — the context the
    baseline was recorded in.  Arm B (True): bench_transformer +
    bench_resnet50 run first in the same process — the driver's
    execution context.  One code template so the arms can't drift."""
    pred = (
        "bench.bench_transformer()\nbench.bench_resnet50()\n"
        if predecessors else ""
    )
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import json, bench\n"
        "%s"
        "rate, spread = bench.bench_ring_engine()\n"
        "print(json.dumps({'rate': rate, 'spread': spread}))\n"
    ) % (
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        pred,
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        check=True,
    )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arms", type=int, default=3)
    args = p.parse_args()
    rows = []
    for i in range(args.arms):
        for arm, predecessors in (("fresh", False), ("after_pred", True)):
            r = _run_arm(predecessors)
            r["arm"] = arm
            r["i"] = i
            rows.append(r)
            print(json.dumps(r), flush=True)
    for arm in ("fresh", "after_pred"):
        rates = [r["rate"] for r in rows if r["arm"] == arm]
        mid = sum(rates) / len(rates)
        half = (max(rates) - min(rates)) / 2
        print(f"{arm}: mean {mid:,.0f} ± {half:,.0f} tokens/s "
              f"({len(rates)} runs)")
    fresh = [r["rate"] for r in rows if r["arm"] == "fresh"]
    after = [r["rate"] for r in rows if r["arm"] == "after_pred"]
    delta = (sum(after) / len(after)) / (sum(fresh) / len(fresh)) - 1
    print(f"after_pred vs fresh: {delta:+.2%}")


if __name__ == "__main__":
    main()
