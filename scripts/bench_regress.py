#!/usr/bin/env python
"""Bench regression gate: compare a ``bench.py`` run against the
recorded baseline, journal the verdict, fail loud on regressions.

    make bench-regress                      # runs `python bench.py`
    python scripts/bench_regress.py                 # same
    python scripts/bench_regress.py --input run.jsonl
    python scripts/bench_regress.py --selftest      # CPU-only gate test
    python scripts/bench_regress.py --synthetic regress   # exits 1

ROADMAP item 5's second half: perf becomes a *gated, journaled* signal
instead of a per-round ritual.  Each tracked metric of a bench run is
compared against BASELINE.md's recorded value (``bench.SELF_BASELINE``
— the single source both bench.py's ``vs_baseline`` field and this gate
read) within that metric's recorded run-to-run spread
(``ALLOWED_SPREAD`` below, transcribed from BASELINE.md's measured
spreads with a safety floor).  The result journals through the obs
plane as a schema-registered ``bench_regress`` event
(scripts/validate_journal.py) carrying per-metric verdicts, so every
future speed PR lands with its number attached and attributable.

Verdicts: ``ok`` (within spread), ``improved`` (above it — update
BASELINE.md!), ``regressed`` (below it — the gate exits non-zero).
Rows bench.py flags ``tracked: false`` (coupled file->device metrics
not measured on the attached chip yet) are reported but never gate.  ``--selftest`` exercises the
gate on synthetic bench output with no accelerator (the tier-1 path);
``--synthetic ok|regress`` drives the FULL pipeline on synthetic rows
so the exit-code contract itself is testable end to end.

Exit status: 0 = no tracked regression, 1 = regression (or a selftest
failure), 2 = usage / unparsable input.  Stdlib only (bench.py itself
needs jax, but --input/--selftest/--synthetic paths never import it).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import subprocess
import sys
import tempfile
from typing import Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# `python scripts/bench_regress.py` puts scripts/ (not the repo root) on
# sys.path; the gate needs the package (obs journal) and its sibling
# validate_journal either way it is invoked.
for _path in (REPO_ROOT, os.path.join(REPO_ROOT, "scripts")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

#: Allowed relative shortfall per tracked metric before the gate trips:
#: BASELINE.md's recorded run-to-run spreads (device rows measure
#: 0.04-1 % — see the table and the "Steadiness" note) widened to a
#: floor that absorbs run-to-run weather without hiding a real
#: regression; host-pipeline rows were recorded on a one-core host that
#: halves under load, so their recorded spread is wider.
DEFAULT_ALLOWED_SPREAD = 0.05
ALLOWED_SPREAD: Dict[str, float] = {
    # Host-side rows: BASELINE.md records 60 % outlier windows on the
    # shared core (trimmed to ~2-15 % spread); gate at 15 %.
    "deepfm_e2e_host_pipeline_records_per_sec": 0.15,
    # Staged for the async staging engine row (round 8): emitted
    # tracked:false until a multi-core driver host replaces the
    # provisional sync-row anchor (on the 1-core CI box the parse pool
    # degenerates to one worker); host-side shared-core row, so the
    # host floor applies once it flips tracked.
    "deepfm_e2e_host_pipeline_async_records_per_sec": 0.15,
    "resnet50_e2e_host_pipeline_images_per_sec": 0.15,
    # 26M-row table rows recorded at 0.5-1.0 % spread; 5 % floor.
    "deepfm_26m_table_samples_per_sec_per_chip": 0.05,
    "deepfm_26m_strict_samples_per_sec_per_chip": 0.05,
    # Fused-kernel headline row: bench.py emits it tracked:false until
    # chip-verified (the flag, not this table, is what defers gating);
    # once the driver records a number and flips it tracked, it gates
    # at the device-row floor.
    "deepfm_train_fused_samples_per_sec_per_chip": 0.05,
    # Staged for the shard_map'd multi-chip fused row (round 7): also
    # emitted tracked:false until a real multi-chip driver run; the
    # entry here is ready for the flip.
    "deepfm_train_fused_multichip_samples_per_sec_per_chip": 0.05,
    # Staged for the serving-plane QPS row (round 13): emitted
    # tracked:false until a driver run replaces the provisional CI-host
    # anchor; host-side shared-core row, so the host floor applies.
    # deepfm_serve_p99_ms deliberately has NO entry: it is
    # lower-is-better and the ratio gate's direction would invert —
    # it lives in UNTRACKED below instead.
    "deepfm_serve_qps_per_replica": 0.15,
}

#: Metrics that never gate even when present (mirrors bench.py's
#: ``tracked: false`` rows — the coupled file->device numbers).
UNTRACKED = frozenset(
    {
        "deepfm_e2e_samples_per_sec_per_chip",
        # Parse-pool scaling ratio: 1.0 by construction on the 1-core
        # CI host, so the ratio gate would be noise-gating the pool's
        # fixed overhead — permanently report-only; the async RATE row
        # above is the one that flips tracked with driver evidence.
        "deepfm_e2e_parse_pool_scaling_x",
        "resnet50_e2e_images_per_sec_per_chip",
        # Lower-is-better tail latency: the ratio gate reads shortfall
        # as value/baseline < 1-spread, which would treat a LATENCY
        # IMPROVEMENT as a regression — permanently report-only.
        "deepfm_serve_p99_ms",
        # Quality-plane math anchor (bench_deepfm_online_auc_window):
        # a synthetic fixed-separation scorer, so the value measures
        # the ledger's window math, never model quality — permanently
        # report-only.
        "deepfm_online_auc_window",
        "bench_backend_probe",
    }
)


def load_baseline() -> Dict[str, float]:
    """bench.py's SELF_BASELINE (the one recorded-value table BOTH the
    bench's vs_baseline field and this gate read), imported by path so
    the import never initializes jax."""
    path = os.path.join(REPO_ROOT, "bench.py")
    spec = importlib.util.spec_from_file_location("_bench_baseline", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return dict(module.SELF_BASELINE)


def parse_rows(lines) -> List[dict]:
    """Metric rows out of a bench.py run's stdout (non-JSON lines —
    logging, mesh banners — skip silently)."""
    rows = []
    for line in lines:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if isinstance(row, dict) and "metric" in row and "value" in row:
            rows.append(row)
    return rows


def judge(rows: List[dict], baseline: Dict[str, float]) -> dict:
    """Per-metric verdicts + the run verdict.

    A metric gates iff it is baseline-recorded, not flagged untracked,
    and its row doesn't carry ``tracked: false``.  The reverse check
    also gates: every tracked baseline metric MUST appear in the run —
    a silently-dropped metric can never regress otherwise (the exact
    judge-reading-prose failure mode this gate exists to prevent)."""
    details = []
    regressed = improved = 0
    for row in rows:
        metric = row["metric"]
        if metric in UNTRACKED or metric not in baseline:
            continue
        tracked = row.get("tracked", True)
        allowed = ALLOWED_SPREAD.get(metric, DEFAULT_ALLOWED_SPREAD)
        ratio = float(row["value"]) / float(baseline[metric])
        if not tracked:
            verdict = "untracked"
        elif ratio < 1.0 - allowed:
            verdict = "regressed"
            regressed += 1
        elif ratio > 1.0 + allowed:
            verdict = "improved"
            improved += 1
        else:
            verdict = "ok"
        details.append(
            {
                "metric": metric,
                "value": float(row["value"]),
                "baseline": float(baseline[metric]),
                "ratio": round(ratio, 4),
                "allowed_spread": allowed,
                "spread": row.get("spread"),
                "verdict": verdict,
            }
        )
    seen = {row["metric"] for row in rows}
    missing = 0
    for metric in sorted(baseline):
        if metric in UNTRACKED or metric in seen:
            continue
        missing += 1
        details.append(
            {
                "metric": metric,
                "baseline": float(baseline[metric]),
                "verdict": "missing",
            }
        )
    return {
        "verdict": "regressed" if (regressed or missing) else "ok",
        "metrics_total": len(details),
        "regressed": regressed,
        "missing": missing,
        "improved": improved,
        "details": details,
    }


def journal_verdict(result: dict, journal_dir: str = "") -> dict:
    """Record the ``bench_regress`` event through the obs plane (and to
    ``<journal_dir>/events.jsonl`` when a directory is given).  The
    record is schema-checked against scripts/validate_journal.py BEFORE
    being trusted — a gate whose own audit trail drifts from the schema
    registry must fail itself."""
    from elasticdl_tpu import obs

    if journal_dir:
        obs.init_journal(journal_dir)
    record = obs.journal().record(
        "bench_regress",
        verdict=result["verdict"],
        metrics_total=result["metrics_total"],
        regressed=result["regressed"],
        missing=result.get("missing", 0),
        improved=result["improved"],
        bench_exit_code=result.get("bench_exit_code", 0),
        details=result["details"],
    )
    import validate_journal

    errors = validate_journal.validate_record(record)
    if errors:
        raise AssertionError(
            f"bench_regress journal record failed its own schema: {errors}"
        )
    return record


def render(result: dict) -> str:
    lines = []
    for detail in result["details"]:
        if detail["verdict"] == "missing":
            lines.append(
                f"  missing    {detail['metric']}: tracked in the "
                "baseline but never emitted by this run"
            )
            continue
        lines.append(
            f"  {detail['verdict']:<10} {detail['metric']}: "
            f"{detail['value']:,.1f} vs baseline "
            f"{detail['baseline']:,.1f} (ratio {detail['ratio']}, "
            f"allowed -{detail['allowed_spread'] * 100:.0f}%)"
        )
    lines.append(
        f"bench-regress: {result['verdict'].upper()} — "
        f"{result['metrics_total']} gated metric(s), "
        f"{result['regressed']} regressed, "
        f"{result.get('missing', 0)} missing, "
        f"{result['improved']} improved"
    )
    if result.get("bench_exit_code"):
        lines.append(
            f"  bench command itself exited "
            f"{result['bench_exit_code']} — the run is not trustworthy "
            "even where emitted rows look healthy"
        )
    if result["improved"] and not result["regressed"]:
        lines.append(
            "  (improvement beyond spread: update BASELINE.md + "
            "bench.SELF_BASELINE so the gain is locked in)"
        )
    return "\n".join(lines)


def synthetic_rows(kind: str, baseline: Dict[str, float]) -> List[dict]:
    """A fake bench run: every tracked metric at baseline, except under
    ``regress`` where the flagship drops far beyond any spread."""
    rows = []
    for metric, value in sorted(baseline.items()):
        if metric in UNTRACKED:
            continue
        rows.append(
            {"metric": metric, "value": value, "unit": "synthetic",
             "spread": 0.0}
        )
    if kind == "regress":
        rows[-1] = dict(rows[-1])
        rows[-1]["value"] = rows[-1]["value"] * 0.5  # far beyond spread
    return rows


def run_bench(cmd: str, timeout_s: int):
    """(stdout lines, exit code).  A non-zero bench exit FAILS the gate
    even when rows were emitted before the crash — a bench that died
    mid-run must not publish its partial output as a passing claim."""
    proc = subprocess.run(
        cmd, shell=True, cwd=REPO_ROOT, capture_output=True, text=True,
        timeout=timeout_s,
    )
    sys.stderr.write(proc.stderr)
    if proc.returncode != 0:
        print(
            f"bench-regress: bench command {cmd!r} exited "
            f"{proc.returncode}", file=sys.stderr,
        )
    return proc.stdout.splitlines(), proc.returncode


def selftest() -> int:
    """The tier-1 gate over the gate: on synthetic output (no
    accelerator), a within-spread run passes, a beyond-spread regression
    trips — and the journaled event schema-validates either way."""
    baseline = load_baseline()
    good = judge(synthetic_rows("ok", baseline), baseline)
    bad = judge(synthetic_rows("regress", baseline), baseline)
    problems = []
    if good["verdict"] != "ok" or good["regressed"]:
        problems.append(f"within-spread run misjudged: {good['verdict']}")
    if not good["metrics_total"]:
        problems.append("no metrics gated — baseline table unreadable?")
    if bad["verdict"] != "regressed" or bad["regressed"] != 1:
        problems.append(
            f"beyond-spread regression not caught: {bad['verdict']} "
            f"({bad['regressed']} regressed)"
        )
    # Fail-closed checks: a tracked metric DROPPED from the run must
    # gate (a metric that stops being emitted can never regress
    # otherwise), and a crashed bench must not publish partial rows.
    dropped = judge(synthetic_rows("ok", baseline)[:-1], baseline)
    if dropped["verdict"] != "regressed" or dropped["missing"] != 1:
        problems.append(
            f"dropped tracked metric not caught: {dropped['verdict']} "
            f"({dropped['missing']} missing)"
        )
    crashed_lines, crashed_rc = run_bench(
        f"{sys.executable} -c \"import json; "
        "print(json.dumps({'metric': 'm', 'value': 1.0})); exit(3)\"",
        timeout_s=60,
    )
    if crashed_rc != 3 or not parse_rows(crashed_lines):
        problems.append("bench-crash harness misbehaved in selftest")
    with tempfile.TemporaryDirectory(prefix="bench_regress_self_") as tmp:
        record = journal_verdict(bad, journal_dir=tmp)
        if record.get("verdict") != "regressed":
            problems.append(f"journaled verdict wrong: {record}")
        import validate_journal

        journal_path = os.path.join(tmp, "events.jsonl")
        if not os.path.exists(journal_path):
            problems.append("bench_regress event never reached the journal")
        elif validate_journal.validate_file(journal_path):
            problems.append("journaled bench_regress file fails the schema")
    if problems:
        print("bench_regress selftest FAILED:", file=sys.stderr)
        for problem in problems:
            print(f"  {problem}", file=sys.stderr)
        return 1
    print(
        f"bench_regress selftest OK ({good['metrics_total']} gated "
        "metrics; synthetic regression trips, dropped-metric trips, "
        "crashed-bench rc propagates, journal schema-valid)"
    )
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Gate a bench.py run against BASELINE.md's recorded "
        "value±spread; journal a bench_regress event; exit non-zero on "
        "beyond-spread regressions.",
    )
    parser.add_argument(
        "--input", default="",
        help="read bench.py JSONL output from this file ('-' = stdin) "
        "instead of running the bench",
    )
    parser.add_argument(
        "--cmd", default=f"{sys.executable} bench.py",
        help="bench command to run when no --input is given",
    )
    parser.add_argument(
        "--timeout", type=int, default=3600,
        help="bench command timeout in seconds",
    )
    parser.add_argument(
        "--journal-dir", default="",
        help="also append the bench_regress event to "
        "<dir>/events.jsonl (e.g. the job's --tensorboard_log_dir)",
    )
    parser.add_argument(
        "--selftest", action="store_true",
        help="exercise the gate on synthetic output (no accelerator)",
    )
    parser.add_argument(
        "--synthetic", choices=("ok", "regress"), default="",
        help="run the full pipeline on a synthetic bench run "
        "(exit-code contract test)",
    )
    args = parser.parse_args(argv)
    if args.selftest:
        return selftest()
    baseline = load_baseline()
    bench_rc = 0
    if args.synthetic:
        rows = synthetic_rows(args.synthetic, baseline)
    elif args.input == "-":
        rows = parse_rows(sys.stdin)
    elif args.input:
        try:
            with open(args.input, "r", encoding="utf-8") as f:
                rows = parse_rows(f)
        except OSError as exc:
            print(f"{args.input}: {exc}", file=sys.stderr)
            return 2
    else:
        lines, bench_rc = run_bench(args.cmd, args.timeout)
        rows = parse_rows(lines)
    if not rows:
        print(
            "bench-regress: no metric rows found — nothing gated "
            "(bench failed before emitting, or wrong --input?)",
            file=sys.stderr,
        )
        return 2
    result = judge(rows, baseline)
    if bench_rc:
        # Fail-closed: partial rows from a crashed bench never publish
        # as a passing perf claim.
        result["bench_exit_code"] = bench_rc
        result["verdict"] = "bench_error"
    journal_verdict(result, journal_dir=args.journal_dir)
    print(render(result))
    return 1 if result["verdict"] != "ok" else 0


if __name__ == "__main__":
    sys.exit(main())
