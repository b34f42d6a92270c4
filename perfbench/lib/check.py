"""The comparison that decides `correct`, run as a child AFTER the job has
ended (a chip belongs to one process): the program restores the job's last
checkpoint the way a relaunched worker does and runs its own `eval_step`
on a small seeded sample (the configuration's reference file says how:
`program`); the plain reference beside it computes the same outputs from
the same checkpoint's weights, once for every precision the configuration
holds the program to (`precisions`); each pair is compared.

    python check.py <spec.json>     ->  last stdout line: one JSON object
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import load_module  # noqa: E402


def main(spec_path: str) -> int:
    with open(spec_path) as f:
        spec = json.load(f)
    root = spec["root"]
    sys.path.insert(0, root)
    os.chdir(root)  # --model_zoo=model_zoo is relative, as in the job

    import jax
    import numpy as np

    from elasticdl_tpu.common import compile_cache
    from elasticdl_tpu.common.args import parse_master_args

    compile_cache.configure()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu" and not spec["rehearse"]:
        print(f"check: no TPU, found {device}", file=sys.stderr)
        return 3

    reference = load_module(spec["reference"])
    model = spec["model"]
    features = reference.sample(spec["seed"], spec["rows"], model)
    args = parse_master_args(spec["job_argv"])
    got, step, program_state = reference.program(args, features)
    if got is None:
        print(f"check: no committed checkpoint in {args.checkpoint_dir}",
              file=sys.stderr)
        return 4
    got = np.asarray(got, np.float32)
    step_dir = os.path.join(args.checkpoint_dir, f"step_{step:012d}")
    weights = reference.weights(step_dir, features, model, program_state)
    finite, rel_rms_diff = bool(np.isfinite(got).all()), {}
    for precision in spec["precisions"]:
        want = np.asarray(
            reference.forward(weights, features, model, precision), np.float32
        )
        if got.shape != want.shape:
            print(f"check: shapes {got.shape} vs {want.shape}", file=sys.stderr)
            return 5
        diff = got.astype(np.float64) - want
        rms = float(np.sqrt(np.mean(np.square(want, dtype=np.float64))))
        finite = finite and bool(np.isfinite(want).all())
        rel_rms_diff[precision] = float(np.sqrt(np.mean(diff * diff))) / rms
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    )
    print(json.dumps({
        "device": device,
        "step": int(step),
        "rows": int(got.shape[0]),
        "outputs": int(got.size),
        "finite": finite,
        "reference_rms": rms,
        "rel_rms_diff": rel_rms_diff,
        "check_peak_bytes": int(peak),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
