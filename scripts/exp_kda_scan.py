#!/usr/bin/env python3
"""The delta rule under a decay a key channel, alone, on the chip: forward
and forward + backward of `ops/gated_delta.chunk_gated_delta_rule_rows` at
the shape of `ling3-flash-vl.train-synth-8k` (1 x 8192 tokens, 32 heads of
128 x 128, float32 rows), beside the scalar-decay rule in both of its
engines for scale.  One JSON line of milliseconds (the median of
`--repeats` timed calls after a warm-up, each ended by
`block_until_ready`).  `--tiny` rehearses the control flow on the CPU.

    chiprun -- python scripts/exp_kda_scan.py          # ~2 min
    python scripts/exp_kda_scan.py --tiny               # here, seconds
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--repeats", type=int, default=5)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from elasticdl_tpu.common import compile_cache
    from elasticdl_tpu.ops import gated_delta

    compile_cache.configure()
    t, h, d = (256, 2, 128) if args.tiny else (8192, 32, 128)
    rng = np.random.default_rng(0)
    rows = lambda: jnp.asarray(rng.normal(size=(1, t, h * d)), jnp.float32)  # noqa: E731
    q, k, v = rows(), rows(), rows()
    unit = lambda x: (  # noqa: E731
        x.reshape(1, t, h, d)
        / jnp.linalg.norm(x.reshape(1, t, h, d), axis=-1, keepdims=True)
    ).reshape(1, t, h * d)
    q, k = unit(q) * d ** -0.5, unit(k)
    beta = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(1, t, h)), jnp.float32))
    vector = -5.0 * jax.nn.sigmoid(
        jnp.asarray(rng.normal(size=(1, t, h, d)) - 4.0, jnp.float32)
    )
    scalar = jnp.mean(vector, axis=-1)

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        seconds = []
        for _ in range(args.repeats):
            start = time.perf_counter()
            jax.block_until_ready(fn(*xs))
            seconds.append(time.perf_counter() - start)
        return 1e3 * statistics.median(seconds)

    def rule(g):
        def forward(q, k, v, g, beta):
            return gated_delta.chunk_gated_delta_rule_rows(q, k, v, g, beta, h)[0]

        both = jax.jit(jax.grad(
            lambda *xs: jnp.sum(forward(*xs) * v), (0, 1, 2, 3, 4)
        ))
        return {
            "fwd_ms": timed(jax.jit(forward), q, k, v, g, beta),
            "fwd_bwd_ms": timed(both, q, k, v, g, beta),
        }

    result = {
        "device": jax.devices()[0].device_kind, "tokens": t, "heads": h,
        "vector_decay": rule(vector), "scalar_decay": rule(scalar),
    }
    if not args.tiny:
        kept = gated_delta.supports
        gated_delta.supports = lambda *shape: False
        try:
            result["scalar_decay_xla_engine"] = rule(scalar)
        finally:
            gated_delta.supports = kept
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
