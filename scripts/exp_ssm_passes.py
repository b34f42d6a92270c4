"""Chip experiment: what a Mamba-2 layer costs around its scan, by engine.

`model_zoo/lm_common.py` `Mamba2Mixer` alone, forward and backward under
`jax.checkpoint` (as the cells rematerialise a layer) at 1 x 8192 tokens
and the two published shapes that run it (`SHAPES`), by the engines of
its two parts, each `xla` or `pallas` (`ENGINES`: passes / scan): the
passes around the scan as the plain `jax.numpy` chains
(`ops/gdn_passes.py`'s `..._xla` definitions) or their kernels as
`gdn_passes.engine_groups` picks them on one chip, and the scan as
`ops/ssd.py`'s XLA form or its kernel pair.  For each it prints
the time of a call and, from a profiler trace of five calls, the device
time under the `ssm` scope split by what the ops are (the last component
of an op's `op_name` path, read by `perfbench/lib/xscope.py`), outside
the `ssm_scan` scope and inside it.

`--passes` also times each pass alone, forward + backward, kernels
beside the XLA chain, with the bytes it has to move (inputs, outputs and
their gradients once) over the time: GB/s; and the scan alone, forward +
backward under `jax.checkpoint` by both engines, with the bytes
`ssm_scan_cost()` counts for one layer (the cells' yardstick), and how
far the kernels' outputs and gradients lie from the XLA form's.

One JSON line a measurement on stdout; the whole table also goes to
`chiprun_out/exp_ssm_passes.json`.

Usage: chiprun -- python scripts/exp_ssm_passes.py --passes
       python scripts/exp_ssm_passes.py --tiny      (CPU rehearsal)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

#: The Mamba-2 layer of each cell that runs it (`perfbench/configs/*.json`).
SHAPES = {
    "nemotron": dict(d=2688, heads=64, head_dim=64, groups=8, state=128,
                     taps=4, chunk=128, tokens=8192),
    "granite": dict(d=2048, heads=64, head_dim=64, groups=1, state=128,
                    taps=4, chunk=256, tokens=8192),
}
TINY = dict(d=64, heads=16, head_dim=64, groups=2, state=128, taps=4,
            chunk=128, tokens=256)
#: (the passes' engine, the scan's): the XLA layer, PR 44's, PR 46's.
ENGINES = (("xla", "xla"), ("pallas", "xla"), ("pallas", "pallas"))


def build(shape, pallas: bool, scan: bool):
    """-> (params, x, g, the jitted gradient of the rematerialised layer)
    with the passes' engine and the scan's dictated."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import gdn_passes, ssd
    from model_zoo.lm_common import Mamba2Mixer

    layer = Mamba2Mixer(
        shape["heads"], shape["head_dim"], shape["groups"], shape["state"],
        shape["taps"], shape["chunk"], 1e-5, jnp.bfloat16,
    )
    rng = np.random.default_rng(44)
    x, g = (
        jnp.asarray(rng.standard_normal((1, shape["tokens"], shape["d"])),
                    jnp.float32)
        for _ in range(2)
    )
    took, scan_took = gdn_passes.supports_groups, ssd.supports
    gdn_passes.supports_groups = lambda *a: pallas and took(*a)
    ssd.supports = lambda *a: scan and scan_took(*a)
    try:
        params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"]

        @jax.checkpoint
        def forward(params, x):
            with jax.named_scope("ssm"):
                return layer.apply({"params": params}, x)

        step = jax.jit(jax.grad(
            lambda params, x, g: jnp.sum(forward(params, x) * g),
            argnums=(0, 1),
        ))
        jax.block_until_ready(step(params, x, g))  # the trace reads the engine
    finally:
        gdn_passes.supports_groups, ssd.supports = took, scan_took
    return params, x, g, step


def scan_alone(shape, interpret: bool):
    """-> ({engine: the jitted gradient of the rematerialised scan}, its
    arguments, the bytes `ssm_scan_cost()` counts for one such layer)."""
    import jax
    import jax.numpy as jnp

    from configs import nemotron_h_reference as reference
    from elasticdl_tpu.ops import ssd

    t, heads, p = shape["tokens"], shape["heads"], shape["head_dim"]
    g, n, chunk = shape["groups"], shape["state"], shape["chunk"]
    rng = np.random.default_rng(46)
    args = (
        jnp.asarray(rng.standard_normal((1, t, heads * p)), jnp.float32),
        jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                       (1, t, heads))), jnp.float32),
        jnp.asarray(-rng.uniform(1.0, 16.0, (heads,)), jnp.float32),
        jnp.asarray(rng.standard_normal((1, t, 2 * g * n)), jnp.float32),
        jnp.asarray(rng.standard_normal((1, t, heads * p)), jnp.float32),
    )

    def xla(x, dt, a, bc):
        b, c = jnp.split(bc.reshape(1, t, 2 * g, n), 2, axis=2)
        return ssd.ssd_chunked_xla(
            x.reshape(1, t, heads, p), dt, a, b, c, chunk=chunk,
            dtype=jnp.bfloat16,
        )[0].reshape(1, t, heads * p)

    def pallas(x, dt, a, bc):
        return ssd.ssd_chunked_pallas(
            x, dt, a, bc, groups=g, chunk=chunk, interpret=interpret
        )[0]

    def step(rule):
        rule = jax.checkpoint(rule)
        return jax.jit(jax.value_and_grad(
            lambda x, dt, a, bc, w: jnp.sum(rule(x, dt, a, bc) * w),
            argnums=(0, 1, 2, 3),
        ))

    model = dict(
        mamba_num_heads=heads, mamba_head_dim=p, n_groups=g,
        ssm_state_size=n, chunk_size=chunk, sample_tokens=t,
        hybrid_override_pattern="M",
    )
    moved = reference.ssm_scan_cost(model, 1)["bytes"]
    return {"xla": step(xla), "pallas": step(pallas)}, args, moved


def single_passes(shape, pallas: bool, interpret: bool):
    """-> {pass: (jitted forward + backward, its arguments, bytes)}."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import gdn_passes

    t, heads = shape["tokens"], shape["heads"]
    inner = heads * shape["head_dim"]
    width = inner + 2 * shape["groups"] * shape["state"]
    rng = np.random.default_rng(45)

    def normal(*dims):
        return jnp.asarray(rng.standard_normal(dims), jnp.float32)

    def conv(rows, taps, bias, g):
        return jnp.sum(gdn_passes.conv_silu(
            rows, taps, bias, pallas=pallas, interpret=interpret
        ) * g)

    def norm(y, x, z, skip, weight, g):
        return jnp.sum(gdn_passes.gated_group_norm(
            y, x, z, skip, weight, groups=shape["groups"], eps=1e-5,
            dtype=jnp.bfloat16, pallas=pallas, interpret=interpret,
        ).astype(jnp.float32) * g)

    rows = 4 * t
    return {
        # forward: read rows, write rows; backward: read rows and d out,
        # write d rows
        "conv_silu": (
            jax.jit(jax.value_and_grad(conv, argnums=(0, 1, 2))),
            (normal(1, t, width), normal(shape["taps"], width),
             normal(width), normal(1, t, width)),
            5 * rows * width,
        ),
        # forward: read y, x, z, write bfloat16; backward: read y, x, z
        # and bfloat16 d out, write d y, d x, d z
        "gated_group_norm": (
            jax.jit(jax.value_and_grad(norm, argnums=(0, 1, 2, 3, 4))),
            (normal(1, t, inner), normal(1, t, inner), normal(1, t, inner),
             normal(heads), normal(inner), normal(1, t, inner)),
            (9 * rows + 2 * 2 * t) * inner,
        ),
    }


def time_calls(step, args, calls: int) -> list:
    import jax

    seconds = []
    for _ in range(calls):
        start = time.perf_counter()
        jax.block_until_ready(step(*args))
        seconds.append(time.perf_counter() - start)
    return seconds


def scope_split(profile_dir: str, calls: int) -> dict:
    """ms a call under `ssm`, the `ssm_scan` scope apart, by the last
    component of the ops' `op_name` path."""
    from lib import xplane, xscope

    out = os.path.join(profile_dir, "plain.json")
    xscope.dump(profile_dir, out)
    with open(out) as f:
        trace = json.load(f)
    split, inside, around, scan = {}, {}, 0, 0
    for plane in trace["planes"]:
        if not xplane.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] != xplane.OPS_LINE:
                continue
            for name, _, dur, scope in line["events"]:
                path = trace["scopes"][scope] if scope >= 0 else ""
                if "ssm" not in path:
                    continue
                if xplane.op_stem(name) in xplane.CONTAINERS:
                    continue
                kind = path.split(";", 1)[0].rsplit("/", 1)[-1]
                if "ssm_scan" in path:
                    scan += dur
                    inside[kind] = inside.get(kind, 0) + dur
                    continue
                split[kind] = split.get(kind, 0) + dur
                around += dur
        break  # one chip
    return {
        "ssm_scan_ms": round(scan / 1e6 / calls, 4),
        "around_scan_ms": round(around / 1e6 / calls, 4),
        **{
            name: {
                kind: round(ns / 1e6 / calls, 4)
                for kind, ns in sorted(ops.items(), key=lambda kv: -kv[1])
            }
            for name, ops in (("by_op_ms", split), ("scan_by_op_ms", inside))
        },
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--passes", action="store_true")
    parser.add_argument("--calls", type=int, default=20)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()

    import jax

    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind,
                      "platform": device.platform}), flush=True)
    shapes = {"tiny": TINY} if args.tiny else {
        name: SHAPES[name] for name in args.shapes.split(",")
    }
    if args.tiny:  # interpret mode off the chip
        from elasticdl_tpu.ops import gated_delta, ssd

        ssd._engine = gated_delta._engine = lambda supported, mesh, *why: (
            "pallas" if supported else "xla", "dictated"
        )
    lines = []
    for name, shape in shapes.items():
        for engine, scan in ENGINES:
            pallas = engine == "pallas"
            params, x, g, step = build(shape, pallas, scan == "pallas")
            time_calls(step, (params, x, g), 3)  # warm
            seconds = time_calls(step, (params, x, g), args.calls)
            line = {
                "shape": name, "engine": engine, "scan": scan,
                "what": "layer", "call_ms": round(1e3 * float(np.median(seconds)), 3),
                "call_ms_min": round(1e3 * min(seconds), 3),
            }
            profile_dir = tempfile.mkdtemp(prefix="exp_ssm_")
            jax.profiler.start_trace(profile_dir)
            time_calls(step, (params, x, g), 5)
            jax.profiler.stop_trace()
            try:
                line.update(scope_split(profile_dir, 5))
            except Exception as error:  # a CPU trace has no device plane
                line["trace_error"] = repr(error)
            lines.append(line)
            print(json.dumps(line), flush=True)
            if not (args.passes or args.tiny) or scan != "xla":
                continue
            for which, (call, operands, moved) in single_passes(
                shape, pallas, interpret=args.tiny
            ).items():
                time_calls(call, operands, 3)
                seconds = float(np.median(
                    time_calls(call, operands, args.calls)
                ))
                line = {
                    "shape": name, "engine": engine, "what": which,
                    "call_ms": round(1e3 * seconds, 3),
                    "gbytes": round(moved / 1e9, 4),
                    "gbytes_per_s": round(moved / 1e9 / seconds, 1),
                }
                lines.append(line)
                print(json.dumps(line), flush=True)
        if not (args.passes or args.tiny):
            continue
        steps, operands, moved = scan_alone(shape, interpret=args.tiny)
        results = {}
        for scan, call in steps.items():
            results[scan] = jax.block_until_ready(call(*operands))
            time_calls(call, operands, 2)
            seconds = float(np.median(time_calls(call, operands, args.calls)))
            line = {
                "shape": name, "scan": scan, "what": "ssm_scan",
                "call_ms": round(1e3 * seconds, 3),
                "gbytes": round(moved / 1e9, 4),
                "gbytes_per_s": round(moved / 1e9 / seconds, 1),
            }
            lines.append(line)
            print(json.dumps(line), flush=True)
        (want_y, want), (got_y, got) = results["xla"], results["pallas"]
        line = {
            "shape": name, "what": "ssm_scan pallas - xla, of the largest",
            "loss": abs(float(got_y - want_y)) / abs(float(want_y)),
            **{
                "d_" + which: float(
                    np.abs(np.asarray(g) - np.asarray(w)).max()
                    / np.abs(np.asarray(w)).max()
                )
                for which, g, w in zip(("x", "dt", "a", "bc"), got, want)
            },
        }
        lines.append(line)
        print(json.dumps(line), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "exp_ssm_passes.json"), "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
