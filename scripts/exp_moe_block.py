"""Chip experiment: what a block of the expert loop costs, by its rows.

`layers/moe.py` `SparseMoeBlock` alone, at the expert shapes of the four
cells that run it (`SHAPES`: the published widths, the held range, the
tokens of one step), rematerialised as the cells run it (the compiled
program holds one forward and one backward loop: the rematerialised
forward loop is dead code, its residuals being its inputs), under a
router whose choice this script
dictates: the first `num_experts` columns of `x` ARE the logits (the
router's weight is the identity there), so a held expert's load is a
number handed in, not an accident of a seed.  Two routers a shape:
`uniform` (the held experts' loads drawn as one multinomial around
tokens x top_k / num_experts) and `skew` (one held expert at 7 times the
mean of the held, `nemotron3-nano.train-synth-8k`'s
`expert_load_max_over_mean.lm`, the others sharing what is left of the
same total).  For each block in `--blocks` it prints the time of a call,
the loop's trip count and the fill (pairs / (blocks x block)).

`--trace <shape>` profiles that shape's calls and splits the device time
under the `moe_experts` scope by what the ops are (the last component of
an op's `op_name` path, read by `perfbench/lib/xscope.py`): the
products, the weight gradients' update, the slices, the gathers and
scatters.  One JSON line a measurement on stdout; the trace's table also
goes to `chiprun_out/exp_moe_block_<shape>.json`.

Usage: chiprun -- python scripts/exp_moe_block.py --trace deepseek
       python scripts/exp_moe_block.py --tiny      (CPU rehearsal)
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

#: One step's expert layer in each cell (`perfbench/configs/*.json`).
SHAPES = {
    "deepseek": dict(d=2048, width=1408, shared=2816, experts=64, held=8,
                     top_k=6, tokens=16384, score="softmax",
                     form="gated_silu"),
    "laguna": dict(d=2048, width=512, shared=512, experts=256, held=32,
                   top_k=8, tokens=8192, score="sigmoid", form="gated_silu"),
    "nemotron": dict(d=2688, width=1856, shared=3712, experts=128, held=8,
                     top_k=6, tokens=8192, score="sigmoid", form="relu2"),
    "qwen": dict(d=2048, width=512, shared=512, experts=512, held=16,
                 top_k=10, tokens=16384, score="softmax", form="gated_silu"),
}
TINY = dict(d=64, width=32, shared=32, experts=16, held=4, top_k=2,
            tokens=512, score="softmax", form="gated_silu")
SKEW = 7.0


def held_loads(shape, router: str, rng) -> np.ndarray:
    held = shape["held"]
    total = shape["tokens"] * shape["top_k"] * held // shape["experts"]
    if router == "uniform":
        return rng.multinomial(total, np.full(held, 1.0 / held))
    hot = min(int(SKEW * total / held), shape["tokens"], total)
    rest = rng.multinomial(total - hot, np.full(held - 1, 1.0 / (held - 1)))
    return np.concatenate([[hot], rest])


def dictated_input(shape, loads, rng) -> np.ndarray:
    """x [tokens, d] float32 whose first `experts` columns are the logits
    that give held expert h exactly `loads[h]` pairs (the held are the
    first `held` experts)."""
    n, e, held = shape["tokens"], shape["experts"], shape["held"]
    x = rng.standard_normal((n, shape["d"])).astype(np.float32)
    logits = rng.gumbel(size=(n, e)).astype(np.float32)
    logits[:, :held] = -30.0
    marks = np.zeros(n, np.int64)
    for h, load in enumerate(loads):
        free = np.flatnonzero(marks < shape["top_k"])
        chosen = rng.choice(free, int(load), replace=False)
        logits[chosen, h] = 30.0 + h
        marks[chosen] += 1
    x[:, :e] = logits
    return x


def build(shape, block):
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.layers.moe import SparseMoeBlock

    layer = SparseMoeBlock(
        shape["experts"], shape["top_k"], shape["width"], shape["shared"],
        (0, shape["held"]), block_rows=block, score=shape["score"],
        expert_form=shape["form"], shared_gated=False,
    )
    x0 = jnp.zeros((shape["tokens"], shape["d"]), jnp.float32)
    variables = jax.jit(layer.init)(jax.random.PRNGKey(0), x0)
    params = jax.tree.map(lambda a: a, variables["params"])
    router = np.zeros((shape["d"], shape["experts"]), np.float32)
    router[np.arange(shape["experts"]), np.arange(shape["experts"])] = 1.0
    if shape["score"] == "softmax":
        params["gate"] = jnp.asarray(router)
    else:
        params["gate"]["weight"] = jnp.asarray(router)

    @jax.checkpoint
    def forward(params, x):
        y, counted = layer.apply(
            {"params": params, "routing": variables["routing"]}, x,
            mutable=["routing"],
        )
        return y, counted["routing"]

    def loss(params, x, g):
        y, counted = forward(params, x)
        return jnp.sum(y * g), counted

    step = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))
    return params, step


def time_calls(step, args, calls: int) -> list:
    import jax

    seconds = []
    for _ in range(calls):
        start = time.perf_counter()
        jax.block_until_ready(step(*args))
        seconds.append(time.perf_counter() - start)
    return seconds


def scope_split(profile_dir: str, calls: int) -> dict:
    """ms a call under `moe_experts`, by the last component of the ops'
    `op_name` path, and the scope's total."""
    from lib import xplane, xscope

    out = os.path.join(profile_dir, "plain.json")
    xscope.dump(profile_dir, out)
    with open(out) as f:
        trace = json.load(f)
    split, total = {}, 0
    for plane in trace["planes"]:
        if not xplane.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] != xplane.OPS_LINE:
                continue
            for name, _, dur, scope in line["events"]:
                path = trace["scopes"][scope] if scope >= 0 else ""
                if "moe_experts" not in path:
                    continue
                if xplane.op_stem(name) in xplane.CONTAINERS:
                    continue
                kind = path.split(";", 1)[0].rsplit("/", 1)[-1]
                split[kind] = split.get(kind, 0) + dur
                total += dur
        break  # one chip
    return {
        "moe_experts_ms": total / 1e6 / calls,
        "by_op_ms": {
            kind: round(ns / 1e6 / calls, 4)
            for kind, ns in sorted(split.items(), key=lambda kv: -kv[1])
        },
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--blocks", default="128,256,512")
    parser.add_argument("--routers", default="uniform,skew")
    parser.add_argument("--trace", default="")
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
    blocks = [int(b) for b in args.blocks.split(",")]
    if args.tiny:
        blocks = [16, 128]
    traced = {}
    for name, shape in shapes.items():
        rng = np.random.default_rng(40)
        inputs = {}
        for router in args.routers.split(","):
            loads = held_loads(shape, router, rng)
            inputs[router] = (loads, jax.device_put(
                dictated_input(shape, loads, rng)
            ))
        g = jax.device_put(
            rng.standard_normal((shape["tokens"], shape["d"])).astype(
                np.float32
            )
        )
        for block in blocks:
            params, step = build(shape, block)
            for router, (loads, x) in inputs.items():
                (_, _), counted = step(params, x, g)
                counted = jax.device_get(counted)
                seconds = time_calls(step, (params, x, g), 3)  # warm
                seconds = time_calls(step, (params, x, g), args.calls)
                n_blocks = int(counted["blocks"])
                line = {
                    "shape": name, "router": router, "block": block,
                    "call_ms": round(1e3 * float(np.median(seconds)), 3),
                    "call_ms_min": round(1e3 * min(seconds), 3),
                    "pairs": int(counted["pairs"]),
                    "processed": int(counted["processed"]),
                    "blocks": int(counted["blocks"]),
                    "fill": round(float(loads.sum()) / (n_blocks * block), 4),
                    "load_max_over_mean": round(
                        float(loads.max() / loads.mean()), 3
                    ),
                }
                if name == args.trace or args.tiny:
                    profile_dir = tempfile.mkdtemp(prefix="exp_moe_")
                    jax.profiler.start_trace(profile_dir)
                    time_calls(step, (params, x, g), 5)
                    jax.profiler.stop_trace()
                    try:
                        line.update(scope_split(profile_dir, 5))
                    except Exception as error:  # a CPU trace has no device plane
                        line["trace_error"] = repr(error)
                    traced.setdefault(name, []).append(line)
                print(json.dumps(line), flush=True)
    for name, lines in traced.items():
        out = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, f"exp_moe_block_{name}.json"), "w") as f:
            json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
