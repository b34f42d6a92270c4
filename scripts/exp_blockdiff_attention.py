#!/usr/bin/env python3
"""The XLA attention engine under the block-diffusion rule, alone, on the
chip: forward + backward of `ops/gqa.causal_gqa_attention` at the shape of
`sdar.train-synth-8k` (one record of 8192 tokens as 16,384 positions, 32
query heads over 4 key-value heads of 128, bfloat16, tiles of 512), with
the NOISED DIAGONAL (16 of the 288 tile visits: 4 x 4 blocks inside a
512 x 512 tile) scored each way:

- `masked tile`: as the engine does it, a tile like the others;
- `clean tiles only` + `blocks alone`: the engine without those visits,
  and the small batched product over the blocks (every noised block's 4
  queries against its 4 keys, a softmax with its log-sum-exp, forward and
  backward by `jax.grad`) that a merge by log-sum-exp would put in their
  place; their sum, with one more pass over the noised half's output for
  the merge, is what the other way would cost.

And for scale the causal rule over the same 16,384 positions (528 visits)
and over two sequences of 8192 (272: Mellum 2's full layer).

    chiprun -- python scripts/exp_blockdiff_attention.py
    python scripts/exp_blockdiff_attention.py --tiny    (CPU rehearsal)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from elasticdl_tpu.ops import gqa  # noqa: E402


class CleanTilesOnly(gqa.BlockDiffusion):
    """The rule without a noised query tile's visit of its own tile (its
    rows' results are then no attention: timing only)."""

    def visits(self, i, block):
        return 0, i % (self.tokens // block) + 1


def timed(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    start = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1000.0 * (time.perf_counter() - start) / reps


def engine(rule, tile):
    def loss(q, k, v):
        return jnp.sum(gqa.causal_gqa_attention(
            q, k, v, tile, None, rule
        ).astype(jnp.float32))

    return jax.jit(jax.value_and_grad(loss, (0, 1, 2)))


def blocks_alone(length, group):
    """The noised blocks against themselves: q [B, Hq, T, D], k, v
    [B, Hkv, T, D] -> (out, lse), and their gradients."""
    def loss(q, k, v):
        b, hq, t, d = q.shape
        n = k.shape[1]
        qb = q.reshape(b, n, group, t // length, length, d)
        kb = k.reshape(b, n, t // length, length, d)
        vb = v.reshape(b, n, t // length, length, d)
        s = jnp.einsum(
            "bngcqd,bnckd->bngcqk", qb, kb,
            preferred_element_type=jnp.float32,
        ) / d ** 0.5
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.exp(s - lse[..., None]).astype(q.dtype)
        out = jnp.einsum(
            "bngcqk,bnckd->bngcqd", p, vb, preferred_element_type=jnp.float32
        )
        return jnp.sum(out) + jnp.sum(lse)

    return jax.jit(jax.value_and_grad(loss, (0, 1, 2)))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()
    tokens, heads, kv, d, tile, length = (
        (256, 4, 2, 16, 64, 4) if args.tiny else (8192, 32, 4, 128, 512, 4)
    )
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        print(f"no TPU, found {device}", file=sys.stderr)
        return 3
    keys = jax.random.split(jax.random.PRNGKey(0), 3)

    def operands(sequences, positions):
        return (
            jax.random.normal(keys[0], (sequences, heads, positions, d),
                              jnp.bfloat16),
            jax.random.normal(keys[1], (sequences, kv, positions, d),
                              jnp.bfloat16),
            jax.random.normal(keys[2], (sequences, kv, positions, d),
                              jnp.bfloat16),
        )

    both = operands(1, 2 * tokens)
    noised = tuple(x[:, :, :tokens] for x in both)
    n = tokens // tile
    readings = {
        "device": {"platform": device.platform, "kind": device.device_kind},
        "shape": {"tokens": tokens, "heads": heads, "kv_heads": kv,
                  "head_dim": d, "tile": tile, "block_length": length},
        "visits": {"block_diffusion": n * (n + 1) + n,
                   "clean_tiles_only": n * (n + 1),
                   "causal_2T": n * (2 * n + 1),
                   "causal_2xT": n * (n + 1)},
        "ms_forward_and_backward": {
            "masked_tile": timed(
                engine(gqa.BlockDiffusion(tokens, length), tile), *both),
            "clean_tiles_only": timed(
                engine(CleanTilesOnly(tokens, length), tile), *both),
            "blocks_alone": timed(
                blocks_alone(length, heads // kv), *noised),
            "causal_2T": timed(engine(gqa.Causal(), tile), *both),
            "causal_2xT": timed(
                engine(gqa.Causal(), tile), *operands(2, tokens)),
        },
    }
    print(json.dumps(readings), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
