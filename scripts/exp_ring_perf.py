"""Perf experiment: ring-attention per-step engines on the real chip.

Not part of the test suite — the measurement harness behind BASELINE.md's
"Ring-attention Pallas engine" table (round 3) and the round-4 carry-
fusion work (VERDICT #2).  Methodology: single-chip-equivalent A/B — the
per-device compute of ONE ring member, R sequential worst-case
(fully-unmasked) KV-block steps run inside one jit (dispatch-amortized), bf16
inputs, H=8 D=128.  The ppermute transfers are deliberately absent: on
real multi-chip hardware they overlap the next step's compute under
XLA's scheduler; what this harness isolates is the per-step BLOCK-ENGINE
cost the VERDICT targets.

Usage:
    python scripts/exp_ring_perf.py fwd t2048_b4_xla t2048_b4_pallas
    python scripts/exp_ring_perf.py grad t2048_b4_pallas_bq1024
    python scripts/exp_ring_perf.py fwd profile_t2048_b4_pallas

Variant tokens (joined by `_`): tN = T_local, bN = batch,
xla|pallas = engine, bqN/bkN = kernel block sizes, rN = ring steps
(default 4), `profile` prefix captures a jax.profiler trace to
/tmp/ring_prof.
"""

from __future__ import annotations

import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

H, D = 8, 128
REPEATS = 5


def parse(spec: str):
    cfg = dict(t=2048, b=4, engine="pallas", bq=None, bk=None, r=4,
               profile=False, inner=INNER)
    for tok in spec.split("_"):
        if tok == "profile":
            cfg["profile"] = True
        elif tok in ("xla", "pallas"):
            cfg["engine"] = tok
        elif tok.startswith("bq"):
            cfg["bq"] = int(tok[2:])
        elif tok.startswith("i") and tok[1:].isdigit():
            cfg["inner"] = int(tok[1:])
        elif tok.startswith("bk"):
            cfg["bk"] = int(tok[2:])
        elif tok.startswith("t"):
            cfg["t"] = int(tok[1:])
        elif tok.startswith("b"):
            cfg["b"] = int(tok[1:])
        elif tok.startswith("r"):
            cfg["r"] = int(tok[1:])
        else:
            raise ValueError(f"unknown token {tok!r}")
    return cfg


def build_step_fn(cfg, mode):
    """fn(q, ks [R,...], vs [R,...]) -> scalar; R INDEPENDENT worst-case
    ring-step invocations, results summed.  Independent — not chained
    through the (acc, lse) carry — because the real multi-chip ring
    overlaps each step with the next KV ppermute, which independent
    iterations model far better than an artificial serial chain.  This
    matches the round-3 table's methodology."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops.flash_attention import (
        NEG_INF,
        flash_ring_step_bwd,
        flash_ring_step_carry,
    )
    from elasticdl_tpu.parallel.ring_attention import (
        _attn_block,
        _finalize,
    )

    t, scale = cfg["t"], 1.0 / D ** 0.5
    kb = dict(causal=True, scale=scale)
    if cfg["bq"]:
        kb["block_q"] = cfg["bq"]
    if cfg["bk"]:
        kb["block_k"] = cfg["bk"]
    # Worst-case unmasked steps: q rows are globally LAST (positions in
    # the final T rows), every KV block earlier -> causal mask never
    # trims work, matching the round-3 table's "fully-unmasked" steps.
    q_pos = jnp.arange((cfg["r"]) * t, (cfg["r"] + 1) * t)
    k_pos_per_step = [jnp.arange(i * t, (i + 1) * t) for i in range(cfg["r"])]

    if cfg["engine"] == "pallas":

        def fwd(q, ks, vs):
            # KV arrive in KERNEL layout [R,B,H,T,D]: production rotates
            # KV pre-transposed (one transpose outside the ring scan,
            # round 4), so the per-step engine cost excludes relayout.
            qk = q.transpose(0, 2, 1, 3)
            acc0 = jnp.zeros(
                (cfg["r"],) + qk.shape, jnp.float32
            )
            lse0 = jnp.full(
                (cfg["r"],) + qk.shape[:3] + (1,), NEG_INF, jnp.float32
            )
            total = jnp.float32(0)
            for i in range(cfg["r"]):
                acc, lse = flash_ring_step_carry(
                    qk, ks[i], vs[i],
                    acc0[i], lse0[i], q_pos, k_pos_per_step[i], **kb,
                )
                total = total + jnp.sum(acc) + jnp.sum(lse)
            return total

        if mode == "fwd":
            return fwd

        def grad_fn(q, ks, vs):
            # R independent bwd-step invocations (the step kernels are
            # stateless by design: they take the FINAL lse/delta).
            qk = q.transpose(0, 2, 1, 3)
            do = jnp.ones_like(qk, jnp.float32)
            lse = jnp.zeros(qk.shape[:3] + (1,), jnp.float32)
            delta = jnp.zeros_like(lse)
            total = jnp.float32(0)
            for i in range(cfg["r"]):
                dq_i, dk_i, dv_i = flash_ring_step_bwd(
                    qk, ks[i], vs[i], do, lse, delta,
                    q_pos, k_pos_per_step[i], causal=True, scale=scale,
                )
                total = total + jnp.sum(dq_i) + jnp.sum(dk_i) + jnp.sum(dv_i)
            return total

        return grad_fn

    # XLA block engine: independent _attn_block invocations from fresh
    # (m, l, acc) — the same per-step work the ring's scan body does.
    def fwd_xla_step(q, k, v, k_pos):
        acc = jnp.zeros_like(q, jnp.float32)
        l = acc[..., 0].transpose(0, 2, 1)
        m = NEG_INF + l
        m, l, acc = _attn_block(
            q, k, v, scale, q_pos, k_pos, True, m, l, acc
        )
        return _finalize(m, l, acc, q.dtype)

    def fwd_xla(q, ks, vs):
        total = jnp.float32(0)
        for i in range(cfg["r"]):
            total = total + jnp.sum(
                fwd_xla_step(q, ks[i], vs[i], k_pos_per_step[i]).astype(
                    jnp.float32
                )
            )
        return total

    if mode == "fwd":
        return fwd_xla

    def grad_xla(q, ks, vs):
        dq, dks, dvs = jax.grad(fwd_xla, argnums=(0, 1, 2))(q, ks, vs)
        return (
            jnp.sum(dq.astype(jnp.float32))
            + jnp.sum(dks.astype(jnp.float32))
            + jnp.sum(dvs.astype(jnp.float32))
        )

    return grad_xla


INNER = 8  # step-group repetitions inside one jit, so the per-dispatch
# host cost is amortized over the group being measured.


def run_variant(spec: str, mode: str):
    import jax
    import jax.numpy as jnp

    cfg = parse(spec)
    rng = np.random.RandomState(0)
    shape = (cfg["b"], cfg["t"], H, D)
    q = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    ks = jnp.asarray(rng.randn(cfg["r"], *shape), jnp.bfloat16)
    vs = jnp.asarray(rng.randn(cfg["r"], *shape), jnp.bfloat16)
    if cfg["engine"] == "pallas":
        # Kernel layout, once, outside the timed region (see build_step_fn).
        ks = ks.transpose(0, 1, 3, 2, 4)
        vs = vs.transpose(0, 1, 3, 2, 4)

    group = build_step_fn(cfg, mode)

    def looped(q, ks, vs):
        # Outer repetitions are independent (an iteration-scaled q, no
        # carry into the attention inputs) so the device pipelines them.
        def body(j, tot):
            return tot + group(q * (1 + 1e-6 * j), ks, vs)

        return jax.lax.fori_loop(0, cfg["inner"], body, jnp.float32(0))

    fn = jax.jit(looped)

    def once():
        start = time.perf_counter()
        jax.block_until_ready(fn(q, ks, vs))
        return time.perf_counter() - start

    once()
    once()
    if cfg["profile"]:
        with jax.profiler.trace("/tmp/ring_prof"):
            times = [once() for _ in range(3)]
    else:
        times = [once() for _ in range(REPEATS)]
    ms = sorted(times)[len(times) // 2] * 1e3 / cfg["inner"]
    print(
        f"{mode} {spec}: {ms:.2f} ms/group of {cfg['r']} steps "
        f"(per step {ms / cfg['r']:.2f})",
        flush=True,
    )
    return ms


def main():
    mode = sys.argv[1]
    assert mode in ("fwd", "grad")
    for spec in sys.argv[2:]:
        run_variant(spec, mode)


if __name__ == "__main__":
    main()
