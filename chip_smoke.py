#!/usr/bin/env python
"""The quickest proof that elasticdl_tpu still starts on the chip.

    python chip_smoke.py             # one TPU chip; what the driver runs
    python chip_smoke.py --chips 4   # the sharded path on a 2x2 mesh, only
    python chip_smoke.py --cpu       # tiny-size rehearsal, no accelerator

With no arguments it drives the main path once on ONE chip, through the
entry points a user calls, at DeepFM's full width (26 fields x vocab
100000 = 2.6M resident rows, 13 dense, minibatch 8192, sparse Adam,
strict apply; weights random from --seed, data written from --seed):

  kernels      every Pallas kernel a user flag reaches, once, against its
               XLA twin (fused sparse lookup / lookup+FM / dedup+apply,
               flash attention fwd+bwd, the ring step), the delta
               rule's kernel pair, the two passes around it and the
               pair around a state-space scan (ops/gdn_passes.py),
               which the backend picks, that scan's own kernel pair
               (ops/ssd.py), and the pass
               in front of the attention engine (ops/rotary_pack.py) —
               correctness only
  train        `python -m elasticdl_tpu.client.main train` with
               ParameterServerStrategy on an ETRF file: master -> task
               dispatch -> one worker subprocess -> file -> native codec
               -> staged windows -> train_window -> checkpoint -> export.
               Run TWICE into one checkpoint dir: the second run restores
               the first's checkpoint and finds its programs in the
               persistent compile cache (cold / warm compile seconds).
  reference    a fresh trainer restores that checkpoint (the elastic
               resume path) and runs eval_step on seeded features
  serve        the export behind one replica process
               (serving.supervisor.start_serving_fleet), a closed loop of
               requests through scripts/loadgen.py, and the replica's
               predictions against the reference phase's
  transformer  a few AllreduceStrategy steps of transformer_lm at the
               bench shape (4 layers x d512, T=2048); the worker's own log
               must show attn_impl=auto resolved to the Pallas kernel

Each phase is a CHILD process, run in sequence: a chip belongs to one
process at a time, the train and serve phases start children of their
own, and this parent never imports jax.  Any phase that fails makes the
script exit non-zero — nothing is caught and downgraded to a warning.

The last line of stdout is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`,
with the device as the process that ran the train steps reported it.
Without a TPU the script fails and prints no such line; `--cpu` runs
tiny shapes on the CPU backend for rehearsal and its last line says
`"ok": false` and names the cpu — it can never claim the chip.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# The driver allows 1200 s, compilation included; leave it a margin.
TOTAL_BUDGET_S = 1100
# What one job, one fleet start or one load run may take.
PHASE_TIMEOUT_S = 900.0

#: One chip runs these, in this order.  `kernels` is first: it is the
#: only phase that needs nothing from an earlier one, so it doubles as
#: the device probe (no TPU -> the run ends here, seconds in).
ONE_CHIP_PHASES = ("kernels", "train", "reference", "serve", "transformer")
#: `--chips 4` runs the sharded path and what it is compared with, and
#: no other phase.
FOUR_CHIP_PHASES = ("train4",)

# Full width (bench.py bench_deepfm / TRANSFORMER_BENCH) and the tiny
# CPU rehearsal.  Depth — minibatches, requests, LM steps — is what a
# smoke cuts; widths are the models' own.
FULL = dict(
    vocab=100_000, minibatch=8192, minibatches=64, tasks=8,
    requests=48, request_rows=8, reference_rows=64,
    lm=dict(vocab=32768, d_model=512, num_heads=8, num_layers=4,
            max_len=2048),
    lm_minibatch=8, lm_steps=6,
    # kernels phase: the DeepFM table, one minibatch of ids; every apply
    # kind at `apply_ids`, Adam (DeepFM's optimizer) also at the full
    # count (the XLA dedup prologue takes ~20 s to compile there).
    table_rows=2_600_000, kernel_batch=8192, apply_ids=1024 * 26,
    attn=dict(b=4, t=2048, h=8),
)
TINY = dict(
    vocab=64, minibatch=32, minibatches=8, tasks=4,
    requests=12, request_rows=4, reference_rows=16,
    lm=dict(vocab=64, d_model=32, num_heads=2, num_layers=1, max_len=32),
    lm_minibatch=4, lm_steps=4,
    table_rows=26 * 64, kernel_batch=8, apply_ids=2 * 26,
    attn=dict(b=1, t=64, h=2),
)

NUM_DENSE, NUM_CAT = 13, 26  # model_zoo/deepfm: Criteo's field counts


class SmokeError(Exception):
    """A phase found something wrong; the message is the finding."""


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f"--- tail of {path} ---\n" + f.read().decode(
                "utf-8", "replace"
            )
    except OSError as exc:
        return f"--- {path}: {exc} ---"


def _result_path(work_dir: str, phase: str) -> str:
    return os.path.join(work_dir, f"result_{phase}.json")


def _write_result(args, phase: str, result: dict) -> None:
    with open(_result_path(args.work_dir, phase), "w") as f:
        json.dump(result, f)


def _read_result(args, phase: str) -> dict:
    with open(_result_path(args.work_dir, phase)) as f:
        return json.load(f)


def _sizes(args) -> dict:
    return TINY if args.cpu else FULL


def _device_of(jax) -> dict:
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def _require_device(args, phase: str, device: dict, count: int = 1) -> None:
    """The device a phase ran on is the one the mode asked for.  Without
    --cpu nothing finishes on the CPU backend."""
    want = "cpu" if args.cpu else "tpu"
    if device["platform"] != want:
        raise SmokeError(
            f"{phase} ran on {device['platform']!r} ({device['kind']}), "
            f"not on {want!r}"
        )
    if device["count"] != count:
        raise SmokeError(
            f"{phase} saw {device['count']} device(s), expected {count}"
        )


def _cache_entries(cache_dir: str) -> int:
    try:
        return len(os.listdir(cache_dir))
    except OSError:
        return 0


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def _criteo_columns(n: int, vocab: int, seed: int):
    """Criteo-shaped columns from a seed.  The label follows a rule the
    model can learn (one dense feature plus the parity of one id), so
    first-vs-last loss says something."""
    import numpy as np

    rng = np.random.RandomState(seed)
    dense = rng.rand(n, NUM_DENSE).astype(np.float32)
    cat = rng.randint(0, vocab, size=(n, NUM_CAT)).astype(np.int32)
    label = ((dense[:, 0] + 0.5 * (cat[:, 0] % 2)) > 0.75).astype(np.uint8)
    return dense, cat, label


def _write_criteo_etrf(path: str, n: int, vocab: int, seed: int) -> None:
    """Fixed-width Criteo records (model_zoo/deepfm criteo_record_layout:
    13 f32 dense, 26 i32 ids, 1 u8 label) in the repo's ETRF format."""
    import numpy as np

    from elasticdl_tpu.data import recordfile

    dense, cat, label = _criteo_columns(n, vocab, seed)
    image = np.concatenate(
        [dense.view(np.uint8), cat.view(np.uint8), label[:, None]], axis=1
    )
    recordfile.write_records(path, (row.tobytes() for row in image))


def _reference_features(args) -> dict:
    sizes = _sizes(args)
    dense, cat, _ = _criteo_columns(
        sizes["reference_rows"], sizes["vocab"], args.seed + 1
    )
    return {"dense": dense, "cat": cat}


# ---------------------------------------------------------------------------
# jobs through the CLI
# ---------------------------------------------------------------------------


def _run_job(phase: str, job_name: str, argv: list, log_path: str,
             ckpt_dir: str, env: dict, timeout_s: float) -> float:
    """`python -m elasticdl_tpu.client.main train ...` as a user runs
    it: this process is only its parent; the master is that process and
    the worker is the master's child.  Returns wall seconds."""
    cmd = [
        sys.executable, "-m", "elasticdl_tpu.client.main", "train",
        f"--job_name={job_name}", *argv,
    ]
    say(phase, "$ " + " ".join(cmd[1:]))
    start = time.monotonic()
    with open(log_path, "wb") as log:
        proc = subprocess.run(
            cmd, cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
            timeout=timeout_s,
        )
    wall = time.monotonic() - start
    if proc.returncode != 0:
        raise SmokeError(
            f"{job_name} exited {proc.returncode} after {wall:.0f}s\n"
            + _tail(log_path) + "\n"
            + _tail(_worker_log(ckpt_dir, job_name), 6000)
        )
    return wall


def _worker_log(ckpt_dir: str, job_name: str) -> str:
    # master/job_runner._build_worker_manager: LocalProcessManager log_dir
    return os.path.join(ckpt_dir, f"{job_name}_worker_logs", "worker_0.log")


_MESH_RE = re.compile(
    r"Built mesh (\d+)x(\d+) .* over (\d+) (\S+) device\(s\) \[(.*)\]"
)
_LOSS_RE = re.compile(r"task \d+ done: step=(\d+) loss=(\S+)")


def _read_job(tb_dir: str, worker_log: str, n_records: int) -> dict:
    """What a finished job's own records say: the master journal
    (dispatch/done per task), the worker journal (compile spans, kernel
    selection) and the worker log (device, codec, cache, losses)."""
    from elasticdl_tpu.obs.report import load_events

    dispatched, done = {}, set()
    for event in load_events(os.path.join(tb_dir, "events.jsonl")):
        if event.get("type") != "TRAINING":
            continue
        if event["event"] == "task_dispatch":
            dispatched[event["task_id"]] = (event["start"], event["end"])
        elif event["event"] == "task_done":
            done.add(event["task_id"])
    covered = sorted(dispatched[t] for t in done if t in dispatched)
    cursor = 0
    for lo, hi in covered:
        if lo != cursor:
            break
        cursor = hi
    if cursor != n_records or len(covered) != len(set(covered)):
        raise SmokeError(
            f"records covered by finished tasks: {covered} — expected "
            f"[0, {n_records}) exactly once"
        )

    compile_s, kernel = 0.0, None
    for event in load_events(os.path.join(tb_dir, "events_worker_0.jsonl")):
        if event["event"] == "span" and event.get("name") == "step.compile":
            compile_s += event["duration_s"]
        elif event["event"] == "sparse_kernel_selected":
            kernel = {k: event[k] for k in ("kernel", "requested", "route")}

    with open(worker_log, errors="replace") as f:
        text = f.read()
    mesh = _MESH_RE.search(text)
    if mesh is None:
        raise SmokeError("worker log has no 'Built mesh' line\n"
                         + _tail(worker_log))
    losses = [(int(s), float(l)) for s, l in _LOSS_RE.findall(text)]
    if not losses or not all(math.isfinite(l) for _, l in losses):
        raise SmokeError(f"worker losses not all finite: {losses}")

    def logged(pattern):
        found = re.search(pattern, text)
        return found.group(1).strip() if found else None

    return {
        "tasks": len(covered),
        "mesh": [int(mesh.group(1)), int(mesh.group(2))],
        "device": {
            "platform": mesh.group(4), "kind": mesh.group(5),
            "count": int(mesh.group(3)),
        },
        "compile_s": round(compile_s, 3),
        "sparse_kernel": kernel,
        "losses": losses,
        "codec": logged(r"ETRF record codec: (\S+)"),
        "cache_dir": logged(r"JAX compilation cache: (\S+)"),
        "attention": logged(r"attention engine: (.*)"),
        "table_bytes": json.loads(
            logged(r"Embedding-table bytes per local device: (\{.*\})")
            or "{}"
        ),
    }


def _deepfm_argv(args, data: str, ckpt: str, out: str, tb: str,
                 extra=()) -> list:
    sizes = _sizes(args)
    n = sizes["minibatch"] * sizes["minibatches"]
    return [
        "--distribution_strategy=ParameterServerStrategy",
        "--num_workers=1",
        "--model_zoo=model_zoo",
        "--model_def=deepfm.deepfm_functional_api",
        f"--model_params=vocab_size={sizes['vocab']}",
        f"--minibatch_size={sizes['minibatch']}",
        f"--records_per_task={n // sizes['tasks']}",
        f"--training_data=recordio:{data}",
        f"--checkpoint_dir={ckpt}",
        f"--output={out}",
        f"--tensorboard_log_dir={tb}",
        *extra,
    ]


def _job_env(args, devices: int = 1) -> dict:
    """Environment of a phase and of a job's processes.  The worker
    inherits it from the master (master/pod_manager.py),
    JAX_COMPILATION_CACHE_DIR included when the machine sets it."""
    env = dict(os.environ)
    if args.cpu:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}"
        )
    return env


def _build_native(phase: str) -> None:
    """Build the C++ record codec HERE, from the tracked sources.
    `libedl_kernels.so` is untracked, and `native.load()` reuses one
    that is newer than its sources — on a copied tree that is another
    machine's binary, and a failed build silently means the Python
    codec."""
    from elasticdl_tpu import native

    path = native.build_native(force=True)
    if path is None:
        raise SmokeError("native record codec did not build (no g++?)")
    say(phase, f"native codec built from kernel_api.cc + recordfile.cc: "
               f"{os.path.relpath(path, REPO)}")


# ---------------------------------------------------------------------------
# phase: kernels (its own jax process)
# ---------------------------------------------------------------------------


def phase_kernels(args) -> dict:
    import functools
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from elasticdl_tpu.common import compile_cache
    from elasticdl_tpu.ops import sparse_embedding as ske
    from elasticdl_tpu.parallel import MeshConfig, build_mesh, sparse_optim
    from elasticdl_tpu.parallel import packed as pk
    from elasticdl_tpu.parallel import ring_attention as ring
    from elasticdl_tpu.parallel.packed import PackedSpec

    fa = importlib.import_module("elasticdl_tpu.ops.flash_attention")

    cache_dir = compile_cache.configure()
    device = _device_of(jax)
    say("kernels", f"device: {device}; compile cache {cache_dir} "
                   f"({_cache_entries(cache_dir)} entries)")
    _require_device(args, "kernels", device)
    on_tpu = device["platform"] == "tpu"
    sizes = _sizes(args)
    checks = []

    def run(name, fn, *fn_args):
        """Compile `fn` ahead of time, so the SAME executable that runs
        is the one searched for the kernel: on the chip a Pallas entry
        point that lowered to anything but a Mosaic custom call (a
        backend whose name differs, a quiet fallback) fails here."""
        start = time.monotonic()
        compiled = jax.jit(fn).lower(*fn_args).compile()
        if on_tpu and "tpu_custom_call" not in compiled.as_text():
            raise SmokeError(f"{name}: no tpu_custom_call in the compiled "
                             "program — the kernel was demoted")
        out = jax.block_until_ready(compiled(*fn_args))
        return out, time.monotonic() - start

    def twin(fn, *fn_args):
        return jax.block_until_ready(jax.jit(fn)(*fn_args))

    def check(name, got, want, rtol, atol, seconds):
        got, want = jnp.asarray(got, jnp.float32), jnp.asarray(
            want, jnp.float32
        )
        err = jnp.abs(got - want)
        excess = float(jnp.max(err - (atol + rtol * jnp.abs(want))))
        worst = float(jnp.max(err))
        finite = bool(jnp.all(jnp.isfinite(got)))
        say("kernels", f"{name}: max|diff|={worst:.3g} "
                       f"(rtol={rtol:g} atol={atol:g}) shape="
                       f"{tuple(got.shape)} [{seconds:.1f}s]")
        if not finite or not excess <= 0:
            raise SmokeError(
                f"{name}: differs from its XLA twin beyond tolerance "
                f"(max|diff|={worst:.3g}, finite={finite})"
            )
        checks.append(name)

    key = jax.random.PRNGKey(args.seed)
    rows, batch = sizes["table_rows"], sizes["kernel_batch"]
    normal = pk.mark_iid(jax.nn.initializers.normal(1.0))

    # -- fused lookup: bit-exact for in-vocab ids (an exact f32 select on
    # both sides; tests/test_sparse_kernels.py pins the same) ------------
    spec = PackedSpec(rows, 16)
    table = pk.packed_init(spec, normal)(key, spec.packed_shape)
    ids = jax.random.randint(key, (batch * NUM_CAT,), 0, rows, jnp.int32)
    got, secs = run("fused_lookup",
                    functools.partial(ske.fused_lookup, spec), table, ids)
    check("fused_lookup == pk.lookup", got,
          twin(functools.partial(pk.lookup, spec), table, ids), 0, 0, secs)

    # -- fused lookup + FM on DeepFM's combined 1+8 table: activations
    # bit-exact, sums to reduction order (kernel: sequential field loop;
    # twin: jnp tree reductions) — the tests' tolerances ---------------
    spec = PackedSpec(rows, 9)
    table = pk.packed_init(spec, normal)(key, spec.packed_shape)
    fm_ids = ids.reshape(batch, NUM_CAT)
    valid = jax.random.bernoulli(key, 0.95, fm_ids.shape)
    bet = jnp.zeros((batch, NUM_CAT, spec.dim), jnp.float32)
    (acts, first, sum_v, sum_sq), secs = run(
        "fused_lookup_fm", functools.partial(ske.fused_lookup_fm, spec),
        table, bet, fm_ids, valid,
    )

    def fm_twin(table, fm_ids, valid):
        acts = pk.lookup(spec, table, fm_ids.reshape(-1)).reshape(
            batch, NUM_CAT, spec.dim
        ) * valid[..., None].astype(jnp.float32)
        return (acts,) + tuple(ske.fm_stats_xla(acts))

    t_acts, t_first, t_sum_v, t_sum_sq = twin(fm_twin, table, fm_ids, valid)
    check("fused_lookup_fm acts == pk.lookup", acts, t_acts, 0, 0, secs)
    check("fused_lookup_fm first ~ fm_stats_xla", first, t_first,
          1e-6, 1e-5, secs)
    check("fused_lookup_fm sum_v ~ fm_stats_xla", sum_v, t_sum_v,
          1e-6, 1e-5, secs)
    check("fused_lookup_fm sum_sq ~ fm_stats_xla", sum_sq, t_sum_sq,
          1e-6, 1e-4, secs)

    # -- fused dedup+apply vs the scatter path, per optimizer kind.  The
    # tests pin <= 1 ulp (rtol 3e-7) where both sides are XLA:CPU; on the
    # chip one side is Mosaic and the other XLA:TPU, whose divide, sqrt
    # and pow are each accurate to an ulp or two but not the same
    # instruction sequence, so the bound here is a few ulp of the update
    # chain (rtol 2e-6), still far below any arithmetic slip ------------
    optimizers = {
        "sgd": lambda mode: sparse_optim.sgd(0.1, mode=mode),
        "momentum": lambda mode: sparse_optim.momentum(0.1, mode=mode),
        "adagrad": lambda mode: sparse_optim.adagrad(0.1, mode=mode),
        "adam": lambda mode: sparse_optim.adam(0.01, mode=mode),
        "adam_global": lambda mode: sparse_optim.adam(
            0.01, mode=mode, bias_correction="global"
        ),
    }
    cases = [(name, sizes["apply_ids"]) for name in optimizers]
    cases.append(("adam", batch * NUM_CAT))
    for name, n_ids in cases:
        a_ids = jax.random.randint(key, (n_ids,), 0, rows, jnp.int32)
        # duplicates, padding and an out-of-range id ride along
        a_ids = a_ids.at[0].set(a_ids[1]).at[2].set(-1).at[3].set(rows + 9)
        grads = jax.random.normal(key, (n_ids, spec.dim), jnp.float32)
        outs = {}
        for mode in ("scatter", "fused"):
            opt = optimizers[name](mode)
            slots = opt.init_slots(spec, table)
            apply = functools.partial(opt.apply, spec)
            if name == "sgd" and mode == "scatter":
                # sgd has no dedup path; apply_acc on the accumulated
                # gradient is its dedup-equivalent (as the tests do).
                def apply(table, slots, a_ids, grads, opt=opt):
                    acc = pk.grad_accumulate(spec, table, a_ids, grads)
                    return opt.apply_acc(spec, table, slots, acc)
            if mode == "fused":
                outs[mode], secs = run(
                    f"fused_dedup_apply[{name}]", apply, table, slots,
                    a_ids, grads,
                )
            else:
                outs[mode] = twin(apply, table, slots, a_ids, grads)
        label = f"fused_dedup_apply[{name}, {n_ids} ids]"
        check(f"{label} table", outs["fused"][0], outs["scatter"][0],
              2e-6, 1e-6, secs)
        for slot in sorted(outs["scatter"][1]):
            check(f"{label} slot {slot}", outs["fused"][1][slot],
                  outs["scatter"][1][slot], 2e-6, 1e-6, secs)
        del outs

    # -- flash attention vs the XLA blockwise engine, bf16 as the model
    # runs it: outputs and input gradients within bf16's own resolution
    # (tests/test_flash_attention.py: atol = rtol = 0.05 at bf16) --------
    shape = sizes["attn"]
    for d in (64, 128):
        qkv = [
            jax.random.normal(k, (shape["b"], shape["t"], shape["h"], d),
                              jnp.bfloat16)
            for k in jax.random.split(jax.random.PRNGKey(args.seed + d), 3)
        ]

        def flash(q, k, v):
            return fa.flash_attention(q, k, v, causal=True)

        def blockwise(q, k, v):
            return ring.blockwise_attention(q, k, v, causal=True)

        def grads_of(attend):
            def loss(q, k, v):
                return jnp.sum(attend(q, k, v).astype(jnp.float32) ** 2)

            return jax.grad(loss, argnums=(0, 1, 2))

        got, secs = run(f"flash_attention fwd D{d}", flash, *qkv)
        check(f"flash_attention fwd D{d} ~ blockwise_attention", got,
              twin(blockwise, *qkv), 0.05, 0.05, secs)
        got, secs = run(f"flash_attention bwd D{d}", grads_of(flash), *qkv)
        want = twin(grads_of(blockwise), *qkv)
        for g, w, which in zip(got, want, "qkv"):
            scale = float(jnp.max(jnp.abs(w.astype(jnp.float32))))
            check(f"flash_attention bwd D{d} d{which} ~ blockwise", g, w,
                  0.05, 0.05 * scale, secs)

    # -- the ring step kernels (flash_ring_step_carry / _bwd) through the
    # ring engine on a one-device ring, against the XLA ring engine ------
    mesh = build_mesh(MeshConfig())
    qkv = [
        jax.random.normal(k, (shape["b"], shape["t"], shape["h"], 128),
                          jnp.bfloat16)
        for k in jax.random.split(jax.random.PRNGKey(args.seed + 7), 3)
    ]

    def ring_grads(impl):
        attend = ring.make_ring_attention(mesh, causal=True, impl=impl)

        def loss(q, k, v):
            out = attend(q, k, v)
            return jnp.sum(out.astype(jnp.float32) ** 2), out

        return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)

    (got_g, got_out), secs = run("ring step", ring_grads("pallas"), *qkv)
    want_g, want_out = twin(ring_grads("xla"), *qkv)
    check("ring step carry ~ xla ring engine", got_out, want_out,
          0.05, 0.05, secs)
    for g, w, which in zip(got_g, want_g, "qkv"):
        scale = float(jnp.max(jnp.abs(w.astype(jnp.float32))))
        check(f"ring step bwd d{which} ~ xla ring engine", g, w,
              0.05, 0.05 * scale, secs)

    # -- the chunked gated delta rule's kernel pair against its XLA engine
    # (both three bfloat16 passes a float32 product on the chip, 2e-5 rms
    # apart there; one pass reads 4e-3) ------------------------------------
    from elasticdl_tpu.ops import gated_delta

    t_rule = shape["t"] // 2
    keys = jax.random.split(jax.random.PRNGKey(args.seed + 11), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    rule_args = [
        unit(jax.random.normal(keys[0], (1, t_rule, 2, 128))) / 128 ** 0.5,
        unit(jax.random.normal(keys[1], (1, t_rule, 2, 128))),
        jax.random.normal(keys[2], (1, t_rule, 4, 128)),
        -0.3 * jnp.exp(jax.random.normal(keys[3], (1, t_rule, 4))),
        jax.nn.sigmoid(jax.random.normal(keys[4], (1, t_rule, 4))),
    ]

    def rule_grads(rule):
        def loss(*xs):
            out, state = rule(*xs)
            return jnp.sum(out ** 2) + jnp.sum(state), out

        return jax.grad(loss, argnums=range(5), has_aux=True)

    (got_g, got_out), secs = run(
        "delta rule", rule_grads(gated_delta.chunk_gated_delta_rule_pallas),
        *rule_args,
    )
    want_g, want_out = twin(
        rule_grads(gated_delta.chunk_gated_delta_rule_xla), *rule_args
    )
    check("delta rule fwd ~ xla engine", got_out, want_out, 1e-3, 1e-3, secs)
    for g, w, which in zip(got_g, want_g, ("q", "k", "v", "g", "beta")):
        scale = float(jnp.max(jnp.abs(w)))
        check(f"delta rule bwd d{which} ~ xla engine", g, w,
              1e-3, 1e-3 * scale, secs)

    # -- what surrounds the rule, one pass each way, against the plain
    # jax.numpy chain: float32 elementwise on both sides, so 1e-5 ----------
    from elasticdl_tpu.ops import gdn_passes

    keys = jax.random.split(jax.random.PRNGKey(args.seed + 13), 5)
    rows, gate, d_out = (
        jax.random.normal(k, (2, t_rule, 4 * 128)) for k in keys[:3]
    )
    taps = jax.random.normal(keys[3], (4, 4 * 128))
    weight = jax.random.normal(keys[4], (128,))

    def pass_grads(pallas):
        def loss(rows, taps, gate, weight):
            mixed = gdn_passes.conv_silu(
                rows, taps, head=128, scale=128 ** -0.5, pallas=pallas
            )
            out = gdn_passes.gated_rms_norm(
                rows + mixed, gate, weight, pallas=pallas
            )
            return jnp.sum(out * d_out), (mixed, out)

        return jax.grad(loss, argnums=range(4), has_aux=True)

    (got_g, got_out), secs = run(
        "gdn passes", pass_grads(True), rows, taps, gate, weight
    )
    want_g, want_out = twin(pass_grads(False), rows, taps, gate, weight)
    for g, w, which in zip(
        got_out + got_g, want_out + want_g,
        ("conv_silu", "gated_rms_norm", "d rows", "d taps", "d gate",
         "d weight"),
    ):
        scale = max(float(jnp.max(jnp.abs(w))), 1.0)
        check(f"gdn passes {which} ~ jax.numpy chain", g, w,
              1e-5, 1e-5 * scale, secs)

    # -- the same for the pair a Mamba-2 layer calls: the convolution with
    # its bias and no norm by head, then the skip, the gate and the norm by
    # group -----------------------------------------------------------------
    keys = jax.random.split(jax.random.PRNGKey(args.seed + 15), 7)
    rows, y, z, d_out = (
        jax.random.normal(k, (2, t_rule, 512)) for k in keys[:4]
    )
    taps = jax.random.normal(keys[4], (4, 512))
    bias, weight = jax.random.normal(keys[5], (2, 512))
    skip = jax.random.normal(keys[6], (8,))

    def group_grads(pallas):
        def loss(rows, taps, bias, y, z, skip, weight):
            x = gdn_passes.conv_silu(rows, taps, bias, pallas=pallas)
            out = gdn_passes.gated_group_norm(
                y, x, z, skip, weight, groups=2, eps=1e-5, pallas=pallas
            )
            return jnp.sum(out * d_out), (x, out)

        return jax.grad(loss, argnums=range(7), has_aux=True)

    operands = (rows, taps, bias, y, z, skip, weight)
    (got_g, got_out), secs = run(
        "state-space passes", group_grads(True), *operands
    )
    want_g, want_out = twin(group_grads(False), *operands)
    for g, w, which in zip(
        got_out + got_g, want_out + want_g,
        ("conv_silu", "gated_group_norm", "d rows", "d taps", "d bias",
         "d y", "d z", "d skip", "d weight"),
    ):
        scale = max(float(jnp.max(jnp.abs(w))), 1.0)
        check(f"state-space passes {which} ~ jax.numpy chain", g, w,
              1e-5, 1e-5 * scale, secs)

    # -- the scan between them (ops/ssd.py): its kernel pair against the
    # XLA form at the same bfloat16 products, two groups of eight heads in
    # chunks of 128 and one of sixteen in chunks of 256; the roundings
    # fall at the same places and the float32 sums in another order, so a
    # rounding moves here and there: 1% of the largest (3% off the chip,
    # where the XLA form's backward keeps d y float32 and the kernels
    # round it as a TPU's default does) -------------------------------------
    from elasticdl_tpu.ops import ssd

    for groups, chunk in ((2, 128), (1, 256)):
        keys = jax.random.split(jax.random.PRNGKey(args.seed + 16), 5)
        scan_args = (
            jax.random.normal(keys[0], (1, t_rule, 16 * 64)),
            jnp.exp(jax.random.uniform(
                keys[1], (1, t_rule, 16), minval=jnp.log(1e-3),
                maxval=jnp.log(0.1),
            )),
            -jax.random.uniform(keys[2], (16,), minval=1.0, maxval=16.0),
            jax.random.normal(keys[3], (1, t_rule, 2 * groups * 128)),
        )
        d_out = jax.random.normal(keys[4], (1, t_rule, 16 * 64))

        def scan_grads(kernels):
            def loss(x, dt, a, bc):
                if kernels:
                    out, state = ssd.ssd_chunked_pallas(
                        x, dt, a, bc, groups=groups, chunk=chunk
                    )
                else:
                    b, c = jnp.split(
                        bc.reshape(1, t_rule, 2 * groups, 128), 2, axis=2
                    )
                    out, state = ssd.ssd_chunked_xla(
                        x.reshape(1, t_rule, 16, 64), dt, a, b, c,
                        chunk=chunk, dtype=jnp.bfloat16,
                    )
                    out = out.reshape(1, t_rule, 16 * 64)
                return jnp.sum(out * d_out) + jnp.sum(state), (out, state)

            return jax.grad(loss, argnums=range(4), has_aux=True)

        (got_g, got_out), secs = run(
            f"state-space scan, chunks of {chunk}", scan_grads(True),
            *scan_args,
        )
        want_g, want_out = twin(scan_grads(False), *scan_args)
        for g, w, which in zip(
            got_out + got_g, want_out + want_g,
            ("y", "final state", "d x", "d dt", "d a", "d [B | C]"),
        ):
            limit = 1e-2 if on_tpu else 3e-2
            check(f"state-space scan, chunks of {chunk}, {which} ~ xla "
                  "engine", g, w, limit,
                  limit * float(jnp.max(jnp.abs(w))), secs)

    # -- from a projection's result to the attention engine's operand
    # (ops/rotary_pack.py), against apply_rotary after the head norm: the
    # same float32 ops on both sides (1e-6: the order of a norm's sum);
    # the bfloat16 operand within one rounding ------------------------------
    from elasticdl_tpu.ops import gqa, rotary_pack

    keys = jax.random.split(jax.random.PRNGKey(args.seed + 17), 3)
    result = jax.random.normal(keys[0], (2, t_rule, 4, 128))
    weight = 1.0 + 0.1 * jax.random.normal(keys[1], (128,))
    d_operand = jax.random.normal(keys[2], (2, 4, t_rule, 128))
    for rotary_dim, normed in ((128, False), (64, True)):
        cos, sin = gqa.rotary_tables(jnp.arange(t_rule), rotary_dim, 1e4)

        def pack_grads(kernels, dtype):
            def loss(x, w):
                w = w if normed else None
                out = (
                    rotary_pack.rotary_pack(
                        x, cos, sin, dtype, w, interpret=not on_tpu
                    ) if kernels
                    else rotary_pack.rotary_pack_xla(x, cos, sin, dtype, w)
                )
                return jnp.sum(out * d_operand), out

            return jax.grad(loss, argnums=(0, 1), has_aux=True)

        what = f"rotary_pack {rotary_dim}/128" + (" normed" if normed else "")
        for dtype, rtol in ((jnp.float32, 1e-6), (jnp.bfloat16, 2 ** -7)):
            (got_g, got_out), secs = run(
                what, pack_grads(True, dtype), result, weight
            )
            want_g, want_out = twin(pack_grads(False, dtype), result, weight)
            name = f"{what} {jnp.dtype(dtype).name}"
            check(f"{name} ~ apply_rotary", got_out, want_out, rtol, 1e-6,
                  secs)
            check(f"{name} d result ~ its vjp", got_g[0], want_g[0],
                  1e-5, 1e-5, secs)
            if normed:
                scale = float(jnp.max(jnp.abs(want_g[1])))
                check(f"{name} d weight ~ its vjp", got_g[1], want_g[1],
                      1e-5, 1e-5 * scale, secs)

    return {"device": device, "checks": len(checks)}


# ---------------------------------------------------------------------------
# phase: train (parent of the CLI; touches no backend)
# ---------------------------------------------------------------------------


def _train_paths(args) -> dict:
    w = args.work_dir
    return {
        "data": os.path.join(w, "criteo.etrf"),
        "ckpt": os.path.join(w, "deepfm_ckpt"),
        "export": os.path.join(w, "deepfm_export"),
    }


def _report_job(phase: str, tag: str, job: dict, wall: float) -> None:
    first, last = job["losses"][0], job["losses"][-1]
    say(phase, f"{tag}: wall {wall:.1f}s, compile {job['compile_s']:.1f}s, "
               f"{job['tasks']} tasks, "
               f"steps {first[0]}..{last[0]}, loss {first[1]:.5f} -> "
               f"{last[1]:.5f}")
    say(phase, f"{tag}: mesh {job['mesh'][0]}x{job['mesh'][1]} on "
               f"{job['device']}, sparse kernel {job['sparse_kernel']}, "
               f"codec {job['codec']}, cache {job['cache_dir']}")


def phase_train(args) -> dict:
    sizes = _sizes(args)
    paths = _train_paths(args)
    n = sizes["minibatch"] * sizes["minibatches"]
    _build_native("train")
    start = time.monotonic()
    _write_criteo_etrf(paths["data"], n, sizes["vocab"], args.seed)
    say("train", f"wrote {n} Criteo-shaped records "
                 f"({os.path.getsize(paths['data']) / 1e6:.0f} MB ETRF, seed "
                 f"{args.seed}) in {time.monotonic() - start:.1f}s")
    say("train", f"DeepFM vocab_size={sizes['vocab']} x {NUM_CAT} fields, "
                 f"minibatch {sizes['minibatch']}, {sizes['minibatches']} "
                 f"minibatches in {sizes['tasks']} tasks")
    steps = sizes["minibatches"]
    runs = []
    for i, tag in enumerate(("run 1 (fresh)", "run 2 (resumed)")):
        tb = os.path.join(args.work_dir, f"deepfm_tb{i + 1}")
        wall = _run_job(
            "train", "smoke-deepfm",
            _deepfm_argv(args, paths["data"], paths["ckpt"],
                         paths["export"], tb),
            os.path.join(args.work_dir, f"deepfm_job{i + 1}.log"),
            paths["ckpt"], _job_env(args), PHASE_TIMEOUT_S,
        )
        job = _read_job(tb, _worker_log(paths["ckpt"], "smoke-deepfm"), n)
        _report_job("train", tag, job, wall)
        _require_device(args, f"train {tag}", job["device"])
        if job["codec"] != "native":
            raise SmokeError(f"the worker read the file with the "
                             f"{job['codec']!r} codec, not the native one")
        # Run 2 restores run 1's final checkpoint, so its steps go on
        # from there: that is the elastic-resume path, on the chip.
        want_last = steps * (i + 1)
        if job["losses"][-1][0] != want_last:
            raise SmokeError(f"{tag} ended at step {job['losses'][-1][0]}, "
                             f"expected {want_last}")
        if not os.path.isfile(os.path.join(paths["export"],
                                           "signature.json")):
            raise SmokeError(f"{tag} left no export at {paths['export']}")
        runs.append(dict(job, wall_s=round(wall, 1)))
    say("train", f"compile seconds: {runs[0]['compile_s']:.1f} in run 1, "
                 f"{runs[1]['compile_s']:.1f} in run 2 (same programs, "
                 f"persistent cache {runs[1]['cache_dir']}, "
                 f"{_cache_entries(runs[1]['cache_dir'])} entries now)")
    return {"device": runs[0]["device"], "runs": runs,
            "final_step": runs[-1]["losses"][-1][0]}


# ---------------------------------------------------------------------------
# phase: reference (its own jax process): restore + eval_step
# ---------------------------------------------------------------------------


def phase_reference(args) -> dict:
    import jax
    import numpy as np

    from elasticdl_tpu.checkpoint import ShardedCheckpointSaver
    from elasticdl_tpu.common import compile_cache
    from elasticdl_tpu.common.args import parse_master_args
    from elasticdl_tpu.common.model_utils import load_model_spec
    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.ps_trainer import ShardedEmbeddingTrainer

    compile_cache.configure()
    device = _device_of(jax)
    _require_device(args, "reference", device)
    paths = _train_paths(args)
    os.chdir(REPO)  # --model_zoo=model_zoo is relative, as in the job
    job_args = parse_master_args(_deepfm_argv(
        args, paths["data"], paths["ckpt"], paths["export"], ""
    ))
    spec = load_model_spec(job_args)
    mesh = build_mesh(MeshConfig())
    # The trainer as worker/main.py builds it for this job.
    trainer = ShardedEmbeddingTrainer(
        model=spec.build_model(mesh=mesh),
        loss_fn=spec.loss,
        optimizer=spec.optimizer(),
        mesh=mesh,
        embedding_optimizer=spec.embedding_optimizer(),
        sparse_apply_every=job_args.sparse_apply_every,
        sparse_kernel=job_args.sparse_kernel,
    )
    saver = ShardedCheckpointSaver(paths["ckpt"])
    step = saver.latest_step()
    want = _read_result(args, "train")["final_step"]
    if step != want:
        raise SmokeError(f"latest checkpoint is step {step}, the job "
                         f"ended at {want}")
    trainer.set_sharded_restore(saver, step)
    start = time.monotonic()
    outputs = np.asarray(trainer.eval_step(_reference_features(args)))
    say("reference", f"restored checkpoint step {step} into a fresh "
                     f"trainer and ran eval_step on {len(outputs)} seeded "
                     f"rows in {time.monotonic() - start:.1f}s "
                     f"(mean prediction {float(outputs.mean()):.5f})")
    if not np.isfinite(outputs).all():
        raise SmokeError("eval_step outputs are not all finite")
    np.save(os.path.join(args.work_dir, "reference.npy"), outputs)
    return {"device": device, "step": step}


# ---------------------------------------------------------------------------
# phase: serve (parent of the replica; touches no backend)
# ---------------------------------------------------------------------------


def phase_serve(args) -> dict:
    import numpy as np

    from elasticdl_tpu.serving.frontend import PredictClient, encode_features
    from elasticdl_tpu.serving.supervisor import (
        start_serving_fleet,
        wait_for_replicas,
    )

    sizes = _sizes(args)
    paths = _train_paths(args)
    serve_dir = os.path.join(args.work_dir, "serve")
    os.makedirs(serve_dir, exist_ok=True)
    feats = _reference_features(args)
    warm = os.path.join(args.work_dir, "warmup.npz")
    with open(warm, "wb") as f:
        f.write(encode_features({k: v[:1] for k, v in feats.items()}))
    os.chdir(REPO)
    start = time.monotonic()
    manager = start_serving_fleet(
        num_replicas=1, model_dir=paths["export"], serve_dir=serve_dir,
        worker_env=_job_env(args), model_zoo="model_zoo",
        warmup_features=warm, max_restarts=0,
    )
    replica_log = os.path.join(serve_dir, "logs")
    client = None
    try:
        try:
            live = wait_for_replicas(
                serve_dir, 1, timeout_s=600.0
            )
        except TimeoutError as exc:
            logs = [os.path.join(replica_log, name)
                    for name in sorted(os.listdir(replica_log))]
            raise SmokeError(f"{exc}\n" + "\n".join(map(_tail, logs)))
        say("serve", f"replica {live[0]['replica_id']} up on port "
                     f"{live[0]['port']} in {time.monotonic() - start:.1f}s")
        summary_path = os.path.join(args.work_dir, "loadgen.json")
        cmd = [
            sys.executable, os.path.join(REPO, "scripts", "loadgen.py"),
            "--serve_dir", serve_dir, "--mode", "closed",
            "--requests", str(sizes["requests"]), "--concurrency", "4",
            "--batch_rows", str(sizes["request_rows"]),
            "--vocab_size", str(sizes["vocab"]), "--seed", str(args.seed),
            "--deadline_s", "120", "--output", summary_path,
        ]
        say("serve", "$ " + " ".join(cmd[1:]))
        with open(os.path.join(args.work_dir, "loadgen.log"), "wb") as log:
            rc = subprocess.run(
                cmd, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
                timeout=PHASE_TIMEOUT_S,
            ).returncode
        if rc != 0:
            raise SmokeError(f"loadgen exited {rc}\n" + _tail(
                os.path.join(args.work_dir, "loadgen.log")))
        with open(summary_path) as f:
            summary = json.load(f)
        say("serve", f"loadgen closed loop: {summary['served']}/"
                     f"{sizes['requests']} served, availability "
                     f"{summary['availability_ratio']}, p50 "
                     f"{summary['latency']['p50_ms']:.1f} ms, p99 "
                     f"{summary['latency']['p99_ms']:.1f} ms")
        if (summary["served"] != sizes["requests"]
                or summary["availability_ratio"] != 1.0):
            raise SmokeError(f"not every request was served: {summary}")

        client = PredictClient(f"127.0.0.1:{live[0]['port']}",
                               deadline_s=120.0)
        served = np.asarray(client.predict(feats))
        want = np.load(os.path.join(args.work_dir, "reference.npy"))
        # Same f32 weights (the export and the checkpoint are two
        # serializations of one state) and the same default matmul
        # precision on both sides; the programs differ only in batch
        # padding and fusion, so they agree to f32 rounding, not bitwise
        # (tests/test_serving.py holds the same pair to rtol 1e-5 on the
        # CPU; 1e-4 leaves room for the chip's bf16-pass matmuls
        # reassociating across a padded batch).
        worst = float(np.max(np.abs(served - want)))
        say("serve", f"replica predictions vs the restored trainer's "
                     f"eval_step on {len(want)} rows: max|diff|="
                     f"{worst:.3g} (rtol=1e-4 atol=1e-5)")
        if served.shape != want.shape or not np.allclose(
                served, want, rtol=1e-4, atol=1e-5):
            raise SmokeError("served predictions differ from the "
                             "trainer's eval_step beyond tolerance")
        stats = client.stats()
    finally:
        if client is not None:
            client.close()
        manager.stop()
    logs = sorted(os.listdir(replica_log))
    with open(os.path.join(replica_log, logs[0]), errors="replace") as f:
        mesh = _MESH_RE.search(f.read())
    if mesh is None:
        raise SmokeError("replica log has no 'Built mesh' line")
    device = {"platform": mesh.group(4), "kind": mesh.group(5),
              "count": int(mesh.group(3))}
    say("serve", f"replica ran on {device}; generation "
                 f"{stats.get('generation')} at step {stats.get('step')}")
    _require_device(args, "serve", device)
    return {"device": device, "served": summary["served"]}


# ---------------------------------------------------------------------------
# phase: transformer (parent of the CLI; touches no backend)
# ---------------------------------------------------------------------------


def phase_transformer(args) -> dict:
    sizes = _sizes(args)
    lm = sizes["lm"]
    n = sizes["lm_minibatch"] * sizes["lm_steps"]
    ckpt = os.path.join(args.work_dir, "lm_ckpt")
    tb = os.path.join(args.work_dir, "lm_tb")
    params = ",".join(f"{k}={v}" for k, v in lm.items())
    argv = [
        "--distribution_strategy=AllreduceStrategy",
        "--num_workers=1",
        "--model_zoo=model_zoo",
        "--model_def=transformer.transformer_lm",
        f"--model_params={params}",
        f"--minibatch_size={sizes['lm_minibatch']}",
        f"--records_per_task={n // 2}",
        f"--training_data=synthetic://lm?n={n}&len={lm['max_len']}"
        f"&vocab={lm['vocab']}&seed={args.seed}",
        f"--checkpoint_dir={ckpt}",
        f"--tensorboard_log_dir={tb}",
    ]
    say("transformer", f"transformer_lm {lm}, minibatch "
                       f"{sizes['lm_minibatch']}, {sizes['lm_steps']} steps")
    wall = _run_job("transformer", "smoke-lm", argv,
                    os.path.join(args.work_dir, "lm_job.log"), ckpt,
                    _job_env(args), PHASE_TIMEOUT_S)
    job = _read_job(tb, _worker_log(ckpt, "smoke-lm"), n)
    first, last = job["losses"][0], job["losses"][-1]
    say("transformer", f"wall {wall:.1f}s, compile {job['compile_s']:.1f}s,"
                       f" steps {first[0]}..{last[0]}, loss {first[1]:.5f}"
                       f" -> {last[1]:.5f} on {job['device']}")
    say("transformer", f"attention engine (worker log): {job['attention']}")
    _require_device(args, "transformer", job["device"])
    if last[0] != sizes["lm_steps"]:
        raise SmokeError(f"ended at step {last[0]}, expected "
                         f"{sizes['lm_steps']}")
    # attn_impl=auto: the Pallas kernel, compiled, on the chip; the XLA
    # blockwise engine on the CPU (transformer_lm._single_device_attend).
    want = ("xla blockwise_attention" if args.cpu
            else "pallas flash_attention")
    attention = job["attention"] or ""
    if want not in attention or "interpret=True" in attention:
        raise SmokeError(f"attn_impl=auto resolved to {attention!r}; "
                         f"expected the {want} engine")
    return {"device": job["device"], "attention": attention}


# ---------------------------------------------------------------------------
# phase: train4 (--chips 4; parent of the CLI; touches no backend)
# ---------------------------------------------------------------------------

# One chip of a four-chip host (libtpu's own variables; jax's
# multi-process tests pin chips to processes the same way).
_ONE_OF_FOUR_CHIPS = {
    "TPU_VISIBLE_CHIPS": "0",
    "TPU_VISIBLE_DEVICES": "0",  # the same, by libtpu's older name
    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
    "TPU_PROCESS_BOUNDS": "1,1,1",
}


def phase_train4(args) -> dict:
    sizes = _sizes(args)
    data = os.path.join(args.work_dir, "criteo.etrf")
    n = sizes["minibatch"] * sizes["minibatches"]
    _build_native("train4")
    _write_criteo_etrf(data, n, sizes["vocab"], args.seed)
    say("train4", f"DeepFM vocab_size={sizes['vocab']} x {NUM_CAT} fields, "
                  f"minibatch {sizes['minibatch']}, {sizes['minibatches']} "
                  f"minibatches, seed {args.seed}")
    jobs = {}
    # The one-device run goes first: it is the cheaper one to lose.
    for tag, devices, extra, env_extra in (
        ("one", 1, (), {} if args.cpu else _ONE_OF_FOUR_CHIPS),
        ("mesh", 4, ("--mesh_model_axis=2",), {}),
    ):
        ckpt = os.path.join(args.work_dir, f"ckpt_{tag}")
        tb = os.path.join(args.work_dir, f"tb_{tag}")
        wall = _run_job(
            "train4", f"smoke-{tag}",
            _deepfm_argv(args, data, ckpt,
                         os.path.join(args.work_dir, f"export_{tag}"), tb,
                         extra),
            os.path.join(args.work_dir, f"job_{tag}.log"), ckpt,
            {**_job_env(args, devices), **env_extra}, PHASE_TIMEOUT_S,
        )
        job = _read_job(tb, _worker_log(ckpt, f"smoke-{tag}"), n)
        _report_job("train4", f"{devices}-device run", job, wall)
        _require_device(args, f"train4 {tag}", job["device"], devices)
        jobs[tag] = job
    if jobs["mesh"]["mesh"] != [2, 2]:
        raise SmokeError(f"expected a 2x2 mesh, got {jobs['mesh']['mesh']}")

    # Same records, same order, same seed: per-task losses agree.  Four
    # devices reduce the batch in a different order (psum over `data`,
    # shard-local segment sums over `model`), and the chip's matmuls run
    # bf16 passes, so agreement is to reassociation, not bitwise: 2e-3
    # relative holds a 64-step f32 trajectory well apart from a wrong
    # gradient (tests/test_sparse_kernels.py holds the multi-device
    # windowed run to 1e-4 on the CPU over 3 steps).
    one, mesh = jobs["one"]["losses"], jobs["mesh"]["losses"]
    if [s for s, _ in one] != [s for s, _ in mesh]:
        raise SmokeError(f"step sequences differ: {one} vs {mesh}")
    worst = max(abs(a - b) / max(abs(a), 1e-6)
                for (_, a), (_, b) in zip(one, mesh))
    say("train4", f"per-task loss, one device vs 2x2 mesh: max relative "
                  f"difference {worst:.3g} over {len(one)} tasks "
                  f"(tolerance 2e-3)")
    if not worst <= 2e-3:
        raise SmokeError(f"losses diverge: {one} vs {mesh}")

    shares = jobs["mesh"]["table_bytes"]
    total = sum(shares.values())
    say("train4", f"embedding-table bytes per device (worker log): "
                  f"{shares}; one device holds "
                  f"{sum(jobs['one']['table_bytes'].values())}")
    if len(shares) != 4 or total == 0 or any(
            abs(v / total - 0.25) > 0.05 for v in shares.values()):
        raise SmokeError(f"tables are not spread over four devices in "
                         f"roughly equal shares: {shares}")
    return {"device": jobs["mesh"]["device"], "loss_rel_diff": worst}


PHASE_FNS = {
    "kernels": phase_kernels,
    "train": phase_train,
    "reference": phase_reference,
    "serve": phase_serve,
    "transformer": phase_transformer,
    "train4": phase_train4,
}


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------


def _phase_cmd(phase: str, args) -> list:
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--work_dir", args.work_dir, "--seed", str(args.seed),
           "--chips", str(args.chips)]
    return cmd + (["--cpu"] if args.cpu else [])


def _kill_group(proc) -> None:
    """Stop everything a phase started: it led its own session, and the
    master's worker and the fleet's replica stayed in it."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def run_phases(phases, args, phase_cmd=_phase_cmd) -> dict:
    """Run each phase's child to its end, in order; the first one that
    fails raises SmokeError.  Returns each phase's result."""
    deadline = time.monotonic() + TOTAL_BUDGET_S
    results = {}
    for phase in phases:
        say(phase, "start")
        start = time.monotonic()
        proc = subprocess.Popen(
            phase_cmd(phase, args), env=_job_env(args),
            start_new_session=True,
        )
        try:
            rc = proc.wait(timeout=max(1.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            raise SmokeError(f"phase {phase} overran the script's "
                             f"{TOTAL_BUDGET_S}s budget")
        finally:
            _kill_group(proc)
            proc.wait()
        if rc != 0:
            raise SmokeError(f"phase {phase} exited {rc}")
        results[phase] = _read_result(args, phase)
        say(phase, f"ok in {time.monotonic() - start:.1f}s")
    return results


def _final_line(args, phases, results) -> dict:
    """The device is what the process that ran the train steps reported
    (its worker log), and every other phase must have seen the same
    kind of device."""
    device = results[phases[-1] if args.chips == 4 else "train"]["device"]
    for phase in phases:
        other = results[phase]["device"]
        if (other["platform"], other["kind"]) != (
                device["platform"], device["kind"]):
            raise SmokeError(f"phase {phase} ran on {other}, the train "
                             f"steps on {device}")
    if args.cpu:
        # A rehearsal: every phase passed, but on the CPU backend at a
        # tiny size — never a claim about the chip.
        return {"ok": False, "rehearsal": "cpu", "phases_passed": True,
                "device": device}
    if device["platform"] != "tpu" or device["count"] != args.chips:
        raise SmokeError(f"ran on {device}, not on {args.chips} TPU chip(s)")
    return {"ok": True, "device": device}


def parse_args(argv=None):
    parser = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
    )
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: the sharded path on a 2x2 mesh against "
                        "one device, and no other phase")
    parser.add_argument("--cpu", action="store_true",
                        help="tiny-size rehearsal on the CPU backend; the "
                        "last line then says ok=false")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--work_dir", default="",
                        help="keep the run's files here (default: a temp "
                        "dir, removed at the end)")
    parser.add_argument("--phase", choices=sorted(PHASE_FNS), default="",
                        help=argparse.SUPPRESS)  # child mode
    return parser.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.phase:
        # Child mode: one phase, in this process.
        sys.path.insert(0, REPO)
        try:
            _write_result(args, args.phase, PHASE_FNS[args.phase](args))
        except SmokeError as exc:
            print(f"[{args.phase}] FAILED: {exc}", file=sys.stderr,
                  flush=True)
            return 1
        return 0

    if not os.path.isdir(os.path.join(REPO, "elasticdl_tpu")):
        print("chip_smoke.py runs from the root of an elasticdl_tpu "
              "checkout; there is none beside it", file=sys.stderr)
        return 2
    keep = bool(args.work_dir)
    args.work_dir = os.path.abspath(
        args.work_dir or tempfile.mkdtemp(prefix="chip_smoke_")
    )
    os.makedirs(args.work_dir, exist_ok=True)
    phases = FOUR_CHIP_PHASES if args.chips == 4 else ONE_CHIP_PHASES
    start = time.monotonic()
    try:
        results = run_phases(phases, args)
        final = _final_line(args, phases, results)
    except SmokeError as exc:
        print(f"chip_smoke FAILED after {time.monotonic() - start:.0f}s: "
              f"{exc}", file=sys.stderr, flush=True)
        return 1
    finally:
        if not keep:
            shutil.rmtree(args.work_dir, ignore_errors=True)
    say("smoke", f"all of {', '.join(phases)} passed in "
                 f"{time.monotonic() - start:.0f}s")
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
