"""Transformer LM (long-context config) tests: single-device and
context-parallel (ring attention over the model axis) training, plus
parity between the two; and, as the GPT-2 decoder of
`gpt2-medium.train-synth`, the zoo's language-model contract
(`tests/lm_contract.py`, at this file's `SPEC`) against the plain reference
that decides that cell's `correct` (`perfbench/configs/gpt2_reference.py`)."""

import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.parallel import MeshConfig, build_mesh
from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer
from lm_contract import (  # noqa: F401  (the contract's cases, collected here)
    LMSpec, lm, program_and_reference, pytest_generate_tests,
    test_full_size_configuration_counts_the_parameters_it_states,
    test_gradients_match_the_reference,
    test_logits_and_loss_match_the_reference,
    test_scopes_are_on_the_op_names_and_leave_outputs_bit_equal,
)
from model_zoo import datasets
from model_zoo.transformer import transformer_lm as zoo

# The cell's JSON speaks the source's keys (`n_embd`, `n_head`, ...), the
# stack its own.  The reference reads flax's automatic names (`Embed_0`,
# `Dense_0`, `LayerNorm_0`) and counts what a matmul or a gather reads: not
# the biases and the LayerNorms' pairs.  Two layers, so that a block after
# the first is read by its name too.
SPEC = LMSpec(
    model_def="transformer.transformer_lm",
    reference="gpt2_reference.py",
    cell="gpt2-medium.json",
    parameters=406_336_593,
    stated="406.3M",
    kwargs=lambda m: dict(
        vocab=m["vocab_size"], d_model=m["n_embd"], num_heads=m["n_head"],
        num_layers=m["n_layer"], max_len=m["n_positions"],
    ),
    whole_model_changes={"n_layer": 2},
    uncounted=lambda name, leaf: name.endswith(("['bias']", "['scale']")),
    job_only={},
    scope_widths=dict(vocab=64, d_model=32, num_heads=2, num_layers=1,
                      max_len=16),
    scopes=("fwd_bwd", "attn", "mlp", "lm_head_loss", "optimizer"),
)


def _batches(n=64, mb=16, seq_len=64, seed=0):
    from elasticdl_tpu.data.dataset import Dataset, _stack
    from elasticdl_tpu.proto import elasticdl_pb2 as pb

    reader = datasets.synthetic_lm_reader(
        n=n, seq_len=seq_len, vocab=zoo.VOCAB, seed=seed
    )
    task = pb.Task(task_id=1, shard_name="s", start=0, end=n)
    records = list(
        zoo.dataset_fn(
            Dataset.from_generator(lambda: reader.read_records(task)),
            "training",
            None,
        )
    )
    for i in range(0, n, mb):
        yield _stack(records[i : i + mb])


def test_lm_trains_single_device():
    mesh = build_mesh(MeshConfig(data=1, model=1),
                      devices=jax.devices()[:1])
    trainer = DataParallelTrainer(
        zoo.custom_model(d_model=64, num_layers=2),
        zoo.loss, zoo.optimizer(), mesh,
    )
    losses = []
    for epoch in range(4):
        for tokens, labels in _batches(seed=epoch % 2):
            losses.append(float(trainer.train_step(tokens, labels)))
    assert losses[-1] < losses[0] * 0.7, (
        f"no learning: {losses[:2]} -> {losses[-2:]}"
    )


def test_lm_trains_context_parallel():
    """dp=2 x cp=4: batch over `data`, sequence ring over `model`."""
    mesh = build_mesh(MeshConfig(data=2, model=4))
    trainer = DataParallelTrainer(
        zoo.custom_model(d_model=64, num_layers=2, mesh=mesh),
        zoo.loss, zoo.optimizer(), mesh,
    )
    losses = []
    for epoch in range(4):
        for tokens, labels in _batches(seed=epoch % 2):
            losses.append(float(trainer.train_step(tokens, labels)))
    assert losses[-1] < losses[0] * 0.7, (
        f"no learning: {losses[:2]} -> {losses[-2:]}"
    )


def test_cp_and_single_device_agree():
    """Same init, same batch: the context-parallel forward must match the
    single-device forward (ring attention is exact, not approximate)."""
    mesh = build_mesh(MeshConfig(data=2, model=4))
    tokens, _ = next(_batches(n=8, mb=8, seq_len=64))
    tokens = jnp.asarray(tokens)

    single = zoo.custom_model(d_model=64, use_bf16=False)
    ringed = zoo.custom_model(d_model=64, use_bf16=False, mesh=mesh)
    variables = single.init(jax.random.PRNGKey(0), tokens)
    out_single = single.apply(variables, tokens)
    out_ring = ringed.apply(variables, tokens)
    np.testing.assert_allclose(
        np.asarray(out_single), np.asarray(out_ring), atol=2e-4, rtol=2e-4
    )


import pytest as _pytest


@_pytest.mark.parametrize("extra_model_params", ["", ",model_axis_mode=tp"])
def test_lm_cluster_e2e_cp_and_tp(tmp_path, monkeypatch, extra_model_params):
    """Full cluster path, parametrized over what the model axis carries:
    2 worker processes x 2 CPU devices = a 4-device world,
    --mesh_model_axis=2 -> mesh 2x2 (data x model).

    - default (cp): the sequence ring spans PROCESS boundaries;
    - model_axis_mode=tp: GSPMD's tensor-parallel collectives run across
      processes instead.

    Both must train every record and write a checkpoint, and the worker
    logs must show the mesh genuinely reached the model (without it the
    model silently degrades to the single-device layout)."""
    import os

    from elasticdl_tpu.common.args import parse_master_args
    from elasticdl_tpu.common.constants import Mode
    from elasticdl_tpu.master.job_runner import run_allreduce_job

    monkeypatch.setenv(
        "ELASTICDL_WORKER_ENV",
        ";".join(
            f"{k}={v}"
            for k, v in {
                "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                "JAX_PLATFORMS": "cpu",
            }.items()
        ),
    )
    args = parse_master_args([
        "--model_zoo=model_zoo",
        "--model_def=transformer.transformer_lm",
        "--model_params=d_model=32,num_layers=1,num_heads=2"
        + extra_model_params,
        "--training_data=synthetic://lm?n=64&len=32",
        "--records_per_task=32",
        "--minibatch_size=8",
        "--num_workers=2",
        "--mesh_model_axis=2",
        "--distribution_strategy=AllreduceStrategy",
        f"--checkpoint_dir={tmp_path / 'ckpt'}",
        "--checkpoint_steps=4",
        "--num_epochs=1",
    ])
    rc = run_allreduce_job(args, Mode.TRAINING)
    assert rc == 0
    assert any(p.startswith("step_") for p in os.listdir(tmp_path / "ckpt"))
    # The mesh reached the model in every worker (see build_model's log).
    log_root = next(
        tmp_path / "ckpt" / d
        for d in os.listdir(tmp_path / "ckpt")
        if d.endswith("_worker_logs")
    )
    logs = "".join(
        open(log_root / f).read() for f in os.listdir(log_root)
    )
    assert "Mesh-aware model: forwarding mesh" in logs



def test_pallas_attn_impl_matches_xla():
    """attn_impl='pallas' (interpret mode on CPU) must match the XLA
    blockwise implementation through the full model."""
    tokens, _ = next(_batches(n=4, mb=4, seq_len=32))
    tokens = jnp.asarray(tokens)
    xla_model = zoo.custom_model(d_model=32, num_heads=2, num_layers=1,
                                 use_bf16=False, attn_impl="xla")
    pls_model = zoo.custom_model(d_model=32, num_heads=2, num_layers=1,
                                 use_bf16=False, attn_impl="pallas")
    variables = xla_model.init(jax.random.PRNGKey(0), tokens)
    out_x = xla_model.apply(variables, tokens)
    out_p = pls_model.apply(variables, tokens)
    np.testing.assert_allclose(
        np.asarray(out_x), np.asarray(out_p), atol=2e-4, rtol=2e-4
    )


def test_zigzag_cp_matches_single_device():
    """cp_layout='zigzag' (balanced causal ring) must be numerically
    identical to the single-device forward."""
    mesh = build_mesh(MeshConfig(data=2, model=4))
    tokens, _ = next(_batches(n=8, mb=8, seq_len=64))
    tokens = jnp.asarray(tokens)
    single = zoo.custom_model(d_model=64, use_bf16=False)
    zigzag = zoo.custom_model(d_model=64, use_bf16=False, mesh=mesh,
                              cp_layout="zigzag")
    variables = single.init(jax.random.PRNGKey(0), tokens)
    np.testing.assert_allclose(
        np.asarray(single.apply(variables, tokens)),
        np.asarray(zigzag.apply(variables, tokens)),
        atol=2e-4, rtol=2e-4,
    )


def test_remat_matches_and_trains():
    """remat=True must not change the math (same loss trajectory) while
    rematerializing block activations."""
    mesh = build_mesh(MeshConfig(data=1, model=1),
                      devices=jax.devices()[:1])
    batches = list(_batches(n=32, mb=8, seq_len=32))

    def run(remat):
        trainer = DataParallelTrainer(
            zoo.custom_model(d_model=32, num_heads=2, num_layers=2,
                             use_bf16=False, remat=remat),
            zoo.loss, zoo.optimizer(), mesh,
        )
        return [float(trainer.train_step(t, l)) for t, l in batches]

    plain, remat = run(False), run(True)
    np.testing.assert_allclose(plain, remat, rtol=1e-4, atol=1e-5)


def test_cp_worker_kill_elastic_recovery(tmp_path, monkeypatch):
    """Elasticity composes with sequence parallelism: kill a worker in a
    context-parallel (2 procs x 2 devices, ring over model axis) job —
    the world re-forms (budget 0 => shrinks to 1 fresh proc, mesh 1x2,
    the ring shrinks with it), restores from checkpoint, and every
    record still trains (asserted by the shared driver in conftest)."""
    from elasticdl_tpu.common.args import parse_master_args
    from tests.conftest import run_kill_recovery_job

    worker_env = {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "JAX_PLATFORMS": "cpu",
    }
    n_records = 512
    args = parse_master_args([
        "--model_zoo=model_zoo",
        "--model_def=transformer.transformer_lm",
        "--model_params=d_model=32,num_layers=1,num_heads=2",
        f"--training_data=synthetic://lm?n={n_records}&len=32",
        "--records_per_task=32",
        "--minibatch_size=4",
        "--num_workers=2",
        "--mesh_model_axis=2",
        "--max_worker_restarts=0",
        "--distribution_strategy=AllreduceStrategy",
        f"--checkpoint_dir={tmp_path / 'ckpt'}",
        "--checkpoint_steps=4",
        "--num_epochs=1",
    ])
    run_kill_recovery_job(
        args, n_records, worker_env, str(tmp_path / "logs"),
        wait_timeout=600,
    )


def test_tp_matches_single_device_and_trains():
    """model_axis_mode='tp': heads + MLP hidden shard over the model
    axis (Megatron-style, GSPMD splits the matmuls).  Same params =>
    same outputs as single-device; training through the trainer learns."""
    mesh = build_mesh(MeshConfig(data=2, model=4))
    tokens, _ = next(_batches(n=8, mb=8, seq_len=64))
    tokens = jnp.asarray(tokens)

    single = zoo.custom_model(d_model=64, use_bf16=False)
    tp = zoo.custom_model(d_model=64, use_bf16=False, mesh=mesh,
                          model_axis_mode="tp")
    variables = single.init(jax.random.PRNGKey(0), tokens)
    np.testing.assert_allclose(
        np.asarray(single.apply(variables, tokens)),
        np.asarray(tp.apply(variables, tokens)),
        atol=2e-4, rtol=2e-4,
    )

    trainer = DataParallelTrainer(
        zoo.custom_model(d_model=64, num_layers=2, mesh=mesh,
                         model_axis_mode="tp"),
        zoo.loss, zoo.optimizer(), mesh,
    )
    losses = []
    for epoch in range(4):
        for toks, labels in _batches(seed=epoch % 2):
            losses.append(float(trainer.train_step(toks, labels)))
    assert losses[-1] < losses[0] * 0.7, (
        f"no learning: {losses[:2]} -> {losses[-2:]}"
    )


def test_model_axis_mode_validated():
    import pytest

    mesh = build_mesh(MeshConfig(data=2, model=4))
    model = zoo.custom_model(d_model=32, mesh=mesh, model_axis_mode="typo")
    tokens = jnp.zeros((4, 32), jnp.int32)
    with pytest.raises(ValueError, match="model_axis_mode"):
        model.init(jax.random.PRNGKey(0), tokens)


def test_bf16_logits_head_parity_and_checkpoint_names():
    """logits_compute='bf16' (MXU-native head: bf16 operands, f32
    accumulate/out) must produce the same parameter tree as the f32 head
    (checkpoint-interchangeable) and logits within bf16 rounding of it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from model_zoo.transformer import transformer_lm as zoo

    kwargs = dict(vocab=128, d_model=64, num_heads=2, num_layers=1,
                  max_len=32)
    f32 = zoo.custom_model(**kwargs)
    bf16 = zoo.custom_model(logits_compute="bf16", **kwargs)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 128, size=(2, 32)), jnp.int32
    )
    v32 = f32.init(jax.random.PRNGKey(0), tokens)
    v16 = bf16.init(jax.random.PRNGKey(0), tokens)
    paths32 = {p for p, _ in jax.tree_util.tree_flatten_with_path(v32)[0]}
    paths16 = {p for p, _ in jax.tree_util.tree_flatten_with_path(v16)[0]}
    assert paths32 == paths16
    out32 = f32.apply(v32, tokens)
    out16 = bf16.apply(v32, tokens)  # SAME params through the bf16 head
    assert out16.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(out16), np.asarray(out32), rtol=0.05, atol=0.05
    )
