"""Granite 4.0-H's descriptor (`tests/lm_contract.py`): where the stack,
its plain reference and its cell are, the widths the two are compared at,
and what is the model's alone.  `tests/test_granite_hybrid.py` holds the
model against its reference by it, `tests/test_granite_hybrid_program.py`
runs it as a job does.
"""

import os

from lm_contract import (
    COUNTER_SPANS, Bf16Case, CompileSpec, LMSpec, _reference,
)


def _whole_model_in_bfloat16():
    """Ten layers deep: the chunked form rounds a chunk's masked scores and
    the chunk states where the token-by-token reference rounds dt x, B and
    C (the same operands, in other products): 0.008 / 0.013 / 0.029."""
    tokens = ref.sample(5, 1, TINY)
    return SPEC.build(TINY, use_bf16=True), (tokens,), (
        lambda params, reading: ref.forward(params, tokens, TINY, reading)[0]
    )


def _full_size(shapes, model):
    """The published widths, the cut's ten layers and an eighth of the
    vocabulary: 772,160,448 parameters by hand too; every published key
    the cut leaves alone stands as published."""
    config = SPEC.config
    mamba = (
        2048 * (4096 + 4096 + 128 + 128 + 64) + 4 * 4352 + 4352 + 3 * 64
        + 4096 + 4096 * 2048
    )
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp = 2048 * 16384 + 8192 * 2048
    assert mamba == 25_847_232 and attention == 10_485_760
    assert mlp == 50_331_648
    by_hand = (
        9 * (mamba + mlp + 4096) + attention + mlp + 4096
        + 12_544 * 2048 + 2048
    )
    assert by_hand == SPEC.parameters
    for key, value in model.items():
        if key in config and key not in config["reduced"]:
            assert config[key] == value, key
    assert config["tie_word_embeddings"] is True
    assert config["position_embedding_type"] == "nope"
    assert config["mamba_expand"] * model["hidden_size"] == (
        model["mamba_n_heads"] * model["mamba_d_head"]
    )
    assert config["shared_intermediate_size"] == config["intermediate_size"]


def _costs(cost, model):
    """At the published widths, 1 x 8192 tokens, against a count by
    hand."""
    tokens = 8192
    mamba = 2048 * 8512 + 4096 * 2048      # in_proj + out_proj
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp = 3 * 2048 * 8192
    head = 2048 * 12_544
    one = ref._ssd_forward(model, 1)
    # 32 chunks x (ONE group's C B^T + 64 heads' three products), 9 layers
    assert one["flops"] == 9 * 32 * (
        2 * 256 * 256 * 128 + 64 * (2 * 256 * 256 * 64 + 4 * 256 * 64 * 128)
    )
    assert one["tensors"] == 9 * tokens * (2 * 4096 + 2 * 128 + 64)
    assert one["states"] == 9 * 32 * 64 * 64 * 128
    scan = ref.ssm_scan_cost(model, 1)
    assert scan["flops"] == 4 * one["flops"]
    assert scan["bytes"] == 4 * (
        2 * (one["tensors"] + 2 * one["states"])
        + 2 * one["tensors"] + 3 * one["states"]
    )
    # memory bound on a v5e: bytes / 819e9 is above flops / 197e12
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12
    # the same rule as Nemotron-H's, at its shape: one yardstick
    nemotron = _reference("nemotron_h_reference.py")
    theirs = dict(
        hybrid_override_pattern="M" * 9, mamba_num_heads=64,
        mamba_head_dim=64, n_groups=1, ssm_state_size=128, chunk_size=256,
        sample_tokens=tokens,
    )
    assert nemotron.ssm_scan_cost(theirs, 1) == scan
    mlps = ref.mlp_cost(model, 1)
    assert mlps["flops"] == 8 * 10 * mlp * tokens
    assert mlps["bytes"] == 16 * 10 * mlp + 24 * 10 * tokens * 2048
    # compute bound: 10 MLPs are 33 TFLOP a step as run, 0.17 s at peak
    assert mlps["flops"] / 197e12 > 10 * mlps["bytes"] / 819e9
    assert cost["flops"] == (
        6 * (9 * mamba + attention + 10 * mlp + head) * tokens
        + 3 * 4 * tokens * tokens * 32 * 64 // 2
        + 3 * one["flops"]
    )


def _journal(job, events):
    """The two-step window program twice, the cadence checkpoint at step
    4; a second run of the same job restores it."""
    saved = sorted(
        p for p in os.listdir(job.tmp_path / "ckpt") if p.startswith("step_")
    )
    assert saved and saved[-1] == "step_000000000004"
    executed = [
        e for e in events
        if e.get("event") == "span" and e.get("name") in (
            "step.compile", "step.execute")
    ]
    assert [e["steps"] for e in executed] == [2, 2]
    # neither expert layers nor a loop: none of the counters' spans
    assert not any(e.get("name") in COUNTER_SPANS for e in events)
    assert not any(e.get("event") == "checkpoint_restored" for e in events)
    assert job.run(job.tmp_path / "tb2") == 0
    restored = [
        e for e in job.events(job.tmp_path / "tb2")
        if e.get("event") == "checkpoint_restored"
    ]
    assert [e["step"] for e in restored] == [4]


# The ten-layer pattern at hidden 64: 4 Mamba-2 heads of 16 in ONE group,
# state 16, chunks of 32 at T = 128; 4 / 2 attention heads of 16; MLP 128.
SPEC = LMSpec(
    model_def="granite_hybrid.granite_hybrid_lm",
    reference="granite_hybrid_reference.py",
    cell="granite-4.0-h-micro.json",
    parameters=772_160_448,
    # every leaf, the tied table's among them
    grad_limit=("rms", 1e-5),
    grad_leaves=9 * 12 + 8 + 2,
    reduced=("num_hidden_layers", "layer_types", "vocab_size"),
    full_size=_full_size,
    # The bfloat16 model has NO product of float32 operands (no router, no
    # gate: every product is a projection, a state-space product, an
    # attention product or the head, all with bfloat16 operands), so none
    # is left to a TPU's default.  9 x (2 projections + 4 state-space
    # products) + 4 projections and the engine's own + 10 x 2 of the MLPs
    # + the head.  (In the float32 model the state-space form's four
    # products ask for `HIGHEST` themselves: tests/test_ssd.py.)
    products_above=9 * 6 + 4 + 20 + 1,
    # closer than in float32; with everything in bfloat16 further than
    # either
    bf16=Bf16Case(_whole_model_in_bfloat16, 1.2e-2, 1.3,
                  (("bfloat16", 1.5, "highest"),), seed=2),
    also_report=("stated", "bfloat16", "no_residual_multiplier", "sqrt_scale"),
    # 39.7 TFLOP a step, no recompute
    step_flops=(39e12, 40.5e12),
    costs=_costs,
    optimizer_kwargs={"warmup_steps": 2},
    e2e_records=(8, 4, 2),
    journal=_journal,
    # 9.27 GB of state donated (12 B x 772,160,448: the LARGEST state of
    # any cell; the tied table is in it once), each of the ten layers
    # rematerialised (nine Mamba-2 layers at ONE group in chunks of 256,
    # whose decays, 537 MB a layer in the XLA form, stay in VMEM in the
    # scan's kernels since PR 46, and one attention layer in the Pallas
    # kernel: K + V of a head of 64 are 4 MiB, under `supports`' cap).
    # 12.15 GB at 1 x 8192 tokens (2.89 GB of temporaries; 12.62 and 3.35
    # until PR 46, and the cell's file still states PR 38's 12.58 and
    # 3.31: the benchmark's to restate); the chip holds 16 and ISSUE 38
    # sets 15.5 as the most this cell may need before it would have to run
    # 4096 tokens.
    compile=CompileSpec(
        # 12.92 GB, 3.65 of them temporaries; 12.15 and 2.89 until PR 52:
        # the one attention layer keeps 34 MB, and the scheduler's order
        # of the other nine layers' backward holds 0.77 GB more at its
        # peak (PERF.md section 7)
        state=(9.26e9, 9.27e9), total={1: (12.3e9, 13.3e9)},
        # the Mamba-2 layers' passes (`ops/gdn_passes.py`) and their scan
        # (`ops/ssd.py`) in their kernels
        in_text=("conv_silu_fwd", "conv_silu_bwd", "gated_group_norm_fwd",
                 "gated_group_norm_bwd", "ssd_fwd", "ssd_bwd"),
        # 0.23 GB and a tenth (`scripts/program_copies.py granite_hybrid`):
        # the embedding's rows and attention's v; 12.08 GB until PR 46,
        # x, y and d y laid out again around the scan's XLA ops
        copy_bytes=(0, 0.26e9),
        stated_sizes=("12.58 GB", "3.31 GB"), names_mesh=True,
    ),
    # a Mamba-2 and an attention layer, each followed by its MLP
    scope_widths=dict(
        vocab_size=64, hidden_size=32, mamba_d_head=8, mamba_d_state=8,
        mamba_chunk_size=8, head_dim=8, shared_intermediate_size=48,
        remat=True,
    ),
    scopes=("fwd_bwd", "ssm", "ssm_scan", "attn", "mlp", "lm_head_loss",
            "optimizer"),
)
zoo, ref, TINY, CONFIG = SPEC.zoo, SPEC.ref, SPEC.tiny, SPEC.config
