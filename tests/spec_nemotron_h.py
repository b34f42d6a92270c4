"""Nemotron-H's descriptor (`tests/lm_contract.py`): where the stack,
its plain reference and its cell are, the widths the two are compared at,
and what is the model's alone.  `tests/test_nemotron_h.py` holds the
model against its reference by it, `tests/test_nemotron_h_program.py`
runs it as a job does.
"""

import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.layers.moe import RoutingLedger, SparseMoeBlock
from lm_contract import (
    SELECTION_BIAS, Bf16Case, CompileSpec, LMSpec, counter_spans,
    sublayer_at_the_stated_precision,
)


def _sublayer(kind):
    """The program's sublayer in bfloat16 and the reference's function, at
    widths where a rounding shows."""
    m = dict(SPEC.tiny, hidden_size=256, mamba_head_dim=32,
             ssm_state_size=32, head_dim=64, moe_intermediate_size=64,
             moe_shared_expert_intermediate_size=128)
    bf16 = jnp.bfloat16
    if kind == "ssm":
        return sublayer_at_the_stated_precision(zoo.Mamba2Mixer(
            m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
            m["ssm_state_size"], m["conv_kernel"], m["chunk_size"],
            m["layer_norm_epsilon"], bf16,
        ), ref._mamba2, m)
    if kind == "attn":
        return sublayer_at_the_stated_precision(zoo.Attention(
            m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"],
            bf16,
        ), ref._attention, m)
    return sublayer_at_the_stated_precision(SparseMoeBlock(
        m["n_routed_experts"], m["num_experts_per_tok"],
        m["moe_intermediate_size"], m["moe_shared_expert_intermediate_size"],
        (m["experts_first"], m["experts_held"]), True, bf16,
        score="sigmoid", expert_form="relu2",
        routed_scale=m["routed_scaling_factor"],
    ), ref._experts, m)


def _full_size(shapes, model):
    """The file's top level is the catalog's config with the reduced
    keys, and `model` (what the job and the reference run) agrees."""
    config = SPEC.config
    pattern = config["hybrid_override_pattern"]
    assert config["published"]["hybrid_override_pattern"].startswith(pattern)
    assert len(pattern) == config["num_hidden_layers"] == 9
    assert config["n_routed_experts"] == model["experts_held"] == 8
    assert (model["n_routed_experts"]
            == config["published"]["n_routed_experts"] == 128)
    # `model` keeps the router's width and the model's depth as published
    # (the init divides by sqrt of it); the top level counts what is held
    assert (model["num_hidden_layers"]
            == config["published"]["num_hidden_layers"] == 52)
    for key, value in model.items():
        if key in config and key not in ("n_routed_experts",
                                         "num_hidden_layers"):
            assert config[key] == value, key


def _costs(cost, model):
    one = ref._ssd_forward(model, 1)
    # 64 chunks x (8 groups' C B^T + 64 heads' three products), 4 layers
    assert one["flops"] == 4 * 64 * (
        8 * 2 * 128 * 128 * 128 + 64 * (2 * 128 * 128 * 64 + 4 * 128 * 64 * 128)
    )
    scan = ref.ssm_scan_cost(model, 1)
    assert scan["flops"] == 4 * one["flops"] < 0.03 * cost["flops"]
    # memory bound on a v5e: bytes / 819e9 is above flops / 197e12
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12
    experts = ref.moe_experts_cost(model, pairs=4 * 384 * 8, steps=1)
    assert experts["flops"] == 6 * 2 * 2688 * 1856 * 4 * 384 * 8


def _trained(trainer, model):
    counted = RoutingLedger()
    counted.seed_once({})
    fields = counted.task_delta(trainer.state.model_state)
    assert fields["layers"] == 4 and fields["dropped"] == 0
    # three steps of 4 x 64 tokens, two choices each, four expert layers
    assert 0 < fields["pairs"] < 3 * 4 * 64 * 2 * 4
    # 64 pairs an expert a step: blocks of 128, at most one an expert
    assert fields["block_rows"] == 128
    assert 3 * 4 <= fields["blocks"] <= 3 * 4 * 4
    # the selection bias took three steps of the balancing rule, each
    # +-1e-3 (or 0 for an expert at the mean), and none of AdamW
    gate = trainer.state.params["backbone"]["layers_1"]["mixer"]["gate"]
    moved = np.asarray(gate[SELECTION_BIAS], np.float64) / 1e-3
    np.testing.assert_allclose(moved, np.round(moved), atol=1e-3)
    assert 0 < np.abs(np.round(moved)).max() <= 3


def _journal(job, events):
    """`moe.routing` a task."""
    routing = counter_spans(events)
    assert [e["step"] for e in routing] == [2, 4]
    assert all(e["layers"] == 4 and e["held"] == 4 for e in routing)
    assert all(e["dropped"] == 0 and e["pairs"] > 0 for e in routing)
    assert all(e["load_max"] >= e["load_mean"] > 0 for e in routing)


# T = 150 in chunks of 32: four whole chunks and a padded one.
SPEC = LMSpec(
    model_def="nemotron_h.nemotron_h_lm",
    reference="nemotron_h_reference.py",
    cell="nemotron-3-nano-30b-a3b.json",
    # 666.96M parameters at the published widths, cut as the file says
    parameters=666_963_456,
    sample_tokens=150,
    held=(("held-2..5", 2, 4), ("all-held", 0, 8)),
    # the program hands the bias the load's violation in a gradient's
    # place: test_selection_bias_receives_the_load_violation
    selection_leaves=SELECTION_BIAS,
    # the reference counts what a matmul reads: not the norms, the
    # per-head vectors and the convolution's taps
    uncounted=lambda name, leaf: leaf.ndim == 1 or "conv1d" in name,
    reduced=("hybrid_override_pattern", "num_hidden_layers",
             "n_routed_experts", "vocab_size"),
    full_size=_full_size,
    # The only products of float32 operands are the routers' (`HIGHEST`),
    # one an expert layer: the state-space form's four products take
    # bfloat16 operands there (and ask for `HIGHEST` themselves in the
    # float32 model: tests/test_ssd.py).
    float32_highest=lambda tiny: tiny["hybrid_override_pattern"].count("E"),
    products_above=30,
    # The state-space layer's limit is the widest: the chunked form rounds
    # a chunk's masked scores and the chunk states where the token-by-token
    # reference rounds dt x, B and C; the same operands, in other products.
    bf16={
        "ssm-0.003": Bf16Case(lambda: _sublayer("ssm"), 3e-3, 2),
        "attn-0.001": Bf16Case(lambda: _sublayer("attn"), 1e-3, 2),
        "moe-0.0001": Bf16Case(lambda: _sublayer("moe"), 1e-4, 2),
    },
    tolerances=("highest", "highest_clear"),
    also_report=("stated", "bfloat16", "no_routed_scale"),
    # ~17.6 TFLOP a step of 8192 tokens without recomputation
    step_flops=(17e12, 18.5e12),
    costs=_costs,
    optimizer_kwargs={"warmup_steps": 2},
    trained=_trained,
    journal=_journal,
    # 1 x 8192 tokens a step: 8.0 GB of state donated (12 B x 667M) and
    # 3.94 GB of temporaries with each layer rematerialised (3.25 until
    # PR 44: a Mamba-2 layer's `in_proj` as four products holds 1.4 GB more
    # at the program's peak than one product whose result was sliced; 4.62
    # until PR 46, whose scan keeps its decays and masked scores in VMEM;
    # at 2 x 8192 it needs 16.8 GB and does not fit), the attention layer in
    # the Pallas kernel exactly at `supports`' cap (K + V of a head are 8 MiB
    # of float32).
    compile=CompileSpec(
        state=(8.0e9, 8.01e9), total={1: (0, 12.7e9)},
        # the Mamba-2 layers' passes (`ops/gdn_passes.py`) and their scan
        # (`ops/ssd.py`) in their kernels
        in_text=("conv_silu_fwd", "conv_silu_bwd", "gated_group_norm_fwd",
                 "gated_group_norm_bwd", "ssd_fwd", "ssd_bwd"),
        # 2.83 GB and a tenth (`scripts/program_copies.py nemotron_h`): the
        # experts' operands and the embedding; 9.68 GB until PR 46, 6.8 of
        # them x, y and d y laid out again between the passes' kernels and
        # the scan's XLA ops, seven times a layer
        copy_bytes=(0, 3.12e9),
        names_mesh=True,
    ),
    scope_widths=dict(
        vocab_size=64, hidden_size=32, hybrid_override_pattern="ME*",
        mamba_head_dim=8, ssm_state_size=8, chunk_size=8, head_dim=8,
        moe_intermediate_size=16, moe_shared_expert_intermediate_size=16,
        experts_first=2, experts_held=4, remat=True,
    ),
    scopes=("fwd_bwd", "ssm", "ssm_scan", "attn", "moe", "moe_route",
            "moe_experts", "moe_shared", "lm_head_loss", "optimizer"),
)
zoo, ref, TINY, CONFIG = SPEC.zoo, SPEC.ref, SPEC.tiny, SPEC.config
