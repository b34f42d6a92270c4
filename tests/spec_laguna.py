"""Laguna's descriptor (`tests/lm_contract.py`): where the stack,
its plain reference and its cell are, the widths the two are compared at,
and what is the model's alone.  `tests/test_laguna.py` holds the
model against its reference by it, `tests/test_laguna_program.py`
runs it as a job does.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers.moe import RoutingLedger
from lm_contract import (
    SELECTION_BIAS, Bf16Case, CompileSpec, LMSpec, _size, rounded_parts,
    counter_spans,
)


def _attention_in_bfloat16(kind, heads):
    """The attention sublayer in bfloat16 against the reference with
    bfloat16 operands in the same products (the gate float32 in both)."""
    model = dict(TINY, hidden_size=256, head_dim=64, sample_tokens=128)
    cfg = SPEC.build(model, use_bf16=True).cfg
    layer = zoo.Attention(cfg, kind == "sliding_attention", heads)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, 128, 256)), jnp.float32
    )
    return layer, (x, *zoo.rotary_tables(cfg, 128)[kind]), (
        lambda params, reading: ref._attention(
            params, x[0], model, kind, heads, rounded_parts(reading)
        )
    )


def _full_size(shapes, model):
    config = SPEC.config
    stack = shapes["model"]
    assert _size(stack["layers_0"]["self_attn"]) == 29_458_432   # full
    assert _size(stack["layers_1"]["self_attn"]) == 37_879_808   # sliding
    assert _size(stack["layers_0"]["mlp"]) == 50_331_648
    assert _size(stack["layers_1"]["mlp"]) == 104_333_312 + 256  # + the bias
    # every width as published; the cut is in depth, experts held, vocabulary
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "shared_expert_intermediate_size", "num_key_value_heads",
                "head_dim", "num_experts_per_tok", "sliding_window",
                "gating", "rms_norm_eps"):
        assert model[key] == config[key], key
    assert model["moe_routed_scaling_factor"] == config[
        "moe_routed_scaling_factor"
    ]
    assert model["num_experts"] == config["published"]["num_experts"] == 256
    assert model["experts_held"] == config["num_experts"] == 32
    # the three lists stand as published; the stack is their first five
    for name in ("layer_types", "mlp_layer_types",
                 "num_attention_heads_per_layer"):
        assert len(config[name]) == 40
        assert model[name] == config[name][:config["num_hidden_layers"]]
    for kind, group in config["rope_parameters"].items():
        if not isinstance(group, dict):
            continue
        for key, value in group.items():
            if key == "attention_factor":
                # no flag carries it: YaRN's own magnitude IS the number
                from elasticdl_tpu.ops import gqa

                factor = group["factor"]
                assert value == pytest.approx(
                    gqa.yarn_mscale(factor, 1.0) / gqa.yarn_mscale(factor, 0.0),
                    rel=1e-12,
                )
                assert value == pytest.approx(
                    ref.rotary_inv_freq(model, kind)[1], rel=1e-12
                )
            elif key != "rope_type":
                flat = f"rope_{kind}_{key.replace('rope_theta', 'theta')}"
                assert model[flat] == value, flat


def _costs(step, model):
    t, d = 8192, 128
    full = ref.attn_full_cost(model, 1)
    band = ref.attn_window_cost(model, 1)
    # 9 products (2 forward, 2 again under the rematerialisation, 5
    # backward) of 2 x keys x 128 FLOPs a head: T^2 / 2 keys over the 96
    # heads of the two full layers, T W - W^2 / 2 over the 192 of the
    # three sliding ones
    assert full["flops"] == 9 * 2 * (t * t // 2) * d * 96
    assert band["flops"] == 9 * 2 * (t * 512 - 512 * 512 // 2) * d * 192
    assert 0.24 < band["flops"] / full["flops"] < 0.25
    # compute bound on a v5e, both
    for cost in (full, band):
        assert cost["flops"] / 197e12 > cost["bytes"] / 819e9
    # bytes: q, o a query head and k, v a key-value head, bfloat16, read
    # and written 4 times in the two forwards and 4 in the backward
    assert full["bytes"] == 2 * t * d * (8 * 96 + 8 * 16)
    assert (full["flops"] + band["flops"]) * 6 / 9 < 0.45 * step["flops"]
    experts = ref.moe_experts_cost(model, pairs=4 * 8192, steps=1)
    assert experts["flops"] == 6 * 3 * 2048 * 512 * 4 * 8192
    assert experts["bytes"] == 12 * 4 * 32 * 3 * 2048 * 512 + (
        4 * 8192 * 12 * 2048
    )


def _trained(trainer, model):
    counted = RoutingLedger()
    counted.seed_once({})
    fields = counted.task_delta(trainer.state.model_state)
    assert fields["layers"] == 4 and fields["dropped"] == 0
    # three steps of 4 x 64 tokens, two choices each, four expert layers
    assert 0 < fields["pairs"] < 3 * 4 * 64 * 2 * 4
    # the selection bias took three steps of the balancing rule, each
    # +-1e-3 (or 0 for an expert at the mean), and none of AdamW
    gate = trainer.state.params["model"]["layers_1"]["mlp"]["gate"]
    moved = np.asarray(gate[SELECTION_BIAS], np.float64) / 1e-3
    np.testing.assert_allclose(moved, np.round(moved), atol=1e-3)
    assert 0 < np.abs(np.round(moved)).max() <= 3


def _journal(job, events):
    """`moe.routing` a task; the per-layer lists rode the job's flat
    flags as a/b/c."""
    routing = counter_spans(events)
    assert all(e["layers"] == 4 and e["held"] == 4 for e in routing)
    assert all(e["dropped"] == 0 and e["pairs"] > 0 for e in routing)


# The cut's five layers (full, sliding x 3, full; dense, sparse x 4) with
# 6 and 8 query heads over 2 key-value heads, a window of 32.  T = 256:
# the XLA engine walks a sliding layer in two blocks of 128 and a full
# one in one of 256 (YaRN's original length is 32 here: positions past
# it are what the ramp is for).
SPEC = LMSpec(
    model_def="laguna.laguna_lm",
    reference="laguna_reference.py",
    cell="laguna-xs.2.json",
    parameters=691_624_960,
    sample_tokens=256,
    held=(("held-2..5", 2, 4), ("all-held", 0, 8)),
    losses=lambda ref, params, tokens, model: (
        ref.loss_fn(params, tokens, tokens, model), 0.0
    ),
    # a selection is not differentiated: the reference has no gradient for
    # the bias, the program hands it the load violation
    selection_leaves=SELECTION_BIAS,
    reduced=("num_hidden_layers", "num_experts", "vocab_size"),
    job_only={"remat": True, "attn_impl": "xla"},
    full_size=_full_size,
    # In the bfloat16 model the only products of float32 operands are the
    # routers' (one an expert layer) and the gates' (one a layer): four
    # routers, five gates.  5 layers x (4 projections + scores + values) +
    # MLPs + experts + head in all.
    float32_tokens=64,
    float32_highest=lambda tiny: 4 + 5,
    products_above=40,
    bf16={
        "full_attention-6": Bf16Case(
            lambda: _attention_in_bfloat16("full_attention", 6), 3e-3, 2,
            seed=0),
        "sliding_attention-8": Bf16Case(
            lambda: _attention_in_bfloat16("sliding_attention", 8), 3e-3, 2,
            seed=0),
    },
    tolerances=("highest", "highest_clear"),
    also_report=("stated", "bfloat16", "no_window", "no_gate"),
    # 6.5 TFLOP a sequence forward (ISSUE 36), three times that a step
    step_flops=(19e12, 20.5e12),
    costs=_costs,
    optimizer_kwargs={"warmup_steps": 2},
    trained=_trained,
    journal=_journal,
    # 8.30 GB of state donated (12 B x 691,624,960), each layer
    # rematerialised, both kinds of attention layer in the XLA block engine
    # (the configuration's `attn_impl=xla`: measured faster than the Pallas
    # kernels at 6 and 8 query heads a key-value head).  ONE sequence a step
    # fits with room (12.95 GB since PR 43: the pass in front of the engine
    # is a kernel, and the compiler holds 1.9 GB more around it than around
    # its own fusions); two need more than the chip has: the cell runs one.
    # Top-level copies of 16 MB and more: 4.18 GB a step (14.56 before PR
    # 43 took q, k and their gradients out of them; what is left is the
    # output's way to `o_proj` and back, PERF.md section 6).
    compile=CompileSpec(
        state=(8.29e9, 8.31e9),
        # 12.15 GB at the cell's one sequence (12.95 until PR 52); at TWO,
        # which the chip could not hold (16.81 GB: the slabs of the
        # engine's second forward at the backward's peak), 12.08 GB
        # since the layers keep the engine's results
        total={1: (11.7e9, 12.7e9), 2: (11.6e9, 12.6e9)},
        in_text=("rotary_pack_fwd", "rotary_pack_bwd"),
        # 1.76 GB and a tenth; 4.18 until PR 52 (2.4 GB of them lay
        # around the engine's second forward)
        copy_bytes=(0.88e9, 1.94e9),
    ),
    # a full layer with the dense MLP, a sliding one with experts
    scope_widths=dict(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        head_dim=8, sliding_window=4, rope_full_attention_factor=64,
        rope_full_attention_original_max_position_embeddings=8,
        experts_first=2, experts_held=4, remat=True,
    ),
    scopes=("fwd_bwd", "attn", "attn_full", "attn_window", "attn_gate", "mlp",
            "moe", "moe_route", "moe_experts", "moe_shared", "lm_head_loss",
            "optimizer"),
)
zoo, ref, TINY, CONFIG = SPEC.zoo, SPEC.ref, SPEC.tiny, SPEC.config
