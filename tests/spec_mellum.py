"""Mellum 2's descriptor (`tests/lm_contract.py`): where the stack, its
plain reference and its cell are, the widths the two are compared at, and
what is the model's alone.  `tests/test_mellum.py` holds the model against
its reference by it, `tests/test_mellum_program.py` runs it as a job does.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers.moe import RoutingLedger
from lm_contract import (
    Bf16Case, CompileSpec, LMSpec, _size, rounded_parts, counter_spans,
)


def _attention_in_bfloat16(kind):
    """The attention sublayer in bfloat16 against the reference with
    bfloat16 operands in the same products (the head norms and both
    tables float32 in both)."""
    model = dict(TINY, hidden_size=256, head_dim=64, sample_tokens=128)
    cfg = SPEC.build(model, use_bf16=True).cfg
    layer = zoo.Attention(cfg, kind == "sliding_attention")
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, 128, 256)), jnp.float32
    )
    return layer, (x, *zoo.rotary_tables(cfg, 128)[kind]), (
        lambda params, reading: ref._attention(
            params, x[0], model, kind, rounded_parts(reading)
        )
    )


def _full_size(shapes, model):
    config = SPEC.config
    stack = shapes["model"]
    for i in range(4):  # one head count, so one size, whatever the type
        assert _size(stack[f"layers_{i}"]["self_attn"]) == 21_233_664 + 256
        # the router and 16 held experts, and NOTHING else
        assert _size(stack[f"layers_{i}"]["mlp"]) == 147_456 + 99_090_432
        assert set(stack[f"layers_{i}"]["mlp"]) == {
            "gate", "experts_gate_proj", "experts_up_proj",
            "experts_down_proj",
        }
    # every width as published; the cut is in depth, experts held, vocabulary
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "num_experts_per_tok", "sliding_window", "norm_topk_prob",
                "rms_norm_eps"):
        assert model[key] == config[key], key
    assert model["num_experts"] == config["published"]["num_experts"] == 64
    assert model["experts_held"] == config["num_experts"] == 16
    assert model["vocab_size"] * 4 == config["published"]["vocab_size"]
    # the two lists stand as published; the stack is their first four
    for name in ("layer_types", "mlp_layer_types"):
        assert len(config[name]) == 28
        assert model[name] == config[name][:config["num_hidden_layers"]]
    assert config["layer_types"] == 7 * (
        3 * ["sliding_attention"] + ["full_attention"]
    )
    assert set(config["mlp_layer_types"]) == {"sparse"}
    for kind, group in config["rope_parameters"].items():
        for key, value in group.items():
            if key == "attention_factor":
                # no flag carries it: YaRN's own magnitude IS the number
                from elasticdl_tpu.ops import gqa

                factor = group["factor"]
                assert value == pytest.approx(0.1 * np.log(16) + 1, rel=1e-12)
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
    t, d, seqs = 8192, 128, 2
    full = ref.attn_full_cost(model, seqs)
    band = ref.attn_window_cost(model, seqs)
    # 9 products (2 forward, 2 again under the rematerialisation, 5
    # backward) of 2 x keys x 128 FLOPs a head a sequence: T^2 / 2 keys
    # over the 32 heads of the one full layer, T W - W^2 / 2 over the 96
    # of the three sliding ones, W = 1024
    assert full["flops"] == 9 * 2 * (t * t // 2) * d * 32 * seqs
    assert band["flops"] == 9 * 2 * (t * 1024 - 1024 * 1024 // 2) * d * 96 * seqs
    assert 0.70 < band["flops"] / full["flops"] < 0.71
    # compute bound on a v5e, both
    for cost in (full, band):
        assert cost["flops"] / 197e12 > cost["bytes"] / 819e9
    # bytes: q, o a query head and k, v a key-value head, bfloat16, read
    # and written 4 times in the two forwards and 4 in the backward
    assert full["bytes"] == 2 * seqs * t * d * (8 * 32 + 8 * 4)
    assert (full["flops"] + band["flops"]) * 6 / 9 < 0.35 * step["flops"]
    # the four projections: 2304 x (32 + 4 + 4 + 32) x 128 weights a layer
    proj = ref.attn_proj_cost(model, seqs)
    weights = 4 * 2304 * 72 * 128
    assert weights == 4 * 21_233_664
    assert proj["flops"] == 8 * weights * seqs * t
    assert proj["bytes"] == 16 * weights + 18 * seqs * t * 4 * (
        2 * 2304 + 40 * 128 + 32 * 128
    )
    # compute bound on a v5e: 56.5 ms of products at the peak, 21.6 of bytes
    assert proj["flops"] / 197e12 > 2.5 * proj["bytes"] / 819e9
    experts = ref.moe_experts_cost(model, pairs=4 * 32768, steps=1)
    assert experts["flops"] == 6 * 3 * 2304 * 896 * 4 * 32768
    assert experts["bytes"] == 12 * 4 * 16 * 3 * 2304 * 896 + (
        4 * 32768 * 12 * 2304
    )


def _trained(trainer, model):
    counted = RoutingLedger()
    counted.seed_once({})
    fields = counted.task_delta(trainer.state.model_state, steps=3)
    assert fields["layers"] == 4 and fields["dropped"] == 0
    # three steps of 4 x 64 tokens, two choices each, four expert layers
    assert 0 < fields["pairs"] < 3 * 4 * 64 * 2 * 4
    # alpha x (about 1 where the routing is about even), a layer
    assert 0.05 < fields["balance_loss"] < 0.3
    # nothing beside the routed experts, in the state either
    assert not any(
        "shared" in key for key in trainer.state.params["model"]["layers_0"]["mlp"]
    )


def _journal(job, events):
    """`moe.routing` a task with the balancing loss on it; the per-layer
    lists rode the job's flat flags as a/b/c."""
    routing = counter_spans(events)
    assert all(e["layers"] == 4 and e["held"] == 4 for e in routing)
    assert all(e["dropped"] == 0 and e["pairs"] > 0 for e in routing)
    assert all(0.05 < e["balance_loss"] < 0.3 for e in routing)


# The period's four layers (sliding x 3, full; every one sparse) with 8
# query heads over 2 key-value heads of 16, a window of 32.  T = 256: the
# XLA engine walks a sliding layer in two blocks of 128 and the full one
# in one of 256 (YaRN's original length is 32 here: positions past it are
# what the ramp is for).
SPEC = LMSpec(
    model_def="mellum.mellum_lm",
    reference="mellum_reference.py",
    cell="mellum2-12b-a2.5b.json",
    parameters=595_154_176,
    sample_tokens=256,
    held=(("held-2..5", 2, 4), ("all-held", 0, 8)),
    # (the cell's alpha, 0.1, stands well above the comparison's tolerance
    # on the routers: no other is needed here)
    # The program differentiates the cross-entropy and INJECTS the
    # balancing loss's gradient; the reference differentiates their sum.
    losses=lambda ref, params, tokens, model: ref.loss_and_balance(
        params, tokens, tokens, model
    ),
    added_loss_above=0.2,  # four layers of ~alpha each
    reduced=("num_hidden_layers", "num_experts", "vocab_size"),
    job_only={"remat": True, "attn_impl": "xla"},
    full_size=_full_size,
    # In the bfloat16 model the only products of float32 operands are the
    # routers', one a layer.  4 layers x (4 projections + scores + values)
    # + experts + head in all.
    float32_tokens=64,
    float32_highest=lambda tiny: 4,
    products_above=30,
    bf16={
        "full_attention": Bf16Case(
            lambda: _attention_in_bfloat16("full_attention"), 3e-3, 2, seed=0),
        "sliding_attention": Bf16Case(
            lambda: _attention_in_bfloat16("sliding_attention"), 3e-3, 2,
            seed=0),
    },
    tolerances=("highest", "highest_clear"),
    also_report=("stated", "bfloat16", "no_window", "no_qk_norm", "no_yarn"),
    # 24.46 TFLOP a step of 2 x 8192 tokens without recomputation: 13.97
    # in the projections, routers and head, 4.87 in the held experts at
    # uniform routing, 5.62 in the attention cores
    step_flops=(24.3e12, 24.6e12),
    costs=_costs,
    optimizer_kwargs={"warmup_steps": 2},
    trained=_trained,
    journal=_journal,
    # 7.14 GB of state donated (12 B x 595,154,176), each layer
    # rematerialised, both kinds of attention layer in the XLA block engine
    # (the configuration's `attn_impl=xla`): TWO sequences a step fit, 13.42
    # GB of the chip's 16 since PR 43 (13.94 before, the number the cell's
    # file still states: a `benchmark` PR's to restate), so the cell runs
    # two.  Top-level copies of 16 MB and more: 2.89 GB a step, 1.61 of it
    # the expert layers' weight-gradient stack (11.79 before PR 43).
    compile=CompileSpec(
        # 12.51 GB, 5.36 of them temporaries (13.42 and 6.28 until PR 52:
        # the layers keep the engine's results, and its second forward
        # held more; the configuration's file states PR 42's sizes)
        state=(7.14e9, 7.15e9), total={2: (12.0e9, 13.0e9)},
        in_text=("rotary_pack_fwd", "rotary_pack_bwd"),
        stated_sizes=("13.94 GB", "6.80 GB"),
        # 3.42 GB and a tenth; 2.89 until PR 52: each layer's kept `out`
        # (bfloat16 [2, 32, 8192, 128], 0.13 GB) is laid out once more
        # on its way into the backward pass
        copy_bytes=(1.71e9, 3.76e9),
    ),
    # a sliding and a full layer, both with experts
    scope_widths=dict(
        vocab_size=64, hidden_size=32, moe_intermediate_size=16,
        layer_types="sliding_attention/full_attention",
        mlp_layer_types="sparse/sparse", head_dim=8, sliding_window=4,
        rope_full_attention_factor=16,
        rope_full_attention_original_max_position_embeddings=8,
        experts_first=2, experts_held=4, remat=True,
    ),
    scopes=("fwd_bwd", "attn", "attn_proj", "attn_rotary", "attn_full",
            "attn_window", "moe", "moe_route", "moe_experts", "lm_head_loss",
            "optimizer"),
)
zoo, ref, TINY, CONFIG = SPEC.zoo, SPEC.ref, SPEC.tiny, SPEC.config
