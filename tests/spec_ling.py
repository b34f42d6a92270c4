"""Ling's descriptor (`tests/lm_contract.py`): where the stack, its plain
reference and its cell are, the widths the two are compared at, and what
is the model's alone.  `tests/test_ling.py` holds the model against its
reference by it, `tests/test_ling_program.py` runs it as a job does.
"""

import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.layers.delta_gates import GateLedger
from elasticdl_tpu.layers.moe import RoutingLedger, SparseMoeBlock
from lm_contract import (
    SELECTION_BIAS, Bf16Case, CompileSpec, LMSpec, _size, counter_spans,
    rounded_parts, sublayer_at_the_stated_precision,
)


def _wide():
    """Widths where a rounding shows."""
    return dict(TINY, hidden_size=256, head_dim=64, qk_nope_head_dim=64,
                qk_rope_head_dim=32, rotary_dim=32, v_head_dim=64,
                kv_lora_rank=128, moe_intermediate_size=64,
                moe_shared_expert_intermediate_size=64, sample_tokens=128)


def _sublayer(kind):
    """The program's sublayer in bfloat16 and the reference's function."""
    m = _wide()
    cfg = SPEC.build(m, use_bf16=True).cfg
    if kind == "kda":
        return sublayer_at_the_stated_precision(zoo.KimiDeltaAttention(
            cfg.num_attention_heads, cfg.head_dim, cfg.short_conv_kernel_size,
            float(cfg.kda_lower_bound), cfg.rms_norm_eps, cfg.dtype,
        ), ref._delta_attention, m)
    if kind == "mla":
        layer = zoo.LatentAttention(
            cfg.num_attention_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank,
            cfg.rms_norm_eps, cfg.dtype,
            (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5,
            head_norm_eps=cfg.rms_norm_eps, head_gate=True,
        )
        x = jnp.asarray(
            np.random.default_rng(0).normal(size=(1, 128, 256)), jnp.float32
        )
        from elasticdl_tpu.ops import gqa

        tables = gqa.rotary_tables(
            jnp.arange(128), cfg.rotary_dim, cfg.rope_theta
        )
        return layer, (x, *tables), lambda params, reading: ref._attention(
            params, x[0], m, rounded_parts(reading)
        )
    return sublayer_at_the_stated_precision(SparseMoeBlock(
        m["num_experts"], m["num_experts_per_tok"],
        m["moe_intermediate_size"], m["moe_shared_expert_intermediate_size"],
        (m["experts_first"], m["experts_held"]), True, jnp.bfloat16,
        score="sigmoid", routed_scale=m["routed_scaling_factor"],
        shared_gated=False, n_group=m["n_group"], topk_group=m["topk_group"],
    ), ref._experts, m)


#: a delta-attention mixer, a latent-attention mixer, an expert layer's
#: router + bias + shared expert + 8 held experts, the dense MLP
KDA, MLA, ROUTED, DENSE = 52_646_048, 31_966_080, 54_395_392, 47_185_920


def _full_size(shapes, model):
    config = SPEC.config
    stack = shapes["model"]
    assert set(stack) == {"embed_tokens", "norm"} | {
        f"layers_{i}" for i in range(1, 8)
    }
    for i in range(1, 8):
        layer = stack[f"layers_{i}"]
        if i == 5:  # (5 + 1) % 6 == 0: the period's one latent layer
            # 384 more than ISSUE 53's 31,965,696: the two head norms
            assert _size(layer["self_attn"]) == MLA
        else:
            assert _size(layer["linear_attn"]) == KDA
        assert _size(layer["mlp"]) == (DENSE if i == 1 else ROUTED)
    assert SPEC.parameters == (
        6 * KDA + MLA + DENSE + 6 * ROUTED + 7 * 2 * 2560 + 2560
        + 2 * 19_648 * 2560
    )
    # every width as published; the cut is in depth, the dense layers and
    # the experts held, and the vocabulary
    catalog = dict(config)
    for key, value in model.items():
        if key in catalog and key not in config["reduced"]:
            assert catalog[key] == value, key
    assert model["num_experts"] == config["published"]["num_experts"] == 512
    assert model["experts_held"] == config["num_experts"] == 8
    assert model["first_k_dense_replace"] == config["published"][
        "first_k_dense_replace"
    ] == 2
    # the dense layers HELD: published layer 1 of 0 and 1
    assert config["first_k_dense_replace"] == sum(
        i < 2 for i in range(model["first_layer"], model["first_layer"] + 7)
    ) == 1
    assert config["num_hidden_layers"] == model["num_hidden_layers"] == 7
    # the held range lies inside ONE group of the router's eight
    group = model["num_experts"] // model["n_group"]
    assert model["experts_first"] // group == (
        model["experts_first"] + model["experts_held"] - 1
    ) // group == 7


def _costs(step, model):
    t, h, d = 8192, 32, 128
    scan = ref.kda_scan_cost(model, 1)
    chunks = t // 64 * h
    assert scan["flops"] == 3 * 6 * chunks * (
        2 * 64 * 64 * 5 * d + 6 * 64 * d * d
    )
    # q, k, v, g, o and their gradients, float32: g is a tensor of q's size
    assert scan["bytes"] == 6 * 4 * (14 * t * h * d + 3 * t * h)
    # memory bound on a v5e: the rule's least time is its traffic's
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12
    assert scan["flops"] < 0.05 * step["flops"]
    whole = ref.kda_cost(model, 1)
    assert whole["flops"] == scan["flops"] + 6 * t * 6 * (
        5 * 2560 * 4096 + 2 * 2560 * 32
    )
    core = ref.mla_core_cost(model, 1)
    # ONE forward (192 + 128) and the backward's five products, over the
    # causal half, 32 heads, one layer
    assert core["flops"] == t * t * 32 * (320 + 3 * 192 + 2 * 128)
    assert core["flops"] / 197e12 > core["bytes"] / 819e9
    experts = ref.moe_experts_cost(model, pairs=6 * 1024, steps=1)
    assert experts["flops"] == 6 * 3 * 2560 * 768 * 6 * 1024
    assert experts["bytes"] == 12 * 6 * 8 * 3 * 2560 * 768 + (
        6 * 1024 * 12 * 2560
    )
    # a step counts the pairs this chip HOLDS (8 of 512 experts), not a
    # token's eight
    held = 6 * 3 * 2560 * 768 * (6 * t * 8 * 8 / 512)
    all_eight = 6 * 3 * 2560 * 768 * (6 * t * 8)
    assert step["flops"] - held > 0.98 * step["flops"]
    assert step["flops"] - held + all_eight > 1.5 * step["flops"]


def _trained(trainer, model):
    state = trainer.state.model_state
    counted = RoutingLedger()
    counted.seed_once({})
    fields = counted.task_delta(state)
    assert fields["layers"] == 6 and fields["dropped"] == 0
    # three steps of 4 x 64 tokens, two choices each, six expert layers
    assert 0 < fields["pairs"] < 3 * 4 * 64 * 2 * 6
    # every token's two choices within its router's two best groups
    assert 1.0 <= fields["groups_mean"] <= fields["group_limit"] == 2
    assert counted.refuse(fields) is None
    assert "more than the 2" in counted.refuse(dict(fields, groups_mean=2.5))
    gates = GateLedger()
    gates.seed_once({})
    fields = gates.task_delta(state, 3)
    assert fields["layers"] == 6
    assert np.exp(-5.0) < fields["retention"] < 1.0
    assert 0.0 < fields["beta"] < 1.0
    assert 0.0 <= fields["at_bound_share"] < 0.5
    # the selection bias took three steps of the balancing rule, each
    # +-1e-3 (or 0 for an expert at the mean), and none of AdamW
    gate = trainer.state.params["model"]["layers_2"]["mlp"]["gate"]
    moved = np.asarray(gate[SELECTION_BIAS], np.float64) / 1e-3
    np.testing.assert_allclose(moved, np.round(moved), atol=1e-3)
    assert 0 < np.abs(np.round(moved)).max() <= 3


def _journal(job, events):
    """`moe.routing` and `kda.gates` a task."""
    routing = counter_spans(events, also=("kda.gates",))
    assert all(e["layers"] == 6 and e["held"] == 4 for e in routing)
    assert all(e["dropped"] == 0 and e["pairs"] > 0 for e in routing)
    assert all(1.0 <= e["groups_mean"] <= e["group_limit"] == 2
               for e in routing)
    gates = counter_spans(events, "kda.gates", also=("moe.routing",))
    assert [e["step"] for e in gates] == [2, 4]
    assert all(e["layers"] == 6 and 0 < e["retention"] < 1 for e in gates)


_HIGH = (jax.lax.Precision.HIGH,) * 2

# The stage's seven layers (published 1-7: delta attention with the dense
# MLP; delta x 3, latent, delta x 2, each with experts) at 2 heads of 16,
# 16 experts in 4 groups.  T = 100: a chunk of the rule and a part of a
# second, so that the padding and the sub-chunks' edges are in it.
SPEC = LMSpec(
    model_def="ling.ling_lm",
    reference="ling_reference.py",
    cell="ling-3.0-flash-vl.json",
    # 384 more than ISSUE 53's 822,036,416: the latent layer's two head
    # norms (`use_qk_norm`), 192 weights each
    parameters=822_036_800,
    sample_tokens=100,
    held=(("held-12..15", 12, 4), ("all-held", 0, 16)),
    logits_rel=1e-4,
    losses=lambda ref, params, tokens, model: (
        ref.loss_fn(params, tokens, tokens, model), 0.0
    ),
    # a selection is not differentiated: the reference has no gradient for
    # the bias, the program hands it the load violation
    selection_leaves=SELECTION_BIAS,
    reduced=("num_hidden_layers", "first_k_dense_replace", "num_experts",
             "vocab_size"),
    full_size=_full_size,
    # In the bfloat16 model the products of float32 operands are the delta
    # rule's (`Precision.HIGH`, the state among their operands) and, at
    # `HIGHEST`, the six routers' and the seven output gates' (one a
    # mixer).
    float32_tokens=64,
    float32_highest=lambda tiny: 6 + 7,
    float32_also=(_HIGH,),
    products_above=60,
    bf16={
        "kda": Bf16Case(lambda: _sublayer("kda"), 2e-3, 3),
        "mla": Bf16Case(lambda: _sublayer("mla"), 3e-3, 2, seed=0),
        "moe-router": Bf16Case(
            lambda: _sublayer("moe"), 1e-4, 10, (("router", 10, "stated"),)),
    },
    tolerances=("highest", "highest_clear"),
    also_report=("stated", "bfloat16", "scalar_decay", "no_group_limit",
                 "no_routed_scale"),
    # 27 TFLOP a step of 8192 tokens (24.0 the projections, dense layer,
    # routers, shared experts and head; 2.1 the one latent core; 0.85 the
    # rule; 0.2 the held experts)
    step_flops=(26e12, 28.5e12),
    costs=_costs,
    optimizer_kwargs={"warmup_steps": 2},
    trained=_trained,
    journal=_journal,
    # 1 x 8192 tokens a step: 9.86 GB of state donated (12 B x 822M, the
    # largest of any cell) and 4.26 GB of temporaries, 14.13 GB of the
    # 15.5 a cell may need (ISSUE 53's step 0: its arithmetic said
    # 13.7-14.2); two sequences do not fit and are not compiled.  The
    # convolutions run their kernels, the rule under a vector decay the
    # XLA engine.  Top-level copies of 16 MB and more: 14.78 GB a step,
    # 7.25 of them the rule's operands laid out by group and chunk
    # (q, k, v, g and o of six layers, each pass), which a kernel that
    # reads rows would not make (`scripts/program_copies.py ling`).
    compile=CompileSpec(
        state=(9.86e9, 9.87e9),
        total={1: (13.3e9, 14.4e9)},
        in_text=("conv_silu_fwd", "conv_silu_bwd"),
        not_in_text=("delta_rule_fwd", "delta_rule_bwd"),
        copy_bytes=(12.8e9, 15.7e9),
        stated_sizes=("9.86 GB", "4.26 GB"),
        names_mesh=True,
    ),
    scope_widths=dict(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, moe_shared_expert_intermediate_size=16,
        head_dim=8, qk_nope_head_dim=8, qk_rope_head_dim=4, rotary_dim=4,
        v_head_dim=8, kv_lora_rank=16, first_layer=4, num_hidden_layers=2,
        first_k_dense_replace=5, experts_first=12, experts_held=4,
        remat=True,
    ),
    scopes=("fwd_bwd", "kda", "kda_mix", "kda_gate", "kda_scan", "attn",
            "mla_latent", "mla_core", "attn_gate", "mlp", "moe", "moe_route",
            "moe_experts", "moe_shared", "lm_head_loss", "optimizer"),
)
zoo, ref, TINY, CONFIG = SPEC.zoo, SPEC.ref, SPEC.tiny, SPEC.config
