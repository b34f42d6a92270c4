"""DeepSeek-V2-Lite's descriptor (`tests/lm_contract.py`): where the stack,
its plain reference and its cell are, the widths the two are compared at,
and what is the model's alone.  `tests/test_deepseek_v2.py` holds the
model against its reference by it, `tests/test_deepseek_v2_program.py`
runs it as a job does.
"""

import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.layers.moe import RoutingLedger
from lm_contract import (
    Bf16Case, CompileSpec, LMSpec, _model_kwargs, _size, rounded_parts,
    counter_spans,
)


def _attention_at_the_stated_precision():
    """The attention sublayer in bfloat16 against the reference with
    bfloat16 operands in the same products."""
    model = dict(SPEC.tiny, hidden_size=256, qk_nope_head_dim=64,
                 qk_rope_head_dim=32, v_head_dim=64, kv_lora_rank=128,
                 sample_tokens=128)
    layer, tables = zoo.latent_attention(
        zoo.DeepseekV2Config(**_model_kwargs(model)), 128
    )
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, 128, 256)), jnp.float32
    )
    return layer, (x, *tables), lambda params, reading: ref._attention(
        params, x[0], model, rounded_parts(reading)
    )


def _full_size(shapes, model):
    config = SPEC.config
    assert _size(shapes["model"]["layers_0"]["self_attn"]) == 13_763_072
    # every width as published; the cut is in depth, experts held, vocabulary
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "kv_lora_rank", "num_experts_per_tok",
                "n_shared_experts", "first_k_dense_replace"):
        assert model[key] == config[key], key
    assert model["n_routed_experts"] == config["published"]["n_routed_experts"]
    assert model["experts_held"] == config["n_routed_experts"] == 8
    for key, value in config["rope_scaling"].items():
        if key != "type":
            assert model[f"rope_scaling_{key}"] == value, key


def _costs(cost, model):
    # The attention core by hand: 5 layers x 16 heads x 2 sequences, each
    # product over the causal half of 8192^2 (8192^2 / 2 x 2 FLOPs a
    # unit of head size).  Forward 192 + 128, once more under remat,
    # backward 192 x 3 + 128 x 2.
    core = ref.mla_core_cost(model, 2)
    half = 8192 * 8192 // 2
    assert core["flops"] == 5 * 16 * 2 * half * 2 * (
        2 * (192 + 128) + (3 * 192 + 2 * 128)
    )
    assert core["flops"] == 5 * 16 * 2 * 8192 * 8192 * 1472
    # bfloat16 rows of 16,384 tokens x 16 heads x 5 layers: two forwards
    # read q, k, v and write o; the backward reads five and writes three
    rows = 2 * 8192 * 16 * 5
    assert core["bytes"] == 2 * rows * (
        2 * (192 + 192 + 128 + 128) + (192 + 192 + 128 + 128 + 128)
        + (192 + 192 + 128)
    )
    # compute bound on a v5e, and 80 ms of it at the peak
    assert core["flops"] / 197e12 > 5 * core["bytes"] / 819e9
    assert 0.079 < core["flops"] / 197e12 < 0.082
    # the scope's work is under the step's with each forward run twice
    assert core["flops"] < 0.45 * cost["flops"] * 4 / 3
    experts = ref.moe_experts_cost(model, pairs=4 * 1536 * 8, steps=1)
    assert experts["flops"] == 6 * 3 * 2048 * 1408 * 4 * 1536 * 8


def _trained(trainer, model):
    counted = RoutingLedger()
    counted.seed_once({})
    fields = counted.task_delta(trainer.state.model_state, steps=3)
    assert fields["layers"] == 1 and fields["dropped"] == 0
    assert 0 < fields["pairs"] < 3 * 4 * 32 * 2
    # alpha x (about 1 where the routing is about even)
    assert 0.5e-3 < fields["balance_loss"] < 3e-3


def _journal(job, events):
    """`moe.routing` a task, with the balancing loss on it."""
    routing = counter_spans(events)
    assert all(e["layers"] == 1 and e["held"] == 4 for e in routing)
    assert all(e["dropped"] == 0 and e["pairs"] > 0 for e in routing)
    assert all(0.5e-3 < e["balance_loss"] < 3e-3 for e in routing)


# One dense and two expert layers; T = 80 is no multiple of 64, so the
# XLA engine runs one block of 80 (YaRN's original length is 32 here:
# positions past it are what the ramp is for).
SPEC = LMSpec(
    model_def="deepseek_v2.deepseek_v2_lm",
    reference="deepseek_v2_reference.py",
    cell="deepseek-v2-lite.json",
    parameters=535_060_992,
    sample_tokens=80,
    held=(("held-2..5", 2, 4), ("all-held", 0, 8)),
    # alpha 0.05, not 0.001: the balancing loss's gradient has to stand
    # well above the comparison's tolerance on the routers.
    whole_model_changes={"aux_loss_alpha": 0.05},
    # The program differentiates the cross-entropy and INJECTS the
    # balancing loss's gradient; the reference differentiates their sum
    # (that the routers' gradients would NOT agree without the injected
    # term: test_moe.py's test_injected_gradient_is_the_explicit_sums).
    losses=lambda ref, params, tokens, model: ref.loss_and_balance(
        params, tokens, tokens, model
    ),
    added_loss_above=0.05,  # two layers of ~alpha each
    reduced=("num_hidden_layers", "n_routed_experts", "vocab_size"),
    full_size=_full_size,
    # the routers' (`HIGHEST`), one an expert layer
    float32_highest=lambda tiny: tiny["num_hidden_layers"] - 1,
    # 3 layers x (4 projections + scores + values) + MLPs + experts + head
    products_above=25,
    bf16=Bf16Case(_attention_at_the_stated_precision, 2e-3, 3, seed=0),
    also_report=("stated", "bfloat16", "no_mscale"),
    # ~35.7 TFLOP a step of 2 x 8192 tokens without recomputation
    step_flops=(35e12, 36.5e12),
    costs=_costs,
    trainer_changes={"num_hidden_layers": 2, "sample_tokens": 32},
    optimizer_kwargs={"warmup_steps": 2},
    trained=_trained,
    journal=_journal,
    # 6.42 GB of state donated (12 B x 535,060,992), each layer
    # rematerialised, attention in the XLA block engine (K and V of a head
    # at 192 and 128 are 10 MiB of float32 at T = 8192, past the Pallas
    # kernel's cap): 12.51 GB of the chip's 16.
    compile=CompileSpec(
        state=(6.42e9, 6.43e9), total={2: (0, 13.0e9)},
        not_in_text=("tpu_custom_call",),
        # 8.49 GB before PR 43: the engine's blocked layout was a physical
        # transpose of q, k, v, dout and out at two sequences; 1.68 until
        # PR 52, 1.01 with the engine's second forward gone
        copy_bytes=(0.50e9, 1.11e9),
    ),
    # one dense and one expert layer
    scope_widths=dict(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=2, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, kv_lora_rank=16,
        rope_scaling_factor=40, rope_scaling_mscale_all_dim=0.707,
        rope_scaling_original_max_position_embeddings=8,
        experts_first=2, experts_held=4, remat=True,
    ),
    scopes=("fwd_bwd", "attn", "mla_latent", "mla_core", "mlp", "moe",
            "moe_route", "moe_experts", "moe_shared", "lm_head_loss",
            "optimizer"),
)
zoo, ref, TINY = SPEC.zoo, SPEC.ref, SPEC.tiny
