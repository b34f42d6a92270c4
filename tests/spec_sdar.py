"""SDAR's descriptor (`tests/lm_contract.py`): where the stack, its plain
reference and its cell are, the widths the two are compared at, and what
is the model's alone: features that are a record's tokens WITH its noise
(mask, t), a prediction that is a named tree (the logits of the noised
half and the loss's weights), a loss over the masked positions, the
`diffusion.noise` span beside `moe.routing`.  `tests/test_sdar.py` holds
the model against its reference by it, `tests/test_sdar_program.py` runs
it as a job does.
"""

import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.layers.diffusion_noise import NoiseLedger
from elasticdl_tpu.layers.moe import RoutingLedger
from lm_contract import (
    Bf16Case, CompileSpec, LMSpec, _size, counter_spans, rounded_parts,
)


def features_of(tokens):
    """Tokens [.., sequences, T] -> (tokens, mask, t): every other
    position masked at t = 1/2 (a shape gives shapes, on its device)."""
    if isinstance(tokens, jax.ShapeDtypeStruct):
        def like(shape, dtype):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=tokens.sharding
            )

        return (tokens, like(tokens.shape, jnp.bool_),
                like(tokens.shape[:-1], jnp.float32))
    mask = np.broadcast_to(np.arange(tokens.shape[-1]) % 2 == 0, tokens.shape)
    return tokens, mask, np.full(tokens.shape[:-1], 0.5, np.float32)


def _attention_in_bfloat16():
    """The attention sublayer in bfloat16, over a noised and a clean copy
    under the block-diffusion mask, against the reference with bfloat16
    operands in the same products (the head norms and the table float32
    in both)."""
    from elasticdl_tpu.ops import gqa

    t = 64
    model = dict(TINY, hidden_size=256, head_dim=64, sample_tokens=t)
    cfg = SPEC.build(model, use_bf16=True).cfg
    layer = zoo.RotaryAttention(
        cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
        cfg.dtype, head_norm_eps=cfg.rms_norm_eps,
        block_diffusion=(t, cfg.block_length),
    )
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, 2 * t, 256)), jnp.float32
    )
    positions = jnp.concatenate([jnp.arange(t), jnp.arange(t)])
    tables = gqa.rotary_tables(positions, cfg.head_dim, cfg.rope_theta)
    every = jnp.arange(2 * t)
    return layer, (x, *tables), (
        lambda params, reading: ref._attention(
            params, x[0], model, positions,
            lambda rows: ref.allowed(rows, every, t, cfg.block_length),
            rounded_parts(reading),
        )
    )


def _full_size(shapes, model):
    config = SPEC.config
    stack = shapes["model"]
    assert sorted(k for k in stack if k.startswith("layers_")) == [
        f"layers_{i}" for i in range(6)
    ]
    for i in range(6):
        layer = stack[f"layers_{i}"]
        assert _size(layer["self_attn"]) == 18_874_368 + 256
        # the router and 16 held experts, and NOTHING else
        assert _size(layer["mlp"]) == 262_144 + 16 * 4_718_592
        assert set(layer["mlp"]) == {
            "gate", "experts_gate_proj", "experts_up_proj",
            "experts_down_proj",
        }
        assert _size(layer) == 94_638_336
    assert _size(stack["embed_tokens"]) == _size(shapes["lm_head"]) == (
        18_992 * 2048)
    # ISSUE 51's count, reckoned again
    assert 6 * 94_638_336 + 2 * 18_992 * 2048 + 2048 == 645_623_296
    # every width as published; the cut is in depth, experts held, vocabulary
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
                "rope_theta", "decoder_sparse_step"):
        assert model[key] == config[key], key
    assert model["num_experts"] == config["published"]["num_experts"] == 128
    assert model["experts_held"] == config["num_experts"] == 16
    assert model["experts_first"] + model["experts_held"] == 128
    assert model["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert model["num_hidden_layers"] * 8 == config["published"][
        "num_hidden_layers"] == 48
    assert model["mask_token_id"] == model["vocab_size"] - 1
    assert config["mlp_only_layers"] == [] and config["rope_scaling"] is None
    # the whole model by the same count: the name's 30B
    assert 48 * (18_874_368 + 262_144 + 4_352 + 128 * 4_718_592) + (
        2 * 151_936 * 2048 + 2048) == 30_532_122_624
    assert "30,532,122,624" in config["deployment"]
    assert "v5e-64" in config["deployment"]
    # every assumption names its other reading
    for key in ("block_length", "noise_schedule", "noise_is_data",
                "predict_shift", "mask_token_id", "positions", "head_norms",
                "router", "balancing_loss", "optimizer", "attention_engine",
                "remat"):
        assert config["assumed"][key], key
    for key in ("block_length", "noise_schedule", "noise_is_data",
                "predict_shift", "positions", "head_norms", "balancing_loss"):
        assert "OTHER READING" in config["assumed"][key], key


def _costs(step, model):
    t, d, layers, heads = 8192, 128, 6, 32
    # the MASK's pairs a head and record: T^2 + 4 T, whatever tiles an
    # engine visits; a plain causal mask over the 2 T positions has twice
    pairs = ref.allowed_pairs(model)
    assert pairs == t * t + 4 * t == 67_141_632
    assert pairs < 0.51 * (2 * t) * (2 * t + 1) // 2
    core = ref.attn_blockdiff_cost(model, 1)
    # 9 products (2 forward, 2 again under the rematerialisation, 5
    # backward) of 2 x pairs x 128 FLOPs a head: Mellum's rule per pair
    assert core["flops"] == 9 * 2 * pairs * d * heads * layers
    # bytes: q, o a query head and k, v a key-value head over the 2 T
    # rows, bfloat16, read and written 4 times in the two forwards and 4
    # in the backward
    assert core["bytes"] == 2 * (2 * t) * d * layers * (8 * 32 + 8 * 4)
    assert core["flops"] / 197e12 > core["bytes"] / 819e9  # compute bound
    # the four projections over the 2 T rows
    proj = ref.attn_proj_cost(model, 1)
    weights = layers * 2 * 2048 * 128 * (32 + 4)
    assert weights == layers * 18_874_368
    assert proj["flops"] == 8 * weights * 2 * t
    assert proj["bytes"] == 16 * weights + 18 * 2 * t * layers * (
        2 * 2048 + 40 * 128 + 32 * 128
    )
    assert proj["flops"] / 197e12 > proj["bytes"] / 819e9
    experts = ref.moe_experts_cost(model, pairs=6 * 16384, steps=1)
    assert experts["flops"] == 6 * 3 * 2048 * 768 * 6 * 16384
    assert experts["bytes"] == 12 * 6 * 16 * 3 * 2048 * 768 + (
        6 * 16384 * 12 * 2048
    )
    # a step: 2 T rows through the projections, the routers and the held
    # experts (at uniform routing), T through the head, the mask's pairs
    routers = layers * 2048 * 128
    assert step["flops"] == (
        6 * (weights + routers) * 2 * t + 6 * 2048 * 18_992 * t
        + 6 * 3 * 2048 * 768 * layers * 2 * t * 8 * 16 / 128
        + core["flops"] * 6 / 9
    )
    # 6 x parameters x the record's tokens understates it: the experts'
    # weights count at their expected rows in both, the rest twofold
    assert step["flops"] > 1.9 * 6 * t * (
        weights + routers + 2048 * 18_992 / 2
        + layers * 3 * 2048 * 768 * 8 * 16 / 128
    )


def _trained(trainer, model):
    state = trainer.state.model_state
    noise = NoiseLedger()
    noise.seed_once({})
    fields = noise.task_delta(state, steps=3)
    # three steps over the contract's four records of 64 tokens
    _, mask, t = ref.sample(11, 4, model)
    assert fields["tokens"] == 3 * 4 * 64
    assert fields["masked"] == 3 * int(mask.sum()) > 0
    assert abs(fields["t_mean"] - float(t.mean())) < 1e-5
    routing = RoutingLedger()
    routing.seed_once({})
    fields = routing.task_delta(state, steps=3)
    assert fields["layers"] == 3 and fields["dropped"] == 0
    # three steps of 4 x 128 ROWS (both copies), four choices each
    assert 0 < fields["pairs"] < 3 * 4 * 128 * 4 * 3
    assert not any(
        "shared" in key
        for key in trainer.state.params["model"]["layers_0"]["mlp"]
    )


def _journal(job, events):
    """`diffusion.noise` a task beside `moe.routing`: the task's tokens,
    about half of them masked (U(0, 1] a record), `t_mean` the records'."""
    noise = counter_spans(events, "diffusion.noise", also=("moe.routing",))
    for e in noise:
        # two steps of 4 x 64 tokens, and the rows the trainer pads a
        # minibatch with to its devices' multiple (8 here): counted too
        assert e["tokens"] in (2 * 4 * 64, 2 * 8 * 64)
        assert 0 < e["masked"] < e["tokens"]
        assert 1e-3 <= e["t_mean"] <= 1.0
        assert abs(e["masked"] / e["tokens"] - e["t_mean"]) < 0.1
    assert [e["step"] for e in noise] == [2, 4]
    routing = counter_spans(events, also=("diffusion.noise",))
    assert all(e["layers"] == 3 and e["held"] == 8 for e in routing)
    assert all(e["dropped"] == 0 and e["pairs"] > 0 for e in routing)


# Three layers at hidden 64, 4 query heads over 2 key-value heads of 16
# with the head norms, 16 experts of 32 wide (8 held, 4 a token),
# vocabulary 64, T = 128 run as 256 positions in blocks of 4 (the XLA
# engine walks each copy in one tile of 128).
SPEC = LMSpec(
    model_def="sdar.sdar_lm",
    reference="sdar_reference.py",
    cell="sdar-30b-a3b.json",
    parameters=645_623_296,
    held=(("held-4..11", 4, 8), ("all-held", 0, 16)),
    features=features_of,
    compared=lambda predicted: predicted["logits"],
    # The program differentiates the weighted cross-entropy and INJECTS
    # the balancing loss's gradient; the reference differentiates their sum.
    losses=lambda ref, params, features, model: ref.loss_and_balance(
        params, features, features[0], model
    ),
    added_loss_above=0.15,  # three layers of ~alpha each
    reduced=("num_hidden_layers", "num_experts", "vocab_size"),
    job_only={"remat": True, "attn_impl": "xla"},
    full_size=_full_size,
    # In the bfloat16 model the only products of float32 operands are the
    # routers', one a layer.  3 layers x (4 projections + scores + values)
    # + experts + head in all.
    float32_tokens=64,
    float32_highest=lambda tiny: 3,
    products_above=22,
    bf16=Bf16Case(_attention_in_bfloat16, 3e-3, 2, seed=0),
    tolerances=("highest", "highest_clear"),
    also_report=("stated", "bfloat16", "causal", "leak"),
    # 35.78 TFLOP a step of 1 x 8192 tokens (16,384 rows) without
    # recomputation: 11.29 in the projections and routers of six layers
    # over both copies, 1.91 in the head over the noised half, 2.78 in the
    # held experts at uniform routing, 19.80 in the attention cores over
    # the pairs the mask allows
    step_flops=(35.6e12, 36.0e12),
    costs=_costs,
    optimizer_kwargs={"warmup_steps": 2},
    trained=_trained,
    journal=_journal,
    # 7.75 GB of state donated (12 B x 645,623,296), each layer
    # rematerialised, the engine under the block-diffusion rule in tiles of
    # 512: ONE record of 8192 tokens, run as 16,384 positions, fits: 13.43
    # GB of the chip's 16 (ISSUE 51 reckoned 13-14), 5.68 of them
    # temporaries.  Two records a step would not.
    compile=CompileSpec(
        # 10.96 GB, 3.21 of them temporaries; 13.43 and 5.68 until PR 52,
        # what the configuration's file still states (a `benchmark` PR's
        # to restate): the engine's second forward under `remat` held
        # more at the backward's peak than the two results kept now
        state=(7.74e9, 7.76e9), total={1: (10.5e9, 11.4e9)},
        in_text=("rotary_pack_fwd", "rotary_pack_bwd"),
        not_in_text=("flash_attention",),
        stated_sizes=("13.43 GB", "5.68 GB"),
    ),
    scope_widths=dict(
        vocab_size=64, hidden_size=32, moe_intermediate_size=16,
        num_hidden_layers=2, head_dim=8, block_length=4,
        experts_first=2, experts_held=4, remat=True,
    ),
    scopes=("fwd_bwd", "attn", "attn_proj", "attn_rotary", "attn_blockdiff",
            "moe", "moe_route", "moe_experts", "lm_head_loss", "optimizer"),
)
zoo, ref, TINY, CONFIG = SPEC.zoo, SPEC.ref, SPEC.tiny, SPEC.config
