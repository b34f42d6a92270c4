"""Ouro's descriptor (`tests/lm_contract.py`): where the stack, its plain
reference and its cell are, the widths the two are compared at, and what
is the model's alone: a prediction that is a named tree, compared as ONE
array of joint log-probabilities; a loss over the exit distribution; the
`loop.exits` span.  `tests/test_ouro.py` holds the model against its
reference by it, `tests/test_ouro_program.py` runs it as a job does.
"""

import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.layers.loop_exits import ExitLedger
from lm_contract import (
    Bf16Case, CompileSpec, LMSpec, _size, counter_spans, rounded_parts,
)


def _attention_in_bfloat16():
    """The attention sublayer in bfloat16 against the reference with
    bfloat16 operands in the same products (the table float32 in both)."""
    from elasticdl_tpu.ops import gqa

    model = dict(TINY, hidden_size=256, head_dim=64, sample_tokens=128)
    cfg = SPEC.build(model, use_bf16=True).cfg
    layer = zoo.RotaryAttention(
        cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
        cfg.dtype,
    )
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, 128, 256)), jnp.float32
    )
    tables = gqa.rotary_tables(jnp.arange(128), cfg.head_dim, cfg.rope_theta)
    return layer, (x, *tables), (
        lambda params, reading: ref._attention(
            params, x[0], model, "blocks" in rounded_parts(reading)
        )
    )


def _full_size(shapes, model):
    config = SPEC.config
    stack = shapes["model"]
    # ONE set of six layers, whatever `total_ut_steps` is
    assert sorted(k for k in stack if k.startswith("layers_")) == [
        f"layers_{i}" for i in range(6)
    ]
    for i in range(6):
        layer = stack[f"layers_{i}"]
        assert _size(layer["self_attn"]) == 4 * 2048 * 2048 == 16_777_216
        assert _size(layer["mlp"]) == 3 * 2048 * 5632 == 34_603_008
        assert _size(layer) == 51_388_416  # and four norms
    assert _size(stack["early_exit_gate"]) == 2049
    # every width and the number of passes as published; the cut is in
    # depth and vocabulary
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "rope_theta",
                "rms_norm_eps", "total_ut_steps"):
        assert model[key] == config[key], key
    assert config["total_ut_steps"] == 4
    assert model["num_hidden_layers"] * 8 == config["published"][
        "num_hidden_layers"] == len(config["layer_types"]) == 48
    assert set(config["layer_types"]) == {"full_attention"}
    assert model["vocab_size"] * 8 == config["published"]["vocab_size"]
    # the whole model by the same count: the name's 2.6B
    assert 48 * 51_388_416 + 2 * 49_152 * 2048 + 2048 + 2049 == 2_667_974_657
    assert "2,667,974,657" in config["deployment"]
    # every assumption names its other reading
    for key in ("norms", "loop_norm", "bias", "gate", "loss", "optimizer",
                "passes", "attention_engine", "remat"):
        assert config["assumed"][key], key


def _costs(step, model):
    t, d, passes, layers = 8192, 128, 4, 6
    full = ref.attn_full_cost(model, 1)
    # 9 products (2 forward, 2 again under the rematerialisation, 5
    # backward) of 2 x keys x 128 FLOPs a head: T^2 / 2 keys over the 16
    # heads of six layers, FOUR passes
    assert full["flops"] == passes * 9 * 2 * (t * t // 2) * d * 16 * layers
    assert full["bytes"] == passes * 2 * t * d * layers * (8 * 16 + 8 * 16)
    assert full["flops"] / 197e12 > full["bytes"] / 819e9
    proj = ref.attn_proj_cost(model, 1)
    weights = layers * 4 * 2048 * 2048
    assert proj["flops"] == passes * 8 * weights * t
    assert proj["bytes"] == passes * (
        16 * weights + 18 * t * layers * (2 * 2048 + 48 * 128 + 16 * 128)
    )
    mlps = ref.mlp_cost(model, 1)
    mlp = layers * 3 * 2048 * 5632
    assert mlps["flops"] == passes * 8 * mlp * t
    assert mlps["bytes"] == passes * (16 * mlp + 24 * layers * t * 2048)
    loop = ref.loop_cost(model, 1)
    for key in ("flops", "bytes"):
        assert loop[key] == full[key] + proj[key] + mlps[key]
    # a step's products are FOUR times what 6 x parameters x tokens says:
    # the head's too (four exits)
    once = 6 * (weights + mlp + 2048 * 6144) * t
    assert step["flops"] == passes * once + full["flops"] * 6 / 9
    assert step["flops"] > 3.9 * 6 * ref._all_params(model) * t
    # one pass of this yardstick is Mellum's and Granite's at these shapes
    single = dict(model, total_ut_steps=1)
    assert ref.mlp_cost(single, 1)["flops"] * passes == mlps["flops"]
    assert ref.attn_proj_cost(single, 1)["bytes"] * passes == proj["bytes"]


def _trained(trainer, model):
    counted = ExitLedger()
    counted.seed_once({})
    fields = counted.task_delta(trainer.state.model_state, steps=3)
    assert fields["tokens"] == 3 * 4 * 64
    p = [fields[f"p_exit_{r}"] for r in (1, 2, 3, 4)]
    assert abs(sum(p) - 1.0) < 1e-5
    # from a zero gate: (1/2, 1/4, 1/8, 1/8), 1.2130 nats; three steps on
    assert np.allclose(p, [0.5, 0.25, 0.125, 0.125], atol=0.05)
    assert 1.0 < fields["entropy"] <= np.log(4)
    # six layers' weights... here three: the tree did not grow with passes
    assert sorted(
        k for k in trainer.state.params["model"] if k.startswith("layers_")
    ) == ["layers_0", "layers_1", "layers_2"]


def _journal(job, events):
    """`loop.exits` a task: the task's tokens, an exit distribution that
    sums to 1 and its entropy; no `moe.routing` (no expert layer)."""
    exits = counter_spans(events, "loop.exits")
    for e in exits:
        # two steps of 4 x 64 tokens, and the rows the trainer pads a
        # minibatch with to its devices' multiple (8 here): counted too
        assert e["tokens"] in (2 * 4 * 64, 2 * 8 * 64)
        p = [e[f"p_exit_{r}"] for r in (1, 2, 3, 4)]
        assert abs(sum(p) - 1.0) < 1e-5 and min(p) > 0.05
        assert 1.0 < e["entropy"] <= np.log(4)
    assert [e["step"] for e in exits] == [2, 4]
    # a second run of the same job restores the cadence checkpoint and its
    # counters with it: nothing is left to train, so it writes no span
    assert job.run(job.tmp_path / "tb2") == 0
    again = job.events(job.tmp_path / "tb2")
    assert [e["step"] for e in again
            if e.get("event") == "checkpoint_restored"] == [4]


# Three layers at hidden 64, 4 / 4 heads of 16, an MLP 160 wide, vocabulary
# 64, T = 128 (the XLA engine walks it in one block), FOUR passes.
SPEC = LMSpec(
    model_def="ouro.ouro_lm",
    reference="ouro_reference.py",
    cell="ouro-2.6b.json",
    parameters=333_500_417,
    compared=lambda predicted: ref.joint(
        jnp.asarray(predicted["logits"]), jnp.asarray(predicted["exit_logp"])
    ),
    # the program reports what it descends: the expected cross-entropy
    # under the exit distribution less the entropy bonus
    losses=lambda ref, params, tokens, model: (
        ref.loss_fn(params, tokens, tokens, model), 0.0
    ),
    reduced=("num_hidden_layers", "vocab_size"),
    full_size=_full_size,
    # In the bfloat16 model the one product of float32 operands is the
    # gate's (all four exits' states against its [hidden, 1] kernel), at
    # HIGHEST.  ONE scanned body of 3 layers x (4 projections + scores +
    # values + 3 of the MLP), whatever the passes, + the head in all.
    float32_tokens=64,
    float32_highest=lambda tiny: 1,
    products_above=3 * 9,
    bf16=Bf16Case(_attention_in_bfloat16, 3e-3, 2, seed=0),
    also_report=("stated", "bfloat16", "one_pass", "no_post_norm",
                 "norm_outside", "positions_run_on"),
    # 82.9 TFLOP a step of 1 x 8192 tokens without recomputation: FOUR
    # times 15.15 in the projections and MLPs of six layers and 0.62 in
    # the head (63.1), 19.8 in the attention cores of 24 layer applications
    step_flops=(82.5e12, 83.2e12),
    costs=_costs,
    optimizer_kwargs={"warmup_steps": 2},
    trained=_trained,
    journal=_journal,
    # 4.00 GB of state donated (12 B x 333,500,417), each of the 24 layer
    # applications rematerialised on its own, the four passes ONE scanned
    # body: 13.78 GB at 1 x 8192 tokens, 9.77 of them temporaries (the
    # scan carries the float32 gradient of the six layers' 308M weights
    # through its backward loop; the unrolled loop compiled to 10.54 GB
    # and ran 1.5-2.5% slower, PR 45).  Two sequences a step do not fit.
    compile=CompileSpec(
        # 14.59 GB, 10.59 of them temporaries; 13.78 and 9.77 until PR 52,
        # what the configuration's file still states: the kernel's
        # forward holds no large temporaries to give back, so the
        # program GROWS by what it keeps, 34 MB an application x 24
        state=(4.0e9, 4.01e9), total={1: (14.1e9, 15.0e9)},
        in_text=("rotary_pack_fwd", "rotary_pack_bwd", "flash_attention"),
        stated_sizes=("13.78 GB", "9.77 GB"),
    ),
    scope_widths=dict(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=2, head_dim=8, total_ut_steps=3, remat=True,
    ),
    scopes=("fwd_bwd", "loop", "attn", "attn_proj", "attn_rotary",
            "attn_full", "mlp", "block_norm", "lm_head_loss", "exit_gate",
            "optimizer"),
)
zoo, ref, TINY, CONFIG = SPEC.zoo, SPEC.ref, SPEC.tiny, SPEC.config
