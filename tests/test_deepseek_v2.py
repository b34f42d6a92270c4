"""DeepSeek-V2 on the normal training path (ISSUE 32): the zoo model with
its latent attention, leading dense layer, softmax-routed expert layers
with an ungated shared expert and the sequence-wise balancing loss, each
against the plain reference that decides the benchmark cell's `correct`
(`perfbench/configs/deepseek_v2_reference.py`, which shares no code with
the program).  Tiny sizes, seeded random weights, float32 on the CPU, so
tolerances are those of float32 summation order: 1e-5 of the outputs'
size, gradients 2e-3 of each leaf's largest entry as for the other
hybrid models.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import moe
from elasticdl_tpu.layers.moe import (
    ROUTING_COLLECTION, RoutingLedger, SparseMoeBlock,
)
from model_zoo.deepseek_v2 import deepseek_v2_lm as zoo

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO_ROOT, "perfbench", "configs")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(CONFIGS, "deepseek_v2_reference.py"), "deepseek_v2_ref")

with open(os.path.join(CONFIGS, "deepseek-v2-lite.json")) as f:
    CONFIG = json.load(f)

# One dense and two expert layers; T = 80 is no multiple of 64, so the
# XLA engine runs one block of 80 (YaRN's original length is 32 here:
# positions past it are what the ramp is for).
TINY = dict(CONFIG["rehearse"]["model"], sample_tokens=80)


def _model_kwargs(model):
    return {k: v for k, v in model.items() if k != "sample_tokens"}


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def _perturbed(tree, seed, scale=0.05):
    """Norm weights start at 1: move every leaf off its special value so
    that a dropped term would show."""
    leaves, treedef = jax.tree.flatten(tree)
    key = jax.random.PRNGKey(seed)
    return jax.tree.unflatten(treedef, [
        leaf + scale * jax.random.normal(jax.random.fold_in(key, i),
                                         leaf.shape)
        for i, leaf in enumerate(leaves)
    ])


# ---------------------------------------------------------------------------
# The whole model against the reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module", params=[(2, 4), (0, 8)],
                ids=["held-2..5", "all-held"])
def program_and_reference(request):
    first, held = request.param
    # alpha 0.05, not 0.001: the balancing loss's gradient has to stand
    # well above the comparison's tolerance on the routers.
    model = dict(TINY, experts_first=first, experts_held=held,
                 aux_loss_alpha=0.05)
    module = zoo.custom_model(use_bf16=False, **_model_kwargs(model))
    tokens = ref.sample(3, 2, model)
    variables = module.init(jax.random.PRNGKey(0), tokens)
    params = _perturbed(variables["params"], 1)
    routing = variables[ROUTING_COLLECTION]

    def program(p):
        return module.apply({"params": p, ROUTING_COLLECTION: routing}, tokens)

    def reference(p):
        return ref.forward(p, tokens, model)

    return program, reference, params, tokens, model


def test_logits_and_loss_match_the_reference(program_and_reference):
    program, reference, params, tokens, model = program_and_reference
    got, want = program(params), reference(params)
    assert got.shape == want.shape == tokens.shape + (TINY["vocab_size"],)
    assert _rel(got, want) < 1e-5
    cross_entropy, balance = ref.loss_and_balance(params, tokens, tokens, model)
    # the program REPORTS the cross-entropy alone
    np.testing.assert_allclose(
        float(zoo.loss(tokens, got)), float(cross_entropy), rtol=1e-5
    )
    assert float(balance) > 0.05  # two layers of ~alpha each


def test_planted_fault_reads_far_from_the_program(program_and_reference):
    """`no_mscale` is the reference with the softmax scale left without
    YaRN's mscale^2 (1.59): the cell reports the program's distance to it."""
    program, _, params, tokens, model = program_and_reference
    fault = ref.forward(params, tokens, model, "no_mscale")
    assert _rel(program(params), fault) > 100 * 1e-5


def test_gradients_match_the_reference_with_the_balancing_loss_added(
    program_and_reference,
):
    """The program differentiates the cross-entropy and INJECTS the
    balancing loss's gradient; the reference differentiates their sum."""
    program, _, params, tokens, model = program_and_reference
    got = jax.grad(lambda p: zoo.loss(tokens, program(p)))(params)
    want = jax.grad(
        lambda p: sum(ref.loss_and_balance(p, tokens, tokens, model))
    )(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    assert len(flat_got) == len(jax.tree.leaves(want))
    for (path, g), w in zip(flat_got, jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        assert float(jnp.abs(g - w).max()) < 2e-3 * scale, name
    # (that the routers' gradients would NOT agree without the injected
    # term: test_injected_gradient_is_the_explicit_sums)


def test_parameter_names_and_layouts_follow_the_source():
    module = zoo.custom_model(use_bf16=False, **_model_kwargs(TINY))
    shapes = jax.eval_shape(
        module.init, jax.random.PRNGKey(0), ref.sample(0, 1, TINY)
    )["params"]
    m = TINY
    d, h = m["hidden_size"], m["num_attention_heads"]
    nope, rope, dv, rank = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                            m["v_head_dim"], m["kv_lora_rank"])
    assert set(shapes) == {"model", "lm_head"}
    stack = shapes["model"]
    assert set(stack) == {"embed_tokens", "norm", "layers_0", "layers_1",
                          "layers_2"}
    attn = {k: jax.tree.leaves(v)[0].shape
            for k, v in stack["layers_1"]["self_attn"].items()}
    assert attn == {
        "q_proj": (d, h * (nope + rope)),
        "kv_a_proj_with_mqa": (d, rank + rope),
        "kv_a_layernorm": (rank,),
        "kv_b_proj": (rank, h * (nope + dv)),
        "o_proj": (h * dv, d),
    }
    dense = stack["layers_0"]["mlp"]
    assert set(dense) == {"gate_proj", "up_proj", "down_proj"}
    assert dense["up_proj"]["kernel"].shape == (d, m["intermediate_size"])
    experts = stack["layers_1"]["mlp"]
    assert set(experts) == {
        "gate", "experts_gate_proj", "experts_up_proj", "experts_down_proj",
        "shared_experts",
    }  # no `shared_expert_gate`: the shared experts are ungated
    assert experts["gate"].shape == (d, m["n_routed_experts"])
    assert experts["experts_up_proj"].shape == (
        m["experts_held"], d, m["moe_intermediate_size"]
    )
    assert experts["shared_experts"]["down_proj"]["kernel"].shape == (
        m["n_shared_experts"] * m["moe_intermediate_size"], d
    )
    with pytest.raises(ValueError):
        zoo.custom_model(q_lora_rank=1536)
    with pytest.raises(ValueError):
        zoo.custom_model(no_such_key=1)
    with pytest.raises(ValueError):
        zoo.custom_model(seq_aux=False)


def test_full_size_configuration_counts_the_parameters_it_states():
    model = CONFIG["model"]
    module = zoo.custom_model(use_bf16=True, **_model_kwargs(model))
    shapes = jax.eval_shape(
        module.init, jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)
    )["params"]
    counted = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert counted == ref._all_params(model) == 535_060_992
    assert "535,060,992" in CONFIG["device_bytes"]
    attn = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(
        shapes["model"]["layers_0"]["self_attn"]
    ))
    assert attn == 13_763_072
    # every width as published; the cut is in depth, experts held, vocabulary
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "kv_lora_rank", "num_experts_per_tok",
                "n_shared_experts", "first_k_dense_replace"):
        assert model[key] == CONFIG[key], key
    assert model["n_routed_experts"] == CONFIG["published"]["n_routed_experts"]
    assert model["experts_held"] == CONFIG["n_routed_experts"] == 8
    assert CONFIG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"
    ]
    scaling = CONFIG["rope_scaling"]
    for key, value in scaling.items():
        if key != "type":
            assert model[f"rope_scaling_{key}"] == value, key
    # the job's flags say what `model` says
    from elasticdl_tpu.common.args import parse_dict_params

    flags = next(f for f in CONFIG["job"] if f.startswith("--model_params="))
    parsed = parse_dict_params(flags.split("=", 1)[1])
    assert parsed.pop("remat") is True
    assert parsed == _model_kwargs(model)


# ---------------------------------------------------------------------------
# The expert layer: softmax scores, an ungated shared expert, its share
# ---------------------------------------------------------------------------

MOE = dict(TINY, experts_first=0, experts_held=8)


def _moe_layer(first, held, block_rows=16, alpha=0.0):
    return SparseMoeBlock(
        MOE["n_routed_experts"], MOE["num_experts_per_tok"],
        MOE["moe_intermediate_size"],
        MOE["n_shared_experts"] * MOE["moe_intermediate_size"],
        (first, held), False, jnp.float32, block_rows=block_rows,
        shared_gated=False, balance_alpha=alpha,
    )


def _moe_params(seed=0):
    x = jnp.zeros((2, MOE["hidden_size"]), jnp.float32)
    params = _moe_layer(0, 8).init(jax.random.PRNGKey(seed), x)["params"]
    return _perturbed(params, seed + 1)


def _share(params, first, held):
    return {
        k: v[first:first + held] if k.startswith("experts_") else v
        for k, v in params.items()
    }


def _apply_moe(params, x, first, held, alpha=0.0):
    layer = _moe_layer(first, held, alpha=alpha)
    zeros = layer.init(jax.random.PRNGKey(0), x[:2])[ROUTING_COLLECTION]
    return layer.apply(
        {"params": _share(params, first, held), ROUTING_COLLECTION: zeros},
        x, mutable=[ROUTING_COLLECTION],
    )


@pytest.mark.parametrize("held", [1, 4, 8])
def test_shares_add_up_to_the_uncut_layer(held):
    """What all the shares' routed parts give, with the shared experts
    counted once, is what the reference gives for the whole layer."""
    params = _moe_params()
    x = jnp.asarray(
        np.random.default_rng(held).normal(size=(200, MOE["hidden_size"])),
        jnp.float32,
    )
    uncut = ref._experts(params, x, dict(MOE, experts_first=0, experts_held=8))
    shared = ref._experts(params, x, dict(MOE, experts_first=0, experts_held=0))
    routed = sum(
        _apply_moe(params, x, first, held)[0] - shared
        for first in range(0, 8, held)
    )
    assert _rel(routed + shared, uncut) < 1e-5
    assert _rel(shared, uncut) > 0.05  # the routed part is in the sum
    # and one share alone is the reference's same share
    one = ref._experts(
        _share(params, 8 - held, held), x,
        dict(MOE, experts_first=8 - held, experts_held=held),
    )
    assert _rel(_apply_moe(params, x, 8 - held, held)[0], one) < 1e-5


# The four cells that run the layer, by one step's tokens, top-k and the
# router's width (`perfbench/configs/*.json`), and two ends of the rule.
@pytest.mark.parametrize("tokens,top_k,num_experts,want", [
    pytest.param(2 * 8192, 6, 64, 512, id="deepseek-v2-lite"),
    pytest.param(8192, 6, 128, 512, id="nemotron-3-nano"),
    pytest.param(2 * 8192, 10, 512, 512, id="qwen3-next"),
    pytest.param(8192, 8, 256, 256, id="laguna-xs.2"),
    pytest.param(80, 2, 8, 128, id="never-under-128"),
    pytest.param(65536, 8, 8, 512, id="never-over-512"),
    pytest.param(1028, 2, 8, 512, id="257-pairs-take-one-block-of-512"),
])
def test_block_rows_come_from_the_shapes(tokens, top_k, num_experts, want):
    """The smallest power of two that holds a uniform router's pairs an
    expert (1,536, 384, 320, 256 in the four cells), within [128, 512]."""
    assert moe.block_rows_for(tokens, top_k, num_experts) == want


@pytest.mark.parametrize("tokens,told,want", [
    (200, None, 128), (900, None, 256), (1100, None, 512), (2048, 16, 16),
])
def test_layer_takes_the_shapes_block_unless_it_is_told_one(
    tokens, told, want
):
    """8 experts, 2 a token: 900 tokens are 225 pairs an expert, 1100
    are 275; the `block_rows` counter says what the loop ran with."""
    params = _moe_params()
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(tokens, MOE["hidden_size"])),
        jnp.float32,
    )
    layer = _moe_layer(2, 4, told)
    zeros = layer.init(jax.random.PRNGKey(0), x[:2])[ROUTING_COLLECTION]
    assert int(zeros["block_rows"]) == int(zeros["blocks"]) == 0
    y, counted = layer.apply(
        {"params": _share(params, 2, 4), ROUTING_COLLECTION: zeros},
        x, mutable=[ROUTING_COLLECTION],
    )
    counted = counted[ROUTING_COLLECTION]
    assert int(counted["block_rows"]) == want
    load = np.asarray(counted["load"], np.int64)
    assert int(counted["blocks"]) == int(np.ceil(load / want).sum())
    model = dict(MOE, experts_first=2, experts_held=4)
    assert _rel(y, ref._experts(_share(params, 2, 4), x, model)) < 1e-5


# Held experts 2..5 under a router that is told its choice: none, exactly
# one block of 512 (and whole blocks of 128 and 16), more than 512, and
# a part of any block.
DICTATED_LOADS = (0, 512, 600, 37)


def _dictated(loads, tokens, seed):
    """(x [tokens, d], a router weight) such that held expert 2 + h is
    chosen by exactly `loads[h]` tokens: x's first 8 columns are the
    logits (chosen 2 to 2.5, an expert held elsewhere -0.5 to 0.5, a held
    one not chosen under -2) and the router is the identity on them."""
    rng = np.random.default_rng(seed)
    experts, k, d = (MOE["n_routed_experts"], MOE["num_experts_per_tok"],
                     MOE["hidden_size"])
    logits = rng.uniform(-0.5, 0.5, size=(tokens, experts))
    logits[:, 2:6] = -2.0 - rng.uniform(0, 0.5, size=(tokens, 4))
    marks = np.zeros(tokens, np.int64)
    for h, load in enumerate(loads):
        chosen = rng.choice(np.flatnonzero(marks < k), load, replace=False)
        logits[chosen, 2 + h] = 2.0 + rng.uniform(0, 0.5, size=load)
        marks[chosen] += 1
    x = rng.normal(size=(tokens, d))
    x[:, :experts] = logits
    return jnp.asarray(x, jnp.float32), jnp.eye(d, experts, dtype=jnp.float32)


@pytest.fixture(scope="module")
def dictated():
    """The layer's output, counters and gradients (held parameters, the
    router among them, and x) at blocks of 16, 128 and 512, and the
    reference's, under `DICTATED_LOADS`."""
    x, router = _dictated(DICTATED_LOADS, 700, 7)
    share = dict(_share(_moe_params(5), 2, 4), gate=router)
    model = dict(MOE, experts_first=2, experts_held=4)
    weight = jnp.asarray(
        np.random.default_rng(8).normal(size=x.shape), jnp.float32
    )

    def run(block_rows):
        layer = _moe_layer(2, 4, block_rows)
        zeros = layer.init(jax.random.PRNGKey(0), x[:2])[ROUTING_COLLECTION]

        def loss(p, x):
            y, counted = layer.apply(
                {"params": p, ROUTING_COLLECTION: zeros}, x,
                mutable=[ROUTING_COLLECTION],
            )
            return jnp.sum(weight * y), (y, counted[ROUTING_COLLECTION])

        grads, (y, counted) = jax.grad(loss, (0, 1), has_aux=True)(share, x)
        return y, counted, grads

    def reference(p, x):
        return jnp.sum(weight * ref._experts(p, x, model))

    return (
        {block: run(block) for block in (16, 128, 512)},
        ref._experts(share, x, model),
        jax.grad(reference, (0, 1))(share, x),
    )


@pytest.mark.parametrize("block_rows", [16, 128, 512])
def test_any_block_gives_the_references_output_and_gradients(
    dictated, block_rows
):
    """Output, dx, the three weights' gradients and the pair weights'
    (which reach the router) do not depend on the block: each block's are
    the dense reference's, and the blocks' own agree closer still."""
    runs, want_y, want_grads = dictated
    y, _, grads = runs[block_rows]
    assert _rel(y, want_y) < 1e-5
    assert _rel(y, runs[16][0]) < 1e-6
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), w, g16 in zip(
        flat, jax.tree.leaves(want_grads), jax.tree.leaves(runs[16][2])
    ):
        scale = float(jnp.abs(w).max())
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.abs(g - w).max()) < 1e-4 * scale, (
            jax.tree_util.keystr(path)
        )
        assert float(jnp.abs(g - g16).max()) < 1e-5 * scale
    # the expert of no rows has no gradient, whatever the block
    assert float(jnp.abs(grads[0]["experts_up_proj"][0]).max()) == 0.0


@pytest.mark.parametrize("block_rows", [16, 128, 512])
def test_no_pair_dropped_and_blocks_counted_under_dictated_loads(
    dictated, block_rows
):
    _, counted, _ = dictated[0][block_rows]
    loads = np.asarray(DICTATED_LOADS)
    np.testing.assert_array_equal(np.asarray(counted["load"]), loads)
    assert int(counted["pairs"]) == int(counted["processed"]) == loads.sum()
    assert int(counted["blocks"]) == int(np.ceil(loads / block_rows).sum())
    assert int(counted["block_rows"]) == block_rows
    fields = RoutingLedger().task_delta(
        {ROUTING_COLLECTION: {"layers_1": {"mlp": counted}}}
    )
    assert fields["dropped"] == 0 and fields["pairs"] == loads.sum()
    assert fields["blocks"] == int(np.ceil(loads / block_rows).sum())
    assert fields["block_rows"] == block_rows


def test_weights_are_the_softmax_at_the_chosen_not_renormalised():
    """`norm_topk_prob: false`: a token's routing weights are p at its
    top-k and sum to less than 1; renormalised they would sum to 1."""
    params = _moe_params(2)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(64, MOE["hidden_size"])),
        jnp.float32,
    )
    probs, ids, top = ref._route(params, x, MOE)
    assert float(jnp.max(jnp.sum(top, -1))) < 0.9
    np.testing.assert_allclose(
        top, jnp.take_along_axis(probs, ids, axis=-1), rtol=1e-6
    )
    renormalised = ref._experts(params, x, dict(MOE, norm_topk_prob=True))
    got = _apply_moe(params, x, 0, 8)[0]
    assert _rel(got, ref._experts(params, x, MOE)) < 1e-5
    assert _rel(got, renormalised) > 0.05


# ---------------------------------------------------------------------------
# The balancing loss
# ---------------------------------------------------------------------------


def test_balance_loss_on_a_hand_made_routing():
    """Two sequences of 4 tokens, 4 experts, 2 a token.  Sequence 0
    chooses experts (0, 1) always: f = [2, 2, 0, 0]; with p uniform
    P = 1/4 each and sum f P = 1.  Sequence 1 spreads evenly: f = 1
    everywhere, sum f P = 1 for any p.  A router that favours what it
    chooses reads above 1."""
    uniform = jnp.full((8, 4), 0.25)
    expert = jnp.asarray(
        [[0, 1]] * 4 + [[0, 1], [2, 3], [0, 2], [1, 3]], jnp.int32
    )
    assert float(moe.sequence_balance_loss(uniform, expert, 2)) == (
        pytest.approx(1.0)
    )
    skewed = jnp.asarray([[0.4, 0.4, 0.1, 0.1]] * 4 + [[0.25] * 4] * 4)
    # sequence 0: 2 x 0.4 + 2 x 0.4 = 1.6; sequence 1: 1; mean 1.3
    assert float(moe.sequence_balance_loss(skewed, expert, 2)) == (
        pytest.approx(1.3)
    )
    # as ONE sequence of 8 tokens: f = [1.5, 1.5, .5, .5],
    # P = [.325, .325, .175, .175] -> 1.15
    assert float(moe.sequence_balance_loss(skewed, expert, 1)) == (
        pytest.approx(1.15)
    )
    model = dict(aux_loss_alpha=0.5)
    assert float(ref.balance_loss(skewed[:4], expert[:4], model)) == (
        pytest.approx(0.8)
    )


def test_injected_gradient_is_the_explicit_sums():
    """The layer's output does not change with alpha and the router
    receives alpha x d(sum f P)/dW_r on top of its gradient; the counts
    are constants."""
    alpha = 0.3
    params = _moe_params(5)
    x = jnp.asarray(
        np.random.default_rng(5).normal(size=(2, 40, MOE["hidden_size"])),
        jnp.float32,
    )
    weight = jnp.asarray(
        np.random.default_rng(6).normal(size=x.shape), jnp.float32
    )

    def program(p, a):
        return jnp.sum(weight * _apply_moe(p, x, 0, 8, alpha=a)[0])

    np.testing.assert_array_equal(
        _apply_moe(params, x, 0, 8, alpha=alpha)[0],
        _apply_moe(params, x, 0, 8)[0],
    )

    def explicit(p):
        balance = 0.0
        for row in x:
            probs, ids, _ = ref._route(p, row, MOE)
            balance += ref.balance_loss(
                probs, ids, dict(aux_loss_alpha=alpha)
            ) / len(x)
        return balance

    with_loss = jax.grad(program)(params, alpha)
    without = jax.grad(program)(params, 0.0)
    added = jax.grad(explicit)(params)
    for key in params:
        extra = jax.tree.map(lambda a, b: a - b, with_loss[key], without[key])
        for got, want in zip(jax.tree.leaves(extra),
                             jax.tree.leaves(added[key])):
            if key == "gate":
                scale = float(jnp.abs(want).max())
                assert scale > 0
                # (the difference of two gradients ten times its size)
                assert float(jnp.abs(got - want).max()) < 5e-3 * scale
            else:  # the loss needs only the router
                assert float(jnp.abs(want).max()) == 0
                assert float(jnp.abs(got).max()) < 1e-6
    # the routing collection counts the loss; the ledger gives the mean
    _, state = _apply_moe(params, x, 0, 8, alpha=alpha)
    counted = float(state[ROUTING_COLLECTION]["balance"])
    assert counted == pytest.approx(float(explicit(params)), rel=1e-5)
    ledger = RoutingLedger()
    ledger.seed_once({})
    fields = ledger.task_delta(
        {ROUTING_COLLECTION: {"layers_1": {"mlp": state[ROUTING_COLLECTION]}}},
        steps=2,
    )
    assert fields["balance_loss"] == pytest.approx(counted / 2, rel=1e-6)
    assert fields["dropped"] == 0 and fields["layers"] == 1


def _program_text(module, tokens):
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0), tokens)

    def fwd_bwd(variables):
        def total(params):
            out, state = module.apply(
                {**variables, "params": params}, tokens,
                mutable=[ROUTING_COLLECTION],
            )
            return jnp.sum(out), state

        return jax.grad(total, has_aux=True)(variables["params"])

    return str(jax.make_jaxpr(fwd_bwd)(variables))


def test_alpha_zero_traces_no_extra_op_into_the_other_models():
    """Qwen3-Next and Nemotron-H tell their expert layers no alpha: their
    programs (forward and backward, counters included) are op for op
    what an expert layer WITHOUT the balancing code traces, and hold no
    `balance` counter."""
    from model_zoo.nemotron_h import nemotron_h_lm as nemotron
    from model_zoo.qwen3_next import qwen3_next_lm as qwen

    tokens = jnp.zeros((2, 32), jnp.int32)
    for module in (
        qwen.custom_model(use_bf16=False, num_hidden_layers=2,
                          full_attention_interval=2),
        nemotron.custom_model(use_bf16=False, hybrid_override_pattern="ME*E",
                              chunk_size=32),
    ):
        text = _program_text(module, tokens)

        def refuse(*_):
            raise AssertionError("the balancing loss was traced")

        saved = (moe.sequence_balance_loss, moe._with_auxiliary_loss)
        moe.sequence_balance_loss = moe._with_auxiliary_loss = refuse
        try:
            assert _program_text(module, tokens) == text
        finally:
            moe.sequence_balance_loss, moe._with_auxiliary_loss = saved
        state = jax.eval_shape(module.init, jax.random.PRNGKey(0), tokens)
        names = {
            jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_leaves_with_path(state[ROUTING_COLLECTION])
        }
        assert names and not any("balance" in name for name in names)
    with pytest.raises(ValueError):
        SparseMoeBlock(
            8, 2, 16, 16, (0, 8), score="sigmoid", expert_form="relu2",
            balance_alpha=0.1,
        ).init(jax.random.PRNGKey(0), jnp.zeros((4, 8)))


# ---------------------------------------------------------------------------
# The stated precision
# ---------------------------------------------------------------------------


def _dot_precisions(jaxpr):
    """-> [(operand dtype, precision)] of every product, inner jaxprs too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(
                (eqn.invars[0].aval.dtype, eqn.params["precision"])
            )
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found += _dot_precisions(inner)
    return found


def test_float32_products_ask_for_their_precision():
    """In the bfloat16 model the only products of float32 operands are
    the routers' (`HIGHEST`), one an expert layer: a product left to a
    TPU's default would round its float32 operands to bfloat16."""
    highest = jax.lax.Precision.HIGHEST
    module = zoo.custom_model(use_bf16=True, **_model_kwargs(TINY))
    tokens = ref.sample(0, 1, TINY)
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0), tokens)
    found = _dot_precisions(
        jax.make_jaxpr(lambda v, t: module.apply(v, t))(
            variables, tokens
        ).jaxpr
    )
    float32 = [p for dtype, p in found if dtype == jnp.float32]
    assert len(float32) == TINY["num_hidden_layers"] - 1
    assert all(p == (highest, highest) for p in float32)
    # 3 layers x (4 projections + scores + values) + MLPs + experts + head
    assert len(found) > 25


def test_bf16_program_is_the_reference_at_the_stated_precision():
    """The attention sublayer in bfloat16 against the reference with
    bfloat16 operands in the same products: closer than to `highest` by
    an order of magnitude."""
    model = dict(TINY, hidden_size=256, qk_nope_head_dim=64,
                 qk_rope_head_dim=32, v_head_dim=64, kv_lora_rank=128,
                 sample_tokens=128)
    cfg = zoo.DeepseekV2Config(**_model_kwargs(model))
    layer = zoo.LatentAttention(cfg)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, 128, 256)), jnp.float32
    )
    variables = layer.init(jax.random.PRNGKey(0), x)
    got = layer.apply(variables, x)[0]
    stated = ref._attention(
        variables["params"], x[0], model, frozenset({"blocks"})
    )
    highest = ref._attention(variables["params"], x[0], model)
    assert _rel(got, stated) < 2e-3
    assert _rel(got, highest) > 3 * _rel(got, stated)


def test_the_cell_checks_precisions_the_reference_has():
    check = CONFIG["check"]
    assert "highest" in check["tolerance_rel_rms"]
    assert set(check["also_report"]) >= {"stated", "bfloat16", "no_mscale"}
    for name in list(check["tolerance_rel_rms"]) + check["also_report"]:
        assert name in ref.PRECISIONS
    assert "also_report" not in CONFIG["rehearse"]["check"]
    with pytest.raises(ValueError):
        ref.forward({}, np.zeros((1, 4), np.int32), TINY, "float16")


# ---------------------------------------------------------------------------
# Through the trainer, the saver and `elasticdl train`
# ---------------------------------------------------------------------------


def _trainer():
    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer

    model = dict(TINY, num_hidden_layers=2, sample_tokens=32)
    return DataParallelTrainer(
        zoo.custom_model(use_bf16=False, remat=True, **_model_kwargs(model)),
        zoo.loss, zoo.optimizer(warmup_steps=2),
        build_mesh(MeshConfig(data=1, model=1), devices=jax.devices()[:1]),
    ), model


def test_trainer_carries_the_counters_and_checkpoint_restores_the_logits(
    tmp_path,
):
    from elasticdl_tpu.checkpoint import CheckpointSaver

    trainer, model = _trainer()
    tokens = ref.sample(11, 4, model)
    losses = [float(trainer.train_step(tokens, tokens)) for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    counted = RoutingLedger()
    counted.seed_once({})
    fields = counted.task_delta(trainer.state.model_state, steps=3)
    assert fields["layers"] == 1 and fields["dropped"] == 0
    assert 0 < fields["pairs"] < 3 * 4 * 32 * 2
    # alpha x (about 1 where the routing is about even)
    assert 0.5e-3 < fields["balance_loss"] < 3e-3
    before = trainer.eval_step(tokens)
    CheckpointSaver(str(tmp_path)).save(trainer.state_to_host(), 3)
    restored, step = CheckpointSaver(str(tmp_path)).load_latest()
    assert step == 3
    fresh, _ = _trainer()
    fresh.state = restored
    np.testing.assert_array_equal(fresh.eval_step(tokens), before)
    want = ref.forward(restored.params, tokens, model)
    assert _rel(before, want) < 1e-5


def test_optimizer_warms_up():
    import optax

    tx = zoo.optimizer(lr=1e-2, warmup_steps=4)
    params = {"w": jnp.ones((3,))}
    state = tx.init(params)
    sizes = []
    for _ in range(6):
        updates, state = tx.update({"w": jnp.ones((3,))}, state, params)
        sizes.append(float(jnp.abs(updates["w"]).max()))
        params = optax.apply_updates(params, updates)
    # Adam's first steps move by about the rate: n / 4 of it, then all
    ratios = np.asarray(sizes[:4]) / sizes[4]
    np.testing.assert_allclose(ratios, [0.25, 0.5, 0.75, 1.0], rtol=0.15)
    assert sizes[5] == pytest.approx(sizes[4], rel=0.1)


def test_two_task_elasticdl_train_end_to_end(tmp_path):
    """`elasticdl train` as a user runs it: master, task dispatch, one
    collective worker, a cadence checkpoint, `moe.routing` a task with
    the balancing loss on it."""
    from elasticdl_tpu.common.args import parse_master_args
    from elasticdl_tpu.common.constants import Mode
    from elasticdl_tpu.master.job_runner import run_allreduce_job

    model = dict(TINY, num_hidden_layers=2, sample_tokens=32)
    params = ",".join(
        f"{k}={str(v).lower() if isinstance(v, bool) else v}"
        for k, v in _model_kwargs(model).items()
    )
    tb = tmp_path / "tb"
    args = parse_master_args([
        "--model_zoo=model_zoo",
        "--model_def=deepseek_v2.deepseek_v2_lm",
        f"--model_params={params},remat=true",
        "--training_data=synthetic://lm?n=16&len=32&vocab=64&seed=5",
        "--records_per_task=8",
        "--minibatch_size=4",
        "--num_workers=1",
        "--use_bf16=false",
        "--distribution_strategy=AllreduceStrategy",
        f"--checkpoint_dir={tmp_path / 'ckpt'}",
        f"--tensorboard_log_dir={tb}",
        "--checkpoint_steps=2",
        "--num_epochs=1",
    ])
    assert run_allreduce_job(args, Mode.TRAINING) == 0
    assert any(p.startswith("step_") for p in os.listdir(tmp_path / "ckpt"))
    with open(tb / "events_worker_0.jsonl") as f:
        events = [json.loads(line) for line in f]
    routing = [e for e in events
               if e.get("event") == "span" and e.get("name") == "moe.routing"]
    assert len(routing) == 2
    assert [e["steps"] for e in routing] == [2, 2]
    assert all(e["layers"] == 1 and e["held"] == 4 for e in routing)
    assert all(e["dropped"] == 0 and e["pairs"] > 0 for e in routing)
    assert all(0.5e-3 < e["balance_loss"] < 3e-3 for e in routing)


def test_benchmark_cost_functions_count_what_they_say():
    model = CONFIG["model"]
    cost = ref.step_cost(model, 2)
    # ~35.7 TFLOP a step of 2 x 8192 tokens without recomputation
    assert 35e12 < cost["flops"] < 36.5e12
    assert cost["bytes"] == 28 * ref._all_params(model)
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
