"""Laguna on the normal training path (ISSUE 36): the zoo model with its
window and full attention layers, a head count a layer type, two rotary
tables, the per-head output gate, the leading dense layer and the
sigmoid-routed gated-SiLU expert layers, each against the plain reference
that decides the benchmark cell's `correct`
(`perfbench/configs/laguna_reference.py`, which shares no code with the
program).  Tiny sizes, seeded random weights, float32 on the CPU, so
tolerances are those of float32 summation order: 1e-5 of the outputs'
size, gradients 2e-3 of each leaf's largest entry as for the other hybrid
models.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers.moe import (
    ROUTING_COLLECTION, RoutingLedger, SparseMoeBlock,
)
from model_zoo.laguna import laguna_lm as zoo

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO_ROOT, "perfbench", "configs")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(CONFIGS, "laguna_reference.py"), "laguna_ref")

with open(os.path.join(CONFIGS, "laguna-xs.2.json")) as f:
    CONFIG = json.load(f)

# The cut's five layers (full, sliding x 3, full; dense, sparse x 4) with
# 6 and 8 query heads over 2 key-value heads, a window of 32.  T = 256:
# the XLA engine walks a sliding layer in two blocks of 128 and a full
# one in one of 256 (YaRN's original length is 32 here: positions past
# it are what the ramp is for).
TINY = dict(CONFIG["rehearse"]["model"], sample_tokens=256)


def _model_kwargs(model):
    return {k: v for k, v in model.items() if k != "sample_tokens"}


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def _perturbed(tree, seed, scale=0.05):
    """Norm weights start at 1 and selection biases at 0: move every leaf
    off its special value so that a dropped term would show."""
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
    model = dict(TINY, experts_first=first, experts_held=held)
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
    np.testing.assert_allclose(
        float(zoo.loss(tokens, got)),
        float(ref.loss_fn(params, tokens, tokens, model)), rtol=1e-5,
    )


@pytest.mark.parametrize("fault", ["no_window", "no_gate"])
def test_planted_faults_read_far_from_the_program(program_and_reference, fault):
    """`no_window` is the reference with the sliding layers given the full
    causal mask, `no_gate` with the heads' outputs left ungated: the
    readings every run of the cell prints beside its tolerances."""
    program, _, params, tokens, model = program_and_reference
    reading = _rel(program(params), ref.forward(params, tokens, model, fault))
    assert reading > 3 * CONFIG["check"]["tolerance_rel_rms"]["highest"]
    assert reading > 1000 * 1e-5


def test_clear_tokens_are_the_references_own_choice(
    program_and_reference, monkeypatch
):
    """`highest_clear` is `highest` where every expert layer's selection
    is at least `CLEAR_MARGIN` from a tie IN THE REFERENCE, and the
    outputs `program` kept elsewhere, whatever those are."""
    program, reference, params, tokens, model = program_and_reference
    monkeypatch.setattr(ref, "CLEAR_MARGIN", 0.02)
    monkeypatch.setattr(ref, "_PROGRAM", {})
    with pytest.raises(ValueError):
        ref.forward(params, tokens, model, "highest_clear")
    theirs = np.asarray(program(params), np.float32) + 1.0
    ref._PROGRAM["outputs"] = theirs
    got = np.asarray(ref.forward(params, tokens, model, "highest_clear"))
    highest = np.asarray(reference(params))
    margins = []
    with jax.default_matmul_precision("highest"):
        for row in tokens:
            ref.decoder(params, row, model, margins=margins)
    clear = np.stack([
        np.min(np.stack(margins[r * 4:(r + 1) * 4]), axis=0)
        for r in range(len(tokens))
    ]) >= 0.02
    assert 0.05 < clear.mean() < 0.95
    np.testing.assert_array_equal(got[clear], highest[clear])
    np.testing.assert_array_equal(got[~clear], theirs[~clear])


def test_gradients_match_the_reference(program_and_reference):
    program, _, params, tokens, model = program_and_reference
    got = jax.grad(lambda p: zoo.loss(tokens, program(p)))(params)
    want = jax.grad(lambda p: ref.loss_fn(p, tokens, tokens, model))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    assert len(flat_got) == len(jax.tree.leaves(want))
    for (path, g), w in zip(flat_got, jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        if "e_score_correction_bias" in name:
            # a selection is not differentiated: the reference has no
            # gradient for it, the program hands it the load violation
            assert float(jnp.abs(w).max()) == 0.0
            assert set(np.unique(np.asarray(g))) <= {-1.0, 0.0, 1.0}
            continue
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        assert float(jnp.abs(g - w).max()) < 2e-3 * scale, name


def test_parameter_names_and_shapes_go_by_the_layer_type():
    module = zoo.custom_model(use_bf16=False, **_model_kwargs(TINY))
    shapes = jax.tree.map(
        lambda a: a.shape,
        jax.eval_shape(module.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 64), jnp.int32))["params"],
    )
    assert set(shapes) == {"model", "lm_head"}
    stack = shapes["model"]
    assert set(stack) == {"embed_tokens", "norm"} | {
        f"layers_{i}" for i in range(5)
    }
    d, hd, hkv = TINY["hidden_size"], TINY["head_dim"], 2
    for i, (kind, heads) in enumerate(zip(
        TINY["layer_types"], TINY["num_attention_heads_per_layer"]
    )):
        layer = stack[f"layers_{i}"]
        assert set(layer) == {"input_layernorm", "self_attn",
                              "post_attention_layernorm", "mlp"}
        attn = layer["self_attn"]
        assert attn["q_proj"]["kernel"] == (d, heads * hd)
        assert attn["k_proj"]["kernel"] == (d, hkv * hd)
        assert attn["v_proj"]["kernel"] == (d, hkv * hd)
        assert attn["g_proj"] == (d, heads)          # one scalar a head
        assert attn["o_proj"]["kernel"] == (heads * hd, d)
        assert heads == (8 if kind == "sliding_attention" else 6)
        if i == 0:
            assert set(layer["mlp"]) == {"gate_proj", "up_proj", "down_proj"}
        else:
            assert set(layer["mlp"]) == {
                "gate", "experts_gate_proj", "experts_up_proj",
                "experts_down_proj", "shared_experts",
            }
            assert set(layer["mlp"]["gate"]) == {
                "weight", "e_score_correction_bias"
            }
            assert set(layer["mlp"]["shared_experts"]) == {
                "gate_proj", "up_proj", "down_proj"
            }
            assert layer["mlp"]["experts_gate_proj"] == (4, d, 32)
    # no query / key norm, no bias
    assert not any(
        "q_norm" in name or "k_norm" in name or name.endswith("bias']")
        and "e_score" not in name
        for name in (jax.tree_util.keystr(p) for p, _ in
                     jax.tree_util.tree_leaves_with_path(shapes))
    )
    without = zoo.custom_model(**dict(_model_kwargs(TINY), gating=False))
    shapes = jax.eval_shape(without.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 64), jnp.int32))["params"]
    assert "g_proj" not in shapes["model"]["layers_0"]["self_attn"]


def test_full_size_configuration_counts_the_parameters_it_states():
    model = CONFIG["model"]
    module = zoo.custom_model(use_bf16=True, **_model_kwargs(model))
    shapes = jax.eval_shape(
        module.init, jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)
    )["params"]
    counted = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert counted == ref._all_params(model) == 691_624_960
    assert "691,624,960" in CONFIG["device_bytes"]

    def of(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))

    stack = shapes["model"]
    assert of(stack["layers_0"]["self_attn"]) == 29_458_432   # full
    assert of(stack["layers_1"]["self_attn"]) == 37_879_808   # sliding
    assert of(stack["layers_0"]["mlp"]) == 50_331_648
    assert of(stack["layers_1"]["mlp"]) == 104_333_312 + 256  # + the bias
    # every width as published; the cut is in depth, experts held, vocabulary
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "shared_expert_intermediate_size", "num_key_value_heads",
                "head_dim", "num_experts_per_tok", "sliding_window",
                "gating", "rms_norm_eps"):
        assert model[key] == CONFIG[key], key
    assert model["moe_routed_scaling_factor"] == CONFIG[
        "moe_routed_scaling_factor"
    ]
    assert model["num_experts"] == CONFIG["published"]["num_experts"] == 256
    assert model["experts_held"] == CONFIG["num_experts"] == 32
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    # the three lists stand as published; the stack is their first five
    for name in ("layer_types", "mlp_layer_types",
                 "num_attention_heads_per_layer"):
        assert len(CONFIG[name]) == 40
        assert model[name] == CONFIG[name][:CONFIG["num_hidden_layers"]]
    for kind, group in CONFIG["rope_parameters"].items():
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
    # the job's flags say what `model` says
    from elasticdl_tpu.common.args import parse_dict_params

    flags = next(f for f in CONFIG["job"] if f.startswith("--model_params="))
    parsed = parse_dict_params(flags.split("=", 1)[1])
    assert parsed.pop("remat") is True
    assert parsed.pop("attn_impl") == "xla"
    built = zoo.custom_model(**parsed).cfg
    want = zoo.custom_model(**_model_kwargs(model)).cfg
    assert built == want


def test_published_forty_layers_and_the_cut_are_the_same_code():
    """The lists as published with `num_hidden_layers` 40 build the whole
    stack (shapes only: 33.4B parameters with all 256 experts held); with
    5 the same lists build the cut."""
    whole = dict(_model_kwargs(CONFIG["model"]), num_hidden_layers=40,
                 experts_first=0, experts_held=256, vocab_size=100352)
    for name in ("layer_types", "mlp_layer_types",
                 "num_attention_heads_per_layer"):
        whole[name] = CONFIG[name]
    module = zoo.custom_model(**whole)
    shapes = jax.eval_shape(
        module.init, jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
    )["params"]
    counted = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 33.3e9 < counted < 33.5e9
    assert len(shapes["model"]) == 40 + 2
    cut = zoo.custom_model(**dict(whole, num_hidden_layers=5)).cfg
    assert cut.num_hidden_layers == 5
    # a job's flat flags carry a list as a/b/c
    flat = zoo.custom_model(
        layer_types="full_attention/sliding_attention",
        mlp_layer_types="dense/sparse", num_attention_heads_per_layer="6/8",
    ).cfg
    assert flat.layer_types == ("full_attention", "sliding_attention")
    assert flat.num_attention_heads_per_layer == (6, 8)
    assert flat.num_hidden_layers == 2
    for bad in (dict(layer_types="full_attention/window"),
                dict(mlp_layer_types="dense"),          # one of two layers
                dict(num_attention_heads_per_layer="6/7"),
                dict(no_such_key=1)):
        with pytest.raises(ValueError):
            zoo.custom_model(**bad)


# ---------------------------------------------------------------------------
# The attention sublayer: two kinds, two tables, the gate
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_rotary_tables_are_the_references(kind):
    """YaRN's blended frequencies on half of a full layer's head, times
    `attention_factor`; plain frequencies on all of a sliding layer's."""
    cfg = zoo.custom_model(**_model_kwargs(TINY)).cfg
    cos, sin = zoo.rotary_tables(cfg, 256)[kind]
    inv_freq, magnitude = ref.rotary_inv_freq(TINY, kind)
    width = 8 if kind == "full_attention" else 16
    assert cos.shape == sin.shape == (256, width) == (256, 2 * len(inv_freq))
    angles = np.arange(256)[:, None] * inv_freq[None, :]
    np.testing.assert_allclose(
        cos[:, :width // 2], np.cos(angles) * magnitude, atol=2e-4
    )
    np.testing.assert_allclose(
        sin[:, width // 2:], np.sin(angles) * magnitude, atol=2e-4
    )
    if kind == "full_attention":
        assert magnitude == pytest.approx(1.4158883083359672)
        assert magnitude == pytest.approx(0.1 * np.log(64) + 1)
        plain = 500000.0 ** (-np.arange(4) / 4)
        assert not np.allclose(inv_freq, plain)      # the ramp is at work
        assert inv_freq[0] == plain[0]               # the fastest pair kept
        assert inv_freq[-1] == pytest.approx(plain[-1] / 64)
    else:
        assert magnitude == 1.0
        np.testing.assert_allclose(inv_freq, 10000.0 ** (-np.arange(8) / 8))


@pytest.mark.parametrize("kind,heads", [
    ("full_attention", 6), ("sliding_attention", 8),
])
def test_attention_sublayer_matches_the_reference(kind, heads):
    cfg = zoo.custom_model(use_bf16=False, **_model_kwargs(TINY)).cfg
    layer = zoo.Attention(cfg, kind == "sliding_attention", heads)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, 256, 64)), jnp.float32
    )
    tables = zoo.rotary_tables(cfg, 256)[kind]
    variables = layer.init(jax.random.PRNGKey(0), x, *tables)
    got = layer.apply(variables, x, *tables)[0]
    want = ref._attention(variables["params"], x[0], TINY, kind, heads)
    assert _rel(got, want) < 1e-5
    for fault in ("no_window", "no_gate"):
        other = ref._attention(
            variables["params"], x[0], TINY, kind, heads, frozenset({fault})
        )
        changes = fault == "no_gate" or kind == "sliding_attention"
        assert (_rel(got, other) > 1e-2) == changes, (kind, fault)


# ---------------------------------------------------------------------------
# The expert layer: sigmoid scores over gated-SiLU experts, its share
# ---------------------------------------------------------------------------

MOE = dict(TINY, experts_first=0, experts_held=8)


def _moe_layer(first, held, block_rows=16):
    return SparseMoeBlock(
        MOE["num_experts"], MOE["num_experts_per_tok"],
        MOE["moe_intermediate_size"], MOE["shared_expert_intermediate_size"],
        (first, held), True, jnp.float32, block_rows=block_rows,
        score="sigmoid", expert_form="gated_silu",
        routed_scale=MOE["moe_routed_scaling_factor"], shared_gated=False,
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


def _apply_moe(params, x, first, held):
    layer = _moe_layer(first, held)
    zeros = layer.init(jax.random.PRNGKey(0), x[:2])[ROUTING_COLLECTION]
    y, state = layer.apply(
        {"params": _share(params, first, held), ROUTING_COLLECTION: zeros},
        x, mutable=[ROUTING_COLLECTION],
    )
    return y, state[ROUTING_COLLECTION]


def test_third_pairing_has_a_biased_gate_and_three_products():
    params = _moe_params()
    assert set(params) == {
        "gate", "experts_gate_proj", "experts_up_proj", "experts_down_proj",
        "shared_experts",
    }
    assert set(params["gate"]) == {"weight", "e_score_correction_bias"}
    assert set(params["shared_experts"]) == {
        "gate_proj", "up_proj", "down_proj"
    }
    for bad in (dict(score="tanh"), dict(expert_form="gelu")):
        with pytest.raises(ValueError):
            SparseMoeBlock(8, 2, 16, 16, (0, 8), **bad).init(
                jax.random.PRNGKey(0), jnp.zeros((4, 32))
            )


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_shares_add_up_to_the_uncut_layer(held):
    """What all the shares of a layer give, the shared expert counted
    once, is what the reference gives for the whole layer (the guide's
    section 4: a cut that every chip makes alike must add up)."""
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
    # and one share alone is the reference's same share
    one = ref._experts(
        _share(params, 8 - held, held), x,
        dict(MOE, experts_first=8 - held, experts_held=held),
    )
    assert _rel(_apply_moe(params, x, 8 - held, held)[0], one) < 1e-5


def test_weights_are_the_sigmoids_renormalised_and_scaled():
    """w = 2.5 s_chosen / sum(s_chosen): a token's weights add up to 2.5
    whatever its scores, and the bias chooses without entering them."""
    params = _moe_params(4)
    x = jnp.asarray(
        np.random.default_rng(5).normal(size=(64, MOE["hidden_size"])),
        jnp.float32,
    )
    scores = jax.nn.sigmoid(x @ params["gate"]["weight"])
    _, ids = jax.lax.top_k(
        scores + params["gate"]["e_score_correction_bias"], 2
    )
    top = jnp.take_along_axis(scores, ids, axis=-1)
    weights = 2.5 * top / top.sum(-1, keepdims=True)
    want = jnp.zeros_like(x)
    for e in range(8):
        w = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        hidden = jax.nn.silu(x @ params["experts_gate_proj"][e]) * (
            x @ params["experts_up_proj"][e]
        )
        want = want + w[:, None] * (hidden @ params["experts_down_proj"][e])
    shared = ref._experts(params, x, dict(MOE, experts_first=0, experts_held=0))
    got, counters = _apply_moe(params, x, 0, 8)
    assert _rel(got - shared, want) < 1e-5
    assert int(counters["pairs"]) == int(counters["processed"]) == 128


# ---------------------------------------------------------------------------
# The stated precision
# ---------------------------------------------------------------------------


def _dot_precisions(jaxpr):
    """(dtype of the first operand, precision) of every dot_general, inner
    jaxprs included."""
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
    """In the bfloat16 model the only products of float32 operands are the
    routers' (one an expert layer) and the gates' (one a layer), and each
    asks for `HIGHEST`: a product left to a TPU's default would round its
    float32 operands to bfloat16."""
    highest = jax.lax.Precision.HIGHEST
    model = dict(TINY, sample_tokens=64)
    module = zoo.custom_model(use_bf16=True, **_model_kwargs(model))
    tokens = ref.sample(0, 1, model)
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0), tokens)
    found = _dot_precisions(
        jax.make_jaxpr(lambda v, t: module.apply(v, t))(
            variables, tokens
        ).jaxpr
    )
    float32 = [p for dtype, p in found if dtype == jnp.float32]
    assert len(float32) == 4 + 5  # four routers, five gates
    assert all(p == (highest, highest) for p in float32)
    # 5 layers x (4 projections + scores + values) + MLPs + experts + head
    assert len(found) > 40


@pytest.mark.parametrize("kind,heads", [
    ("full_attention", 6), ("sliding_attention", 8),
])
def test_bf16_program_is_the_reference_at_the_stated_precision(kind, heads):
    """The attention sublayer in bfloat16 against the reference with
    bfloat16 operands in the same products (the gate float32 in both):
    closer than to `highest`."""
    model = dict(TINY, hidden_size=256, head_dim=64, sample_tokens=128)
    cfg = zoo.custom_model(use_bf16=True, **_model_kwargs(model)).cfg
    layer = zoo.Attention(cfg, kind == "sliding_attention", heads)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, 128, 256)), jnp.float32
    )
    tables = zoo.rotary_tables(cfg, 128)[kind]
    variables = layer.init(jax.random.PRNGKey(0), x, *tables)
    got = layer.apply(variables, x, *tables)[0]
    stated = ref._attention(
        variables["params"], x[0], model, kind, heads, frozenset({"blocks"})
    )
    highest = ref._attention(variables["params"], x[0], model, kind, heads)
    assert _rel(got, stated) < 3e-3
    assert _rel(got, highest) > 2 * _rel(got, stated)


def test_the_cell_checks_precisions_the_reference_has():
    check = CONFIG["check"]
    assert set(check["tolerance_rel_rms"]) == {"highest", "highest_clear"}
    assert set(check["also_report"]) >= {
        "stated", "bfloat16", "no_window", "no_gate"
    }
    for name in list(check["tolerance_rel_rms"]) + check["also_report"]:
        assert name in ref.PRECISIONS
    assert "also_report" not in CONFIG["rehearse"]["check"]
    with pytest.raises(ValueError):
        ref.forward({}, np.zeros((1, 4), np.int32), TINY, "float16")
    for key in ("source", "reduced", "published", "deployment", "assumed",
                "guarantees", "device_bytes", "check", "rehearse"):
        assert CONFIG[key], key


# ---------------------------------------------------------------------------
# Through the trainer, the saver and `elasticdl train`
# ---------------------------------------------------------------------------


def _trainer():
    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer

    model = dict(TINY, sample_tokens=64)
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
    fields = counted.task_delta(trainer.state.model_state)
    assert fields["layers"] == 4 and fields["dropped"] == 0
    # three steps of 4 x 64 tokens, two choices each, four expert layers
    assert 0 < fields["pairs"] < 3 * 4 * 64 * 2 * 4
    # the selection bias took three steps of the balancing rule, each
    # +-1e-3 (or 0 for an expert at the mean), and none of AdamW
    gate = trainer.state.params["model"]["layers_1"]["mlp"]["gate"]
    moved = np.asarray(gate["e_score_correction_bias"], np.float64) / 1e-3
    np.testing.assert_allclose(moved, np.round(moved), atol=1e-3)
    assert 0 < np.abs(np.round(moved)).max() <= 3
    before = trainer.eval_step(tokens)
    CheckpointSaver(str(tmp_path)).save(trainer.state_to_host(), 3)
    restored, step = CheckpointSaver(str(tmp_path)).load_latest()
    assert step == 3
    fresh, _ = _trainer()
    fresh.state = restored
    np.testing.assert_array_equal(fresh.eval_step(tokens), before)
    want = ref.forward(restored.params, tokens, model)
    assert _rel(before, want) < 1e-5


def test_two_task_elasticdl_train_end_to_end(tmp_path):
    """`elasticdl train` as a user runs it: master, task dispatch, one
    collective worker, a cadence checkpoint, `moe.routing` a task; the
    per-layer lists ride the job's flat flags as a/b/c."""
    from elasticdl_tpu.common.args import parse_master_args
    from elasticdl_tpu.common.constants import Mode
    from elasticdl_tpu.master.job_runner import run_allreduce_job

    flags = next(
        f for f in CONFIG["rehearse"]["job"]
        if f.startswith("--model_params=")
    )
    tb = tmp_path / "tb"
    args = parse_master_args([
        "--model_zoo=model_zoo",
        "--model_def=laguna.laguna_lm",
        flags,
        "--training_data=synthetic://lm?n=16&len=64&vocab=64&seed=5",
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
    assert all(e["layers"] == 4 and e["held"] == 4 for e in routing)
    assert all(e["dropped"] == 0 and e["pairs"] > 0 for e in routing)


def test_benchmark_cost_functions_count_what_they_say():
    model = CONFIG["model"]
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
    step = ref.step_cost(model, 1)
    assert step["bytes"] == 28 * ref._all_params(model)
    # 6.5 TFLOP a sequence forward (ISSUE 36), three times that a step
    assert 19e12 < step["flops"] < 20.5e12
    assert (full["flops"] + band["flops"]) * 6 / 9 < 0.45 * step["flops"]
    experts = ref.moe_experts_cost(model, pairs=4 * 8192, steps=1)
    assert experts["flops"] == 6 * 3 * 2048 * 512 * 4 * 8192
    assert experts["bytes"] == 12 * 4 * 32 * 3 * 2048 * 512 + (
        4 * 8192 * 12 * 2048
    )
