"""Mellum 2 on the normal training path (ISSUE 42): the zoo model with its
three sliding-window layers to one full one, the norm on every query and
key head, two rotary tables over the whole head, and the expert sublayer
that is its routed experts and nothing else behind a renormalised softmax
router with the balancing loss, each against the plain reference that
decides the benchmark cell's `correct`
(`perfbench/configs/mellum_reference.py`, which shares no code with the
program).  The contract's cases are `tests/lm_contract.py`'s, at
`tests/spec_mellum.py`'s `SPEC` (the model as a job runs it:
`tests/test_mellum_program.py`); the expert layer's own are
`tests/test_moe.py`, where this router is one of `ROUTERS` (that the four
shares of a layer add up to the uncut reference's whole layer is
`test_shares_add_up_to_the_uncut_layer[mellum-2]` there).  Tiny sizes,
seeded random weights, float32 on the CPU, so tolerances are those of
float32 summation order: 1e-5 of the outputs' size, gradients 2e-3 of each
leaf's largest entry as for the other hybrid models.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers.moe import ROUTING_COLLECTION, SparseMoeBlock
from lm_contract import (  # noqa: F401  (the contract's cases, collected here)
    _model_kwargs, _perturbed, _rel, _size, bf16_case, lm,
    program_and_reference, pytest_generate_tests,
    test_benchmark_cost_functions_count_what_they_say,
    test_bf16_program_is_the_reference_at_the_stated_precision,
    test_float32_products_ask_for_their_precision,
    test_full_size_configuration_counts_the_parameters_it_states,
    test_gradients_match_the_reference,
    test_logits_and_loss_match_the_reference,
    test_the_cell_checks_precisions_the_reference_has,
)
# `lm` hands the cases this SPEC
from spec_mellum import SPEC, CONFIG, TINY, ref, zoo  # noqa: F401

FULL, SLIDING = "full_attention", "sliding_attention"
T = 256


@pytest.mark.parametrize("fault", ["no_window", "no_qk_norm", "no_yarn"])
def test_planted_faults_read_far_from_the_program(program_and_reference, fault):
    """`no_window` is the reference with the sliding layers given the full
    causal mask, `no_qk_norm` with queries and keys left unnormed,
    `no_yarn` with the plain table and factor 1 in the full layer: the
    readings every run of the cell prints beside its tolerances."""
    program, _, params, tokens, model = program_and_reference
    reading = _rel(program(params), ref.forward(params, tokens, model, fault))
    assert reading > 1000 * 1e-5


def test_clear_tokens_are_the_references_own_choice(
    program_and_reference, monkeypatch
):
    """`highest_clear` is `highest` where every expert layer's selection
    is at least `CLEAR_MARGIN` of a logit from a tie IN THE REFERENCE, and
    the outputs `program` kept elsewhere, whatever those are."""
    program, reference, params, tokens, model = program_and_reference
    monkeypatch.setattr(ref, "CLEAR_MARGIN", 0.05)
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
            watch = ref._watch()
            ref.decoder(params, row, model, watch=watch)
            margins.append(np.min(np.stack(watch["margins"]), axis=0))
    clear = np.stack(margins) >= 0.05
    assert 0.05 < clear.mean() < 0.95
    np.testing.assert_array_equal(got[clear], highest[clear])
    np.testing.assert_array_equal(got[~clear], theirs[~clear])


def _shapes(module):
    return jax.tree.map(
        lambda a: a.shape,
        jax.eval_shape(module.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 64), jnp.int32))["params"],
    )


def test_parameter_names_and_shapes_are_the_sources():
    shapes = _shapes(zoo.custom_model(use_bf16=False, **_model_kwargs(TINY)))
    assert set(shapes) == {"model", "lm_head"}
    stack = shapes["model"]
    assert set(stack) == {"embed_tokens", "norm"} | {
        f"layers_{i}" for i in range(4)
    }
    d, hd, h, hkv = TINY["hidden_size"], TINY["head_dim"], 8, 2
    for i in range(4):  # one head count, whatever the layer's type
        layer = stack[f"layers_{i}"]
        assert set(layer) == {"input_layernorm", "self_attn",
                              "post_attention_layernorm", "mlp"}
        attn = layer["self_attn"]
        assert set(attn) == {"q_proj", "k_proj", "v_proj", "q_norm",
                             "k_norm", "o_proj"}
        assert attn["q_proj"]["kernel"] == (d, h * hd)
        assert attn["k_proj"]["kernel"] == (d, hkv * hd)
        assert attn["v_proj"]["kernel"] == (d, hkv * hd)
        assert attn["q_norm"]["weight"] == attn["k_norm"]["weight"] == (hd,)
        assert attn["o_proj"]["kernel"] == (h * hd, d)
        # the router and the held experts: nothing shared, no bias
        assert set(layer["mlp"]) == {
            "gate", "experts_gate_proj", "experts_up_proj",
            "experts_down_proj",
        }
        assert layer["mlp"]["gate"] == (d, 8)
        assert layer["mlp"]["experts_gate_proj"] == (4, d, 32)
    assert not any(
        name.endswith("bias']") for name in (
            jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_leaves_with_path(shapes)
        )
    )


def test_published_lists_and_the_cut_are_the_same_code():
    """The lists as published with `num_hidden_layers` 28 build the whole
    stack (shapes only: 12.15B parameters with all 64 experts held), 21
    sliding layers and 7 full ones; with 4 the same lists build the cut;
    a `dense` entry builds the gated MLP of `intermediate_size`."""
    whole = dict(_model_kwargs(CONFIG["model"]), num_hidden_layers=28,
                 experts_first=0, experts_held=64, vocab_size=98304)
    for name in ("layer_types", "mlp_layer_types"):
        whole[name] = CONFIG[name]
    module = zoo.custom_model(**whole)
    shapes = jax.eval_shape(
        module.init, jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
    )["params"]
    assert 12.1e9 < _size(shapes) < 12.2e9
    assert len(shapes["model"]) == 28 + 2
    kinds = module.cfg.layer_types[:module.cfg.num_hidden_layers]
    assert (kinds.count(SLIDING), kinds.count(FULL)) == (21, 7)
    cut = zoo.custom_model(**dict(whole, num_hidden_layers=4)).cfg
    assert cut.num_hidden_layers == 4
    # a job's flat flags carry a list as a/b/c
    flat = zoo.custom_model(
        layer_types="sliding_attention/full_attention",
        mlp_layer_types="dense/sparse", intermediate_size=48,
    )
    assert flat.cfg.layer_types == (SLIDING, FULL)
    assert flat.cfg.num_hidden_layers == 2
    stack = _shapes(flat)["model"]
    assert stack["layers_0"]["mlp"] == {
        name: {"kernel": shape} for name, shape in (
            ("gate_proj", (64, 48)), ("up_proj", (64, 48)),
            ("down_proj", (48, 64)),
        )
    }
    assert "gate" in stack["layers_1"]["mlp"]
    for bad in (dict(layer_types="full_attention/window"),
                dict(mlp_layer_types="sparse"),          # one of four layers
                dict(num_attention_heads=7),
                dict(no_such_key=1)):
        with pytest.raises(ValueError):
            zoo.custom_model(**bad)


def test_dense_entry_runs_and_matches_the_reference():
    model = dict(TINY, mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
                 sample_tokens=64)
    module = zoo.custom_model(use_bf16=False, **_model_kwargs(model))
    tokens = ref.sample(5, 1, model)
    variables = dict(module.init(jax.random.PRNGKey(0), tokens))
    params = _perturbed(variables.pop("params"), 2)
    got = module.apply({"params": params, **variables}, tokens)
    assert _rel(got, ref.forward(params, tokens, model)) < 1e-5


# ---------------------------------------------------------------------------
# The model's own pieces, a case each: leave the piece out and the case fails
# ---------------------------------------------------------------------------


def _attention(kind, **changes):
    """-> (the attention sublayer of `kind`, its parameters, its inputs,
    its output for them), float32."""
    cfg = zoo.custom_model(
        use_bf16=False, **dict(_model_kwargs(TINY), **changes)
    ).cfg
    layer = zoo.Attention(cfg, kind == SLIDING)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, T, 64)), jnp.float32
    )
    tables = zoo.rotary_tables(cfg, T)[kind]
    params = _perturbed(
        layer.init(jax.random.PRNGKey(0), x, *tables)["params"], 4
    )
    return layer, params, (x, tables), layer.apply({"params": params}, x, *tables)


def _reference_attention(params, x, kind, *faults):
    return ref._attention(params, x[0], TINY, kind, frozenset(faults))


def _expert_layer(first, held):
    return SparseMoeBlock(
        8, 2, 32, 0, (first, held), True, jnp.float32, block_rows=16,
        score="softmax", expert_form="gated_silu",
    )


def _piece_head_norms():
    """Every query and key head is normed over its own 16 columns, by one
    weight vector for the queries and one for the keys, before rotary."""
    for kind in (SLIDING, FULL):
        _, params, (x, _), got = _attention(kind)
        assert _rel(got[0], _reference_attention(params, x, kind)) < 1e-5
        # the planted fault is caught, and it IS the program without them
        assert _rel(got[0], _reference_attention(
            params, x, kind, "no_qk_norm")) > 1e-2
        _, bare, (x, _), without = _attention(kind, qk_norm=False)
        assert "q_norm" not in bare and "k_norm" not in bare
        assert _rel(without[0], _reference_attention(
            bare, x, kind, "no_qk_norm")) < 1e-5


def _piece_window_edge():
    """A sliding layer's query at t reads the 32 keys t - 32 < s <= t:
    key t - 32 is unread, key t - 31 is read."""
    layer, params, (x, tables), got = _attention(SLIDING)
    assert _rel(got[0], _reference_attention(params, x, SLIDING)) < 1e-5
    assert _rel(got[0], _reference_attention(
        params, x, SLIDING, "no_window")) > 1e-2
    s = 100
    moved = layer.apply(
        {"params": params}, x.at[0, s].add(1.0), *tables
    )
    changed = np.abs(np.asarray(moved - got)[0]).max(axis=-1) > 1e-6
    assert changed[s:s + 32].all()       # t - 31 <= s: read
    assert not changed[s + 32:].any()    # t - 32 >= s: unread
    assert not changed[:s].any()         # causal
    # the full layer reads it at every later position
    layer, params, (x, tables), got = _attention(FULL)
    moved = layer.apply({"params": params}, x.at[0, s].add(1.0), *tables)
    assert (np.abs(np.asarray(moved - got)[0]).max(axis=-1) > 1e-6)[s:].all()


def _piece_yarn():
    """The full layer's table: YaRN's blended frequencies over the WHOLE
    head, cos and sin times the published `attention_factor`."""
    cfg = zoo.custom_model(**_model_kwargs(TINY)).cfg
    cos, sin = zoo.rotary_tables(cfg, T)[FULL]
    inv_freq, magnitude = ref.rotary_inv_freq(TINY, FULL)
    assert cos.shape == sin.shape == (T, 16) == (T, 2 * len(inv_freq))
    angles = np.arange(T)[:, None] * inv_freq[None, :]
    np.testing.assert_allclose(cos[:, :8], np.cos(angles) * magnitude, atol=2e-4)
    np.testing.assert_allclose(sin[:, 8:], np.sin(angles) * magnitude, atol=2e-4)
    published = CONFIG["rope_parameters"][FULL]["attention_factor"]
    assert published == 1.2772588722239782
    assert magnitude == pytest.approx(published, rel=1e-12)
    assert magnitude == pytest.approx(0.1 * np.log(16) + 1, rel=1e-12)
    plain = 500000.0 ** (-np.arange(8) / 8)
    assert not np.allclose(inv_freq, plain)      # the ramp is at work
    assert inv_freq[0] == plain[0]               # the fastest pair kept
    assert inv_freq[-1] == pytest.approx(plain[-1] / 16)
    _, params, (x, _), got = _attention(FULL)
    assert _rel(got[0], _reference_attention(params, x, FULL)) < 1e-5
    assert _rel(got[0], _reference_attention(params, x, FULL, "no_yarn")) > 1e-2


def _piece_plain_sliding_table():
    """A sliding layer's table is the plain one at the same theta, whole
    head, magnitude 1: `no_yarn` changes nothing there."""
    cfg = zoo.custom_model(**_model_kwargs(TINY)).cfg
    cos, sin = zoo.rotary_tables(cfg, T)[SLIDING]
    inv_freq, magnitude = ref.rotary_inv_freq(TINY, SLIDING)
    assert magnitude == 1.0
    np.testing.assert_allclose(inv_freq, 500000.0 ** (-np.arange(8) / 8))
    angles = np.arange(T)[:, None] * inv_freq[None, :]
    assert cos.shape == (T, 16)
    np.testing.assert_allclose(cos[:, 8:], np.cos(angles), atol=2e-4)
    np.testing.assert_allclose(sin[:, :8], np.sin(angles), atol=2e-4)
    full = zoo.rotary_tables(cfg, T)[FULL]
    assert not np.allclose(cos, full[0], atol=1e-2)
    _, params, (x, _), got = _attention(SLIDING)
    same = _reference_attention(params, x, SLIDING, "no_yarn")
    assert _rel(got[0], same) < 1e-5


def _piece_renormalised_top_k():
    """A token's weights are its two probabilities over THEIR sum, held
    or not: with experts 2..5 held, a token whose other choice lies
    elsewhere still gives its held one p / (p + p_elsewhere)."""
    layer = _expert_layer(2, 4)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(200, 64)), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(1), x)
    params = _perturbed(variables["params"], 3)
    got = np.asarray(layer.apply(
        {"params": params, ROUTING_COLLECTION: variables[ROUTING_COLLECTION]}, x
    ))
    model = dict(TINY, experts_first=2, experts_held=4)
    assert _rel(got, ref._experts(params, x, model)) < 1e-5
    # by hand, in numpy
    p = np.asarray(jax.nn.softmax(np.asarray(x) @ np.asarray(params["gate"])))
    ids = np.argsort(-p, axis=-1)[:, :2]
    top = np.take_along_axis(p, ids, axis=-1)
    over_all = top / top.sum(-1, keepdims=True)
    over_held = top / np.where((ids >= 2) & (ids < 6), top, 0).sum(
        -1, keepdims=True).clip(1e-30)

    def by_hand(weights):
        y = np.zeros_like(got)
        for e in range(2, 6):
            w = np.where(ids == e, weights, 0.0).sum(-1)
            gate = np.asarray(x) @ np.asarray(params["experts_gate_proj"][e - 2])
            up = np.asarray(x) @ np.asarray(params["experts_up_proj"][e - 2])
            y += w[:, None] * ((gate / (1 + np.exp(-gate)) * up)
                               @ np.asarray(params["experts_down_proj"][e - 2]))
        return y

    assert _rel(got, by_hand(over_all)) < 1e-5
    assert _rel(got, by_hand(over_held)) > 1e-2
    assert _rel(got, by_hand(top)) > 1e-2        # nor left unrenormalised
    mixed = ((ids >= 2) & (ids < 6)).sum(-1) == 1
    assert mixed.sum() > 20                      # the case is in the sample


PIECES = {
    "head_norms": _piece_head_norms,
    "window_edge": _piece_window_edge,
    "yarn_table_and_attention_factor": _piece_yarn,
    "plain_table_in_the_sliding_layers": _piece_plain_sliding_table,
    "renormalised_over_all_chosen": _piece_renormalised_top_k,
}


@pytest.mark.parametrize("piece", list(PIECES))
def test_the_models_own_pieces(piece):
    PIECES[piece]()


def test_token_with_no_choice_held_leaves_the_sublayer_as_it_entered():
    """With nothing beside the routed experts, a token none of whose
    choices is held here gets EXACTLY 0 from the expert sublayer, so the
    residual hands on what entered."""
    layer = _expert_layer(2, 2)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(300, 64)), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(2), x)
    params = variables["params"]
    y = np.asarray(layer.apply(
        {"params": params, ROUTING_COLLECTION: variables[ROUTING_COLLECTION]}, x
    ))
    _, ids = jax.lax.top_k(jax.nn.softmax(x @ params["gate"]), 2)
    none_held = ~np.asarray((ids >= 2) & (ids < 4)).any(axis=-1)
    assert 30 < none_held.sum() < 270
    assert (y[none_held] == 0).all()
    assert (np.abs(y[~none_held]).max(axis=-1) > 0).all()
    assert (np.asarray(x + y)[none_held] == np.asarray(x)[none_held]).all()
