"""Laguna on the normal training path (ISSUE 36): the zoo model with its
window and full attention layers, a head count a layer type, two rotary
tables, the per-head output gate, the leading dense layer and the
sigmoid-routed gated-SiLU expert layers, each against the plain reference
that decides the benchmark cell's `correct`
(`perfbench/configs/laguna_reference.py`, which shares no code with the
program).  The contract's cases are `tests/lm_contract.py`'s, at
`tests/spec_laguna.py`'s `SPEC` (the model as a job runs it:
`tests/test_laguna_program.py`); the expert layer's own are
`tests/test_moe.py`.  Tiny sizes, seeded random weights, float32 on the
CPU, so tolerances are those of float32 summation order: 1e-5 of the
outputs' size, gradients 2e-3 of each leaf's largest entry as for the other
hybrid models.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lm_contract import (  # noqa: F401  (the contract's cases, collected here)
    _model_kwargs, _rel, _size, bf16_case, lm, program_and_reference,
    pytest_generate_tests,
    test_benchmark_cost_functions_count_what_they_say,
    test_bf16_program_is_the_reference_at_the_stated_precision,
    test_float32_products_ask_for_their_precision,
    test_full_size_configuration_counts_the_parameters_it_states,
    test_gradients_match_the_reference,
    test_logits_and_loss_match_the_reference,
    test_the_cell_checks_precisions_the_reference_has,
)
# `lm` hands the cases this SPEC
from spec_laguna import SPEC, CONFIG, TINY, ref, zoo  # noqa: F401


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
    assert 33.3e9 < _size(shapes) < 33.5e9
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
