"""Ouro on the normal training path (ISSUE 45): the zoo model whose stack
of plain decoder layers is applied four times over ONE set of weights,
with an exit (the head and a learned gate) behind every pass and a loss
over the exit distribution, against the plain reference that decides the
benchmark cell's `correct` (`perfbench/configs/ouro_reference.py`, which
shares no code with the program).  The contract's cases are
`tests/lm_contract.py`'s, at `tests/spec_ouro.py`'s `SPEC` (the model as
a job runs it: `tests/test_ouro_program.py`); what is compared is ONE
array, the joint log-probability of leaving at exit r with id v.  Tiny
sizes, seeded random weights, float32 on the CPU, so tolerances are those
of float32 summation order: 1e-5 of the outputs' size.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import loop_exits
from elasticdl_tpu.ops import gqa
from lm_contract import (  # noqa: F401  (the contract's cases, collected here)
    _perturbed, _rel, _size, bf16_case, lm, program_and_reference,
    pytest_generate_tests,
    test_benchmark_cost_functions_count_what_they_say,
    test_bf16_program_is_the_reference_at_the_stated_precision,
    test_float32_products_ask_for_their_precision,
    test_full_size_configuration_counts_the_parameters_it_states,
    test_gradients_match_the_reference,
    test_logits_and_loss_match_the_reference,
    test_the_cell_checks_precisions_the_reference_has,
)
from model_zoo import lm_common
# `lm` hands the cases this SPEC
from spec_ouro import SPEC, CONFIG, TINY, ref, zoo  # noqa: F401

FAR = 1000 * 1e-5  # a planted fault reads at least this far from the program


def _with(program_and_reference, **changes):
    """The program with `changes` to its configuration, at the same
    weights (less the leaves the changed stack does not have) and tokens
    -> its compared array."""
    _, _, params, tokens, model = program_and_reference
    module = SPEC.build(dict(model, **changes), use_bf16=False)
    have = jax.eval_shape(module.init, jax.random.PRNGKey(0), tokens)["params"]
    kept = jax.tree_util.tree_map_with_path(
        lambda path, _: _leaf(params, path), have
    )
    return SPEC.array(module.apply({"params": kept}, tokens))


def _leaf(tree, path):
    for key in path:
        tree = tree[key.key]
    return tree


def _piece_passes(whole):
    """The stack runs `total_ut_steps` times: the reference that applies
    it once and hands that state to all four exits is far off."""
    program, _, params, tokens, model = whole
    got = SPEC.array(program(params))
    assert _rel(got, ref.forward(params, tokens, model, "one_pass")) > FAR
    # and every exit's logits are its own pass's
    logits = np.asarray(program(params)["logits"])
    for r in range(1, 4):
        assert _rel(logits[:, r], logits[:, r - 1]) > FAR


def _piece_output_norms(whole):
    """A sublayer's OUTPUT is normed too: without the two `_2` norms the
    program is the reference's `no_post_norm`, far from the model."""
    program, _, params, tokens, model = whole
    want = ref.forward(params, tokens, model, "no_post_norm")
    assert _rel(SPEC.array(program(params)), want) > FAR
    assert _rel(_with(whole, sandwich_norm=False), want) < 1e-5


def _piece_loop_norm(whole):
    """The final norm closes every pass and feeds the next: with the raw
    stream going round the program is the reference's `norm_outside`."""
    program, _, params, tokens, model = whole
    want = ref.forward(params, tokens, model, "norm_outside")
    assert _rel(SPEC.array(program(params)), want) > FAR
    assert _rel(_with(whole, loop_norm=False), want) < 1e-5


def _piece_rotary_positions(whole, monkeypatch):
    """Every pass turns q and k by positions 0..T-1.  No OUTPUT can hold
    this piece: rotary scores depend on the difference of two positions,
    so the reference whose positions run on from T in the second pass
    (`positions_run_on`) reads the rounding of a float32 angle and no
    more.  What holds it is the program's table: ONE, of positions 0..T-1,
    read by all 4 x 3 layer applications."""
    program, _, params, tokens, model = whole
    built = []
    tables = gqa.rotary_tables
    monkeypatch.setattr(
        gqa, "rotary_tables",
        lambda positions, *a: built.append(np.asarray(positions))
        or tables(positions, *a),
    )
    got = SPEC.array(program(params))
    assert len(built) == 1
    np.testing.assert_array_equal(built[0], np.arange(tokens.shape[1]))
    run_on = _rel(got, ref.forward(params, tokens, model, "positions_run_on"))
    assert run_on < 1e-4, run_on


def _piece_exit_distribution(whole):
    """p_1 = lambda_1, p_r = lambda_r times what the earlier gates left,
    and the LAST exit takes what remains, so the four sum to 1."""
    program, _, params, tokens, model = whole
    logp = np.asarray(program(params)["exit_logp"], np.float64)
    assert logp.shape == tokens.shape[:1] + (4,) + tokens.shape[1:]
    np.testing.assert_allclose(np.exp(logp).sum(axis=1), 1.0, atol=1e-6)
    gate = np.random.default_rng(0).normal(size=(2, 4, 5))
    lam = 1.0 / (1.0 + np.exp(-gate))
    p = np.exp(np.asarray(loop_exits.exit_log_probs(jnp.asarray(gate))))
    np.testing.assert_allclose(p[:, 0], lam[:, 0], rtol=1e-5)
    np.testing.assert_allclose(
        p[:, 2], lam[:, 2] * (1 - lam[:, 0]) * (1 - lam[:, 1]), rtol=1e-5
    )
    np.testing.assert_allclose(  # the last gate's own value is not read
        p[:, 3], np.prod(1 - lam[:, :3], axis=1), rtol=1e-5
    )
    # perturbed weights: the distribution is no longer the zero gate's
    assert np.abs(np.exp(logp)[:, 0] - 0.5).max() > 0.01
    want = np.stack([
        np.asarray(ref.decoder(params, row, model)[1]) for row in tokens
    ])
    assert _rel(logp, want) < 1e-5


def _piece_entropy_sign(whole):
    """The loss REWARDS entropy: it is the expected cross-entropy LESS
    beta H(p), the reference's own, and a program with the sign turned
    reads 2 beta mean(H) higher."""
    program, _, params, tokens, model = whole
    predicted = program(params)
    reported = float(zoo.loss(tokens, predicted))
    np.testing.assert_allclose(
        reported, float(ref.loss_fn(params, tokens, tokens, model)), rtol=1e-5
    )
    entropy = float(jnp.mean(loop_exits.exit_entropy(predicted["exit_logp"])))
    assert 0.5 < entropy <= np.log(4)
    expected = float(zoo.eval_metrics_fn()["expected_cross_entropy"](
        predicted, tokens
    ))
    np.testing.assert_allclose(
        reported, expected - model["exit_beta"] * entropy, rtol=1e-5
    )
    turned = SPEC.build(dict(model, exit_beta=-model["exit_beta"]),
                        use_bf16=False).apply({"params": params}, tokens)
    np.testing.assert_allclose(
        float(zoo.loss(tokens, turned)) - reported,
        2 * model["exit_beta"] * entropy, rtol=1e-4,
    )


PIECES = {
    "passes": _piece_passes,
    "output_norms": _piece_output_norms,
    "loop_norm": _piece_loop_norm,
    "rotary_positions": _piece_rotary_positions,
    "exit_distribution": _piece_exit_distribution,
    "entropy_sign": _piece_entropy_sign,
}


@pytest.mark.parametrize("piece", list(PIECES))
def test_the_models_own_pieces(piece, program_and_reference, monkeypatch):
    """Each piece of what is new here, failing when it is left out; the
    matching planted fault of the reference is caught where an output can
    catch it."""
    check = PIECES[piece]
    if piece == "rotary_positions":
        check(program_and_reference, monkeypatch)
    else:
        check(program_and_reference)


def test_parameter_tree_does_not_grow_with_the_passes():
    """One set of layers whatever `total_ut_steps` is: the trees for 1
    and 4 passes are the same, by name and shape."""
    tokens = ref.sample(0, 1, TINY)
    shapes = [
        jax.tree.map(
            lambda leaf: leaf.shape,
            jax.eval_shape(
                SPEC.build(dict(TINY, total_ut_steps=passes)).init,
                jax.random.PRNGKey(0), tokens,
            )["params"],
        )
        for passes in (1, 4)
    ]
    assert shapes[0] == shapes[1]
    assert sorted(shapes[0]["model"]) == [
        "early_exit_gate", "embed_tokens", "layers_0", "layers_1", "layers_2",
        "norm",
    ]
    assert sorted(shapes[0]["model"]["layers_0"]) == [
        "input_layernorm", "input_layernorm_2", "mlp",
        "post_attention_layernorm", "post_attention_layernorm_2", "self_attn",
    ]
    assert shapes[0]["model"]["early_exit_gate"] == {
        "kernel": (64, 1), "bias": (1,),
    }
    with pytest.raises(ValueError):
        zoo.custom_model(num_experts=8)
    with pytest.raises(ValueError):
        zoo.custom_model(total_ut_steps=0)


def test_shared_leaf_gradient_is_the_sum_over_the_passes(
    program_and_reference
):
    """A layer's leaf has FOUR producers of its gradient: the program's is
    the sum of the four gradients of the reference given four unshared
    copies of the layers' weights."""
    program, _, params, tokens, model = program_and_reference
    got = jax.grad(lambda p: zoo.loss(tokens, program(p)))(params)
    layers = {k: v for k, v in params["model"].items()
              if k.startswith("layers_")}
    unshared = jax.grad(
        lambda copies: ref.loss_fn(params, tokens, tokens, model,
                                   per_pass=copies)
    )([layers] * 4)
    assert len(unshared) == 4
    flat = jax.tree_util.tree_leaves_with_path(
        {k: got["model"][k] for k in layers}
    )
    summed = jax.tree.leaves(jax.tree.map(lambda *g: sum(g), *unshared))
    assert len(flat) == 3 * 11
    for (path, g), w in zip(flat, summed):
        assert _rel(g, w) < 1e-5, jax.tree_util.keystr(path)
    # no one pass carries it: the first pass's share alone is far off
    first = jax.tree.leaves(unshared[0])
    assert all(_rel(g, w) > 0.05 for (_, g), w in zip(flat, first))


def test_one_pass_without_the_bonus_is_a_plain_decoder(program_and_reference):
    """`total_ut_steps=1`, `exit_beta=0`: one exit with p = 1, and the
    loss is `lm_common.loss` of its logits, the reference's of ONE pass:
    the same code."""
    _, _, params, tokens, model = program_and_reference
    plain = dict(model, total_ut_steps=1, exit_beta=0.0)
    predicted = SPEC.build(plain, use_bf16=False).apply(
        {"params": params}, tokens
    )
    assert predicted["logits"].shape[:2] == (tokens.shape[0], 1)
    np.testing.assert_array_equal(predicted["exit_logp"], 0.0)
    np.testing.assert_array_equal(predicted["exit_bonus"], 0.0)
    reported = float(zoo.loss(tokens, predicted))
    assert reported == float(lm_common.loss(tokens, predicted["logits"][:, 0]))
    np.testing.assert_allclose(
        reported, float(ref.loss_fn(params, tokens, tokens, plain)), rtol=1e-5
    )
    metrics = zoo.eval_metrics_fn()
    outputs = jax.tree.map(np.asarray, predicted)
    np.testing.assert_allclose(
        metrics["perplexity"](outputs, np.asarray(tokens)),
        lm_common.eval_metrics_fn()["perplexity"](
            outputs["logits"][:, 0], np.asarray(tokens)),
        rtol=1e-6,
    )
    assert metrics["accuracy"](outputs, np.asarray(tokens)) == (
        lm_common.eval_metrics_fn()["accuracy"](
            outputs["logits"][:, 0], np.asarray(tokens))
    )


def test_sliced_vocabulary_is_the_whole_vocabularys_columns():
    """This chip's slice of the table and the head gives the columns the
    whole vocabulary's reference gives for the same ids; the gate and the
    exit distribution do not see the cut."""
    whole = dict(TINY, vocab_size=256)
    module = SPEC.build(whole, use_bf16=False)
    tokens = ref.sample(5, 2, TINY)  # ids of the slice
    params = _perturbed(
        module.init(jax.random.PRNGKey(2), tokens)["params"], 3
    )
    cut = TINY["vocab_size"]
    sliced = jax.tree.map(lambda a: a, params)
    sliced["model"]["embed_tokens"] = params["model"]["embed_tokens"][:cut]
    sliced["lm_head"] = params["lm_head"][:, :cut]
    got = SPEC.build(TINY, use_bf16=False).apply({"params": sliced}, tokens)
    for row, mine in zip(tokens, got["logits"]):
        logits, logp = ref.decoder(params, row, whole)
        assert _rel(mine, logits[..., :cut]) < 1e-5
    assert _rel(got["exit_logp"], np.stack([
        np.asarray(ref.decoder(params, row, whole)[1]) for row in tokens
    ])) < 1e-5
