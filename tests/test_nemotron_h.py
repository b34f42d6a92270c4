"""Nemotron-H on the normal training path (ISSUE 30): the zoo model, a
stack of Mamba-2, attention without a position embedding and the
sigmoid-scored expert layer with two-product relu^2 experts, against the
plain reference that decides the benchmark cell's `correct`
(`perfbench/configs/nemotron_h_reference.py`, which shares no code with the
program).  The contract's cases are `tests/lm_contract.py`'s, at
`tests/spec_nemotron_h.py`'s `SPEC` (the model as a job runs it:
`tests/test_nemotron_h_program.py`); the state-space form's and the expert
layer's own are `tests/test_ssd.py` and `tests/test_moe.py`.  Tiny sizes,
seeded random weights, float32 on the CPU, so tolerances are those of
float32 summation order: 1e-5 of the outputs' size for one operator and for
the whole model (nine layers, none of which amplifies a rounding);
gradients 2e-3 of each leaf's largest entry, as for the other hybrid model.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import gqa
from lm_contract import (  # noqa: F401  (the contract's cases, collected here)
    _log_lines, _model_kwargs, _rel, bf16_case, lm,
    mamba_mixer_in_its_kernels, program_and_reference, pytest_generate_tests,
    test_benchmark_cost_functions_count_what_they_say,
    test_bf16_program_is_the_reference_at_the_stated_precision,
    test_float32_products_ask_for_their_precision,
    test_full_size_configuration_counts_the_parameters_it_states,
    test_gradients_match_the_reference,
    test_logits_and_loss_match_the_reference,
    test_the_cell_checks_precisions_the_reference_has,
)
# `lm` hands the cases this SPEC
from spec_nemotron_h import SPEC, CONFIG, TINY, ref, zoo  # noqa: F401


def test_planted_fault_reads_far_from_the_program(program_and_reference):
    """`no_routed_scale` is the reference WITHOUT the routed experts' 2.5:
    the reading every run of the cell prints beside its tolerance."""
    program, _, params, tokens, model = program_and_reference
    fault = ref.forward(params, tokens, model, "no_routed_scale")
    limit = CONFIG["check"]["tolerance_rel_rms"]["highest"]
    reading = _rel(program(params), fault)
    assert reading > 5 * limit


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
    assert 0.1 < clear.mean() < 0.9
    np.testing.assert_array_equal(got[clear], highest[clear])
    np.testing.assert_array_equal(got[~clear], theirs[~clear])



def test_selection_bias_receives_the_load_violation(program_and_reference):
    """In place of a gradient the bias of every expert layer gets
    sign(times chosen - mean) over ALL experts, counted by the reference's
    own router at the same weights."""
    program, _, params, tokens, model = program_and_reference
    got = jax.grad(lambda p: zoo.loss(tokens, program(p)))(params)
    counts = ref.chosen_counts(params, tokens, model)
    assert counts.shape == (4, 8)
    assert (counts.sum(axis=1) == tokens.size * 2).all()
    expert_layers = [
        i for i, kind in enumerate(TINY["hybrid_override_pattern"])
        if kind == "E"
    ]
    for layer, count in zip(expert_layers, counts):
        gate = got["backbone"][f"layers_{layer}"]["mixer"]["gate"]
        np.testing.assert_array_equal(
            gate["e_score_correction_bias"], np.sign(count - count.mean())
        )
        assert np.abs(np.sign(count - count.mean())).sum() > 0


def test_parameter_names_and_layouts_follow_the_source():
    module = zoo.custom_model(use_bf16=False, **_model_kwargs(TINY))
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    assert set(shapes) == {"backbone", "lm_head"}
    backbone = shapes["backbone"]
    pattern = TINY["hybrid_override_pattern"]
    assert set(backbone) == {"embeddings", "norm_f"} | {
        f"layers_{i}" for i in range(len(pattern))
    }
    mixers = {
        "M": {"in_proj", "conv1d", "A_log", "D", "dt_bias", "norm", "out_proj"},
        "E": {"gate", "experts_up_proj", "experts_down_proj", "shared_experts"},
        "*": {"q_proj", "k_proj", "v_proj", "o_proj"},
    }
    for i, kind in enumerate(pattern):
        layer = backbone[f"layers_{i}"]
        assert set(layer) == {"norm", "mixer"}
        assert set(layer["mixer"]) == mixers[kind], i
    d = TINY["hidden_size"]
    h, p = TINY["mamba_num_heads"], TINY["mamba_head_dim"]
    bc = TINY["n_groups"] * TINY["ssm_state_size"]
    mamba = backbone["layers_0"]["mixer"]
    assert mamba["in_proj"]["kernel"].shape == (d, 2 * h * p + 2 * bc + h)
    assert mamba["conv1d"]["kernel"].shape == (4, h * p + 2 * bc)
    assert mamba["conv1d"]["bias"].shape == (h * p + 2 * bc,)
    assert mamba["norm"].shape == (h * p,)
    assert mamba["A_log"].shape == mamba["D"].shape == (h,)
    experts = backbone["layers_1"]["mixer"]
    assert experts["gate"]["weight"].shape == (d, TINY["n_routed_experts"])
    assert experts["gate"]["e_score_correction_bias"].shape == (
        TINY["n_routed_experts"],)
    assert experts["experts_up_proj"].shape == (
        TINY["experts_held"], d, TINY["moe_intermediate_size"])
    assert set(experts["shared_experts"]) == {"up_proj", "down_proj"}


def test_in_proj_is_one_parameter_read_as_four_products():
    """`SplitDense` holds and seeds `dense`'s parameter, and its results,
    side by side, are `dense`'s."""
    from model_zoo.lm_common import SplitDense, dense

    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 9, 24)),
                    jnp.float32)
    split = SplitDense((16, 16, 8, 4), jnp.float32, name="in_proj")
    whole = dense(44, jnp.float32, "in_proj")
    variables = split.init(jax.random.PRNGKey(2), x)
    assert jax.tree.map(jnp.shape, variables) == {
        "params": {"kernel": (24, 44)}
    }
    np.testing.assert_array_equal(
        variables["params"]["kernel"],
        whole.init(jax.random.PRNGKey(2), x)["params"]["kernel"],
    )
    parts = split.apply(variables, x)
    assert [p.shape[-1] for p in parts] == [16, 16, 8, 4]
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            jnp.concatenate(split.apply(variables, x), axis=-1),
            whole.apply(variables, x), rtol=1e-6, atol=1e-6,
        )


def test_initial_steps_and_decays_are_the_sources():
    """`dt_bias` is the inverse softplus of a step in [time_step_min,
    time_step_max], `A` in [-16, -1]: the decays a trained model has."""
    module = zoo.custom_model(use_bf16=False, **_model_kwargs(TINY))
    params = module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]["backbone"]["layers_0"]["mixer"]
    dt = jax.nn.softplus(params["dt_bias"])
    assert float(dt.min()) >= 1e-3 * 0.999 and float(dt.max()) <= 0.1 * 1.001
    a = jnp.exp(params["A_log"])
    assert float(a.min()) >= 1.0 and float(a.max()) <= 16.0


def test_mamba_sublayer_in_its_kernels_matches_the_reference(monkeypatch):
    """Two groups of 128 columns over rows of 512: widths the passes'
    kernels take (`ops/gdn_passes.py`), which the tiny ones are not."""
    m = dict(TINY, mamba_head_dim=64, ssm_state_size=64)
    mamba_mixer_in_its_kernels(monkeypatch, zoo.Mamba2Mixer(
        m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
        m["ssm_state_size"], m["conv_kernel"], m["chunk_size"],
        m["layer_norm_epsilon"], jnp.float32,
    ), ref._mamba2, m)


# ---------------------------------------------------------------------------
# Attention without a position embedding
# ---------------------------------------------------------------------------


def test_attention_sublayer_matches_the_reference_and_has_no_position():
    m = TINY
    layer = zoo.Attention(
        m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"],
        jnp.float32,
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 70, m["hidden_size"])), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(1), x)
    got = layer.apply(variables, x)[0]
    want = ref._attention(variables["params"], x[0], m, query_block=32)
    assert _rel(got, want) < 1e-5
    # No rotary: the last token's output does not change when the tokens
    # before it are permuted (softmax over a set of keys).
    order = np.concatenate([rng.permutation(69), [69]])
    shuffled = layer.apply(variables, x[:, order])[0]
    np.testing.assert_allclose(shuffled[-1], got[-1], atol=1e-5)


@pytest.mark.parametrize("t,d,engine", [
    (8192, 128, "pallas flash_attention"),   # the cell: exactly at the cap
    (8192, 256, "xla causal_gqa_attention"),
])
def test_engine_choice_at_the_cells_shape_on_a_tpu_backend(
    t, d, engine, monkeypatch
):
    """32 query heads on 2 key-value heads of 128 at T = 8192: K + V of a
    head are 8 MiB, the kernel's cap, so `impl="auto"` takes the Pallas
    kernel (traced only: shapes, no device)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((1, t, 32, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, t, 2, d), jnp.bfloat16)
    lines, handler = _log_lines(gqa.logger)
    try:
        out = jax.eval_shape(gqa.causal_attention, q, kv, kv)
    finally:
        gqa.logger.removeHandler(handler)
    assert out.shape == q.shape
    assert any(
        line.startswith(f"attention engine: {engine} T={t} D={d}")
        for line in lines
    ), lines


def test_optimizer_warms_up_and_balances_the_routers():
    """Step n of the warm-up runs AdamW at lr n / warmup_steps; the
    selection bias alone descends its load violation at the update rate,
    with no moment and no decay."""
    params = {
        "mixer": {"gate": {"weight": jnp.ones((3, 4)),
                           "e_score_correction_bias": jnp.zeros((4,))}},
        "lm_head": jnp.ones((3, 2)),
    }
    violation = jnp.asarray([1.0, -1.0, 0.0, 1.0])
    grads = jax.tree.map(jnp.ones_like, params)
    grads["mixer"]["gate"]["e_score_correction_bias"] = violation
    tx = zoo.optimizer(lr=1e-2, warmup_steps=4, bias_update_rate=1e-3)
    state = tx.init(params)
    steps = []
    for _ in range(6):
        updates, state = tx.update(grads, state, params)
        steps.append(updates)
    for n, updates in enumerate(steps, 1):
        rate = 1e-2 * min(1.0, n / 4)
        # AdamW's first-order step on a constant gradient is -rate, plus
        # the decay of a weight of 1
        np.testing.assert_allclose(
            updates["lm_head"], -rate * (1 + 0.01), rtol=1e-4
        )
        np.testing.assert_allclose(
            updates["mixer"]["gate"]["e_score_correction_bias"],
            -1e-3 * violation,
        )


def test_pattern_letters_are_checked():
    with pytest.raises(ValueError):
        zoo.custom_model(hybrid_override_pattern="MEX")
    with pytest.raises(ValueError):
        zoo.custom_model(no_such_key=1)
