"""Ling-3.0-flash's language model on the normal training path (ISSUE 53):
the zoo model with its delta-attention layers (a delta rule whose decay is
one rate a key channel under a bounded gate), its one latent-attention
layer with head norms and a head-wise gate, the leading dense layer and the
expert layers behind a sigmoid router that picks its groups first, each
against the plain reference that decides the benchmark cell's `correct`
(`perfbench/configs/ling_reference.py`, which shares no code with the
program).  The contract's cases are `tests/lm_contract.py`'s, at
`tests/spec_ling.py`'s `SPEC` (the model as a job runs it:
`tests/test_ling_program.py`).  Then what this model brought to shared
code: the chunked rule under a vector decay against the token-by-token
recurrence, the scalar rule and the ungrouped routers as the programs they
were (texts recorded at the parent commit), group-limited selection
against a definition by sorting, the passes' new forms, and the share of
the held experts against the uncut layer.  Tiny sizes, seeded random
weights, float32 on the CPU.
"""

import gzip
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers.moe import SparseMoeBlock
from elasticdl_tpu.ops import gated_delta, gdn_passes
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
from spec_ling import SPEC, CONFIG, TINY, ref, zoo  # noqa: F401

DATA = os.path.join(os.path.dirname(__file__), "data")
BOUND = -5.0


# ---------------------------------------------------------------------------
# The rule under a decay a key channel
# ---------------------------------------------------------------------------


def _rule_inputs(t, h=3, d=32, b=2, seed=0):
    rng = np.random.default_rng(seed)
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
        for _ in range(3)
    )
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / d ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(b, t, h)), jnp.float32))
    g = BOUND * jax.nn.sigmoid(
        jnp.asarray(rng.normal(size=(b, t, h, d)) * 2, jnp.float32)
    )
    return q, k, v, g, beta


def _both(fn, *inputs):
    """-> (outputs and final state, every input's gradient) of a rule."""
    v = inputs[2]

    def scalar(*xs):
        out, state = fn(*xs)
        return jnp.sum(out * v) + jnp.sum(state)

    return fn(*inputs), jax.grad(scalar, tuple(range(5)))(*inputs)


# 64, 128: whole chunks; 65: a chunk and one token; 200: three chunks and
# a part, sub-chunks' edges inside; 16, 17: a sub-chunk and one token;
# 1100: 18 chunks, so two steps of the scan (`GROUP_CHANNELS` 16) and the
# state carried from one to the next, the second step padded
@pytest.mark.parametrize("t", [16, 17, 64, 65, 128, 200, 1100])
def test_chunked_rule_under_a_vector_decay_is_the_recurrence(t):
    inputs = _rule_inputs(t)
    with jax.default_matmul_precision("highest"):
        want, want_grads = _both(gated_delta.gated_delta_rule_recurrent, *inputs)
        got, got_grads = _both(gated_delta.chunk_gated_delta_rule, *inputs)
    assert _rel(got[0], want[0]) < 1e-5 and _rel(got[1], want[1]) < 1e-5
    for name, g, w in zip("qkvgb", got_grads, want_grads):
        assert _rel(g, w) < 1e-4, name


@pytest.mark.parametrize("level", [BOUND, 0.0], ids=["at-the-bound", "at-0"])
def test_gates_at_the_bound_and_at_zero_overflow_in_neither_pass(level):
    """g = -5 in every channel for 200 tokens running (three chunks and a
    part: a sub-chunk's column factors reach e^75, a chunk's would reach
    e^315), and g = 0 (no decay at all): both passes finite, both the
    recurrence's."""
    q, k, v, _, beta = _rule_inputs(200)
    g = jnp.full(q.shape, level, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, want_grads = _both(
            gated_delta.gated_delta_rule_recurrent, q, k, v, g, beta
        )
        got, got_grads = _both(
            gated_delta.chunk_gated_delta_rule, q, k, v, g, beta
        )
    for x in (*got, *got_grads):
        assert bool(jnp.isfinite(x).all())
    assert _rel(got[0], want[0]) < 1e-5 and _rel(got[1], want[1]) < 1e-5
    for name, g_, w in zip("qkvgb", got_grads, want_grads):
        # at the bound a token's state is gone after one step: g's own
        # gradient is e^-5 of the others' size, and as exact in absolute
        assert _rel(g_, w) < (1e-3 if name == "g" else 1e-4), name


def test_a_chunk_of_alike_keys_is_the_recurrence_too():
    """Keys that are nearly one vector for chunks on end (a stream that
    one component dominates: what seeded layers hand the later ones) and
    a write strength near 1: M is near all ones under the diagonal, the
    powers the whole chunk's finite product goes through reach 1e17, and
    the inverse's entries of size 1 are lost in float32.  The vector
    path takes the inverse by blocks (`_unit_lower_inverse_by_blocks`);
    the scalar path's own reading is in PERF.md section 7."""
    rng = np.random.default_rng(0)
    q, _, v, g, _ = _rule_inputs(192, h=2)
    g = g * 0.02
    k = jnp.asarray(
        rng.normal(size=(1, 1, 2, 32))
        + 0.05 * rng.normal(size=(2, 192, 2, 32)), jnp.float32,
    )
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    assert float(jnp.einsum("bthd,bshd->bhts", k, k).min()) > 0.8
    beta = jnp.full(g.shape[:3], 0.95, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, want_grads = _both(
            gated_delta.gated_delta_rule_recurrent, q, k, v, g, beta
        )
        got, got_grads = _both(
            gated_delta.chunk_gated_delta_rule, q, k, v, g, beta
        )
    assert _rel(got[0], want[0]) < 1e-5 and _rel(got[1], want[1]) < 1e-5
    for name, g_, w in zip("qkvgb", got_grads, want_grads):
        assert _rel(g_, w) < 1e-3, name
    # the inverse itself, at the worst case: 0.9 under the whole diagonal
    m = jnp.tril(jnp.full((64, 64), 0.9, jnp.float32), -1)
    exact = np.linalg.inv(np.eye(64) + np.asarray(m, np.float64))
    with jax.default_matmul_precision("highest"):
        by_blocks = gated_delta._unit_lower_inverse_by_blocks(m)
        whole = gated_delta._unit_lower_inverse(m)
    assert np.abs(np.asarray(by_blocks) - exact).max() < 1e-5
    assert np.abs(np.asarray(whole) - exact).max() > 1e3


def test_a_vector_decay_equal_in_a_heads_channels_is_the_scalar_rule():
    q, k, v, g, beta = _rule_inputs(130)
    scalar = g[..., 0]
    with jax.default_matmul_precision("highest"):
        want = gated_delta.chunk_gated_delta_rule(q, k, v, scalar, beta)
        got = gated_delta.chunk_gated_delta_rule(
            q, k, v, jnp.broadcast_to(scalar[..., None], g.shape), beta
        )
    assert _rel(got[0], want[0]) < 1e-5 and _rel(got[1], want[1]) < 1e-5


def test_a_vector_decay_keeps_the_xla_engine_where_the_kernels_would_run():
    """`supports` holds at heads of 128, and a TPU would give a scalar
    decay its kernels; a decay [B, T, H, Dk] keeps the XLA engine (no
    kernel walks it yet) and says so."""
    from lm_contract import _log_lines

    lines, handler = _log_lines(gated_delta.logger)
    rows = jax.ShapeDtypeStruct((1, 128, 2 * 128), jnp.float32)
    g = jax.ShapeDtypeStruct((1, 128, 2, 128), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, 128, 2), jnp.float32)
    try:
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            traced = str(jax.make_jaxpr(
                lambda *xs: gated_delta.chunk_gated_delta_rule_rows(*xs, 2)
            )(rows, rows, rows, g, beta))
    finally:
        gated_delta.logger.removeHandler(handler)
    assert "pallas_call" not in traced
    assert any("xla" in line and "a decay a key channel" in line
               for line in lines), lines


# ---------------------------------------------------------------------------
# Shared code stays the program it was: texts recorded at the parent commit
# ---------------------------------------------------------------------------


def _scalar_rule_xla():
    q = jax.ShapeDtypeStruct((1, 200, 2, 32), jnp.float32)
    v = jax.ShapeDtypeStruct((1, 200, 4, 32), jnp.float32)
    g = jax.ShapeDtypeStruct((1, 200, 4), jnp.float32)
    return jax.jit(jax.value_and_grad(
        lambda q, k, v, g, beta: jnp.sum(
            gated_delta.chunk_gated_delta_rule_xla(q, k, v, g, beta)[0] ** 2
        ), (0, 1, 2, 3, 4),
    )).lower(q, q, v, g, g).as_text()


def _scalar_rule_pallas():
    q = jax.ShapeDtypeStruct((1, 1024, 2 * 128), jnp.float32)
    v = jax.ShapeDtypeStruct((1, 1024, 4 * 128), jnp.float32)
    g = jax.ShapeDtypeStruct((1, 1024, 4), jnp.float32)
    with mock.patch.object(
        gated_delta, "_engine",
        lambda supported, mesh, *why: ("pallas", "as on a tpu"),
    ):
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda q, k, v, g, beta: jnp.sum(
                gated_delta.chunk_gated_delta_rule_rows(
                    q, k, v, g, beta, 2
                )[0] ** 2
            ), (0, 1, 2, 3, 4),
        ))(q, q, v, g, g))


def _routing_program(**fields):
    block = SparseMoeBlock(16, 4, 32, 32, (4, 8), dtype=jnp.bfloat16, **fields)
    x = jax.ShapeDtypeStruct((2, 96, 64), jnp.float32)
    variables = jax.eval_shape(block.init, jax.random.PRNGKey(0), x)

    def loss(params, routing, x):
        y, _ = block.apply(
            {"params": params, "routing": routing}, x, mutable=["routing"],
        )
        return jnp.sum(y ** 2)

    return jax.jit(jax.value_and_grad(loss)).lower(
        variables["params"], variables["routing"], x
    ).as_text()


@pytest.mark.parametrize("golden,text", [
    # Qwen3-Next's rule: the XLA engine's program (its `while` loops), and
    # the kernel pair's whole traced form (its two `pallas_call`s)
    ("delta_rule_scalar_xla.hlo.gz", _scalar_rule_xla),
    ("delta_rule_scalar_pallas.jaxpr.gz", _scalar_rule_pallas),
    # DeepSeek-V2's router (softmax, scaled, the balancing loss) and
    # Nemotron-H's (sigmoid with its bias, relu2 experts), `n_group` 1
    ("moe_routing_softmax.hlo.gz", lambda: _routing_program(
        norm_topk_prob=False, routed_scale=16.0, shared_gated=False,
        balance_alpha=0.001)),
    ("moe_routing_sigmoid.hlo.gz", lambda: _routing_program(
        score="sigmoid", expert_form="relu2", routed_scale=2.5)),
], ids=["scalar-rule-xla", "scalar-rule-pallas", "router-softmax",
        "router-sigmoid"])
def test_shared_code_lowers_to_the_parents_program(golden, text):
    """A scalar decay and an ungrouped router reach the text they reached
    at the commit before ISSUE 53 (`tests/data/`, gzipped, written by
    these very functions there), forward and backward: no other cell's
    program moved, bit for bit."""
    with gzip.open(os.path.join(DATA, golden), "rt") as f:
        recorded = f.read()
    assert text() == recorded


# ---------------------------------------------------------------------------
# The passes' new forms
# ---------------------------------------------------------------------------


def test_gated_norm_takes_its_gates_form_from_its_shape_and_activation():
    rng = np.random.default_rng(0)
    rows, column = (
        jnp.asarray(rng.normal(size=(2, 24, 3 * 16)), jnp.float32)
        for _ in range(2)
    )
    head = jnp.asarray(rng.normal(size=(2, 24, 3)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    normed = rows.reshape(2, 24, 3, 16)
    normed = weight * normed / jnp.sqrt(
        jnp.mean(normed ** 2, -1, keepdims=True) + 1e-6
    )
    np.testing.assert_allclose(
        gdn_passes.gated_rms_norm(rows, head, weight, activation="sigmoid"),
        (normed * jax.nn.sigmoid(head)[..., None]).reshape(rows.shape),
        rtol=1e-6, atol=1e-6,
    )
    # a column's silu: the form it always had, and the default
    np.testing.assert_allclose(
        gdn_passes.gated_rms_norm(rows, column, weight),
        (normed * jax.nn.silu(column.reshape(normed.shape))).reshape(
            rows.shape),
        rtol=1e-6, atol=1e-6,
    )
    # the kernels compute a column's silu alone: any other form is the
    # plain chain whatever `pallas` says
    traced = str(jax.make_jaxpr(lambda *xs: gdn_passes.gated_rms_norm(
        *xs, activation="sigmoid", pallas=True, interpret=True
    ))(rows, head, weight))
    assert "pallas_call" not in traced


def test_decay_gate_is_bounded_below_whatever_its_input():
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.normal(size=(1, 50, 2 * 8)) * 30, jnp.float32)
    a_log = jnp.log(jnp.asarray([1.0, 16.0]))
    dt_bias = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    g = gdn_passes.decay_gate(rows, a_log, dt_bias, bound=BOUND)
    assert g.shape == rows.shape and g.dtype == jnp.float32
    assert float(g.min()) >= BOUND and float(g.max()) <= 0.0
    assert float(g.min()) < 0.99 * BOUND and float(g.max()) > -1e-3
    want = BOUND * 0.5 * (1.0 + np.tanh(0.5 * (
        np.repeat(np.exp(np.asarray(a_log)), 8)
        * (np.asarray(rows, np.float64) + np.asarray(dt_bias, np.float64))
    )))
    np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Group-limited selection
# ---------------------------------------------------------------------------


def _block(held, shared=32, **fields):
    return SparseMoeBlock(
        16, 2, 32, shared, held, True, jnp.float32, score="sigmoid",
        routed_scale=2.5, shared_gated=False, n_group=4, topk_group=2,
        block_rows=16, **fields,
    )


def _applied(block, params, x):
    return block.apply({"params": params}, x, mutable=["routing"])[0]


@pytest.mark.parametrize(
    "levels", [None, 5, 2], ids=["distinct", "ties", "two-levels"]
)
def test_group_limited_selection_is_the_definition_by_sorting(levels):
    """The block's selection (top-k over the scores it leaves finite)
    against `ling_reference.select`, which sorts: with distinct scores,
    and with scores on 5 or 2 levels, where groups and experts tie all
    over and the lower index wins in both."""
    rng = np.random.default_rng(1)
    selection = rng.uniform(size=(300, 16)).astype(np.float32)
    if levels:
        selection = np.round(selection * (levels - 1)) / (levels - 1)
    selection = jnp.asarray(selection)
    model = dict(num_experts_per_tok=2, n_group=4, topk_group=2)
    want, _ = ref.select(selection, model)
    _, got = jax.lax.top_k(_block((0, 16))._within_groups(selection), 2)
    np.testing.assert_array_equal(got, want)
    # every token's choices inside its two best groups, and the top k over
    # all would have left them for a share of the tokens
    assert int((np.asarray(got) // 4 != np.asarray(got)[:, :1] // 4).sum()) > 0
    free, _ = ref.select(selection, model, group_limited=False)
    assert (np.asarray(free) != np.asarray(want)).any()
    groups = jnp.sum(-jnp.sort(-selection.reshape(300, 4, 4), -1)[..., :2], -1)
    best = np.argsort(-np.asarray(groups), axis=-1, kind="stable")[:, :2]
    assert all(
        set(np.asarray(got[i]) // 4) <= set(best[i]) for i in range(300)
    )


def test_groups_that_do_not_fit_the_router_are_refused():
    x = jnp.zeros((1, 8, 64), jnp.float32)
    for fields in (dict(n_group=3), dict(n_group=4, topk_group=5),
                   dict(n_group=16, topk_group=4),
                   dict(n_group=8, topk_group=1, top_k=4)):
        block = SparseMoeBlock(**dict(
            dict(num_experts=16, top_k=2, expert_width=32, shared_width=32,
                 held=(0, 16), score="sigmoid"), **fields
        ))
        with pytest.raises(ValueError, match="no groups of 16 experts"):
            block.init(jax.random.PRNGKey(0), x)


def test_every_share_of_the_held_experts_adds_up_to_the_uncut_layer():
    """The routed block's result summed over ALL shares of `experts_held`
    (four chips of four experts, each share inside ONE of the router's
    four groups; the shared expert, which every chip computes alike,
    counted once) is the uncut reference's for the whole layer: what a
    chip leaves out is exactly what the other chips add."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 70, 64)), jnp.float32)
    whole = _block((0, 16))
    params = whole.init(jax.random.PRNGKey(3), x)["params"]
    params["gate"]["e_score_correction_bias"] = jnp.asarray(
        rng.normal(size=(16,)) * 0.1, jnp.float32
    )
    routed_only = {k: v for k, v in params.items() if k != "shared_experts"}
    model = dict(
        num_experts_per_tok=2, n_group=4, topk_group=2, norm_topk_prob=True,
        routed_scaling_factor=2.5, experts_first=0, experts_held=16,
    )
    with jax.default_matmul_precision("highest"):
        total = jnp.zeros_like(x)
        for first in range(0, 16, 4):
            share = {
                k: v[first:first + 4] if k.startswith("experts_") else v
                for k, v in routed_only.items()
            }
            total = total + _applied(_block((first, 4), shared=0), share, x)
        first_share = lambda tree: {  # noqa: E731
            k: v[:4] if k.startswith("experts_") else v
            for k, v in tree.items()
        }
        shared = _applied(_block((0, 4)), first_share(params), x) - _applied(
            _block((0, 4), shared=0), first_share(routed_only), x
        )
        want = jnp.stack([ref._experts(params, row, model) for row in x])
        uncut = _applied(whole, params, x)
    assert _rel(total + shared, want) < 1e-5
    assert _rel(uncut, want) < 1e-5
    # and one share alone is far from it: the cut is not a rounding
    assert _rel(_applied(_block((12, 4)), {
        k: v[12:] if k.startswith("experts_") else v
        for k, v in params.items()
    }, x), want) > 0.1


# ---------------------------------------------------------------------------
# The model's own
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "fault", ["scalar_decay", "no_group_limit", "no_routed_scale"]
)
def test_planted_faults_read_far_from_the_program(program_and_reference, fault):
    """`scalar_decay` is the reference with the gate averaged over a
    head's channels (the rule the repo had), `no_group_limit` with the top
    k taken over all experts at once, `no_routed_scale` with the routed
    weights left at sum 1: the readings every run of the cell prints
    beside its tolerances."""
    program, _, params, tokens, model = program_and_reference
    reading = _rel(program(params), ref.forward(params, tokens, model, fault))
    assert reading > 1000 * 1e-4


def test_clear_tokens_are_the_references_own_choice(
    program_and_reference, monkeypatch
):
    """`highest_clear` is `highest` where every expert layer's selection,
    of groups and of experts, is at least `CLEAR_MARGIN` from a tie IN THE
    REFERENCE, and the outputs `program` kept elsewhere."""
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
        np.min(np.stack(margins[r * 6:(r + 1) * 6]), axis=0)
        for r in range(len(tokens))
    ]) >= 0.02
    assert 0.02 < clear.mean() < 0.98
    np.testing.assert_array_equal(got[clear], highest[clear])
    np.testing.assert_array_equal(got[~clear], theirs[~clear])


def test_the_gates_the_program_counts_are_the_references(program_and_reference):
    """`kda.gates` of one step: the mean retention and the share at the
    bound that `ling_reference.gate_statistics` computes from the same
    weights and tokens."""
    from elasticdl_tpu.layers.delta_gates import GateLedger

    _, _, params, tokens, model = program_and_reference
    module = SPEC.build(model, use_bf16=False)
    variables = dict(module.init(jax.random.PRNGKey(0), tokens), params=params)
    _, state = module.apply(variables, tokens, mutable=["routing", "gates"])
    ledger = GateLedger()
    ledger.seed_once({})
    fields = ledger.task_delta(state, 1)
    retention, at_bound = ref.gate_statistics(params, tokens, model)
    assert fields["layers"] == 6
    assert fields["retention"] == pytest.approx(retention, rel=1e-4)
    assert fields["at_bound_share"] == pytest.approx(at_bound, abs=1e-4)
    assert 0.5 < retention < 1.0


def test_parameter_names_and_shapes_go_by_the_published_index():
    module = zoo.custom_model(use_bf16=False, **_model_kwargs(TINY))
    shapes = jax.tree.map(
        lambda a: a.shape,
        jax.eval_shape(module.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 64), jnp.int32))["params"],
    )
    assert set(shapes) == {"model", "lm_head"}
    stack = shapes["model"]
    # a STAGE: published layers 1..7
    assert set(stack) == {"embed_tokens", "norm"} | {
        f"layers_{i}" for i in range(1, 8)
    }
    d, h, hd = TINY["hidden_size"], 2, 16
    for i in range(1, 8):
        layer = stack[f"layers_{i}"]
        mixer = "self_attn" if i == 5 else "linear_attn"
        assert set(layer) == {"input_layernorm", mixer,
                              "post_attention_layernorm", "mlp"}
        if i == 5:
            attn = layer["self_attn"]
            assert attn["q_proj"]["kernel"] == (d, h * 24)
            assert attn["kv_a_proj_with_mqa"]["kernel"] == (d, 32 + 8)
            assert attn["kv_b_proj"]["kernel"] == (32, h * 32)
            assert attn["q_norm"]["weight"] == attn["k_norm"]["weight"] == (24,)
            assert attn["g_proj"] == (d, h)          # one value a head
        else:
            kda = layer["linear_attn"]
            assert set(kda) == {
                "q_proj", "k_proj", "v_proj", "f_proj", "b_proj", "o_proj",
                "q_conv1d", "k_conv1d", "v_conv1d", "A_log", "dt_bias",
                "g_proj", "o_norm",
            }
            assert kda["f_proj"]["kernel"] == (d, h * hd)  # full rank
            assert kda["A_log"] == (h,) and kda["dt_bias"] == (h * hd,)
            assert kda["g_proj"] == (d, h) and kda["o_norm"] == (hd,)
            assert kda["q_conv1d"] == (4, h * hd)
        if i == 1:  # i < first_k_dense_replace
            assert set(layer["mlp"]) == {"gate_proj", "up_proj", "down_proj"}
        else:
            assert set(layer["mlp"]["gate"]) == {
                "weight", "e_score_correction_bias"
            }
            assert layer["mlp"]["experts_gate_proj"] == (4, d, 32)


@pytest.mark.parametrize("config,match", [
    (dict(expert_swiglu_limit_list=[0, 0, 0, 4] + [0] * 38),
     "expert_swiglu_limit_list is not 0"),
    (dict(share_expert_swiglu_limit_list="0/0/5"),
     "share_expert_swiglu_limit_list is not 0"),
    (dict(image_patch_token=157157), "image_patch_token: the vision tower"),
    (dict(vision_config={}), "vision_config: the vision tower"),
    (dict(num_nextn_predict_layers=1),
     "num_nextn_predict_layers: the multi-token-prediction head"),
    (dict(mtp_use_kda=True), "mtp_use_kda: the multi-token-prediction head"),
    (dict(kda_safe_gate=False), "kda_safe_gate=False is not built"),
    (dict(no_kda_lora=False), "no_kda_lora=False is not built"),
    (dict(score_function="softmax"), "score_function='softmax' is not built"),
    (dict(q_lora_rank=1536), "q_lora_rank"),
    (dict(rotary_dim=4), "rotary_dim=4 is not built"),
    (dict(hidden_dim=64), r"no parameter\(s\) \['hidden_dim'\]"),
])
def test_what_is_not_built_is_refused_by_name(config, match):
    with pytest.raises(ValueError, match=match):
        zoo.custom_model(**dict(_model_kwargs(TINY), **config))


def test_published_limits_beyond_the_stage_and_an_unset_mtp_key_are_read():
    """The two lists stand as published (non-zero from layer 34 on): a
    stage of layers 1-7 reads its own entries alone; `mtp_use_kda: false`
    is the published value and builds nothing."""
    config = dict(_model_kwargs(TINY), mtp_use_kda=False)
    assert any(config["expert_swiglu_limit_list"][34:])
    assert zoo.custom_model(**config).cfg.first_layer == 1
    with pytest.raises(ValueError, match="is not 0 for a layer of"):
        zoo.custom_model(**dict(config, first_layer=30, num_hidden_layers=7))
