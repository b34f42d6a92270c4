"""Nemotron-H on the normal training path (ISSUE 30): the zoo model, the
chunked state-space-dual form of the Mamba-2 recurrence, attention without
a position embedding and the sigmoid-scored expert layer with two-product
relu^2 experts, each against the plain reference that decides the
benchmark cell's `correct` (`perfbench/configs/nemotron_h_reference.py`,
which shares no code with the program).  Tiny sizes, seeded random
weights, float32 on the CPU, so tolerances are those of float32 summation
order: 1e-5 of the outputs' size for one operator and for the whole model
(nine layers, none of which amplifies a rounding); gradients 2e-3 of each
leaf's largest entry, as for the other hybrid model.
"""

import importlib.util
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.layers.moe import (
    ROUTING_COLLECTION, RoutingLedger, SparseMoeBlock,
)
from elasticdl_tpu.ops import gqa, ssd
from elasticdl_tpu.ops.ssd import ssd_chunked, ssd_recurrent
from model_zoo.nemotron_h import nemotron_h_lm as zoo

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO_ROOT, "perfbench", "configs")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(CONFIGS, "nemotron_h_reference.py"), "nemotron_h_ref")

with open(os.path.join(CONFIGS, "nemotron-3-nano-30b-a3b.json")) as f:
    CONFIG = json.load(f)

# T = 150 in chunks of 32: four whole chunks and a padded one.
TINY = dict(CONFIG["rehearse"]["model"], sample_tokens=150)


def _model_kwargs(model):
    return {k: v for k, v in model.items() if k != "sample_tokens"}


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def _perturbed(tree, seed, scale=0.05):
    """Norm weights and `D` start at 1 and the selection bias at 0: move
    every leaf off its special value so that a dropped term would show."""
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
    program, reference, params, tokens, _ = program_and_reference
    got, want = program(params), reference(params)
    assert got.shape == want.shape == tokens.shape + (TINY["vocab_size"],)
    assert _rel(got, want) < 1e-5
    np.testing.assert_allclose(
        float(zoo.loss(tokens, got)), float(zoo.loss(tokens, want)),
        rtol=1e-5,
    )


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


def test_gradients_match_the_reference(program_and_reference):
    program, reference, params, tokens, _ = program_and_reference
    got = jax.grad(lambda p: zoo.loss(tokens, program(p)))(params)
    want = jax.grad(lambda p: zoo.loss(tokens, reference(p)))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(w).max())
        if "e_score_correction_bias" in name:
            # A selection is not differentiated: the reference has no
            # gradient for the bias.  The program hands the optimizer the
            # load's violation in its place (the next test).
            assert scale == 0
            continue
        assert scale > 0, name
        assert float(jnp.abs(g - w).max()) < 2e-3 * scale, name


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


def test_full_size_configuration_counts_the_parameters_it_states():
    """666.96M parameters at the published widths, cut as the file says;
    the file's top level is the catalog's config with the reduced keys,
    and `model` (what the job and the reference run) agrees."""
    model = CONFIG["model"]
    module = zoo.custom_model(**_model_kwargs(model))
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == 666_963_456
    assert f"{count:,}" in CONFIG["device_bytes"]
    assert count == ref._all_params(model) + sum(
        int(np.prod(leaf.shape))
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes)
        if leaf.ndim == 1 or "conv1d" in jax.tree_util.keystr(path)
    )
    assert CONFIG["reduced"] == [
        "hybrid_override_pattern", "num_hidden_layers", "n_routed_experts",
        "vocab_size",
    ]
    pattern = CONFIG["hybrid_override_pattern"]
    assert CONFIG["published"]["hybrid_override_pattern"].startswith(pattern)
    assert len(pattern) == CONFIG["num_hidden_layers"] == 9
    assert CONFIG["n_routed_experts"] == model["experts_held"] == 8
    assert (model["n_routed_experts"]
            == CONFIG["published"]["n_routed_experts"] == 128)
    # `model` keeps the router's width and the model's depth as published
    # (the init divides by sqrt of it); the top level counts what is held
    assert (model["num_hidden_layers"]
            == CONFIG["published"]["num_hidden_layers"] == 52)
    for key, value in model.items():
        if key in CONFIG and key not in ("n_routed_experts",
                                         "num_hidden_layers"):
            assert CONFIG[key] == value, key
    params = dict(
        item.split("=", 1) for item in
        CONFIG["job"][2].split("=", 1)[1].split(",")
    )
    for key, value in _model_kwargs(model).items():
        assert params[key] == (
            str(value).lower() if isinstance(value, bool) else str(value)
        ), key
    assert params["remat"] == "true"


# ---------------------------------------------------------------------------
# The chunked state-space-dual form
# ---------------------------------------------------------------------------


def _ssd_inputs(t, seed, b=2, h=4, p=8, g=2, n=16, dt_max=0.5):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, t, h, p))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(dt_max), size=(b, t, h)))
    a = -rng.uniform(1.0, 16.0, size=(h,))
    bm = rng.normal(size=(b, t, g, n))
    cm = rng.normal(size=(b, t, g, n))
    return [jnp.asarray(v, jnp.float32) for v in (x, dt, a, bm, cm)]


# One chunk; several chunks; two T that are no multiple of 128 (one of
# them shorter than a chunk); many chunks, the last one padded; and the
# family's other published shape (Granite 4.0-H): ONE group that every
# head reads, in chunks of 256, two whole and a padded one.  There dt goes
# up to 0.1, the largest step a model starts from, where the others go to
# 0.5: a chunk's running sum of dt A is twice as long at 256, and float32
# resolves a decay no finer than that sum (see the strong-decay test).
@pytest.mark.parametrize("t,g,chunk,dt_max", [
    (128, 2, 128, 0.5), (512, 2, 128, 0.5), (200, 2, 128, 0.5),
    (50, 2, 128, 0.5), (1100, 2, 128, 0.5), (600, 1, 256, 0.1),
])
def test_chunked_ssd_matches_the_recurrence(t, g, chunk, dt_max):
    inputs = _ssd_inputs(t, seed=t, g=g, dt_max=dt_max)
    want, want_state = ssd_recurrent(*inputs)
    got, got_state = ssd_chunked(*inputs, chunk=chunk)
    assert got.shape == want.shape and got.dtype == jnp.float32
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 1e-5 * max(scale, 1.0)
    assert float(jnp.abs(got_state - want_state).max()) < 1e-5 * max(
        float(jnp.abs(want_state).max()), 1.0)


@pytest.mark.parametrize("t,chunk,g,dt_max", [
    (128, 128, 2, 0.5), (384, 128, 2, 0.5), (200, 128, 2, 0.5),
    (150, 32, 2, 0.5), (600, 256, 1, 0.1),
])
def test_chunked_ssd_gradients_match_the_recurrence(t, chunk, g, dt_max):
    """All five gradients, through the outputs and the final state."""
    inputs = _ssd_inputs(t, seed=100 + t, g=g, dt_max=dt_max)
    rng = np.random.default_rng(t)
    weight = jnp.asarray(rng.normal(size=inputs[0].shape), jnp.float32)
    state_weight = jnp.asarray(rng.normal(size=(2, 4, 8, 16)), jnp.float32)

    def grads(rule):
        def total(*a):
            out, state = rule(*a)
            return jnp.sum(out * weight) + jnp.sum(state * state_weight)

        return jax.grad(total, argnums=range(5))(*inputs)

    want = grads(ssd_recurrent)
    got = grads(lambda *a: ssd_chunked(*a, chunk=chunk))
    for name, g, w in zip("x dt a b c".split(), got, want):
        assert float(jnp.abs(g - w).max()) < 5e-5 * float(jnp.abs(w).max()), name


def test_chunked_ssd_stays_finite_under_strong_decay():
    """dt A down to -80 a token: the decays are differences of running
    sums that never leave (-inf, 0], so nothing overflows and a fully
    decayed state reads 0, forward and backward.  The running sum reaches
    -10,000 inside a chunk here, where float32 resolves 1e-3, so a decay
    is right to 1e-3 of itself (1e-5 at the steps the model starts from:
    the source's kernels take the same differences in float32)."""
    x, dt, a, b, c = _ssd_inputs(256, seed=9)
    dt = dt * 10.0
    got, state = ssd_chunked(x, dt, a, b, c)
    want, _ = ssd_recurrent(x, dt, a, b, c)
    assert bool(jnp.isfinite(got).all()) and bool(jnp.isfinite(state).all())
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(jnp.abs(want).max())
    grads = jax.grad(
        lambda *v: jnp.sum(ssd_chunked(*v)[0]), argnums=range(5)
    )(x, dt, a, b, c)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


def test_reference_scan_is_the_written_recurrence():
    """The reference's own token-by-token scan against the program's
    recurrent form: two independent writings of the same equations."""
    x, dt, a, b, c = _ssd_inputs(96, seed=5, b=1)
    want, _ = ssd_recurrent(x, dt, a, b, c)
    got = ref._selective_scan(
        x[0], dt[0], a, jnp.repeat(b[0], 2, axis=1), jnp.repeat(c[0], 2, axis=1)
    )
    np.testing.assert_allclose(got, want[0], atol=1e-5)


def test_ssd_engine_line_names_the_trace(monkeypatch):
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    ssd.logger.addHandler(handler)
    try:
        shapes = [
            jax.ShapeDtypeStruct(s, jnp.float32) for s in (
                (1, 8192, 64, 64), (1, 8192, 64), (64,), (1, 8192, 8, 128),
                (1, 8192, 8, 128),
            )
        ]
        out, state = jax.eval_shape(
            lambda *a: ssd_chunked(*a, dtype=jnp.bfloat16), *shapes
        )
    finally:
        ssd.logger.removeHandler(handler)
    assert out.shape == (1, 8192, 64, 64) and state.shape == (1, 64, 64, 128)
    assert lines == [
        "ssd engine: xla ssd_chunked T=8192 H=64 P=64 N=128 "
        "(chunks of 128, products in bfloat16)"
    ]


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
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    gqa.logger.addHandler(handler)
    try:
        out = jax.eval_shape(gqa.causal_attention, q, kv, kv)
    finally:
        gqa.logger.removeHandler(handler)
    assert out.shape == q.shape
    assert any(
        line.startswith(f"attention engine: {engine} T={t} D={d}")
        for line in lines
    ), lines


# ---------------------------------------------------------------------------
# The expert layer: sigmoid scores, a selection bias, two-product experts
# ---------------------------------------------------------------------------

MOE = dict(n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=16,
           moe_shared_expert_intermediate_size=24, norm_topk_prob=True,
           routed_scaling_factor=2.5, hidden_size=32)


def _moe_layer(first, held, block_rows=128):
    return SparseMoeBlock(
        MOE["n_routed_experts"], MOE["num_experts_per_tok"],
        MOE["moe_intermediate_size"],
        MOE["moe_shared_expert_intermediate_size"], (first, held), True,
        jnp.float32, block_rows, score="sigmoid", expert_form="relu2",
        routed_scale=MOE["routed_scaling_factor"],
    )


def _moe_params(seed=0):
    layer = _moe_layer(0, 8)
    x = jnp.zeros((4, MOE["hidden_size"]), jnp.float32)
    return layer.init(jax.random.PRNGKey(seed), x)["params"]


def _share(params, first, held):
    """The parameters one chip of the layer holds."""
    cut = dict(params)
    for name in ("experts_up_proj", "experts_down_proj"):
        cut[name] = params[name][first:first + held]
    return cut


def _apply_moe(params, x, first, held, block_rows=128):
    layer = _moe_layer(first, held, block_rows)
    zeros = layer.init(jax.random.PRNGKey(0), x[:2])[ROUTING_COLLECTION]
    y, state = layer.apply(
        {"params": _share(params, first, held), ROUTING_COLLECTION: zeros},
        x, mutable=[ROUTING_COLLECTION],
    )
    return y, state[ROUTING_COLLECTION]


def test_sigmoid_layer_has_the_sources_parameters_and_no_third_product():
    params = _moe_params()
    assert set(params) == {
        "gate", "experts_up_proj", "experts_down_proj", "shared_experts",
    }
    assert set(params["gate"]) == {"weight", "e_score_correction_bias"}
    assert set(params["shared_experts"]) == {"up_proj", "down_proj"}
    with pytest.raises(ValueError):
        SparseMoeBlock(8, 2, 16, 16, (0, 8), score="sigmoid_relu2").init(
            jax.random.PRNGKey(0), jnp.zeros((4, 32))
        )


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_shares_add_up_to_the_uncut_layer(held):
    """What all the shares give, the shared expert counted once, is what
    the reference gives for the whole layer."""
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


def test_selection_bias_changes_the_choice_and_not_the_weights():
    """A bias of +10 on expert 5 puts it among every token's two; its
    weight there is still its own sigmoid score over the two scores' sum
    times 2.5, which the bias never enters."""
    params = jax.tree.map(lambda a: a, _moe_params(4))
    x = jnp.asarray(
        np.random.default_rng(5).normal(size=(64, MOE["hidden_size"])),
        jnp.float32,
    )
    scores = jax.nn.sigmoid(x @ params["gate"]["weight"])
    _, plain = jax.lax.top_k(scores, 2)
    assert not bool(jnp.all(jnp.any(plain == 5, axis=-1)))
    biased = dict(params, gate=dict(
        params["gate"],
        e_score_correction_bias=jnp.zeros((8,)).at[5].set(10.0),
    ))
    _, counters = _apply_moe(biased, x, 5, 1)
    assert int(counters["pairs"]) == 64        # every token chose expert 5
    # the other chosen expert is each token's best of the rest
    rest = jnp.argmax(scores.at[:, 5].set(-1.0), axis=-1)
    weight5 = 2.5 * scores[:, 5] / (
        scores[:, 5] + jnp.take_along_axis(scores, rest[:, None], 1)[:, 0]
    )
    up, down = params["experts_up_proj"][5], params["experts_down_proj"][5]
    want = weight5[:, None] * (jnp.square(jax.nn.relu(x @ up)) @ down)
    shared = ref._experts(biased, x, dict(MOE, experts_first=0, experts_held=0))
    got, _ = _apply_moe(biased, x, 5, 1)
    assert _rel(got - shared, want) < 1e-5
    # and the reference reads the biased layer the same way
    model = dict(MOE, experts_first=5, experts_held=1)
    assert _rel(got, ref._experts(_share(biased, 5, 1), x, model)) < 1e-5


@pytest.mark.parametrize("block_rows,tokens", [
    (128, 300), (16, 300), (512, 300), (512, 600), (None, 300),
])
def test_no_pair_dropped_and_counters_right_under_a_skewed_router(
    block_rows, tokens
):
    """Every token's first choice is ONE held expert (held range 2..5,
    expert 3): 300 pairs on one expert, more than two blocks of 128 and
    less than one of 512; 600, more than one of 512.  Told no block, the
    layer takes the shapes' (300 x 2 / 8 = 75 pairs an expert: 128)."""
    params = jax.tree.map(lambda a: a, _moe_params(1))
    rng = np.random.default_rng(2)
    x = jnp.asarray(
        np.abs(rng.normal(size=(tokens, MOE["hidden_size"]))) + 0.1,
        jnp.float32,
    )
    params["gate"] = dict(
        params["gate"], weight=params["gate"]["weight"].at[:, 3].set(4.0)
    )
    y, counters = _apply_moe(params, x, 2, 4, block_rows)
    model = dict(MOE, experts_first=2, experts_held=4)
    want = ref._experts(_share(params, 2, 4), x, model)
    assert _rel(y, want) < 1e-5
    _, ids = jax.lax.top_k(jax.nn.sigmoid(x @ params["gate"]["weight"]), 2)
    assert bool(jnp.all(jnp.any(ids == 3, axis=-1)))
    load = np.bincount(np.asarray(ids).ravel(), minlength=8)[2:6]
    assert load[1] == tokens
    np.testing.assert_array_equal(np.asarray(counters["load"]), load)
    assert int(counters["pairs"]) == int(counters["processed"]) == load.sum()
    block = block_rows or 128
    blocks = int(np.ceil(load / block).sum())
    assert int(counters["blocks"]) == blocks
    ledger = RoutingLedger()
    ledger.seed_once({})
    fields = ledger.task_delta(
        {ROUTING_COLLECTION: {"layers_1": {"mixer": counters}}}
    )
    assert fields == {
        "layers": 1, "held": 4, "pairs": int(load.sum()), "dropped": 0,
        "blocks": blocks, "block_rows": block,
        "load_max": tokens, "load_mean": float(load.mean()),
    }


@pytest.mark.parametrize("block_rows,tokens", [
    (32, 150), (16, 700), (128, 700), (512, 700),
])
def test_expert_layer_gradients_match_the_reference(block_rows, tokens):
    """The hand-written backward's two-product form (and the router's
    through the renormalised, scaled sigmoid scores), whatever the block:
    700 tokens and a router column that makes held expert 3 every
    token's choice give one expert more than a block of 512 and the
    others a part of one."""
    params = jax.tree.map(lambda a: a, _moe_params(3))
    x = jnp.asarray(
        np.random.default_rng(3).normal(size=(tokens, MOE["hidden_size"])),
        jnp.float32,
    )
    if tokens == 700:
        x = jnp.abs(x) + 0.1
        params["gate"] = dict(
            params["gate"], weight=params["gate"]["weight"].at[:, 3].set(0.5)
        )
    model = dict(MOE, experts_first=2, experts_held=4)
    share = _share(params, 2, 4)
    layer = _moe_layer(2, 4, block_rows)
    zeros = layer.init(jax.random.PRNGKey(0), x[:2])[ROUTING_COLLECTION]
    weight = jnp.asarray(
        np.random.default_rng(4).normal(size=x.shape), jnp.float32
    )

    def program(p, x):
        return jnp.sum(weight * layer.apply(
            {"params": p, ROUTING_COLLECTION: zeros}, x
        ))

    def reference(p, x):
        return jnp.sum(weight * ref._experts(p, x, model))

    got = jax.grad(program, (0, 1))(share, x)
    want = jax.grad(reference, (0, 1))(share, x)
    flat_want = jax.tree.leaves(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), flat_want):
        scale = float(jnp.abs(w).max())
        if scale == 0:  # the selection bias: no gradient, the violation
            chosen = []
            ref._experts(share, x, model, chosen=chosen)
            count = np.bincount(np.asarray(chosen[0]).reshape(-1), minlength=8)
            np.testing.assert_array_equal(g, np.sign(count - count.mean()))
            continue
        assert float(jnp.abs(g - w).max()) < 1e-4 * scale, (
            jax.tree_util.keystr(path)
        )


# ---------------------------------------------------------------------------
# The stated precision: bfloat16 operands in the blocks' products only
# ---------------------------------------------------------------------------

WIDE = dict(TINY, hidden_size=256, mamba_head_dim=32, ssm_state_size=32,
            head_dim=64, moe_intermediate_size=64,
            moe_shared_expert_intermediate_size=128)
BLOCKS = frozenset({"blocks"})


def _sublayer(kind):
    """(the program's sublayer in bfloat16, the reference's function)."""
    m, bf16 = WIDE, jnp.bfloat16
    if kind == "ssm":
        return zoo.Mamba2Mixer(
            m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
            m["ssm_state_size"], m["conv_kernel"], m["chunk_size"],
            m["layer_norm_epsilon"], bf16,
        ), ref._mamba2
    if kind == "attn":
        return zoo.Attention(
            m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"],
            bf16,
        ), ref._attention
    return SparseMoeBlock(
        m["n_routed_experts"], m["num_experts_per_tok"],
        m["moe_intermediate_size"], m["moe_shared_expert_intermediate_size"],
        (m["experts_first"], m["experts_held"]), True, bf16,
        score="sigmoid", expert_form="relu2",
        routed_scale=m["routed_scaling_factor"],
    ), ref._experts


@pytest.mark.parametrize("kind,limit", [
    ("ssm", 3e-3), ("attn", 1e-3), ("moe", 1e-4),
])
def test_bf16_program_is_the_reference_at_the_stated_precision(kind, limit):
    """With bfloat16 operands where the program has them, the reference
    is the program to the flips of a rounding, and closer than in
    float32.  The state-space layer's limit is the widest: the chunked
    form rounds a chunk's masked scores and the chunk states where the
    token-by-token reference rounds dt x, B and C; the same operands, in
    other products."""
    module, reference = _sublayer(kind)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, 300, WIDE["hidden_size"])),
        jnp.float32,
    )
    variables = module.init(jax.random.PRNGKey(1), x)
    got = module.apply(variables, x)[0]
    with jax.default_matmul_precision("highest"):
        stated = reference(variables["params"], x[0], WIDE, BLOCKS)
        highest = reference(variables["params"], x[0], WIDE)
    assert _rel(got, stated) < limit
    assert _rel(got, highest) > 2 * _rel(got, stated)


def test_the_cell_checks_precisions_the_reference_has():
    check = CONFIG["check"]
    assert set(check["tolerance_rel_rms"]) == {"highest", "highest_clear"}
    assert check["also_report"] == ["stated", "bfloat16", "no_routed_scale"]
    for name in list(check["tolerance_rel_rms"]) + check["also_report"]:
        assert name in ref.PRECISIONS
    assert "bfloat16" in ref.PRECISIONS  # the reading the limit refuses
    # the rehearsal's program is float32: only `highest` applies to it
    assert "also_report" not in CONFIG["rehearse"]["check"]
    with pytest.raises(ValueError):
        ref.forward({}, np.zeros((1, 4), np.int32), TINY, "float16")


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
    the routers' (`HIGHEST`), one an expert layer: the state-space form's
    four products take bfloat16 operands there.  In the float32 model
    those four ask for `HIGHEST` themselves: a product left to a TPU's
    default would round its float32 operands to bfloat16."""
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
    assert len(float32) == TINY["hybrid_override_pattern"].count("E")
    assert all(p == (highest, highest) for p in float32)
    assert len(found) > 30
    for dtype, count in ((jnp.float32, 4), (jnp.bfloat16, 0)):
        rule = _dot_precisions(
            jax.make_jaxpr(lambda *a: ssd_chunked(*a, dtype=dtype))(
                *_ssd_inputs(200, seed=0)
            ).jaxpr
        )
        assert len(rule) == 4
        assert sum(p == (highest, highest) for _, p in rule) == count
        assert all(d == dtype for d, _ in rule)


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
    # 64 pairs an expert a step: blocks of 128, at most one an expert
    assert fields["block_rows"] == 128
    assert 3 * 4 <= fields["blocks"] <= 3 * 4 * 4
    # the selection bias took three steps of the balancing rule, each
    # +-1e-3 (or 0 for an expert at the mean), and none of AdamW
    gate = trainer.state.params["backbone"]["layers_1"]["mixer"]["gate"]
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


def test_balancing_rule_brings_a_starved_expert_back():
    """A router whose weights keep expert 5 out of every token's choice:
    the rule alone, the weights frozen (lr 0), raises 5's bias a step at a
    time until it carries its share."""
    layer = _moe_layer(0, 8)
    params = _moe_params(4)
    # every input is positive, so a column of -0.02 scores about 0.38
    # for every token, under each token's two best of the other seven
    starved = params["gate"]["weight"].at[:, 5].set(-0.02)
    params = dict(params, gate=dict(params["gate"], weight=starved))
    x = jnp.abs(jnp.asarray(
        np.random.default_rng(6).normal(size=(256, MOE["hidden_size"])),
        jnp.float32,
    ))
    routing = layer.init(jax.random.PRNGKey(0), x[:2])[ROUTING_COLLECTION]
    tx = zoo.optimizer(lr=0.0, bias_update_rate=5e-3)
    state = tx.init(params)

    @jax.jit
    def step(params, state):
        def total(p):
            y, counters = layer.apply(
                {"params": p, ROUTING_COLLECTION: routing}, x,
                mutable=[ROUTING_COLLECTION],
            )
            return jnp.sum(y), counters[ROUTING_COLLECTION]["load"]
        (_, load), grads = jax.value_and_grad(total, has_aux=True)(params)
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), state, load

    loads = []
    for _ in range(120):
        params, state, load = step(params, state)
        loads.append(np.asarray(load))
    assert loads[0][5] == 0
    mean = 256 * 2 / 8
    assert abs(int(loads[-1][5]) - mean) < 0.25 * mean
    assert loads[-1].max() < 1.5 * mean
    assert float(params["gate"]["e_score_correction_bias"][5]) > 0
    np.testing.assert_array_equal(params["gate"]["weight"], starved)


def test_two_task_elasticdl_train_end_to_end(tmp_path):
    """`elasticdl train` as a user runs it: master, task dispatch, one
    collective worker, a cadence checkpoint, `moe.routing` a task."""
    from elasticdl_tpu.common.args import parse_master_args
    from elasticdl_tpu.common.constants import Mode
    from elasticdl_tpu.master.job_runner import run_allreduce_job

    model = dict(TINY, sample_tokens=64)
    params = ",".join(
        f"{k}={str(v).lower() if isinstance(v, bool) else v}"
        for k, v in _model_kwargs(model).items()
    )
    tb = tmp_path / "tb"
    args = parse_master_args([
        "--model_zoo=model_zoo",
        "--model_def=nemotron_h.nemotron_h_lm",
        f"--model_params={params},remat=true",
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
    assert [e["step"] for e in routing] == [2, 4]
    assert all(e["layers"] == 4 and e["held"] == 4 for e in routing)
    assert all(e["dropped"] == 0 and e["pairs"] > 0 for e in routing)
    assert all(e["load_max"] >= e["load_mean"] > 0 for e in routing)


def test_pattern_letters_are_checked():
    with pytest.raises(ValueError):
        zoo.custom_model(hybrid_override_pattern="MEX")
    with pytest.raises(ValueError):
        zoo.custom_model(no_such_key=1)


def test_benchmark_cost_functions_count_what_they_say():
    model = CONFIG["model"]
    cost = ref.step_cost(model, 1)
    # ~17.6 TFLOP a step of 8192 tokens without recomputation
    assert 17e12 < cost["flops"] < 18.5e12
    assert cost["bytes"] == 28 * ref._all_params(model)
    one = ref._ssd_forward(model, 1)
    # 64 chunks x (8 groups' C B^T + 64 heads' three products), 4 layers
    assert one["flops"] == 4 * 64 * (
        8 * 2 * 128 * 128 * 128 + 64 * (2 * 128 * 128 * 64 + 4 * 128 * 64 * 128)
    )
    scan = ref.ssm_scan_cost(model, 1)
    assert scan["flops"] == 4 * one["flops"] < 0.03 * cost["flops"]
    # memory bound on a v5e: bytes / 819e9 is above flops / 197e12
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12
    experts = ref.moe_experts_cost(model, pairs=4 * 384 * 8, steps=1)
    assert experts["flops"] == 6 * 2 * 2688 * 1856 * 4 * 384 * 8
