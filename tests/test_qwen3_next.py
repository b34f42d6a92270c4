"""Qwen3-Next on the normal training path (ISSUE 26): the zoo model, the
chunked gated delta rule, grouped-query attention with rotary, and the
expert layer that holds a range of the experts, each against the plain
reference that decides the benchmark cell's `correct`
(`perfbench/configs/qwen3_next_reference.py`, which shares no code with
the program).  Tiny sizes, seeded random weights, float32 on the CPU, so
tolerances are those of float32 summation order: 1e-5 of the outputs' size
for one operator, 5e-5 for the whole model (at head size 16 a DeltaNet
layer amplifies a rounding of its input about fivefold, three in a row);
gradients 2e-3 of each leaf's largest entry, through four layers and a
softmax.
"""

import importlib.util
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers.moe import (
    ROUTING_COLLECTION, RoutingLedger, SparseMoeBlock,
)
from elasticdl_tpu.ops import gated_delta, gdn_passes, gqa
from elasticdl_tpu.ops.gated_delta import (
    chunk_gated_delta_rule, chunk_gated_delta_rule_pallas,
    chunk_gated_delta_rule_xla, gated_delta_rule_recurrent,
)
from model_zoo.qwen3_next import qwen3_next_lm as zoo

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO_ROOT, "perfbench", "configs")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(CONFIGS, "qwen3_next_reference.py"), "qwen3_next_ref")

with open(os.path.join(CONFIGS, "qwen3-next-80b-a3b.json")) as f:
    CONFIG = json.load(f)

TINY = dict(CONFIG["rehearse"]["model"], sample_tokens=150)


def _model_kwargs(model):
    return {k: v for k, v in model.items() if k != "sample_tokens"}


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def _perturbed(tree, seed, scale=0.05):
    """Norm weights start at 0 or 1 and the embedding at 0.02: move every
    leaf off its special value so that a dropped `1 + w` would show."""
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

    return program, reference, params, tokens


def test_logits_and_loss_match_the_reference(program_and_reference):
    program, reference, params, tokens = program_and_reference
    got, want = program(params), reference(params)
    assert got.shape == want.shape == tokens.shape + (TINY["vocab_size"],)
    assert _rel(got, want) < 5e-5
    np.testing.assert_allclose(
        float(zoo.loss(tokens, got)), float(zoo.loss(tokens, want)),
        rtol=1e-5,
    )


def test_gradients_match_the_reference(program_and_reference):
    program, reference, params, tokens = program_and_reference
    got = jax.grad(lambda p: zoo.loss(tokens, program(p)))(params)
    want = jax.grad(lambda p: zoo.loss(tokens, reference(p)))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        scale = float(jnp.abs(w).max())
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.abs(g - w).max()) < 2e-3 * scale, (
            jax.tree_util.keystr(path)
        )


def test_parameter_names_and_layouts_follow_the_source():
    module = zoo.custom_model(use_bf16=False, **_model_kwargs(TINY))
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    assert set(shapes) == {
        "embed_tokens", "layers_0", "layers_1", "layers_2", "layers_3",
        "norm", "lm_head",
    }
    assert set(shapes["layers_0"]) == {
        "input_layernorm", "linear_attn", "post_attention_layernorm", "mlp",
    }
    assert set(shapes["layers_3"]) == {
        "input_layernorm", "self_attn", "post_attention_layernorm", "mlp",
    }
    hk, hv = TINY["linear_num_key_heads"], TINY["linear_num_value_heads"]
    dk, dv = TINY["linear_key_head_dim"], TINY["linear_value_head_dim"]
    linear = shapes["layers_0"]["linear_attn"]
    assert linear["in_proj_qkvz"]["kernel"].shape == (
        TINY["hidden_size"], 2 * hk * dk + 2 * hv * dv)
    assert linear["in_proj_ba"]["kernel"].shape == (
        TINY["hidden_size"], 2 * hv)
    assert linear["conv1d"].shape == (4, 2 * hk * dk + hv * dv)
    mlp = shapes["layers_0"]["mlp"]
    assert mlp["gate"].shape == (TINY["hidden_size"], TINY["num_experts"])
    assert mlp["experts_gate_proj"].shape == (
        TINY["experts_held"], TINY["hidden_size"],
        TINY["moe_intermediate_size"])


def test_full_size_configuration_counts_the_parameters_it_states():
    """424.3M parameters at the published widths, cut as the file says;
    the file's top level is the catalog's config with the three reduced
    keys, and `model` (what the job and the reference run) agrees."""
    model = CONFIG["model"]
    module = zoo.custom_model(**_model_kwargs(model))
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == 424_340_544
    assert count == ref._all_params(model) + sum(
        int(np.prod(leaf.shape))
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes)
        if leaf.ndim == 1 or "conv1d" in jax.tree_util.keystr(path)
    )
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert CONFIG["num_experts"] == model["experts_held"] == 16
    assert model["num_experts"] == CONFIG["published"]["num_experts"] == 512
    for key, value in model.items():
        if key in CONFIG and key != "num_experts":
            assert CONFIG[key] == value, key
    params = dict(
        item.split("=", 1) for item in
        CONFIG["job"][2].split("=", 1)[1].split(",")
    )
    for key, value in _model_kwargs(model).items():
        assert params[key] == (
            str(value).lower() if isinstance(value, bool) else str(value)
        ), key


# ---------------------------------------------------------------------------
# The stated precision: bfloat16 operands in the blocks' products only
# ---------------------------------------------------------------------------

WIDE = dict(TINY, hidden_size=256, head_dim=64, linear_key_head_dim=64,
            linear_value_head_dim=64, moe_intermediate_size=64,
            shared_expert_intermediate_size=64)
BLOCKS = frozenset({"blocks"})


def _sublayer(kind):
    """(the program's sublayer in bfloat16, the reference's function)."""
    m, bf16 = WIDE, jnp.bfloat16
    if kind == "gdn":
        return zoo.GatedDeltaNet(
            m["linear_num_key_heads"], m["linear_num_value_heads"],
            m["linear_key_head_dim"], m["linear_value_head_dim"],
            m["linear_conv_kernel_dim"], m["rms_norm_eps"], bf16,
        ), ref._gated_delta_net
    if kind == "attn":
        return zoo.GatedAttention(
            m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"],
            int(m["head_dim"] * m["partial_rotary_factor"]), m["rope_theta"],
            m["rms_norm_eps"], bf16,
        ), ref._gated_attention
    return SparseMoeBlock(
        m["num_experts"], m["num_experts_per_tok"],
        m["moe_intermediate_size"], m["shared_expert_intermediate_size"],
        (m["experts_first"], m["experts_held"]), True, bf16,
    ), ref._experts


@pytest.mark.parametrize("kind,limit,below", [
    ("gdn", 1e-4, "state"), ("attn", 1e-3, None), ("moe", 1e-4, "router"),
])
def test_bf16_program_is_the_reference_at_the_stated_precision(
    kind, limit, below
):
    """With bfloat16 operands where the program has them, the reference
    is the program to the flips of a rounding (a mismatch d before a
    rounding becomes ~sqrt(d 2^-8) after it: attention rounds four times
    in a row), ten times closer than in float32; and one more part in
    bfloat16 (`below`: the delta rule's state, the router) is at least
    ten times further off than that: what the benchmark's second
    tolerance tells apart."""
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
        lower = below and reference(
            variables["params"], x[0], WIDE, BLOCKS | {below}
        )
    assert _rel(got, stated) < limit
    assert _rel(got, highest) > 10 * _rel(got, stated)
    if below:
        assert _rel(got, lower) > 10 * _rel(got, stated)


def test_the_cell_checks_precisions_the_reference_has():
    """`highest` is the limit; `stated` is reported with every run (on
    the chip it reads 0.9% against controls of 1.0-1.6%: too close for a
    limit, see the configuration's `check.why`)."""
    check = CONFIG["check"]
    assert set(check["tolerance_rel_rms"]) == {"highest"}
    assert check["also_report"] == ["stated"]
    for name in list(check["tolerance_rel_rms"]) + check["also_report"]:
        assert name in ref.PRECISIONS
    # the rehearsal's program is float32: only `highest` applies to it
    assert "also_report" not in CONFIG["rehearse"]["check"]
    with pytest.raises(ValueError):
        ref.forward({}, np.zeros((1, 4), np.int32), TINY, "float16")


def _eqns(jaxpr, kernel=None):
    """Every equation of a jaxpr and of the jaxprs inside it -> (the
    equation, the name of the `pallas_call` that holds it or None)."""
    for eqn in jaxpr.eqns:
        yield eqn, kernel
        within = kernel
        if eqn.primitive.name == "pallas_call":
            within = eqn.params.get("name") or "pallas_call"
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, within)


def _dots(jaxpr):
    """Every `dot_general` of a jaxpr and of the jaxprs inside it ->
    (the equation, whether a `pallas_call` holds it)."""
    return (
        (eqn, kernel is not None) for eqn, kernel in _eqns(jaxpr)
        if eqn.primitive.name == "dot_general"
    )


def _dot_precisions(jaxpr):
    """-> [(operand dtype, precision)] of every product."""
    return [
        (eqn.invars[0].aval.dtype, eqn.params["precision"])
        for eqn, _ in _dots(jaxpr)
    ]


def test_float32_products_ask_for_their_precision():
    """What the logits cannot tell on the chip, the traced program can:
    in the bfloat16 model every product of float32 operands is either
    the delta rule's (all at `Precision.HIGH`, the state among their
    operands) or the router's (`HIGHEST`); a product left to a TPU's
    default would round its float32 operands to bfloat16."""
    high, highest = jax.lax.Precision.HIGH, jax.lax.Precision.HIGHEST
    module = zoo.custom_model(use_bf16=True, **_model_kwargs(TINY))
    tokens = ref.sample(0, 1, TINY)
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0), tokens)
    found = _dot_precisions(
        jax.make_jaxpr(lambda v, t: module.apply(v, t))(
            variables, tokens
        ).jaxpr
    )
    float32 = [p for dtype, p in found if dtype == jnp.float32]
    assert float32 and len(float32) < len(found)
    layers = TINY["num_hidden_layers"]
    assert sum(p == (highest, highest) for p in float32) == layers
    assert all(p in ((high, high), (highest, highest)) for p in float32)
    rule = _dot_precisions(
        jax.make_jaxpr(lambda *a: chunk_gated_delta_rule(*a))(
            *_delta_inputs(200, seed=0)
        ).jaxpr
    )
    assert len(rule) > 10
    assert all(p == (high, high) for _, p in rule)


def test_layer_moves_no_tensor_and_its_passes_are_float32(monkeypatch):
    """The DeltaNet sublayer as a TPU traces it at the cell's shapes
    (abstract), forward and backward: between the projections and the
    rule, and between the rule and the out-projection, no tensor of
    B T 2048 elements or more is reshaped, transposed, split,
    concatenated, padded or sliced (each a relayout or a copy of 270-800
    MB on a TPU); and inside the four kernels of the passes every
    floating-point value is float32, but for the bfloat16 the gated norm
    hands the out-projection and takes back as its cotangent."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    module = zoo.GatedDeltaNet(16, 32, 128, 128, 4, 1e-6, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((2, 8192, 2048), jnp.float32)
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda v, x: jnp.sum(module.apply(v, x).astype(jnp.float32)),
        argnums=(0, 1),
    ))(variables, x).jaxpr
    moves = ("reshape", "transpose", "concatenate", "split", "pad", "slice",
             "dynamic_slice", "gather", "squeeze", "expand_dims")
    kernels = {}
    for eqn, kernel in _eqns(jaxpr):
        avals = [v.aval for v in list(eqn.invars) + list(eqn.outvars)
                 if hasattr(v.aval, "shape")]
        if kernel is None:
            if eqn.primitive.name in moves:
                assert max(
                    int(np.prod(a.shape)) for a in avals
                ) < 2 * 8192 * 2048, eqn
            continue
        kernels[kernel] = kernels.get(kernel, 0) + 1
        if kernel.startswith(("conv_silu", "gated_norm")):
            for aval in avals:
                if not jnp.issubdtype(aval.dtype, jnp.floating):
                    continue
                assert aval.dtype == jnp.float32 or (
                    kernel.startswith("gated_norm")
                    and aval.dtype == jnp.bfloat16
                    and eqn.primitive.name in (
                        "get", "swap", "convert_element_type"
                    )
                ), (kernel, eqn)
    assert set(kernels) == {
        "conv_silu_fwd", "conv_silu_bwd", "gated_norm_fwd", "gated_norm_bwd",
        "delta_rule_fwd", "delta_rule_bwd",
    }


@pytest.mark.parametrize("passes", ["forward", "backward"])
def test_kernel_products_are_three_bfloat16_passes(passes):
    """The Pallas engine's own jaxprs (interpret mode on a CPU computes
    in float32 whatever is asked, so no number here can say it): Mosaic
    takes no `Precision.HIGH`, so every product in the kernels is HIGH
    written out: bfloat16 operands, float32 accumulation, and the three
    terms hi hi + hi lo + lo hi as one contraction of [hi | hi | lo]
    with [hi | lo | hi], three times the product's own length; no
    float32 operand reaches a product at any precision."""
    inputs = _delta_inputs(200, seed=0, hk=1, hv=2, dk=128, dv=128)

    def forward(*a):
        return chunk_gated_delta_rule_pallas(*a, interpret=False)[0]

    fn = forward if passes == "forward" else jax.grad(
        lambda *a: jnp.sum(forward(*a)), argnums=range(5)
    )
    dots = list(_dots(jax.make_jaxpr(fn)(*inputs).jaxpr))
    # 17 products a chunk forward; the backward kernel walks forward too
    assert len(dots) >= (17 if passes == "forward" else 60)
    for eqn, in_kernel in dots:
        assert in_kernel  # outside its kernels the engine multiplies nothing
        lhs, rhs = (var.aval for var in eqn.invars)
        assert lhs.dtype == rhs.dtype == jnp.bfloat16, (lhs, rhs)
        assert eqn.outvars[0].aval.dtype == jnp.float32
        assert eqn.params["precision"] is None
        (lhs_axes, rhs_axes), _ = eqn.params["dimension_numbers"]
        contracted = lhs.shape[lhs_axes[0]]
        assert contracted == rhs.shape[rhs_axes[0]]
        assert contracted in (3 * 64, 3 * 128, 3 * 256), contracted


# ---------------------------------------------------------------------------
# The chunked gated delta rule
# ---------------------------------------------------------------------------


def _delta_inputs(t, seed, b=2, hk=2, hv=4, dk=16, dv=8):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(b, t, hk, dk))
    k = rng.normal(size=(b, t, hk, dk))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(b, t, hv, dv))
    g = -0.3 * np.exp(rng.normal(size=(b, t, hv)))
    beta = 1.0 / (1.0 + np.exp(-rng.normal(size=(b, t, hv))))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


def _recurrent(q, k, v, g, beta):
    repeat = v.shape[2] // k.shape[2]
    return gated_delta_rule_recurrent(
        jnp.repeat(q, repeat, axis=2), jnp.repeat(k, repeat, axis=2),
        v, g, beta,
    )


# One chunk; several chunks in one group; a T that is no multiple of 64;
# several groups, the last one padded.
@pytest.mark.parametrize("t", [64, 256, 200, 1100])
def test_chunked_delta_rule_matches_the_recurrence(t):
    inputs = _delta_inputs(t, seed=t)
    want, want_state = _recurrent(*inputs)
    got, got_state = chunk_gated_delta_rule(*inputs)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 1e-5 * max(scale, 1.0)
    assert float(jnp.abs(got_state - want_state).max()) < 1e-5


@pytest.mark.parametrize("t", [64, 256, 200])
def test_chunked_delta_rule_gradients_match_the_recurrence(t):
    inputs = _delta_inputs(t, seed=100 + t)
    weight = jnp.asarray(
        np.random.default_rng(t).normal(size=inputs[2].shape), jnp.float32
    )
    want = jax.grad(
        lambda *a: jnp.sum(_recurrent(*a)[0] * weight), argnums=range(5)
    )(*inputs)
    got = jax.grad(
        lambda *a: jnp.sum(chunk_gated_delta_rule(*a)[0] * weight),
        argnums=range(5),
    )(*inputs)
    for name, g, w in zip("q k v g beta".split(), got, want):
        assert float(jnp.abs(g - w).max()) < 2e-5 * float(jnp.abs(w).max()), name


# The Pallas engine (interpret mode here) at head sizes it takes: one
# chunk, one group, a padded tail, several groups; one pair of value
# heads a key head, two pairs of one key head (their q and k gradients
# add up in the kernel), two key heads.  Its products are three bfloat16
# passes, float32 to about 1e-5 of a product where the XLA engine on a
# CPU is float32 itself.
_KERNEL_CASES = [
    (64, dict(b=1, hk=1, hv=2)),
    (128, dict(b=2, hk=1, hv=2)),
    (200, dict(b=2, hk=1, hv=2)),
    (1100, dict(b=1, hk=1, hv=2)),
    (200, dict(b=1, hk=1, hv=4)),
    (200, dict(b=1, hk=2, hv=4)),
]


def _kernel(*inputs):
    return chunk_gated_delta_rule_pallas(*inputs, interpret=True)


@pytest.mark.parametrize("t,shape", _KERNEL_CASES)
def test_delta_rule_kernel_matches_the_recurrence(t, shape):
    inputs = _delta_inputs(t, seed=t, dk=128, dv=128, **shape)
    want, want_state = _recurrent(*inputs)
    xla, xla_state = chunk_gated_delta_rule_xla(*inputs)
    got, got_state = jax.jit(_kernel)(*inputs)
    scale = max(float(jnp.abs(want).max()), 1.0)
    for other, other_state in ((want, want_state), (xla, xla_state)):
        assert float(jnp.abs(got - other).max()) < 1e-5 * scale
        assert float(jnp.abs(got_state - other_state).max()) < 2e-5


@pytest.mark.parametrize("t,shape", _KERNEL_CASES)
def test_delta_rule_kernel_gradients_match_the_recurrence(t, shape):
    """All five gradients, through the outputs and the final state."""
    inputs = _delta_inputs(t, seed=100 + t, dk=128, dv=128, **shape)
    rng = np.random.default_rng(t)
    weight = jnp.asarray(rng.normal(size=inputs[2].shape), jnp.float32)
    state_weight = jnp.asarray(
        rng.normal(size=(inputs[2].shape[0], inputs[2].shape[2], 128, 128)),
        jnp.float32,
    )

    def grads(rule):
        def total(*a):
            out, state = rule(*a)
            return jnp.sum(out * weight) + jnp.sum(state * state_weight)

        return jax.jit(jax.grad(total, argnums=range(5)))(*inputs)

    got = grads(_kernel)
    for other in (grads(_recurrent), grads(chunk_gated_delta_rule_xla)):
        for name, g, w in zip("q k v g beta".split(), got, other):
            assert (
                float(jnp.abs(g - w).max()) < 5e-5 * float(jnp.abs(w).max())
            ), name


def _cpu_mesh(data, model):
    from elasticdl_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    return jax.sharding.Mesh(
        np.asarray(jax.devices()[:data * model]).reshape(data, model),
        (DATA_AXIS, MODEL_AXIS),
    )


@pytest.mark.parametrize("backend,devices,mesh,hk,hv,dk,engine,why", [
    # the published shapes on one chip, the cell's case
    ("tpu", 1, None, 16, 32, 128, "pallas", "one device"),
    ("tpu", 4, (1, 1), 16, 32, 128, "pallas", "one device"),
    # a mesh of several chips: the kernels a data shard a device
    ("tpu", 4, (2, 2), 16, 32, 128, "pallas",
     "under shard_map over {'data': 2, 'model': 2}"),
    # several chips and no mesh named: the trace may be for all of them
    ("tpu", 4, None, 16, 32, 128, "xla", "4 devices and no mesh given"),
    # heads of 256: fewer of them a grid step, for VMEM
    ("tpu", 1, None, 16, 32, 256, "pallas", "one device"),
    # one key head's sixteen value heads of 256 do not fit in VMEM
    ("tpu", 1, None, 1, 16, 256, "xla",
     "head sizes or counts the kernels do not take"),
    # a head is no whole lane tile
    ("tpu", 1, None, 2, 4, 16, "xla",
     "head sizes or counts the kernels do not take"),
    # no two value heads a key head
    ("tpu", 1, None, 2, 2, 128, "xla",
     "head sizes or counts the kernels do not take"),
    # interpret mode is for tests
    ("cpu", 1, None, 16, 32, 128, "xla", "backend cpu"),
])
def test_delta_rule_engine_choice(backend, devices, mesh, hk, hv, dk, engine,
                                  why, monkeypatch):
    """On a TPU the kernels where `supports` holds and the trace is for
    one device or names its mesh, the XLA form for every other shape,
    for a trace that may be for several devices and off the TPU; the
    worker's log line says which and why (traced only: shapes, no
    device)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    mesh = mesh and _cpu_mesh(*mesh)
    t = 8192
    qk = jax.ShapeDtypeStruct((2, t, hk, dk), jnp.float32)
    v = jax.ShapeDtypeStruct((2, t, hv, dk), jnp.float32)
    gate = jax.ShapeDtypeStruct((2, t, hv), jnp.float32)
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    gated_delta.logger.addHandler(handler)
    try:
        jaxpr = jax.make_jaxpr(  # a new function: no cached trace
            lambda *a: chunk_gated_delta_rule(*a, mesh=mesh)
        )(qk, qk, v, gate, gate)
    finally:
        gated_delta.logger.removeHandler(handler)
    assert [aval.shape for aval in jaxpr.out_avals] == [
        v.shape, (2, hv, dk, dk)
    ]
    assert lines == [
        f"delta rule engine: {engine} chunk_gated_delta_rule "
        f"T={t} Dk={dk} Dv={dk} ({why})"
    ]
    assert ("pallas_call" in str(jaxpr)) == (engine == "pallas")
    assert ("shard_map" in str(jaxpr)) == why.startswith("under shard_map")


@pytest.mark.parametrize("b,mesh", [(2, (2, 2)), (4, (4, 1)), (1, (2, 1))])
def test_delta_rule_kernel_under_a_mesh_is_the_kernel(b, mesh):
    """Under a mesh of several devices each pass runs inside a shard_map
    over the data axis (a sequence or two a device; all of them on every
    device where the axis does not divide the batch): outputs, final
    state and all five gradients are the unmapped kernels' own."""
    inputs = _delta_inputs(200, seed=7 + b, dk=128, dv=128, b=b, hk=1, hv=2)
    rng = np.random.default_rng(b)
    weight = jnp.asarray(rng.normal(size=inputs[2].shape), jnp.float32)

    def run(mesh):
        def total(*a):
            out, state = chunk_gated_delta_rule_pallas(
                *a, interpret=True, mesh=mesh
            )
            return jnp.sum(out * weight) + jnp.sum(state), (out, state)

        return jax.jit(
            jax.value_and_grad(total, argnums=range(5), has_aux=True)
        )(*inputs)

    (_, want), want_grads = run(None)
    (_, got), got_grads = run(_cpu_mesh(*mesh))
    for g, w in zip(got + got_grads, want + want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# What surrounds the rule: one pass each way over head-major rows
# ---------------------------------------------------------------------------

# T = 64 and 200 are one block of as many rows; with blocks of 256 rows
# (the cell's 8192 are four of 2048) 1100 is four blocks and a ragged
# fifth, each edge inside the convolution's reach.  One and two value
# heads a key head, two key heads.
_PASS_CASES = [
    (t, hk, hv) for t in (64, 200, 1100)
    for hk, hv in ((1, 1), (1, 2), (2, 4))
]


@pytest.fixture
def blocks_of_256_rows(monkeypatch):
    monkeypatch.setattr(gdn_passes, "ROWS", 256)


def _close(got, want, limit, what):
    assert _rel(got, want) < limit, what


@pytest.mark.parametrize("t,hk,hv", _PASS_CASES)
@pytest.mark.parametrize("bias", [False, True])
def test_conv_silu_kernels_match_the_plain_chain(t, hk, hv, bias,
                                                 blocks_of_256_rows):
    """q's pass (the l2-norm by head, scaled), v's (none) and, with a
    bias, a state-space layer's: outputs and every gradient (rows, taps,
    bias) against the `jax.numpy` chain, which pads and shifts."""
    rng = np.random.default_rng(t + hk + hv)
    for width, head, scale in ((hk * 128, 128, 128 ** -0.5),
                               (hv * 128, 0, 1.0)):
        rows, weight = (
            jnp.asarray(rng.normal(size=(2, t, width)), jnp.float32)
            for _ in range(2)
        )
        taps = jnp.asarray(rng.normal(size=(4, width)), jnp.float32)
        offset = bias and jnp.asarray(rng.normal(size=(width,)), jnp.float32)

        def run(pallas):
            def total(rows, taps, offset):
                out = gdn_passes.conv_silu(
                    rows, taps, offset if bias else None, head=head,
                    scale=scale, pallas=pallas, interpret=True,
                )
                return jnp.sum(out * weight), out

            return jax.jit(jax.value_and_grad(
                total, argnums=(0, 1, 2) if bias else (0, 1), has_aux=True
            ))(rows, taps, offset)

        ((_, got), got_grads), ((_, want), want_grads) = run(True), run(False)
        _close(got, want, 1e-6, (width, "out"))
        for g, w, name in zip(got_grads, want_grads, ("rows", "taps", "bias")):
            assert g.shape == w.shape
            _close(g, w, 2e-6, (width, name))


@pytest.mark.parametrize("t,hk,hv", _PASS_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gated_norm_kernels_match_the_plain_chain(t, hk, hv, dtype,
                                                  blocks_of_256_rows):
    """Outputs (in the out-projection's operand type) and the gradients
    of o, z and the norm's weight."""
    rng = np.random.default_rng(t + hv)
    out, gate, weight = (
        jnp.asarray(rng.normal(size=(2, t, hv * 128)), jnp.float32)
        for _ in range(3)
    )
    norm_weight = jnp.asarray(rng.normal(size=(128,)), jnp.float32)

    def run(pallas):
        def total(out, gate, norm_weight):
            y = gdn_passes.gated_rms_norm(
                out, gate, norm_weight, eps=1e-6, dtype=dtype,
                pallas=pallas, interpret=True,
            )
            return jnp.sum(y.astype(jnp.float32) * weight), y

        return jax.jit(jax.value_and_grad(
            total, argnums=(0, 1, 2), has_aux=True
        ))(out, gate, norm_weight)

    ((_, got), got_grads), ((_, want), want_grads) = run(True), run(False)
    assert got.dtype == want.dtype == dtype
    # a bfloat16 result may round the last float32 bit the other way
    _close(got.astype(jnp.float32), want.astype(jnp.float32),
           1e-6 if dtype == jnp.float32 else 1e-3, "out")
    for g, w, name in zip(got_grads, want_grads, ("o", "z", "weight")):
        assert g.shape == w.shape
        _close(g, w, 2e-6, name)


def _parent_gated_delta_net(params, x, hk, hv, dk, dv, eps):
    """`GatedDeltaNet.__call__` as it was before the layer kept one
    layout (float32): the projection's result viewed by key head, split,
    concatenated, padded and shifted, [B, T, H, D] into the rule."""
    b, t, _ = x.shape
    r = hv // hk
    qkvz = (x @ params["in_proj_qkvz"]["kernel"]).reshape(
        b, t, hk, 2 * dk + 2 * r * dv
    )
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    ba = (x @ params["in_proj_ba"]["kernel"]).reshape(b, t, hk, 2 * r)
    beta_in, a = ba[..., :r].reshape(b, t, hv), ba[..., r:].reshape(b, t, hv)
    mixed = jnp.concatenate(
        [q.reshape(b, t, hk * dk), k.reshape(b, t, hk * dk),
         v.reshape(b, t, hv * dv)], axis=-1,
    )
    conv = params["conv1d"]
    padded = jnp.pad(mixed, ((0, 0), (conv.shape[0] - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(
        padded[:, j:j + t] * conv[j] for j in range(conv.shape[0])
    ))
    q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)

    def l2norm(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    g = -jnp.exp(params["A_log"]) * jax.nn.softplus(a + params["dt_bias"])
    out, _ = chunk_gated_delta_rule_xla(
        l2norm(q.reshape(b, t, hk, dk)) / np.sqrt(dk),
        l2norm(k.reshape(b, t, hk, dk)), v.reshape(b, t, hv, dv),
        g, jax.nn.sigmoid(beta_in),
    )
    out = out * jax.lax.rsqrt(
        jnp.mean(out * out, axis=-1, keepdims=True) + eps
    )
    out = params["norm"] * out * jax.nn.silu(z.reshape(b, t, hv, dv))
    return out.reshape(b, t, hv * dv) @ params["out_proj"]["kernel"]


def _engines_as_on_a_tpu(monkeypatch):
    """The engines a TPU would be given, in interpret mode: the choice
    by shapes alone."""
    monkeypatch.setattr(
        gated_delta, "_engine",
        lambda supported, mesh, *why: (
            "pallas" if supported else "xla", "as on a tpu"
        ),
    )
    monkeypatch.setattr(gated_delta, "_use_interpret", lambda: True)
    monkeypatch.setattr(gdn_passes, "_use_interpret", lambda: True)


@pytest.mark.parametrize("t,hk,hv", [(200, 1, 2), (320, 2, 4), (200, 1, 1)])
def test_layer_in_one_layout_is_the_layer_it_was(t, hk, hv, monkeypatch):
    """The whole DeltaNet sublayer on the path a TPU takes (the passes
    and, where it takes the heads, the rule in their kernels) against
    the body it had, from the same parameters in the source's column
    order, at float32: forward to 1e-5, every gradient to 1e-4 of its
    rms."""
    _engines_as_on_a_tpu(monkeypatch)
    module = zoo.GatedDeltaNet(hk, hv, 128, 128, 4, 1e-6, jnp.float32)
    rng = np.random.default_rng(t)
    x = jnp.asarray(rng.normal(size=(2, t, 64)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(2, t, 64)), jnp.float32)
    params = _perturbed(module.init(jax.random.PRNGKey(0), x)["params"], 3)

    def new(p, x):
        return module.apply({"params": p}, x)

    def old(p, x):
        return _parent_gated_delta_net(p, x, hk, hv, 128, 128, 1e-6)

    with jax.default_matmul_precision("highest"):
        _close(new(params, x), old(params, x), 1e-5, "forward")
        got, want = (
            jax.grad(lambda p, x: jnp.sum(f(p, x) * weight), argnums=(0, 1))(
                params, x
            )
            for f in (new, old)
        )
    flat = jax.tree_util.tree_leaves_with_path(got)
    assert len(flat) == 8  # seven parameters' gradients and x's
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        _close(g, w, 1e-4, jax.tree_util.keystr(path))


@pytest.mark.parametrize("backend,devices,mesh,t,dk,taps,engine,why", [
    # the published shapes on one chip, the cell's case
    ("tpu", 1, None, 8192, 128, 4, "pallas", "one device"),
    ("tpu", 4, (1, 1), 8192, 128, 4, "pallas", "one device"),
    ("tpu", 4, (2, 2), 8192, 128, 4, "pallas",
     "under shard_map over {'data': 2, 'model': 2}"),
    ("tpu", 4, None, 8192, 128, 4, "xla", "4 devices and no mesh given"),
    ("tpu", 1, None, 8192, 256, 4, "pallas", "one device"),
    # a head is no whole lane tile; rows that are no whole tiles; taps
    # that reach past the tile before a block
    ("tpu", 1, None, 8192, 16, 4, "xla",
     "head sizes, a length or taps the kernels do not take"),
    ("tpu", 1, None, 150, 128, 4, "xla",
     "head sizes, a length or taps the kernels do not take"),
    ("tpu", 1, None, 8192, 128, 10, "xla",
     "head sizes, a length or taps the kernels do not take"),
    # a block of one head of 1024 is 8 MiB, a dozen of them past VMEM
    ("tpu", 1, None, 8192, 1024, 4, "xla",
     "head sizes, a length or taps the kernels do not take"),
    ("cpu", 1, None, 8192, 128, 4, "xla", "backend cpu"),
])
def test_gdn_passes_engine_choice(backend, devices, mesh, t, dk, taps, engine,
                                  why, monkeypatch):
    """The passes' engine by the rule's own rule (backend, what the
    trace is for) and their `supports`; the layer's trace logs it beside
    the rule's line and holds the kernels, mapped where a mesh of
    several devices is named (traced only: shapes, no device)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    mesh = mesh and _cpu_mesh(*mesh)
    module = zoo.GatedDeltaNet(2, 4, dk, dk, taps, 1e-6, jnp.bfloat16, mesh)
    x = jax.ShapeDtypeStruct((2, t, 64), jnp.float32)
    variables = jax.eval_shape(
        zoo.GatedDeltaNet(2, 4, dk, dk, taps, 1e-6, jnp.float32).init,
        jax.random.PRNGKey(0), x,
    )
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    gated_delta.logger.addHandler(handler)
    try:
        jaxpr = str(jax.make_jaxpr(
            jax.grad(lambda v, x: jnp.sum(module.apply(v, x)))
        )(variables, x))
    finally:
        gated_delta.logger.removeHandler(handler)
    assert lines[0] == (
        f"gdn passes engine: {engine} T={t} Hk=2 Hv=4 D={dk} ({why})"
    )
    assert lines[1].startswith("delta rule engine: ")
    kernels = ("conv_silu_fwd", "conv_silu_bwd", "gated_norm_fwd",
               "gated_norm_bwd")
    for name in kernels:
        assert (name in jaxpr) == (engine == "pallas"), name
    if why.startswith("under shard_map"):
        # the three conv passes, the rule, the norm, each forward and
        # backward, each mapped on its own
        assert jaxpr.count("shard_map") >= 10


@pytest.mark.parametrize("b,mesh", [(2, (2, 2)), (1, (2, 1))])
def test_gdn_passes_under_a_mesh_are_the_kernels(b, mesh):
    """Under a mesh of several devices each pass runs inside a shard_map
    over the data axis, the taps and the norm's weight whole on every
    device: outputs and every gradient are the unmapped kernels' own."""
    rng = np.random.default_rng(b)
    rows, gate, weight = (
        jnp.asarray(rng.normal(size=(b, 200, 256)), jnp.float32)
        for _ in range(3)
    )
    taps = jnp.asarray(rng.normal(size=(4, 256)), jnp.float32)
    norm_weight = jnp.asarray(rng.normal(size=(128,)), jnp.float32)

    def run(mesh):
        def total(rows, taps, gate, norm_weight):
            mixed = gdn_passes.conv_silu(
                rows, taps, head=128, pallas=True, interpret=True, mesh=mesh
            )
            out = gdn_passes.gated_rms_norm(
                mixed, gate, norm_weight, pallas=True, interpret=True,
                mesh=mesh,
            )
            return jnp.sum(out * weight), (mixed, out)

        return jax.jit(jax.value_and_grad(
            total, argnums=range(4), has_aux=True
        ))(rows, taps, gate, norm_weight)

    (_, want), want_grads = run(None)
    (_, got), got_grads = run(_cpu_mesh(*mesh))
    for g, w in zip(got + got_grads, want + want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)


def test_reference_delta_rule_is_the_written_recurrence():
    """The reference's own token-by-token rule against the program's
    recurrent form: two independent writings of the same equations."""
    q, k, v, g, beta = _delta_inputs(96, seed=5, b=1)
    want, _ = _recurrent(q, k, v, g, beta)
    got = ref._delta_rule(
        jnp.repeat(q[0], 2, axis=1), jnp.repeat(k[0], 2, axis=1),
        v[0], g[0], beta[0],
    )
    np.testing.assert_allclose(got, want[0], atol=1e-6)


# ---------------------------------------------------------------------------
# Rotary and grouped-query heads
# ---------------------------------------------------------------------------


def test_rotary_matches_the_reference_and_leaves_the_rest():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 40, 3, 32)), jnp.float32)
    cos, sin = gqa.rotary_tables(jnp.arange(40), 8, 1e7)
    got = gqa.apply_rotary(x, cos, sin)
    for row in range(2):
        want = ref._rotate(x[row], jnp.arange(40), 8, 1e7)
        np.testing.assert_allclose(got[row], want, atol=1e-6)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-7)  # position 0


def _explicit_attention(q, k, v):
    """Each key-value head repeated to its query heads, full scores."""
    b, t, hq, d = q.shape
    group = hq // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("t,block", [(150, 512), (256, 64), (512, 128)])
def test_grouped_query_attention_matches_the_explicit_form(t, block):
    rng = np.random.default_rng(t)
    q = jnp.asarray(rng.normal(size=(2, t, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, t, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, t, 2, 16)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

    def engine(q, k, v):
        return gqa.causal_attention(q, k, v, impl="xla", block=block)

    np.testing.assert_allclose(
        engine(q, k, v), _explicit_attention(q, k, v), atol=2e-6
    )
    got = jax.grad(lambda *a: jnp.sum(engine(*a) * weight), (0, 1, 2))(q, k, v)
    want = jax.grad(
        lambda *a: jnp.sum(_explicit_attention(*a) * weight), (0, 1, 2)
    )(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5)


@pytest.mark.parametrize("t,d,engine", [
    (8192, 256, "xla causal_gqa_attention"),   # K+V of a head exceed VMEM
    (1024, 64, "pallas flash_attention"),
])
def test_engine_choice_on_a_tpu_backend(t, d, engine, monkeypatch):
    """`impl="auto"` on a TPU: the Pallas kernel where `supports(T, D)`
    holds, the XLA engine otherwise; the worker's log line says which
    (traced only: shapes, no device)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((1, t, 4, d), jnp.bfloat16)
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


def test_reference_attention_is_the_explicit_form():
    model = dict(TINY, sample_tokens=70)
    rng = np.random.default_rng(1)
    d = model["hidden_size"]
    h, hkv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    p = {
        "q_proj": {"kernel": rng.normal(size=(d, 2 * h * hd)) / 8},
        "k_proj": {"kernel": rng.normal(size=(d, hkv * hd)) / 8},
        "v_proj": {"kernel": rng.normal(size=(d, hkv * hd)) / 8},
        "o_proj": {"kernel": np.eye(h * hd)},
        "q_norm": {"weight": np.zeros(hd)}, "k_norm": {"weight": np.zeros(hd)},
    }
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), p)
    x = jnp.asarray(rng.normal(size=(70, d)), jnp.float32)
    blocked = ref._gated_attention(p, x, model, query_block=32)
    whole = ref._gated_attention(p, x, model, query_block=70)
    np.testing.assert_allclose(blocked, whole, atol=1e-6)


# ---------------------------------------------------------------------------
# The expert layer and its shares
# ---------------------------------------------------------------------------

MOE = dict(num_experts=8, num_experts_per_tok=2, moe_intermediate_size=16,
           shared_expert_intermediate_size=16, norm_topk_prob=True,
           hidden_size=32)


def _moe_layer(first, held, block_rows=128):
    return SparseMoeBlock(
        MOE["num_experts"], MOE["num_experts_per_tok"],
        MOE["moe_intermediate_size"], MOE["shared_expert_intermediate_size"],
        (first, held), True, jnp.float32, block_rows,
    )


def _moe_params(seed=0):
    layer = _moe_layer(0, 8)
    x = jnp.zeros((4, MOE["hidden_size"]), jnp.float32)
    return layer.init(jax.random.PRNGKey(seed), x)["params"]


def _share(params, first, held):
    """The parameters one chip of the layer holds."""
    cut = dict(params)
    for name in ("experts_gate_proj", "experts_up_proj", "experts_down_proj"):
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


@pytest.mark.parametrize("block_rows", [128, 16])
def test_no_pair_dropped_and_counters_right_under_a_skewed_router(block_rows):
    """Every token's first choice is ONE held expert (held range 2..5,
    expert 3): 300 pairs on one expert, more than two blocks of 128."""
    params = dict(_moe_params(1))
    rng = np.random.default_rng(2)
    x = jnp.asarray(np.abs(rng.normal(size=(300, MOE["hidden_size"]))) + 0.1,
                    jnp.float32)
    params["gate"] = params["gate"].at[:, 3].set(4.0)
    y, counters = _apply_moe(params, x, 2, 4, block_rows)
    model = dict(MOE, experts_first=2, experts_held=4)
    want = ref._experts(_share(params, 2, 4), x, model)
    assert _rel(y, want) < 1e-5
    probs = jax.nn.softmax(x @ params["gate"], axis=-1)
    _, ids = jax.lax.top_k(probs, 2)
    assert bool(jnp.all(ids[:, 0] == 3))
    load = np.bincount(np.asarray(ids).ravel(), minlength=8)[2:6]
    assert load[1] == 300
    np.testing.assert_array_equal(np.asarray(counters["load"]), load)
    assert int(counters["pairs"]) == int(counters["processed"]) == load.sum()
    # the worker's per-task reading of the same counters
    ledger = RoutingLedger()
    ledger.seed_once({})
    fields = ledger.task_delta({ROUTING_COLLECTION: {"layers_0": {"mlp": counters}}})
    blocks = int(np.ceil(load / block_rows).sum())
    assert int(counters["blocks"]) == blocks
    assert fields == {
        "layers": 1, "held": 4, "pairs": int(load.sum()), "dropped": 0,
        "blocks": blocks, "block_rows": block_rows,
        "load_max": 300, "load_mean": float(load.mean()),
    }
    again = ledger.task_delta({ROUTING_COLLECTION: {"layers_0": {"mlp": counters}}})
    assert again["pairs"] == 0 and again["load_max"] == 0
    assert again["blocks"] == 0 and again["block_rows"] == block_rows


@pytest.mark.parametrize("counter", ["blocks", "pairs"])
def test_task_delta_is_right_across_a_uint32_wrap(counter):
    """The counters are cumulative uint32 sums: a task whose reading has
    wrapped past 2**32 still reads its own share."""
    def state(pairs, blocks):
        layer = {
            "pairs": np.uint32(pairs), "processed": np.uint32(pairs),
            "blocks": np.uint32(blocks), "block_rows": np.uint32(256),
            "load": np.asarray([pairs, 0], np.uint32),
        }
        return {ROUTING_COLLECTION: {"layers_0": {"mlp": layer}}}

    near = 2 ** 32 - 2
    before = dict(pairs=1000, blocks=10)
    after = dict(pairs=1600, blocks=13)
    before[counter] = near
    after[counter] = (near + {"pairs": 600, "blocks": 3}[counter]) % 2 ** 32
    assert after[counter] < before[counter]  # it wrapped
    ledger = RoutingLedger()
    ledger.seed_once(state(**before))
    fields = ledger.task_delta(state(**after))
    assert fields["pairs"] == 600 and fields["blocks"] == 3
    assert fields["dropped"] == 0 and fields["block_rows"] == 256


def test_expert_layer_gradients_match_the_reference():
    params = _moe_params(3)
    x = jnp.asarray(
        np.random.default_rng(3).normal(size=(150, MOE["hidden_size"])),
        jnp.float32,
    )
    model = dict(MOE, experts_first=2, experts_held=4)
    share = _share(params, 2, 4)
    layer = _moe_layer(2, 4, 32)
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
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.abs(g - w).max()) < 1e-4 * float(jnp.abs(w).max())


def test_counters_stand_still_in_evaluation():
    params = _moe_params()
    x = jnp.ones((8, MOE["hidden_size"]), jnp.float32)
    layer = _moe_layer(0, 8)
    zeros = layer.init(jax.random.PRNGKey(0), x)[ROUTING_COLLECTION]
    assert int(zeros["pairs"]) == 0  # init counts nothing
    layer.apply({"params": params, ROUTING_COLLECTION: zeros}, x)  # immutable


# ---------------------------------------------------------------------------
# Through the trainer, the saver and `elasticdl train`
# ---------------------------------------------------------------------------


def _trainer():
    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer

    model = dict(TINY, sample_tokens=64)
    return DataParallelTrainer(
        zoo.custom_model(use_bf16=False, remat=True, **_model_kwargs(model)),
        zoo.loss, zoo.optimizer(),
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
    # three steps of 4 x 64 tokens, two choices each, half the experts held
    assert 0 < fields["pairs"] < 3 * 4 * 64 * 2 * 4
    before = trainer.eval_step(tokens)
    CheckpointSaver(str(tmp_path)).save(trainer.state_to_host(), 3)
    restored, step = CheckpointSaver(str(tmp_path)).load_latest()
    assert step == 3
    fresh, _ = _trainer()
    fresh.state = restored
    np.testing.assert_array_equal(fresh.eval_step(tokens), before)
    want = ref.forward(restored.params, tokens, model)
    assert _rel(before, want) < 5e-5


def test_checkpoint_older_than_the_block_counters_restores_them_at_zero(
    tmp_path,
):
    """A `routing` collection saved before the layer counted `blocks` and
    `block_rows` (ISSUE 40) restores with both at zero and the counters it
    had as they were; the restored trainer trains on and counts."""
    from elasticdl_tpu.checkpoint import CheckpointSaver
    from flax.traverse_util import flatten_dict, unflatten_dict

    trainer, model = _trainer()
    tokens = ref.sample(11, 4, model)
    trainer.train_step(tokens, tokens)
    state = trainer.state_to_host()
    flat = flatten_dict(state.model_state)
    assert sum(path[-1] == "blocks" for path in flat) == 4
    older = unflatten_dict({
        path: leaf for path, leaf in flat.items()
        if path[-1] not in ("blocks", "block_rows")
    })
    CheckpointSaver(str(tmp_path)).save(state._replace(model_state=older), 1)
    restored, _ = CheckpointSaver(str(tmp_path)).load_latest()
    assert not any(
        path[-1] == "blocks" for path in flatten_dict(restored.model_state)
    )
    fresh, _ = _trainer()
    fresh.state = restored
    got = flatten_dict(jax.device_get(fresh.state.model_state))
    for path, leaf in got.items():
        if path[-1] in ("blocks", "block_rows"):
            assert leaf.dtype == np.uint32 and int(leaf) == 0
        else:
            np.testing.assert_array_equal(leaf, flat[path])
    ledger = RoutingLedger()
    ledger.seed_once(fresh.state.model_state)
    assert np.isfinite(float(fresh.train_step(tokens, tokens)))
    fields = ledger.task_delta(fresh.state.model_state)
    assert fields["blocks"] > 0 and fields["block_rows"] == 128
    assert fields["dropped"] == 0 and fields["pairs"] > 0


def test_sharded_restore_starts_an_absent_block_counter_at_zero(tmp_path):
    """The restore by the template's leaf keys: a `routing` counter the
    checkpoint's writer did not keep yet starts at zero; any other absent
    leaf is still an error."""
    import pickle

    from elasticdl_tpu.checkpoint import ShardedCheckpointSaver
    from flax.traverse_util import flatten_dict

    trainer, model = _trainer()
    tokens = ref.sample(11, 4, model)
    trainer.train_step(tokens, tokens)
    saver = ShardedCheckpointSaver(str(tmp_path))
    trainer.save_checkpoint(saver, 1)
    dense_path = tmp_path / "step_000000000001" / "dense.pkl"
    with open(dense_path, "rb") as f:
        dense = pickle.load(f)
    younger = [
        key for key in dense["leaves"]
        if key.endswith(("/blocks", "/block_rows"))
    ]
    assert len(younger) == 8 and all("/routing/" in key for key in younger)

    def rewrite(without):
        with open(dense_path, "wb") as f:
            pickle.dump(dict(dense, leaves={
                key: leaf for key, leaf in dense["leaves"].items()
                if key not in without
            }), f)

    rewrite(younger)
    fresh, _ = _trainer()
    fresh.set_sharded_restore(ShardedCheckpointSaver(str(tmp_path)), 1)
    fresh.ensure_initialized(tokens)
    routing = jax.device_get(fresh.state.model_state[ROUTING_COLLECTION])
    kept = jax.device_get(trainer.state.model_state[ROUTING_COLLECTION])
    for got, want in zip(jax.tree.leaves(routing), jax.tree.leaves(kept)):
        assert got.dtype == want.dtype and got.shape == want.shape
    routing, kept = flatten_dict(routing), flatten_dict(kept)
    for path, leaf in routing.items():
        if path[-1] in ("blocks", "block_rows"):
            assert int(leaf) == 0 and int(kept[path]) > 0
        else:
            np.testing.assert_array_equal(leaf, kept[path])
    assert np.isfinite(float(fresh.train_step(tokens, tokens)))
    rewrite(younger + [next(
        key for key in dense["leaves"] if "/routing/" not in key
    )])
    broken, _ = _trainer()
    broken.set_sharded_restore(ShardedCheckpointSaver(str(tmp_path)), 1)
    with pytest.raises(KeyError, match="missing leaf"):
        broken.ensure_initialized(tokens)


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
        "--model_def=qwen3_next.qwen3_next_lm",
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
    assert all(e["dropped"] == 0 and e["pairs"] > 0 for e in routing)
    assert all(e["load_max"] >= e["load_mean"] > 0 for e in routing)
    # 4 x 64 tokens, 2 of 8 experts each: 64 pairs an expert, blocks of 128
    assert all(e["block_rows"] == 128 for e in routing)
    assert all(
        e["blocks"] * e["block_rows"] >= e["pairs"] and e["blocks"] > 0
        for e in routing
    )


def test_benchmark_cost_functions_count_what_they_say():
    model = CONFIG["model"]
    cost = ref.step_cost(model, 2)
    # ~22 TFLOP a step of 16,384 tokens, 1.37 GFLOP a token
    assert 21e12 < cost["flops"] < 24e12
    assert cost["bytes"] == 28 * 424_340_544 - 28 * (
        424_340_544 - ref._all_params(model)
    )
    scan = ref.gdn_scan_cost(model, 2)
    assert scan["flops"] < 0.05 * cost["flops"]
    experts = ref.moe_experts_cost(model, pairs=4 * 5120, steps=1)
    assert experts["flops"] == 6 * 3 * 2048 * 512 * 4 * 5120
