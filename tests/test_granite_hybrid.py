"""Granite 4.0-H on the normal training path (ISSUE 38): the zoo model (a
block of two sublayers under multipliers, a tied head over a sliced
vocabulary, the Mamba-2 mixer at one group and the attention without a
position embedding imported from `model_zoo/nemotron_h`) against the
plain reference that decides the benchmark cell's `correct`
(`perfbench/configs/granite_hybrid_reference.py`, which shares no code
with the program).  Tiny sizes, seeded random weights, float32 on the
CPU, so tolerances are those of float32 summation order: 1e-5 of the
outputs' size for the logits, the loss and every gradient leaf.
"""

import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops.ssd import ssd_chunked
from model_zoo.granite_hybrid import granite_hybrid_lm as zoo

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO_ROOT, "perfbench", "configs")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(
    os.path.join(CONFIGS, "granite_hybrid_reference.py"), "granite_hybrid_ref"
)

with open(os.path.join(CONFIGS, "granite-4.0-h-micro.json")) as f:
    CONFIG = json.load(f)

# The ten-layer pattern at hidden 64: 4 Mamba-2 heads of 16 in ONE group,
# state 16, chunks of 32 at T = 128; 4 / 2 attention heads of 16; MLP 128.
TINY = CONFIG["rehearse"]["model"]


def _model_kwargs(model):
    return {k: v for k, v in model.items() if k != "sample_tokens"}


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def _perturbed(tree, seed, scale=0.05):
    """Norm weights and `D` start at 1: move every leaf off its special
    value so that a dropped term would show."""
    leaves, treedef = jax.tree.flatten(tree)
    key = jax.random.PRNGKey(seed)
    return jax.tree.unflatten(treedef, [
        leaf + scale * jax.random.normal(jax.random.fold_in(key, i),
                                         leaf.shape)
        for i, leaf in enumerate(leaves)
    ])


def _program(model, tokens, **changed):
    module = zoo.custom_model(
        use_bf16=False, **dict(_model_kwargs(model), **changed)
    )
    return lambda p: module.apply({"params": p}, tokens)


# ---------------------------------------------------------------------------
# The whole model against the reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def program_and_reference():
    tokens = ref.sample(3, 2, TINY)
    module = zoo.custom_model(use_bf16=False, **_model_kwargs(TINY))
    params = _perturbed(
        module.init(jax.random.PRNGKey(0), tokens)["params"], 1
    )
    return (
        _program(TINY, tokens), lambda p: ref.forward(p, tokens, TINY),
        params, tokens,
    )


def test_logits_and_loss_match_the_reference(program_and_reference):
    program, reference, params, tokens = program_and_reference
    got, want = program(params), reference(params)
    assert got.shape == want.shape == tokens.shape + (TINY["vocab_size"],)
    assert _rel(got, want) < 1e-5
    np.testing.assert_allclose(
        float(zoo.loss(tokens, got)), float(zoo.loss(tokens, want)),
        rtol=1e-5,
    )


def test_gradients_match_the_reference(program_and_reference):
    """Every leaf, the tied table's among them, by `jax.grad` of each
    side's own forward pass."""
    program, reference, params, tokens = program_and_reference
    got = jax.grad(lambda p: zoo.loss(tokens, program(p)))(params)
    want = jax.grad(lambda p: zoo.loss(tokens, reference(p)))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want) == 9 * 12 + 8 + 2
    for (path, g), w in zip(flat_got, flat_want):
        assert float(jnp.abs(w).max()) > 0, path
        assert _rel(g, w) < 1e-5, jax.tree_util.keystr(path)


#: piece -> what a stack written for the usual conventions would run in
#: its place (the reference's planted faults are two of these)
USUAL = {
    "embedding_multiplier": 1.0,
    "attention_multiplier": TINY["head_dim"] ** -0.5,
    "residual_multiplier": 1.0,
    "logits_scaling": 1.0,
}


@pytest.mark.parametrize("piece", sorted(USUAL) + ["tie"])
def test_each_multiplier_and_the_tie_is_held_by_the_reference(
    program_and_reference, piece
):
    """A program that leaves one piece out reads far from the reference
    (so `test_logits_and_loss_match_the_reference` fails for it), and the
    reference with the same fault planted IS that program: the distance
    is the piece's and nothing else's."""
    program, reference, params, tokens = program_and_reference
    clean = _rel(program(params), reference(params))
    if piece == "tie":
        # An untied head: the logits read off another table.  The program
        # has no such option, so the fault is planted in the reference.
        head = _perturbed(params["model"]["embed_tokens"], 7, scale=0.02)
        untied = jnp.stack([
            ref.decoder(params, row, TINY, head=head) for row in tokens
        ])
        assert _rel(program(params), untied) > 1000 * clean
        return
    faulty = _program(TINY, tokens, **{piece: USUAL[piece]})(params)
    assert _rel(faulty, reference(params)) > 1000 * clean
    planted = ref.forward(params, tokens, dict(TINY, **{piece: USUAL[piece]}))
    assert _rel(faulty, planted) < 1e-5


@pytest.mark.parametrize("fault,piece", [
    ("no_residual_multiplier", "residual_multiplier"),
    ("sqrt_scale", "attention_multiplier"),
])
def test_planted_faults_are_the_usual_conventions(
    program_and_reference, fault, piece
):
    """The two faults every run of the cell reports its distance to."""
    program, _, params, tokens = program_and_reference
    planted = ref.forward(params, tokens, TINY, fault)
    assert _rel(program(params), planted) > 1e-3
    usual = _program(TINY, tokens, **{piece: USUAL[piece]})(params)
    assert _rel(usual, planted) < 1e-5


def test_tied_table_is_one_leaf_with_both_gradients(program_and_reference):
    """`embed_tokens` is ONE leaf and there is no `lm_head`; its gradient
    is the gather's scatter-add plus the head's matmul, each taken alone
    from the reference with the two readings of the table held apart."""
    _, _, params, _ = program_and_reference
    paths = [
        jax.tree_util.keystr(path)
        for path, _ in jax.tree_util.tree_leaves_with_path(params)
    ]
    assert sum("embed_tokens" in path for path in paths) == 1
    assert not any("head" in path for path in paths)
    # ids from the first 48 of the 64: the rest are rows no token draws
    tokens = ref.sample(3, 2, dict(TINY, vocab_size=48))
    program = _program(TINY, tokens)
    table = params["model"]["embed_tokens"]

    def apart(gathered, head):
        w = {"model": dict(params["model"], embed_tokens=gathered)}
        return zoo.loss(tokens, jnp.stack([
            ref.decoder(w, row, TINY, head=head) for row in tokens
        ]))

    by_gather, by_head = jax.grad(apart, argnums=(0, 1))(table, table)
    got = jax.grad(lambda p: zoo.loss(tokens, program(p)))(params)
    got = got["model"]["embed_tokens"]
    assert _rel(got, by_gather + by_head) < 1e-5
    # neither part is negligible: a program that dropped one would show
    assert _rel(got, by_gather) > 0.05 and _rel(got, by_head) > 0.05
    # rows no token drew receive the head's gradient alone
    assert not np.asarray(by_gather)[48:].any()
    assert np.asarray(by_head)[48:].any()
    np.testing.assert_allclose(got[48:], by_head[48:], rtol=1e-4, atol=1e-9)


def test_parameter_names_and_layouts_follow_the_source():
    m = TINY
    module = zoo.custom_model(use_bf16=False, **_model_kwargs(m))
    shapes = jax.tree.map(
        lambda a: a.shape,
        jax.eval_shape(
            module.init, jax.random.PRNGKey(0), ref.sample(0, 1, m)
        )["params"]["model"],
    )
    d, inner = m["hidden_size"], m["mamba_n_heads"] * m["mamba_d_head"]
    bc = m["mamba_n_groups"] * m["mamba_d_state"]
    mlp = {
        "input_linear": {"kernel": (d, 2 * m["shared_intermediate_size"])},
        "output_linear": {"kernel": (m["shared_intermediate_size"], d)},
    }
    norms = {
        "input_layernorm": {"weight": (d,)},
        "post_attention_layernorm": {"weight": (d,)},
    }
    assert shapes["layers_0"] == dict(norms, shared_mlp=mlp, mamba={
        "in_proj": {"kernel": (d, 2 * inner + 2 * bc + m["mamba_n_heads"])},
        "conv1d": {"kernel": (m["mamba_d_conv"], inner + 2 * bc),
                   "bias": (inner + 2 * bc,)},
        "A_log": (m["mamba_n_heads"],), "D": (m["mamba_n_heads"],),
        "dt_bias": (m["mamba_n_heads"],), "norm": (inner,),
        "out_proj": {"kernel": (inner, d)},
    })
    heads, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                     m["head_dim"])
    assert shapes["layers_5"] == dict(norms, shared_mlp=mlp, self_attn={
        "q_proj": {"kernel": (d, heads * hd)},
        "k_proj": {"kernel": (d, kv * hd)},
        "v_proj": {"kernel": (d, kv * hd)},
        "o_proj": {"kernel": (heads * hd, d)},
    })
    assert set(shapes) == (
        {f"layers_{i}" for i in range(10)} | {"embed_tokens", "norm"}
    )


def test_published_forty_layers_and_the_cut_are_the_same_code():
    published = CONFIG["published"]["layer_types"]
    assert len(published) == CONFIG["published"]["num_hidden_layers"] == 40
    assert [i for i, k in enumerate(published) if k == "attention"] == [
        5, 15, 25, 35
    ]
    assert CONFIG["model"]["layer_types"] == published[:10]
    tokens = ref.sample(0, 1, TINY)
    widths = _model_kwargs(TINY)

    def layers(**config):
        module = zoo.custom_model(**dict(widths, **config))
        params = jax.eval_shape(
            module.init, jax.random.PRNGKey(0), tokens
        )["params"]["model"]
        return [
            "mamba" if "mamba" in params[f"layers_{i}"] else "attention"
            for i in range(len(params) - 2)
        ]

    whole = layers(layer_types=published, num_hidden_layers=40)
    assert whole == published
    assert whole.count("mamba") == 36 and whole.count("attention") == 4
    # the cut: the same list, its first ten entries; and as a job's flags
    # carry a list
    assert layers(layer_types=published, num_hidden_layers=10) == whole[:10]
    assert layers(
        layer_types="/".join(published), num_hidden_layers=0
    ) == whole


def test_configuration_keys_are_checked():
    with pytest.raises(ValueError, match="routed variant"):
        zoo.custom_model(num_local_experts=8)
    with pytest.raises(ValueError, match="no_such_key"):
        zoo.custom_model(no_such_key=1)
    with pytest.raises(ValueError, match="not made of"):
        zoo.custom_model(layer_types="mamba/full_attention")
    with pytest.raises(ValueError, match="lists 2 layers of 3"):
        zoo.custom_model(layer_types="mamba/attention", num_hidden_layers=3)


def test_full_size_configuration_counts_the_parameters_it_states():
    """The published widths, the cut's ten layers and an eighth of the
    vocabulary: 772,160,448 parameters, by the program's own shapes, by
    hand, and by the reference's count; the job's flags say what `model`
    says, and every published key the cut leaves alone stands as
    published."""
    model = CONFIG["model"]
    module = zoo.custom_model(**_model_kwargs(model))
    params = jax.eval_shape(
        module.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)
    )["params"]
    count = sum(leaf.size for leaf in jax.tree.leaves(params))
    mamba = (
        2048 * (4096 + 4096 + 128 + 128 + 64) + 4 * 4352 + 4352 + 3 * 64
        + 4096 + 4096 * 2048
    )
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp = 2048 * 16384 + 8192 * 2048
    assert mamba == 25_847_232 and attention == 10_485_760
    assert mlp == 50_331_648
    by_hand = (
        9 * (mamba + mlp + 4096) + attention + mlp + 4096
        + 12_544 * 2048 + 2048
    )
    assert count == by_hand == ref._all_params(model) == 772_160_448
    assert "772,160,448" in CONFIG["device_bytes"]
    flags = dict(
        pair.split("=", 1) for pair in next(
            f for f in CONFIG["job"] if f.startswith("--model_params=")
        ).split("=", 1)[1].split(",")
    )
    assert flags.pop("remat") == "true"
    assert flags.pop("layer_types").split("/") == model["layer_types"]
    assert {k: float(v) for k, v in flags.items()} == {
        k: float(v) for k, v in _model_kwargs(model).items()
        if k != "layer_types"
    }
    assert CONFIG["reduced"] == ["num_hidden_layers", "layer_types",
                                 "vocab_size"]
    for key, value in model.items():
        if key in CONFIG and key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["tie_word_embeddings"] is True
    assert CONFIG["position_embedding_type"] == "nope"
    assert CONFIG["mamba_expand"] * model["hidden_size"] == (
        model["mamba_n_heads"] * model["mamba_d_head"]
    )
    assert CONFIG["shared_intermediate_size"] == CONFIG["intermediate_size"]


# ---------------------------------------------------------------------------
# Precision
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
    """The bfloat16 model has NO product of float32 operands (no router,
    no gate: every product is a projection, a state-space product, an
    attention product or the head, all with bfloat16 operands), so none
    is left to a TPU's default, which would round float32 operands
    unasked.  In the float32 model the state-space form's four products
    ask for `HIGHEST` themselves, at one group and chunks of 256 as at
    Nemotron-H's shape."""
    highest = jax.lax.Precision.HIGHEST
    module = zoo.custom_model(use_bf16=True, **_model_kwargs(TINY))
    tokens = ref.sample(0, 1, TINY)
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0), tokens)
    found = _dot_precisions(
        jax.make_jaxpr(lambda v, t: module.apply(v, t))(
            variables, tokens
        ).jaxpr
    )
    # 9 x (2 projections + 4 state-space products) + 4 projections and
    # the engine's own + 10 x 2 of the MLPs + the head
    assert len(found) > 9 * 6 + 4 + 20 + 1
    assert [p for dtype, p in found if dtype == jnp.float32] == []
    shapes = [
        jax.ShapeDtypeStruct(s, jnp.float32) for s in (
            (1, 600, 4, 8), (1, 600, 4), (4,), (1, 600, 1, 16),
            (1, 600, 1, 16),
        )
    ]
    for dtype, count in ((jnp.float32, 4), (jnp.bfloat16, 0)):
        rule = _dot_precisions(
            jax.make_jaxpr(
                lambda *a: ssd_chunked(*a, chunk=256, dtype=dtype)
            )(*shapes).jaxpr
        )
        assert len(rule) == 4
        assert sum(p == (highest, highest) for _, p in rule) == count
        assert all(d == dtype for d, _ in rule)


def test_bf16_program_is_the_reference_at_the_stated_precision():
    """With bfloat16 operands where the program has them, the reference
    is the program to the flips of a rounding, closer than in float32;
    with everything in bfloat16 it is further than either."""
    tokens = ref.sample(5, 1, TINY)
    module = zoo.custom_model(use_bf16=True, **_model_kwargs(TINY))
    params = module.init(jax.random.PRNGKey(2), tokens)["params"]
    got = module.apply({"params": params}, tokens)
    stated, highest, low = (
        _rel(got, ref.forward(params, tokens, TINY, precision))
        for precision in ("stated", "highest", "bfloat16")
    )
    # ten layers deep: the chunked form rounds a chunk's masked scores and
    # the chunk states where the token-by-token reference rounds dt x, B
    # and C (the same operands, in other products): 0.008 / 0.013 / 0.029
    assert stated < 1.2e-2
    assert highest > 1.3 * stated
    assert low > 1.5 * highest


def test_the_cell_checks_precisions_the_reference_has():
    check = CONFIG["check"]
    assert list(check["tolerance_rel_rms"]) == ["highest"]  # ONE limit
    assert check["also_report"] == [
        "stated", "bfloat16", "no_residual_multiplier", "sqrt_scale",
    ]
    for name in list(check["tolerance_rel_rms"]) + check["also_report"]:
        assert name in ref.PRECISIONS
    # the rehearsal's program is float32: only `highest` applies to it
    assert "also_report" not in CONFIG["rehearse"]["check"]
    with pytest.raises(ValueError):
        ref.forward({}, np.zeros((1, 4), np.int32), TINY, "float16")


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


def test_traced_step_carries_the_scopes_and_one_pair_of_moments():
    """The compiled two-step window program names `ssm` > `ssm_scan`,
    `attn`, `mlp` and `lm_head_loss` on its ops (what the benchmark's
    readers sum), and the optimizer's state holds the tied table once."""
    trainer, model = _trainer()
    tokens = ref.sample(11, 4, model)
    trainer.ensure_initialized(tokens)
    batch = (tokens, tokens, np.ones((4,), np.float32))
    window = trainer.stage_window([batch, batch])
    text = trainer._train_window_jit.lower(
        trainer.state, *window
    ).compile().as_text()
    names = " ".join(re.findall(r'op_name="([^"]+)"', text))
    for scope in ("fwd_bwd", "ssm", "ssm_scan", "attn", "mlp",
                  "lm_head_loss", "optimizer"):
        assert f"/{scope}/" in names or f"({scope})" in names, scope
    assert "/ssm/" in names and "ssm_scan" in names.split("/ssm/", 1)[1]
    for absent in ("moe", "gdn", "mla_core", "attn_window"):
        assert f"/{absent}/" not in names
    moments = [
        leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            trainer.state.opt_state
        )
        if "embed_tokens" in jax.tree_util.keystr(path)
    ]
    table = (model["vocab_size"], model["hidden_size"])
    assert moments == [table, table]  # mu and nu, once each


def test_trainer_trains_and_checkpoint_restores_the_logits(tmp_path):
    from elasticdl_tpu.checkpoint import CheckpointSaver

    trainer, model = _trainer()
    tokens = ref.sample(11, 4, model)
    losses = [float(trainer.train_step(tokens, tokens)) for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    before = trainer.eval_step(tokens)
    CheckpointSaver(str(tmp_path)).save(trainer.state_to_host(), 3)
    restored, step = CheckpointSaver(str(tmp_path)).load_latest()
    assert step == 3
    fresh, _ = _trainer()
    fresh.state = restored
    np.testing.assert_array_equal(fresh.eval_step(tokens), before)
    want = ref.forward(restored.params, tokens, model)
    assert _rel(before, want) < 1e-5


def test_optimizer_warms_up_and_decays():
    """Step n of the warm-up runs AdamW at lr n / warmup_steps."""
    params = {"embed_tokens": jnp.ones((3, 2))}
    grads = jax.tree.map(jnp.ones_like, params)
    tx = zoo.optimizer(lr=1e-2, warmup_steps=4)
    state = tx.init(params)
    for n in range(1, 7):
        updates, state = tx.update(grads, state, params)
        rate = 1e-2 * min(1.0, n / 4)
        np.testing.assert_allclose(
            updates["embed_tokens"], -rate * (1 + 0.01), rtol=1e-4
        )


def test_two_task_elasticdl_train_end_to_end(tmp_path):
    """`elasticdl train` as a user runs it: master, task dispatch, one
    collective worker, the two-step window program, a cadence checkpoint;
    a second run of the same job restores it."""
    from elasticdl_tpu.common.args import parse_master_args
    from elasticdl_tpu.common.constants import Mode
    from elasticdl_tpu.master.job_runner import run_allreduce_job

    model = dict(TINY, sample_tokens=64)
    params = ",".join(
        f"{k}={'/'.join(v) if isinstance(v, list) else v}"
        for k, v in _model_kwargs(model).items()
    )

    def run(tb):
        return run_allreduce_job(parse_master_args([
            "--model_zoo=model_zoo",
            "--model_def=granite_hybrid.granite_hybrid_lm",
            f"--model_params={params},remat=true",
            "--training_data=synthetic://lm?n=8&len=64&vocab=64&seed=5",
            "--records_per_task=4",
            "--minibatch_size=2",
            "--num_workers=1",
            "--use_bf16=false",
            "--distribution_strategy=AllreduceStrategy",
            f"--checkpoint_dir={tmp_path / 'ckpt'}",
            f"--tensorboard_log_dir={tb}",
            "--checkpoint_steps=2",
            "--num_epochs=1",
        ]), Mode.TRAINING)

    def events(tb):
        with open(tb / "events_worker_0.jsonl") as f:
            return [json.loads(line) for line in f]

    assert run(tmp_path / "tb") == 0
    saved = sorted(
        p for p in os.listdir(tmp_path / "ckpt") if p.startswith("step_")
    )
    assert saved and saved[-1] == "step_000000000004"
    first = events(tmp_path / "tb")
    executed = [
        e for e in first
        if e.get("event") == "span" and e.get("name") in (
            "step.compile", "step.execute")
    ]
    assert [e["steps"] for e in executed] == [2, 2]
    assert not any(e.get("event") == "checkpoint_restored" for e in first)
    assert run(tmp_path / "tb2") == 0
    restored = [
        e for e in events(tmp_path / "tb2")
        if e.get("event") == "checkpoint_restored"
    ]
    assert [e["step"] for e in restored] == [4]


# ---------------------------------------------------------------------------
# The benchmark's cost functions
# ---------------------------------------------------------------------------


def test_benchmark_cost_functions_count_what_they_say():
    """At the published widths, 1 x 8192 tokens, against a count by
    hand."""
    model = CONFIG["model"]
    tokens = 8192
    mamba = 2048 * 8512 + 4096 * 2048      # in_proj + out_proj
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp = 3 * 2048 * 8192
    head = 2048 * 12_544
    one = ref._ssd_forward(model, 1)
    # 32 chunks x (ONE group's C B^T + 64 heads' three products), 9 layers
    assert one["flops"] == 9 * 32 * (
        2 * 256 * 256 * 128 + 64 * (2 * 256 * 256 * 64 + 4 * 256 * 64 * 128)
    )
    assert one["tensors"] == 9 * tokens * (2 * 4096 + 2 * 128 + 64)
    assert one["states"] == 9 * 32 * 64 * 64 * 128
    scan = ref.ssm_scan_cost(model, 1)
    assert scan["flops"] == 4 * one["flops"]
    assert scan["bytes"] == 4 * (
        2 * (one["tensors"] + 2 * one["states"])
        + 2 * one["tensors"] + 3 * one["states"]
    )
    # memory bound on a v5e: bytes / 819e9 is above flops / 197e12
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12
    # the same rule as Nemotron-H's, at its shape: one yardstick
    nemotron = _load(
        os.path.join(CONFIGS, "nemotron_h_reference.py"), "nemotron_h_ref"
    )
    theirs = dict(
        hybrid_override_pattern="M" * 9, mamba_num_heads=64,
        mamba_head_dim=64, n_groups=1, ssm_state_size=128, chunk_size=256,
        sample_tokens=tokens,
    )
    assert nemotron.ssm_scan_cost(theirs, 1) == scan
    mlps = ref.mlp_cost(model, 1)
    assert mlps["flops"] == 8 * 10 * mlp * tokens
    assert mlps["bytes"] == 16 * 10 * mlp + 24 * 10 * tokens * 2048
    # compute bound: 10 MLPs are 33 TFLOP a step as run, 0.17 s at peak
    assert mlps["flops"] / 197e12 > 10 * mlps["bytes"] / 819e9
    cost = ref.step_cost(model, 1)
    assert cost["flops"] == (
        6 * (9 * mamba + attention + 10 * mlp + head) * tokens
        + 3 * 4 * tokens * tokens * 32 * 64 // 2
        + 3 * one["flops"]
    )
    assert 39e12 < cost["flops"] < 40.5e12  # 39.7 TFLOP a step, no recompute
    assert cost["bytes"] == 28 * 772_160_448
