"""SDAR on the normal training path (ISSUE 51): block-diffusion training of
a decoder of routed experts.  Every sequence runs as a noised copy beside
its clean copy under the three-part block-diffusion mask, the loss over
the masked positions weighted by 1 / t, the noise a part of the record;
the zoo model against the plain reference that decides the benchmark
cell's `correct` (`perfbench/configs/sdar_reference.py`, which shares no
code with the program).  The contract's cases are `tests/lm_contract.py`'s,
at `tests/spec_sdar.py`'s `SPEC` (the model as a job runs it:
`tests/test_sdar_program.py`); the engine's own under this mask are
`tests/test_window_attention.py`'s.  Tiny sizes, seeded random weights,
float32 on the CPU, so tolerances are those of float32 summation order:
1e-5 of the outputs' size, gradients 2e-3 of each leaf's largest entry.
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
from spec_sdar import SPEC, CONFIG, TINY, ref, zoo  # noqa: F401

T, B = 128, 4


def _built(seed=0, rows=2, **changes):
    """-> (apply(params, features) -> the prediction's tree, perturbed
    parameters, features, the widths), float32."""
    model = dict(TINY, **changes)
    module = zoo.custom_model(use_bf16=False, **_model_kwargs(model))
    features = ref.sample(seed + 5, rows, model)
    variables = dict(module.init(jax.random.PRNGKey(seed), features))
    params = _perturbed(variables.pop("params"), seed + 1)

    def apply(p, features):
        return module.apply({"params": p, **variables}, features)

    return apply, params, features, model


# ---------------------------------------------------------------------------
# The model's own pieces, a case each: leave the piece out and the case
# fails (the matching planted fault in the reference is caught)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fault", [
    "causal", "leak", "no_clean", "positions_run_on", "shifted",
])
def test_planted_faults_read_far_from_the_program(program_and_reference, fault):
    """The three parts of the mask (`causal`: a plain causal mask over
    the 2 T positions; `leak`: a noised query also reads the clean copy
    of its own block; `no_clean`: the noised half alone), the shared
    positions (`positions_run_on`) and the label being the position's own
    token (`shifted`): each planted in the reference reads far from the
    program, which agrees with the reference proper to 1e-5."""
    program, reference, params, features, model = program_and_reference
    got = program(params)["logits"]
    assert _rel(got, reference(params)) < 1e-5
    assert _rel(got, ref.forward(params, features, model, fault)) > 1000 * 1e-5


@pytest.mark.parametrize("fault", [
    "unweighted", "per_masked", "every_position", "shifted",
])
def test_loss_weighs_masked_positions_by_one_over_t_over_all_tokens(fault):
    """loss = (1 / T) sum_i m[i] (1 / t) CE(logits[i], x0[i]): the 1 / t
    weight (`unweighted`), the normalisation by T and not by the masked
    count (`per_masked`), the masked positions alone (`every_position`),
    the label a position's OWN token (`shifted`): the reference with each
    got wrong differs from the program's loss, which is the reference's."""
    apply, params, features, model = _built(rows=3)
    tokens = features[0]
    got = float(zoo.loss(tokens, apply(params, features)))
    want = float(ref.loss_fn(params, features, tokens, model))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    wrong = float(ref.loss_fn(
        params, features, tokens, model, frozenset({fault})
    ))
    assert abs(wrong - want) > 0.02 * want, (fault, wrong, want)
    # by hand, in numpy, from the program's own logits
    predicted = apply(params, features)
    logits = np.asarray(predicted["logits"], np.float64)
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    ce = -np.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
    _, mask, t = features
    by_hand = np.mean(np.sum(mask * ce / t[:, None], axis=-1) / T)
    np.testing.assert_allclose(got, by_hand, rtol=1e-5)
    np.testing.assert_allclose(
        predicted["weight"], mask / t[:, None], rtol=1e-6
    )


def test_all_masked_step_at_t_one_is_the_references():
    """With `t` forced to 1 and every position masked, every noised token
    is the mask id and the loss is the plain mean cross-entropy over all
    T positions: the reference's all-masked step."""
    apply, params, (tokens, _, _), model = _built()
    features = (tokens, np.ones(tokens.shape, bool),
                np.ones(tokens.shape[0], np.float32))
    predicted = apply(params, features)
    assert _rel(predicted["logits"], ref.forward(params, features, model)) < 1e-5
    np.testing.assert_array_equal(predicted["weight"], 1.0)
    got = float(zoo.loss(tokens, predicted))
    np.testing.assert_allclose(
        got, float(ref.loss_fn(params, features, tokens, model)), rtol=1e-5
    )
    # every weight 1 over T positions: the plain mean
    np.testing.assert_allclose(got, float(ref.loss_fn(
        params, features, tokens, model, frozenset({"every_position"})
    )), rtol=1e-6)
    # the noised half holds no token of the record: another record with
    # the same CLEAN first block gives block 0 the same logits
    other = tokens.copy()
    other[:, B:] = (other[:, B:] + 1) % model["vocab_size"]
    moved = apply(params, (other,) + features[1:])["logits"]
    np.testing.assert_allclose(
        moved[:, :B], predicted["logits"][:, :B], atol=1e-5
    )


def test_mask_means_what_it_says():
    """The mask, tested by what it means: a change to a CLEAN token at
    block b changes no logit of a noised block <= b (and does change the
    next block's); a change to a NOISED token (a position unmasked, so
    that its own token shows) changes no logit outside its block."""
    apply, params, (tokens, mask, t), model = _built(rows=1)
    b = 9
    at = slice(b * B, (b + 1) * B)
    # a position of block b that the noise masked: its clean token shows
    # in the clean copy alone
    hidden = b * B + int(np.argmax(mask[0, at]))
    assert mask[0, hidden]
    base = np.asarray(apply(params, (tokens, mask, t))["logits"])[0]
    moved_tokens = tokens.copy()
    moved_tokens[0, hidden] = (tokens[0, hidden] + 7) % (model["vocab_size"] - 1)
    moved = np.asarray(apply(params, (moved_tokens, mask, t))["logits"])[0]
    changed = np.abs(moved - base).max(axis=-1) > 1e-6
    assert not changed[:(b + 1) * B].any()      # blocks <= b: unread
    assert changed[(b + 1) * B:(b + 2) * B].all()   # block b + 1 reads it
    # unmask that position: the NOISED copy changes there and the clean
    # one does not, and only block b's logits move
    unmasked = mask.copy()
    unmasked[0, hidden] = False
    moved = np.asarray(apply(params, (tokens, unmasked, t))["logits"])[0]
    changed = np.abs(moved - base).max(axis=-1) > 1e-6
    assert changed[at].all()
    assert not changed[:b * B].any() and not changed[(b + 1) * B:].any()


def test_a_block_of_the_whole_sequence_is_dense_bidirectional_attention():
    """`block_length = T`: the noised half attends to itself in both
    directions and to nothing else, which is the reference's `no_clean`
    reading (a masked-LM step) at the same weights."""
    apply, params, features, model = _built(block_length=T)
    got = apply(params, features)["logits"]
    assert _rel(got, ref.forward(params, features, model)) < 1e-5
    assert _rel(got, ref.forward(params, features, model, "no_clean")) < 1e-5
    # and blocks of 16 are the reference's too, not those of 4
    apply, params, features, model = _built(block_length=16)
    got = apply(params, features)["logits"]
    assert _rel(got, ref.forward(params, features, model)) < 1e-5
    assert _rel(got, ref.forward(
        params, features, dict(model, block_length=4))) > 1e-2


def test_dense_mask_is_the_three_lines():
    """The reference's mask written out, against the rule in words."""
    allowed = np.asarray(ref.allowed(
        jnp.arange(2 * T), jnp.arange(2 * T), T, B
    ))
    for i in (0, 5, 64, T - 1):
        blk = i // B
        own = np.arange(blk * B, (blk + 1) * B)
        # a noised query: its own noised block, both directions, and the
        # clean blocks strictly before it
        assert set(np.flatnonzero(allowed[i])) == set(own) | set(
            T + np.arange(blk * B))
        # a clean query: the clean blocks up to and with its own
        assert set(np.flatnonzero(allowed[T + i])) == set(
            T + np.arange((blk + 1) * B))
    assert allowed.sum() == T * T + 4 * T == ref.allowed_pairs(
        dict(TINY, sample_tokens=T))
    assert allowed[np.arange(2 * T), np.arange(2 * T)].all()  # its own key


def test_shares_add_up_to_the_uncut_layer():
    """The 8 ranges of 16 experts' outputs (the guide's shares test, at
    the tiny widths: 8 ranges of 2 of 16), summed, give the uncut
    reference's whole layer: every chip's router is the same, a chip adds
    its own experts' part and nothing else."""
    def layer(first, held):
        return SparseMoeBlock(
            16, 4, 32, 0, (first, held), True, jnp.float32, block_rows=16,
            score="softmax", expert_form="gated_silu",
        )

    x = jnp.asarray(np.random.default_rng(1).normal(size=(256, 64)), jnp.float32)
    whole = layer(0, 16)
    variables = whole.init(jax.random.PRNGKey(1), x)
    params = _perturbed(variables["params"], 3)
    model = dict(TINY, experts_first=0, experts_held=16)
    uncut = ref._experts(params, x, model)
    total = 0.0
    for first in range(0, 16, 2):
        part = {
            name: value if name == "gate" else value[first:first + 2]
            for name, value in params.items()
        }
        share = layer(first, 2)
        counters = share.init(jax.random.PRNGKey(0), x)[ROUTING_COLLECTION]
        y = share.apply({"params": part, ROUTING_COLLECTION: counters}, x)
        assert _rel(y, ref._experts(
            part, x, dict(model, experts_first=first, experts_held=2))) < 1e-5
        total = total + y
    assert _rel(total, uncut) < 1e-5
    assert _rel(whole.apply(
        {"params": params, ROUTING_COLLECTION: variables[ROUTING_COLLECTION]},
        x,
    ), uncut) < 1e-5


def test_sliced_vocabulary_is_the_whole_vocabularys_columns():
    """The slice's logits are the whole-vocabulary reference's columns:
    rows of the table and columns of the head, nothing else (the mask id
    is the slice's last id in both)."""
    apply, params, features, model = _built()
    whole = dict(model, vocab_size=4 * model["vocab_size"])
    rng = np.random.default_rng(7)
    v, d = model["vocab_size"], model["hidden_size"]
    grown = jax.tree.map(lambda a: a, params)
    grown["model"] = dict(params["model"], embed_tokens=jnp.concatenate([
        params["model"]["embed_tokens"],
        jnp.asarray(rng.normal(size=(3 * v, d)), jnp.float32),
    ]))
    grown["lm_head"] = jnp.concatenate([
        params["lm_head"],
        jnp.asarray(rng.normal(size=(d, 3 * v)), jnp.float32),
    ], axis=1)
    got = apply(params, features)["logits"]
    want = ref.forward(grown, features, whole)  # mask id: the slice's last
    assert want.shape[-1] == 4 * v
    assert _rel(got, want[..., :v]) < 1e-5


def test_clear_tokens_are_the_references_own_choice(
    program_and_reference, monkeypatch
):
    """`highest_clear` is `highest` where every expert layer's selection
    on the token's NOISED row is at least `CLEAR_MARGIN` of a logit from a
    tie IN THE REFERENCE, and the outputs `program` kept elsewhere."""
    program, reference, params, features, model = program_and_reference
    monkeypatch.setattr(ref, "CLEAR_MARGIN", 0.05)
    monkeypatch.setattr(ref, "_PROGRAM", {})
    with pytest.raises(ValueError):
        ref.forward(params, features, model, "highest_clear")
    theirs = np.asarray(program(params)["logits"], np.float32) + 1.0
    ref._PROGRAM["outputs"] = theirs
    got = np.asarray(ref.forward(params, features, model, "highest_clear"))
    highest = np.asarray(reference(params))
    margins = []
    with jax.default_matmul_precision("highest"):
        for row, noise in zip(features[0], features[1]):
            watch = ref._watch()
            ref.decoder(params, row, noise, model, watch=watch)
            margins.append(
                np.min(np.stack(watch["margins"]), axis=0)[:T]
            )
    clear = np.stack(margins) >= 0.05
    assert 0.05 < clear.mean() < 0.95
    np.testing.assert_array_equal(got[clear], highest[clear])
    np.testing.assert_array_equal(got[~clear], theirs[~clear])


def _shapes(module):
    return jax.tree.map(
        lambda a: a.shape,
        jax.eval_shape(
            module.init, jax.random.PRNGKey(0),
            SPEC.features_of(jnp.zeros((1, 64), jnp.int32)),
        )["params"],
    )


def test_parameter_names_and_shapes_are_the_sources():
    shapes = _shapes(zoo.custom_model(use_bf16=False, **_model_kwargs(TINY)))
    assert set(shapes) == {"model", "lm_head"}
    stack = shapes["model"]
    assert set(stack) == {"embed_tokens", "norm"} | {
        f"layers_{i}" for i in range(3)
    }
    d, hd, h, hkv = TINY["hidden_size"], TINY["head_dim"], 4, 2
    for i in range(3):
        layer = stack[f"layers_{i}"]
        assert set(layer) == {"input_layernorm", "self_attn",
                              "post_attention_layernorm", "mlp"}
        attn = layer["self_attn"]
        assert set(attn) == {"q_proj", "k_proj", "v_proj", "q_norm",
                             "k_norm", "o_proj"}
        assert attn["q_proj"]["kernel"] == (d, h * hd)
        assert attn["k_proj"]["kernel"] == attn["v_proj"]["kernel"] == (
            d, hkv * hd)
        assert attn["q_norm"]["weight"] == attn["k_norm"]["weight"] == (hd,)
        assert attn["o_proj"]["kernel"] == (h * hd, d)
        # the router over ALL 16 and the 8 held experts: nothing shared
        assert layer["mlp"] == {
            "gate": (d, 16), "experts_gate_proj": (8, d, 32),
            "experts_up_proj": (8, d, 32), "experts_down_proj": (8, 32, d),
        }
    assert not any(
        name.endswith("bias']") for name in (
            jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_leaves_with_path(shapes)
        )
    )


def test_published_depth_and_the_cut_are_the_same_code():
    """`num_hidden_layers` 48 with all 128 experts held and the whole
    vocabulary builds the published model (shapes only: the name's 30B);
    the source's rule builds a dense layer where it says so, and that
    layer is the reference's; what is not built says so."""
    whole = dict(_model_kwargs(CONFIG["model"]), num_hidden_layers=48,
                 experts_first=0, experts_held=128, vocab_size=151936,
                 mask_token_id=151935)
    shapes = jax.eval_shape(
        zoo.custom_model(**whole).init, jax.random.PRNGKey(0),
        SPEC.features_of(jnp.zeros((1, 16), jnp.int32)),
    )["params"]
    assert _size(shapes) == 30_532_122_624
    assert len(shapes["model"]) == 48 + 2
    # a job's flat flags carry the list as a/b/c
    flat = zoo.custom_model(mlp_only_layers="0/2", num_hidden_layers=3)
    assert flat.cfg.mlp_only_layers == (0, 2)
    assert [flat.cfg.dense(i) for i in range(3)] == [True, False, True]
    assert [zoo.custom_model(decoder_sparse_step=2).cfg.dense(i)
            for i in range(4)] == [True, False, True, False]
    stack = _shapes(flat)["model"]
    assert stack["layers_0"]["mlp"] == {
        name: {"kernel": shape} for name, shape in (
            ("gate_proj", (64, 128)), ("up_proj", (64, 128)),
            ("down_proj", (128, 64)),
        )
    }
    assert "gate" in stack["layers_1"]["mlp"]
    apply, params, features, model = _built(mlp_only_layers=[1])
    assert _rel(
        apply(params, features)["logits"], ref.forward(params, features, model)
    ) < 1e-5
    for bad in (dict(num_attention_heads=3), dict(no_such_key=1),
                dict(noise_per="block"), dict(predict_shift=True),
                dict(t_min=0.0)):
        with pytest.raises(ValueError):
            zoo.custom_model(**bad)
    with pytest.raises(ValueError, match="no block-diffusion mask"):
        _built(attn_impl="pallas")


# ---------------------------------------------------------------------------
# The noise as data
# ---------------------------------------------------------------------------


def _records(n, tokens=64, vocab=64, seed=3):
    return zoo.custom_data_reader(
        f"synthetic://lm?n={n}&len={tokens}&vocab={vocab}&seed={seed}"
    )


def _parsed(records, mode="training"):
    from elasticdl_tpu.data.dataset import Dataset

    return list(zoo.dataset_fn(
        Dataset.from_generator(lambda: iter(records)), mode, None
    ))


def test_the_same_record_meets_the_same_noise_wherever_it_is_read():
    """The draw is keyed by (`noise_seed`, the record's tokens) and by
    nothing else: two workers (two processes' worth of fresh module
    state), two tasks that hold the record at different places among
    other records, and another `noise_seed` (which does change it)."""
    import importlib

    zoo.custom_model(noise_seed=0)
    rng = np.random.default_rng(0)
    records = [
        (rng.integers(0, 64, 64).astype(np.int32),) * 2 for _ in range(12)
    ]
    first = {
        features[0].tobytes(): features
        for features, _ in _parsed(records[:8])
    }
    # another task: the last four of those records among four others, in
    # another order, read by "another worker" (the module loaded afresh)
    fresh = importlib.reload(importlib.import_module("model_zoo.sdar.sdar_lm"))
    try:
        fresh.custom_model(noise_seed=0)
        from elasticdl_tpu.data.dataset import Dataset

        again = list(fresh.dataset_fn(
            Dataset.from_generator(lambda: iter(records[:3:-1])),
            "training", None,
        ))
    finally:
        importlib.reload(fresh)  # (the zoo module other tests hold)
    met = 0
    for (tokens, mask, t), label in again:
        np.testing.assert_array_equal(label, tokens)
        if tokens.tobytes() in first:
            met += 1
            _, want_mask, want_t = first[tokens.tobytes()]
            np.testing.assert_array_equal(mask, want_mask)
            assert t == want_t
    assert met == 4
    # evaluation reads the same noise; another seed another
    for (tokens, mask, t), _ in _parsed(records[:8], "evaluation"):
        np.testing.assert_array_equal(mask, first[tokens.tobytes()][1])
    zoo.custom_model(noise_seed=1)
    other = _parsed(records[:8])
    assert any(
        not np.array_equal(mask, first[tokens.tobytes()][1])
        for (tokens, mask, _), _ in other
    )
    zoo.custom_model(noise_seed=0)
    mask, t = zoo.record_noise(records[0][0], 0, 1e-3)
    assert mask.dtype == bool and mask.shape == (64,) and t.dtype == np.float32


def test_noise_has_the_stated_distribution():
    """Over 4,096 records the mean of `t` and the masked share are within
    1% of 1/2 (t ~ U(t_min, 1], each position masked with probability t),
    every t lies in (t_min, 1], and a record's masked share follows its
    t."""
    rng = np.random.default_rng(1)
    records = rng.integers(0, 64, size=(4096, 256)).astype(np.int32)
    drawn = [zoo.record_noise(record, 7, 1e-3) for record in records]
    t = np.asarray([level for _, level in drawn], np.float64)
    share = np.asarray([mask.mean() for mask, _ in drawn])
    assert t.min() > 1e-3 and t.max() <= 1.0
    assert abs(t.mean() - 0.5005) < 0.01 * 0.5 * 1.5  # sd/sqrt(n) = 0.0045
    assert abs(share.mean() - t.mean()) < 0.005
    assert np.abs(share - t).max() < 0.15          # 256 draws a record
    assert np.corrcoef(share, t)[0, 1] > 0.99
    # U(t_min, 1]: a quarter of the records under a quarter, and so on
    for q in (0.25, 0.5, 0.75):
        assert abs(np.mean(t <= q) - q) < 0.03
    # the reference's own draw states the same distribution
    _, mask, level = ref.sample(3, 4096, dict(TINY, sample_tokens=256))
    assert abs(level.mean() - 0.5) < 0.0075 and abs(mask.mean() - 0.5) < 0.0075
    assert level.min() > 1e-3 and level.max() <= 1.0


def test_a_task_requeued_after_a_kill_trains_on_identical_features():
    """The master's own queue: a worker takes a task and dies without
    reporting; the task times out and is handed to another worker, whose
    `dataset_fn` (module state afresh, as in a new process) gives the
    records' features to the bit: tokens, mask and t."""
    import time

    from elasticdl_tpu.data.dataset import Dataset
    from elasticdl_tpu.master.task_manager import TaskManager

    reader = _records(16)
    manager = TaskManager(
        training_shards=reader.create_shards(), records_per_task=8,
        task_timeout_s=0.05,
    )

    def features_of(task, module):
        module.custom_model(noise_seed=0)
        return list(module.dataset_fn(
            Dataset.from_generator(lambda: reader.read_records(task)),
            "training", None,
        ))

    task = manager.get(0)
    seen = features_of(task, zoo)
    # the worker is killed: no report; the next `get` sweeps the timed-out
    # task back and hands it to another worker
    time.sleep(0.1)
    again = manager.get(1)
    assert (again.shard_name, again.start, again.end) == (
        task.shard_name, task.start, task.end)
    assert manager.report(task.task_id, True, 0) is False  # the stale one
    import importlib

    fresh = importlib.reload(importlib.import_module("model_zoo.sdar.sdar_lm"))
    try:
        retrained = features_of(again, fresh)
    finally:
        importlib.reload(fresh)
    assert len(retrained) == len(seen) == 8
    for ((tokens, mask, t), label), ((tokens2, mask2, t2), label2) in zip(
        seen, retrained
    ):
        np.testing.assert_array_equal(tokens, tokens2)
        np.testing.assert_array_equal(mask, mask2)
        assert t == t2
        np.testing.assert_array_equal(label, label2)
