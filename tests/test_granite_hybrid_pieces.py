"""Granite 4.0-H, what is the model's alone (ISSUE 38): each of the four
multipliers and the tie is held by the reference (a program that leaves one
out reads far from it, and the reference with the same fault planted IS
that program), the two planted faults every run of the cell reports are the
usual conventions, the tied table is one leaf with both gradients, and the
traced step carries the scopes and one pair of moments.  The model against
its reference is `tests/test_granite_hybrid.py`, its descriptor
`tests/spec_granite_hybrid.py` (a file of the model's own, so that `--dist
loadfile` gives each a worker).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lm_contract import (  # noqa: F401  (the fixture is built from `SPEC`)
    _model_kwargs, _perturbed, _rel, program_and_reference,
)
from spec_granite_hybrid import SPEC, TINY, ref, zoo


def _program(model, tokens, **changed):
    module = zoo.custom_model(
        use_bf16=False, **dict(_model_kwargs(model), **changed)
    )
    return lambda p: module.apply({"params": p}, tokens)



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
    program, reference, params, tokens, _ = program_and_reference
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
    program, _, params, tokens, _ = program_and_reference
    planted = ref.forward(params, tokens, TINY, fault)
    assert _rel(program(params), planted) > 1e-3
    usual = _program(TINY, tokens, **{piece: USUAL[piece]})(params)
    assert _rel(usual, planted) < 1e-5


def test_tied_table_is_one_leaf_with_both_gradients(program_and_reference):
    """`embed_tokens` is ONE leaf and there is no `lm_head`; its gradient
    is the gather's scatter-add plus the head's matmul, each taken alone
    from the reference with the two readings of the table held apart."""
    _, _, params, _, _ = program_and_reference
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


def test_traced_step_carries_the_scopes_and_one_pair_of_moments():
    """The compiled two-step window program names `ssm` > `ssm_scan`,
    `attn`, `mlp` and `lm_head_loss` on its ops (what the benchmark's
    readers sum), and the optimizer's state holds the tied table once."""
    trainer, model = SPEC.trainer()
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
