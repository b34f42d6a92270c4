"""`layers/moe.py`: the expert layer that holds a range of the experts, as
each model of the zoo configures it, against the plain references that
decide the benchmark cells' `correct` (`perfbench/configs/*_reference.py`,
which share no code with the program).

Five routers, one a model (`ROUTERS`): Qwen3-Next's renormalised softmax
over gated-SiLU experts with a gated shared expert; Nemotron-H's sigmoid
scores with a selection bias over two-product relu^2 experts; DeepSeek-V2's
softmax left unrenormalised with an ungated shared expert and the
sequence-wise balancing loss; Laguna's sigmoid scores with a selection bias
over gated-SiLU experts; Mellum 2's renormalised softmax WITH the balancing
loss over gated-SiLU experts and nothing beside them (`shared_width` 0).
What holds for every router is one case
parametrised by it; what one router alone has stands beside it.  Tiny
sizes, seeded random weights, float32 on the CPU: 1e-5 of the outputs'
size, gradients 1e-4 of each leaf's largest entry.
"""

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.layers import moe
from elasticdl_tpu.layers.moe import (
    ROUTING_COLLECTION, RoutingLedger, SparseMoeBlock,
)
from lm_contract import _config, _perturbed, _reference, _rel
from model_zoo.lm_common import balancing_adamw
# the whole model, where a case needs the trainer's state
from spec_qwen3_next import SPEC as QWEN_MODEL


@dataclasses.dataclass(frozen=True)
class Router:
    """The expert layer as one model configures it: `build(moe, first,
    held, block_rows, alpha)` the layer that holds experts `first ..
    first + held`, `moe` the widths the reference's `_experts` reads."""

    ref: Any
    moe: dict
    build: Callable
    block_rows: Optional[int]   # what the model's own cases told the loop
    perturbed: bool             # move the leaves off 0 and 1 first
    #: where the router's weight [hidden, experts] is among the parameters
    gate: tuple = ("gate",)
    scores: Callable = staticmethod(lambda logits: jax.nn.softmax(logits, -1))
    #: whether anything stands beside the routed experts (`shared_width` > 0)
    shared: bool = True
    #: the key of `moe` that carries the balancing loss's alpha
    alpha_key: str = "aux_loss_alpha"

    def layer(self, first, held, block_rows=-1, alpha=0.0):
        if block_rows == -1:
            block_rows = self.block_rows
        return self.build(self.moe, first, held, block_rows, alpha)

    def params(self, seed=0):
        """The whole layer's parameters, all eight experts held."""
        x = jnp.zeros((4, self.moe["hidden_size"]), jnp.float32)
        params = self.layer(0, 8).init(jax.random.PRNGKey(seed), x)["params"]
        return _perturbed(params, seed + 1) if self.perturbed else params

    @staticmethod
    def share(params, first, held):
        """The parameters one chip of the layer holds."""
        return {
            k: v[first:first + held] if k.startswith("experts_") else v
            for k, v in params.items()
        }

    def apply(self, params, x, first, held, block_rows=-1, alpha=0.0):
        """-> (the share's output, its routing counters after one call)."""
        layer = self.layer(first, held, block_rows, alpha)
        zeros = layer.init(jax.random.PRNGKey(0), x[:2])[ROUTING_COLLECTION]
        y, state = layer.apply(
            {"params": self.share(params, first, held),
             ROUTING_COLLECTION: zeros},
            x, mutable=[ROUTING_COLLECTION],
        )
        return y, state[ROUTING_COLLECTION]

    def gate_weight(self, params):
        for key in self.gate:
            params = params[key]
        return params

    def with_gate_column(self, params, column, value):
        """`params` with one expert's column of the router set."""
        weight = self.gate_weight(params).at[:, column].set(value)
        if self.gate == ("gate",):
            return dict(params, gate=weight)
        return dict(params, gate=dict(params["gate"], weight=weight))

    def experts(self, params, x, first, held):
        """The reference's reading of the share `first .. first + held`."""
        return self.ref._experts(
            params, x, dict(self.moe, experts_first=first, experts_held=held)
        )


def _tiny(cell, sample_tokens):
    return dict(_config(cell)["rehearse"]["model"],
                sample_tokens=sample_tokens, experts_first=0, experts_held=8)


QWEN = Router(
    _reference("qwen3_next_reference.py"),
    dict(num_experts=8, num_experts_per_tok=2, moe_intermediate_size=16,
         shared_expert_intermediate_size=16, norm_topk_prob=True,
         hidden_size=32),
    lambda m, first, held, block_rows, alpha: SparseMoeBlock(
        m["num_experts"], m["num_experts_per_tok"],
        m["moe_intermediate_size"], m["shared_expert_intermediate_size"],
        (first, held), True, jnp.float32, block_rows,
    ),
    block_rows=128, perturbed=False,
)
NEMOTRON = Router(
    _reference("nemotron_h_reference.py"),
    dict(n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=16,
         moe_shared_expert_intermediate_size=24, norm_topk_prob=True,
         routed_scaling_factor=2.5, hidden_size=32),
    lambda m, first, held, block_rows, alpha: SparseMoeBlock(
        m["n_routed_experts"], m["num_experts_per_tok"],
        m["moe_intermediate_size"], m["moe_shared_expert_intermediate_size"],
        (first, held), True, jnp.float32, block_rows, score="sigmoid",
        expert_form="relu2", routed_scale=m["routed_scaling_factor"],
    ),
    block_rows=128, perturbed=False, gate=("gate", "weight"),
    scores=jax.nn.sigmoid,
)
DEEPSEEK = Router(
    _reference("deepseek_v2_reference.py"),
    _tiny("deepseek-v2-lite.json", 80),
    lambda m, first, held, block_rows, alpha: SparseMoeBlock(
        m["n_routed_experts"], m["num_experts_per_tok"],
        m["moe_intermediate_size"],
        m["n_shared_experts"] * m["moe_intermediate_size"],
        (first, held), False, jnp.float32, block_rows=block_rows,
        shared_gated=False, balance_alpha=alpha,
    ),
    block_rows=16, perturbed=True,
)
LAGUNA = Router(
    _reference("laguna_reference.py"),
    _tiny("laguna-xs.2.json", 256),
    lambda m, first, held, block_rows, alpha: SparseMoeBlock(
        m["num_experts"], m["num_experts_per_tok"],
        m["moe_intermediate_size"], m["shared_expert_intermediate_size"],
        (first, held), True, jnp.float32, block_rows=block_rows,
        score="sigmoid", expert_form="gated_silu",
        routed_scale=m["moe_routed_scaling_factor"], shared_gated=False,
    ),
    block_rows=16, perturbed=True, gate=("gate", "weight"),
    scores=jax.nn.sigmoid,
)
MELLUM = Router(
    _reference("mellum_reference.py"),
    _tiny("mellum2-12b-a2.5b.json", 256),
    lambda m, first, held, block_rows, alpha: SparseMoeBlock(
        m["num_experts"], m["num_experts_per_tok"],
        m["moe_intermediate_size"], 0, (first, held), True, jnp.float32,
        block_rows=block_rows, score="softmax", expert_form="gated_silu",
        balance_alpha=alpha,
    ),
    block_rows=16, perturbed=True, shared=False, alpha_key="balance_alpha",
)
ROUTERS = {"qwen3-next": QWEN, "nemotron-h": NEMOTRON,
           "deepseek-v2": DEEPSEEK, "laguna": LAGUNA, "mellum": MELLUM}


def _normal(seed, *shape):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape), jnp.float32
    )


# ---------------------------------------------------------------------------
# Every router: the shares, a skewed router, the gradients
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("router,held", [
    pytest.param(name, held, id=f"{name}-{held}")
    for name in ROUTERS
    for held in ((1, 4, 8) if name == "deepseek-v2" else (1, 2, 4, 8))
])
def test_shares_add_up_to_the_uncut_layer(router, held):
    """What all the shares of a layer give, the shared expert counted
    once, is what the reference gives for the whole layer (the
    model-configs guide's section 4: a cut that every chip makes alike
    must add up)."""
    r = ROUTERS[router]
    params = r.params()
    x = _normal(held, 200, r.moe["hidden_size"])
    uncut, shared = r.experts(params, x, 0, 8), r.experts(params, x, 0, 0)
    routed = sum(
        r.apply(params, x, first, held)[0] - shared
        for first in range(0, 8, held)
    )
    assert _rel(routed + shared, uncut) < 1e-5
    assert _rel(shared, uncut) > 0.05  # the routed part is in the sum
    # `shared_width` 0: no leaf, no op, and the layer IS the routed sum
    layer = r.layer(0, held)
    variables = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    lowered = jax.jit(lambda v, x: layer.apply(v, x)).lower(variables, x)
    assert ("moe_shared" in lowered.as_text(debug_info=True)) == r.shared
    assert any("shared" in key for key in params) == r.shared
    assert bool(np.asarray(shared).any()) == r.shared
    # and one share alone is the reference's same share
    one = r.experts(r.share(params, 8 - held, held), x, 8 - held, held)
    assert _rel(r.apply(params, x, 8 - held, held)[0], one) < 1e-5


@pytest.mark.parametrize("router,block_rows,tokens", [
    pytest.param("qwen3-next", 128, 300, id="qwen3-next-128"),
    pytest.param("qwen3-next", 16, 300, id="qwen3-next-16"),
    pytest.param("mellum", 128, 300, id="mellum-128"),
] + [
    pytest.param("nemotron-h", block, tokens, id=f"nemotron-h-{block}-{tokens}")
    for block, tokens in ((128, 300), (16, 300), (512, 300), (512, 600),
                          (None, 300))
])
def test_no_pair_dropped_and_counters_right_under_a_skewed_router(
    router, block_rows, tokens
):
    """Every token chooses ONE held expert (held range 2..5, expert 3):
    300 pairs on one expert, more than two blocks of 128 and less than
    one of 512; 600, more than one of 512.  Told no block, the layer
    takes the shapes' (300 x 2 / 8 = 75 pairs an expert: 128)."""
    r = ROUTERS[router]
    x = jnp.abs(_normal(2, tokens, r.moe["hidden_size"])) + 0.1
    params = r.with_gate_column(r.params(1), 3, 4.0)
    y, counters = r.apply(params, x, 2, 4, block_rows)
    assert _rel(y, r.experts(r.share(params, 2, 4), x, 2, 4)) < 1e-5
    _, ids = jax.lax.top_k(r.scores(x @ r.gate_weight(params)), 2)
    assert bool(jnp.all(jnp.any(ids == 3, axis=-1)))
    load = np.bincount(np.asarray(ids).ravel(), minlength=8)[2:6]
    assert load[1] == tokens
    np.testing.assert_array_equal(np.asarray(counters["load"]), load)
    assert int(counters["pairs"]) == int(counters["processed"]) == load.sum()
    block = block_rows or 128
    blocks = int(np.ceil(load / block).sum())
    assert int(counters["blocks"]) == blocks
    # the worker's per-task reading of the same counters
    state = {ROUTING_COLLECTION: {"layers_1": {"mixer": counters}}}
    ledger = RoutingLedger()
    ledger.seed_once({})
    assert ledger.task_delta(state) == {
        "layers": 1, "held": 4, "pairs": int(load.sum()), "dropped": 0,
        "blocks": blocks, "block_rows": block,
        "load_max": tokens, "load_mean": float(load.mean()),
    }
    again = ledger.task_delta(state)
    assert again["pairs"] == 0 and again["load_max"] == 0
    assert again["blocks"] == 0 and again["block_rows"] == block


@pytest.mark.parametrize("router,block_rows,tokens", [
    pytest.param("qwen3-next", 32, 150, id="qwen3-next"),
    pytest.param("mellum", 32, 150, id="mellum"),
    pytest.param("mellum", 128, 700, id="mellum-128-700"),
] + [
    pytest.param("nemotron-h", block, tokens, id=f"nemotron-h-{block}-{tokens}")
    for block, tokens in ((32, 150), (16, 700), (128, 700), (512, 700))
])
def test_expert_layer_gradients_match_the_reference(
    router, block_rows, tokens
):
    """The hand-written backward (three products, or two under relu^2)
    and the router's through its renormalised weights, whatever the
    block: 700 tokens and a router column that makes held expert 3 every
    token's choice give one expert more than a block of 512 and the
    others a part of one."""
    r = ROUTERS[router]
    params = r.params(3)
    x = _normal(3, tokens, r.moe["hidden_size"])
    if tokens == 700:
        x = jnp.abs(x) + 0.1
        params = r.with_gate_column(params, 3, 0.5)
    model = dict(r.moe, experts_first=2, experts_held=4)
    share = r.share(params, 2, 4)
    layer = r.layer(2, 4, block_rows)
    zeros = layer.init(jax.random.PRNGKey(0), x[:2])[ROUTING_COLLECTION]
    weight = _normal(4, *x.shape)

    def program(p, x):
        return jnp.sum(weight * layer.apply(
            {"params": p, ROUTING_COLLECTION: zeros}, x
        ))

    def reference(p, x):
        return jnp.sum(weight * r.ref._experts(p, x, model))

    got = jax.grad(program, (0, 1))(share, x)
    want = jax.grad(reference, (0, 1))(share, x)
    flat_want = jax.tree.leaves(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), flat_want):
        scale = float(jnp.abs(w).max())
        if scale == 0:  # the selection bias: no gradient, the violation
            assert "e_score_correction_bias" in jax.tree_util.keystr(path)
            chosen = []
            r.ref._experts(share, x, model, chosen=chosen)
            count = np.bincount(np.asarray(chosen[0]).reshape(-1), minlength=8)
            np.testing.assert_array_equal(g, np.sign(count - count.mean()))
            continue
        assert float(jnp.abs(g - w).max()) < 1e-4 * scale, (
            jax.tree_util.keystr(path)
        )


# ---------------------------------------------------------------------------
# The block of the expert loop (DeepSeek-V2's router)
# ---------------------------------------------------------------------------

# The four cells that run the layer, by one step's tokens, top-k and the
# router's width (`perfbench/configs/*.json`), and two ends of the rule.
@pytest.mark.parametrize("tokens,top_k,num_experts,want", [
    pytest.param(2 * 8192, 6, 64, 512, id="deepseek-v2-lite"),
    pytest.param(8192, 6, 128, 512, id="nemotron-3-nano"),
    pytest.param(2 * 8192, 10, 512, 512, id="qwen3-next"),
    pytest.param(8192, 8, 256, 256, id="laguna-xs.2"),
    pytest.param(80, 2, 8, 128, id="never-under-128"),
    pytest.param(65536, 8, 8, 512, id="never-over-512"),
    pytest.param(1028, 2, 8, 512, id="257-pairs-take-one-block-of-512"),
])
def test_block_rows_come_from_the_shapes(tokens, top_k, num_experts, want):
    """The smallest power of two that holds a uniform router's pairs an
    expert (1,536, 384, 320, 256 in the four cells), within [128, 512]."""
    assert moe.block_rows_for(tokens, top_k, num_experts) == want


@pytest.mark.parametrize("tokens,told,want", [
    (200, None, 128), (900, None, 256), (1100, None, 512), (2048, 16, 16),
])
def test_layer_takes_the_shapes_block_unless_it_is_told_one(
    tokens, told, want
):
    """8 experts, 2 a token: 900 tokens are 225 pairs an expert, 1100
    are 275; the `block_rows` counter says what the loop ran with."""
    params = DEEPSEEK.params()
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(tokens, DEEPSEEK.moe["hidden_size"])),
        jnp.float32,
    )
    layer = DEEPSEEK.layer(2, 4, told)
    zeros = layer.init(jax.random.PRNGKey(0), x[:2])[ROUTING_COLLECTION]
    assert int(zeros["block_rows"]) == int(zeros["blocks"]) == 0
    y, counted = layer.apply(
        {"params": DEEPSEEK.share(params, 2, 4), ROUTING_COLLECTION: zeros},
        x, mutable=[ROUTING_COLLECTION],
    )
    counted = counted[ROUTING_COLLECTION]
    assert int(counted["block_rows"]) == want
    load = np.asarray(counted["load"], np.int64)
    assert int(counted["blocks"]) == int(np.ceil(load / want).sum())
    want_y = DEEPSEEK.experts(DEEPSEEK.share(params, 2, 4), x, 2, 4)
    assert _rel(y, want_y) < 1e-5



# Held experts 2..5 under a router that is told its choice: none, exactly
# one block of 512 (and whole blocks of 128 and 16), more than 512, and
# a part of any block.
DICTATED_LOADS = (0, 512, 600, 37)


def _dictated(loads, tokens, seed):
    """(x [tokens, d], a router weight) such that held expert 2 + h is
    chosen by exactly `loads[h]` tokens: x's first 8 columns are the
    logits (chosen 2 to 2.5, an expert held elsewhere -0.5 to 0.5, a held
    one not chosen under -2) and the router is the identity on them."""
    rng = np.random.default_rng(seed)
    experts, k, d = (
        DEEPSEEK.moe["n_routed_experts"], DEEPSEEK.moe["num_experts_per_tok"],
        DEEPSEEK.moe["hidden_size"],
    )
    logits = rng.uniform(-0.5, 0.5, size=(tokens, experts))
    logits[:, 2:6] = -2.0 - rng.uniform(0, 0.5, size=(tokens, 4))
    marks = np.zeros(tokens, np.int64)
    for h, load in enumerate(loads):
        chosen = rng.choice(np.flatnonzero(marks < k), load, replace=False)
        logits[chosen, 2 + h] = 2.0 + rng.uniform(0, 0.5, size=load)
        marks[chosen] += 1
    x = rng.normal(size=(tokens, d))
    x[:, :experts] = logits
    return jnp.asarray(x, jnp.float32), jnp.eye(d, experts, dtype=jnp.float32)


@pytest.fixture(scope="module")
def dictated():
    """The layer's output, counters and gradients (held parameters, the
    router among them, and x) at blocks of 16, 128 and 512, and the
    reference's, under `DICTATED_LOADS`."""
    x, router = _dictated(DICTATED_LOADS, 700, 7)
    share = dict(DEEPSEEK.share(DEEPSEEK.params(5), 2, 4), gate=router)
    model = dict(DEEPSEEK.moe, experts_first=2, experts_held=4)
    weight = jnp.asarray(
        np.random.default_rng(8).normal(size=x.shape), jnp.float32
    )

    def run(block_rows):
        layer = DEEPSEEK.layer(2, 4, block_rows)
        zeros = layer.init(jax.random.PRNGKey(0), x[:2])[ROUTING_COLLECTION]

        def loss(p, x):
            y, counted = layer.apply(
                {"params": p, ROUTING_COLLECTION: zeros}, x,
                mutable=[ROUTING_COLLECTION],
            )
            return jnp.sum(weight * y), (y, counted[ROUTING_COLLECTION])

        grads, (y, counted) = jax.grad(loss, (0, 1), has_aux=True)(share, x)
        return y, counted, grads

    def reference(p, x):
        return jnp.sum(weight * DEEPSEEK.ref._experts(p, x, model))

    return (
        {block: run(block) for block in (16, 128, 512)},
        DEEPSEEK.ref._experts(share, x, model),
        jax.grad(reference, (0, 1))(share, x),
    )


@pytest.mark.parametrize("block_rows", [16, 128, 512])
def test_any_block_gives_the_references_output_and_gradients(
    dictated, block_rows
):
    """Output, dx, the three weights' gradients and the pair weights'
    (which reach the router) do not depend on the block: each block's are
    the dense reference's, and the blocks' own agree closer still."""
    runs, want_y, want_grads = dictated
    y, _, grads = runs[block_rows]
    assert _rel(y, want_y) < 1e-5
    assert _rel(y, runs[16][0]) < 1e-6
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), w, g16 in zip(
        flat, jax.tree.leaves(want_grads), jax.tree.leaves(runs[16][2])
    ):
        scale = float(jnp.abs(w).max())
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.abs(g - w).max()) < 1e-4 * scale, (
            jax.tree_util.keystr(path)
        )
        assert float(jnp.abs(g - g16).max()) < 1e-5 * scale
    # the expert of no rows has no gradient, whatever the block
    assert float(jnp.abs(grads[0]["experts_up_proj"][0]).max()) == 0.0


@pytest.mark.parametrize("block_rows", [16, 128, 512])
def test_no_pair_dropped_and_blocks_counted_under_dictated_loads(
    dictated, block_rows
):
    _, counted, _ = dictated[0][block_rows]
    loads = np.asarray(DICTATED_LOADS)
    np.testing.assert_array_equal(np.asarray(counted["load"]), loads)
    assert int(counted["pairs"]) == int(counted["processed"]) == loads.sum()
    assert int(counted["blocks"]) == int(np.ceil(loads / block_rows).sum())
    assert int(counted["block_rows"]) == block_rows
    fields = RoutingLedger().task_delta(
        {ROUTING_COLLECTION: {"layers_1": {"mlp": counted}}}
    )
    assert fields["dropped"] == 0 and fields["pairs"] == loads.sum()
    assert fields["blocks"] == int(np.ceil(loads / block_rows).sum())
    assert fields["block_rows"] == block_rows


def test_weights_are_the_softmax_at_the_chosen_not_renormalised():
    """`norm_topk_prob: false`: a token's routing weights are p at its
    top-k and sum to less than 1; renormalised they would sum to 1."""
    params = DEEPSEEK.params(2)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(64, DEEPSEEK.moe["hidden_size"])),
        jnp.float32,
    )
    probs, ids, top = DEEPSEEK.ref._route(params, x, DEEPSEEK.moe)
    assert float(jnp.max(jnp.sum(top, -1))) < 0.9
    np.testing.assert_allclose(
        top, jnp.take_along_axis(probs, ids, axis=-1), rtol=1e-6
    )
    renormalised = DEEPSEEK.ref._experts(
        params, x, dict(DEEPSEEK.moe, norm_topk_prob=True)
    )
    got = DEEPSEEK.apply(params, x, 0, 8)[0]
    assert _rel(got, DEEPSEEK.ref._experts(params, x, DEEPSEEK.moe)) < 1e-5
    assert _rel(got, renormalised) > 0.05


# ---------------------------------------------------------------------------
# The balancing loss
# ---------------------------------------------------------------------------


def test_balance_loss_on_a_hand_made_routing():
    """Two sequences of 4 tokens, 4 experts, 2 a token.  Sequence 0
    chooses experts (0, 1) always: f = [2, 2, 0, 0]; with p uniform
    P = 1/4 each and sum f P = 1.  Sequence 1 spreads evenly: f = 1
    everywhere, sum f P = 1 for any p.  A router that favours what it
    chooses reads above 1."""
    uniform = jnp.full((8, 4), 0.25)
    expert = jnp.asarray(
        [[0, 1]] * 4 + [[0, 1], [2, 3], [0, 2], [1, 3]], jnp.int32
    )
    assert float(moe.sequence_balance_loss(uniform, expert, 2)) == (
        pytest.approx(1.0)
    )
    skewed = jnp.asarray([[0.4, 0.4, 0.1, 0.1]] * 4 + [[0.25] * 4] * 4)
    # sequence 0: 2 x 0.4 + 2 x 0.4 = 1.6; sequence 1: 1; mean 1.3
    assert float(moe.sequence_balance_loss(skewed, expert, 2)) == (
        pytest.approx(1.3)
    )
    # as ONE sequence of 8 tokens: f = [1.5, 1.5, .5, .5],
    # P = [.325, .325, .175, .175] -> 1.15
    assert float(moe.sequence_balance_loss(skewed, expert, 1)) == (
        pytest.approx(1.15)
    )
    model = dict(aux_loss_alpha=0.5)
    assert float(DEEPSEEK.ref.balance_loss(skewed[:4], expert[:4], model)) == (
        pytest.approx(0.8)
    )


@pytest.mark.parametrize("router", ["deepseek-v2", "mellum"])
def test_injected_gradient_is_the_explicit_sums(router):
    """The layer's output does not change with alpha and the router
    receives alpha x d(sum f P)/dW_r on top of its gradient; the counts
    are constants.  Whether the weights are renormalised (Mellum 2's) or
    not (DeepSeek-V2's), and whether a shared expert stands beside."""
    r = ROUTERS[router]
    alpha = 0.3
    params = r.params(5)
    x = jnp.asarray(
        np.random.default_rng(5).normal(size=(2, 40, r.moe["hidden_size"])),
        jnp.float32,
    )
    weight = jnp.asarray(
        np.random.default_rng(6).normal(size=x.shape), jnp.float32
    )

    def program(p, a):
        return jnp.sum(weight * r.apply(p, x, 0, 8, alpha=a)[0])

    np.testing.assert_array_equal(
        r.apply(params, x, 0, 8, alpha=alpha)[0],
        r.apply(params, x, 0, 8)[0],
    )

    def explicit(p):
        balance = 0.0
        for row in x:
            probs, ids, *_ = r.ref._route(p, row, r.moe)
            balance += r.ref.balance_loss(
                probs, ids, {r.alpha_key: alpha}
            ) / len(x)
        return balance

    with_loss = jax.grad(program)(params, alpha)
    without = jax.grad(program)(params, 0.0)
    added = jax.grad(explicit)(params)
    for key in params:
        extra = jax.tree.map(lambda a, b: a - b, with_loss[key], without[key])
        for got, want in zip(jax.tree.leaves(extra),
                             jax.tree.leaves(added[key])):
            if key == "gate":
                scale = float(jnp.abs(want).max())
                assert scale > 0
                # (the difference of two gradients ten times its size)
                assert float(jnp.abs(got - want).max()) < 5e-3 * scale
            else:  # the loss needs only the router
                assert float(jnp.abs(want).max()) == 0
                assert float(jnp.abs(got).max()) < 1e-6
    # the routing collection counts the loss; the ledger gives the mean
    _, state = r.apply(params, x, 0, 8, alpha=alpha)
    counted = float(state["balance"])
    assert counted == pytest.approx(float(explicit(params)), rel=1e-5)
    ledger = RoutingLedger()
    ledger.seed_once({})
    fields = ledger.task_delta(
        {ROUTING_COLLECTION: {"layers_1": {"mlp": state}}},
        steps=2,
    )
    assert fields["balance_loss"] == pytest.approx(counted / 2, rel=1e-6)
    assert fields["dropped"] == 0 and fields["layers"] == 1


def _program_text(module, tokens):
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0), tokens)

    def fwd_bwd(variables):
        def total(params):
            out, state = module.apply(
                {**variables, "params": params}, tokens,
                mutable=[ROUTING_COLLECTION],
            )
            return jnp.sum(out), state

        return jax.grad(total, has_aux=True)(variables["params"])

    return str(jax.make_jaxpr(fwd_bwd)(variables))


def test_alpha_zero_traces_no_extra_op_into_the_other_models():
    """Qwen3-Next and Nemotron-H tell their expert layers no alpha: their
    programs (forward and backward, counters included) are op for op
    what an expert layer WITHOUT the balancing code traces, and hold no
    `balance` counter."""
    from model_zoo.nemotron_h import nemotron_h_lm as nemotron
    from model_zoo.qwen3_next import qwen3_next_lm as qwen

    tokens = jnp.zeros((2, 32), jnp.int32)
    for module in (
        qwen.custom_model(use_bf16=False, num_hidden_layers=2,
                          full_attention_interval=2),
        nemotron.custom_model(use_bf16=False, hybrid_override_pattern="ME*E",
                              chunk_size=32),
    ):
        text = _program_text(module, tokens)

        def refuse(*_):
            raise AssertionError("the balancing loss was traced")

        saved = (moe.sequence_balance_loss, moe._with_auxiliary_loss)
        moe.sequence_balance_loss = moe._with_auxiliary_loss = refuse
        try:
            assert _program_text(module, tokens) == text
        finally:
            moe.sequence_balance_loss, moe._with_auxiliary_loss = saved
        state = jax.eval_shape(module.init, jax.random.PRNGKey(0), tokens)
        names = {
            jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_leaves_with_path(state[ROUTING_COLLECTION])
        }
        assert names and not any("balance" in name for name in names)
    with pytest.raises(ValueError):
        SparseMoeBlock(
            8, 2, 16, 16, (0, 8), score="sigmoid", expert_form="relu2",
            balance_alpha=0.1,
        ).init(jax.random.PRNGKey(0), jnp.zeros((4, 8)))



# ---------------------------------------------------------------------------
# Sigmoid scores and a selection bias (Nemotron-H's router)
# ---------------------------------------------------------------------------


def test_sigmoid_layer_has_the_sources_parameters_and_no_third_product():
    params = NEMOTRON.params()
    assert set(params) == {
        "gate", "experts_up_proj", "experts_down_proj", "shared_experts",
    }
    assert set(params["gate"]) == {"weight", "e_score_correction_bias"}
    assert set(params["shared_experts"]) == {"up_proj", "down_proj"}
    with pytest.raises(ValueError):
        SparseMoeBlock(8, 2, 16, 16, (0, 8), score="sigmoid_relu2").init(
            jax.random.PRNGKey(0), jnp.zeros((4, 32))
        )



def test_selection_bias_changes_the_choice_and_not_the_weights():
    """A bias of +10 on expert 5 puts it among every token's two; its
    weight there is still its own sigmoid score over the two scores' sum
    times 2.5, which the bias never enters."""
    params = jax.tree.map(lambda a: a, NEMOTRON.params(4))
    x = jnp.asarray(
        np.random.default_rng(5).normal(size=(64, NEMOTRON.moe["hidden_size"])),
        jnp.float32,
    )
    scores = jax.nn.sigmoid(x @ params["gate"]["weight"])
    _, plain = jax.lax.top_k(scores, 2)
    assert not bool(jnp.all(jnp.any(plain == 5, axis=-1)))
    biased = dict(params, gate=dict(
        params["gate"],
        e_score_correction_bias=jnp.zeros((8,)).at[5].set(10.0),
    ))
    _, counters = NEMOTRON.apply(biased, x, 5, 1)
    assert int(counters["pairs"]) == 64        # every token chose expert 5
    # the other chosen expert is each token's best of the rest
    rest = jnp.argmax(scores.at[:, 5].set(-1.0), axis=-1)
    weight5 = 2.5 * scores[:, 5] / (
        scores[:, 5] + jnp.take_along_axis(scores, rest[:, None], 1)[:, 0]
    )
    up, down = params["experts_up_proj"][5], params["experts_down_proj"][5]
    want = weight5[:, None] * (jnp.square(jax.nn.relu(x @ up)) @ down)
    shared = NEMOTRON.experts(biased, x, 0, 0)
    got, _ = NEMOTRON.apply(biased, x, 5, 1)
    assert _rel(got - shared, want) < 1e-5
    # and the reference reads the biased layer the same way
    one = NEMOTRON.experts(NEMOTRON.share(biased, 5, 1), x, 5, 1)
    assert _rel(got, one) < 1e-5



def test_balancing_rule_brings_a_starved_expert_back():
    """A router whose weights keep expert 5 out of every token's choice:
    the rule alone, the weights frozen (lr 0), raises 5's bias a step at a
    time until it carries its share."""
    layer = NEMOTRON.layer(0, 8)
    params = NEMOTRON.params(4)
    # every input is positive, so a column of -0.02 scores about 0.38
    # for every token, under each token's two best of the other seven
    starved = params["gate"]["weight"].at[:, 5].set(-0.02)
    params = dict(params, gate=dict(params["gate"], weight=starved))
    x = jnp.abs(jnp.asarray(
        np.random.default_rng(6).normal(size=(256, NEMOTRON.moe["hidden_size"])),
        jnp.float32,
    ))
    routing = layer.init(jax.random.PRNGKey(0), x[:2])[ROUTING_COLLECTION]
    tx = balancing_adamw(lr=0.0, bias_update_rate=5e-3)
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


# ---------------------------------------------------------------------------
# The routing counters: a task's share of them, evaluation, a checkpoint
# older than a counter
# ---------------------------------------------------------------------------


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



def test_counters_stand_still_in_evaluation():
    params = QWEN.params()
    x = jnp.ones((8, QWEN.moe["hidden_size"]), jnp.float32)
    layer = QWEN.layer(0, 8)
    zeros = layer.init(jax.random.PRNGKey(0), x)[ROUTING_COLLECTION]
    assert int(zeros["pairs"]) == 0  # init counts nothing
    layer.apply({"params": params, ROUTING_COLLECTION: zeros}, x)  # immutable



def test_checkpoint_older_than_the_block_counters_restores_them_at_zero(
    tmp_path,
):
    """A `routing` collection saved before the layer counted `blocks` and
    `block_rows` (ISSUE 40) restores with both at zero and the counters it
    had as they were; the restored trainer trains on and counts."""
    from elasticdl_tpu.checkpoint import CheckpointSaver
    from flax.traverse_util import flatten_dict, unflatten_dict

    trainer, model = QWEN_MODEL.trainer()
    tokens = QWEN_MODEL.ref.sample(11, 4, model)
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
    fresh, _ = QWEN_MODEL.trainer()
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

    trainer, model = QWEN_MODEL.trainer()
    tokens = QWEN_MODEL.ref.sample(11, 4, model)
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
    fresh, _ = QWEN_MODEL.trainer()
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
    broken, _ = QWEN_MODEL.trainer()
    broken.set_sharded_restore(ShardedCheckpointSaver(str(tmp_path)), 1)
    with pytest.raises(KeyError, match="missing leaf"):
        broken.ensure_initialized(tokens)



# ---------------------------------------------------------------------------
# Sigmoid scores over gated-SiLU experts (Laguna's router)
# ---------------------------------------------------------------------------


def test_third_pairing_has_a_biased_gate_and_three_products():
    params = LAGUNA.params()
    assert set(params) == {
        "gate", "experts_gate_proj", "experts_up_proj", "experts_down_proj",
        "shared_experts",
    }
    assert set(params["gate"]) == {"weight", "e_score_correction_bias"}
    assert set(params["shared_experts"]) == {
        "gate_proj", "up_proj", "down_proj"
    }
    for bad in (dict(score="tanh"), dict(expert_form="gelu")):
        with pytest.raises(ValueError):
            SparseMoeBlock(8, 2, 16, 16, (0, 8), **bad).init(
                jax.random.PRNGKey(0), jnp.zeros((4, 32))
            )



def test_weights_are_the_sigmoids_renormalised_and_scaled():
    """w = 2.5 s_chosen / sum(s_chosen): a token's weights add up to 2.5
    whatever its scores, and the bias chooses without entering them."""
    params = LAGUNA.params(4)
    x = jnp.asarray(
        np.random.default_rng(5).normal(size=(64, LAGUNA.moe["hidden_size"])),
        jnp.float32,
    )
    scores = jax.nn.sigmoid(x @ params["gate"]["weight"])
    _, ids = jax.lax.top_k(
        scores + params["gate"]["e_score_correction_bias"], 2
    )
    top = jnp.take_along_axis(scores, ids, axis=-1)
    weights = 2.5 * top / top.sum(-1, keepdims=True)
    want = jnp.zeros_like(x)
    for e in range(8):
        w = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        hidden = jax.nn.silu(x @ params["experts_gate_proj"][e]) * (
            x @ params["experts_up_proj"][e]
        )
        want = want + w[:, None] * (hidden @ params["experts_down_proj"][e])
    shared = LAGUNA.experts(params, x, 0, 0)
    got, counters = LAGUNA.apply(params, x, 0, 8)
    assert _rel(got - shared, want) < 1e-5
    assert int(counters["pairs"]) == int(counters["processed"]) == 128

