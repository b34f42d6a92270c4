"""A routed expert layer that holds a RANGE of the experts.

Expert parallelism divides a layer's experts over chips.  Each chip then
routes its tokens over ALL experts (the router keeps its published
width), computes the part of the result that ITS experts give for the
tokens routed to them, and adds what every chip computes alike (the
shared expert).  `SparseMoeBlock` is that layer for one chip: it is told
which experts it holds (`held = (first, count)`) and adds nothing that
stands in for the absent chips or their exchange.  With
`held = (0, num_experts)` it is the whole layer.  What varies between
architectures is said in fields.  `score` names the router's score
function and `expert_form` what an expert (and the shared expert)
computes; the two are independent:

    score="softmax"
    p = softmax(W_r x)  over all experts, float32
    top-k of p; with `norm_topk_prob` the weights are divided by their
    sum (over all k, held or not); times `routed_scale`

    score="sigmoid"
    s = sigmoid(W_r x)  over all experts, float32
    top-k of s + b (a selection bias: it chooses, and is in no weight)
    w = s at the chosen; with `norm_topk_prob` divided by their sum;
    times `routed_scale`

    `n_group` > 1: GROUP-LIMITED selection, either score function.  The
    experts are `n_group` runs of consecutive experts (in a deployment a
    group is the experts of a few chips, and a token's pairs then reach
    `topk_group` groups of chips, not all).  With the selection scores
    (s + b, or p): a group's score is the sum of its two largest; the
    `topk_group` best groups; the top-k inside them alone.  The weights
    are as above, of the chosen.  1 / 1: the top-k over all experts, the
    program it was before the fields existed.

    expert_form="gated_silu"    three products
    y = sum_k w_k * W_down,k (silu(W_gate,k x) * W_up,k x)   held k only
      + [sigmoid(w_sg . x) *] shared(x)     shared: the same gated form,
                                            where `shared_width` > 0

    expert_form="relu2"         two products
    y = sum_k w_k * W_down,k relu(W_up,k x)^2                held k only
      + W_down,s relu(W_up,s x)^2           the shared expert

The zoo's pairings:

    softmax + gated_silu    model_zoo/qwen3_next, deepseek_v2
    sigmoid + relu2         model_zoo/nemotron_h
    sigmoid + gated_silu    model_zoo/laguna
    softmax + gated_silu    model_zoo/mellum: renormalised, WITH the
                            balancing loss, and no shared expert

`shared_width` 0 is a layer with NOTHING beside its routed experts: no
shared module, no parameter, no `moe_shared` op (decided at trace time),
and a token none of whose choices is held here gets exactly 0 from it.
`shared_gated` says whether a sigmoid gate of the token multiplies the
shared expert (Qwen3-Next's does; Nemotron-H's, Laguna's and
DeepSeek-V2's, which is its `n_shared_experts` experts as ONE MLP of
their summed width, do not; where the field is None a softmax router's
is gated and a sigmoid router's is not, as their first sources have it).
The gated shared expert is the module `shared_expert` with
`shared_expert_gate`, the ungated one `shared_experts`, as in the
sources.  `balance_alpha` adds a softmax router's balancing loss (below).

All run the SAME block plan, loop, counters and scopes; the expert form
is two or three stacked weight tensors handed to one loop.

**A softmax router's balancing loss** (`balance_alpha` > 0; DeepSeek-V2's
`seq_aux`).  For each sequence of T tokens, `f_i` = (times expert i was
chosen in the sequence) E / (k T) and `P_i` = the mean of `p_i` over the
sequence; `L_bal = alpha * mean over sequences of sum_i f_i P_i`, over
ALL E experts (it needs only the router).  The counts are constants: the
gradient reaches the router through `P`.  As the source's
`AddAuxiliaryLoss`, the loss the trainer reports does not change:
`_with_auxiliary_loss` is the identity on the routing weights whose
backward pass hands `L_bal` a cotangent of 1, so its gradient is ADDED
to the router's and no trainer knows of it.  (A trainer that scales its
loss's cotangent would have to scale this one too; none here does.)  The
layer counts the loss in its ``routing`` collection (`balance`, a
float32 sum), and the task's mean rides on ``moe.routing``.

**The selection bias balances the load, and no loss does.**  `b` is in no
weight, so the loss has no gradient for it.  The source moves it by the
rule of DeepSeek-V3's router, whose `gate` this is (auxiliary-loss-free
balancing, arXiv:2408.15664): after a step, `b_e <- b_e + rate` for an
expert that was chosen less often than the mean of all experts and
`- rate` for one chosen more often.  `_load_violation` hands the
optimizer exactly that: in the backward pass the bias receives
`sign(chosen_e - mean)` over ALL experts (the router counts its own
top-k, held or not) IN PLACE OF a gradient, so plain gradient descent at
`rate` on this one parameter IS the rule (`model_zoo/nemotron_h`'s
`optimizer` does that and keeps AdamW off it).  Under data parallelism
the counts are the global batch's, as every chip's router must agree.

**No pair is dropped, whatever the imbalance.**  A capacity bound would
make shapes static by dropping; a dense product over a worst-case
[tokens x k] slab would cost 32 times the useful work when one chip of 32
holds the experts.  Here the (token, expert) pairs of held experts are
sorted by expert and cut into blocks of `block_rows` rows, each block one
expert's; a loop whose trip count is the number of blocks the routing
NEEDED (a device-side `while`, not a static bound) gathers a block's
rows, runs the expert's products and scatter-adds the weighted
result.  Work is the pairs held, rounded up to a block an expert.  Sort
was chosen over `lax.ragged_dot` because the latter needs a static row
count, which without dropping is tokens x k.  A dynamic trip count has
no reverse-mode rule, so `grouped_expert_mlp` carries its own backward
pass: the same loop, recomputing a block's activations and accumulating
the held experts' weight gradients in float32.

**A block's rows come from the shapes** (`block_rows_for`).  A block
costs about as much as 220 of its rows whatever it holds: the products
read the expert's weights out of the stack and, backward, read and write
the expert's whole float32 gradient once a BLOCK; only the products, the
gather and the scatter-add grow with the rows.  So a block is the
smallest power of two that holds what a uniform router gives an expert,
tokens x top_k / num_experts, in ONE block (two blocks of half the rows
would pad to the same rows and cross the weights twice), kept within 128
and 512 (past 512 the padding of an expert's last block costs more
products than the weights' traffic saves: PERF.md section 6, PR 40).
`SparseMoeBlock.block_rows` is None for that rule and a number to
overrule it (tests do).  Every input of the rule is a static shape the
layer is handed; no model is named.  The scatter-add is NOT told that a
block's tokens are sorted and unique (they are): on a TPU v5e that hint
made it seven times slower (same section).

Counters (the ``routing`` collection, cumulative, uint32, updated only
where the collection is mutable, i.e. in training): pairs routed to held
experts, rows the loop processed, the loop's trips (`blocks`), the load
of each held expert, and, not cumulative, the `block_rows` the layer last
ran with; a layer with `n_group` > 1 also counts its tokens and the
distinct groups each token's choices fell in (`group_tokens`, `groups`)
and keeps, not cumulative, its `topk_group` (`group_limit`), and the
ledger REFUSES a task whose tokens reached more groups than that.  The worker journals their per-task differences as
``moe.routing``: `pairs / (blocks x block_rows)` is the fill of the
blocks, what the padding cost.  A state restored from a checkpoint that
has no `blocks` or `block_rows` gets them at zero (`with_absent_counters`).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax.traverse_util import flatten_dict, unflatten_dict

from elasticdl_tpu.layers.ledger import TaskLedger

ROUTING_COLLECTION = "routing"
#: The bounds of a block's rows where the shapes decide (module docstring).
MIN_BLOCK_ROWS, MAX_BLOCK_ROWS = 128, 512


def block_rows_for(tokens: int, top_k: int, num_experts: int) -> int:
    """The expert loop's block for a layer of these static shapes: the
    smallest power of two that holds the pairs a uniform router gives an
    expert, within [MIN_BLOCK_ROWS, MAX_BLOCK_ROWS]."""
    share = max(-(-tokens * top_k // num_experts), 1)
    return min(
        max(1 << (share - 1).bit_length(), MIN_BLOCK_ROWS), MAX_BLOCK_ROWS
    )


def _block_plan(local_ids, n_held: int, block: int):
    """local_ids [P]: a pair's expert counted from the first held one, or
    `n_held` for a pair of an expert held elsewhere."""
    order = jnp.argsort(local_ids, stable=True)
    counts = jnp.bincount(local_ids, length=n_held + 1)[:n_held]
    blocks = (counts + block - 1) // block
    return {
        "order": order.astype(jnp.int32),
        "counts": counts.astype(jnp.int32),
        "group_start": (jnp.cumsum(counts) - counts).astype(jnp.int32),
        "blocks": blocks.astype(jnp.int32),
        "block_end": jnp.cumsum(blocks).astype(jnp.int32),
    }


def _block_rows(j, plan, block: int):
    """Block j -> (its expert, its pairs [block], which of them are real)."""
    expert = jnp.searchsorted(plan["block_end"], j, side="right")
    first = plan["block_end"][expert] - plan["blocks"][expert]
    offset = (j - first) * block + jnp.arange(block, dtype=jnp.int32)
    valid = offset < plan["counts"][expert]
    at = jnp.where(valid, plan["group_start"][expert] + offset, 0)
    return expert, plan["order"][at], valid


def _expert_forward(xb, weights, dtype):
    """A block's rows through ONE expert.  `weights` says the form: three
    tensors (gate, up, down) are `down(silu(gate x) * up x)`, two (up,
    down) are `down(relu(up x)^2)`.  -> (what the backward needs of the
    first products, hidden in `dtype`, y float32)."""
    f32 = dict(preferred_element_type=jnp.float32)
    if len(weights) == 3:
        w_gate, w_up, w_down = weights
        gate = jnp.dot(xb, w_gate, **f32)
        up = jnp.dot(xb, w_up, **f32)
        saved, hidden = (gate, up), (jax.nn.silu(gate) * up).astype(dtype)
    else:
        w_up, w_down = weights
        up = jnp.dot(xb, w_up, **f32)
        saved, hidden = (up,), jnp.square(jax.nn.relu(up)).astype(dtype)
    return saved, hidden, jnp.dot(hidden, w_down, **f32)


def _expert_backward(xb, weights, saved, hidden, dyb, dtype):
    """dyb [block, d] in `dtype`, already weighted -> (dx float32, the
    weights' gradients in their order, float32)."""
    f32 = dict(preferred_element_type=jnp.float32)
    dhidden = jnp.dot(dyb, weights[-1].T, **f32)
    if len(weights) == 3:
        gate, up = saved
        sig = jax.nn.sigmoid(gate)
        dup = (dhidden * gate * sig).astype(dtype)
        dgate = (
            dhidden * up * sig * (1.0 + gate * (1.0 - sig))
        ).astype(dtype)
        dxb = jnp.dot(dgate, weights[0].T, **f32) + jnp.dot(
            dup, weights[1].T, **f32
        )
        first = (jnp.dot(xb.T, dgate, **f32), jnp.dot(xb.T, dup, **f32))
    else:
        (up,) = saved
        dup = (dhidden * 2.0 * jax.nn.relu(up)).astype(dtype)
        dxb = jnp.dot(dup, weights[0].T, **f32)
        first = (jnp.dot(xb.T, dup, **f32),)
    return dxb, first + (jnp.dot(hidden.T, dyb, **f32),)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_expert_mlp(x, weights, pair_weight, plan, top_k: int, block: int):
    """x [N, d] in the compute dtype; weights: the held experts' stacked
    float32 tensors, (gate, up, down) or (up, down) (`_expert_forward`);
    pair_weight [N * top_k] float32; plan from `_block_plan`.
    -> (y [N, d] float32, rows processed (float32 scalar))."""
    out, _ = _grouped_fwd(x, weights, pair_weight, plan, top_k, block)
    return out


def _grouped_fwd(x, weights, pair_weight, plan, top_k, block):
    dtype = x.dtype
    with jax.named_scope("moe_experts"):
        cast = tuple(w.astype(dtype) for w in weights)

        def body(j, carry):
            y, rows = carry
            expert, pair, valid = _block_rows(j, plan, block)
            token = pair // top_k
            _, _, yb = _expert_forward(
                x[token], tuple(w[expert] for w in cast), dtype
            )
            weight = jnp.where(valid, pair_weight[pair], 0.0)
            return (
                y.at[token].add(weight[:, None] * yb),
                rows + jnp.sum(valid).astype(jnp.float32),
            )

        y, rows = jax.lax.fori_loop(
            0, plan["block_end"][-1], body,
            (jnp.zeros(x.shape, jnp.float32), jnp.float32(0.0)),
        )
    return (y, rows), (x, weights, pair_weight, plan)


def _grouped_bwd(top_k, block, residuals, cotangent):
    x, weights, pair_weight, plan = residuals
    dy, _ = cotangent
    dtype = x.dtype
    n_pairs = pair_weight.shape[0]
    with jax.named_scope("moe_experts"):
        cast = tuple(w.astype(dtype) for w in weights)

        def body(j, carry):
            dx, dweights, dweight = carry
            expert, pair, valid = _block_rows(j, plan, block)
            token = pair // top_k
            xb = x[token]
            mine = tuple(w[expert] for w in cast)
            saved, hidden, yb = _expert_forward(xb, mine, dtype)
            dyb = dy[token]
            weight = jnp.where(valid, pair_weight[pair], 0.0)
            dweight = dweight.at[jnp.where(valid, pair, n_pairs)].set(
                jnp.sum(dyb * yb, axis=-1), mode="drop"
            )
            # Rows that are not real carry weight 0: everything below
            # is 0 for them.
            dyb = (weight[:, None] * dyb).astype(dtype)
            dxb, dmine = _expert_backward(xb, mine, saved, hidden, dyb, dtype)
            return (
                dx.at[token].add(dxb),
                tuple(dw.at[expert].add(d) for dw, d in zip(dweights, dmine)),
                dweight,
            )

        dx, dweights, dweight = jax.lax.fori_loop(
            0, plan["block_end"][-1], body,
            (
                jnp.zeros(x.shape, jnp.float32),
                tuple(jnp.zeros(w.shape, jnp.float32) for w in weights),
                jnp.zeros((n_pairs,), jnp.float32),
            ),
        )
    no_plan = jax.tree.map(
        lambda a: np.zeros(a.shape, jax.dtypes.float0), plan
    )
    return dx.astype(dtype), dweights, dweight, no_plan


grouped_expert_mlp.defvjp(_grouped_fwd, _grouped_bwd)


def _dense(dtype):
    """The shared experts' projections: no bias, operands in `dtype`,
    float32 results."""
    return partial(
        nn.Dense, use_bias=False, dtype=dtype,
        dot_general=partial(
            jax.lax.dot_general, preferred_element_type=jnp.float32
        ),
    )


class GatedMLP(nn.Module):
    """down(silu(gate x) * up x), no biases (the shared expert); operands
    in `dtype`, float32 results."""

    width: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        dense = _dense(self.dtype)
        hidden = nn.silu(dense(self.width, name="gate_proj")(x)) * dense(
            self.width, name="up_proj"
        )(x)
        return dense(x.shape[-1], name="down_proj")(hidden)


class Relu2MLP(nn.Module):
    """down(relu(up x)^2), no biases (the ungated shared expert);
    operands in `dtype`, float32 results."""

    width: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        dense = _dense(self.dtype)
        hidden = jnp.square(nn.relu(dense(self.width, name="up_proj")(x)))
        return dense(x.shape[-1], name="down_proj")(hidden)


@jax.custom_vjp
def _load_violation(weight, select_bias, violation):
    """`weight`, unchanged.  In the backward pass the selection bias
    receives `violation` (sign(times chosen - mean), over all experts) in
    place of a gradient: what the balancing rule descends (module
    docstring)."""
    return weight


def _load_violation_fwd(weight, select_bias, violation):
    return weight, violation


def _load_violation_bwd(violation, cotangent):
    return cotangent, violation, jnp.zeros_like(violation)


_load_violation.defvjp(_load_violation_fwd, _load_violation_bwd)


@jax.custom_vjp
def _with_auxiliary_loss(weight, loss):
    """`weight`, unchanged.  In the backward pass `loss` (a scalar)
    receives a cotangent of 1, whatever `weight`'s is: its gradient is
    added to the parameters' and the reported loss does not contain it
    (module docstring)."""
    return weight


def _with_auxiliary_loss_fwd(weight, loss):
    return weight, None


def _with_auxiliary_loss_bwd(_, cotangent):
    return cotangent, jnp.ones((), jnp.float32)


_with_auxiliary_loss.defvjp(_with_auxiliary_loss_fwd, _with_auxiliary_loss_bwd)


def sequence_balance_loss(scores, expert, sequences: int):
    """scores [N, E] (a softmax over the experts), expert [N, k] (the
    chosen), N = sequences x T -> mean over the sequences of
    sum_i f_i P_i, f_i = count_i E / (k T) a constant, P_i = mean of
    scores_i over the sequence (DeepSeek-V2's `seq_aux`, before alpha)."""
    n, num_experts = scores.shape
    t, k = n // sequences, expert.shape[-1]
    chosen = jnp.sum(
        expert.reshape(sequences, t * k, 1) == jnp.arange(num_experts),
        axis=1, dtype=jnp.float32,
    )
    f = jax.lax.stop_gradient(chosen) * (num_experts / (k * t))
    mean_score = jnp.mean(scores.reshape(sequences, t, num_experts), axis=1)
    return jnp.mean(jnp.sum(f * mean_score, axis=-1))


class _BiasedGate(nn.Module):
    """The source's `gate` of a sigmoid router: its `weight` and the
    `e_score_correction_bias` that takes part in the selection only."""

    num_experts: int

    @nn.compact
    def __call__(self, d: int):
        return (
            self.param("weight", nn.initializers.lecun_normal(),
                       (d, self.num_experts), jnp.float32),
            self.param("e_score_correction_bias", nn.initializers.zeros_init(),
                       (self.num_experts,), jnp.float32),
        )


class SparseMoeBlock(nn.Module):
    num_experts: int                 # the router's width, as published
    top_k: int
    expert_width: int
    shared_width: int
    held: Tuple[int, int]            # (first held expert, how many)
    norm_topk_prob: bool = True
    dtype: Any = jnp.bfloat16
    # A block of the expert loop; None: from the shapes (`block_rows_for`).
    block_rows: Optional[int] = None
    # The router's score function (module docstring): "softmax", or
    # "sigmoid" with its selection bias.
    score: str = "softmax"
    # What an expert computes: "gated_silu" (three products) or "relu2"
    # (two).
    expert_form: str = "gated_silu"
    routed_scale: float = 1.0
    # Whether a sigmoid gate multiplies the shared expert; None: as the
    # score function's first source has it (softmax: gated, sigmoid: not).
    shared_gated: Optional[bool] = None
    # > 0: the softmax router's sequence-wise balancing loss, times this.
    balance_alpha: float = 0.0
    # Group-limited selection (module docstring): the experts as `n_group`
    # runs, a token's choices within its `topk_group` best; 1 / 1: none.
    n_group: int = 1
    topk_group: int = 1

    @nn.compact
    def __call__(self, x):
        first, n_held = self.held
        if not (0 <= first and n_held > 0
                and first + n_held <= self.num_experts):
            raise ValueError(
                f"held={self.held} is no range of {self.num_experts} experts"
            )
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"no router score function {self.score!r}")
        if self.expert_form not in ("gated_silu", "relu2"):
            raise ValueError(f"no expert form {self.expert_form!r}")
        softmax = self.score == "softmax"
        gated = self.expert_form == "gated_silu"
        shared_gated = (
            softmax if self.shared_gated is None else self.shared_gated
        )
        if self.balance_alpha and not softmax:
            raise ValueError("the balancing loss is a softmax router's")
        group = self.num_experts // max(self.n_group, 1)
        if self.n_group > 1 and not (
            self.num_experts % self.n_group == 0 and group >= 2
            and 1 <= self.topk_group <= self.n_group
            and self.top_k <= self.topk_group * group
        ):
            raise ValueError(
                f"n_group={self.n_group}, topk_group={self.topk_group}: no "
                f"groups of {self.num_experts} experts for a top "
                f"{self.top_k}"
            )
        shape, d = x.shape, x.shape[-1]
        x = x.reshape(-1, d)
        block = self.block_rows or block_rows_for(
            x.shape[0], self.top_k, self.num_experts
        )
        init = nn.initializers.lecun_normal()
        expert_init = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
            batch_axis=(0,),
        )
        width = self.expert_width
        weights = tuple(
            self.param(f"experts_{name}", expert_init, (n_held,) + dims,
                       jnp.float32)
            for name, dims in (
                [("gate_proj", (d, width))] if gated else []
            ) + [("up_proj", (d, width)), ("down_proj", (width, d))]
        )
        with jax.named_scope("moe_route"):
            if softmax:
                router = self.param("gate", init, (d, self.num_experts),
                                    jnp.float32)
            else:
                router, select_bias = _BiasedGate(
                    self.num_experts, name="gate"
                )(d)
            logits = jnp.dot(
                x.astype(jnp.float32), router,
                precision=jax.lax.Precision.HIGHEST,
            )
            balance = None
            if softmax:
                scores = jax.nn.softmax(logits, axis=-1)
                weight, expert = jax.lax.top_k(
                    self._within_groups(scores), self.top_k
                )
                if self.balance_alpha:
                    balance = self.balance_alpha * sequence_balance_loss(
                        scores, expert, shape[0] if len(shape) == 3 else 1
                    )
                    weight = _with_auxiliary_loss(weight, balance)
            else:
                scores = jax.nn.sigmoid(logits)
                _, expert = jax.lax.top_k(
                    self._within_groups(scores + select_bias), self.top_k
                )
                weight = jnp.take_along_axis(scores, expert, axis=-1)
                chosen = jnp.bincount(
                    expert.reshape(-1), length=self.num_experts
                ).astype(jnp.float32)
                weight = _load_violation(
                    weight, select_bias, jnp.sign(chosen - jnp.mean(chosen))
                )
            if self.norm_topk_prob:
                weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
            if self.routed_scale != 1.0:
                weight = weight * self.routed_scale
            is_held = (expert >= first) & (expert < first + n_held)
            local = jnp.where(is_held, expert - first, n_held).reshape(-1)
            plan = _block_plan(local.astype(jnp.int32), n_held, block)
        y, rows = grouped_expert_mlp(
            x.astype(self.dtype), weights, weight.reshape(-1),
            plan, self.top_k, block,
        )
        if self.shared_width:  # 0: the routed experts and nothing beside
            with jax.named_scope("moe_shared"):
                shared = (GatedMLP if gated else Relu2MLP)(
                    self.shared_width, self.dtype,
                    name="shared_expert" if shared_gated else "shared_experts",
                )(x)
                if shared_gated:
                    shared_gate = self.param(
                        "shared_expert_gate", init, (d, 1), jnp.float32
                    )
                    # A block's product like the expert's own: operands in
                    # `dtype`, written out so that no backend's default
                    # decides.
                    shared = jax.nn.sigmoid(jnp.dot(
                        x.astype(self.dtype), shared_gate.astype(self.dtype),
                        preferred_element_type=jnp.float32,
                    )) * shared
                y = y + shared
        self._count(is_held, rows, plan, block, balance, expert)
        return y.reshape(shape)

    def _within_groups(self, selection):
        """The selection scores [N, E] with every expert outside the
        token's `topk_group` best groups at -inf (a group's score: the
        sum of its two largest); as they are where `n_group` is 1."""
        if self.n_group <= 1:
            return selection
        n = selection.shape[0]
        grouped = selection.reshape(n, self.n_group, -1)
        best_two, _ = jax.lax.top_k(grouped, 2)
        _, groups = jax.lax.top_k(jnp.sum(best_two, axis=-1), self.topk_group)
        allowed = jnp.any(
            groups[:, :, None] == jnp.arange(self.n_group), axis=1
        )
        return jnp.where(allowed[:, :, None], grouped, -jnp.inf).reshape(n, -1)

    def _count(self, is_held, rows, plan, block: int, balance,
               expert) -> None:
        zero = lambda *s: jnp.zeros(s, jnp.uint32)  # noqa: E731
        pairs = self.variable(ROUTING_COLLECTION, "pairs", zero)
        processed = self.variable(ROUTING_COLLECTION, "processed", zero)
        blocks = self.variable(ROUTING_COLLECTION, "blocks", zero)
        block_rows = self.variable(ROUTING_COLLECTION, "block_rows", zero)
        load = self.variable(
            ROUTING_COLLECTION, "load", zero, plan["counts"].shape[0]
        )
        counting = self.is_mutable_collection(ROUTING_COLLECTION) and (
            not self.is_initializing()
        )
        if counting:
            pairs.value = pairs.value + jnp.sum(is_held).astype(jnp.uint32)
            processed.value = processed.value + rows.astype(jnp.uint32)
            blocks.value = blocks.value + plan["block_end"][-1].astype(
                jnp.uint32
            )
            block_rows.value = jnp.uint32(block)
            load.value = load.value + plan["counts"].astype(jnp.uint32)
        if self.n_group > 1:  # only a layer that selects within groups
            tokens = self.variable(ROUTING_COLLECTION, "group_tokens", zero)
            groups = self.variable(ROUTING_COLLECTION, "groups", zero)
            limit = self.variable(ROUTING_COLLECTION, "group_limit", zero)
            if counting:
                reached = jnp.any(
                    (expert // (self.num_experts // self.n_group))[:, :, None]
                    == jnp.arange(self.n_group), axis=1,
                )
                tokens.value = tokens.value + jnp.uint32(expert.shape[0])
                groups.value = groups.value + jnp.sum(reached).astype(
                    jnp.uint32
                )
                limit.value = jnp.uint32(self.topk_group)
        if balance is not None:  # only a layer that is told an alpha
            total = self.variable(
                ROUTING_COLLECTION, "balance",
                lambda: jnp.zeros((), jnp.float32),
            )
            if counting:
                total.value = total.value + jax.lax.stop_gradient(balance)


def with_absent_counters(model_state):
    """`model_state` whose ``routing`` layers all hold `blocks` and
    `block_rows`: a checkpoint written before the layer counted them
    restores with them at zero, so the state keeps the tree the layer
    writes.  The same object where nothing is absent."""
    routing = (model_state or {}).get(ROUTING_COLLECTION)
    if not routing:
        return model_state
    flat = flatten_dict(dict(routing))
    absent = {
        path[:-1] + (key,): np.zeros((), np.uint32)
        for path in flat if path[-1] == "pairs"
        for key in ("blocks", "block_rows") if path[:-1] + (key,) not in flat
    }
    if not absent:
        return model_state
    return {
        **model_state,
        ROUTING_COLLECTION: unflatten_dict({**flat, **absent}),
    }


class RoutingLedger(TaskLedger):
    """``moe.routing``: a task's share of the cumulative ``routing``
    counters (`layers/ledger.py`; `block_rows` is no sum: the largest of
    the layers' last).  uint32 differences are right across a wrap."""

    span = "moe.routing"

    def _read(self, model_state) -> dict:
        """{counter: [layers, ...]} of every expert layer's counters."""
        flat = flatten_dict(dict(model_state.get(ROUTING_COLLECTION, {})))
        if not flat:
            return {}
        flat = jax.device_get(flat)
        layers = sorted({path[:-1] for path in flat})
        return {
            key: np.stack([
                np.asarray(flat[layer + (key,)], dtype)
                for layer in layers
            ])
            for key, dtype in (
                ("pairs", np.uint32), ("processed", np.uint32),
                ("blocks", np.uint32), ("block_rows", np.uint32),
                ("load", np.uint32), ("balance", np.float32),
                ("group_tokens", np.uint32), ("groups", np.uint32),
                ("group_limit", np.uint32),
            )
            if layers[0] + (key,) in flat
        }

    def _fields(self, now, seen, steps):
        pairs, processed, blocks, load = (
            (now[key] - seen[key]).astype("int64")
            for key in ("pairs", "processed", "blocks", "load")
        )
        fields = {
            "layers": int(load.shape[0]),
            "held": int(load.shape[1]),
            "pairs": int(pairs.sum()),
            "dropped": int(pairs.sum() - processed.sum()),
            "blocks": int(blocks.sum()),
            "block_rows": int(now["block_rows"].max()),
            "load_max": int(load.max()),
            "load_mean": float(load.mean()),
        }
        if "groups" in now:  # distinct groups a token's choices fell in
            tokens, groups = (
                (now[key] - seen[key]).astype("int64").sum()
                for key in ("group_tokens", "groups")
            )
            fields["groups_mean"] = float(groups) / max(int(tokens), 1)
            fields["group_limit"] = int(now["group_limit"].max())
        if "balance" in now:  # the mean over the layers and the steps
            fields["balance_loss"] = float(
                np.mean(now["balance"] - seen["balance"]) / max(steps, 1)
            )
        return fields

    def refuse(self, fields):
        if fields["dropped"]:
            return (
                f"the expert layers dropped {fields['dropped']} routed "
                "pair(s): they are built to drop none"
            )
        if fields.get("groups_mean", 0) > fields.get("group_limit", 0):
            return (
                f"a token's choices fell in {fields['groups_mean']:.2f} "
                f"groups on average, more than the {fields['group_limit']} "
                "its router may reach"
            )
        return None
