"""Plain reference of Mellum 2 (three sliding-window attention layers to
one full one, a norm on every query and key head, two rotary tables over
the whole head, and in every layer routed experts behind a renormalised
softmax router with NOTHING beside them) for ONE chip's share of it: the
range of experts `model` says is held, the slice of the vocabulary it
gives.  float32 `jax.numpy`, no kernel, no custom backward, no blocks of
keys, and no code of the program or of another reference.  For layer i of
type `layer_types[i]`, H = `num_attention_heads` query heads over Hkv =
`num_key_value_heads` key-value heads of D = `head_dim`::

    h  = rmsnorm(x)
    q  = h Wq -> [T, H, D]    k = h Wk -> [T, Hkv, D]    v = h Wv -> [T, Hkv, D]
    q  = rmsnorm_D(q; w_qn)   k = rmsnorm_D(k; w_kn)     each head's D columns
    sliding: all D columns of q and k turned by the plain table
    full:    all D columns turned by the YaRN table (frequencies computed
             HERE from the formulas; cos and sin both times
             `attention_factor` = 0.1 ln(factor) + 1)
    a  = softmax(q k^T / sqrt(D) + M) v, query head j reads key-value head
         j // (H / Hkv); the mask M written out over ALL T keys:
         s <= t (full), t - window < s <= t (sliding); one block of
         queries at a time so that 8192 tokens fit
    x  = x + concat_heads(a) Wo
    u  = rmsnorm(x)
    sparse: p = softmax(u Wr) over ALL experts; the k largest; w = p at
            the chosen over their sum (all k, held or not); the experts by
            a loop over the held range, each over every token, a token's
            weight zero where the expert is not among its k.  NOTHING
            else: a token none of whose choices is held adds 0.  What
            experts held elsewhere would add is left out, here as in the
            program.
    dense:  x = x + Wd (silu(Wgate u) * Wup u)   (no published layer)
    logits = rmsnorm(x) W_head

The balancing loss (`balance_alpha` x the mean over the sequences of
sum_i f_i P_i, a layer) is ADDED to the cross-entropy explicitly by
`loss_and_balance`; the program injects its gradient and reports the
cross-entropy alone, and the two gradients must agree.

The same code also runs AT THE PRECISION THE CONFIGURATION STATES
(`forward(..., "stated")`: the operands of the four attention
projections, of the score and value products, of the experts and the head
rounded to bfloat16 with float32 accumulation, everything else float32:
norms, tables, the router), with EVERY weight and activation in bfloat16
(`"bfloat16"`: the nearest precision below the stated one, which the
cell's limits refuse), over the tokens clear of a top-k tie
(`"highest_clear"`), and with three planted faults in what is new here:
`"no_window"` (the sliding layers given the full causal mask),
`"no_qk_norm"` (queries and keys left unnormed) and `"no_yarn"` (the
full layers given the plain table and factor 1).

Beside it: where the program's own outputs come from (`program`), the
least work of a training step (`step_cost`) and of the scopes whose
roofline share the benchmark reports (`attn_proj_cost`, `attn_full_cost`,
`attn_window_cost`, `moe_experts_cost`).

Departures from the published description: none from the equations of
ISSUE 42; what the source's `config.json` leaves open is listed in the
configuration's `assumed` (the head norms, the router's form, the
balancing loss, the window's 1024 keys including the query's own, rotary
columns in half-split order, a float32 residual stream, no
multi-token-prediction head).
"""

from __future__ import annotations

import math
import sys

import numpy as np

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"

#: A token is CLEAR of a tie when, in every expert layer, the router's
#: logit of its last chosen expert and that of the first one left out lie
#: at least this far apart in the reference at `highest`.  (The softmax is
#: monotone: the order of the logits is the order of the probabilities.)
CLEAR_MARGIN = 0.01

#: "outputs": what `program` returned last (`highest_clear` repeats its
#: rows where a token is not clear, so that they drop out of the harness's
#: one rms over all rows).
_PROGRAM = {}


def sample(seed: int, rows: int, model: dict):
    rng = np.random.default_rng(seed)
    return rng.integers(
        0, model["vocab_size"], size=(rows, model["sample_tokens"])
    ).astype(np.int32)


def weights(step_dir: str, features, model: dict, program_state=None):
    """The flax params of the job's checkpoint, as the program's saver
    unpickled them (one read serves both sides)."""
    return program_state.params


def program(args, features):
    """The program's own logits for `features` at the job's last
    checkpoint (the trainer is built as
    `worker/main._build_collective_worker` builds it; `eval_step` reads
    the weights and the model state, so the optimizer's state stays on the
    host).  -> (outputs, step, program_state)."""
    from elasticdl_tpu.checkpoint import CheckpointSaver
    from elasticdl_tpu.common.model_utils import load_model_spec
    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer

    spec = load_model_spec(args)
    mesh = build_mesh(MeshConfig(model=args.mesh_model_axis))
    trainer = DataParallelTrainer(
        model=spec.build_model(mesh=mesh),
        loss_fn=spec.loss,
        optimizer=spec.optimizer(),
        mesh=mesh,
        dense_sharding=args.dense_sharding,
    )
    state, step = CheckpointSaver(args.checkpoint_dir).load_latest()
    if state is None:
        return None, None, None
    # Only the weights go to the device: the two Adam moments (4.8 GB of
    # the 7.1 GB saved) would leave the reference no room beside them.
    trainer.state = state._replace(opt_state=())
    _PROGRAM["outputs"] = np.asarray(trainer.eval_step(features), np.float32)
    return _PROGRAM["outputs"], step, state


# -- the forward pass ----------------------------------------------------------


def _bf16(x):
    """x with bfloat16's 8 bits of mantissa, in x's own dtype."""
    import jax.numpy as jnp

    return x.astype(jnp.bfloat16).astype(x.dtype)


def _mm(a, b, rounded: bool):
    """a @ b; with `rounded`, of operands rounded to bfloat16 (their
    products are exact in float32, where they are accumulated)."""
    return _bf16(a) @ _bf16(b) if rounded else a @ b


def _rms_norm(x, weight, eps):
    """Over the last axis: a row of the stream, or one head's columns."""
    import jax.numpy as jnp

    return weight * x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def _gated_mlp(p, x, rounded: bool):
    """down(silu(gate x) * up x): a `dense` entry of `mlp_layer_types`."""
    hidden = _silu(_mm(x, p["gate_proj"]["kernel"], rounded)) * _mm(
        x, p["up_proj"]["kernel"], rounded
    )
    return _mm(hidden, p["down_proj"]["kernel"], rounded)


def rotary_inv_freq(model: dict, kind: str, plain: bool = False):
    """-> (the frequencies of the D / 2 rotary pairs of a head in a layer
    of `kind`, float64 numpy; what cos and sin are multiplied by).  Plain
    `theta^(-2i/D)`; under YaRN (`factor` > 1, a full layer's, unless
    `plain`) the blend of that (extrapolated) and that over `factor`
    (interpolated) by a ramp between the pairs that turn `beta_fast` and
    `beta_slow` times over the original positions, and the tables times
    0.1 ln(factor) + 1 (the source's `attention_factor`,
    1.2772588722239782 at 16)."""
    prefix = f"rope_{kind}_"
    dim = model["head_dim"]
    base = float(model[prefix + "theta"])
    pairs = np.arange(dim // 2, dtype=np.float64)
    extrapolated = base ** (-2.0 * pairs / dim)
    factor = float(model.get(prefix + "factor", 1.0))
    if plain or factor <= 1.0:
        return extrapolated, 1.0
    original = model[prefix + "original_max_position_embeddings"]

    def pair_turning(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(base)
        )

    low = max(math.floor(pair_turning(model[prefix + "beta_fast"])), 0)
    high = min(math.ceil(pair_turning(model[prefix + "beta_slow"])), dim - 1)
    ramp = np.clip((pairs - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (
        extrapolated / factor * ramp + extrapolated * (1.0 - ramp),
        0.1 * math.log(factor) + 1.0,
    )


def _rotary(x, model, kind, plain=False):
    """x [T, heads, D]: pair i is (x_i, x_{i + D/2}), turned by
    position x inv_freq_i; every column of the head belongs to a pair."""
    import jax.numpy as jnp

    inv_freq, magnitude = rotary_inv_freq(model, kind, plain)
    half = len(inv_freq)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32
    )[None, :]
    cos = (jnp.cos(angles) * magnitude)[:, None, :].astype(x.dtype)
    sin = (jnp.sin(angles) * magnitude)[:, None, :].astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(p, x, model, kind, low=frozenset(), query_block=128):
    import jax.numpy as jnp

    t = x.shape[0]
    heads, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["head_dim"]
    blocks = "blocks" in low
    op = _bf16 if blocks else (lambda a: a)
    q = _mm(x, p["q_proj"]["kernel"], blocks).reshape(t, heads, d)
    k = _mm(x, p["k_proj"]["kernel"], blocks).reshape(t, hkv, d)
    v = _mm(x, p["v_proj"]["kernel"], blocks).reshape(t, hkv, d)
    if model.get("qk_norm", True) and "no_qk_norm" not in low:
        eps = model["rms_norm_eps"]
        q = _rms_norm(q, p["q_norm"]["weight"], eps)
        k = _rms_norm(k, p["k_norm"]["weight"], eps)
    plain = "no_yarn" in low
    q, k = _rotary(q, model, kind, plain), _rotary(k, model, kind, plain)
    # Query head j reads key-value head j // (heads / hkv).
    k, v = (jnp.repeat(a, heads // hkv, axis=1) for a in (k, v))
    window = (
        model["sliding_window"]
        if kind == SLIDING and "no_window" not in low else None
    )
    positions = jnp.arange(t)
    outs = []
    for start in range(0, t, query_block):
        qb = q[start:start + query_block]
        at = positions[start:start + query_block, None]
        scores = jnp.einsum("qhd,khd->hqk", op(qb), op(k)) / math.sqrt(d)
        allowed = positions[None, :] <= at
        if window is not None:
            allowed = allowed & (positions[None, :] > at - window)
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        # softmax, written out: the weights are rounded (where they are)
        # before they are normalised, the sum is of the unrounded ones.
        weights = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
        mixed = jnp.einsum("hqk,khd->qhd", op(weights), op(v))
        total = jnp.moveaxis(jnp.sum(weights, -1), 1, 0)[..., None]
        outs.append(mixed / total)
    out = jnp.concatenate(outs)                       # [T, heads, D]
    return _mm(out.reshape(t, heads * d), p["o_proj"]["kernel"], blocks)


def _route(p, x, model):
    """-> (probabilities [T, E], chosen ids [T, k], their weights [T, k],
    how far each token's last chosen LOGIT lies above the first one left
    out [T])."""
    import jax
    import jax.numpy as jnp

    k = model["num_experts_per_tok"]
    logits = _mm(x, p["gate"], False)
    ranked, _ = jax.lax.top_k(logits, k + 1)
    margin = ranked[:, k - 1] - ranked[:, k]
    exp = jnp.exp(logits - jnp.max(logits, -1, keepdims=True))
    probs = exp / jnp.sum(exp, -1, keepdims=True)
    top, ids = jax.lax.top_k(probs, k)
    if model.get("norm_topk_prob", True):
        top = top / jnp.sum(top, -1, keepdims=True)
    return probs, ids, top, margin


def balance_loss(probs, ids, model: dict):
    """ONE sequence's sum_i f_i P_i times alpha: f_i = (times expert i
    was chosen in the sequence) E / (k T), a constant; P_i = the mean of
    p_i over the sequence; over all E experts."""
    import jax
    import jax.numpy as jnp

    t, experts = probs.shape
    counts = jnp.sum(
        (ids[:, :, None] == jnp.arange(experts)).astype(probs.dtype), (0, 1)
    )
    f = jax.lax.stop_gradient(counts) * experts / (ids.shape[1] * t)
    return model.get("balance_alpha", 0.0) * jnp.sum(
        f * jnp.mean(probs, axis=0)
    )


def _watch():
    return {"chosen": [], "margins": [], "balance": []}


def _experts(p, x, model, low=frozenset(), watch=None):
    """Softmax router over all experts, renormalised over the chosen; the
    held range's part and nothing else.  `watch`: a dict whose lists
    receive this layer's choices [T, k] (`chosen`), how far each token
    was from a tie [T] (`margins`) and the balancing loss (`balance`)."""
    import jax.numpy as jnp

    blocks = "blocks" in low
    probs, ids, top, margin = _route(p, x, model)
    if watch is not None:
        watch["chosen"].append(ids)
        watch["margins"].append(margin)
        watch["balance"].append(balance_loss(probs, ids, model))
    first = model["experts_first"]
    y = jnp.zeros_like(x)
    for local in range(model["experts_held"]):
        weight = jnp.sum(jnp.where(ids == first + local, top, 0.0), axis=-1)
        hidden = _silu(_mm(x, p["experts_gate_proj"][local], blocks)) * _mm(
            x, p["experts_up_proj"][local], blocks
        )
        y = y + weight[:, None] * _mm(
            hidden, p["experts_down_proj"][local], blocks
        )
    return y


def decoder(w: dict, tokens, model: dict, low=frozenset(), watch=None):
    """One sequence [T] -> logits [T, V], in the dtype of `w`; `low`: what
    departs from float32 (`blocks`: products round their operands to
    bfloat16; `no_window`, `no_qk_norm`, `no_yarn`: the planted faults);
    `watch`: see `_experts`."""
    eps = model["rms_norm_eps"]
    stack = w["model"]
    x = stack["embed_tokens"][tokens]
    for i in range(model["num_hidden_layers"]):
        p = stack[f"layers_{i}"]
        x = x + _attention(
            p["self_attn"], _rms_norm(x, p["input_layernorm"]["weight"], eps),
            model, model["layer_types"][i], low,
        )
        u = _rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
        if model["mlp_layer_types"][i] == DENSE:
            x = x + _gated_mlp(p["mlp"], u, "blocks" in low)
        else:
            x = x + _experts(p["mlp"], u, model, low, watch)
    return _mm(
        _rms_norm(x, stack["norm"]["weight"], eps), w["lm_head"],
        "blocks" in low,
    )


#: precision -> (dtype of every weight and activation, what departs)
PRECISIONS = {
    "highest": ("float32", frozenset()),
    "highest_clear": ("float32", frozenset()),
    "stated": ("float32", frozenset({"blocks"})),
    "bfloat16": ("bfloat16", frozenset()),
    "no_window": ("float32", frozenset({"no_window"})),
    "no_qk_norm": ("float32", frozenset({"no_qk_norm"})),
    "no_yarn": ("float32", frozenset({"no_yarn"})),
}


def _layers(model: dict, name: str, kind: str) -> int:
    return list(model[name][:model["num_hidden_layers"]]).count(kind)


def chosen_counts(w: dict, tokens, model: dict):
    """How often each expert layer's router chose each of ALL experts
    over `tokens` [rows, T], at `highest` -> int array [expert layers,
    num_experts].  The held range's columns are the pairs this chip
    computes; a uniform router gives rows x T x k / num_experts
    everywhere."""
    import jax
    import jax.numpy as jnp

    watch = _watch()
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), w)
        for row in jnp.asarray(tokens):
            decoder(w, row, model, watch=watch)
    layers = _layers(model, "mlp_layer_types", SPARSE)
    counts = np.zeros((layers, model["num_experts"]), np.int64)
    for i, ids in enumerate(watch["chosen"]):
        counts[i % layers] += np.bincount(
            np.asarray(ids).reshape(-1), minlength=model["num_experts"]
        )
    return counts


def forward(w: dict, tokens, model: dict, precision: str = "highest"):
    """`highest`: float32 throughout.  `stated`: what the configuration
    states (bfloat16 operands in the blocks' products, the rest float32).
    `bfloat16`: the same code with EVERY weight and activation in
    bfloat16 (norms, tables, router and softmax statistics too).
    `no_window`, `no_qk_norm`, `no_yarn`: `highest` with one piece of
    what is new here left out (the band, the head norms, YaRN's table and
    magnitude): planted faults, reported beside the limits.

    `highest_clear`: `highest` over the tokens that are clear of a tie
    (`CLEAR_MARGIN`).  A top-k selection is discontinuous: a token whose
    last chosen expert and the first one left out score within a rounding
    of each other gets another expert in a program that rounds upstream,
    and with 16 of 64 experts held and NOTHING beside them in the
    sublayer that is a whole routed contribution (about 1 / 8 of an
    expert's output, of the two a token has here on average) gained or
    lost, which no precision of the products would repair.  Which tokens
    are clear is decided HERE, from the reference's own logits at
    `highest`; for the others this returns the program's own rows
    (`program` kept them), so that their difference is exactly 0 in the
    harness's rms over all rows: the reading is the clear tokens' squared
    error over ALL rows' count, sqrt(share clear) times their own rel.
    rms."""
    import jax
    import jax.numpy as jnp

    if precision not in PRECISIONS:
        raise ValueError(f"no precision {precision!r}")
    dtype, low = PRECISIONS[precision]
    watch = _watch() if precision in ("highest", "highest_clear") else None
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, dtype), w)
        out = jnp.stack([
            decoder(w, row, model, low, watch).astype(jnp.float32)
            for row in jnp.asarray(tokens)
        ])
    if watch is None or any(
        isinstance(ids, jax.core.Tracer) for ids in watch["chosen"]
    ):
        return out
    layers = _layers(model, "mlp_layer_types", SPARSE)
    if precision == "highest" or not layers:
        _log_held_pairs(watch["chosen"], len(tokens), model)
        return out
    margin = jnp.stack([  # [rows, T]: the least margin over the layers
        jnp.min(jnp.stack(watch["margins"][r * layers:(r + 1) * layers]), 0)
        for r in range(len(tokens))
    ])
    clear = margin >= CLEAR_MARGIN
    theirs = _PROGRAM.get("outputs")
    if theirs is None or theirs.shape != out.shape:
        raise ValueError("`highest_clear` needs the outputs `program` kept")
    print(
        f"reference: {int(clear.sum())} of {clear.size} compared tokens "
        f"are clear of a tie by {CLEAR_MARGIN:g} of a router's logit in "
        f"every expert layer ({int((margin >= CLEAR_MARGIN / 2).sum())} by "
        f"half that, {int((margin >= 2 * CLEAR_MARGIN).sum())} by twice)",
        file=sys.stderr, flush=True,
    )
    return jnp.where(clear[..., None], out, theirs)


def _log_held_pairs(chosen, rows: int, model: dict) -> None:
    """One line on stderr (the harness keeps it in the run's `check.log`):
    the pairs the held experts carry in the compared sample, a layer, and
    the tokens none of whose choices is held."""
    first, held = model["experts_first"], model["experts_held"]
    layers = _layers(model, "mlp_layer_types", SPARSE)
    if not layers:
        return
    pairs, none_held = [0] * layers, [0] * layers
    for i, ids in enumerate(chosen):
        here = (np.asarray(ids) >= first) & (np.asarray(ids) < first + held)
        pairs[i % layers] += int(here.sum())
        none_held[i % layers] += int((~here.any(axis=-1)).sum())
    uniform = (rows * model["sample_tokens"] * model["num_experts_per_tok"]
               * held / model["num_experts"])
    print(
        f"reference: pairs on the {held} held experts in the compared "
        f"sample, a layer: {pairs}; a uniform router gives {uniform:.0f}; "
        f"tokens with no choice held, a layer: {none_held}",
        file=sys.stderr, flush=True,
    )


def loss_and_balance(w: dict, tokens, labels, model: dict):
    """tokens, labels [rows, T] -> (mean next-token cross-entropy over all
    tokens, float32 at `highest`; the balancing loss: alpha x the mean
    over the sequences of sum_i f_i P_i, summed over the expert layers).
    A training step descends their SUM; the program reports the first."""
    import jax
    import jax.numpy as jnp

    cross_entropy, balance = [], 0.0
    with jax.default_matmul_precision("highest"):
        for row, target in zip(tokens, labels):
            watch = _watch()
            logits = decoder(w, row, model, watch=watch)
            logp = jax.nn.log_softmax(logits, axis=-1)
            cross_entropy.append(
                -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]
            )
            balance = balance + sum(watch["balance"]) / len(tokens)
    return jnp.mean(jnp.stack(cross_entropy)), balance


def loss_fn(w: dict, tokens, labels, model: dict):
    """The loss the program REPORTS: the cross-entropy alone."""
    return loss_and_balance(w, tokens, labels, model)[0]


# -- the least work ------------------------------------------------------------


def _matmul_params(model: dict) -> dict:
    """Parameters that multiply a token's activations, by part."""
    d, hd = model["hidden_size"], model["head_dim"]
    layers = model["num_hidden_layers"]
    return {
        # q and o a query head, k and v a key-value head, a layer
        "attn": layers * 2 * d * hd * (
            model["num_attention_heads"] + model["num_key_value_heads"]
        ),
        "dense": _layers(model, "mlp_layer_types", DENSE) * 3 * d
        * model["intermediate_size"],
        "router": _layers(model, "mlp_layer_types", SPARSE) * d
        * model["num_experts"],
        "expert": 3 * d * model["moe_intermediate_size"],  # ONE expert
        "head": d * model["vocab_size"],
    }


def _all_params(model: dict) -> int:
    """Every parameter this chip holds (the layers' two norms, the two
    head norms and the final norm included)."""
    m = _matmul_params(model)
    d, layers = model["hidden_size"], model["num_hidden_layers"]
    sparse = _layers(model, "mlp_layer_types", SPARSE)
    head_norms = 2 * model["head_dim"] * bool(model.get("qk_norm", True))
    return (
        m["attn"] + m["dense"] + m["router"] + m["head"]
        + sparse * model["experts_held"] * m["expert"]
        + model["vocab_size"] * d + layers * (2 * d + head_norms) + d
    )


def attn_proj_cost(model: dict, minibatch: int) -> dict:
    """The `attn_proj` scope (q, k, v and o of every layer) for one
    training step AS THE CONFIGURATION RUNS IT: 8 FLOPs a weight a token
    (forward 2, once more under the layer's rematerialisation, backward
    4).  Bytes: the float32 weights read in each of the three passes and
    their gradient written, plus a token's rows in and out of the four
    products, bfloat16 in and float32 out forward (twice) and the reverse
    backward."""
    m = _matmul_params(model)
    tokens = minibatch * model["sample_tokens"]
    d, hd = model["hidden_size"], model["head_dim"]
    # rows a token: x (d) in and q, k, v out; the heads' outputs in, d out
    heads = model["num_attention_heads"]
    wide = (heads + 2 * model["num_key_value_heads"]) * hd
    rows = model["num_hidden_layers"] * (2 * d + wide + heads * hd)
    return {
        "flops": 8 * m["attn"] * tokens,
        "bytes": 4 * 4 * m["attn"] + 3 * 6 * rows * tokens,
    }


def _core_cost(model: dict, minibatch: int, kind: str, keys: float) -> dict:
    """The attention core (scores, softmax, values) of the layers of
    `kind` over `keys` (query, key) positions a head a sequence that the
    MASK requires, for one training step AS THE CONFIGURATION RUNS IT,
    whichever engine implements it and however many blocks it visits.
    FLOPs, each product 2 x keys x D a query head: a forward is q k^T and
    p v; it runs once more under the layer's rematerialisation; the
    backward is five products (the scores again, dS K, dS^T Q, P^T dO,
    dO V^T).  Bytes, bfloat16: a forward reads q and writes o a query
    head and reads k and v a key-value head; the backward reads q, o, dO
    and writes dq a query head, reads k, v and writes dk, dv a key-value
    head."""
    layers = _layers(model, "layer_types", kind)
    heads = layers * model["num_attention_heads"]
    kv_heads = layers * model["num_key_value_heads"]
    rows = minibatch * model["sample_tokens"] * model["head_dim"]
    forward = 2 * heads + 2 * kv_heads
    backward = 4 * heads + 4 * kv_heads
    return {
        "flops": 2 * keys * model["head_dim"] * heads * minibatch * (2 * 2 + 5),
        "bytes": 2 * rows * (2 * forward + backward),
    }


def attn_full_cost(model: dict, minibatch: int) -> dict:
    """The `attn_full` scope of ALL full-attention layers for one training
    step: T^2 / 2 key positions a head under the causal mask
    (`_core_cost`)."""
    t = model["sample_tokens"]
    return _core_cost(model, minibatch, FULL, t * t / 2)


def attn_window_cost(model: dict, minibatch: int) -> dict:
    """The `attn_window` scope of ALL sliding layers for one training
    step: T W - W^2 / 2 key positions a head under the band (a query at t
    reads min(t + 1, W) keys), W = `sliding_window` capped at T."""
    t = model["sample_tokens"]
    w = min(model["sliding_window"], t)
    return _core_cost(model, minibatch, SLIDING, t * w - w * w / 2)


def moe_experts_cost(model: dict, pairs: float, steps: int) -> dict:
    """The held experts' three products for `pairs` (token, expert) pairs
    COUNTED over `steps` training steps, all layers: 6 FLOPs a weight a
    pair (forward 2, backward 4).  Bytes: each held expert's float32
    weights read forward and backward and its gradient written, once a
    step, plus a pair's input row read (2 B an element) and output row
    written (4 B) forward and the reverse backward."""
    m = _matmul_params(model)
    held = _layers(model, "mlp_layer_types", SPARSE) * model["experts_held"]
    return {
        "flops": 6 * m["expert"] * pairs,
        "bytes": steps * 3 * 4 * held * m["expert"]
        + pairs * 2 * 6 * model["hidden_size"],
    }


def step_cost(model: dict, minibatch: int) -> dict:
    """The least a training step needs, from shapes.  FLOPs: 6 a matmul
    parameter a token over the attention projections, a dense layer, the
    routers and the head; the routed experts at the EXPECTED pairs of a
    uniform router (tokens x k x held / all); the attention cores' score
    and value products over the key positions the masks require, forward
    and backward at twice that.  No recomputation.  Bytes: AdamW reads
    weight, gradient and two moments and writes weight and two moments,
    7 x 4 bytes a parameter."""
    m = _matmul_params(model)
    tokens = minibatch * model["sample_tokens"]
    pairs = (
        _layers(model, "mlp_layer_types", SPARSE) * tokens
        * model["num_experts_per_tok"] * model["experts_held"]
        / model["num_experts"]
    )
    # forward 2 products + backward 4 of a core's 9 with rematerialisation
    attention = (
        attn_full_cost(model, minibatch)["flops"]
        + attn_window_cost(model, minibatch)["flops"]
    ) * 6 / 9
    dense = m["attn"] + m["dense"] + m["router"] + m["head"]
    return {
        "flops": 6 * dense * tokens + 6 * m["expert"] * pairs + attention,
        "bytes": 7 * 4 * _all_params(model),
    }
