"""Plain reference of SDAR's BLOCK-DIFFUSION training step (a decoder of
routed experts behind a renormalised softmax router, a norm on every query
and key head, plain rotary over the whole head; every sequence run as a
noised copy beside its clean copy under the three-part block-diffusion
mask, the loss over the masked positions weighted by 1 / t) for ONE chip's
share of it: the range of experts `model` says is held, the slice of the
vocabulary it gives.  float32 `jax.numpy`, no kernel, no custom backward,
no tiles of keys, and no code of the program or of another reference.  One
record: T tokens `x0`, a mask `m` [T] and a noise level `t`, GIVEN (the
features; `sample` draws them here by its own draw); B = `block_length`,
M = `mask_token_id`, H query heads over Hkv key-value heads of D::

    x_t[i] = M if m[i] else x0[i]
    u   = E[concat(x_t, x0)]                          [2T, hidden]
    pos = concat(0..T-1, 0..T-1)
    for a position i of the 2T:  noised(i) = i < T,  blk(i) = (i mod T) // B
    allowed(i, j) =   noised(i) and noised(j)         and blk(j) == blk(i)
                   or noised(i) and not noised(j)     and blk(j) <  blk(i)
                   or not noised(i) and not noised(j) and blk(j) <= blk(i)
    layer:
      a = rmsnorm(u)
      q = a Wq -> [2T, H, D]   k = a Wk -> [2T, Hkv, D]   v = a Wv
      q = rope(rmsnorm_D(q; w_qn), pos)   k = rope(rmsnorm_D(k; w_kn), pos)
      o = softmax(q k^T / sqrt(D) + (0 where allowed else -inf)) v
          query head j reads key-value head j // (H / Hkv); the mask
          written out DENSE over all 2T keys from the three lines above,
          one block of queries at a time so that 16,384 positions fit
      u = u + concat_heads(o) Wo
      n = rmsnorm(u)
      p = softmax(n W_r) over ALL experts; the k largest; g = p at the
          chosen over their sum; the experts by a loop over the held
          range, each over every row, a row's weight zero where the
          expert is not among its k; a row none of whose choices is held
          adds 0; what experts held elsewhere would add is left out, here
          as in the program.  (A `dense` layer, which no published layer
          is: u + Wd (silu(Wgate n) * Wup n).)
    logits = rmsnorm(u[0:T]) W_head                   the NOISED half alone
    loss = (1 / T) sum_i m[i] (1 / t) CE(logits[i], x0[i])

The balancing loss (`balance_alpha` x sum_i f_i P_i over a record's 2T
rows, a layer, the mean over the records) is ADDED to that explicitly by
`loss_and_balance`; the program injects its gradient and reports the
weighted cross-entropy alone, and the two gradients must agree.

`forward` returns the logits of the noised half at EVERY position, masked
or not.  The same code also runs AT THE PRECISION THE CONFIGURATION STATES
(`"stated"`: the operands of the four attention projections, of the score
and value products, of the experts and the head rounded to bfloat16 with
float32 accumulation, everything else float32), with EVERY weight and
activation in bfloat16 (`"bfloat16"`: the nearest precision below the
stated one, which the cell's limits refuse), over the tokens clear of a
top-k tie (`"highest_clear"`), and with five planted faults in what is new
here: `"causal"` (a plain causal mask over the 2T positions), `"leak"` (a
noised query also reads the CLEAN copy of its own block: `<=` where the
rule says `<`), `"no_clean"` (the noised half alone, T positions, every
noised query reading every noised key: a masked-LM step),
`"positions_run_on"` (the clean half at positions T..2T-1) and
`"shifted"` (position i - 1's logits stand where position i's should: the
autoregressive shift kept).

Beside it: where the program's own outputs come from (`program`), the
least work of a training step (`step_cost`) and of the scopes whose
roofline share the benchmark reports (`attn_proj_cost`,
`attn_blockdiff_cost`, `moe_experts_cost`).

Departures from the equations of ISSUE 51: none.  What the source's
`config.json` leaves open is listed in the configuration's `assumed` (the
block length, the noise schedule and its one `t` a sequence, no shift, the
mask id, the head norms, the router's form, the balancing loss over a
record's 2T rows, rotary columns in half-split order, a float32 residual
stream).
"""

from __future__ import annotations

import math
import sys

import numpy as np

#: A token is CLEAR of a tie when, in every expert layer, the router's
#: logit of its last chosen expert and that of the first one left out lie
#: at least this far apart in the reference at `highest`, for its noised
#: row (the one whose logits are compared).
CLEAR_MARGIN = 0.01

#: "outputs": what `program` returned last (`highest_clear` repeats its
#: rows where a token is not clear).
_PROGRAM = {}


def sample(seed: int, rows: int, model: dict):
    """-> the features of `rows` records: (tokens [rows, T] int32 from the
    vocabulary slice, mask [rows, T] bool, t [rows] float32), one `t` a
    record from U(t_min, 1] and each position masked with probability
    `t`: the stated distribution by this file's own draw (the comparison
    is at GIVEN features)."""
    rng = np.random.default_rng(seed)
    tokens = rng.integers(
        0, model["vocab_size"], size=(rows, model["sample_tokens"])
    ).astype(np.int32)
    t_min = float(model.get("t_min", 1e-3))
    t = (t_min + (1.0 - t_min) * (1.0 - rng.random(rows))).astype(np.float32)
    mask = rng.random(tokens.shape) < t[:, None]
    return tokens, mask, t


def weights(step_dir: str, features, model: dict, program_state=None):
    """The flax params of the job's checkpoint, as the program's saver
    unpickled them (one read serves both sides)."""
    return program_state.params


def program(args, features):
    """The program's own logits of the noised half for `features` at the
    job's last checkpoint (the trainer is built as
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
    # Only the weights go to the device: the two Adam moments would leave
    # the reference no room beside them.
    trainer.state = state._replace(opt_state=())
    _PROGRAM["outputs"] = np.asarray(
        trainer.eval_step(features)["logits"], np.float32
    )
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
    hidden = _silu(_mm(x, p["gate_proj"]["kernel"], rounded)) * _mm(
        x, p["up_proj"]["kernel"], rounded
    )
    return _mm(hidden, p["down_proj"]["kernel"], rounded)


def _rotary(x, positions, model):
    """x [rows, heads, D]: pair i is (x_i, x_{i + D/2}), turned by
    position x theta^(-2i/D); every column of the head belongs to a
    pair."""
    import jax.numpy as jnp

    dim = model["head_dim"]
    inv_freq = float(model["rope_theta"]) ** (
        -2.0 * np.arange(dim // 2, dtype=np.float64) / dim
    )
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32
    )[None, :]
    cos = jnp.cos(angles)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, None, :].astype(x.dtype)
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def allowed(rows, cols, tokens: int, length: int, low=frozenset()):
    """The mask, dense: [len(rows), len(cols)] bool for query positions
    `rows` and key positions `cols` of the 2 `tokens` positions [noised |
    clean], from the three lines of the module docstring (`leak`: the
    second line's `<` as `<=`; `causal`: j <= i and nothing else)."""
    i, j = rows[:, None], cols[None, :]
    if "causal" in low:
        return j <= i
    noised_i, noised_j = i < tokens, j < tokens
    blk_i, blk_j = (i % tokens) // length, (j % tokens) // length
    before = (blk_j <= blk_i) if "leak" in low else (blk_j < blk_i)
    return (
        (noised_i & noised_j & (blk_j == blk_i))
        | (noised_i & ~noised_j & before)
        | (~noised_i & ~noised_j & (blk_j <= blk_i))
    )


def _attention(p, x, model, positions, mask_of, low=frozenset(),
               query_block=128):
    """x [rows, hidden] at `positions`; `mask_of(query rows' indices)` ->
    [block, rows] bool, which keys each of them reads."""
    import jax.numpy as jnp

    rows = x.shape[0]
    heads, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["head_dim"]
    blocks = "blocks" in low
    op = _bf16 if blocks else (lambda a: a)
    q = _mm(x, p["q_proj"]["kernel"], blocks).reshape(rows, heads, d)
    k = _mm(x, p["k_proj"]["kernel"], blocks).reshape(rows, hkv, d)
    v = _mm(x, p["v_proj"]["kernel"], blocks).reshape(rows, hkv, d)
    if model.get("qk_norm", True):
        eps = model["rms_norm_eps"]
        q = _rms_norm(q, p["q_norm"]["weight"], eps)
        k = _rms_norm(k, p["k_norm"]["weight"], eps)
    q, k = _rotary(q, positions, model), _rotary(k, positions, model)
    # Query head j reads key-value head j // (heads / hkv).
    k, v = (jnp.repeat(a, heads // hkv, axis=1) for a in (k, v))
    outs = []
    for start in range(0, rows, query_block):
        qb = q[start:start + query_block]
        scores = jnp.einsum("qhd,khd->hqk", op(qb), op(k)) / math.sqrt(d)
        reads = mask_of(jnp.arange(start, start + qb.shape[0]))
        scores = jnp.where(reads[None], scores, -jnp.inf)
        # softmax, written out: the weights are rounded (where they are)
        # before they are normalised, the sum is of the unrounded ones.
        weights = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
        mixed = jnp.einsum("hqk,khd->qhd", op(weights), op(v))
        total = jnp.moveaxis(jnp.sum(weights, -1), 1, 0)[..., None]
        outs.append(mixed / total)
    out = jnp.concatenate(outs)                       # [rows, heads, D]
    return _mm(out.reshape(rows, heads * d), p["o_proj"]["kernel"], blocks)


def _route(p, x, model):
    """-> (probabilities [rows, E], chosen ids [rows, k], their weights
    [rows, k], how far each row's last chosen LOGIT lies above the first
    one left out [rows])."""
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
    """ONE record's sum_i f_i P_i times alpha over its rows (both copies):
    f_i = (times expert i was chosen) E / (k rows), a constant; P_i = the
    mean of p_i over the rows; over all E experts."""
    import jax
    import jax.numpy as jnp

    rows, experts = probs.shape
    counts = jnp.sum(
        (ids[:, :, None] == jnp.arange(experts)).astype(probs.dtype), (0, 1)
    )
    f = jax.lax.stop_gradient(counts) * experts / (ids.shape[1] * rows)
    return model.get("balance_alpha", 0.0) * jnp.sum(
        f * jnp.mean(probs, axis=0)
    )


def _watch():
    return {"chosen": [], "margins": [], "balance": []}


def _experts(p, x, model, low=frozenset(), watch=None):
    """Softmax router over all experts, renormalised over the chosen; the
    held range's part and nothing else.  `watch`: a dict whose lists
    receive this layer's choices [rows, k] (`chosen`), how far each row
    was from a tie [rows] (`margins`) and the balancing loss
    (`balance`)."""
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


def is_dense(model: dict, layer: int) -> bool:
    """The source's rule: layer i is sparse unless it is in
    `mlp_only_layers` or (i + 1) is no multiple of `decoder_sparse_step`."""
    return layer in tuple(model.get("mlp_only_layers", ())) or bool(
        (layer + 1) % model.get("decoder_sparse_step", 1)
    )


def decoder(w: dict, tokens, mask, model: dict, low=frozenset(), watch=None):
    """One record (tokens [T], mask [T]) -> the logits of its noised half
    [T, V], in the dtype of `w`; `low`: what departs from float32
    (`blocks`: products round their operands to bfloat16; `causal`,
    `leak`, `no_clean`, `positions_run_on`, `shifted`: the planted
    faults); `watch`: see `_experts`."""
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]
    t, length = tokens.shape[0], model["block_length"]
    stack = w["model"]
    mask_id = model.get("mask_token_id", -1) % model["vocab_size"]
    noised = jnp.where(jnp.asarray(mask, bool), mask_id, tokens)
    at = jnp.arange(t)
    if "no_clean" in low:  # the noised half alone, every key read
        ids, positions = noised, at
        mask_of = lambda rows: jnp.ones((rows.shape[0], t), bool)  # noqa: E731
    else:
        ids = jnp.concatenate([noised, tokens])
        positions = jnp.concatenate(
            [at, at + t if "positions_run_on" in low else at]
        )
        every = jnp.arange(2 * t)
        mask_of = lambda rows: allowed(rows, every, t, length, low)  # noqa: E731
    x = stack["embed_tokens"][ids]
    for i in range(model["num_hidden_layers"]):
        p = stack[f"layers_{i}"]
        x = x + _attention(
            p["self_attn"], _rms_norm(x, p["input_layernorm"]["weight"], eps),
            model, positions, mask_of, low,
        )
        u = _rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
        if is_dense(model, i):
            x = x + _gated_mlp(p["mlp"], u, "blocks" in low)
        else:
            x = x + _experts(p["mlp"], u, model, low, watch)
    logits = _mm(
        _rms_norm(x[:t], stack["norm"]["weight"], eps), w["lm_head"],
        "blocks" in low,
    )
    if "shifted" in low:  # position i - 1's logits where position i's belong
        logits = jnp.concatenate([logits[:1], logits[:-1]])
    return logits


#: precision -> (dtype of every weight and activation, what departs)
PRECISIONS = {
    "highest": ("float32", frozenset()),
    "highest_clear": ("float32", frozenset()),
    "stated": ("float32", frozenset({"blocks"})),
    "bfloat16": ("bfloat16", frozenset()),
    "causal": ("float32", frozenset({"causal"})),
    "leak": ("float32", frozenset({"leak"})),
    "no_clean": ("float32", frozenset({"no_clean"})),
    "positions_run_on": ("float32", frozenset({"positions_run_on"})),
    "shifted": ("float32", frozenset({"shifted"})),
}


def _sparse_layers(model: dict) -> int:
    return sum(
        not is_dense(model, i) for i in range(model["num_hidden_layers"])
    )


def forward(w: dict, features, model: dict, precision: str = "highest"):
    """features (tokens [rows, T], mask [rows, T], t [rows]) -> the logits
    of the noised half [rows, T, V] float32 (`t` weighs the loss and moves
    no logit).  `highest`: float32 throughout.  `stated`: what the
    configuration states (bfloat16 operands in the blocks' products, the
    rest float32).  `bfloat16`: the same code with EVERY weight and
    activation in bfloat16.  `causal`, `leak`, `no_clean`,
    `positions_run_on`, `shifted`: `highest` with one piece of what is
    new here got wrong: planted faults, reported beside the limits.

    `highest_clear`: `highest` over the tokens that are clear of a tie
    (`CLEAR_MARGIN`) in every expert layer, as `mellum_reference.py`
    reads it: a top-k selection is discontinuous, and with 16 of 128
    experts held and NOTHING beside them in the sublayer a flipped last
    choice is a whole routed contribution gained or lost.  Which tokens
    are clear is decided HERE, from the reference's own logits at
    `highest` on the token's NOISED row; for the others this returns the
    program's own rows (`program` kept them), so that their difference is
    exactly 0 in the harness's rms over all rows."""
    import jax
    import jax.numpy as jnp

    if precision not in PRECISIONS:
        raise ValueError(f"no precision {precision!r}")
    dtype, low = PRECISIONS[precision]
    tokens, mask, _ = features
    t = np.shape(tokens)[-1]
    watch = _watch() if precision in ("highest", "highest_clear") else None
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, dtype), w)
        out = jnp.stack([
            decoder(w, row, noise, model, low, watch).astype(jnp.float32)
            for row, noise in zip(jnp.asarray(tokens), jnp.asarray(mask))
        ])
    if watch is None or any(
        isinstance(ids, jax.core.Tracer) for ids in watch["chosen"]
    ):
        return out
    layers = _sparse_layers(model)
    if precision == "highest" or not layers:
        _log_held_pairs(watch["chosen"], len(tokens), model)
        return out
    margin = jnp.stack([  # [rows, T]: the least margin over the layers
        jnp.min(jnp.stack(
            [m[:t] for m in watch["margins"][r * layers:(r + 1) * layers]]
        ), 0)
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
    the pairs the held experts carry in the compared sample, a layer, over
    both copies' rows, and the rows none of whose choices is held."""
    first, held = model["experts_first"], model["experts_held"]
    layers = _sparse_layers(model)
    if not layers:
        return
    pairs, none_held = [0] * layers, [0] * layers
    for i, ids in enumerate(chosen):
        here = (np.asarray(ids) >= first) & (np.asarray(ids) < first + held)
        pairs[i % layers] += int(here.sum())
        none_held[i % layers] += int((~here.any(axis=-1)).sum())
    uniform = (rows * 2 * model["sample_tokens"] * model["num_experts_per_tok"]
               * held / model["num_experts"])
    print(
        f"reference: pairs on the {held} held experts in the compared "
        f"sample, a layer: {pairs}; a uniform router gives {uniform:.0f}; "
        f"rows with no choice held, a layer: {none_held}",
        file=sys.stderr, flush=True,
    )


def loss_and_balance(w: dict, features, labels, model: dict,
                     low=frozenset()):
    """features as `forward`'s, labels [rows, T] (the clean tokens) ->
    (the mean over the records of (1 / T) sum_i m[i] (1 / t) CE(logits[i],
    labels[i]), float32 at `highest`; the balancing loss: alpha x the mean
    over the records of sum_i f_i P_i, summed over the expert layers).  A
    training step descends their SUM; the program reports the first.
    `low`: the loss's own planted faults, a piece each: `unweighted` (no
    1 / t), `per_masked` (normalised by the masked positions, not by T),
    `every_position` (the unmasked positions too), `shifted` (the label of
    position i is token i + 1), and the decoder's."""
    import jax
    import jax.numpy as jnp

    tokens, mask, t = features
    total, balance = [], 0.0
    with jax.default_matmul_precision("highest"):
        for row, noise, level, target in zip(tokens, mask, t, labels):
            watch = _watch()
            logits = decoder(
                w, row, noise, model, low - {"shifted"}, watch=watch
            )
            if "shifted" in low:
                target = jnp.concatenate([target[1:], target[-1:]])
            logp = jax.nn.log_softmax(logits, axis=-1)
            cross_entropy = -jnp.take_along_axis(
                logp, target[:, None], axis=-1
            )[:, 0]
            m = jnp.asarray(noise, jnp.float32)
            if "every_position" in low:
                m = jnp.ones_like(m)
            weight = m if "unweighted" in low else m / level
            norm = jnp.sum(m) if "per_masked" in low else m.shape[0]
            total.append(jnp.sum(weight * cross_entropy) / norm)
            balance = balance + sum(watch["balance"]) / len(tokens)
    return jnp.mean(jnp.stack(total)), balance


def loss_fn(w: dict, features, labels, model: dict, low=frozenset()):
    """The loss the program REPORTS: the weighted cross-entropy alone."""
    return loss_and_balance(w, features, labels, model, low)[0]


# -- the least work ------------------------------------------------------------


def _matmul_params(model: dict) -> dict:
    """Parameters that multiply a row's activations, by part."""
    d, hd = model["hidden_size"], model["head_dim"]
    layers = model["num_hidden_layers"]
    sparse = _sparse_layers(model)
    return {
        # q and o a query head, k and v a key-value head, a layer
        "attn": layers * 2 * d * hd * (
            model["num_attention_heads"] + model["num_key_value_heads"]
        ),
        "dense": (layers - sparse) * 3 * d * model["intermediate_size"],
        "router": sparse * d * model["num_experts"],
        "expert": 3 * d * model["moe_intermediate_size"],  # ONE expert
        "head": d * model["vocab_size"],
    }


def _all_params(model: dict) -> int:
    """Every parameter this chip holds (the layers' two norms, the two
    head norms and the final norm included)."""
    m = _matmul_params(model)
    d, layers = model["hidden_size"], model["num_hidden_layers"]
    head_norms = 2 * model["head_dim"] * bool(model.get("qk_norm", True))
    return (
        m["attn"] + m["dense"] + m["router"] + m["head"]
        + _sparse_layers(model) * model["experts_held"] * m["expert"]
        + model["vocab_size"] * d + layers * (2 * d + head_norms) + d
    )


def allowed_pairs(model: dict) -> int:
    """The (query, key) pairs the mask allows, a head a record: T^2 + 4 T
    at B = 4 (2 sum_i (B floor(i / B) + B) over the T tokens: a clean row
    reads the clean blocks up to and with its own, its noised twin as
    many keys, its own noised block in their last block's place)."""
    t, length = model["sample_tokens"], model["block_length"]
    return 2 * sum(length * (i // length) + length for i in range(t))


def attn_proj_cost(model: dict, minibatch: int) -> dict:
    """The `attn_proj` scope (q, k, v and o of every layer) for one
    training step AS THE CONFIGURATION RUNS IT, over the 2 T ROWS of a
    record: 8 FLOPs a weight a row (forward 2, once more under the
    layer's rematerialisation, backward 4).  Bytes: the float32 weights
    read in each of the three passes and their gradient written, plus a
    row's columns in and out of the four products, bfloat16 in and
    float32 out forward (twice) and the reverse backward."""
    m = _matmul_params(model)
    rows = 2 * minibatch * model["sample_tokens"]
    d, hd = model["hidden_size"], model["head_dim"]
    heads = model["num_attention_heads"]
    wide = (heads + 2 * model["num_key_value_heads"]) * hd
    columns = model["num_hidden_layers"] * (2 * d + wide + heads * hd)
    return {
        "flops": 8 * m["attn"] * rows,
        "bytes": 4 * 4 * m["attn"] + 3 * 6 * columns * rows,
    }


def attn_blockdiff_cost(model: dict, minibatch: int) -> dict:
    """The `attn_blockdiff` scope (scores, softmax, values of every
    layer) for one training step AS THE CONFIGURATION RUNS IT, over the
    pairs the MASK allows (`allowed_pairs`), whichever engine implements
    it and whatever tiles it visits, by the rule per pair of the other
    references' attention cores.  FLOPs, each product 2 x pairs x D a
    query head: a forward is q k^T and p v; it runs once more under the
    layer's rematerialisation; the backward is five products.  Bytes,
    bfloat16, over the 2 T rows: a forward reads q and writes o a query
    head and reads k and v a key-value head; the backward reads q, o, dO
    and writes dq a query head, reads k, v and writes dk, dv a key-value
    head."""
    layers = model["num_hidden_layers"]
    heads = layers * model["num_attention_heads"]
    kv_heads = layers * model["num_key_value_heads"]
    rows = 2 * minibatch * model["sample_tokens"] * model["head_dim"]
    forward = 2 * heads + 2 * kv_heads
    backward = 4 * heads + 4 * kv_heads
    return {
        "flops": 2 * allowed_pairs(model) * model["head_dim"] * heads
        * minibatch * (2 * 2 + 5),
        "bytes": 2 * rows * (2 * forward + backward),
    }


def moe_experts_cost(model: dict, pairs: float, steps: int) -> dict:
    """The held experts' three products for `pairs` (row, expert) pairs
    COUNTED over `steps` training steps, all layers: 6 FLOPs a weight a
    pair (forward 2, backward 4).  Bytes: each held expert's float32
    weights read forward and backward and its gradient written, once a
    step, plus a pair's input row read (2 B an element) and output row
    written (4 B) forward and the reverse backward."""
    m = _matmul_params(model)
    held = _sparse_layers(model) * model["experts_held"]
    return {
        "flops": 6 * m["expert"] * pairs,
        "bytes": steps * 3 * 4 * held * m["expert"]
        + pairs * 2 * 6 * model["hidden_size"],
    }


def step_cost(model: dict, minibatch: int) -> dict:
    """The least a training step needs, from shapes.  FLOPs: 6 a matmul
    parameter a ROW over the attention projections, a dense layer and the
    routers, 2 T rows a record, and over the head, T rows; the routed
    experts at the EXPECTED pairs of a uniform router (2 T rows x k x
    held / all); the attention cores' score and value products over the
    pairs the mask allows, forward and backward at twice that.  No
    recomputation.  (6 x parameters x the record's tokens understates
    this step twofold.)  Bytes: AdamW reads weight, gradient and two
    moments and writes weight and two moments, 7 x 4 bytes a parameter."""
    m = _matmul_params(model)
    tokens = minibatch * model["sample_tokens"]
    rows = 2 * tokens
    pairs = (
        _sparse_layers(model) * rows * model["num_experts_per_tok"]
        * model["experts_held"] / model["num_experts"]
    )
    # forward 2 products + backward 4 of a core's 9 with rematerialisation
    attention = attn_blockdiff_cost(model, minibatch)["flops"] * 6 / 9
    return {
        "flops": 6 * (m["attn"] + m["dense"] + m["router"]) * rows
        + 6 * m["head"] * tokens + 6 * m["expert"] * pairs + attention,
        "bytes": 7 * 4 * _all_params(model),
    }
