"""Plain reference of Ling-3.0-flash's language model (five Kimi Delta
Attention layers, a delta rule whose decay is one rate a KEY CHANNEL
under a bounded gate, to one latent-attention layer; a leading dense
layer, then routed experts behind a 512-way sigmoid router that picks its
groups first) for ONE chip's share of it: the stage of layers `model`
says (`first_layer`, `num_hidden_layers`), the range of experts it says is
held, the slice of the vocabulary it gives.  float32 `jax.numpy`, no
kernel, no custom backward, no chunk and no sub-chunk, and no code of the
program.  For the layer of published index i::

    h  = rmsnorm(x)
    delta attention (every layer but those with (i + 1) % 6 == 0):
        q, k, v = silu(conv4(h Wq)), silu(conv4(h Wk)), silu(conv4(h Wv))
        q = q / |q| / sqrt(Dk) by head,  k = k / |k| by head
        beta = sigmoid(h Wb) [H]
        g = bound * sigmoid(exp(A_log)[h] * (h Wf + dt_bias))  [H, Dk]
        TOKEN BY TOKEN, per head, S [Dk, Dv] from zero (a `lax.scan`
        over T):  S <- diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);
                  S <- S + k_t d^T;  o_t = S^T q_t
        y = w * o / rms_head(o) * sigmoid(h Wg)[h];   x = x + y Wo
    latent attention ((i + 1) % 6 == 0):
        q = h Wq [H, 128 + 64];  [c | k_pe] = h Wa;  c = rmsnorm(c)
        [k_nope | v] = c Wb;  k_h = [k_nope,h | k_pe]
        use_qk_norm: q_h, k_h <- rmsnorm over the head's 192 columns
        rotary (theta 6e6, frequencies computed HERE) over the last 64
        a = softmax(q k^T / sqrt(192) + causal mask) v, one block of
        queries at a time so that 8192 tokens fit
        a[:, h] *= sigmoid(h_in Wg)[h];  x = x + concat(a) Wo
    h2 = rmsnorm(x)
    dense (i < first_k_dense_replace): x = x + Wd (silu(Wgate h2) * Wup h2)
    sparse: s = sigmoid(h2 Wr) over ALL experts; s' = s + b; the experts
            as n_group runs; a group's score the sum of its two largest
            s' (by SORTING); the topk_group best groups; the k largest s'
            inside them; w = scale * s_chosen / sum(s_chosen); the experts
            by a loop over the held range, each over every token, a
            token's weight zero where the expert is not among its k; plus
            the ungated shared expert.  What experts held elsewhere would
            add is left out, here as in the program.
    logits = rmsnorm(x) W_head

The same code also runs AT THE PRECISION THE CONFIGURATION STATES
(`forward(..., "stated")`: the operands of the projections, of the score
and value products, of the dense layer, the experts, the shared expert and
the head rounded to bfloat16 with float32 accumulation, everything else
float32: norms, convolutions, gates, beta, the rule and its state, tables,
the router), with EVERY weight and activation in bfloat16 (`"bfloat16"`:
the nearest precision below the stated one, which the cell's limits
refuse), over the tokens clear of a top-k tie (`"highest_clear"`), and
with three planted faults in what is new here: `"scalar_decay"` (the gate
averaged over a head's channels: the rule the repo had), `"no_group_limit"`
(the top k over all experts at once) and `"no_routed_scale"` (the routed
weights left at sum 1).

Beside it: where the program's own outputs come from (`program`), the
least work of a training step (`step_cost`) and of the scopes whose
roofline share the benchmark reports (`kda_scan_cost`, `kda_cost`,
`mla_core_cost`, `moe_experts_cost`).

What the source's `config.json` leaves open is listed in the
configuration's `assumed`, each with the other reading.
"""

from __future__ import annotations

import math
import sys

import numpy as np

CHUNK = 64  # the program's chunk: only `kda_scan_cost` needs it

#: A token is CLEAR of a tie when, in every expert layer, the selection
#: score of its last chosen expert and that of the first one left out
#: (within the chosen groups), and the scores of the last chosen GROUP and
#: of the first group left out, lie at least this far apart in the
#: reference at `highest`.  The scores are sigmoids, 512 of them in (0, 1);
#: bfloat16 operands upstream of a router move one by about 1e-3.
CLEAR_MARGIN = 2e-3

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
    # Only the weights go to the device: the two Adam moments (6.6 GB of
    # the 9.9 GB saved) would leave the reference no room beside them.
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
    import jax.numpy as jnp

    return weight * x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _sigmoid(x):
    """1 / (1 + e^-x) as (tanh(x / 2) + 1) / 2: the decay's gate reaches
    arguments of -100 and less, where e^-x squared overflows in the
    quotient's derivative."""
    import jax.numpy as jnp

    return 0.5 * (jnp.tanh(0.5 * x) + 1.0)


def _silu(x):
    return x * _sigmoid(x)


def _gated_mlp(p, x, rounded: bool):
    """down(silu(gate x) * up x)."""
    hidden = _silu(_mm(x, p["gate_proj"]["kernel"], rounded)) * _mm(
        x, p["up_proj"]["kernel"], rounded
    )
    return _mm(hidden, p["down_proj"]["kernel"], rounded)


def is_latent(model: dict, index: int) -> bool:
    """Whether the layer of published index `index` is a latent-attention
    layer."""
    return (index + 1) % model["layer_group_size"] == 0


def stage(model: dict) -> range:
    """The published indices of the layers this chip holds."""
    first = model.get("first_layer", 0)
    return range(first, first + model["num_hidden_layers"])


def _conv_silu(x, taps):
    """out[t] = silu(sum_j taps[j] x[t - (K - 1) + j]): causal, depthwise."""
    import jax.numpy as jnp

    width, t = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    return _silu(sum(padded[j:j + t] * taps[j] for j in range(width)))


def _delta_rule(q, k, v, g, beta):
    """q, k, g [T, H, Dk], v [T, H, Dv], beta [T, H] -> o [T, H, Dv], one
    token a step; the decay a rate a key channel: a row of the state."""
    import jax
    import jax.numpy as jnp

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[:, :, None]
        delta = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + jnp.einsum("hk,hv->hkv", k_t, delta)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), q.dtype)
    return jax.lax.scan(step, state, (q, k, v, g, beta))[1]


def decay_gate(p, x, model, low=frozenset()):
    """The log-decay [T, H, Dk] of a delta-attention layer for its normed
    input x, in (kda_lower_bound, 0); `scalar_decay`: a head's mean over
    its channels in every channel."""
    import jax.numpy as jnp

    h, dk = model["num_attention_heads"], model["head_dim"]
    pre = _mm(x, p["f_proj"]["kernel"], "blocks" in low) + p["dt_bias"]
    g = model["kda_lower_bound"] * _sigmoid(
        jnp.exp(p["A_log"])[:, None] * pre.reshape(-1, h, dk)
    )
    if "scalar_decay" in low:
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    return g


def _delta_attention(p, x, model, low=frozenset()):
    import jax.numpy as jnp

    t = x.shape[0]
    h, dk = model["num_attention_heads"], model["head_dim"]
    blocks = "blocks" in low
    q, k, v = (
        _conv_silu(
            _mm(x, p[f"{name}_proj"]["kernel"], blocks), p[f"{name}_conv1d"]
        ).reshape(t, h, dk)
        for name in "qkv"
    )
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / math.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    beta = _sigmoid(_mm(x, p["b_proj"]["kernel"], blocks))
    o = _delta_rule(q, k, v, decay_gate(p, x, model, low), beta)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + model["rms_norm_eps"])
    o = p["o_norm"] * o * _sigmoid(x @ p["g_proj"])[:, :, None]
    return _mm(o.reshape(t, h * dk), p["o_proj"]["kernel"], blocks)


def rotary_inv_freq(model: dict):
    """theta^(-2i / rotary_dim) of the rotary pairs, float64 numpy."""
    dim = model["rotary_dim"]
    return float(model["rope_theta"]) ** (
        -2.0 * np.arange(dim // 2, dtype=np.float64) / dim
    )


def _rotary(x, model):
    """x [T, heads, rope]: pair i is (x_i, x_{i + rope/2}), turned by
    position x inv_freq_i."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        rotary_inv_freq(model), jnp.float32
    )[None, :]
    cos = jnp.cos(angles)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, None, :].astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(p, x, model, low=frozenset(), query_block=256):
    import jax.numpy as jnp

    t = x.shape[0]
    h, nope, rope, dv = (model["num_attention_heads"],
                         model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                         model["v_head_dim"])
    rank, eps = model["kv_lora_rank"], model["rms_norm_eps"]
    blocks = "blocks" in low
    op = _bf16 if blocks else (lambda a: a)
    q = _mm(x, p["q_proj"]["kernel"], blocks).reshape(t, h, nope + rope)
    latent = _mm(x, p["kv_a_proj_with_mqa"]["kernel"], blocks)
    c = _rms_norm(latent[:, :rank], p["kv_a_layernorm"]["weight"], eps)
    kv = _mm(c, p["kv_b_proj"]["kernel"], blocks).reshape(t, h, nope + dv)
    k = jnp.concatenate([
        kv[..., :nope],
        jnp.broadcast_to(latent[:, None, rank:], (t, h, rope)),
    ], -1)
    if model.get("use_qk_norm", True):
        q = _rms_norm(q, p["q_norm"]["weight"], eps)
        k = _rms_norm(k, p["k_norm"]["weight"], eps)
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], model)], -1)
    k = jnp.concatenate([k[..., :nope], _rotary(k[..., nope:], model)], -1)
    v = kv[..., nope:]
    scale = (nope + rope) ** -0.5
    positions = jnp.arange(t)
    outs = []
    for start in range(0, t, query_block):
        qb = q[start:start + query_block]
        scores = jnp.einsum("qhd,khd->hqk", op(qb), op(k)) * scale
        allowed = (
            positions[None, :] <= positions[start:start + query_block, None]
        )
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        # softmax, written out: the weights are rounded (where they are)
        # before they are normalised, the sum is of the unrounded ones.
        weights = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
        mixed = jnp.einsum("hqk,khd->qhd", op(weights), op(v))
        total = jnp.moveaxis(jnp.sum(weights, -1), 1, 0)[..., None]
        outs.append(mixed / total)
    out = jnp.concatenate(outs) * _sigmoid(x @ p["g_proj"])[:, :, None]
    return _mm(out.reshape(t, h * dv), p["o_proj"]["kernel"], blocks)


def select(selection, model: dict, group_limited: bool = True):
    """selection [T, E] (the scores with the selection bias) -> (the k
    chosen experts [T, k] best first, how far each token was from another
    choice [T]), all BY SORTING: a stable descending sort takes the lower
    index of two equal scores.  Group-limited: a group's score is the sum
    of its two largest; the `topk_group` best groups; the k largest
    inside them.  The margin is the least of (the last chosen group's
    score less the first left out's) and (the last chosen expert's less
    the first left out's among the allowed)."""
    import jax.numpy as jnp

    t, experts = selection.shape
    k = model["num_experts_per_tok"]
    n_group = model.get("n_group", 1) if group_limited else 1
    margin = jnp.full((t,), jnp.inf, selection.dtype)
    if n_group > 1:
        keep = model["topk_group"]
        grouped = selection.reshape(t, n_group, experts // n_group)
        score = jnp.sum(-jnp.sort(-grouped, axis=-1)[..., :2], axis=-1)
        order = jnp.argsort(-score, axis=-1, stable=True)
        ranked = jnp.take_along_axis(score, order, axis=-1)
        if keep < n_group:
            margin = ranked[:, keep - 1] - ranked[:, keep]
        allowed = jnp.any(
            order[:, :keep, None] == jnp.arange(n_group), axis=1
        )
        selection = jnp.where(
            allowed[:, :, None], grouped, -jnp.inf
        ).reshape(t, experts)
    order = jnp.argsort(-selection, axis=-1, stable=True)
    ranked = jnp.take_along_axis(selection, order[:, :k + 1], axis=-1)
    return order[:, :k], jnp.minimum(margin, ranked[:, k - 1] - ranked[:, k])


def _experts(p, x, model, low=frozenset(), chosen=None, margins=None,
             held=None):
    """Sigmoid router over all experts, group-limited selection; the held
    range's part plus the shared expert's.  `low`: `blocks` as everywhere,
    `router` the router's product in bfloat16 operands too (a sublayer's
    test tells it apart), the planted faults.  `chosen`, `margins`: lists
    that receive this layer's choices [T, k] and margins [T]; `held`:
    another range (first, count) than the model's, and whether the shared
    expert is added, as a third entry (the share test)."""
    import jax.numpy as jnp

    blocks = "blocks" in low
    scores = _sigmoid(_mm(x, p["gate"]["weight"], "router" in low))
    ids, margin = select(
        scores + p["gate"]["e_score_correction_bias"], model,
        "no_group_limit" not in low,
    )
    if chosen is not None:
        chosen.append(ids)
    if margins is not None:
        margins.append(margin)
    top = jnp.take_along_axis(scores, ids, axis=-1)  # the bias is not in them
    if model.get("norm_topk_prob", True):
        top = top / jnp.sum(top, -1, keepdims=True)
    if "no_routed_scale" not in low:
        top = top * model["routed_scaling_factor"]
    first, count, shared = held or (
        model["experts_first"], model["experts_held"], True
    )
    y = jnp.zeros_like(x)
    for local in range(count):
        weight = jnp.sum(jnp.where(ids == first + local, top, 0.0), axis=-1)
        hidden = _silu(_mm(x, p["experts_gate_proj"][local], blocks)) * _mm(
            x, p["experts_up_proj"][local], blocks
        )
        y = y + weight[:, None] * _mm(
            hidden, p["experts_down_proj"][local], blocks
        )
    if shared:
        y = y + _gated_mlp(p["shared_experts"], x, blocks)
    return y


def decoder(w: dict, tokens, model: dict, low=frozenset(), chosen=None,
            margins=None):
    """One sequence [T] -> logits [T, V], in the dtype of `w`; `low`: what
    departs from float32 (`blocks`: products round their operands to
    bfloat16; the planted faults by name); `chosen` and `margins`: lists
    that receive every expert layer's choices and how far each was from a
    tie."""
    eps = model["rms_norm_eps"]
    stack = w["model"]
    x = stack["embed_tokens"][tokens]
    for i in stage(model):
        p = stack[f"layers_{i}"]
        u = _rms_norm(x, p["input_layernorm"]["weight"], eps)
        if is_latent(model, i):
            x = x + _attention(p["self_attn"], u, model, low)
        else:
            x = x + _delta_attention(p["linear_attn"], u, model, low)
        u = _rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
        if i < model["first_k_dense_replace"]:
            x = x + _gated_mlp(p["mlp"], u, "blocks" in low)
        else:
            x = x + _experts(p["mlp"], u, model, low, chosen, margins)
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
    "scalar_decay": ("float32", frozenset({"scalar_decay"})),
    "no_group_limit": ("float32", frozenset({"no_group_limit"})),
    "no_routed_scale": ("float32", frozenset({"no_routed_scale"})),
}


def _expert_layers(model: dict) -> int:
    return sum(i >= model["first_k_dense_replace"] for i in stage(model))


def _latent_layers(model: dict) -> int:
    return sum(is_latent(model, i) for i in stage(model))


def gate_statistics(w: dict, tokens, model: dict):
    """Over `tokens` [rows, T] at `highest` -> (the mean retention exp(g),
    the share of gate values within 1% of the bound) of the
    delta-attention layers: what the program's `kda.gates` span counts."""
    import jax
    import jax.numpy as jnp

    eps, found = model["rms_norm_eps"], []
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), w)
        for row in jnp.asarray(tokens):
            x = w["model"]["embed_tokens"][row]
            for i in stage(model):
                p = w["model"][f"layers_{i}"]
                u = _rms_norm(x, p["input_layernorm"]["weight"], eps)
                if is_latent(model, i):
                    x = x + _attention(p["self_attn"], u, model)
                else:
                    found.append(decay_gate(p["linear_attn"], u, model))
                    x = x + _delta_attention(p["linear_attn"], u, model)
                u = _rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
                if i < model["first_k_dense_replace"]:
                    x = x + _gated_mlp(p["mlp"], u, False)
                else:
                    x = x + _experts(p["mlp"], u, model)
    g = jnp.stack(found)
    return float(jnp.mean(jnp.exp(g))), float(
        jnp.mean(g < 0.99 * model["kda_lower_bound"])
    )


def forward(w: dict, tokens, model: dict, precision: str = "highest"):
    """`highest`: float32 throughout.  `stated`: what the configuration
    states (bfloat16 operands in the blocks' products, the rest float32).
    `bfloat16`: the same code with EVERY weight and activation in
    bfloat16 (norms, gates, the rule's state, tables, router and softmax
    statistics too).  `scalar_decay`, `no_group_limit`, `no_routed_scale`:
    `highest` with one planted fault each (module docstring); each has to
    read many times the tolerance.

    `highest_clear`: `highest` over the tokens that are clear of a tie
    (`CLEAR_MARGIN`).  A top-k selection is discontinuous: a token whose
    last chosen expert (or group) and the first one left out score within
    a rounding of each other gets another expert in a program that rounds
    upstream, and with 8 of 512 experts held that is a whole routed
    contribution gained or lost, which no precision of the products would
    repair.  Which tokens are clear is decided HERE, from the reference's
    own scores at `highest`; for the others this returns the program's
    own rows (`program` kept them), so that their difference is exactly 0
    in the harness's rms over all rows."""
    import jax
    import jax.numpy as jnp

    if precision not in PRECISIONS:
        raise ValueError(f"no precision {precision!r}")
    dtype, low = PRECISIONS[precision]
    watch = precision in ("highest", "highest_clear")
    chosen, margins = ([], []) if watch else (None, None)
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, dtype), w)
        out = jnp.stack([
            decoder(w, row, model, low, chosen, margins).astype(jnp.float32)
            for row in jnp.asarray(tokens)
        ])
    if not watch or any(isinstance(ids, jax.core.Tracer) for ids in chosen):
        return out
    if precision == "highest":
        _log_held_pairs(chosen, len(tokens), model)
        return out
    layers = _expert_layers(model)
    clear = jnp.stack([  # [rows, T]: the least margin over the layers
        jnp.min(jnp.stack(margins[r * layers:(r + 1) * layers]), axis=0)
        for r in range(len(tokens))
    ]) >= CLEAR_MARGIN
    theirs = _PROGRAM.get("outputs")
    if theirs is None or theirs.shape != out.shape:
        raise ValueError("`highest_clear` needs the outputs `program` kept")
    # The harness divides by the rms over ALL rows, so what it reads is the
    # distance over the clear rows times sqrt(their share): say both.
    alone = jnp.sqrt(
        jnp.sum(jnp.where(clear[..., None], (theirs - out) ** 2, 0.0))
        / jnp.sum(jnp.where(clear[..., None], out ** 2, 0.0))
    )
    print(
        f"reference: {int(clear.sum())} of {clear.size} compared tokens "
        f"are clear of a tie by {CLEAR_MARGIN:g} in every expert layer; "
        f"over them ALONE the program differs by {float(alone):.4g} of the "
        f"reference's rms",
        file=sys.stderr, flush=True,
    )
    return jnp.where(clear[..., None], out, theirs)


def _log_held_pairs(chosen, rows: int, model: dict) -> None:
    """One line on stderr (the harness keeps it in the run's `check.log`):
    the pairs the held experts carry in the compared sample, a layer."""
    first, held = model["experts_first"], model["experts_held"]
    layers = _expert_layers(model)
    pairs = [0] * layers
    for i, ids in enumerate(chosen):
        ids = np.asarray(ids)
        pairs[i % layers] += int(((ids >= first) & (ids < first + held)).sum())
    uniform = (rows * model["sample_tokens"] * model["num_experts_per_tok"]
               * held / model["num_experts"])
    print(
        f"reference: pairs on the {held} held experts in the compared "
        f"sample, a layer: {pairs}; a uniform router gives {uniform:.0f}",
        file=sys.stderr, flush=True,
    )


def loss_fn(w: dict, tokens, labels, model: dict):
    """tokens, labels [rows, T] -> mean next-token cross-entropy over all
    tokens, float32 at `highest`."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        logits = jnp.stack([decoder(w, row, model) for row in tokens])
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(
            jnp.take_along_axis(logp, labels[..., None], axis=-1)
        )


# -- the least work ------------------------------------------------------------


def _matmul_params(model: dict) -> dict:
    """Parameters that multiply a token's activations, by part."""
    d, h, hd = (model["hidden_size"], model["num_attention_heads"],
                model["head_dim"])
    nope, rope, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                      model["v_head_dim"])
    rank = model["kv_lora_rank"]
    latent = _latent_layers(model)
    dense = model["num_hidden_layers"] - _expert_layers(model)
    return {
        # q, k, v, the decay's projection and o a head, beta and the gate
        "kda": (model["num_hidden_layers"] - latent) * (
            5 * d * h * hd + 2 * d * h
        ),
        "attn": latent * (
            d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + dv) + h * dv * d + d * h
        ),
        "dense": dense * 3 * d * model["intermediate_size"],
        "router_shared": _expert_layers(model) * (
            d * model["num_experts"]
            + 3 * d * model["moe_shared_expert_intermediate_size"]
        ),
        "expert": 3 * d * model["moe_intermediate_size"],  # ONE expert
        "head": d * model["vocab_size"],
    }


def _all_params(model: dict) -> int:
    """Every parameter this chip holds (norm weights, the convolutions'
    taps, `A_log`, `dt_bias` and the routers' selection biases included)."""
    m = _matmul_params(model)
    d, h, hd = (model["hidden_size"], model["num_attention_heads"],
                model["head_dim"])
    layers, latent = model["num_hidden_layers"], _latent_layers(model)
    sparse = _expert_layers(model)
    head_norms = 2 * (model["qk_nope_head_dim"] + model["qk_rope_head_dim"])
    return (
        m["kda"] + m["attn"] + m["dense"] + m["router_shared"] + m["head"]
        + (layers - latent) * (
            3 * model["short_conv_kernel_size"] * h * hd + h * hd + h + hd
        )
        + latent * (
            model["kv_lora_rank"]
            + head_norms * bool(model.get("use_qk_norm", True))
        )
        + sparse * model["experts_held"] * m["expert"]
        + sparse * model["num_experts"]
        + model["vocab_size"] * d + layers * 2 * d + d
    )


def kda_scan_cost(model: dict, minibatch: int) -> dict:
    """The delta rule of ALL delta-attention layers for one training step
    (forward, and backward at twice the forward), from shapes, whichever
    engine computes it.  FLOPs: the products of the chunked WY form per
    chunk of C tokens and head, 2 C^2 (3 Dk + 2 Dv) inside the chunk (k k^T
    and q k^T with the decay inside them, the two applications of the
    inverse, scores x values) and 6 C Dk Dv with the state; the triangular
    inverse, the decays' elementwise passes and all recomputation are not
    counted.  Bytes: float32 q, k, v, o AND g (one rate a key channel: a
    tensor of q's size, where a head's scalar decay is 1/128 of one) and
    their gradients: 5 tensors forward (4 read, o written), 9 backward
    (q, k, v, g, dO read; dq, dk, dv, dg written); beta and its gradient."""
    h, dk = model["num_attention_heads"], model["head_dim"]
    layers = model["num_hidden_layers"] - _latent_layers(model)
    tokens = minibatch * model["sample_tokens"]
    per_chunk = 2 * CHUNK * CHUNK * 5 * dk + 6 * CHUNK * dk * dk
    chunks = -(-model["sample_tokens"] // CHUNK) * minibatch * h
    elements = tokens * h * dk  # one [B, T, H, D] tensor
    return {
        "flops": 3 * layers * chunks * per_chunk,
        "bytes": layers * 4 * (14 * elements + 3 * tokens * h),
    }


def kda_cost(model: dict, minibatch: int) -> dict:
    """The delta-attention sublayers whole (the `kda` scope) for one
    training step: 6 FLOPs a matmul parameter a token over their seven
    projections plus the rule as `kda_scan_cost` counts it; bytes the
    rule's and the projections' float32 weights read forward and backward
    and their gradients written.  No recomputation."""
    scan = kda_scan_cost(model, minibatch)
    params = _matmul_params(model)["kda"]
    tokens = minibatch * model["sample_tokens"]
    return {
        "flops": 6 * params * tokens + scan["flops"],
        "bytes": scan["bytes"] + 3 * 4 * params,
    }


def _core_products(model: dict, minibatch: int) -> int:
    """FLOPs of ONE product of the attention core a unit of head size,
    all latent layers: 2 x T^2 / 2 (the causal half) a head a sequence."""
    t = model["sample_tokens"]
    return (
        t * t * minibatch * model["num_attention_heads"]
        * _latent_layers(model)
    )


def mla_core_cost(model: dict, minibatch: int) -> dict:
    """The attention core (scores, softmax, values: the `mla_core` scope,
    whichever engine implements it) of the latent layers for one training
    step AS THE CONFIGURATION RUNS IT, from shapes.  FLOPs, each product
    over the causal half of [T, T] a head: ONE forward (q k^T over Dqk =
    nope + rope and p v over Dv: a rematerialised layer keeps the engine's
    results, so it is not run again) and the backward's five products (the
    scores again, dS K and dS^T Q over Dqk, P^T dO and dO V^T over Dv).
    Bytes, bfloat16: the forward reads q, k [Dqk a head] and v and writes
    o [Dv]; the backward reads q, k, v, o and dO and writes dq, dk, dv."""
    dqk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    dv = model["v_head_dim"]
    one = _core_products(model, minibatch)
    rows = (
        minibatch * model["sample_tokens"] * model["num_attention_heads"]
        * _latent_layers(model)
    )
    return {
        "flops": one * ((dqk + dv) + (3 * dqk + 2 * dv)),
        "bytes": 2 * rows * (
            (2 * dqk + 2 * dv) + (2 * dqk + 3 * dv) + (2 * dqk + dv)
        ),
    }


def moe_experts_cost(model: dict, pairs: float, steps: int) -> dict:
    """The held experts' three products for `pairs` (token, expert) pairs
    COUNTED over `steps` training steps, all layers: 6 FLOPs a weight a
    pair (forward 2, backward 4).  Bytes: each held expert's float32
    weights read forward and backward and its gradient written, once a
    step, plus a pair's input row read (2 B an element) and output row
    written (4 B) forward and the reverse backward."""
    m = _matmul_params(model)
    held = _expert_layers(model) * model["experts_held"]
    return {
        "flops": 6 * m["expert"] * pairs,
        "bytes": steps * 3 * 4 * held * m["expert"]
        + pairs * 2 * 6 * model["hidden_size"],
    }


def step_cost(model: dict, minibatch: int) -> dict:
    """The least a training step needs, from shapes.  FLOPs: 6 a matmul
    parameter a token over both mixers' projections and gates, the dense
    layer, routers, shared experts and head; the routed experts at the
    pairs this chip HOLDS of a uniform router's (tokens x k x held / all,
    not a token's 8); the latent layers' score and value products over the
    causal half, forward (Dqk + Dv) and backward at twice that; the delta
    rule as `kda_scan_cost` counts it.  No recomputation.  Bytes: AdamW
    reads weight, gradient and two moments and writes weight and two
    moments, 7 x 4 bytes a parameter."""
    m = _matmul_params(model)
    tokens = minibatch * model["sample_tokens"]
    pairs = (
        _expert_layers(model) * tokens * model["num_experts_per_tok"]
        * model["experts_held"] / model["num_experts"]
    )
    dqk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    attention = 3 * _core_products(model, minibatch) * (
        dqk + model["v_head_dim"]
    )
    dense = (
        m["kda"] + m["attn"] + m["dense"] + m["router_shared"] + m["head"]
    )
    return {
        "flops": 6 * dense * tokens + 6 * m["expert"] * pairs + attention
        + kda_scan_cost(model, minibatch)["flops"],
        "bytes": 7 * 4 * _all_params(model),
    }
