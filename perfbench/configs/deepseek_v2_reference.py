"""Plain reference of DeepSeek-V2 (latent attention in every layer, a
leading dense feed-forward layer, then routed experts with shared ones
and a balancing loss) for ONE chip's share of it: the range of experts
`model` says is held, the slice of the vocabulary it gives.  float32
`jax.numpy`, no kernel, no custom backward, and no code of the program:

- latent attention as written: `q = u W_q` split a head into a
  position-free and a rotary part; `[c | k_pe] = u W_kva`,
  `c = RMSNorm(c)`, `[k_nope | v] = c W_kvb`; rotary (YaRN's
  frequencies, computed HERE from the formulas) on every head's `q_pe`
  and on the one `k_pe` a token that all heads share; explicit scores
  over the 192 dimensions, an explicit causal mask, a softmax and the
  128-wide values, one block of queries at a time so that 8192 tokens
  fit;
- the dense layer and every expert `down(silu(gate u) * up u)`; the
  router a softmax over ALL experts, the top k of it, weights NOT
  renormalised; the experts by a loop over the held range, each over
  every token, the routing weight of a token being zero where the expert
  is not among its top k; the shared experts one ungated MLP.  What
  experts held elsewhere would add is left out, here as in the program,
  and that partial sum goes on to the next layer;
- the balancing loss from its definition (`balance_loss`), which
  `loss_and_balance` ADDS to the cross-entropy explicitly (the program
  injects its gradient and reports the cross-entropy alone; the two
  gradients must agree).

The same code also runs AT THE PRECISION THE CONFIGURATION STATES
(`forward(..., "stated")`: the operands of the five attention
projections, of the score and value products, of the dense layer, the
experts, the shared expert and the head rounded to bfloat16 with float32
accumulation, everything else float32), with EVERY weight and activation
in bfloat16 (`"bfloat16"`: the nearest precision below the stated one,
which the cell's tolerance refuses), and with a planted fault
(`"no_mscale"`: the softmax scale without YaRN's `mscale^2`).

Beside it: where the program's own outputs come from (`program`), the
least work of a training step (`step_cost`) and of the two scopes whose
roofline share the benchmark reports (`moe_experts_cost`,
`mla_core_cost`).

Departures from the published description (`modeling_deepseek.py`), each
also in the configuration's `assumed`: the rotary columns of `W_q` and
`W_kva` are in half-split order (the source stores them interleaved and
de-interleaves before it rotates: a fixed permutation of seeded
columns); the residual stream is float32.
"""

from __future__ import annotations

import math
import sys

import numpy as np

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
    # Only the weights go to the device: the two Adam moments (4.3 GB of
    # the 6.4 GB saved) would leave the reference less room beside them.
    trainer.state = state._replace(opt_state=())
    return np.asarray(trainer.eval_step(features), np.float32), step, state


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


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def _gated_mlp(p, x, rounded: bool):
    """down(silu(gate x) * up x)."""
    hidden = _silu(_mm(x, p["gate_proj"]["kernel"], rounded)) * _mm(
        x, p["up_proj"]["kernel"], rounded
    )
    return _mm(hidden, p["down_proj"]["kernel"], rounded)


def yarn_mscale(factor: float, mscale: float) -> float:
    """m(f, a) = 0.1 a ln f + 1."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(model: dict):
    """The rotary frequencies of the `qk_rope_head_dim / 2` pairs, float64
    numpy: plain `theta^(-2i/dim)` without scaling; under YaRN the blend
    of that (extrapolated) and that over `factor` (interpolated) by a
    ramp between the pairs that turn `beta_fast` and `beta_slow` times
    over the original positions."""
    dim, base = model["qk_rope_head_dim"], float(model["rope_theta"])
    pairs = np.arange(dim // 2, dtype=np.float64)
    extrapolated = base ** (-2.0 * pairs / dim)
    factor = float(model.get("rope_scaling_factor", 1.0))
    if factor <= 1.0:
        return extrapolated
    original = model["rope_scaling_original_max_position_embeddings"]

    def pair_turning(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(base)
        )

    low = max(math.floor(pair_turning(model["rope_scaling_beta_fast"])), 0)
    high = min(math.ceil(pair_turning(model["rope_scaling_beta_slow"])),
               dim - 1)
    ramp = np.clip((pairs - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extrapolated / factor * ramp + extrapolated * (1.0 - ramp)


def _rotary(x, model):
    """x [T, heads, rope]: pair i is (x_i, x_{i + rope/2}), turned by
    position x inv_freq_i; the tables' magnitude is
    m(factor, mscale) / m(factor, mscale_all_dim)."""
    import jax.numpy as jnp

    t, half = x.shape[0], x.shape[-1] // 2
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(model), jnp.float32
    )[None, :]
    factor = float(model.get("rope_scaling_factor", 1.0))
    magnitude = yarn_mscale(
        factor, model.get("rope_scaling_mscale", 1.0)
    ) / yarn_mscale(factor, model.get("rope_scaling_mscale_all_dim", 0.0))
    cos = (jnp.cos(angles) * magnitude)[:, None, :].astype(x.dtype)
    sin = (jnp.sin(angles) * magnitude)[:, None, :].astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def softmax_scale(model: dict, with_mscale: bool = True) -> float:
    """(nope + rope)^-0.5 m(factor, mscale_all_dim)^2."""
    scale = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5
    factor = float(model.get("rope_scaling_factor", 1.0))
    all_dim = model.get("rope_scaling_mscale_all_dim", 0.0)
    if with_mscale and factor > 1.0 and all_dim:
        scale *= yarn_mscale(factor, all_dim) ** 2
    return scale


def _attention(p, x, model, low=frozenset(), query_block=512):
    import jax.numpy as jnp

    t = x.shape[0]
    h, nope, rope, dv = (model["num_attention_heads"],
                         model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                         model["v_head_dim"])
    rank = model["kv_lora_rank"]
    blocks = "blocks" in low
    op = _bf16 if blocks else (lambda a: a)
    q = _mm(x, p["q_proj"]["kernel"], blocks).reshape(t, h, nope + rope)
    latent = _mm(x, p["kv_a_proj_with_mqa"]["kernel"], blocks)
    k_pe = _rotary(latent[:, rank:].reshape(t, 1, rope), model)
    c = _rms_norm(
        latent[:, :rank], p["kv_a_layernorm"]["weight"], model["rms_norm_eps"]
    )
    kv = _mm(c, p["kv_b_proj"]["kernel"], blocks).reshape(t, h, nope + dv)
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], model)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (t, h, rope))], -1
    )
    v = kv[..., nope:]
    scale = softmax_scale(model, "no_mscale" not in low)
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
    out = jnp.concatenate(outs).reshape(t, h * dv)
    return _mm(out, p["o_proj"]["kernel"], blocks)


def _route(p, x, model):
    """-> (probabilities [T, E], chosen ids [T, k], their weights
    [T, k])."""
    import jax
    import jax.numpy as jnp

    k = model["num_experts_per_tok"]
    logits = _mm(x, p["gate"], False)
    logits = logits - jnp.max(logits, -1, keepdims=True)
    exp = jnp.exp(logits)
    probs = exp / jnp.sum(exp, -1, keepdims=True)
    top, ids = jax.lax.top_k(probs, k)
    if model.get("norm_topk_prob", False):
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * model.get("routed_scaling_factor", 1.0)
    return probs, ids, top


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
    return model.get("aux_loss_alpha", 0.0) * jnp.sum(
        f * jnp.mean(probs, axis=0)
    )


def _experts(p, x, model, low=frozenset(), watch=None):
    """The held range's part plus the shared experts'.  `watch`: a dict
    whose lists receive this layer's choices [T, k] (`chosen`) and
    balancing loss (`balance`)."""
    import jax.numpy as jnp

    blocks = "blocks" in low
    probs, ids, top = _route(p, x, model)
    if watch is not None:
        watch["chosen"].append(ids)
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
    return y + _gated_mlp(p["shared_experts"], x, blocks)


def decoder(w: dict, tokens, model: dict, low=frozenset(), watch=None):
    """One sequence [T] -> logits [T, V], in the dtype of `w`; `low`: what
    departs from float32 (`blocks`: products round their operands to
    bfloat16; `no_mscale`: the planted fault); `watch`: see `_experts`."""
    eps = model["rms_norm_eps"]
    stack = w["model"]
    x = stack["embed_tokens"][tokens]
    for i in range(model["num_hidden_layers"]):
        p = stack[f"layers_{i}"]
        x = x + _attention(
            p["self_attn"],
            _rms_norm(x, p["input_layernorm"]["weight"], eps), model, low,
        )
        u = _rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
        if i < model["first_k_dense_replace"]:
            x = x + _gated_mlp(p["mlp"], u, "blocks" in low)
        else:
            x = x + _experts(p["mlp"], u, model, low, watch)
    return _mm(
        _rms_norm(x, stack["norm"]["weight"], eps), w["lm_head"],
        "blocks" in low,
    )


def _watch():
    return {"chosen": [], "balance": []}


def loss_and_balance(w: dict, tokens, labels, model: dict):
    """tokens, labels [rows, T] -> (mean next-token cross-entropy over all
    tokens, the balancing loss: alpha x the mean over the sequences of
    sum_i f_i P_i, summed over the expert layers).  A training step
    descends their SUM; the program reports the first."""
    import jax
    import jax.numpy as jnp

    cross_entropy, balance = [], 0.0
    for row, target in zip(tokens, labels):
        watch = _watch()
        logits = decoder(w, row, model, watch=watch)
        logp = jax.nn.log_softmax(logits, axis=-1)
        cross_entropy.append(
            -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]
        )
        balance = balance + sum(watch["balance"]) / len(tokens)
    return jnp.mean(jnp.stack(cross_entropy)), balance


#: precision -> (dtype of every weight and activation, what departs)
PRECISIONS = {
    "highest": ("float32", frozenset()),
    "stated": ("float32", frozenset({"blocks"})),
    "bfloat16": ("bfloat16", frozenset()),
    "no_mscale": ("float32", frozenset({"no_mscale"})),
}


def _expert_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


def chosen_counts(w: dict, tokens, model: dict):
    """How often each expert layer's router chose each of ALL experts
    over `tokens` [rows, T], at `highest` -> int array [expert layers,
    n_routed_experts].  The held range's columns are the pairs this chip
    computes; a uniform router gives rows x T x k / n_routed_experts
    everywhere."""
    import jax
    import jax.numpy as jnp

    watch = _watch()
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), w)
        for row in jnp.asarray(tokens):
            decoder(w, row, model, watch=watch)
    layers = _expert_layers(model)
    counts = np.zeros((layers, model["n_routed_experts"]), np.int64)
    for i, ids in enumerate(watch["chosen"]):
        counts[i % layers] += np.bincount(
            np.asarray(ids).reshape(-1), minlength=model["n_routed_experts"]
        )
    return counts


def forward(w: dict, tokens, model: dict, precision: str = "highest"):
    """`highest`: float32 throughout.  `stated`: what the configuration
    states (bfloat16 operands in the blocks' products, the rest float32).
    `bfloat16`: the same code with EVERY weight and activation in
    bfloat16 (norms, router and softmax statistics too).  `no_mscale`:
    `highest` with the softmax scale LEFT WITHOUT YaRN's mscale^2 (1.59),
    a planted fault in what is new here: it has to read many times the
    tolerance."""
    import jax
    import jax.numpy as jnp

    if precision not in PRECISIONS:
        raise ValueError(f"no precision {precision!r}")
    dtype, low = PRECISIONS[precision]
    watch = _watch() if precision == "highest" else None
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, dtype), w)
        out = jnp.stack([
            decoder(w, row, model, low, watch).astype(jnp.float32)
            for row in jnp.asarray(tokens)
        ])
    if watch is not None and not any(
        isinstance(ids, jax.core.Tracer) for ids in watch["chosen"]
    ):
        _log_held_pairs(watch["chosen"], len(tokens), model)
    return out


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
               * held / model["n_routed_experts"])
    print(
        f"reference: pairs on the {held} held experts in the compared "
        f"sample, a layer: {pairs}; a uniform router gives {uniform:.0f}",
        file=sys.stderr, flush=True,
    )


# -- the least work ------------------------------------------------------------


def _matmul_params(model: dict) -> dict:
    """Parameters that multiply a token's activations, by part."""
    d = model["hidden_size"]
    h, nope, rope, dv = (model["num_attention_heads"],
                         model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                         model["v_head_dim"])
    rank = model["kv_lora_rank"]
    shared = model["n_shared_experts"] * model["moe_intermediate_size"]
    return {
        "attn": model["num_hidden_layers"] * (
            d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + dv) + h * dv * d
        ),
        "dense": model["first_k_dense_replace"] * 3 * d
        * model["intermediate_size"],
        "router_shared": _expert_layers(model) * (
            d * model["n_routed_experts"] + 3 * d * shared
        ),
        "expert": 3 * d * model["moe_intermediate_size"],  # ONE expert
        "head": d * model["vocab_size"],
    }


def _all_params(model: dict) -> int:
    """Every parameter this chip holds (norm weights included)."""
    m = _matmul_params(model)
    d, layers = model["hidden_size"], model["num_hidden_layers"]
    return (
        m["attn"] + m["dense"] + m["router_shared"] + m["head"]
        + _expert_layers(model) * model["experts_held"] * m["expert"]
        + model["vocab_size"] * d
        + layers * (2 * d + model["kv_lora_rank"]) + d
    )


def _core_products(model: dict, minibatch: int) -> int:
    """FLOPs of ONE product of the attention core a unit of head size,
    all layers: 2 x T^2 / 2 (the causal half) a head a sequence."""
    t = model["sample_tokens"]
    return (
        t * t * minibatch * model["num_attention_heads"]
        * model["num_hidden_layers"]
    )


def mla_core_cost(model: dict, minibatch: int) -> dict:
    """The attention core (scores, softmax, values: the `mla_core` scope,
    whichever engine implements it) of ALL layers for one training step AS
    THE CONFIGURATION RUNS IT, from shapes.  FLOPs, each product over the
    causal half of [T, T] a head (T^2 x its head size): a forward is
    q k^T over Dqk = nope + rope and p v over Dv; it runs once more under
    the layer's rematerialisation; the backward is five products, the
    scores again, dS K and dS^T Q over Dqk, P^T dO and dO V^T over Dv.
    Bytes, bfloat16: a forward reads q, k [Dqk a head] and v and writes o
    [Dv]; the backward reads q, k, v, o and dO and writes dq, dk, dv."""
    dqk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    dv = model["v_head_dim"]
    one = _core_products(model, minibatch)
    forward, backward = dqk + dv, 3 * dqk + 2 * dv
    rows = (
        minibatch * model["sample_tokens"] * model["num_attention_heads"]
        * model["num_hidden_layers"]
    )
    return {
        "flops": one * (2 * forward + backward),
        "bytes": 2 * rows * (
            2 * (2 * dqk + 2 * dv) + (2 * dqk + 3 * dv) + (2 * dqk + dv)
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
    parameter a token over the attention projections, the dense layer,
    router, shared experts and head; the routed experts at the EXPECTED
    pairs of a uniform router (tokens x k x held / all); the attention
    core's score and value products over the causal half, forward (Dqk +
    Dv) and backward at twice that.  No recomputation.  Bytes: AdamW
    reads weight, gradient and two moments and writes weight and two
    moments, 7 x 4 bytes a parameter."""
    m = _matmul_params(model)
    tokens = minibatch * model["sample_tokens"]
    pairs = (
        _expert_layers(model) * tokens * model["num_experts_per_tok"]
        * model["experts_held"] / model["n_routed_experts"]
    )
    dqk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    attention = 3 * _core_products(model, minibatch) * (
        dqk + model["v_head_dim"]
    )
    dense = m["attn"] + m["dense"] + m["router_shared"] + m["head"]
    return {
        "flops": 6 * dense * tokens + 6 * m["expert"] * pairs + attention,
        "bytes": 7 * 4 * _all_params(model),
    }
