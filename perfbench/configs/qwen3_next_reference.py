"""Plain reference of Qwen3-Next (Gated DeltaNet and gated attention
layers, each followed by routed experts plus a shared one) for ONE chip's
share of it: the range of experts `model` says is held, the slice of the
vocabulary it gives.  float32 `jax.numpy`, no kernel, no chunk, no
grouping, and no code of the program:

- the delta rule token by token, as written:
  `S <- S exp(g_t); d = beta_t (v_t - S^T k_t); S <- S + k_t d^T;
  o_t = S^T q_t`;
- softmax attention over explicit scores and an explicit causal mask,
  one block of queries at a time so that 8192 tokens fit;
- the experts by a loop over the held range, each over every token, the
  routing weight of a token being zero where the expert is not among its
  top k.  What experts held elsewhere would add is left out, here as in
  the program, and that partial sum goes on to the next layer.

The same code also runs AT THE PRECISION THE CONFIGURATION STATES
(`forward(..., "stated")`): the operands of the projections, attention,
expert and head products rounded to bfloat16 with float32 accumulation,
everything else (norms, router, decay, the delta rule and its state)
float32 as before.  `low` names the parts whose products take rounded
operands: `blocks` is what is stated; `state` (the delta rule's products
with its state) and `router` (the router's logits) are the controls one
step BELOW it, which the comparison at the stated precision has to tell
from the stated one.

Beside it: where the program's own outputs come from (`program`), the
least work of a training step (`step_cost`) and of the two scopes whose
roofline share the benchmark reports (`gdn_scan_cost`,
`moe_experts_cost`).

Parameter layouts are the source's (`transformers` `Qwen3Next*`), kernels
[in, out]: `in_proj_qkvz` per key head [q, k, v x r, z x r], `in_proj_ba`
per key head [b x r, a x r], `conv1d` [width, channels of q | k | v].
"""

from __future__ import annotations

import numpy as np

CHUNK = 64  # the program's chunk: only `gdn_scan_cost` needs it


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
    `worker/main._build_collective_worker` builds it).
    -> (outputs, step, program_state)."""
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
    trainer.state = state
    return trainer.eval_step(features), step, state


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

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + weight)


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def _delta_rule(q, k, v, g, beta, rounded: bool = False):
    """[T, H, D] each (g, beta [T, H]) -> o [T, H, Dv], one token a step.
    `rounded`: the state and what it is multiplied with are rounded to
    bfloat16 for each product (the kept state stays float32), as a
    matmul unit at its default precision would."""
    import jax
    import jax.numpy as jnp

    op = _bf16 if rounded else (lambda x: x)

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[:, None, None]
        delta = beta_t[:, None] * (
            v_t - jnp.einsum("hkv,hk->hv", op(state), op(k_t))
        )
        state = state + jnp.einsum("hk,hv->hkv", op(k_t), op(delta))
        return state, jnp.einsum("hkv,hk->hv", op(state), op(q_t))

    state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), q.dtype)
    return jax.lax.scan(step, state, (q, k, v, g, beta))[1]


def _gated_delta_net(p, x, model, low=frozenset()):
    import jax.numpy as jnp

    t = x.shape[0]
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    r = hv // hk
    blocks = "blocks" in low
    qkvz = _mm(x, p["in_proj_qkvz"]["kernel"], blocks).reshape(
        t, hk, 2 * dk + 2 * r * dv
    )
    ba = _mm(x, p["in_proj_ba"]["kernel"], blocks).reshape(t, hk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(t, hv, dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(t, hv, dv)
    b, a = ba[..., :r].reshape(t, hv), ba[..., r:].reshape(t, hv)
    mixed = jnp.concatenate(
        [q.reshape(t, -1), k.reshape(t, -1), v.reshape(t, -1)], axis=-1
    )
    width = p["conv1d"].shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((width - 1, mixed.shape[1]), mixed.dtype), mixed]
    )
    # out[t] = sum_j w[j] in[t - (width - 1) + j]: causal, depthwise.
    mixed = _silu(sum(padded[j:j + t] * p["conv1d"][j] for j in range(width)))
    q = mixed[:, :hk * dk].reshape(t, hk, dk)
    k = mixed[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = mixed[:, 2 * hk * dk:].reshape(t, hv, dv)
    beta = 1.0 / (1.0 + jnp.exp(-b))
    g = -jnp.exp(p["A_log"]) * jnp.logaddexp(a + p["dt_bias"], 0.0)
    q = jnp.repeat(q, r, axis=1)
    k = jnp.repeat(k, r, axis=1)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / np.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    o = _delta_rule(q, k, v, g, beta, "state" in low)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + model["rms_norm_eps"])
    o = p["norm"] * o * _silu(z)
    return _mm(o.reshape(t, hv * dv), p["out_proj"]["kernel"], blocks)


def _rotate(x, positions, rotary_dim, theta):
    """Rotate-half on the first `rotary_dim` dimensions.  x [T, H, D]."""
    import jax.numpy as jnp

    half = rotary_dim // 2
    freq = theta ** (-jnp.arange(half, dtype=x.dtype) * 2.0 / rotary_dim)
    angle = positions.astype(x.dtype)[:, None, None] * freq[None, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([
        x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
        x2 * jnp.cos(angle) + x1 * jnp.sin(angle),
        rest,
    ], axis=-1)


def _gated_attention(p, x, model, low=frozenset(), query_block=512):
    import jax.numpy as jnp

    t = x.shape[0]
    h, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    d, eps = model["head_dim"], model["rms_norm_eps"]
    blocks = "blocks" in low
    op = _bf16 if blocks else (lambda a: a)
    q_gate = _mm(x, p["q_proj"]["kernel"], blocks).reshape(t, h, 2 * d)
    q, gate = q_gate[..., :d], q_gate[..., d:]
    k = _mm(x, p["k_proj"]["kernel"], blocks).reshape(t, hkv, d)
    v = _mm(x, p["v_proj"]["kernel"], blocks).reshape(t, hkv, d)
    q = _rms_norm(q, p["q_norm"]["weight"], eps)
    k = _rms_norm(k, p["k_norm"]["weight"], eps)
    positions = jnp.arange(t)
    rotary_dim = int(d * model["partial_rotary_factor"])
    q = _rotate(q, positions, rotary_dim, model["rope_theta"])
    k = _rotate(k, positions, rotary_dim, model["rope_theta"])
    group = h // hkv  # query head i reads key-value head i // group
    outs = []
    for start in range(0, t, query_block):
        qb = q[start:start + query_block].reshape(-1, hkv, group, d)
        scores = jnp.einsum("qngd,knd->ngqk", op(qb), op(k)) / np.sqrt(d)
        allowed = (
            positions[None, :] <= positions[start:start + query_block, None]
        )
        scores = jnp.where(allowed[None, None], scores, -jnp.inf)
        # softmax, written out: the weights are rounded (where they are)
        # before they are normalised, the sum is of the unrounded ones.
        weights = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
        mixed = jnp.einsum("ngqk,knd->qngd", op(weights), op(v))
        total = jnp.moveaxis(jnp.sum(weights, -1), 2, 0)[..., None]
        outs.append((mixed / total).reshape(-1, h, d))
    out = op(jnp.concatenate(outs)) / (1.0 + jnp.exp(-gate))
    return _mm(out.reshape(t, h * d), p["o_proj"]["kernel"], blocks)


def _experts(p, x, model, low=frozenset()):
    """Router over all experts; the held range's part plus the shared one."""
    import jax
    import jax.numpy as jnp

    blocks = "blocks" in low
    probs = jax.nn.softmax(_mm(x, p["gate"], "router" in low), axis=-1)
    top, ids = jax.lax.top_k(probs, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
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
    s = p["shared_expert"]
    shared = _mm(
        _silu(_mm(x, s["gate_proj"]["kernel"], blocks))
        * _mm(x, s["up_proj"]["kernel"], blocks),
        s["down_proj"]["kernel"], blocks,
    )
    gate = _mm(x, p["shared_expert_gate"], blocks)
    return y + shared / (1.0 + jnp.exp(-gate))


def decoder(w: dict, tokens, model: dict, low=frozenset()):
    """One sequence [T] -> logits [T, V], in the dtype of `w`; `low`: the
    parts whose products take operands rounded to bfloat16."""
    eps = model["rms_norm_eps"]
    x = w["embed_tokens"][tokens]
    for i in range(model["num_hidden_layers"]):
        p = w[f"layers_{i}"]
        h = _rms_norm(x, p["input_layernorm"]["weight"], eps)
        if (i + 1) % model["full_attention_interval"] == 0:
            x = x + _gated_attention(p["self_attn"], h, model, low)
        else:
            x = x + _gated_delta_net(p["linear_attn"], h, model, low)
        h = _rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
        x = x + _experts(p["mlp"], h, model, low)
    return _mm(
        _rms_norm(x, w["norm"]["weight"], eps), w["lm_head"], "blocks" in low
    )


#: precision -> (dtype of every weight and activation, parts whose
#: products round their operands to bfloat16)
PRECISIONS = {
    "highest": ("float32", frozenset()),
    "stated": ("float32", frozenset({"blocks"})),
    "stated_bf16_state": ("float32", frozenset({"blocks", "state"})),
    "stated_bf16_router": ("float32", frozenset({"blocks", "router"})),
    "bfloat16": ("bfloat16", frozenset()),
}


def forward(w: dict, tokens, model: dict, precision: str = "highest"):
    """`highest`: float32 throughout.  `stated`: what the configuration
    states (bfloat16 operands in the blocks' products, the rest float32).
    Below it, each for a reading that sets a tolerance:
    `stated_bf16_state` and `stated_bf16_router` round one more part's
    operands; `bfloat16` is the same code with EVERY weight and
    activation in bfloat16 (norms, router and recurrent state too)."""
    import jax
    import jax.numpy as jnp

    if precision not in PRECISIONS:
        raise ValueError(f"no precision {precision!r}")
    dtype, low = PRECISIONS[precision]
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, dtype), w)
        return jnp.stack([
            decoder(w, row, model, low).astype(jnp.float32)
            for row in jnp.asarray(tokens)
        ])


# -- the least work ------------------------------------------------------------


def _matmul_params(model: dict) -> dict:
    """Parameters that multiply a token's activations, by part."""
    d = model["hidden_size"]
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    h, hkv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    layers = model["num_hidden_layers"]
    attn_layers = layers // model["full_attention_interval"]
    return {
        "gdn": (layers - attn_layers) * (
            d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d
        ),
        "attn": attn_layers * (d * 2 * h * hd + 2 * d * hkv * hd + h * hd * d),
        "router_shared": layers * (
            d * model["num_experts"]
            + 3 * d * model["shared_expert_intermediate_size"] + d
        ),
        "expert": 3 * d * model["moe_intermediate_size"],  # ONE expert
        "head": d * model["vocab_size"],
    }


def _all_params(model: dict) -> int:
    m = _matmul_params(model)
    return (
        m["gdn"] + m["attn"] + m["router_shared"] + m["head"]
        + model["num_hidden_layers"] * model["experts_held"] * m["expert"]
        + model["vocab_size"] * model["hidden_size"]
    )


def gdn_scan_cost(model: dict, minibatch: int) -> dict:
    """The chunked delta rule of ALL DeltaNet layers for one training
    step (forward, and backward at twice the forward), from shapes.
    FLOPs: the products of the WY form per chunk of C tokens and head,
    2 C^2 (3 Dk + 2 Dv) inside the chunk (k k^T, q k^T, the two
    applications of the inverse, scores x values) and 6 C Dk Dv with the
    state; the triangular inverse itself and all recomputation are not
    counted.  Bytes: float32 q, k, v, o and their gradients at the value
    heads' count, g and beta: 4 tensors forward, 7 backward."""
    hv = model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    layers = model["num_hidden_layers"]
    gdn_layers = layers - layers // model["full_attention_interval"]
    tokens = minibatch * model["sample_tokens"]
    per_chunk = 2 * CHUNK * CHUNK * (3 * dk + 2 * dv) + 6 * CHUNK * dk * dv
    chunks = -(-model["sample_tokens"] // CHUNK) * minibatch * hv
    elements = tokens * hv * (2 * dk + 2 * dv) / 4  # one [B,T,H,D] tensor
    return {
        "flops": 3 * gdn_layers * chunks * per_chunk,
        "bytes": gdn_layers * 4 * (11 * elements + 4 * tokens * hv),
    }


def moe_experts_cost(model: dict, pairs: float, steps: int) -> dict:
    """The held experts' three products for `pairs` (token, expert) pairs
    COUNTED over `steps` training steps, all layers: 6 FLOPs a weight a
    pair (forward 2, backward 4).  Bytes: each held expert's float32
    weights read forward and backward and its gradient written, once a
    step, plus a pair's input row read (2 B an element) and output row
    written (4 B) forward and the reverse backward."""
    m = _matmul_params(model)
    held = model["num_hidden_layers"] * model["experts_held"]
    return {
        "flops": 6 * m["expert"] * pairs,
        "bytes": steps * 3 * 4 * held * m["expert"]
        + pairs * 2 * 6 * model["hidden_size"],
    }


def step_cost(model: dict, minibatch: int) -> dict:
    """The least a training step needs, from shapes.  FLOPs: 6 a matmul
    parameter a token over the projections, router, shared expert and
    head; the routed experts at the EXPECTED pairs of a uniform router
    (tokens x k x held / all); causal attention's score and value
    products, 4 T^2 H D a sequence forward, halved, times 3; the delta
    rule as `gdn_scan_cost` counts it.  No recomputation.  Bytes: AdamW
    reads weight, gradient and two moments and writes weight and two
    moments, 7 x 4 bytes a parameter."""
    m = _matmul_params(model)
    t = model["sample_tokens"]
    tokens = minibatch * t
    layers = model["num_hidden_layers"]
    attn_layers = layers // model["full_attention_interval"]
    pairs = (
        layers * tokens * model["num_experts_per_tok"]
        * model["experts_held"] / model["num_experts"]
    )
    attention = 3 * attn_layers * minibatch * (
        4 * t * t * model["num_attention_heads"] * model["head_dim"]
    ) // 2
    dense = m["gdn"] + m["attn"] + m["router_shared"] + m["head"]
    return {
        "flops": 6 * dense * tokens + 6 * m["expert"] * pairs + attention
        + gdn_scan_cost(model, minibatch)["flops"],
        "bytes": 7 * 4 * _all_params(model),
    }
