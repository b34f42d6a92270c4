"""Plain reference of Nemotron-H (a stack of single-mixer layers: Mamba-2
state-space layers, routed experts with a shared one, softmax attention
without a position embedding) for ONE chip's share of it: the range of
experts `model` says is held, the slice of the vocabulary it gives.
float32 `jax.numpy`, no kernel, no chunk, and no code of the program:

- the Mamba-2 recurrence token by token, as written:
  `S <- exp(dt_t A) S + (dt_t x_t) B_t^T; y_t = S C_t + D x_t`, one
  `lax.scan` step a token; the causal convolution as four shifted adds;
- softmax attention over explicit scores and an explicit causal mask,
  one block of queries at a time so that 8192 tokens fit;
- the experts by a loop over the held range, each over every token, the
  routing weight of a token being zero where the expert is not among its
  top k.  What experts held elsewhere would add is left out, here as in
  the program, and that partial sum goes on to the next layer.

The same code also runs AT THE PRECISION THE CONFIGURATION STATES
(`forward(..., "stated")`): the operands of the projections, the
state-space products (dt x, B and C), attention, expert and head products
rounded to bfloat16 with float32 accumulation, everything else (norms,
router, dt, the decays, the recurrent state) float32 as before; and with
EVERY weight and activation in bfloat16 (`"bfloat16"`: the nearest
precision below the stated one, which the cell's tolerance refuses).

Beside it: where the program's own outputs come from (`program`), the
least work of a training step (`step_cost`) and of the two scopes whose
roofline share the benchmark reports (`ssm_scan_cost`,
`moe_experts_cost`).

Departures from the published description (`transformers`
`modeling_nemotron_h.py`), each also in the configuration's `assumed`:
the renormalised routing weights are divided by their sum, not by their
sum + 1e-20; `dt` is not clamped (the source's `time_step_limit` is
(0, inf)); the residual stream is float32 (`residual_in_fp32` is false
in the source).

Parameter layouts are the source's, kernels [in, out]: `in_proj` gives
[z | x | B | C | dt], `conv1d.kernel` [taps, channels of x | B | C],
head h of the state-space layer reads group h // (H / G).
"""

from __future__ import annotations

import sys

import numpy as np

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"

#: A token is CLEAR of a tie when, in every expert layer, the score (with
#: its selection bias) of its last chosen expert and that of the first one
#: left out lie at least this far apart in the reference at `highest`.
#: The scores are sigmoids, 128 of them in (0, 1); bfloat16 operands in the
#: products upstream of a router move one by about 1e-3.
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
    # Only the weights go to the device: the two Adam moments (5.3 GB of
    # the 8.0 GB saved) would leave the reference no room beside them.
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


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def _relu2(x):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(x, 0.0))


def _selective_scan(x, dt, a, b, c, rounded: bool = False):
    """x [T, H, P]; dt [T, H]; a [H]; b, c [T, H, N] -> y [T, H, P], one
    token a step.  `rounded`: what enters a product (dt x, B, C) is
    rounded to bfloat16; the decays and the state stay float32."""
    import jax
    import jax.numpy as jnp

    op = _bf16 if rounded else (lambda v: v)

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = state * jnp.exp(dt_t * a)[:, None, None] + (
            op(dt_t[:, None] * x_t)[:, :, None] * op(b_t)[:, None, :]
        )
        return state, jnp.einsum("hpn,hn->hp", state, op(c_t))

    state = jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), x.dtype)
    return jax.lax.scan(step, state, (x, dt, b, c))[1]


def _mamba2(p, u, model, low=frozenset()):
    import jax.numpy as jnp

    t = u.shape[0]
    h, pd = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n = model["n_groups"], model["ssm_state_size"]
    inner, bc = h * pd, g * n
    blocks = "blocks" in low
    proj = _mm(u, p["in_proj"]["kernel"], blocks)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * bc],
                  proj[:, 2 * inner + 2 * bc:])
    kernel = p["conv1d"]["kernel"]
    width = kernel.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((width - 1, xbc.shape[1]), xbc.dtype), xbc]
    )
    # out[t] = bias + sum_j w[j] in[t - (width - 1) + j]: causal, depthwise.
    xbc = _silu(p["conv1d"]["bias"] + sum(
        padded[j:j + t] * kernel[j] for j in range(width)
    ))
    x = xbc[:, :inner].reshape(t, h, pd)
    b = jnp.repeat(xbc[:, inner:inner + bc].reshape(t, g, n), h // g, axis=1)
    c = jnp.repeat(xbc[:, inner + bc:].reshape(t, g, n), h // g, axis=1)
    dt = jnp.logaddexp(dt + p["dt_bias"], 0.0)
    y = _selective_scan(x, dt, -jnp.exp(p["A_log"]), b, c, blocks)
    y = (y + p["D"][:, None] * x).reshape(t, inner) * _silu(z)
    y = y.reshape(t, g, inner // g)
    y = y / jnp.sqrt(
        jnp.mean(y * y, -1, keepdims=True) + model["layer_norm_epsilon"]
    )
    return _mm(p["norm"] * y.reshape(t, inner), p["out_proj"]["kernel"], blocks)


def _attention(p, x, model, low=frozenset(), query_block=512):
    import jax.numpy as jnp

    t = x.shape[0]
    h, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["head_dim"]
    blocks = "blocks" in low
    op = _bf16 if blocks else (lambda a: a)
    q = _mm(x, p["q_proj"]["kernel"], blocks).reshape(t, h, d)
    k = _mm(x, p["k_proj"]["kernel"], blocks).reshape(t, hkv, d)
    v = _mm(x, p["v_proj"]["kernel"], blocks).reshape(t, hkv, d)
    positions = jnp.arange(t)
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
    out = jnp.concatenate(outs).reshape(t, h * d)
    return _mm(out, p["o_proj"]["kernel"], blocks)


def _experts(p, x, model, low=frozenset(), chosen=None, margins=None):
    """Sigmoid router over all experts; the held range's part plus the
    shared expert's.  `chosen`: a list that receives this layer's choices
    [T, k]; `margins`: one that receives, a token, how far the last
    chosen expert's selection score lies above the first one's left out
    [T]."""
    import jax
    import jax.numpy as jnp

    blocks = "blocks" in low
    k = model["num_experts_per_tok"]
    scores = 1.0 / (1.0 + jnp.exp(-_mm(x, p["gate"]["weight"], False)))
    ranked, ids = jax.lax.top_k(
        scores + p["gate"]["e_score_correction_bias"], k + 1
    )
    ids = ids[:, :k]
    if chosen is not None:
        chosen.append(ids)
    if margins is not None:
        margins.append(ranked[:, k - 1] - ranked[:, k])
    top = jnp.take_along_axis(scores, ids, axis=-1)  # the bias is not in them
    if model["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * model["routed_scaling_factor"]
    first = model["experts_first"]
    y = jnp.zeros_like(x)
    for local in range(model["experts_held"]):
        weight = jnp.sum(jnp.where(ids == first + local, top, 0.0), axis=-1)
        hidden = _relu2(_mm(x, p["experts_up_proj"][local], blocks))
        y = y + weight[:, None] * _mm(
            hidden, p["experts_down_proj"][local], blocks
        )
    s = p["shared_experts"]
    return y + _mm(
        _relu2(_mm(x, s["up_proj"]["kernel"], blocks)),
        s["down_proj"]["kernel"], blocks,
    )


_MIXERS = {MAMBA: _mamba2, EXPERTS: _experts, ATTENTION: _attention}


def decoder(w: dict, tokens, model: dict, low=frozenset(), chosen=None,
            margins=None):
    """One sequence [T] -> logits [T, V], in the dtype of `w`; `low`: the
    parts whose products take operands rounded to bfloat16; `chosen` and
    `margins`: lists that receive every expert layer's choices and how
    far each was from a tie."""
    eps = model["layer_norm_epsilon"]
    backbone = w["backbone"]
    x = backbone["embeddings"][tokens]
    for i, kind in enumerate(model["hybrid_override_pattern"]):
        p = backbone[f"layers_{i}"]
        more = (
            {"chosen": chosen, "margins": margins} if kind == EXPERTS else {}
        )
        x = x + _MIXERS[kind](
            p["mixer"], _rms_norm(x, p["norm"]["weight"], eps), model, low,
            **more,
        )
    return _mm(
        _rms_norm(x, backbone["norm_f"]["weight"], eps), w["lm_head"],
        "blocks" in low,
    )


#: precision -> (dtype of every weight and activation, parts whose
#: products round their operands to bfloat16)
PRECISIONS = {
    "highest": ("float32", frozenset()),
    "highest_clear": ("float32", frozenset()),
    "stated": ("float32", frozenset({"blocks"})),
    "bfloat16": ("bfloat16", frozenset()),
    "no_routed_scale": ("float32", frozenset()),
}


def chosen_counts(w: dict, tokens, model: dict):
    """How often each expert layer's router chose each of ALL experts
    over `tokens` [rows, T], at `highest` -> int array [expert layers,
    n_routed_experts].  The held range's columns are the pairs this chip
    computes; a uniform router gives rows x T x k / n_routed_experts
    everywhere.  The source's balancing rule moves a layer's selection
    bias by the sign of (mean - count)."""
    import jax
    import jax.numpy as jnp

    chosen = []
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), w)
        for row in jnp.asarray(tokens):
            decoder(w, row, model, chosen=chosen)
    layers = _layers(model, EXPERTS)
    counts = np.zeros((layers, model["n_routed_experts"]), np.int64)
    for i, ids in enumerate(chosen):
        counts[i % layers] += np.bincount(
            np.asarray(ids).reshape(-1), minlength=model["n_routed_experts"]
        )
    return counts


def forward(w: dict, tokens, model: dict, precision: str = "highest"):
    """`highest`: float32 throughout.  `stated`: what the configuration
    states (bfloat16 operands in the blocks' products, the rest float32).
    `bfloat16`: the same code with EVERY weight and activation in
    bfloat16 (norms, router, decays and recurrent state too).
    `no_routed_scale`: `highest` WITHOUT the routed experts' scale of 2.5,
    a planted fault: the program's distance to it says how much of the
    compared logits the held experts carry at these weights, so it has to
    read many times the tolerance.

    `highest_clear`: `highest` over the tokens that are clear of a tie
    (`CLEAR_MARGIN`).  A top-k selection is discontinuous: a token whose
    last chosen expert and the first one left out score within a rounding
    of each other gets another expert in a program that rounds upstream,
    and with 8 of 128 experts held that is a whole routed contribution
    gained or lost, which no precision of the products would repair.
    Which tokens are clear is decided HERE, from the reference's own
    scores at `highest`; for the others this returns the program's own
    rows (`program` kept them), so that their difference is exactly 0 in
    the harness's rms over all rows: the reading is the clear tokens'
    squared error over ALL rows' count, sqrt(share clear) times their own
    rel. rms."""
    import jax
    import jax.numpy as jnp

    if precision not in PRECISIONS:
        raise ValueError(f"no precision {precision!r}")
    dtype, low = PRECISIONS[precision]
    if precision == "no_routed_scale":
        model = dict(model, routed_scaling_factor=1.0)
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
    layers = _layers(model, EXPERTS)
    clear = jnp.stack([  # [rows, T]: the least margin over the layers
        jnp.min(jnp.stack(margins[r * layers:(r + 1) * layers]), axis=0)
        for r in range(len(tokens))
    ]) >= CLEAR_MARGIN
    theirs = _PROGRAM.get("outputs")
    if theirs is None or theirs.shape != out.shape:
        raise ValueError("`highest_clear` needs the outputs `program` kept")
    print(
        f"reference: {int(clear.sum())} of {clear.size} compared tokens "
        f"are clear of a tie by {CLEAR_MARGIN:g} in every expert layer",
        file=sys.stderr, flush=True,
    )
    return jnp.where(clear[..., None], out, theirs)


def _log_held_pairs(chosen, rows: int, model: dict) -> None:
    """One line on stderr (the harness keeps it in the run's `check.log`):
    the pairs the held experts carry in the compared sample, a layer."""
    first, held = model["experts_first"], model["experts_held"]
    layers = _layers(model, EXPERTS)
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


def _layers(model: dict, kind: str) -> int:
    return model["hybrid_override_pattern"].count(kind)


def _matmul_params(model: dict) -> dict:
    """Parameters that multiply a token's activations, by part."""
    d = model["hidden_size"]
    inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    bc = model["n_groups"] * model["ssm_state_size"]
    h, hkv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    return {
        "ssm": _layers(model, MAMBA) * (
            d * (2 * inner + 2 * bc + model["mamba_num_heads"]) + inner * d
        ),
        "attn": _layers(model, ATTENTION) * (
            2 * d * h * hd + 2 * d * hkv * hd
        ),
        "router_shared": _layers(model, EXPERTS) * (
            d * model["n_routed_experts"]
            + 2 * d * model["moe_shared_expert_intermediate_size"]
        ),
        "expert": 2 * d * model["moe_intermediate_size"],  # ONE expert
        "head": d * model["vocab_size"],
    }


def _all_params(model: dict) -> int:
    m = _matmul_params(model)
    return (
        m["ssm"] + m["attn"] + m["router_shared"] + m["head"]
        + _layers(model, EXPERTS) * model["experts_held"] * m["expert"]
        + model["vocab_size"] * model["hidden_size"]
    )


def _ssd_forward(model: dict, minibatch: int) -> dict:
    """ONE forward pass of the chunked state-space-dual form over all
    Mamba-2 layers, from shapes.  FLOPs per chunk of Q tokens: C B^T a
    group (2 Q^2 N), and a head the scores with dt x (2 Q^2 P), the chunk
    state (2 Q P N) and C S (2 Q N P); the decays, the mask and the carry
    over the chunks are elementwise and not counted.  `tensors`: float32
    elements of x and y (T H P each), B and C (T G N each) and dt (T H);
    `states`: of the chunk states (chunks x H P N), written once and read
    once."""
    h, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n = model["n_groups"], model["ssm_state_size"]
    q, t = model["chunk_size"], model["sample_tokens"]
    chunks = -(-t // q) * minibatch
    layers = _layers(model, MAMBA)
    return {
        "flops": layers * chunks * (
            g * 2 * q * q * n + h * (2 * q * q * p + 4 * q * p * n)
        ),
        "tensors": layers * minibatch * t * (2 * h * p + 2 * g * n + h),
        "states": layers * chunks * h * p * n,
    }


def ssm_scan_cost(model: dict, minibatch: int) -> dict:
    """The state-space core (`ssd_chunked`) of ALL Mamba-2 layers for one
    training step AS THE CONFIGURATION RUNS IT: the forward, the forward
    once more under the layer's rematerialisation, and the backward at
    twice a forward's FLOPs.  Bytes, float32: a forward reads x, B, C, dt
    and writes y, and writes and reads the chunk states; the backward
    reads those five and the states, and writes the four gradients and
    writes and reads the states' own."""
    one = _ssd_forward(model, minibatch)
    forward = one["tensors"] + 2 * one["states"]
    backward = 2 * one["tensors"] + 3 * one["states"]
    return {
        "flops": 4 * one["flops"],
        "bytes": 4 * (2 * forward + backward),
    }


def moe_experts_cost(model: dict, pairs: float, steps: int) -> dict:
    """The held experts' TWO products for `pairs` (token, expert) pairs
    COUNTED over `steps` training steps, all layers: 6 FLOPs a weight a
    pair (forward 2, backward 4).  Bytes: each held expert's float32
    weights read forward and backward and its gradient written, once a
    step, plus a pair's input row read (2 B an element) and output row
    written (4 B) forward and the reverse backward."""
    m = _matmul_params(model)
    held = _layers(model, EXPERTS) * model["experts_held"]
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
    products, 4 T^2 H D a sequence forward, halved, times 3; the
    state-space form forward and backward (3 forwards' FLOPs).  No
    recomputation.  Bytes: AdamW reads weight, gradient and two moments
    and writes weight and two moments, 7 x 4 bytes a parameter."""
    m = _matmul_params(model)
    t = model["sample_tokens"]
    tokens = minibatch * t
    pairs = (
        _layers(model, EXPERTS) * tokens * model["num_experts_per_tok"]
        * model["experts_held"] / model["n_routed_experts"]
    )
    attention = 3 * _layers(model, ATTENTION) * minibatch * (
        4 * t * t * model["num_attention_heads"] * model["head_dim"]
    ) // 2
    dense = m["ssm"] + m["attn"] + m["router_shared"] + m["head"]
    return {
        "flops": 6 * dense * tokens + 6 * m["expert"] * pairs + attention
        + 3 * _ssd_forward(model, minibatch)["flops"],
        "bytes": 7 * 4 * _all_params(model),
    }
