"""Plain reference of Granite 4.0-H (every layer a mixer, Mamba-2 or
softmax attention without a position embedding, and then a gated-SiLU
MLP; four scalar multipliers; the output head is the embedding table) for
ONE chip's share of it: the slice of the vocabulary `model` gives.
float32 `jax.numpy`, no kernel, no chunk, and no code of the program.
With m_e `embedding_multiplier`, m_a `attention_multiplier`, m_r
`residual_multiplier`, m_l `logits_scaling`::

    x = m_e E[tokens]
    layer i:   x = x + m_r mixer_i(rmsnorm(x))
               x = x + m_r W_out (silu(g) u),  [g | u] = rmsnorm(x) W_in
    logits = rmsnorm(x) E^T / m_l

- the Mamba-2 recurrence token by token, as written:
  `S <- exp(dt_t A) S + (dt_t x_t) B_t^T; y_t = S C_t + D x_t`, one
  `lax.scan` step a token, every head of a group reading the group's B
  and C; the causal convolution as four shifted adds; the gated norm
  over each group of the inner width (ONE group here: the whole width);
- softmax attention over explicit scores TIMES m_a (not 1/sqrt(d)) and
  an explicit causal mask, one block of queries at a time so that 8192
  tokens fit; query head j reads key-value head j // (heads / kv heads).

The same code also runs AT THE PRECISION THE CONFIGURATION STATES
(`forward(..., "stated")`): the operands of the projections, the
state-space products (dt x, B and C), attention, the MLP's and the head's
products rounded to bfloat16 with float32 accumulation, everything else
(norms, dt, the decays, the recurrent state, the multipliers) float32 as
before; with EVERY weight and activation in bfloat16 (`"bfloat16"`: the
nearest precision below the stated one, which the cell's tolerance
refuses); and with one of two PLANTED FAULTS: `no_residual_multiplier`
(m_r = 1) and `sqrt_scale` (scores times 1/sqrt(head_dim) in place of
m_a).

Beside it: where the program's own outputs come from (`program`), the
least work of a training step (`step_cost`) and of the two scopes whose
roofline share the benchmark reports (`ssm_scan_cost`, `mlp_cost`).

Departures from the published description (`transformers`
`modeling_granitemoehybrid.py`), each also in the configuration's
`assumed`: the residual stream is float32 (the source's is the weights'
dtype); `dt` is not clamped (the source's `time_step_limit` is (0, inf),
so neither is it there).

Parameter layouts are the source's, kernels [in, out]: `in_proj` gives
[z | x | B | C | dt], `conv1d.kernel` [taps, channels of x | B | C],
`input_linear` [gate | up]; head h of the state-space layer reads group
h // (H / G).
"""

from __future__ import annotations

import numpy as np

MAMBA, ATTENTION = "mamba", "attention"


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
    # Only the weights go to the device: the two Adam moments (6.2 GB of
    # the 9.3 GB saved) would leave the reference no room beside them.
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


def _mamba2(p, u, model, rounded: bool = False):
    import jax.numpy as jnp

    t = u.shape[0]
    h, pd = model["mamba_n_heads"], model["mamba_d_head"]
    g, n = model["mamba_n_groups"], model["mamba_d_state"]
    inner, bc = h * pd, g * n
    proj = _mm(u, p["in_proj"]["kernel"], rounded)
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
    y = _selective_scan(x, dt, -jnp.exp(p["A_log"]), b, c, rounded)
    y = (y + p["D"][:, None] * x).reshape(t, inner) * _silu(z)
    y = y.reshape(t, g, inner // g)
    y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + model["rms_norm_eps"])
    return _mm(p["norm"] * y.reshape(t, inner), p["out_proj"]["kernel"], rounded)


def _attention(p, x, model, rounded: bool = False, query_block=512):
    import jax.numpy as jnp

    t = x.shape[0]
    h, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["head_dim"]
    op = _bf16 if rounded else (lambda a: a)
    q = _mm(x, p["q_proj"]["kernel"], rounded).reshape(t, h, d)
    k = _mm(x, p["k_proj"]["kernel"], rounded).reshape(t, hkv, d)
    v = _mm(x, p["v_proj"]["kernel"], rounded).reshape(t, hkv, d)
    positions = jnp.arange(t)
    group = h // hkv  # query head i reads key-value head i // group
    outs = []
    for start in range(0, t, query_block):
        qb = q[start:start + query_block].reshape(-1, hkv, group, d)
        scores = jnp.einsum(
            "qngd,knd->ngqk", op(qb), op(k)
        ) * model["attention_multiplier"]
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
    return _mm(out, p["o_proj"]["kernel"], rounded)


def _mlp(p, x, model, rounded: bool = False):
    width = model["shared_intermediate_size"]
    fused = _mm(x, p["input_linear"]["kernel"], rounded)
    return _mm(
        _silu(fused[:, :width]) * fused[:, width:],
        p["output_linear"]["kernel"], rounded,
    )


def decoder(w: dict, tokens, model: dict, rounded: bool = False, head=None):
    """One sequence [T] -> logits [T, V], in the dtype of `w`; `rounded`:
    the products take operands rounded to bfloat16; `head`: the table the
    logits are read off, THE EMBEDDING TABLE where None (the model's tie;
    the tests hold the two readings apart with it)."""
    eps, m_r = model["rms_norm_eps"], model["residual_multiplier"]
    w = w["model"]
    table = w["embed_tokens"]
    x = model["embedding_multiplier"] * table[tokens]
    kinds = model["layer_types"][:model["num_hidden_layers"]]
    for i, kind in enumerate(kinds):
        p = w[f"layers_{i}"]
        h = _rms_norm(x, p["input_layernorm"]["weight"], eps)
        if kind == MAMBA:
            x = x + m_r * _mamba2(p["mamba"], h, model, rounded)
        else:
            x = x + m_r * _attention(p["self_attn"], h, model, rounded)
        h = _rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
        x = x + m_r * _mlp(p["shared_mlp"], h, model, rounded)
    return _mm(
        _rms_norm(x, w["norm"]["weight"], eps),
        (table if head is None else head).T, rounded,
    ) / model["logits_scaling"]


#: precision -> (dtype of every weight and activation, whether the
#: products round their operands to bfloat16, the planted fault: the
#: multiplier it replaces and what a usual stack has there, of `model`)
PRECISIONS = {
    "highest": ("float32", False, None),
    "stated": ("float32", True, None),
    "bfloat16": ("bfloat16", False, None),
    "no_residual_multiplier": (
        "float32", False, ("residual_multiplier", lambda model: 1.0),
    ),
    "sqrt_scale": (
        "float32", False,
        ("attention_multiplier", lambda model: model["head_dim"] ** -0.5),
    ),
}


def forward(w: dict, tokens, model: dict, precision: str = "highest"):
    """`highest`: float32 throughout.  `stated`: what the configuration
    states (bfloat16 operands in the products, the rest float32).
    `bfloat16`: the same code with EVERY weight and activation in
    bfloat16 (norms, decays and recurrent state too).  Two planted
    faults, each `highest` with one multiplier wrong, as a stack written
    for the usual conventions would have it: `no_residual_multiplier`
    adds every sublayer at 1 where the model says 0.22; `sqrt_scale`
    scales the scores by 1/sqrt(head_dim) = 1/8 where the model says
    1/64.  The program's distance to each says how much of the compared
    logits that multiplier carries at these weights."""
    import jax
    import jax.numpy as jnp

    if precision not in PRECISIONS:
        raise ValueError(f"no precision {precision!r}")
    dtype, rounded, fault = PRECISIONS[precision]
    if fault is not None:
        key, usual = fault
        model = dict(model, **{key: usual(model)})
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, dtype), w)
        return jnp.stack([
            decoder(w, row, model, rounded).astype(jnp.float32)
            for row in jnp.asarray(tokens)
        ])


# -- the least work ------------------------------------------------------------


def _layers(model: dict, kind: str) -> int:
    return model["layer_types"][:model["num_hidden_layers"]].count(kind)


def _matmul_params(model: dict) -> dict:
    """Parameters that multiply a token's activations, by part."""
    d = model["hidden_size"]
    heads = model["mamba_n_heads"]
    inner = heads * model["mamba_d_head"]
    bc = model["mamba_n_groups"] * model["mamba_d_state"]
    h, hkv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    return {
        "ssm": _layers(model, MAMBA) * (
            d * (2 * inner + 2 * bc + heads) + inner * d
        ),
        "attn": _layers(model, ATTENTION) * (
            2 * d * h * hd + 2 * d * hkv * hd
        ),
        "mlp": model["num_hidden_layers"] * (
            3 * d * model["shared_intermediate_size"]
        ),
        "head": d * model["vocab_size"],  # the table, read as the head
    }


def _all_params(model: dict) -> int:
    """Every parameter: the products' (the tied table ONCE), the
    convolutions', `A_log`, `D`, `dt_bias`, the gated norms', the layers'
    two norms and the final one."""
    m = _matmul_params(model)
    heads = model["mamba_n_heads"]
    inner = heads * model["mamba_d_head"]
    bc = model["mamba_n_groups"] * model["mamba_d_state"]
    d = model["hidden_size"]
    return (
        m["ssm"] + m["attn"] + m["mlp"] + m["head"]
        + _layers(model, MAMBA) * (
            (model["mamba_d_conv"] + 1) * (inner + 2 * bc) + 3 * heads + inner
        )
        + (2 * model["num_hidden_layers"] + 1) * d
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
    h, p = model["mamba_n_heads"], model["mamba_d_head"]
    g, n = model["mamba_n_groups"], model["mamba_d_state"]
    q, t = model["mamba_chunk_size"], model["sample_tokens"]
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
    training step AS THE CONFIGURATION RUNS IT, by the rule of
    `nemotron_h_reference.ssm_scan_cost` (one yardstick at two shapes):
    the forward, the forward once more under the layer's
    rematerialisation, and the backward at twice a forward's FLOPs.
    Bytes, float32: a forward reads x, B, C, dt and writes y, and writes
    and reads the chunk states; the backward reads those five and the
    states, and writes the four gradients and writes and reads the
    states' own."""
    one = _ssd_forward(model, minibatch)
    forward = one["tensors"] + 2 * one["states"]
    backward = 2 * one["tensors"] + 3 * one["states"]
    return {
        "flops": 4 * one["flops"],
        "bytes": 4 * (2 * forward + backward),
    }


def mlp_cost(model: dict, minibatch: int) -> dict:
    """The `mlp` scope (every layer's gated-SiLU MLP with its norm and
    residual) for one training step AS THE CONFIGURATION RUNS IT: 2 FLOPs
    a weight a token forward, the same once more under the layer's
    rematerialisation, 4 backward.  Bytes: the float32 weights read by
    each of the three passes and their gradient written once (4 x 4 B a
    weight), and a token's float32 row of the stream read and written by
    each pass."""
    weights = _matmul_params(model)["mlp"]
    tokens = minibatch * model["sample_tokens"]
    rows = model["num_hidden_layers"] * tokens * model["hidden_size"]
    return {
        "flops": 8 * weights * tokens,
        "bytes": 4 * 4 * weights + 3 * 2 * 4 * rows,
    }


def step_cost(model: dict, minibatch: int) -> dict:
    """The least a training step needs, from shapes.  FLOPs: 6 a matmul
    parameter a token over the mixers' projections, the MLPs and the
    head (the table's other reading, the gather, multiplies nothing);
    causal attention's score and value products, 4 T^2 H D a sequence
    forward, halved, times 3; the state-space form forward and backward
    (3 forwards' FLOPs).  No recomputation.  Bytes: AdamW reads weight,
    gradient and two moments and writes weight and two moments, 7 x 4
    bytes a parameter."""
    m = _matmul_params(model)
    t = model["sample_tokens"]
    attention = 3 * _layers(model, ATTENTION) * minibatch * (
        4 * t * t * model["num_attention_heads"] * model["head_dim"]
    ) // 2
    dense = m["ssm"] + m["attn"] + m["mlp"] + m["head"]
    return {
        "flops": 6 * dense * minibatch * t + attention
        + 3 * _ssd_forward(model, minibatch)["flops"],
        "bytes": 7 * 4 * _all_params(model),
    }
