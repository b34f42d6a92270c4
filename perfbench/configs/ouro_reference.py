"""Plain reference of Ouro (a stack of plain decoder layers applied R =
`total_ut_steps` times to its own output over ONE set of weights, an exit
behind every pass: the head's logits and a learned gate, and a loss over
the distribution of exits) for ONE chip's share of it: the layers `model`
gives, the slice of the vocabulary it gives.  float32 `jax.numpy`, no
kernel, no custom backward, no blocks of keys, the passes a plain Python
loop over one list of layer weights, and no code of the program or of
another reference.  With d the hidden size, H query heads over Hkv
key-value heads of D, every norm `w x / sqrt(mean(x^2) + eps)`::

    h_0 = E[tokens]
    pass r = 1..R, the SAME layers every pass:
      x = h_{r-1}
      layer i:
        a = rmsnorm(x; w_in)
        q = a Wq -> [T, H, D]   k = a Wk -> [T, Hkv, D]   v = a Wv -> [T, Hkv, D]
        q, k turned by the plain rotary table over ALL D columns (pair i is
             (x_i, x_{i + D/2})), positions 0..T-1 in EVERY pass
        o = softmax(q k^T / sqrt(D) + causal mask) v, query head j reads
            key-value head j // (H / Hkv); the mask written out over all T
            keys, one block of queries at a time so that 8192 tokens fit
        x = x + rmsnorm(concat_heads(o) Wo; w_in2)      the sublayer's OUTPUT is normed too
        m = rmsnorm(x; w_post)
        x = x + rmsnorm(Wd (silu(Wg m) * Wu m); w_post2)
      h_r = rmsnorm(x; w_f)      closes EVERY pass and is the next pass's input
      logits_r = h_r W_head      the same untied head at every exit
      lambda_r = sigmoid(h_r w_g + b_g)
    p_1 = lambda_1;  p_r = lambda_r prod_{j<r} (1 - lambda_j);  p_R = prod_{j<R} (1 - lambda_j)
    loss = mean over tokens of [ sum_r p_r CE(logits_r, next) - beta H(p) ]

WHAT `forward` AND `program` RETURN, and the harness compares, is ONE
array that holds both new things: the joint log-probability of leaving at
exit r with id v, `ln p_r + log_softmax(logits_r)[v]`, [rows, R, T, V].
A wrong gate moves a whole row of V by a constant, a wrong pass moves its
exit.  `forward(..., "highest")` also prints the two parts' own distances
to what `program` kept (the run's `check.log`).

The same code also runs AT THE PRECISION THE CONFIGURATION STATES
(`"stated"`: the operands of the four attention projections, of the score
and value products, of the MLP and of the head rounded to bfloat16 with
float32 accumulation, everything else float32: norms, table, softmax
statistics, gate, exit distribution), with EVERY weight and activation in
bfloat16 (`"bfloat16"`: the nearest precision below the stated one, which
the cell's limit refuses), and with four PLANTED FAULTS in what is new
here: `one_pass` (the stack applied ONCE and its state handed to all R
exits), `no_post_norm` (the two output norms of a layer left out),
`norm_outside` (the raw stream goes round and the final norm stands
before each exit only), `positions_run_on` (pass r turns q and k by
positions (r - 1) T .. r T - 1).  The last one CANNOT show in any output:
rotary scores depend on the difference of two positions alone, so a shift
of a whole pass leaves them as they were, to the rounding of a float32
angle; the reading says how large that rounding is, and the piece is held
by a test of the program's table instead (`tests/test_ouro.py`).

Beside it: where the program's own outputs come from (`program`), the
least work of a training step (`step_cost`) and of the scopes whose
roofline share the benchmark reports (`loop_cost`, `attn_proj_cost`,
`attn_full_cost`, `mlp_cost`), each R times one pass's work by the rule
the other references reckon that scope by.

Departures from the equations of ISSUE 45: none.  What the source's
`config.json` leaves open is listed in the configuration's `assumed` (the
four norms a layer, the final norm inside the loop, no bias and no head
norm, the gate, the loss and its beta, rotary columns in half-split
order, a float32 residual stream).
"""

from __future__ import annotations

import math
import sys

import numpy as np

#: What `program` returned last, apart: {"logits": log_softmax [rows, R, T,
#: V], "exit_logp": [rows, R, T]} (for `forward`'s line in check.log).
_PROGRAM = {}

FAULTS = ("one_pass", "no_post_norm", "norm_outside", "positions_run_on")


def sample(seed: int, rows: int, model: dict):
    rng = np.random.default_rng(seed)
    return rng.integers(
        0, model["vocab_size"], size=(rows, model["sample_tokens"])
    ).astype(np.int32)


def weights(step_dir: str, features, model: dict, program_state=None):
    """The flax params of the job's checkpoint, as the program's saver
    unpickled them (one read serves both sides)."""
    return program_state.params


def joint(logits, exit_logp):
    """logits [.., R, T, V], ln p [.., R, T] -> ln p_r +
    log_softmax(logits_r) [.., R, T, V]: what is compared."""
    import jax

    return exit_logp[..., None] + jax.nn.log_softmax(logits, axis=-1)


def program(args, features):
    """The program's own joint log-probabilities for `features` at the
    job's last checkpoint (the trainer is built as
    `worker/main._build_collective_worker` builds it; `eval_step` reads
    the weights and the model state, so the optimizer's state stays on the
    host), from the two entries of its prediction.  -> (outputs, step,
    program_state)."""
    import jax
    import jax.numpy as jnp

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
    # Only the weights go to the device: the two Adam moments (2.7 GB of
    # the 4.0 GB saved) have no part in a forward pass.
    trainer.state = state._replace(opt_state=())
    predicted = trainer.eval_step(features)
    logits = jnp.asarray(predicted["logits"], jnp.float32)
    exit_logp = jnp.asarray(predicted["exit_logp"], jnp.float32)
    _PROGRAM["exit_logp"] = np.asarray(exit_logp)
    _PROGRAM["logits"] = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    return np.asarray(joint(logits, exit_logp), np.float32), step, state


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


def _rotary(x, model, first: int = 0):
    """x [T, heads, D]: pair i is (x_i, x_{i + D/2}), turned by
    (first + t) x theta^(-2i/D); every column of the head belongs to a
    pair."""
    import jax.numpy as jnp

    dim = model["head_dim"]
    half = dim // 2
    inv_freq = float(model["rope_theta"]) ** (
        -2.0 * np.arange(half, dtype=np.float64) / dim
    )
    angles = (first + jnp.arange(x.shape[0], dtype=jnp.float32))[:, None] * (
        jnp.asarray(inv_freq, jnp.float32)[None, :]
    )
    cos = jnp.cos(angles)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, None, :].astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(p, x, model, rounded=False, first=0, query_block=512):
    import jax.numpy as jnp

    t = x.shape[0]
    heads, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["head_dim"]
    op = _bf16 if rounded else (lambda a: a)
    q = _mm(x, p["q_proj"]["kernel"], rounded).reshape(t, heads, d)
    k = _mm(x, p["k_proj"]["kernel"], rounded).reshape(t, hkv, d)
    v = _mm(x, p["v_proj"]["kernel"], rounded).reshape(t, hkv, d)
    q, k = _rotary(q, model, first), _rotary(k, model, first)
    # Query head j reads key-value head j // (heads / hkv).
    k, v = (jnp.repeat(a, heads // hkv, axis=1) for a in (k, v))
    positions = jnp.arange(t)
    outs = []
    for start in range(0, t, query_block):
        qb = q[start:start + query_block]
        at = positions[start:start + query_block, None]
        scores = jnp.einsum("qhd,khd->hqk", op(qb), op(k)) / math.sqrt(d)
        scores = jnp.where((positions[None, :] <= at)[None], scores, -jnp.inf)
        # softmax, written out: the weights are rounded (where they are)
        # before they are normalised, the sum is of the unrounded ones.
        weights = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
        mixed = jnp.einsum("hqk,khd->qhd", op(weights), op(v))
        total = jnp.moveaxis(jnp.sum(weights, -1), 1, 0)[..., None]
        outs.append(mixed / total)
    out = jnp.concatenate(outs)                       # [T, heads, D]
    return _mm(out.reshape(t, heads * d), p["o_proj"]["kernel"], rounded)


def _mlp(p, x, rounded: bool):
    hidden = _silu(_mm(x, p["gate_proj"]["kernel"], rounded)) * _mm(
        x, p["up_proj"]["kernel"], rounded
    )
    return _mm(hidden, p["down_proj"]["kernel"], rounded)


def _layer(p, x, model, rounded, fault, first):
    eps = model["rms_norm_eps"]
    sandwich = model.get("sandwich_norm", True) and fault != "no_post_norm"

    def out_norm(y, name):
        return _rms_norm(y, p[name]["weight"], eps) if sandwich else y

    a = _rms_norm(x, p["input_layernorm"]["weight"], eps)
    x = x + out_norm(
        _attention(p["self_attn"], a, model, rounded, first),
        "input_layernorm_2",
    )
    m = _rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
    return x + out_norm(_mlp(p["mlp"], m, rounded), "post_attention_layernorm_2")


def decoder(w: dict, tokens, model: dict, rounded=False, fault=None,
            per_pass=None):
    """One sequence [T] -> (logits [R, T, V], ln p [R, T]), in the dtype
    of `w`.  `rounded`: the blocks' products take operands rounded to
    bfloat16.  `fault`: one of `FAULTS`.  `per_pass`: R trees of layer
    weights (`layers_<i>` each) for a stack whose passes do NOT share
    them, in place of `w["model"]`'s (the tests': a shared leaf's gradient
    is the sum of these)."""
    import jax
    import jax.numpy as jnp

    eps, body = model["rms_norm_eps"], w["model"]
    passes, t = model["total_ut_steps"], tokens.shape[0]
    loop_norm = model.get("loop_norm", True) and fault != "norm_outside"
    x = body["embed_tokens"][tokens]
    exits = []
    for r in range(passes):
        layers = body if per_pass is None else per_pass[r]
        first = r * t if fault == "positions_run_on" else 0
        if not (fault == "one_pass" and r):
            for i in range(model["num_hidden_layers"]):
                x = _layer(layers[f"layers_{i}"], x, model, rounded, fault,
                           first)
        exits.append(_rms_norm(x, body["norm"]["weight"], eps))
        if loop_norm and fault != "one_pass":
            x = exits[-1]
    states = jnp.stack(exits)                                   # [R, T, d]
    logits = _mm(states, w["lm_head"], rounded)
    gate = (states @ body["early_exit_gate"]["kernel"])[..., 0] + (
        body["early_exit_gate"]["bias"][0]
    )
    # ln sigmoid(g) = -ln(1 + e^-g), ln (1 - sigmoid(g)) = -ln(1 + e^g)
    leave, stay = -jnp.logaddexp(0.0, -gate), -jnp.logaddexp(0.0, gate)
    logp = [
        sum(stay[j] for j in range(r)) + (leave[r] if r < passes - 1 else 0.0)
        for r in range(passes)
    ]
    return logits, jnp.stack(logp).astype(logits.dtype)


#: precision -> (dtype of every weight and activation, whether the blocks'
#: products round their operands to bfloat16, the planted fault)
PRECISIONS = {
    "highest": ("float32", False, None),
    "stated": ("float32", True, None),
    "bfloat16": ("bfloat16", False, None),
    **{fault: ("float32", False, fault) for fault in FAULTS},
}


def _parts(w, tokens, model, precision):
    """-> (log_softmax of every exit's logits [rows, R, T, V], ln p
    [rows, R, T]), float32."""
    import jax
    import jax.numpy as jnp

    if precision not in PRECISIONS:
        raise ValueError(f"no precision {precision!r}")
    dtype, rounded, fault = PRECISIONS[precision]
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, dtype), w)
        rows = [
            decoder(w, row, model, rounded, fault)
            for row in jnp.asarray(tokens)
        ]
        return (
            jnp.stack([
                jax.nn.log_softmax(logits, axis=-1).astype(jnp.float32)
                for logits, _ in rows
            ]),
            jnp.stack([logp.astype(jnp.float32) for _, logp in rows]),
        )


def forward(w: dict, tokens, model: dict, precision: str = "highest"):
    """tokens [rows, T] -> the joint log-probabilities [rows, R, T, V],
    float32.  `highest`: float32 throughout.  `stated`: what the
    configuration states.  `bfloat16`: the same code with EVERY weight and
    activation in bfloat16 (norms, table, softmax statistics, gate and
    log-probabilities too).  `one_pass`, `no_post_norm`, `norm_outside`,
    `positions_run_on`: `highest` with one piece of what is new here wrong
    (module docstring): planted faults, reported beside the limit."""
    logits, logp = _parts(w, tokens, model, precision)
    theirs = _PROGRAM.get("logits")
    if precision == "highest" and theirs is not None and (
        theirs.shape == logits.shape
    ):
        def rel(got, want):
            want = np.asarray(want, np.float64)
            return float(np.sqrt(np.mean((got - want) ** 2))
                         / np.sqrt(np.mean(want ** 2)))

        print(
            "reference: the program's two parts against `highest`, each "
            f"over its own rms: log_softmax(logits) {rel(theirs, logits):.4g}"
            f", ln p of the exits {rel(_PROGRAM['exit_logp'], logp):.4g}; "
            "the reference's mean exit distribution over the compared "
            f"tokens {np.exp(np.asarray(logp)).mean(axis=(0, 2)).round(4)}",
            file=sys.stderr, flush=True,
        )
    return logits + logp[..., None]


def loss_fn(w: dict, tokens, labels, model: dict, per_pass=None):
    """tokens, labels [rows, T] -> the mean over all tokens of the
    expected next-token cross-entropy under the exit distribution less
    `exit_beta` times its entropy, float32 at `highest`: the loss the
    program reports and descends."""
    import jax
    import jax.numpy as jnp

    beta, per_token = model.get("exit_beta", 0.05), []
    with jax.default_matmul_precision("highest"):
        for row, target in zip(tokens, labels):
            logits, logp = decoder(w, row, model, per_pass=per_pass)
            cross_entropy = -jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1), target[None, :, None],
                axis=-1,
            )[..., 0]                                           # [R, T]
            p = jnp.exp(logp)
            per_token.append(
                jnp.sum(p * cross_entropy, 0) + beta * jnp.sum(p * logp, 0)
            )
    return jnp.mean(jnp.stack(per_token))


# -- the least work ------------------------------------------------------------


def _matmul_params(model: dict) -> dict:
    """Parameters that multiply a token's activations ONCE A PASS, by
    part (the gate's 2048 are a matrix-vector product and not counted)."""
    d, hd = model["hidden_size"], model["head_dim"]
    layers = model["num_hidden_layers"]
    return {
        # q and o a query head, k and v a key-value head, a layer
        "attn": layers * 2 * d * hd * (
            model["num_attention_heads"] + model["num_key_value_heads"]
        ),
        "mlp": layers * 3 * d * model["intermediate_size"],
        "head": d * model["vocab_size"],
    }


def _all_params(model: dict) -> int:
    """Every parameter this chip holds: ONE set of layers whatever
    `total_ut_steps` is (a layer's norms: four, or two without the output
    norms), the table, the head, the final norm, the gate and its bias."""
    m = _matmul_params(model)
    d = model["hidden_size"]
    norms = 4 if model.get("sandwich_norm", True) else 2
    return (
        m["attn"] + m["mlp"] + m["head"] + model["vocab_size"] * d
        + model["num_hidden_layers"] * norms * d + d + d + 1
    )


def _passes(cost: dict, model: dict) -> dict:
    return {key: model["total_ut_steps"] * value for key, value in cost.items()}


def attn_proj_cost(model: dict, minibatch: int) -> dict:
    """The `attn_proj` scope (q, k, v and o of every layer, in every
    pass) for one training step AS THE CONFIGURATION RUNS IT: R times a
    pass's work by `mellum_reference.attn_proj_cost`'s rule (one yardstick
    at two shapes).  A pass: 8 FLOPs a weight a token (forward 2, once
    more under the layer's rematerialisation, backward 4); bytes: the
    float32 weights read in each of the three and their gradient written,
    plus a token's rows in and out of the four products, bfloat16 in and
    float32 out forward (twice) and the reverse backward."""
    m = _matmul_params(model)
    tokens = minibatch * model["sample_tokens"]
    d, hd = model["hidden_size"], model["head_dim"]
    heads = model["num_attention_heads"]
    wide = (heads + 2 * model["num_key_value_heads"]) * hd
    rows = model["num_hidden_layers"] * (2 * d + wide + heads * hd)
    return _passes({
        "flops": 8 * m["attn"] * tokens,
        "bytes": 4 * 4 * m["attn"] + 3 * 6 * rows * tokens,
    }, model)


def attn_full_cost(model: dict, minibatch: int) -> dict:
    """The `attn_full` scope (the attention core of every layer, in every
    pass) for one training step: R times a pass's work by
    `mellum_reference._core_cost`'s rule over the T^2 / 2 key positions a
    head that the causal mask requires, whichever engine runs it.  FLOPs,
    each product 2 x keys x D a query head: a forward is q k^T and p v; it
    runs once more under the layer's rematerialisation; the backward is
    five products.  Bytes, bfloat16: a forward reads q and writes o a
    query head and reads k and v a key-value head; the backward reads q,
    o, dO and writes dq a query head, reads k, v and writes dk, dv a
    key-value head."""
    t = model["sample_tokens"]
    heads = model["num_hidden_layers"] * model["num_attention_heads"]
    kv_heads = model["num_hidden_layers"] * model["num_key_value_heads"]
    rows = minibatch * t * model["head_dim"]
    forward = 2 * heads + 2 * kv_heads
    backward = 4 * heads + 4 * kv_heads
    return _passes({
        "flops": 2 * (t * t / 2) * model["head_dim"] * heads * minibatch
        * (2 * 2 + 5),
        "bytes": 2 * rows * (2 * forward + backward),
    }, model)


def mlp_cost(model: dict, minibatch: int) -> dict:
    """The `mlp` scope (every layer's gated-SiLU MLP, in every pass) for
    one training step: R times a pass's work by
    `granite_hybrid_reference.mlp_cost`'s rule.  A pass: 2 FLOPs a weight
    a token forward, the same once more under the layer's
    rematerialisation, 4 backward; bytes: the float32 weights read by each
    of the three and their gradient written once, and a token's float32
    row of the stream read and written by each."""
    weights = _matmul_params(model)["mlp"]
    tokens = minibatch * model["sample_tokens"]
    rows = model["num_hidden_layers"] * tokens * model["hidden_size"]
    return _passes({
        "flops": 8 * weights * tokens,
        "bytes": 4 * 4 * weights + 3 * 2 * 4 * rows,
    }, model)


def loop_cost(model: dict, minibatch: int) -> dict:
    """The `loop` scope (all R passes of the stack) for one training step
    AS THE CONFIGURATION RUNS IT: the three scopes above, which hold every
    product of a layer; the norms, the rotation and the adds multiply no
    matrix and are not counted."""
    parts = [
        cost(model, minibatch)
        for cost in (attn_proj_cost, attn_full_cost, mlp_cost)
    ]
    return {key: sum(part[key] for part in parts) for key in ("flops", "bytes")}


def step_cost(model: dict, minibatch: int) -> dict:
    """The least a training step needs, from shapes.  FLOPs: 6 a matmul
    parameter a token A PASS over the projections, the MLPs and the head
    (R exits: the head multiplies every pass's state), which is R times
    what the parameter count says; the attention cores' score and value
    products over the key positions the mask requires, forward and
    backward at twice that.  No recomputation.  Bytes: AdamW reads weight,
    gradient and two moments and writes weight and two moments, 7 x 4
    bytes a parameter, ONCE a step whatever R is."""
    m = _matmul_params(model)
    tokens = minibatch * model["sample_tokens"]
    dense = model["total_ut_steps"] * (m["attn"] + m["mlp"] + m["head"])
    # forward 2 products + backward 4 of a core's 9 with rematerialisation
    attention = attn_full_cost(model, minibatch)["flops"] * 6 / 9
    return {
        "flops": 6 * dense * tokens + attention,
        "bytes": 7 * 4 * _all_params(model),
    }
