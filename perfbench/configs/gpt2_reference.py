"""Plain reference of the GPT-2 decoder as `model_zoo/transformer` builds it
(pre-LN blocks, learned positions, tanh-GELU, causal softmax attention
scaled by 1/sqrt(head)), in float32 `jax.numpy` with no kernel, and the
step's least work, and how the program's own outputs are had from a job's
checkpoint.

Departures of the zoo from the source, kept here because the reference has
to compute what the job's weights mean: the output head is a Dense of its
own (kernel [d, V] and bias), not the transposed token embedding, and
LayerNorm's epsilon is 1e-6.  The program computes the blocks in bfloat16
and the logits in float32; the reference computes everything in float32.
"""

from __future__ import annotations

import numpy as np


def sample(seed: int, rows: int, model: dict):
    rng = np.random.default_rng(seed)
    return rng.integers(
        0, model["vocab_size"], size=(rows, model["n_positions"])
    ).astype(np.int32)


def weights(step_dir: str, features, model: dict, program_state=None):
    """The flax params of the job's checkpoint, as the program's saver
    unpickled them (one 4.9 GB read serves both sides)."""
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


def _layer_norm(x, p, eps):
    import jax.numpy as jnp

    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def forward(w: dict, tokens, model: dict, precision: str = "highest"):
    import jax
    import jax.numpy as jnp

    if precision != "highest":
        raise ValueError(f"no precision {precision!r}")
    eps = model["layer_norm_epsilon"]
    heads = model["n_head"]
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        b, t = tokens.shape
        x = jnp.asarray(w["Embed_0"]["embedding"], jnp.float32)[tokens]
        x = x + jnp.asarray(w["Embed_1"]["embedding"], jnp.float32)[:t][None]
        causal = jnp.tril(jnp.ones((t, t), bool))
        for i in range(model["n_layer"]):
            p = jax.tree.map(
                lambda a: jnp.asarray(a, jnp.float32), w[f"block_{i}"]
            )
            h = _layer_norm(x, p["LayerNorm_0"], eps)
            qkv = (
                jnp.einsum("bte,echd->btchd", h, p["attn"]["qkv"]["kernel"])
                + p["attn"]["qkv"]["bias"]
            )
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(
                q.shape[-1]
            )
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            attn = jnp.einsum(
                "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v
            ).reshape(b, t, heads * q.shape[-1])
            x = x + attn @ p["attn"]["proj"]["kernel"] + p["attn"]["proj"]["bias"]
            h = _layer_norm(x, p["LayerNorm_1"], eps)
            h = jax.nn.gelu(
                h @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"],
                approximate=True,
            )
            x = x + h @ p["Dense_1"]["kernel"] + p["Dense_1"]["bias"]
        x = _layer_norm(x, w["LayerNorm_0"], eps)
        head = w["lm_head"]
        return x @ jnp.asarray(head["kernel"], jnp.float32) + head["bias"]


def step_cost(model: dict, minibatch: int) -> dict:
    """The least a training step needs, from shapes.

    FLOPs: 6 per matmul parameter per token (forward 2, backward 4) over the
    blocks (12 d^2 a layer) and the output head (d x V), plus attention's
    score and value products, 4 T^2 d a layer a sequence forward, halved by
    causality, times 3 for forward and backward.  Recomputation is not
    counted.  Bytes: AdamW reads the weight, the gradient and two moments
    and writes the weight and two moments, 7 x 4 bytes a parameter."""
    d, layers, t = model["n_embd"], model["n_layer"], model["n_positions"]
    vocab = model["vocab_size"]
    matmul_params = layers * (4 * d * d + 2 * d * model["n_inner"]) + d * vocab
    all_params = matmul_params + (vocab + t) * d
    tokens = minibatch * t
    attention = 3 * layers * minibatch * (4 * t * t * d) // 2
    return {
        "flops": 6 * matmul_params * tokens + attention,
        "bytes": 7 * 4 * all_params,
    }
