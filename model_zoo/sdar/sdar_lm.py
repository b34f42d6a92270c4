"""SDAR: a decoder of routed experts trained by BLOCK DIFFUSION.  The layer
is a plain one (grouped-query attention with a norm on every query and
key head behind rotary positions, then routed experts behind a softmax
router renormalised over its top k, nothing beside them); what is new is
the training step: every sequence runs as a NOISED copy beside its CLEAN
copy, 2 T positions, attention goes under the three-part block-diffusion
mask, and the loss is taken over the masked positions, each predicting
ITS OWN token, weighted by 1 / t.

Source: https://huggingface.co/JetLM/SDAR-30B-A3B-Chat (its `config.json`,
`model_type: sdar_moe`); the objective is BD3-LM's (arXiv:2503.09573),
which SDAR (arXiv:2510.06303) adapts an autoregressive model to.  One
record, T tokens `x0`, its noise `(m, t)` beside it in the FEATURES::

    x_t[i] = mask_token_id if m[i] else x0[i]
    u   = E[concat(x_t, x0)]                       [2 T, hidden]
    pos = concat(0..T-1, 0..T-1)       both copies of a token at ITS position
    layer: u = u + self_attn(input_layernorm(u))   rotary by `pos`, under
                                                   the block-diffusion mask
           u = u + mlp(post_attention_layernorm(u))
    logits = norm(u[:T]) W_head                    the NOISED half alone
    loss = (1 / T) sum_i m[i] (1 / t) CE(logits[i], x0[i])

The mask (`ops/gqa.BlockDiffusion`, in blocks of `block_length`): a noised
query reads its own noised block in both directions and the clean blocks
strictly before it; a clean query reads the clean blocks up to and with
its own.  No logit of a noised block depends on the clean copy of its own
or a later block.  Every token-wise part (norms, projections, router,
experts) runs over the 2 T rows; the router's balancing loss takes a
record's 2 T rows as its sequence.

THE NOISE IS DATA.  `dp_trainer` hands a model no random key, and must
not: in an elastic job a task is re-run by whichever worker the master
gives it to after a kill, and the records the master counted must train
the same way twice.  So `dataset_fn` draws a record's `t` and mask on the
host (`record_noise`: numpy's counter-based Philox, keyed by `noise_seed`
and a 64-bit digest of the record's tokens; never by the worker, its
step count or the clock): ONE `t` a sequence from U(`t_min`, 1], then each
position masked with probability `t`.  The same record meets the same
noise on any worker, in any task, after any restart, and in every epoch
(`metadata` carries no epoch to fold in).  The zoo contract hands
`dataset_fn` no model parameter, so `custom_model` keeps the job's
`noise_seed` and `t_min` for this process's `dataset_fn` (`_NOISE`).

Features are `(tokens [B, T] int32, mask [B, T] bool, t [B] float32)`;
the label of a position is its own token.  THE PREDICTION IS A NAMED
TREE: `logits` [B, T, V] float32, at every position, masked or not, and
`weight` [B, T] float32, `m / t`.  `loss`, `eval_metrics_fn` and
`dataset_fn` below are this stack's own.

A layer's second sublayer is `sparse` (`layers/moe.py` `SparseMoeBlock`:
`p = softmax(W_r u)` over ALL `num_experts`, the top `num_experts_per_tok`,
weights `p` at the chosen over their sum (`norm_topk_prob`), gated-SiLU
experts of `moe_intermediate_size`, NO shared expert; the layer holds a
RANGE of the experts, `experts_first` / `experts_held`, and a row none of
whose choices is held here leaves as it entered) where the source's rule
says so (layer i is sparse unless i is in `mlp_only_layers` or (i + 1) is
no multiple of `decoder_sparse_step`: every published layer), else a
gated-SiLU MLP of `intermediate_size`.

Module and parameter names: `model` holding `embed_tokens`, `layers_<i>`
(`input_layernorm`, `self_attn` with `q_proj`, `k_proj`, `v_proj`,
`q_norm`, `k_norm`, `o_proj`; `post_attention_layernorm`; `mlp` with `gate`
[hidden, experts] and `experts_gate_proj` / `experts_up_proj` /
`experts_down_proj` (the held experts, stacked [held, in, out]), or
`gate_proj` / `up_proj` / `down_proj` in a dense layer) and `norm`;
`lm_head`.  Kernels in flax's [in, out] layout.

Assumed where the source's `config.json` is silent, each also in the
configuration's `assumed` with its other reading: `block_length` 4; the
noise schedule (linear, absorbing, one `t` a sequence, weight 1 / t,
normalised by T: `noise_per="sequence"`; a `t` a block is not built and
raises); no shift (`predict_shift=False`; true is not built and raises);
the mask id (the vocabulary slice's last); the head norms (`qk_norm`);
the router's form and `balance_alpha`; the optimizer's numbers; rotary
columns in the half-split order; a float32 residual stream.  Generation
by denoising a block over several steps is not built.

Precision: parameters float32; with `use_bf16` the four attention
projections, scores and values, the expert products (a dense layer's too)
and the head take bfloat16 operands and accumulate in float32.  Always
float32: the residual stream, every norm (the heads' too), the rotary
table, the attention softmax's statistics, the router (logits at
`Precision.HIGHEST`, softmax, top-k, the balancing loss), logits and loss.

`attn_impl` is handed to `ops/gqa.causal_attention` as it is; under this
mask every value but `pallas` (which raises) is the XLA block engine.

Device scopes (obs/tracing.py DEVICE_SCOPES): `attn` (the sublayer with
its norm and residual) > `attn_proj`, `attn_rotary`, `attn_blockdiff` (the
engine's call under this mask); `moe` > `moe_route`, `moe_experts` (or
`mlp`, a dense layer); `lm_head_loss` (the final norm and the head over
the noised half, and the weighted loss).  Counters:
`layers/diffusion_noise.py`'s, journaled a task as `diffusion.noise`, and
`moe.routing`.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.layers.diffusion_noise import count_noise
from elasticdl_tpu.layers.moe import GatedMLP, SparseMoeBlock
from elasticdl_tpu.ops import gqa
# The norm, the attention behind rotary positions, the optimizer's warm-up
# and the reader of `synthetic://lm` data; the features, the loss and the
# metrics are this stack's own (a step takes the record's noise with its
# tokens, and its prediction is a tree).
from model_zoo.lm_common import (  # noqa: F401
    KEEP_ATTENTION_RESULTS, VOCAB, RMSNorm, RotaryAttention,
    custom_data_reader, listed, warmup_adamw,
)


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """The source's `config.json` keys this model reads, then the
    objective's, what this chip holds and how it computes."""

    vocab_size: int = VOCAB
    hidden_size: int = 64
    intermediate_size: int = 128
    num_hidden_layers: int = 2
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    rope_theta: float = 1000000.0
    num_experts: int = 8
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 32
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    rms_norm_eps: float = 1e-6
    block_length: int = 4
    mask_token_id: int = -1  # -1: the vocabulary's last id
    noise_per: str = "sequence"
    t_min: float = 1e-3
    noise_seed: int = 0
    predict_shift: bool = False
    experts_first: int = 0
    experts_held: int = 8
    qk_norm: bool = True
    balance_alpha: float = 0.001
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    remat: bool = False

    def dense(self, layer: int) -> bool:
        """Whether layer `layer`'s second sublayer is the dense MLP (the
        source's rule; no published layer)."""
        return layer in self.mlp_only_layers or bool(
            (layer + 1) % self.decoder_sparse_step
        )


class DecoderLayer(nn.Module):
    cfg: SdarConfig
    dense: bool
    tokens: int    # T: x holds the noised copy, then the clean one

    @nn.compact
    def __call__(self, x, cos, sin):
        c = self.cfg
        with jax.named_scope("attn"):
            h = RMSNorm(c.rms_norm_eps, name="input_layernorm")(x)
            x = x + RotaryAttention(
                c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                c.dtype, c.attn_impl,
                head_norm_eps=c.rms_norm_eps if c.qk_norm else None,
                block_diffusion=(self.tokens, c.block_length),
                name="self_attn",
            )(h, cos, sin)
        with jax.named_scope("mlp" if self.dense else "moe"):
            h = RMSNorm(c.rms_norm_eps, name="post_attention_layernorm")(x)
            if self.dense:
                return x + GatedMLP(c.intermediate_size, c.dtype, name="mlp")(h)
            return x + SparseMoeBlock(
                c.num_experts, c.num_experts_per_tok, c.moe_intermediate_size,
                0,  # no shared expert: the routed experts and nothing else
                (c.experts_first, c.experts_held), c.norm_topk_prob, c.dtype,
                score="softmax", expert_form="gated_silu",
                balance_alpha=c.balance_alpha, name="mlp",
            )(h)


class _Model(nn.Module):
    """-> the final norm of the NOISED half's stream [B, T, hidden]."""

    cfg: SdarConfig

    @nn.compact
    def __call__(self, tokens, mask):
        c = self.cfg
        t = tokens.shape[-1]
        embedding = self.param(
            "embed_tokens", nn.initializers.normal(0.02),
            (c.vocab_size, c.hidden_size), jnp.float32,
        )
        mask_id = c.mask_token_id % c.vocab_size
        noised = jnp.where(mask, jnp.asarray(mask_id, tokens.dtype), tokens)
        x = embedding[jnp.concatenate([noised, tokens], axis=-1)]
        # the noised and the clean copy of a token share its position
        positions = jnp.concatenate([jnp.arange(t), jnp.arange(t)])
        cos, sin = gqa.rotary_tables(positions, c.head_dim, c.rope_theta)
        layer_cls = nn.remat(
            DecoderLayer, policy=KEEP_ATTENTION_RESULTS
        ) if c.remat else DecoderLayer
        for i in range(c.num_hidden_layers):
            x = layer_cls(c, c.dense(i), t, name=f"layers_{i}")(x, cos, sin)
        with jax.named_scope("lm_head_loss"):
            return RMSNorm(c.rms_norm_eps, name="norm")(x[:, :t])


class SdarLM(nn.Module):
    cfg: SdarConfig

    @nn.compact
    def __call__(self, features, train: bool = False):
        c = self.cfg
        tokens, mask, t = features
        mask = mask.astype(bool)
        x = _Model(c, name="model")(tokens, mask)
        count_noise(self, mask, t)
        with jax.named_scope("lm_head_loss"):
            head = self.param(
                "lm_head", nn.initializers.lecun_normal(),
                (c.hidden_size, c.vocab_size), jnp.float32,
            )
            logits = jnp.dot(
                x.astype(c.dtype), head.astype(c.dtype),
                preferred_element_type=jnp.float32,
            )
            weight = mask.astype(jnp.float32) / t.astype(jnp.float32)[:, None]
            return {"logits": logits, "weight": weight}


def _weighted_cross_entropy(labels, predictions):
    """-> [B, T]: `weight` x the cross-entropy of a position's logits
    against ITS OWN token (0 where the position was not masked)."""
    return predictions["weight"] * (
        optax.softmax_cross_entropy_with_integer_labels(
            predictions["logits"].astype(jnp.float32),
            labels.astype(jnp.int32),
        )
    )


def loss(labels, predictions):
    """(1 / T) sum over a sequence's masked positions of (1 / t) CE, the
    mean over the sequences; labels [B, T] (the clean tokens), predictions
    the module docstring's tree."""
    with jax.named_scope("lm_head_loss"):
        return jnp.mean(_weighted_cross_entropy(labels, predictions))


def eval_metrics_fn():
    """The weighted masked cross-entropy (the loss), and the accuracy
    over the masked positions; `outputs` the prediction's tree as the
    master concatenates it."""
    def masked_accuracy(outputs, labels):
        masked = np.asarray(outputs["weight"]) > 0
        hit = np.argmax(outputs["logits"], axis=-1) == np.asarray(labels)
        return float(np.sum(hit & masked) / max(np.sum(masked), 1))

    return {
        "masked_cross_entropy": lambda outputs, labels: float(loss(
            jnp.asarray(labels), jax.tree.map(jnp.asarray, outputs)
        )),
        "masked_accuracy": masked_accuracy,
    }


# -- the noise, drawn with the record ----------------------------------------

#: The job's `noise_seed` and `t_min`, as `custom_model` last read them:
#: the zoo contract hands `dataset_fn` no model parameter.
_NOISE = {"noise_seed": 0, "t_min": 1e-3}


def record_noise(tokens, noise_seed: int, t_min: float):
    """One record's noise -> (mask [T] bool, t float32): `t` from
    U(t_min, 1], then each position masked with probability `t`, by a
    Philox generator keyed by `noise_seed` and a 64-bit digest of the
    record's tokens, and by nothing else."""
    tokens = np.ascontiguousarray(tokens, np.int32)
    digest = int.from_bytes(
        hashlib.blake2b(tokens.tobytes(), digest_size=8).digest(), "little"
    )
    rng = np.random.Generator(np.random.Philox(
        key=[noise_seed % 2 ** 64, digest]
    ))
    t = np.float32(t_min + (1.0 - t_min) * (1.0 - rng.random()))
    return rng.random(tokens.shape[-1]) < t, t


def dataset_fn(dataset, mode, metadata):
    """A `synthetic://lm` record (tokens, next tokens) -> features
    (tokens, mask, t), the record's noise drawn here (`record_noise`), and
    the label, which is the tokens themselves."""
    def parse(record):
        tokens = np.asarray(record[0], np.int32)
        mask, t = record_noise(tokens, **_NOISE)
        return (tokens, mask, t), tokens

    dataset = dataset.map(parse)
    if mode == "training":
        dataset = dataset.shuffle(1024, seed=0)
    return dataset


def custom_model(use_bf16: bool = True, **config):
    """`config`: the source's `config.json` keys this model reads (see
    `SdarConfig`; `mlp_only_layers` as a sequence or as `a/b/c`), plus
    `block_length`, `mask_token_id`, `noise_per`, `t_min`, `noise_seed`,
    `predict_shift`, `experts_first` / `experts_held` (the range of
    experts this chip holds), `qk_norm`, `balance_alpha`, `attn_impl` and
    `remat` (rematerialise each decoder layer in the backward pass)."""
    unknown = set(config) - set(SdarConfig.__dataclass_fields__)
    if unknown:
        raise ValueError(f"sdar_lm has no parameter(s) {sorted(unknown)}")
    if "mlp_only_layers" in config:
        config["mlp_only_layers"] = listed(config["mlp_only_layers"], int)
    config.setdefault("experts_held", config.get("num_experts", 8))
    cfg = SdarConfig(dtype=jnp.bfloat16 if use_bf16 else jnp.float32, **config)
    if cfg.num_attention_heads % cfg.num_key_value_heads:
        raise ValueError(
            "the query heads are a multiple of num_key_value_heads"
        )
    if cfg.noise_per != "sequence":
        raise ValueError(
            f"noise_per={cfg.noise_per!r}: one noise level a sequence is "
            "what is built (a level a block is BD3-LM's other reading)"
        )
    if cfg.predict_shift:
        raise ValueError(
            "predict_shift: a masked position's own logits predict its own "
            "token here (the shifted reading is not built)"
        )
    if not 0.0 < cfg.t_min < 1.0:
        raise ValueError(f"t_min lies in (0, 1), got {cfg.t_min}")
    _NOISE.update(noise_seed=int(cfg.noise_seed), t_min=float(cfg.t_min))
    return SdarLM(cfg)


def optimizer(lr: float = 3e-4, warmup_steps: int = 2000):
    """AdamW (b1 0.9, b2 0.95, weight decay 0.1) whose rate rises linearly
    to `lr` over the first `warmup_steps` steps and stays, as a
    pre-training job's first steps run.  The source names no optimizer:
    these are the 8k cells' numbers."""
    return warmup_adamw(lr, warmup_steps, b1=0.9, b2=0.95, weight_decay=0.1)
