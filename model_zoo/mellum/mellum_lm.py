"""Mellum 2 causal LM: three sliding-window attention layers to one full
one, a norm on every query and key head, two rotary tables over the whole
head, and in EVERY layer an expert sublayer that is its routed experts
and nothing else, behind a softmax router renormalised over its top k.

Source: https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct (its
`config.json`, `model_type: mellum`).  Layer i of the stack is::

    x = x + self_attn(input_layernorm(x))
    x = x + mlp(post_attention_layernorm(x))

plain RMSNorm (`y = w x rsqrt(mean(x^2) + eps)`, w from 1), a final norm,
an untied output head, no bias anywhere.  Two lists of the source say what
layer i is, and the stack is built from their first `num_hidden_layers`
entries (the published 28 and a cut of 4 are the same code):

- `layer_types[i]`: `sliding_attention` or `full_attention`, the same
  `num_attention_heads` query heads over `num_key_value_heads` key-value
  heads of `head_dim` in both (32 x 128 = 4096 is not the hidden size
  2304: `head_dim` is a key of its own).  A full layer's queries read
  every key up to their own; a sliding layer's the last `sliding_window`
  keys, the query's own among them (`ops/gqa.causal_attention(window=)`,
  which skips what lies outside the band).  Rotary goes by the type too
  (`rope_parameters`, flat here as `rope_<type>_<key>` because a job's
  flags are flat) and turns ALL of a head's columns in both: a full layer
  by YaRN's table (`ops/gqa.yarn_rotary_tables`; cos and sin both times
  0.1 ln(`factor`) + 1, the source's `attention_factor`; the softmax scale
  stays 1/sqrt(head_dim)), a sliding layer by the plain table.  Both
  tables are computed once a forward pass and handed to the layers of
  their type.
- `mlp_layer_types[i]`: `sparse` in every published layer
  (`layers/moe.py` `SparseMoeBlock`: `p = softmax(W_r u)` over ALL
  experts, the top k of `p`, weights `p` at the chosen over their sum
  (`norm_topk_prob`), gated-SiLU experts, NO shared expert; the layer
  holds a RANGE of the experts, `experts_first` / `experts_held`, and a
  token none of whose choices is held here leaves the sublayer as it
  entered), or `dense` (a gated-SiLU MLP of `intermediate_size`: the key
  exists, the published lists never use it).

`qk_norm`: an RMSNorm over each head's `head_dim` columns of q and of k,
one weight vector each (`q_norm`, `k_norm`), before rotary.  `balance_alpha`:
the router's sequence-wise balancing loss, its gradient injected
(`layers/moe.py`).

Module and parameter names: `model` holding `embed_tokens`, `layers_<i>`
(`input_layernorm`, `self_attn` with `q_proj`, `k_proj`, `v_proj`,
`q_norm`, `k_norm`, `o_proj`; `post_attention_layernorm`; `mlp` with
`gate` [hidden, experts] and `experts_gate_proj` / `experts_up_proj` /
`experts_down_proj` (the held experts, stacked [held, in, out]) in a
sparse layer, `gate_proj` / `up_proj` / `down_proj` in a dense one) and
`norm`; `lm_head`.  Kernels in flax's [in, out] layout.

Assumed where the source's `config.json` is silent, each also in the
configuration's `assumed`: the head norms (no key names them; the keys the
row does have are the Qwen3-MoE configuration's, whose attention norms
every query and key head); softmax scores over all experts, the top k
renormalised, scale 1, weights on the experts' outputs; the balancing
loss's form and coefficient; the window holds `sliding_window` keys
INCLUDING the query's own; rotary columns in the half-split order
`apply_rotary` reads; the warm-up and numbers of `optimizer`.  The
residual stream is float32.  No multi-token-prediction head is built (the
`config` has no key for one).

Precision: parameters float32; with `use_bf16` the four attention
projections, scores and values, the expert products (a dense layer's too)
and the head take bfloat16 operands and accumulate in float32.  Always
float32: the residual stream, every norm (the heads' too), both rotary
tables, the attention softmax's statistics, the router (logits at
`Precision.HIGHEST`, softmax, top-k, the balancing loss), logits and loss.

`attn_impl` is handed to `ops/gqa.causal_attention` as it is, and its
default `auto` is the SLOWER engine for a full layer at this model's
published shape: at 8 query heads a key-value head `auto` takes the Pallas
kernels, which repeat K and V 8 times, 51.35 ms a layer forward and
backward at 2 x 8192 tokens against 35.10 ms in the XLA block engine (a
v5e, PR 42), so the cell's job says `attn_impl=xla`, as Laguna's does at 6
query heads a key-value head.  The rule belongs in `causal_attention`, by
the group size it sees, in a PR that measures every cell whose stack calls
it (`PERF.md` section 7); a sliding layer runs the XLA band whatever the
field says short of `pallas`, which raises.

Device scopes (obs/tracing.py DEVICE_SCOPES): `attn` (the sublayer with
its norm and residual) > `attn_proj` (the four projections: entered for
q, k and v and again for `o_proj`), `attn_rotary` (the head norms and both
rotations), `attn_full` | `attn_window` (the engine's call); `moe` >
`moe_route`, `moe_experts` (or `mlp`, a dense layer); `lm_head_loss`.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.layers.moe import GatedMLP, SparseMoeBlock
from elasticdl_tpu.ops import gqa
# The norm, the attention behind rotary positions, the optimizer's warm-up
# and the rest of the zoo contract of any causal LM on `synthetic://lm` data: mean next-token
# cross-entropy over float32 logits (under the `lm_head_loss` scope),
# perplexity and accuracy.
from model_zoo.lm_common import (  # noqa: F401
    KEEP_ATTENTION_RESULTS, VOCAB, RMSNorm, RotaryAttention, check_listed,
    custom_data_reader, dataset_fn, eval_metrics_fn, listed, loss,
    warmup_adamw,
)

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


def Attention(cfg, sliding: bool, **module):
    """`lm_common.RotaryAttention` at this model's shapes: a norm on every
    query and key head where `qk_norm`, a sliding layer's band."""
    return RotaryAttention(
        cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
        cfg.dtype, cfg.attn_impl,
        cfg.sliding_window if sliding else None,
        cfg.rms_norm_eps if cfg.qk_norm else None, **module,
    )


class DecoderLayer(nn.Module):
    cfg: Any       # MellumConfig
    sliding: bool
    dense: bool

    @nn.compact
    def __call__(self, x, cos, sin):
        c = self.cfg
        with jax.named_scope("attn"):
            h = RMSNorm(c.rms_norm_eps, name="input_layernorm")(x)
            x = x + Attention(c, self.sliding, name="self_attn")(h, cos, sin)
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


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """The source's `config.json` keys this model reads (`rope_parameters`'
    as `rope_<layer type>_<key>`, the two per-layer lists as tuples), then
    what this chip holds and how it computes."""

    vocab_size: int = VOCAB
    hidden_size: int = 64
    intermediate_size: int = 128
    num_hidden_layers: int = 0  # 0: as many as `layer_types` lists
    layer_types: tuple = (SLIDING, SLIDING, SLIDING, FULL)
    mlp_layer_types: tuple = (SPARSE, SPARSE, SPARSE, SPARSE)
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 16
    sliding_window: int = 8
    rope_full_attention_theta: float = 500000.0
    rope_full_attention_factor: float = 1.0  # 1: plain rotary, no YaRN
    rope_full_attention_original_max_position_embeddings: int = 8192
    rope_full_attention_beta_fast: float = 32.0
    rope_full_attention_beta_slow: float = 1.0
    rope_sliding_attention_theta: float = 500000.0
    num_experts: int = 8
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 32
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    experts_first: int = 0
    experts_held: int = 8
    qk_norm: bool = True
    balance_alpha: float = 0.001
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    remat: bool = False


def rotary_tables(cfg: MellumConfig, t: int) -> dict:
    """layer type -> (cos, sin), each [T, head_dim].  A full layer's are
    YaRN's where `factor` > 1, cos and sin both times 0.1 ln(factor) + 1
    (the source's `attention_factor`: a test holds the published number to
    it); a sliding layer's are the plain table."""
    positions = jnp.arange(t)
    if cfg.rope_full_attention_factor <= 1.0:
        full = gqa.rotary_tables(
            positions, cfg.head_dim, cfg.rope_full_attention_theta
        )
    else:
        full = gqa.yarn_rotary_tables(
            positions, cfg.head_dim, cfg.rope_full_attention_theta,
            factor=cfg.rope_full_attention_factor,
            original=cfg.rope_full_attention_original_max_position_embeddings,
            beta_fast=cfg.rope_full_attention_beta_fast,
            beta_slow=cfg.rope_full_attention_beta_slow,
        )
    return {
        FULL: full,
        SLIDING: gqa.rotary_tables(
            positions, cfg.head_dim, cfg.rope_sliding_attention_theta
        ),
    }


class _Model(nn.Module):
    cfg: MellumConfig

    @nn.compact
    def __call__(self, tokens):
        c = self.cfg
        embedding = self.param(
            "embed_tokens", nn.initializers.normal(0.02),
            (c.vocab_size, c.hidden_size), jnp.float32,
        )
        x = embedding[tokens]
        tables = rotary_tables(c, tokens.shape[-1])
        layer_cls = nn.remat(
            DecoderLayer, policy=KEEP_ATTENTION_RESULTS
        ) if c.remat else DecoderLayer
        for i in range(c.num_hidden_layers):
            kind = c.layer_types[i]
            x = layer_cls(
                c, kind == SLIDING, c.mlp_layer_types[i] == DENSE,
                name=f"layers_{i}",
            )(x, *tables[kind])
        with jax.named_scope("lm_head_loss"):
            return RMSNorm(c.rms_norm_eps, name="norm")(x)


class MellumLM(nn.Module):
    cfg: MellumConfig

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        c = self.cfg
        x = _Model(c, name="model")(tokens)
        with jax.named_scope("lm_head_loss"):
            head = self.param(
                "lm_head", nn.initializers.lecun_normal(),
                (c.hidden_size, c.vocab_size), jnp.float32,
            )
            return jnp.dot(
                x.astype(c.dtype), head.astype(c.dtype),
                preferred_element_type=jnp.float32,
            )


def custom_model(use_bf16: bool = True, **config):
    """`config`: the source's `config.json` keys this model reads (see
    `MellumConfig`; a per-layer list as a sequence or as `a/b/c`), plus
    `experts_first` / `experts_held` (the range of experts this chip
    holds), `qk_norm`, `balance_alpha`, `attn_impl` and `remat`
    (rematerialise each decoder layer in the backward pass).  The stack is
    the lists' first `num_hidden_layers` entries."""
    unknown = set(config) - set(MellumConfig.__dataclass_fields__)
    if unknown:
        raise ValueError(f"mellum_lm has no parameter(s) {sorted(unknown)}")
    for name in ("layer_types", "mlp_layer_types"):
        if name in config:
            config[name] = listed(config[name])
    config.setdefault("experts_held", config.get("num_experts", 8))
    cfg = MellumConfig(
        dtype=jnp.bfloat16 if use_bf16 else jnp.float32, **config
    )
    layers = cfg.num_hidden_layers or len(cfg.layer_types)
    cfg = dataclasses.replace(cfg, num_hidden_layers=layers)
    for name, known in (("layer_types", {FULL, SLIDING}),
                        ("mlp_layer_types", {DENSE, SPARSE})):
        check_listed(name, getattr(cfg, name), layers, known)
    if cfg.num_attention_heads % cfg.num_key_value_heads:
        raise ValueError(
            "the query heads are a multiple of num_key_value_heads"
        )
    return MellumLM(cfg)


def optimizer(lr: float = 4.2e-4, warmup_steps: int = 2000):
    """AdamW (b1 0.9, b2 0.95, weight decay 0.1) whose rate rises linearly
    to `lr` over the first `warmup_steps` steps (step n of them runs at
    lr n / warmup_steps) and stays, as a pre-training job's first steps
    run.  The source names no optimizer: these are the numbers of the
    zoo's other stack behind a softmax router with the balancing loss."""
    return warmup_adamw(lr, warmup_steps, b1=0.9, b2=0.95, weight_decay=0.1)
