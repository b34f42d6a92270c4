"""Laguna causal LM: window and full attention in one stack, a head count
a layer type, two rotary tables, a per-head output gate, and routed
expert layers behind a sigmoid router after a leading dense layer.

Source: https://huggingface.co/poolside/Laguna-XS.2 (its `config.json`,
`model_type: laguna`).  Layer i of the stack is::

    x = x + self_attn(input_layernorm(x))
    x = x + mlp(post_attention_layernorm(x))

plain RMSNorm (`y = w x rsqrt(mean(x^2) + eps)`, w from 1), a final norm,
an untied output head, no bias anywhere.  Three lists of the source say
what layer i is, and the stack is built from their first
`num_hidden_layers` entries (the published 40 and a cut of 5 are the
same code):

- `layer_types[i]`: `full_attention` or `sliding_attention`.  A full
  layer's queries read every key up to their own; a sliding layer's the
  last `sliding_window` keys, the query's own among them
  (`ops/gqa.causal_attention(window=)`, which skips what lies outside the
  band).  Rotary goes by the type too (`rope_parameters`, flat here as
  `rope_<type>_<key>` because a job's flags are flat): a full layer
  turns the first `partial_rotary_factor` of each head's columns by
  YaRN's table (`ops/gqa.yarn_rotary_tables`; cos and sin both times
  0.1 ln(`factor`) + 1, which is the source's `attention_factor`; the
  softmax scale stays 1/sqrt(head_dim)), a
  sliding layer all of them by the plain table.  Both tables are
  computed once a forward pass and handed to the layers of their type.
- `num_attention_heads_per_layer[i]`: the query heads (48 in a full
  layer of Laguna-XS.2, 64 in a sliding one) over `num_key_value_heads`
  key-value heads of `head_dim`, so `q_proj`, `g_proj` and `o_proj`
  differ in SHAPE between the types.
- `mlp_layer_types[i]`: `dense` (a gated-SiLU MLP of `intermediate_size`)
  or `sparse` (`layers/moe.py` `SparseMoeBlock`: `s = sigmoid(W_r u)` over
  ALL experts, the top k of `s + b` (b the selection bias, in no weight),
  weights `s` at the chosen over their sum times
  `moe_routed_scaling_factor`; gated-SiLU experts; one ungated gated-SiLU
  shared expert; the layer holds a RANGE of the experts, `experts_first`
  / `experts_held`).

`gating`: a sigmoid gate of the token, one scalar a head, multiplies the
head's output before `o_proj`: `g = sigmoid(u W_g)` [T, H],
`a[:, j, :] *= g[:, j]`; `W_g` (`g_proj`) is a bias-free projection of the
sublayer's normed input.

Module and parameter names: `model` holding `embed_tokens`, `layers_<i>`
(`input_layernorm`, `self_attn` with `q_proj`, `k_proj`, `v_proj`,
`g_proj`, `o_proj`; `post_attention_layernorm`; `mlp` with `gate_proj` /
`up_proj` / `down_proj` in a dense layer and `gate` (`weight`
[hidden, experts] and `e_score_correction_bias`), `experts_gate_proj` /
`experts_up_proj` / `experts_down_proj` (the held experts, stacked
[held, in, out]) and `shared_experts` in a sparse one) and `norm`;
`lm_head`.  Kernels in flax's [in, out] layout.

Assumed where the source's `config.json` is silent, each also in the
configuration's `assumed`: the gate's form (per head, as the sibling
`Laguna-S-2.1` names it); sigmoid scores, renormalised; no query/key
norm; the window holds `sliding_window` keys INCLUDING the query's own;
rotary columns in the half-split order `apply_rotary` reads; the
balancing rule and the warm-up of `optimizer` (`lm_common.balancing_adamw`,
Nemotron-H's too: the same router).  The residual stream is float32.

Precision: parameters float32; with `use_bf16` the four attention
projections, scores and values, the dense layer, the expert products and
the head take bfloat16 operands and accumulate in float32.  Always
float32: the residual stream, every norm, both rotary tables, the
attention softmax's statistics, the gate (its projection at
`Precision.HIGHEST`, its sigmoid and the product with the heads), the
router, logits and loss.

Device scopes (obs/tracing.py DEVICE_SCOPES): `attn` (the sublayer with
its norm and residual) > `attn_full` | `attn_window` (the engine's call),
`attn_gate`; `mlp` (the dense layer); `moe` > `moe_route`, `moe_experts`,
`moe_shared`; `lm_head_loss`.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.layers.moe import GatedMLP, SparseMoeBlock
from elasticdl_tpu.ops import gqa
from elasticdl_tpu.ops.rotary_pack import rotary_pack
# The norm, the projection, and the rest of the zoo contract of any causal
# LM on `synthetic://lm` data: mean next-token cross-entropy over float32
# logits (under the `lm_head_loss` scope), perplexity and accuracy.  The
# optimizer is that of a stack behind the sigmoid router (Nemotron-H's
# too): AdamW under a warm-up, the selection biases moved by the balancing
# rule.
from model_zoo.lm_common import (  # noqa: F401
    KEEP_ATTENTION_RESULTS, VOCAB, RMSNorm, balancing_adamw as optimizer,
    check_listed, custom_data_reader, dataset_fn, dense, eval_metrics_fn,
    listed, loss,
)

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


class Attention(nn.Module):
    cfg: Any       # LagunaConfig
    sliding: bool
    heads: int     # this layer's query heads

    @nn.compact
    def __call__(self, x, cos, sin):
        c = self.cfg
        b, t, d = x.shape
        h, hkv, hd = self.heads, c.num_key_value_heads, c.head_dim
        q, k, v = (
            dense(n * hd, c.dtype, name)(x).reshape(b, t, n, hd)
            for name, n in (("q_proj", h), ("k_proj", hkv), ("v_proj", hkv))
        )
        q, k = (rotary_pack(p, cos, sin, c.dtype) for p in (q, k))
        with jax.named_scope("attn_window" if self.sliding else "attn_full"):
            out = gqa.heads_first(gqa.causal_attention(
                q, k, gqa.heads_first(v.astype(c.dtype)), impl=c.attn_impl,
                window=c.sliding_window if self.sliding else None,
                packed=True,
            ))
        if c.gating:
            with jax.named_scope("attn_gate"):
                w_gate = self.param(
                    "g_proj", nn.initializers.lecun_normal(), (d, h),
                    jnp.float32,
                )
                gate = jax.nn.sigmoid(jnp.dot(
                    x.astype(jnp.float32), w_gate,
                    precision=jax.lax.Precision.HIGHEST,
                ))
                out = out.astype(jnp.float32) * gate[..., None]
        return dense(d, c.dtype, "o_proj")(
            out.reshape(b, t, h * hd).astype(c.dtype)
        )


class DecoderLayer(nn.Module):
    cfg: Any       # LagunaConfig
    sliding: bool
    heads: int
    dense: bool

    @nn.compact
    def __call__(self, x, cos, sin):
        c = self.cfg
        with jax.named_scope("attn"):
            h = RMSNorm(c.rms_norm_eps, name="input_layernorm")(x)
            x = x + Attention(
                c, self.sliding, self.heads, name="self_attn"
            )(h, cos, sin)
        with jax.named_scope("mlp" if self.dense else "moe"):
            h = RMSNorm(c.rms_norm_eps, name="post_attention_layernorm")(x)
            if self.dense:
                return x + GatedMLP(c.intermediate_size, c.dtype, name="mlp")(h)
            return x + SparseMoeBlock(
                c.num_experts, c.num_experts_per_tok, c.moe_intermediate_size,
                c.shared_expert_intermediate_size,
                (c.experts_first, c.experts_held), c.norm_topk_prob, c.dtype,
                score="sigmoid", expert_form="gated_silu",
                routed_scale=c.moe_routed_scaling_factor, shared_gated=False,
                name="mlp",
            )(h)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The source's `config.json` keys this model reads (`rope_parameters`'
    as `rope_<layer type>_<key>`, the three per-layer lists as tuples),
    then what this chip holds and how it computes."""

    vocab_size: int = VOCAB
    hidden_size: int = 64
    intermediate_size: int = 128
    num_hidden_layers: int = 0  # 0: as many as `layer_types` lists
    layer_types: tuple = (FULL, SLIDING)
    mlp_layer_types: tuple = (DENSE, SPARSE)
    num_attention_heads_per_layer: tuple = (6, 8)
    num_key_value_heads: int = 2
    head_dim: int = 16
    sliding_window: int = 8
    rope_full_attention_theta: float = 500000.0
    rope_full_attention_factor: float = 1.0  # 1: plain rotary, no YaRN
    rope_full_attention_original_max_position_embeddings: int = 4096
    rope_full_attention_beta_fast: float = 32.0
    rope_full_attention_beta_slow: float = 1.0
    rope_full_attention_partial_rotary_factor: float = 0.5
    rope_sliding_attention_theta: float = 10000.0
    rope_sliding_attention_partial_rotary_factor: float = 1.0
    gating: bool = True
    num_experts: int = 8
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 32
    shared_expert_intermediate_size: int = 32
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    experts_first: int = 0
    experts_held: int = 8
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    remat: bool = False
    # layer type -> columns of a head its rotary table turns; `custom_model`
    # works them out of `head_dim` and the two `partial_rotary_factor`s
    rotary_columns: tuple = ()


def rotary_tables(cfg: LagunaConfig, t: int) -> dict:
    """layer type -> (cos, sin), each [T, rotary columns of a head].  A
    full layer's are YaRN's where `factor` > 1, cos and sin both times
    0.1 ln(factor) + 1 (the source's `attention_factor`: a test holds the
    published number to it)."""
    positions = jnp.arange(t)
    columns = dict(cfg.rotary_columns)
    if cfg.rope_full_attention_factor <= 1.0:
        full = gqa.rotary_tables(
            positions, columns[FULL], cfg.rope_full_attention_theta
        )
    else:
        full = gqa.yarn_rotary_tables(
            positions, columns[FULL], cfg.rope_full_attention_theta,
            factor=cfg.rope_full_attention_factor,
            original=cfg.rope_full_attention_original_max_position_embeddings,
            beta_fast=cfg.rope_full_attention_beta_fast,
            beta_slow=cfg.rope_full_attention_beta_slow,
        )
    return {
        FULL: full,
        SLIDING: gqa.rotary_tables(
            positions, columns[SLIDING], cfg.rope_sliding_attention_theta,
        ),
    }


class _Model(nn.Module):
    cfg: LagunaConfig

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
                c, kind == SLIDING, c.num_attention_heads_per_layer[i],
                c.mlp_layer_types[i] == DENSE, name=f"layers_{i}",
            )(x, *tables[kind])
        with jax.named_scope("lm_head_loss"):
            return RMSNorm(c.rms_norm_eps, name="norm")(x)


class LagunaLM(nn.Module):
    cfg: LagunaConfig

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
    `LagunaConfig`; a per-layer list as a sequence or as `a/b/c`), plus
    `experts_first` / `experts_held` (the range of experts this chip
    holds), `attn_impl` and `remat` (rematerialise each decoder layer in
    the backward pass).  The stack is the lists' first `num_hidden_layers`
    entries."""
    unknown = set(config) - (
        set(LagunaConfig.__dataclass_fields__) - {"rotary_columns"}
    )
    if unknown:
        raise ValueError(f"laguna_lm has no parameter(s) {sorted(unknown)}")
    for name, cast in (("layer_types", str), ("mlp_layer_types", str),
                       ("num_attention_heads_per_layer", int)):
        if name in config:
            config[name] = listed(config[name], cast)
    config.setdefault("experts_held", config.get("num_experts", 8))
    cfg = LagunaConfig(
        dtype=jnp.bfloat16 if use_bf16 else jnp.float32, **config
    )
    layers = cfg.num_hidden_layers or len(cfg.layer_types)
    cfg = dataclasses.replace(
        cfg, num_hidden_layers=layers, rotary_columns=tuple(
            (kind, int(cfg.head_dim * getattr(
                cfg, f"rope_{kind}_partial_rotary_factor"
            ))) for kind in (FULL, SLIDING)
        ),
    )
    for name, known in (("layer_types", {FULL, SLIDING}),
                        ("mlp_layer_types", {DENSE, SPARSE}),
                        ("num_attention_heads_per_layer", None)):
        check_listed(name, getattr(cfg, name), layers, known)
    if any(h % cfg.num_key_value_heads
           for h in cfg.num_attention_heads_per_layer[:layers]):
        raise ValueError(
            "every layer's query heads are a multiple of num_key_value_heads"
        )
    return LagunaLM(cfg)
