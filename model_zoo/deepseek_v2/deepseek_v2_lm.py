"""DeepSeek-V2 causal LM: multi-head latent attention (MLA) in every
layer, a leading dense feed-forward layer, then routed expert layers
with shared experts and a balancing loss.

Source: https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite (its
`config.json`, `model_type: deepseek_v2`, and `modeling_deepseek.py`;
the DeepSeek-V2 report, arXiv:2405.04434).  Layer i of the stack is::

    x = x + self_attn(input_layernorm(x))
    x = x + mlp(post_attention_layernorm(x))

`mlp` a gated-SiLU MLP of width `intermediate_size` for the first
`first_k_dense_replace` layers and an expert layer after them; plain
RMSNorm (`y = w x rsqrt(mean(x^2) + eps)`, w from 1), a final norm, an
untied output head, no bias anywhere.

- `self_attn` (`lm_common.LatentAttention`, which Ling's stack shares;
  `q_lora_rank` null: no latent for the queries): `q = q_proj(u)` [H heads of `qk_nope_head_dim` +
  `qk_rope_head_dim`]; `[c | k_pe] = kv_a_proj_with_mqa(u)`
  [`kv_lora_rank` | `qk_rope_head_dim`]; `c = kv_a_layernorm(c)`;
  `[k_nope | v] = kv_b_proj(c)` [H heads of `qk_nope_head_dim` |
  `v_head_dim`].  Rotary turns a head's `q_pe` and the ONE `k_pe` a
  token, which every head shares: `q_h = [q_nope,h | rope(q_pe,h)]`,
  `k_h = [k_nope,h | rope(k_pe)]`.  Causal softmax of `q_h k_h^T s` over
  the two parts' 192 dimensions, times `v_h` of 128: two head sizes in
  one attention (`ops/gqa.causal_attention`); `o_proj`.  Keys and values
  are materialised from the latent, as training computes them; the
  absorbed form (`kv_b_proj` folded into `q_proj` and `o_proj`, a cache
  of latents) is a decoding form and is not built.
- the rotary tables are YaRN's where `rope_scaling_factor` > 1
  (`ops/gqa.yarn_rotary_tables`), and the softmax scale is then
  `s = (nope + rope)^-0.5 m(factor, mscale_all_dim)^2`, m(f, a) =
  0.1 a ln f + 1 (`softmax_scale`).
- the expert layer (`layers/moe.py` `SparseMoeBlock`): `p = softmax(W_r
  u)` over ALL experts, the top k of p (`topk_method: greedy`, one
  group), weights p at the chosen, NOT renormalised (`norm_topk_prob`
  false), times `routed_scaling_factor`; gated-SiLU experts; the
  `n_shared_experts` shared experts are ONE ungated gated-SiLU MLP of
  width `n_shared_experts x moe_intermediate_size`; the layer holds a
  RANGE of the experts (`experts_first`, `experts_held`).  With
  `seq_aux` the sequence-wise balancing loss at `aux_loss_alpha` adds
  its gradient to the router's and stays out of the reported loss, as
  the source's `AddAuxiliaryLoss` (`layers/moe.py`).

Module and parameter names follow the source's: `model` holding
`embed_tokens`, `layers_<i>` (`input_layernorm`, `self_attn` with
`q_proj`, `kv_a_proj_with_mqa`, `kv_a_layernorm`, `kv_b_proj`, `o_proj`;
`post_attention_layernorm`; `mlp` with `gate_proj` / `up_proj` /
`down_proj` in a dense layer and `gate` (the router [hidden, experts]),
`experts_gate_proj` / `experts_up_proj` / `experts_down_proj` (the held
experts, stacked [held, in, out]: the source's `experts.K.*`) and
`shared_experts` in an expert layer) and `norm`; `lm_head`.  Kernels in
flax's [in, out] layout.

Departures from the source, each also in the configuration's `assumed`:
the source de-interleaves each rotary pair before it rotates
(`view(.., d/2, 2).transpose`), a fixed permutation of the 64 rotary
columns of `q_proj` (a head) and of `kv_a_proj_with_mqa`'s last 64; with
seeded weights those columns are stored in the half-split order
`apply_rotary` reads, and a loader of published weights would permute
them.  The residual stream is float32.  `q_lora_rank` other than null,
`topk_method` other than greedy and `seq_aux` false are not built.

Precision: parameters float32; with `use_bf16` the five attention
projections, scores and values, the dense layer, the expert products
and the head take bfloat16 operands and accumulate in float32.  Always
float32: the residual stream, every norm (the latent's too), the rotary
tables, the router (logits at `Precision.HIGHEST`, softmax, top-k, the
balancing loss), the attention softmax's statistics, logits and loss.

Device scopes (obs/tracing.py DEVICE_SCOPES): `attn` (the sublayer with
its norm and residual) > `mla_latent` (the latent's projections, its
norm, the rotary of q and of the shared key, the assembly of q and k),
`mla_core` (the engine: scores, softmax, values); `mlp` (the dense
layer); `moe` > `moe_route`, `moe_experts`, `moe_shared`;
`lm_head_loss`.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.layers.moe import GatedMLP, SparseMoeBlock
from elasticdl_tpu.ops import gqa
# The norm, the projection, the optimizer's warm-up and the rest of the zoo
# contract of any causal LM on `synthetic://lm` data: mean next-token
# cross-entropy over float32 logits (under the `lm_head_loss` scope),
# perplexity and accuracy.
from model_zoo.lm_common import (  # noqa: F401
    KEEP_ATTENTION_RESULTS, VOCAB, LatentAttention, RMSNorm,
    custom_data_reader, dataset_fn, dense, eval_metrics_fn, loss,
    warmup_adamw,
)


def softmax_scale(cfg) -> float:
    """(nope + rope)^-0.5, times m(factor, mscale_all_dim)^2 under YaRN
    with a non-zero `mscale_all_dim`."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_scaling_factor > 1.0 and cfg.rope_scaling_mscale_all_dim:
        scale *= gqa.yarn_mscale(
            cfg.rope_scaling_factor, cfg.rope_scaling_mscale_all_dim
        ) ** 2
    return scale


def _rotary_tables(cfg, t: int):
    positions = jnp.arange(t)
    if cfg.rope_scaling_factor <= 1.0:
        return gqa.rotary_tables(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    return gqa.yarn_rotary_tables(
        positions, cfg.qk_rope_head_dim, cfg.rope_theta,
        factor=cfg.rope_scaling_factor,
        original=cfg.rope_scaling_original_max_position_embeddings,
        beta_fast=cfg.rope_scaling_beta_fast,
        beta_slow=cfg.rope_scaling_beta_slow,
        mscale=cfg.rope_scaling_mscale,
        mscale_all_dim=cfg.rope_scaling_mscale_all_dim,
    )


def latent_attention(cfg, t: int, **module):
    """-> (`lm_common.LatentAttention` at this configuration's sizes and
    softmax scale, with neither head norms nor a gate; its rotary tables
    (cos, sin) for `t` positions)."""
    return LatentAttention(
        cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
        cfg.v_head_dim, cfg.kv_lora_rank, cfg.rms_norm_eps, cfg.dtype,
        softmax_scale(cfg), cfg.attn_impl, **module,
    ), _rotary_tables(cfg, t)


class DecoderLayer(nn.Module):
    cfg: Any     # DeepseekV2Config
    dense: bool  # one of the leading dense layers

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        with jax.named_scope("attn"):
            h = RMSNorm(c.rms_norm_eps, name="input_layernorm")(x)
            with jax.named_scope("mla_latent"):
                attention, tables = latent_attention(
                    c, x.shape[1], name="self_attn"
                )
            x = x + attention(h, *tables)
        with jax.named_scope("mlp" if self.dense else "moe"):
            h = RMSNorm(c.rms_norm_eps, name="post_attention_layernorm")(x)
            if self.dense:
                return x + GatedMLP(c.intermediate_size, c.dtype, name="mlp")(h)
            return x + SparseMoeBlock(
                c.n_routed_experts, c.num_experts_per_tok,
                c.moe_intermediate_size,
                c.n_shared_experts * c.moe_intermediate_size,
                (c.experts_first, c.experts_held), c.norm_topk_prob, c.dtype,
                routed_scale=c.routed_scaling_factor, shared_gated=False,
                balance_alpha=c.aux_loss_alpha, name="mlp",
            )(h)


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    """The source's `config.json` keys this model reads (`rope_scaling`'s
    as `rope_scaling_<key>`: a job's flags are flat), then what this chip
    holds and how it computes."""

    vocab_size: int = VOCAB
    hidden_size: int = 64
    intermediate_size: int = 160
    moe_intermediate_size: int = 32
    num_hidden_layers: int = 3
    first_k_dense_replace: int = 1
    num_attention_heads: int = 2
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    kv_lora_rank: int = 32
    q_lora_rank: Any = None
    rope_theta: float = 10000.0
    rope_scaling_factor: float = 1.0  # 1: plain rotary, no YaRN
    rope_scaling_original_max_position_embeddings: int = 4096
    rope_scaling_beta_fast: float = 32.0
    rope_scaling_beta_slow: float = 1.0
    rope_scaling_mscale: float = 1.0
    rope_scaling_mscale_all_dim: float = 0.0
    n_routed_experts: int = 8
    n_shared_experts: int = 2
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    seq_aux: bool = True
    aux_loss_alpha: float = 0.001
    rms_norm_eps: float = 1e-6
    experts_first: int = 0
    experts_held: int = 8
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    remat: bool = False


class _Model(nn.Module):
    cfg: DeepseekV2Config

    @nn.compact
    def __call__(self, tokens):
        c = self.cfg
        embedding = self.param(
            "embed_tokens", nn.initializers.normal(0.02),
            (c.vocab_size, c.hidden_size), jnp.float32,
        )
        x = embedding[tokens]
        layer_cls = nn.remat(
            DecoderLayer, policy=KEEP_ATTENTION_RESULTS
        ) if c.remat else DecoderLayer
        for i in range(c.num_hidden_layers):
            x = layer_cls(
                c, i < c.first_k_dense_replace, name=f"layers_{i}"
            )(x)
        with jax.named_scope("lm_head_loss"):
            return RMSNorm(c.rms_norm_eps, name="norm")(x)


class DeepseekV2LM(nn.Module):
    cfg: DeepseekV2Config

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
    `DeepseekV2Config`), plus `experts_first` / `experts_held` (the range
    of experts this chip holds), `attn_impl` and `remat` (rematerialise
    each decoder layer in the backward pass)."""
    unknown = set(config) - set(DeepseekV2Config.__dataclass_fields__)
    if unknown:
        raise ValueError(f"deepseek_v2_lm has no parameter(s) {sorted(unknown)}")
    config.setdefault("experts_held", config.get("n_routed_experts", 8))
    cfg = DeepseekV2Config(
        dtype=jnp.bfloat16 if use_bf16 else jnp.float32, **config
    )
    if cfg.q_lora_rank not in (None, 0, "null", "None"):
        raise ValueError("a latent for the queries (q_lora_rank) is not built")
    if not cfg.seq_aux and cfg.aux_loss_alpha:
        raise ValueError("only the sequence-wise balancing loss is built")
    return DeepseekV2LM(cfg)


def optimizer(lr: float = 4.2e-4, warmup_steps: int = 2000):
    """AdamW (b1 0.9, b2 0.95, weight decay 0.1) whose rate rises linearly
    to `lr` over the first `warmup_steps` steps (step n of them runs at
    lr n / warmup_steps) and stays, as a pre-training job's first steps
    run."""
    return warmup_adamw(lr, warmup_steps, b1=0.9, b2=0.95, weight_decay=0.1)
