"""Granite 4.0-H causal LM: a block of TWO sublayers under multipliers.
Every layer is a mixer (Mamba-2 or softmax attention, by `layer_types`)
and then a gated-SiLU MLP, each added to the stream at
`residual_multiplier`; the embedding, the attention scores and the logits
carry a multiplier of their own, and the output head is the embedding
table.

Source: https://huggingface.co/ibm-granite/granite-4.0-h-micro (its
`config.json`, `model_type: granitemoehybrid`, and the
`GraniteMoeHybrid*` modules of `transformers`).  With m_e
`embedding_multiplier`, m_a `attention_multiplier`, m_r
`residual_multiplier` and m_l `logits_scaling`::

    x = m_e E[tokens]
    layer i:   x = x + m_r mixer_i(input_layernorm(x))
               x = x + m_r shared_mlp(post_attention_layernorm(x))
    logits = norm(x) E^T / m_l

plain RMSNorm (`y = w x rsqrt(mean(x^2) + eps)`, w from 1), no bias but
the convolution's.  `layer_types[i]` says what mixer layer i has, and the
stack is built from the list's first `num_hidden_layers` entries (the
published 40 and a cut of 10 are the same code):

- `mamba` (`model_zoo/lm_common.py` `Mamba2Mixer`): `[z | xBC |
  dt] = in_proj(u)`, the causal depthwise convolution with its bias and
  silu over `xBC`, `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`, the
  selective state-space recurrence of `ops/ssd.py` in chunks of
  `mamba_chunk_size` with the `mamba_n_heads` heads in `mamba_n_groups`
  groups that share B and C, the skip `D x`, the gated RMSNorm over each
  group of the inner width, `out_proj`.
- `attention` (`model_zoo/lm_common.py` `Attention`):
  grouped-query heads, NO position embedding (`position_embedding_type:
  "nope"`), causal softmax of the scores times m_a, NOT 1/sqrt(head_dim)
  (0.015625 = 1/64 where sqrt(64) would give 1/8).
- `shared_mlp`: `[g | u] = input_linear(h)`, both `shared_intermediate_size`
  wide, in that order (the source chunks the fused output in two and
  activates the first); `output_linear(silu(g) u)`.  `num_local_experts` is
  0 in this model: the family's routed variant adds experts beside the
  shared MLP and is not built here.

THE TIE.  `embed_tokens` is ONE parameter, read by the gather and, as its
transpose, by the head's product: its gradient is the sum of a
scatter-add and a matmul, and the optimizer keeps one pair of moments
for it.

Module and parameter names follow the source's: `model` holding
`embed_tokens`, `layers_<i>` (`input_layernorm`, `mamba` or `self_attn`,
`post_attention_layernorm`, `shared_mlp` with `input_linear` and
`output_linear`) and `norm`; no `lm_head`.  Kernels in flax's [in, out]
layout; the Mamba-2 mixer's and the attention's parameters as
`model_zoo/lm_common.py` names them.

Assumed where the source's `config.json` is silent, each also in the
configuration's `assumed`: the Mamba-2 initialisation is `Mamba2Mixer`'s
(`A_log = log U(1, 16)`, `dt_bias` the inverse softplus of
`dt ~ exp(U(log 1e-3, log 0.1))` floored at 1e-4, `D` 1: the Mamba-2
source's defaults; the `granitemoehybrid` module itself fills
`A_log = log(1..H)`, `dt_bias = 1`, `D = 1`, placeholders for a loaded
checkpoint); no `rescale_prenorm_residual` (`out_proj` at scale 1);
the table normal(0.02), kernels lecun-normal; the residual stream
float32.

Precision: parameters float32; with `use_bf16` the projections, the
state-space form's four products, attention, the MLP's two products and
the head take bfloat16 operands and accumulate in float32.  Always
float32: the residual stream, every norm, `dt`, the decays, the recurrent
state, the attention softmax's statistics, the four multipliers'
products, the logits and the loss; and in a Mamba-2 layer everything
between `in_proj`'s float32 result and `out_proj`'s operand but the scan's
four products: the convolution's taps, silu, the skip, the gate and the
norm over the one group, whose result alone is cast (`ops/gdn_passes.py`,
in its kernels and in its plain chain alike).

Device scopes (obs/tracing.py DEVICE_SCOPES): `ssm` (the Mamba-2 sublayer
with its norm and residual) > `ssm_scan`; `attn`; `mlp` (every MLP
sublayer with its norm and residual); `lm_head_loss` (the final norm, the
tied product and the loss).  `ssm` outside `ssm_scan` is the two
projections and the passes of `ops/gdn_passes.py` (on a TPU the kernels
`conv_silu_fwd|bwd`, twice a layer, and `gated_group_norm_fwd|bwd`; the
worker's log line `gdn passes engine:` says which engine a trace held),
which name no scope of their own.
`ssm_scan` is `ops/ssd.py`'s chunked form alone: on a TPU, with bfloat16
products, the kernel pair `ssd_fwd` / `ssd_bwd`, which reads x and
[B | C] as the rows the convolutions wrote and writes y as the rows the
norm reads, and keeps the decays and the masked scores in VMEM; the XLA
form elsewhere (the log line `ssd engine: pallas|xla ... (why)`).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

# The two mixers are the ones Nemotron-H's stack runs too: the same
# Mamba-2 layer at another shape (one group, chunks of 256) and the same
# attention without a position embedding, under a caller's scale.  With
# them the norm, the projection, the optimizer's warm-up and the rest of
# the zoo contract of any causal LM on `synthetic://lm` data: mean
# next-token cross-entropy over float32 logits (under the `lm_head_loss`
# scope), perplexity and accuracy.
from model_zoo.lm_common import (  # noqa: F401
    KEEP_ATTENTION_RESULTS, VOCAB, Attention, Mamba2Mixer, RMSNorm,
    custom_data_reader, dataset_fn, dense, eval_metrics_fn, loss, warmup_adamw,
)

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The source's `config.json` keys this model reads, then how this
    chip computes."""

    vocab_size: int = VOCAB
    hidden_size: int = 64
    num_hidden_layers: int = 0  # 0: every entry of `layer_types`
    layer_types: tuple = (MAMBA, ATTENTION)
    mamba_n_heads: int = 4
    mamba_d_head: int = 16
    mamba_n_groups: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    shared_intermediate_size: int = 128
    num_local_experts: int = 0
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False
    mesh: Any = None


class SharedMLP(nn.Module):
    """`output_linear(silu(g) u)` with `[g | u] = input_linear(h)`."""

    width: int
    dtype: Any

    @nn.compact
    def __call__(self, h):
        gate, up = jnp.split(
            dense(2 * self.width, self.dtype, "input_linear")(h), 2, axis=-1
        )
        return dense(h.shape[-1], self.dtype, "output_linear")(
            (nn.silu(gate) * up).astype(self.dtype)
        )


class DecoderLayer(nn.Module):
    """The block: the mixer sublayer, then the MLP sublayer, each added to
    the stream at `residual_multiplier`."""

    cfg: GraniteHybridConfig
    kind: str  # an entry of `layer_types`

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        mamba = self.kind == MAMBA
        with jax.named_scope("ssm" if mamba else "attn"):
            mixer = Mamba2Mixer(
                c.mamba_n_heads, c.mamba_d_head, c.mamba_n_groups,
                c.mamba_d_state, c.mamba_d_conv, c.mamba_chunk_size,
                c.rms_norm_eps, c.dtype, mesh=c.mesh, name="mamba",
            ) if mamba else Attention(
                c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                c.dtype, scale=c.attention_multiplier, name="self_attn",
            )
            x = x + c.residual_multiplier * mixer(
                RMSNorm(c.rms_norm_eps, name="input_layernorm")(x)
            )
        with jax.named_scope("mlp"):
            return x + c.residual_multiplier * SharedMLP(
                c.shared_intermediate_size, c.dtype, name="shared_mlp",
            )(RMSNorm(c.rms_norm_eps, name="post_attention_layernorm")(x))


class _Model(nn.Module):
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, tokens):
        """-> (the final norm's output, the table the head reads too)."""
        c = self.cfg
        embedding = self.param(
            "embed_tokens", nn.initializers.normal(0.02),
            (c.vocab_size, c.hidden_size), jnp.float32,
        )
        x = c.embedding_multiplier * embedding[tokens]
        layer_cls = nn.remat(
            DecoderLayer, policy=KEEP_ATTENTION_RESULTS
        ) if c.remat else DecoderLayer
        for i, kind in enumerate(c.layer_types[:c.num_hidden_layers]):
            x = layer_cls(c, kind, name=f"layers_{i}")(x)
        with jax.named_scope("lm_head_loss"):
            return RMSNorm(c.rms_norm_eps, name="norm")(x), embedding


class GraniteHybridLM(nn.Module):
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        c = self.cfg
        x, embedding = _Model(c, name="model")(tokens)
        with jax.named_scope("lm_head_loss"):
            return jnp.dot(
                x.astype(c.dtype), embedding.T.astype(c.dtype),
                preferred_element_type=jnp.float32,
            ) / c.logits_scaling


def custom_model(use_bf16: bool = True, mesh=None, **config):
    """`config`: the source's `config.json` keys this model reads (see
    `GraniteHybridConfig`; `layer_types` as a sequence or, as a job's flat
    flags carry it, `mamba/mamba/attention`), plus `remat` (rematerialise
    each layer in the backward pass).  The stack is the first
    `num_hidden_layers` entries of `layer_types`.  `mesh`: the job's
    mesh, which `ModelSpec.build_model` hands to a model that names it;
    under a mesh of several devices the Mamba-2 layers' passes run a data
    shard's sequences a device (`ops/gdn_passes.py`)."""
    unknown = set(config) - set(GraniteHybridConfig.__dataclass_fields__)
    if unknown:
        raise ValueError(
            f"granite_hybrid_lm has no parameter(s) {sorted(unknown)}"
        )
    kinds = config.get("layer_types", GraniteHybridConfig.layer_types)
    config["layer_types"] = tuple(
        kinds.split("/") if isinstance(kinds, str) else kinds
    )
    cfg = GraniteHybridConfig(
        dtype=jnp.bfloat16 if use_bf16 else jnp.float32, mesh=mesh, **config
    )
    if cfg.num_local_experts:
        raise ValueError(
            f"num_local_experts={cfg.num_local_experts}: the routed variant "
            "of the granitemoehybrid family (experts beside the shared MLP) "
            "is not built; this stack runs the dense one (0)"
        )
    layers = cfg.num_hidden_layers or len(cfg.layer_types)
    if not 0 < layers <= len(cfg.layer_types):
        raise ValueError(
            f"layer_types lists {len(cfg.layer_types)} layers of {layers}"
        )
    if set(cfg.layer_types[:layers]) - {MAMBA, ATTENTION}:
        raise ValueError(
            f"layer_types {cfg.layer_types!r} is not made of {MAMBA!r} and "
            f"{ATTENTION!r}"
        )
    return GraniteHybridLM(dataclasses.replace(cfg, num_hidden_layers=layers))


def optimizer(lr: float = 3e-4, warmup_steps: int = 2000):
    """AdamW whose rate rises linearly to `lr` over the first
    `warmup_steps` steps (step n of them runs at lr n / warmup_steps) and
    stays, as a pre-training job's first steps run and as the zoo's other
    8k stacks do; weight decay 0.01.  No router, so no balancing rule."""
    return warmup_adamw(lr, warmup_steps, weight_decay=0.01)
