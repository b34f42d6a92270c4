"""Qwen3-Next causal LM: a hybrid of Gated DeltaNet linear attention and
gated softmax attention, every layer followed by a routed expert layer.

Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct (its
`config.json` and the `Qwen3Next*` modules of `transformers`).  Layer i
(from 0) of the stack is::

    x = x + mixer_i(norm(x))        gated attention where (i + 1) % 4 == 0,
    x = x + moe(norm(x))            else Gated DeltaNet

with zero-centred RMSNorm (`y = x rsqrt(mean(x^2) + eps) (1 + w)`), an
untied output head, no biases anywhere.  Module and parameter names
follow the source's (`embed_tokens`, `layers_<i>` with `input_layernorm`,
`linear_attn` or `self_attn`, `post_attention_layernorm`, `mlp`; `norm`;
`lm_head`), kernels in flax's [in, out] layout:

- `linear_attn.in_proj_qkvz` is grouped by KEY head as the source's
  `fix_query_key_value_ordering` reads it: per key head
  [q (Dk), k (Dk), v (r Dv), z (r Dv)] with r = value heads per key head;
  `in_proj_ba` per key head [b (r), a (r)].  That is the order in the
  state and in a checkpoint; the program multiplies by views of the
  kernel's columns, so that its results are head-major (`_HeadMajorDense`);
- `linear_attn.conv1d` is [width, channels] over the channels [q, k, v];
- `mlp` holds a RANGE of the experts (`experts_first`, `experts_held`) as
  stacked [held, in, out] tensors (`layers/moe.py`); the router `gate`
  stays as wide as published.

Precision: parameters float32; with `use_bf16` the projections, attention
and expert products take bfloat16 operands and accumulate in float32.
Always float32: the residual stream, every norm, the router (logits at
`Precision.HIGHEST`, softmax, top-k), the decay `g`, the delta rule and
its state, the logits and the loss.

Left out: the source's multi-token-prediction module and any auxiliary
load-balancing loss (its config names neither a key nor a coefficient).

Device scopes (obs/tracing.py DEVICE_SCOPES): `gdn` (the DeltaNet
sublayer with its norm and residual) > `gdn_mix` (conv taps, silu and
l2-norm before the rule; the gated norm after it), `gdn_scan` (the rule
alone); `attn`; `moe` >
`moe_route`, `moe_experts`, `moe_shared`; `lm_head_loss`.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.layers.moe import SparseMoeBlock
from elasticdl_tpu.ops import gdn_passes, gqa
from elasticdl_tpu.ops.gated_delta import chunk_gated_delta_rule_rows
# The projection and the rest of the zoo contract of any causal LM on
# `synthetic://lm` data: mean next-token cross-entropy over float32
# logits (under the `lm_head_loss` scope), perplexity and accuracy.
from model_zoo.lm_common import (  # noqa: F401
    KEEP_ATTENTION_RESULTS, VOCAB, custom_data_reader, dataset_fn, dense,
    eval_metrics_fn, loss,
)


class RMSNorm(nn.Module):
    """y = x rsqrt(mean(x^2) + eps) (1 + w), w from 0; float32.  The
    zero-centred norm of this source alone: `lm_common.RMSNorm` is the
    plain one (w from 1) and another function."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        weight = self.param(
            "weight", nn.initializers.zeros_init(), (x.shape[-1],),
            jnp.float32,
        )
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.eps)
        return x * (1.0 + weight)


class _HeadMajorDense(nn.Module):
    """A projection whose `kernel` [in, features] keeps the source's
    column order, per key head a run of each part in turn (`parts`:
    their widths within one key head), and whose RESULTS are head-major:
    one [B, T, groups x width] tensor a part, each the product with a
    view of the kernel's columns (a slice and a reshape of the weight,
    100 MB, where the result's would be a relayout of 800 MB).  Operands
    in `dtype`, float32 results, as `lm_common.dense`."""

    groups: int
    parts: tuple
    dtype: Any

    @nn.compact
    def __call__(self, x):
        d, per_group = x.shape[-1], sum(self.parts)
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (d, self.groups * per_group), jnp.float32,
        ).astype(self.dtype).reshape(d, self.groups, per_group)
        x = x.astype(self.dtype)
        starts = np.cumsum((0,) + tuple(self.parts))
        return [
            jnp.dot(
                x, kernel[:, :, lo:hi].reshape(d, -1),
                preferred_element_type=jnp.float32,
            )
            for lo, hi in zip(starts, starts[1:])
        ]


class GatedDeltaNet(nn.Module):
    """From the projections to the out-projection q, k, v, z and o are
    head-major [B, T, H D] rows, the layout the rule's kernels read and
    write: no tensor of the sequence's size is reshaped, split or
    concatenated on the way (`ops/gdn_passes.py`, `ops/gated_delta.py`)."""

    num_k_heads: int
    num_v_heads: int
    head_k_dim: int
    head_v_dim: int
    conv_kernel: int
    eps: float
    dtype: Any
    mesh: Any = None  # what the program is compiled for: the engines' choice

    @nn.compact
    def __call__(self, x):
        _, t, d = x.shape
        hk, hv, dk, dv = (self.num_k_heads, self.num_v_heads,
                          self.head_k_dim, self.head_v_dim)
        r = hv // hk
        q, k, v, z = _HeadMajorDense(
            hk, (dk, dk, r * dv, r * dv), self.dtype, name="in_proj_qkvz"
        )(x)
        beta_in, a = _HeadMajorDense(
            hk, (r, r), self.dtype, name="in_proj_ba"
        )(x)
        conv = self.param(
            "conv1d", nn.initializers.lecun_normal(),
            (self.conv_kernel, 2 * hk * dk + hv * dv), jnp.float32,
        )
        pallas = gdn_passes.engine(
            t, hk, hv, dk, dv, self.conv_kernel, self.mesh
        ) == "pallas"
        # Causal depthwise convolution over [q | k | v], then silu (the
        # taps accumulated in float32), then the l2-norm of q and k by
        # head: one pass over each.
        mix = partial(gdn_passes.conv_silu, pallas=pallas, mesh=self.mesh)
        with jax.named_scope("gdn_mix"):
            q = mix(q, conv[:, :hk * dk], head=dk, scale=dk ** -0.5)
            k = mix(k, conv[:, hk * dk:2 * hk * dk], head=dk)
            v = mix(v, conv[:, 2 * hk * dk:])
        a_log = self.param(
            "A_log",
            lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, jnp.float32, 1e-3, 16.0)
            ),
            (hv,),
        )
        dt_bias = self.param("dt_bias", nn.initializers.ones_init(), (hv,),
                             jnp.float32)
        beta = jax.nn.sigmoid(beta_in)
        g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
        # Each key head serves r consecutive value heads (the source's
        # repeat_interleave; the rule repeats q and k a group at a time).
        with jax.named_scope("gdn_scan"):
            out, _ = chunk_gated_delta_rule_rows(
                q, k, v, g, beta, hk, mesh=self.mesh
            )
        # Gated RMSNorm per head (w from 1), in float32.
        weight = self.param("norm", nn.initializers.ones_init(), (dv,),
                            jnp.float32)
        with jax.named_scope("gdn_mix"):
            out = gdn_passes.gated_rms_norm(
                out, z, weight, eps=self.eps, dtype=self.dtype,
                pallas=pallas, mesh=self.mesh,
            )
        return dense(d, self.dtype, "out_proj")(out)


class GatedAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    eps: float
    dtype: Any
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        h, hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        q_gate = dense(h * hd * 2, self.dtype, "q_proj")(x)
        q, gate = jnp.split(q_gate.reshape(b, t, h, 2 * hd), 2, axis=-1)
        k = dense(hkv * hd, self.dtype, "k_proj")(x).reshape(b, t, hkv, hd)
        v = dense(hkv * hd, self.dtype, "v_proj")(x).reshape(b, t, hkv, hd)
        q = RMSNorm(self.eps, name="q_norm")(q)
        k = RMSNorm(self.eps, name="k_norm")(k)
        cos, sin = gqa.rotary_tables(
            jnp.arange(t), self.rotary_dim, self.rope_theta
        )
        q = gqa.apply_rotary(q, cos, sin).astype(self.dtype)
        k = gqa.apply_rotary(k, cos, sin).astype(self.dtype)
        out = gqa.causal_attention(
            q, k, v.astype(self.dtype), impl=self.attn_impl
        )
        out = out.astype(jnp.float32) * jax.nn.sigmoid(
            gate.astype(jnp.float32)
        )
        out = out.reshape(b, t, h * hd).astype(self.dtype)
        return dense(d, self.dtype, "o_proj")(out)


class DecoderLayer(nn.Module):
    cfg: Any            # Qwen3NextConfig
    full_attention: bool

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        if self.full_attention:
            with jax.named_scope("attn"):
                h = RMSNorm(c.rms_norm_eps, name="input_layernorm")(x)
                x = x + GatedAttention(
                    c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                    int(c.head_dim * c.partial_rotary_factor), c.rope_theta,
                    c.rms_norm_eps, c.dtype, c.attn_impl, name="self_attn",
                )(h)
        else:
            with jax.named_scope("gdn"):
                h = RMSNorm(c.rms_norm_eps, name="input_layernorm")(x)
                x = x + GatedDeltaNet(
                    c.linear_num_key_heads, c.linear_num_value_heads,
                    c.linear_key_head_dim, c.linear_value_head_dim,
                    c.linear_conv_kernel_dim, c.rms_norm_eps, c.dtype,
                    c.mesh, name="linear_attn",
                )(h)
        with jax.named_scope("moe"):
            h = RMSNorm(c.rms_norm_eps, name="post_attention_layernorm")(x)
            return x + SparseMoeBlock(
                c.num_experts, c.num_experts_per_tok, c.moe_intermediate_size,
                c.shared_expert_intermediate_size,
                (c.experts_first, c.experts_held), c.norm_topk_prob, c.dtype,
                name="mlp",
            )(h)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The source's `config.json` keys this model reads, then what this
    chip holds and how it computes."""

    vocab_size: int = VOCAB
    hidden_size: int = 64
    num_hidden_layers: int = 4
    full_attention_interval: int = 4
    num_attention_heads: int = 2
    num_key_value_heads: int = 1
    head_dim: int = 32
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 2
    linear_num_value_heads: int = 4
    linear_key_head_dim: int = 16
    linear_value_head_dim: int = 16
    linear_conv_kernel_dim: int = 4
    num_experts: int = 8
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 32
    shared_expert_intermediate_size: int = 32
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    experts_first: int = 0
    experts_held: int = 8
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    remat: bool = False
    mesh: Any = None


class Qwen3NextLM(nn.Module):
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, tokens, train: bool = False):
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
                c, (i + 1) % c.full_attention_interval == 0,
                name=f"layers_{i}",
            )(x)
        with jax.named_scope("lm_head_loss"):
            x = RMSNorm(c.rms_norm_eps, name="norm")(x)
            head = self.param(
                "lm_head", nn.initializers.lecun_normal(),
                (c.hidden_size, c.vocab_size), jnp.float32,
            )
            return jnp.dot(
                x.astype(c.dtype), head.astype(c.dtype),
                preferred_element_type=jnp.float32,
            )


def custom_model(use_bf16: bool = True, mesh=None, **config):
    """`config`: the source's `config.json` keys this model reads (see
    `Qwen3NextConfig`), plus `experts_first` / `experts_held` (the range of
    experts this chip holds), `attn_impl` and `remat` (rematerialise each
    decoder layer in the backward pass).  `mesh`: the job's mesh, which
    `ModelSpec.build_model` hands to a model that names it; under a mesh
    of several devices the delta rule's kernels run a data shard's
    sequences a device (`ops/gated_delta.py`)."""
    unknown = set(config) - set(Qwen3NextConfig.__dataclass_fields__)
    if unknown:
        raise ValueError(f"qwen3_next_lm has no parameter(s) {sorted(unknown)}")
    config.setdefault("experts_held", config.get("num_experts", 8))
    return Qwen3NextLM(Qwen3NextConfig(
        dtype=jnp.bfloat16 if use_bf16 else jnp.float32, mesh=mesh, **config
    ))


def optimizer(lr: float = 3e-4):
    return optax.adamw(lr, weight_decay=0.01)
