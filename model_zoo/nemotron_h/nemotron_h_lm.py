"""Nemotron-H causal LM: a stack in which every layer is ONE mixer, a
Mamba-2 state-space layer, a routed expert layer or softmax attention,
by the letters of a pattern string.

Source: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
(its `config.json`, `model_type: nemotron_h`, and the `NemotronH*`
modules of `transformers`).  Layer i of the stack is::

    x = x + mixer_i(norm(x))        'M' Mamba-2, 'E' experts, '*' attention

by `hybrid_override_pattern[i]`, with plain RMSNorm
(`y = w x rsqrt(mean(x^2) + eps)`, w from 1), a final norm, an untied
output head and no bias but the convolution's.

- 'M' (`model_zoo/lm_common.py` `Mamba2Mixer`): `[z | xBC | dt] =
  in_proj(u)`; `xBC` through a causal depthwise convolution of `conv_kernel` taps with a bias, then
  silu, split into x [H heads of P], B and C [G groups of N];
  `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)` a scalar a head; the
  selective state-space recurrence of `ops/ssd.py` (heads of group g
  share B and C), plus the skip `D x`; `y = GroupRMSNorm(y silu(z))` over
  G groups with a weight; `out_proj`.
- 'E' (`layers/moe.py` `SparseMoeBlock` with sigmoid scores): scores
  `sigmoid(W_r u)` over ALL experts, the top k of scores + a selection
  bias, weights the scores at the chosen (the bias is not in them),
  renormalised and scaled by `routed_scaling_factor`; experts and the
  ungated shared expert are `down(relu(up u)^2)`.  The layer holds a
  RANGE of the experts (`experts_first`, `experts_held`).
- '*' (`model_zoo/lm_common.py` `Attention`): q, k, v without bias,
  grouped-query heads, NO position embedding (the Nemotron-H report, arXiv:2504.03624: the
  Mamba layers carry position; `rope_theta` in the config is unused by
  `nemotron_h`), causal softmax at 1/sqrt(head_dim), `o_proj`.

Module and parameter names follow the source's: `backbone` holding
`embeddings`, `layers_<i>` (each `norm` and `mixer`) and `norm_f`;
`lm_head`.  Kernels in flax's [in, out] layout.  The mixer of an 'M'
layer: `in_proj`, `conv1d` (`kernel` [taps, channels of x | B | C],
`bias`), `A_log`, `D`, `dt_bias`, `norm` [H P], `out_proj`; of an 'E'
layer: `gate` (`weight` [hidden, experts], `e_score_correction_bias`),
`experts_up_proj` / `experts_down_proj` (the held experts, stacked
[held, in, out]: the source's `experts.K.up_proj/down_proj`),
`shared_experts` (`up_proj`, `down_proj`); of a '*' layer: `q_proj`,
`k_proj`, `v_proj`, `o_proj`.

Precision: parameters float32; with `use_bf16` the projections, the
state-space form's four products, attention and the expert products take
bfloat16 operands and accumulate in float32.  Always float32: the
residual stream, every norm, the router (logits at `Precision.HIGHEST`,
sigmoid, top-k), `dt`, the decays `exp(dt A)`, the recurrent state, the
logits and the loss; and in a Mamba-2 layer everything between `in_proj`'s
float32 result and `out_proj`'s operand but the scan's four products: the
convolution's taps, silu, the skip, the gate and the group norm, whose
result alone is cast (`ops/gdn_passes.py`, in its kernels and in its plain
chain alike).

Seeded initialisation, as the source's: `A_log = log U(1, 16)`,
`dt_bias` the inverse softplus of `dt ~ exp(U(log time_step_min,
log time_step_max))` floored at `time_step_floor`, `D` and the norms 1,
the Mamba `out_proj` scaled by 1/sqrt(`num_hidden_layers`)
(`rescale_prenorm_residual`: the depth of the MODEL, 52 as published,
whatever part of the pattern this chip holds); `e_score_correction_bias`
from 0.

Training (`optimizer`, `model_zoo/lm_common.py` `balancing_adamw`, which
Laguna's stack behind the same router takes too): AdamW under a linear
warm-up, and for the routers' `e_score_correction_bias` alone the
balancing rule in its place: the bias takes part in no gradient (a selection is not differentiated),
`layers/moe.py` hands it `sign(times chosen - mean)` over all experts
instead, and plain descent at `bias_update_rate` on that is
auxiliary-loss-free balancing (arXiv:2408.15664), the rule of the router
this `gate` is taken from.

Device scopes (obs/tracing.py DEVICE_SCOPES): `ssm` (the Mamba-2
sublayer with its norm and residual) > `ssm_scan`; `attn`; `moe` >
`moe_route`, `moe_experts`, `moe_shared`; `lm_head_loss`.  `ssm` outside
`ssm_scan` is the two projections and the passes of `ops/gdn_passes.py`
(on a TPU the kernels `conv_silu_fwd|bwd`, twice a layer, and
`gated_group_norm_fwd|bwd`; the worker's log line `gdn passes engine:`
says which engine a trace held), which name no scope of their own.
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

from elasticdl_tpu.layers.moe import SparseMoeBlock
# The two mixers another stack runs too, the norm, and the rest of the zoo
# contract of any causal LM on `synthetic://lm` data: mean next-token
# cross-entropy over float32 logits (under the `lm_head_loss` scope),
# perplexity and accuracy.
from model_zoo.lm_common import (  # noqa: F401
    KEEP_ATTENTION_RESULTS, SELECTION_BIAS, VOCAB, Attention, Mamba2Mixer,
    RMSNorm, balancing_adamw, custom_data_reader, dataset_fn, eval_metrics_fn,
    loss,
)

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


class NemotronHLayer(nn.Module):
    cfg: Any    # NemotronHConfig
    kind: str   # one letter of the pattern

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        scope = {MAMBA: "ssm", EXPERTS: "moe", ATTENTION: "attn"}[self.kind]
        with jax.named_scope(scope):
            h = RMSNorm(c.layer_norm_epsilon, name="norm")(x)
            if self.kind == MAMBA:
                mixer = Mamba2Mixer(
                    c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                    c.ssm_state_size, c.conv_kernel, c.chunk_size,
                    c.layer_norm_epsilon, c.dtype,
                    (c.time_step_min, c.time_step_max, c.time_step_floor),
                    (c.num_hidden_layers or len(c.hybrid_override_pattern))
                    ** -0.5 if c.rescale_prenorm_residual else 1.0,
                    c.mesh, name="mixer",
                )
            elif self.kind == EXPERTS:
                mixer = SparseMoeBlock(
                    c.n_routed_experts, c.num_experts_per_tok,
                    c.moe_intermediate_size,
                    c.moe_shared_expert_intermediate_size,
                    (c.experts_first, c.experts_held), c.norm_topk_prob,
                    c.dtype, score="sigmoid", expert_form="relu2",
                    routed_scale=c.routed_scaling_factor, name="mixer",
                )
            else:
                mixer = Attention(
                    c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                    c.dtype, name="mixer",
                )
            return x + mixer(h)


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The source's `config.json` keys this model reads, then what this
    chip holds and how it computes."""

    vocab_size: int = VOCAB
    hidden_size: int = 64
    hybrid_override_pattern: str = "MEMEM*EME"
    # The depth of the whole model, which `rescale_prenorm_residual`
    # divides by; 0: the pattern held IS the model.
    num_hidden_layers: int = 0
    mamba_num_heads: int = 4
    mamba_head_dim: int = 16
    n_groups: int = 2
    ssm_state_size: int = 16
    conv_kernel: int = 4
    chunk_size: int = 128
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    n_routed_experts: int = 8
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 32
    moe_shared_expert_intermediate_size: int = 64
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    layer_norm_epsilon: float = 1e-5
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    rescale_prenorm_residual: bool = True
    experts_first: int = 0
    experts_held: int = 8
    dtype: Any = jnp.bfloat16
    remat: bool = False
    mesh: Any = None


class _Backbone(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, tokens):
        c = self.cfg
        embedding = self.param(
            "embeddings", nn.initializers.normal(0.02),
            (c.vocab_size, c.hidden_size), jnp.float32,
        )
        x = embedding[tokens]
        layer_cls = nn.remat(
            NemotronHLayer, policy=KEEP_ATTENTION_RESULTS
        ) if c.remat else NemotronHLayer
        for i, kind in enumerate(c.hybrid_override_pattern):
            x = layer_cls(c, kind, name=f"layers_{i}")(x)
        with jax.named_scope("lm_head_loss"):
            return RMSNorm(c.layer_norm_epsilon, name="norm_f")(x)


class NemotronHLM(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        c = self.cfg
        x = _Backbone(c, name="backbone")(tokens)
        with jax.named_scope("lm_head_loss"):
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
    `NemotronHConfig`), plus `experts_first` / `experts_held` (the range of
    experts this chip holds) and `remat` (rematerialise each layer in the
    backward pass).  `mesh`: the job's mesh, which `ModelSpec.build_model`
    hands to a model that names it; under a mesh of several devices the
    Mamba-2 layers' passes run a data shard's sequences a device
    (`ops/gdn_passes.py`)."""
    unknown = set(config) - set(NemotronHConfig.__dataclass_fields__)
    if unknown:
        raise ValueError(f"nemotron_h_lm has no parameter(s) {sorted(unknown)}")
    config.setdefault("experts_held", config.get("n_routed_experts", 8))
    cfg = NemotronHConfig(
        dtype=jnp.bfloat16 if use_bf16 else jnp.float32, mesh=mesh, **config
    )
    letters = set(cfg.hybrid_override_pattern)
    if not letters or letters - {MAMBA, EXPERTS, ATTENTION}:
        raise ValueError(
            f"hybrid_override_pattern {cfg.hybrid_override_pattern!r} is not "
            f"made of {MAMBA!r}, {EXPERTS!r} and {ATTENTION!r}"
        )
    return NemotronHLM(cfg)


# AdamW under a linear warm-up, the routers' selection biases moved by the
# balancing rule instead (module docstring): `lr`, `warmup_steps`,
# `bias_update_rate`.
optimizer = balancing_adamw
