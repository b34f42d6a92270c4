"""Ling-3.0-flash's language model: a hybrid of Kimi Delta Attention (a
delta rule whose decay is one rate a KEY CHANNEL, bounded below) and
multi-head latent attention, five of the first to one of the second, a
dense feed-forward layer in the leading layers and, behind them, expert
layers under a 512-way sigmoid router that picks its GROUPS first.

Source: https://huggingface.co/inclusionAI/Ling-3.0-flash-VL (its
`config.json`: the language model's keys; the linear-attention layer is
Kimi Delta Attention, arXiv:2510.26692, under this config's keys).  A
STAGE of the stack: the `num_hidden_layers` layers from the published
index `first_layer` on.  Layer i (its PUBLISHED index) is::

    x = x + mixer_i(input_layernorm(x))      latent attention where
    x = x + mlp_i(post_attention_layernorm(x))   (i + 1) % layer_group_size
                                                 == 0, else delta attention

`mlp_i` a gated-SiLU MLP of `intermediate_size` for i <
`first_k_dense_replace` and an expert layer after them; plain RMSNorm (w
from 1), a final norm, an untied head, no bias anywhere.

- `linear_attn` (`KimiDeltaAttention`; H heads, Dk = Dv = `head_dim`):
  `q, k, v = silu(conv(x q_proj)), ...` (causal depthwise convolutions of
  `short_conv_kernel_size` taps, `q_conv1d` / `k_conv1d` / `v_conv1d`
  [taps, H D]); q and k l2-normalised by head, q times Dk^-1/2;
  `beta = sigmoid(x b_proj)` [H];
  `g = kda_lower_bound sigmoid(exp(A_log)[h] (x f_proj + dt_bias))`
  [H, Dk], in (`kda_lower_bound`, 0): the SAFE gate (`kda_safe_gate`;
  `f_proj` at full rank, `no_kda_lora`).  Per head, S [Dk, Dv] from 0::

      S <- diag(exp(g_t)) S;  d_t = beta_t (v_t - S^T k_t)
      S <- S + k_t d_t^T;     o_t = S^T q_t

  (`ops/gated_delta.py`, a decay [B, T, H, Dk]: the XLA engine in
  sub-chunks).  Then `y = o_norm (o / rms_head(o)) sigmoid(x g_proj)[h]`
  (`group_norm_size` 1: a head; ONE gate value a head,
  `gated_attention_proj_granularity_type: head_wise`) and `o_proj`.  No
  rotary.
- `self_attn` (`lm_common.LatentAttention`, DeepSeek-V2's, at
  `num_attention_heads` heads): no latent for the queries (`q_lora_rank`
  null), rotary over `rotary_dim` = `qk_rope_head_dim` columns at
  `rope_theta`, no scaling; with `use_qk_norm` an RMSNorm of each head's
  assembled q and k before the rotary; the heads' outputs times
  `sigmoid(x g_proj)[h]`.
- the expert layer (`layers/moe.py` `SparseMoeBlock`): sigmoid scores
  plus a selection bias, the `n_group` groups' scores (the sum of a
  group's two largest), the `topk_group` best groups, the top k inside
  them; weights the scores of the chosen, renormalised, times
  `routed_scaling_factor`; gated-SiLU experts; one ungated shared
  expert; the layer holds a RANGE of the experts (`experts_first`,
  `experts_held`).

Refused BY NAME, not guessed: a vision tower (`vision_config`, the
patch and start token ids), a multi-token-prediction head
(`num_nextn_predict_layers`, any `mtp_*` key that is set), a non-zero
entry of `expert_swiglu_limit_list` / `share_expert_swiglu_limit_list`
for a layer of this stage (a clamp inside the gated unit whose form the
config does not give), the other reading of the decay's gate
(`kda_safe_gate` false), a low-rank decay projection, a latent for the
queries, any other score function.

Precision: parameters float32; with `use_bf16` the projections, the
attention's scores and values, the dense layer, the expert products and
the head take bfloat16 operands and accumulate in float32.  Always
float32: the residual stream, every norm, the convolutions, the decay's
gate, beta, the delta rule and its state (`Precision.HIGH`), the output
gates and the router (their projections at `Precision.HIGHEST`), the
rotary tables, the softmax's statistics, logits and loss.

Device scopes (obs/tracing.py DEVICE_SCOPES): `kda` (the delta-attention
sublayer with its norm and residual) > `kda_mix` (convolutions, l2-norms,
the gated norm), `kda_gate` (the decay's projection and gate),
`kda_scan` (the rule alone); `attn` > `mla_latent`, `mla_core`,
`attn_gate`; `mlp`; `moe` > `moe_route`, `moe_experts`, `moe_shared`;
`lm_head_loss`.  Counters: `moe.routing` and `kda.gates`
(`layers/delta_gates.py`).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.layers.delta_gates import count_gates
from elasticdl_tpu.layers.moe import GatedMLP, SparseMoeBlock
from elasticdl_tpu.ops import gdn_passes, gqa
from elasticdl_tpu.ops.gated_delta import chunk_gated_delta_rule_rows
# The norm, the projection, latent attention and the rest of the zoo
# contract of any causal LM on `synthetic://lm` data.  The optimizer is
# that of a stack behind the sigmoid router (Nemotron-H's, Laguna's):
# AdamW under a warm-up, the selection biases moved by the balancing rule.
from model_zoo.lm_common import (  # noqa: F401
    KEEP_ATTENTION_RESULTS, VOCAB, LatentAttention, RMSNorm, _dt_bias_init,
    balancing_adamw as optimizer, custom_data_reader, dataset_fn, dense,
    eval_metrics_fn, listed, loss,
)

#: Keys of the source's `config.json` that belong to what is NOT built.
TOWER_KEYS = ("vision_config", "image_patch_token", "video_patch_token",
              "image_start_token", "video_start_token")
MTP_KEYS = ("num_nextn_predict_layers", "mtp_use_kda", "mtp_loss_scaling_factor")
SWIGLU_LIMITS = ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list")


def _a_log_init(key, shape):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


class KimiDeltaAttention(nn.Module):
    """From the projections to the out-projection q, k, v, g and o are
    head-major [B, T, H D] rows (`ops/gdn_passes.py`), as Qwen3-Next's
    DeltaNet layer holds them.  Seeded by the family's convention
    (`fla`'s): `A_log = log U(1, 16)` a head, `dt_bias` the inverse
    softplus of `dt ~ exp(U(log 1e-3, log 0.1))` a channel, which under
    the safe gate starts every channel near full retention."""

    num_heads: int
    head_dim: int
    conv_kernel: int
    lower_bound: float
    eps: float
    dtype: Any
    mesh: Any = None  # what the program is compiled for: the engines' choice

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        h, dk = self.num_heads, self.head_dim
        width = h * dk
        pallas = gdn_passes.engine(
            t, h, h, dk, dk, self.conv_kernel, self.mesh
        ) == "pallas"
        mix = partial(gdn_passes.conv_silu, pallas=pallas, mesh=self.mesh)
        with jax.named_scope("kda_mix"):
            q, k, v = (
                mix(
                    dense(width, self.dtype, f"{name}_proj")(x),
                    self.param(
                        f"{name}_conv1d", nn.initializers.lecun_normal(),
                        (self.conv_kernel, width), jnp.float32,
                    ),
                    **norm,
                )
                for name, norm in (
                    ("q", dict(head=dk, scale=dk ** -0.5)),
                    ("k", dict(head=dk)), ("v", {}),
                )
            )
            beta = jax.nn.sigmoid(dense(h, self.dtype, "b_proj")(x))
        with jax.named_scope("kda_gate"):
            g = gdn_passes.decay_gate(
                dense(width, self.dtype, "f_proj")(x),
                self.param("A_log", _a_log_init, (h,)),
                self.param("dt_bias", _dt_bias_init(1e-3, 0.1, 1e-4),
                           (width,)),
                bound=self.lower_bound,
            )
            count_gates(self, g, beta, self.lower_bound)
        with jax.named_scope("kda_scan"):
            out, _ = chunk_gated_delta_rule_rows(
                q, k, v, g.reshape(b, t, h, dk), beta, h, mesh=self.mesh
            )
        with jax.named_scope("kda_mix"):
            # ONE gate value a head, float32 at HIGHEST as the latent
            # layer's (`lm_common.LatentAttention`).
            gate = jnp.dot(
                x.astype(jnp.float32),
                self.param("g_proj", nn.initializers.lecun_normal(), (d, h),
                           jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            out = gdn_passes.gated_rms_norm(
                out, gate,
                self.param("o_norm", nn.initializers.ones_init(), (dk,),
                           jnp.float32),
                eps=self.eps, dtype=self.dtype, activation="sigmoid",
            )
        return dense(d, self.dtype, "o_proj")(out)


class DecoderLayer(nn.Module):
    cfg: Any     # LingConfig
    index: int   # the layer's PUBLISHED index: it decides both sublayers

    @nn.compact
    def __call__(self, x, cos, sin):
        c = self.cfg
        if (self.index + 1) % c.layer_group_size == 0:
            with jax.named_scope("attn"):
                h = RMSNorm(c.rms_norm_eps, name="input_layernorm")(x)
                x = x + LatentAttention(
                    c.num_attention_heads, c.qk_nope_head_dim,
                    c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank,
                    c.rms_norm_eps, c.dtype,
                    (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5,
                    c.attn_impl,
                    head_norm_eps=c.rms_norm_eps if c.use_qk_norm else None,
                    head_gate=True, name="self_attn",
                )(h, cos, sin)
        else:
            with jax.named_scope("kda"):
                h = RMSNorm(c.rms_norm_eps, name="input_layernorm")(x)
                x = x + KimiDeltaAttention(
                    c.num_attention_heads, c.head_dim,
                    c.short_conv_kernel_size, float(c.kda_lower_bound),
                    c.rms_norm_eps, c.dtype, c.mesh, name="linear_attn",
                )(h)
        dense_layer = self.index < c.first_k_dense_replace
        with jax.named_scope("mlp" if dense_layer else "moe"):
            h = RMSNorm(c.rms_norm_eps, name="post_attention_layernorm")(x)
            if dense_layer:
                return x + GatedMLP(c.intermediate_size, c.dtype, name="mlp")(h)
            return x + SparseMoeBlock(
                c.num_experts, c.num_experts_per_tok, c.moe_intermediate_size,
                c.moe_shared_expert_intermediate_size,
                (c.experts_first, c.experts_held), c.norm_topk_prob, c.dtype,
                score="sigmoid", expert_form="gated_silu",
                routed_scale=c.routed_scaling_factor, shared_gated=False,
                n_group=c.n_group, topk_group=c.topk_group, name="mlp",
            )(h)


@dataclasses.dataclass(frozen=True)
class LingConfig:
    """The source's `config.json` keys this model reads, then the stage of
    the stack and the experts this chip holds, and how it computes."""

    vocab_size: int = VOCAB
    hidden_size: int = 64
    intermediate_size: int = 160
    moe_intermediate_size: int = 32
    moe_shared_expert_intermediate_size: int = 32
    num_hidden_layers: int = 7
    first_k_dense_replace: int = 2
    layer_group_size: int = 6
    num_attention_heads: int = 2
    head_dim: int = 16
    short_conv_kernel_size: int = 4
    linear_silu: bool = True
    group_norm_size: int = 1
    gated_attention_proj_granularity_type: str = "head_wise"
    kda_safe_gate: bool = True
    kda_lower_bound: float = -5.0
    no_kda_lora: bool = True
    q_lora_rank: Any = None
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rotary_dim: int = 8
    rope_theta: float = 6e6
    use_qk_norm: bool = True
    num_experts: int = 16
    num_experts_per_tok: int = 2
    n_group: int = 4
    topk_group: int = 2
    score_function: str = "sigmoid"
    moe_router_enable_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    expert_swiglu_limit_list: tuple = ()
    share_expert_swiglu_limit_list: tuple = ()
    rms_norm_eps: float = 1e-6
    first_layer: int = 1     # the published index of the stage's first layer
    experts_first: int = 0
    experts_held: int = 16
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    remat: bool = False
    mesh: Any = None


class _Model(nn.Module):
    cfg: LingConfig

    @nn.compact
    def __call__(self, tokens):
        c = self.cfg
        embedding = self.param(
            "embed_tokens", nn.initializers.normal(0.02),
            (c.vocab_size, c.hidden_size), jnp.float32,
        )
        x = embedding[tokens]
        cos, sin = gqa.rotary_tables(
            jnp.arange(tokens.shape[-1]), c.rotary_dim, c.rope_theta
        )
        layer_cls = nn.remat(
            DecoderLayer, policy=KEEP_ATTENTION_RESULTS
        ) if c.remat else DecoderLayer
        for i in range(c.first_layer, c.first_layer + c.num_hidden_layers):
            x = layer_cls(c, i, name=f"layers_{i}")(x, cos, sin)
        with jax.named_scope("lm_head_loss"):
            return RMSNorm(c.rms_norm_eps, name="norm")(x)


class LingLM(nn.Module):
    cfg: LingConfig

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


def _not_built(cfg: LingConfig) -> None:
    """Raise for a value of a key this stack reads that names a form it
    does not build."""
    held = range(cfg.first_layer, cfg.first_layer + cfg.num_hidden_layers)
    for name in SWIGLU_LIMITS:
        limits = getattr(cfg, name)
        if any(limits[i] for i in held if i < len(limits)):
            raise ValueError(
                f"{name} is not 0 for a layer of {held}: the clamp inside "
                "the experts' gated unit is not built"
            )
    for name, built in (
        ("kda_safe_gate", True), ("no_kda_lora", True), ("linear_silu", True),
        ("group_norm_size", 1), ("score_function", "sigmoid"),
        ("moe_router_enable_expert_bias", True),
        ("gated_attention_proj_granularity_type", "head_wise"),
        ("rotary_dim", cfg.qk_rope_head_dim),
    ):
        if getattr(cfg, name) != built:
            raise ValueError(
                f"{name}={getattr(cfg, name)!r} is not built: only {built!r}"
            )
    if cfg.q_lora_rank not in (None, 0, "null", "None"):
        raise ValueError("a latent for the queries (q_lora_rank) is not built")


def custom_model(use_bf16: bool = True, mesh=None, **config):
    """`config`: the source's `config.json` keys this model reads (see
    `LingConfig`; the two per-layer lists as a sequence or as `a/b/c`),
    plus `first_layer` (the published index of the first layer of this
    stage), `experts_first` / `experts_held` (the range of experts this
    chip holds), `attn_impl` and `remat` (rematerialise each decoder
    layer in the backward pass).  `mesh`: the job's mesh, for the
    engines' choice (`ops/gdn_passes.py`)."""
    for name in TOWER_KEYS:
        if name in config:
            raise ValueError(
                f"{name}: the vision tower is not built; this is the "
                "language model on token ids"
            )
    for name in [k for k in config if k in MTP_KEYS or k.startswith("mtp_")]:
        if config.pop(name):  # `mtp_use_kda: false` builds nothing
            raise ValueError(
                f"{name}: the multi-token-prediction head is not built"
            )
    unknown = set(config) - set(LingConfig.__dataclass_fields__)
    if unknown:
        raise ValueError(f"ling_lm has no parameter(s) {sorted(unknown)}")
    for name in SWIGLU_LIMITS:
        if name in config:
            config[name] = listed(config[name], float)
    config.setdefault("experts_held", config.get("num_experts", 16))
    cfg = LingConfig(
        dtype=jnp.bfloat16 if use_bf16 else jnp.float32, mesh=mesh, **config
    )
    _not_built(cfg)
    return LingLM(cfg)
