"""What the zoo's causal-LM stacks share, defined once.

A language model of the zoo is a STACK (`model_zoo/<m>/<m>_lm.py`: the
modules whose names and layouts follow the model's source) over the pieces
here.  A stack imports from this module and from `elasticdl_tpu` only,
never from another model's directory (tests/test_zoo.py holds that), so an
edit to a stack meets that model's cells and no other; an edit HERE meets
every cell whose stack uses the piece:

- the causal-LM zoo contract on `synthetic://lm` data (`VOCAB`, `SEQ_LEN`,
  `custom_data_reader`, `dataset_fn`, `eval_metrics_fn`, `loss`): seven
  stacks (`gpt2-medium`, `qwen3-next`, `nemotron3-nano`, `deepseek-v2-lite`,
  `laguna-xs2`, `granite4-h-micro`, `mellum2`); the eighth, `ouro`, takes
  the data contract alone: its prediction is a named tree (four exits'
  logits and the exit distribution), so its `loss` and `eval_metrics_fn`
  are its own; the ninth, `sdar`, takes the READER alone
  (`custom_data_reader`): its features are a record's tokens with the
  record's noise, drawn by its own `dataset_fn`, its labels the tokens
  themselves, and its prediction a tree too;
- `dense`, the bias-free projection with a float32 result: the eight 8k
  stacks (`SplitDense`, the same parameter read as a product a range of
  its columns: `Mamba2Mixer`); `RMSNorm` (weight from 1): all of them
  but Qwen3-Next, whose zero-centred norm is another function and stays
  in its file (Mellum 2 also norms every query and key head with it: it
  is over the last axis, so a [B, T, heads, head_dim] tensor gets one
  weight vector of `head_dim`; Ouro has four of them a layer and one
  that closes every pass; SDAR's as Mellum 2's);
- `RotaryAttention`, the projections, `ops/rotary_pack.py` and
  `ops/gqa.causal_attention` as one piece (a head norm and a band where
  the stack says so): Mellum 2 (both, by the layer's type), Ouro
  (neither) and SDAR (the head norm, and in place of a causal mask the
  block-diffusion one over a noised and a clean copy, `block_diffusion=`:
  the positions are the tables' rows, so the copies share them);
- `KEEP_ATTENTION_RESULTS`, the policy of every stack's `nn.remat`: the
  eight 8k stacks;
- `warmup_adamw`: DeepSeek-V2, Granite, Mellum 2, Ouro and SDAR; `balancing_adamw`, which wraps
  it with the rule that moves a sigmoid router's selection biases:
  Nemotron-H and Laguna, the two stacks behind that router;
- `listed` and `check_listed`, a source's per-layer lists as a job's flat
  flags carry them and the check that they cover the stack: Laguna and
  Mellum 2 (SDAR: `listed` for its `mlp_only_layers`);
- `LatentAttention` (MLA without a latent for the queries): DeepSeek-V2
  as it is, Ling with a head-wise output gate and head norms, each an
  argument DeepSeek-V2 leaves off;
- `Mamba2Mixer` (with `_Conv1d`, `_dt_bias_init`; its float32 passes are
  `ops/gdn_passes.py`'s, which Qwen3-Next's DeltaNet layers call too) and
  the position-free `Attention`: Nemotron-H and Granite 4.0-H.

This module imports no stack and no ring attention: importing an 8k stack
brings neither `transformer_lm` nor `parallel/ring_attention.py` with it.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.ops import gdn_passes, gqa
from elasticdl_tpu.ops.rotary_pack import rotary_pack
from elasticdl_tpu.ops.ssd import ssd_chunked_rows
from model_zoo import datasets

VOCAB = 256
SEQ_LEN = 128


# -- the zoo contract of a causal LM on `synthetic://lm` data ---------------

def loss(labels, predictions):
    """Mean next-token cross-entropy; labels [B, T], logits [B, T, V]."""
    with jax.named_scope("lm_head_loss"):
        return optax.softmax_cross_entropy_with_integer_labels(
            predictions.astype(jnp.float32), labels.astype(jnp.int32)
        ).mean()


def dataset_fn(dataset, mode, metadata):
    def parse(record):
        tokens, next_tokens = record
        return np.asarray(tokens, np.int32), np.asarray(
            next_tokens, np.int32
        )

    dataset = dataset.map(parse)
    if mode == "training":
        dataset = dataset.shuffle(1024, seed=0)
    return dataset


def eval_metrics_fn():
    def perplexity(outputs, labels):
        ce = float(loss(jnp.asarray(labels), jnp.asarray(outputs)))
        return float(np.exp(min(ce, 20.0)))

    return {
        "perplexity": perplexity,
        "accuracy": lambda outputs, labels: float(
            np.mean(np.argmax(outputs, axis=-1) == labels)
        ),
    }


def custom_data_reader(data_path: str, **kwargs):
    name, params = datasets.parse_synthetic_path(data_path)
    if name != "lm":
        return None
    return datasets.synthetic_lm_reader(
        n=params.get("n", 2048),
        seq_len=params.get("len", SEQ_LEN),
        vocab=params.get("vocab", VOCAB),
        seed=params.get("seed", 0),
    )


def warmup_adamw(lr: float, warmup_steps: int, **adamw):
    """AdamW whose rate rises linearly to `lr` over the first
    `warmup_steps` steps (step n of them runs at lr n / warmup_steps) and
    stays, as a pre-training job's first steps run.  `adamw`: optax's own
    (`b1`, `b2`, `weight_decay`, ...)."""
    return optax.adamw(
        lambda count: lr * jnp.minimum(1.0, (count + 1) / warmup_steps),
        **adamw,
    )


def listed(value, cast=str) -> tuple:
    """A per-layer list of a source's `config.json` as a job's flat flags
    carry it (`a/b/c`), as a Python caller hands it (a sequence), or one
    entry."""
    if isinstance(value, str):
        value = value.split("/")
    elif not isinstance(value, (list, tuple)):
        value = (value,)
    return tuple(cast(entry) for entry in value)


def check_listed(name: str, entries: tuple, layers: int, known=None) -> None:
    """A per-layer list has an entry for each of the stack's `layers`
    layers, each one of `known` (where the entries are names)."""
    if len(entries) < layers:
        raise ValueError(f"{name} lists {len(entries)} layers of {layers}")
    if known and set(entries[:layers]) - set(known):
        raise ValueError(f"{name} {entries!r} is not made of {sorted(known)}")


SELECTION_BIAS = "e_score_correction_bias"


def balancing_adamw(lr: float = 3e-4, warmup_steps: int = 2000,
                    bias_update_rate: float = 1e-3):
    """The `optimizer` of a stack behind `layers/moe.py`'s sigmoid router
    (Nemotron-H, Laguna): `warmup_adamw` at weight decay 0.01, and for the
    routers' selection biases alone the balancing rule in its place.  The
    bias takes part in no gradient (a selection is not differentiated),
    `layers/moe.py` hands it `sign(times chosen - mean)` over all experts
    instead, and plain descent at `bias_update_rate` on that, no moments,
    no decay, is auxiliary-loss-free balancing (arXiv:2408.15664)."""
    return optax.multi_transform(
        {
            "adamw": warmup_adamw(lr, warmup_steps, weight_decay=0.01),
            "balance": optax.sgd(bias_update_rate),
        },
        lambda params: jax.tree_util.tree_map_with_path(
            lambda path, _: "balance"
            if getattr(path[-1], "key", None) == SELECTION_BIAS else "adamw",
            params,
        ),
    )


# -- blocks -----------------------------------------------------------------

#: What a rematerialised layer KEEPS for its backward pass (`nn.remat`'s
#: `policy`, in every stack that rematerialises a layer): the attention
#: engine's two results, `out` and the log-sum-exp, which are residuals of
#: the engine's own backward pass (`ops/gqa.py` names them in both
#: engines' forward rules).  Everything else is recomputed: the norms, the
#: projections, q and k (`rotary_pack`), the MLPs, a state-space or
#: delta-rule layer whole (nothing under these names).  Without it the
#: recomputed forward runs the whole engine again only to hand these two
#: to the backward pass, a fifth to a quarter of an attention core's time,
#: and what that second forward needs at the backward's peak weighs more
#: than the kept results (bfloat16 [tokens, Hq Dv] + float32 [Hq, tokens] a
#: layer): three of the four XLA-engine programs SHRINK (PERF.md section
#: 6, PR 52).
KEEP_ATTENTION_RESULTS = jax.checkpoint_policies.save_only_these_names(
    gqa.ATTN_OUT, gqa.ATTN_LSE
)


def dense(features, dtype, name, kernel_init=nn.initializers.lecun_normal()):
    """A projection without bias, operands in `dtype`, a float32 result:
    what the MXU accumulates is not rounded again on the way out (a
    bfloat16 result carries 2^-9 of rounding into a delta rule or a scan,
    which amplifies it; the operands' rounding averages out over the
    dot)."""
    return nn.Dense(
        features, use_bias=False, dtype=dtype, name=name,
        kernel_init=kernel_init,
        dot_general=partial(
            jax.lax.dot_general, preferred_element_type=jnp.float32
        ),
    )


class SplitDense(nn.Module):
    """`dense` whose result comes as one array a range of its columns:
    ONE parameter, `kernel` [in, sum(widths)] as `dense` would hold and
    seed it, and a product for each of `widths` (operands in `dtype`,
    float32 results).  For a layer that hands the ranges to kernels: each
    is then an array as it lies, and its gradient reaches the weight's
    product whole, with no slice laid out on the way in and no pad summed
    on the way back."""

    widths: tuple
    dtype: Any

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], sum(self.widths)), jnp.float32,
        ).astype(self.dtype)
        x = x.astype(self.dtype)
        ends = np.cumsum(self.widths)
        return tuple(
            jnp.dot(x, kernel[:, end - width:end],
                    preferred_element_type=jnp.float32)
            for width, end in zip(self.widths, ends)
        )


class RMSNorm(nn.Module):
    """y = w x rsqrt(mean(x^2) + eps), w from 1; float32."""

    eps: float

    @nn.compact
    def __call__(self, x):
        weight = self.param(
            "weight", nn.initializers.ones_init(), (x.shape[-1],), jnp.float32,
        )
        x = x.astype(jnp.float32)
        return weight * x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + self.eps
        )


class NormWeight(nn.Module):
    """An `RMSNorm`'s parameter alone (`weight` [width], from 1), under
    the norm's name, for a pass that takes the norm inside
    (`ops/rotary_pack.py`)."""

    width: int

    @nn.compact
    def __call__(self):
        return self.param(
            "weight", nn.initializers.ones_init(), (self.width,), jnp.float32,
        )


class _Conv1d(nn.Module):
    """The source's depthwise `conv1d`: `kernel` [taps, channels], `bias`."""

    taps: int

    @nn.compact
    def __call__(self, channels: int):
        return (
            self.param("kernel", nn.initializers.lecun_normal(),
                       (self.taps, channels), jnp.float32),
            self.param("bias", nn.initializers.zeros_init(), (channels,),
                       jnp.float32),
        )


def _dt_bias_init(dt_min: float, dt_max: float, dt_floor: float):
    def init(key, shape):
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, np.log(dt_min), np.log(dt_max)
        ))
        dt = jnp.maximum(dt, dt_floor)
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)

    return init


class Mamba2Mixer(nn.Module):
    """The Mamba-2 layer of `mamba_ssm` / `transformers`, parameters by the
    source's names (`in_proj`, `conv1d` with `kernel` [taps, channels of
    x | B | C] and `bias`, `A_log`, `D`, `dt_bias`, `norm` [H P],
    `out_proj`): `[z | xBC | dt] = in_proj(u)`; `xBC` through a causal
    depthwise convolution of `conv_kernel` taps with a bias, then silu,
    split into x [H heads of P], B and C [G groups of N];
    `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)` a scalar a head; the
    selective state-space recurrence of `ops/ssd.py` (heads of group g
    share B and C; its kernel pair or its XLA form, as `ssd_chunked_rows`
    finds from the backend, the shapes, `dtype` and `mesh`: x and [B | C]
    go in as the rows the convolutions wrote and y comes back as the rows
    the norm reads, so no [B, T, H, P] is laid out on the way) under the
    `ssm_scan` scope, plus the skip `D x`;
    `y = GroupRMSNorm(y silu(z))` over G groups with a weight; `out_proj`.
    The float32 chains between the projections and the scan are passes of
    `ops/gdn_passes.py`: `conv_silu` for x and for [B | C], and
    `gated_group_norm` for the skip, the gate and the norm, each ONE
    kernel forward and one backward where `gdn_passes.engine_groups` finds
    a TPU, widths in whole lane tiles and one device or the `mesh` the
    program is compiled for, and that module's plain `jax.numpy`
    definitions elsewhere (the CPU's tests); `in_proj` is ONE parameter
    and four products (`SplitDense`), so that z, x, [B | C] and dt reach
    the passes as arrays of their own.
    Seeded as the source: `A_log = log U(1, 16)`, `dt_bias` the inverse
    softplus of `dt ~ exp(U(log min, log max))` floored (`time_step`), `D`
    and the norm 1, `out_proj` at `out_scale`."""

    num_heads: int
    head_dim: int
    n_groups: int
    state_size: int
    conv_kernel: int
    chunk_size: int
    eps: float
    dtype: Any
    time_step: tuple = (1e-3, 0.1, 1e-4)  # min, max, floor: the init only
    out_scale: float = 1.0                # `rescale_prenorm_residual`
    mesh: Any = None  # what the program is compiled for: the engines' choice

    @nn.compact
    def __call__(self, u):
        b, t, d = u.shape
        h, p, g, n = (self.num_heads, self.head_dim, self.n_groups,
                      self.state_size)
        inner, bc = h * p, g * n
        # z, x, [B | C] and dt, float32: ONE parameter, a product a range
        # of its columns, so that each is an array the passes take as it
        # lies and its gradient comes back whole (a slice handed to a
        # kernel is a copy in HBM, and its gradient a pad).
        z, x, bc_in, dt = SplitDense(
            (inner, inner, 2 * bc, h), self.dtype, name="in_proj"
        )(u)
        passes = dict(mesh=self.mesh, pallas=gdn_passes.engine_groups(
            t, inner + 2 * bc, g, inner // g, self.conv_kernel, self.mesh
        ) == "pallas")
        # Causal depthwise convolution over [x | B | C], then silu, the
        # taps accumulated in float32: a column at a time, so x's pass and
        # [B | C]'s are two ([B | C] stays ONE array: the scan reads B and
        # C as column ranges of it).
        kernel, bias = _Conv1d(self.conv_kernel, name="conv1d")(
            inner + 2 * bc
        )
        x = gdn_passes.conv_silu(
            x, kernel[:, :inner], bias[:inner], **passes
        )
        bc_in = gdn_passes.conv_silu(
            bc_in, kernel[:, inner:], bias[inner:], **passes
        )
        a_log = self.param(
            "A_log",
            lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
            ),
            (h,),
        )
        skip = self.param("D", nn.initializers.ones_init(), (h,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init(*self.time_step), (h,))
        dt = jax.nn.softplus(dt + dt_bias)
        # x [B, T, H P], [B | C] [B, T, 2 G N] and y as the passes write
        # and read them: rows, which the scan's kernels take as they lie
        with jax.named_scope("ssm_scan"):
            y, _ = ssd_chunked_rows(
                x, dt, -jnp.exp(a_log), bc_in, groups=g,
                chunk=self.chunk_size, dtype=self.dtype, mesh=self.mesh,
            )
        # The skip, the gate and the RMSNorm over each of the G groups of
        # the inner width, float32; the result in the out-projection's type.
        weight = self.param("norm", nn.initializers.ones_init(), (inner,),
                            jnp.float32)
        y = gdn_passes.gated_group_norm(
            y, x, z, skip, weight, groups=g,
            eps=self.eps, dtype=self.dtype, **passes,
        )
        init = nn.initializers.variance_scaling(
            self.out_scale ** 2, "fan_in", "truncated_normal"
        )
        return dense(d, self.dtype, "out_proj", init)(y)


class RotaryAttention(nn.Module):
    """Grouped-query causal softmax attention behind rotary positions, as
    one piece: `q_proj`, `k_proj`, `v_proj` without bias; q and k from
    the projections' float32 results to the engine's operands in one pass
    (`ops/rotary_pack.py`: a norm over each head's columns where
    `head_norm_eps` is a number, with the weights `q_norm` / `k_norm`,
    then the rotation by `cos` / `sin` over as many of a head's columns as
    the tables have, one rounding, heads in front of tokens);
    `ops/gqa.causal_attention` over every key up to the query's own, or
    over the last `window` of them, or, with `block_diffusion=(T, B)`,
    over a noised and a clean copy of T tokens (x holds the 2 T
    positions, `cos` / `sin` their rows: both copies of a token at ITS
    position) under the block-diffusion mask in blocks of B; `o_proj`.
    Device scopes: `attn_proj` (entered for q, k, v and again for
    `o_proj`), `attn_rotary`, `attn_full` | `attn_window` |
    `attn_blockdiff`."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    dtype: Any
    impl: str = "auto"       # `ops/gqa.causal_attention`'s, as it is
    window: Any = None       # keys a query reads, its own among them
    head_norm_eps: Any = None  # None: q and k heads are not normed
    block_diffusion: Any = None  # (tokens of a copy, a block's length)

    @nn.compact
    def __call__(self, x, cos, sin):
        b, t, d = x.shape
        h, hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        with jax.named_scope("attn_proj"):
            q, k, v = (
                dense(n * hd, self.dtype, name)(x).reshape(b, t, n, hd)
                for name, n in (("q_proj", h), ("k_proj", hkv),
                                ("v_proj", hkv))
            )
        with jax.named_scope("attn_rotary"):
            # over a head's columns, one weight vector each
            q, k = (
                rotary_pack(
                    p, cos, sin, self.dtype,
                    None if self.head_norm_eps is None
                    else NormWeight(hd, name=norm)(),
                    self.head_norm_eps or 1e-6,
                )
                for p, norm in ((q, "q_norm"), (k, "k_norm"))
            )
        engine_scope = (
            "attn_blockdiff" if self.block_diffusion is not None
            else "attn_full" if self.window is None else "attn_window"
        )
        with jax.named_scope(engine_scope):
            out = gqa.heads_first(gqa.causal_attention(
                q, k, gqa.heads_first(v.astype(self.dtype)), impl=self.impl,
                window=self.window, block_diffusion=self.block_diffusion,
                packed=True,
            ))
        with jax.named_scope("attn_proj"):
            return dense(d, self.dtype, "o_proj")(
                out.reshape(b, t, h * hd).astype(self.dtype)
            )


class LatentAttention(nn.Module):
    """Multi-head latent attention (MLA) with no latent for the queries,
    parameters by DeepSeek-V2's names: `q = q_proj(x)` [H heads of `nope`
    + `rope`]; `[c | k_pe] = kv_a_proj_with_mqa(x)` [`kv_lora_rank` |
    `rope`]; `c = kv_a_layernorm(c)`; `[k_nope | v] = kv_b_proj(c)` [H
    heads of `nope` | `dv`].  The rotary (`cos`, `sin`: the caller's
    tables over `rope` columns) turns a head's `q_pe` and the ONE `k_pe` a
    token, which every head shares; causal softmax over the two parts'
    `nope + rope` dimensions at `scale`, times `v_h`: two head sizes in
    one attention (`ops/gqa.causal_attention`); `o_proj`.  Keys and values
    are materialised from the latent, as training computes them.  What a
    stack may add, each off where DeepSeek-V2 leaves it off:

    - `head_norm_eps` a number: an RMSNorm of each head's assembled q and
      k (`q_norm`, `k_norm`, one weight vector of `nope + rope` each)
      BEFORE the rotary; a head's rotary key part is then its own (the
      shared `k_pe` under that head's norm);
    - `head_gate`: the heads' outputs times `sigmoid(x g_proj)` [H], one
      value a head (float32 at `Precision.HIGHEST`, as Laguna's), under
      the `attn_gate` scope.

    Device scopes: `mla_latent` (the latent's projections, its norm, the
    head norms, the rotary, the assembly of q and k), `mla_core` (the
    engine), `attn_gate`."""

    num_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    eps: float
    dtype: Any
    scale: float
    impl: str = "auto"
    head_norm_eps: Any = None
    head_gate: bool = False

    @nn.compact
    def __call__(self, x, cos, sin):
        b, t, d = x.shape
        h, nope, rope, dv = (self.num_heads, self.qk_nope_head_dim,
                             self.qk_rope_head_dim, self.v_head_dim)
        rank, dtype = self.kv_lora_rank, self.dtype
        q = dense(h * (nope + rope), dtype, "q_proj")(x).reshape(
            b, t, h, nope + rope
        )
        with jax.named_scope("mla_latent"):
            latent = dense(rank + rope, dtype, "kv_a_proj_with_mqa")(x)
            k_pe = latent[..., rank:].reshape(b, t, 1, rope)
            latent = RMSNorm(self.eps, name="kv_a_layernorm")(
                latent[..., :rank]
            )
            kv = dense(h * (nope + dv), dtype, "kv_b_proj")(latent).reshape(
                b, t, h, nope + dv
            )
            k_nope = kv[..., :nope]
            if self.head_norm_eps is not None:
                q = RMSNorm(self.head_norm_eps, name="q_norm")(q)
                k = RMSNorm(self.head_norm_eps, name="k_norm")(
                    jnp.concatenate(
                        [k_nope, jnp.broadcast_to(k_pe, (b, t, h, rope))],
                        axis=-1,
                    )
                )
                k_nope, k_pe = k[..., :nope], k[..., nope:]
            q = jnp.concatenate([
                q[..., :nope].astype(dtype),
                gqa.apply_rotary(q[..., nope:], cos, sin).astype(dtype),
            ], axis=-1)
            # The one rotated key part a token, given to every head.
            k = jnp.concatenate([
                k_nope.astype(dtype),
                jnp.broadcast_to(
                    gqa.apply_rotary(k_pe, cos, sin).astype(dtype),
                    (b, t, h, rope),
                ),
            ], axis=-1)
            v = kv[..., nope:].astype(dtype)
        with jax.named_scope("mla_core"):
            out = gqa.causal_attention(
                q, k, v, scale=self.scale, impl=self.impl
            )
        if self.head_gate:
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
        return dense(d, dtype, "o_proj")(
            out.reshape(b, t, h * dv).astype(dtype)
        )


class Attention(nn.Module):
    """Grouped-query causal softmax attention with NO position embedding:
    `q_proj`, `k_proj`, `v_proj`, `o_proj` without bias."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    dtype: Any
    scale: Any = None  # of the scores; None: 1/sqrt(head_dim)

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        h, hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        q, k, v = (
            dense(heads * hd, self.dtype, name)(x)
            .reshape(b, t, heads, hd).astype(self.dtype)
            for name, heads in (("q_proj", h), ("k_proj", hkv), ("v_proj", hkv))
        )
        out = gqa.causal_attention(q, k, v, scale=self.scale)
        return dense(d, self.dtype, "o_proj")(out.reshape(b, t, h * hd))
