"""Transformer causal LM — the long-context / context-parallel config.

Net-new scope beyond the reference (SURVEY.md §5: the reference predates
long-context training and has none; this framework treats it as
first-class).  A pre-LN decoder-only transformer whose attention runs:

- single-device: `blockwise_attention` (flash numerics; KV processed in
  chunks so score slabs are [T, kv_chunk], never the full [T, T]), or
- context-parallel: `ring_attention` under shard_map — the sequence dim
  shards over the mesh's `model` axis, K/V blocks rotate over ICI
  (parallel/ring_attention.py) — when built with `custom_model(mesh=...)`
  and the mesh's model axis is > 1.

Everything else is ordinary flax the DataParallelTrainer already handles:
params replicated (f32), bf16 compute, batch sharded over `data`, XLA
psums the grads.  The model-zoo contract functions are
`model_zoo/lm_common.py`'s; synthetic `synthetic://lm?n=N&len=T&vocab=V`
data (model_zoo/datasets.py) makes next-token loss genuinely learnable in
tests.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from elasticdl_tpu.parallel.ring_attention import (
    blockwise_attention,
    make_ring_attention,
)
# The zoo contract of a causal LM on `synthetic://lm` data, which the 8k
# stacks share with this one.
from model_zoo.lm_common import (  # noqa: F401
    SEQ_LEN, VOCAB, custom_data_reader, dataset_fn, eval_metrics_fn, loss,
)

logger = get_logger("model_zoo.transformer")


def _tp_active(mesh, model_axis_mode: str) -> bool:
    return (
        model_axis_mode == "tp"
        and mesh is not None
        and mesh.shape.get(MODEL_AXIS, 1) > 1
    )


def _constrain(mesh, x, *spec):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec))
    )


class CausalSelfAttention(nn.Module):
    num_heads: int
    dtype: Any = jnp.bfloat16
    mesh: Any = None  # jax.sharding.Mesh -> ring attention over `model`
    # "auto": the Pallas flash kernel on TPU when the shape qualifies,
    # XLA blockwise otherwise.  "pallas"/"xla" force one implementation.
    attn_impl: str = "auto"
    # Context-parallel sequence layout: "contiguous" or "zigzag" (the
    # balanced causal ring; see parallel/ring_attention.py).
    cp_layout: str = "contiguous"
    # What the mesh's `model` axis carries: "cp" (ring attention over the
    # sequence) or "tp" (Megatron-style tensor parallelism: heads and MLP
    # hidden sharded over the axis via sharding constraints; GSPMD splits
    # the matmuls and inserts the reduce).
    model_axis_mode: str = "cp"

    def _single_device_attend(self, t: int, head_dim: int):
        from elasticdl_tpu.ops import flash_attention
        from elasticdl_tpu.ops.flash_attention import (
            _use_interpret,
            supports,
            warn_if_vmem_is_sole_blocker,
        )

        use_pallas = self.attn_impl == "pallas" or (
            self.attn_impl == "auto"
            and jax.default_backend() == "tpu"
            and supports(t, head_dim)
        )
        # Trace-time record of the engine this compile got (once per
        # compile): chip_smoke.py reads it from the worker log, so a
        # demotion to the fallback or to the interpreter is never silent.
        if use_pallas:
            logger.info(
                "attention engine: pallas flash_attention T=%d D=%d "
                "(interpret=%s)",
                t, head_dim, _use_interpret(),
            )
            return partial(flash_attention, causal=True)
        if self.attn_impl == "auto" and jax.default_backend() == "tpu":
            warn_if_vmem_is_sole_blocker("model_zoo.transformer", t, head_dim)
        logger.info(
            "attention engine: xla blockwise_attention T=%d D=%d",
            t, head_dim,
        )
        return partial(blockwise_attention, causal=True)

    @nn.compact
    def __call__(self, x):
        if self.attn_impl not in ("auto", "pallas", "xla"):
            raise ValueError(
                f"attn_impl must be 'auto', 'pallas' or 'xla', "
                f"got {self.attn_impl!r}"
            )
        if self.model_axis_mode not in ("cp", "tp"):
            raise ValueError(
                f"model_axis_mode must be 'cp' or 'tp', "
                f"got {self.model_axis_mode!r}"
            )
        b, t, e = x.shape
        head_dim = e // self.num_heads
        sharded_axis = (
            self.mesh is not None
            and self.mesh.shape.get(MODEL_AXIS, 1) > 1
        )
        cp = sharded_axis and self.model_axis_mode == "cp"
        tp = sharded_axis and self.model_axis_mode == "tp"
        zigzag = cp and self.cp_layout == "zigzag"
        inv = None
        if zigzag:
            # Balanced causal ring: permute the sequence into the zigzag
            # shard layout around the attention only (hidden states stay
            # in natural order for pos-emb / loss).  Permuting x ONCE
            # here — the qkv projection is position-wise — instead of
            # q/k/v separately cuts the cross-shard permute traffic 3x.
            from elasticdl_tpu.parallel.ring_attention import zigzag_orders

            order, inv = (
                jnp.asarray(o)
                for o in zigzag_orders(t, self.mesh.shape[MODEL_AXIS])
            )
            x = x[:, order]
        qkv = nn.DenseGeneral(
            (3, self.num_heads, head_dim), dtype=self.dtype, name="qkv"
        )(x)
        if tp:
            # Column-parallel qkv: heads shard over the model axis, so
            # each device computes its heads' attention locally (the
            # single-device kernels below partition head-wise under
            # GSPMD; pallas custom calls don't, hence the xla path).
            qkv = _constrain(
                self.mesh, qkv, DATA_AXIS, None, None, MODEL_AXIS, None
            )
        q, k, v = (qkv[:, :, i] for i in range(3))  # [B, T, H, D] each
        if cp:
            # The ring's per-step block engine: 'auto' runs the Pallas
            # flash kernels whenever the local shard shape fits (round 3
            # — the ring previously always used the XLA block math and
            # forfeited the measured 2.4x kernel win exactly where long
            # context matters; see ring_attention_pallas).
            attend = make_ring_attention(
                self.mesh, causal=True, layout=self.cp_layout,
                impl=self.attn_impl,
            )
        elif tp:
            if self.attn_impl == "pallas":
                raise ValueError(
                    "attn_impl='pallas' cannot partition over the model "
                    "axis (custom calls are opaque to GSPMD); tensor-"
                    "parallel attention runs the XLA blockwise engine"
                )
            attend = partial(blockwise_attention, causal=True)
        else:
            attend = self._single_device_attend(t, head_dim)
        out = attend(q, k, v)  # [B, T, H, D]
        if zigzag:
            out = out[:, inv]
        out = out.reshape(b, t, e)
        out = nn.Dense(e, dtype=self.dtype, name="proj")(out)
        if tp:
            # Row-parallel proj closes the TP block: output replicated
            # over the model axis (GSPMD inserts the partial-sum reduce).
            out = _constrain(self.mesh, out, DATA_AXIS, None, None)
        return out


class Block(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    mesh: Any = None
    attn_impl: str = "auto"
    cp_layout: str = "contiguous"
    model_axis_mode: str = "cp"

    @nn.compact
    def __call__(self, x):
        e = x.shape[-1]
        attn = CausalSelfAttention(
            self.num_heads, self.dtype, self.mesh, self.attn_impl,
            self.cp_layout, self.model_axis_mode, name="attn",
        )
        # Device scopes (obs/tracing.py DEVICE_SCOPES): each sublayer
        # with its LayerNorm and residual, so a block's device time is
        # `attn` + `mlp`.
        with jax.named_scope("attn"):
            h = nn.LayerNorm(dtype=self.dtype)(x)
            x = x + attn(h)
        with jax.named_scope("mlp"):
            h = nn.LayerNorm(dtype=self.dtype)(x)
            h = nn.Dense(e * self.mlp_ratio, dtype=self.dtype)(h)
            if _tp_active(self.mesh, self.model_axis_mode):
                # Column-parallel up-projection / row-parallel
                # down-projection (the Megatron MLP): hidden shards over
                # the model axis (batch stays on `data`), the residual
                # add below stays replicated over `model`.
                h = _constrain(self.mesh, h, DATA_AXIS, None, MODEL_AXIS)
            h = nn.gelu(h)
            return x + nn.Dense(e, dtype=self.dtype)(h)


class _Bf16AccF32Head(nn.Module):
    """LM head with bf16 operands and f32 accumulation/output: params
    stay f32 and use nn.Dense's names (kernel/bias), so checkpoints are
    interchangeable with the f32 head; only the matmul INPUTS round to
    bf16 (the MXU's native mode — same numerics as the bf16 blocks),
    while logits and the loss softmax stay full precision."""

    vocab: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (x.shape[-1], self.vocab),
            jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.vocab,), jnp.float32
        )
        logits = jax.lax.dot_general(
            x.astype(jnp.bfloat16),
            kernel.astype(jnp.bfloat16),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return logits + bias


class TransformerLM(nn.Module):
    vocab: int = VOCAB
    d_model: int = 128
    num_heads: int = 4
    num_layers: int = 2
    max_len: int = 4096
    dtype: Any = jnp.bfloat16
    mesh: Any = None
    attn_impl: str = "auto"
    cp_layout: str = "contiguous"
    model_axis_mode: str = "cp"
    # Rematerialize each block's activations in backward (jax.checkpoint)
    # — trades ~30% more FLOPs for O(layers) less activation memory, the
    # standard long-context lever.
    remat: bool = False
    # LM-head matmul precision.  "f32": f32 x f32 (the conservative
    # default).  "bf16": bf16 operands on the MXU with f32 ACCUMULATION
    # and f32 logits out (preferred_element_type) — the same numerics as
    # every other matmul in the bf16 blocks; the head is ~half the
    # model's FLOPs at this vocab/d_model, so its matmul rate moves the
    # headline (BASELINE.md long-context section).
    logits_compute: str = "f32"

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        if self.logits_compute not in ("f32", "bf16"):
            raise ValueError(
                f"logits_compute must be 'f32' or 'bf16', "
                f"got {self.logits_compute!r}"
            )
        b, t = tokens.shape
        tok = nn.Embed(self.vocab, self.d_model, dtype=self.dtype)(tokens)
        pos = nn.Embed(self.max_len, self.d_model, dtype=self.dtype)(
            jnp.arange(t)[None, :]
        )
        x = tok + pos
        block_cls = nn.remat(Block) if self.remat else Block
        for i in range(self.num_layers):
            x = block_cls(
                self.num_heads, dtype=self.dtype, mesh=self.mesh,
                attn_impl=self.attn_impl, cp_layout=self.cp_layout,
                model_axis_mode=self.model_axis_mode,
                name=f"block_{i}",
            )(x)
        with jax.named_scope("lm_head_loss"):
            x = nn.LayerNorm(dtype=self.dtype)(x)
            if self.logits_compute == "bf16":
                return _Bf16AccF32Head(self.vocab, name="lm_head")(x)
            # Logits in f32: the loss softmax wants full precision.
            return nn.Dense(
                self.vocab, dtype=jnp.float32, name="lm_head"
            )(x)


def custom_model(
    vocab: int = VOCAB,
    d_model: int = 128,
    num_heads: int = 4,
    num_layers: int = 2,
    max_len: int = 4096,
    use_bf16: bool = True,
    mesh: Optional[Any] = None,
    attn_impl: str = "auto",
    cp_layout: str = "contiguous",
    model_axis_mode: str = "cp",
    remat: bool = False,
    logits_compute: str = "f32",
):
    """`mesh=None` -> single-device attention (Pallas flash kernel on
    TPU).  With the trainer's mesh and model axis > 1, `model_axis_mode`
    picks what that axis carries: "cp" (default) runs ring-attention
    context parallelism — the model-axis size must then divide the
    sequence length (each device holds T / model_axis positions) — and
    "tp" runs Megatron-style tensor parallelism (heads and MLP hidden
    shard over the axis; no sequence-divisibility requirement, though
    num_heads should divide the axis size for an even split)."""
    return TransformerLM(
        vocab=vocab,
        d_model=d_model,
        num_heads=num_heads,
        num_layers=num_layers,
        max_len=max_len,
        dtype=jnp.bfloat16 if use_bf16 else jnp.float32,
        mesh=mesh,
        attn_impl=attn_impl,
        cp_layout=cp_layout,
        model_axis_mode=model_axis_mode,
        remat=remat,
        logits_compute=logits_compute,
    )


def optimizer(lr: float = 3e-3):
    return optax.adamw(lr, weight_decay=0.01)
