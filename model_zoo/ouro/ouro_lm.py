"""Ouro causal LM: a stack of plain decoder layers applied `total_ut_steps`
times to its own output over ONE set of weights, an exit (the head and a
learned gate) behind every pass, and a loss taken over the distribution
of exits.

Source: https://huggingface.co/ByteDance/Ouro-2.6B (its `config.json`,
`model_type: ouro`).  With R = `total_ut_steps` and L =
`num_hidden_layers`, every norm an RMSNorm (`y = w x rsqrt(mean(x^2) +
eps)`, w from 1), no bias but the gate's::

    h_0 = E[tokens]
    pass r = 1..R, the SAME layers 1..L every pass:
      x = h_{r-1}
      layer i:
        x = x + input_layernorm_2(self_attn(input_layernorm(x)))
        x = x + post_attention_layernorm_2(mlp(post_attention_layernorm(x)))
      h_r = norm(x)            the final norm closes every pass, and its
                               output is the next pass's input
      logits_r = h_r W_head    the same untied head at every exit
      g_r = h_r w_g + b_g      the exit gate, lambda_r = sigmoid(g_r)
    p = the exit distribution of the gates (`layers/loop_exits.py`:
        lambda_r times what the earlier gates left, the rest on exit R)
    loss = mean over tokens of sum_r p_r CE(logits_r, next) - exit_beta H(p)

`self_attn`: `num_attention_heads` query heads over `num_key_value_heads`
key-value heads of `head_dim`, rotary over ALL of a head's columns at
`rope_theta` with positions 0..T-1 in every pass, full causal softmax
(`lm_common.RotaryAttention`, no head norm); `mlp`: gated SiLU of
`intermediate_size`.  The parameter tree holds L layers whatever R is: a
layer's leaf has R producers of its gradient.

THE PREDICTION IS A NAMED TREE, not one logits array:

- `logits` [B, R, T, V] float32, exit r's at index r - 1;
- `exit_logp` [B, R, T] float32, ln p_r of the exit distribution;
- `exit_bonus` [B, T] float32, `exit_beta` H(p): what the loss subtracts
  (the entropy is computed once, where the counters need it too, and the
  zoo's `loss(labels, predictions)` has no configuration to read a
  coefficient from).

`loss` and `eval_metrics_fn` below are this stack's own.  With
`total_ut_steps=1` the one exit has p = 1, H = 0, and the loss is
`lm_common.loss` of a plain decoder's logits: the same code.

Module and parameter names: `model` holding `embed_tokens`, `layers_<i>`
(`input_layernorm`, `self_attn` with `q_proj`, `k_proj`, `v_proj`,
`o_proj`, `input_layernorm_2`, `post_attention_layernorm`, `mlp` with
`gate_proj`, `up_proj`, `down_proj`, `post_attention_layernorm_2`), `norm`
and `early_exit_gate` (`kernel` [hidden, 1], `bias` [1]); `lm_head`.
Kernels in flax's [in, out] layout.

Assumed where the source's `config.json` is silent, each also in the
configuration's `assumed` with its other reading: the four norms a layer
(`sandwich_norm`; false: the plain pre-norm pair, no `_2` leaves); the
final norm inside the loop (`loop_norm`; false: the raw stream goes round
and the norm stands before each exit only); no bias in q, k, v, o and no
head norm; the gate's form; the loss and `exit_beta`; the optimizer's
numbers; rotary columns in the half-split order `apply_rotary` reads;
`o_proj` and `down_proj` not rescaled by depth; a float32 residual
stream.  The second training stage of the source (the gate alone, the
model frozen) and early exit at inference are not built.

Precision: parameters float32; with `use_bf16` the four attention
projections, scores and values, the MLP's products and the head take
bfloat16 operands and accumulate in float32.  Always float32: the residual
stream, every norm, the rotary table, the attention softmax's statistics,
the gate (its product at `Precision.HIGHEST`), the exit distribution,
logits and loss.

The passes are ONE `nn.scan` with the parameters broadcast, so the
program holds L layer bodies whatever R is, each application
rematerialised on its own under `remat` (all but the attention kernel's
two results, `lm_common.KEEP_ATTENTION_RESULTS`: 34 MB an application,
0.82 GB over the 24, 14.59 GB compiled, for a forward kernel fewer an
application, 47 ms of a 951 ms step on a v5e: PERF.md section 6, PR 52).
At the cell's widths the scan builds in a third of the unrolled loop's
time and runs 1.5-2.5% faster (first two-step window 24.7 s against 75.0,
then 1.903 s against 1.952 a window; a v5e, PR 45), at 13.78 GB compiled
against 10.54 (the scan carries the layers' float32 gradient through its
backward loop), so the loop is not kept as a second path.

`attn_impl` is handed to `ops/gqa.causal_attention` as it is.  Its
default `auto` takes the Pallas kernel at this shape (T 8192 is its cap
at heads of 128; at 16 query heads over 16 nothing is repeated for it)
and is the slower engine by 2.5% of the step, 1.903 s a window against
1.857 in the XLA block engine (the same call).  The cell's job keeps
`auto` all the same: the XLA engine's loops are ~3,000 device events a
layer application where the kernel is three, and the profiler's stop,
which a traced run of the cell pays INSIDE its window, took 19.97 s with
it against 1.85 s, which pushed that run's cadence save to 0.6 s from the
window's end (`perfbench/configs/ouro-2.6b.json` `assumed`).

Device scopes (obs/tracing.py DEVICE_SCOPES): `loop` (all R passes:
everything between the table's gather and the exits) > `attn` (the
sublayer between its two norms) > `attn_proj`, `attn_rotary`, `attn_full`;
`mlp`; `block_norm` (a layer's four norms with their residual adds,
entered four times a layer); then `lm_head_loss` (the R exits' head
product and cross-entropies) and `exit_gate` (the gate's unit, the exit
distribution, its entropy and the weighting of the R losses).  Counters:
`layers/loop_exits.py`'s, journaled a task as `loop.exits`.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from elasticdl_tpu.layers.loop_exits import (
    count_exits, exit_entropy, exit_log_probs,
)
from elasticdl_tpu.layers.moe import GatedMLP
from elasticdl_tpu.ops import gqa
from model_zoo import lm_common
# The norm, the attention behind rotary positions, the optimizer's warm-up
# and the data contract of any causal LM on `synthetic://lm` data; the
# loss and the metrics are this stack's own (its prediction is a tree).
from model_zoo.lm_common import (  # noqa: F401
    KEEP_ATTENTION_RESULTS, VOCAB, RMSNorm, RotaryAttention,
    custom_data_reader, dataset_fn, warmup_adamw,
)


class DecoderLayer(nn.Module):
    cfg: Any       # OuroConfig

    @nn.compact
    def __call__(self, x, cos, sin):
        c = self.cfg

        def norm(name):
            return RMSNorm(c.rms_norm_eps, name=name)

        def add(x, y, name):
            with jax.named_scope("block_norm"):
                return x + (norm(name)(y) if c.sandwich_norm else y)

        with jax.named_scope("block_norm"):
            h = norm("input_layernorm")(x)
        with jax.named_scope("attn"):
            h = RotaryAttention(
                c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                c.dtype, c.attn_impl, name="self_attn",
            )(h, cos, sin)
        x = add(x, h, "input_layernorm_2")
        with jax.named_scope("block_norm"):
            h = norm("post_attention_layernorm")(x)
        with jax.named_scope("mlp"):
            h = GatedMLP(c.intermediate_size, c.dtype, name="mlp")(h)
        return add(x, h, "post_attention_layernorm_2")


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """The source's `config.json` keys this model reads, then the two
    readings the source leaves open, the loss's coefficient and how this
    chip computes."""

    vocab_size: int = VOCAB
    hidden_size: int = 64
    intermediate_size: int = 160
    num_hidden_layers: int = 2
    num_attention_heads: int = 4
    num_key_value_heads: int = 4
    head_dim: int = 16
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    total_ut_steps: int = 4
    sandwich_norm: bool = True
    loop_norm: bool = True
    exit_beta: float = 0.05
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    remat: bool = False


class _Model(nn.Module):
    """-> (every pass's closing state [B, R, T, hidden], the gates' values
    before the sigmoid [B, R, T])."""

    cfg: OuroConfig

    @nn.compact
    def __call__(self, tokens):
        c = self.cfg
        embedding = self.param(
            "embed_tokens", nn.initializers.normal(0.02),
            (c.vocab_size, c.hidden_size), jnp.float32,
        )
        x = embedding[tokens]
        cos, sin = gqa.rotary_tables(
            jnp.arange(tokens.shape[-1]), c.head_dim, c.rope_theta
        )
        layer_cls = nn.remat(
            DecoderLayer, policy=KEEP_ATTENTION_RESULTS
        ) if c.remat else DecoderLayer

        def one_pass(x):
            """-> (the next pass's input, this pass's closing state).  The
            modules are made inside the scanned function, once: every
            pass reads the same parameters."""
            for i in range(c.num_hidden_layers):
                x = layer_cls(c, name=f"layers_{i}")(x, cos, sin)
            closed = RMSNorm(c.rms_norm_eps, name="norm")(x)
            return (closed if c.loop_norm else x), closed

        # ONE body for all the passes: a scan over them with the
        # parameters broadcast, so the program holds L layer bodies
        # whatever R is (module docstring).
        with jax.named_scope("loop"):
            _, states = nn.scan(
                lambda _, x, __: one_pass(x),
                variable_broadcast="params", split_rngs={"params": False},
                length=c.total_ut_steps, out_axes=1,
            )(self, x, None)
        with jax.named_scope("exit_gate"):
            gate = nn.Dense(
                1, dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST,
                kernel_init=nn.initializers.zeros_init(),
                name="early_exit_gate",
            )(states)[..., 0]
        return states, gate


class OuroLM(nn.Module):
    cfg: OuroConfig

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        c = self.cfg
        states, gate = _Model(c, name="model")(tokens)
        with jax.named_scope("lm_head_loss"):
            head = self.param(
                "lm_head", nn.initializers.lecun_normal(),
                (c.hidden_size, c.vocab_size), jnp.float32,
            )
            logits = jnp.dot(
                states.astype(c.dtype), head.astype(c.dtype),
                preferred_element_type=jnp.float32,
            )
        with jax.named_scope("exit_gate"):
            logp = exit_log_probs(gate)
            entropy = exit_entropy(logp)
            count_exits(self, logp, entropy)
            return {
                "logits": logits, "exit_logp": logp,
                "exit_bonus": c.exit_beta * entropy,
            }


def _exit_cross_entropy(labels, logits):
    """labels [B, T], logits [B, R, T, V] -> [B, R, T]."""
    with jax.named_scope("lm_head_loss"):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32),
            jnp.broadcast_to(
                labels.astype(jnp.int32)[:, None, :], logits.shape[:-1]
            ),
        )


def _expected_cross_entropy(labels, predictions):
    """The exits' cross-entropies under the exit distribution -> [B, T]."""
    cross_entropy = _exit_cross_entropy(labels, predictions["logits"])
    with jax.named_scope("exit_gate"):
        return jnp.sum(
            jnp.exp(predictions["exit_logp"]) * cross_entropy, axis=1
        )


def loss(labels, predictions):
    """The mean over tokens of the expected next-token cross-entropy under
    the exit distribution less the entropy bonus; labels [B, T],
    predictions the module docstring's tree."""
    expected = _expected_cross_entropy(labels, predictions)
    with jax.named_scope("exit_gate"):
        return jnp.mean(expected - predictions["exit_bonus"])


def eval_metrics_fn():
    """`lm_common`'s perplexity and accuracy of the LAST exit (what a
    reader that never leaves early gets) and the expected cross-entropy
    under the exit distribution; `outputs` the prediction's tree as the
    master concatenates it."""
    last_exit = lm_common.eval_metrics_fn()

    def of_last(name):
        return lambda outputs, labels: last_exit[name](
            outputs["logits"][:, -1], labels
        )

    return {
        "perplexity": of_last("perplexity"),
        "accuracy": of_last("accuracy"),
        "expected_cross_entropy": lambda outputs, labels: float(jnp.mean(
            _expected_cross_entropy(
                jnp.asarray(labels), jax.tree.map(jnp.asarray, outputs)
            )
        )),
    }


def custom_model(use_bf16: bool = True, **config):
    """`config`: the source's `config.json` keys this model reads (see
    `OuroConfig`; `total_ut_steps` among them), plus `sandwich_norm`,
    `loop_norm`, `exit_beta`, `attn_impl` and `remat` (rematerialise each
    application of a decoder layer in the backward pass)."""
    unknown = set(config) - set(OuroConfig.__dataclass_fields__)
    if unknown:
        raise ValueError(f"ouro_lm has no parameter(s) {sorted(unknown)}")
    cfg = OuroConfig(dtype=jnp.bfloat16 if use_bf16 else jnp.float32, **config)
    if cfg.num_attention_heads % cfg.num_key_value_heads:
        raise ValueError(
            "the query heads are a multiple of num_key_value_heads"
        )
    if cfg.total_ut_steps < 1:
        raise ValueError("total_ut_steps is at least 1")
    return OuroLM(cfg)


def optimizer(lr: float = 3e-4, warmup_steps: int = 2000):
    """AdamW (b1 0.9, b2 0.95, weight decay 0.1) whose rate rises linearly
    to `lr` over the first `warmup_steps` steps and stays, as a
    pre-training job's first steps run.  The source's `config.json` names
    no optimizer: these numbers are recalled from its report."""
    return warmup_adamw(lr, warmup_steps, b1=0.9, b2=0.95, weight_decay=0.1)
