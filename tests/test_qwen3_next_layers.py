"""Qwen3-Next's sublayers (ISSUE 26, 29, 31): the Gated DeltaNet layer in
the one layout a TPU runs it in, against the body it had and without a
relayout between its projections and the rule; rotary on a part of the
head, grouped-query heads and the attention engine's choice; the
reference's blocked attention against its whole form.  The model against
its reference is `tests/test_qwen3_next.py`, its descriptor
`tests/spec_qwen3_next.py` (a file of the model's own, so that `--dist
loadfile` gives each a worker).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import gated_delta, gdn_passes, gqa
from elasticdl_tpu.ops.gated_delta import chunk_gated_delta_rule_xla
from lm_contract import (
    _close, _eqns, _log_lines, _perturbed, engines_as_on_a_tpu,
)
from spec_qwen3_next import TINY, ref, zoo


def test_layer_moves_no_tensor_and_its_passes_are_float32(monkeypatch):
    """The DeltaNet sublayer as a TPU traces it at the cell's shapes
    (abstract), forward and backward: between the projections and the
    rule, and between the rule and the out-projection, no tensor of
    B T 2048 elements or more is reshaped, transposed, split,
    concatenated, padded or sliced (each a relayout or a copy of 270-800
    MB on a TPU); and inside the four kernels of the passes every
    floating-point value is float32, but for the bfloat16 the gated norm
    hands the out-projection and takes back as its cotangent."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    module = zoo.GatedDeltaNet(16, 32, 128, 128, 4, 1e-6, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((2, 8192, 2048), jnp.float32)
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda v, x: jnp.sum(module.apply(v, x).astype(jnp.float32)),
        argnums=(0, 1),
    ))(variables, x).jaxpr
    moves = ("reshape", "transpose", "concatenate", "split", "pad", "slice",
             "dynamic_slice", "gather", "squeeze", "expand_dims")
    kernels = {}
    for eqn, kernel in _eqns(jaxpr):
        avals = [v.aval for v in list(eqn.invars) + list(eqn.outvars)
                 if hasattr(v.aval, "shape")]
        if kernel is None:
            if eqn.primitive.name in moves:
                assert max(
                    int(np.prod(a.shape)) for a in avals
                ) < 2 * 8192 * 2048, eqn
            continue
        kernels[kernel] = kernels.get(kernel, 0) + 1
        if kernel.startswith(("conv_silu", "gated_norm")):
            for aval in avals:
                if not jnp.issubdtype(aval.dtype, jnp.floating):
                    continue
                assert aval.dtype == jnp.float32 or (
                    kernel.startswith("gated_norm")
                    and aval.dtype == jnp.bfloat16
                    and eqn.primitive.name in (
                        "get", "swap", "convert_element_type"
                    )
                ), (kernel, eqn)
    assert set(kernels) == {
        "conv_silu_fwd", "conv_silu_bwd", "gated_norm_fwd", "gated_norm_bwd",
        "delta_rule_fwd", "delta_rule_bwd",
    }



def _parent_gated_delta_net(params, x, hk, hv, dk, dv, eps):
    """`GatedDeltaNet.__call__` as it was before the layer kept one
    layout (float32): the projection's result viewed by key head, split,
    concatenated, padded and shifted, [B, T, H, D] into the rule."""
    b, t, _ = x.shape
    r = hv // hk
    qkvz = (x @ params["in_proj_qkvz"]["kernel"]).reshape(
        b, t, hk, 2 * dk + 2 * r * dv
    )
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    ba = (x @ params["in_proj_ba"]["kernel"]).reshape(b, t, hk, 2 * r)
    beta_in, a = ba[..., :r].reshape(b, t, hv), ba[..., r:].reshape(b, t, hv)
    mixed = jnp.concatenate(
        [q.reshape(b, t, hk * dk), k.reshape(b, t, hk * dk),
         v.reshape(b, t, hv * dv)], axis=-1,
    )
    conv = params["conv1d"]
    padded = jnp.pad(mixed, ((0, 0), (conv.shape[0] - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(
        padded[:, j:j + t] * conv[j] for j in range(conv.shape[0])
    ))
    q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)

    def l2norm(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    g = -jnp.exp(params["A_log"]) * jax.nn.softplus(a + params["dt_bias"])
    out, _ = chunk_gated_delta_rule_xla(
        l2norm(q.reshape(b, t, hk, dk)) / np.sqrt(dk),
        l2norm(k.reshape(b, t, hk, dk)), v.reshape(b, t, hv, dv),
        g, jax.nn.sigmoid(beta_in),
    )
    out = out * jax.lax.rsqrt(
        jnp.mean(out * out, axis=-1, keepdims=True) + eps
    )
    out = params["norm"] * out * jax.nn.silu(z.reshape(b, t, hv, dv))
    return out.reshape(b, t, hv * dv) @ params["out_proj"]["kernel"]


@pytest.mark.parametrize("t,hk,hv", [(200, 1, 2), (320, 2, 4), (200, 1, 1)])
def test_layer_in_one_layout_is_the_layer_it_was(t, hk, hv, monkeypatch):
    """The whole DeltaNet sublayer on the path a TPU takes (the passes
    and, where it takes the heads, the rule in their kernels) against
    the body it had, from the same parameters in the source's column
    order, at float32: forward to 1e-5, every gradient to 1e-4 of its
    rms."""
    engines_as_on_a_tpu(monkeypatch)
    module = zoo.GatedDeltaNet(hk, hv, 128, 128, 4, 1e-6, jnp.float32)
    rng = np.random.default_rng(t)
    x = jnp.asarray(rng.normal(size=(2, t, 64)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(2, t, 64)), jnp.float32)
    params = _perturbed(module.init(jax.random.PRNGKey(0), x)["params"], 3)

    def new(p, x):
        return module.apply({"params": p}, x)

    def old(p, x):
        return _parent_gated_delta_net(p, x, hk, hv, 128, 128, 1e-6)

    with jax.default_matmul_precision("highest"):
        _close(new(params, x), old(params, x), 1e-5, "forward")
        got, want = (
            jax.grad(lambda p, x: jnp.sum(f(p, x) * weight), argnums=(0, 1))(
                params, x
            )
            for f in (new, old)
        )
    flat = jax.tree_util.tree_leaves_with_path(got)
    assert len(flat) == 8  # seven parameters' gradients and x's
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        _close(g, w, 1e-4, jax.tree_util.keystr(path))



# ---------------------------------------------------------------------------
# Rotary and grouped-query heads
# ---------------------------------------------------------------------------


def test_rotary_matches_the_reference_and_leaves_the_rest():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 40, 3, 32)), jnp.float32)
    cos, sin = gqa.rotary_tables(jnp.arange(40), 8, 1e7)
    got = gqa.apply_rotary(x, cos, sin)
    for row in range(2):
        want = ref._rotate(x[row], jnp.arange(40), 8, 1e7)
        np.testing.assert_allclose(got[row], want, atol=1e-6)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-7)  # position 0


def _explicit_attention(q, k, v):
    """Each key-value head repeated to its query heads, full scores."""
    b, t, hq, d = q.shape
    group = hq // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("t,block", [(150, 512), (256, 64), (512, 128)])
def test_grouped_query_attention_matches_the_explicit_form(t, block):
    rng = np.random.default_rng(t)
    q = jnp.asarray(rng.normal(size=(2, t, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, t, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, t, 2, 16)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

    def engine(q, k, v):
        return gqa.causal_attention(q, k, v, impl="xla", block=block)

    np.testing.assert_allclose(
        engine(q, k, v), _explicit_attention(q, k, v), atol=2e-6
    )
    got = jax.grad(lambda *a: jnp.sum(engine(*a) * weight), (0, 1, 2))(q, k, v)
    want = jax.grad(
        lambda *a: jnp.sum(_explicit_attention(*a) * weight), (0, 1, 2)
    )(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5)


@pytest.mark.parametrize("t,d,engine", [
    (8192, 256, "xla causal_gqa_attention"),   # K+V of a head exceed VMEM
    (1024, 64, "pallas flash_attention"),
])
def test_engine_choice_on_a_tpu_backend(t, d, engine, monkeypatch):
    """`impl="auto"` on a TPU: the Pallas kernel where `supports(T, D)`
    holds, the XLA engine otherwise; the worker's log line says which
    (traced only: shapes, no device)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((1, t, 4, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, t, 2, d), jnp.bfloat16)
    lines, handler = _log_lines(gqa.logger)
    try:
        out = jax.eval_shape(gqa.causal_attention, q, kv, kv)
    finally:
        gqa.logger.removeHandler(handler)
    assert out.shape == q.shape
    assert any(
        line.startswith(f"attention engine: {engine} T={t} D={d}")
        for line in lines
    ), lines


def test_reference_attention_is_the_explicit_form():
    model = dict(TINY, sample_tokens=70)
    rng = np.random.default_rng(1)
    d = model["hidden_size"]
    h, hkv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    p = {
        "q_proj": {"kernel": rng.normal(size=(d, 2 * h * hd)) / 8},
        "k_proj": {"kernel": rng.normal(size=(d, hkv * hd)) / 8},
        "v_proj": {"kernel": rng.normal(size=(d, hkv * hd)) / 8},
        "o_proj": {"kernel": np.eye(h * hd)},
        "q_norm": {"weight": np.zeros(hd)}, "k_norm": {"weight": np.zeros(hd)},
    }
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), p)
    x = jnp.asarray(rng.normal(size=(70, d)), jnp.float32)
    blocked = ref._gated_attention(p, x, model, query_block=32)
    whole = ref._gated_attention(p, x, model, query_block=70)
    np.testing.assert_allclose(blocked, whole, atol=1e-6)

