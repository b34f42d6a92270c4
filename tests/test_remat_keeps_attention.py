"""What a rematerialised layer keeps (`model_zoo/lm_common.py`
`KEEP_ATTENTION_RESULTS`): the attention engine's two results, named in
both engines' forward rules (`ops/gqa.py` `ATTN_OUT`, `ATTN_LSE`).

At rehearsal widths on the CPU, a stack rematerialised WITH the policy
gives the loss and every gradient leaf of the same stack rematerialised
without it, bit for bit (the kept values are the values the second forward
produced), and its differentiated program holds one engine forward fewer
for every attention layer: the XLA engine under its three mask rules
(Mellum 2: a banded layer and a causal one; SDAR: block diffusion) and the
Pallas kernel in interpret mode (Ouro, in its scan's one body).  Bit for
bit holds where the engine's loops ARE loops in both programs: a query
loop of ONE tile (Mellum 2's causal layer at its 256 rehearsal tokens) is
unrolled by XLA's CPU compiler and fused with its neighbours, another way
in each program, and every leaf then differs in its last bit (2e-9 to
7e-8), so that stack is read at 1024 tokens, two tiles of 512.
That the cells' own programs hold no rematerialised engine is each
stack's `test_window_program_compiles_and_fits_for_v5e`.
"""

from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spec_mellum
import spec_ouro
import spec_sdar
from elasticdl_tpu.ops import gqa
from lm_contract import _eqns, _perturbed, _tokens

#: id -> (the descriptor, its rehearsal widths' changes and what its job
#: says besides `remat`, the one
#: equation a run of the engine's forward is, as (primitive, the kernel
#: that holds it): the XLA engine's key loop, whose bounds are traced (its
#: query loop is a `scan`), or the kernel's call; the attention layers of
#: the differentiated program)
STACKS = {
    "xla-causal-and-band": (
        spec_mellum.SPEC,
        dict(num_hidden_layers=2, sample_tokens=1024,
             layer_types=["sliding_attention", "full_attention"],
             mlp_layer_types=["sparse", "sparse"]),
        {"attn_impl": "xla"}, ("while", None), 2,
    ),
    "xla-block-diffusion": (
        spec_sdar.SPEC, {}, {"attn_impl": "xla"}, ("while", None), 3,
    ),
    # a scan over the passes: ONE body of three layers
    "pallas-interpret": (
        spec_ouro.SPEC, {}, {"attn_impl": "pallas"}, ("pallas_call", None),
        3,
    ),
}


def _differentiated(spec, model, flags, policy, monkeypatch):
    """-> (loss, gradients, the equations of the differentiated program
    counted by (primitive, the kernel that holds them)) of the stack at
    the widths `model`, each layer rematerialised under `policy`."""
    monkeypatch.setattr(spec.zoo, "KEEP_ATTENTION_RESULTS", policy)
    module = spec.build(model, use_bf16=False, remat=True, **flags)
    features = spec.ref.sample(3, 2, model)
    variables = dict(module.init(jax.random.PRNGKey(0), features))
    params = _perturbed(variables.pop("params"), 1)

    def program(p):
        return spec.zoo.loss(
            _tokens(features),
            module.apply({"params": p, **variables}, features),
        )

    differentiated = jax.value_and_grad(program)
    counts = Counter(
        (eqn.primitive.name, kernel)
        for eqn, kernel in _eqns(jax.make_jaxpr(differentiated)(params).jaxpr)
    )
    return (*jax.jit(differentiated)(params), counts)


@pytest.mark.parametrize("stack", list(STACKS))
def test_kept_results_leave_loss_and_gradients_equal(stack, monkeypatch):
    spec, changes, flags, engine, layers = STACKS[stack]
    model = dict(spec.tiny, **changes)
    kept = spec.zoo.KEEP_ATTENTION_RESULTS
    assert kept is not None  # the stack's `nn.remat` is handed the policy
    loss, grads, counts = _differentiated(
        spec, model, flags, kept, monkeypatch
    )
    plain_loss, plain_grads, plain = _differentiated(
        spec, model, flags, None, monkeypatch
    )
    # one forward of the engine fewer for every attention layer, and
    # nothing else of the program changes its count
    assert plain[engine] - counts[engine] == layers, (
        plain[engine], counts[engine]
    )
    assert counts[engine] > 0
    np.testing.assert_array_equal(loss, plain_loss)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == len(jax.tree.leaves(plain_grads)) > 0
    for (path, got), want in zip(flat, jax.tree.leaves(plain_grads)):
        np.testing.assert_array_equal(
            got, want, err_msg=jax.tree_util.keystr(path)
        )


def test_the_forward_rule_names_each_result_once():
    """`_gqa_fwd` holds the two names once each, on its results (a forward
    pass alone, serving or evaluation, reads them as the identity)."""
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, 2, 128, 16)), jnp.float32)
        for _ in range(3)
    )
    text = str(jax.make_jaxpr(
        lambda *qkv: gqa._gqa_fwd(*qkv, 64, None, gqa.Causal())
    )(q, k, v))
    for name in (gqa.ATTN_OUT, gqa.ATTN_LSE):
        assert text.count(f"name[name={name}]") == 1, name
