"""Granite 4.0-H on the normal training path (ISSUE 38): the zoo model (a
block of two sublayers under multipliers, a tied head over a sliced
vocabulary, the Mamba-2 mixer at one group and the attention without a
position embedding of `model_zoo/lm_common.py`) against the plain reference
that decides the benchmark cell's `correct`
(`perfbench/configs/granite_hybrid_reference.py`, which shares no code
with the program).  The contract's cases are `tests/lm_contract.py`'s, at
`tests/spec_granite_hybrid.py`'s `SPEC` (the model as a job runs it:
`tests/test_granite_hybrid_program.py`); the state-space form's own are
`tests/test_ssd.py`, and
what is this model's alone (each multiplier and the tie against a program
that leaves it out, the planted faults, the tied table's two gradients,
the traced step's scopes) is `tests/test_granite_hybrid_pieces.py`.
Tiny sizes, seeded random weights, float32 on the CPU, so tolerances are
those of float32 summation order: 1e-5 of the outputs' size for the
logits, the loss and every gradient leaf.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lm_contract import (  # noqa: F401  (the contract's cases, collected here)
    _model_kwargs, bf16_case, lm, mamba_mixer_in_its_kernels,
    program_and_reference, pytest_generate_tests,
    test_benchmark_cost_functions_count_what_they_say,
    test_bf16_program_is_the_reference_at_the_stated_precision,
    test_float32_products_ask_for_their_precision,
    test_full_size_configuration_counts_the_parameters_it_states,
    test_gradients_match_the_reference,
    test_logits_and_loss_match_the_reference,
    test_the_cell_checks_precisions_the_reference_has,
)
# `lm` hands the cases this SPEC
from spec_granite_hybrid import SPEC, CONFIG, TINY, ref, zoo  # noqa: F401


def test_parameter_names_and_layouts_follow_the_source():
    m = TINY
    module = zoo.custom_model(use_bf16=False, **_model_kwargs(m))
    shapes = jax.tree.map(
        lambda a: a.shape,
        jax.eval_shape(
            module.init, jax.random.PRNGKey(0), ref.sample(0, 1, m)
        )["params"]["model"],
    )
    d, inner = m["hidden_size"], m["mamba_n_heads"] * m["mamba_d_head"]
    bc = m["mamba_n_groups"] * m["mamba_d_state"]
    mlp = {
        "input_linear": {"kernel": (d, 2 * m["shared_intermediate_size"])},
        "output_linear": {"kernel": (m["shared_intermediate_size"], d)},
    }
    norms = {
        "input_layernorm": {"weight": (d,)},
        "post_attention_layernorm": {"weight": (d,)},
    }
    assert shapes["layers_0"] == dict(norms, shared_mlp=mlp, mamba={
        "in_proj": {"kernel": (d, 2 * inner + 2 * bc + m["mamba_n_heads"])},
        "conv1d": {"kernel": (m["mamba_d_conv"], inner + 2 * bc),
                   "bias": (inner + 2 * bc,)},
        "A_log": (m["mamba_n_heads"],), "D": (m["mamba_n_heads"],),
        "dt_bias": (m["mamba_n_heads"],), "norm": (inner,),
        "out_proj": {"kernel": (inner, d)},
    })
    heads, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                     m["head_dim"])
    assert shapes["layers_5"] == dict(norms, shared_mlp=mlp, self_attn={
        "q_proj": {"kernel": (d, heads * hd)},
        "k_proj": {"kernel": (d, kv * hd)},
        "v_proj": {"kernel": (d, kv * hd)},
        "o_proj": {"kernel": (heads * hd, d)},
    })
    assert set(shapes) == (
        {f"layers_{i}" for i in range(10)} | {"embed_tokens", "norm"}
    )


def test_mamba_sublayer_in_its_kernels_matches_the_reference(monkeypatch):
    """One group of 256 columns over rows of 384: widths the passes'
    kernels take (`ops/gdn_passes.py`), which the tiny ones are not."""
    m = dict(TINY, mamba_d_head=64, mamba_d_state=64)
    mamba_mixer_in_its_kernels(monkeypatch, zoo.Mamba2Mixer(
        m["mamba_n_heads"], m["mamba_d_head"], m["mamba_n_groups"],
        m["mamba_d_state"], m["mamba_d_conv"], m["mamba_chunk_size"],
        m["rms_norm_eps"], jnp.float32,
    ), ref._mamba2, m)


def test_published_forty_layers_and_the_cut_are_the_same_code():
    published = CONFIG["published"]["layer_types"]
    assert len(published) == CONFIG["published"]["num_hidden_layers"] == 40
    assert [i for i, k in enumerate(published) if k == "attention"] == [
        5, 15, 25, 35
    ]
    assert CONFIG["model"]["layer_types"] == published[:10]
    tokens = ref.sample(0, 1, TINY)
    widths = _model_kwargs(TINY)

    def layers(**config):
        module = zoo.custom_model(**dict(widths, **config))
        params = jax.eval_shape(
            module.init, jax.random.PRNGKey(0), tokens
        )["params"]["model"]
        return [
            "mamba" if "mamba" in params[f"layers_{i}"] else "attention"
            for i in range(len(params) - 2)
        ]

    whole = layers(layer_types=published, num_hidden_layers=40)
    assert whole == published
    assert whole.count("mamba") == 36 and whole.count("attention") == 4
    # the cut: the same list, its first ten entries; and as a job's flags
    # carry a list
    assert layers(layer_types=published, num_hidden_layers=10) == whole[:10]
    assert layers(
        layer_types="/".join(published), num_hidden_layers=0
    ) == whole


def test_configuration_keys_are_checked():
    with pytest.raises(ValueError, match="routed variant"):
        zoo.custom_model(num_local_experts=8)
    with pytest.raises(ValueError, match="no_such_key"):
        zoo.custom_model(no_such_key=1)
    with pytest.raises(ValueError, match="not made of"):
        zoo.custom_model(layer_types="mamba/full_attention")
    with pytest.raises(ValueError, match="lists 2 layers of 3"):
        zoo.custom_model(layer_types="mamba/attention", num_hidden_layers=3)


def test_optimizer_warms_up_and_decays():
    """Step n of the warm-up runs AdamW at lr n / warmup_steps."""
    params = {"embed_tokens": jnp.ones((3, 2))}
    grads = jax.tree.map(jnp.ones_like, params)
    tx = zoo.optimizer(lr=1e-2, warmup_steps=4)
    state = tx.init(params)
    for n in range(1, 7):
        updates, state = tx.update(grads, state, params)
        rate = 1e-2 * min(1.0, n / 4)
        np.testing.assert_allclose(
            updates["embed_tokens"], -rate * (1 + 0.01), rtol=1e-4
        )
