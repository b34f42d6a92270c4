"""Qwen3-Next on the normal training path (ISSUE 26): the zoo model with
its Gated DeltaNet and gated attention layers and the expert layer that
holds a range of the experts, against the plain reference that decides the
benchmark cell's `correct` (`perfbench/configs/qwen3_next_reference.py`,
which shares no code with the program).  The contract's cases are
`tests/lm_contract.py`'s, at `tests/spec_qwen3_next.py`'s `SPEC` (the model
as a job runs it: `tests/test_qwen3_next_program.py`); the delta rule's,
its passes' and the expert layer's own are `tests/test_gated_delta.py`,
`tests/test_gdn_passes.py` and `tests/test_moe.py`; the model's sublayers'
(the DeltaNet layer in one layout, rotary and grouped-query heads) are
`tests/test_qwen3_next_layers.py`.  Tiny sizes, seeded
random weights, float32 on the CPU, so tolerances are those of float32
summation order: 1e-5 of the outputs' size for one operator, 5e-5 for the
whole model (at head size 16 a DeltaNet layer amplifies a rounding of its
input about fivefold, three in a row); gradients 2e-3 of each leaf's
largest entry, through four layers and a softmax.
"""

import jax
import jax.numpy as jnp

from lm_contract import (  # noqa: F401  (the contract's cases, collected here)
    _model_kwargs, bf16_case, lm, program_and_reference,
    pytest_generate_tests,
    test_benchmark_cost_functions_count_what_they_say,
    test_bf16_program_is_the_reference_at_the_stated_precision,
    test_float32_products_ask_for_their_precision,
    test_full_size_configuration_counts_the_parameters_it_states,
    test_gradients_match_the_reference,
    test_logits_and_loss_match_the_reference,
    test_the_cell_checks_precisions_the_reference_has,
)
from spec_qwen3_next import SPEC, TINY, zoo  # noqa: F401  (`lm` reads SPEC)


def test_parameter_names_and_layouts_follow_the_source():
    module = zoo.custom_model(use_bf16=False, **_model_kwargs(TINY))
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    assert set(shapes) == {
        "embed_tokens", "layers_0", "layers_1", "layers_2", "layers_3",
        "norm", "lm_head",
    }
    assert set(shapes["layers_0"]) == {
        "input_layernorm", "linear_attn", "post_attention_layernorm", "mlp",
    }
    assert set(shapes["layers_3"]) == {
        "input_layernorm", "self_attn", "post_attention_layernorm", "mlp",
    }
    hk, hv = TINY["linear_num_key_heads"], TINY["linear_num_value_heads"]
    dk, dv = TINY["linear_key_head_dim"], TINY["linear_value_head_dim"]
    linear = shapes["layers_0"]["linear_attn"]
    assert linear["in_proj_qkvz"]["kernel"].shape == (
        TINY["hidden_size"], 2 * hk * dk + 2 * hv * dv)
    assert linear["in_proj_ba"]["kernel"].shape == (
        TINY["hidden_size"], 2 * hv)
    assert linear["conv1d"].shape == (4, 2 * hk * dk + hv * dv)
    mlp = shapes["layers_0"]["mlp"]
    assert mlp["gate"].shape == (TINY["hidden_size"], TINY["num_experts"])
    assert mlp["experts_gate_proj"].shape == (
        TINY["experts_held"], TINY["hidden_size"],
        TINY["moe_intermediate_size"])
