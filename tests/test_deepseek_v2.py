"""DeepSeek-V2 on the normal training path (ISSUE 32): the zoo model with
its latent attention, leading dense layer, softmax-routed expert layers
with an ungated shared expert and the sequence-wise balancing loss,
against the plain reference that decides the benchmark cell's `correct`
(`perfbench/configs/deepseek_v2_reference.py`, which shares no code with
the program).  The contract's cases are `tests/lm_contract.py`'s, at
`tests/spec_deepseek_v2.py`'s `SPEC` (the model as a job runs it:
`tests/test_deepseek_v2_program.py`); the expert layer's own are
`tests/test_moe.py`'s.  Tiny sizes, seeded random weights, float32 on the
CPU, so tolerances are those of float32 summation order: 1e-5 of the
outputs' size, gradients 2e-3 of each leaf's largest entry as for the other
hybrid models.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lm_contract import (  # noqa: F401  (the contract's cases, collected here)
    _model_kwargs, _rel, bf16_case, lm, program_and_reference,
    pytest_generate_tests,
    test_benchmark_cost_functions_count_what_they_say,
    test_bf16_program_is_the_reference_at_the_stated_precision,
    test_float32_products_ask_for_their_precision,
    test_full_size_configuration_counts_the_parameters_it_states,
    test_gradients_match_the_reference
    as test_gradients_match_the_reference_with_the_balancing_loss_added,
    test_logits_and_loss_match_the_reference,
    test_the_cell_checks_precisions_the_reference_has,
)
# `lm` hands the cases this SPEC
from spec_deepseek_v2 import SPEC, TINY, ref, zoo  # noqa: F401


def test_planted_fault_reads_far_from_the_program(program_and_reference):
    """`no_mscale` is the reference with the softmax scale left without
    YaRN's mscale^2 (1.59): the cell reports the program's distance to it."""
    program, _, params, tokens, model = program_and_reference
    fault = ref.forward(params, tokens, model, "no_mscale")
    assert _rel(program(params), fault) > 100 * 1e-5


def test_parameter_names_and_layouts_follow_the_source():
    module = zoo.custom_model(use_bf16=False, **_model_kwargs(TINY))
    shapes = jax.eval_shape(
        module.init, jax.random.PRNGKey(0), ref.sample(0, 1, TINY)
    )["params"]
    m = TINY
    d, h = m["hidden_size"], m["num_attention_heads"]
    nope, rope, dv, rank = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                            m["v_head_dim"], m["kv_lora_rank"])
    assert set(shapes) == {"model", "lm_head"}
    stack = shapes["model"]
    assert set(stack) == {"embed_tokens", "norm", "layers_0", "layers_1",
                          "layers_2"}
    attn = {k: jax.tree.leaves(v)[0].shape
            for k, v in stack["layers_1"]["self_attn"].items()}
    assert attn == {
        "q_proj": (d, h * (nope + rope)),
        "kv_a_proj_with_mqa": (d, rank + rope),
        "kv_a_layernorm": (rank,),
        "kv_b_proj": (rank, h * (nope + dv)),
        "o_proj": (h * dv, d),
    }
    dense = stack["layers_0"]["mlp"]
    assert set(dense) == {"gate_proj", "up_proj", "down_proj"}
    assert dense["up_proj"]["kernel"].shape == (d, m["intermediate_size"])
    experts = stack["layers_1"]["mlp"]
    assert set(experts) == {
        "gate", "experts_gate_proj", "experts_up_proj", "experts_down_proj",
        "shared_experts",
    }  # no `shared_expert_gate`: the shared experts are ungated
    assert experts["gate"].shape == (d, m["n_routed_experts"])
    assert experts["experts_up_proj"].shape == (
        m["experts_held"], d, m["moe_intermediate_size"]
    )
    assert experts["shared_experts"]["down_proj"]["kernel"].shape == (
        m["n_shared_experts"] * m["moe_intermediate_size"], d
    )
    with pytest.raises(ValueError):
        zoo.custom_model(q_lora_rank=1536)
    with pytest.raises(ValueError):
        zoo.custom_model(no_such_key=1)
    with pytest.raises(ValueError):
        zoo.custom_model(seq_aux=False)


def test_optimizer_warms_up():
    import optax

    tx = zoo.optimizer(lr=1e-2, warmup_steps=4)
    params = {"w": jnp.ones((3,))}
    state = tx.init(params)
    sizes = []
    for _ in range(6):
        updates, state = tx.update({"w": jnp.ones((3,))}, state, params)
        sizes.append(float(jnp.abs(updates["w"]).max()))
        params = optax.apply_updates(params, updates)
    # Adam's first steps move by about the rate: n / 4 of it, then all
    ratios = np.asarray(sizes[:4]) / sizes[4]
    np.testing.assert_allclose(ratios, [0.25, 0.5, 0.75, 1.0], rtol=0.15)
    assert sizes[5] == pytest.approx(sizes[4], rel=0.1)
