"""The models' sublayers compile for the chip and fit it.

As `tests/test_tpu_compile.py` (the kernels):
the TPU compiler in the sandbox compiles for a DESCRIBED `v5e:2x2` device
from shapes alone, at the real widths of the cell, and nothing runs.  Here
are the pieces between a kernel and a whole program: the banded XLA
attention engine, Qwen3-Next's sublayers and the expert layer at a cell's
shapes, the Mamba-2 mixer, and the DeltaNet sublayer on a four-chip mesh.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from elasticdl_tpu.parallel.mesh import DATA_AXIS
from lm_contract import four_chip_mesh

pytestmark = pytest.mark.usefixtures("no_persistent_cache")


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
def test_xla_band_compiles_for_v5e(topo, backward):
    """A sliding layer of Laguna (model_zoo/laguna): 64 heads of 128 over
    8 key-value heads, a band of 512 keys at T = 8192.  The XLA block
    engine is the one engine a band has: no custom call, K and V never
    repeated, and one [8, 8, 256, 256] slab of scores alive at a time
    (blocks of half the window), so the temporaries stay small beside
    q, k, v and the output (128 + 2 x 16 + 128 MiB of bfloat16)."""
    from elasticdl_tpu.ops import gqa

    def out(q, k, v):
        return gqa.causal_attention(q, k, v, window=512)

    def loss(q, k, v):
        return jnp.sum(out(q, k, v).astype(jnp.float32))

    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [
        jax.ShapeDtypeStruct((1, 8192, heads, 128), jnp.bfloat16,
                             sharding=one_chip)
        for heads in (64, 8, 8)
    ]
    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else out
    compiled = jax.jit(fn).lower(*args).compile()
    memory = compiled.memory_analysis()
    print("xla band bytes", backward, memory.temp_size_in_bytes)
    assert "tpu_custom_call" not in compiled.as_text()
    assert memory.temp_size_in_bytes < 0.5e9


# The hybrid expert model's sublayers (model_zoo/qwen3_next) at the
# widths and the 2 x 8192 tokens of `qwen3-next.train-synth-8k`: XLA ops
# (`gdn_pallas`: the DeltaNet sublayer as a TPU backend traces it, its
# rule in the Pallas kernels; a described device leaves
# `jax.default_backend()` at the CPU, so the test says "tpu" for it),
# so what the compile shows is that forward and backward FIT, with
# the temporaries that decided their form (the whole-sequence delta rule
# needed 10.2 GB where the grouped scan needs 6.6 with float32 projection
# results; independent rematerialised query blocks 11.3 GB where the
# scanned engine needs 1.3).
_HYBRID_TOKENS = (2, 8192, 2048)


def _hybrid_sublayer(kind, mesh=None):
    from elasticdl_tpu.layers.moe import SparseMoeBlock
    from model_zoo.qwen3_next import qwen3_next_lm as zoo

    bf16 = jnp.bfloat16
    if kind in ("gdn", "gdn_pallas"):
        return (zoo.GatedDeltaNet(16, 32, 128, 128, 4, 1e-6, bf16, mesh),
                bf16, 7.5)
    if kind == "attn":
        return (zoo.GatedAttention(16, 2, 256, 64, 1e7, 1e-6, bf16, "xla"),
                bf16, 2.0)
    return (SparseMoeBlock(512, 10, 512, 512, (240, 16), True, bf16),
            jnp.float32, 1.0)


def _sublayer_fwd_bwd(module, dtype, weights, tokens):
    """The sublayer's forward and backward compiled for the described
    device(s) the two shardings name."""
    variables = jax.eval_shape(
        lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros(_HYBRID_TOKENS, dtype)
        )
    )

    def fwd_bwd(variables, x):
        def total(params, x):
            return jnp.sum(module.apply(
                {**variables, "params": params}, x
            ).astype(jnp.float32))

        return jax.grad(total, argnums=(0, 1))(variables["params"], x)

    return jax.jit(fwd_bwd).lower(
        jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=weights),
            variables,
        ),
        jax.ShapeDtypeStruct(_HYBRID_TOKENS, dtype, sharding=tokens),
    ).compile()


@pytest.mark.parametrize("kind", ["gdn", "gdn_pallas", "attn", "moe"])
def test_hybrid_sublayer_compiles_and_fits_for_v5e(topo, kind, monkeypatch):
    module, dtype, temp_gb = _hybrid_sublayer(kind)
    if kind == "gdn_pallas":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(jax, "device_count", lambda: 1)
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = _sublayer_fwd_bwd(module, dtype, one_chip, one_chip)
    assert compiled.memory_analysis().temp_size_in_bytes < temp_gb * 1e9
    for kernel in ("delta_rule_bwd", "conv_silu_bwd", "gated_norm_bwd"):
        assert (kernel in compiled.as_text()) == (kind == "gdn_pallas")


def test_expert_layer_compiles_at_the_shapes_block_for_v5e(topo):
    """`deepseek-v2-lite.train-synth-8k`'s expert layer (8 of 64 experts
    of width 1408, top-6, 2 x 8192 tokens: 1,536 pairs an expert) told
    no block: the loop's body gathers blocks of 512 rows, rematerialised
    as the cell runs it, within 1 GB of temporaries."""
    from elasticdl_tpu.layers.moe import SparseMoeBlock, block_rows_for

    module = SparseMoeBlock(
        64, 6, 1408, 2816, (0, 8), False, jnp.bfloat16, shared_gated=False
    )
    tokens = (2, 8192, 2048)
    assert block_rows_for(2 * 8192, 6, 64) == 512
    variables = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros(tokens, jnp.float32)
    ))

    def fwd_bwd(variables, x):
        @jax.checkpoint
        def total(params, x):
            return jnp.sum(module.apply({**variables, "params": params}, x))

        return jax.grad(total, argnums=(0, 1))(variables["params"], x)

    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = jax.jit(fwd_bwd).lower(
        jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            variables,
        ),
        jax.ShapeDtypeStruct(tokens, jnp.float32, sharding=one_chip),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
    text = compiled.as_text()
    assert "bf16[512,2048]" in text and "bf16[128,2048]" not in text


# The Mamba-2 sublayer (`model_zoo/lm_common.py`) at the widths and the
# 1 x 8192 tokens of `nemotron3-nano.train-synth-8k`: XLA ops, so what its
# compile shows is that forward and backward fit, the decays of 64 heads x
# 64 chunks ([128, 128] float32 each, 268 MB) among the temporaries.
def test_mamba2_sublayer_compiles_and_fits_for_v5e(topo):
    from model_zoo.nemotron_h import nemotron_h_lm as zoo

    module = zoo.Mamba2Mixer(64, 64, 8, 128, 4, 128, 1e-5, jnp.bfloat16)
    one_chip = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((1, 8192, 2688), jnp.float32, sharding=one_chip)
    variables = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype))
    )

    def fwd_bwd(variables, x):
        return jax.grad(
            lambda p, x: jnp.sum(module.apply({"params": p}, x)), (0, 1)
        )(variables["params"], x)

    compiled = jax.jit(fwd_bwd).lower(
        jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            variables,
        ), x,
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 3.0e9  # 2.34


@pytest.mark.parametrize("names_mesh", [True, False])
def test_delta_rule_sublayer_compiles_on_four_chip_mesh(
    topo, names_mesh, monkeypatch
):
    """The DeltaNet sublayer as `dp_trainer` compiles it on a four-chip
    host: weights on every chip, the two sequences split over `data`.
    A Mosaic kernel cannot be partitioned automatically, so the model
    hands the rule the job's mesh and the kernels run a sequence a
    device under a shard_map; a trace that names no mesh keeps the XLA
    engine, which compiles for the four as it did before the kernels."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    mesh = four_chip_mesh(topo)
    module, dtype, _ = _hybrid_sublayer(
        "gdn_pallas", mesh if names_mesh else None
    )
    compiled = _sublayer_fwd_bwd(
        module, dtype, NamedSharding(mesh, P()),
        NamedSharding(mesh, P(DATA_AXIS)),
    )
    assert ("delta_rule_bwd" in compiled.as_text()) == names_mesh
