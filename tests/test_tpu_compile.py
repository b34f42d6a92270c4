"""The Pallas kernels of the main path compile for the chip.

Interpret mode (every other kernel test here) cannot see what Mosaic
refuses: a dynamic lane slice, a block that misses the tiling, more
SMEM or VMEM than the chip has.  The TPU compiler is installed in the
sandbox and compiles for a DESCRIBED `v5e:2x2` device from shapes alone
(/opt/skills/guides/on-chip-measurement section 2), so each kernel entry
point is lowered with `interpret=False` at the real widths of the cell
that uses it.  Nothing runs: a pass here is not a chip run.
"""

import functools
import importlib
import json
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from elasticdl_tpu.ops import sparse_embedding as ske
from elasticdl_tpu.parallel import ring_attention, sparse_optim
from elasticdl_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from elasticdl_tpu.parallel.packed import PackedSpec

# `elasticdl_tpu.ops.flash_attention` the attribute is the function (the
# package re-exports it over the submodule's name).
fa = importlib.import_module("elasticdl_tpu.ops.flash_attention")

# DeepFM at full width (bench.py bench_deepfm): 26 fields x vocab
# 100000 resident rows, minibatch 8192, embedding_dim 8 -> the combined
# 1+8 table; (rows, 16) is the unpadded-slot variant of the same table.
ROWS, BATCH, FIELDS = 2_600_000, 8192, 26
N_IDS = BATCH * FIELDS
DEEPFM_TABLE = PackedSpec(ROWS, 9)
# Transformer bench (bench.TRANSFORMER_BENCH: d512 / 8 heads -> D=64)
# and the ring-engine bench shape (bench.RING_BENCH: D=128).
B, T, H = 4, 2048, 8

# Kernel kind -> the hyperparameters its sparse optimizer hands the kernel.
_HYPER = {
    "sgd": sparse_optim.sgd().hyperparams,
    "momentum": sparse_optim.momentum().hyperparams,
    "adagrad": sparse_optim.adagrad().hyperparams,
    "adam": sparse_optim.adam().hyperparams,
    "adam_global": sparse_optim.adam(bias_correction="global").hyperparams,
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no libtpu here: nothing to compile with
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc}")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip (the next one warns and
    recompiles): keep the cache out of these cases."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _flash_fwd(d):
    def fn(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, interpret=False)

    return fn, [((B, T, H, d), jnp.bfloat16)] * 3


def _flash_bwd(d):
    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2)), [((B, T, H, d), jnp.bfloat16)] * 3


def _flash_two_sizes(backward):
    """Latent attention's heads (model_zoo/deepseek_v2): q and k of 192,
    v of 128, at T = 4096, the longest `supports` lets the kernel take
    (K + V of a head are 5 MiB of float32 there), with a scale."""
    def out(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, scale=0.1147, interpret=False
        )

    def loss(q, k, v):
        return jnp.sum(out(q, k, v).astype(jnp.float32))

    shapes = [((2, 4096, 16, d), jnp.bfloat16) for d in (192, 192, 128)]
    return (jax.grad(loss, argnums=(0, 1, 2)) if backward else out), shapes


def _ring_step_carry():
    d = 128

    def fn(q, k, v, acc, lse, q_pos, k_pos):
        return fa.flash_ring_step_carry(
            q, k, v, acc, lse, q_pos, k_pos,
            causal=True, scale=d ** -0.5, interpret=False,
        )

    blk = ((B, H, T, d), jnp.bfloat16)
    return fn, [
        blk, blk, blk,
        ((B, H, T, d), jnp.float32), ((B, H, T, 1), jnp.float32),
        ((T,), jnp.int32), ((T,), jnp.int32),
    ]


def _ring_step_bwd():
    d = 128

    def fn(q, k, v, do, lse, delta, q_pos, k_pos):
        return fa.flash_ring_step_bwd(
            q, k, v, do, lse, delta, q_pos, k_pos,
            causal=True, scale=d ** -0.5, interpret=False,
        )

    blk = ((B, H, T, d), jnp.bfloat16)
    stat = ((B, H, T, 1), jnp.float32)
    return fn, [
        blk, blk, blk, ((B, H, T, d), jnp.float32), stat, stat,
        ((T,), jnp.int32), ((T,), jnp.int32),
    ]


def _fused_lookup():
    spec = PackedSpec(ROWS, 16)

    def fn(packed, ids):
        return ske.fused_lookup(spec, packed, ids, interpret=False)

    return fn, [(spec.packed_shape, jnp.float32), ((N_IDS,), jnp.int32)]


def _fused_lookup_fm():
    spec = DEEPFM_TABLE

    def fn(packed, bet, ids, valid):
        return ske.fused_lookup_fm(
            spec, packed, bet, ids, valid, interpret=False
        )

    return fn, [
        (spec.packed_shape, jnp.float32),
        ((BATCH, FIELDS, spec.dim), jnp.float32),
        ((BATCH, FIELDS), jnp.int32),
        ((BATCH, FIELDS), jnp.bool_),
    ]


def _fused_apply(kind):
    """The apply KERNEL at DeepFM's id count, fed an already
    segment-combined batch: the XLA dedup prologue in front of it is the
    scatter path's own code and takes ~20 s to compile at this size."""
    spec = DEEPFM_TABLE
    n_tables = 1 + len(ske._KIND_SLOTS[kind])

    def fn(safe, gsum, touched, tr, *tables):
        return ske._apply_representatives(
            spec, kind, _HYPER[kind], tables, safe, gsum, touched, tr,
            False, ske.DEFAULT_IDS_PER_TILE,
        )

    return fn, [
        ((N_IDS,), jnp.int32), ((N_IDS, spec.dim), jnp.float32),
        ((N_IDS,), jnp.bool_), ((1, 1), jnp.float32),
    ] + [(spec.packed_shape, jnp.float32)] * n_tables


def _delta_rule(backward, d=128):
    """The delta rule's kernels at the heads and the 2 x 8192 tokens of
    `qwen3-next.train-synth-8k` (16 key and 32 value heads of 128), and
    at heads of 256, where `supports` holds because fewer heads go into
    a grid step (sixteen of them would need 144 MB of a v5e's 128)."""
    from elasticdl_tpu.ops import gated_delta

    assert gated_delta.supports(d, d, 16, 32)

    def fwd(*args):
        return gated_delta.chunk_gated_delta_rule_pallas(
            *args, interpret=False
        )

    def bwd(*args):
        return jax.grad(
            lambda *a: jnp.sum(fwd(*a)[0]), argnums=range(5)
        )(*args)

    qk, v, gate = (2, 8192, 16, d), (2, 8192, 32, d), (2, 8192, 32)
    return bwd if backward else fwd, [
        (shape, jnp.float32) for shape in (qk, qk, v, gate, gate)
    ]


def _gdn_pass(which, backward):
    """The passes around the rule (`ops/gdn_passes.py`) at the cell's
    rows: q's conv + silu + l2-norm over 16 heads of 128, v's conv +
    silu over 32, the gated norm over 32 with its bfloat16 result."""
    from elasticdl_tpu.ops import gdn_passes

    rows = {"q": 16 * 128, "v": 32 * 128, "norm": 32 * 128}[which]
    if which == "norm":
        def fwd(out, gate, weight):
            return gdn_passes.gated_rms_norm(
                out, gate, weight, dtype=jnp.bfloat16, pallas=True,
                interpret=False,
            )

        shapes = [(2, 8192, rows), (2, 8192, rows), (128,)]
    else:
        def fwd(x, taps):
            return gdn_passes.conv_silu(
                x, taps, head=128 if which == "q" else 0, scale=128 ** -0.5,
                pallas=True, interpret=False,
            )

        shapes = [(2, 8192, rows), (4, rows)]

    def bwd(*args):
        return jax.grad(
            lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)),
            argnums=range(len(shapes)),
        )(*args)

    return bwd if backward else fwd, [(s, jnp.float32) for s in shapes]


_CASES = {
    **{
        f"gdn_{which}_{'bwd' if backward else 'fwd'}":
            functools.partial(_gdn_pass, which, backward)
        for which in ("q", "v", "norm") for backward in (False, True)
    },
    "delta_rule_fwd": functools.partial(_delta_rule, False),
    "delta_rule_bwd": functools.partial(_delta_rule, True),
    "delta_rule_bwd_d256": functools.partial(_delta_rule, True, 256),
    "flash_fwd_d64": functools.partial(_flash_fwd, 64),
    "flash_fwd_d128": functools.partial(_flash_fwd, 128),
    "flash_bwd_d64": functools.partial(_flash_bwd, 64),
    "flash_bwd_d128": functools.partial(_flash_bwd, 128),
    "flash_fwd_d192_dv128": functools.partial(_flash_two_sizes, False),
    "flash_bwd_d192_dv128": functools.partial(_flash_two_sizes, True),
    "ring_step_carry": _ring_step_carry,
    "ring_step_bwd": _ring_step_bwd,
    "fused_lookup": _fused_lookup,
    "fused_lookup_fm": _fused_lookup_fm,
    **{
        f"fused_apply_{kind}": functools.partial(_fused_apply, kind)
        for kind in ske._KIND_SLOTS
    },
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_kernel_compiles_for_v5e(topo, case):
    fn, shapes = _CASES[case]()
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


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


def test_qwen3_next_window_program_compiles_and_fits_for_v5e(
    topo, monkeypatch
):
    """`dp_trainer`'s two-step window program as the worker compiles it
    for `qwen3-next.train-synth-8k` (2 x 8192 tokens a step; "tpu" said
    for the engines' choice, as above): 5.09 GB of state donated, and
    with its temporaries 8.93 GB of the chip's 16 (13.06 GB before the
    DeltaNet layers kept one layout, PR 29)."""
    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer
    from model_zoo.qwen3_next import qwen3_next_lm as zoo

    with open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs", "qwen3-next-80b-a3b.json",
    )) as f:
        model = {
            k: v for k, v in json.load(f)["model"].items()
            if k != "sample_tokens"
        }
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    mesh = build_mesh(MeshConfig(data=1, model=1), devices=topo.devices[:1])
    trainer = DataParallelTrainer(
        zoo.custom_model(use_bf16=True, mesh=mesh, remat=True, **model),
        zoo.loss, zoo.optimizer(), mesh,
    )
    on_chip = NamedSharding(mesh, P())
    state, _ = jax.eval_shape(
        lambda: trainer._make_state(
            jax.random.PRNGKey(0), jnp.zeros((2, 8192), jnp.int32)
        )
    )
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip),
        state,
    )
    window = jax.ShapeDtypeStruct((2, 2, 8192), jnp.int32, sharding=on_chip)
    mask = jax.ShapeDtypeStruct((2, 2), jnp.float32, sharding=on_chip)
    compiled = jax.jit(
        trainer._train_window_impl, donate_argnums=(0,)
    ).lower(state, window, window, mask).compile()
    memory = compiled.memory_analysis()
    assert 5.09e9 < memory.argument_size_in_bytes < 5.10e9  # 12 B x 424M
    assert memory.alias_size_in_bytes > 5.09e9              # donated
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert total < 9.5e9, total
    text = compiled.as_text()
    for kernel in ("conv_silu_fwd", "conv_silu_bwd", "gated_norm_fwd",
                   "gated_norm_bwd", "delta_rule_fwd", "delta_rule_bwd"):
        assert kernel in text, kernel


# The state-space hybrid (model_zoo/nemotron_h) at the widths and the
# 1 x 8192 tokens of `nemotron3-nano.train-synth-8k`.  The Mamba-2
# sublayer is XLA ops: what its compile shows is that forward and
# backward fit, the decays of 64 heads x 64 chunks ([128, 128] float32
# each, 268 MB) among the temporaries.  The whole two-step window program
# is what the worker runs: 8.0 GB of state donated and 3.25 GB of
# temporaries with each layer rematerialised (at 2 x 8192 it needs
# 16.8 GB and does not fit), the attention layer in the Pallas kernel
# exactly at `supports`' cap (K + V of a head are 8 MiB of float32).
_NEMOTRON = dict(
    vocab_size=16384, hidden_size=2688, hybrid_override_pattern="MEMEM*EME",
    mamba_num_heads=64, mamba_head_dim=64, n_groups=8, ssm_state_size=128,
    conv_kernel=4, chunk_size=128, num_attention_heads=32,
    num_key_value_heads=2, head_dim=128, n_routed_experts=128,
    num_experts_per_tok=6, moe_intermediate_size=1856,
    moe_shared_expert_intermediate_size=3712, experts_first=56,
    experts_held=8, remat=True,
)


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


def test_nemotron_window_program_compiles_and_fits_for_v5e(topo, monkeypatch):
    """`dp_trainer`'s two-step window program as the worker compiles it
    for the cell (a described device leaves `jax.default_backend()` at the
    CPU, so the test says "tpu" for the attention engine's choice)."""
    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer
    from model_zoo.nemotron_h import nemotron_h_lm as zoo

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = build_mesh(MeshConfig(data=1, model=1), devices=topo.devices[:1])
    trainer = DataParallelTrainer(
        zoo.custom_model(use_bf16=True, **_NEMOTRON), zoo.loss,
        zoo.optimizer(), mesh,
    )
    on_chip = NamedSharding(mesh, P())
    state, _ = jax.eval_shape(
        lambda: trainer._make_state(
            jax.random.PRNGKey(0), jnp.zeros((1, 8192), jnp.int32)
        )
    )
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip),
        state,
    )
    window = jax.ShapeDtypeStruct((2, 1, 8192), jnp.int32, sharding=on_chip)
    mask = jax.ShapeDtypeStruct((2, 1), jnp.float32, sharding=on_chip)
    compiled = jax.jit(
        trainer._train_window_impl, donate_argnums=(0,)
    ).lower(state, window, window, mask).compile()
    memory = compiled.memory_analysis()
    assert 8.0e9 < memory.argument_size_in_bytes < 8.01e9  # 12 B x 667M
    assert memory.alias_size_in_bytes > 8.0e9              # donated
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12.5e9
    assert "tpu_custom_call" in compiled.as_text()         # the flash kernel


# The latent-attention expert model (model_zoo/deepseek_v2) at the widths
# and the 2 x 8192 tokens of `deepseek-v2-lite.train-synth-8k`: the whole
# two-step window program as the worker runs it, 6.42 GB of state donated
# (12 B x 535,060,992), each layer rematerialised, attention in the XLA
# block engine (K and V of a head at 192 and 128 are 10 MiB of float32 at
# T = 8192, past the Pallas kernel's cap).
def test_deepseek_v2_window_program_compiles_and_fits_for_v5e(
    topo, monkeypatch
):
    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer
    from model_zoo.deepseek_v2 import deepseek_v2_lm as zoo

    with open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs", "deepseek-v2-lite.json",
    )) as f:
        model = {
            k: v for k, v in json.load(f)["model"].items()
            if k != "sample_tokens"
        }
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = build_mesh(MeshConfig(data=1, model=1), devices=topo.devices[:1])
    trainer = DataParallelTrainer(
        zoo.custom_model(use_bf16=True, remat=True, **model), zoo.loss,
        zoo.optimizer(), mesh,
    )
    on_chip = NamedSharding(mesh, P())
    state, _ = jax.eval_shape(
        lambda: trainer._make_state(
            jax.random.PRNGKey(0), jnp.zeros((2, 8192), jnp.int32)
        )
    )
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip),
        state,
    )
    window = jax.ShapeDtypeStruct((2, 2, 8192), jnp.int32, sharding=on_chip)
    mask = jax.ShapeDtypeStruct((2, 2), jnp.float32, sharding=on_chip)
    compiled = jax.jit(
        trainer._train_window_impl, donate_argnums=(0,)
    ).lower(state, window, window, mask).compile()
    memory = compiled.memory_analysis()
    print("deepseek window bytes", memory.argument_size_in_bytes,
          memory.temp_size_in_bytes, memory.alias_size_in_bytes)
    assert 6.42e9 < memory.argument_size_in_bytes < 6.43e9  # 12 B x 535M
    assert memory.alias_size_in_bytes > 6.42e9              # donated
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert total < 13.0e9, total  # 12.51: the chip holds 16
    assert "tpu_custom_call" not in compiled.as_text()      # the XLA engine


# The window-and-full-attention expert model (model_zoo/laguna) at the
# widths of `laguna-xs2.train-synth-8k`: the whole two-step window program
# as the worker runs it, 8.30 GB of state donated (12 B x 691,624,960),
# each layer rematerialised, both kinds of attention layer in the XLA
# block engine (the configuration's `attn_impl=xla`: measured faster than
# the Pallas kernels at 6 and 8 query heads a key-value head).  ONE
# sequence a step fits with room (11.02 GB); two need 17.31 GB in this
# engine, more than the chip has: the cell runs one.
@pytest.mark.parametrize("sequences,least,most", [
    (1, 10.5e9, 11.5e9), (2, 16.0e9, 18.0e9),
])
def test_laguna_window_program_compiles_and_fits_for_v5e(
    topo, monkeypatch, sequences, least, most
):
    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer
    from model_zoo.laguna import laguna_lm as zoo

    with open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs", "laguna-xs.2.json",
    )) as f:
        model = {
            k: v for k, v in json.load(f)["model"].items()
            if k != "sample_tokens"
        }
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = build_mesh(MeshConfig(data=1, model=1), devices=topo.devices[:1])
    trainer = DataParallelTrainer(
        zoo.custom_model(use_bf16=True, remat=True, attn_impl="xla", **model),
        zoo.loss, zoo.optimizer(), mesh,
    )
    on_chip = NamedSharding(mesh, P())
    state, _ = jax.eval_shape(
        lambda: trainer._make_state(
            jax.random.PRNGKey(0), jnp.zeros((sequences, 8192), jnp.int32)
        )
    )
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip),
        state,
    )
    window = jax.ShapeDtypeStruct(
        (2, sequences, 8192), jnp.int32, sharding=on_chip
    )
    mask = jax.ShapeDtypeStruct((2, sequences), jnp.float32, sharding=on_chip)
    compiled = jax.jit(
        trainer._train_window_impl, donate_argnums=(0,)
    ).lower(state, window, window, mask).compile()
    memory = compiled.memory_analysis()
    print("laguna window bytes", sequences, memory.argument_size_in_bytes,
          memory.temp_size_in_bytes, memory.alias_size_in_bytes)
    assert 8.29e9 < memory.argument_size_in_bytes < 8.31e9  # 12 B x 692M
    assert memory.alias_size_in_bytes > 8.29e9              # donated
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert least < total < most, total  # the chip holds 16
    assert "tpu_custom_call" not in compiled.as_text()      # the XLA engine


# The two-sublayer block under multipliers (model_zoo/granite_hybrid) at
# the widths of `granite4-h-micro.train-synth`: the whole two-step window
# program as the worker runs it, 9.27 GB of state donated (12 B x
# 772,160,448: the LARGEST state of any cell; the tied table is in it
# once), each of the ten layers rematerialised (nine Mamba-2 layers at ONE
# group in chunks of 256, whose decays are 537 MB a layer, and one
# attention layer in the Pallas kernel: K + V of a head of 64 are 4 MiB,
# under `supports`' cap).  12.58 GB at 1 x 8192 tokens; the chip holds 16
# and ISSUE 38 sets 15.5 as the most this cell may need before it would
# have to run 4096 tokens.
def test_granite_window_program_compiles_and_fits_for_v5e(topo, monkeypatch):
    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer
    from model_zoo.granite_hybrid import granite_hybrid_lm as zoo

    with open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs", "granite-4.0-h-micro.json",
    )) as f:
        config = json.load(f)
    model = {
        k: v for k, v in config["model"].items() if k != "sample_tokens"
    }
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = build_mesh(MeshConfig(data=1, model=1), devices=topo.devices[:1])
    trainer = DataParallelTrainer(
        zoo.custom_model(use_bf16=True, remat=True, **model), zoo.loss,
        zoo.optimizer(), mesh,
    )
    on_chip = NamedSharding(mesh, P())
    state, _ = jax.eval_shape(
        lambda: trainer._make_state(
            jax.random.PRNGKey(0), jnp.zeros((1, 8192), jnp.int32)
        )
    )
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip),
        state,
    )
    window = jax.ShapeDtypeStruct((2, 1, 8192), jnp.int32, sharding=on_chip)
    mask = jax.ShapeDtypeStruct((2, 1), jnp.float32, sharding=on_chip)
    compiled = jax.jit(
        trainer._train_window_impl, donate_argnums=(0,)
    ).lower(state, window, window, mask).compile()
    memory = compiled.memory_analysis()
    print("granite window bytes", memory.argument_size_in_bytes,
          memory.temp_size_in_bytes, memory.alias_size_in_bytes)
    assert 9.26e9 < memory.argument_size_in_bytes < 9.27e9  # 12 B x 772M
    assert memory.alias_size_in_bytes > 9.26e9              # donated
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 12.0e9 < total < 13.2e9, total  # 12.58; never over 15.5
    assert "tpu_custom_call" in compiled.as_text()          # the flash kernel
    # the sizes the configuration's file states are these
    for text in (config["device_bytes"], config["assumed"]["remat"]):
        assert "12.58 GB" in text and "3.31 GB" in text


# The PS trainer's init at the widths of `deepfm-dac.train-file`: 26
# fields x 1,000,000 rows of 1 + 10 floats padded to 16 lanes, minibatch
# 8192, sparse Adam: ONE program whose outputs are the 6.66 GB of state
# (the table, Adam's m, v and per-row step) born in their layout.  What
# it needs beside them is held under the 8.0 GB named here, so that the
# 2 x 6.7 GB the eager init and its host round trip once held
# (`memory_peak_bytes` 13.35 GB, PERF.md §6 PR 37) cannot come back
# unseen.
def test_ps_init_compiles_and_fits_for_v5e(topo):
    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.ps_trainer import ShardedEmbeddingTrainer
    from model_zoo.deepfm import deepfm_functional_api as zoo

    with open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs", "deepfm-criteo-dac.json",
    )) as f:
        config = json.load(f)
    sizes, flags = config["model"], config["job"]
    assert "--minibatch_size=8192" in flags
    assert "--sparse_apply_every=auto" in flags
    mesh = build_mesh(MeshConfig(data=1, model=1), devices=topo.devices[:1])
    trainer = ShardedEmbeddingTrainer(
        zoo.custom_model(
            vocab_size=sizes["vocab_size"],
            embedding_dim=sizes["embedding_dim"],
            hidden=sizes["hidden"][0], sparse_apply_every="auto",
        ),
        zoo.loss, zoo.optimizer(), mesh,
        embedding_optimizer=zoo.embedding_optimizer(),
        sparse_apply_every="auto", sparse_kernel="xla",
    )
    rng = jax.random.PRNGKey(0)
    features = {
        "dense": jnp.zeros((8192, sizes["num_dense"]), jnp.float32),
        "cat": jnp.zeros((8192, sizes["num_categorical"]), jnp.int32),
    }
    shapes = jax.eval_shape(trainer._make_state, rng, features)
    (spec,) = trainer._table_specs.values()  # the trace left it
    assert (spec.vocab_size, spec.dim, spec.dim_padded) == (
        26_000_000, 11, 16)
    on_chip = NamedSharding(mesh, P())
    compiled = jax.jit(
        trainer._make_state,
        out_shardings=trainer._state_shardings(shapes),
    ).lower(
        *jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip),
            (rng, features),
        )
    ).compile()
    memory = compiled.memory_analysis()
    print("ps_init bytes", memory.argument_size_in_bytes,
          memory.output_size_in_bytes, memory.temp_size_in_bytes)
    assert 6.65e9 < memory.output_size_in_bytes < 6.67e9  # 26e6 x 16 x 4 x 4
    total = (
        memory.argument_size_in_bytes + memory.output_size_in_bytes
        + memory.temp_size_in_bytes
    )
    assert total < 8.0e9, total  # the chip holds 16


def _four_chip_mesh(topo):
    return jax.sharding.Mesh(
        np.asarray(topo.devices).reshape(2, 2), (DATA_AXIS, MODEL_AXIS)
    )


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
    mesh = _four_chip_mesh(topo)
    module, dtype, _ = _hybrid_sublayer(
        "gdn_pallas", mesh if names_mesh else None
    )
    compiled = _sublayer_fwd_bwd(
        module, dtype, NamedSharding(mesh, P()),
        NamedSharding(mesh, P(DATA_AXIS)),
    )
    assert ("delta_rule_bwd" in compiled.as_text()) == names_mesh


@pytest.mark.parametrize("kernel", ["lookup", "lookup_fm", "apply_adam"])
def test_fused_kernels_compile_sharded_on_four_chip_mesh(topo, kernel):
    """`--sparse_kernel=fused --mesh_model_axis=2`: the shard_map route
    of each fused kernel (table blocks over `model`, batch over `data`)
    on a 2x2 mesh of described chips.  The apply case feeds 256 x 26 ids
    (the XLA dedup prologue's compile time grows steeply past 32k ids);
    table widths are DeepFM's."""
    mesh = _four_chip_mesh(topo)
    spec = DEEPFM_TABLE

    def sharded(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*axes))
        )

    table = sharded(spec.packed_shape, jnp.float32, MODEL_AXIS)
    if kernel == "lookup":
        fn = functools.partial(
            ske.fused_lookup, spec, mesh=mesh, interpret=False
        )
        args = [table, sharded((N_IDS,), jnp.int32, DATA_AXIS)]
    elif kernel == "lookup_fm":
        fn = functools.partial(
            ske.fused_lookup_fm, spec, mesh=mesh, interpret=False
        )
        args = [
            table,
            sharded((BATCH, FIELDS, spec.dim), jnp.float32, DATA_AXIS),
            sharded((BATCH, FIELDS), jnp.int32, DATA_AXIS),
            sharded((BATCH, FIELDS), jnp.bool_, DATA_AXIS),
        ]
    else:
        fn = functools.partial(
            ske.fused_dedup_apply, spec, "adam", _HYPER["adam"],
            mesh=mesh, interpret=False,
        )
        n = 256 * FIELDS
        args = [
            table, {name: table for name in ske._KIND_SLOTS["adam"]},
            sharded((n,), jnp.int32, DATA_AXIS),
            sharded((n, spec.dim), jnp.float32, DATA_AXIS),
        ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("check_vma", [False, True])
def test_ring_attention_compiles_on_four_chip_mesh(topo, check_vma):
    """The Pallas ring engine under shard_map on a 2x2 mesh of described
    chips, forward and backward.  `check_vma=False` is how
    parallel/ring_attention.make_ring_attention builds it today (the
    kernel INTERPRETER trips the checker); True shows the compiled
    kernels carry their varying-axes types and do not need the escape."""
    from elasticdl_tpu.parallel import compile as pc

    mesh = _four_chip_mesh(topo)
    spec = P(DATA_AXIS, MODEL_AXIS, None, None)
    ring = pc.shard_map_call(
        functools.partial(
            ring_attention.ring_attention_pallas, axis_name=MODEL_AXIS,
            causal=True, interpret=False,
        ),
        mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=check_vma,
    )

    def loss(q, k, v):
        return jnp.sum(ring(q, k, v).astype(jnp.float32))

    arg = jax.ShapeDtypeStruct(
        (B, 2 * T, H, 128), jnp.bfloat16, sharding=NamedSharding(mesh, spec)
    )
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg, arg, arg
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
