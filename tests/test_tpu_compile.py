"""The Pallas kernels of the main path, and the cells' whole programs,
compile for the chip.

Interpret mode (every other kernel test here) cannot see what Mosaic
refuses: a dynamic lane slice, a block that misses the tiling, more
SMEM or VMEM than the chip has.  The TPU compiler is installed in the
sandbox and compiles for a DESCRIBED `v5e:2x2` device from shapes alone
(/opt/skills/guides/on-chip-measurement section 2), so each kernel entry
point is lowered with `interpret=False` at the real widths of the cell
that uses it.  Nothing runs: a pass here is not a chip run.

Beside the kernels stands the PS trainer's init, a program a worker runs
WHOLE.  A language model's whole program, `dp_trainer`'s two-step window,
compiles in that model's own `tests/test_<m>_program.py` (ONE case,
`tests/lm_contract.py`'s: widths from the cell's JSON `model`, the byte
bounds and the engines' traces from the descriptor's `CompileSpec`), so no
file holds more than one model's heavy compiles; the sublayers and the
delta rule on a four-chip mesh are `tests/test_tpu_compile_sublayers.py`.
`topo` and `no_persistent_cache` are `tests/conftest.py`'s.
"""

import functools
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from elasticdl_tpu.ops import sparse_embedding as ske
from elasticdl_tpu.parallel import ring_attention, sparse_optim
from elasticdl_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from elasticdl_tpu.parallel.packed import PackedSpec
from lm_contract import _config, four_chip_mesh

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


pytestmark = pytest.mark.usefixtures("no_persistent_cache")


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


def _ssd(groups, chunk, backward):
    """The state-space scan's kernels at the 1 x 8192 tokens and the 64
    heads of 64 of both cells that run them: Nemotron-H's 8 groups in
    chunks of 128, Granite's ONE group in chunks of 256."""
    from elasticdl_tpu.ops import ssd

    assert ssd.supports(8192, 64, 64, groups, 128, chunk)

    def fwd(*args):
        return ssd.ssd_chunked_pallas(
            *args, groups=groups, chunk=chunk, interpret=False
        )

    def bwd(*args):
        return jax.grad(
            lambda *a: jnp.sum(fwd(*a)[0]), argnums=range(4)
        )(*args)

    return bwd if backward else fwd, [
        (shape, jnp.float32) for shape in (
            (1, 8192, 4096), (1, 8192, 64), (64,),
            (1, 8192, 2 * groups * 128),
        )
    ]


def _gdn_pass(which, backward):
    """The passes of `ops/gdn_passes.py` at the cells' rows.  Around the
    delta rule: q's conv + silu + l2-norm over 16 heads of 128, v's conv
    + silu over 32, the gated norm over 32 with its bfloat16 result.
    Around the state-space scan, 1 x 8192 rows: the conv + silu with its
    bias over x (4096 columns in both models) and over [B | C] (2048 in
    Nemotron-H, 256 in Granite), the skip, gate and norm over 4096 columns
    in 8 groups and in 1."""
    from elasticdl_tpu.ops import gdn_passes

    if which.startswith("group_norm"):
        groups = int(which.rsplit("_", 1)[1])

        def fwd(y, x, z, skip, weight):
            return gdn_passes.gated_group_norm(
                y, x, z, skip, weight, groups=groups, eps=1e-5,
                dtype=jnp.bfloat16, pallas=True, interpret=False,
            )

        shapes = [(1, 8192, 4096)] * 3 + [(64,), (4096,)]
    elif which.startswith("conv_bias"):
        rows = int(which.rsplit("_", 1)[1])

        def fwd(x, taps, bias):
            return gdn_passes.conv_silu(
                x, taps, bias, pallas=True, interpret=False
            )

        shapes = [(1, 8192, rows), (4, rows), (rows,)]
    elif which == "norm":
        rows = 32 * 128

        def fwd(out, gate, weight):
            return gdn_passes.gated_rms_norm(
                out, gate, weight, dtype=jnp.bfloat16, pallas=True,
                interpret=False,
            )

        shapes = [(2, 8192, rows), (2, 8192, rows), (128,)]
    else:
        rows = {"q": 16 * 128, "v": 32 * 128}[which]

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


def _rotary_pack(which, backward):
    """`ops/rotary_pack.py` at the cells' shapes: Laguna's 64 query heads
    of a banded layer (the whole head rotated: one roll), its 48 of a
    full layer (half of the head: two rolls), Mellum 2's 4 key heads of
    two sequences under the head norm."""
    from elasticdl_tpu.ops import gqa, rotary_pack

    b, h, rotary_dim, normed = {
        "laguna_window_q": (1, 64, 128, False),
        "laguna_full_q": (1, 48, 64, False),
        "mellum_k": (2, 4, 128, True),
    }[which]

    def fwd(x, *weight):
        cos, sin = gqa.rotary_tables(jnp.arange(8192), rotary_dim, 1e4)
        return rotary_pack.rotary_pack(
            x, cos, sin, jnp.bfloat16, *weight, interpret=False
        )

    def bwd(d_out, *args):
        return jax.vjp(fwd, *args)[1](d_out)

    shapes = [((b, 8192, h, 128), jnp.float32)] + [
        ((128,), jnp.float32)
    ] * normed
    if backward:
        return bwd, [((b, h, 8192, 128), jnp.bfloat16)] + shapes
    return fwd, shapes


_CASES = {
    **{
        f"rotary_pack_{which}_{'bwd' if backward else 'fwd'}":
            functools.partial(_rotary_pack, which, backward)
        for which in ("laguna_window_q", "laguna_full_q", "mellum_k")
        for backward in (False, True)
    },
    **{
        f"gdn_{which}_{'bwd' if backward else 'fwd'}":
            functools.partial(_gdn_pass, which, backward)
        for which in ("q", "v", "norm", "conv_bias_4096", "conv_bias_2048",
                      "conv_bias_256", "group_norm_8", "group_norm_1")
        for backward in (False, True)
    },
    **{
        f"ssd_{'bwd' if backward else 'fwd'}_{groups}_groups_chunks_of_{chunk}":
            functools.partial(_ssd, groups, chunk, backward)
        for groups, chunk in ((8, 128), (1, 256))
        for backward in (False, True)
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


@pytest.mark.parametrize("kernel", ["lookup", "lookup_fm", "apply_adam"])
def test_fused_kernels_compile_sharded_on_four_chip_mesh(topo, kernel):
    """`--sparse_kernel=fused --mesh_model_axis=2`: the shard_map route
    of each fused kernel (table blocks over `model`, batch over `data`)
    on a 2x2 mesh of described chips.  The apply case feeds 256 x 26 ids
    (the XLA dedup prologue's compile time grows steeply past 32k ids);
    table widths are DeepFM's."""
    mesh = four_chip_mesh(topo)
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

    mesh = four_chip_mesh(topo)
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

    config = _config("deepfm-criteo-dac.json")
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
