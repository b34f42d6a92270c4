"""The contract every language model of the zoo is held to, written once.

A language model is a STACK (`model_zoo/<m>/<m>_lm.py`) and a DESCRIPTOR:
an `LMSpec` named `SPEC` in `tests/spec_<m>.py` (a helper module, as this
one is; GPT-2's, which one file reads, is in that file), which says where
the stack, its plain reference and its benchmark cell are
(`perfbench/configs/`, read and never edited), at what reduced widths the
two are compared, and what is the model's alone: its tolerances, the leaves
with a rule of their own, its sublayers at the stated precision, its
trainer, the bytes its window program may take on a v5e and the scopes its
ops carry.

The cases below are the contract.  A test file that has a `SPEC` IMPORTS
the ones it takes (with `pytest_generate_tests`, `lm` and
`program_and_reference`), so each is collected in that file, under its own
name, and `--dist loadfile` spreads the models over the workers; nothing is
collected from this module (its name matches no `test_*.py`).  A model has
two such files: `tests/test_<m>.py` holds it against its reference (and
what is the model's alone), `tests/test_<m>_program.py` runs it as a job
does: through the trainer and the saver, `elasticdl train`, the window
program compiled for a described v5e, and the device scopes.  No test file
imports another.

Adding a model: the stack, its reference and cell under `perfbench/`, a
`tests/spec_<m>.py`, and the two files with their imports.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO_ROOT, "perfbench", "configs")

SELECTION_BIAS = "e_score_correction_bias"


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _model_kwargs(model):
    return {k: v for k, v in model.items() if k != "sample_tokens"}


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def _perturbed(tree, seed, scale=0.05):
    """Norm weights and `D` start at 0 or 1, a selection bias at 0, an
    embedding at 0.02: move every leaf off its special value so that a
    dropped term (a `1 + w`, a bias) would show."""
    leaves, treedef = jax.tree.flatten(tree)
    key = jax.random.PRNGKey(seed)
    return jax.tree.unflatten(treedef, [
        leaf + scale * jax.random.normal(jax.random.fold_in(key, i),
                                         leaf.shape)
        for i, leaf in enumerate(leaves)
    ])


def _close(got, want, limit, what):
    assert _rel(got, want) < limit, what


def _cpu_mesh(data, model):
    from elasticdl_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    return jax.sharding.Mesh(
        np.asarray(jax.devices()[:data * model]).reshape(data, model),
        (DATA_AXIS, MODEL_AXIS),
    )


def four_chip_mesh(topo):
    """The 2x2 mesh of a described `v5e:2x2`'s chips, `data` by `model`."""
    from elasticdl_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    return jax.sharding.Mesh(
        np.asarray(topo.devices).reshape(2, 2), (DATA_AXIS, MODEL_AXIS)
    )


def _log_lines(logger):
    """-> (the list a handler appends the logger's messages to, the
    handler to remove again)."""
    import logging

    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    logger.addHandler(handler)
    return lines, handler


def engines_as_on_a_tpu(monkeypatch):
    """The engines a TPU would be given, in interpret mode: the choice
    of the delta rule's kernels and of `ops/gdn_passes.py`'s by shapes
    alone."""
    from elasticdl_tpu.ops import gated_delta, gdn_passes

    monkeypatch.setattr(
        gated_delta, "_engine",
        lambda supported, mesh, *why: (
            "pallas" if supported else "xla", "as on a tpu"
        ),
    )
    monkeypatch.setattr(gated_delta, "_use_interpret", lambda: True)
    monkeypatch.setattr(gdn_passes, "_use_interpret", lambda: True)


def mamba_mixer_in_its_kernels(monkeypatch, module, reference, model):
    """`Mamba2Mixer` (`module`, float32) on the path a TPU takes, the
    convolution and the norm in their kernels (interpret mode), against
    the plain reference's function of the sublayer at the same
    parameters: the outputs to 1e-5 of their size and every gradient,
    the input's among them, to 1e-4."""
    engines_as_on_a_tpu(monkeypatch)
    rng = np.random.default_rng(0)
    x, weight = (
        jnp.asarray(rng.normal(size=(1, 200, model["hidden_size"])),
                    jnp.float32)
        for _ in range(2)
    )
    params = _perturbed(module.init(jax.random.PRNGKey(1), x)["params"], 3)

    def program(p, x):
        return module.apply({"params": p}, x)[0]

    def plain(p, x):
        return reference(p, x[0], model)

    traced = str(jax.make_jaxpr(program)(params, x))
    assert "conv_silu_fwd" in traced and "gated_group_norm_fwd" in traced
    with jax.default_matmul_precision("highest"):
        assert _rel(program(params, x), plain(params, x)) < 1e-5
        got, want = (
            jax.grad(lambda p, x: jnp.sum(f(p, x) * weight[0]), (0, 1))(
                params, x
            )
            for f in (program, plain)
        )
    flat = jax.tree_util.tree_leaves_with_path(got)
    assert len(flat) == 9  # eight parameters' gradients and the input's
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        assert _rel(g, w) < 1e-4, jax.tree_util.keystr(path)


def _eqns(jaxpr, kernel=None):
    """Every equation of a jaxpr and of the jaxprs inside it -> (the
    equation, the name of the `pallas_call` that holds it or None)."""
    for eqn in jaxpr.eqns:
        yield eqn, kernel
        within = kernel
        if eqn.primitive.name == "pallas_call":
            within = eqn.params.get("name") or "pallas_call"
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, within)


def _dots(jaxpr):
    """Every `dot_general` of a jaxpr and of the jaxprs inside it ->
    (the equation, whether a `pallas_call` holds it)."""
    return (
        (eqn, kernel is not None) for eqn, kernel in _eqns(jaxpr)
        if eqn.primitive.name == "dot_general"
    )


def _dot_precisions(jaxpr):
    """-> [(operand dtype, precision)] of every product, inner jaxprs too."""
    return [
        (eqn.invars[0].aval.dtype, eqn.params["precision"])
        for eqn, _ in _dots(jaxpr)
    ]


def _tokens(features):
    """A step's tokens: its features, or the first of them where the
    features are a tuple with the record's noise beside the tokens
    (`model_zoo/sdar`: tokens, mask, t).  They are the labels too."""
    return features[0] if isinstance(features, tuple) else features


def _size(tree):
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))


def _flag(value):
    """A value as a job's flat `--model_params` carry it."""
    if isinstance(value, bool):
        return str(value).lower()
    if isinstance(value, (list, tuple)):
        return "/".join(str(entry) for entry in value)
    return str(value)


#: The spans the worker writes a task from a model's counters
#: (`layers/ledger.py`), each by the kind of model that has it.
COUNTER_SPANS = ("moe.routing", "loop.exits", "diffusion.noise", "kda.gates")


def counter_spans(events, name="moe.routing", also=()):
    """The two tasks' `name` spans of a two-task job's journal; a model
    with that counter writes no other kind but those in `also`."""
    found = {
        kind: [e for e in events
               if e.get("event") == "span" and e.get("name") == kind]
        for kind in COUNTER_SPANS
    }
    spans = found.pop(name)
    assert len(spans) == 2
    assert [e["steps"] for e in spans] == [2, 2]
    assert not any(
        spans for kind, spans in found.items() if kind not in also
    )
    return spans


# ---------------------------------------------------------------------------
# The descriptor
# ---------------------------------------------------------------------------


class Whole(NamedTuple):
    """What `program_and_reference` hands a case: the program and the
    reference as functions of the parameters, at the same perturbed
    weights and tokens, and the widths both were built at."""

    program: Callable
    reference: Callable
    params: Any
    tokens: Any                # the step's features (`_tokens` of them)
    model: dict


@dataclasses.dataclass(frozen=True)
class Bf16Case:
    """One reading of `test_bf16_program_is_the_reference_at_the_stated_
    precision`.  `build() -> (module, inputs, reference)`: the program's
    piece in bfloat16, what it is called on, and `reference(params,
    reading)` for the readings `stated` (bfloat16 operands where the
    program has them), `highest` (float32) and each name in `further`."""

    build: Callable
    limit: float               # rel(program, stated) stays under it
    ratio: float               # rel(program, highest) > ratio x stated's
    #: (reading, times, of): rel(program, reading) > times x rel(program, of)
    further: tuple = ()
    seed: int = 1              # of the piece's initialisation


@dataclasses.dataclass(frozen=True)
class CompileSpec:
    """The two-step window program at the cell's widths on a described
    v5e: the bytes of the donated state, the bytes of state and
    temporaries together for each count of sequences a step (the cell's
    own first), and what the engines leave in the compiled text."""

    state: tuple                       # (least, most) argument bytes
    total: dict                        # sequences -> (least, most)
    in_text: tuple = ()
    not_in_text: tuple = ()
    #: (least, most) bytes of the top-level `copy` ops of 16 MB and more
    #: (`program_moves`) at the cell's own count of sequences
    copy_bytes: tuple = ()
    #: sizes the cell's `device_bytes` and `assumed.remat` state in words
    stated_sizes: tuple = ()
    names_mesh: bool = False           # the stack is told the trainer's mesh


@dataclasses.dataclass(frozen=True)
class LMSpec:
    model_def: str             # the cells' `--model_def`
    reference: str             # the plain reference, under perfbench/configs
    cell: str                  # the cell's JSON, under perfbench/configs
    parameters: int            # at the cell's full widths
    #: the reduced widths are `rehearse.model` of the JSON, at this length
    sample_tokens: Optional[int] = None
    #: a JSON `model` -> `custom_model`'s keywords
    kwargs: Callable = _model_kwargs
    #: (id, experts_first, experts_held) of the whole-model cases
    held: tuple = ()
    whole_model_changes: dict = dataclasses.field(default_factory=dict)
    #: tokens [.., sequences, T] (an array or its shape) -> the features of
    #: a step that takes more than tokens; None: the tokens are the features
    features: Optional[Callable] = None
    logits_rel: float = 1e-5
    #: a prediction that is a named tree -> the ONE array the reference's
    #: `forward` returns and the cell compares; None: the prediction is it
    compared: Optional[Callable] = None
    #: (ref, params, tokens, model) -> (the loss the program reports, what
    #: its gradient has on top); None: the zoo's loss over `ref.forward`
    losses: Optional[Callable] = None
    added_loss_above: Optional[float] = None
    #: ("max", x): |g - w| under x of the leaf's largest |w|; ("rms", x)
    grad_limit: tuple = ("max", 2e-3)
    grad_leaves: Optional[int] = None
    #: leaves a selection reads: no gradient in the reference, the load's
    #: violation (-1, 0, 1) in the program
    selection_leaves: Optional[str] = None
    #: as the cell's `device_bytes` words the count; None: with commas
    stated: Optional[str] = None
    uncounted: Optional[Callable] = None         # (leaf's path, leaf) -> bool
    reduced: tuple = ()
    job_only: dict = dataclasses.field(
        default_factory=lambda: {"remat": True}
    )
    full_size: Optional[Callable] = None         # (shapes, model)
    float32_tokens: Optional[int] = None
    float32_highest: Callable = lambda tiny: 0
    float32_also: tuple = ()
    products_above: int = 0
    bf16: Any = None           # a Bf16Case, or {id: Bf16Case}
    tolerances: tuple = ("highest",)
    also_report: tuple = ()
    step_flops: tuple = (0.0, float("inf"))
    costs: Optional[Callable] = None             # (cost of a step, model)
    trainer_changes: dict = dataclasses.field(
        default_factory=lambda: {"sample_tokens": 64}
    )
    optimizer_kwargs: dict = dataclasses.field(default_factory=dict)
    trained: Optional[Callable] = None           # (trainer, model)
    e2e_records: tuple = (16, 8, 4)  # records, a task, a minibatch
    journal: Optional[Callable] = None           # (job, events)
    compile: Optional[CompileSpec] = None
    scope_widths: dict = dataclasses.field(default_factory=dict)
    scopes: tuple = ()

    @property
    def zoo(self):
        return importlib.import_module("model_zoo." + self.model_def)

    @property
    def ref(self):
        return _reference(self.reference)

    @property
    def config(self):
        return _config(self.cell)

    @property
    def tiny(self):
        tiny = self.config["rehearse"]["model"]
        if self.sample_tokens is None:
            return tiny
        return dict(tiny, sample_tokens=self.sample_tokens)

    @property
    def job_flags(self):
        """The cell's `--model_params` and `--minibatch_size`, parsed."""
        from elasticdl_tpu.common.args import parse_dict_params

        flags = {
            flag.split("=", 1)[0]: flag.split("=", 1)[1]
            for flag in self.config["job"]
        }
        return (parse_dict_params(flags["--model_params"]),
                int(flags["--minibatch_size"]))

    def build(self, model, **keywords):
        return self.zoo.custom_model(**self.kwargs(model), **keywords)

    def features_of(self, tokens):
        return self.features(tokens) if self.features else tokens

    def array(self, prediction):
        """What is compared of a prediction (`compared`)."""
        return self.compared(prediction) if self.compared else prediction

    def whole(self, first_held=None) -> Whole:
        """The float32 program and the reference at the reduced widths."""
        model = dict(self.tiny, **self.whole_model_changes)
        if first_held is not None:
            model.update(experts_first=first_held[0],
                         experts_held=first_held[1])
        module = self.build(model, use_bf16=False)
        tokens = self.ref.sample(3, 2, model)
        variables = dict(module.init(jax.random.PRNGKey(0), tokens))
        params = _perturbed(variables.pop("params"), 1)

        def program(p):
            return module.apply({"params": p, **variables}, tokens)

        def reference(p):
            return self.ref.forward(p, tokens, model)

        return Whole(program, reference, params, tokens, model)

    def reference_losses(self, params, whole: Whole):
        if self.losses is not None:
            return self.losses(self.ref, params, whole.tokens, whole.model)
        return self.zoo.loss(
            _tokens(whole.tokens), whole.reference(params)
        ), 0.0

    def trainer(self):
        """-> (a float32 trainer at the reduced widths, each layer
        rematerialised as the cells run it, those widths)."""
        from elasticdl_tpu.parallel import MeshConfig, build_mesh
        from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer

        model = dict(self.tiny, **self.trainer_changes)
        return DataParallelTrainer(
            self.build(model, use_bf16=False, remat=True),
            self.zoo.loss, self.zoo.optimizer(**self.optimizer_kwargs),
            build_mesh(MeshConfig(data=1, model=1), devices=jax.devices()[:1]),
        ), model


_LOADED = {}


def _reference(name):
    if name not in _LOADED:
        _LOADED[name] = _load(
            os.path.join(CONFIGS, name), name[:-len("erence.py")]
        )
    return _LOADED[name]


def _config(name):
    if name not in _LOADED:
        with open(os.path.join(CONFIGS, name)) as f:
            _LOADED[name] = json.load(f)
    return _LOADED[name]


# ---------------------------------------------------------------------------
# Fixtures (a model's file imports them with the cases)
# ---------------------------------------------------------------------------


def pytest_generate_tests(metafunc):
    spec = getattr(metafunc.module, "SPEC", None)
    if spec is None:
        return
    if "program_and_reference" in metafunc.fixturenames and spec.held:
        metafunc.parametrize(
            "program_and_reference",
            [(first, held) for _, first, held in spec.held],
            ids=[name for name, _, _ in spec.held],
            indirect=True, scope="module",
        )
    if "bf16_case" in metafunc.fixturenames and isinstance(spec.bf16, dict):
        metafunc.parametrize(
            "bf16_case", list(spec.bf16.values()), ids=list(spec.bf16)
        )
    if "sequences" in metafunc.fixturenames:
        metafunc.parametrize("sequences", list(spec.compile.total))


@pytest.fixture(scope="module")
def lm(request) -> LMSpec:
    return request.module.SPEC


@pytest.fixture(scope="module")
def program_and_reference(request) -> Whole:
    return request.module.SPEC.whole(getattr(request, "param", None))


@pytest.fixture
def bf16_case(lm):
    return lm.bf16  # the model's one reading; several are parametrised


# ---------------------------------------------------------------------------
# The whole model against the reference
# ---------------------------------------------------------------------------


def test_logits_and_loss_match_the_reference(lm, program_and_reference):
    program, reference, params, tokens, _ = whole = program_and_reference
    predicted, want = program(params), reference(params)
    got = lm.array(predicted)  # the prediction itself, or a named tree's
    assert got.shape == want.shape
    assert (got.shape[0], got.shape[-2], got.shape[-1]) == _tokens(
        tokens).shape + (lm.tiny["vocab_size"],)
    assert _rel(got, want) < lm.logits_rel
    # the program REPORTS the first of the two alone
    reported, added = lm.reference_losses(params, whole)
    np.testing.assert_allclose(
        float(lm.zoo.loss(_tokens(tokens), predicted)), float(reported),
        rtol=1e-5
    )
    if lm.added_loss_above is not None:
        assert float(added) > lm.added_loss_above


def test_gradients_match_the_reference(lm, program_and_reference):
    """Every leaf by `jax.grad` of each side's own forward pass; where the
    program injects a gradient (a balancing loss), the reference
    differentiates the sum."""
    program, _, params, tokens, _ = whole = program_and_reference
    got = jax.grad(
        lambda p: lm.zoo.loss(_tokens(tokens), program(p))
    )(params)
    want = jax.grad(lambda p: sum(lm.reference_losses(p, whole)))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    if lm.grad_leaves is not None:
        assert len(flat_got) == lm.grad_leaves
    kind, limit = lm.grad_limit
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(w).max())
        if lm.selection_leaves and lm.selection_leaves in name:
            # A selection is not differentiated: the reference has no
            # gradient for the bias.  The program hands the optimizer the
            # load's violation in its place.
            assert scale == 0, name
            assert set(np.unique(np.asarray(g))) <= {-1.0, 0.0, 1.0}, name
            continue
        assert scale > 0, name
        if kind == "max":
            assert float(jnp.abs(g - w).max()) < limit * scale, name
        else:
            assert _rel(g, w) < limit, name


def test_full_size_configuration_counts_the_parameters_it_states(lm):
    """The cell's `model` at its full widths counts the parameters the
    descriptor, the cell's `device_bytes` and the reference state; the
    cut is the one the file lists; and the job's flags say what `model`
    says."""
    from elasticdl_tpu.common.args import parse_dict_params

    config, model = lm.config, lm.config["model"]
    shapes = jax.eval_shape(
        lm.build(model).init, jax.random.PRNGKey(0),
        lm.features_of(jnp.zeros((1, 8), jnp.int32)),
    )["params"]
    count = _size(shapes)
    assert count == lm.parameters
    assert (lm.stated or f"{count:,}") in config["device_bytes"]
    # what the reference counts: AdamW's 28 bytes a parameter
    by_reference = lm.ref.step_cost(model, 1)["bytes"] // 28
    assert count == by_reference + sum(
        int(np.prod(leaf.shape))
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes)
        if lm.uncounted and lm.uncounted(jax.tree_util.keystr(path), leaf)
    )
    assert config["reduced"] == list(lm.reduced)
    flags = next(f for f in config["job"] if f.startswith("--model_params="))
    parsed = parse_dict_params(flags.split("=", 1)[1])
    for key, value in lm.job_only.items():
        assert parsed.pop(key) == value, key
    wanted = lm.kwargs(model)
    assert set(parsed) == set(wanted)
    for key, value in wanted.items():
        # a flat flag carries a sequence joined and a boolean in words
        assert parsed[key] == value or _flag(parsed[key]) == _flag(value), key
    built, want = lm.zoo.custom_model(**parsed), lm.build(model)
    assert getattr(built, "cfg", built) == getattr(want, "cfg", want)
    if lm.full_size:
        lm.full_size(shapes, model)


# ---------------------------------------------------------------------------
# The stated precision
# ---------------------------------------------------------------------------

BLOCKS = frozenset({"blocks"})


def rounded_parts(reading):
    """What a reference's sublayer rounds to bfloat16 for a reading:
    `stated` the blocks' products, `highest` nothing, any other name that
    part beside the blocks."""
    return {"stated": BLOCKS, "highest": frozenset()}.get(
        reading, BLOCKS | {reading}
    )


def sublayer_at_the_stated_precision(module, reference, model, rows=300):
    """A `Bf16Case.build` of one sublayer: `module` in bfloat16 on `rows`
    normal rows of the model's width, `reference(params, x, model,
    rounded parts)` the reference's function of the same sublayer."""
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, rows, model["hidden_size"])),
        jnp.float32,
    )
    return module, (x,), lambda params, reading: reference(
        params, x[0], model, rounded_parts(reading)
    )


def test_float32_products_ask_for_their_precision(lm):
    """What the logits cannot tell on the chip, the traced program can: in
    the bfloat16 model every product of float32 operands is one the model
    means to keep (a router's, a gate's: `HIGHEST`; a delta rule's:
    `HIGH`) and asks for its precision; a product left to a TPU's default
    would round its float32 operands to bfloat16."""
    highest = (jax.lax.Precision.HIGHEST,) * 2
    model = lm.tiny
    if lm.float32_tokens:
        model = dict(model, sample_tokens=lm.float32_tokens)
    module = lm.build(model, use_bf16=True)
    tokens = lm.ref.sample(0, 1, model)
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0), tokens)
    found = _dot_precisions(
        jax.make_jaxpr(lambda v, t: module.apply(v, t))(
            variables, tokens
        ).jaxpr
    )
    float32 = [p for dtype, p in found if dtype == jnp.float32]
    assert sum(p == highest for p in float32) == lm.float32_highest(model)
    others = [p for p in float32 if p != highest]
    assert all(p in lm.float32_also for p in others), others
    assert bool(others) == bool(lm.float32_also)
    assert len(found) > max(lm.products_above, len(float32))


def test_bf16_program_is_the_reference_at_the_stated_precision(bf16_case):
    """With bfloat16 operands where the program has them, the reference
    is the program to the flips of a rounding (a mismatch d before a
    rounding becomes ~sqrt(d 2^-8) after it), and closer than in float32
    by the case's `ratio`; a reading with one more part in bfloat16
    (`further`) is further off again: what the benchmark's `stated` and
    `bfloat16` readings tell apart."""
    case = bf16_case
    module, inputs, reference = case.build()
    variables = module.init(jax.random.PRNGKey(case.seed), *inputs)
    got = module.apply(variables, *inputs)[0]
    with jax.default_matmul_precision("highest"):
        reading = {
            name: _rel(got, reference(variables["params"], name))
            for name in ("stated", "highest") + tuple(
                name for name, _, _ in case.further
            )
        }
    assert reading["stated"] < case.limit, reading
    assert reading["highest"] > case.ratio * reading["stated"], reading
    for name, times, of in case.further:
        assert reading[name] > times * reading[of], reading


def test_the_cell_checks_precisions_the_reference_has(lm):
    """The limits (`tolerance_rel_rms`) and the readings every run prints
    beside them (`also_report`) are the descriptor's, and each is one the
    reference computes."""
    config, ref = lm.config, lm.ref
    check = config["check"]
    assert list(check["tolerance_rel_rms"]) == list(lm.tolerances)
    assert check["also_report"] == list(lm.also_report)
    for name in list(check["tolerance_rel_rms"]) + check["also_report"]:
        assert name in ref.PRECISIONS
    # the rehearsal's program is float32: only the limits apply to it
    assert "also_report" not in config["rehearse"]["check"]
    with pytest.raises(ValueError):
        ref.forward({}, np.zeros((1, 4), np.int32), lm.tiny, "float16")
    for key in ("source", "reduced", "published", "deployment", "assumed",
                "guarantees", "device_bytes", "check", "rehearse"):
        assert config[key], key


def test_benchmark_cost_functions_count_what_they_say(lm):
    """A step's least work at the cell's widths and minibatch: AdamW's
    28 bytes a parameter the reference counts, FLOPs in the range the
    descriptor states; then the model's own scopes by hand (`costs`)."""
    model = lm.config["model"]
    _, minibatch = lm.job_flags
    cost = lm.ref.step_cost(model, minibatch)
    least, most = lm.step_flops
    assert least < cost["flops"] < most
    assert cost["bytes"] == 28 * lm.ref._all_params(model)
    if lm.costs:
        lm.costs(cost, model)


# ---------------------------------------------------------------------------
# Through the trainer, the saver and `elasticdl train`
# ---------------------------------------------------------------------------


def test_trainer_carries_the_counters_and_checkpoint_restores_the_logits(
    lm, tmp_path,
):
    """Three steps train; what the model's state carries beside the
    parameters is the descriptor's to read (`trained`: the routing
    counters, a selection bias); a restored checkpoint gives the same
    logits, and they are the reference's at the restored weights."""
    from elasticdl_tpu.checkpoint import CheckpointSaver

    trainer, model = lm.trainer()
    tokens = lm.ref.sample(11, 4, model)
    losses = [
        float(trainer.train_step(tokens, _tokens(tokens))) for _ in range(3)
    ]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    if lm.trained:
        lm.trained(trainer, model)
    before = trainer.eval_step(tokens)
    CheckpointSaver(str(tmp_path)).save(trainer.state_to_host(), 3)
    restored, step = CheckpointSaver(str(tmp_path)).load_latest()
    assert step == 3
    fresh, _ = lm.trainer()
    fresh.state = restored
    jax.tree.map(
        np.testing.assert_array_equal, fresh.eval_step(tokens), before
    )
    want = lm.ref.forward(restored.params, tokens, model)
    assert _rel(lm.array(before), want) < lm.logits_rel


class Job(NamedTuple):
    """`elasticdl train` of the two-task case, for a descriptor's
    `journal` to read or to run once more."""

    run: Callable          # (tensorboard dir) -> exit code
    events: Callable       # (tensorboard dir) -> the worker's journal
    tmp_path: Any


def test_two_task_elasticdl_train_end_to_end(lm, tmp_path):
    """`elasticdl train` as a user runs it: master, task dispatch, one
    collective worker, the two-step window program, a cadence checkpoint;
    what the worker's journal holds a task is the descriptor's to read
    (`journal`: `moe.routing`, a restore)."""
    from elasticdl_tpu.common.args import parse_master_args
    from elasticdl_tpu.common.constants import Mode
    from elasticdl_tpu.master.job_runner import run_allreduce_job

    _, model = lm.trainer()
    records, a_task, minibatch = lm.e2e_records
    params = ",".join(
        f"{k}={_flag(v)}" for k, v in lm.kwargs(model).items()
    )

    def run(tb):
        return run_allreduce_job(parse_master_args([
            "--model_zoo=model_zoo",
            f"--model_def={lm.model_def}",
            f"--model_params={params},remat=true",
            f"--training_data=synthetic://lm?n={records}"
            f"&len={model['sample_tokens']}&vocab={model['vocab_size']}"
            "&seed=5",
            f"--records_per_task={a_task}",
            f"--minibatch_size={minibatch}",
            "--num_workers=1",
            "--use_bf16=false",
            "--distribution_strategy=AllreduceStrategy",
            f"--checkpoint_dir={tmp_path / 'ckpt'}",
            f"--tensorboard_log_dir={tb}",
            "--checkpoint_steps=2",
            "--num_epochs=1",
        ]), Mode.TRAINING)

    def events(tb):
        with open(tb / "events_worker_0.jsonl") as f:
            return [json.loads(line) for line in f]

    assert run(tmp_path / "tb") == 0
    assert any(p.startswith("step_") for p in os.listdir(tmp_path / "ckpt"))
    if lm.journal:
        lm.journal(Job(run, events, tmp_path), events(tmp_path / "tb"))


# ---------------------------------------------------------------------------
# The window program on a described v5e, and its device scopes
# ---------------------------------------------------------------------------


def compile_program(lm, topo, sequences, one_step=False):
    """`dp_trainer`'s two-step window program (or its one step) as the
    worker compiles it for the model's cell, at the widths of the cell's
    JSON `model` and the flags its job adds (`job_only`), for a DESCRIBED
    v5e (`conftest.topo`), the state donated.  A described device leaves
    `jax.default_backend()` at the CPU, so for the engines' choice the
    compile says "tpu" and one device.  `scripts/program_copies.py` reads
    the same program."""
    from unittest import mock

    from jax.sharding import NamedSharding, PartitionSpec as P

    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer

    want, config, zoo = lm.compile, lm.config, lm.zoo
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.object(jax, "device_count", lambda: 1):
        mesh = build_mesh(
            MeshConfig(data=1, model=1), devices=topo.devices[:1]
        )
        keywords = dict(
            lm.job_only, **({"mesh": mesh} if want.names_mesh else {})
        )
        tokens = config["model"]["sample_tokens"]
        trainer = DataParallelTrainer(
            lm.build(config["model"], use_bf16=True, **keywords),
            zoo.loss, zoo.optimizer(), mesh,
        )
        on_chip = NamedSharding(mesh, P())
        state, _ = jax.eval_shape(
            lambda: trainer._make_state(
                jax.random.PRNGKey(0),
                lm.features_of(jnp.zeros((sequences, tokens), jnp.int32)),
            )
        )
        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip),
            state,
        )
        lead = () if one_step else (2,)
        batch = jax.ShapeDtypeStruct(
            lead + (sequences, tokens), jnp.int32, sharding=on_chip
        )
        mask = jax.ShapeDtypeStruct(
            lead + (sequences,), jnp.float32, sharding=on_chip
        )
        program = (trainer._train_step_impl if one_step
                   else trainer._train_window_impl)
        return jax.jit(program, donate_argnums=(0,)).lower(
            state, lm.features_of(batch), batch, mask
        ).compile()


_HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
              "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
              "u64": 8}
_HLO_OP = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\("
)
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')


def program_moves(text, opcodes=("copy",), least=16 << 20):
    """The ops of a compiled program's text that lay an array out again
    in HBM: every instruction with one of `opcodes` that stands in a
    computation of its own (the entry, a loop's body; not inside a
    fusion) and writes `least` bytes or more -> [(opcode, "f32[8,128]",
    bytes written, op_name or "")].  A loop's body counts once: the
    window's two steps are one step's bytes."""
    found, fused = [], False
    for line in text.splitlines():
        if not line.startswith(" "):  # a computation's header, or its end
            fused = "fused_computation" in line
            continue
        match = None if fused else _HLO_OP.match(line)
        if match is None or match.group(3) not in opcodes:
            continue
        dtype, dims = match.group(1), match.group(2)
        size = _HLO_BYTES.get(dtype, 4) * int(
            np.prod([int(d) for d in dims.split(",") if d], dtype=np.int64)
        )
        if size >= least:
            name = _HLO_OP_NAME.search(line)
            found.append((match.group(3), f"{dtype}[{dims}]", size,
                          name.group(1) if name else ""))
    return found


_ENGINE_OP = re.compile(r"/attn/(?:closed_call/)?(while|pallas_call)$")
_HLO_CALL = re.compile(r" (?:while|custom-call)\(")  # a result may be a tuple


def op_direction(op_name: str) -> str:
    """Which pass of the step an op belongs to, by the transformations its
    `op_name` carries: `fwd`, `remat` (the forward run again inside a
    rematerialised layer's backward pass) or `bwd`."""
    if "rematted_computation" in op_name:
        return "remat"
    return "bwd" if "transpose(jvp" in op_name else "fwd"


def attention_engine_runs(text):
    """The attention engines in a compiled program's text, by direction:
    -> {"loops": {fwd, remat, bwd}, "kernels": {fwd, remat, bwd}}.  Both
    engines enter the scope `attn` around their `custom_vjp`
    (`ops/gqa._xla_engine`, `ops/flash_attention.flash_attention`), so an
    engine's op is one whose `op_name` ENDS in that scope and the
    primitive: the XLA engine's query loop (a `while`; its key loop, a
    `while` in that loop's body, is not counted again) or a
    flash-attention kernel (one forward; a pair, dq and dk/dv, backward).
    A loop's body is in the text once, whatever its trip count: the
    window's steps, and the passes of a scanned stack, are one body."""
    runs = {kind: dict(fwd=0, remat=0, bwd=0) for kind in ("loops", "kernels")}
    for line in text.splitlines():
        if not _HLO_CALL.search(line):
            continue
        name = _HLO_OP_NAME.search(line)
        engine = _ENGINE_OP.search(name.group(1)) if name else None
        if engine is not None:
            kind = "loops" if engine.group(1) == "while" else "kernels"
            runs[kind][op_direction(name.group(1))] += 1
    return runs


_OWN_PROGRAM = {}


def own_window_program(lm, topo):
    """-> (`memory_analysis()`, the text) of the cell's window program
    at the cell's OWN count of sequences, compiled once for the cases
    that read it (a compile is one to two minutes); the last cell's alone
    is kept, a text being tens of megabytes."""
    if lm.cell not in _OWN_PROGRAM:
        _OWN_PROGRAM.clear()
        compiled = compile_program(lm, topo, lm.job_flags[1])
        _OWN_PROGRAM[lm.cell] = (
            compiled.memory_analysis(), compiled.as_text()
        )
    return _OWN_PROGRAM[lm.cell]


def test_window_program_compiles_and_fits_for_v5e(
    topo, no_persistent_cache, lm, sequences
):
    """The cell's window program (`compile_program`): the state is donated
    and, with the temporaries, fits the chip's 16 GB (or, at a count of
    sequences the cell does not run, is known not to); at the cell's own
    count the engines' operands are not laid out again in HBM beyond the
    bytes the descriptor states."""
    want, config = lm.compile, lm.config
    assert next(iter(want.total)) == lm.job_flags[1]  # the cell's own
    if sequences == lm.job_flags[1]:
        memory, text = own_window_program(lm, topo)
    else:
        compiled = compile_program(lm, topo, sequences)
        memory, text = compiled.memory_analysis(), compiled.as_text()
    print(lm.cell, "window bytes", sequences, memory.argument_size_in_bytes,
          memory.temp_size_in_bytes, memory.alias_size_in_bytes)
    least, most = want.state
    assert least < memory.argument_size_in_bytes < most  # 12 B a parameter
    assert memory.alias_size_in_bytes > least            # donated
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    least, most = want.total[sequences]
    assert least < total < most, total                   # the chip holds 16
    for kernel in want.in_text:
        assert kernel in text, kernel
    for kernel in want.not_in_text:
        assert kernel not in text, kernel
    if want.copy_bytes and sequences == lm.job_flags[1]:
        copied = sum(size for _, _, size, _ in program_moves(text))
        least, most = want.copy_bytes
        assert least <= copied <= most, copied
    # the sizes the configuration's file states are these
    for size in want.stated_sizes:
        assert size in config["device_bytes"], size
        assert size in config["assumed"]["remat"], size


def test_rematerialised_layers_run_no_attention_engine_again(
    topo, no_persistent_cache, lm
):
    """A rematerialised layer KEEPS the attention engine's two results
    (`lm_common.KEEP_ATTENTION_RESULTS` over the names `ops/gqa.py` gives
    them), so the cell's window program holds every engine forward and
    backward and NONE under `rematted_computation`, whichever engine a
    layer took; what feeds it (the projections, `rotary_pack`) is
    recomputed as before."""
    runs = attention_engine_runs(own_window_program(lm, topo)[1])
    print(lm.cell, "attention engines", runs)
    loops, kernels = runs["loops"], runs["kernels"]
    assert loops["fwd"] + kernels["fwd"] > 0, runs
    assert loops["remat"] == kernels["remat"] == 0, runs
    assert loops["bwd"] == loops["fwd"], runs
    assert kernels["bwd"] == 2 * kernels["fwd"], runs  # dq; dk and dv


def _lm_window(spec, seed=0):
    """(trainer, staged window) of a tiny language model of the zoo on the
    dp trainer, at the widths its descriptor names (`scope_widths`; each
    layer rematerialised where the benchmark's configuration runs it so)."""
    from elasticdl_tpu.parallel import MeshConfig, build_mesh
    from elasticdl_tpu.parallel.dp_trainer import DataParallelTrainer

    zoo = spec.zoo
    trainer = DataParallelTrainer(
        model=zoo.custom_model(**spec.scope_widths), loss_fn=zoo.loss,
        optimizer=zoo.optimizer(), mesh=build_mesh(MeshConfig()),
    )
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, 64, size=(8, 16)).astype(np.int32)
    features = spec.features_of(tokens)
    trainer.ensure_initialized(features)
    batch = (features, tokens, np.ones((8,), np.float32))
    return trainer, trainer.stage_window([batch, batch])


def _window_outputs(build):
    trainer, window = build()
    losses = trainer.train_window(window)
    state = jax.device_get(trainer.state)
    return np.asarray(losses), [np.asarray(x) for x in jax.tree.leaves(state)]


def _op_names(trainer, jitted, window):
    import re

    text = jitted.lower(trainer.state, *window).compile().as_text()
    return " ".join(re.findall(r'op_name="([^"]+)"', text))


def scopes_are_metadata(build, jit_attr, scopes, monkeypatch):
    """Device scopes are metadata only: `build() -> (trainer, staged
    window)`'s compiled window program carries each of `scopes` on its op
    names, and without `jax.named_scope` the same window gives bit-equal
    losses and state."""
    import contextlib

    trainer, window = build()
    names = _op_names(trainer, getattr(trainer, jit_attr), window)
    for scope in scopes:
        assert f"/{scope}/" in names or f"({scope})" in names, scope
    with_scopes = _window_outputs(build)
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
    )
    trainer, window = build()
    bare = _op_names(trainer, getattr(trainer, jit_attr), window)
    for scope in ("fwd_bwd", "sparse_apply", "optimizer", "dense_update"):
        assert f"/{scope}/" not in bare
    without = _window_outputs(build)
    np.testing.assert_array_equal(with_scopes[0], without[0])
    assert len(with_scopes[1]) == len(without[1])
    for a, b in zip(with_scopes[1], without[1]):
        np.testing.assert_array_equal(a, b)


def test_scopes_are_on_the_op_names_and_leave_outputs_bit_equal(
    lm, monkeypatch
):
    """The scopes `perfbench/lib/xscope.py` reads a traced run by are on
    the tiny model's window program, and are metadata only."""
    import functools

    scopes_are_metadata(
        functools.partial(_lm_window, lm), "_train_window_jit", lm.scopes,
        monkeypatch,
    )
