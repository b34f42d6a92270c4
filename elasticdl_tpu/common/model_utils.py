"""Model-zoo module loading.

Parity: elasticdl/python/common/model_utils.py in the reference — dynamic
import of the user's model module by zoo path + dotted module name, and
resolution of the contract functions (custom_model / loss / optimizer /
dataset_fn / eval_metrics_fn / callbacks / custom_data_reader).
"""

from __future__ import annotations

import importlib
import os
import sys
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from elasticdl_tpu.common.args import parse_dict_params
from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("common.model_utils")


@dataclass
class ModelSpec:
    module: Any
    custom_model: Callable
    loss: Callable
    optimizer: Callable
    dataset_fn: Callable
    # Optional vectorized twin of dataset_fn: (columns dict, mode,
    # metadata) -> (features tree, labels) operating on whole column
    # arrays.  With a reader exposing read_columns(task), the worker's
    # task pipeline then never touches individual records
    # (data/columnar.py — the 1-core-host data plane).
    columnar_dataset_fn: Optional[Callable] = None
    eval_metrics_fn: Optional[Callable] = None
    callbacks: Optional[Callable] = None
    custom_data_reader: Optional[Callable] = None
    # Optional: returns a parallel.sparse_optim.SparseOptimizer for the
    # model's sharded embedding tables (PS mode; reference: the Go PS ran
    # one optimizer for dense+sparse, here the sparse path is explicit).
    embedding_optimizer: Optional[Callable] = None
    model_params: dict = field(default_factory=dict)

    def build_model(self, mesh=None):
        """`mesh` is forwarded only to mesh-aware models (custom_model
        declaring a `mesh` parameter — e.g. the transformer's ring
        attention needs the mesh for its context axis)."""
        import inspect

        params = dict(self.model_params)
        if mesh is not None and "mesh" not in params:
            try:
                accepts_mesh = (
                    "mesh" in inspect.signature(self.custom_model).parameters
                )
            except (TypeError, ValueError):
                accepts_mesh = False
            if accepts_mesh:
                from elasticdl_tpu.common.log_utils import get_logger

                params["mesh"] = mesh
                # e2e tests grep this line to prove the mesh actually
                # reached the model (TP/CP silently degrade to
                # single-device layouts without it).
                get_logger("common.model_utils").info(
                    "Mesh-aware model: forwarding mesh %s",
                    dict(mesh.shape),
                )
        return self.custom_model(**params)


def load_module(model_zoo: str, model_def: str):
    """Import `model_def` (dotted module path) from the `model_zoo` directory.

    `model_zoo` may be a directory (added to sys.path, reference behavior)
    or an importable package name.
    """
    if os.path.isdir(model_zoo):
        parent = os.path.abspath(os.path.join(model_zoo, os.pardir))
        if parent not in sys.path:
            sys.path.insert(0, parent)
        zoo_package = os.path.basename(os.path.normpath(model_zoo))
        module_name = f"{zoo_package}.{model_def}"
    else:
        module_name = f"{model_zoo}.{model_def}" if model_zoo else model_def
    return importlib.import_module(module_name)


def _forward_flag(custom_model, model_params: dict, name, value) -> None:
    """Inject a job-flag value into model_params when custom_model
    declares the parameter and --model_params didn't set it explicitly."""
    import inspect

    try:
        accepts = name in inspect.signature(custom_model).parameters
    except (TypeError, ValueError):
        accepts = False
    if accepts and name not in model_params:
        model_params[name] = value


#: Frameworks a zoo module may bring along; `spec.load` says which of
#: them entered `sys.modules` while it ran.
_FRAMEWORKS = ("jax", "flax", "torch", "tensorflow")


def load_model_spec(args) -> ModelSpec:
    """Resolve the model-zoo contract from parsed args, inside a
    `spec.load` span: the zoo module's import is seconds of a master's
    and of a worker's boot."""
    from elasticdl_tpu.obs import tracing

    with tracing.span("spec.load") as span:
        absent = [name for name in _FRAMEWORKS if name not in sys.modules]
        spec = _load_model_spec(args)
        span.fields["imported"] = [
            name for name in absent if name in sys.modules
        ]
    return spec


def _load_model_spec(args) -> ModelSpec:
    module = load_module(args.model_zoo, args.model_def)

    def require(name):
        fn = getattr(module, name, None)
        if fn is None:
            raise ValueError(
                f"Model module {args.model_def!r} must define {name}()"
            )
        return fn

    def optional(name):
        return getattr(module, name, None) if name else None

    custom_model = require("custom_model")
    model_params = parse_dict_params(args.model_params)
    # Job flags reach opted-in models here: a zoo model declares the
    # parameter on custom_model() and the flag value flows into
    # model_params.  Explicit --model_params wins; models without the
    # parameter are untouched.
    # - use_bf16: mixed precision (e.g. cifar10's conv/activation dtype).
    # - sparse_apply_every: per-mode table layout (deepfm splits its
    #   merged table under strict apply at large scale — BASELINE.md
    #   table-scale probe).
    _forward_flag(
        custom_model, model_params, "use_bf16",
        bool(getattr(args, "use_bf16", True)),
    )
    job_w = getattr(args, "sparse_apply_every", 1) or 1
    if job_w != "auto":
        job_w = int(job_w)
    explicit_w = model_params.get("sparse_apply_every")
    if explicit_w is not None and explicit_w != job_w and job_w != "auto":
        # job_w == "auto" resolves only at trainer init, so no static
        # comparison is possible here — and an explicit numeric layout
        # pin under the auto default is the documented escape hatch, not
        # an inconsistency; warning on every such job would be noise.
        # An explicit --model_params sparse_apply_every wins over the job
        # flag here (layout override is a supported escape hatch), but
        # the trainer still APPLIES with the job flag's W — the model
        # would run a layout the strict/windowed cost analysis picked for
        # a different mode.  Numerically valid, so warn rather than fail.
        logger.warning(
            "model_params sparse_apply_every=%s overrides the job flag "
            "--sparse_apply_every=%s for the TABLE LAYOUT only; the "
            "trainer still applies with the job flag's interval. Drop "
            "the model param unless you are deliberately pinning a "
            "layout.",
            explicit_w, job_w,
        )
    _forward_flag(
        custom_model, model_params, "sparse_apply_every", job_w,
    )
    # - sparse_kernel: lookup/FM engine selection for models that thread
    #   it into their Embedding layers (deepfm); worker main also sets
    #   the process default, so this forward only matters for the
    #   layout-aware auto rules (deepfm merges its table under fused).
    _forward_flag(
        custom_model, model_params, "sparse_kernel",
        getattr(args, "sparse_kernel", "auto") or "auto",
    )

    return ModelSpec(
        module=module,
        custom_model=custom_model,
        loss=require(args.loss),
        optimizer=require(args.optimizer),
        dataset_fn=require(args.dataset_fn),
        columnar_dataset_fn=optional("columnar_dataset_fn"),
        eval_metrics_fn=optional(args.eval_metrics_fn),
        callbacks=optional(args.callbacks),
        custom_data_reader=optional(args.custom_data_reader),
        embedding_optimizer=optional("embedding_optimizer"),
        model_params=model_params,
    )
