"""Model export for serving.

Parity: elasticdl/python/common/model_handler.py `get_model_to_export` in
the reference — pull trained parameters, materialize the distributed
embedding tables, and write a self-contained servable artifact.  There the
artifact is a TF SavedModel; here it is a directory a fresh process can
load with `load_for_serving` and run inference from, bit-identical to the
trainer's own eval outputs.

Layout:

    <out_dir>/
      signature.json   - model identity (zoo/def/params), array inventory,
                         framework version: everything needed to rebuild
                         the flax module and bind the variables
      variables.pkl    - nested variables tree (dense params + batch
                         stats); embedding-table leaves are replaced by
                         {"__table__": "tables/<i>.npy"} references
      tables/<i>.npy   - one memmap-friendly .npy per embedding table,
                         written in bounded row chunks (a mesh-sharded
                         table is streamed out range-by-range; the
                         exporting host never holds more than chunk_rows
                         of it in memory)

Tables are stored in the model's own packed lane-tiled layout
(parallel/packed.py) so serving applies the exact variables training used;
`ServingModel.logical_tables()` exposes the unpacked [vocab, dim] view for
external consumers (feature stores, ANN indexes).
"""

from __future__ import annotations

import json
import os
import pickle
from types import SimpleNamespace
from typing import Any, Dict, Optional

import numpy as np

from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("serving.export")

_SIGNATURE = "signature.json"
_VARIABLES = "variables.pkl"
_TABLES_DIR = "tables"
_TABLE_REF = "__table__"


def _set_in_tree(tree: Dict, path, value):
    node = tree
    for part in path[:-1]:
        node = node[part]
    node[path[-1]] = value


def _stream_table_to_npy(array, path: str, chunk_rows: int, write: bool):
    """Write a (possibly mesh-sharded, device-resident) array to .npy in
    row chunks: each chunk fetches only those rows to host, so export
    memory stays bounded regardless of table size.

    COLLECTIVE for process-spanning arrays: a chunk whose rows live on
    another process's devices is not addressable here, so every process
    must call this (the per-chunk gather is a collective); only the
    `write`-ing process (rank 0) touches the file."""
    import jax

    out = None
    if write:
        out = np.lib.format.open_memmap(
            path,
            mode="w+",
            dtype=np.dtype(str(array.dtype)),
            shape=array.shape,
        )
    rows = array.shape[0]
    for lo in range(0, rows, chunk_rows):
        hi = min(rows, lo + chunk_rows)
        chunk = array[lo:hi]
        if getattr(chunk, "is_fully_addressable", True):
            host = np.asarray(chunk)
        else:
            from jax.experimental import multihost_utils

            host = np.asarray(
                multihost_utils.process_allgather(chunk, tiled=True)
            )
        if out is not None:
            out[lo:hi] = host
    if out is not None:
        out.flush()
        del out


def export_model(
    trainer,
    out_dir: str,
    model_zoo: str = "",
    model_def: str = "",
    model_params: str = "",
    chunk_rows: int = 65536,
) -> str:
    """Write the servable artifact for a trained DataParallelTrainer /
    ShardedEmbeddingTrainer.

    In a multi-process world EVERY process must call this (PS-mode tables
    are sharded across all processes, so materializing them is a
    collective row-gather); only rank 0 writes files.
    """
    state = trainer.state
    if state is None:
        raise ValueError("Cannot export: model was never initialized")
    import jax

    write = jax.process_index() == 0
    if write:
        os.makedirs(out_dir, exist_ok=True)
    if hasattr(state, "tables"):
        # PS mode: dense params are replicated (tables handled below).
        params = jax.device_get(state.params)
        model_state = jax.device_get(state.model_state)
    else:
        # Gather ONLY what serving needs (params + batch stats) — never
        # the optimizer state, which doubles-or-triples the transfer for
        # nothing.  gather_to_host is a collective for FSDP-sharded
        # leaves and a plain host fetch for replicated/local state.
        from elasticdl_tpu.parallel import sharding as _shd

        host = _shd.gather_to_host(
            {"params": state.params, "model_state": state.model_state}
        )
        params = host["params"]
        model_state = host["model_state"]
    # Unfreeze so table placeholders can be replaced by refs in place.
    params = jax.tree.map(lambda x: x, params)

    tables_meta = []
    if hasattr(state, "tables") and state.tables:
        # PS mode: placeholders sit where the packed tables belong
        # (ps_trainer splits them out at init); stream each device-sharded
        # table to its own file and point the tree at it.
        if write:
            os.makedirs(os.path.join(out_dir, _TABLES_DIR), exist_ok=True)
        for i, (key, array) in enumerate(sorted(state.tables.items())):
            rel = f"{_TABLES_DIR}/{i}.npy"
            _stream_table_to_npy(
                array, os.path.join(out_dir, rel), chunk_rows, write
            )
            spec = trainer._table_specs[key]
            tables_meta.append(
                {
                    "key": key,
                    "file": rel,
                    "vocab_size": spec.vocab_size,
                    "dim": spec.dim,
                    "packed_shape": list(array.shape),
                }
            )
            _set_in_tree(
                params, trainer._table_paths[key], {_TABLE_REF: rel}
            )

    if not write:
        return out_dir

    variables = {"params": params, **model_state}
    with open(os.path.join(out_dir, _VARIABLES), "wb") as f:
        pickle.dump(variables, f)

    import elasticdl_tpu

    signature = {
        "format": "elasticdl_tpu_serving/1",
        "framework_version": elasticdl_tpu.__version__,
        "model_zoo": model_zoo,
        "model_def": model_def,
        "model_params": model_params,
        "tables": tables_meta,
        "step": int(np.asarray(jax.device_get(state.step))),
    }
    with open(os.path.join(out_dir, _SIGNATURE), "w") as f:
        json.dump(signature, f, indent=2)
    logger.info(
        "Exported servable model to %s (step %d, %d embedding table(s))",
        out_dir,
        signature["step"],
        len(tables_meta),
    )
    return out_dir


class ServingModel:
    """A loaded artifact: rebuildable module + bound variables.

    `predict` runs the model's inference path (train=False, no mutable
    collections — the Embedding layers' training-only sows are no-ops), so
    outputs are bit-identical to the trainer's eval for the same inputs.
    """

    def __init__(
        self,
        model,
        variables: Dict,
        signature: dict,
        base_dir: str,
        tables: Optional[Dict[str, np.ndarray]] = None,
    ):
        self._model = model
        self._variables = variables
        self.signature = signature
        self._base_dir = base_dir
        # key -> resolved packed table (host view); what delta apply
        # patches row-wise (serving/runtime.py).  Empty for artifacts
        # loaded by callers that never delta-apply.
        self.tables: Dict[str, np.ndarray] = tables or {}

    def predict(self, features):
        from elasticdl_tpu.parallel.trainer import model_apply

        outputs, _ = model_apply(
            self._model, self._variables, features, train=False, mutable=False
        )
        return outputs

    @property
    def model(self):
        return self._model

    @property
    def variables(self) -> Dict:
        return self._variables

    @property
    def base_dir(self) -> str:
        return self._base_dir

    def logical_tables(self) -> Dict[str, np.ndarray]:
        """Unpacked [vocab, dim] embedding tables (external-consumer view:
        feature stores, ANN indexes).  Materializes each table on host."""
        from elasticdl_tpu.parallel import packed as pk
        from elasticdl_tpu.parallel.packed import PackedSpec

        out = {}
        for meta in self.signature["tables"]:
            packed = np.load(
                os.path.join(self._base_dir, meta["file"]), mmap_mode="r"
            )
            spec = PackedSpec(meta["vocab_size"], meta["dim"])
            out[meta["key"]] = np.asarray(pk.unpack(spec, packed))
        return out


def load_for_serving(
    out_dir: str,
    model_zoo: str = "",
    mmap: bool = True,
) -> ServingModel:
    """Load an artifact in a fresh process.  `model_zoo` overrides the
    recorded zoo path when the artifact moved between machines."""
    from elasticdl_tpu.common.model_utils import load_model_spec

    with open(os.path.join(out_dir, _SIGNATURE)) as f:
        signature = json.load(f)
    with open(os.path.join(out_dir, _VARIABLES), "rb") as f:
        variables = pickle.load(f)

    key_by_file = {m["file"]: m["key"] for m in signature.get("tables", [])}
    tables: Dict[str, np.ndarray] = {}

    def resolve(leaf):
        if isinstance(leaf, dict) and _TABLE_REF in leaf:
            array = np.load(
                os.path.join(out_dir, leaf[_TABLE_REF]),
                mmap_mode="r" if mmap else None,
            )
            key = key_by_file.get(leaf[_TABLE_REF])
            if key is not None:
                tables[key] = array
            return array
        return leaf

    variables = _map_tree_with_refs(variables, resolve)
    spec_args = SimpleNamespace(
        model_zoo=model_zoo or signature["model_zoo"],
        model_def=signature["model_def"],
        model_params=signature["model_params"],
        loss="loss",
        optimizer="optimizer",
        dataset_fn="dataset_fn",
        eval_metrics_fn="",
        callbacks="",
        custom_data_reader="",
    )
    model = load_model_spec(spec_args).build_model()
    return ServingModel(model, variables, signature, out_dir, tables=tables)


def _map_tree_with_refs(tree, fn):
    """tree.map that treats {"__table__": ...} dicts as leaves."""
    if isinstance(tree, dict):
        if _TABLE_REF in tree:
            return fn(tree)
        return {k: _map_tree_with_refs(v, fn) for k, v in tree.items()}
    return fn(tree)
