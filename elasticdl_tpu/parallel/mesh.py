"""Device mesh construction and (re-)formation.

The reference scales data-parallel training over NCCL/Gloo rings whose
membership is managed by FTlib gossip or Horovod's Gloo rendezvous
(SURVEY.md §2.1).  On TPU the communicator *is* the compiled program: we
build a `jax.sharding.Mesh` over the visible devices and let XLA lower
`psum`/`all_gather`/`all_to_all` onto ICI.  Elasticity then means
re-building the mesh over the surviving process set (see
elasticdl_tpu.parallel.elastic), not re-building a ring library.

Axis conventions (used across the framework):

- ``data``  — data parallel (batch dim).  Always present.
- ``model`` — tensor/model parallel (embedding-table shards, matmul
  sharding).  Size 1 unless requested.

A mesh of shape (data, model) covers every parallelism the reference has
(data parallel + PS-partitioned embedding tables, SURVEY.md §2.6) and is
the substrate the sharded embedding engine rides on.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("parallel.mesh")

DATA_AXIS = "data"
MODEL_AXIS = "model"


@dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape. -1 for `data` means "all remaining devices"."""

    data: int = -1
    model: int = 1

    def resolve(self, n_devices: int) -> tuple[int, int]:
        model = max(1, self.model)
        if n_devices % model != 0:
            raise ValueError(
                f"model axis {model} does not divide device count {n_devices}"
            )
        data = self.data if self.data != -1 else n_devices // model
        if data * model != n_devices:
            raise ValueError(
                f"mesh {data}x{model} != device count {n_devices}"
            )
        return data, model


def build_mesh(
    config: MeshConfig = MeshConfig(),
    devices: Optional[Sequence] = None,
):
    """Build a 2-D (data, model) Mesh over `devices` (default: all)."""
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    data, model = config.resolve(len(devices))
    mesh = Mesh(
        np.asarray(devices).reshape(data, model), (DATA_AXIS, MODEL_AXIS)
    )
    logger.info(
        "Built mesh %dx%d (%s x %s) over %d %s device(s) [%s]",
        data,
        model,
        DATA_AXIS,
        MODEL_AXIS,
        len(devices),
        devices[0].platform,
        devices[0].device_kind,
    )
    return mesh


def force_virtual_cpu_devices(n: int) -> None:
    """Emulate an n-chip slice on CPU (must run before jax backend init).

    This is the test-harness fake-device layer (SURVEY.md §4): pjit/psum/
    mesh-reformation logic runs identically on n virtual CPU devices and on
    a real TPU slice.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
