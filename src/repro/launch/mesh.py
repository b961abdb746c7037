"""Mesh construction: every mesh in the repository is built here.

Axes are ``AxisType.Auto``: sharding propagates through jit as in the
planner's PartitionSpecs, with no per-op ``out_sharding``.  (``jax.make_mesh``
defaults to Explicit axes since JAX 0.7, which breaks gathers and grads whose
operands are sharded.)

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import)."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.core.types import MULTI_POD_MESH, SINGLE_POD_MESH, MeshConfig


def make_mesh(shape: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence] = None) -> Mesh:
    """A mesh of ``shape`` with Auto axes, over ``devices`` when given
    (described TPU devices for compile rehearsals, a subset of the host's
    devices), otherwise over the first ``prod(shape)`` visible devices."""
    axis_types = (AxisType.Auto,) * len(shape)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axis_names),
                             axis_types=axis_types)
    devs = np.asarray(devices, dtype=object).reshape(tuple(shape))
    return Mesh(devs, tuple(axis_names), axis_types=axis_types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MULTI_POD_MESH if multi_pod else SINGLE_POD_MESH
