"""Mesh construction: every mesh in the repository is built here.

Axes are ``AxisType.Auto``: sharding propagates through jit as in the
planner's PartitionSpecs, with no per-op ``out_sharding``.  (``jax.make_mesh``
defaults to Explicit axes since JAX 0.7, which breaks gathers and grads whose
operands are sharded.)

A function, not a module-level mesh: importing this module never touches
jax device state (callers may set XLA_FLAGS before the first device query)."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


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

