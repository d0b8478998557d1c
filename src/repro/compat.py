"""One spelling for the mesh and ``shard_map`` APIs this code uses.

``jax.make_mesh`` defaults to Explicit axes; the sharded index relies on
the partitioner propagating shardings, so meshes here take Auto axes.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AbstractMesh, AxisType


def shard_map(f, mesh, in_specs, out_specs, check_rep: bool = False):
    """``jax.shard_map``; ``check_rep`` maps onto ``check_vma``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes over the first prod(shape)
    devices (or ``devices``)."""
    if devices is None:
        devices = np.array(jax.devices()[:math.prod(shape)])
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def abstract_mesh(shape, axes):
    """``AbstractMesh`` from axis sizes and names."""
    return AbstractMesh(tuple(shape), tuple(axes))
