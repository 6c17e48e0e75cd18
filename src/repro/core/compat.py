"""The few JAX calls the repo makes with fixed arguments, in one place.

``make_mesh`` always uses Auto axis types and ``shard_map`` never checks
varying-manual-axes; every mesh and shard_map in the repo goes through
these two so the choice is made once.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], names: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names))


def axis_size(axis_name: str):
    """Size of a named mesh axis inside ``shard_map``."""
    return jax.lax.axis_size(axis_name)


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` (replication checking off by default)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
