"""Mesh and shard_map helpers shared by every module that builds a mesh
or a manual region.

Every caller goes through these helpers, so the spelling of the mesh
and shard_map APIs of the installed JAX (0.9) lives in one place.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax import lax
from jax.sharding import AbstractMesh, AxisType

__all__ = ["AxisType", "abstract_mesh", "axis_size", "make_mesh",
           "shard_map"]


def axis_size(axis: str) -> int:
    """Size of a bound mesh/vmap axis, from inside shard_map/vmap."""
    return int(lax.axis_size(axis))


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              axis_types: Optional[Tuple[Any, ...]] = None):
    """``jax.make_mesh`` with every axis ``Auto`` unless told otherwise."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(axis_types or
                                     (AxisType.Auto,) * len(axes)))


def abstract_mesh(shape: Sequence[int], axes: Sequence[str]):
    """A device-free mesh of ``shape`` over ``axes``."""
    return AbstractMesh(tuple(shape), tuple(axes))


def shard_map(f, mesh, in_specs, out_specs, check: bool = False,
              axis_names: Optional[set] = None):
    """``jax.shard_map``; ``check`` is its ``check_vma`` and
    ``axis_names`` selects a partial-manual region."""
    kw: Dict[str, Any] = {"check_vma": check}
    if axis_names is not None:
        kw["axis_names"] = axis_names
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)
