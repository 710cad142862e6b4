"""Mesh / shard_map helpers shared by the distributed paths.

Spellings over JAX's mesh API (``jax.make_mesh`` with Auto axis types,
``jax.shard_map`` without the varying-axes check), kept in one place so
every caller builds meshes and shard_maps the same way. Callers install an
ambient mesh with ``jax.set_mesh``.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], names: tuple[str, ...]):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(shape))


def shard_map(body, mesh, in_specs: Any, out_specs: Any):
    """``jax.shard_map`` without the varying-manual-axes check; `mesh=None`
    means "use the ambient mesh"."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def flat_axis_index(axes: tuple[str, ...]):
    """Row-major flattened index over several mesh axes."""
    import jax.numpy as jnp
    pid = jnp.int32(0)
    for ax in axes:
        pid = pid * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
    return pid
