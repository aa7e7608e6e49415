"""The two jax entry points every mesh and shard_map goes through.

Written for the installed jax (0.9): ``jax.shard_map`` with ``check_vma``
and ``jax.make_mesh(..., axis_types=...)``.  Call sites use these names,
so a later jax API change is one edit here.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """jax.shard_map with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """jax.make_mesh with Auto axis types."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names), **kwargs)


def jaxpr_types():
    """The (Jaxpr, ClosedJaxpr) classes, which the static analyzer uses to
    recurse into sub-jaxprs."""
    from jax.extend import core as xcore
    return xcore.Jaxpr, xcore.ClosedJaxpr
