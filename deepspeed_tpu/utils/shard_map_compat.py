"""The one place the package spells ``jax.shard_map`` and mesh-axis
introspection (``analysis/lint.py`` R1 keeps raw ``shard_map`` calls out of
every other module), so explicit-collective code — onebit, zeropp, the
pipeline, tests — shares one calling convention.
"""

import jax
from jax import lax


def shard_map_nocheck(fn, mesh, in_specs, out_specs):
    """shard_map with the varying-manual-axes check disabled."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def shard_map(fn, mesh, in_specs, out_specs, **kw):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def axis_size(axis) -> int:
    """Size of a mesh axis from inside shard_map (trace-time static)."""
    return int(lax.axis_size(axis))


def manual_axes() -> frozenset:
    """Mesh axes currently bound manual (i.e. tracing inside a shard_map).
    Callers that would NEST a shard_map (the collective-matmul overlap
    wiring) must stay on the declarative path when this is non-empty."""
    return frozenset(jax.sharding.get_abstract_mesh().manual_axes)


def shard_map_nocheck_manual(fn, mesh, in_specs, out_specs, axis_names):
    """``shard_map_nocheck`` over an explicit manual-axes set; the mesh's
    other axes stay automatic."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False,
                         axis_names=set(axis_names))
