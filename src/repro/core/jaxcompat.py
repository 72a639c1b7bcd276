"""Mesh and ``shard_map`` spellings shared by the sharded layers.

``jax.make_mesh`` defaults to explicit-sharding axis types; every mesh this
package builds uses ``Auto`` axes, which ``shard_map`` bodies and
``NamedSharding`` placement here assume.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def make_mesh(shape, axis_names, devices=None):
    return jax.make_mesh(
        shape, axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
