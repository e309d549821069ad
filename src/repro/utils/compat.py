"""Mesh and ``shard_map`` helpers for the installed jax (0.9).

* ``make_mesh`` — a device mesh whose axes are all ``AxisType.Auto``: the
  LDA steps place data with ``NamedSharding``s and let the compiler
  propagate them.
* ``shard_map`` — ``jax.shard_map`` with replication checking off by
  default (the LDA steps mix replicated and sharded outputs on purpose).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """Device mesh of ``shape`` over ``axes``, every axis Auto."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    """Per-device SPMD mapping of ``f`` over ``mesh``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
