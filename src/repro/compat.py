"""Thin helpers over the jax API constructs the repo spells in one way.

The repo runs on jax 0.9.0. Each construct that call sites would otherwise
repeat lives here, in one helper:

* ``make_mesh`` -- `jax.make_mesh` with every axis typed
  ``jax.sharding.AxisType.Auto``;
* ``cost_analysis`` -- a compiled program's cost dict (empty when the
  backend reports none);
* ``shard_map`` -- `jax.shard_map` with its replication check
  (``check_vma``) named ``check_replication``.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax


def make_mesh(shape: Tuple[int, ...], axis_names: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """`jax.make_mesh` with auto axis types."""
    kwargs = {}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(
        tuple(shape), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        **kwargs)


def cost_analysis(compiled) -> dict:
    """Flat cost dict of a compiled computation ({} when unreported)."""
    return compiled.cost_analysis() or {}


def shard_map(f, *, mesh, in_specs, out_specs, check_replication: bool = True):
    """`jax.shard_map`; ``check_replication`` is its ``check_vma``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_replication)
