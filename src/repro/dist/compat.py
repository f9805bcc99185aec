"""Mesh and shard_map entry points for the distribution layer (jax 0.9).

* ``shard_map`` keeps the repo's positional ``(f, mesh, in_specs,
  out_specs)`` calling convention, with replication checking off by default.
* ``make_mesh`` pins every axis to ``AxisType.Auto`` (GSPMD sharding
  propagation, which the step code relies on) and takes a prefix subset of
  the devices when the host has more than the mesh needs.
"""

from __future__ import annotations

from typing import Any, Callable

import jax

__all__ = ["shard_map", "make_mesh"]


def shard_map(f: Callable, mesh: Any, in_specs: Any, out_specs: Any, *, check_rep: bool = False) -> Callable:
    """``jax.shard_map`` over every axis of ``mesh``; ``check_rep`` maps to
    ``check_vma``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_rep)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axes over a prefix subset of devices
    (plain ``jax.make_mesh`` insists on using all of them)."""
    n = 1
    for s in axis_shapes:
        n *= int(s)
    if devices is None:
        avail = jax.devices()
        if len(avail) > n:
            devices = avail[:n]
    return jax.make_mesh(
        tuple(axis_shapes),
        tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices,
    )
