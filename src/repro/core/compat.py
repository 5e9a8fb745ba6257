"""Thin wrappers over the JAX mesh and ``shard_map`` APIs the repo uses.

They spell two choices once, so call sites stay short: every mesh axis is
``AxisType.Auto``, and ``shard_map`` runs with its replication check off
(the FFT bodies mix collectives whose replication the checker cannot see).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh


def _auto(n: int) -> tuple:
    return (AxisType.Auto,) * n


def make_mesh(shape: Sequence[int], names: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    shape, names = tuple(shape), tuple(names)
    return jax.make_mesh(shape, names, axis_types=_auto(len(shape)))


def mesh_from_devices(dev_array, names: Sequence[str]) -> Mesh:
    """``Mesh(devices, names)`` with Auto axis types."""
    names = tuple(names)
    return Mesh(dev_array, names, axis_types=_auto(len(names)))


def abstract_mesh(shape: Sequence[int], names: Sequence[str]):
    """Device-less mesh for plan construction/inspection."""
    return AbstractMesh(tuple(shape), tuple(names))


def is_tracer(x) -> bool:
    """True when ``x`` is an abstract tracer (inside jit/vmap tracing).

    Placement pinning must switch from ``device_put`` (eager arrays) to
    ``with_sharding_constraint`` (tracers).
    """
    return isinstance(x, jax.core.Tracer)


def shard_map(body, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
