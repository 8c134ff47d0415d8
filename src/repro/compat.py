"""The jax APIs this repo uses, spelled once for the installed jax (0.9).

Every use site imports these names from here:

  * ``VMEM`` / ``CompilerParams`` — Pallas TPU scratch + params.
  * ``make_mesh(shape, axes)`` — a mesh whose axes are ``AxisType.Auto``.
    jax 0.9's ``jax.make_mesh`` defaults to Explicit axes, which change how
    jit places un-annotated arrays; the repo's sharding rules are written
    for Auto (``with_sharding_constraint`` + ``in_shardings``).
  * ``shard_map`` — ``jax.shard_map`` called with its mesh installed as the
    ambient mesh, so an eager call works without the caller entering
    ``set_mesh`` first.
  * ``set_mesh(mesh)`` / ``get_abstract_mesh()`` — the ambient mesh.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AxisType

VMEM = pltpu.MemorySpace.VMEM
CompilerParams = pltpu.CompilerParams
pcast = jax.lax.pcast
set_mesh = jax.sharding.set_mesh


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def get_abstract_mesh():
    """The ambient mesh, or None outside a mesh context."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def shard_map(f, *, mesh, in_specs, out_specs, **kw):
    """``jax.shard_map`` over `mesh`; outside any mesh context the call
    runs under ``set_mesh(mesh)``."""
    mapped = jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, **kw)

    def call(*args):
        if get_abstract_mesh() is not None:
            return mapped(*args)
        with set_mesh(mesh):
            return mapped(*args)

    return call
