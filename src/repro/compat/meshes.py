"""Mesh construction.

``jax.make_mesh`` builds meshes with Explicit axis types by default, under
which a gather on a sharded operand (``jnp.take`` of a vocab-sharded
embedding) must state its ``out_sharding``.  The repo's sharding rules are
written for Auto axes — the compiler propagates shardings — so every mesh
is built here with all axes Auto.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: tuple, axis_names: tuple, *, devices=None):
    """``jax.make_mesh(axis_shapes, axis_names)`` with every axis Auto."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)
