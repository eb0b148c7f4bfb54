"""Pytree API aliases.

One set of names for the ``jax.tree`` namespace, so nothing outside
``repro.compat`` spells it (grep-enforced by ``tests/test_compat.py``).
"""
from __future__ import annotations

import jax

tree_map = jax.tree.map
tree_leaves = jax.tree.leaves
tree_structure = jax.tree.structure
tree_flatten = jax.tree.flatten
tree_unflatten = jax.tree.unflatten
tree_reduce = jax.tree.reduce
tree_flatten_with_path = jax.tree.flatten_with_path
tree_map_with_path = jax.tree.map_with_path
