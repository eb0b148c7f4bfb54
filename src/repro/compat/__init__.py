"""Portability layer: every version-sensitive JAX surface, one import.

The repo is floored at the installed JAX (0.9.0).  The policy that keeps an
API move a one-file change:

* **No module outside ``repro.compat`` imports ``shard_map``, calls
  ``cost_analysis()`` / ``make_mesh`` raw, decides Pallas interpret mode
  itself, touches the ``jax.tree``/``jax.tree_util`` namespaces directly, or
  constructs a ``NamedSharding`` raw.**  Grep-enforced by
  ``tests/test_compat.py``.
* ``capabilities()`` snapshots the backend once per process; the kernel
  dispatch registry (``repro.kernels.dispatch``), the dry-run env record, and
  the test env report all read that one snapshot.
"""
from repro.compat.capabilities import Capabilities, capabilities
from repro.compat.meshes import make_mesh
from repro.compat.pallas import backend, pallas_interpret, pallas_native
from repro.compat.shardings import named_sharding
from repro.compat.shmap import shard_map
from repro.compat.trees import (
    tree_flatten,
    tree_flatten_with_path,
    tree_leaves,
    tree_map,
    tree_map_with_path,
    tree_reduce,
    tree_structure,
    tree_unflatten,
)
from repro.compat.xla import cost_analysis, memory_analysis

__all__ = [
    "Capabilities", "capabilities",
    "make_mesh",
    "backend", "pallas_interpret", "pallas_native",
    "named_sharding",
    "shard_map",
    "tree_flatten", "tree_flatten_with_path",
    "tree_leaves", "tree_map", "tree_map_with_path", "tree_reduce",
    "tree_structure", "tree_unflatten",
    "cost_analysis", "memory_analysis",
]
