"""``shard_map`` behind one stable signature.

All repo code imports ``shard_map`` from ``repro.compat`` — never from
``jax`` directly — so a future API move is a one-file change.
"""
from __future__ import annotations

from typing import Callable

import jax


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check_vma: bool = True) -> Callable:
    """``jax.shard_map`` with keyword-only mesh/spec arguments."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
