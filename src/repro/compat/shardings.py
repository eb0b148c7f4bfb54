"""``NamedSharding`` construction.

``named_sharding(mesh, spec)`` is the one constructor the rest of the repo
calls (grep-enforced by ``tests/test_compat.py``: no module outside
``repro.compat`` constructs a ``NamedSharding`` raw), so specs given as
tuples or ``None`` are normalized in one place.
"""
from __future__ import annotations

from jax.sharding import NamedSharding, PartitionSpec


def named_sharding(mesh, spec=None):
    """``NamedSharding(mesh, spec)``.

    ``spec`` may be a ``PartitionSpec``, a tuple/list of axis entries (wrapped
    into one), or ``None`` (replicated)."""
    if spec is None:
        spec = PartitionSpec()
    elif not isinstance(spec, PartitionSpec):
        spec = PartitionSpec(*spec)
    return NamedSharding(mesh, spec)
