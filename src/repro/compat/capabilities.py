"""One-shot capability snapshot of the installed JAX + backend.

``capabilities()`` is probed lazily on first call and cached for the process:
the kernel dispatch registry, the dry-run env record, and the test env report
all read the same snapshot, so every layer agrees on what the runtime can do.
"""
from __future__ import annotations

import functools
from dataclasses import asdict, dataclass

import jax

from repro.compat.pallas import backend, pallas_interpret, pallas_native


@dataclass(frozen=True)
class Capabilities:
    jax_version: str
    backend: str
    device_count: int
    pallas_native: bool              # Pallas compiles to this backend
    pallas_interpret: bool           # interpret mode for Pallas calls

    def to_dict(self) -> dict:
        return asdict(self)


@functools.lru_cache(maxsize=None)
def capabilities() -> Capabilities:
    """Probe once, then serve the cached snapshot."""
    return Capabilities(
        jax_version=jax.__version__,
        backend=backend(),
        device_count=jax.device_count(),
        pallas_native=pallas_native(),
        pallas_interpret=pallas_interpret(),
    )
