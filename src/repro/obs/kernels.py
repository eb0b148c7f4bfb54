"""Kernel hooks: dispatch paths and autotune decisions.

``kernels/dispatch.py`` calls :func:`record_path` when an op resolves and
:func:`record_autotune` when an autotune decision is used.  All recording is
plain-dict bookkeeping — no jax import at module load — so the hooks cost
nothing measurable on the dispatch fast path.
"""
from __future__ import annotations

from typing import Any, Dict

_paths: Dict[str, Dict[str, Any]] = {}
_autotune: Dict[str, Dict[str, Any]] = {}


def record_path(op: str, path: str, *, prefer_pallas: bool = False) -> None:
    """An op resolved to a dispatch path ('pallas' / 'interpret' / 'xla')."""
    entry = _paths.setdefault(op, {"path": path, "count": 0,
                                   "prefer_pallas": prefer_pallas})
    entry["path"] = path
    entry["prefer_pallas"] = prefer_pallas
    entry["count"] += 1


def record_autotune(kind: str, key: Any, decision: Dict[str, Any]) -> None:
    """An autotune decision (cached or freshly swept) was used."""
    _autotune[f"{kind}/{key}"] = dict(decision)


def snapshot() -> Dict[str, Any]:
    return {
        "paths": {k: dict(v) for k, v in sorted(_paths.items())},
        "autotune": {k: dict(v) for k, v in sorted(_autotune.items())},
    }


def reset() -> None:
    _paths.clear()
    _autotune.clear()
