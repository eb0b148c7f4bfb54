"""Compiled-artifact introspection.

``cost_analysis`` always returns a plain (possibly empty) dict so callers
can ``.get()`` without checks, including on backends that do not implement
the analysis.  This is the only place in the repo allowed to call the raw
method.
"""
from __future__ import annotations


def cost_analysis(compiled) -> dict:
    """Per-device cost analysis of a ``jax`` ``Compiled`` object."""
    try:
        ca = compiled.cost_analysis()
    except Exception:                                   # backend w/o support
        return {}
    return dict(ca) if isinstance(ca, dict) else {}


def memory_analysis(compiled):
    """``Compiled.memory_analysis()`` or None where the backend lacks it."""
    try:
        return compiled.memory_analysis()
    except Exception:
        return None
