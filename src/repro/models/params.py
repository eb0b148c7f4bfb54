"""Random parameter initialization, compiled as one program."""
from __future__ import annotations

from typing import Any

import jax

from repro.models import encdec, layers as L, transformer

PyTree = Any


def init_params(cfg, rng) -> tuple[PyTree, PyTree]:
    """Random weights for ``cfg`` from ``rng``: (values, logical-axes tree).

    The init is traced and compiled as one program.  Run op by op, every
    small init op compiles on its own: about 50 s for smollm_360m on a TPU
    v5e, most of it under the persistent compile cache's 1 s threshold, so
    paid again on every start.  ``jax.random`` draws the same bits either
    way."""
    init_fn = encdec.init if cfg.family == "encdec" else transformer.init
    captured = {}

    def values(key):
        vals, captured["axes"] = L.split_params(init_fn(key, cfg))
        return vals

    return jax.jit(values)(rng), captured["axes"]
