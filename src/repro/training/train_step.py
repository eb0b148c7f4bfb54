"""Train-step builder: loss → grads → (compressed) reduce → AdamW, under pjit.

Microbatch gradient accumulation is a ``lax.scan`` whose per-microbatch
data-parallel reduction XLA can overlap with the next microbatch's backward
(latency-hiding scheduler) — the accumulate-then-step structure is what makes
that overlap legal.  Gradients are cast to ``grad_reduce_dtype`` (default
bf16) at the autodiff boundary so the cross-replica all-reduce moves half the
bytes (verified in the dry-run HLO, §Perf).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro import compat
from repro.configs.base import RunConfig
from repro.distributed import compression
from repro.models import encdec, transformer
from repro.models.params import init_params
from repro.optim import adamw

PyTree = Any


def loss_for(cfg) -> Callable:
    return encdec.loss_fn if cfg.family == "encdec" else transformer.loss_fn


def make_train_step(run: RunConfig) -> Callable:
    """Pure (params, opt_state, batch) → (params, opt_state, metrics)."""
    cfg = run.model
    loss_fn = loss_for(cfg)
    n_micro = run.parallel.microbatches

    def train_step(params, opt_state, batch):
        grad_fn = jax.value_and_grad(
            lambda p, b: loss_fn(p, b, cfg), has_aux=True)
        if n_micro == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            mb = compat.tree_map(
                lambda x: x.reshape((n_micro, x.shape[0] // n_micro)
                                    + x.shape[1:]), batch)

            def body(acc, b_i):
                (l, m), g = grad_fn(params, b_i)
                g = compression.cast_grads(g, run.parallel.grad_reduce_dtype)
                acc = compat.tree_map(
                    lambda a, x: a + x.astype(jnp.float32), acc, g)
                return acc, (l, m)

            zeros = compat.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, (losses, ms) = jax.lax.scan(body, zeros, mb)
            grads = compat.tree_map(lambda g: g / n_micro, grads)
            loss = losses.mean()
            metrics = compat.tree_map(jnp.mean, ms)
        grads = compression.cast_grads(grads, run.parallel.grad_reduce_dtype)
        params, opt_state, om = adamw.update(grads, opt_state, params,
                                             run.optimizer)
        return params, opt_state, {**metrics, **om, "loss_out": loss}

    return train_step


def init_state(run: RunConfig, rng) -> tuple[PyTree, PyTree, PyTree]:
    """Returns (params, opt_state, axes_tree)."""
    cfg = run.model
    params, axes = init_params(cfg, rng)
    opt_state = adamw.init(params)
    return params, opt_state, axes

