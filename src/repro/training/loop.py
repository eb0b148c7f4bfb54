"""Fault-tolerant training loop: checkpoint/restart, straggler watchdog,
heartbeats, crash-exact data resumption.

Failure model (what actually happens at 1000+ nodes): a worker dies → the job
is rescheduled → every host restarts this loop → ``run()`` restores the last
COMMITTED checkpoint and the counter-based data pipeline regenerates exactly
the next batch.  The loop is deliberately a dumb idempotent function of
(checkpoint dir, step) — all cleverness lives in the substrate:

* ``CheckpointManager`` — async + atomic commit (no torn checkpoints);
* ``SyntheticDataset.batch(step)`` — stateless data (no iterator state to
  lose);
* step-time watchdog — median-based straggler detection; on real clusters
  this is where you'd trigger hot-spare swap; here it logs and records;
* heartbeat file — external orchestrators (k8s/B****) kill hung workers by
  heartbeat age, which composes with restart-from-checkpoint above.

``inject_failure`` lets tests crash the loop at an arbitrary step and assert
bit-exact recovery (tests/test_fault_tolerance.py).
"""
from __future__ import annotations

import json
import os
from typing import Callable, Optional

import jax
import numpy as np

from repro import compat
from repro.obs import clock as obs_clock
from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import RunConfig
from repro.data.synthetic import SyntheticConfig, SyntheticDataset


class StragglerWatchdog:
    """Flags steps slower than ``factor`` × running median."""

    def __init__(self, factor: float = 3.0, window: int = 50):
        self.factor = factor
        self.times: list[float] = []
        self.window = window
        self.events: list[dict] = []

    def observe(self, step: int, dt: float) -> bool:
        self.times.append(dt)
        self.times = self.times[-self.window:]
        med = float(np.median(self.times))
        if len(self.times) >= 5 and dt > self.factor * med:
            self.events.append({"step": step, "dt": dt, "median": med})
            return True
        return False


def run(run_cfg: RunConfig, *, steps: int, train_step: Callable,
        params, opt_state, shardings=None, batch_sharding=None,
        dataset: Optional[SyntheticDataset] = None,
        inject_failure: Optional[Callable[[int], None]] = None,
        log: Callable[[str], None] = print):
    """Run ``steps`` optimizer steps with checkpoint/restart semantics.

    Returns (params, opt_state, history).  Restores from the newest committed
    checkpoint in ``run_cfg.checkpoint_dir`` if one exists (restart path).
    ``batch_sharding`` places each batch (sharded over the data axes under
    a mesh); unset, batches go to the default device.
    """
    cfg = run_cfg.model
    ckpt = CheckpointManager(run_cfg.checkpoint_dir,
                             keep=run_cfg.keep_checkpoints)
    dataset = dataset or SyntheticDataset(SyntheticConfig(
        vocab_size=cfg.real_vocab_size or cfg.vocab_size,
        seq_len=128, global_batch=8, seed=run_cfg.seed))
    watchdog = StragglerWatchdog()
    hb_path = os.path.join(run_cfg.checkpoint_dir, "heartbeat")

    start = 0
    latest = ckpt.latest_step()
    if latest is not None:
        log(f"[restore] resuming from committed step {latest}")
        state = ckpt.restore(latest, {"params": params, "opt": opt_state},
                             shardings=shardings)
        params, opt_state = state["params"], state["opt"]
        start = latest

    history = []
    step = start
    while step < steps:
        batch = dataset.batch(step)
        batch = compat.tree_map(
            lambda x: jax.device_put(x, batch_sharding), batch)
        if inject_failure is not None:
            inject_failure(step)          # may raise — simulated node death
        t0 = obs_clock.monotonic()
        params, opt_state, metrics = train_step(params, opt_state, batch)
        metrics = {k: float(v) for k, v in metrics.items()
                   if np.ndim(v) == 0}
        dt = obs_clock.monotonic() - t0
        if watchdog.observe(step, dt):
            log(f"[straggler] step {step} took {dt:.3f}s "
                f"(median {np.median(watchdog.times):.3f}s)")
        with open(hb_path, "w") as f:
            json.dump({"step": step, "t": obs_clock.wall_time()}, f)
        history.append({"step": step, "dt": dt, **metrics})
        if step % run_cfg.log_every == 0:
            log(f"[step {step}] loss={metrics.get('loss', float('nan')):.4f} "
                f"dt={dt * 1e3:.1f}ms")
        step += 1
        if step % run_cfg.checkpoint_every == 0 or step == steps:
            ckpt.save(step, {"params": params, "opt": opt_state})
    ckpt.wait()
    return params, opt_state, history
