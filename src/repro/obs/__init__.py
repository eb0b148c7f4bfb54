"""Zero-dependency observability layer for the serving stack.

Four seams, all stdlib-only at import time:

- :mod:`repro.obs.clock` — injectable wall-clock (``monotonic`` /
  ``perf_counter`` / ``wall_time``).  Everything in ``src/`` that needs a
  timestamp goes through here (grep-enforced by ``tests/test_compat.py``),
  so tests can swap in a :class:`~repro.obs.clock.VirtualClock` and assert
  latencies deterministically.
- :mod:`repro.obs.trace` — a :class:`~repro.obs.trace.Tracer` emitting
  Chrome ``trace_event`` spans (request lifecycle + per-tick scheduler
  work) to a file that both Perfetto and ``repro.obs.report`` can read;
  its nested spans are also ``sched.*`` annotations on the JAX profiler's
  clock (``jax`` is imported on the first span, not at import).
- :mod:`repro.obs.metrics` — process-wide registry of counters / gauges /
  histograms.  Off by default; every instrument method is a guarded no-op
  when the registry is disabled.
- :mod:`repro.obs.kernels` — records which dispatch path each op resolved
  to and the autotune decisions used.
- :mod:`repro.obs.history` / :mod:`repro.obs.regress` — the performance
  regression sentry: an append-only JSONL store of ``benchmarks/run.py
  --json`` records keyed by env fingerprint, and the noise-aware detector
  ``run.py check`` gates CI on (verdicts ``ok`` / ``regressed`` /
  ``improved`` / ``no-baseline``).

``python -m repro.obs.report trace.json`` renders a tick timeline,
per-request waterfall, and preemption-cause table from a trace file;
``--diff A.json B.json`` compares two traces, and ``python -m
repro.obs.merge`` aligns per-replica traces into one Perfetto view.
"""
from repro.obs import clock, history, kernels, metrics, regress, trace

__all__ = ["clock", "history", "kernels", "metrics", "regress", "trace"]
