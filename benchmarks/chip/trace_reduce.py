"""Reduce a JAX profiler trace of one window to device busy time, device
time per op and per jitted program, and the idle gaps labelled by what the
host was doing.

What a TPU v5e trace holds (read by hand from one): the plane
``/device:TPU:<n>`` has the lines ``XLA Modules`` (one event per run of a
jitted program, named ``jit_<function>(<hash>)``), ``XLA Ops`` (one event
per HLO op, named ``%<op>.<n> = <shape> <opcode>(...)``; a ``while`` op
spans the ops of its body) and ``Async XLA Ops`` (copy-start/done pairs,
not counted as busy).  A Pallas kernel is an op named after the function
that calls ``pallas_call`` (``flash_decode_paged_pallas``,
``flash_attention_paged_pallas``, ``softmax_topk_pallas``).  Host threads
sit on ``/host:CPU``, where the benchmark's ``TraceAnnotation`` spans are
named ``bench.*``.  Device and host events share one nanosecond clock.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from typing import Optional

CONTROL_OPS = ("while", "conditional", "call")   # ops that contain others


def op_base(name: str) -> str:
    """``%flash_decode_paged_pallas.9 = bf16[...] ...`` → the op's name
    without its ``%`` and numeric suffix."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def module_base(name: str) -> str:
    """``jit_decode(1270...)`` → ``jit_decode``."""
    return name.split("(", 1)[0]


def union_length(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list:
    """Uncovered stretches of ``[lo, hi]`` as (start, end)."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


class Trace:
    """One device's events over the traced window, in nanoseconds."""

    def __init__(self, ops: list, modules: list, host: list):
        self.ops = ops            # (base name, start, end)
        self.modules = modules    # (base name, start, end)
        self.host = host          # (annotation name, start, end)
        spans = [(s, e) for _, s, e in ops] + [(s, e) for _, s, e in host]
        self.lo = min((s for s, _ in spans), default=0.0)
        self.hi = max((e for _, e in spans), default=0.0)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which some op ran on the device (nested ops once)."""
        return union_length([(s, e) for _, s, e in self.ops]) * 1e-9

    def op_seconds(self, base: str) -> tuple:
        """(summed device seconds, event count) of ops named ``base``."""
        d = [e - s for b, s, e in self.ops if b == base]
        return sum(d) * 1e-9, len(d)

    def module_seconds(self, base: str) -> list:
        """Device seconds of each run of the jitted program ``base``."""
        return [(e - s) * 1e-9 for b, s, e in self.modules if b == base]

    def idle_gaps(self) -> list:
        """(label, start, end) of each stretch with no device op, labelled
        by the host annotation covering most of it."""
        busy = [(s, e) for _, s, e in self.ops]
        out = []
        for s, e in gaps(busy, self.lo, self.hi):
            best, cover = "outside the benchmark's spans", 0.0
            for name, hs, he in self.host:
                c = min(e, he) - max(s, hs)
                if c > cover:
                    best, cover = name, c
            out.append((best, s, e))
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (control-flow ops, which
        contain others, left out) and the longest idle gaps."""
        per: dict = {}
        for b, s, e in self.ops:
            if b not in CONTROL_OPS:
                per[b] = per.get(b, 0.0) + (e - s) * 1e-9
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_gaps(), key=lambda g: g[1] - g[2])[:top]
        return {"device_ops": [[n, v] for n, v in ops],
                "idle_gaps": [[n, (e - s) * 1e-9] for n, s, e in idle]}


def from_space(space, device_id: int = 0) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    ops, modules, host = [], [], []
    want = f"/device:TPU:{device_id}"
    for plane in space.planes:
        if plane.name == want:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(op_base(ev.name), ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for ev in line.events]
                elif line.name == "XLA Modules":
                    modules += [(module_base(ev.name), ev.start_ns,
                                 ev.start_ns + ev.duration_ns)
                                for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events
                         if ev.name.startswith("bench.")]
    return Trace(ops, modules, host)


def read_file(path: str, device_id: int = 0) -> Trace:
    """An ``.xplane.pb`` file, gzip-compressed or not."""
    import jax
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = f.read()
    return from_space(jax.profiler.ProfileData.from_serialized_xspace(data),
                      device_id)


def load(trace_dir: str, devices) -> Optional[Trace]:
    """The trace ``jax.profiler`` wrote under ``trace_dir``, reduced for
    the first of ``devices``."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return read_file(files[-1], devices[0].id)
