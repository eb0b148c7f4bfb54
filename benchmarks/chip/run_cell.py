"""Run one benchmark cell once, on the TPU this process finds.

    python benchmarks/chip/run_cell.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Steps, in order: find the chips (none: exit 1, no result); draw the weights
on the device from the seed; build one paged ``Engine`` with the cell's
settings; compile every shape the window runs; offer the cell's traffic for
its pre-window, then measure for ``--seconds``; check what the window served
against the float32 reference.  ``--trace 1`` records the window with the
JAX profiler and reports the cell's per-layer metrics instead of its
end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``: each number compared, with its limit.  The same
numbers close standard error.  ``setup_s`` runs from process start to the
window's start, pre-window included.
"""
import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import numpy as np  # noqa: E402

import flops  # noqa: E402
import harness  # noqa: E402

# the comparison covers at least this many served tokens, from up to this
# many finished requests (the longest among them)
WANT_TOKENS = 320
MAX_REQUESTS = 8
MIN_TOKENS = 64


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, flush=True)


def setup(cell, seed: int, annotate: bool = False):
    """Weights, engine and warm-up: the cell's ``harness.Driver``."""
    import jax
    cfg = harness.program_config(cell)
    t = time.monotonic()
    params = harness.make_params(cell, cfg, seed)
    jax.block_until_ready(params)
    log(f"weights: {time.monotonic() - t:.3f} s (on the device, one program)")
    driver = harness.Driver(cell, params, cfg, seed, annotate=annotate)
    t = time.monotonic()
    driver.warm()
    log(f"warm-up: {time.monotonic() - t:.3f} s")
    return driver


def kernel_lines(n_sweeps: int) -> list:
    """Which path each op took and each autotune decision, with whether it
    was swept in this process or read from the checkout's cache."""
    from repro.obs import kernels as obs_kernels
    snap = obs_kernels.snapshot()
    out = [f"kernel path: {op} -> {rec['path']}"
           for op, rec in snap["paths"].items()]
    how = "swept here" if n_sweeps else "read from .cache/autotune.json"
    for key, dec in snap["autotune"].items():
        choice = dec.get("tiles", dec.get("block"))
        out.append(f"autotune {key}: {choice} ({how}; timings_us "
                   f"{dec.get('timings_us')})")
    return out


def window_stats(win, setup_s: float) -> dict:
    """Every end-to-end statistic of the window, whichever the cell
    reports."""
    vals = {"setup_s": setup_s,
            "output_tok_s": harness.output_tokens(win) / win.seconds}
    itl = harness.itl_samples(win)
    ttft = harness.ttft_samples(win)
    for name, x, stat in (("itl_p50_ms", itl, harness.p50),
                          ("itl_p95_ms", itl, harness.p95),
                          ("ttft_p95_ms", ttft, harness.p95)):
        v = stat(x)
        if v is not None:
            vals[name] = v * 1e3
    return vals


def end_to_end(cell, vals: dict) -> dict:
    names = {m["name"]: m["unit"] for m in cell.end_to_end}
    return {k: {"value": vals[k], "unit": names[k]} for k in names
            if k in vals}


def run(cell, seed: int, seconds: float, trace: bool, devices) -> dict:
    """One run of ``cell`` on ``devices``; returns the result line."""
    import jax
    cache_dir = harness.enable_caches()
    clock = harness.CompileClock()
    from repro.kernels import dispatch
    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{cache_dir}")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    driver = setup(cell, seed, annotate=trace)
    state = {}

    def window_start():
        state["compiles"] = clock.count
        if trace_dir:
            # host spans from the benchmark's TraceAnnotations only: the
            # Python function tracer would slow the host loop it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)

    win = driver.run(float(cell.traffic["pre_window_s"]), seconds,
                     on_window_start=window_start)
    if trace_dir:
        jax.profiler.stop_trace()
    setup_s = win.start - PROCESS_START
    mem = devices[0].memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    log(f"compiling: {clock.seconds:.3f} s in {clock.count} compiles; "
        f"{clock.count - state['compiles']} compiles inside the window")
    for line in kernel_lines(dispatch._SWEEPS):
        log(line)
    if win.lateness:
        late = np.asarray(win.lateness) * 1e3
        log(f"generator lateness ms: median {np.median(late):.3f} p95 "
            f"{np.percentile(late, 95):.3f} max {late.max():.3f}")
    log(f"peak_bytes_in_use: {peak_bytes}")
    attempted = sum(1 for d in win.due.values()
                    if win.start <= d < win.end)
    failed = sum(1 for r in win.results if r.evicted
                 and win.start <= win.due.get(r.rid, -1.0) < win.end)
    log(f"window: {win.seconds:.3f} s, {len(win.ticks)} ticks, "
        f"{attempted} requests due, {failed} evicted, "
        f"{harness.output_tokens(win)} output tokens, "
        f"{len(harness.itl_samples(win))} gaps")
    stats = window_stats(win, setup_s)
    log("window stats: " + json.dumps(stats))

    out = {"correct": False, "attempted": attempted, "failed": failed}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": jax.device_count(),
              "memory_peak_bytes": peak_bytes}
    if trace:
        import trace_reduce
        tr = trace_reduce.load(trace_dir, devices)
        shutil.rmtree(trace_dir, ignore_errors=True)
        record = harness.RunRecord(
            cell=cell, window=win, trace=tr,
            peak=flops.peaks(devices[0].device_kind))
        metrics = {}
        for m in cell.per_layer:
            v = harness.layer_reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        out["breakdown"] = tr.breakdown()
        log(f"trace: busy {tr.busy_s:.6f} s of {tr.window_s:.6f} s")
    else:
        metrics = end_to_end(cell, stats)
    out["metrics"] = metrics
    out["device"] = device

    prompts = driver.prompts
    finished, streaming = driver.finished(), driver.streaming()
    driver.close()
    del driver
    sample = harness.pick_sample(finished, seed, WANT_TOKENS, MAX_REQUESTS,
                                 streaming)
    t = time.monotonic()
    got = harness.compare(cell, seed, sample, prompts)
    log(f"reference: {got['requests_compared']} requests, "
        f"{got['served_tokens_compared']} served tokens, "
        f"{time.monotonic() - t:.3f} s")
    out["correct"], out["checks"] = harness.verdict(
        got["max_logit_gap"], got["served_tokens_compared"],
        cell.config["limits"]["max_logit_gap"], MIN_TOKENS)
    return out


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 1
    out = run(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
