"""Observability layer (ISSUE 7): injectable clock, request-lifecycle
tracing, metrics registry, kernel path/autotune hooks, and the trace report CLI.

The load-bearing claims:

* **The clock is a seam**: a ``VirtualClock`` injected into the scheduler
  makes every latency and phase duration an exact multiple of the advance
  step — no wall-clock noise in assertions, and the phase split
  (``queued_ms`` / ``prefill_ms`` / ``decode_ms``) tiles the request's
  lifetime exactly.
* **Traces are structurally sound**: every opened span is closed, spans on
  each track nest, and a preempted-then-resumed request's track reconstructs
  its exact token timeline (the ``token`` instants ARE the result stream).
* **Observability is free when off**: serving without a tracer produces
  bit-identical token streams to serving with one, and a disabled metrics
  registry records nothing.
* **Latency recording is bounded**: ``RequestResult`` keeps at most
  ``MAX_RECORDED_LATENCIES`` samples and counts the overflow instead of
  growing without bound.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import repro.configs as configs
from repro.models import layers as L, transformer
from repro.obs import clock as obs_clock
from repro.obs import kernels as obs_kernels
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace
from repro.serving import scheduler
from repro.serving.engine_api import Engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOT_LEN = 48
BLOCK = 8
CHUNK = 8
TOP_K = 5
BASE_RNG = jax.random.PRNGKey(7)


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_smoke("smollm_360m")
    params, _ = L.split_params(transformer.init(jax.random.PRNGKey(0), cfg))
    return params, cfg


def _workload(n=3, seed=2, max_new=4):
    rng = np.random.default_rng(seed)
    return [scheduler.Request(rid=i, prompt=rng.integers(0, 512, 5 + 2 * i),
                              max_new_tokens=max_new, arrival_tick=i)
            for i in range(n)]


def _priority_workload():
    """Low-priority long decodes + an urgent mid-flight arrival over an
    undersized pool — the known-preempting recipe from test_serving_slo."""
    rng = np.random.default_rng(11)
    lo = [scheduler.Request(rid=i, prompt=rng.integers(0, 512, 9 + 2 * i),
                            max_new_tokens=12, arrival_tick=0, priority=1)
          for i in range(2)]
    hi = [scheduler.Request(rid=2, prompt=rng.integers(0, 512, 8),
                            max_new_tokens=4, arrival_tick=5, priority=0)]
    return lo + hi


def _engine(params, cfg, **kw):
    base = dict(num_slots=2, slot_len=SLOT_LEN, prefill_chunk=CHUNK,
                top_k=TOP_K, base_rng=BASE_RNG)
    base.update(kw)
    return Engine(params, cfg, **base)


def _serve_stepwise(eng, requests, clock, dt=0.010):
    """Drive the engine one tick per ``advance``: tick k runs at k*dt."""
    eng.begin()
    for r in requests:
        eng.submit(r)
    while eng.step():
        clock.advance(dt)
    return eng.report()


# ---------------------------------------------------------------------------
# Clock seam.
# ---------------------------------------------------------------------------
def test_virtual_clock_semantics():
    vc = obs_clock.VirtualClock(start=5.0)
    assert vc.monotonic() == 5.0
    assert vc.perf_counter() == 5.0 and vc.wall_time() == 5.0
    vc.advance(1.5)
    assert vc.monotonic() == 6.5
    with pytest.raises(ValueError):
        vc.advance(-0.1)


def test_set_clock_swaps_module_default():
    vc = obs_clock.VirtualClock()
    prev = obs_clock.set_clock(vc)
    try:
        assert obs_clock.get() is vc
        vc.advance(2.0)
        assert obs_clock.monotonic() == 2.0
    finally:
        obs_clock.set_clock(prev)
    assert obs_clock.get() is prev


def test_virtual_clock_latencies_exact_and_phases_tile(model):
    """Every recorded latency is an exact multiple of the tick advance, the
    phase split tiles arrival→finish exactly, and a re-run under the same
    virtual schedule reproduces the latencies bit-for-bit."""
    params, cfg = model
    dt = 0.010

    def once():
        vc = obs_clock.VirtualClock()
        rep = _serve_stepwise(_engine(params, cfg, clock=vc),
                              _workload(), vc, dt)
        return rep

    report = once()
    assert len(report.results) == 3
    for r in report.results:
        assert r.latencies, f"rid {r.rid}: no latencies recorded"
        for lat in r.latencies:
            ticks = lat / dt
            assert ticks == pytest.approx(round(ticks), abs=1e-9), (
                f"rid {r.rid}: latency {lat} is not a whole tick")
        assert r.queued_ms is not None and r.queued_ms >= 0.0
        # single-chunk prompts prefill inside one tick: exactly 0.0 virtual ms
        assert r.prefill_ms is not None and r.prefill_ms >= 0.0
        assert r.decode_ms is not None and r.decode_ms >= 0.0
        total = (r.finish_time - r.arrival_time) * 1e3
        assert r.queued_ms + r.prefill_ms + r.decode_ms == pytest.approx(
            total, abs=1e-6)
    again = once()
    assert ([r.latencies for r in report.results]
            == [r.latencies for r in again.results])
    assert report.wall_time == pytest.approx(again.wall_time)


def test_latency_recording_bounded(monkeypatch):
    monkeypatch.setattr(scheduler.RequestResult, "MAX_RECORDED_LATENCIES", 10)
    r = scheduler.RequestResult(rid=0, prompt_len=1)
    for i in range(100):
        r.record_latency(0.001)
    assert len(r.latencies) == 10
    assert r.dropped_latencies == 90
    assert r.dropped_sum == pytest.approx(0.090)


# ---------------------------------------------------------------------------
# Trace integrity.
# ---------------------------------------------------------------------------
def test_trace_closed_nested_and_perfetto_loadable(model, tmp_path):
    params, cfg = model
    path = tmp_path / "trace.json"
    vc = obs_clock.VirtualClock()
    tracer = obs_trace.Tracer(str(path), clock=vc)
    rep = _serve_stepwise(_engine(params, cfg, clock=vc, tracer=tracer),
                          _workload(), vc)
    events = tracer.close()

    with open(path) as f:
        loaded = json.load(f)          # a real JSON array: Perfetto-ready
    assert isinstance(loaded, list) and len(loaded) == len(events)
    assert obs_report.validate(loaded) == []
    phases = {e["ph"] for e in loaded}
    assert {"X", "i", "C", "M"} <= phases
    names = {e["name"] for e in loaded}
    assert {"tick", "admit", "prefill", "decode", "queued",
            "token", "retire", "sched", "thread_name"} <= names
    # one token instant per generated token, one retire per request
    tokens = [e for e in loaded if e["ph"] == "i" and e["name"] == "token"]
    assert len(tokens) == sum(len(r.tokens) for r in rep.results)
    retires = [e for e in loaded if e["ph"] == "i" and e["name"] == "retire"]
    assert len(retires) == len(rep.results)


def test_preempted_request_trace_reconstructs_token_timeline(model, tmp_path):
    """The acceptance pin: a preempted-then-resumed request's track replays
    its exact token stream, shows the suspension, and stays structurally
    sound."""
    params, cfg = model
    path = tmp_path / "preempt_trace.json"
    tracer = obs_trace.Tracer(str(path))
    eng = _engine(params, cfg, paged=True, block_size=BLOCK, num_blocks=8,
                  tracer=tracer)
    rep = eng.serve(_priority_workload())
    events = tracer.close()
    assert rep.preemptions >= 1, "workload must actually preempt"
    assert obs_report.validate(events) == []

    by_rid = {r.rid: r for r in rep.results}
    preempted = [r.rid for r in rep.results if r.preempted]
    assert preempted
    for rid, res in by_rid.items():
        tid = rid + 1
        track = [e for e in events if e.get("tid") == tid]
        toks = [e["args"]["token"] for e in track
                if e["ph"] == "i" and e["name"] == "token"]
        assert toks == res.tokens, f"rid {rid}: trace/result stream mismatch"
        # token instants are time-ordered: the timeline is reconstructible
        ts = [e["ts"] for e in track
              if e["ph"] == "i" and e["name"] == "token"]
        assert ts == sorted(ts)
    for rid in preempted:
        track = [e for e in events if e.get("tid") == rid + 1]
        assert any(e["ph"] == "i" and e["name"] == "preempt" for e in track)
        assert any(e["ph"] == "X" and e["name"] == "suspended"
                   for e in track), "swap-out must appear as a suspended span"
    total_preempts = sum(1 for e in events
                         if e["ph"] == "i" and e["name"] == "preempt")
    assert total_preempts == rep.preemptions


def test_tracing_off_streams_bit_identical(model):
    params, cfg = model
    rep_off = _engine(params, cfg).serve(_workload())
    tracer = obs_trace.Tracer(None)            # buffer-only, no file
    rep_on = _engine(params, cfg, tracer=tracer).serve(_workload())
    events = tracer.close()
    assert events, "traced run must have produced events"
    assert ({r.rid: r.tokens for r in rep_off.results}
            == {r.rid: r.tokens for r in rep_on.results})
    assert rep_off.decode_steps == rep_on.decode_steps
    assert rep_off.prefill_chunks == rep_on.prefill_chunks


def test_tracer_incremental_flush_bounds_buffer(model, tmp_path):
    """``flush_every=N`` keeps at most N events in memory over a real serve
    while the file stays one valid, complete JSON array."""
    params, cfg = model
    path = tmp_path / "flushed_trace.json"
    N = 16
    tracer = obs_trace.Tracer(str(path), flush_every=N)
    peak = 0
    emit = tracer._emit

    def spying_emit(entry):
        nonlocal peak
        emit(entry)
        peak = max(peak, len(tracer._buf))

    tracer._emit = spying_emit
    rep = _engine(params, cfg, tracer=tracer).serve(_workload())
    tail = tracer.close()
    assert peak <= N, f"buffer peaked at {peak} events (bound {N})"
    assert tracer.total_events > N, "workload too small to force a flush"
    assert len(tail) < N                   # close returns only the remainder
    with open(path) as f:
        loaded = json.load(f)
    assert len(loaded) == tracer.total_events
    assert obs_report.validate(loaded) == []
    tokens = [e for e in loaded if e["ph"] == "i" and e["name"] == "token"]
    assert len(tokens) == sum(len(r.tokens) for r in rep.results)


def test_tracer_flush_every_needs_path():
    with pytest.raises(ValueError, match="path"):
        obs_trace.Tracer(None, flush_every=4)
    with pytest.raises(ValueError, match="flush_every"):
        obs_trace.Tracer("/tmp/x.json", flush_every=0)


def test_tracer_streaming_survives_preempt_and_swap(model, tmp_path):
    """A preempt-and-swap lifecycle traced through ``flush_every=N``:
    suspend/resume spans and preempt instants land intact even though most
    of the trace left the buffer mid-run, and ``close()`` is idempotent."""
    params, cfg = model
    path = tmp_path / "stream_preempt.json"
    tracer = obs_trace.Tracer(str(path), flush_every=8)
    eng = _engine(params, cfg, paged=True, block_size=BLOCK, num_blocks=8,
                  tracer=tracer)
    rep = eng.serve(_priority_workload())
    tracer.close()
    assert rep.preemptions >= 1, "workload must actually preempt"
    assert tracer.total_events > 8, "workload too small to force a flush"

    with open(path) as f:
        loaded = json.load(f)
    assert len(loaded) == tracer.total_events
    assert obs_report.validate(loaded) == []
    preempts = [e for e in loaded
                if e["ph"] == "i" and e["name"] == "preempt"]
    assert len(preempts) == rep.preemptions
    assert any(e["ph"] == "X" and e["name"] == "suspended" for e in loaded)
    for res in rep.results:
        toks = [e["args"]["token"] for e in loaded
                if e.get("tid") == res.rid + 1
                and e["ph"] == "i" and e["name"] == "token"]
        assert toks == res.tokens, f"rid {res.rid}: stream mismatch"
    # second close: no new events, file bytes untouched
    before = path.read_bytes()
    assert tracer.close() == []
    assert path.read_bytes() == before


# ---------------------------------------------------------------------------
# Metrics registry.
# ---------------------------------------------------------------------------
@pytest.fixture
def registry():
    """Clean registry, restored (disabled + cleared) afterwards."""
    was = obs_metrics.enabled()
    obs_metrics.reset()
    yield obs_metrics
    obs_metrics.reset()
    (obs_metrics.enable if was else obs_metrics.disable)()


def test_metrics_disabled_records_nothing(registry):
    registry.disable()
    registry.counter("c").inc()
    registry.gauge("g").set(3.0)
    registry.histogram("h").observe(1.0)
    assert registry.snapshot() == {}


def test_metrics_enabled_counts_and_snapshots(registry):
    registry.enable()
    registry.counter("c").inc()
    registry.counter("c").inc(4)
    registry.gauge("g").set(3.0)
    registry.gauge("g").set(1.0)
    for v in (0.001, 0.002, 0.004):
        registry.histogram("h").observe(v)
    snap = registry.snapshot()
    assert snap["c"]["value"] == 5 and snap["c"]["type"] == "counter"
    assert snap["g"]["value"] == 1.0
    assert snap["g"]["min"] == 1.0 and snap["g"]["max"] == 3.0
    assert snap["h"]["count"] == 3
    assert snap["h"]["mean"] == pytest.approx(7.0 / 3000.0)
    with pytest.raises(TypeError):
        registry.gauge("c")                   # name already a counter


def test_histogram_percentiles_estimated_within_bounds(registry):
    registry.enable()
    h = registry.histogram("h")
    assert h.percentile(50) is None              # empty: no estimate
    h.observe(0.25)
    assert h.percentile(50) == pytest.approx(0.25)   # single value: exact
    assert h.percentile(95) == pytest.approx(0.25)
    for v in (0.001, 0.002, 0.004, 0.008, 0.016, 0.512):
        registry.histogram("spread").observe(v)
    s = registry.histogram("spread")
    p50, p95 = s.percentile(50), s.percentile(95)
    assert s.min <= p50 <= p95 <= s.max          # clamped, monotone in q
    assert p50 < s.mean < p95                    # the outlier skews the mean
    snap = registry.snapshot()
    assert snap["spread"]["p50"] == pytest.approx(p50)
    assert snap["spread"]["p95"] == pytest.approx(p95)
    assert "p50" not in snap.get("h_missing", {})


def test_engine_stats_metrics_include_percentiles(model, registry):
    params, cfg = model
    registry.enable()
    eng = _engine(params, cfg)
    eng.serve(_workload(n=2))
    m = eng.stats()["metrics"]
    occ = m["serving.occupancy"]
    assert occ["type"] == "histogram"
    assert occ["min"] <= occ["p50"] <= occ["p95"] <= occ["max"]


def test_engine_stats_attach_metrics_snapshot(model, registry):
    params, cfg = model
    registry.enable()
    eng = _engine(params, cfg, paged=True, block_size=BLOCK)
    rep = eng.serve(_workload(n=2))
    st = eng.stats()
    assert len(rep.results) == 2
    m = st["metrics"]
    assert m["serving.tokens"]["value"] == rep.total_tokens
    assert m["serving.occupancy"]["count"] == rep.decode_steps
    assert "serving.free_blocks" in m         # low-water via gauge min
    registry.disable()
    assert "metrics" not in eng.stats()


# ---------------------------------------------------------------------------
# Kernel profiling hooks.
# ---------------------------------------------------------------------------
def test_kernel_profile_paths_and_costs(model):
    """The kernels layer records the path each op resolved to and every
    autotune decision used; the profile holds nothing else, and ``reset``
    empties it."""
    from repro.kernels import dispatch
    params, cfg = model
    obs_kernels.reset()
    # record_path fires at jit-trace time; the shared decode steps were
    # compiled by earlier tests, so resolve an op explicitly too
    path, _ = dispatch.lookup("softmax_topk")
    decision = dispatch.block_decision(cfg.vocab_size)
    eng = _engine(params, cfg)
    eng.serve(_workload(n=2))
    prof = eng.kernel_profile()
    assert set(prof) == {"paths", "autotune"}
    assert prof["paths"], "dispatch must have recorded resolved paths"
    assert prof["paths"]["softmax_topk"]["path"] == path
    for entry in prof["paths"].values():
        assert entry["path"] in ("pallas", "interpret", "xla")
        assert entry["count"] >= 1
    blocks = {k: v for k, v in prof["autotune"].items()
              if k.startswith("block/")}
    assert decision.to_dict() in blocks.values(), prof["autotune"]
    assert decision.block in {min(b, cfg.vocab_size)
                              for b in dispatch.BLOCK_CANDIDATES}
    obs_kernels.reset()
    assert eng.kernel_profile() == {"paths": {}, "autotune": {}}


# ---------------------------------------------------------------------------
# Report CLI (tier-1 smoke): a generated trace summarizes cleanly.
# ---------------------------------------------------------------------------
def test_report_cli_runs_on_generated_trace(model, tmp_path):
    params, cfg = model
    path = tmp_path / "trace.json"
    tracer = obs_trace.Tracer(str(path))
    _engine(params, cfg, tracer=tracer).serve(_workload(n=2))
    tracer.close()

    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(REPO, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-m", "repro.obs.report", str(path)],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "tick timeline" in out.stdout
    assert "request waterfall" in out.stdout
    assert "retire causes:" in out.stdout
    assert "trace OK: all spans closed and nested" in out.stdout

    out2 = subprocess.run([sys.executable, "-m", "repro.obs.report"],
                          capture_output=True, text=True, timeout=60, env=env)
    assert out2.returncode == 2                # usage error


def test_report_cli_nonzero_on_broken_trace(tmp_path):
    """Satellite pin: the trace smoke can gate CI because a structurally
    broken trace exits 1 and names the problem."""
    path = tmp_path / "broken.json"
    path.write_text(json.dumps([
        {"name": "decode", "ph": "X", "ts": 0.0, "dur": 5.0,
         "pid": 0, "tid": 1, "args": {"unclosed": True}},
    ]))
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(REPO, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-m", "repro.obs.report", str(path)],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 1, out.stdout
    assert "TRACE PROBLEM" in out.stderr
    assert "unclosed" in out.stderr


# ---------------------------------------------------------------------------
# Trace diff + multi-replica merge.
# ---------------------------------------------------------------------------
def _tick(pid, ts, dur, tick, **extra):
    args = {"tick": tick, "active": 0, "queue": 0, "free_slots": 2}
    args.update(extra)
    return {"name": "tick", "ph": "X", "ts": ts, "dur": dur,
            "pid": pid, "tid": 0, "args": args}


def test_merge_aligns_first_ticks_and_renumbers_colliding_pids():
    from repro.obs import merge as obs_merge
    # both files claim pid 0 and start their clocks at different epochs
    a = [_tick(0, 1000.0, 5.0, 1), _tick(0, 1100.0, 5.0, 2)]
    b = [_tick(0, 9000.0, 7.0, 1), _tick(0, 9100.0, 7.0, 2)]
    merged = obs_merge.merge_events([a, b], labels=["a.json", "b.json"])
    ticks = [e for e in merged if e["ph"] == "X"]
    # first tick of each file lands at t=0: the common fiducial
    assert sorted(e["ts"] for e in ticks) == [0.0, 0.0, 100.0, 100.0]
    assert {e["pid"] for e in ticks} == {0, 1}   # collision → renumbered
    names = [e for e in merged if e["ph"] == "M"]
    assert {n["args"]["name"] for n in names} == {
        "replica 0 (a.json)", "replica 1 (b.json)"}
    assert obs_report.validate(merged) == []
    # distinct pids merge untouched — no renumbering, no name metadata
    c = [_tick(1, 500.0, 5.0, 1)]
    merged2 = obs_merge.merge_events([a, c])
    assert {e.get("pid") for e in merged2} == {0, 1}
    assert not any(e["ph"] == "M" for e in merged2)


def test_diff_reports_aligned_ticks_and_class_latency():
    a = [_tick(0, 0.0, 1000.0, 1), _tick(0, 2000.0, 1000.0, 2)]
    b = [_tick(0, 0.0, 2000.0, 1), _tick(0, 3000.0, 2000.0, 2)]
    text = obs_report.diff(a, b, label_a="a.json", label_b="b.json")
    assert "## Trace diff — a.json → b.json" in text
    assert "| ticks | 2 | 2 | +0.0% |" in text
    assert "| 0 | 1.000 | 2.000 | +100.0% |" in text   # aligned by index
    assert "Aligned tick timeline" in text


def test_serve_trace_dir_writes_per_replica_and_merged(model, tmp_path):
    """Acceptance pin: ``--replicas 2 --trace dir/`` produces per-replica
    traces plus a merged view that load-validates, and ``report --diff``
    runs clean on the pair."""
    tdir = tmp_path / "traces"
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(REPO, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--smoke",
         "--continuous", "--paged", "--replicas", "2", "--requests", "6",
         "--tokens", "6", "--no-affinity", "--trace", str(tdir) + os.sep],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode in (0, 1), out.stderr[-2000:]  # 1 = occupancy warn
    paths = [tdir / f"replica{i}.json" for i in range(2)]
    merged_path = tdir / "merged.json"
    assert all(p.exists() for p in paths) and merged_path.exists()
    assert "merged view" in out.stdout

    merged = obs_report.load_trace(str(merged_path))
    assert obs_report.validate(merged) == []
    assert {e.get("pid") for e in merged} == {0, 1}
    per_replica = [obs_report.load_trace(str(p)) for p in paths]
    # the merged stream is exactly the per-replica events, clock-aligned
    assert len(merged) == sum(len(t) for t in per_replica)
    for t in per_replica:
        assert obs_report.validate(t) == []
        assert any(e["ph"] == "X" and e["name"] == "tick" for e in t)

    rep = subprocess.run(
        [sys.executable, "-m", "repro.obs.report", str(merged_path)],
        capture_output=True, text=True, timeout=120, env=env)
    assert rep.returncode == 0, rep.stderr[-2000:]
    dif = subprocess.run(
        [sys.executable, "-m", "repro.obs.report", "--diff",
         str(paths[0]), str(paths[1])],
        capture_output=True, text=True, timeout=120, env=env)
    assert dif.returncode == 0, dif.stderr[-2000:]
    assert "## Trace diff" in dif.stdout
    assert "Aligned tick timeline" in dif.stdout
    mrg = subprocess.run(
        [sys.executable, "-m", "repro.obs.merge", str(paths[0]),
         str(paths[1]), "--out", str(tmp_path / "re_merged.json")],
        capture_output=True, text=True, timeout=120, env=env)
    assert mrg.returncode == 0, mrg.stderr[-2000:]
    assert "merged 2 traces" in mrg.stdout
