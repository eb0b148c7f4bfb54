"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV:
  bench_softmax        → paper Fig. 1 & 2 (naive/safe/online × V × batch)
  bench_softmax_topk   → paper Fig. 3 & 4 (fused vs unfused, K=5)
  bench_topk_sweep     → paper §5.2 (K degradation)
  bench_attention      → beyond-paper (online attention)
  bench_chunked_ce     → beyond-paper (§7 fusion at the LM head)
  bench_serving        → beyond-paper (continuous batching: tok/s, p50/p95
                         per-token latency, occupancy vs drain-and-refill;
                         ``--paged`` serves through the paged KV cache and
                         adds block-sharing accounting; ``--replicas N``
                         routes over N engines with prefix affinity;
                         ``--kv int8`` serves through the quantized cache
                         family and adds a pool-capacity row)

``--smoke`` shrinks every sweep to a seconds-long sanity pass (tiny V/batch,
one case per module) — the tier-1 suite runs it so the harness itself can't
rot between full benchmark runs.  ``--json PATH`` additionally records the
rows plus the probed backend capabilities to a results file.

``report A.json B.json`` diffs two such result files into an
EXPERIMENTS.md-style markdown table (name | baseline | candidate | Δ%),
flagging rows present on only one side and any env mismatch — paste it into
EXPERIMENTS.md as the record of a before/after run.

``--history PATH`` (or ``REPRO_BENCH_HISTORY``) additionally appends every
``--json`` run as one record to the append-only JSONL history store
(``repro.obs.history``), keyed by row name + env fingerprint.

``check`` is the CI regression gate: it takes a candidate run (``--from
results.json``, or runs the named benches itself), compares each row
against the noise-aware baseline built from the last K same-env history
records (``repro.obs.regress``: median + fastest-half mean, per-row
relative thresholds), prints the verdict table, and exits nonzero iff any
row regressed.  ``--update-baseline`` records the candidate into history
(and exits 0) — how a fresh environment seeds its baseline and how an
accepted perf change becomes the new normal.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# runnable as `python benchmarks/run.py` from anywhere: put the repo root
# (for `benchmarks.*`) and src (for `repro.*`) on the path
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_REPO, os.path.join(_REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _load_results(path: str) -> dict:
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict) or "rows" not in data:
        raise SystemExit(f"{path}: not a benchmarks/run.py --json results "
                         "file (no 'rows')")
    return data


def report(baseline_path: str, candidate_path: str, out=None) -> str:
    """Markdown diff of two ``--json`` result files (EXPERIMENTS.md-style)."""
    base = _load_results(baseline_path)
    cand = _load_results(candidate_path)
    b_rows = {r["name"]: r for r in base["rows"]}
    c_rows = {r["name"]: r for r in cand["rows"]}
    lines = [f"## Benchmark diff — {os.path.basename(baseline_path)} → "
             f"{os.path.basename(candidate_path)}", ""]
    env_keys = sorted(set(base.get("env", {})) | set(cand.get("env", {})))
    if env_keys:
        lines += ["| env | baseline | candidate |", "|---|---|---|"]
        for k in env_keys:
            bv = base.get("env", {}).get(k, "—")
            cv = cand.get("env", {}).get(k, "—")
            flag = "" if bv == cv else " ⚠"
            lines.append(f"| {k}{flag} | {bv} | {cv} |")
        lines.append("")
    lines += ["| name | baseline µs | candidate µs | Δ% | derived |",
              "|---|---:|---:|---:|---|"]
    for name in sorted(set(b_rows) & set(c_rows)):
        b, c = b_rows[name], c_rows[name]
        bu, cu = float(b["us_per_call"]), float(c["us_per_call"])
        delta = (cu - bu) / bu * 100.0 if bu else float("inf")
        derived = c.get("derived") or b.get("derived") or ""
        lines.append(f"| {name} | {bu:.2f} | {cu:.2f} | {delta:+.1f}% "
                     f"| {derived} |")
    only_b = sorted(set(b_rows) - set(c_rows))
    only_c = sorted(set(c_rows) - set(b_rows))
    if only_b:
        lines += ["", "Rows only in baseline: " + ", ".join(only_b)]
    if only_c:
        lines += ["", "Rows only in candidate: " + ", ".join(only_c)]
    text = "\n".join(lines) + "\n"
    if out:
        with open(out, "w") as f:
            f.write(text)
    return text


def _report_main(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="run.py report",
        description="Diff two --json result files into a markdown table.")
    ap.add_argument("baseline", help="results JSON of the 'before' run")
    ap.add_argument("candidate", help="results JSON of the 'after' run")
    ap.add_argument("--out", metavar="PATH", default=None,
                    help="also write the markdown to PATH")
    args = ap.parse_args(argv)
    sys.stdout.write(report(args.baseline, args.candidate, out=args.out))
    return 0


def _capability_env() -> dict:
    from repro import compat
    caps = compat.capabilities()
    return {"backend": caps.backend,
            "jax_version": caps.jax_version,
            "device_count": caps.device_count,
            "pallas_native": caps.pallas_native}


def _bench_mods() -> dict:
    from benchmarks import (
        bench_attention,
        bench_chunked_ce,
        bench_serving,
        bench_softmax,
        bench_softmax_topk,
        bench_topk_sweep,
    )
    return {
        "softmax": bench_softmax,
        "softmax_topk": bench_softmax_topk,
        "topk_sweep": bench_topk_sweep,
        "attention": bench_attention,
        "chunked_ce": bench_chunked_ce,
        "serving": bench_serving,
    }


def _collect_rows(benches, *, smoke: bool) -> list:
    """Run the named benches (default kwargs) and return their rows."""
    mods = _bench_mods()
    unknown = [b for b in benches if b not in mods]
    if unknown:
        raise SystemExit(f"unknown bench(es) {unknown}; "
                         f"choose from {list(mods)}")
    rows = []
    for name in benches or list(mods):
        rows.extend(mods[name].run(smoke=smoke))
    return rows


def _check_main(argv) -> int:
    """``run.py check``: gate a candidate run against the history store."""
    from repro.obs import history, regress

    ap = argparse.ArgumentParser(
        prog="run.py check",
        description="Noise-aware regression gate: compare a candidate run "
                    "against the per-row baseline from the last K same-env "
                    "history records; exit 1 iff any row regressed.")
    ap.add_argument("benches", nargs="*", metavar="bench",
                    help="benches to run as the candidate (ignored with "
                         "--from)")
    ap.add_argument("--from", dest="from_json", metavar="RESULTS.json",
                    default=None,
                    help="use a recorded --json results file as the "
                         "candidate instead of running benches")
    ap.add_argument("--smoke", action="store_true",
                    help="run the candidate benches in smoke mode")
    ap.add_argument("--history", metavar="PATH", default=None,
                    help="history store (default: $REPRO_BENCH_HISTORY, "
                         f"then {history.DEFAULT_PATH})")
    ap.add_argument("--k", type=int, default=regress.DEFAULT_K,
                    help="baseline window: last K same-env records "
                         "(default %(default)s)")
    ap.add_argument("--min-records", type=int,
                    default=regress.DEFAULT_MIN_RECORDS,
                    help="records required before a row has a baseline "
                         "(default %(default)s; fewer → no-baseline, "
                         "never a failure)")
    ap.add_argument("--threshold", type=float, default=None, metavar="PCT",
                    help="override the per-row relative thresholds with one "
                         "global band, in percent (e.g. 30)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="append the candidate to the history store and "
                         "exit 0 — seeds a fresh env's baseline / accepts "
                         "a perf change as the new normal")
    args = ap.parse_args(argv)

    if args.from_json:
        data = _load_results(args.from_json)
        rows = data["rows"]
        env, smoke = data.get("env", {}), bool(data.get("smoke"))
        label = os.path.basename(args.from_json)
    else:
        rows = _collect_rows(args.benches, smoke=args.smoke)
        env, smoke = _capability_env(), bool(args.smoke)
        label = "check:" + ",".join(args.benches or ["all"])

    path = history.history_path(args.history, default=history.DEFAULT_PATH)
    store = history.HistoryStore(path)
    fp = history.fingerprint(env, smoke=smoke)
    threshold = args.threshold / 100.0 if args.threshold is not None else None
    verdicts = regress.check_rows(
        rows, store, env, smoke=smoke, k=args.k,
        min_records=args.min_records, threshold=threshold)
    sys.stdout.write(regress.render(verdicts, fp=fp))
    if store.skipped:
        print(f"(history: skipped {store.skipped} unparseable lines in "
              f"{path})", file=sys.stderr)
    if args.update_baseline:
        store.append(env, rows, smoke=smoke, label=label)
        print(f"baseline updated: recorded {len(list(rows))} rows → {path}")
        return 0
    return 1 if regress.regressions(verdicts) else 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "report":
        return _report_main(argv[1:])
    if argv and argv[0] == "check":
        return _check_main(argv[1:])
    from benchmarks.common import emit

    mods = _bench_mods()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("benches", nargs="*", metavar="bench",
                    help=f"subset to run (default: all): {', '.join(mods)}")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, one case per module (CI sanity pass)")
    ap.add_argument("--paged", action="store_true",
                    help="serving bench uses the paged KV cache (block pool "
                         "+ prefix sharing); rows keep the slot-pool names "
                         "so `report` diffs the two modes directly")
    ap.add_argument("--priorities", action="store_true",
                    help="serving bench uses a mixed-priority workload and "
                         "adds SLO-attainment / p95-by-class / preemption "
                         "rows")
    ap.add_argument("--no-preempt", action="store_true",
                    help="serving bench disables preempt-and-swap (the "
                         "baseline `report` diffs a --priorities run "
                         "against)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="serving bench routes a prefix-heavy workload over "
                         "N paged engine replicas (ReplicaRouter) and adds "
                         "tok_s_total / slo_attained_pct / prefix_hit_rate "
                         "/ backpressure_rejects rows; the workload is the "
                         "same for every N so `report` diffs replica counts")
    ap.add_argument("--no-affinity", action="store_true",
                    help="serving bench routes round-robin instead of by "
                         "prefix affinity (the baseline a --replicas run "
                         "diffs against)")
    ap.add_argument("--arch", metavar="NAME", default="smollm_360m",
                    help="serving bench architecture (smoke config name): "
                         "zamba2_1p2b / xlstm_125m page SSM/xLSTM state as "
                         "single fixed-size blocks, whisper_small pages the "
                         "encoder output as shared immutable blocks; "
                         "non-default archs emit serving/{tag}/{arch}/* "
                         "rows so default-row diffs stay comparable")
    ap.add_argument("--kv", metavar="DTYPE", default="",
                    help="serving bench stores K/V in this cache dtype "
                         "(e.g. int8 → the dense_int8 family: quantized "
                         "pools + scale pages, dequantized in the paged "
                         "gather); rows keep their fp names so `report` "
                         "diffs the two precisions, and paged runs add a "
                         "pool_bytes_per_token capacity row")
    ap.add_argument("--obs", action="store_true",
                    help="serving bench re-runs the identical workload with "
                         "tracing + metrics armed and adds a per_token_obs "
                         "row (overhead=%% vs the off run); with --json the "
                         "payload also records the metrics snapshot")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows + backend capabilities to PATH")
    ap.add_argument("--history", metavar="PATH", default=None,
                    help="append this run to the JSONL history store "
                         "(also honoured via $REPRO_BENCH_HISTORY); "
                         "requires --json semantics, so rows are recorded "
                         "even without a results file")
    args = ap.parse_args(argv)
    unknown = [b for b in args.benches if b not in mods]
    if unknown:
        ap.error(f"unknown bench(es) {unknown}; choose from {list(mods)}")
    from repro import cache_dirs
    cache_dirs.enable_compile_cache()

    rows = []
    for name in args.benches or list(mods):
        kwargs = {}
        if name == "serving":
            if args.paged:
                kwargs["paged"] = True
            if args.priorities:
                kwargs["priorities"] = True
            if args.no_preempt:
                kwargs["preempt"] = False
            if args.replicas:
                kwargs["replicas"] = args.replicas
                kwargs["affinity"] = not args.no_affinity
            if args.obs:
                kwargs["obs"] = True
            if args.arch != "smollm_360m":
                kwargs["arch"] = args.arch
            if args.kv:
                kwargs["kv"] = args.kv
        rows.extend(mods[name].run(smoke=args.smoke, **kwargs))
    emit(rows)
    from repro.obs import history
    hist_path = history.history_path(args.history)
    if args.json or hist_path:
        env = _capability_env()
        row_dicts = [{"name": n, "us_per_call": round(us, 2), "derived": d}
                     for n, us, d in rows]
    if args.json:
        payload = {"smoke": bool(args.smoke), "env": env, "rows": row_dicts}
        if args.obs:
            from repro.obs import metrics as obs_metrics
            payload["metrics"] = obs_metrics.snapshot()
        os.makedirs(os.path.dirname(os.path.abspath(args.json)),
                    exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
    if hist_path:
        history.HistoryStore(hist_path).append(
            env, row_dicts, smoke=bool(args.smoke),
            label="run:" + ",".join(args.benches or ["all"]))
        print(f"history: recorded {len(row_dicts)} rows → {hist_path}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
