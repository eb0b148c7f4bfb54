"""Serving launcher: lockstep baseline and the continuous-batching loop.

``python -m repro.launch.serve --smoke --continuous`` drives the slot-pool
scheduler (``repro.serving.scheduler``) over synthetic Poisson-staggered
arrivals and reports throughput, p50/p95 per-token latency, and batch
occupancy against the drain-and-refill bound.  Adding ``--paged`` switches
the KV cache to the block pool (``repro.serving.paged``): admission gates on
free blocks, every prompt carries a shared synthetic prefix
(``--shared-prefix``, the system-prompt pattern), and the report adds
block-pool accounting — free-block low-water mark, blocks saved by prefix
sharing, copy-on-write count, persistent-prefix-cache residency/hits.
``--priority-classes N`` makes the workload mixed-priority (admission
orders by (priority, arrival); in paged mode a blocked urgent request
preempts lower-priority decodes by swapping their blocks out — disable
with ``--no-preempt``) and ``--slo-ms`` attaches a completion deadline to
the urgent class; the report then adds p95-by-class, SLO attainment, and
preemption/swap counts.  ``--replicas N`` serves the same workload through
``repro.serving.router.ReplicaRouter`` over N engine replicas —
prefix-affinity routed (``--no-affinity`` for round-robin), with admission
backpressure and a globally merged report.  ``--profile DIR`` records a JAX
profiler trace of the serve loop: the scheduler's ``sched.*`` sections on the
device trace's clock beside the ops they dispatched, each op under its
model-step scopes (``docs/observability.md``).  Without ``--continuous`` the original
lockstep batch runs: one shared cache length, prefill-everything-then-decode
— kept as the baseline the scheduler has to beat.  Either way the decode hot
path is the paper's §4 scenario: project to the vocabulary, fused
online-softmax + top-k, sample.
"""
from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro import cache_dirs
from repro.models import transformer
from repro.models.params import init_params
from repro.obs import clock as obs_clock
from repro.obs import kernels as obs_kernels
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serving import cache_family, engine


def _lockstep(args, cfg, params) -> int:
    """The original drain-and-refill loop (one shared cache_len)."""
    max_len = args.max_len or (args.prompt_len + args.tokens)
    rng = jax.random.PRNGKey(0)
    vocab = cfg.real_vocab_size or cfg.vocab_size
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0, vocab)
    patch = None
    if cfg.num_patches:
        patch = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.num_patches, cfg.d_model), jnp.float32)

    prefill = jax.jit(lambda p, t, pe: engine.prefill(
        p, t, cfg, max_len=max_len + (cfg.num_patches or 0),
        patch_embeds=pe))
    decode = jax.jit(lambda p, c, ln, t, r: engine.decode_step(
        p, c, ln, t, cfg, rng=r, top_k=args.top_k), donate_argnums=(1,))

    t0 = obs_clock.monotonic()
    last_hidden, caches, length = prefill(params, prompts, patch)
    logits = transformer.logits_last(params, last_hidden[:, None], cfg)
    from repro.core import topk_sample
    tok, _ = topk_sample(jax.random.PRNGKey(3), logits, args.top_k)
    jax.block_until_ready(tok)
    t_prefill = obs_clock.monotonic() - t0

    out = [tok]
    t0 = obs_clock.monotonic()
    for i in range(args.tokens - 1):
        tok, caches, length = decode(params, caches, length, tok[:, None],
                                     jax.random.fold_in(rng, i))
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = obs_clock.monotonic() - t0
    gen = jnp.stack(out, axis=1)
    print(f"prefill: {args.batch}×{args.prompt_len} in {t_prefill*1e3:.1f}ms")
    print(f"decode: {args.tokens - 1} steps × {args.batch} seqs in "
          f"{t_decode*1e3:.1f}ms "
          f"({(args.tokens - 1) * args.batch / max(t_decode, 1e-9):.1f} tok/s)")
    print("sample token ids:", gen[0, :16].tolist())
    return 0


def _continuous(args, cfg, params) -> int:
    """Continuous batching over staggered (Poisson) synthetic arrivals.

    Always drives a ``ReplicaRouter`` — with ``--replicas 1`` (the default)
    it owns a single ``Engine`` and the report lines are byte-identical to
    the pre-router CLI (pinned by tests/test_serving_router.py); with more,
    traffic spreads across replicas by prefix affinity (``--no-affinity``
    for the round-robin baseline) and the report merges globally."""
    from repro.serving import scheduler as sched_mod
    from repro.serving.router import ReplicaRouter

    vocab = cfg.real_vocab_size or cfg.vocab_size
    slot_len = args.max_len or (args.prompt_len + args.tokens + 8)
    if args.paged:                     # the paged determinism contract
        slot_len += -slot_len % args.block_size
    shared_prefix = args.shared_prefix if args.paged else 0
    requests = sched_mod.poisson_workload(
        args.requests, rate_per_tick=args.rate,
        prompt_lens=(max(2, args.prompt_len // 4), args.prompt_len),
        decode_lens=(max(2, args.tokens // 8), args.tokens),
        vocab=vocab, seed=1, shared_prefix=shared_prefix,
        priority_classes=args.priority_classes,
        slo_ms=args.slo_ms or None)
    family = cache_family.resolve(cfg)
    if family.kind == "encdec":
        # prompts are audio: a small set of distinct frame-id sequences, each
        # filling the encoder window; repeats of the same audio are where the
        # shared encoder blocks (and zero recompute) pay
        rng = np.random.default_rng(2)
        audios = [rng.integers(0, vocab, cfg.encoder_seq_len)
                  for _ in range(max(1, args.audios))]
        for r in requests:
            r.prompt = audios[r.rid % len(audios)]
    elif family.kind == "state":
        # single-shot prefill through the chunked scan: snap prompt lengths
        # to the scan's chunk quantum (≤ q, or a multiple of q)
        q = family.prompt_quantum()
        for r in requests:
            n = len(r.prompt)
            if n > q and n % q:
                r.prompt = r.prompt[:n - n % q]
    if args.metrics:
        obs_metrics.enable()
    # --trace FILE shares one Tracer across replicas (pids split the
    # tracks); --trace DIR/ writes replica{i}.json per replica plus a
    # clock-aligned merged.json via repro.obs.merge
    trace_dir = None
    tracer = None
    tracers = None
    if args.trace:
        if args.trace.endswith(os.sep) or os.path.isdir(args.trace):
            trace_dir = args.trace.rstrip(os.sep) or os.sep
            os.makedirs(trace_dir, exist_ok=True)
            tracers = [obs_trace.Tracer(
                           os.path.join(trace_dir, f"replica{i}.json"))
                       for i in range(args.replicas)]
        else:
            tracer = obs_trace.Tracer(args.trace)
    elif args.profile:
        # the scheduler's sched.* profiler annotations come from its Tracer;
        # with no --trace file it records in memory only
        tracer = obs_trace.Tracer()
    router = ReplicaRouter(
        params, cfg, replicas=args.replicas,
        affinity=not args.no_affinity,
        num_slots=args.slots, slot_len=slot_len,
        prefill_chunk=args.prefill_chunk, top_k=args.top_k,
        base_rng=jax.random.PRNGKey(0), paged=args.paged,
        block_size=args.block_size,
        num_blocks=args.blocks or None,
        preempt=not args.no_preempt, tracer=tracer, tracers=tracers)
    if args.profile:
        with jax.profiler.trace(args.profile, create_perfetto_trace=True):
            report = router.serve(requests)
    else:
        report = router.serve(requests)
    if tracer is not None:
        tracer.close()
    merged_path = None
    if tracers is not None:
        for t in tracers:
            t.close()
        from repro.obs import merge as obs_merge
        merged_path = os.path.join(trace_dir, "merged.json")
        obs_merge.merge_traces(
            [os.path.join(trace_dir, f"replica{i}.json")
             for i in range(args.replicas)],
            out=merged_path)

    pct = report.latency_percentiles((50, 95))
    baseline = report.baseline_occupancy(args.slots * args.replicas)
    mode = "paged continuous batching" if args.paged else "continuous batching"
    where = (f"{args.slots} slots" if args.replicas == 1
             else f"{args.replicas} replicas × {args.slots} slots")
    print(f"{mode}: {len(report.results)} requests over "
          f"{where} (slot_len={slot_len}, "
          f"prefill_chunk={args.prefill_chunk})")
    print(f"tokens: {report.total_tokens} in {report.wall_time:.2f}s "
          f"→ {report.tokens_per_s:.1f} tok/s")
    print(f"per-token latency: p50={pct['p50']*1e3:.1f}ms "
          f"p95={pct['p95']*1e3:.1f}ms")
    print(f"decode steps: {report.decode_steps}  "
          f"prefill chunks: {report.prefill_chunks}")
    print(f"batch occupancy: {report.occupancy:.3f} "
          f"(drain-and-refill baseline: {baseline:.3f})")
    if report.paged is not None:
        p = report.paged
        print(f"block pool: {p['num_blocks']}×{p['block_size']} blocks, "
              f"free now {p['free_blocks']}, "
              f"min free {p['min_free_blocks']}")
        print(f"blocks saved by sharing: {p['blocks_shared']} "
              f"(prefill tokens reused: {p['tokens_reused']}, "
              f"copy-on-write copies: {p['cow_copies']})")
        print(f"prefix cache: {p['cached_blocks']} blocks resident, "
              f"{p['prefix_cache_hits']} hits, "
              f"{p['reclaimed_blocks']} reclaimed under pressure")
    if args.replicas > 1:
        r = report.router
        routing = "prefix-affinity" if r["affinity"] else "round-robin"
        print(f"router: {routing}, per-replica requests "
              f"{r['per_replica']}, affinity routes {r['affinity_routes']}")
        if r["backpressure_rejects"]:
            print(f"backpressure: {r['backpressure_rejects']} rejected "
                  f"{r['rejected']}")
    if args.priority_classes > 1:
        for pr, pct_c in sorted(
                report.latency_percentiles_by_class((50, 95)).items()):
            rs = [r for r in report.results if r.priority == pr]
            npre = sum(r.preempted for r in rs)
            # phase split: queue wait / prefill compute / decode, so a slow
            # first token can be attributed instead of conflated
            def _mean(vals):
                vals = [v for v in vals if v is not None]
                return sum(vals) / len(vals) if vals else 0.0
            print(f"class {pr}: n={len(rs)} p50={pct_c['p50']*1e3:.1f}ms "
                  f"p95={pct_c['p95']*1e3:.1f}ms "
                  f"queued={_mean([r.queued_ms for r in rs]):.1f}ms "
                  f"prefill={_mean([r.prefill_ms for r in rs]):.1f}ms "
                  f"decode={_mean([r.decode_ms for r in rs]):.1f}ms "
                  f"preemptions={npre}")
        att = report.slo_attainment()
        if att is not None:
            bearing = sum(1 for r in report.results if r.slo_ms is not None)
            print(f"SLO attainment: {att*100:.1f}% of {bearing} "
                  f"deadline-bearing requests")
        if report.paged is not None:
            p = report.paged
            print(f"preemptions: {report.preemptions} "
                  f"(blocks swapped out: {p['swapped_blocks_out']}, "
                  f"swapped back in: {p['swapped_blocks_in']})")
    evicted = [r.rid for r in report.results if r.evicted]
    if evicted:
        print(f"evicted at capacity: {evicted}")
    if args.metrics:
        prof = obs_kernels.snapshot()
        for op, rec in prof["paths"].items():
            print(f"kernel path: {op} → {rec['path']} (×{rec['count']})")
        snap = obs_metrics.snapshot()
        for mname, rec in snap.items():
            if rec.get("type") != "histogram":
                continue
            print(f"metric {mname}: n={rec['count']} "
                  f"mean={rec['mean']:.4g} p50={rec['p50']:.4g} "
                  f"p95={rec['p95']:.4g}")
        print(f"metrics: {len(snap)} instruments recorded")
    if args.profile:
        print(f"profile: {args.profile} (sched.* host spans and the "
              f"device's ops on one clock; open the run with xprof, or "
              f"its perfetto_trace.json.gz in Perfetto)")
    if args.trace and tracer is not None:
        print(f"trace: {len(tracer.events)} events → {args.trace} "
              f"(open in Perfetto, or: python -m repro.obs.report "
              f"{args.trace})")
    if merged_path is not None:
        print(f"trace: {args.replicas} per-replica files in {trace_dir}"
              f"{os.sep} → merged view {merged_path} "
              f"(open in Perfetto, or: python -m repro.obs.report "
              f"{merged_path})")
    if report.occupancy <= baseline:
        print("WARNING: occupancy did not beat the drain-and-refill baseline")
        return 1
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--top-k", type=int, default=5)
    ap.add_argument("--max-len", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="slot-pool continuous batching over Poisson arrivals")
    ap.add_argument("--slots", type=int, default=4,
                    help="KV-cache slots in the pool (continuous mode)")
    ap.add_argument("--requests", type=int, default=16,
                    help="synthetic requests to serve (continuous mode)")
    ap.add_argument("--rate", type=float, default=2.0,
                    help="mean arrivals per scheduler tick (continuous mode)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt tokens prefilled per tick (continuous mode)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: block pool + prefix sharing "
                         "(continuous mode)")
    ap.add_argument("--block-size", type=int, default=8,
                    help="KV block size in tokens (paged mode)")
    ap.add_argument("--blocks", type=int, default=0,
                    help="pool capacity in blocks (paged mode; 0 = enough "
                         "for every slot at full length)")
    ap.add_argument("--audios", type=int, default=3,
                    help="distinct synthetic audios in the enc-dec workload "
                         "(requests cycle through them, so repeats share "
                         "encoder blocks)")
    ap.add_argument("--shared-prefix", type=int, default=8,
                    help="shared synthetic prompt prefix length (paged "
                         "mode; demonstrates block sharing)")
    ap.add_argument("--priority-classes", type=int, default=1,
                    help="priority classes in the synthetic workload (>1 "
                         "assigns each request a random class; smaller = "
                         "more urgent; report adds p95-by-class)")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="completion deadline attached to priority-0 "
                         "requests; report adds SLO attainment (0 = off)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the router (continuous "
                         "mode; 1 = the single-engine CLI, byte-identical "
                         "report)")
    ap.add_argument("--no-affinity", action="store_true",
                    help="route round-robin instead of by prefix affinity "
                         "(multi-replica baseline)")
    ap.add_argument("--no-preempt", action="store_true",
                    help="disable preempt-and-swap of lower-priority "
                         "decodes (paged mode; priorities stay "
                         "ordering-only)")
    ap.add_argument("--trace", default="",
                    help="write request-lifecycle + scheduler spans to this "
                         "Chrome trace_event file (continuous mode; open in "
                         "Perfetto or summarize with repro.obs.report); a "
                         "directory (trailing '/' or existing dir) writes "
                         "one replica{i}.json per replica plus a "
                         "clock-aligned merged.json")
    ap.add_argument("--metrics", action="store_true",
                    help="enable the repro.obs metrics registry; prints "
                         "dispatch paths and a snapshot summary after the "
                         "run")
    ap.add_argument("--profile", default="",
                    help="record a JAX profiler trace of the serve loop "
                         "into this directory (continuous mode): the "
                         "scheduler's sched.* spans on the device trace's "
                         "clock, and the device ops under their model-step "
                         "scopes")
    ap.add_argument("--kv-cache-dtype", default="",
                    help="override the config's KV-cache dtype (e.g. int8: "
                         "quantized K/V with per-position scales, dequantized "
                         "in the gather; empty = config default)")
    args = ap.parse_args(argv)
    cache_dirs.enable_compile_cache()

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get(args.arch))
    if args.kv_cache_dtype:
        cfg = cfg.replace(kv_cache_dtype=args.kv_cache_dtype)
    family = cache_family.resolve(cfg)
    if family.requires_paged and not (args.continuous and args.paged):
        raise SystemExit(f"{args.arch}: enc-dec serves under --continuous "
                         "--paged (the encoder output pages as immutable "
                         "shared blocks)")
    if args.continuous and cfg.num_patches:
        raise SystemExit("continuous batching serves text-only archs for now")
    if args.paged and not args.continuous:
        raise SystemExit("--paged requires --continuous (the lockstep "
                         "baseline keeps its contiguous cache)")

    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    if args.continuous:
        return _continuous(args, cfg, params)
    return _lockstep(args, cfg, params)


if __name__ == "__main__":
    raise SystemExit(main())
