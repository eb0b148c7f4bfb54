"""Serving benchmark: the continuous-batching loop under staggered load.

Reports throughput (as µs per generated token), p50/p95 per-token latency,
and batch occupancy against the drain-and-refill bound — the serving-side
numbers the paper's §4 fusion is supposed to move.  Smoke mode runs a
seconds-long workload so tier-1 keeps the harness honest.

``paged=True`` serves the same workload through the paged KV cache
(``repro.serving.paged``): block-pool allocation, a shared prompt prefix so
the prefix index engages, and extra rows for the block accounting.  The
common row names are deliberately identical to the slot-pool run so
``run.py report slotpool.json paged.json`` diffs the two modes directly.

``priorities=True`` makes the workload mixed-priority (two classes, the
urgent one deadline-bearing) over a deliberately undersized block pool, and
adds SLO-attainment / p95-by-class / preemption rows; ``preempt=False``
serves the identical workload with preempt-and-swap disabled, so
``run.py report preempt_off.json preempt_on.json`` isolates what preemption
buys the urgent class.

``replicas=N`` serves a prefix-heavy workload (four prefix groups, every
request deadline-bearing) through ``repro.serving.router.ReplicaRouter``
over N paged engine replicas and adds ``tok_s_total`` /
``slo_attained_pct`` / ``prefix_hit_rate`` / ``backpressure_rejects`` rows.
The workload is IDENTICAL for every N (and for ``affinity=False``), so
``run.py report replicas1.json replicas4.json`` is the scaling diff and an
affinity-off run isolates what prefix routing buys.

``kv="int8"`` serves the identical workload with the quantized KV cache
(``kv_cache_dtype`` on the config → the ``dense_int8`` cache family):
int8 K/V pools beside bfloat16 scale pages, dequantized inside the paged
gather.  Row names stay identical to the fp run — ``run.py report fp.json
int8.json`` is the capacity/latency diff — and paged runs gain a
``pool_capacity`` row (bytes per cacheable token) so the report quantifies
what quantization buys in pool footprint.  The family is non-shareable, so
the shared-prefix stats read 0 by design; the workload stays the same for
comparability.

``arch=NAME`` serves a different smoke architecture through the same
harness: ``zamba2_1p2b`` / ``xlstm_125m`` exercise the fixed-state cache
family (one refcounted block per sequence; prompts snap to the state scan's
chunk quantum), ``whisper_small`` the enc-dec family (prompts become a small
pool of repeated audio clips so encoder-block sharing engages).  Non-default
archs are forced paged — the block accounting is the point — and emit
``serving/{tag}/{arch}/*`` rows so default-arch diffs stay comparable.

All modes drive the engine layer (``Engine`` / ``ReplicaRouter``) — the
grep-policy test pins that nothing here touches ``ContinuousScheduler``
directly.
"""
from __future__ import annotations

import dataclasses

import jax

from benchmarks import common


def run(smoke: bool = False, paged: bool = False, priorities: bool = False,
        preempt: bool = True, replicas: int = 0,
        affinity: bool = True, obs: bool = False,
        arch: str = "smollm_360m", kv: str = "") -> list:
    import repro.configs as configs
    from repro.models.params import init_params
    from repro.serving import cache_family, scheduler
    from repro.serving.engine_api import Engine
    from repro.serving.router import ReplicaRouter

    cfg = configs.get_smoke(arch)
    if kv:
        cfg = cfg.replace(kv_cache_dtype=kv)
    family = cache_family.resolve(cfg)
    if family.kind != "token" and (priorities or replicas or obs):
        raise SystemExit(f"--arch {arch} ({family.name}): only the plain "
                         "serving rows are benchmarked for non-dense "
                         "cache families")
    if family.requires_paged or family.kind == "state":
        paged = True               # the families this arch flag exists for
    block_size = 8
    slo_ms = 60_000.0                  # generous CPU-CI deadline: the metric
    if smoke:                          # should move, not saturate at 0
        n_req, slots, slot_len, chunk = 6, 2, 40, 8
        prompt_lens, decode_lens, rate = (4, 12), (2, 8), 2.0
        shared_prefix = 8              # one full block at block_size=8
    else:
        n_req, slots, slot_len, chunk = 32, 8, 96, 16
        prompt_lens, decode_lens, rate = (8, 48), (4, 40), 3.0
        shared_prefix = 16
    if replicas:
        paged = True                   # affinity is a paged-cache economy
    paged_kw = dict(paged=True, block_size=block_size) if paged else {}
    if priorities and paged and not replicas:
        # undersize the pool so urgent arrivals actually contend with
        # running low-priority decodes — the regime preemption exists for
        paged_kw["num_blocks"] = (slots + 1) * (slot_len // block_size) // 2
    paged_kw["preempt"] = preempt

    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    if replicas:
        # prefix-heavy: four groups, each sharing its own system prompt —
        # the SAME workload for every replica count / routing policy, so
        # cross-run diffs measure the router, not the traffic
        per_group = 3 if smoke else 8
        requests = []
        for g in range(4):
            for r in scheduler.poisson_workload(
                    per_group, rate_per_tick=rate / 2,
                    prompt_lens=prompt_lens, decode_lens=decode_lens,
                    vocab=cfg.vocab_size, seed=10 + g,
                    shared_prefix=shared_prefix, slo_ms=slo_ms):
                requests.append(dataclasses.replace(
                    r, rid=g * per_group + r.rid))
        requests.sort(key=lambda r: (r.arrival_tick, r.rid))
    else:
        # priorities seed: urgent (priority-0) arrivals land AFTER
        # low-priority decodes occupy the pool — the contention preemption
        # exists to resolve
        requests = scheduler.poisson_workload(
            n_req, rate_per_tick=rate, prompt_lens=prompt_lens,
            decode_lens=decode_lens, vocab=cfg.vocab_size,
            seed=6 if priorities else 0,
            shared_prefix=shared_prefix if paged else 0,
            priority_classes=2 if priorities else 1,
            slo_ms=slo_ms if priorities else None)

    import numpy as np
    if family.kind == "encdec":
        # prompts are audio: a small pool of distinct clips, repeated, so
        # the encoder-block sharing the family exists for actually engages
        audio_rng = np.random.default_rng(2)
        audios = [audio_rng.integers(0, cfg.vocab_size, cfg.encoder_seq_len)
                  for _ in range(3)]
        requests = [dataclasses.replace(r, prompt=audios[r.rid % len(audios)])
                    for r in requests]
        # headroom so finished requests' encoder chains survive in the LRU
        # prefix cache until the repeat arrives — the sharing being measured
        nc = cfg.encoder_seq_len // block_size
        paged_kw["num_blocks"] = slots * (nc + 1) + len(audios) * nc
    elif family.kind == "state":
        # single-shot prefill goes through the chunked state scan: snap
        # prompt lengths onto the scan's quantum
        q = family.prompt_quantum()
        requests = [dataclasses.replace(
            r, prompt=np.resize(r.prompt, len(r.prompt)
                                if len(r.prompt) <= q
                                else max(q, len(r.prompt) // q * q)))
            for r in requests]

    # warmup: the compiled step functions are shared across scheduler
    # instances (and all router replicas), and a prompt of 2*chunk-1 hits
    # every prefill width the binary chunk schedule can produce — so the
    # timed run below measures serving, not jit compilation
    warm = Engine(
        params, cfg, num_slots=slots, slot_len=slot_len, prefill_chunk=chunk,
        top_k=5, base_rng=jax.random.PRNGKey(1), **paged_kw)
    if family.kind == "encdec":
        warm_reqs = [scheduler.Request(rid=0, prompt=audios[0],
                                       max_new_tokens=2)]
    elif family.kind == "state":
        warm_reqs = [scheduler.Request(
            rid=0, prompt=np.arange(family.prompt_quantum()) % 100,
            max_new_tokens=2)]
    elif family.single_shot_prefill:
        # single-shot prefill (quantized families) jits once per distinct
        # prompt length — the binary chunk schedule never engages — so warm
        # every length the workload will present
        warm_reqs = [scheduler.Request(rid=i, prompt=np.arange(n) % 100,
                                       max_new_tokens=2)
                     for i, n in enumerate(sorted({len(r.prompt)
                                                   for r in requests}))]
    else:
        warm_reqs = [scheduler.Request(rid=0, prompt=np.arange(2 * chunk - 1)
                                       % 100, max_new_tokens=2)]
    if priorities and preempt and not replicas:
        # also warm the preempt-and-swap path (swap-in's block restore jits
        # once per pool shape): low-priority decodes filling every row, then
        # an urgent arrival that must swap one out
        warm_reqs = [
            scheduler.Request(rid=i, prompt=np.arange(2 * chunk - 1) % 100,
                              max_new_tokens=10, priority=1)
            for i in range(slots)
        ] + [scheduler.Request(rid=slots, prompt=np.arange(chunk) % 100,
                               max_new_tokens=2, arrival_tick=3, priority=0)]
    warm.serve(warm_reqs)

    if replicas:
        router = ReplicaRouter(
            params, cfg, replicas=replicas, affinity=affinity,
            num_slots=slots, slot_len=slot_len, prefill_chunk=chunk,
            top_k=5, base_rng=jax.random.PRNGKey(0), **paged_kw)
        report = router.serve(requests)
    else:
        eng = Engine(
            params, cfg, num_slots=slots, slot_len=slot_len,
            prefill_chunk=chunk, top_k=5, base_rng=jax.random.PRNGKey(0),
            **paged_kw)
        report = eng.serve(requests)

    obs_row = None
    if obs and not replicas:
        # overhead measurement: the IDENTICAL workload on a fresh engine
        # (jits shared via lru_cache) with tracing + metrics armed, so the
        # per_token vs per_token_obs diff is the full observability cost
        import os
        import tempfile
        from repro.obs import metrics as obs_metrics
        from repro.obs import trace as obs_trace
        def _serve_once(tracer):
            eng2 = Engine(
                params, cfg, num_slots=slots, slot_len=slot_len,
                prefill_chunk=chunk, top_k=5,
                base_rng=jax.random.PRNGKey(0), tracer=tracer, **paged_kw)
            return eng2.serve(requests)

        # interleaved fastest-half comparison: this shared-CPU box adds
        # ±5-8% of contention noise per serve, but the noise is strictly
        # additive (neighbours only ever slow a serve down), so the fastest
        # serves of each mode approach the uncontended cost.  The mean of
        # the fastest HALF (rather than the single min) keeps six samples
        # in the estimate, which a lone unlucky burst can't swing; strict
        # on/off interleaving with alternating order means both modes
        # sample the same calm windows.  A median of paired ratios — the
        # obvious alternative — inherits the full per-pair scatter and
        # needs ~10x the samples to say anything under 5%.
        def _serve_on():
            obs_metrics.enable()
            fd, trace_path = tempfile.mkstemp(suffix=".json")
            os.close(fd)
            tracer = obs_trace.Tracer(trace_path)
            rate = _serve_once(tracer).tokens_per_s
            tracer.close()
            n_events = tracer.total_events
            os.unlink(trace_path)
            obs_metrics.disable()
            return rate, n_events

        was_enabled = obs_metrics.enabled()
        obs_metrics.disable()
        on_rates, off_rates, events = [], [], 0
        for i in range(16):
            if i % 2 == 0:                 # alternate order within the
                rate_on, events = _serve_on()   # interleave as well
                off_rates.append(_serve_once(None).tokens_per_s)
            else:
                off_rates.append(_serve_once(None).tokens_per_s)
                rate_on, events = _serve_on()
            on_rates.append(rate_on)
        if was_enabled:
            obs_metrics.enable()
        fast_on = common.fastest_half_mean(on_rates, bigger_is_faster=True)
        fast_off = common.fastest_half_mean(off_rates, bigger_is_faster=True)
        overhead = (fast_off / max(fast_on, 1e-9) - 1.0) * 100.0
        obs_row = (1e6 / max(fast_on, 1e-9),
                   f"overhead={overhead:+.1f}% events={events}")

    pct = report.latency_percentiles((50, 95))
    baseline = report.baseline_occupancy(slots * max(replicas, 1))
    tag = "smoke" if smoke else "full"
    if arch != "smollm_360m":
        # default rows keep their pinned serving/{smoke,full}/* names so
        # existing report diffs keep working; other archs get their own
        tag = f"{tag}/{arch}"
    rows = [
        (f"serving/{tag}/per_token", 1e6 / max(report.tokens_per_s, 1e-9),
         f"{report.tokens_per_s:.1f}tok/s"),
        (f"serving/{tag}/p50_latency", pct["p50"] * 1e6,
         f"n={report.total_tokens}"),
        (f"serving/{tag}/p95_latency", pct["p95"] * 1e6,
         f"n={report.total_tokens}"),
        (f"serving/{tag}/occupancy_pct", report.occupancy * 100.0,
         f"drain_refill={baseline * 100.0:.1f}"),
    ]
    if obs_row is not None:
        rows.insert(1, (f"serving/{tag}/per_token_obs", *obs_row))
    if report.paged is not None:
        p = report.paged
        rows.append((f"serving/{tag}/blocks_shared", float(p["blocks_shared"]),
                     f"tokens_reused={p['tokens_reused']} "
                     f"cow={p['cow_copies']} "
                     f"min_free={p['min_free_blocks']}/{p['num_blocks']}"))
        if family.kind == "token":
            # pool footprint per cacheable token — the number quantized K/V
            # exists to move.  eval_shape so the row costs no allocation;
            # the family owns the layout, so scale pages are counted
            # without this harness knowing any dtype.  (Only token-kind
            # families page block_size tokens per block; state/enc-dec
            # blocks hold whole rows, so the unit would lie there.)
            pool_sds = jax.eval_shape(
                lambda: family.init_paged_cache(p["num_blocks"], block_size,
                                                slot_len))
            pool_bytes = sum(l.size * l.dtype.itemsize
                             for l in jax.tree_util.tree_leaves(pool_sds))
            pool_tokens = (p["num_blocks"] - 1) * block_size  # minus sentinel
            rows.append((f"serving/{tag}/pool_bytes_per_token",
                         pool_bytes / max(pool_tokens, 1),
                         f"kv={cfg.kv_cache_dtype or 'fp'} "
                         f"tok_per_kib="
                         f"{1024.0 * pool_tokens / pool_bytes:.2f} "
                         f"pool_kib={pool_bytes / 1024.0:.1f}"))
    if replicas:
        p = report.paged
        r = report.router
        prompt_tokens = sum(res.prompt_len for res in report.results)
        att = report.slo_attainment()
        bearing = sum(1 for res in report.results if res.slo_ms is not None)
        routing = "affinity" if r["affinity"] else "round_robin"
        rows.append((f"serving/{tag}/tok_s_total", report.tokens_per_s,
                     f"replicas={replicas} routing={routing}"))
        rows.append((f"serving/{tag}/slo_attained_pct",
                     (att or 0.0) * 100.0,
                     f"slo_ms={slo_ms:.0f} n={bearing}"))
        rows.append((f"serving/{tag}/prefix_hit_rate",
                     100.0 * p["tokens_reused"] / max(prompt_tokens, 1),
                     f"tokens_reused={p['tokens_reused']}"
                     f"/{prompt_tokens} routing={routing}"))
        rows.append((f"serving/{tag}/backpressure_rejects",
                     float(r["backpressure_rejects"]),
                     f"of {len(requests)} submitted"))
    if priorities and not replicas:
        att = report.slo_attainment()
        bearing = sum(1 for r in report.results if r.slo_ms is not None)
        by_class = report.latency_percentiles_by_class((95,))
        rows.append((f"serving/{tag}/slo_attained_pct",
                     (att or 0.0) * 100.0,
                     f"slo_ms={slo_ms:.0f} n={bearing} "
                     f"preempt={'on' if preempt else 'off'}"))
        rows.append((f"serving/{tag}/p95_latency_hipri",
                     by_class.get(0, {}).get("p95", 0.0) * 1e6,
                     "priority=0"))
        if report.paged is not None:
            p = report.paged
            rows.append((f"serving/{tag}/preemptions",
                         float(report.preemptions),
                         f"swap_out={p['swapped_blocks_out']} "
                         f"swap_in={p['swapped_blocks_in']} "
                         f"reclaimed={p['reclaimed_blocks']}"))
    return rows
