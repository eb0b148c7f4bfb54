"""Continuous-batching scheduler: request queue, slot-based KV pool, and an
interleaved prefill/decode loop.

The decode hot path — vocab projection + fused online-softmax top-k (paper
§4) — only realizes its memory-access savings when decode steps run at full
batch occupancy.  A lockstep batch can't do that: it drains until its longest
member finishes, leaving slots idle.  This scheduler keeps the batch full:

* **SlotPool** — a fixed pool of KV-cache slots (one batch row each) with a
  per-slot length vector.  Finished slots are overwritten in place by the
  next request's prefilled cache; nothing ever waits for the batch to drain.
* **Admission** — by (priority, arrival tick), ties broken by submission
  order; with every request at the default priority this degenerates to the
  PR-2 FIFO.  A request is admitted when (a) it has arrived, (b) a slot is
  free, and (c) no
  other prefill is in flight (one prefill at a time bounds the decode stall a
  new request can inflict — the latency-aware part).  Its prompt then prefills **chunked**,
  interleaved with decode: the per-tick chunk budget scales with the number
  of idle slots (a nearly-full pool prefills one chunk per decode step to
  bound the stall; idle slots cost more tokens than a longer stall, so a
  drained pool prefills faster), and runs flat out when nothing is decoding.
  Time-to-first-token for queued work thus overlaps token generation for
  running work.  Each chunk prefills at its slot's running offset
  (``q_offset = cache_len``, ``kv_valid_len = cache_len + chunk``), operands
  the Pallas flash kernel now masks natively — chunked prefill is no longer
  pinned to the chunked XLA form on TPU serving.
* **Eviction** — a sequence is retired when it has produced its
  ``max_new_tokens``, emits ``eos_id``, or its slot is full
  (``len == slot_len``; recorded as ``evicted`` — the capacity backstop).
  Retirement frees the slot in the same tick, so the next queued request is
  admitted without interrupting anyone else.

* **Paged mode** (``paged=True``) — KV memory is a pool of fixed-size blocks
  with per-slot block tables (``repro.serving.paged``).  Admission is gated
  on free *blocks* after prefix matching (a request sharing another's prompt
  prefix adopts its physical blocks and prefills only from the divergence
  point), prefill chunks and decode tokens write straight into the pool
  through the table, and retirement — including the new out-of-blocks
  eviction backstop, which fires *before* a decode step the pool cannot
  back — returns every non-shared block to the free list in the same tick.
  Retired prompt blocks the prefix index still maps park in the pool's
  persistent LRU cache instead (entries outlive their last sequence);
  admission/decode pressure reclaims them coldest-first.

* **Priorities, SLOs, preemption** — requests carry a ``priority`` class
  (smaller = more urgent) and an optional ``slo_ms`` completion deadline
  that ``ServeReport`` scores per class.  Admission is deadline-aware
  within a class: candidates order by (priority, deadline slack, arrival),
  where slack = ``slo_ms`` minus time already waited — tighter deadlines
  place first, deadline-bearing requests outrank deadline-free peers, and
  uniform-SLO workloads keep their arrival order.  In paged mode with
  ``preempt=True``, a request that cannot be
  placed — no free row, or out of blocks *after* the pool reclaimed its cold
  prefix-cache blocks — swaps out the lowest-priority active decode,
  preferring deadline-free then loosest-slack then longest-remaining
  victims (``PagedPool.swap_out``: exclusive blocks to a host-side
  store, shared prefix blocks kept resident by reference).  The victim
  resumes later with no re-prefill and, because sample keys are
  (request id, token index), a token stream bit-identical to the
  never-preempted run.  The same swap runs before the out-of-blocks
  eviction backstop: live low-priority work yields before anyone is killed.
  While suspended work waits, each decode tick prefetches the next
  resume's host blocks back onto the device (``PagedPool.
  prefetch_swap_in``) concurrently with the step already in flight.

* **Engine layer** — the driving loop lives in ``repro.serving.engine_api``:
  ``Engine`` owns a scheduler instance and exposes the narrow
  ``submit / step / drain / stats / cache_probe`` surface that
  ``launch/serve.py``, the benchmarks, and ``repro.serving.router``'s
  multi-replica ``ReplicaRouter`` drive.  ``ContinuousScheduler.run``
  survives as a thin compatibility wrapper over ``Engine.serve``.

Determinism: a request's sample stream is keyed by (base_rng, request id,
token index) and sampling is per-slot (``engine.sample_per_slot``), so the
tokens a request produces are identical to running it alone through the
single-sequence decode path — regardless of arrival order, batch neighbours,
how its prefill was chunked, or whether its cache was contiguous or paged.
``tests/test_serving_continuous.py`` and ``tests/test_serving_paged.py`` pin
these equivalences.
"""
from __future__ import annotations

import contextlib
import functools
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.obs import clock as obs_clock
from repro.obs import metrics as obs_metrics
from repro.serving import cache_family, engine

Array = jax.Array


# ---------------------------------------------------------------------------
# Requests and results.
# ---------------------------------------------------------------------------
@dataclass(eq=False)                    # identity semantics: ndarray fields
class Request:                          # make generated __eq__ a crash hazard
    """One generation request.

    ``arrival_tick``: the scheduler tick at which the request becomes
    visible (0 = already waiting).  ``priority``: admission class, smaller
    is more urgent (default 0); admission orders by (priority, arrival) and
    — in paged mode with preemption on — a request that cannot be placed may
    swap out a strictly-lower-priority running decode.  ``slo_ms``: optional
    completion deadline in milliseconds measured from arrival; it does not
    change scheduling directly, but ``ServeReport.slo_attainment`` scores it
    and the serve CLI reports attainment per priority class."""
    rid: int
    prompt: np.ndarray                  # [T] token ids
    max_new_tokens: int
    arrival_tick: int = 0
    priority: int = 0                   # smaller = more urgent
    slo_ms: Optional[float] = None      # completion deadline from arrival


@dataclass
class RequestResult:
    rid: int
    prompt_len: int
    tokens: list = field(default_factory=list)
    arrival_time: float = 0.0           # wall-clock when first seen arrived
    admitted_time: Optional[float] = None   # queue wait ends (prefill starts)
    first_token_time: Optional[float] = None
    finish_time: float = 0.0
    evicted: bool = False               # retired by the slot-capacity backstop
    priority: int = 0                   # copied from the request
    slo_ms: Optional[float] = None      # copied from the request
    preempted: int = 0                  # times this request was swapped out
    dropped_latencies: int = 0          # per-token samples beyond the cap
    dropped_sum: float = 0.0
    _latencies: list = field(default_factory=list)

    # Per-token latency samples kept per request; percentile math stays
    # exact below the cap, and beyond it only count+sum are accumulated —
    # long-running streams no longer grow result memory without bound.
    MAX_RECORDED_LATENCIES = 8192

    def record_latency(self, latency: float) -> None:
        if len(self._latencies) < self.MAX_RECORDED_LATENCIES:
            self._latencies.append(latency)
        else:
            self.dropped_latencies += 1
            self.dropped_sum += latency

    @property
    def latencies(self) -> list:
        """Per-token latency: first token end-to-end from arrival, rest
        inter-token (capped — see ``record_latency``)."""
        return self._latencies

    @property
    def queued_ms(self) -> Optional[float]:
        """Queue wait: arrival → admission (prefill start)."""
        if self.admitted_time is None:
            return None
        return (self.admitted_time - self.arrival_time) * 1e3

    @property
    def prefill_ms(self) -> Optional[float]:
        """Prefill compute: admission → first token out."""
        if self.admitted_time is None or self.first_token_time is None:
            return None
        return (self.first_token_time - self.admitted_time) * 1e3

    @property
    def decode_ms(self) -> Optional[float]:
        """Decode: first token → finish (includes any suspended time)."""
        if self.first_token_time is None:
            return None
        return (self.finish_time - self.first_token_time) * 1e3

    @property
    def slo_met(self) -> Optional[bool]:
        """Whether the request finished inside its deadline (None: no SLO)."""
        if self.slo_ms is None:
            return None
        return (self.finish_time - self.arrival_time) * 1e3 <= self.slo_ms


@dataclass
class ServeReport:
    results: list                       # RequestResult, by completion order
    decode_steps: int
    prefill_chunks: int
    occupancy: float                    # mean active-slot fraction per decode step
    wall_time: float
    paged: Optional[dict] = None        # PagedPool.stats() when serving paged
    preemptions: int = 0                # swap-outs performed by the scheduler
    router: Optional[dict] = None       # ReplicaRouter stats (merged reports)
    started_at: Optional[float] = None  # serve-loop start (engine clock)
    ended_at: Optional[float] = None    # serve-loop end

    @property
    def total_tokens(self) -> int:
        return sum(len(r.tokens) for r in self.results)

    @property
    def tokens_per_s(self) -> float:
        return self.total_tokens / max(self.wall_time, 1e-9)

    def latency_percentiles(self, qs=(50, 95)) -> dict:
        lats = [l for r in self.results for l in r.latencies]
        if not lats:
            return {f"p{q}": 0.0 for q in qs}
        return {f"p{q}": float(np.percentile(lats, q)) for q in qs}

    def latency_percentiles_by_class(self, qs=(50, 95)) -> dict:
        """Per-token latency percentiles keyed by priority class — the
        p95-by-class view the SLO work is judged on."""
        out = {}
        for pr in sorted({r.priority for r in self.results}):
            lats = [l for r in self.results if r.priority == pr
                    for l in r.latencies]
            out[pr] = {f"p{q}": (float(np.percentile(lats, q)) if lats
                                 else 0.0) for q in qs}
        return out

    def slo_attainment(self) -> Optional[float]:
        """Fraction of SLO-bearing requests that finished inside their
        deadline (None when no request carried one)."""
        bearing = [r for r in self.results if r.slo_ms is not None]
        if not bearing:
            return None
        return sum(1 for r in bearing if r.slo_met) / len(bearing)

    def slo_counts_by_class(self) -> dict:
        """{priority: (met, bearing)} over deadline-carrying requests.
        Counts — unlike percentiles — combine across replicas by plain
        summation, so this is the per-class SLO view ``merge`` preserves
        exactly."""
        out: dict = {}
        for r in self.results:
            if r.slo_ms is None:
                continue
            met, bearing = out.get(r.priority, (0, 0))
            out[r.priority] = (met + (1 if r.slo_met else 0), bearing + 1)
        return out

    @classmethod
    def merge(cls, reports, *, router: Optional[dict] = None) -> "ServeReport":
        """Combine per-replica reports into one global report.

        Percentile inputs stay RAW: the per-request results (each carrying
        its latency samples) concatenate, so ``latency_percentiles`` and
        the by-class/SLO views run over the union of raw latencies — never
        an average of per-replica p95s, which would understate the tail.
        Counters (decode steps, prefill chunks, preemptions, the paged
        accounting incl. per-replica free/min-free capacities) sum;
        occupancy weights each replica by its decode steps.  Wall time is
        the true overlapped interval ``max(ended_at) - min(started_at)``
        when every report carries its serve start/end stamps (replicas
        serve concurrently but need not start together); reports without
        stamps fall back to ``max(wall_time)``."""
        reports = list(reports)
        if not reports:
            raise ValueError("merge needs at least one report")
        steps = sum(r.decode_steps for r in reports)
        occ = (reports[0].occupancy if len(reports) == 1
               else (sum(r.occupancy * r.decode_steps for r in reports)
                     / steps if steps else 0.0))
        paged_dicts = [r.paged for r in reports if r.paged is not None]
        paged = None
        if paged_dicts:
            paged = {k: (paged_dicts[0][k] if k == "block_size"
                         else sum(d[k] for d in paged_dicts))
                     for k in paged_dicts[0]}
        stamped = all(r.started_at is not None and r.ended_at is not None
                      for r in reports)
        started = min(r.started_at for r in reports) if stamped else None
        ended = max(r.ended_at for r in reports) if stamped else None
        wall = (ended - started if stamped
                else max(r.wall_time for r in reports))
        return cls(
            results=[res for r in reports for res in r.results],
            decode_steps=steps,
            prefill_chunks=sum(r.prefill_chunks for r in reports),
            occupancy=occ,
            wall_time=wall,
            paged=paged,
            preemptions=sum(r.preemptions for r in reports),
            router=router,
            started_at=started,
            ended_at=ended)

    def baseline_occupancy(self, num_slots: int) -> float:
        """Drain-and-refill bound on THIS workload, batched in the recorded
        arrival order (completion order would regroup similar lengths and
        misstate the bound — every report consumer should call this rather
        than re-deriving the ordering)."""
        ordered = sorted(self.results,
                         key=lambda r: (r.arrival_time, r.rid))
        return drain_and_refill_occupancy(
            [len(r.tokens) for r in ordered], num_slots)


def drain_and_refill_occupancy(decode_lens, num_slots: int) -> float:
    """Slot-step occupancy of the lockstep baseline on the same workload:
    batches of up to ``num_slots`` requests (pass ``decode_lens`` in ARRIVAL
    order — completion order would regroup similar lengths and misstate the
    bound) decode until the LONGEST member finishes, then the whole batch is
    swapped.  This is the bound the continuous scheduler has to beat."""
    decode_lens = list(decode_lens)
    if not decode_lens:
        return 0.0
    steps = 0
    for i in range(0, len(decode_lens), num_slots):
        steps += max(decode_lens[i:i + num_slots])
    return sum(decode_lens) / float(steps * num_slots)


# what ``ContinuousScheduler._span`` hands back without a tracer (a
# nullcontext holds no state, so one serves every call)
_NO_SPAN = contextlib.nullcontext()


# ---------------------------------------------------------------------------
# Compiled step functions — shared across scheduler instances via lru_cache
# (ModelConfig is frozen/hashable), so a fresh scheduler (or a benchmark's
# warmup instance) reuses already-compiled code instead of re-jitting.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _jitted_write(cfg: ModelConfig):
    return jax.jit(
        lambda pool, seq, slot: engine.write_slot(cfg, pool, seq, slot),
        donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _jitted_steps(cfg: ModelConfig, top_k: int, temperature: float):
    def decode(params, caches, lens, tokens, rids, produced, base_rng):
        # per-slot keys folded INSIDE the jit: one dispatch per tick instead
        # of 2B host-side fold_ins (bit-identical to the eager fold_in the
        # single-sequence reference path uses)
        keys = jax.vmap(lambda r, p: jax.random.fold_in(
            jax.random.fold_in(base_rng, r), p))(rids, produced)
        return engine.decode_step_slots(params, caches, lens, tokens, cfg,
                                        rngs=keys, top_k=top_k,
                                        temperature=temperature)

    return (jax.jit(decode, donate_argnums=(1,)),
            jax.jit(functools.partial(engine.prefill_chunk, cfg=cfg),
                    donate_argnums=(1,)),
            jax.jit(functools.partial(engine.logits_from_hidden, cfg=cfg)),
            jax.jit(functools.partial(engine.sample_per_slot, top_k=top_k,
                                      temperature=temperature)))


@functools.lru_cache(maxsize=None)
def _jitted_paged_steps(cfg: ModelConfig, top_k: int, temperature: float):
    """Paged-mode step functions: decode over (pools, block tables) and the
    block-table prefill chunk.  Same per-slot PRNG fold as the slot-pool
    decode, so a request's stream is independent of the cache layout."""
    def decode(params, pools, tables, lens, tokens, rids, produced, base_rng):
        keys = jax.vmap(lambda r, p: jax.random.fold_in(
            jax.random.fold_in(base_rng, r), p))(rids, produced)
        return engine.decode_step_paged(params, pools, tables, lens, tokens,
                                        cfg, rngs=keys, top_k=top_k,
                                        temperature=temperature)

    return (jax.jit(decode, donate_argnums=(1,)),
            jax.jit(functools.partial(engine.prefill_chunk_paged, cfg=cfg),
                    donate_argnums=(1,)))


@functools.lru_cache(maxsize=None)
def _jitted_state_steps(cfg: ModelConfig, top_k: int, temperature: float):
    """Fixed-state paged decode: gather each active slot's state row, run
    the ordinary slot decode, scatter back.  Same per-slot PRNG fold — the
    stream is independent of where the state physically lives."""
    def decode(params, pools, rows, active, lens, tokens, rids, produced,
               base_rng):
        keys = jax.vmap(lambda r, p: jax.random.fold_in(
            jax.random.fold_in(base_rng, r), p))(rids, produced)
        return engine.decode_step_state(params, pools, rows, active, lens,
                                        tokens, cfg, rngs=keys, top_k=top_k,
                                        temperature=temperature)

    return jax.jit(decode, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _jitted_encdec_steps(cfg: ModelConfig, slot_len: int, top_k: int,
                         temperature: float):
    """Enc-dec paged steps: (decode, fresh prefill, prefix-hit prefill,
    cross gather).  The two prefill forms produce bit-identical decoder
    caches for the same audio — the cached one just skips the encoder."""
    def decode(params, pools, cross_tables, self_rows, active, lens, tokens,
               rids, produced, base_rng):
        keys = jax.vmap(lambda r, p: jax.random.fold_in(
            jax.random.fold_in(base_rng, r), p))(rids, produced)
        return engine.decode_step_encdec_paged(
            params, pools, cross_tables, self_rows, active, lens, tokens,
            cfg, rngs=keys, top_k=top_k, temperature=temperature)

    return (jax.jit(decode, donate_argnums=(1,)),
            jax.jit(functools.partial(engine.encdec_prefill, cfg=cfg,
                                      max_len=slot_len)),
            jax.jit(functools.partial(engine.encdec_prefill_cached, cfg=cfg,
                                      max_len=slot_len)),
            jax.jit(engine.gather_encdec_cross))


# ---------------------------------------------------------------------------
# Slot pool.
# ---------------------------------------------------------------------------
class SlotPool:
    """Fixed pool of per-sequence KV-cache slots with a [num_slots] length
    vector — the thing that replaces the lockstep batch's shared scalar."""

    def __init__(self, cfg: ModelConfig, num_slots: int, slot_len: int):
        self.cfg = cfg
        self.num_slots = num_slots
        self.slot_len = slot_len
        self.caches = engine.init_cache(cfg, num_slots, slot_len)
        self.lens = jnp.zeros((num_slots,), jnp.int32)
        self._free = deque(range(num_slots))
        self._write = _jitted_write(cfg)

    @property
    def free_slots(self) -> int:
        return len(self._free)

    def acquire(self) -> Optional[int]:
        return self._free.popleft() if self._free else None

    def release(self, slot: int) -> None:
        self.lens = self.lens.at[slot].set(0)
        self._free.append(slot)

    def insert(self, slot: int, seq_caches: list, length: int) -> None:
        """Overwrite ``slot`` with a prefilled batch-1 cache of ``length``."""
        self.caches = self._write(self.caches, seq_caches, jnp.int32(slot))
        self.lens = self.lens.at[slot].set(length)


# ---------------------------------------------------------------------------
# The scheduler.
# ---------------------------------------------------------------------------
@dataclass
class _InFlight:
    req: Request
    result: RequestResult
    slot: int = -1
    produced: int = 0                   # tokens sampled so far (keys the rng)
    remaining: int = 0
    last_token_time: float = 0.0        # inter-token latency baseline
    span: object = None                 # open lifecycle span (tracing only)


@dataclass
class _Suspended:
    """A preempted in-flight request parked off-pool: the flight keeps its
    produced/remaining counters (they key the PRNG stream) and ``token`` is
    the last sampled token, re-fed to decode on resume."""
    flight: _InFlight
    token: int


class ContinuousScheduler:
    """Drives the slot pool: admission → chunked prefill → pooled decode.

    One ``tick()`` = admit what fits, advance the in-flight prefill by one
    chunk, run one decode step over every slot.  ``run()`` loops until the
    queue, the prefill, the pool, and the suspended store are all empty.

    Keyword arguments
    -----------------
    num_slots:
        KV slots / batch rows in the pool (the decode batch width).
    slot_len:
        Per-sequence cache capacity in tokens (paged mode: must be a
        multiple of ``block_size``).
    prefill_chunk:
        Prompt tokens prefilled per scheduler tick while decodes are in
        flight (the latency/occupancy knob; see ``_advance_prefill``).
    top_k / temperature:
        Sampling parameters for the fused softmax+top-k draw.
    base_rng:
        PRNG key the per-(request id, token index) sample keys fold out of.
    eos_id:
        Token id that retires a sequence early (None: length-only).
    paged:
        Use the block-pool KV cache (``repro.serving.paged``) instead of
        contiguous slots; enables prefix sharing, the persistent prefix
        cache, and preempt-and-swap.
    block_size / num_blocks:
        Paged-mode pool geometry (tokens per block / usable blocks;
        ``num_blocks=None`` sizes the pool for every slot at full length).
    preempt:
        Paged mode only: allow a request that cannot be placed (no free
        row, or out of blocks even after LRU cache reclamation) to swap out
        a strictly-lower-priority running decode (``PagedPool.swap_out``).
        The victim resumes later bit-identically; ``False`` makes priorities
        ordering-only, the preemption-off baseline the benchmarks diff.
    clock:
        Time source for every latency/SLO stamp (default: the process-wide
        ``repro.obs.clock``).  Tests inject a ``VirtualClock`` here and
        advance it per tick for exact latency assertions.
    tracer / trace_pid:
        Optional ``repro.obs.trace.Tracer``: request-lifecycle spans go to
        track ``rid + 1``, scheduler ticks to track 0, under process id
        ``trace_pid`` (the replica index).  The tick's nested sections are
        also ``sched.*`` annotations on the JAX profiler's clock.  ``None``
        — the default — keeps the hot path free of any tracing work.
    """

    def __init__(self, params, cfg: ModelConfig, *, num_slots: int,
                 slot_len: int, prefill_chunk: int = 32, top_k: int = 5,
                 temperature: float = 1.0, base_rng: Optional[Array] = None,
                 eos_id: Optional[int] = None, paged: bool = False,
                 block_size: int = 8, num_blocks: Optional[int] = None,
                 preempt: bool = True, clock: Optional[obs_clock.Clock] = None,
                 tracer=None, trace_pid: int = 0):
        self.params = params
        self.cfg = cfg
        self.family = cache_family.resolve(cfg)
        self.paged = paged
        if self.family.requires_paged and not paged:
            raise ValueError(
                f"{cfg.name!r} serves only in paged mode: the encoder output "
                "pages as immutable shared blocks "
                f"(family={self.family.name!r})")
        self.preempt = preempt
        self.clock = clock or obs_clock.get()
        self.tracer = tracer
        self._pid = trace_pid
        self._queued_spans: dict[int, object] = {}     # rid → open queued span
        self._metrics = (self._build_metrics(trace_pid)
                         if obs_metrics.enabled() else None)
        if paged:
            from repro.serving import paged as paged_mod
            self.pool = paged_mod.PagedPool(cfg, num_slots, slot_len,
                                            block_size, num_blocks)
        else:
            self.pool = SlotPool(cfg, num_slots, slot_len)
        self.prefill_chunk = max(1, prefill_chunk)
        # a family whose prefill drops information when chunked (quantized
        # caches, recurrent state) sends its prompts in whole
        self._single_shot_prefill = self.family.single_shot_prefill
        self.top_k = top_k
        self.temperature = temperature
        self.base_rng = (base_rng if base_rng is not None
                         else jax.random.PRNGKey(0))
        self.eos_id = eos_id

        self.queue: deque[Request] = deque()
        self.active: dict[int, _InFlight] = {}         # slot → in-flight
        self._suspended: dict[int, _Suspended] = {}    # rid → preempted
        self.preemptions = 0
        self._prefill: Optional[dict] = None           # in-progress prefill
        self._arrival_times: dict[int, float] = {}     # rid → wall-clock seen
        self._seen_rids: set[int] = set()
        self.finished: list[RequestResult] = []
        self.tick_count = 0
        self.decode_steps = 0
        self.prefill_chunks = 0
        self._occupancy_sum = 0.0
        self.tokens = jnp.zeros((num_slots,), jnp.int32)
        (self._decode, self._prefill_step, self._logits,
         self._sample) = _jitted_steps(cfg, top_k, float(temperature))
        if paged and self.family.kind == "token":
            (self._decode_paged, self._prefill_paged) = _jitted_paged_steps(
                cfg, top_k, float(temperature))
        elif paged and self.family.kind == "state":
            self._decode_state = _jitted_state_steps(cfg, top_k,
                                                     float(temperature))
        elif paged and self.family.kind == "encdec":
            (self._decode_encdec, self._encdec_prefill,
             self._encdec_prefill_cached,
             self._encdec_gather_cross) = _jitted_encdec_steps(
                cfg, slot_len, top_k, float(temperature))

    # -- rng ----------------------------------------------------------------
    def _key(self, rid: int, token_index: int) -> Array:
        return jax.random.fold_in(
            jax.random.fold_in(self.base_rng, rid), token_index)

    # -- observability --------------------------------------------------------
    # The plain counters (decode_steps, preemptions, pool stats, …) stay the
    # authoritative inputs to ServeReport — they must read the same whether
    # the registry is on or off.  The registry only MIRRORS them (plus
    # distributions the report cannot hold), so disabling it changes nothing.
    def _build_metrics(self, pid: int) -> dict:
        prefix = f"serving.r{pid}" if pid else "serving"
        m = {
            "tokens": obs_metrics.counter(f"{prefix}.tokens"),
            "preemptions": obs_metrics.counter(f"{prefix}.preemptions"),
            "occupancy": obs_metrics.histogram(f"{prefix}.occupancy"),
            "tick_ms": obs_metrics.histogram(f"{prefix}.tick_ms"),
            "active": obs_metrics.gauge(f"{prefix}.active"),
            "queue_depth": obs_metrics.gauge(f"{prefix}.queue_depth"),
            "free_slots": obs_metrics.gauge(f"{prefix}.free_slots"),
        }
        if self.paged:
            # free_blocks is a Gauge, so its .min IS the low-water mark
            for k in ("free_blocks", "cached_blocks", "prefix_cache_hits",
                      "swapped_bytes_out", "swapped_bytes_in"):
                m[k] = obs_metrics.gauge(f"{prefix}.{k}")
        return m

    def _update_metrics(self) -> None:
        m = self._metrics
        m["active"].set(len(self.active))
        m["queue_depth"].set(len(self.queue) + len(self._suspended))
        if self.paged:
            m["free_slots"].set(self.pool.free_slots)
            m["free_blocks"].set(self.pool.free_blocks)
            m["cached_blocks"].set(self.pool.cached_blocks)
            m["prefix_cache_hits"].set(self.pool.prefix_cache_hits)
            m["swapped_bytes_out"].set(self.pool.swapped_bytes_out)
            m["swapped_bytes_in"].set(self.pool.swapped_bytes_in)
        else:
            m["free_slots"].set(self.pool.free_slots)

    @staticmethod
    def _tid(rid: int) -> int:
        """Trace track for a request (track 0 is the scheduler's)."""
        return rid + 1

    def _span(self, name: str, *, tid: int = 0, args=None):
        """Scheduler-side span context, also a ``sched.<name>`` profiler
        annotation (``Tracer.span``); a no-op without a tracer."""
        if self.tracer is None:
            return _NO_SPAN
        return self.tracer.span(name, tid=tid, pid=self._pid, args=args)

    def _begin_phase(self, flight: _InFlight, name: str, args=None) -> None:
        """Close the flight's current lifecycle span and open ``name``."""
        if self.tracer is None:
            return
        if flight.span is not None:
            self.tracer.end(flight.span)
        flight.span = self.tracer.begin(
            name, tid=self._tid(flight.req.rid), pid=self._pid, args=args)

    def _end_phase(self, flight: _InFlight) -> None:
        if self.tracer is not None and flight.span is not None:
            self.tracer.end(flight.span)
            flight.span = None

    # -- API ----------------------------------------------------------------
    def submit(self, req: Request) -> None:
        if len(req.prompt) == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.rid}: max_new_tokens must be ≥ 1 "
                             f"(got {req.max_new_tokens})")
        try:
            # family-specific admissibility: dense/state prompts must leave
            # decode room in the slot; enc-dec prompts are audio frames that
            # must fill the encoder window exactly
            self.family.validate_prompt(len(req.prompt), self.pool.slot_len)
        except ValueError as e:
            raise ValueError(f"request {req.rid}: {e}") from None
        if self.paged and not self.pool.fits(len(req.prompt)):
            raise ValueError(
                f"request {req.rid}: prompt of {len(req.prompt)} can never "
                "be admitted — its block need exceeds the whole pool")
        if req.rid in self._seen_rids:
            raise ValueError(f"duplicate request id {req.rid}: rids key the "
                             "sample streams and result bookkeeping")
        self._seen_rids.add(req.rid)
        self.queue.append(req)

    def tick(self) -> None:
        self.tick_count += 1
        now = self.clock.monotonic()
        for r in self.queue:           # stamp arrivals BEFORE admission, so
            if (r.arrival_tick <= self.tick_count     # queue wait is counted
                    and r.rid not in self._arrival_times):
                self._arrival_times[r.rid] = now
                if self.tracer is not None:
                    self.tracer.thread_name(self._tid(r.rid), f"req {r.rid}",
                                            pid=self._pid)
                    self._queued_spans[r.rid] = self.tracer.begin(
                        "queued", tid=self._tid(r.rid), pid=self._pid,
                        args={"rid": r.rid, "priority": r.priority})
        # section spans only when the section has work — idle sections are
        # trace noise and, at ~3 spans/tick, a measurable share of overhead
        with self._span("tick", args={"tick": self.tick_count}):
            if self.queue:
                with self._span("admit"):
                    self._admit()
            else:
                self._admit()
            if self._prefill is not None:
                with self._span("prefill"):
                    self._advance_prefill()
            else:
                self._advance_prefill()
            if self.active:
                with self._span("decode"):
                    self._decode_tick()
            else:
                self._decode_tick()
        if self.tracer is not None:
            self.tracer.counter("sched", {
                "active": len(self.active), "queue": len(self.queue),
                "free_slots": self.pool.free_slots}, pid=self._pid)
            if self.paged:
                self.tracer.counter("blocks", {
                    "free": self.pool.free_blocks,
                    "cached": self.pool.cached_blocks}, pid=self._pid)
        if self._metrics is not None:
            self._update_metrics()
            self._metrics["tick_ms"].observe(
                (self.clock.monotonic() - now) * 1e3)

    @property
    def busy(self) -> bool:
        """Work remains: queued (incl. future arrivals), prefilling,
        decoding, or suspended."""
        return bool(self.queue or self.active or self._prefill
                    or self._suspended)

    def run(self, requests=None, *, max_ticks: int = 100_000) -> ServeReport:
        """Serve ``requests`` to completion and report.  Thin wrapper: the
        loop itself lives in the engine layer — this wraps the scheduler in
        an ``Engine`` view and drives ``Engine.step`` until idle, so every
        consumer (CLI, router, benchmarks, this method) runs the exact same
        loop."""
        from repro.serving.engine_api import Engine   # avoids import cycle
        return Engine.wrap(self).serve(requests, max_ticks=max_ticks)

    # -- admission ----------------------------------------------------------
    def _admit(self) -> None:
        """Place waiting work in (priority, arrival) order.

        Suspended (preempted) requests compete with the queue under the same
        key — preferred on ties, since their prefill is already paid.  Any
        number of resumes can happen per tick (no prefill involved); at most
        one NEW prefill starts, preserving the one-in-flight bound.  The
        head never skips: when the best candidate cannot be placed — even
        after the pool reclaimed cold prefix-cache blocks and, failing that,
        preemption swapped out strictly-lower-priority decodes — admission
        stops for this tick."""
        while True:
            cand = self._next_candidate()
            if cand is None:
                return
            kind, obj = cand
            if kind == "resume":
                prio = self._suspended[obj].flight.req.priority
                if self._try_resume(obj) or self._make_room(
                        prio, lambda: self._try_resume(obj)):
                    continue
                return
            if self._prefill is not None:
                return                       # one prefill in flight at a time
            if self._start_prefill(obj) or self._make_room(
                    obj.priority, lambda: self._start_prefill(obj)):
                return                       # one new prefill per tick
            return

    def _slack(self, req: Request, now: float) -> float:
        """Deadline headroom in ms: ``slo_ms`` minus the time already
        waited since arrival (+inf for deadline-free requests)."""
        if req.slo_ms is None:
            return float("inf")
        arrived = self._arrival_times.get(req.rid, now)
        return req.slo_ms - (now - arrived) * 1e3

    def _next_candidate(self):
        """Best waiting work item: ``("resume", rid)`` or ``("admit", req)``,
        ordered by (priority, deadline slack, arrival tick,
        resume-before-admit, FIFO).  Slack makes admission deadline-aware
        WITHIN a priority class: tighter deadlines place first, and a
        deadline-bearing request outranks deadline-free peers (slack +inf).
        With uniform ``slo_ms`` per class — every workload the generator
        produces — slack order equals arrival order, so the FIFO
        equivalence pins are untouched."""
        now = self.clock.monotonic()
        best = None
        for i, (rid, rec) in enumerate(self._suspended.items()):
            req = rec.flight.req
            key = (req.priority, self._slack(req, now), req.arrival_tick, 0, i)
            if best is None or key < best[0]:
                best = (key, ("resume", rid))
        for i, r in enumerate(self.queue):
            if r.arrival_tick > self.tick_count:
                continue
            key = (r.priority, self._slack(r, now), r.arrival_tick, 1, i)
            if best is None or key < best[0]:
                best = (key, ("admit", r))
        return best[1] if best else None

    def _start_prefill(self, req: Request) -> bool:
        """Claim capacity for ``req`` and set up its chunked prefill; False
        when the pool cannot place it (it stays queued)."""
        result = RequestResult(
            rid=req.rid, prompt_len=len(req.prompt), priority=req.priority,
            slo_ms=req.slo_ms, arrival_time=self._arrival_times[req.rid])
        if self.paged:
            # admission gates on free BLOCKS (after prefix matching and LRU
            # cache reclamation), not a whole worst-case-length slot
            seq = self.pool.admit(req.prompt)
            if seq is None:
                return False
            self.queue.remove(req)
            flight = _InFlight(req=req, result=result, slot=seq.slot,
                               remaining=req.max_new_tokens)
            if self.family.kind == "state":
                # single-shot into a batch-1 scratch cache, installed into
                # the sequence's state block at finish (the pool row is
                # donated to the decode jit, so prefill can't write it live)
                self._prefill = {
                    "flight": flight, "seq": seq,
                    "caches": engine.init_cache(self.cfg, 1,
                                                self.pool.slot_len),
                    "length": jnp.asarray(0, jnp.int32), "pos": 0,
                    "sizes": deque([len(req.prompt)]), "last": None,
                }
            elif self.family.kind == "encdec":
                # one shot: encode (or adopt the shared cross blocks) and
                # prime the decoder row at BOS — see _advance_encdec_prefill
                self._prefill = {
                    "flight": flight, "seq": seq, "caches": None,
                    "length": jnp.asarray(0, jnp.int32), "pos": 0,
                    "sizes": deque([len(req.prompt)]), "last": None,
                }
            else:
                self._prefill = {
                    "flight": flight,
                    "seq": seq,
                    "length": jnp.asarray(seq.matched, jnp.int32),
                    "pos": seq.matched,
                    # prefill resumes at the first unmatched token — shared
                    # prefix blocks already hold bit-identical cache content;
                    # single-shot families (quantized prefill never re-reads
                    # the stored prefix) get the whole remainder in one chunk
                    "sizes": deque(
                        [len(req.prompt) - seq.matched]
                        if self._single_shot_prefill
                        else engine.prefill_schedule(
                            len(req.prompt) - seq.matched,
                            self.prefill_chunk)),
                    "last": None,
                }
            self._admitted(self._prefill["flight"])
            return True
        if self.pool.free_slots == 0:
            return False
        self.queue.remove(req)
        self._prefill = {
            "flight": _InFlight(req=req, result=result,
                                remaining=req.max_new_tokens),
            "caches": engine.init_cache(self.cfg, 1, self.pool.slot_len),
            "length": jnp.asarray(0, jnp.int32),
            "pos": 0,
            # same schedule as chunked_prefill → same cache contents as a
            # solo prefill, and only O(log chunk) compiled tail widths
            "sizes": deque([len(req.prompt)] if self._single_shot_prefill
                           else engine.prefill_schedule(len(req.prompt),
                                                        self.prefill_chunk)),
            "last": None,
        }
        self._admitted(self._prefill["flight"])
        return True

    def _admitted(self, flight: _InFlight) -> None:
        """Queue wait ends here: stamp the phase split and flip the trace
        track from ``queued`` to ``prefill``."""
        flight.result.admitted_time = self.clock.monotonic()
        if self.tracer is not None:
            span = self._queued_spans.pop(flight.req.rid, None)
            if span is not None:
                self.tracer.end(span)
            flight.span = self.tracer.begin(
                "prefill", tid=self._tid(flight.req.rid), pid=self._pid,
                args={"prompt_len": flight.result.prompt_len})

    # -- preemption ---------------------------------------------------------
    def _make_room(self, priority: int, attempt) -> bool:
        """Swap out lower-priority victims one at a time, retrying
        ``attempt`` after each, until it succeeds, no victim remains, or a
        victim's swap freed no blocks while a row already sat free (blocks
        are then the binding constraint and further victims — whose pool
        residue is all shared — would be suspended for nothing).  Cold
        prefix-cache blocks were already reclaimed inside the pool —
        preempting live work is strictly the last resort."""
        if not (self.paged and self.preempt):
            return False                # SlotPool has no preemption (or off)
        while True:
            blocks_before = self.pool.free_blocks
            if not self._preempt_one(priority):
                return False
            if attempt():
                return True
            if (self.pool.free_slots > 0
                    and self.pool.free_blocks <= blocks_before):
                return False

    def _preempt_one(self, priority: int) -> bool:
        """Swap out ONE active decode strictly below ``priority``: the
        lowest-priority class first; within a class, prefer deadline-free
        victims, then the loosest deadline, then the longest remaining
        decode (the victim that frees capacity for the longest).  False
        when preemption is off, unpaged, or no strictly-lower-priority
        decode is running — equal-priority work is never preempted, so
        every class makes progress.  When no victim bears a deadline the
        key degenerates to the pre-deadline (priority, remaining, rid)
        order, so deadline-free workloads preempt exactly as before."""
        if not (self.paged and self.preempt) or not self.active:
            return False
        victims = [f for f in self.active.values()
                   if f.req.priority > priority]
        if not victims:
            return False
        now = self.clock.monotonic()
        victim = max(victims, key=lambda f: (f.req.priority,
                                             f.req.slo_ms is None,
                                             self._slack(f.req, now),
                                             f.remaining,
                                             f.req.rid))
        self._swap_out(victim)
        return True

    def _swap_out(self, flight: _InFlight) -> None:
        slot = flight.slot
        del self.active[slot]
        self.pool.swap_out(slot, flight.req.rid)
        self._suspended[flight.req.rid] = _Suspended(
            flight=flight, token=flight.result.tokens[-1])
        flight.slot = -1
        flight.result.preempted += 1
        self.preemptions += 1
        if self._metrics is not None:
            self._metrics["preemptions"].inc()
        if self.tracer is not None:
            self.tracer.instant(
                "preempt", tid=self._tid(flight.req.rid), pid=self._pid,
                args={"cause": "priority", "produced": flight.produced})
        self._begin_phase(flight, "suspended")

    def _prefetch_swap_in(self) -> None:
        """Stage the host-resident blocks of the suspended request most
        likely to resume next (same key order as ``_next_candidate``) onto
        the device while the current decode step is still in flight."""
        now = self.clock.monotonic()
        best = None
        for i, (rid, rec) in enumerate(self._suspended.items()):
            req = rec.flight.req
            key = (req.priority, self._slack(req, now), req.arrival_tick, i)
            if best is None or key < best[0]:
                best = (key, rid)
        if best is not None:
            self.pool.prefetch_swap_in(best[1])

    def _try_resume(self, rid: int) -> bool:
        """Reattach a suspended request: ``PagedPool.swap_in`` rebuilds its
        blocks/table/length, the last sampled token is re-fed, and decode
        continues — the (rid, token index) sample keys make the remaining
        stream bit-identical to the never-preempted run."""
        rec = self._suspended[rid]
        seq = self.pool.swap_in(rid)
        if seq is None:
            return False
        flight = rec.flight
        flight.slot = seq.slot
        self.tokens = self.tokens.at[seq.slot].set(rec.token)
        self.active[seq.slot] = flight
        del self._suspended[rid]
        self._begin_phase(flight, "decode", args={"resumed": True})
        return True

    # -- prefill ------------------------------------------------------------
    def _advance_prefill(self) -> None:
        if self._prefill is None:
            return
        # latency/occupancy tradeoff: one chunk per tick while the pool is
        # nearly full (bounded decode stall), proportionally more when slots
        # sit idle — idle slots cost more tokens than a longer stall — and
        # everything at once when nobody is waiting on decode
        budget = max(1, self.pool.free_slots) if self.active else 10 ** 9
        pf = self._prefill
        if self.paged and self.family.kind == "encdec":
            self._advance_encdec_prefill(pf)
            return
        prompt = pf["flight"].req.prompt
        while budget > 0 and pf["sizes"]:
            width = pf["sizes"].popleft()
            chunk = np.asarray(prompt[pf["pos"]:pf["pos"] + width])[None, :]
            with self._span("prefill_chunk",
                            tid=self._tid(pf["flight"].req.rid),
                            args={"pos": pf["pos"], "width": width}):
                if self.paged and self.family.kind == "token":
                    # chunks write straight into the shared pool through
                    # this sequence's block-table row — no batch-1 scratch
                    # cache, no insert copy at the end
                    pf["last"], self.pool.caches, pf["length"] = \
                        self._prefill_paged(
                            self.params, self.pool.caches,
                            self.pool.device_row(pf["flight"].slot),
                            pf["length"], jnp.asarray(chunk))
                else:
                    # unpaged slots AND paged fixed-state: single-sequence
                    # prefill into the scratch cache (state installs into
                    # its pool block at finish)
                    pf["last"], pf["caches"], pf["length"] = \
                        self._prefill_step(self.params, pf["caches"],
                                           pf["length"], jnp.asarray(chunk))
                pf["pos"] += width
                self.prefill_chunks += 1
                budget -= 1
        if pf["sizes"]:
            return
        self._finish_prefill()

    def _advance_encdec_prefill(self, pf: dict) -> None:
        """One-shot enc-dec prefill: a whole-audio prefix hit gathers the
        shared cross blocks and skips the encoder entirely; a miss encodes
        the frames.  Both paths prime the decoder row at BOS and produce
        bit-identical decoder caches for the same audio."""
        flight = pf["flight"]
        seq = pf["seq"]
        bos = jnp.full((1, 1), engine.ENCDEC_BOS, jnp.int32)
        with self._span("prefill_chunk", tid=self._tid(flight.req.rid),
                        args={"pos": 0, "width": len(flight.req.prompt),
                              "encoder_skipped": bool(seq.matched)}):
            if seq.matched:
                nc = self.pool.max_blocks - 1
                cross = self._encdec_gather_cross(
                    self.pool.caches, jnp.asarray(seq.blocks[:nc], jnp.int32))
                pf["last"], pf["caches"], pf["length"] = \
                    self._encdec_prefill_cached(self.params, cross, bos)
            else:
                frames = engine.encdec_frames_from_ids(flight.req.prompt,
                                                       self.cfg)
                pf["last"], pf["caches"], pf["length"] = \
                    self._encdec_prefill(self.params, frames, bos)
            pf["sizes"].clear()
            pf["pos"] = len(flight.req.prompt)
            self.prefill_chunks += 1
        self._finish_prefill()

    def _finish_prefill(self) -> None:
        pf = self._prefill
        self._prefill = None
        flight: _InFlight = pf["flight"]
        rid = flight.req.rid
        with self._span("first_token"):
            logits = self._logits(self.params, pf["last"])
            tok = self._sample(self._key(rid, 0)[None], logits)
            with self._span("first_token.wait"):
                # the prompt's length is ready with its last hidden state
                first, length = int(tok[0]), int(pf["length"])
            # the first sampled token closes the prefill phase: record it,
            # then flip the lifecycle track to decode
            self._record_token(flight, first)
            self._begin_phase(flight, "decode")
        if flight.remaining <= 0 or self._hit_eos(flight):
            self._finish(flight)
            return
        if self.paged:
            slot = flight.slot               # row claimed at admission
            if self.family.kind == "state":
                self.pool.install_state(pf["seq"], pf["caches"])
            elif self.family.kind == "encdec":
                self.pool.install_encdec(pf["seq"], pf["caches"])
            self.pool.finalize_prefill(pf["seq"])
            self.pool.lens = self.pool.lens.at[slot].set(length)
        else:
            slot = self.pool.acquire()
            assert slot is not None          # _admit gated on a free slot
            self.pool.insert(slot, pf["caches"], length)
            flight.slot = slot
        self.tokens = self.tokens.at[slot].set(first)
        self.active[slot] = flight

    # -- decode -------------------------------------------------------------
    def _decode_tick(self) -> None:
        """One decode step over every active row, in four sections (each a
        span under a tracer): ``decode.prepare`` backs each row's next write
        position, ``decode.dispatch`` launches the jitted step,
        ``decode.wait`` blocks on its tokens, ``decode.record`` books them.
        Every point where the host blocks on the device is a ``*wait``
        span."""
        if not self.active:
            return
        if self.paged:
            with self._span("decode.prepare"):
                self._prepare_decode_writes()
            if not self.active:
                return
        with self._span("decode.dispatch"):
            tok = self._dispatch_decode()
        with self._span("decode.wait"):
            tok_host = np.asarray(tok)
            lens_host = np.asarray(self.pool.lens)  # one sync, not per slot
        with self._span("decode.record"):
            for slot in list(self.active):
                flight = self.active[slot]
                self._record_token(flight, int(tok_host[slot]))
                slot_full = int(lens_host[slot]) >= self.pool.slot_len
                if (flight.remaining <= 0 or self._hit_eos(flight)
                        or slot_full):
                    flight.result.evicted = (slot_full
                                             and flight.remaining > 0
                                             and not self._hit_eos(flight))
                    self._finish(flight)

    def _prepare_decode_writes(self) -> None:
        """Make every active row's next write position backed by an
        exclusively-owned block (allocate across boundaries, CoW shared
        blocks).  A row the pool cannot back — even after reclaiming cold
        prefix-cache blocks inside prepare_write — first swaps out
        strictly-lower-priority decodes (they resume bit-identically); only
        with no such victim left is it evicted, returning its non-shared
        blocks to the free list in this same tick."""
        with self._span("decode.lens_wait"):
            lens_pre = np.asarray(self.pool.lens)
        for slot in list(self.active):
            flight = self.active.get(slot)
            if flight is None:          # swapped out as a victim above
                continue
            ok = self.pool.prepare_write(slot, int(lens_pre[slot]))
            while not ok:
                blocks_before = self.pool.free_blocks
                if not self._preempt_one(flight.req.priority):
                    break
                ok = self.pool.prepare_write(slot, int(lens_pre[slot]))
                if not ok and self.pool.free_blocks <= blocks_before:
                    break               # victim freed nothing usable
            if not ok:
                flight.result.evicted = True
                self._finish(flight)

    def _dispatch_decode(self):
        """Launch the family's jitted decode step over the whole pool and
        advance the device-side lengths; returns the sampled tokens without
        waiting for them."""
        rids = np.full((self.pool.num_slots,), -1, np.int32)   # -1: idle slot
        produced = np.zeros((self.pool.num_slots,), np.int32)  # (sample dropped)
        active_mask = np.zeros((self.pool.num_slots,), bool)
        for s, flight in self.active.items():
            rids[s] = flight.req.rid
            produced[s] = flight.produced
            active_mask[s] = True
        if self.paged and self.family.kind == "state":
            # each active slot decodes in its own state row; inactive slots
            # gather the sentinel row and their writes drop
            rows = np.zeros((self.pool.num_slots,), np.int32)
            for s in self.active:
                rows[s] = self.pool.seqs[s].blocks[0]
            tok, self.pool.caches, new_lens = self._decode_state(
                self.params, self.pool.caches, jnp.asarray(rows),
                jnp.asarray(active_mask), self.pool.lens,
                self.tokens[:, None], jnp.asarray(rids),
                jnp.asarray(produced), self.base_rng)
        elif self.paged and self.family.kind == "encdec":
            # table row = [cross blocks..., self row]; cross is immutable so
            # only the self rows scatter back
            tables = self.pool.device_tables(self.active.keys())
            nc = self.pool.max_blocks - 1
            tok, self.pool.caches, new_lens = self._decode_encdec(
                self.params, self.pool.caches, tables[:, :nc], tables[:, nc],
                jnp.asarray(active_mask), self.pool.lens,
                self.tokens[:, None], jnp.asarray(rids),
                jnp.asarray(produced), self.base_rng)
        elif self.paged:
            # non-active rows (idle OR mid-prefill) are masked to the
            # sentinel table row: their lens-0 garbage write must land in
            # block 0, never in a live block a prefill already filled
            tok, self.pool.caches, new_lens = self._decode_paged(
                self.params, self.pool.caches,
                self.pool.device_tables(self.active.keys()),
                self.pool.lens, self.tokens[:, None], jnp.asarray(rids),
                jnp.asarray(produced), self.base_rng)
        else:
            tok, self.pool.caches, new_lens = self._decode(
                self.params, self.pool.caches, self.pool.lens,
                self.tokens[:, None], jnp.asarray(rids),
                jnp.asarray(produced), self.base_rng)
        # idle slots don't age: their garbage write lands at 0 and is fully
        # overwritten by the next insert
        self.pool.lens = jnp.where(jnp.asarray(active_mask), new_lens, 0)
        self.tokens = tok
        self.decode_steps += 1
        self._occupancy_sum += len(self.active) / self.pool.num_slots
        if self._metrics is not None:
            self._metrics["occupancy"].observe(
                len(self.active) / self.pool.num_slots)
        if self.paged and self._suspended:
            # Overlap host→device swap-in staging with the decode step just
            # dispatched above: JAX queues the transfers asynchronously, so
            # they run while decode.wait blocks on the tokens.  Bit-exact —
            # swap_in consumes the staged device copies of the same payloads.
            self._prefetch_swap_in()
        return tok

    # -- bookkeeping --------------------------------------------------------
    def _record_token(self, flight: _InFlight, token: int) -> None:
        now = self.clock.monotonic()
        result = flight.result
        result.tokens.append(token)
        if flight.produced == 0:
            result.first_token_time = now
            result.record_latency(now - result.arrival_time)
        else:
            result.record_latency(now - flight.last_token_time)
        flight.last_token_time = now
        if self._metrics is not None:
            self._metrics["tokens"].inc()
        if self.tracer is not None:
            self.tracer.instant(
                "token", tid=self._tid(flight.req.rid), pid=self._pid,
                args={"i": flight.produced, "token": token})
        flight.produced += 1
        flight.remaining -= 1

    def _hit_eos(self, flight: _InFlight) -> bool:
        return (self.eos_id is not None and flight.result.tokens
                and flight.result.tokens[-1] == self.eos_id)

    def _finish(self, flight: _InFlight) -> None:
        flight.result.finish_time = self.clock.monotonic()
        self._end_phase(flight)
        if self.tracer is not None:
            cause = ("evicted" if flight.result.evicted
                     else "eos" if self._hit_eos(flight) and flight.remaining > 0
                     else "completed")
            self.tracer.instant(
                "retire", tid=self._tid(flight.req.rid), pid=self._pid,
                args={"cause": cause, "tokens": len(flight.result.tokens)})
        self.finished.append(flight.result)
        if flight.slot >= 0:
            # paged flights own their row (and blocks) from admission, so a
            # request retired straight out of prefill is not in `active` yet
            self.active.pop(flight.slot, None)
            self.pool.release(flight.slot)


# ---------------------------------------------------------------------------
# Synthetic workloads.
# ---------------------------------------------------------------------------
def poisson_workload(n_requests: int, *, rate_per_tick: float,
                     prompt_lens=(8, 32), decode_lens=(4, 32),
                     vocab: int = 1000, seed: int = 0,
                     shared_prefix: int = 0, priority_classes: int = 1,
                     slo_ms: Optional[float] = None) -> list:
    """Staggered synthetic requests: Poisson arrivals (exponential
    inter-arrival gaps in scheduler ticks), uniform prompt/decode lengths.

    ``shared_prefix > 0`` prepends the same random prefix to every prompt —
    the system-prompt pattern paged serving's prefix index deduplicates.
    ``priority_classes > 1`` assigns each request a uniform-random priority
    in [0, classes) — the mixed-priority workload the SLO scheduling is
    benchmarked on — and ``slo_ms`` attaches a completion deadline to the
    urgent class (priority 0), whose attainment the serve report scores."""
    rng = np.random.default_rng(seed)
    prefix = (rng.integers(0, vocab, shared_prefix) if shared_prefix
              else None)
    t = 0.0
    out = []
    for rid in range(n_requests):
        t += rng.exponential(1.0 / max(rate_per_tick, 1e-9))
        body = rng.integers(0, vocab, rng.integers(prompt_lens[0],
                                                   prompt_lens[1] + 1))
        priority = (int(rng.integers(0, priority_classes))
                    if priority_classes > 1 else 0)
        out.append(Request(
            rid=rid,
            prompt=body if prefix is None else np.concatenate([prefix, body]),
            max_new_tokens=int(rng.integers(decode_lens[0],
                                            decode_lens[1] + 1)),
            arrival_tick=int(t), priority=priority,
            slo_ms=slo_ms if (slo_ms and priority == 0) else None))
    return out
