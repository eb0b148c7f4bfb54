"""Capability-probing kernel dispatch registry.

One registry maps each op (online-softmax, softmax+top-k, attention) to its
implementations by execution path:

* ``pallas``           — Pallas kernel compiled natively (TPU Mosaic)
* ``pallas-interpret`` — same kernel body, Pallas interpret mode (faithful
                         execution on backends without native lowering)
* ``xla``              — the semantically-identical XLA form from
                         ``repro.core`` (chunked/online; production CPU path)
* ``xla-naive``        — materializing reference (oracle; small shapes only)

Path selection happens once per (op, preference) pair: the first call probes
``repro.compat.capabilities()`` and the choice is cached for the process.
Model code states *preferences* (``cfg.use_pallas``, ``cfg.use_online_attention``)
and the registry resolves them against what the backend can actually do, so
a config asking for Pallas on a CPU host degrades to interpret mode instead
of crashing — the portability counterpart of the compat import shims.

Block sizes are not hard-coded either: ``block_decision`` runs a lightweight
autotune sweep over the ⊕-tree shape (``online_normalizer_blocked``'s
``block`` knob — §3.1 of the paper: any reduction tree gives the same
``(m, d)``, so the sweep is free to pick the fastest; a candidate displaces
the registry default only when it is clearly faster, see ``_pick``) and
caches the winner per (backend, vocab, dtype).  The second call for the
same key is a pure cache hit.  Decisions persist to an on-disk JSON cache
inside the checkout (``repro.cache_dirs.AUTOTUNE_CACHE_PATH``; overridable via
``REPRO_AUTOTUNE_CACHE``, set it empty to disable) loaded at import, so a
serving restart skips the sweep entirely.

Attention tile shapes go through the same seam: ``attention_tiles`` resolves
``bq``/``bk`` for the Pallas flash kernels — decode ``bk`` AND the prefill
forms (fresh, offset, paged ``bq``) are swept on native backends — so
``kernels/ops.py`` carries no hard-coded 512s.

Paged KV serving adds ``paged_attention`` / ``paged_decode_attention``:
block-pool K/V addressed through a ``[B, max_blocks]`` block table
(``sdpa(block_tables=...)`` routes them).  The Pallas paths gather pages in
kernel index maps; the XLA fallback gathers the table into a contiguous
cache and reuses the chunked online form.  Quantized (int8) pools carry
``k_scale``/``v_scale`` pages beside K/V; both paged paths dequantize
AFTER the gather — scale pages ride the same block table and the same
clamped page index, so the pool lifecycle never sees fp data.

Each public op (``online_softmax``, ``softmax_topk``, ``sdpa``) runs under
a ``jax.named_scope`` named ``kernel.<op>`` — for ``sdpa`` by route:
``kernel.paged_decode``, ``kernel.paged_prefill``, ``kernel.decode``,
``kernel.attention`` — whichever implementation it picks, so a device
trace's name stacks attribute op time to the kernel entry that issued it.

Reduced-precision softmax forms (PAPERS.md 2201.04562 / 2111.10770) are
registry ops too — ``online_softmax_bf16`` (bf16 normalizer accumulator)
and ``online_softmax_exp2`` (exp2-based exponentials) — selected by a
process preference (``set_softmax_form`` / ``REPRO_SOFTMAX_FORM``); their
analytic error bounds live in ``repro.core.softmax_forms`` and
``tests/test_numerics.py`` pins every form inside them.
"""
from __future__ import annotations

import concurrent.futures
import functools
import json
import os
import statistics
from dataclasses import asdict, dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from repro import cache_dirs, compat
from repro import core
from repro.obs import clock as obs_clock
from repro.obs import kernels as obs_kernels

Array = jax.Array

PATH_PALLAS = "pallas"
PATH_PALLAS_INTERPRET = "pallas-interpret"
PATH_XLA = "xla"
PATH_XLA_NAIVE = "xla-naive"

_REGISTRY: dict[str, dict[str, Callable]] = {}


def register(op: str, *paths: str):
    """Decorator: register ``fn`` as the implementation of ``op`` on ``paths``."""
    def deco(fn: Callable) -> Callable:
        for path in paths:
            _REGISTRY.setdefault(op, {})[path] = fn
        return fn
    return deco


def available(op: str) -> tuple[str, ...]:
    return tuple(_REGISTRY.get(op, ()))


def implementation(op: str, path: str) -> Callable:
    """The registered implementation of ``op`` on ``path``, bypassing
    selection (to compare two paths on one backend)."""
    return _REGISTRY[op][path]


@functools.lru_cache(maxsize=None)
def select_path(op: str, prefer_pallas: bool = False) -> str:
    """Resolve the execution path for ``op`` on the probed backend (cached).

    Policy: native Pallas wins wherever it exists; a Pallas *preference* on a
    backend without native lowering resolves to interpret mode (kernel-body
    fidelity over speed — what the kernel test suite pins); otherwise the XLA
    form is the production path.
    """
    table = _REGISTRY[op]
    caps = compat.capabilities()
    if caps.pallas_native and PATH_PALLAS in table:
        return PATH_PALLAS
    if prefer_pallas and PATH_PALLAS_INTERPRET in table:
        return PATH_PALLAS_INTERPRET
    if PATH_XLA in table:
        return PATH_XLA
    return next(iter(table))


def lookup(op: str, prefer_pallas: bool = False) -> tuple[str, Callable]:
    path = select_path(op, prefer_pallas)
    # dict bookkeeping only (lookup runs at trace time, not per token):
    # repro.obs surfaces which path each op actually resolved to
    obs_kernels.record_path(op, path, prefer_pallas=prefer_pallas)
    return path, _REGISTRY[op][path]


# ---------------------------------------------------------------------------
# Block-size autotune: per-(backend, vocab, dtype), ⊕-tree-shape sweep.
# ---------------------------------------------------------------------------
BLOCK_CANDIDATES = (256, 512, 1024, 2048, 4096)
BLOCK_DEFAULT = 2048     # the incumbent vocab block (clamped to the vocab)
_TUNE_ROWS = 4           # sample batch height: enough to engage vectorization
_TUNE_REPS = 7           # timed calls per candidate, round-robin
_TUNE_MARGIN = 0.10      # share by which a challenger must beat the incumbent

_BLOCK_CACHE: dict[tuple[str, int, str], "BlockDecision"] = {}
_TILE_CACHE: dict[tuple, "TileDecision"] = {}
_SWEEPS = 0              # number of real sweeps run (tests assert cache hits)

# Attention tile registry defaults (the former hard-coded ops.py values).
# On native Pallas backends every entry is swept: decode ``bk``, prefill
# ``bq``/``bk`` for the fresh and offset forms, and ``bq`` for the paged
# form (its KV tile is pinned to the pool block size).  Off-TPU these
# defaults stand in — an interpret-mode timing would only rank Python
# overhead.
ATTN_TILE_DEFAULTS = {
    "flash_attention": {"bq": 512, "bk": 512},
    "flash_attention_offset": {"bq": 512, "bk": 512},
    "flash_attention_paged": {"bq": 512},
    "flash_decode": {"bk": 512},
}
DECODE_BK_CANDIDATES = (128, 256, 512, 1024)
PREFILL_TILE_CANDIDATES = (256, 512, 1024)
_PAGED_TUNE_BLOCK = 128      # synthetic pool page size for the paged sweep


@dataclass(frozen=True)
class BlockDecision:
    backend: str
    vocab: int
    dtype: str
    block: int                       # winning ⊕-tree leaf width
    timings_us: tuple                # ((candidate, median_us), ...)

    def to_dict(self) -> dict:
        return asdict(self)


@dataclass(frozen=True)
class TileDecision:
    op: str                          # "flash_attention" | "flash_decode"
    backend: str
    kv_len: int
    head_dim: int
    dtype: str
    tiles: dict                      # resolved {"bq": ..} / {"bk": ..}
    timings_us: tuple                # ((candidate, median_us), ...) or ()

    def to_dict(self) -> dict:
        return asdict(self)


# ---------------------------------------------------------------------------
# On-disk persistence: decisions survive the process so serving restarts skip
# the sweep.  Best-effort — an unwritable/corrupt cache never breaks dispatch.
# ---------------------------------------------------------------------------
AUTOTUNE_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
CACHE_SCHEMA_VERSION = 2       # stamped into every payload; mismatch → re-sweep
_DEFAULT_CACHE_PATH = cache_dirs.AUTOTUNE_CACHE_PATH
_CACHE_WARNED = False          # warn once per process, then stay quiet


def autotune_cache_path() -> str | None:
    """Resolved cache file path; ``REPRO_AUTOTUNE_CACHE=`` (empty) disables."""
    p = os.environ.get(AUTOTUNE_CACHE_ENV)
    if p is not None:
        return p or None
    return _DEFAULT_CACHE_PATH


def _warn_cache_once(path: str, why: str) -> None:
    global _CACHE_WARNED
    if _CACHE_WARNED:
        return
    _CACHE_WARNED = True
    import warnings
    warnings.warn(f"ignoring autotune cache {path!r} ({why}); decisions will "
                  "be re-swept and the file rewritten", stacklevel=3)


def _read_cache_payload(path: str) -> dict | None:
    """Parse + schema-check one cache file; None (with a one-time warning)
    on anything unusable.  This runs at import, so it must never raise."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        _warn_cache_once(path, f"unreadable: {e}")
        return None
    if not isinstance(data, dict):
        _warn_cache_once(path, f"top-level {type(data).__name__}, not an "
                               "object")
        return None
    if data.get("version") != CACHE_SCHEMA_VERSION:
        _warn_cache_once(path, f"schema version {data.get('version')!r} != "
                               f"{CACHE_SCHEMA_VERSION}")
        return None
    if not isinstance(data.get("blocks", []), list) \
            or not isinstance(data.get("tiles", []), list):
        _warn_cache_once(path, "blocks/tiles are not lists")
        return None
    return data


def load_persisted_decisions(path: str | None = None) -> int:
    """Merge on-disk decisions into the in-process caches (existing in-memory
    entries win).  Returns the number of entries loaded.  A corrupt or
    schema-mismatched file warns once and loads nothing — the sweeps run
    again and the next save rewrites the file with the current schema."""
    path = path if path is not None else autotune_cache_path()
    if not path or not os.path.exists(path):
        return 0
    data = _read_cache_payload(path)
    if data is None:
        return 0
    n = 0
    for d in data.get("blocks", ()):
        try:
            dec = BlockDecision(
                backend=str(d["backend"]), vocab=int(d["vocab"]),
                dtype=str(d["dtype"]), block=int(d["block"]),
                timings_us=tuple(tuple(t) for t in d["timings_us"]))
        except (KeyError, TypeError, ValueError):
            continue
        key = (dec.backend, dec.vocab, dec.dtype)
        if key not in _BLOCK_CACHE:
            _BLOCK_CACHE[key] = dec
            n += 1
    for d in data.get("tiles", ()):
        try:
            dec = TileDecision(
                op=str(d["op"]), backend=str(d["backend"]),
                kv_len=int(d["kv_len"]), head_dim=int(d["head_dim"]),
                dtype=str(d["dtype"]), tiles=dict(d["tiles"]),
                timings_us=tuple(tuple(t) for t in d["timings_us"]))
        except (KeyError, TypeError, ValueError):
            continue
        key = (dec.op, dec.backend, dec.kv_len, dec.head_dim, dec.dtype)
        if key not in _TILE_CACHE:
            _TILE_CACHE[key] = dec
            n += 1
    return n


def save_persisted_decisions(path: str | None = None) -> bool:
    """Write the merged (disk ∪ memory, memory wins) decision set to disk.
    A corrupt or schema-mismatched existing file contributes nothing to the
    merge and is simply overwritten with the current schema."""
    path = path if path is not None else autotune_cache_path()
    if not path:
        return False
    merged_blocks: dict[tuple, dict] = {}
    merged_tiles: dict[tuple, dict] = {}
    old = _read_cache_payload(path) if os.path.exists(path) else None
    if old is not None:
        try:
            for d in old.get("blocks", ()):
                merged_blocks[(d["backend"], int(d["vocab"]), d["dtype"])] = d
            for d in old.get("tiles", ()):
                merged_tiles[(d["op"], d["backend"], int(d["kv_len"]),
                              int(d["head_dim"]), d["dtype"])] = d
        except (KeyError, TypeError, ValueError):
            pass
    for key, dec in _BLOCK_CACHE.items():
        merged_blocks[key] = dec.to_dict()
    for key, dec in _TILE_CACHE.items():
        merged_tiles[key] = dec.to_dict()
    payload = {"version": CACHE_SCHEMA_VERSION,
               "blocks": list(merged_blocks.values()),
               "tiles": list(merged_tiles.values())}
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, path)
        return True
    except OSError:
        return False


def _outside_trace(fn: Callable):
    """Run ``fn`` with no ambient JAX trace and return its result.

    A sweep may fire while an outer ``jax.jit`` is tracing (the serving
    step jits decode).  Its candidates must execute for real — traced, they
    would time per-candidate tracing overhead and ``block_until_ready``
    would no-op.  JAX's trace state is per thread, so a fresh thread runs
    them concretely.  (``jax.ensure_compile_time_eval`` does not do: a
    Pallas kernel cannot be traced under it.)"""
    with concurrent.futures.ThreadPoolExecutor(max_workers=1) as pool:
        return pool.submit(fn).result()


def _timed_us(runners: dict) -> tuple:
    """Median µs of each zero-argument runner over ``_TUNE_REPS`` calls,
    after one warm-up call each (compile).  Calls go round-robin across the
    candidates, so drift in the host's load hits every candidate alike."""
    for run in runners.values():
        run()
    samples = {c: [] for c in runners}
    for _ in range(_TUNE_REPS):
        for c, run in runners.items():
            t0 = obs_clock.perf_counter()
            run()
            samples[c].append(obs_clock.perf_counter() - t0)
    return tuple((c, round(statistics.median(t) * 1e6, 2))
                 for c, t in samples.items())


def _pick(timings: tuple, incumbent):
    """The fastest candidate, unless the incumbent's median is within
    ``_TUNE_MARGIN`` of it.  Host-clock timings of these short calls spread
    by about that much between runs (a TPU v5e's vocab-block sweep ranked
    its candidates differently in each of three runs), and a pick made by
    noise would differ between restarts and between checkouts."""
    best, best_us = min(timings, key=lambda t: t[1])
    incumbent_us = dict(timings).get(incumbent)
    if incumbent_us is not None \
            and best_us > incumbent_us * (1 - _TUNE_MARGIN):
        return incumbent
    return best


def _blocked_runner(x: Array, block: int) -> Callable:
    if compat.pallas_native():
        # time the thing being configured: the Pallas kernel at this tile
        # width (an XLA-scan proxy would not rank Mosaic VMEM tiles)
        from repro.kernels import ops
        fn = jax.jit(functools.partial(ops.online_normalizer, v_blk=block))
    else:
        fn = jax.jit(functools.partial(core.online_normalizer_blocked,
                                       block=block))
    return lambda: jax.block_until_ready(fn(x))


def block_decision(vocab: int, dtype=jnp.float32) -> BlockDecision:
    """Winning vocab-axis block for this (backend, vocab, dtype) — cached."""
    vocab = int(vocab)
    key = (compat.backend(), vocab, jnp.dtype(dtype).name)
    hit = _BLOCK_CACHE.get(key)
    if hit is not None:
        obs_kernels.record_autotune("block", key, hit.to_dict())
        return hit
    global _SWEEPS
    _SWEEPS += 1

    def sweep():
        cands = sorted({min(b, vocab) for b in BLOCK_CANDIDATES})
        x = (jnp.arange(_TUNE_ROWS * vocab, dtype=jnp.float32) % 251.0
             ).reshape(_TUNE_ROWS, vocab).astype(dtype)
        return _timed_us({b: _blocked_runner(x, b) for b in cands})

    timings = _outside_trace(sweep)
    winner = _pick(timings, min(BLOCK_DEFAULT, vocab))
    decision = BlockDecision(backend=key[0], vocab=vocab, dtype=key[2],
                             block=winner, timings_us=timings)
    _BLOCK_CACHE[key] = decision
    obs_kernels.record_autotune("block", key, decision.to_dict())
    save_persisted_decisions()
    return decision


def tuned_block(vocab: int, dtype=jnp.float32) -> int:
    return block_decision(vocab, dtype).block


def _decode_runner(kv_len: int, head_dim: int, dtype, bk: int) -> Callable:
    from repro.kernels import ops
    q = jnp.ones((_TUNE_ROWS, 8, head_dim), dtype)
    kc = jnp.ones((_TUNE_ROWS, kv_len, 8, head_dim), dtype)
    vlen = jnp.full((_TUNE_ROWS,), kv_len, jnp.int32)
    fn = jax.jit(functools.partial(ops.flash_decode, bk=bk))
    return lambda: jax.block_until_ready(fn(q, kc, kc, vlen))


def _prefill_runner(op: str, kv_len: int, head_dim: int, dtype,
                    bq: int, bk: int) -> Callable:
    """One (bq, bk) candidate of a prefill-form flash kernel, ready to time.

    ``flash_attention`` times the fresh self-attention form;
    ``flash_attention_offset`` the cached-chunk form (queries offset halfway
    into the cache); ``flash_attention_paged`` a synthetic block pool of
    ``_PAGED_TUNE_BLOCK``-wide pages (bk is the page size there — only bq is
    a free knob)."""
    from repro.kernels import ops
    hq, hkv = 8, 8
    if op == "flash_attention_paged":
        bs = _PAGED_TUNE_BLOCK
        m = max(kv_len // bs, 1)
        tq = max(min(bq, kv_len), 1)
        q = jnp.ones((_TUNE_ROWS, tq, hq, head_dim), dtype)
        pool = jnp.ones((_TUNE_ROWS * m + 1, hkv, bs, head_dim), dtype)
        tables = (jnp.arange(_TUNE_ROWS * m, dtype=jnp.int32)
                  .reshape(_TUNE_ROWS, m) + 1)
        qoff = jnp.full((_TUNE_ROWS,), (m - 1) * bs, jnp.int32)
        vlen = jnp.full((_TUNE_ROWS,), m * bs, jnp.int32)
        fn = jax.jit(functools.partial(ops.paged_flash_attention, bq=bq))
        args = (q, pool, pool, qoff, vlen, tables)
    elif op == "flash_attention_offset":
        tq = max(kv_len // 2, 1)
        q = jnp.ones((_TUNE_ROWS, tq, hq, head_dim), dtype)
        kv = jnp.ones((_TUNE_ROWS, kv_len, hkv, head_dim), dtype)
        qoff = jnp.full((_TUNE_ROWS,), kv_len - tq, jnp.int32)
        vlen = jnp.full((_TUNE_ROWS,), kv_len, jnp.int32)
        fn = jax.jit(functools.partial(ops.flash_attention, bq=bq, bk=bk))
        args = (q, kv, kv)
        fn = functools.partial(fn, q_offset=qoff, kv_valid_len=vlen)
    else:
        q = jnp.ones((_TUNE_ROWS, kv_len, hq, head_dim), dtype)
        kv = jnp.ones((_TUNE_ROWS, kv_len, hkv, head_dim), dtype)
        fn = jax.jit(functools.partial(ops.flash_attention, bq=bq, bk=bk))
        args = (q, kv, kv)
    return lambda: jax.block_until_ready(fn(*args))


_PREFILL_TILE_OPS = ("flash_attention", "flash_attention_offset",
                     "flash_attention_paged")


def attention_tiles(op: str, *, kv_len: int, head_dim: int,
                    dtype=jnp.float32) -> dict:
    """Resolved attention tile sizes for ``op`` — the one seam for bq/bk.

    On backends with native Pallas lowering every form is swept per
    (backend, kv_len, head_dim, dtype): decode ``bk``, prefill ``bq``/``bk``
    for the fresh and offset forms, and ``bq`` for the paged form (whose KV
    tile is the pool block size).  Elsewhere the registry defaults apply (a
    meaningless interpret-mode timing would just rank Python overhead).
    Decisions are cached in-process and persisted alongside the vocab-block
    decisions in the version-stamped ``REPRO_AUTOTUNE_CACHE``.
    """
    kv_len, head_dim = int(kv_len), int(head_dim)
    key = (op, compat.backend(), kv_len, head_dim, jnp.dtype(dtype).name)
    hit = _TILE_CACHE.get(key)
    if hit is not None:
        obs_kernels.record_autotune("tiles", key, hit.to_dict())
        return dict(hit.tiles)
    defaults = dict(ATTN_TILE_DEFAULTS[op])
    global _SWEEPS
    if op == "flash_decode" and compat.pallas_native():
        _SWEEPS += 1
        cands = sorted({min(b, kv_len) for b in DECODE_BK_CANDIDATES
                        if kv_len % min(b, kv_len) == 0})
        timings = _outside_trace(lambda: _timed_us(
            {b: _decode_runner(kv_len, head_dim, dtype, b) for b in cands}))
        defaults["bk"] = _pick(timings, min(defaults["bk"], kv_len))
    elif op in _PREFILL_TILE_OPS and compat.pallas_native():
        _SWEEPS += 1
        bqs = sorted({min(c, kv_len) for c in PREFILL_TILE_CANDIDATES})
        if op == "flash_attention_paged":       # bk pinned to the page size
            cands = [(bq, 0) for bq in bqs]
        else:
            bks = sorted({min(c, kv_len) for c in PREFILL_TILE_CANDIDATES
                          if kv_len % min(c, kv_len) == 0})
            cands = [(bq, bk) for bq in bqs for bk in bks]
        timings = _outside_trace(lambda: _timed_us(
            {(bq, bk): _prefill_runner(op, kv_len, head_dim, dtype, bq, bk)
             for bq, bk in cands}))
        incumbent = (min(defaults["bq"], kv_len),
                     min(defaults.get("bk", 0), kv_len))
        best_bq, best_bk = _pick(timings, incumbent)
        defaults["bq"] = best_bq
        if "bk" in defaults:
            defaults["bk"] = best_bk
    else:
        timings = ()
    decision = TileDecision(op=op, backend=key[1], kv_len=kv_len,
                            head_dim=head_dim, dtype=key[4],
                            tiles=defaults, timings_us=timings)
    _TILE_CACHE[key] = decision
    obs_kernels.record_autotune("tiles", key, decision.to_dict())
    if timings:                      # defaults-only decisions aren't worth IO
        save_persisted_decisions()
    return dict(decision.tiles)


def autotune_stats() -> dict:
    return {"sweeps": _SWEEPS, "entries": len(_BLOCK_CACHE)}


def tile_stats() -> dict:
    return {"entries": len(_TILE_CACHE)}


def reset_autotune_cache() -> None:
    """Clear the in-process decision caches (the on-disk cache is untouched;
    it is only consulted at import via ``load_persisted_decisions``)."""
    global _SWEEPS, _CACHE_WARNED
    _BLOCK_CACHE.clear()
    _TILE_CACHE.clear()
    _SWEEPS = 0
    _CACHE_WARNED = False


# ---------------------------------------------------------------------------
# Registered implementations.  Pallas entries import lazily so the registry
# stays importable on hosts where jax.experimental.pallas cannot load.
# ---------------------------------------------------------------------------
@register("online_softmax", PATH_PALLAS, PATH_PALLAS_INTERPRET)
def _online_softmax_pallas(x: Array) -> Array:
    from repro.kernels import ops
    return ops.online_softmax(x)               # v_blk unset → tuned_block


@register("online_softmax", PATH_XLA)
def _online_softmax_xla(x: Array) -> Array:
    return core.online_softmax(x)


# Reduced-precision forms: same online (m, d) recurrence, cheaper arithmetic.
# XLA-only for now — the paper's associativity argument makes them drop-in
# for the kernels once a native backend wants them; the analytic bounds in
# core.softmax_forms (pinned by tests/test_numerics.py) are the gate.
@register("online_softmax_bf16", PATH_XLA)
def _online_softmax_bf16(x: Array) -> Array:
    from repro.core import softmax_forms
    return softmax_forms.softmax_bf16(x)


@register("online_softmax_exp2", PATH_XLA)
def _online_softmax_exp2(x: Array) -> Array:
    from repro.core import softmax_forms
    return softmax_forms.softmax_exp2(x)


@register("softmax_topk", PATH_PALLAS, PATH_PALLAS_INTERPRET)
def _softmax_topk_pallas(x: Array, k: int) -> "core.SoftmaxTopK":
    from repro.kernels import ops
    vals, idx, lse = ops.softmax_topk(x, k)    # v_blk unset → tuned_block
    return core.SoftmaxTopK(vals, idx, lse)


@register("softmax_topk", PATH_XLA)
def _softmax_topk_xla(x: Array, k: int,
                      block: int | None = None) -> "core.SoftmaxTopK":
    return core.softmax_topk(x, k, block=block)


@register("attention", PATH_PALLAS, PATH_PALLAS_INTERPRET)
def _attention_pallas(cfg, q, k, v, *, causal, q_offset, kv_valid_len, scale):
    from repro.kernels import ops
    if kv_valid_len is None and isinstance(q_offset, int) and q_offset == 0:
        # fresh (train / no-cache) self-attention: the differentiable form
        return ops.flash_attention(q, k, v, causal=causal)
    # cached (chunked) prefill: queries offset into a partially-valid cache —
    # absolute-coordinate causal masking + per-row valid-length masking on
    # the kernel (inference-only)
    return ops.flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                               kv_valid_len=kv_valid_len)


@register("attention", PATH_XLA)
def _attention_xla(cfg, q, k, v, *, causal, q_offset, kv_valid_len, scale):
    return core.online_attention(q, k, v, causal=causal, q_offset=q_offset,
                                 kv_valid_len=kv_valid_len,
                                 chunk_size=cfg.attn_chunk, scale=scale,
                                 causal_blocks=cfg.attn_causal_blocks)


@register("attention", PATH_XLA_NAIVE)
def _attention_naive(cfg, q, k, v, *, causal, q_offset, kv_valid_len, scale):
    return core.naive_attention(q, k, v, causal=causal, q_offset=q_offset,
                                kv_valid_len=kv_valid_len, scale=scale)


@register("decode_attention", PATH_PALLAS)
def _decode_attention_pallas(cfg, q, k, v, *, q_offset, kv_valid_len, scale):
    """Single-token decode on the Pallas streaming kernel.  ``kv_valid_len``
    [B] is the per-slot length vector — each cache slot masks its own tail,
    which is what lets continuous batching mix ragged sequences in one call.
    The kernel bakes in the default 1/sqrt(d) scale; a custom scale (MLA)
    falls back to the chunked XLA form."""
    if scale is not None and scale != q.shape[-1] ** -0.5:
        return _decode_attention_xla(cfg, q, k, v, q_offset=q_offset,
                                     kv_valid_len=kv_valid_len, scale=scale)
    from repro.kernels import ops
    return ops.flash_decode(q[:, 0], k, v, kv_valid_len)[:, None]


@register("decode_attention", PATH_XLA)
def _decode_attention_xla(cfg, q, k, v, *, q_offset, kv_valid_len, scale):
    return core.online_attention(q, k, v, causal=False, q_offset=q_offset,
                                 kv_valid_len=kv_valid_len,
                                 chunk_size=cfg.attn_chunk, scale=scale)


# ---------------------------------------------------------------------------
# Paged KV cache: block-pool + block-table attention.  The pools are
# [P, Hkv, BS, D]; ``block_tables`` [B, M] maps each row's logical blocks to
# physical pool blocks.  Pallas paths gather pages in the kernel's index
# maps; the XLA fallback gathers the table into a contiguous [B, M·BS] cache
# and runs the chunked online form — bit-identical to the contiguous
# slot-pool path for every valid position (masked columns update (m, d)
# exactly), which is what the paged serving equivalence tests pin.
# ---------------------------------------------------------------------------
def _gather_pages(pool: Array, block_tables: Array) -> Array:
    """[P, Hkv, BS, D] + [B, M] → contiguous [B, M·BS, Hkv, D] (model layout).

    Positions past a row's ``kv_valid_len`` gather stale or sentinel blocks —
    finite garbage the attention mask erases exactly."""
    g = pool[block_tables]                      # [B, M, Hkv, BS, D]
    g = jnp.swapaxes(g, 2, 3)                   # [B, M, BS, Hkv, D]
    return g.reshape(block_tables.shape[0], -1, pool.shape[1], pool.shape[3])


def _gather_scale_pages(pool: Array, block_tables: Array) -> Array:
    """Scale pages [P, Hkv, BS] + [B, M] → contiguous [B, M·BS, Hkv] — the
    ``k_scale``/``v_scale`` layout ``_chunked_fwd_impl`` dequantizes with.
    Same table, same ordering as ``_gather_pages``, so position i's scale
    lands exactly beside position i's int8 row."""
    g = pool[block_tables]                      # [B, M, Hkv, BS]
    g = jnp.swapaxes(g, 2, 3)                   # [B, M, BS, Hkv]
    return g.reshape(block_tables.shape[0], -1, pool.shape[1])


def _gathered_int8_chunked(cfg, q, k, v, *, causal, q_offset, kv_valid_len,
                           block_tables, scale, k_scale, v_scale):
    """Quantized paged fallback: gather int8 pages + scale pages through the
    table, then run the SAME dequantizing chunked form the unpaged int8
    cache uses (`_chunked_fwd_impl`).  The gathered length is M·BS =
    slot_len, so the chunk split, the dequant arithmetic, and the masking
    are identical to the unpaged call — which is what makes paged int8
    decode bit-exact against unpaged int8 decode."""
    from repro.core.attention import _chunked_fwd_impl
    kg = _gather_pages(k, block_tables)
    vg = _gather_pages(v, block_tables)
    b = q.shape[0]
    out, _ = _chunked_fwd_impl(
        q, kg, vg, jnp.asarray(q_offset, jnp.int32),
        jnp.broadcast_to(jnp.asarray(kv_valid_len, jnp.int32), (b,)),
        causal, min(cfg.attn_chunk, kg.shape[1]),
        scale if scale is not None else q.shape[-1] ** -0.5,
        k_scale=_gather_scale_pages(k_scale, block_tables),
        v_scale=_gather_scale_pages(v_scale, block_tables))
    return out


@register("paged_attention", PATH_PALLAS, PATH_PALLAS_INTERPRET)
def _paged_attention_pallas(cfg, q, k, v, *, causal, q_offset, kv_valid_len,
                            block_tables, scale, k_scale=None, v_scale=None):
    from repro.kernels import ops
    return ops.paged_flash_attention(q, k, v, q_offset, kv_valid_len,
                                     block_tables, causal=causal,
                                     k_scale_pool=k_scale,
                                     v_scale_pool=v_scale)


@register("paged_attention", PATH_XLA)
def _paged_attention_xla(cfg, q, k, v, *, causal, q_offset, kv_valid_len,
                         block_tables, scale, k_scale=None, v_scale=None):
    if k_scale is not None:
        return _gathered_int8_chunked(
            cfg, q, k, v, causal=causal, q_offset=q_offset,
            kv_valid_len=kv_valid_len, block_tables=block_tables,
            scale=scale, k_scale=k_scale, v_scale=v_scale)
    return core.online_attention(
        q, _gather_pages(k, block_tables), _gather_pages(v, block_tables),
        causal=causal, q_offset=q_offset, kv_valid_len=kv_valid_len,
        chunk_size=cfg.attn_chunk, scale=scale)


@register("paged_decode_attention", PATH_PALLAS, PATH_PALLAS_INTERPRET)
def _paged_decode_attention_pallas(cfg, q, k, v, *, q_offset, kv_valid_len,
                                   block_tables, scale, k_scale=None,
                                   v_scale=None):
    """Single-token decode over paged KV on the Pallas streaming kernel.
    Quantized pools pass their scale pages through — the kernel dequantizes
    tile-local."""
    from repro.kernels import ops
    return ops.paged_flash_decode(q[:, 0], k, v, block_tables,
                                  kv_valid_len, k_scale_pool=k_scale,
                                  v_scale_pool=v_scale)[:, None]


@register("paged_decode_attention", PATH_XLA)
def _paged_decode_attention_xla(cfg, q, k, v, *, q_offset, kv_valid_len,
                                block_tables, scale, k_scale=None,
                                v_scale=None):
    if k_scale is not None:
        return _gathered_int8_chunked(
            cfg, q, k, v, causal=False, q_offset=q_offset,
            kv_valid_len=kv_valid_len, block_tables=block_tables,
            scale=scale, k_scale=k_scale, v_scale=v_scale)
    return core.online_attention(
        q, _gather_pages(k, block_tables), _gather_pages(v, block_tables),
        causal=False, q_offset=q_offset, kv_valid_len=kv_valid_len,
        chunk_size=cfg.attn_chunk, scale=scale)


def _paged_sdpa(cfg, q, k, v, *, causal, q_offset, kv_valid_len, scale,
                decode, block_tables, k_scale=None, v_scale=None):
    """Routing for block-table attention: ``select_path``'s own policy.

    Decode and prefill take the Pallas paged kernels wherever Pallas
    compiles natively.  Off the chip, ``cfg.use_pallas`` asks for both
    kernels in interpret mode; otherwise the gather + chunked XLA form
    runs.  Shapes the kernels cannot
    represent (custom scale, value-dim ≠ key-dim) always take the XLA form.
    Quantized pools (``k_scale``/``v_scale`` pages set) ride the same
    routing — every path dequantizes after its gather.  Paged serving is
    single-host, so no Pallas call here needs partitioning: an ambient
    ShardContext is a routing bug, not a fallback case."""
    from repro.distributed import context
    if context.get() is not None:
        raise NotImplementedError(
            "paged KV attention has no sharded ⊕-merge form yet; drop the "
            "ShardContext or serve unpaged")
    op = "paged_decode_attention" if decode else "paged_attention"
    if ((scale is None or scale == q.shape[-1] ** -0.5)
            and v.shape[-1] == q.shape[-1]):
        _, fn = lookup(op, prefer_pallas=cfg.use_pallas)
    else:
        obs_kernels.record_path(op, PATH_XLA, prefer_pallas=cfg.use_pallas)
        fn = implementation(op, PATH_XLA)
    kw = {} if decode else {"causal": causal}
    return fn(cfg, q, k, v, q_offset=q_offset, kv_valid_len=kv_valid_len,
              block_tables=block_tables, scale=scale, k_scale=k_scale,
              v_scale=v_scale, **kw)


# ---------------------------------------------------------------------------
# Public dispatched ops.
# ---------------------------------------------------------------------------
SOFTMAX_FORMS = ("exact", "bf16", "exp2")
_SOFTMAX_FORM = "exact"


def softmax_form() -> str:
    """The reduced-precision softmax form currently preferred ("exact" /
    "bf16" / "exp2")."""
    return _SOFTMAX_FORM


def set_softmax_form(form: str) -> str:
    """Set the process softmax-form preference; returns the previous form.

    "exact" is the registry's standard online form; "bf16" accumulates the
    normalizer in bfloat16; "exp2" computes exponentials as
    ``2^((x−m)·log2 e)`` (the hardware-exp2 menu of PAPERS.md 2201.04562 /
    2111.10770).  Every form's worst-case deviation from the fp32 two-pass
    reference is bounded analytically in ``core.softmax_forms`` and pinned
    by ``tests/test_numerics.py``.  Also settable via the
    ``REPRO_SOFTMAX_FORM`` environment variable (read at import).
    """
    global _SOFTMAX_FORM
    if form not in SOFTMAX_FORMS:
        raise ValueError(
            f"unknown softmax form {form!r}; expected one of {SOFTMAX_FORMS}")
    prev = _SOFTMAX_FORM
    _SOFTMAX_FORM = form
    return prev


def online_softmax(x: Array) -> Array:
    """Softmax over the last axis via the best path for this backend,
    honoring the process softmax-form preference (``set_softmax_form`` /
    ``REPRO_SOFTMAX_FORM``)."""
    op = ("online_softmax" if _SOFTMAX_FORM == "exact"
          else f"online_softmax_{_SOFTMAX_FORM}")
    _, fn = lookup(op)
    with jax.named_scope("kernel.online_softmax"):
        return fn(x)


def softmax_topk(x: Array, k: int,
                 differentiable: bool = False) -> "core.SoftmaxTopK":
    """Fused softmax+top-k (paper Algorithm 4) via the registry.

    Every path is differentiable: the Pallas kernel carries a custom VJP
    (recompute-the-softmax-from-LSE backward, mirroring ``flash_attention``'s
    recompute-from-(m, d) rule), so autodiff callers — the MoE router under
    ``value_and_grad`` — route through the same backend policy as everyone
    else.  ``differentiable`` is kept for caller compatibility; it no longer
    pins the XLA form.
    """
    del differentiable
    _, fn = lookup("softmax_topk")
    with jax.named_scope("kernel.softmax_topk"):
        return fn(x, k)


def sdpa(cfg, q, k, v, *, causal, q_offset, kv_valid_len, scale=None,
         decode: bool = False, k_scale=None, v_scale=None,
         block_tables=None):
    """Attention dispatch — the single entry model layers call.

    Routing order: paged block-table attention (``block_tables`` set: K/V
    are block pools, see ``_paged_sdpa``) → sharded ⊕-merge decode (ambient
    ``ShardContext``) → int8-cache direct chunked decode → registry (pallas /
    pallas-interpret / xla-chunked / naive by config preference and backend
    capability).

    Arguments
    ---------
    cfg:
        Model config; ``cfg.use_pallas`` / ``cfg.use_online_attention``
        state the path preference, ``cfg.attn_chunk`` sizes the chunked
        XLA form.
    q, k, v:
        q [B, Tq, Hq, D].  Contiguous: k/v [B, S, Hkv, D] caches (or fresh
        prompt K/V).  Paged: k/v are block *pools* [P, Hkv, BS, D] shared
        by every sequence.
    causal:
        Causal masking in absolute coordinates (``k_pos ≤ q_offset + i``).
    q_offset:
        Absolute position of query row 0 — scalar, or [B] with one offset
        per slot (continuous batching; a resumed preempted sequence simply
        carries its pre-swap length here).
    kv_valid_len:
        Valid cache prefix per row (scalar or [B]); columns at or past it
        are masked to −inf before the online ``(m, d)`` update, which is
        exact — ragged slots, dead page entries, and pool padding cannot
        perturb numerics.
    scale:
        Softmax scale; None = 1/√D.  A custom scale (MLA) pins the chunked
        XLA form — the kernels bake the default in.
    decode:
        Single-token decode (Tq == 1 semantics): routes the streaming
        decode kernels / decode registry ops instead of the prefill forms.
    k_scale, v_scale:
        Per-position int8-cache dequant scales: contiguous [B, S, Hkv]
        (selects the direct dequantizing chunked path), or scale *pages*
        [P, Hkv, BS] when ``block_tables`` is set — gathered/prefetched
        alongside the int8 pools and applied after the read
        (inference-only).
    block_tables:
        [B, max_blocks] logical→physical block map (paged serving).  Built
        ONLY by ``repro.serving.paged``; consumed here.  Selects the paged
        registry ops with the gather + chunked-XLA fallback off-TPU.
    """
    if block_tables is not None:
        with jax.named_scope("kernel.paged_decode" if decode
                             else "kernel.paged_prefill"):
            return _paged_sdpa(cfg, q, k, v, causal=causal,
                               q_offset=q_offset, kv_valid_len=kv_valid_len,
                               scale=scale, decode=decode,
                               block_tables=block_tables, k_scale=k_scale,
                               v_scale=v_scale)
    with jax.named_scope("kernel.decode" if decode else "kernel.attention"):
        return _contiguous_sdpa(cfg, q, k, v, causal=causal,
                                q_offset=q_offset, kv_valid_len=kv_valid_len,
                                scale=scale, decode=decode, k_scale=k_scale,
                                v_scale=v_scale)


def _contiguous_sdpa(cfg, q, k, v, *, causal, q_offset, kv_valid_len, scale,
                     decode, k_scale, v_scale):
    """``sdpa`` over contiguous K/V (no block tables)."""
    from repro.distributed import context
    ctx = context.get()
    if decode and ctx is not None:
        from repro.distributed.decode_attention import sharded_decode_attention
        return sharded_decode_attention(
            q, k, v, kv_valid_len, mesh=ctx.mesh,
            seq_axes=ctx.cache_seq_axes, batch_axes=ctx.batch_axes,
            chunk_size=cfg.attn_chunk,
            scale=scale if scale is not None else q.shape[-1] ** -0.5,
            k_scale=k_scale, v_scale=v_scale)
    if k_scale is not None:
        # int8 cache, single-device decode: inference-only direct call
        from repro.core.attention import _chunked_fwd_impl
        b = q.shape[0]
        out, _ = _chunked_fwd_impl(
            q, k, v, jnp.asarray(q_offset, jnp.int32),
            jnp.broadcast_to(jnp.asarray(kv_valid_len, jnp.int32), (b,)),
            causal, min(cfg.attn_chunk, k.shape[1]),
            scale if scale is not None else q.shape[-1] ** -0.5,
            k_scale=k_scale, v_scale=v_scale)
        return out
    if decode:
        # single-token decode: per-row kv_valid_len masking (ragged slot
        # lengths under continuous batching).  Same preference semantics as
        # prefill — Pallas stays opt-in via cfg.use_pallas (streaming kernel
        # where native, chunked XLA otherwise), use_online_attention picks
        # chunked XLA, and neither keeps the naive oracle form.
        if cfg.use_pallas and select_path("decode_attention") == PATH_PALLAS:
            fn = _REGISTRY["decode_attention"][PATH_PALLAS]
        elif cfg.use_online_attention or cfg.use_pallas:
            fn = _REGISTRY["decode_attention"][PATH_XLA]
        else:
            return _REGISTRY["attention"][PATH_XLA_NAIVE](
                cfg, q, k, v, causal=False, q_offset=q_offset,
                kv_valid_len=kv_valid_len, scale=scale)
        return fn(cfg, q, k, v, q_offset=q_offset,
                  kv_valid_len=kv_valid_len, scale=scale)
    if (cfg.use_pallas and q.shape[1] > 1
            and (scale is None or scale == q.shape[-1] ** -0.5)
            and v.shape[-1] == q.shape[-1]):
        # prefill — fresh OR cached/chunked: the flash kernel carries
        # q_offset/kv_valid_len operands (absolute-coordinate causal mask,
        # per-row valid-length mask), so cached chunked prefill no longer
        # has to detour through the chunked XLA form on native backends.
        # Still XLA: custom-scale or value-dim≠key-dim attention (MLA's
        # absorbed decode), which the kernel does not model.
        path = select_path("attention", prefer_pallas=True)
    elif cfg.use_online_attention or cfg.use_pallas:
        # chunked XLA fallback (masks offset + valid length exactly) — also
        # the landing spot for the kernel-unrepresentable cases above
        path = PATH_XLA
    else:
        path = PATH_XLA_NAIVE
    return _REGISTRY["attention"][path](
        cfg, q, k, v, causal=causal, q_offset=q_offset,
        kv_valid_len=kv_valid_len, scale=scale)


# Import-time: merge persisted decisions so a serving restart skips the sweep.
load_persisted_decisions()
# Import-time: honor the softmax-form environment preference.
if os.environ.get("REPRO_SOFTMAX_FORM"):
    set_softmax_form(os.environ["REPRO_SOFTMAX_FORM"])
