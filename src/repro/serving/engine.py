"""Serving: KV-cache management, prefill, and decode with fused top-k sampling.

The decode step ends in the paper's §4 scenario verbatim: a projection to the
full vocabulary followed by TopK — served by ``core.topk_sample`` (Algorithm 4,
single pass over the vocab, or the Pallas ``softmax_topk`` kernel on TPU).

Cache layout mirrors the model's segment structure: one stacked cache pytree
per segment (leading axis = layers in the segment).  Attention caches have a
static ``max_len``; validity is tracked per sequence.  Two serving shapes sit
on top of that layout:

* **Lockstep batch** (``prefill`` + ``decode_step``): one scalar ``cache_len``
  shared by every row — the drain-and-refill baseline, still what the dry-run
  and the whisper path drive.
* **Slot pool** (``chunked_prefill`` / ``write_slot`` / ``decode_step_slots``):
  the batch axis is a pool of independent cache *slots*, each with its own
  length in a ``[B]`` vector that flows through ``kv_valid_len`` into the
  attention masks.  A finished slot is overwritten in place by the next
  request's prefilled cache — continuous batching, orchestrated by
  ``repro.serving.scheduler`` — so decode always runs at full batch occupancy
  with ragged sequence lengths.  Sampling keys are per-slot
  (``sample_per_slot``), which makes a slot's token stream independent of its
  batch neighbours: the scheduler-equivalence guarantee the tests pin.
* **Paged pool** (``init_paged_cache`` / ``prefill_chunk_paged`` /
  ``decode_step_paged`` / ``copy_paged_block``): KV memory is a shared pool
  of fixed-size blocks with per-sequence **block tables** ([B, max_blocks])
  mapping logical to physical blocks — capacity scales with tokens actually
  held rather than worst-case slot length, and identical prompt prefixes
  share physical blocks (copy-on-write on divergence).  Allocation, prefix
  hashing, and table construction live in ``repro.serving.paged``; these
  primitives only run model steps through tables they are handed.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import compat, core
from repro.configs.base import ModelConfig
from repro.models import encdec, transformer
from repro.serving import cache_family

Array = jax.Array
PyTree = Any


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    """Build the contiguous cache pytree (zeros) — layout owned by the
    config's cache family (``serving.cache_family``)."""
    return cache_family.resolve(cfg).init_cache(batch, max_len)


def prefill(params: PyTree, tokens: Array, cfg: ModelConfig, *,
            max_len: int, patch_embeds: Optional[Array] = None):
    """Run the prompt through the model, filling a fresh cache.

    Returns (last_hidden [B, D], caches, cache_len scalar)."""
    b, t = tokens.shape
    caches = init_cache(cfg, b, max_len)
    hidden, new_caches, _ = transformer.forward(
        params, tokens, cfg, patch_embeds=patch_embeds, caches=caches,
        cache_len=jnp.asarray(0, jnp.int32))
    return hidden[:, -1], new_caches, jnp.asarray(
        t + (cfg.num_patches if patch_embeds is not None else 0), jnp.int32)


def logits_from_hidden(params: PyTree, last_hidden: Array,
                       cfg: ModelConfig) -> Array:
    """LM-head logits [B, V] from the last-position hidden state [B, D],
    with padded vocab rows masked to -inf (scoped ``head``)."""
    with jax.named_scope("head"):
        logits = transformer.logits_last(params, last_hidden[:, None], cfg)
        if cfg.real_vocab_size and cfg.real_vocab_size < cfg.vocab_size:
            mask = jnp.arange(cfg.vocab_size) < cfg.real_vocab_size
            logits = jnp.where(mask, logits, float("-inf"))
        return logits


def decode_step(params: PyTree, caches: list, cache_len: Array,
                tokens: Array, cfg: ModelConfig, *, rng: Array,
                top_k: int = 5, temperature: float = 1.0):
    """One lockstep decode step: tokens [B, 1] → (next_token [B], new caches).

    The final vocab softmax+topk+sample is the fused single-pass form.
    """
    hidden, new_caches, _ = transformer.forward(
        params, tokens, cfg, caches=caches, cache_len=cache_len)
    logits = logits_from_hidden(params, hidden[:, -1], cfg)
    from repro.distributed import context
    ctx = context.get()
    if ctx is not None:
        from repro.distributed.decode_attention import sharded_topk_sample
        next_tok, _ = sharded_topk_sample(
            rng, logits, top_k, mesh=ctx.mesh, batch_axes=ctx.batch_axes,
            vocab_axis=ctx.par.model_axis, temperature=temperature)
    else:
        # single-pass block width: the autotuned ⊕-tree choice for this
        # (backend, vocab, dtype), not a hard-coded chunk heuristic
        from repro.kernels import dispatch
        block = dispatch.tuned_block(logits.shape[-1], logits.dtype)
        next_tok, _ = core.topk_sample(rng, logits, top_k,
                                       temperature=temperature,
                                       block=min(block, logits.shape[-1]))
    return next_tok, new_caches, cache_len + 1


# ---------------------------------------------------------------------------
# Continuous batching: slot-pool primitives.
# ---------------------------------------------------------------------------
def prefill_schedule(t: int, chunk: int) -> list:
    """Chunk widths for a ``t``-token prompt: full ``chunk``s, then a binary
    (power-of-two) decomposition of the remainder.

    A jitted per-chunk forward compiles once per distinct width; naive
    ``t % chunk`` tails would recompile the whole model for nearly every
    prompt length mid-serving, so the tail is capped at O(log chunk) widths
    shared by all prompts instead."""
    sizes = []
    rem = int(t)
    while rem >= chunk:
        sizes.append(chunk)
        rem -= chunk
    p = 1
    while p * 2 <= rem:
        p *= 2
    while rem:
        if p <= rem:
            sizes.append(p)
            rem -= p
        p //= 2
    return sizes


def chunked_prefill(params: PyTree, tokens: Array, cfg: ModelConfig, *,
                    max_len: int, chunk: int = 0):
    """Prefill a prompt in chunks against a fresh cache.

    ``chunk=0`` (or ≥ the prompt) degenerates to single-shot prefill.  This is
    the canonical single-sequence prefill of the slot pool: the scheduler runs
    the same per-chunk step (``prefill_chunk``) over the same
    ``prefill_schedule`` interleaved with decode, so a request's cache
    contents are identical whether it prefilled alone or while the pool was
    busy.  Every chunk after the first runs at ``q_offset > 0`` against the
    partially-valid cache — the case the offset-aware flash kernel serves
    natively (``dispatch.sdpa`` routes it; XLA chunked elsewhere).
    Returns (last_hidden [B, D], caches, length)."""
    b, t = tokens.shape
    if cache_family.resolve(cfg).single_shot_prefill:
        # the family's prefill would drop information chunked (int8 prefill
        # computes on the current chunk's exact fp tensors only; SSM/xLSTM
        # chunked prefill does not thread prefix state) — go in whole
        chunk = 0
    caches = init_cache(cfg, b, max_len)
    length = jnp.asarray(0, jnp.int32)
    last = None
    pos = 0
    for c in prefill_schedule(t, chunk or t):
        last, caches, length = prefill_chunk(
            params, caches, length, tokens[:, pos:pos + c], cfg)
        pos += c
    return last, caches, length


def prefill_chunk(params: PyTree, caches: list, cache_len: Array,
                  tokens: Array, cfg: ModelConfig):
    """Advance a prefill by one chunk: tokens [B, c] are written into the
    cache at ``cache_len`` and attended causally against everything before
    them.  Returns (last_hidden [B, D], new caches, new length).

    ``cache_len`` is a scalar (one sequence, or a lockstep batch) or a [B]
    vector (per-slot offsets).  Either way it threads through the model as
    ``q_offset`` with ``kv_valid_len = cache_len + c``, which is exactly the
    operand pair the Pallas flash kernel masks on — so chunked prefill at
    ``q_offset > 0`` runs the kernel on native backends instead of detouring
    through the chunked XLA form (the PR-2 routing pin, now lifted)."""
    hidden, new_caches, _ = transformer.forward(
        params, tokens, cfg, caches=caches, cache_len=cache_len)
    return hidden[:, -1], new_caches, cache_len + tokens.shape[1]


def write_slot(cfg: ModelConfig, pool: list, seq: list, slot) -> list:
    """Overwrite slot ``slot`` of the pool cache with a batch-1 sequence cache.

    Both pytrees come from ``init_cache`` with the same ``max_len``; the whole
    per-slot slice is replaced, so whatever a retired sequence left behind is
    gone.  Stacked segment leaves carry batch on axis 1 (after the layer
    axis); Zamba2's shared block is stored unstacked, batch on axis 0."""
    slot = jnp.asarray(slot, jnp.int32)
    out: list = []
    for (kind, _), pc, sc in zip(transformer.block_pattern(cfg), pool, seq):
        axis = 0 if kind == "shared_attn" else 1
        out.append(compat.tree_map(
            lambda p, s, a=axis: jax.lax.dynamic_update_slice_in_dim(
                p, s.astype(p.dtype), slot, axis=a), pc, sc))
    return out


def sample_per_slot(rngs: Array, logits: Array, top_k: int,
                    temperature: float = 1.0) -> Array:
    """Fused softmax+top-k sampling with one PRNG key per row.

    ``rngs`` [B, 2]: independent keys, so row b's token depends only on its
    own logits and key — a slot samples the same stream at batch size 1 or N,
    which is what makes continuous batching reproduce single-sequence decode
    token-for-token.  The single vocab pass (paper Alg. 4) goes through the
    dispatch registry (Pallas kernel on TPU); only the Gumbel draw is
    per-row.  Scoped ``sample``."""
    from repro.kernels import dispatch
    with jax.named_scope("sample"):
        if temperature != 1.0:
            logits = logits / temperature
        out = dispatch.softmax_topk(logits, top_k)
        k = out.values.shape[-1]
        g = jax.vmap(lambda r: jax.random.gumbel(r, (k,), jnp.float32))(rngs)
        return core.gumbel_pick(out, g)


def decode_step_slots(params: PyTree, caches: list, slot_lens: Array,
                      tokens: Array, cfg: ModelConfig, *, rngs: Array,
                      top_k: int = 5, temperature: float = 1.0):
    """One decode step over the whole slot pool: tokens [B, 1], per-slot
    lengths [B] → (next_token [B], new caches, slot_lens + 1).

    Every slot advances by one position at its own offset; masking comes from
    the ``kv_valid_len`` vector, so ragged sequences coexist in one fused
    batch — the full-occupancy regime where the single-pass softmax's memory
    savings actually pay (ISSUE 2 / Dukhan & Ablavatski 2020)."""
    hidden, new_caches, _ = transformer.forward(
        params, tokens, cfg, caches=caches, cache_len=slot_lens)
    logits = logits_from_hidden(params, hidden[:, -1], cfg)
    next_tok = sample_per_slot(rngs, logits, top_k, temperature)
    return next_tok, new_caches, slot_lens + 1


# ---------------------------------------------------------------------------
# Paged KV cache: block-pool primitives.  Allocation, prefix sharing, and
# block-TABLE construction live exclusively in ``repro.serving.paged``
# (grep-enforced); this module only initializes pools and runs model steps
# through tables it is handed.
# ---------------------------------------------------------------------------
def paged_supported(cfg: ModelConfig) -> bool:
    """Paged serving covers every config whose cache family implements the
    block-pool layout: dense token blocks, quantized dense blocks (int8 K/V
    pools beside bfloat16 scale pages, dequantized in the gather —
    ``cache_family.DenseInt8Family.dequantize_block`` states the arithmetic),
    fixed-size state rows, enc-dec cross/self blocks.  MLA latent caches are
    the registered follow-up."""
    return cache_family.resolve(cfg).paged_serveable


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     slot_len: Optional[int] = None) -> PyTree:
    """Build the block-pool cache pytree (zeros) for the config's family.

    Dense leaves are [n_layers, P, Hkv, BS, D] — kernel-native page layout,
    NO batch axis: the pool is shared by every sequence and block tables
    carry the per-sequence mapping.  State/enc-dec families size per-block
    rows by ``slot_len``.  Every family puts the physical-block axis at leaf
    position 1; ``num_blocks`` counts physical blocks including the sentinel
    block 0 (see ``serving.paged.PagedPool``)."""
    return cache_family.resolve(cfg).init_paged_cache(
        num_blocks, block_size, slot_len)


def copy_paged_block(pools: list, src, dst) -> list:
    """Copy physical block ``src`` over block ``dst`` in every layer's pool —
    the copy-on-write primitive behind prefix-sharing divergence."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return compat.tree_map(
        lambda x: jax.lax.dynamic_update_slice_in_dim(
            x, jax.lax.dynamic_slice_in_dim(x, src, 1, axis=1), dst, axis=1),
        pools)


def write_paged_block(pools: list, block: list, dst) -> list:
    """Write one physical block's content into slot ``dst`` of every layer
    pool — the swap-in restore primitive behind preempt-and-swap.

    ``block`` is the pytree ``compat.tree_map(lambda x: x[:, bid], pools)``
    produces (leaves [n_layers, Hkv, BS, D] — one pool entry, no block axis),
    round-tripped through the host by ``serving.paged.PagedPool.swap_out``.
    The write is a full-slot replacement in the same dtype, so a
    swap-out/swap-in cycle is bit-exact."""
    dst = jnp.asarray(dst, jnp.int32)
    return compat.tree_map(
        lambda x, b: jax.lax.dynamic_update_slice_in_dim(
            x, jnp.asarray(b, x.dtype)[:, None], dst, axis=1), pools, block)


def prefill_chunk_paged(params: PyTree, pools: list, block_tables: Array,
                        cache_len: Array, tokens: Array, cfg: ModelConfig):
    """Advance a paged prefill by one chunk: tokens [1, c] are scattered into
    pool blocks through ``block_tables`` [1, M] at offset ``cache_len`` and
    attended causally (absolute coordinates) against the already-valid
    prefix — which may include blocks shared from another request's
    identical prompt prefix.  Returns (last_hidden [1, D], new pools, new
    length)."""
    hidden, new_pools, _ = transformer.forward(
        params, tokens, cfg, caches=pools, cache_len=cache_len,
        block_tables=block_tables)
    return hidden[:, -1], new_pools, cache_len + tokens.shape[1]


def decode_step_paged(params: PyTree, pools: list, block_tables: Array,
                      slot_lens: Array, tokens: Array, cfg: ModelConfig, *,
                      rngs: Array, top_k: int = 5, temperature: float = 1.0):
    """One decode step over the paged pool: tokens [B, 1], block_tables
    [B, M], per-slot lengths [B] → (next_token [B], new pools, lens + 1).

    Identical sampling scheme to ``decode_step_slots`` (per-slot keys), and
    — because the gather fallback masks exactly and pool values equal what a
    contiguous slot would hold — identical token streams, which is the
    equivalence ``tests/test_serving_paged.py`` pins."""
    hidden, new_pools, _ = transformer.forward(
        params, tokens, cfg, caches=pools, cache_len=slot_lens,
        block_tables=block_tables)
    logits = logits_from_hidden(params, hidden[:, -1], cfg)
    next_tok = sample_per_slot(rngs, logits, top_k, temperature)
    return next_tok, new_pools, slot_lens + 1


# ---------------------------------------------------------------------------
# Fixed-state (SSM / xLSTM / hybrid) paged serving: one block = one
# sequence's entire state row.  The pool layout is the contiguous slot-cache
# layout with the batch axis serving as the block axis (shared-attention
# segments carry a unit layer axis so every leaf keeps the block axis at
# position 1 — the pool contract in ``serving.cache_family``).
# ---------------------------------------------------------------------------
def gather_state_rows(cfg: ModelConfig, pools: list, rows: Array) -> list:
    """Gather pool rows ``rows`` [B] into a contiguous batch-B cache list —
    the exact pytree ``init_cache(cfg, B, slot_len)`` produces, so the
    ordinary slot-pool decode step runs on it unchanged."""
    rows = jnp.asarray(rows, jnp.int32)
    out: list = []
    for (kind, _), c in zip(transformer.block_pattern(cfg), pools):
        if kind == "shared_attn":
            out.append(compat.tree_map(
                lambda x: jnp.take(x[0], rows, axis=0), c))
        else:
            out.append(compat.tree_map(
                lambda x: jnp.take(x, rows, axis=1), c))
    return out


def scatter_state_rows(cfg: ModelConfig, pools: list, caches: list,
                       rows: Array) -> list:
    """Write a contiguous batch-B cache list back into pool rows ``rows``
    [B].  Out-of-range row indices are dropped — the scheduler routes
    inactive slots out of bounds so a gather/decode over garbage rows never
    writes anything back."""
    rows = jnp.asarray(rows, jnp.int32)
    out: list = []
    for (kind, _), p, c in zip(transformer.block_pattern(cfg), pools, caches):
        if kind == "shared_attn":
            out.append(compat.tree_map(
                lambda x, v: x.at[0, rows].set(v.astype(x.dtype),
                                               mode="drop"), p, c))
        else:
            out.append(compat.tree_map(
                lambda x, v: x.at[:, rows].set(v.astype(x.dtype),
                                               mode="drop"), p, c))
    return out


def decode_step_state(params: PyTree, pools: list, rows: Array,
                      active: Array, slot_lens: Array, tokens: Array,
                      cfg: ModelConfig, *, rngs: Array, top_k: int = 5,
                      temperature: float = 1.0):
    """One decode step over a fixed-state block pool: gather each active
    slot's state row, run the ordinary slot-pool decode, scatter the new
    state back.  Inactive slots gather the (zero-initialized) sentinel row
    and their writes are dropped, so their compute is discarded without
    touching live state — and because rows are independent through the whole
    network, the active slots' streams are bit-identical to solo decode."""
    rows = jnp.asarray(rows, jnp.int32)
    active = jnp.asarray(active, bool)
    num_rows = compat.tree_leaves(pools)[0].shape[1]
    caches = gather_state_rows(cfg, pools, jnp.where(active, rows, 0))
    next_tok, new_caches, new_lens = decode_step_slots(
        params, caches, slot_lens, tokens, cfg, rngs=rngs, top_k=top_k,
        temperature=temperature)
    new_pools = scatter_state_rows(
        cfg, pools, new_caches, jnp.where(active, rows, num_rows))
    return next_tok, new_pools, new_lens


# ---------------------------------------------------------------------------
# Encoder–decoder (whisper) serving.
# ---------------------------------------------------------------------------
def encdec_prefill(params: PyTree, frames: Array, bos_tokens: Array,
                   cfg: ModelConfig, *, max_len: int):
    """Encode audio-frame embeddings and prime the decoder cache."""
    b = frames.shape[0]
    enc_out = encdec.encode(params, frames, cfg)
    dt = jnp.dtype(cfg.dtype)
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    n = cfg.num_layers
    caches = {
        "self": {"k": jnp.zeros((n, b, max_len, hkv, hd), dt),
                 "v": jnp.zeros((n, b, max_len, hkv, hd), dt)},
        "cross": {"k": jnp.zeros((n, b, enc_out.shape[1], hkv, hd), dt),
                  "v": jnp.zeros((n, b, enc_out.shape[1], hkv, hd), dt)},
    }
    hidden, new_caches = encdec.decode_hidden(
        params, bos_tokens, enc_out, cfg, caches=caches,
        cache_len=jnp.asarray(0, jnp.int32))
    return hidden[:, -1], new_caches, jnp.asarray(bos_tokens.shape[1], jnp.int32)


def encdec_decode_step(params: PyTree, caches: PyTree, cache_len: Array,
                       tokens: Array, cfg: ModelConfig, *, rng: Array,
                       top_k: int = 5):
    hidden, new_caches = encdec.decode_hidden(
        params, tokens, None, cfg, caches=caches, cache_len=cache_len)
    logits = transformer.logits_last(params, hidden, cfg)
    next_tok, _ = core.topk_sample(rng, logits, top_k)
    return next_tok, new_caches, cache_len + 1


# ---------------------------------------------------------------------------
# Enc-dec paged serving: the prompt is the audio (frame ids); the encoder
# output's cross-K/V projection is sliced into immutable, shareable pool
# blocks, and each sequence additionally owns one growing decoder self-K/V
# row block.  The scheduler's key property: a repeated same-audio request
# adopts the cross blocks refcount++ and the encoder NEVER re-runs.
# ---------------------------------------------------------------------------
#: Decoder start token for served enc-dec requests.  Fixed — the prompt is
#: the audio; every decoder row begins at the same BOS, so two same-audio
#: requests differ only in their (rid, token-index) sample keys.
ENCDEC_BOS = 0


def encdec_frames_from_ids(ids, cfg: ModelConfig) -> Array:
    """Deterministic stand-in audio features for serving workloads: frame id
    ``i`` maps to the ``i``-th row of a sinusoidal table, so identical id
    sequences are identical audio.  Returns frames [1, S_enc, D]."""
    table = encdec.sinusoidal(cfg.vocab_size, cfg.d_model)
    return table[jnp.asarray(ids, jnp.int32)][None]


def encdec_prefill_cached(params: PyTree, cross: PyTree, bos_tokens: Array,
                          cfg: ModelConfig, *, max_len: int):
    """Prime a decoder cache from an already-computed cross-K/V projection
    ``{k, v: [n, B, S_enc, Hkv, D]}`` — the zero-encoder-recompute path a
    whole-audio prefix hit takes.  Bit-identical to ``encdec_prefill`` of
    the same audio: the stored K/V are exactly what the fresh encode
    produced, and attention over given K/V is the same computation either
    way.  Returns (last_hidden [B, D], caches, length)."""
    b = bos_tokens.shape[0]
    caches = dict(cache_family.resolve(cfg).init_cache(b, max_len))
    caches["cross"] = cross
    hidden, new_caches = encdec.decode_hidden(
        params, bos_tokens, None, cfg, caches=caches,
        cache_len=jnp.asarray(0, jnp.int32))
    return hidden[:, -1], new_caches, jnp.asarray(
        bos_tokens.shape[1], jnp.int32)


def encdec_decode_step_slots(params: PyTree, caches: PyTree,
                             slot_lens: Array, tokens: Array,
                             cfg: ModelConfig, *, rngs: Array,
                             top_k: int = 5, temperature: float = 1.0):
    """One continuous-batching decode step for enc-dec: tokens [B, 1],
    per-slot decoder lengths [B] → (next_token [B], new caches, lens + 1).
    Per-slot sampling keys, so streams are independent of batch neighbours —
    the same scheduler-equivalence guarantee as ``decode_step_slots``."""
    hidden, new_caches = encdec.decode_hidden(
        params, tokens, None, cfg, caches=caches, cache_len=slot_lens)
    logits = logits_from_hidden(params, hidden[:, -1], cfg)
    next_tok = sample_per_slot(rngs, logits, top_k, temperature)
    return next_tok, new_caches, slot_lens + 1


def gather_encdec_rows(pools: PyTree, cross_tables: Array,
                       self_rows: Array) -> PyTree:
    """Assemble contiguous decoder caches from the block pool:
    ``cross_tables`` [B, S_enc // BS] gathers and re-flattens the encoder
    blocks, ``self_rows`` [B] picks each sequence's self-K/V row."""
    cross_tables = jnp.asarray(cross_tables, jnp.int32)
    self_rows = jnp.asarray(self_rows, jnp.int32)
    b = cross_tables.shape[0]

    def flat_cross(x):
        g = x[:, cross_tables]                  # [n, B, nc, BS, Hkv, D]
        n, _, nc, bs = g.shape[:4]
        return g.reshape((n, b, nc * bs) + g.shape[4:])

    return {
        "self": compat.tree_map(lambda x: x[:, self_rows], pools["self"]),
        "cross": compat.tree_map(flat_cross, pools["cross"]),
    }


def gather_encdec_cross(pools: PyTree, cross_bids: Array) -> PyTree:
    """Re-flatten shared encoder blocks ``cross_bids`` [nc] into one
    contiguous batch-1 cross projection ``{k, v: [n, 1, S_enc, Hkv, D]}`` —
    the operand a whole-audio prefix hit hands ``encdec_prefill_cached``."""
    bids = jnp.asarray(cross_bids, jnp.int32)

    def flat(x):
        g = x[:, bids]                          # [n, nc, BS, Hkv, D]
        n, nc, bs = g.shape[:3]
        return g.reshape((n, 1, nc * bs) + g.shape[3:])

    return compat.tree_map(flat, pools["cross"])


def install_encdec_row(pools: PyTree, caches: PyTree, cross_bids: Array,
                       self_row: Array) -> PyTree:
    """Scatter a freshly-prefilled batch-1 decoder cache into the pool:
    the cross projection sliced into blocks ``cross_bids`` [nc] and the
    self row into block ``self_row``.  Out-of-range indices are dropped —
    a prefix-hit install passes out-of-range cross bids so the shared
    (identical) blocks are simply not rewritten."""
    cross_bids = jnp.asarray(cross_bids, jnp.int32)
    self_row = jnp.asarray(self_row, jnp.int32).reshape((1,))
    nc = cross_bids.shape[0]

    def put_cross(x, v):
        n, _, s_enc = v.shape[:3]
        blocks = v.reshape((n, nc, s_enc // nc) + v.shape[3:])
        return x.at[:, cross_bids].set(blocks.astype(x.dtype), mode="drop")

    return {
        "self": compat.tree_map(
            lambda x, v: x.at[:, self_row].set(v.astype(x.dtype),
                                               mode="drop"),
            pools["self"], caches["self"]),
        "cross": compat.tree_map(put_cross, pools["cross"],
                                 caches["cross"]),
    }


def decode_step_encdec_paged(params: PyTree, pools: PyTree,
                             cross_tables: Array, self_rows: Array,
                             active: Array, slot_lens: Array, tokens: Array,
                             cfg: ModelConfig, *, rngs: Array,
                             top_k: int = 5, temperature: float = 1.0):
    """One enc-dec decode step through the block pool: gather cross + self
    rows, run the slot decode, scatter ONLY the self rows back (cross blocks
    are immutable — possibly shared — and a decode step never changes
    them).  Inactive slots gather the sentinel row and their writes drop."""
    self_rows = jnp.asarray(self_rows, jnp.int32)
    active = jnp.asarray(active, bool)
    num_rows = compat.tree_leaves(pools)[0].shape[1]
    caches = gather_encdec_rows(
        pools, cross_tables, jnp.where(active, self_rows, 0))
    next_tok, new_caches, new_lens = encdec_decode_step_slots(
        params, caches, slot_lens, tokens, cfg, rngs=rngs, top_k=top_k,
        temperature=temperature)
    rows = jnp.where(active, self_rows, num_rows)
    new_self = compat.tree_map(
        lambda x, v: x.at[:, rows].set(v.astype(x.dtype), mode="drop"),
        pools["self"], new_caches["self"])
    return next_tok, {"self": new_self, "cross": pools["cross"]}, new_lens
