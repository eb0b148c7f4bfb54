"""Pallas TPU kernel: single-token decode attention over a KV cache.

The serving-side hot spot: one query token attends to a long cache.  This is
*pure* memory streaming — arithmetic intensity ~1 FLOP/byte — i.e. exactly the
regime the paper targets: the online ``(m, d)`` carry means the cache is read
ONCE (vs twice for a safe-softmax decode), and no [S]-sized score vector ever
round-trips to HBM.

``flash_decode_pallas``: grid (batch, kv_head, kv_block).  All G query heads
of a KV group are processed together so the score tile is [G, BK] (sublanes ×
lanes).  The valid cache length is a scalar-prefetch operand (SMEM) used to
mask the tail tile; tiles entirely past ``valid_len`` are skipped.

``flash_decode_paged_pallas`` is the paged-KV form: the cache is a pool of
fixed-size blocks shared by every sequence and a scalar-prefetched
``[B, max_blocks]`` block table maps each row's logical block *j* to a
physical pool block.  Its grid is ``(batch,)``: each step copies only its
row's live pages out of HBM, a compute block of pages at a time, all KV heads
at once, with the next block's copies in flight while one computes (the
paper's order-agnostic ``(m, d)`` update is what makes walking an arbitrary
page list in one pass safe).  Its int8 form keeps a ``(batch, kv_head,
max_blocks)`` grid of one page per step, clamping dead table entries to the
row's last live block so they schedule no fetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _make_kernel(*, scale: float, g: int, bk: int, n_kv: int):
    def kernel(vlen_ref, q_ref, k_ref, v_ref, o_ref, m_sc, d_sc, acc_sc):
        b = pl.program_id(0)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            m_sc[...] = jnp.full_like(m_sc, NEG_INF)
            d_sc[...] = jnp.zeros_like(d_sc)
            acc_sc[...] = jnp.zeros_like(acc_sc)

        vlen = vlen_ref[b]
        run = j * bk < vlen           # skip tiles wholly past the valid cache

        @pl.when(run)
        def _compute():
            q = q_ref[0, 0].astype(jnp.float32) * scale     # [G, D]
            k = k_ref[0, 0].astype(jnp.float32)             # [BK, D]
            v = v_ref[0, 0].astype(jnp.float32)
            s = q @ k.T                                     # [G, BK]
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos < vlen, s, NEG_INF)
            m_prev = m_sc[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            alpha = jnp.exp(jnp.where(m_prev == m_new, 0.0, m_prev - m_new))
            p = jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - m_new))
            d_sc[...] = d_sc[...] * alpha + jnp.sum(p, -1, keepdims=True)
            acc_sc[...] = acc_sc[...] * alpha + p @ v
            m_sc[...] = m_new

        @pl.when(j == n_kv - 1)
        def _finalize():
            o_ref[0, 0] = (acc_sc[...] /
                           jnp.maximum(d_sc[...], 1e-30)).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def flash_decode_pallas(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                        kv_valid_len: jax.Array, *, bk: int = 512,
                        interpret: bool = False) -> jax.Array:
    """q [B, Hq, D]; caches [B, Hkv, S, D]; kv_valid_len [B] → out [B, Hq, D]."""
    b, hq, dh = q.shape
    _, hkv, s, _ = k_cache.shape
    g = hq // hkv
    bk = min(bk, s)
    assert s % bk == 0, (s, bk)
    n_kv = s // bk
    qg = q.reshape(b, hkv, g, dh)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda b_, h, j, vlen: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h, j, vlen: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h, j, vlen: (b_, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh), lambda b_, h, j, vlen: (b_, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, dh), jnp.float32)],
    )
    out = pl.pallas_call(
        _make_kernel(scale=dh ** -0.5, g=g, bk=bk, n_kv=n_kv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        interpret=interpret,
    )(jnp.asarray(kv_valid_len, jnp.int32), qg, k_cache, v_cache)
    return out.reshape(b, hq, dh)


# ---------------------------------------------------------------------------
# Paged form: the cache is a block pool + per-row block table.
# ---------------------------------------------------------------------------
BLOCK_TOKENS = 128       # least tokens one compute block of the row loop reads


def _online_update(m, d, acc, scores, values):
    """The paper's online ``(m, d)`` update over one tile whose scores arrive
    as slices ``scores[i]`` [..., G, T_i] beside their values ``values[i]``
    [..., T_i, D].  Masked scores are −inf and add nothing; a fully masked
    row keeps ``m = −inf`` with ``alpha = 1``.  The order of the slices, like
    the order of the tiles, does not change the result."""
    m_new = m
    for s in scores:
        m_new = jnp.maximum(m_new, jnp.max(s, -1, keepdims=True))
    alpha = jnp.exp(jnp.where(m == m_new, 0.0, m - m_new))
    d, acc = d * alpha, acc * alpha
    for s, v in zip(scores, values):
        p = jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - m_new))
        d = d + jnp.sum(p, -1, keepdims=True)
        acc = acc + jnp.matmul(p, v)
    return m_new, d, acc


def _lane_fold(bs: int, dh: int) -> int:
    """Tokens per pool row in the kernel's view of a page: the largest power
    of two F dividing ``bs`` with ``F·dh ≤ 128``, so a ``dh < 128`` page
    fills the 128 lanes a TPU copy is tiled by."""
    f = 1
    while bs % (2 * f) == 0 and 2 * f * dh <= 128:
        f *= 2
    return f


def _make_paged_kernel(*, scale: float, bs: int, fold: int, pages: int):
    """One grid step per row: walk the row's live pages, ``pages`` at a time,
    with each block's K/V page copies in flight while the previous block
    computes."""
    rows = bs // fold                     # rows of a page in the folded view
    block = pages * bs                    # tokens per compute block

    def kernel(tbl_ref, vlen_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
               sem):
        b = pl.program_id(0)
        vlen = vlen_ref[b]
        n = jnp.minimum(pl.cdiv(vlen, bs), tbl_ref.shape[1])  # live pages
        n_blocks = pl.cdiv(n, pages)

        def each_live_page(blk, slot, act):
            # one copy of [Hkv, rows, fold·D] per live page and pool; table
            # entries at or past n are never read
            for c in range(pages):
                @pl.when(blk * pages + c < n)
                def _():
                    page = tbl_ref[b, blk * pages + c]
                    for kv, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                     (v_hbm, v_buf))):
                        act(pltpu.make_async_copy(hbm.at[page],
                                                  buf.at[slot, :, c],
                                                  sem.at[kv, slot]))

        q = q_ref[0].astype(jnp.float32) * scale             # [Hkv, G, D]
        hkv, g, dh = q.shape
        width = pages * rows

        def body(i, carry):
            slot = i % 2

            @pl.when(i + 1 < n_blocks)
            def _prefetch():
                each_live_page(i + 1, 1 - slot, lambda cp: cp.start())

            each_live_page(i, slot, lambda cp: cp.wait())
            k = k_buf[slot].astype(jnp.float32).reshape(hkv, width, fold * dh)
            v = v_buf[slot].astype(jnp.float32).reshape(hkv, width, fold * dh)
            # lane slice h of folded row t holds token i·block + fold·t + h
            s_pos = i * block + fold * jax.lax.broadcasted_iota(
                jnp.int32, (hkv, g, width), 2)
            v_pos = i * block + fold * jax.lax.broadcasted_iota(
                jnp.int32, (hkv, width, dh), 1)
            scores, values = [], []
            for h in range(fold):
                lanes = slice(h * dh, (h + 1) * dh)
                s = jnp.einsum("hgd,htd->hgt", q, k[..., lanes],
                               preferred_element_type=jnp.float32)
                scores.append(jnp.where(s_pos + h < vlen, s, NEG_INF))
                # stale buffer rows past n may hold anything: zero them so
                # a masked score's 0 weight cannot meet a NaN
                values.append(jnp.where(v_pos + h < vlen, v[..., lanes], 0.0))
            return _online_update(*carry, scores, values)

        @pl.when(n_blocks > 0)
        def _first():
            each_live_page(0, 0, lambda cp: cp.start())

        m, d, acc = jax.lax.fori_loop(
            0, n_blocks, body,
            (jnp.full((hkv, g, 1), NEG_INF, jnp.float32),
             jnp.zeros((hkv, g, 1), jnp.float32),
             jnp.zeros((hkv, g, dh), jnp.float32)))
        o_ref[0] = (acc / jnp.maximum(d, 1e-30)).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode_paged_pallas(q: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array, block_tables: jax.Array,
                              kv_valid_len: jax.Array, *,
                              k_scale_pool: jax.Array | None = None,
                              v_scale_pool: jax.Array | None = None,
                              interpret: bool = False) -> jax.Array:
    """q [B, Hq, D]; pools [P, Hkv, BS, D]; block_tables [B, M] (physical pool
    block per logical block, scalar-prefetched); kv_valid_len [B] →
    out [B, Hq, D].

    Grid ``(B,)``: one step per row, all KV heads at once.  The pools stay in
    HBM; the step walks only the row's ``n = ceil(valid_len / BS)`` live
    pages, ``ceil(BLOCK_TOKENS / BS)`` pages per compute block, one async
    copy per page and pool into a double buffer, so block *i + 1*'s copies
    run while block *i* computes its ``[Hkv, G, block]`` scores and the
    online update.  Table entries at or past ``n`` are never read (they may
    be stale or the sentinel); the tail is masked by absolute position, and
    a row with ``valid_len == 0`` copies nothing and writes zeros.

    For ``D < 128`` the pools are viewed as ``[P, Hkv, BS/F, F·D]``
    (``_lane_fold``): the TPU compiler copies a page only in whole 128-lane
    rows.  Lane slice *h* of folded row *r* is token ``F·r + h``, and each
    slice is scored on its own; the online update does not depend on the
    order of the keys, so this is the same attention.  The reshape is free
    on a row-major pool; where XLA holds the pool in another layout (the
    paged decode step's layer scan does), it costs one more relayout copy.

    ``k_scale_pool``/``v_scale_pool`` [P, Hkv, BS, 1] set selects the
    quantized form (``_flash_decode_paged_int8``): int8 pools with bf16
    scale pages, on a ``(B, Hkv, M)`` grid of one page per step.
    """
    if k_scale_pool is not None:
        return _flash_decode_paged_int8(q, k_pool, v_pool, block_tables,
                                        kv_valid_len, k_scale_pool,
                                        v_scale_pool, interpret=interpret)
    b, hq, dh = q.shape
    p, hkv, bs, _ = k_pool.shape
    m = block_tables.shape[1]
    g = hq // hkv
    fold = _lane_fold(bs, dh)
    pages = min(pl.cdiv(BLOCK_TOKENS, bs), m)
    k_view = k_pool.reshape(p, hkv, bs // fold, fold * dh)
    v_view = v_pool.reshape(p, hkv, bs // fold, fold * dh)
    row_block = pl.BlockSpec((1, hkv, g, dh),
                             lambda b_, tbl, vl: (b_, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    buf = pltpu.VMEM((2, hkv, pages, bs // fold, fold * dh), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[row_block, hbm, hbm],
        out_specs=row_block,
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))],
    )
    out = pl.pallas_call(
        _make_paged_kernel(scale=dh ** -0.5, bs=bs, fold=fold, pages=pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(kv_valid_len, jnp.int32), q.reshape(b, hkv, g, dh),
      k_view, v_view)
    return out.reshape(b, hq, dh)


# ---------------------------------------------------------------------------
# Quantized paged form: int8 K/V pages beside bf16 scale pages, on a grid of
# one page of one head per step.  The scale pages cannot join the row loop:
# the TPU compiler refuses a copy of one scale page in every view of the
# scale pool tried ([P, Hkv, BS, 1], [P, Hkv, BS], [P, Hkv·BS],
# [P, 1, Hkv·BS]), while a BlockSpec fetches its (BS, 1) block.
# ---------------------------------------------------------------------------
def _make_paged_int8_kernel(*, scale: float, bs: int, n_blocks: int):
    def kernel(tbl_ref, vlen_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
               m_sc, d_sc, acc_sc):
        b = pl.program_id(0)
        j = pl.program_id(2)      # logical block of row b

        @pl.when(j == 0)
        def _init():
            m_sc[...] = jnp.full_like(m_sc, NEG_INF)
            d_sc[...] = jnp.zeros_like(d_sc)
            acc_sc[...] = jnp.zeros_like(acc_sc)

        vlen = vlen_ref[b]

        @pl.when(j * bs < vlen)
        def _compute():
            # dequantize AFTER the HBM read: int8 page × per-position scale
            # column, both fetched through the same table entry
            k = (k_ref[0, 0].astype(jnp.float32)
                 * ks_ref[0, 0].astype(jnp.float32))        # [BS, D]·[BS, 1]
            v = (v_ref[0, 0].astype(jnp.float32)
                 * vs_ref[0, 0].astype(jnp.float32))
            q = q_ref[0, 0].astype(jnp.float32) * scale     # [G, D]
            s = q @ k.T                                     # [G, BS]
            k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos < vlen, s, NEG_INF)
            m_sc[...], d_sc[...], acc_sc[...] = _online_update(
                m_sc[...], d_sc[...], acc_sc[...], [s], [v])

        @pl.when(j == n_blocks - 1)
        def _finalize():
            o_ref[0, 0] = (acc_sc[...] /
                           jnp.maximum(d_sc[...], 1e-30)).astype(o_ref.dtype)

    return kernel


def _flash_decode_paged_int8(q, k_pool, v_pool, block_tables, kv_valid_len,
                             k_scale_pool, v_scale_pool, *, interpret):
    """The quantized form of ``flash_decode_paged_pallas``.  Dead table
    entries (at or past ``ceil(valid_len / BS)``) are clamped to the row's
    last live block, so they schedule no fetch, and their compute is
    skipped.  The trailing unit axis of the scale pools makes a scale
    block's last two dims (BS, 1), which the TPU compiler accepts; a
    (1, 1, BS) block of a [P, Hkv, BS] array is refused."""
    b, hq, dh = q.shape
    _, hkv, bs, _ = k_pool.shape
    m = block_tables.shape[1]
    g = hq // hkv

    def page_index(b_, h, j, tbl_ref, vlen_ref):
        last = jnp.maximum((vlen_ref[b_] + bs - 1) // bs - 1, 0)
        return (tbl_ref[b_, jnp.minimum(j, last)], h, 0, 0)

    row_head = pl.BlockSpec((1, 1, g, dh),
                            lambda b_, h, j, tbl, vl: (b_, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, m),
        in_specs=[row_head,
                  pl.BlockSpec((1, 1, bs, dh), page_index),
                  pl.BlockSpec((1, 1, bs, dh), page_index),
                  pl.BlockSpec((1, 1, bs, 1), page_index),
                  pl.BlockSpec((1, 1, bs, 1), page_index)],
        out_specs=row_head,
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, dh), jnp.float32)],
    )
    out = pl.pallas_call(
        _make_paged_int8_kernel(scale=dh ** -0.5, bs=bs, n_blocks=m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(kv_valid_len, jnp.int32), q.reshape(b, hkv, g, dh),
      k_pool, v_pool, k_scale_pool, v_scale_pool)
    return out.reshape(b, hq, dh)
