"""Pallas TPU kernel: single-token decode attention over a KV cache.

The serving-side hot spot: one query token attends to a long cache.  This is
*pure* memory streaming — arithmetic intensity ~1 FLOP/byte — i.e. exactly the
regime the paper targets: the online ``(m, d)`` carry means the cache is read
ONCE (vs twice for a safe-softmax decode), and no [S]-sized score vector ever
round-trips to HBM.

Grid: (batch, kv_head, kv_block).  All G query heads of a KV group are
processed together so the score tile is [G, BK] (sublanes × lanes).  The valid
cache length is a scalar-prefetch operand (SMEM) used to mask the tail tile;
tiles entirely past ``valid_len`` are skipped.

``flash_decode_paged_pallas`` is the paged-KV form: the cache is a pool of
fixed-size blocks shared by every sequence and a scalar-prefetched
``[B, max_blocks]`` block table maps each row's logical block *j* to a
physical pool block.  The K/V index maps gather one pool block per grid step
(the paper's order-agnostic ``(m, d)`` update is what makes walking an
arbitrary page list in one pass safe), clamping dead table entries to the
row's last live block so they schedule no fetch — the paged twin of the
offset kernel's clamped index maps.
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
# Paged form: the cache is a block pool + per-row block table.  The quantized
# variant streams int8 K/V pages plus their bf16 scale pages (same table,
# same clamped page index) and dequantizes tile-local in VMEM — HBM traffic
# stays ~1 byte per cache element.
# ---------------------------------------------------------------------------
def _make_paged_kernel(*, scale: float, g: int, bs: int, n_blocks: int,
                       quantized: bool = False):
    def _update(j, vlen, q_ref, k, v, m_sc, d_sc, acc_sc):
        q = q_ref[0, 0].astype(jnp.float32) * scale         # [G, D]
        s = q @ k.T                                         # [G, BS]
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < vlen, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(jnp.where(m_prev == m_new, 0.0, m_prev - m_new))
        p = jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - m_new))
        d_sc[...] = d_sc[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + p @ v
        m_sc[...] = m_new

    def _init(m_sc, d_sc, acc_sc):
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        d_sc[...] = jnp.zeros_like(d_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def _finalize(o_ref, m_sc, d_sc, acc_sc):
        o_ref[0, 0] = (acc_sc[...] /
                       jnp.maximum(d_sc[...], 1e-30)).astype(o_ref.dtype)

    if quantized:
        def kernel(tbl_ref, vlen_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                   o_ref, m_sc, d_sc, acc_sc):
            b = pl.program_id(0)
            j = pl.program_id(2)      # logical block of row b
            pl.when(j == 0)(lambda: _init(m_sc, d_sc, acc_sc))
            vlen = vlen_ref[b]

            @pl.when(j * bs < vlen)
            def _compute():
                # dequantize AFTER the HBM read: int8 page × per-position
                # scale column, both fetched through the same table entry
                k = (k_ref[0, 0].astype(jnp.float32)
                     * ks_ref[0, 0].astype(jnp.float32))    # [BS, D]·[BS, 1]
                v = (v_ref[0, 0].astype(jnp.float32)
                     * vs_ref[0, 0].astype(jnp.float32))
                _update(j, vlen, q_ref, k, v, m_sc, d_sc, acc_sc)

            pl.when(j == n_blocks - 1)(
                lambda: _finalize(o_ref, m_sc, d_sc, acc_sc))
    else:
        def kernel(tbl_ref, vlen_ref, q_ref, k_ref, v_ref, o_ref, m_sc, d_sc,
                   acc_sc):
            b = pl.program_id(0)
            j = pl.program_id(2)      # logical block of row b
            pl.when(j == 0)(lambda: _init(m_sc, d_sc, acc_sc))
            vlen = vlen_ref[b]

            @pl.when(j * bs < vlen)
            def _compute():
                k = k_ref[0, 0].astype(jnp.float32)             # [BS, D]
                v = v_ref[0, 0].astype(jnp.float32)
                _update(j, vlen, q_ref, k, v, m_sc, d_sc, acc_sc)

            pl.when(j == n_blocks - 1)(
                lambda: _finalize(o_ref, m_sc, d_sc, acc_sc))

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

    The KV tile width is the pool's block size: each grid step streams one
    physical block, addressed through the table.  Logical blocks at or past
    ``ceil(valid_len / BS)`` are dead — their table entries may be stale or
    the sentinel — so the index maps clamp to the row's last live block (no
    fetch scheduled, compute skipped via ``pl.when``), and the tail block's
    out-of-range columns are masked to −inf before the online update.

    ``k_scale_pool``/``v_scale_pool`` [P, Hkv, BS, 1] set selects the
    quantized form: the pools are int8 and each grid step additionally
    streams the page's per-position scale column — through the SAME clamped
    table index — dequantizing in VMEM before the online update.  The
    trailing unit axis makes the scale block's last two dims (BS, 1), which
    the TPU compiler accepts; a (1, 1, BS) block of a [P, Hkv, BS] array is
    refused (its second-to-last dim, 1, is neither a multiple of 8 nor Hkv).
    """
    b, hq, dh = q.shape
    _, hkv, bs, _ = k_pool.shape
    m = block_tables.shape[1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, dh)
    quantized = k_scale_pool is not None

    def page_index(tbl_ref, vlen_ref, b_, h, j):
        last = jnp.maximum((vlen_ref[b_] + bs - 1) // bs - 1, 0)
        return (tbl_ref[b_, jnp.minimum(j, last)], h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g, dh),
                     lambda b_, h, j, tbl, vl: (b_, h, 0, 0)),
        pl.BlockSpec((1, 1, bs, dh),
                     lambda b_, h, j, tbl, vl: page_index(tbl, vl, b_,
                                                          h, j)),
        pl.BlockSpec((1, 1, bs, dh),
                     lambda b_, h, j, tbl, vl: page_index(tbl, vl, b_,
                                                          h, j)),
    ]
    operands = [qg, k_pool, v_pool]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, bs, 1),
                         lambda b_, h, j, tbl, vl: page_index(tbl, vl, b_,
                                                              h, j)),
            pl.BlockSpec((1, 1, bs, 1),
                         lambda b_, h, j, tbl, vl: page_index(tbl, vl, b_,
                                                              h, j)),
        ]
        operands += [k_scale_pool, v_scale_pool]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, m),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda b_, h, j, tbl, vl: (b_, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, dh), jnp.float32)],
    )
    out = pl.pallas_call(
        _make_paged_kernel(scale=dh ** -0.5, g=g, bs=bs, n_blocks=m,
                           quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(kv_valid_len, jnp.int32), *operands)
    return out.reshape(b, hq, dh)
