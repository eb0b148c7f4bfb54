"""Pallas TPU kernel: FlashAttention forward — the paper's Algorithm 3 carried
through attention (``(m, d)`` plus a weighted-value accumulator in VMEM).

Grid: (batch, q_head, q_block, kv_block), kv innermost.  GQA is handled by the
K/V index_map (``h // group``) — no materialized head repeat.  With
``causal=True``, KV tiles strictly above the diagonal are skipped via
``pl.when`` (compute never issued; the tile fetch is still scheduled by the
grid — see §Perf for the measured effect of tightening this).

Accumulators (m, d, acc) are fp32 VMEM scratch; output and LSE are written
once per q-block when the kv sweep finishes.

Two entry points share the masking math:

* ``flash_attention_pallas`` — the fresh-prefill / training form: queries and
  keys are self-aligned (query row i is absolute position i), every KV
  position is valid.  This is the differentiable path (``ops.flash_attention``
  wraps it in a custom VJP).
* ``flash_attention_offset_pallas`` — the serving form: ``q_offset`` [B] is
  the absolute position of query row 0 (per batch row, scalar-prefetched to
  SMEM) and ``kv_valid_len`` [B] is the number of valid cache positions per
  row.  Causal masking runs in absolute coordinates
  (``k_pos <= q_offset + i``), columns at or past ``kv_valid_len`` are masked
  to −inf before the online-softmax update, and KV tiles entirely past the
  valid length (or entirely above the causal diagonal) are skipped two ways:
  ``pl.when`` skips their compute, and the K/V index maps clamp the block
  index to the last live tile so the pipeline schedules no new fetch for
  them — ragged slots don't pay HBM traffic for dead tiles.  This is what
  lets cached chunked prefill (queries offset into a longer, partially-valid
  cache) run on the kernel instead of the chunked XLA fallback.
* ``flash_attention_paged_pallas`` — the paged-KV serving form: same masking
  math as the offset kernel, but K/V live in a shared pool of fixed-size
  blocks and a scalar-prefetched ``[B, max_blocks]`` block table maps each
  row's logical blocks to physical pool blocks.  The K/V index maps gather
  one pool block per grid step (the tile width IS the block size); dead
  table entries clamp to the last live block so they are never dereferenced.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _init_scratch(m_sc, d_sc, acc_sc):
    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    d_sc[...] = jnp.zeros_like(d_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)


def _online_update(s, v, m_sc, d_sc, acc_sc):
    """One ⊕ step of Algorithm 3 over a masked score tile ``s`` [BQ, BK]:
    rescale the carried (m, d, acc) and fold the tile in.  Shared verbatim by
    the offsetless and offset kernels so their numerics cannot drift."""
    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    alpha = jnp.exp(jnp.where(m_prev == m_new, 0.0, m_prev - m_new))
    p = jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - m_new))
    d_sc[...] = d_sc[...] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_sc[...] = acc_sc[...] * alpha + p @ v
    m_sc[...] = m_new


def _make_kernel(*, scale: float, causal: bool, bq: int, bk: int, n_kv: int):
    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, d_sc, acc_sc):
        i = pl.program_id(2)          # q block
        j = pl.program_id(3)          # kv block

        @pl.when(j == 0)
        def _init():
            _init_scratch(m_sc, d_sc, acc_sc)

        # causal: skip tiles entirely above the diagonal
        run = (not causal) or (j * bk <= i * bq + bq - 1)

        @pl.when(run)
        def _compute():
            q = q_ref[0, 0].astype(jnp.float32) * scale      # [BQ, D]
            k = k_ref[0, 0].astype(jnp.float32)              # [BK, D]
            v = v_ref[0, 0].astype(jnp.float32)
            s = q @ k.T                                   # [BQ, BK] (MXU)
            if causal:
                q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                          (bq, bk), 0)
                k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                          (bq, bk), 1)
                s = jnp.where(k_pos <= q_pos, s, NEG_INF)
            _online_update(s, v, m_sc, d_sc, acc_sc)

        @pl.when(j == n_kv - 1)
        def _finalize():
            d = jnp.maximum(d_sc[...], 1e-30)
            o_ref[0, 0] = (acc_sc[...] / d).astype(o_ref.dtype)
            lse_ref[0, 0] = m_sc[...] + jnp.log(d)

    return kernel


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, bq: int = 512, bk: int = 512,
                           interpret: bool = False):
    """q [B, Hq, Tq, D]; k, v [B, Hkv, Tk, D] → (out [B,Hq,Tq,D], lse [B,Hq,Tq,1]).

    Tq % bq == 0 and Tk % bk == 0 (pad upstream in ops.py).
    """
    b, hq, tq, dh = q.shape
    _, hkv, tk, _ = k.shape
    g = hq // hkv
    bq = min(bq, tq)
    bk = min(bk, tk)
    assert tq % bq == 0 and tk % bk == 0
    n_kv = tk // bk
    grid = (b, hq, tq // bq, n_kv)
    scale = dh ** -0.5
    out, lse = pl.pallas_call(
        _make_kernel(scale=scale, causal=causal, bq=bq, bk=bk, n_kv=n_kv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h, i, j: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h, i, j: (b_, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, hq, tq, dh), q.dtype),
                   jax.ShapeDtypeStruct((b, hq, tq, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dh), jnp.float32)],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Offset / valid-length form: cached (chunked) prefill on the kernel.
# ---------------------------------------------------------------------------
def _make_offset_kernel(*, scale: float, causal: bool, bq: int, bk: int,
                        n_kv: int):
    def kernel(qoff_ref, vlen_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
               m_sc, d_sc, acc_sc):
        b = pl.program_id(0)
        i = pl.program_id(2)          # q block
        j = pl.program_id(3)          # kv block

        @pl.when(j == 0)
        def _init():
            _init_scratch(m_sc, d_sc, acc_sc)

        qoff = qoff_ref[b]
        vlen = vlen_ref[b]
        # live tile: starts inside the valid cache, and (causal) at or below
        # the absolute diagonal of this q block's last row
        run = j * bk < vlen
        if causal:
            run = jnp.logical_and(run, j * bk <= qoff + i * bq + bq - 1)

        @pl.when(run)
        def _compute():
            q = q_ref[0, 0].astype(jnp.float32) * scale      # [BQ, D]
            k = k_ref[0, 0].astype(jnp.float32)              # [BK, D]
            v = v_ref[0, 0].astype(jnp.float32)
            s = q @ k.T                                      # [BQ, BK]
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = k_pos < vlen
            if causal:
                # absolute coordinates: query row i_local sits at qoff+i_local
                q_pos = qoff + i * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0)
                mask = jnp.logical_and(mask, k_pos <= q_pos)
            _online_update(jnp.where(mask, s, NEG_INF), v, m_sc, d_sc, acc_sc)

        @pl.when(j == n_kv - 1)
        def _finalize():
            d = jnp.maximum(d_sc[...], 1e-30)
            o_ref[0, 0] = (acc_sc[...] / d).astype(o_ref.dtype)
            lse_ref[0, 0] = jnp.where(d_sc[...] > 0,
                                      m_sc[...] + jnp.log(d), NEG_INF)

    return kernel


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention_offset_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                                  q_offset: jax.Array,
                                  kv_valid_len: jax.Array, *,
                                  causal: bool = True, bq: int = 512,
                                  bk: int = 512, interpret: bool = False):
    """Cached-prefill flash attention: absolute-position causal masking plus
    per-row valid-length masking.

    q [B, Hq, Tq, D]; k, v [B, Hkv, Tk, D]; q_offset [B] (absolute position
    of query row 0 per batch row); kv_valid_len [B] (valid cache prefix per
    row) → (out [B,Hq,Tq,D], lse [B,Hq,Tq,1]).  Tq % bq == 0 and Tk % bk == 0
    (pad upstream in ops.py — padded KV columns sit at positions ≥
    ``kv_valid_len`` and are masked).

    Dead KV tiles (entirely past ``kv_valid_len``, or entirely above the
    causal diagonal) skip compute via ``pl.when`` AND skip their HBM→VMEM
    fetch: the K/V index maps clamp the block index to the last live tile of
    the row, so the pipeline re-addresses an already-resident block instead
    of scheduling a new copy.
    """
    b, hq, tq, dh = q.shape
    _, hkv, tk, _ = k.shape
    g = hq // hkv
    bq = min(bq, tq)
    bk = min(bk, tk)
    assert tq % bq == 0 and tk % bk == 0
    n_kv = tk // bk
    scale = dh ** -0.5
    q_offset = jnp.asarray(q_offset, jnp.int32).reshape(b)
    kv_valid_len = jnp.asarray(kv_valid_len, jnp.int32).reshape(b)

    def last_live_tile(b_, i, qoff_ref, vlen_ref):
        # last tile index any row of this (b, i) block may touch
        last = jnp.maximum((vlen_ref[b_] + bk - 1) // bk - 1, 0)
        if causal:
            diag = (qoff_ref[b_] + i * bq + bq - 1) // bk
            last = jnp.minimum(last, jnp.maximum(diag, 0))
        return last

    def kv_index(qoff_ref, vlen_ref, b_, h, i, j):
        return (b_, h // g, jnp.minimum(j, last_live_tile(b_, i, qoff_ref,
                                                          vlen_ref)), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hq, tq // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh),
                         lambda b_, h, i, j, qo, vl: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h, i, j, qo, vl: kv_index(qo, vl, b_, h,
                                                              i, j)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h, i, j, qo, vl: kv_index(qo, vl, b_, h,
                                                              i, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dh),
                         lambda b_, h, i, j, qo, vl: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b_, h, i, j, qo, vl: (b_, h, i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dh), jnp.float32)],
    )
    out, lse = pl.pallas_call(
        _make_offset_kernel(scale=scale, causal=causal, bq=bq, bk=bk,
                            n_kv=n_kv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hq, tq, dh), q.dtype),
                   jax.ShapeDtypeStruct((b, hq, tq, 1), jnp.float32)],
        interpret=interpret,
    )(q_offset, kv_valid_len, q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Paged form: offset/valid-length prefill over a block pool + block table.
# ---------------------------------------------------------------------------
def _make_paged_kernel(*, scale: float, causal: bool, bq: int, bs: int,
                       n_blocks: int, quantized: bool = False):
    def body(b, i, j, q_ref, load_kv, o_ref, lse_ref, m_sc, d_sc, acc_sc,
             qoff_ref, vlen_ref):
        pl.when(j == 0)(lambda: _init_scratch(m_sc, d_sc, acc_sc))
        qoff = qoff_ref[b]
        vlen = vlen_ref[b]
        # live block: starts inside the valid cache, and (causal) at or below
        # the absolute diagonal of this q block's last row
        run = j * bs < vlen
        if causal:
            run = jnp.logical_and(run, j * bs <= qoff + i * bq + bq - 1)

        @pl.when(run)
        def _compute():
            q = q_ref[0, 0].astype(jnp.float32) * scale      # [BQ, D]
            k, v = load_kv()                                 # [BS, D] fp32
            s = q @ k.T                                      # [BQ, BS]
            k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 1)
            mask = k_pos < vlen
            if causal:
                q_pos = qoff + i * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bs), 0)
                mask = jnp.logical_and(mask, k_pos <= q_pos)
            _online_update(jnp.where(mask, s, NEG_INF), v, m_sc, d_sc, acc_sc)

        @pl.when(j == n_blocks - 1)
        def _finalize():
            d = jnp.maximum(d_sc[...], 1e-30)
            o_ref[0, 0] = (acc_sc[...] / d).astype(o_ref.dtype)
            lse_ref[0, 0] = jnp.where(d_sc[...] > 0,
                                      m_sc[...] + jnp.log(d), NEG_INF)

    if quantized:
        def kernel(qoff_ref, vlen_ref, tbl_ref, q_ref, k_ref, v_ref, ks_ref,
                   vs_ref, o_ref, lse_ref, m_sc, d_sc, acc_sc):
            del tbl_ref               # consumed by the index maps only
            b = pl.program_id(0)
            i = pl.program_id(2)      # q block
            j = pl.program_id(3)      # logical KV block of row b

            def load_kv():
                # dequantize AFTER the HBM read: int8 page × per-position
                # scale column, gathered through the same clamped table entry
                return ((k_ref[0, 0].astype(jnp.float32)
                         * ks_ref[0, 0].astype(jnp.float32)),   # [BS, 1]
                        (v_ref[0, 0].astype(jnp.float32)
                         * vs_ref[0, 0].astype(jnp.float32)))

            body(b, i, j, q_ref, load_kv, o_ref, lse_ref, m_sc, d_sc, acc_sc,
                 qoff_ref, vlen_ref)
    else:
        def kernel(qoff_ref, vlen_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref,
                   lse_ref, m_sc, d_sc, acc_sc):
            del tbl_ref               # consumed by the index maps only
            b = pl.program_id(0)
            i = pl.program_id(2)      # q block
            j = pl.program_id(3)      # logical KV block of row b

            def load_kv():
                return (k_ref[0, 0].astype(jnp.float32),
                        v_ref[0, 0].astype(jnp.float32))

            body(b, i, j, q_ref, load_kv, o_ref, lse_ref, m_sc, d_sc, acc_sc,
                 qoff_ref, vlen_ref)

    return kernel


@functools.partial(jax.jit, static_argnames=("causal", "bq", "interpret"))
def flash_attention_paged_pallas(q: jax.Array, k_pool: jax.Array,
                                 v_pool: jax.Array, q_offset: jax.Array,
                                 kv_valid_len: jax.Array,
                                 block_tables: jax.Array, *,
                                 k_scale_pool: jax.Array | None = None,
                                 v_scale_pool: jax.Array | None = None,
                                 causal: bool = True, bq: int = 512,
                                 interpret: bool = False):
    """Paged cached-prefill flash attention.

    q [B, Hq, Tq, D]; pools [P, Hkv, BS, D] (a shared pool of fixed-size KV
    blocks); q_offset [B]; kv_valid_len [B]; block_tables [B, M] (physical
    pool block per logical block, scalar-prefetched) →
    (out [B,Hq,Tq,D], lse [B,Hq,Tq,1]).  Tq % bq == 0 (pad upstream).

    The KV tile is one pool block, gathered through the table by the K/V
    index maps.  Dead logical blocks (entirely past ``kv_valid_len`` or
    entirely above the causal diagonal) clamp to the row's last live block —
    their table entries are never read as addresses and no fetch is
    scheduled — and partial tail blocks mask out-of-range columns to −inf
    before the online-softmax update, exactly like the contiguous offset
    kernel above.  The online ``(m, d)`` carry (paper Alg. 3) is what makes
    one pass over an arbitrary page list correct.

    ``k_scale_pool``/``v_scale_pool`` [P, Hkv, BS, 1] set selects the
    quantized form: int8 pools plus per-position scale pages gathered through
    the SAME clamped table index and applied in VMEM before the online
    update (the trailing unit axis keeps the scale block TPU-tileable, as in
    ``flash_decode_paged_pallas``).
    """
    b, hq, tq, dh = q.shape
    _, hkv, bs, _ = k_pool.shape
    m = block_tables.shape[1]
    g = hq // hkv
    bq = min(bq, tq)
    assert tq % bq == 0
    scale = dh ** -0.5
    q_offset = jnp.asarray(q_offset, jnp.int32).reshape(b)
    kv_valid_len = jnp.asarray(kv_valid_len, jnp.int32).reshape(b)
    quantized = k_scale_pool is not None

    def last_live_block(b_, i, qoff_ref, vlen_ref):
        last = jnp.maximum((vlen_ref[b_] + bs - 1) // bs - 1, 0)
        if causal:
            diag = (qoff_ref[b_] + i * bq + bq - 1) // bs
            last = jnp.minimum(last, jnp.maximum(diag, 0))
        return last

    def kv_index(qoff_ref, vlen_ref, tbl_ref, b_, h, i, j):
        jc = jnp.minimum(j, last_live_block(b_, i, qoff_ref, vlen_ref))
        return (tbl_ref[b_, jc], h // g, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, bq, dh),
                     lambda b_, h, i, j, qo, vl, tbl: (b_, h, i, 0)),
        pl.BlockSpec((1, 1, bs, dh),
                     lambda b_, h, i, j, qo, vl, tbl: kv_index(
                         qo, vl, tbl, b_, h, i, j)),
        pl.BlockSpec((1, 1, bs, dh),
                     lambda b_, h, i, j, qo, vl, tbl: kv_index(
                         qo, vl, tbl, b_, h, i, j)),
    ]
    operands = [q, k_pool, v_pool]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, bs, 1),
                         lambda b_, h, i, j, qo, vl, tbl: kv_index(
                             qo, vl, tbl, b_, h, i, j)),
            pl.BlockSpec((1, 1, bs, 1),
                         lambda b_, h, i, j, qo, vl, tbl: kv_index(
                             qo, vl, tbl, b_, h, i, j)),
        ]
        operands += [k_scale_pool, v_scale_pool]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hq, tq // bq, m),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, dh),
                         lambda b_, h, i, j, qo, vl, tbl: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b_, h, i, j, qo, vl, tbl: (b_, h, i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dh), jnp.float32)],
    )
    out, lse = pl.pallas_call(
        _make_paged_kernel(scale=scale, causal=causal, bq=bq, bs=bs,
                           n_blocks=m, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hq, tq, dh), q.dtype),
                   jax.ShapeDtypeStruct((b, hq, tq, 1), jnp.float32)],
        interpret=interpret,
    )(q_offset, kv_valid_len, jnp.asarray(block_tables, jnp.int32),
      *operands)
    return out, lse
