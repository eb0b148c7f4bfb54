"""Public jit'd entry points for the Pallas kernels.

Dispatch policy (MaxText-style fallback):
* On TPU: Pallas kernels with explicit VMEM tiling.
* On CPU (this container, and the multi-pod dry-run): ``interpret=True``
  executes the kernel body faithfully for correctness tests, while the model
  stack uses the semantically-identical XLA implementations in ``repro.core``
  (Pallas can't lower to the CPU target).

Execution mode comes from ``repro.compat.pallas_interpret()`` — the one place
that decides interpret-vs-compiled; path *selection* between Pallas and the
XLA forms lives in ``repro.kernels.dispatch``.  Vocab-axis block sizes
default to the dispatch registry's autotuned per-(backend, vocab, dtype)
choice, and attention tile shapes (``bq``/``bk``) resolve through the same
registry seam (``dispatch.attention_tiles``); pass them explicitly to pin a
shape (kernel tests do).

Autodiff:
* ``flash_attention`` (fresh prefill, ``q_offset``/``kv_valid_len`` unset) is
  differentiable: Pallas forward + Pallas backward via ``jax.custom_vjp``
  (the backward recomputes P from the forward's saved LSE — FlashAttention
  economics).  The *offset* form (cached chunked prefill: queries offset into
  a longer, partially-valid cache) is inference-only — the backward kernels
  have no offset operands yet, so the residual rule is never installed for it
  and a grad through it fails loudly instead of silently mis-masking.
* ``softmax_topk`` is differentiable on every path: the kernel forward saves
  ``(x, values, lse)`` and the backward recomputes the full softmax from the
  saved LSE — the paper's ``(m, d)`` in log form — in one extra pass
  (``softmax_j = e^{x_j - lse}``), so the forward stays single-pass and no
  [R, V] probability matrix is ever stored.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import compat
from repro.core import attention as core_attention
from repro.kernels.flash_attention import (
    flash_attention_offset_pallas,
    flash_attention_paged_pallas,
    flash_attention_pallas,
)
from repro.kernels.flash_decode import (
    flash_decode_paged_pallas,
    flash_decode_pallas,
)
from repro.kernels.online_softmax import (
    online_normalizer_pallas,
    online_softmax_pallas,
)
from repro.kernels.softmax_topk import softmax_topk_pallas

Array = jax.Array


def _v_blk(v: int, v_blk: int | None, dtype) -> int:
    if v_blk is None:
        from repro.kernels.dispatch import tuned_block
        v_blk = tuned_block(v, dtype)
    return _largest_divisor_block(v, v_blk)


def online_softmax(x: Array, *, r_blk: int = 256,
                   v_blk: int | None = None) -> Array:
    """Softmax over the last axis; any leading batch shape."""
    lead = x.shape[:-1]
    v = x.shape[-1]
    x2 = x.reshape(-1, v)
    r = x2.shape[0]
    y = online_softmax_pallas(x2, r_blk=_largest_divisor_block(r, r_blk),
                              v_blk=_v_blk(v, v_blk, x.dtype),
                              interpret=compat.pallas_interpret())
    return y.reshape(*lead, v)


def online_normalizer(x: Array, *, r_blk: int = 256,
                      v_blk: int | None = None):
    lead = x.shape[:-1]
    v = x.shape[-1]
    x2 = x.reshape(-1, v)
    m, d = online_normalizer_pallas(
        x2, r_blk=_largest_divisor_block(x2.shape[0], r_blk),
        v_blk=_v_blk(v, v_blk, x.dtype),
        interpret=compat.pallas_interpret())
    return m.reshape(lead), d.reshape(lead)


# ---------------------------------------------------------------------------
# Differentiable fused softmax+top-k: Pallas forward, recompute-from-LSE
# backward.  The custom_vjp lives on the 2-D core; the public wrapper only
# reshapes.
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _softmax_topk2d(x, k, r_blk, v_blk):
    vals, idx, lse = softmax_topk_pallas(
        x, k, r_blk=r_blk, v_blk=v_blk, interpret=compat.pallas_interpret())
    return vals, idx, lse


def _softmax_topk2d_fwd(x, k, r_blk, v_blk):
    out = _softmax_topk2d(x, k, r_blk, v_blk)
    vals, idx, lse = out
    return out, (x, vals, idx, lse)


def _softmax_topk2d_bwd(k, r_blk, v_blk, res, dout):
    """∂/∂x of (values, lse): values_i = e^{x_{p_i} − lse}, lse = logsumexp.

    dx_j = softmax_j · (dlse − Σᵢ dvalᵢ·valᵢ) + [j = pᵢ]·dvalᵢ·valᵢ, with
    softmax recomputed from the saved LSE (one extra pass over x; nothing
    beyond (values, indices, lse) was stored by the forward).  ``indices``
    is integer-valued — its cotangent is discarded.
    """
    x, vals, idx, lse = res
    dvals, _, dlse = dout
    r = x.shape[0]
    xf = x.astype(jnp.float32)
    s = jnp.exp(xf - lse[:, None])                       # [R, V]
    dv_v = dvals.astype(jnp.float32) * vals.astype(jnp.float32)   # [R, K]
    coeff = dlse.astype(jnp.float32) - jnp.sum(dv_v, axis=-1)     # [R]
    dx = s * coeff[:, None]
    dx = dx.at[jnp.arange(r)[:, None], idx].add(dv_v)
    return (dx.astype(x.dtype),)


_softmax_topk2d.defvjp(_softmax_topk2d_fwd, _softmax_topk2d_bwd)


def softmax_topk(x: Array, k: int, *, r_blk: int = 256,
                 v_blk: int | None = None):
    lead = x.shape[:-1]
    v = x.shape[-1]
    x2 = x.reshape(-1, v)
    vals, idx, lse = _softmax_topk2d(
        x2, k, _largest_divisor_block(x2.shape[0], r_blk),
        _v_blk(v, v_blk, x.dtype))
    return (vals.reshape(*lead, k), idx.reshape(*lead, k), lse.reshape(lead))


def _largest_divisor_block(n: int, target: int) -> int:
    target = min(target, n)
    while n % target:
        target -= 1
    return target


# ---------------------------------------------------------------------------
# Differentiable flash attention: Pallas forward, XLA-chunked backward.
# Layout here matches the model stack: q [B,Tq,Hq,D]; k,v [B,Tk,Hkv,D].
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, bq, bk):
    out, _ = _flash_fwd_impl(q, k, v, causal, bq, bk)
    return out


def _flash_fwd_impl(q, k, v, causal, bq, bk):
    qh = jnp.swapaxes(q, 1, 2)       # [B,Hq,Tq,D]
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    out, lse = flash_attention_pallas(qh, kh, vh, causal=causal, bq=bq, bk=bk,
                                      interpret=compat.pallas_interpret())
    return jnp.swapaxes(out, 1, 2), lse


def _flash_fwd(q, k, v, causal, bq, bk):
    out, lse = _flash_fwd_impl(q, k, v, causal, bq, bk)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, bq, bk, res, dout):
    """Backward: Pallas dq/dkv kernels (interpret on CPU); recomputes P from
    the forward's saved LSE — the paper's (m, d) in log form.

    Only the fresh-prefill forward (self-aligned q/k, fully-valid KV) installs
    this rule; the backward kernels have no ``q_offset``/``kv_valid_len``
    operands, so the offset forward below stays out of the custom_vjp."""
    from repro.kernels.flash_attention_bwd import flash_attention_bwd_pallas
    q, k, v, out, lse = res
    b, tq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    oh = jnp.swapaxes(out, 1, 2)
    doh = jnp.swapaxes(dout, 1, 2)
    dq, dk_h, dv_h = flash_attention_bwd_pallas(
        qh, kh, vh, oh, lse, doh, causal=causal, bq=bq, bk=bk,
        interpret=compat.pallas_interpret())
    # reduce per-Q-head dk/dv into KV heads (GQA)
    tk = k.shape[1]
    dk = dk_h.reshape(b, hkv, g, tk, dh).sum(axis=2)
    dv = dv_h.reshape(b, hkv, g, tk, dh).sum(axis=2)
    return (jnp.swapaxes(dq, 1, 2).astype(q.dtype),
            jnp.swapaxes(dk, 1, 2).astype(k.dtype),
            jnp.swapaxes(dv, 1, 2).astype(v.dtype))


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    bq: int | None = None, bk: int | None = None,
                    q_offset: Array | None = None,
                    kv_valid_len: Array | None = None) -> Array:
    """Online-softmax attention (Pallas fwd on TPU).

    q [B, Tq, Hq, D]; k, v [B, Tk, Hkv, D] (grouped-query: Hq a multiple
    of Hkv) → out [B, Tq, Hq, D].

    ``causal``: mask queries from keys after them (in absolute coordinates
    when the serving operands below are set); False runs full
    cross-attention over the valid prefix.

    ``bq``/``bk`` unset → the dispatch registry's resolved tiles (kernel
    tests pin explicit values; nothing here is hard-coded).

    ``q_offset``/``kv_valid_len`` unset → the fresh-prefill differentiable
    form (training path).  Set, they select the serving form: ``q_offset``
    (scalar or [B]) is the absolute position of query row 0 and
    ``kv_valid_len`` (scalar or [B]) the per-row valid cache prefix; causal
    masking runs in absolute coordinates and out-of-range KV columns are
    masked before the online update.  KV is padded up to a tile multiple
    (padded columns sit past ``kv_valid_len``, so the mask erases them) —
    this form is inference-only (no VJP installed).  This is the operand
    pair the serving stack threads per slot: the scheduler's chunked
    prefill passes ``q_offset = cache_len`` (a [B] vector under continuous
    batching, including a just-swapped-in sequence resuming at its
    pre-preemption length) and ``kv_valid_len = cache_len + chunk``."""
    if bq is None or bk is None:
        from repro.kernels.dispatch import attention_tiles
        offset_form = q_offset is not None or kv_valid_len is not None
        tiles = attention_tiles(
            "flash_attention_offset" if offset_form else "flash_attention",
            kv_len=k.shape[1], head_dim=q.shape[-1], dtype=q.dtype)
        bq = tiles["bq"] if bq is None else bq
        bk = tiles["bk"] if bk is None else bk
    bq = _largest_divisor_block(q.shape[1], bq)
    if q_offset is None and kv_valid_len is None:
        bk = _largest_divisor_block(k.shape[1], bk)
        return _flash(q, k, v, causal, bq, bk)
    return _flash_offset(q, k, v, q_offset, kv_valid_len, causal, bq, bk)


def _flash_offset(q, k, v, q_offset, kv_valid_len, causal, bq, bk):
    """Cached-prefill flash attention (model layout), inference-only."""
    b, tq, _, _ = q.shape
    tk = k.shape[1]
    if q_offset is None:
        q_offset = 0
    if kv_valid_len is None:
        kv_valid_len = tk
    q_offset = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    kv_valid_len = jnp.minimum(
        jnp.broadcast_to(jnp.asarray(kv_valid_len, jnp.int32), (b,)), tk)
    bk = min(bk, tk)
    pad_k = -tk % bk
    if pad_k:     # padded KV columns sit at positions ≥ kv_valid_len: masked
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    out, _ = flash_attention_offset_pallas(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        q_offset, kv_valid_len, causal=causal, bq=bq, bk=bk,
        interpret=compat.pallas_interpret())
    return jnp.swapaxes(out, 1, 2)


def flash_decode(q: Array, k_cache: Array, v_cache: Array,
                 kv_valid_len: Array, *, bk: int | None = None) -> Array:
    """Decode attention: q [B,Hq,D] vs caches [B,S,Hkv,D] → [B,Hq,D].

    ``kv_valid_len`` [B] masks each row's cache tail independently — the
    per-slot length vector of the continuous-batching pool flows in here.
    ``bk`` unset → the registry's swept decode tile for this cache length."""
    kh = jnp.swapaxes(k_cache, 1, 2)   # [B,Hkv,S,D]
    vh = jnp.swapaxes(v_cache, 1, 2)
    if bk is None:
        from repro.kernels.dispatch import attention_tiles
        bk = attention_tiles("flash_decode", kv_len=k_cache.shape[1],
                             head_dim=q.shape[-1], dtype=q.dtype)["bk"]
    bk = _largest_divisor_block(kh.shape[2], bk)
    return flash_decode_pallas(q, kh, vh, kv_valid_len, bk=bk,
                               interpret=compat.pallas_interpret())


# ---------------------------------------------------------------------------
# Paged KV cache: block-pool + block-table forms (inference-only).
# Pool layout is the kernel-native [P, Hkv, BS, D] — one physical block is
# one KV tile, so the kernels gather pages with zero re-layout on the hot
# path.  Block tables are CONSUMED here; building them is the exclusive
# business of ``repro.serving.paged`` (grep-enforced).
# ---------------------------------------------------------------------------
def _scale_column(pool: Array | None) -> Array | None:
    """Scale pages [P, Hkv, BS] → the kernels' [P, Hkv, BS, 1] view (a free
    reshape; the cache layout keeps the block axis at leaf position 1)."""
    return None if pool is None else pool[..., None]


def paged_flash_decode(q: Array, k_pool: Array, v_pool: Array,
                       block_tables: Array, kv_valid_len: Array, *,
                       k_scale_pool: Array | None = None,
                       v_scale_pool: Array | None = None) -> Array:
    """Paged decode attention: q [B,Hq,D]; pools [P,Hkv,BS,D]; block_tables
    [B,M]; kv_valid_len [B] → [B,Hq,D].

    The kernel walks each row's live pages in compute blocks of
    ``flash_decode.BLOCK_TOKENS`` tokens (no free tile knob), so nothing
    resolves through ``attention_tiles`` here.
    ``k_scale_pool``/``v_scale_pool`` [P,Hkv,BS] select the quantized (int8
    pools + per-position scale pages) form."""
    return flash_decode_paged_pallas(q, k_pool, v_pool, block_tables,
                                     kv_valid_len,
                                     k_scale_pool=_scale_column(k_scale_pool),
                                     v_scale_pool=_scale_column(v_scale_pool),
                                     interpret=compat.pallas_interpret())


def paged_flash_attention(q: Array, k_pool: Array, v_pool: Array,
                          q_offset: Array, kv_valid_len: Array,
                          block_tables: Array, *, causal: bool = True,
                          bq: int | None = None,
                          k_scale_pool: Array | None = None,
                          v_scale_pool: Array | None = None) -> Array:
    """Paged cached-prefill flash attention (model layout), inference-only.

    q [B, Tq, Hq, D]; pools [P, Hkv, BS, D]; q_offset / kv_valid_len [B];
    block_tables [B, M] → out [B, Tq, Hq, D].  ``bq`` unset resolves through
    the registry's paged-prefill sweep; the KV tile is pinned to the pool
    block size.  ``k_scale_pool``/``v_scale_pool`` [P, Hkv, BS] select the
    quantized (int8 pools + per-position scale pages) form."""
    b, tq = q.shape[:2]
    bs = k_pool.shape[2]
    if bq is None:
        from repro.kernels.dispatch import attention_tiles
        bq = attention_tiles("flash_attention_paged",
                             kv_len=block_tables.shape[1] * bs,
                             head_dim=q.shape[-1], dtype=q.dtype)["bq"]
    bq = _largest_divisor_block(tq, bq)
    q_offset = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    kv_valid_len = jnp.broadcast_to(jnp.asarray(kv_valid_len, jnp.int32),
                                    (b,))
    out, _ = flash_attention_paged_pallas(
        jnp.swapaxes(q, 1, 2), k_pool, v_pool, q_offset, kv_valid_len,
        block_tables, causal=causal, bq=bq,
        k_scale_pool=_scale_column(k_scale_pool),
        v_scale_pool=_scale_column(v_scale_pool),
        interpret=compat.pallas_interpret())
    return jnp.swapaxes(out, 1, 2)
