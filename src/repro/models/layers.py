"""Shared model layers, parameter system, and sharding annotations.

Parameters are plain nested dicts of ``jax.Array``.  Every ``*_init`` builds a
matching *logical-axis* tree (tuples of axis names per leaf) alongside the
values via the ``Param`` box; ``split_params`` separates them.  Logical names
("embed", "ffn", "heads", "vocab", "expert", …) are mapped to mesh axes by
``repro.distributed.sharding`` — the model code never mentions a mesh.

Attention comes in two implementations of the same math:
* ``repro.core.online_attention`` — chunked online-softmax (XLA; default, and
  the thing the multi-pod dry-run lowers), and
* ``repro.kernels.ops.flash_attention`` — the Pallas TPU kernel
  (``cfg.use_pallas``).
Which one runs is resolved by ``repro.kernels.dispatch`` against the probed
backend capabilities; this module only states preferences.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import compat, core
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

Array = jax.Array
PyTree = Any


# ---------------------------------------------------------------------------
# Parameter boxing: value + logical axes in one leaf, split after init.
# ---------------------------------------------------------------------------
class Param(NamedTuple):
    value: Array
    axes: tuple


def is_param(x) -> bool:
    return isinstance(x, Param)


def split_params(tree: PyTree) -> tuple[PyTree, PyTree]:
    values = compat.tree_map(lambda p: p.value, tree, is_leaf=is_param)
    axes = compat.tree_map(lambda p: p.axes, tree, is_leaf=is_param)
    return values, axes


def stack_layer_init(init_fn, key, n: int) -> PyTree:
    """vmap an init over ``n`` layer keys, stacking values on a leading
    "layers" axis.  (The string axes inside Param boxes can't be vmapped, so
    values are batched separately and re-boxed.)"""
    keys = jax.random.split(key, n)
    template = init_fn(keys[0])
    boxes = compat.tree_leaves(template, is_leaf=is_param)
    treedef = compat.tree_structure(template, is_leaf=is_param)

    def values_only(k):
        return [p.value for p in compat.tree_leaves(init_fn(k), is_leaf=is_param)]

    stacked = jax.vmap(values_only)(keys)
    reboxed = [Param(v, ("layers",) + p.axes) for v, p in zip(stacked, boxes)]
    return compat.tree_unflatten(treedef, reboxed)


def _dense_init(key, shape, axes, *, scale: Optional[float] = None,
                dtype=jnp.float32) -> Param:
    fan_in = shape[0] if len(shape) > 1 else shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    v = jax.random.normal(key, shape, dtype=jnp.float32) * scale
    return Param(v.astype(dtype), axes)


def _zeros(shape, axes, dtype=jnp.float32) -> Param:
    return Param(jnp.zeros(shape, dtype), axes)


def _ones(shape, axes, dtype=jnp.float32) -> Param:
    return Param(jnp.ones(shape, dtype), axes)


# ---------------------------------------------------------------------------
# Norms & positional encoding.
# ---------------------------------------------------------------------------
def rms_norm_init(cfg: ModelConfig, d: Optional[int] = None) -> PyTree:
    return {"scale": _ones((d or cfg.d_model,), ("embed",))}


def rms_norm(p: PyTree, x: Array, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)


def layer_norm_init(cfg: ModelConfig, d: Optional[int] = None) -> PyTree:
    d = d or cfg.d_model
    return {"scale": _ones((d,), ("embed",)), "bias": _zeros((d,), ("embed",))}


def layer_norm(p: PyTree, x: Array, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"]
            + p["bias"]).astype(x.dtype)


def rope(x: Array, positions: Array, theta: float) -> Array:
    """Rotary embedding. x [..., T, H, D_rot]; positions [..., T] or [T]."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., :, None].astype(jnp.float32) * freqs   # [..,T,D/2]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense GQA attention.
# ---------------------------------------------------------------------------
def attention_init(key, cfg: ModelConfig) -> PyTree:
    """Projections are stored FLAT ([D, H·hd]) under the "qkv_out"/"kv_out"
    logical axes: H·hd shards over the model axis even when H itself does not
    divide it (the sequence-parallel fallback then reshards activations, not
    weights — DESIGN.md §4)."""
    d, hq, hkv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense_init(ks[0], (d, hq * hd), ("embed", "qkv_out"), dtype=dt),
        "wk": _dense_init(ks[1], (d, hkv * hd), ("embed", "kv_out"), dtype=dt),
        "wv": _dense_init(ks[2], (d, hkv * hd), ("embed", "kv_out"), dtype=dt),
        "wo": _dense_init(ks[3], (hq * hd, d), ("qkv_out", "embed"), dtype=dt),
    }


def _shard_ctx():
    from repro.distributed import context
    return context.get()


def cache_write(cache_arr: Array, new: Array, cache_len) -> Array:
    """Write ``new`` ([B, t, ...]) into ``cache_arr`` ([B, S, ...]) at offset
    ``cache_len`` along the sequence axis.

    A scalar ``cache_len`` is the lockstep-batch case (one shared offset); a
    ``[B]`` vector writes each row at its own offset — the continuous-batching
    slot pool, where every cache slot holds a sequence of different length.
    """
    ln = jnp.asarray(cache_len, jnp.int32)
    new = new.astype(cache_arr.dtype)
    if ln.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache_arr, new, ln, axis=1)
    return jax.vmap(
        lambda c, n, l: jax.lax.dynamic_update_slice_in_dim(c, n, l, axis=0)
    )(cache_arr, new, ln)


def paged_cache_write(pool: Array, new: Array, cache_len,
                      block_tables: Array) -> Array:
    """Write ``new`` ([B, t, Hkv, D]) into the block pool ([P, Hkv, BS, D])
    through the block table ([B, M]).

    Row b's position ``cache_len[b] + i`` lands in physical block
    ``block_tables[b, pos // BS]`` at offset ``pos % BS``.  The allocator
    guarantees distinct rows never write the same (block, offset) — shared
    prefix blocks are copy-on-write'd by ``serving.paged`` before any write —
    except idle rows (length 0), which all land harmlessly in the sentinel
    block the allocator never hands out.  Scoped ``pool.write`` in the
    program's op metadata."""
    with jax.named_scope("pool.write"):
        b, t = new.shape[:2]
        bs = pool.shape[2]
        ln = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
        pos = ln[:, None] + jnp.arange(t, dtype=jnp.int32)      # [B, t]
        bids = jnp.take_along_axis(jnp.asarray(block_tables, jnp.int32),
                                   pos // bs, axis=1)
        offs = pos % bs
        flat = new.astype(pool.dtype).reshape((b * t,) + new.shape[2:])
        return pool.at[bids.reshape(-1), :, offs.reshape(-1)].set(flat)


def _valid_len(cache_len, t: int, b: int) -> Array:
    """Per-row valid KV length after writing ``t`` new positions."""
    return jnp.broadcast_to(jnp.asarray(cache_len + t, jnp.int32), (b,))


def _sdpa(cfg: ModelConfig, q, k, v, *, causal, q_offset, kv_valid_len,
          scale: Optional[float] = None, decode: bool = False,
          k_scale=None, v_scale=None, block_tables=None):
    """Attention via the capability-probing registry (kernels.dispatch):
    shard_map ⊕-merge decode / Pallas (compiled or interpret) / XLA chunked;
    ``block_tables`` set routes the paged block-pool forms."""
    from repro.kernels import dispatch
    return dispatch.sdpa(cfg, q, k, v, causal=causal, q_offset=q_offset,
                         kv_valid_len=kv_valid_len, scale=scale,
                         decode=decode, k_scale=k_scale, v_scale=v_scale,
                         block_tables=block_tables)


def _quantize_kv(x: Array) -> tuple[Array, Array]:
    """Per-(position, head) int8 quantization: x [B,T,H,D] → (int8, scale)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _constrain_seq_parallel(ctx, q, k, v):
    """Sequence-parallel (context-parallel) attention sharding: q sharded on
    T over the model axis, K/V gathered — used when the head count does not
    divide the model axis (DESIGN.md §4)."""
    from jax.sharding import PartitionSpec as P
    dp = ctx.batch_axes
    m = ctx.par.model_axis
    mesh = ctx.mesh
    q = jax.lax.with_sharding_constraint(
        q, compat.named_sharding(mesh, P(dp, m, None, None)))
    k = jax.lax.with_sharding_constraint(
        k, compat.named_sharding(mesh, P(dp, None, None, None)))
    v = jax.lax.with_sharding_constraint(
        v, compat.named_sharding(mesh, P(dp, None, None, None)))
    return q, k, v


def _maybe_expand_kv(ctx, cfg: ModelConfig, k, v):
    """Heads-sharded GQA with kv_heads not divisible by the model axis:
    expand K/V to Hq (h -> h // G map) so the head axis shards cleanly."""
    if ctx is None or ctx.par.attn_mode != "heads":
        return k, v
    mp = ctx.mesh.shape[ctx.par.model_axis]
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    if hkv % mp == 0 or hkv == hq:
        return k, v
    reps = hq // hkv
    return (jnp.repeat(k, reps, axis=2), jnp.repeat(v, reps, axis=2))


def attention_apply(p: PyTree, x: Array, cfg: ModelConfig, *,
                    positions: Array, causal: bool = True,
                    cache: Optional[dict] = None,
                    cache_len: Optional[Array] = None,
                    kv_source: Optional[Array] = None,
                    block_tables: Optional[Array] = None):
    """x [B, T, D] → (out [B, T, D], new_cache).

    * train/prefill: ``cache=None`` (prefill callers build the cache from the
      returned k/v — see ``serving``).
    * decode: ``cache={k,v}`` with static length S, ``cache_len`` giving the
      number of valid entries; the new token is written at ``cache_len``.
    * paged serving: ``block_tables`` [B, M] set — ``cache`` leaves are block
      *pools* ([P, Hkv, BS, D], shared by every sequence); this step's K/V
      are scattered through the table at ``cache_len`` and attention gathers
      pages (Pallas index maps, or a gather + chunked-XLA fallback).
    * ``kv_source``: cross-attention (whisper decoder) reads K/V from here.
    """
    b, t, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    src = x if kv_source is None else kv_source
    s_len = src.shape[1]
    q = (x @ p["wq"]).reshape(b, t, hq, hd)
    k = (src @ p["wk"]).reshape(b, s_len, hkv, hd)
    v = (src @ p["wv"]).reshape(b, s_len, hkv, hd)
    if kv_source is None:                      # self-attention: rotary
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    ctx = _shard_ctx()
    new_cache = None
    if cache is not None and block_tables is not None:
        # paged: scatter this step's K/V into the shared pool through the
        # block table, then attend over the gathered page list.  Single-host
        # (dispatch raises under an ambient ShardContext).
        if "k_scale" in cache:
            # quantized pool: int8 K/V pages + bf16 per-(pos, head) scale
            # pages share one block table; the gather step dequantizes
            # after the HBM read.  Prefill is single-shot (scheduler policy
            # from the family), so t > 1 attends over the exact fp tensors
            # of the whole prompt — identical math to the unpaged int8
            # prefill — while the quantized pages are written for decode.
            k8, ks = _quantize_kv(k)
            v8, vs = _quantize_kv(v)
            new_cache = {
                "k": paged_cache_write(cache["k"], k8, cache_len,
                                       block_tables),
                "v": paged_cache_write(cache["v"], v8, cache_len,
                                       block_tables),
                "k_scale": paged_cache_write(cache["k_scale"], ks, cache_len,
                                             block_tables),
                "v_scale": paged_cache_write(cache["v_scale"], vs, cache_len,
                                             block_tables)}
            valid = _valid_len(cache_len, t, b)
            if t > 1:
                out = _sdpa(cfg, q, k, v, causal=True, q_offset=cache_len,
                            kv_valid_len=valid)
            else:
                out = _sdpa(cfg, q, new_cache["k"], new_cache["v"],
                            causal=False, q_offset=cache_len,
                            kv_valid_len=valid, decode=True,
                            k_scale=new_cache["k_scale"],
                            v_scale=new_cache["v_scale"],
                            block_tables=block_tables)
        else:
            k_pool = paged_cache_write(cache["k"], k, cache_len, block_tables)
            v_pool = paged_cache_write(cache["v"], v, cache_len, block_tables)
            new_cache = {"k": k_pool, "v": v_pool}
            valid = _valid_len(cache_len, t, b)
            out = _sdpa(cfg, q, k_pool, v_pool, causal=t > 1,
                        q_offset=cache_len, kv_valid_len=valid,
                        decode=(t == 1), block_tables=block_tables)
    elif cache is not None and "k_scale" in cache:
        # the cache layout, not a config string, selects the quantized path
        # (layout construction lives in serving.cache_family)
        # quantized cache: store int8 + per-(pos, head) scales; decode
        # dequantizes per chunk AFTER the HBM read (1 byte/elem streamed)
        k8, ks = _quantize_kv(k)
        v8, vs = _quantize_kv(v)
        new_cache = {"k": cache_write(cache["k"], k8, cache_len),
                     "v": cache_write(cache["v"], v8, cache_len),
                     "k_scale": cache_write(cache["k_scale"], ks, cache_len),
                     "v_scale": cache_write(cache["v_scale"], vs, cache_len)}
        valid = _valid_len(cache_len, t, b)
        if t > 1:   # prefill computes on the exact fp tensors
            if ctx is not None and ctx.par.attn_mode == "sequence":
                q, k, v = _constrain_seq_parallel(ctx, q, k, v)
            else:
                k, v = _maybe_expand_kv(ctx, cfg, k, v)
            out = _sdpa(cfg, q, k, v, causal=True, q_offset=cache_len,
                        kv_valid_len=valid)
        else:
            out = _sdpa(cfg, q, new_cache["k"], new_cache["v"],
                        causal=False, q_offset=cache_len, kv_valid_len=valid,
                        decode=True, k_scale=new_cache["k_scale"],
                        v_scale=new_cache["v_scale"])
    elif cache is not None:
        # decode: append this step's k/v at cache_len (scalar: lockstep batch;
        # [B] vector: per-slot offsets), attend over the cache
        k_cache = cache_write(cache["k"], k, cache_len)
        v_cache = cache_write(cache["v"], v, cache_len)
        new_cache = {"k": k_cache, "v": v_cache}
        valid = _valid_len(cache_len, t, b)
        ka, va = k_cache, v_cache
        if t > 1:      # prefill: same compute sharding as the train path
            if ctx is not None and ctx.par.attn_mode == "sequence":
                q, ka, va = _constrain_seq_parallel(ctx, q, ka, va)
            else:
                ka, va = _maybe_expand_kv(ctx, cfg, ka, va)
        # t == 1 (decode): the valid-length mask alone implies causality.
        out = _sdpa(cfg, q, ka, va, causal=t > 1,
                    q_offset=cache_len, kv_valid_len=valid, decode=(t == 1))
    else:
        if ctx is not None and ctx.par.attn_mode == "sequence" and t > 1:
            q, k, v = _constrain_seq_parallel(ctx, q, k, v)
        else:
            k, v = _maybe_expand_kv(ctx, cfg, k, v)
        out = _sdpa(cfg, q, k, v, causal=causal, q_offset=0, kv_valid_len=None)
    out = out.reshape(b, t, hq * hd) @ p["wo"]
    return out, new_cache


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2).
# ---------------------------------------------------------------------------
def mla_init(key, cfg: ModelConfig) -> PyTree:
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    return {
        "wdq": _dense_init(ks[0], (d, m.q_lora_rank), ("embed", None), dtype=dt),
        "q_norm": rms_norm_init(cfg, m.q_lora_rank),
        "wuq": _dense_init(ks[1], (m.q_lora_rank, h * qk), (None, "qkv_out"), dtype=dt),
        "wdkv": _dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                            ("embed", None), dtype=dt),
        "kv_norm": rms_norm_init(cfg, m.kv_lora_rank),
        "wuk": _dense_init(ks[3], (m.kv_lora_rank, h * m.qk_nope_head_dim),
                           (None, "qkv_out"), dtype=dt),
        "wuv": _dense_init(ks[4], (m.kv_lora_rank, h * m.v_head_dim),
                           (None, "qkv_out"), dtype=dt),
        "wo": _dense_init(ks[5], (h * m.v_head_dim, d), ("qkv_out", "embed"), dtype=dt),
    }


def mla_apply(p: PyTree, x: Array, cfg: ModelConfig, *, positions: Array,
              cache: Optional[dict] = None, cache_len: Optional[Array] = None):
    """MLA attention.  Cache stores the COMPRESSED c_kv + shared rope key —
    the latent form that makes MLA's KV cache ~9x smaller; decode uses the
    absorbed-matmul trick so the cache is never decompressed."""
    m: MLAConfig = cfg.mla
    b, t, d = x.shape
    h = cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    cq = rms_norm(p["q_norm"], x @ p["wdq"], cfg.norm_eps)
    q = (cq @ p["wuq"]).reshape(b, t, h, qk)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = rope(q_rope, positions, cfg.rope_theta)

    dkv = x @ p["wdkv"]                                     # [B,T,Rkv+Dr]
    c_kv, k_rope = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    c_kv = rms_norm(p["kv_norm"], c_kv, cfg.norm_eps)
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]

    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    shard_ctx = _shard_ctx()
    if cache is not None:
        c_cache = cache_write(cache["c_kv"], c_kv, cache_len)
        r_cache = cache_write(cache["k_rope"], k_rope, cache_len)
        new_cache = {"c_kv": c_cache, "k_rope": r_cache}
        # absorbed decode: q_eff = W_uk^T q_nope  ∈ R^{Rkv} per head
        wuk3 = p["wuk"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
        q_eff = jnp.einsum("bthk,rhk->bthr", q_nope, wuk3)
        # scores over latent cache: MQA-like (shared "key" = [c_kv, k_rope])
        q_cat = jnp.concatenate([q_eff, q_rope], axis=-1)       # [B,T,H,Rkv+Dr]
        k_cat = jnp.concatenate([c_cache, r_cache], axis=-1)    # [B,S,Rkv+Dr]
        valid = _valid_len(cache_len, t, b)
        kk = k_cat[:, :, None, :]
        vv = c_cache[:, :, None, :]
        if shard_ctx is not None and t > 1:
            q_cat, kk, vv = _constrain_seq_parallel(shard_ctx, q_cat, kk, vv)
        ctx = _sdpa(cfg, q_cat, kk, vv, causal=t > 1, q_offset=cache_len,
                    kv_valid_len=valid, scale=scale, decode=(t == 1))
        wuv3 = p["wuv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
        out = jnp.einsum("bthr,rhk->bthk", ctx, wuv3)           # absorb W_uv
    else:
        new_cache = None
        k_nope = (c_kv @ p["wuk"]).reshape(b, t, h, m.qk_nope_head_dim)
        v = (c_kv @ p["wuv"]).reshape(b, t, h, m.v_head_dim)
        k = jnp.concatenate([k_nope,
                             jnp.broadcast_to(k_rope[:, :, None, :],
                                              (b, t, h, m.qk_rope_head_dim))],
                            axis=-1)
        qc = jnp.concatenate([q_nope, q_rope], axis=-1)
        if shard_ctx is not None and shard_ctx.par.attn_mode == "sequence":
            qc, k, v = _constrain_seq_parallel(shard_ctx, qc, k, v)
        out = core.online_attention(qc, k, v, causal=True,
                                    chunk_size=cfg.attn_chunk, scale=scale)
    out_flat = out.reshape(b, t, h * m.v_head_dim)
    return out_flat @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU).
# ---------------------------------------------------------------------------
def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> PyTree:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    p = {"w_up": _dense_init(ks[1], (d, f), ("embed", "ffn"), dtype=dt),
         "w_down": _dense_init(ks[2], (f, d), ("ffn", "embed"), dtype=dt)}
    if cfg.act == "silu":
        p["w_gate"] = _dense_init(ks[0], (d, f), ("embed", "ffn"), dtype=dt)
    return p


def mlp_apply(p: PyTree, x: Array, cfg: ModelConfig) -> Array:
    up = x @ p["w_up"]
    if cfg.act == "silu":
        h = jax.nn.silu(x @ p["w_gate"]) * up
    else:
        h = jax.nn.gelu(up)
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Mixture of Experts.  Router = paper's Algorithm 4 (fused softmax+top-k over
# experts); capacity-bucketed one-hot dispatch (Mesh-TF style) so the
# collective pattern (all-to-all on [G, E, C, D]) is explicit in the HLO.
# ---------------------------------------------------------------------------
def moe_init(key, cfg: ModelConfig) -> PyTree:
    mc: MoEConfig = cfg.moe
    e = mc.pad_experts_to or mc.num_experts
    d, f = cfg.d_model, mc.d_ff_expert
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (d, mc.num_experts), ("embed", None),
                              dtype=jnp.float32),
        "w_gate": _dense_init(ks[1], (e, d, f), ("expert", "embed", "expert_ffn"), dtype=dt),
        "w_up": _dense_init(ks[2], (e, d, f), ("expert", "embed", "expert_ffn"), dtype=dt),
        "w_down": _dense_init(ks[3], (e, f, d), ("expert", "expert_ffn", "embed"), dtype=dt),
    }
    if mc.d_ff_shared:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=mc.d_ff_shared)
    return p


def moe_apply(p: PyTree, x: Array, cfg: ModelConfig) -> tuple[Array, dict]:
    mc: MoEConfig = cfg.moe
    b, t, d = x.shape
    e_pad = mc.pad_experts_to or mc.num_experts
    k = mc.experts_per_token
    # ---- group tokens for capacity bucketing ------------------------------
    n = b * t
    s = min(mc.group_size, t)
    g = n // s
    xg = x.reshape(g, s, d)
    # ---- router: fused softmax+top-k (paper Alg. 4 at V = num_experts) ----
    from repro.kernels import dispatch
    logits = (xg.astype(jnp.float32) @ p["router"])          # [G,S,E]
    # the router sits under value_and_grad in training; the Pallas kernel's
    # custom VJP (recompute-from-LSE) makes the registry's own backend choice
    # safe here — no XLA pin
    probs, idx, lse = dispatch.softmax_topk(logits, k)       # [G,S,K]
    probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)
    cap = int(math.ceil(s * k * mc.capacity_factor / mc.num_experts))
    cap = max(cap, 4)
    # ---- capacity assignment ----------------------------------------------
    em = jax.nn.one_hot(idx, e_pad, dtype=jnp.float32)       # [G,S,K,E]
    em_flat = em.transpose(0, 1, 2, 3).reshape(g, s * k, e_pad)
    pos = jnp.cumsum(em_flat, axis=1) * em_flat - 1.0        # [G,S*K,E]
    keep = (pos >= 0) & (pos < cap)
    disp_sk = jax.nn.one_hot(pos.clip(0), cap, dtype=jnp.float32) \
        * keep[..., None] * em_flat[..., None]               # [G,S*K,E,C]
    disp = disp_sk.reshape(g, s, k, e_pad, cap)
    combine = jnp.einsum("gske,gskec->gsec",
                         em * probs[..., None], disp)        # [G,S,E,C]
    dispatch = disp.sum(axis=2)                              # [G,S,E,C] 0/1
    # ---- expert computation ------------------------------------------------
    xe = jnp.einsum("gsec,gsd->gecd", dispatch.astype(x.dtype), xg)
    hg = jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])
    hu = jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    he = jax.nn.silu(hg) * hu
    ye = jnp.einsum("gecf,efd->gecd", he, p["w_down"])
    y = jnp.einsum("gecd,gsec->gsd", ye, combine.astype(x.dtype))
    y = y.reshape(b, t, d)
    # ---- aux losses ---------------------------------------------------------
    me = jnp.mean(em.sum(2), axis=1)                          # fraction routed
    pe = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=1)
    pe = jnp.pad(pe, ((0, 0), (0, e_pad - mc.num_experts)))
    lb_loss = mc.num_experts * jnp.mean(jnp.sum(me * pe, axis=-1))
    z_loss = mc.router_z_loss * jnp.mean(jnp.square(lse))
    if "shared" in p:
        y = y + mlp_apply(p["shared"], x, cfg)
    return y, {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss}


# ---------------------------------------------------------------------------
# Embedding / LM head.
# ---------------------------------------------------------------------------
def embedding_init(key, cfg: ModelConfig) -> PyTree:
    dt = jnp.dtype(cfg.dtype)
    p = {"embed": _dense_init(key, (cfg.vocab_size, cfg.d_model),
                              ("vocab", "embed"), scale=1.0, dtype=dt)}
    if not cfg.tie_embeddings:
        p["head"] = _dense_init(jax.random.fold_in(key, 1),
                                (cfg.d_model, cfg.vocab_size),
                                ("embed", "vocab"), dtype=dt)
    return p


def embed_tokens(p: PyTree, tokens: Array) -> Array:
    return jnp.take(p["embed"], tokens, axis=0)


def head_matrix(p: PyTree, cfg: ModelConfig) -> Array:
    return p["embed"].T if cfg.tie_embeddings else p["head"]
