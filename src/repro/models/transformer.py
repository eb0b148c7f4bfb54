"""Decoder-only LM covering all assigned families via a segment/block system.

A model is a sequence of *segments*; each segment is ``count`` copies of one
block kind with params stacked on a leading layer axis and executed with
``lax.scan`` (HLO stays O(1 block), which keeps 512-device compiles cheap and
gives remat a uniform cut point).  Heterogeneous stacks (xLSTM's
mLSTM/sLSTM mix, Zamba2's mamba-with-shared-attention) are just multiple
segments; Zamba2's shared transformer block has its params stored ONCE at the
top level and is invoked between segments (weight sharing, per the arch).

Block kinds:
  dense   — [norm→GQA attn] + [norm→MLP]
  mla     — [norm→MLA attn] + [norm→MLP]
  moe     — [norm→GQA attn] + [norm→MoE]
  mamba   — [norm→Mamba2]
  mlstm / slstm — xLSTM blocks (own norms/residuals)

The LM head loss uses chunked online cross-entropy (paper §7 fusion) and
decode sampling uses fused softmax+top-k (paper §4).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import core
from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm, xlstm

Array = jax.Array
PyTree = Any


# ---------------------------------------------------------------------------
# Segment pattern per family.
# ---------------------------------------------------------------------------
def block_pattern(cfg: ModelConfig) -> list[tuple[str, int]]:
    if cfg.family in ("dense", "vlm"):
        return [("dense", cfg.num_layers)]
    if cfg.family == "mla":
        return [("mla", cfg.num_layers)]
    if cfg.family == "moe":
        return [("moe", cfg.num_layers)]
    if cfg.family == "ssm":        # xLSTM: sLSTM every `slstm_every` layers
        ev = cfg.xlstm.slstm_every
        segs: list[tuple[str, int]] = []
        run = 0
        for i in range(cfg.num_layers):
            if i % ev == ev - 1:
                if run:
                    segs.append(("mlstm", run))
                    run = 0
                segs.append(("slstm", 1))
            else:
                run += 1
        if run:
            segs.append(("mlstm", run))
        return segs
    if cfg.family == "hybrid":     # Zamba2: shared attn block every N mamba
        ev = cfg.hybrid_attn_every
        segs = []
        remaining = cfg.num_layers
        while remaining > 0:
            n = min(ev, remaining)
            segs.append(("mamba", n))
            remaining -= n
            if remaining > 0 or True:   # shared block also closes the stack
                segs.append(("shared_attn", 1))
        return segs
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Per-block init/apply.
# ---------------------------------------------------------------------------
def _norm_init(cfg: ModelConfig):
    return (L.layer_norm_init(cfg) if cfg.norm_type == "layernorm"
            else L.rms_norm_init(cfg))


def _norm(cfg: ModelConfig, p, x):
    return (L.layer_norm(p, x, cfg.norm_eps) if cfg.norm_type == "layernorm"
            else L.rms_norm(p, x, cfg.norm_eps))


def _dense_block_init(key, cfg: ModelConfig) -> PyTree:
    k1, k2 = jax.random.split(key)
    return {"ln1": _norm_init(cfg), "attn": L.attention_init(k1, cfg),
            "ln2": _norm_init(cfg), "mlp": L.mlp_init(k2, cfg)}


def _mla_block_init(key, cfg: ModelConfig) -> PyTree:
    k1, k2 = jax.random.split(key)
    return {"ln1": _norm_init(cfg), "attn": L.mla_init(k1, cfg),
            "ln2": _norm_init(cfg), "mlp": L.mlp_init(k2, cfg)}


def _moe_block_init(key, cfg: ModelConfig) -> PyTree:
    k1, k2 = jax.random.split(key)
    return {"ln1": _norm_init(cfg), "attn": L.attention_init(k1, cfg),
            "ln2": _norm_init(cfg), "moe": L.moe_init(k2, cfg)}


def _mamba_block_init(key, cfg: ModelConfig) -> PyTree:
    return {"ln": _norm_init(cfg), "mamba": ssm.mamba2_init(key, cfg)}


BLOCK_INIT = {
    "dense": _dense_block_init,
    "mla": _mla_block_init,
    "moe": _moe_block_init,
    "mamba": _mamba_block_init,
    "mlstm": xlstm.mlstm_block_init,
    "slstm": xlstm.slstm_block_init,
    "shared_attn": _dense_block_init,
}


def block_apply(kind: str, p: PyTree, x: Array, cfg: ModelConfig, *,
                positions: Array, cache: Optional[PyTree] = None,
                cache_len: Optional[Array] = None,
                block_tables: Optional[Array] = None):
    """Returns (x_out, new_cache, aux-losses dict).

    ``block_tables`` (paged KV serving) is only meaningful for standard
    attention caches; MLA/SSM/xLSTM block kinds reject it loudly rather than
    silently ignoring the paging request."""
    aux: dict = {}
    if block_tables is not None and kind not in ("dense", "moe",
                                                 "shared_attn"):
        raise ValueError(f"paged KV cache serves standard attention blocks "
                         f"only (got {kind!r})")
    if kind in ("dense", "moe", "mla", "shared_attn"):
        attn_cache = None if cache is None else cache["attn"]
        with jax.named_scope("attn"):
            h = _norm(cfg, p["ln1"], x)
            if kind == "mla":
                a, new_attn_cache = L.mla_apply(p["attn"], h, cfg,
                                                positions=positions,
                                                cache=attn_cache,
                                                cache_len=cache_len)
            else:
                a, new_attn_cache = L.attention_apply(
                    p["attn"], h, cfg, positions=positions, cache=attn_cache,
                    cache_len=cache_len, block_tables=block_tables)
            x = x + a
        with jax.named_scope("mlp"):
            h = _norm(cfg, p["ln2"], x)
            if kind == "moe":
                m, aux = L.moe_apply(p["moe"], h, cfg)
            else:
                m = L.mlp_apply(p["mlp"], h, cfg)
            x = x + m
        new_cache = None if new_attn_cache is None else {"attn": new_attn_cache}
        return x, new_cache, aux
    if kind == "mamba":
        h = _norm(cfg, p["ln"], x)
        y, new_cache = ssm.mamba2_apply(p["mamba"], h, cfg, cache=cache)
        return x + y, new_cache, aux
    if kind == "mlstm":
        y, new_cache = xlstm.mlstm_block_apply(p, x, cfg, cache=cache)
        return y, new_cache, aux
    if kind == "slstm":
        y, new_cache = xlstm.slstm_block_apply(p, x, cfg, cache=cache)
        return y, new_cache, aux
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Whole-model init.
# ---------------------------------------------------------------------------
def init(key, cfg: ModelConfig) -> PyTree:
    """Returns a BOXED param tree (repro.models.layers.Param leaves)."""
    segs = block_pattern(cfg)
    keys = jax.random.split(key, len(segs) + 3)
    params: dict = {"embedding": L.embedding_init(keys[0], cfg),
                    "final_norm": _norm_init(cfg), "segments": []}
    if cfg.pos_embedding == "learned":
        params["pos_embed"] = L._dense_init(
            keys[1], (cfg.max_seq_len, cfg.d_model), (None, "embed"),
            scale=0.02, dtype=jnp.dtype(cfg.dtype))
    if cfg.num_patches:
        params["mm_proj"] = L._dense_init(
            keys[2], (cfg.d_model, cfg.d_model), ("embed", None),
            dtype=jnp.dtype(cfg.dtype))
    shared_done = False
    for si, (kind, count) in enumerate(segs):
        if kind == "shared_attn":
            if not shared_done:
                params["shared_attn"] = BLOCK_INIT[kind](keys[si + 3], cfg)
                shared_done = True
            params["segments"].append({})          # placeholder, uses shared
            continue
        stacked = L.stack_layer_init(
            lambda k, kind=kind: BLOCK_INIT[kind](k, cfg), keys[si + 3], count)
        params["segments"].append(stacked)
    return params


# ---------------------------------------------------------------------------
# Forward pass.
# ---------------------------------------------------------------------------
def _maybe_remat(cfg: ModelConfig, fn, *, inference: bool = False):
    if cfg.remat == "none" or inference:
        # remat exists for the backward pass; on cached/serving forwards it
        # only inserts convert/copy round-trips of the whole cache stack.
        return fn
    policy = (jax.checkpoint_policies.nothing_saveable if cfg.remat == "full"
              else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, policy=policy)


def forward(params: PyTree, tokens: Array, cfg: ModelConfig, *,
            patch_embeds: Optional[Array] = None,
            caches: Optional[list] = None,
            cache_len: Optional[Array] = None,
            block_tables: Optional[Array] = None):
    """tokens [B, T] → (hidden [B, T', D], new_caches).

    VLM: ``patch_embeds [B, P, D]`` are projected and prepended; T' = P + T.
    ``block_tables`` [B, M]: paged KV serving — ``caches`` hold block *pools*
    (no batch axis; see ``serving.engine.init_paged_cache``) and every
    attention layer reads/writes through the table.
    """
    x = L.embed_tokens(params["embedding"], tokens)
    if cfg.num_patches and patch_embeds is not None:
        pe = patch_embeds.astype(x.dtype) @ params["mm_proj"]
        x = jnp.concatenate([pe, x], axis=1)
    b, t, _ = x.shape
    base = jnp.asarray(cache_len if cache_len is not None else 0, jnp.int32)
    # scalar base → positions [T]; per-sequence base [B] (continuous-batching
    # slots at ragged lengths) → positions [B, T]; rope broadcasts either.
    positions = base[..., None] + jnp.arange(t, dtype=jnp.int32)
    if cfg.pos_embedding == "learned":
        x = x + jnp.take(params["pos_embed"], positions, axis=0)

    segs = block_pattern(cfg)
    new_caches: list = []
    aux_total: dict = {}
    # one scope over every segment: a scan's implicit per-layer slicing of
    # its stacked params and caches (and the write-back) lands under it
    with jax.named_scope("layers"):
        for si, (kind, count) in enumerate(segs):
            if kind == "shared_attn":
                cache = None if caches is None else caches[si]
                step = _maybe_remat(
                    cfg, functools.partial(block_apply, "shared_attn",
                                           cfg=cfg, positions=positions,
                                           cache_len=cache_len,
                                           block_tables=block_tables),
                    inference=caches is not None)
                x, nc, _ = step(params["shared_attn"], x, cache=cache)
                new_caches.append(nc)
                continue
            seg_params = params["segments"][si]
            seg_cache = None if caches is None else caches[si]

            def body(x, layer_in, kind=kind):
                p_i, cache_i = layer_in
                out, nc, aux = block_apply(kind, p_i, x, cfg,
                                           positions=positions,
                                           cache=cache_i,
                                           cache_len=cache_len,
                                           block_tables=block_tables)
                return out, (nc, aux)

            body = _maybe_remat(cfg, body, inference=caches is not None)
            x, (nc_stack, aux_stack) = jax.lax.scan(
                body, x, (seg_params, seg_cache))
            new_caches.append(nc_stack)
            for k, v in (aux_stack or {}).items():
                aux_total[k] = aux_total.get(k, 0.0) + jnp.sum(v)
    x = _norm(cfg, params["final_norm"], x)
    return x, new_caches, aux_total


# ---------------------------------------------------------------------------
# Training loss (chunked online CE) and decode logits.
# ---------------------------------------------------------------------------
def loss_fn(params: PyTree, batch: dict, cfg: ModelConfig):
    """batch: tokens [B,T], labels [B,T] (−1 = masked).  Mean CE + aux."""
    hidden, _, aux = forward(params, batch["tokens"], cfg,
                             patch_embeds=batch.get("patch_embeds"))
    if cfg.num_patches and "patch_embeds" in batch:
        hidden = hidden[:, cfg.num_patches:]       # loss on text positions
    b, t, d = hidden.shape
    labels = batch["labels"].reshape(-1)
    w = L.head_matrix(params["embedding"], cfg)
    h2 = hidden.reshape(-1, d)
    valid = labels >= 0
    safe_labels = jnp.where(valid, labels, 0)
    if cfg.use_chunked_ce:
        tok_loss = core.chunked_cross_entropy(h2, w, safe_labels,
                                              num_chunks=cfg.vocab_chunks)
    else:
        tok_loss = core.full_cross_entropy(h2, w, safe_labels)
    denom = jnp.maximum(valid.sum(), 1)
    loss = jnp.sum(tok_loss * valid) / denom
    metrics = {"ce_loss": loss, **{k: v for k, v in aux.items()}}
    for v in aux.values():
        loss = loss + v / max(cfg.num_layers, 1)
    metrics["loss"] = loss
    return loss, metrics


def logits_last(params: PyTree, hidden: Array, cfg: ModelConfig) -> Array:
    """LM-head logits for the last position only (decode path)."""
    w = L.head_matrix(params["embedding"], cfg)
    return hidden[:, -1].astype(jnp.float32) @ w.astype(jnp.float32)
