"""Dense GQA decoder: the reference forward, its weight recipe and its
counts.

The model, per layer: ``x += Wo·attn(rope(Wq·n(x)), rope(Wk·n(x)), Wv·n(x))``
then ``x += Wd·(silu(Wg·n(x)) * Wu·n(x))``, where ``n`` is RMSNorm (unit
scale, ``eps`` from the file), attention is causal with query head ``h``
reading KV head ``h // (heads / kv_heads)`` at scale ``head_dim**-0.5``,
and rope rotates the two halves of each head by ``pos * theta**(-2i/d)``.
The head is the embedding's transpose where ``tie_embeddings``, else its
own matrix.

Weight recipe (the seed's key ``k``): ``k_e, _, _, k_s = split(k, 4)``;
embedding ``N(0, 1)`` from ``k_e``, times the configuration's
``embedding_scale`` where it sets one; the untied head ``N(0, 1/d)`` from
``fold_in(k_e, 1)``; layer ``l`` from ``split(k_s, L)[l]`` split in two,
``(k_a, k_m)``; ``wq, wk, wv, wo`` from ``split(k_a, 4)`` and
``w_gate, w_up, w_down`` from ``split(k_m, 3)``, each ``N(0, 1/fan_in)``.

Counts: every layer has the same projections, a ``3·d·d_ff`` MLP, full
causal attention and full K/V.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import flops
from reference import HI, _fan, _mm, _normal, _q8, _rms, _rope

# configuration-file key -> the program's ModelConfig field
program_fields = (
    ("hidden_size", "d_model"), ("intermediate_size", "d_ff"),
    ("num_hidden_layers", "num_layers"),
    ("num_attention_heads", "num_heads"),
    ("num_key_value_heads", "num_kv_heads"), ("head_dim", "head_dim"),
    ("vocab_size", "vocab_size"), ("rope_theta", "rope_theta"),
    ("rms_norm_eps", "norm_eps"), ("tie_word_embeddings", "tie_embeddings"),
    ("torch_dtype", "dtype"),
)


def model(c: dict) -> dict:
    """Sizes in the reference's names, from the configuration file."""
    return {"vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
            "num_layers": c["num_hidden_layers"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "d_ff": c["intermediate_size"],
            "rope_theta": float(c["rope_theta"]),
            "norm_eps": float(c["rms_norm_eps"]),
            "tie_embeddings": bool(c["tie_word_embeddings"]),
            "dtype": c["torch_dtype"],
            "embedding_scale": float(
                c.get("weights", {}).get("embedding_scale", 1.0))}


# ---------------------------------------------------------------------------
# The forward.
# ---------------------------------------------------------------------------
def _embedding(key, m: dict, dtype):
    """``N(0, 1)`` in the served dtype, then times ``embedding_scale`` and
    rounded to that dtype again (1: left as drawn)."""
    e = _normal(key, (m["vocab_size"], m["d_model"]), 1.0, dtype)
    scale = m.get("embedding_scale", 1.0)
    if scale != 1.0:
        e = (e * scale).astype(dtype).astype(jnp.float32)
    return e


def _layer_weights(key, m: dict, dtype):
    d, hq, hkv, hd, f = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                         m["head_dim"], m["d_ff"])
    k_a, k_m = jax.random.split(key)
    ka = jax.random.split(k_a, 4)
    km = jax.random.split(k_m, 3)
    return {
        "wq": _normal(ka[0], (d, hq * hd), _fan(d), dtype),
        "wk": _normal(ka[1], (d, hkv * hd), _fan(d), dtype),
        "wv": _normal(ka[2], (d, hkv * hd), _fan(d), dtype),
        "wo": _normal(ka[3], (hq * hd, d), _fan(hq * hd), dtype),
        "w_gate": _normal(km[0], (d, f), _fan(d), dtype),
        "w_up": _normal(km[1], (d, f), _fan(d), dtype),
        "w_down": _normal(km[2], (f, d), _fan(f), dtype),
    }


def _attention(q, k, v, block: int, control):
    """Causal GQA over [T, H, hd] in query blocks of ``block`` rows."""
    t, hq, hd = q.shape
    g = hq // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    if control == "fp8":
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 0)
    kpos = jnp.arange(t)

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * hd ** -0.5
        qpos = i * block + jnp.arange(block)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if control == "fp8":
            p = _q8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(one, jnp.arange(t // block))
    return out.reshape(t, hq, hd)


@functools.partial(jax.jit, static_argnames=("model", "control", "block"))
def final_hidden(key, tokens, *, model, control=None, block=256):
    """The final norm's output [T, D] for ``tokens`` [T] (T a multiple of
    ``block``; positions past the real sequence are padding, which causal
    attention keeps out of every earlier row)."""
    m = dict(model)
    dtype = jnp.dtype(m["dtype"])
    hq, hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    t = tokens.shape[0]
    k_e, _, _, k_s = jax.random.split(key, 4)
    x = _embedding(k_e, m, dtype)[tokens]
    pos = jnp.arange(t)

    def layer(x, lkey):
        w = _layer_weights(lkey, m, dtype)
        h = _rms(x, m["norm_eps"])
        q = _rope(_mm(h, w["wq"], control).reshape(t, hq, hd), pos,
                  m["rope_theta"])
        k = _rope(_mm(h, w["wk"], control).reshape(t, hkv, hd), pos,
                  m["rope_theta"])
        v = _mm(h, w["wv"], control).reshape(t, hkv, hd)
        a = _attention(q, k, v, block, control).reshape(t, hq * hd)
        x = x + _mm(a, w["wo"], control)
        h = _rms(x, m["norm_eps"])
        u = jax.nn.silu(_mm(h, w["w_gate"], control)) * _mm(h, w["w_up"],
                                                            control)
        return x + _mm(u, w["w_down"], control), None

    x, _ = jax.lax.scan(layer, x, jax.random.split(k_s, m["num_layers"]))
    return _rms(x, m["norm_eps"])


@functools.partial(jax.jit, static_argnames=("model", "control"))
def head_logits(key, h, *, model, control=None):
    """LM-head logits [R, V] of final hidden rows ``h`` [R, D]."""
    m = dict(model)
    dtype = jnp.dtype(m["dtype"])
    d = m["d_model"]
    k_e = jax.random.split(key, 4)[0]
    if m["tie_embeddings"]:
        head = _embedding(k_e, m, dtype).T
    else:
        head = _normal(jax.random.fold_in(k_e, 1), (d, m["vocab_size"]),
                       _fan(d), dtype)
    return _mm(h, head, control)


# ---------------------------------------------------------------------------
# Counts, from the sizes alone.
# ---------------------------------------------------------------------------
def _layer_matmul_flops(m: dict) -> int:
    d, hq, hkv, hd, f = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                         m["head_dim"], m["d_ff"])
    return 2 * (d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f)


def matmul_flops(m: dict) -> int:
    """Projection and MLP FLOPs of one token through all layers (no
    head)."""
    return m["num_layers"] * _layer_matmul_flops(m)


def attention_flops(m: dict, keys) -> float:
    """QK^T and PV FLOPs of queries that each attend ``keys`` positions,
    summed over the queries and the layers."""
    return (4.0 * m["num_heads"] * m["head_dim"] * m["num_layers"]
            * float(np.sum(keys)))


def kv_bytes_per_token(m: dict) -> int:
    """K and V of one position over all layers, in the served dtype."""
    return (2 * m["num_layers"] * m["num_kv_heads"] * m["head_dim"]
            * flops.BYTES[m["dtype"]])


def kv_bytes_read(m: dict, contexts) -> float:
    """K and V that rows at ``contexts`` positions read, every layer."""
    return (kv_bytes_per_token(m)
            * np.asarray(contexts, np.float64).sum())
