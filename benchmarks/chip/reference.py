"""Plain float32 reference of the dense GQA decoder the cells serve.

It imports nothing of the program and takes nothing the program made: it
draws the weights again from the run's seed by the published recipe below,
rounds them to the configuration's dtype (the weights served), and runs the
forward pass in float32 with every matrix product at ``HIGHEST`` precision.

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

``control="fp8"`` computes the same forward with every matrix product's
inputs rounded to float8 e4m3 (per-row scales for activations, per-column
for weights): the next precision below the configuration's bfloat16, the
step that would tempt a later change.
"""
from __future__ import annotations

import functools
import math
import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                      # largest finite float8 e4m3fn


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, control):
    """``a [.., K] @ w [K, N]`` in float32, or on fp8-rounded inputs."""
    if control == "fp8":
        a, w = _q8(a, -1), _q8(w, 0)
    return jnp.matmul(a, w, precision=HI)


def _fan(fan_in: int) -> float:
    """The draw's scale, ``1 / sqrt(fan_in)``, rounded as the recipe
    rounds it (``fan_in ** -0.5`` can differ in the last bit)."""
    return 1.0 / math.sqrt(fan_in)


def _normal(key, shape, scale, dtype):
    v = jax.random.normal(key, shape, dtype=jnp.float32) * scale
    return v.astype(dtype).astype(jnp.float32)


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


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freqs            # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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
    d, hq, hkv, hd = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                      m["head_dim"])
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


READ_ROWS = 256          # served positions per head call


def _logits(key, frozen, tokens, read, control):
    h = np.asarray(final_hidden(key, jnp.asarray(tokens), model=frozen,
                                control=control))[read]
    out = []
    for i in range(0, len(read), READ_ROWS):
        rows = np.zeros((READ_ROWS, h.shape[1]), np.float32)
        part = h[i:i + READ_ROWS]
        rows[:len(part)] = part
        out.append(np.asarray(head_logits(key, jnp.asarray(rows),
                                          model=frozen, control=control),
                              np.float64)[:len(part)])
    return np.concatenate(out)


def served_gaps(key, model: dict, prompt, served, length: int, *,
                control=None) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best at that position, teacher-forcing ``prompt`` followed
    by the served tokens.  With ``control``, the gap of the token the
    control puts first instead of the served one.  Every sequence is padded
    to ``length`` (the slot length, which bounds prompt and answer), so one
    compiled program serves every request of a configuration."""
    prompt = np.asarray(prompt, np.int64)
    served = np.asarray(served, np.int64)
    seq = np.concatenate([prompt, served[:-1]])
    if len(seq) > length:
        raise ValueError(f"{len(seq)} tokens do not fit the {length} "
                         "the reference was asked to pad to")
    tokens = np.zeros(length, np.int32)
    tokens[:len(seq)] = seq
    read = len(prompt) - 1 + np.arange(len(served))
    frozen = tuple(sorted(model.items()))
    ref = _logits(key, frozen, tokens, read, None)
    best = ref.max(axis=-1)
    if control is None:
        pick = served
    else:
        pick = _logits(key, frozen, tokens, read, control).argmax(axis=-1)
    return best - ref[np.arange(len(served)), pick]


def key_for_seed(seed: int):
    """The raw PRNG key a run's weights are drawn from (any seed up to
    2**64, by the same words the harness gives the program)."""
    words = np.random.SeedSequence(int(seed) & (2**64 - 1)).generate_state(2)
    return jnp.asarray(words, jnp.uint32)
