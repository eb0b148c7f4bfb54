"""Plain float32 reference of the models the cells serve: what every
architecture shares, and the look-up of each architecture's own module.

A configuration file names its architecture under ``"reference"``; the
module ``references/<name>.py`` holds everything that depends on it:

- ``model(config) -> dict``: the sizes, in the module's own names;
- ``program_fields``: pairs of configuration-file key and the program's
  ``ModelConfig`` field (dotted where nested), checked against what runs;
- ``final_hidden(key, tokens, *, model, control, block)`` and
  ``head_logits(key, h, *, model, control)``: the float32 forward at
  ``HIGHEST`` precision, weights redrawn from the seed by the module's
  recipe;
- ``matmul_flops(m)``, ``attention_flops(m, keys)``,
  ``kv_bytes_per_token(m)`` and ``kv_bytes_read(m, contexts)``: the counts
  ``flops.py`` builds on.

It imports nothing of the program and takes nothing the program made: the
weights are drawn again from the run's seed, rounded to the configuration's
dtype (the weights served), and the forward runs in float32 with every
matrix product at ``HIGHEST`` precision.

``control="fp8"`` computes the same forward with every matrix product's
inputs rounded to float8 e4m3 (per-row scales for activations, per-column
for weights): the next precision below the configuration's bfloat16, the
step that would tempt a later change.
"""
from __future__ import annotations

import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                      # largest finite float8 e4m3fn

# where ``arch`` finds the architecture modules
MODULES_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "references")
_LOADED: dict = {}


def arch(name: str):
    """The architecture module ``MODULES_DIR/<name>.py``; a name with no
    such module is an error."""
    if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z0-9_]+", name):
        raise ValueError(f"reference {name!r} is not a module name")
    path = os.path.join(MODULES_DIR, name + ".py")
    if path not in _LOADED:
        if not os.path.isfile(path):
            raise ValueError(f"no reference module {name!r} in {MODULES_DIR}")
        spec = importlib.util.spec_from_file_location("reference_" + name,
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


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


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freqs            # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


READ_ROWS = 256          # served positions per head call


def _logits(mod, key, frozen, tokens, read, control):
    h = np.asarray(mod.final_hidden(key, jnp.asarray(tokens), model=frozen,
                                    control=control))[read]
    out = []
    for i in range(0, len(read), READ_ROWS):
        rows = np.zeros((READ_ROWS, h.shape[1]), np.float32)
        part = h[i:i + READ_ROWS]
        rows[:len(part)] = part
        out.append(np.asarray(mod.head_logits(key, jnp.asarray(rows),
                                              model=frozen, control=control),
                              np.float64)[:len(part)])
    return np.concatenate(out)


def served_gaps(mod, key, model: dict, prompt, served, length: int, *,
                control=None) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best at that position, by the architecture module ``mod``,
    teacher-forcing ``prompt`` followed by the served tokens.  With
    ``control``, the gap of the token the control puts first instead of the
    served one.  Every sequence is padded
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
    ref = _logits(mod, key, frozen, tokens, read, None)
    best = ref.max(axis=-1)
    if control is None:
        pick = served
    else:
        pick = _logits(mod, key, frozen, tokens, read, control).argmax(axis=-1)
    return best - ref[np.arange(len(served)), pick]


def key_for_seed(seed: int):
    """The raw PRNG key a run's weights are drawn from (any seed up to
    2**64, by the same words the harness gives the program)."""
    words = np.random.SeedSequence(int(seed) & (2**64 - 1)).generate_state(2)
    return jnp.asarray(words, jnp.uint32)
