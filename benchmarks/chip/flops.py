"""Operations and bytes the served work needs, from shapes the benchmark
knows (model sizes, live contexts, chunk offsets and widths).  Never from
the implementation's cost analysis, so a number means the same whatever
kernel implements the op.

``m`` is a model-size dict as ``harness.Cell.model`` gives it; its
``"reference"`` names the architecture module (``reference.arch``) whose
counts these build on: its matmul FLOPs, its attention FLOPs at each
query's keys and its K/V bytes written and read.  A dict that names none
(sizes written out by hand) is counted as ``dense_gqa``; every cell's names
its own.  Bytes count each operand read once and each result written once.
"""
from __future__ import annotations

import json
import os

import numpy as np

import reference

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in peaks.json")
    return table["devices"][device_kind]


def _arch(m: dict):
    return reference.arch(m.get("reference", "dense_gqa"))


def layer_matmul_flops(m: dict) -> float:
    """Projection and MLP FLOPs of one token through one layer, the mean
    over the layers."""
    return _arch(m).matmul_flops(m) / m["num_layers"]


def attention_flops(m: dict, keys) -> float:
    """QK^T and PV FLOPs of queries that each attend ``keys`` positions,
    summed over the queries and the layers."""
    return _arch(m).attention_flops(m, keys)


def head_flops(m: dict) -> int:
    return 2 * m["d_model"] * m["vocab_size"]


def decode_flops(m: dict, contexts) -> float:
    """Model FLOPs of decode rows whose new token sees ``contexts`` keys
    (the token itself included): layers, attention and the LM head."""
    n = len(contexts)
    return (n * (_arch(m).matmul_flops(m) + head_flops(m))
            + attention_flops(m, contexts))


def prefill_flops(m: dict, offset: int, width: int) -> float:
    """Model FLOPs of one prefill chunk of ``width`` tokens after
    ``offset`` cached ones (no LM head: the first token's is counted with
    the request by ``head_flops``)."""
    keys = offset + 1 + np.arange(width)
    return (width * _arch(m).matmul_flops(m)
            + attention_flops(m, keys))


def kv_bytes_per_token(m: dict) -> int:
    """K and V written for one position over all layers, in the served
    dtype."""
    return _arch(m).kv_bytes_per_token(m)


def paged_decode_cost(m: dict, contexts) -> tuple:
    """(FLOPs, bytes) of the paged decode attention over rows of
    ``contexts`` keys, every layer: K/V read once, q read and out written."""
    q_out = 2 * m["num_heads"] * m["head_dim"] * BYTES[m["dtype"]]
    nbytes = (_arch(m).kv_bytes_read(m, contexts)
              + m["num_layers"] * q_out * len(contexts))
    return attention_flops(m, contexts), float(nbytes)


def paged_prefill_cost(m: dict, offset: int, width: int) -> tuple:
    """(FLOPs, bytes) of the paged prefill attention of one chunk, every
    layer: causal keys per query; K/V of the whole context read once."""
    keys = offset + 1 + np.arange(width)
    q_out = 2 * width * m["num_heads"] * m["head_dim"] * BYTES[m["dtype"]]
    nbytes = (_arch(m).kv_bytes_read(m, [offset + width])
              + m["num_layers"] * q_out)
    return attention_flops(m, keys), float(nbytes)


def softmax_topk_bytes(rows: int, vocab: int) -> float:
    """The float32 logits read once; the K outputs are negligible."""
    return float(rows) * vocab * 4


def least_time(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of compute and memory time."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
