"""Compile the serving path's Pallas kernels for a described TPU v5e chip.

Interpret mode runs a kernel body on the CPU but says nothing about whether
the TPU compiler accepts its tiling: block shapes whose last two dims are not
(8, 128)-aligned or equal to the array's are refused only there.  These tests
lower each kernel of the paged serving path at smollm_360m's published widths
(15 query heads, 5 KV heads, head_dim 64, vocab 49152) for a ``v5e:2x2``
topology described without the chip, and check that the compiled program
holds the Mosaic kernel (``tpu_custom_call``); the paged decode kernel also
at each benchmark cell's geometry, mistral-nemo-12b's widths included.
Nothing runs; a pass says the compiler accepted the kernel, not that its
numbers are right.

Every autotune candidate the chip sweeps would compile is covered too: the
vocab-axis blocks (``dispatch.BLOCK_CANDIDATES``) and the paged-prefill
``bq`` sweep's synthetic pool.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.kernels.flash_attention import flash_attention_paged_pallas
from repro.kernels.flash_decode import flash_decode_paged_pallas
from repro.kernels.online_softmax import online_normalizer_pallas
from repro.kernels.softmax_topk import softmax_topk_pallas

# smollm_360m published widths and the chip smoke's serving shape
HQ, HKV, D, VOCAB = 15, 5, 64, 49152
BS = 16                      # pool block size (tokens)
SLOTS = 8
SLOT_LEN = 560               # 512-token prompt + 32 new + 8, rounded to BS
M = SLOT_LEN // BS           # block-table width
P = SLOTS * M + 1            # pool blocks incl. the sentinel
CHUNK = 128                  # prefill chunk
TOP_K = 5


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(kernel, *args, **kwargs):
    """Compile ``kernel(*args, **kwargs)`` natively (``interpret=False``:
    on a CPU host ``compat`` would pick interpret mode).  Integer keywords
    are tile sizes, bound statically; the rest are operand shapes."""
    tiles = {k: v for k, v in kwargs.items() if isinstance(v, int)}
    operands = {k: v for k, v in kwargs.items() if k not in tiles}
    fn = jax.jit(functools.partial(kernel, interpret=False, **tiles))
    text = fn.lower(*args, **operands).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _pools(dtype, quantized, sh, pages=P, hkv=HKV, bs=BS):
    pools = [_spec((pages, hkv, bs, D), dtype, sh)] * 2
    scales = ([_spec((pages, hkv, bs, 1), jnp.bfloat16, sh)] * 2
              if quantized else [None, None])
    return pools, scales


# The benchmark cells' decode geometries: (Hq, Hkv, D, rows, table width,
# pool blocks).  smollm-360m.chat: 64 slots x 2048 tokens; the
# mistral-nemo-12b.l8 stage: 32 slots x 4096 tokens.
DECODE_CELLS = {
    "smollm-360m": (HQ, HKV, D, 64, 2048 // BS, 4096),
    "mistral-nemo-12b.l8": (32, 8, 128, 32, 4096 // BS, 6144),
}


@pytest.mark.parametrize("cell", sorted(DECODE_CELLS))
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_decode_compiles(one_chip, no_compile_cache, quantized, cell):
    """Both forms at each cell's widths and geometry.  The benchmark's
    reader finds the kernel by the HLO name of its custom call."""
    sh = one_chip
    hq, hkv, dh, rows, m, blocks = DECODE_CELLS[cell]
    dtype = jnp.int8 if quantized else jnp.bfloat16
    pool = _spec((blocks + 1, hkv, BS, dh), dtype, sh)
    scale = _spec((blocks + 1, hkv, BS, 1), jnp.bfloat16, sh)
    args = [_spec((rows, hq, dh), jnp.bfloat16, sh), pool, pool,
            _spec((rows, m), jnp.int32, sh), _spec((rows,), jnp.int32, sh)]
    text = _assert_kernel(flash_decode_paged_pallas, *args,
                          k_scale_pool=scale if quantized else None,
                          v_scale_pool=scale if quantized else None)
    assert re.search(r"%flash_decode_paged_pallas(\.\d+)? = \S+ custom-call\("
                     r"[^\n]*custom_call_target=\"tpu_custom_call\"", text)


@pytest.mark.parametrize("tq", [CHUNK, 64, 1])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_prefill_compiles(one_chip, no_compile_cache, quantized, tq):
    """The scheduler's chunk widths: full chunks, a power-of-two tail, and
    the one-token tail."""
    sh = one_chip
    (kp, vp), (ks, vs) = _pools(jnp.int8 if quantized else jnp.bfloat16,
                                quantized, sh)
    args = [_spec((1, HQ, tq, D), jnp.bfloat16, sh), kp, vp,
            _spec((1,), jnp.int32, sh), _spec((1,), jnp.int32, sh),
            _spec((1, M), jnp.int32, sh)]
    _assert_kernel(flash_attention_paged_pallas, *args, bq=tq,
                   k_scale_pool=ks, v_scale_pool=vs)


@pytest.mark.parametrize("bq", sorted({min(c, SLOT_LEN) for c in
                                       dispatch.PREFILL_TILE_CANDIDATES}))
def test_paged_prefill_sweep_candidates_compile(one_chip, no_compile_cache,
                                                bq):
    """The shapes ``dispatch._prefill_runner`` builds for the paged
    ``bq`` sweep at this slot length (synthetic 8-head pool of
    ``_PAGED_TUNE_BLOCK``-token pages)."""
    sh = one_chip
    rows, heads, bs = dispatch._TUNE_ROWS, 8, dispatch._PAGED_TUNE_BLOCK
    m = max(SLOT_LEN // bs, 1)
    tq = max(min(bq, SLOT_LEN), 1)
    (kp, vp), _ = _pools(jnp.bfloat16, False, sh, pages=rows * m + 1,
                         hkv=heads, bs=bs)
    _assert_kernel(flash_attention_paged_pallas,
                   _spec((rows, heads, tq, D), jnp.bfloat16, sh), kp, vp,
                   _spec((rows,), jnp.int32, sh),
                   _spec((rows,), jnp.int32, sh),
                   _spec((rows, m), jnp.int32, sh), bq=tq)


@pytest.mark.parametrize("v_blk", dispatch.BLOCK_CANDIDATES)
def test_softmax_topk_compiles(one_chip, no_compile_cache, v_blk):
    _assert_kernel(softmax_topk_pallas,
                   _spec((SLOTS, VOCAB), jnp.float32, one_chip), k=TOP_K,
                   r_blk=SLOTS, v_blk=v_blk)


@pytest.mark.parametrize("v_blk", dispatch.BLOCK_CANDIDATES)
def test_online_normalizer_compiles(one_chip, no_compile_cache, v_blk):
    """The vocab-block sweep's timed kernel (``dispatch._blocked_runner``)."""
    rows = dispatch._TUNE_ROWS
    _assert_kernel(online_normalizer_pallas,
                   _spec((rows, VOCAB), jnp.float32, one_chip), r_blk=rows,
                   v_blk=v_blk)
