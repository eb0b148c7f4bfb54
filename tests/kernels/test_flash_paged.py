"""Paged flash kernels: block-pool + block-table attention vs the
chunked-XLA gather fallback (interpret mode on non-TPU CI).

The contract: gathering K/V pages through a ``[B, max_blocks]`` block table
inside the kernel's index maps computes the same attention as materializing
the gather and running the contiguous forms — across ragged per-row valid
lengths, valid lengths straddling a block boundary, scattered physical
block placement, and tables where several rows share physical blocks
(prefix sharing).  Dead table entries hold the sentinel (0) and must never
influence the result.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.kernels import dispatch, flash_decode, ops

B, HQ, HKV, D = 3, 4, 2, 16
BS, M = 8, 4                       # block size, max blocks per row
P = B * M + 1                      # physical pool incl. sentinel block 0


@pytest.fixture(scope="module")
def pool():
    rng = np.random.default_rng(0)
    k_pool = jnp.asarray(rng.normal(size=(P, HKV, BS, D)).astype(np.float32))
    v_pool = jnp.asarray(rng.normal(size=(P, HKV, BS, D)).astype(np.float32))
    # scattered, non-contiguous physical placement (never the sentinel)
    tables = jnp.asarray(
        rng.permutation(P - 1)[:B * M].reshape(B, M) + 1, jnp.int32)
    return k_pool, v_pool, tables


def _gathered(pool_arr, tables):
    g = jnp.swapaxes(pool_arr[tables], 2, 3)
    return g.reshape(tables.shape[0], -1, HKV, pool_arr.shape[-1])


# ---------------------------------------------------------------------------
# Decode.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("vlens", [
    (5, 17, 32),          # mid-block, block-straddling, full
    (1, 8, 9),            # first position only / exact boundary / boundary+1
    (32, 32, 32),
])
def test_paged_decode_matches_gather_reference(pool, vlens):
    k_pool, v_pool, tables = pool
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, HQ, D)).astype(np.float32))
    vlen = jnp.asarray(vlens, jnp.int32)
    got = ops.paged_flash_decode(q, k_pool, v_pool, tables, vlen)
    want = core.naive_attention(q[:, None], _gathered(k_pool, tables),
                                _gathered(v_pool, tables), causal=False,
                                kv_valid_len=vlen)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_ignores_dead_table_entries(pool):
    """Entries at or past ceil(vlen/BS) are dead; sentinel vs garbage ids
    must not change the result (the index maps clamp, the mask erases)."""
    k_pool, v_pool, tables = pool
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(B, HQ, D)).astype(np.float32))
    vlen = jnp.asarray([7, 12, 3], jnp.int32)   # 1, 2, 1 live blocks
    live = [1, 2, 1]
    t_sentinel = np.asarray(tables).copy()
    t_other = np.asarray(tables).copy()
    for b, n in enumerate(live):
        t_sentinel[b, n:] = 0
        t_other[b, n:] = (b + 1) % (P - 1) + 1  # some other row's live block
    got_s = ops.paged_flash_decode(q, k_pool, v_pool,
                                   jnp.asarray(t_sentinel), vlen)
    got_o = ops.paged_flash_decode(q, k_pool, v_pool,
                                   jnp.asarray(t_other), vlen)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(got_o))


def test_paged_decode_shared_blocks(pool):
    """Two rows whose tables point at the same physical blocks (prefix
    sharing) read identical content: same q ⇒ same output."""
    k_pool, v_pool, tables = pool
    rng = np.random.default_rng(3)
    q_row = rng.normal(size=(1, HQ, D)).astype(np.float32)
    q = jnp.asarray(np.repeat(q_row, B, axis=0))
    shared = np.asarray(tables).copy()
    shared[1] = shared[0]                       # full sharing
    vlen = jnp.asarray([19, 19, 19], jnp.int32)
    out = ops.paged_flash_decode(q, k_pool, v_pool, jnp.asarray(shared), vlen)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))


# ---------------------------------------------------------------------------
# Decode's row loop: tables several compute blocks wide, lane folds.
# ---------------------------------------------------------------------------
LOOP_BS = 16                                   # 8 pages a compute block
LOOP_PAGES = flash_decode.BLOCK_TOKENS // LOOP_BS
LOOP_M = 2 * LOOP_PAGES + 4                    # 2.5 compute blocks wide
LOOP_VLENS = [
    (LOOP_PAGES * LOOP_BS - 1, LOOP_PAGES * LOOP_BS,
     LOOP_PAGES * LOOP_BS + 1),                # a compute-block edge, ±1
    (0, 2 * LOOP_PAGES * LOOP_BS + 1, 0),      # idle rows beside a long one
    (LOOP_M * LOOP_BS, 0, 1),                  # full table, idle, one token
]


def _loop_pools(dh, quantized, seed):
    """Scattered live pages; the sentinel page 0 holds NaN (float pools) or a
    huge scale (int8), so a dead table entry that reached the output
    would show."""
    rng = np.random.default_rng(seed)
    p = B * LOOP_M + 1
    shape = (p, HKV, LOOP_BS, dh)
    ks = vs = None
    if quantized:
        k, v = (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                for _ in range(2))
        ks, vs = (jnp.asarray(rng.uniform(0.01, 0.2, shape[:3]),
                              jnp.bfloat16).at[0].set(1e4)
                  for _ in range(2))
    else:
        k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                .at[0].set(jnp.nan) for _ in range(2))
    tables = rng.permutation(p - 1)[:B * LOOP_M].reshape(B, LOOP_M) + 1
    return k, v, ks, vs, tables


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("dh,fold", [(16, 8), (64, 2), (128, 1)])
@pytest.mark.parametrize("vlens", LOOP_VLENS)
def test_paged_decode_row_loop(vlens, dh, fold, quantized):
    """Live pages are walked a compute block at a time, across block edges;
    entries past a row's live pages point at the sentinel, which must not
    reach the output; an idle row (``vlen == 0``) writes zeros.  ``dh <
    128`` runs the lane fold, whose slices score tokens out of order."""
    assert flash_decode._lane_fold(LOOP_BS, dh) == fold
    k, v, ks, vs, tables = _loop_pools(dh, quantized, seed=dh)
    vlen = np.asarray(vlens, np.int32)
    live = -(-vlen // LOOP_BS)
    for b, n in enumerate(live):
        tables[b, n:] = 0
    tables = jnp.asarray(tables, jnp.int32)
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.normal(size=(B, HQ, dh)).astype(np.float32))
    got = np.asarray(ops.paged_flash_decode(q, k, v, tables,
                                            jnp.asarray(vlen),
                                            k_scale_pool=ks,
                                            v_scale_pool=vs))
    assert np.isfinite(got).all()
    if quantized:
        kf = _dequant_gathered(k, ks, tables)
        vf = _dequant_gathered(v, vs, tables)
    else:
        kf, vf = _gathered(k, tables), _gathered(v, tables)
    for b in range(B):
        if vlen[b] == 0:
            np.testing.assert_array_equal(got[b], 0.0)
            continue
        n = int(vlen[b])
        want = core.naive_attention(q[b:b + 1, None], kf[b:b + 1, :n],
                                    vf[b:b + 1, :n], causal=False)[0, 0]
        np.testing.assert_allclose(got[b], np.asarray(want), rtol=2e-5,
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# Prefill (offset form over pages).
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("qoffs,tq", [
    ((0, 0, 0), 8),               # fresh prefill through the table
    ((2, 9, 20), 6),              # ragged offsets, boundary-straddling vlen
    ((7, 15, 25), 1),             # single-row chunks
])
def test_paged_prefill_matches_chunked_xla(pool, qoffs, tq):
    k_pool, v_pool, tables = pool
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(B, tq, HQ, D)).astype(np.float32))
    qoff = jnp.asarray(qoffs, jnp.int32)
    vlen = qoff + tq
    got = ops.paged_flash_attention(q, k_pool, v_pool, qoff, vlen, tables,
                                    causal=True)
    want = core.online_attention(q, _gathered(k_pool, tables),
                                 _gathered(v_pool, tables), causal=True,
                                 q_offset=qoff, kv_valid_len=vlen,
                                 chunk_size=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_prefill_bq_tiling_consistent(pool):
    """Explicit bq values tile the q axis differently but must agree."""
    k_pool, v_pool, tables = pool
    rng = np.random.default_rng(5)
    tq = 8
    q = jnp.asarray(rng.normal(size=(B, tq, HQ, D)).astype(np.float32))
    qoff = jnp.asarray([0, 4, 16], jnp.int32)
    vlen = qoff + tq
    outs = [ops.paged_flash_attention(q, k_pool, v_pool, qoff, vlen, tables,
                                      causal=True, bq=bq) for bq in (2, 4, 8)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Quantized pools: in-kernel int8 dequant gather (ISSUE 10).
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def int8_pool():
    """int8 K/V pools + bfloat16 scale pages on the same block axis."""
    rng = np.random.default_rng(7)
    k8 = jnp.asarray(rng.integers(-127, 128, (P, HKV, BS, D)), jnp.int8)
    v8 = jnp.asarray(rng.integers(-127, 128, (P, HKV, BS, D)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.2, (P, HKV, BS)), jnp.bfloat16)
    vs = jnp.asarray(rng.uniform(0.01, 0.2, (P, HKV, BS)), jnp.bfloat16)
    tables = jnp.asarray(
        rng.permutation(P - 1)[:B * M].reshape(B, M) + 1, jnp.int32)
    return k8, v8, ks, vs, tables


def _dequant_gathered(pool8, spool, tables):
    """The reference: materialize the gather, THEN dequantize — the kernel
    must compute this while reading int8 + scales through the table."""
    return (_gathered(pool8.astype(jnp.float32), tables)
            * _gathered_scales(spool, tables).astype(jnp.float32)[..., None])


def _gathered_scales(spool, tables):
    g = jnp.swapaxes(spool[tables], 2, 3)
    return g.reshape(tables.shape[0], -1, HKV)


@pytest.mark.parametrize("vlens", [(5, 17, 32), (1, 8, 9)])
def test_paged_decode_int8_dequantizes_in_kernel(int8_pool, vlens):
    k8, v8, ks, vs, tables = int8_pool
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(B, HQ, D)).astype(np.float32))
    vlen = jnp.asarray(vlens, jnp.int32)
    got = ops.paged_flash_decode(q, k8, v8, tables, vlen,
                                 k_scale_pool=ks, v_scale_pool=vs)
    want = core.naive_attention(
        q[:, None], _dequant_gathered(k8, ks, tables),
        _dequant_gathered(v8, vs, tables), causal=False,
        kv_valid_len=vlen)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_prefill_int8_dequantizes_in_kernel(int8_pool):
    k8, v8, ks, vs, tables = int8_pool
    rng = np.random.default_rng(9)
    tq = 6
    q = jnp.asarray(rng.normal(size=(B, tq, HQ, D)).astype(np.float32))
    qoff = jnp.asarray([2, 9, 20], jnp.int32)
    vlen = qoff + tq
    got = ops.paged_flash_attention(q, k8, v8, qoff, vlen, tables,
                                    causal=True, k_scale_pool=ks,
                                    v_scale_pool=vs)
    want = core.online_attention(
        q, _dequant_gathered(k8, ks, tables),
        _dequant_gathered(v8, vs, tables), causal=True, q_offset=qoff,
        kv_valid_len=vlen, chunk_size=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_int8_dead_tiles_stay_dead(int8_pool):
    """Scale pages ride the SAME clamped page index as K/V — dead table
    entries (sentinel vs garbage) must not change the quantized result."""
    k8, v8, ks, vs, tables = int8_pool
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.normal(size=(B, HQ, D)).astype(np.float32))
    vlen = jnp.asarray([7, 12, 3], jnp.int32)
    live = [1, 2, 1]
    t_sentinel = np.asarray(tables).copy()
    t_other = np.asarray(tables).copy()
    for b, n in enumerate(live):
        t_sentinel[b, n:] = 0
        t_other[b, n:] = (b + 1) % (P - 1) + 1
    got_s = ops.paged_flash_decode(q, k8, v8, jnp.asarray(t_sentinel), vlen,
                                   k_scale_pool=ks, v_scale_pool=vs)
    got_o = ops.paged_flash_decode(q, k8, v8, jnp.asarray(t_other), vlen,
                                   k_scale_pool=ks, v_scale_pool=vs)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(got_o))


def test_sdpa_paged_int8_pallas_matches_xla_gather(int8_pool):
    """dispatch.sdpa with quantized pools: the Pallas preference (interpret
    here) and the XLA dequant-gather fallback must agree."""
    import repro.configs as configs
    cfg = configs.get_smoke("smollm_360m").replace(kv_cache_dtype="int8")
    k8, v8, ks, vs, tables = int8_pool
    rng = np.random.default_rng(11)
    tq = 4
    q = jnp.asarray(rng.normal(size=(B, tq, HQ, D)).astype(np.float32))
    qoff = jnp.asarray([0, 5, 11], jnp.int32)
    vlen = qoff + tq
    kw = dict(causal=True, q_offset=qoff, kv_valid_len=vlen,
              block_tables=tables, k_scale=ks, v_scale=vs)
    ref = dispatch.sdpa(cfg, q, k8, v8, **kw)
    got = dispatch.sdpa(cfg.replace(use_pallas=True), q, k8, v8, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Dispatch routing.
# ---------------------------------------------------------------------------
def test_paged_registry_paths_registered():
    assert dispatch.PATH_XLA in dispatch.available("paged_attention")
    assert dispatch.PATH_PALLAS in dispatch.available("paged_attention")
    assert dispatch.PATH_XLA in dispatch.available("paged_decode_attention")
    assert dispatch.PATH_PALLAS in dispatch.available("paged_decode_attention")


def test_sdpa_paged_routes_and_matches(pool):
    """dispatch.sdpa with block_tables set must agree between the Pallas
    preference (interpret here) and the XLA gather fallback — prefill and
    decode."""
    import repro.configs as configs
    cfg = configs.get_smoke("smollm_360m")
    k_pool, v_pool, tables = pool
    rng = np.random.default_rng(6)
    tq = 4
    q = jnp.asarray(rng.normal(size=(B, tq, HQ, D)).astype(np.float32))
    qoff = jnp.asarray([0, 5, 11], jnp.int32)
    vlen = qoff + tq
    kw = dict(causal=True, q_offset=qoff, kv_valid_len=vlen,
              block_tables=tables)
    ref = dispatch.sdpa(cfg, q, k_pool, v_pool, **kw)
    got = dispatch.sdpa(cfg.replace(use_pallas=True), q, k_pool, v_pool, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    qd = q[:, :1]
    kwd = dict(causal=False, q_offset=vlen, kv_valid_len=vlen + 1,
               decode=True, block_tables=tables)
    ref_d = dispatch.sdpa(cfg, qd, k_pool, v_pool, **kwd)
    got_d = dispatch.sdpa(cfg.replace(use_pallas=True), qd, k_pool, v_pool,
                          **kwd)
    # non-native backends route the Pallas decode preference to the XLA
    # gather form (same policy as the contiguous decode), so this is exact
    # there and allclose on TPU
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(ref_d),
                               rtol=2e-5, atol=2e-5)


def test_paged_tiles_resolve_through_registry():
    tiles = dispatch.attention_tiles("flash_attention_paged", kv_len=64,
                                     head_dim=16)
    assert set(tiles) == {"bq"} and tiles["bq"] > 0
    tiles_off = dispatch.attention_tiles("flash_attention_offset", kv_len=64,
                                         head_dim=16)
    assert set(tiles_off) == {"bq", "bk"}
