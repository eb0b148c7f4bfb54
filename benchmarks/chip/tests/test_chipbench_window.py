"""Window statistics: which requests and gaps count, and the p95 over all
of them; and the FLOP and byte counts at shapes worked out by hand."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import harness  # noqa: E402
from repro.serving.scheduler import RequestResult  # noqa: E402


def _result(rid, first, gaps, prompt_len=16):
    r = RequestResult(rid=rid, prompt_len=prompt_len)
    if first is not None:
        r.first_token_time = first
        r.record_latency(first)            # first: time from arrival
        for g in gaps:
            r.record_latency(g)
        r.tokens = list(range(1 + len(gaps)))
    return r


def _window(results, due, start=10.0, end=20.0):
    return harness.Window(start=start, end=end, ticks=[], due=due,
                          results=results, usable_blocks=1,
                          counters0={}, counters1={}, lateness=[])


def test_token_times_rebuild_from_gaps():
    r = _result(0, 11.0, [0.5, 0.25])
    assert np.allclose(harness.token_times(r), [11.0, 11.5, 11.75])
    assert harness.token_times(_result(1, None, [])).size == 0


def test_unserved_request_counts_its_wait_so_far():
    results = [_result(0, 12.0, []), _result(1, None, [])]
    # rid 1 was due at 15 and had no first token when the window closed at
    # 20; rid 2 never reached the scheduler's results at all; rid 3 was due
    # before the window and does not count
    win = _window(results, {0: 11.0, 1: 15.0, 2: 19.0, 3: 9.0})
    assert sorted(harness.ttft_samples(win)) == pytest.approx([1.0, 1.0, 5.0])


def test_ttft_p95_is_over_every_request_due():
    n = 100
    results = [_result(i, 10.0 + i * 0.01 + (i + 1) * 0.001, [])
               for i in range(n)]
    due = {i: 10.0 + i * 0.01 for i in range(n)}
    win = _window(results, due)
    got = harness.p95(harness.ttft_samples(win))
    assert got == pytest.approx(np.percentile((np.arange(n) + 1) * 0.001, 95))


def test_itl_counts_gaps_ending_inside_the_window():
    a = _result(0, 9.0, [0.5, 0.75, 1.0])   # tokens at 9, 9.5, 10.25, 11.25
    b = _result(1, 19.5, [0.25, 0.5])       # tokens at 19.5, 19.75, 20.25
    win = _window([a, b], {})
    assert sorted(harness.itl_samples(win)) == pytest.approx([0.25, 0.75,
                                                              1.0])
    # tokens inside (10, 20]: 10.25, 11.25, 19.5, 19.75
    assert harness.output_tokens(win) == 4


def test_p95_of_nothing_is_none():
    assert harness.p95(np.zeros(0)) is None


SMOLLM = {"vocab_size": 49152, "d_model": 960, "num_layers": 32,
          "num_heads": 15, "num_kv_heads": 5, "head_dim": 64, "d_ff": 2560,
          "dtype": "bfloat16"}


def test_layer_flops_by_hand():
    # q 960x960, k and v 960x320 each, o 960x960, MLP 3 x 960x2560
    per = 960 * 960 * 2 + 2 * 960 * 320 + 3 * 960 * 2560
    assert flops.layer_matmul_flops(SMOLLM) == 2 * per


def test_decode_and_prefill_flops_by_hand():
    m = dict(SMOLLM, num_layers=1)
    mm = flops.layer_matmul_flops(m)
    head = 2 * 960 * 49152
    # two rows at contexts 10 and 20: QK^T and PV are 2 * 2 * 15 * 64 each
    assert flops.decode_flops(m, [10, 20]) == pytest.approx(
        2 * (mm + head) + 4 * 15 * 64 * 30)
    # a 3-token chunk after 5 cached tokens attends 6, 7 and 8 keys
    assert flops.prefill_flops(m, 5, 3) == pytest.approx(
        3 * mm + 4 * 15 * 64 * 21)


def test_kernel_bytes_by_hand():
    m = dict(SMOLLM, num_layers=2)
    kv = 2 * 2 * 5 * 64 * 2                 # K and V, 2 layers, bf16
    assert flops.kv_bytes_per_token(m) == kv
    f, b = flops.paged_decode_cost(m, [100, 28])
    assert f == pytest.approx(4 * 15 * 64 * 2 * 128)
    assert b == pytest.approx(kv * 128 + 2 * 2 * (2 * 15 * 64 * 2))
    f, b = flops.paged_prefill_cost(m, 16, 16)
    assert b == pytest.approx(kv * 32 + 2 * 2 * 16 * 15 * 64 * 2)
    assert flops.softmax_topk_bytes(64, 49152) == 64 * 49152 * 4


def test_least_time_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time(1000.0, 10.0, peak) == (10.0, "compute")
    assert flops.least_time(10.0, 1000.0, peak) == (100.0, "memory")


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")


def _served(rid, prompt_len, n, evicted=False):
    r = RequestResult(rid=rid, prompt_len=prompt_len)
    r.tokens = list(range(n))
    r.evicted = evicted
    return r


def test_sample_takes_the_longest_finished_then_seeded_others():
    done = [_served(i, 16 * (i + 1), 32) for i in range(10)]
    done.append(_served(99, 4000, 32, evicted=True))   # never compared
    pick = harness.pick_sample(done, seed=5, want_tokens=96, max_requests=8)
    assert pick[0].rid == 9                   # longest prompt + answer
    assert len(pick) == 3 and 99 not in {r.rid for r in pick}
    again = harness.pick_sample(done, seed=5, want_tokens=96, max_requests=8)
    assert [r.rid for r in pick] == [r.rid for r in again]
    other = harness.pick_sample(done, seed=6, want_tokens=96, max_requests=8)
    assert other[0].rid == 9


def test_sample_tops_up_with_streamed_tokens():
    done = [_served(0, 16, 16)]
    streaming = [_served(i, 16, 40) for i in range(1, 5)] + [_served(9, 16, 1)]
    pick = harness.pick_sample(done, seed=1, want_tokens=64,
                               max_requests=8, streaming=streaming)
    assert pick[0].rid == 0
    assert sum(len(r.tokens) for r in pick) >= 64
    assert 9 not in {r.rid for r in pick}     # one token: nothing to follow
    assert harness.pick_sample([], seed=1, want_tokens=64,
                               max_requests=8) == []
