"""The trace reducer, on a trace recorded on one TPU v5e: smollm_360m at
published widths, 64 slots x 2048, four paged decode steps, each wrapped in
a ``bench.step`` annotation.  The expected numbers were read off the raw
events by hand (a 1 us occupancy grid for the busy time, plain sums for
the kernels)."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402

TRACE = os.path.join(BENCH, "tests", "data", "v5e_smollm_decode.xplane.pb.gz")


@pytest.fixture(scope="module")
def tr():
    return trace_reduce.read_file(TRACE)


def test_window_and_busy_time(tr):
    # from the first bench.step's start to the last one's end
    assert tr.window_s == pytest.approx(1.641103492, abs=1e-9)
    # busy: union of XLA op intervals, the while loop's body counted once
    assert tr.busy_s == pytest.approx(1.619838, abs=1e-4)
    assert 0.01 < 1 - tr.busy_s / tr.window_s < 0.02


def test_kernel_times(tr):
    spent, calls = tr.op_seconds("flash_decode_paged_pallas")
    assert calls == 128                     # 32 layers x 4 steps
    assert spent == pytest.approx(1.006630877, abs=1e-9)
    spent, calls = tr.op_seconds("softmax_topk_pallas")
    assert calls == 4
    assert spent == pytest.approx(0.00028563, abs=1e-9)
    assert tr.op_seconds("no_such_kernel") == (0.0, 0)


def test_decode_step_runs(tr):
    runs = tr.module_seconds("jit_decode")
    assert runs == pytest.approx([0.404698618, 0.404935137, 0.405076401,
                                  0.405116748], abs=1e-9)


def test_breakdown(tr):
    b = tr.breakdown()
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "flash_decode_paged_pallas"
    assert "while" not in names             # contains the others
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    # every idle gap in this trace falls inside a decode step's host span
    assert {n for n, _ in b["idle_gaps"]} == {"bench.step"}
    assert sum(s for _, s in tr.breakdown(top=10**6)["idle_gaps"]) == \
        pytest.approx(tr.window_s - tr.busy_s, abs=1e-9)


def test_interval_helpers():
    assert trace_reduce.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace_reduce.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4),
                                                         (5, 6)]
    assert trace_reduce.op_base(
        "%flash_decode_paged_pallas.9 = bf16[64,5,3,64] custom-call()") == \
        "flash_decode_paged_pallas"
    assert trace_reduce.module_base("jit_decode(127030)") == "jit_decode"
