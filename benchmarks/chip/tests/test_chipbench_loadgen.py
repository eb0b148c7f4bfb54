"""The traffic generator: deterministic per seed, the same work at the same
times for every seed, and the distributions its files state."""
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import loadgen  # noqa: E402


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


SLOT_LEN = {"decode": 2048, "longdoc": 4096}     # as the cells serve them


def _closed(name, seed, n):
    t = loadgen.Traffic(_traffic(name), vocab=49152, slot_len=SLOT_LEN[name],
                        seed=seed)
    return [t.next_closed() for _ in range(n)]


@pytest.mark.parametrize("name", ["decode", "longdoc"])
def test_closed_loop_is_deterministic_per_seed(name):
    a, b = _closed(name, 2**40 + 3, 70), _closed(name, 2**40 + 3, 70)
    assert all(np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))
    c = _closed(name, 2**40 + 4, 70)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["decode", "longdoc"])
def test_every_seed_draws_the_same_sizes(name):
    """Within a block the sizes are the block's quantiles, in an order
    fixed for every seed: two seeds ask for the same work in the same
    order, with other token ids."""
    block = _traffic(name)["block"]
    a = _closed(name, 1, 2 * block)
    b = _closed(name, 987654321987, 2 * block)
    assert ([(len(r.prompt), r.max_new_tokens) for r in a]
            == [(len(r.prompt), r.max_new_tokens) for r in b])
    assert sorted(len(r.prompt) for r in a[:block]) == sorted(
        len(r.prompt) for r in a[block:])


@pytest.mark.parametrize("start", [0, 5, 63])
def test_part_of_a_block_carries_the_same_work(start):
    """A run that draws only part of a block still gets sizes spread over
    the whole range: any 16 consecutive draws of a 64-block hold one of
    each run of four quantiles."""
    vals = loadgen.StratifiedDraws({"dist": "uniform", "min": 0, "max": 64},
                                   64, start)
    draws = [vals.next() for _ in range(16)]
    assert sorted(d // 4 for d in draws) == list(range(16))


@pytest.mark.parametrize("name", ["decode", "longdoc"])
def test_first_wave_is_the_same_for_every_seed(name):
    """Clients = block: the first requests of all clients pair the same
    prompt lengths with the same answers and shares, whatever the seed."""
    spec = _traffic(name)
    assert spec["clients"] == spec["block"]

    def wave(seed):
        t = loadgen.Traffic(spec, vocab=49152, slot_len=SLOT_LEN[name],
                            seed=seed)
        return sorted((len(p.prompt), p.max_new_tokens) for p in
                      (t.next_closed(first=True)
                       for _ in range(spec["clients"])))
    assert wave(3) == wave(2**40 + 11) == wave(12345)


def test_spread_order():
    assert loadgen.spread_order(8) == [0, 4, 2, 6, 1, 5, 3, 7]
    assert sorted(loadgen.spread_order(12)) == list(range(12))


def test_longdoc_distributions():
    spec = _traffic("longdoc")
    reqs = _closed("longdoc", 5, 4 * spec["block"])
    plen = np.array([len(r.prompt) for r in reqs])
    new = np.array([r.max_new_tokens for r in reqs])
    assert plen.min() >= 1024 and plen.max() <= 3584
    assert new.min() >= 64 and new.max() <= 256
    # medians of the stated lognormals, at whole-token resolution
    assert abs(np.median(plen) - 2048) <= 32
    assert abs(np.median(new) - 128) <= 4
    assert (plen % 16 != 0).sum() > len(plen) // 2


def test_decode_distributions():
    reqs = _closed("decode", 5, 128)
    plen = np.array([len(r.prompt) for r in reqs])
    new = np.array([r.max_new_tokens for r in reqs])
    assert plen.min() >= 64 and plen.max() <= 256
    assert abs(plen.mean() - 160) <= 16
    assert new.min() >= 256 and new.max() <= 1536
    assert abs(np.median(new) - 512) <= 16


def test_first_request_asks_for_a_share_of_its_answer():
    t = loadgen.Traffic(_traffic("decode"), vocab=49152, slot_len=2048,
                        seed=9)
    first = [t.next_closed(first=True).max_new_tokens for _ in range(64)]
    assert min(first) >= 1 and max(first) <= 1536
    assert np.median(first) < 400          # about half of a 512 median


def _chat(seed, horizon=60.0):
    t = loadgen.Traffic(_traffic("chat.smollm-360m"), vocab=49152,
                        slot_len=2048, seed=seed)
    return t.open_schedule(horizon)


def test_open_loop_is_deterministic_and_steady():
    a, b = _chat(77), _chat(77)
    assert [(p.due, len(p.prompt)) for p in a] == [(p.due, len(p.prompt))
                                                   for p in b]
    # another seed: the same schedule and sizes, other tokens
    c = _chat(2**33 + 5)
    assert ([(p.due, len(p.prompt), p.max_new_tokens, p.session) for p in a]
            == [(p.due, len(p.prompt), p.max_new_tokens, p.session)
                for p in c])
    assert not np.array_equal(a[0].prompt, c[0].prompt)
    assert all(0.0 <= p.due < 60.0 for p in a)
    assert [p.due for p in a] == sorted(p.due for p in a)
    # session starts per 10 s period are fixed by the rate, not the seed
    spec = _traffic("chat.smollm-360m")["arrivals"]
    for seed in (1, 2, 2**35):
        starts = loadgen.session_starts(spec, 60.0,
                                        np.random.default_rng(seed))
        per = np.histogram(starts, bins=6, range=(0, 60))[0]
        want = spec["rate_per_s"] * spec["period_s"]
        assert all(abs(n - want) <= 1 for n in per)


def test_open_loop_bursts():
    spec = {"rate_per_s": 10.0, "period_s": 10.0, "burst_s": 2.0,
            "burst_factor": 3.0}
    starts = np.array(loadgen.session_starts(spec, 200.0,
                                             np.random.default_rng(3)))
    assert abs(len(starts) - 2000) <= 1
    # the busiest 2 s of each period hold about 3/7 of its starts
    busiest = []
    for p in range(20):
        s = starts[(starts >= 10 * p) & (starts < 10 * p + 10)] - 10 * p
        busiest.append(max(np.sum((s >= x) & (s < x + 2))
                           for x in np.arange(0, 8.01, 0.1)))
    assert abs(np.mean(busiest) / 100 - 3 / 7) < 0.05


def test_chat_sessions_share_prefixes_and_segments_open_uniquely():
    plan = _chat(5)
    spec = _traffic("chat.smollm-360m")
    sys_len = spec["system_prompt_len"]
    systems = {tuple(p.prompt[:sys_len]) for p in plan}
    assert len(systems) == spec["system_prompts"]
    assert all(len(p.prompt) + p.max_new_tokens <= 2048 for p in plan)
    # a later turn carries its session's earlier turn as a prefix
    by_session = {}
    for p in sorted(plan, key=lambda p: p.turn):
        by_session.setdefault(p.session, []).append(p)
    later = [(s[0], s[1]) for s in by_session.values() if len(s) > 1
             and s[0].turn == s[1].turn - 1]
    assert later
    for a, b in later:
        if len(b.prompt) >= len(a.prompt) + a.max_new_tokens:
            assert np.array_equal(b.prompt[:len(a.prompt)], a.prompt)
    turns = np.array([len(s) for s in by_session.values()])
    assert 1 <= turns.min() and turns.max() <= 4


def test_lengths_are_whole_tokens_not_blocks():
    """Nothing is rounded to the pool's 16-token blocks: user turns and
    answers take every length their distributions give, and so do the
    prompts that carry them."""
    plan = _chat(5, horizon=120.0)
    spec = _traffic("chat.smollm-360m")
    user = [len(p.prompt) - spec["system_prompt_len"] for p in plan
            if p.turn == 0]
    assert any(n % 16 for n in user)
    assert min(user) < 16
    assert any(p.max_new_tokens % 16 for p in plan)
    assert any(len(p.prompt) % 16 for p in plan if p.turn > 0)


def test_stratified_draws_hold_the_quantiles():
    d = loadgen.StratifiedDraws({"dist": "lognormal", "median": 100,
                                 "sigma": 0.5}, 100, 17)
    vals = np.array([d.next() for _ in range(100)])
    assert sorted(vals) == sorted(d.values)
    assert [d.next() for _ in range(100)] == list(vals)   # blocks repeat
    assert abs(np.median(vals) - 100) <= 2
    # the 84th percentile of a lognormal sits one sigma above the median
    assert abs(np.percentile(vals, 84.13) / 100 - np.exp(0.5)) < 0.05
