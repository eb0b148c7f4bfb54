"""The one traffic generator: reads a traffic file's parameters, draws
the token ids from the run's seed, and hands the program only requests.

Two shapes of load, chosen by the file's ``loop``:

* ``open`` — sessions start on a schedule fixed before the run (bursty
  Poisson-like arrivals), each session sends 1..n turns separated by think
  times counted from the previous turn's schedule, not its completion.  A
  slow server gets the same load later, never less of it.
* ``closed`` — ``clients`` callers, each sending its next request as soon as
  its previous one has finished.

Every seed asks for the same work at the same times: sizes come from
fixed sequences of quantiles (each block of ``block`` consecutive draws
holds the block's stratified quantiles of the stated distribution, prompts
in bit-reversed order so that part of a block still spans the range, the
other sizes in fixed orders so that a prompt's answer, think time and turn
count pair with it the same way in every run), and an open loop's session
starts, bursts and system prompts are placed the same way for every seed.
A closed loop with as many clients as a block sends the same first wave.
The seed draws the token ids.  Lengths are whole tokens: nothing is
rounded to the pool's block size.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np

SHARE_STEPS = 1000        # resolution of a first request's share of its answer

# the stream that places an open loop's session starts and bursts, the
# same for every seed
SCHEDULE_STREAM = 4


def _seed_words(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed) & (2**64 - 1), salt]))


def _quantile(dist: dict, q: np.ndarray) -> np.ndarray:
    """Inverse CDF of ``dist`` at ``q`` (before clipping and rounding)."""
    kind = dist["dist"]
    if kind == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        return dist["median"] * np.exp(dist["sigma"] * z)
    if kind == "uniform":
        return dist["min"] + q * (dist["max"] - dist["min"])
    if kind == "geometric":            # on 1, 2, ...: P(n) = p (1-p)^(n-1)
        p = 1.0 / dist["mean"]
        return np.ceil(np.log1p(-q) / math.log1p(-p))
    raise ValueError(f"unknown distribution {kind!r}")


def spread_order(n: int) -> list:
    """``range(n)`` in bit-reversed (van der Corput) order: every run of
    consecutive entries, from any start, samples the range evenly."""
    bits = max(1, (n - 1).bit_length())
    rev = [int(format(i, f"0{bits}b")[::-1], 2) for i in range(1 << bits)]
    return [r for r in rev if r < n]


class StratifiedDraws:
    """Endless draws from ``dist``: each block of ``block`` draws holds the
    stratified quantiles ``(j + 1/2) / block`` in the given order (by
    default bit-reversed, so that any stretch of consecutive draws samples
    the whole range), the sequence entered at ``start``.  Values are
    clipped to ``[min, max]`` and truncated to whole numbers."""

    def __init__(self, dist: dict, block: int, start: int = 0, order=None):
        lo = dist.get("min", -math.inf)
        hi = dist.get("max", math.inf)
        vals = np.clip(_quantile(dist, (np.arange(block) + 0.5) / block),
                       lo, hi)
        order = spread_order(block) if order is None else order
        self.values = vals.astype(np.int64)[order]
        self._i = int(start)

    def next(self) -> int:
        v = self.values[self._i % len(self.values)]
        self._i += 1
        return int(v)


def fixed_order(block: int, stream: int) -> np.ndarray:
    """A permutation of ``range(block)`` that is the same for every seed:
    it pairs one stream's quantiles with another's (an answer's length with
    its prompt's) the same way in every run."""
    return np.random.default_rng(stream).permutation(block)


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it.  ``due`` is seconds after the
    start of traffic for an open loop, and None for a closed loop (due when
    its client sends it)."""
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    due: Optional[float] = None
    session: int = -1
    turn: int = 0


class Tokens:
    """Prompt segments: random ids from the seed."""

    def __init__(self, vocab: int, rng: np.random.Generator):
        self.vocab = vocab
        self._rng = rng

    def segment(self, n: int) -> np.ndarray:
        return self._rng.integers(0, self.vocab, n, dtype=np.int64)


class Traffic:
    """The generator for one traffic file, one model and one seed."""

    def __init__(self, spec: dict, *, vocab: int, slot_len: int, seed: int):
        self.spec = spec
        self.loop = spec["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"traffic loop must be open or closed, not "
                             f"{self.loop!r}")
        self.slot_len = slot_len
        self.block = block = int(spec.get("block", 64))
        self.tokens = Tokens(vocab, _seed_words(seed, 1))
        self.prompt_len = StratifiedDraws(spec["prompt"], block)
        self.new_tokens = StratifiedDraws(spec["new_tokens"], block,
                                          order=fixed_order(block, 1))
        self._first_share = StratifiedDraws(
            {"dist": "uniform", "min": 0, "max": SHARE_STEPS}, block,
            order=fixed_order(block, 2))
        self._rid = 0

    # -- closed loop ------------------------------------------------------
    @property
    def clients(self) -> int:
        return int(self.spec["clients"])

    def next_closed(self, first: bool = False) -> Planned:
        """The next request any client sends (unshared prompt).  A client's
        first request asks for a seeded share of its drawn answer, as if the
        client had been sending before the run began, so that the clients'
        requests do not all end together."""
        new = self.new_tokens.next()
        if first:
            share = self._first_share.next() / SHARE_STEPS
            new = max(1, int(math.ceil(share * new)))
        n = min(self.prompt_len.next(), self.slot_len - new)
        return self._planned(self.tokens.segment(n), new)

    # -- open loop --------------------------------------------------------
    def open_schedule(self, horizon_s: float) -> list:
        """Every request due in ``[0, horizon_s)``, sorted by due time."""
        spec = self.spec
        rng = np.random.default_rng(SCHEDULE_STREAM)
        lead = float(spec.get("lead_s", 0.0))
        starts = [t - lead for t in
                  session_starts(spec["arrivals"], horizon_s + lead, rng)]
        turns = StratifiedDraws(spec["turns"], self.block,
                                order=fixed_order(self.block, 3))
        think = StratifiedDraws(spec["think_ms"], self.block,
                                order=fixed_order(self.block, 4))
        systems = [self.tokens.segment(int(spec["system_prompt_len"]))
                   for _ in range(int(spec["system_prompts"]))]
        order = rng.permutation(len(starts))
        out = []
        for s, t0 in enumerate(starts):
            system = systems[order[s] % len(systems)]
            history: list = []             # [(user segment, answer segment)]
            due = t0
            for turn in range(turns.next()):
                if turn:
                    due += think.next() / 1000.0
                if due >= horizon_s:
                    break
                user = self.tokens.segment(self.prompt_len.next())
                new = self.new_tokens.next()
                room = self.slot_len - new - len(system) - len(user)
                kept = list(history)
                while kept and sum(len(u) + len(a) for u, a in kept) > room:
                    kept.pop(0)               # the oldest turns go first
                body = [x for pair in kept for x in pair] + [user]
                prompt = np.concatenate([system] + body)[:self.slot_len - new]
                if due >= 0.0:
                    out.append(self._planned(prompt, new, due=due, session=s,
                                             turn=turn))
                history.append((user, self.tokens.segment(new)))
        out.sort(key=lambda p: (p.due, p.rid))
        return out

    def _planned(self, prompt, new, **kw) -> Planned:
        p = Planned(rid=self._rid, prompt=np.asarray(prompt, np.int32),
                    max_new_tokens=int(new), **kw)
        self._rid += 1
        return p


def session_starts(arrivals: dict, horizon_s: float,
                   rng: np.random.Generator) -> list:
    """Session start times in ``[0, horizon_s)``.

    Time is cut into periods of ``period_s``.  Each period holds one burst
    of ``burst_s`` at ``burst_factor`` times the base rate, placed by
    ``rng``, and the base rate is set so that the mean is ``rate_per_s``.
    Each stretch of a period receives its expected count of starts, carried
    over as a fraction, at stratified positions jittered by ``rng``."""
    rate = float(arrivals["rate_per_s"])
    period = float(arrivals.get("period_s", 10.0))
    burst = float(arrivals.get("burst_s", 0.0))
    factor = float(arrivals.get("burst_factor", 1.0))
    base = rate * period / (period - burst + factor * burst)
    starts: list = []
    carry = 0.0
    t = 0.0
    while t < horizon_s:
        b0 = rng.uniform(0.0, period - burst) if burst else period
        stretches = [(t, t + b0, base), (t + b0, t + b0 + burst, base * factor),
                     (t + b0 + burst, t + period, base)]
        for lo, hi, r in stretches:
            if hi <= lo:
                continue
            want = r * (hi - lo) + carry
            n = int(want)
            carry = want - n
            pos = (np.arange(n) + rng.uniform(0.0, 1.0, n)) / max(n, 1)
            starts.extend(lo + (hi - lo) * pos)
        t += period
    return sorted(x for x in starts if x < horizon_s)
