"""One benchmark cell, run once: set-up, a pre-window, the measured window,
and the check of what the window served.

Everything a cell is made of is found by name from ``BENCHMARK.json``: its
configuration file (``configs/<config>.json``), its traffic file
(``traffic/<traffic>.json``) and one reader per per-layer metric
(``layer_metrics/<metric>.py``).  A later cell, mix or metric is a new file
and a new entry; this module does not change for it.

The program under test is the serving stack in ``src/repro``: one paged
``Engine`` driven tick by tick.  The benchmark takes from it only what it
served (the ``RequestResult`` of each request), its public counters
(``Engine.stats``, ``Engine.report``), the record its scheduler emits to a
``repro.obs.trace.Tracer`` in a traced run (prefill chunks, pool block
counts) and its kernels' device time.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import time
from typing import Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))

import loadgen  # noqa: E402
import reference  # noqa: E402

# configuration-file key -> the program's ModelConfig field
CONFIG_KEYS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}


WARM_RID = 2**30          # the warm-up request's id, above any traffic id


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# The cell, as the files describe it.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # configs/<config>.json
    traffic: dict           # traffic/<traffic>.json
    end_to_end: list        # BENCHMARK.json end_to_end entries of this cell
    per_layer: list         # BENCHMARK.json per_layer entries of this cell

    @property
    def model(self) -> dict:
        """Sizes in the reference's names."""
        c = self.config
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

    @property
    def serving(self) -> dict:
        return self.config["serving"]


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _covers(entry: dict, cell: str, reported: set) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry.get("moves") in reported if "moves" in entry else True


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = wl[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _covers(m, name, set())]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _covers(m, name, reported)]
    return Cell(name=name, chips=int(w["chips"]),
                config=_json(os.path.join(root, cfg_entry["file"])),
                traffic=_json(os.path.join(BENCH_DIR, "traffic",
                                           w["traffic"] + ".json")),
                end_to_end=e2e, per_layer=layer)


def layer_reader(name: str):
    """``layer_metrics/<name>.py``'s ``read``."""
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# Device and caches.
# ---------------------------------------------------------------------------
def require_chips(n: int):
    """The TPU devices the cell runs on; raises ``NoChip`` otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devs)}")
    return devs[:n]


def enable_caches() -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout
    (``JAX_COMPILATION_CACHE_DIR`` where set), every program kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Backend compiles (a persistent-cache hit counts its retrieval),
    from ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def program_config(cell: Cell):
    """The program's ModelConfig for the cell; every size in the file must
    be what runs."""
    from repro import configs
    c = cell.config
    cfg = configs.get(c["arch"]).replace(num_layers=c["num_hidden_layers"])
    for key, field in CONFIG_KEYS.items():
        got = getattr(cfg, field)
        if field == "head_dim":
            got = cfg.resolved_head_dim
        if field == "vocab_size" and cfg.real_vocab_size:
            got = cfg.real_vocab_size
        if got != c[key]:
            raise ValueError(f"{cell.name}: the program's {field} is {got}, "
                             f"the configuration file says {key}={c[key]}")
    return cfg


def make_params(cell: Cell, cfg, seed: int):
    """The served weights, drawn on the device in one compiled program:
    ``repro.models.params.init_params`` from the seed's key, then the
    embedding times the configuration's ``embedding_scale`` where it sets
    one (rounded back to the served dtype)."""
    import jax
    import jax.numpy as jnp
    from repro.models.params import init_params
    scale = cell.model["embedding_scale"]

    def build(key):
        params, _ = init_params(cfg, key)
        if scale != 1.0:
            emb = params["embedding"]["embed"]
            params["embedding"]["embed"] = (
                emb.astype(jnp.float32) * scale).astype(emb.dtype)
        return params
    return jax.jit(build)(reference.key_for_seed(seed))


# ---------------------------------------------------------------------------
# What the window saw.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Tick:
    decode: list            # keys each decode row attended (new token incl.)
    first_tokens: int       # requests whose prefill finished this tick


@dataclasses.dataclass
class Window:
    """The host-side record of one measured window."""
    start: float
    end: float
    ticks: list
    due: dict               # rid -> due time (open) or send time (closed)
    results: list           # RequestResult of every request seen
    usable_blocks: int
    counters0: dict
    counters1: dict
    lateness: list          # open loop: submit time minus due time, seconds
    # from the scheduler's trace record (traced runs only): each prefill
    # chunk begun in the window as (offset, width), the pool's (free,
    # cached) block counts after each tick, and the prompt length of each
    # request admitted in the window
    chunks: list = dataclasses.field(default_factory=list)
    blocks: list = dataclasses.field(default_factory=list)
    admitted: list = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.end - self.start


def token_times(result) -> np.ndarray:
    """Host stamps of each output token: the first token's stamp, then the
    recorded inter-token gaps added on."""
    if result.first_token_time is None or not result.tokens:
        return np.zeros(0)
    gaps = np.asarray(result.latencies[1:len(result.tokens)], np.float64)
    return result.first_token_time + np.concatenate([[0.0], np.cumsum(gaps)])


def output_tokens(win: Window) -> int:
    return int(sum(np.count_nonzero((t > win.start) & (t <= win.end))
                   for t in map(token_times, win.results)))


def ttft_samples(win: Window) -> np.ndarray:
    """First-token time of every request due in the window, from when it
    was due; one with no first token by the window's end counts its wait so
    far."""
    by_rid = {r.rid: r for r in win.results}
    out = []
    for rid, due in win.due.items():
        if not win.start <= due < win.end:
            continue
        r = by_rid.get(rid)
        first = r.first_token_time if r is not None else None
        out.append((first if first is not None else win.end) - due)
    return np.asarray(out, np.float64)


def itl_samples(win: Window) -> np.ndarray:
    """Every gap between consecutive output tokens of a request whose later
    token falls inside the window."""
    out = []
    for r in win.results:
        t = token_times(r)
        if len(t) < 2:
            continue
        later = t[1:]
        keep = (later > win.start) & (later <= win.end)
        out.append(np.diff(t)[keep])
    return np.concatenate(out) if out else np.zeros(0)


def p95(x: np.ndarray) -> Optional[float]:
    return float(np.percentile(x, 95)) if len(x) else None


def p50(x: np.ndarray) -> Optional[float]:
    return float(np.percentile(x, 50)) if len(x) else None


def sched_record(events: list, t0_us: float, t1_us: float) -> tuple:
    """What the scheduler's ``Tracer`` recorded between ``t0_us`` and
    ``t1_us``: the prefill chunks begun (``prefill_chunk`` spans, as
    (offset, width)), the pool's block counts after each tick (``blocks``
    counters, as (free, cached)), and the ids of the requests admitted
    (``queued`` spans, which end at admission)."""
    chunks, blocks, admitted = [], [], []
    for e in events:
        ts = e.get("ts", -1.0)
        if e["ph"] == "X" and e["name"] == "queued":
            if t0_us <= ts + e["dur"] < t1_us:
                admitted.append(int(e["args"]["rid"]))
        elif not t0_us <= ts < t1_us:
            continue
        elif e["ph"] == "X" and e["name"] == "prefill_chunk":
            chunks.append((int(e["args"]["pos"]), int(e["args"]["width"])))
        elif e["ph"] == "C" and e["name"] == "blocks":
            blocks.append((int(e["args"]["free"]), int(e["args"]["cached"])))
    return chunks, blocks, admitted


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric's reader reads: the cell, the window's
    host-side record, the reduced device trace and the chip's peaks."""
    cell: Cell
    window: Window
    trace: object           # trace_reduce.Trace
    peak: dict


# ---------------------------------------------------------------------------
# Driving the engine.
# ---------------------------------------------------------------------------
class Driver:
    """Offers the cell's traffic to one paged ``Engine`` and records every
    tick of the window."""

    def __init__(self, cell: Cell, params, cfg, seed: int, *, annotate=False):
        import jax
        from repro.obs.trace import Tracer
        from repro.serving.engine_api import Engine
        s = cell.serving
        self.cell = cell
        # a traced run gives the scheduler a tracer: its record of prefill
        # chunks and pool blocks is what the per-layer metrics read
        self.tracer = Tracer() if annotate else None
        self.engine = Engine(
            params, cfg, num_slots=s["slots"], slot_len=s["slot_len"],
            prefill_chunk=s["prefill_chunk"], top_k=s["top_k"],
            temperature=s["temperature"],
            base_rng=jax.random.fold_in(reference.key_for_seed(seed), 1),
            paged=True, block_size=s["block_size"],
            num_blocks=s["num_blocks"], tracer=self.tracer)
        self.sched = self.engine.scheduler
        self.pool = self.sched.pool
        self.clock = self.sched.clock
        self.traffic = loadgen.Traffic(cell.traffic, vocab=cell.config[
            "vocab_size"], slot_len=s["slot_len"], seed=seed)
        self.annotate = annotate
        self._seen_tokens: dict = {}
        self._results: dict = {}      # rid -> RequestResult
        self._due: dict = {}
        self._lateness: list = []
        self.prompts: dict = {}       # rid -> prompt, for the check
        self._client_of: dict = {}    # closed loop: rid -> client
        self._nfin = 0

    # -- warm-up ------------------------------------------------------------
    def warm(self) -> None:
        """Compile every program the window will run: the decode step at
        the cell's slot count and each prefill width a prompt can cut into
        (the chunk, then every power of two below it), with the LM head and
        sampling of a first token.  One unshared request of twice the chunk
        less one token cuts into every width."""
        from repro.serving.scheduler import Request
        chunk = self.cell.serving["prefill_chunk"]
        n = 2 * chunk - 1
        toks = loadgen.Tokens(self.cell.config["vocab_size"],
                              np.random.default_rng(0))
        self.engine.submit(Request(rid=WARM_RID, prompt=toks.segment(n),
                                   max_new_tokens=2))
        self.engine.drain()
        self.sched.finished.clear()
        self._nfin = 0

    # -- one tick -----------------------------------------------------------
    def _submit(self, planned, due: float, now: float) -> None:
        from repro.serving.scheduler import Request
        self.engine.submit(Request(rid=planned.rid, prompt=planned.prompt,
                                   max_new_tokens=planned.max_new_tokens))
        self._due[planned.rid] = due
        self.prompts[planned.rid] = planned.prompt
        if self.traffic.loop == "open":
            self._lateness.append(now - due)

    def _step(self, ticks: Optional[list]) -> list:
        """One scheduler tick; returns the rids that finished in it."""
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                self.engine.step()
        else:
            self.engine.step()
        decode, first = [], 0
        fin = self.sched.finished[self._nfin:]
        self._nfin = len(self.sched.finished)
        for r in [f.result for f in self.sched.active.values()] + fin:
            self._results[r.rid] = r
            seen = self._seen_tokens.get(r.rid, 0)
            n = len(r.tokens)
            if n == seen:
                continue
            self._seen_tokens[r.rid] = n
            for p in range(seen, n):
                if p == 0:
                    first += 1
                else:
                    decode.append(r.prompt_len + p)
        if ticks is not None:
            ticks.append(Tick(decode, first))
        return [r.rid for r in fin]

    # -- the run ------------------------------------------------------------
    def counters(self) -> dict:
        """The engine's public counters: decode steps, the sum of their
        occupancies, prefill chunks and prompt tokens the prefix cache
        served."""
        st = self.engine.stats()
        rep = self.engine.report()
        return {"decode_steps": st["decode_steps"],
                "occupancy_sum": rep.occupancy * rep.decode_steps,
                "tokens_reused": st["tokens_reused"],
                "prefill_chunks": st["prefill_chunks"]}

    def _trace_now(self) -> float:
        return self.tracer.now_us() if self.tracer is not None else 0.0

    def run(self, pre_s: float, seconds: float, on_window_start=None) -> Window:
        """Offer traffic for ``pre_s`` (set-up), call ``on_window_start``,
        then measure for ``seconds``.  Returns the window's record."""
        clock = self.clock
        t0 = clock.monotonic()
        open_loop = self.traffic.loop == "open"
        if open_loop:
            plan = self.traffic.open_schedule(pre_s + seconds + 60.0)
            nxt = 0
        else:
            for c in range(self.traffic.clients):
                p = self.traffic.next_closed(first=True)
                self._client_of[p.rid] = c
                self._submit(p, t0, t0)
        w0 = None
        w_end = None
        ticks: list = []
        c0 = None
        while True:
            now = clock.monotonic()
            if w0 is None and now - t0 >= pre_s:
                if on_window_start is not None:
                    on_window_start()
                now = clock.monotonic()
                w0, w_end = now, now + seconds
                c0 = self.counters()
                tw0 = self._trace_now()
                ticks = []
            if w_end is not None and now >= w_end:
                break
            if open_loop:
                while nxt < len(plan) and t0 + plan[nxt].due <= now:
                    self._submit(plan[nxt], t0 + plan[nxt].due, now)
                    nxt += 1
            if self.engine.busy:
                done = self._step(ticks if w0 is not None else None)
                if not open_loop:
                    now = clock.monotonic()
                    for rid in done:
                        p = self.traffic.next_closed()
                        self._client_of[p.rid] = self._client_of.pop(rid)
                        self._submit(p, now, now)
            else:
                limit = w_end if w_end is not None else t0 + pre_s
                if open_loop and nxt < len(plan):
                    limit = min(limit, t0 + plan[nxt].due)
                wait = limit - clock.monotonic()
                if wait > 0:
                    if self.annotate:
                        import jax
                        with jax.profiler.TraceAnnotation("bench.idle_wait"):
                            time.sleep(wait)
                    else:
                        time.sleep(wait)
        import jax
        jax.block_until_ready(self.pool.caches)
        end = clock.monotonic()
        tw1 = self._trace_now()
        for f in self.sched.active.values():
            self._results[f.result.rid] = f.result
        stats = self.engine.stats()
        chunks, blocks, admitted = (
            sched_record(self.tracer.events, tw0, tw1)
            if self.tracer is not None else ([], [], []))
        return Window(start=w0, end=end, ticks=ticks, due=dict(self._due),
                      results=list(self._results.values()),
                      usable_blocks=stats["num_blocks"],
                      counters0=c0, counters1=self.counters(),
                      lateness=list(self._lateness), chunks=chunks,
                      blocks=blocks,
                      admitted=[len(self.prompts[r]) for r in admitted
                                if r in self.prompts])

    def finished(self) -> list:
        return list(self.sched.finished)

    def streaming(self) -> list:
        """Results of the requests decoding when the window closed, with
        copies of the tokens they had streamed by then."""
        return [dataclasses.replace(f.result, tokens=list(f.result.tokens))
                for f in self.sched.active.values()]

    def close(self) -> None:
        """Drop the engine and its pool, so the device memory is free."""
        self.engine = self.sched = self.pool = self.tracer = None
        gc.collect()


# ---------------------------------------------------------------------------
# The check of what was served.
# ---------------------------------------------------------------------------
def pick_sample(finished: list, seed: int, want_tokens: int,
                max_requests: int, streaming: list = ()) -> list:
    """Requests to compare: the longest finished one (prompt and answer),
    then other finished ones in an order set by the seed, until
    ``want_tokens`` served tokens or ``max_requests`` requests.  Where the
    finished ones hold fewer tokens, requests still decoding when the
    window closed follow, with the tokens they had streamed."""
    def seeded(reqs, salt):
        reqs = sorted(reqs, key=lambda r: r.rid)
        rng = np.random.default_rng(np.random.SeedSequence(
            [int(seed) & (2**64 - 1), salt]))
        return [reqs[i] for i in rng.permutation(len(reqs))]

    done = [r for r in finished if r.tokens and not r.evicted]
    first = ([max(done, key=lambda r: (r.prompt_len + len(r.tokens), r.rid))]
             if done else [])
    out, n = [], 0
    for r in (first + [r for r in seeded(done, 7) if r not in first]
              + seeded([r for r in streaming if len(r.tokens) > 1], 8)):
        if n >= want_tokens or len(out) >= max_requests:
            break
        out.append(r)
        n += len(r.tokens)
    return out


def compare(cell: Cell, seed: int, sample: list, prompts: dict,
            control: Optional[str] = None) -> dict:
    """The reference's gaps over the sampled requests (see
    ``reference.served_gaps``); with ``control``, the control's too."""
    key = reference.key_for_seed(seed)
    gaps, ctl = [], []
    for r in sample:
        prompt = prompts[r.rid]
        length = cell.serving["slot_len"]
        gaps.append(reference.served_gaps(key, cell.model, prompt, r.tokens,
                                          length))
        if control:
            ctl.append(reference.served_gaps(key, cell.model, prompt,
                                             r.tokens, length,
                                             control=control))
    out = {"max_logit_gap": float(max(g.max() for g in gaps)) if gaps
           else None,
           "served_tokens_compared": int(sum(len(g) for g in gaps)),
           "requests_compared": len(gaps)}
    if control:
        out["control_max_logit_gap"] = (float(max(g.max() for g in ctl))
                                        if ctl else None)
    return out


def verdict(max_logit_gap: Optional[float], tokens_compared: int,
            limit: Optional[float], min_tokens: int) -> tuple:
    """``correct``, and each number compared beside its limit: the widest
    gap at most ``limit``, over at least ``min_tokens`` served tokens.  A
    run with no gap to read, or a cell with no limit, is not correct."""
    correct = bool(limit is not None and max_logit_gap is not None
                   and max_logit_gap <= limit
                   and tokens_compared >= min_tokens)
    checks = {"max_logit_gap": {"value": max_logit_gap, "limit": limit},
              "served_tokens_compared": {"value": tokens_compared,
                                         "limit": min_tokens}}
    return correct, checks
