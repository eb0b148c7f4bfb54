#!/usr/bin/env python3
"""Chip smoke: serve smollm_360m at published widths on one TPU.

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --four-chips  # the sharded train step on four chips

One chip: smollm_360m (32 layers, d_model 960, 15 query / 5 KV heads,
head_dim 64, vocab 49152, bf16, random weights from ``--seed``) serves 16
requests through ``ReplicaRouter`` → ``Engine`` → paged pool → dispatch,
the path ``python -m repro.launch.serve --continuous --paged`` drives.  The
smoke then checks that the paged attention ops and ``softmax_topk`` resolved
to the native Pallas kernels, compares one paged decode step, one paged
prefill chunk (bf16 and int8 pools) and ``softmax_topk`` against their
reference forms on the chip (and checks that each comparison catches a
planted fault in the block table), checks the model's logits are finite,
and serves a few requests again with int8 KV.

Four chips: only the sharded train step of smollm_360m at published widths,
for a few steps on two meshes (all four chips on ``data``, the launcher's
default; and 2×2 ``data``×``model``), each compared with the same steps on
one device, with fp32 and with bf16 weights.

Everything runs in this one process (a chip belongs to one process).  Times
printed here are a smoke run's, not measurements.  Any failed phase exits
non-zero; on success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

ARCH = "smollm_360m"
SLOTS = 8
BLOCK = 16             # pool block size in tokens (compile-rehearsed)
CHUNK = 128            # prefill chunk
PREFIX = 64            # shared system-prompt prefix
NEW_TOKENS = 32
MAX_PROMPT = 512
SLOT_LEN = -(-(MAX_PROMPT + NEW_TOKENS + 8) // BLOCK) * BLOCK   # as serve.py
TOP_K = 5
REQUESTS = 16          # bf16 KV
INT8_REQUESTS = 4      # int8 prefill is single-shot: one compile per length


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds of XLA compilation (a persistent-cache hit counts only its
    retrieval), summed from ``jax.monitoring``; tracing is not counted."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


# ---------------------------------------------------------------------------
# One chip: serving.
# ---------------------------------------------------------------------------
def make_requests(n: int, vocab: int, seed: int):
    """``n`` requests behind one shared ``PREFIX``-token system prompt.
    Prompt lengths are PREFIX + a multiple of 64 in [128, 512], so after the
    prefix hit every prefill is whole CHUNKs plus at most one 64-token tail:
    two prefill widths to compile.  Arrivals are Poisson in scheduler
    ticks, as ``scheduler.poisson_workload`` draws them."""
    import numpy as np
    from repro.serving.scheduler import Request
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, PREFIX)
    bodies = rng.choice([64, 128, 192, 320, 448], size=n)
    t = 0.0
    out = []
    for rid, body in enumerate(bodies):
        t += rng.exponential(0.5)
        out.append(Request(
            rid=rid,
            prompt=np.concatenate([prefix, rng.integers(0, vocab, body)]),
            max_new_tokens=NEW_TOKENS, arrival_tick=int(t)))
    return out


def serve(params, cfg, requests, clock, label: str, expect_paths: dict):
    """Serve ``requests`` the way ``repro.launch.serve --continuous --paged``
    does and check what came out."""
    import jax
    from repro.obs import clock as obs_clock
    from repro.obs import kernels as obs_kernels
    from repro.serving.router import ReplicaRouter

    obs_kernels.reset()
    vocab = cfg.real_vocab_size or cfg.vocab_size
    router = ReplicaRouter(
        params, cfg, replicas=1, num_slots=SLOTS, slot_len=SLOT_LEN,
        prefill_chunk=CHUNK, top_k=TOP_K, base_rng=jax.random.PRNGKey(0),
        paged=True, block_size=BLOCK)
    c0, t0 = clock.seconds, obs_clock.monotonic()
    report = router.serve(requests)
    wall = obs_clock.monotonic() - t0
    compiled = clock.seconds - c0
    tokens = report.total_tokens
    print(f"[{label}] served {len(report.results)} requests, {tokens} "
          f"tokens in {wall:.2f}s wall, of which {compiled:.2f}s compiling "
          f"(smoke run, not a measurement: "
          f"{tokens / max(wall - compiled, 1e-9):.1f} tok/s outside "
          "compiles)")
    print(f"[{label}] decode steps {report.decode_steps}, prefill chunks "
          f"{report.prefill_chunks}, occupancy {report.occupancy:.3f}, "
          f"prefill tokens reused {report.paged['tokens_reused']}")
    check(len(report.results) == len(requests),
          f"{label}: {len(report.results)} of {len(requests)} answered")
    for r in report.results:
        check(not r.evicted, f"{label}: request {r.rid} evicted")
        check(len(r.tokens) == NEW_TOKENS,
              f"{label}: request {r.rid} got {len(r.tokens)} tokens")
        check(all(0 <= t < vocab for t in r.tokens),
              f"{label}: request {r.rid} emitted ids outside [0, {vocab})")
    prof = obs_kernels.snapshot()
    for op, rec in prof["paths"].items():
        print(f"[{label}] kernel path: {op} → {rec['path']} "
              f"(×{rec['count']})")
    for op, want in expect_paths.items():
        got = prof["paths"].get(op, {}).get("path")
        check(got == want, f"{label}: {op} resolved to {got}, not {want}")
    for key, dec in prof["autotune"].items():
        choice = dec.get("tiles", dec.get("block"))
        print(f"[{label}] autotune {key}: {choice} "
              f"(timings_us {dec['timings_us']})")
    return report


def model_logits_finite(params, cfg):
    """One paged prefill chunk through the scheduler's own jitted step, then
    the LM head: the logits must be finite, of shape [1, vocab]."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serving import engine, paged
    from repro.serving.scheduler import _jitted_paged_steps

    _, prefill = _jitted_paged_steps(cfg, TOP_K, 1.0)
    pool = paged.PagedPool(cfg, SLOTS, SLOT_LEN, BLOCK)
    prompt = np.arange(CHUNK, dtype=np.int32) * 7 % cfg.vocab_size
    seq = pool.admit(prompt)
    last, _, _ = prefill(params, pool.caches, pool.device_row(seq.slot),
                         jnp.asarray(0, jnp.int32),
                         jnp.asarray(prompt[None]))
    logits = np.asarray(engine.logits_from_hidden(params, last, cfg))
    check(logits.shape == (1, cfg.vocab_size), f"logits {logits.shape}")
    check(np.isfinite(logits).all(), "non-finite logits")
    print(f"logits: shape {logits.shape}, finite, range "
          f"[{logits.min():.3f}, {logits.max():.3f}]")


# ---------------------------------------------------------------------------
# One chip: kernel-vs-reference comparisons on the device.
# ---------------------------------------------------------------------------
def _paged_operands(cfg, quantized: bool, seed: int):
    """A pool of ``SLOTS`` rows at the serving block size.  int8 pools are
    quantized from normal data the way ``layers._quantize_kv`` writes them,
    so dequantized K/V have serving-like magnitudes."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models import layers as L
    rng = np.random.default_rng(seed)
    hkv, d = cfg.num_kv_heads, cfg.resolved_head_dim
    m = SLOT_LEN // BLOCK
    p = SLOTS * m + 1
    kv = [jnp.asarray(rng.standard_normal((p, hkv, BLOCK, d)), jnp.bfloat16)
          for _ in range(2)]
    scales = [None, None]
    if quantized:
        pairs = [L._quantize_kv(x[..., None, :]) for x in kv]
        kv = [q[..., 0, :] for q, _ in pairs]
        scales = [s[..., 0] for _, s in pairs]
    tables = jnp.asarray(rng.permutation(p - 1)[:SLOTS * m]
                         .reshape(SLOTS, m) + 1, jnp.int32)
    return kv, scales, tables, rng


def _drop_last_page(tables, lens):
    """The planted fault: each row's last live page is read from the row's
    first page instead, as an off-by-one page clamp or a stale table entry
    in the kernel's gather would do."""
    import jax.numpy as jnp
    import numpy as np
    t = np.array(tables)
    for r, n in enumerate(-(-np.asarray(lens) // BLOCK)):
        if n >= 2:
            t[r, n - 1] = t[r, 0]
    return jnp.asarray(t)


def _vector_errors(got, want):
    """‖got − want‖₂ / ‖want‖₂ of each attention output vector (one head
    of one query token, over the head dim), as [B, T, H]."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return (np.linalg.norm(got - want, axis=-1)
            / np.maximum(np.linalg.norm(want, axis=-1), 1e-6))


def _compare_vectors(name, got, want, rtol, why):
    import numpy as np
    worst = _vector_errors(got, want).max()
    print(f"compare {name}: worst relative error {worst:.3e}, "
          f"{worst / rtol:.3f} of the bound ({rtol:g}: {why})")
    check(np.isfinite(np.asarray(got, np.float32)).all(),
          f"{name}: non-finite output")
    check(worst <= rtol, f"{name}: outside tolerance")


def _check_fault_caught(name, got, want, rtol, rows):
    """The comparison must fail on every one of ``rows`` when the kernel
    runs with a planted fault."""
    import numpy as np
    per_row = _vector_errors(got, want).max(axis=(1, 2))
    print(f"planted fault, {name}: worst relative error per row "
          f"{np.array2string(per_row, precision=3)} (bound {rtol:g})")
    missed = [r for r in rows if not per_row[r] > rtol]
    check(not missed, f"{name}: a planted fault passed the comparison in "
                      f"rows {missed}")


def _compare_relative(name, got, want, rtol, why):
    """Elementwise |got − want| ≤ rtol·|want|."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    worst = (np.abs(got - want) / np.abs(want)).max()
    print(f"compare {name}: worst relative error {worst:.3e}, "
          f"{worst / rtol:.3f} of the bound ({rtol:g}: {why})")
    check(np.isfinite(got).all(), f"{name}: non-finite output")
    check(worst <= rtol, f"{name}: outside tolerance")


# Bounds on each output vector's relative error (‖Δ‖₂/‖ref‖₂ over the head
# dim).  References run at full fp32 matmul precision.  Each form rounds its
# output to bf16, at most 2^-8 of each element, so the pair may differ by
# 2^-7 ≈ 7.8e-3; the kernel also rounds its softmax weights to bf16 before
# the P·V dot (~2^-9).  The bounds take twice that, and stay a tenth of the
# smallest error a planted fault gives (one wrong 16-token page per row:
# 0.32 to 0.95 per row in interpret mode on a CPU).
_BF16_WHY = ("bf16 output rounding in both forms (≤ 7.8e-3) plus the "
             "kernel's bf16 softmax weights in its P·V dot, doubled")
_INT8_WHY = ("as bf16, plus dequantized K/V (int8 × bf16 scale) rounded to "
             "bf16 in the kernel's dots, ~2^-8 more on scores and values")
RTOL = {False: (2e-2, _BF16_WHY), True: (3e-2, _INT8_WHY)}


def compare_paged_attention(cfg, quantized: bool, seed: int,
                            path: str = "pallas"):
    """One paged decode step over ragged rows (1 to ``SLOT_LEN`` tokens) and
    one paged prefill chunk (the last ``CHUNK`` tokens of a full row):
    dispatch's route (the Pallas kernels on TPU; ``path`` names it) against
    the gather + chunked-XLA form on the same pool.  Each comparison is
    then made again with a planted fault in the kernel's block table, which
    it must catch."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import dispatch
    from repro.obs import kernels as obs_kernels

    (k, v), (ks, vs), tables, rng = _paged_operands(cfg, quantized, seed)
    hq, d = cfg.num_heads, cfg.resolved_head_dim
    rtol, why = RTOL[quantized]
    tag = "int8" if quantized else "bf16"
    obs_kernels.reset()

    lens = np.linspace(1, SLOT_LEN, SLOTS).astype(np.int32)    # ragged
    q = jnp.asarray(rng.standard_normal((SLOTS, 1, hq, d)), jnp.bfloat16)
    kw = dict(q_offset=jnp.asarray(lens - 1), kv_valid_len=jnp.asarray(lens),
              scale=None, k_scale=ks, v_scale=vs)
    got = dispatch.sdpa(cfg, q, k, v, causal=False, decode=True,
                        block_tables=tables, **kw)
    with jax.default_matmul_precision("float32"):
        want = dispatch.implementation("paged_decode_attention",
                                       dispatch.PATH_XLA)(
            cfg, q, k, v, block_tables=tables, **kw)
    name = f"paged decode ({tag}, rows of {lens.tolist()} tokens)"
    _compare_vectors(name, got, want, rtol, why)
    bad = dispatch.sdpa(cfg, q, k, v, causal=False, decode=True,
                        block_tables=_drop_last_page(tables, lens), **kw)
    _check_fault_caught(name, bad, want, rtol,
                        [r for r in range(SLOTS) if lens[r] > BLOCK])

    off, tq = SLOT_LEN - CHUNK, CHUNK
    qp = jnp.asarray(rng.standard_normal((1, tq, hq, d)), jnp.bfloat16)
    kw = dict(q_offset=jnp.asarray([off], jnp.int32),
              kv_valid_len=jnp.asarray([SLOT_LEN], jnp.int32), scale=None,
              k_scale=ks, v_scale=vs)
    got = dispatch.sdpa(cfg, qp, k, v, causal=True, block_tables=tables[:1],
                        **kw)
    with jax.default_matmul_precision("float32"):
        want = dispatch.implementation("paged_attention", dispatch.PATH_XLA)(
            cfg, qp, k, v, causal=True, block_tables=tables[:1], **kw)
    name = f"paged prefill chunk ({tag}, {tq} tokens at offset {off})"
    _compare_vectors(name, got, want, rtol, why)
    bad = dispatch.sdpa(cfg, qp, k, v, causal=True,
                        block_tables=_drop_last_page(tables[:1], [SLOT_LEN]),
                        **kw)
    _check_fault_caught(name, bad, want, rtol, [0])
    paths = obs_kernels.snapshot()["paths"]
    for op in ("paged_decode_attention", "paged_attention"):
        check(paths.get(op, {}).get("path") == path,
              f"{tag} {op} did not take the {path} kernel: {paths.get(op)}")


def compare_softmax_topk(cfg, seed: int):
    """``dispatch.softmax_topk`` (the fused Pallas kernel on TPU) against
    ``lax.top_k(softmax(x))`` in fp32, at the served shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import dispatch

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((SLOTS, cfg.vocab_size)) * 4.0,
                    jnp.float32)
    out = dispatch.softmax_topk(x, TOP_K)
    want_vals, want_idx = jax.lax.top_k(jax.nn.softmax(x, axis=-1), TOP_K)
    # both fp32: exp may differ by a few ulps between the kernel and XLA,
    # and the 49152-term normalizer sums in a different order (~1e-6
    # relative expected; 1e-4 leaves headroom)
    _compare_relative("softmax_topk values", out.values, want_vals, 1e-4,
                      "fp32 exp ulps + normalizer summation order")
    _compare_relative("softmax_topk lse", out.logsumexp,
                      jax.scipy.special.logsumexp(x, axis=-1), 1e-5,
                      "fp32 exp ulps + summation order, lse ≈ 19")
    xs = np.asarray(x)
    got_logits = np.take_along_axis(xs, np.asarray(out.indices), -1)
    want_logits = np.take_along_axis(xs, np.asarray(want_idx), -1)
    check(np.array_equal(got_logits, want_logits),
          "softmax_topk picked different logits than lax.top_k")
    print("compare softmax_topk indices: the same top-5 logits per row")


# ---------------------------------------------------------------------------
# Four chips: the sharded train step against one device.
# ---------------------------------------------------------------------------
TRAIN_STEPS = 3
TRAIN_SEQ = 256
TRAIN_BATCH = 8
# Bound on |Δloss| between a mesh and one device, each step from the same
# state.  fp32 weights: tests/test_distributed.py's 5e-3, absolute.  bf16
# weights (as served): the row-parallel matmuls of the model axis round
# each partial sum to bf16 before adding them, which alone moves the loss
# by ~5e-5 of itself (CPU, 8 layers: 5.0e-5 in bf16, 8.8e-8 in fp32), so
# the bound is relative: 2^-10 of the loss.  Dropping or doubling one model
# shard's partial sum in one layer of eight moves it by 2.2% / 4.8% (CPU).
LOSS_BOUND = {"float32": lambda loss: 5e-3,
              "bfloat16": lambda loss: abs(loss) * 2.0 ** -10}


def train_losses(cfg, seed: int, mesh_dims):
    """Losses of ``TRAIN_STEPS`` train steps on one device, and, from the
    same state at each step, sharded on each mesh of ``mesh_dims`` the way
    ``repro.launch.train`` shards it (params by the sharding rules, each
    batch over the data axes).  Steps start from a common state because
    whole trajectories part: with bf16 weights, AdamW's early updates
    (≈ lr·sign(g)) turn last-bit gradient differences into different
    weights.  Between steps the state waits on the host, so one device
    never holds two copies of it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.configs.base import OptimizerConfig, ParallelConfig, RunConfig
    from repro.data.synthetic import SyntheticConfig, SyntheticDataset
    from repro.distributed import context, sharding
    from repro.models.params import init_params
    from repro.optim import adamw
    from repro.training.train_step import make_train_step

    run = RunConfig(model=cfg, optimizer=OptimizerConfig(
        lr=1e-3, warmup_steps=0, schedule="constant"),
        parallel=ParallelConfig(grad_reduce_dtype="float32"))
    params, axes = init_params(cfg, jax.random.PRNGKey(seed))
    state = jax.device_get((params, adamw.init(params)))
    del params
    ds = SyntheticDataset(SyntheticConfig(
        vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ,
        global_batch=TRAIN_BATCH, seed=seed))
    meshes = []
    for dims in mesh_dims:
        mesh = compat.make_mesh(dims, ("data", "model"))
        par = sharding.derive_parallel(cfg, mesh, run.parallel)
        meshes.append((f"data={dims[0]} x model={dims[1]}", mesh, par,
                       sharding.param_sharding(axes, cfg, par, mesh),
                       compat.named_sharding(mesh, P(par.data_axes)),
                       jax.jit(make_train_step(run), donate_argnums=(0, 1))))
    one = jax.jit(make_train_step(run), donate_argnums=(0, 1))
    ref, got = [], {name: [] for name, *_ in meshes}
    for i in range(TRAIN_STEPS):
        batch = compat.tree_map(jnp.asarray, ds.batch(i))
        for name, mesh, par, p_sh, on_data, step in meshes:
            with mesh, context.use(context.ShardContext(mesh=mesh, par=par)):
                _, _, m = step(jax.device_put(state[0], p_sh), state[1],
                               jax.device_put(batch, on_data))
            got[name].append(float(m["loss"]))
        params, opt, m = one(*jax.device_put(state), batch)
        ref.append(float(m["loss"]))
        state = jax.device_get((params, opt))
        del params, opt
    return ref, got


def four_chip_train(cfg, seed: int, clock):
    """The sharded train step against one device on the launcher's default
    mesh (all chips on ``data``) and on the 2×2 ``data``×``model`` mesh,
    with fp32 weights (the test's bound) and with bf16 weights (as served).
    fp32 dots run at full precision, so that run is the arithmetic with
    next to no rounding."""
    import contextlib
    import jax

    n = len(jax.devices())
    bad = []
    for dtype in ("float32", "bfloat16"):
        c0 = clock.seconds
        prec = (jax.default_matmul_precision("float32")
                if dtype == "float32" else contextlib.nullcontext())
        with prec:
            ref, got = train_losses(cfg.replace(dtype=dtype), seed,
                                    ((n, 1), (n // 2, 2)))
        print(f"[train {dtype}] one device: losses {ref} "
              f"({clock.seconds - c0:.1f}s compiling, all meshes)")
        for name, losses in got.items():
            gaps = [abs(a - b) for a, b in zip(losses, ref)]
            bounds = [LOSS_BOUND[dtype](b) for b in ref]
            print(f"[train {dtype}] mesh {name}: losses {losses}, |Δloss| "
                  f"vs one device from the same state "
                  f"{[f'{g:.3e}' for g in gaps]} "
                  f"(bounds {[f'{b:.3e}' for b in bounds]})")
            if any(g >= b for g, b in zip(gaps, bounds)):
                bad.append(f"{dtype} {name}: {max(gaps):.3e}")
    check(not bad, f"sharded losses differ from one device: {bad}")


# ---------------------------------------------------------------------------
def _phase(name, fn, *args, failures):
    print(f"== {name}", flush=True)
    try:
        return fn(*args)
    except Exception:
        traceback.print_exc()
        failures.append(name)
        print(f"== {name}: FAILED", flush=True)
        return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on four chips "
                         "and its one-device comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the random weights and the workload")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_PALLAS_INTERPRET"):
        print("chip_smoke: REPRO_PALLAS_INTERPRET is set; the chip path must "
              "not run Pallas in interpret mode", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform}: "
              f"{devices[0].device_kind}); nothing to smoke", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 1

    import repro.configs as configs
    from repro import cache_dirs
    from repro.models.params import init_params
    from repro.obs import clock as obs_clock

    print(f"device: {devices[0].device_kind} ×{len(devices)}, jax "
          f"{jax.__version__}")
    print(f"compile cache: {cache_dirs.enable_compile_cache()}")
    clock = CompileClock()
    cfg = configs.get(ARCH)
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"head_dim={cfg.resolved_head_dim} vocab={cfg.vocab_size} "
          f"dtype={cfg.dtype}")
    failures: list = []
    t_start = obs_clock.monotonic()

    if args.four_chips:
        _phase("sharded train step vs one device", four_chip_train, cfg,
               args.seed, clock, failures=failures)
    else:
        c0, t0 = clock.seconds, obs_clock.monotonic()
        params, _ = init_params(cfg, jax.random.PRNGKey(args.seed))
        jax.block_until_ready(params)
        print(f"init: {obs_clock.monotonic() - t0:.2f}s "
              f"({clock.seconds - c0:.2f}s compiling)")
        vocab = cfg.real_vocab_size or cfg.vocab_size
        paths = {"paged_attention": "pallas",
                 "paged_decode_attention": "pallas",
                 "softmax_topk": "pallas"}
        _phase("serve bf16 KV", serve, params, cfg,
               make_requests(REQUESTS, vocab, args.seed), clock,
               "bf16", paths, failures=failures)
        _phase("model logits", model_logits_finite, params, cfg,
               failures=failures)
        for quantized in (False, True):
            _phase(f"paged kernels vs XLA ({'int8' if quantized else 'bf16'})",
                   compare_paged_attention, cfg, quantized, args.seed,
                   failures=failures)
        _phase("softmax_topk vs lax.top_k", compare_softmax_topk, cfg,
               args.seed, failures=failures)
        # int8 prefill is single-shot over the prompt's exact fp K/V (the
        # contiguous attention path), so only decode and top-k are paged
        # here; the int8 paged prefill kernel is compared above
        int8_paths = {"paged_decode_attention": "pallas",
                      "softmax_topk": "pallas"}
        _phase("serve int8 KV", serve, params,
               cfg.replace(kv_cache_dtype="int8"),
               make_requests(INT8_REQUESTS, vocab, args.seed + 1),
               clock, "int8", int8_paths, failures=failures)

    stats = devices[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")
    print(f"total: {obs_clock.monotonic() - t_start:.1f}s, "
          f"{clock.seconds:.1f}s compiling")
    if failures:
        print(f"chip_smoke: FAILED phases: {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
