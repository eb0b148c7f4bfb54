"""Regression tests for the repro.compat portability layer and the
capability-probing kernel dispatch registry.

Three bug classes took down the seed suite (missing ``jax.shard_map``
export, ``cost_analysis()`` list-vs-dict, hard ``import hypothesis``); these
tests pin the shims against the *installed* JAX and grep-enforce the policy
that no module outside ``repro.compat`` touches those surfaces again.
"""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat, core
from repro.kernels import dispatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src", "repro")


# ---------------------------------------------------------------------------
# Shim resolution on the installed JAX.
# ---------------------------------------------------------------------------
def test_shard_map_shim_resolves_and_runs():
    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((1,), ("data",))
    f = compat.shard_map(lambda x: x * 2.0, mesh=mesh, in_specs=P(),
                         out_specs=P(), check_vma=False)
    np.testing.assert_allclose(np.asarray(f(jnp.arange(4.0))),
                               [0.0, 2.0, 4.0, 6.0])


def test_make_mesh_axes_are_auto():
    """Sharding rules rely on propagation: an Explicit-axis mesh would make
    every gather on a sharded operand state its out_sharding."""
    from jax.sharding import AxisType
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
    assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)


def test_tree_shims_resolve_and_run():
    t = {"a": [1, 2], "b": 3}
    assert compat.tree_map(lambda x: x * 2, t) == {"a": [2, 4], "b": 6}
    leaves, treedef = compat.tree_flatten(t)
    assert compat.tree_leaves(t) == leaves == [1, 2, 3]
    assert compat.tree_structure(t) == treedef
    assert compat.tree_unflatten(treedef, leaves) == t
    assert compat.tree_reduce(lambda a, b: a + b, t) == 6
    # is_leaf threads through (the Param-boxing pattern in models.layers)
    pairs = compat.tree_map(lambda p: p[0], {"w": (1, "x")},
                            is_leaf=lambda x: isinstance(x, tuple))
    assert pairs == {"w": 1}


def test_named_sharding_shim_constructs():
    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((1,), ("data",))
    s = compat.named_sharding(mesh, P("data"))
    assert s.spec == P("data")
    assert compat.named_sharding(mesh).spec == P()          # replicated
    assert compat.named_sharding(mesh, ("data", None)).spec == P("data", None)
    # it is a real sharding: jax accepts it as a device_put target
    x = jax.device_put(jnp.arange(4.0), compat.named_sharding(mesh))
    np.testing.assert_allclose(np.asarray(x), [0.0, 1.0, 2.0, 3.0])


def test_cost_analysis_always_a_dict():
    compiled = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    ca = compat.cost_analysis(compiled)
    assert isinstance(ca, dict)
    assert float(ca.get("flops", 0.0)) > 0.0


def test_capabilities_probe_is_cached_and_sane():
    caps = compat.capabilities()
    assert caps is compat.capabilities()          # one snapshot per process
    assert caps.jax_version == jax.__version__
    assert caps.backend in ("cpu", "gpu", "tpu")
    assert caps.device_count >= 1
    # on a non-TPU host Pallas must resolve to interpret mode
    if caps.backend != "tpu":
        assert not caps.pallas_native and caps.pallas_interpret


_CACHE_PROBE = """
import sys
import jax, jax.numpy as jnp
from repro import cache_dirs
cache_dirs.CACHE_ROOT = sys.argv[1]
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(cache_dirs.enable_compile_cache())
jax.jit(lambda x: x * 2 + 1)(jnp.arange(3.0)).block_until_ready()
"""


def _run_cache_probe(root, env_dir=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE, str(root)],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_defaults_to_fixed_dir_in_checkout(tmp_path):
    from repro import cache_dirs
    assert cache_dirs.CACHE_ROOT == os.path.join(REPO, ".cache")
    assert dispatch._DEFAULT_CACHE_PATH == os.path.join(
        cache_dirs.CACHE_ROOT, "autotune.json")   # the autotune cache beside it
    root = tmp_path / "root"
    assert _run_cache_probe(root) == str(root / "jax")
    assert os.listdir(root / "jax")                  # the compile was written


def test_compile_cache_env_dir_is_used_and_nothing_else(tmp_path):
    root, env_dir = tmp_path / "root", tmp_path / "env"
    assert _run_cache_probe(root, env_dir) == str(env_dir)
    assert os.listdir(env_dir)
    assert not root.exists()


# ---------------------------------------------------------------------------
# Grep-clean policy: version-sensitive surfaces only inside repro/compat.
# ---------------------------------------------------------------------------
_FORBIDDEN = (
    ("from jax import shard_map", "shard_map must come from repro.compat"),
    ("from jax.experimental.shard_map", "shard_map must come from repro.compat"),
    ("from jax.experimental import shard_map", "shard_map must come from repro.compat"),
    (".cost_analysis()", "use compat.cost_analysis(compiled)"),
    ("jax.make_mesh(", "use compat.make_mesh"),
    ("default_backend()", "use compat.backend()/pallas_interpret()"),
    # pytree namespace: jax.tree.* vs jax.tree_util.tree_* differs by version
    ("jax.tree.", "use compat.tree_map/tree_leaves/... aliases"),
    ("jax.tree_util", "use compat.tree_map/tree_leaves/... aliases"),
    # one NamedSharding constructor normalizes tuple / None specs
    ("NamedSharding(", "use compat.named_sharding(mesh, spec)"),
)


def test_version_sensitive_surfaces_centralized():
    offenders = []
    for root, _, files in os.walk(SRC):
        if os.path.basename(root) == "compat":
            continue
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    if "``" in line or line.lstrip().startswith("#"):
                        continue                      # doc mention, not a call
                    for pat, why in _FORBIDDEN:
                        if pat in line:
                            offenders.append(
                                f"{os.path.relpath(path, REPO)}:{lineno} "
                                f"[{pat!r} → {why}]")
    assert not offenders, "\n".join(offenders)


# Block-table CONSTRUCTION is the exclusive business of serving/paged.py:
# the allocator, the prefix index, and table row assembly must have exactly
# one home, or refcount bookkeeping and the sharing invariants fragment.
# Kernels, dispatch, and the engine only CONSUME tables they are handed.
_PAGED_ONLY = (
    ("BlockAllocator(", "allocate blocks via serving.paged.PagedPool"),
    ("PrefixIndex(", "prefix sharing lives in serving.paged"),
    ("PagedSeq(", "sequence block bookkeeping lives in serving.paged"),
)


def test_block_table_construction_centralized():
    offenders = []
    paged_home = os.path.join(SRC, "serving", "paged.py")
    for root, _, files in os.walk(SRC):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            if os.path.abspath(path) == paged_home:
                continue
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    if "``" in line or line.lstrip().startswith("#"):
                        continue
                    for pat, why in _PAGED_ONLY:
                        if pat in line:
                            offenders.append(
                                f"{os.path.relpath(path, REPO)}:{lineno} "
                                f"[{pat!r} → {why}]")
    assert not offenders, "\n".join(offenders)


# The serving loop is owned by the engine layer: outside src/repro/serving/
# nobody constructs a ContinuousScheduler or drives its ticks — the CLI,
# benchmarks, and examples all hold an Engine (or a ReplicaRouter over
# several), so the loop, its wedge guard, and its report construction exist
# exactly once.
_ENGINE_ONLY = (
    ("ContinuousScheduler(",
     "engines are built by serving.engine_api.Engine / serving.router"),
    (".tick(", "the step loop lives in serving.engine_api.Engine"),
    ("sched.run(", "batch serving is Engine.serve / ReplicaRouter.serve"),
)


def test_engine_loop_centralized():
    offenders = []
    serving_home = os.path.join(SRC, "serving")
    roots = [SRC, os.path.join(REPO, "benchmarks"),
             os.path.join(REPO, "examples")]
    for top in roots:
        for root, _, files in os.walk(top):
            if os.path.abspath(root).startswith(
                    os.path.abspath(serving_home)):
                continue
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(root, name)
                with open(path) as f:
                    for lineno, line in enumerate(f, 1):
                        if "``" in line or line.lstrip().startswith("#"):
                            continue
                        for pat, why in _ENGINE_ONLY:
                            if pat in line:
                                offenders.append(
                                    f"{os.path.relpath(path, REPO)}:{lineno}"
                                    f" [{pat!r} → {why}]")
    assert not offenders, "\n".join(offenders)


# Cache layout is owned by repro.serving.cache_family: pool/slot tensor
# construction and kv-cache-dtype policy checks anywhere else would fork the
# layout contract the paged substrate (block axis at leaf position 1) and
# the jitted steps are built on.  models/layers.py keys the quantized path
# off the cache payload ("k_scale" in cache), not the config string.
_CACHE_FAMILY_ONLY = (
    ("kv_cache_dtype ==", "dtype policy lives in serving.cache_family"),
    ("kv_cache_dtype !=", "dtype policy lives in serving.cache_family"),
    ("jnp.zeros((n, batch", "slot-cache layout lives in serving.cache_family"),
    ("jnp.zeros((count, num_blocks",
     "pool-cache layout lives in serving.cache_family"),
)


def test_cache_family_centralized():
    offenders = []
    allowed = {os.path.join(SRC, "serving", "cache_family.py"),
               os.path.join(SRC, "serving", "engine.py")}
    for root, _, files in os.walk(SRC):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            if os.path.abspath(path) in {os.path.abspath(a) for a in allowed}:
                continue
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    if "``" in line or line.lstrip().startswith("#"):
                        continue
                    for pat, why in _CACHE_FAMILY_ONLY:
                        if pat in line:
                            offenders.append(
                                f"{os.path.relpath(path, REPO)}:{lineno}"
                                f" [{pat!r} → {why}]")
    assert not offenders, "\n".join(offenders)


# Wall-clock access is owned by repro.obs.clock: every timestamp the serving
# stack takes must go through the injectable clock, or the virtual-clock
# tests (deterministic latencies) and the trace epoch silently diverge from
# what the scheduler actually measured.
_CLOCK_ONLY = (
    ("time.monotonic(", "use repro.obs.clock.monotonic()"),
    ("time.perf_counter(", "use repro.obs.clock.perf_counter()"),
    ("time.time(", "use repro.obs.clock.wall_time()"),
)


def test_wall_clock_access_centralized():
    offenders = []
    obs_home = os.path.join(SRC, "obs")
    for root, _, files in os.walk(SRC):
        if os.path.abspath(root).startswith(os.path.abspath(obs_home)):
            continue                          # the clock's own home
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    if "``" in line or line.lstrip().startswith("#"):
                        continue
                    for pat, why in _CLOCK_ONLY:
                        if pat in line:
                            offenders.append(
                                f"{os.path.relpath(path, REPO)}:{lineno} "
                                f"[{pat!r} → {why}]")
    assert not offenders, "\n".join(offenders)


# ---------------------------------------------------------------------------
# Dispatch registry: path selection on this backend.
# ---------------------------------------------------------------------------
def test_registry_paths_registered():
    for op in ("online_softmax", "softmax_topk", "attention"):
        paths = dispatch.available(op)
        assert dispatch.PATH_XLA in paths, (op, paths)
        assert dispatch.PATH_PALLAS in paths, (op, paths)


def test_path_selection_matches_backend():
    caps = compat.capabilities()
    for op in ("online_softmax", "softmax_topk"):
        path = dispatch.select_path(op)
        if caps.pallas_native:
            assert path == dispatch.PATH_PALLAS
        else:
            assert path == dispatch.PATH_XLA
    # a Pallas preference on a non-native backend degrades to interpret mode
    path = dispatch.select_path("attention", prefer_pallas=True)
    if caps.pallas_native:
        assert path == dispatch.PATH_PALLAS
    else:
        assert path == dispatch.PATH_PALLAS_INTERPRET


def test_differentiable_softmax_topk_has_grad_path():
    """The MoE router differentiates through softmax_topk; the registry must
    never route it to the Pallas kernel (no custom VJP), even on TPU."""
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 64))
    g = jax.grad(lambda x: dispatch.softmax_topk(
        x, 4, differentiable=True).logsumexp.sum())(x)
    assert np.isfinite(np.asarray(g)).all()


def test_dispatched_ops_match_reference():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 512)) * 5
    np.testing.assert_allclose(np.asarray(dispatch.online_softmax(x)),
                               np.asarray(core.safe_softmax(x)),
                               rtol=1e-5, atol=1e-7)
    got = dispatch.softmax_topk(x, 5)
    want = core.softmax_topk(x, 5)
    np.testing.assert_allclose(np.asarray(got.values),
                               np.asarray(want.values), rtol=1e-5, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(got.indices),
                                  np.asarray(want.indices))


# ---------------------------------------------------------------------------
# Autotune: sweep once, cache-hit thereafter.
# ---------------------------------------------------------------------------
def test_autotune_caches_block_decision():
    dispatch.reset_autotune_cache()
    d1 = dispatch.block_decision(1024, jnp.float32)
    assert dispatch.autotune_stats() == {"sweeps": 1, "entries": 1}
    d2 = dispatch.block_decision(1024, jnp.float32)
    assert d2 is d1                              # second call: pure cache hit
    assert dispatch.autotune_stats() == {"sweeps": 1, "entries": 1}
    assert 1 <= d1.block <= 1024
    assert d1.block in [b for b, _ in d1.timings_us]
    # a different (vocab, dtype) key sweeps again — the cache is per-key
    dispatch.block_decision(1024, jnp.bfloat16)
    dispatch.block_decision(512, jnp.float32)
    assert dispatch.autotune_stats() == {"sweeps": 3, "entries": 3}


def test_autotune_pick_keeps_incumbent_within_margin():
    """A challenger displaces the registry default only when its median is
    more than ``_TUNE_MARGIN`` faster; timing noise alone must not move a
    tile choice between runs."""
    m = dispatch._TUNE_MARGIN
    near = ((256, 100.0), (512, 100.0 * (1 - m / 2)), (2048, 100.0))
    assert dispatch._pick(near, 2048) == 2048
    clear = ((256, 100.0), (512, 100.0 * (1 - 2 * m)), (2048, 100.0))
    assert dispatch._pick(clear, 2048) == 512
    # an incumbent outside the candidates (clamped away) cannot hold
    assert dispatch._pick(near, 4096) == 512
    assert dispatch._pick((((256, 0), 9.0), ((512, 0), 9.5)), (512, 0)) \
        == (512, 0)


def test_autotune_sweep_inside_jit_trace_measures_execution():
    """The serving step jits decode, so the first sweep can fire during an
    outer trace; the sweep must still run concretely (a traced sweep would
    time per-candidate tracing overhead instead)."""
    dispatch.reset_autotune_cache()
    cap = {}

    def f(x):
        cap["d"] = dispatch.block_decision(x.shape[-1], jnp.float32)
        return x * 1.0

    jax.jit(f)(jnp.ones((2, 777)))
    d = cap["d"]
    assert dispatch.autotune_stats() == {"sweeps": 1, "entries": 1}
    assert all(us > 0 for _, us in d.timings_us)
    # the in-trace sweep populated the process-wide cache: eager callers
    # reuse the same decision object
    assert dispatch.block_decision(777, jnp.float32) is d


def test_pallas_sweep_inside_jit_trace(monkeypatch):
    """Where Pallas is native the sweep times the Pallas kernel itself; a
    kernel cannot be traced under ``jax.ensure_compile_time_eval``, so the
    in-trace sweep must leave the outer trace some other way.  Steered onto
    the Pallas route here (interpret mode on a CPU host)."""
    dispatch.reset_autotune_cache()
    monkeypatch.setattr(compat, "pallas_native", lambda: True)
    cap = {}

    def f(x):
        cap["d"] = dispatch.block_decision(x.shape[-1], jnp.float32)
        return x * 1.0

    jax.jit(f)(jnp.ones((2, 768)))
    assert all(us > 0 for _, us in cap["d"].timings_us)
    dispatch.reset_autotune_cache()


def test_ops_pick_up_tuned_block():
    """ops.* with v_blk unset consults the autotune cache (no hard-coding)."""
    dispatch.reset_autotune_cache()
    from repro.kernels import ops
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 512))
    y = ops.online_softmax(x)                    # v_blk=None → tuned
    np.testing.assert_allclose(np.asarray(y), np.asarray(core.safe_softmax(x)),
                               rtol=1e-5, atol=1e-7)
    assert dispatch.autotune_stats()["entries"] >= 1


# ---------------------------------------------------------------------------
# Autotune persistence: decisions survive the process (ROADMAP item).
# ---------------------------------------------------------------------------
def test_block_decisions_persist_and_reload(tmp_path, monkeypatch):
    """A restart (simulated: reset + load) must skip the sweep entirely."""
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv(dispatch.AUTOTUNE_CACHE_ENV, path)
    dispatch.reset_autotune_cache()
    d1 = dispatch.block_decision(384, jnp.float32)
    assert os.path.exists(path)
    dispatch.reset_autotune_cache()
    assert dispatch.load_persisted_decisions() >= 1
    d2 = dispatch.block_decision(384, jnp.float32)
    assert dispatch.autotune_stats()["sweeps"] == 0      # disk hit, no sweep
    assert (d2.block, d2.backend, d2.dtype) == (d1.block, d1.backend, d1.dtype)
    dispatch.reset_autotune_cache()


def test_autotune_cache_env_empty_disables_persistence(monkeypatch):
    monkeypatch.setenv(dispatch.AUTOTUNE_CACHE_ENV, "")
    assert dispatch.autotune_cache_path() is None
    assert dispatch.load_persisted_decisions() == 0
    assert not dispatch.save_persisted_decisions()


def test_corrupt_autotune_cache_never_breaks_dispatch(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    path.write_text("{this is not json")
    monkeypatch.setenv(dispatch.AUTOTUNE_CACHE_ENV, str(path))
    assert dispatch.load_persisted_decisions() == 0
    dispatch.reset_autotune_cache()
    dispatch.block_decision(320, jnp.float32)            # sweeps, then saves
    import json as _json
    with open(path) as f:
        saved = _json.load(f)
    assert any(int(b["vocab"]) == 320 for b in saved["blocks"])
    dispatch.reset_autotune_cache()


def test_schema_mismatched_autotune_cache_warns_once_and_resweeps(
        tmp_path, monkeypatch):
    """A version stamp from another schema era must not be trusted: the load
    warns (once), returns nothing, and the next sweep rewrites the file with
    the current stamp."""
    import json as _json
    path = tmp_path / "autotune.json"
    path.write_text(_json.dumps({"version": 99, "blocks": [
        {"backend": "cpu", "vocab": 352, "dtype": "float32", "block": 7,
         "timings_us": [[7, 1.0]]}]}))
    monkeypatch.setenv(dispatch.AUTOTUNE_CACHE_ENV, str(path))
    dispatch.reset_autotune_cache()
    with pytest.warns(UserWarning, match="schema version"):
        assert dispatch.load_persisted_decisions() == 0
    import warnings as _warnings
    with _warnings.catch_warnings(record=True) as rec:
        _warnings.simplefilter("always")
        assert dispatch.load_persisted_decisions() == 0
    assert not rec                                        # warned once only
    d = dispatch.block_decision(352, jnp.float32)         # re-sweeps
    assert dispatch.autotune_stats()["sweeps"] == 1
    assert d.block != 7 or d.timings_us != ((7, 1.0),)    # not the stale row
    saved = _json.loads(path.read_text())
    assert saved["version"] == dispatch.CACHE_SCHEMA_VERSION
    assert any(int(b["vocab"]) == 352 for b in saved["blocks"])
    dispatch.reset_autotune_cache()


def test_non_object_autotune_cache_ignored(tmp_path, monkeypatch):
    """A top-level JSON list (valid JSON, wrong shape) used to crash the
    import-time load with AttributeError; it must be ignored instead."""
    path = tmp_path / "autotune.json"
    path.write_text('[{"version": 1}]')
    monkeypatch.setenv(dispatch.AUTOTUNE_CACHE_ENV, str(path))
    dispatch.reset_autotune_cache()
    with pytest.warns(UserWarning, match="top-level list"):
        assert dispatch.load_persisted_decisions() == 0
    dispatch.reset_autotune_cache()


def test_corrupt_autotune_cache_does_not_break_import(tmp_path):
    """The real failure mode: dispatch loads the cache at import, so a bad
    file must not take down a fresh interpreter."""
    bad = tmp_path / "autotune.json"
    bad.write_text("]]] definitely not json")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + REPO
    env[dispatch.AUTOTUNE_CACHE_ENV] = str(bad)
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.kernels import dispatch as d; "
         "print(d.autotune_stats()['entries'])"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert int(out.stdout.strip()) == 0


def test_fresh_process_loads_persisted_decisions(tmp_path, monkeypatch):
    """The import-time load: a new interpreter sees the saved decisions."""
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv(dispatch.AUTOTUNE_CACHE_ENV, path)
    dispatch.reset_autotune_cache()
    dispatch.block_decision(448, jnp.float32)
    dispatch.reset_autotune_cache()
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + REPO
    env[dispatch.AUTOTUNE_CACHE_ENV] = path
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.kernels import dispatch as d; "
         "print(d.autotune_stats()['entries'])"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert int(out.stdout.strip()) >= 1


# ---------------------------------------------------------------------------
# Attention tile seam: no hard-coded bq/bk in ops.py (ROADMAP item).
# ---------------------------------------------------------------------------
def test_attention_tiles_resolve_through_registry(tmp_path, monkeypatch):
    monkeypatch.setenv(dispatch.AUTOTUNE_CACHE_ENV, str(tmp_path / "t.json"))
    dispatch.reset_autotune_cache()
    tiles = dispatch.attention_tiles("flash_attention", kv_len=64, head_dim=16)
    assert set(tiles) == {"bq", "bk"} and all(v > 0 for v in tiles.values())
    td = dispatch.attention_tiles("flash_decode", kv_len=64, head_dim=16)
    assert td["bk"] > 0
    assert dispatch.tile_stats()["entries"] == 2
    assert dispatch.attention_tiles(
        "flash_decode", kv_len=64, head_dim=16) == td   # cache hit
    assert dispatch.tile_stats()["entries"] == 2
    dispatch.reset_autotune_cache()


def test_ops_attention_defaults_come_from_registry():
    """flash_attention / flash_decode with tiles unset must run through the
    dispatch seam (and still compute correct attention)."""
    from repro.kernels import ops
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 8))
    out = ops.flash_attention(q, q, q, causal=True)      # bq/bk unset
    want = core.naive_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    kc = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 1, 8))
    vc = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 1, 8))
    qd = jax.random.normal(jax.random.PRNGKey(3), (2, 2, 8))
    vlen = jnp.asarray([5, 16], jnp.int32)
    od = ops.flash_decode(qd, kc, vc, vlen)              # bk unset
    want_d = core.naive_attention(qd[:, None], kc, vc, causal=False,
                                  kv_valid_len=vlen)[:, 0]
    np.testing.assert_allclose(np.asarray(od), np.asarray(want_d),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Benchmark harness smoke mode (CI tooling).
# ---------------------------------------------------------------------------
def test_benchmarks_smoke_mode():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + REPO
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--smoke", "softmax", "topk_sweep"],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    lines = [l for l in out.stdout.splitlines() if l.strip()]
    assert lines[0] == "name,us_per_call,derived"
    assert any(l.startswith("softmax/") for l in lines[1:])
    assert any(l.startswith("topk_sweep/") for l in lines[1:])
    for row in lines[1:]:
        name, us, _ = row.split(",", 2)
        assert float(us) > 0, row


def test_benchmarks_attention_smoke_records_prefill_comparison():
    """The prefill Pallas-vs-XLA comparison rides the attention bench."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + REPO
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--smoke", "attention"],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    lines = out.stdout.splitlines()
    assert any("/pallas_fwd" in l and "prefill" in l for l in lines)
    assert any("/xla_chunked_fwd" in l for l in lines)


def test_benchmarks_report_diffs_two_result_files(tmp_path):
    """`run.py report A.json B.json` renders the EXPERIMENTS.md-style diff
    table, flags one-sided rows and env mismatches."""
    import json as _json
    a = {"smoke": True, "env": {"backend": "cpu", "jax_version": "x"},
         "rows": [
             {"name": "softmax/a", "us_per_call": 10.0, "derived": "d1"},
             {"name": "only/base", "us_per_call": 5.0, "derived": ""}]}
    b = {"smoke": True, "env": {"backend": "tpu", "jax_version": "x"},
         "rows": [
             {"name": "softmax/a", "us_per_call": 8.0, "derived": "d1"},
             {"name": "only/cand", "us_per_call": 2.0, "derived": ""}]}
    pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    with open(pa, "w") as f:
        _json.dump(a, f)
    with open(pb, "w") as f:
        _json.dump(b, f)
    md_out = str(tmp_path / "diff.md")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + REPO
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "report", pa, pb, "--out", md_out],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    text = out.stdout
    assert "| softmax/a | 10.00 | 8.00 | -20.0% | d1 |" in text
    assert "backend ⚠" in text                 # env mismatch flagged
    assert "Rows only in baseline: only/base" in text
    assert "Rows only in candidate: only/cand" in text
    with open(md_out) as f:
        assert f.read() == text


def test_benchmarks_serving_smoke_records_json(tmp_path):
    """The serving benchmark smoke path: tokens/s + latency percentiles land
    in a results JSON (first step toward the EXPERIMENTS.md diffing report)."""
    import json as _json
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + REPO
    json_path = str(tmp_path / "results.json")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--smoke", "serving", "--json", json_path],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    with open(json_path) as f:
        data = _json.load(f)
    names = {r["name"] for r in data["rows"]}
    assert {"serving/smoke/per_token", "serving/smoke/p50_latency",
            "serving/smoke/p95_latency",
            "serving/smoke/occupancy_pct"} <= names
    assert data["smoke"] is True
    assert data["env"]["backend"] in ("cpu", "gpu", "tpu")
    for r in data["rows"]:
        assert r["us_per_call"] > 0, r
