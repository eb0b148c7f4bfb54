"""The serving stack on the JAX profiler's clock.

* **Scheduler sections are profiler annotations.**  With a ``Tracer``
  attached, every nested scheduler span is also a ``sched.<name>`` host
  annotation inside a ``jax.profiler`` trace, nested under its
  ``sched.tick``; without one there are none.
* **Model-step layers are named scopes.**  The compiled paged decode and
  prefill steps carry ``layers``, ``attn``, ``pool.write``, ``mlp``,
  ``head``, ``sample`` and ``kernel.*`` in their ops' metadata, so device
  time in a trace can be attributed to the code that issued it.
* **What the chip benchmark reads stays put**: the ``queued`` /
  ``prefill_chunk`` / ``blocks`` records of the tracer, the ``jit_decode``
  program name, and the HLO names of the three Pallas kernels under the new
  scopes.
"""
import functools
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as configs
from repro.kernels.flash_attention import flash_attention_paged_pallas
from repro.kernels.flash_decode import flash_decode_paged_pallas
from repro.kernels.softmax_topk import softmax_topk_pallas
from repro.models import layers as L, transformer
from repro.obs import trace as obs_trace
from repro.serving import scheduler
from repro.serving.engine_api import Engine

SLOT_LEN = 48
BLOCK = 8
CHUNK = 8
TOP_K = 5


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_smoke("smollm_360m")
    params, _ = L.split_params(transformer.init(jax.random.PRNGKey(0), cfg))
    return params, cfg


def _workload():
    """Prompts longer than a chunk (several chunks each), more requests
    than slots, answers long enough for several decode ticks."""
    rng = np.random.default_rng(3)
    return [scheduler.Request(rid=i, prompt=rng.integers(0, 512, 13 + 3 * i),
                              max_new_tokens=4, arrival_tick=i)
            for i in range(3)]


def _engine(params, cfg, tracer=None):
    return Engine(params, cfg, num_slots=2, slot_len=SLOT_LEN,
                  prefill_chunk=CHUNK, top_k=TOP_K,
                  base_rng=jax.random.PRNGKey(7), paged=True,
                  block_size=BLOCK, tracer=tracer)


def _host_events(trace_dir):
    """(name, start_ns, end_ns) of every host event in the profile."""
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    space = jax.profiler.ProfileData.from_file(files[0])
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in space.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def _profiled_serve(params, cfg, trace_dir, tracer):
    _engine(params, cfg).serve(_workload())   # compiles outside the profile
    eng = _engine(params, cfg, tracer=tracer)
    with jax.profiler.trace(str(trace_dir)):
        rep = eng.serve(_workload())
    return rep, _host_events(trace_dir)


def test_scheduler_sections_nest_under_tick_on_the_profiler_clock(
        model, tmp_path):
    params, cfg = model
    tracer = obs_trace.Tracer()
    rep, host = _profiled_serve(params, cfg, tmp_path, tracer)
    sched = [e for e in host if e[0].startswith("sched.")]
    ticks = [e for e in sched if e[0] == "sched.tick"]
    names = {n for n, _, _ in sched}
    for want in ("sched.admit", "sched.prefill", "sched.prefill_chunk",
                 "sched.first_token", "sched.first_token.wait",
                 "sched.decode", "sched.decode.prepare",
                 "sched.decode.lens_wait", "sched.decode.dispatch",
                 "sched.decode.wait", "sched.decode.record"):
        assert want in names, (want, sorted(names))

    def inside(inner, outer_name):
        _, s, e = inner
        return any(os_ <= s and e <= oe for n, os_, oe in sched
                   if n == outer_name)

    for ev in sched:
        if ev[0] != "sched.tick":
            assert inside(ev, "sched.tick"), ev
    for ev in sched:
        if ev[0].startswith("sched.decode."):
            assert inside(ev, "sched.decode"), ev
        if ev[0] == "sched.decode.lens_wait":
            assert inside(ev, "sched.decode.prepare"), ev
        if ev[0] == "sched.first_token.wait":
            assert inside(ev, "sched.first_token"), ev
        if ev[0] == "sched.prefill_chunk":
            assert inside(ev, "sched.prefill"), ev
    # one profiler tick per recorded tick, one chunk annotation per chunk
    assert len(ticks) == sum(1 for e in tracer.events
                             if e["name"] == "tick")
    assert (sum(1 for n, _, _ in sched if n == "sched.prefill_chunk")
            == rep.prefill_chunks)


def test_no_tracer_puts_nothing_on_the_profiler_clock(model, tmp_path):
    params, cfg = model
    _, host = _profiled_serve(params, cfg, tmp_path, None)
    assert host, "the profile recorded no host events at all"
    assert not [n for n, _, _ in host if n.startswith("sched.")]


def test_tracer_record_keeps_what_the_benchmark_reads(model):
    """The chip benchmark's readers take ``queued`` spans (``rid``),
    ``prefill_chunk`` spans (``pos``, ``width``) and ``blocks`` counters
    (``free``, ``cached``) from the tracer's record, under these names."""
    params, cfg = model
    tracer = obs_trace.Tracer()
    rep = _engine(params, cfg, tracer=tracer).serve(_workload())
    ev = tracer.events
    queued = [e for e in ev if e["ph"] == "X" and e["name"] == "queued"]
    chunks = [e for e in ev if e["ph"] == "X"
              and e["name"] == "prefill_chunk"]
    blocks = [e for e in ev if e["ph"] == "C" and e["name"] == "blocks"]
    assert sorted(e["args"]["rid"] for e in queued) == [0, 1, 2]
    assert len(chunks) == rep.prefill_chunks
    for e in chunks:                 # on the request's track, rid + 1
        assert e["tid"] - 1 in (0, 1, 2)
        assert {"pos", "width"} <= set(e["args"])
        assert 1 <= e["args"]["width"] <= CHUNK
    assert blocks and all({"free", "cached"} <= set(e["args"])
                          for e in blocks)
    # the profiler prefix is the annotation's, never the record's
    assert not [e for e in ev if e["name"].startswith("sched.")]


def _paged_steps(params, cfg):
    sched = scheduler.ContinuousScheduler(
        params, cfg, num_slots=2, slot_len=SLOT_LEN, prefill_chunk=CHUNK,
        top_k=TOP_K, paged=True, block_size=BLOCK)
    pool, n = sched.pool, sched.pool.num_slots
    zeros = jnp.zeros((n,), jnp.int32)
    decode = sched._decode_paged.lower(
        params, pool.caches, pool.device_tables(), pool.lens,
        sched.tokens[:, None], zeros, zeros, jax.random.PRNGKey(0))
    prefill = sched._prefill_paged.lower(
        params, pool.caches, pool.device_row(0), jnp.asarray(0, jnp.int32),
        jnp.zeros((1, CHUNK), jnp.int32))
    return decode, prefill


def _op_names(lowered) -> set:
    return set(re.findall(r'op_name="([^"]+)"', lowered.compile().as_text()))


@pytest.mark.parametrize("step,scopes", [
    ("decode", ("layers/", "/attn/", "/attn/pool.write/", "/mlp/", "/head/",
                "/sample/", "/attn/kernel.paged_decode/",
                "/sample/kernel.softmax_topk/",
                "/layers/while/body/dynamic_slice",
                "/layers/while/body/dynamic_update_slice")),
    ("prefill", ("layers/", "/attn/", "/attn/pool.write/", "/mlp/",
                 "/attn/kernel.paged_prefill/")),
])
def test_paged_steps_carry_layer_and_kernel_scopes(model, step, scopes):
    params, cfg = model
    decode, prefill = _paged_steps(params, cfg)
    names = _op_names(decode if step == "decode" else prefill)
    for scope in scopes:
        assert any(scope in n for n in names), (scope, sorted(names)[:20])


def test_decode_step_program_is_jit_decode(model):
    """``model_step.decode_ms`` finds the decode step's runs by this
    program name."""
    params, cfg = model
    decode, _ = _paged_steps(params, cfg)
    assert decode.as_text().splitlines()[0].startswith("module @jit_decode ")


# ---------------------------------------------------------------------------
# The Pallas kernels' names in a program compiled for a described v5e.
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _kernel_call(name, sh):
    """(kernel call, operand specs) at a small serving shape."""
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=sh)
    hq, hkv, d, bs, slots, m = 8, 2, 128, 16, 8, 4
    pool = spec((slots * m + 1, hkv, bs, d), jnp.bfloat16)
    if name == "flash_decode_paged_pallas":
        return (functools.partial(flash_decode_paged_pallas,
                                  interpret=False),
                [spec((slots, hq, d), jnp.bfloat16), pool, pool,
                 spec((slots, m), jnp.int32), spec((slots,), jnp.int32)])
    if name == "flash_attention_paged_pallas":
        return (functools.partial(flash_attention_paged_pallas, bq=16,
                                  interpret=False),
                [spec((1, hq, 16, d), jnp.bfloat16), pool, pool,
                 spec((1,), jnp.int32), spec((1,), jnp.int32),
                 spec((1, m), jnp.int32)])
    return (functools.partial(softmax_topk_pallas, k=TOP_K, r_blk=slots,
                              v_blk=2048, interpret=False),
            [spec((slots, 8192), jnp.float32)])


@pytest.mark.parametrize("name", ["flash_decode_paged_pallas",
                                  "flash_attention_paged_pallas",
                                  "softmax_topk_pallas"])
def test_pallas_kernel_names_survive_the_scopes(one_chip, name):
    """The roofline readers find each kernel by the HLO name of its Pallas
    call; inside the model step's scopes it keeps that name."""
    kernel, operands = _kernel_call(name, one_chip)

    def decode(*args):
        with jax.named_scope("layers"), jax.named_scope("kernel.x"):
            return kernel(*args)

    text = jax.jit(decode).lower(*operands).compile().as_text()
    assert re.search(rf"%{name}(\.\d+)? = ", text), name


def test_serve_profile_flag_writes_sched_spans(tmp_path, capsys):
    """``serve --profile DIR``: the operator's path to the spans above —
    a profiler trace of the serve loop holding the scheduler's sections,
    with no ``--trace`` file asked for."""
    from repro.launch import serve
    rc = serve.main(["--smoke", "--continuous", "--paged",
                     "--profile", str(tmp_path)])
    assert rc == 0, capsys.readouterr().out
    names = {n for n, _, _ in _host_events(tmp_path)}
    assert {"sched.tick", "sched.decode.wait",
            "sched.prefill_chunk"} <= names, sorted(names)[:20]
    assert glob.glob(str(tmp_path / "**" / "perfetto_trace.json.gz"),
                     recursive=True)
