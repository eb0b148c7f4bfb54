"""The check that decides ``correct``, driven on the CPU at a smoke size:
the reference draws the program's weights from the seed, agrees with what
the engine serves, and fails a run whose timed path is broken underneath.

These run the whole of ``run_cell.run`` except its look for a chip: the
engine serves the cell's traffic for a short window through the same
scheduler, pool and jitted steps, and the served requests are compared with
``reference.py``.
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import reference  # noqa: E402
import run_cell  # noqa: E402
from repro import configs  # noqa: E402
from repro.serving import scheduler  # noqa: E402

SEED = 2**40 + 17


def _cell(arch: str, loop: str, dtype: str = "float32", limit=1e-3,
          **sizes):
    cfg = configs.get_smoke(arch).replace(dtype=dtype, **sizes)
    config = {
        "arch": arch, "hidden_size": cfg.d_model,
        "intermediate_size": cfg.d_ff, "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.resolved_head_dim, "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings, "torch_dtype": dtype,
        "serving": {"slots": 4, "slot_len": 256, "block_size": 16,
                    "num_blocks": None, "prefill_chunk": 32, "top_k": 5,
                    "temperature": 1e-4},
        "limits": {"max_logit_gap": limit}}
    traffic = {"loop": loop, "pre_window_s": 0.5, "block": 16, "clients": 4, "lead_s": 4,
               "arrivals": {"rate_per_s": 3.0, "period_s": 4,
                            "burst_s": 1, "burst_factor": 3},
               "system_prompts": 2, "system_prompt_len": 32,
               "turns": {"dist": "geometric", "mean": 2, "max": 4},
               "think_ms": {"dist": "uniform", "min": 300, "max": 1500},
               "prompt": {"dist": "lognormal", "median": 32, "sigma": 1.0,
                          "min": 16, "max": 64},
               "new_tokens": {"dist": "lognormal", "median": 32,
                              "sigma": 0.5, "min": 16, "max": 64}}
    return harness.Cell(name="smoke", chips=1, config=config,
                        traffic=traffic, end_to_end=[
                            {"name": "output_tok_s", "unit": "tokens/s"}],
                        per_layer=[]), cfg


@pytest.fixture
def cpu_run(monkeypatch):
    """``run_cell.run`` on the CPU: the program's smoke config stands in
    for the file's, and the persistent compile cache stays off."""
    monkeypatch.setattr(harness, "enable_caches", lambda: "off")
    monkeypatch.setattr(run_cell, "WANT_TOKENS", 96)
    monkeypatch.setattr(run_cell, "MIN_TOKENS", 32)

    def go(cell, cfg, seconds=1.5):
        monkeypatch.setattr(harness, "program_config", lambda c: cfg)
        return run_cell.run(cell, SEED, seconds, False, jax.devices())
    return go


@pytest.mark.parametrize("arch,scale", [("smollm_360m", 1.0),
                                        ("smollm_360m", 0.02),
                                        ("mistral_nemo_12b", 1.0)])
def test_reference_draws_the_program_weights(arch, scale):
    """Tied (smollm) and untied (mistral) heads, the embedding as drawn or
    scaled: every weight the recipe draws, compiled as the reference
    compiles it, equals the served one bit for bit after rounding to
    bf16."""
    cell, cfg = _cell(arch, "closed", dtype="bfloat16")
    cell.config["weights"] = {"embedding_scale": scale}
    params = harness.make_params(cell, cfg, SEED)
    m = cell.model
    key = reference.key_for_seed(SEED)
    k_e, _, _, k_s = jax.random.split(key, 4)
    dt = jnp.bfloat16
    emb = params["embedding"]
    normal = jax.jit(reference._normal, static_argnums=(1, 2, 3))
    want = jax.jit(lambda k: reference._embedding(k, m, dt))(k_e)
    assert np.array_equal(want, np.asarray(emb["embed"], np.float32))
    if scale != 1.0:
        assert not np.array_equal(want, normal(
            k_e, (m["vocab_size"], m["d_model"]), 1.0, dt))
    if not m["tie_embeddings"]:
        head = normal(jax.random.fold_in(k_e, 1),
                      (m["d_model"], m["vocab_size"]),
                      reference._fan(m["d_model"]), dt)
        assert np.array_equal(head, np.asarray(emb["head"], np.float32))
    seg = params["segments"][0]
    for layer, lkey in enumerate(jax.random.split(k_s, m["num_layers"])):
        w = jax.jit(lambda k: reference._layer_weights(k, m, dt))(lkey)
        for name in ("wq", "wk", "wv", "wo"):
            assert np.array_equal(
                w[name], np.asarray(seg["attn"][name][layer], np.float32))
        for name in ("w_gate", "w_up", "w_down"):
            assert np.array_equal(
                w[name], np.asarray(seg["mlp"][name][layer], np.float32))


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_served_tokens_match_the_reference(cpu_run, loop):
    """In float32 the program and the reference differ by rounding only:
    every served token is the reference's best, to 1e-3."""
    cell, cfg = _cell("smollm_360m", loop)
    out = cpu_run(cell, cfg)
    assert out["correct"], out["checks"]
    assert out["checks"]["served_tokens_compared"]["value"] >= 32
    assert out["checks"]["max_logit_gap"]["value"] <= 1e-3
    assert out["metrics"]["output_tok_s"]["value"] > 0


def _decode_fault(kind):
    """A paged decode step broken underneath the scheduler."""
    real = scheduler._jitted_paged_steps

    @functools.lru_cache(maxsize=None)
    def broken(cfg, top_k, temperature):
        decode, prefill = real(cfg, top_k, temperature)

        def step(params, pools, tables, lens, tokens, *rest):
            before = jax.tree_util.tree_map(jnp.copy, pools)
            tok, new_pools, new_lens = decode(params, pools, tables, lens,
                                              tokens, *rest)
            if kind == "token":          # an answer altered where made
                tok = (tok + 1) % cfg.vocab_size
            elif kind == "state":        # the KV cache returned unchanged
                new_pools = before
            elif kind == "half_batch":   # half the rows left undone
                half = tok.shape[0] // 2
                tok = tok.at[half:].set(tokens[half:, 0])
            return tok, new_pools, new_lens
        return step, prefill
    return broken


@pytest.mark.parametrize("kind", ["token", "state", "half_batch"])
def test_broken_decode_is_not_correct(cpu_run, monkeypatch, kind):
    """Each fault a serving cell can have, planted under the scheduler.  The
    smoke model has six layers and an untied head, so that its best token
    depends on the context and not only on the token fed in (two layers and
    a tied head mostly echo the input: a fault that drops context then
    serves the same tokens)."""
    cell, cfg = _cell("mistral_nemo_12b", "closed", num_layers=6)
    monkeypatch.setattr(scheduler, "_jitted_paged_steps", _decode_fault(kind))
    # compare every request served, so the sample cannot miss the rows
    # a fault touched
    monkeypatch.setattr(run_cell, "WANT_TOKENS", 10**9)
    monkeypatch.setattr(run_cell, "MAX_REQUESTS", 10**6)
    out = cpu_run(cell, cfg)
    assert not out["correct"], out["checks"]
    assert out["checks"]["max_logit_gap"]["value"] > 1e-3


def test_fp8_control_reads_wider_gaps_than_bf16(cpu_run, monkeypatch):
    """The control (the reference with float8 matrix products) puts other
    tokens first than the bf16 program does, at a smoke size: its widest
    gap is well above the program's on the same served requests (the
    six-layer smoke model of the fault test, in bf16)."""
    cell, cfg = _cell("mistral_nemo_12b", "closed", dtype="bfloat16",
                      limit=1e9, num_layers=6)
    captured = {}
    real = harness.compare

    def both(cell_, seed, sample, prompts, control=None):
        captured.update(real(cell_, seed, sample, prompts, control="fp8"))
        return captured
    monkeypatch.setattr(harness, "compare", both)
    monkeypatch.setattr(run_cell, "WANT_TOKENS", 10**9)
    monkeypatch.setattr(run_cell, "MAX_REQUESTS", 10**6)
    cpu_run(cell, cfg)
    assert captured["served_tokens_compared"] >= 32
    # a limit goes between the two readings only where they lie three
    # times apart or more
    assert captured["control_max_logit_gap"] > 3 * captured["max_logit_gap"]
    # through the decision run_cell makes, at each committed limit: the
    # program's reading is correct and the control's is not
    for limit in _committed_limits():
        for gap, want in ((captured["max_logit_gap"], True),
                          (captured["control_max_logit_gap"], False)):
            got, checks = harness.verdict(
                gap, captured["served_tokens_compared"], limit,
                run_cell.MIN_TOKENS)
            assert got is want, checks


def _committed_limits() -> list:
    """``limits.max_logit_gap`` of every configuration in BENCHMARK.json."""
    import json
    root = os.path.dirname(os.path.dirname(BENCH))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = []
    for c in bench["configs"]:
        with open(os.path.join(root, c["file"])) as f:
            out.append(json.load(f)["limits"]["max_logit_gap"])
    return out


@pytest.mark.parametrize("gap,tokens,limit,want", [
    (0.01, 64, 0.15, True), (0.15, 64, 0.15, True), (0.16, 64, 0.15, False),
    (0.01, 63, 0.15, False), (None, 64, 0.15, False), (0.01, 64, None, False)])
def test_verdict_holds_each_number_to_its_limit(gap, tokens, limit, want):
    correct, checks = harness.verdict(gap, tokens, limit, 64)
    assert correct is want
    assert checks["max_logit_gap"] == {"value": gap, "limit": limit}
    assert checks["served_tokens_compared"] == {"value": tokens,
                                                "limit": 64}


def test_scheduler_record_gives_chunks_blocks_and_admissions():
    """A traced run hands the scheduler a tracer; the window's prefill
    chunks, block counts and admissions come from its record, not from the
    scheduler's internals."""
    cell, cfg = _cell("smollm_360m", "open")
    params = harness.make_params(cell, cfg, SEED)
    driver = harness.Driver(cell, params, cfg, SEED, annotate=True)
    driver.warm()
    win = driver.run(0.5, 1.5)
    chunk = cell.serving["prefill_chunk"]
    assert win.chunks and win.blocks and win.admitted
    assert all(w in (chunk, 16, 8, 4, 2, 1) for _, w in win.chunks)
    usable = win.usable_blocks
    assert all(0 <= f + c <= usable for f, c in win.blocks)
    reused = (win.counters1["tokens_reused"]
              - win.counters0["tokens_reused"])
    # every admitted prompt token is either reused or prefilled, and the
    # chunks begun in the window cover at most the rest
    assert 0 <= reused <= sum(win.admitted)
    assert win.counters1["prefill_chunks"] > win.counters0["prefill_chunks"]
    driver.close()
