"""``chip_smoke.py`` off the chip: it must refuse, not fall back.

The smoke's chip run is the proof that the serving path starts on a TPU;
here, on a CPU host, it must exit non-zero without printing its result
line, and its workload must keep the shape its compile budget assumes.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(env_extra):
    env = {k: v for k, v in os.environ.items()
           if k != "REPRO_PALLAS_INTERPRET"}
    env.update(env_extra)
    return subprocess.run([sys.executable, SMOKE], env=env,
                          capture_output=True, text=True, timeout=120)


def _prints_no_result(out):
    lines = out.stdout.strip().splitlines()
    if not lines:
        return True
    try:
        return "ok" not in json.loads(lines[-1])
    except ValueError:
        return True


def test_smoke_refuses_without_tpu():
    out = _run({"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert _prints_no_result(out)


def test_smoke_refuses_interpret_mode():
    out = _run({"JAX_PLATFORMS": "cpu", "REPRO_PALLAS_INTERPRET": "1"})
    assert out.returncode != 0
    assert "REPRO_PALLAS_INTERPRET" in out.stderr
    assert _prints_no_result(out)


def test_smoke_workload_shape():
    """16 prompts of 128–512 tokens behind one shared 64-token prefix,
    each asking for 32 tokens; bodies are multiples of 64, so after the
    prefix hit every prefill is 128-token chunks plus at most one 64-token
    tail (two prefill widths to compile on the chip)."""
    sys.path.insert(0, REPO)
    import chip_smoke
    reqs = chip_smoke.make_requests(16, 49152, 0)
    assert len(reqs) == 16
    prefix = reqs[0].prompt[:chip_smoke.PREFIX]
    for r in reqs:
        n = len(r.prompt)
        assert 128 <= n <= chip_smoke.MAX_PROMPT
        assert (n - chip_smoke.PREFIX) % 64 == 0
        assert (r.prompt[:chip_smoke.PREFIX] == prefix).all()
        assert r.max_new_tokens == chip_smoke.NEW_TOKENS
    assert chip_smoke.SLOT_LEN % chip_smoke.BLOCK == 0
    assert chip_smoke.SLOT_LEN >= chip_smoke.MAX_PROMPT + chip_smoke.NEW_TOKENS


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_comparison_catches_planted_fault(quantized):
    """The smoke's kernel-vs-XLA comparison, run here with the kernels in
    interpret mode at smollm_360m's widths: the kernels pass it, and the
    same kernels reading one wrong page per row fail it on every row of
    more than one page, the longest row included."""
    sys.path.insert(0, REPO)
    import chip_smoke
    import repro.configs as configs
    from repro.kernels import dispatch
    cfg = configs.get("smollm_360m").replace(use_pallas=True)
    chip_smoke.compare_paged_attention(
        cfg, quantized, 0, path=dispatch.PATH_PALLAS_INTERPRET)


def test_drop_last_page_plants_one_wrong_page_per_row():
    sys.path.insert(0, REPO)
    import chip_smoke
    import numpy as np
    bs = chip_smoke.BLOCK
    tables = np.arange(1, 13).reshape(3, 4)
    got = np.asarray(chip_smoke._drop_last_page(tables, [1, 2 * bs, 4 * bs]))
    np.testing.assert_array_equal(got, [[1, 2, 3, 4],        # one page: kept
                                        [5, 5, 7, 8],        # page 1 → page 0
                                        [9, 10, 11, 9]])     # page 3 → page 0


def test_four_chip_train_on_virtual_devices():
    """``--four-chips``' comparison on four virtual CPU devices (smoke
    config): both meshes, fp32 and bf16, inside their bounds."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import chip_smoke as cs, repro.configs as configs\n"
        "cs.TRAIN_STEPS = 2\n"
        "cs.four_chip_train(configs.get_smoke('smollm_360m'), 0, "
        "cs.CompileClock())\n"
        "print('OK')\n") % REPO
    env = {k: v for k, v in os.environ.items()
           if k != "REPRO_PALLAS_INTERPRET"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert out.stdout.count("[train float32] mesh") == 2
    assert out.stdout.count("[train bfloat16] mesh") == 2
