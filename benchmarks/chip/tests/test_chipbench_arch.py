"""The architecture module a configuration names: ``load_cell`` refuses a
file that names none, and both the check that decides ``correct`` and the
counts the per-layer metrics read come from the module named.

``tests/data/planted_dense.py`` stands in for a later architecture: it is
``dense_gqa`` with one weight drawn from another key and its matmul FLOPs
doubled, found only because the test points ``reference.MODULES_DIR`` at
``tests/data``.
"""
import copy
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
DATA = os.path.join(BENCH, "tests", "data")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import flops  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import run_cell  # noqa: E402
from test_chipbench_check import SEED, _cell, cpu_run  # noqa: E402,F401


def _committed(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _bench_dir(tmp_path, config: dict) -> str:
    """A root holding one cell whose configuration file is ``config``."""
    (tmp_path / "c.json").write_text(json.dumps(config))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "c", "file": "c.json"}],
        "workloads": [{"name": "w", "config": "c", "traffic": "decode",
                       "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"}],
        "per_layer": []}))
    return str(tmp_path)


@pytest.mark.parametrize("ref", [None, "no_such_arch", "../dense_gqa",
                                 "references/dense_gqa", ""])
def test_load_cell_refuses_a_config_without_a_reference_module(tmp_path,
                                                               ref):
    config = _committed("smollm-360m")
    if ref is None:
        del config["reference"]
    else:
        config["reference"] = ref
    with pytest.raises(ValueError):
        harness.load_cell("w", root=_bench_dir(tmp_path, config))


def test_load_cell_takes_a_config_that_names_a_module(tmp_path):
    cell = harness.load_cell("w", root=_bench_dir(tmp_path,
                                                  _committed("smollm-360m")))
    assert cell.arch is reference.arch("dense_gqa")
    assert cell.model["reference"] == "dense_gqa"


# the sizes the harness read from each committed file before the
# architecture modules (one fixed key list for every configuration)
DENSE_SIZES = {
    "smollm-360m": dict(
        vocab_size=49152, d_model=960, num_layers=32, num_heads=15,
        num_kv_heads=5, head_dim=64, d_ff=2560, rope_theta=10000.0,
        norm_eps=1e-05, tie_embeddings=True, dtype="bfloat16",
        embedding_scale=0.02),
    "mistral-nemo-12b.l8": dict(
        vocab_size=131072, d_model=5120, num_layers=8, num_heads=32,
        num_kv_heads=8, head_dim=128, d_ff=14336, rope_theta=1000000.0,
        norm_eps=1e-05, tie_embeddings=False, dtype="bfloat16",
        embedding_scale=1.0),
}


def _workloads() -> list:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [(w["name"], w["config"])
                for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("name,config", _workloads())
def test_committed_cells_read_dense_gqa_as_before(name, config):
    """Every committed configuration names ``dense_gqa``, whose sizes are
    the ones the harness read before it had architecture modules."""
    cell = harness.load_cell(name)
    assert cell.config["reference"] == "dense_gqa"
    assert cell.model == dict(DENSE_SIZES[config], reference="dense_gqa")


@pytest.mark.parametrize("name,layers", [("smollm-360m", 32),
                                         ("mistral-nemo-12b.l8", 8)])
def test_program_config_sets_reduced_keys_and_checks_the_rest(name, layers):
    cell = harness.Cell(name=name, chips=1, config=_committed(name),
                        traffic={}, end_to_end=[], per_layer=[])
    assert harness.program_config(cell).num_layers == layers
    wrong = copy.deepcopy(cell.config)
    wrong["hidden_size"] += 64
    with pytest.raises(ValueError, match="d_model"):
        harness.program_config(harness.Cell(
            name=name, chips=1, config=wrong, traffic={}, end_to_end=[],
            per_layer=[]))
    wrong = dict(cell.config, reduced=["model_type"])
    with pytest.raises(ValueError, match="model_type"):
        harness.program_config(harness.Cell(
            name=name, chips=1, config=wrong, traffic={}, end_to_end=[],
            per_layer=[]))


def test_planted_module_decides_correct(cpu_run, monkeypatch):  # noqa: F811
    """One smoke run of ``run_cell.run`` under a configuration that names
    the planted module reads not correct; the same served requests,
    compared by ``dense_gqa``, read correct.  The six-layer smoke model of
    the fault tests, whose best token depends on the context."""
    cell, cfg = _cell("mistral_nemo_12b", "closed", num_layers=6)
    cell.config["reference"] = "planted_dense"
    monkeypatch.setattr(reference, "MODULES_DIR", DATA)
    seen = {}
    real = harness.compare

    def keep(cell_, seed, sample, prompts, control=None):
        seen.update(seed=seed, sample=sample, prompts=prompts)
        return real(cell_, seed, sample, prompts, control)
    monkeypatch.setattr(harness, "compare", keep)
    monkeypatch.setattr(run_cell, "WANT_TOKENS", 10**9)
    monkeypatch.setattr(run_cell, "MAX_REQUESTS", 10**6)
    out = cpu_run(cell, cfg)
    assert not out["correct"], out["checks"]
    assert out["checks"]["max_logit_gap"]["value"] > 1e-3

    monkeypatch.setattr(reference, "MODULES_DIR",
                        os.path.join(BENCH, "references"))
    cell.config["reference"] = "dense_gqa"
    got = real(cell, seen["seed"], seen["sample"], seen["prompts"])
    correct, checks = harness.verdict(
        got["max_logit_gap"], got["served_tokens_compared"],
        cell.config["limits"]["max_logit_gap"], run_cell.MIN_TOKENS)
    assert correct, checks


def test_planted_counts_reach_model_step_mfu(monkeypatch):
    """``model_step.mfu`` over one window, under the committed smollm file
    and under the same file naming the planted module: the planted reading
    is higher by exactly the doubled matmul FLOPs of every token."""
    monkeypatch.setattr(reference, "MODULES_DIR", DATA)
    planted_cfg = dict(_committed("smollm-360m"), reference="planted_dense")
    win = harness.Window(
        start=0.0, end=10.0, due={}, results=[], usable_blocks=1,
        counters0={}, counters1={}, lateness=[],
        ticks=[harness.Tick([100, 200], 1), harness.Tick([101, 201], 0)],
        chunks=[(0, 128), (128, 64)])
    peak = flops.peaks("TPU v5 lite")
    mfu = harness.layer_reader("model_step.mfu")

    def read(config):
        cell = harness.Cell(name="w", chips=1, config=config, traffic={},
                            end_to_end=[], per_layer=[])
        return mfu(harness.RunRecord(cell=cell, window=win, trace=None,
                                     peak=peak)), cell.model

    planted, _ = read(planted_cfg)
    monkeypatch.setattr(reference, "MODULES_DIR",
                        os.path.join(BENCH, "references"))
    dense, m = read(_committed("smollm-360m"))
    tokens = 4 + 128 + 64
    extra = tokens * m["num_layers"] * flops.layer_matmul_flops(m)
    assert planted - dense == pytest.approx(
        100.0 * extra / (10.0 * peak["bf16_flops_per_s"]), rel=1e-12)
    assert planted > dense > 0


def test_every_metric_has_a_reader_and_its_cells_report_what_it_moves():
    """Each per-layer metric of BENCHMARK.json loads its reader, and every
    cell it lists reports the end-to-end metric it moves (a split reading
    such as ``itl.model_step.mfu`` reads what its base metric reads)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in reported, (w["name"], m["name"])
            assert callable(harness.layer_reader(m["name"]))
    win = harness.Window(
        start=0.0, end=10.0, due={}, results=[], usable_blocks=1,
        counters0={}, counters1={}, lateness=[],
        ticks=[harness.Tick([100, 200], 1)], chunks=[(0, 128)])
    cell = harness.load_cell("smollm-360m.decode.closed")
    run = harness.RunRecord(cell=cell, window=win, trace=None,
                            peak=flops.peaks("TPU v5 lite"))
    assert harness.layer_reader("itl.model_step.mfu")(run) == \
        harness.layer_reader("model_step.mfu")(run) > 0
