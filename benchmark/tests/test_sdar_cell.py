"""The cell ``sdar_30b_a3b-train-b1-l4096``: its manifest entries load,
its arithmetic is ISSUE 31's, its readers read what the runner leaves,
and ``--rehearse`` walks its control flow on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import flops_sdar_moe as flops
from benchmark import manifest
from benchmark.measure import Measurement
from benchmark.readers import scoped

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "sdar_30b_a3b-train-b1-l4096"
NEW_METRICS = {"block_attention_fwd_roofline", "block_attention_bwd_roofline",
               "moe_experts_roofline", "moe_route_share",
               "moe_load_imbalance"}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(ROOT, CELL)


def test_the_cell_loads_with_its_five_metrics(cell):
    assert cell.chips == 1 and cell.traffic["kind"] == "train_fit_tokens"
    names = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= names and "lstm_fwd_roofline" not in names
    for m in cell.per_layer:
        if m["name"] in NEW_METRICS:
            assert callable(manifest.resolve(m["reader"]))
    assert {m["name"] for m in cell.end_to_end} == {"train_examples_per_s",
                                                    "setup_s"}


def test_no_width_differs_from_the_published_config(cell):
    published = {"head_dim": 128, "hidden_size": 2048,
                 "moe_intermediate_size": 768, "num_attention_heads": 32,
                 "num_key_value_heads": 4, "num_experts_per_tok": 8,
                 "rms_norm_eps": 1e-06, "rope_theta": 1000000,
                 "intermediate_size": 6144}
    for key, value in published.items():
        assert cell.config[key] == value, key
    kw = cell.config["kwargs"]
    assert (kw["hidden"], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"],
            kw["expert_width"], kw["experts_per_token"], kw["n_experts"]) == (
                2048, 32, 4, 128, 768, 8, 128)
    assert cell.config["reduced"] == ["num_hidden_layers", "num_experts",
                                      "vocab_size"]
    assert cell.config["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}


def test_required_work_is_the_issues(cell):
    """8.95 TFLOP a step; 16,793,600 visible pairs a sequence and head."""
    step = flops.train_step(cell.config, cell.traffic)["flops"]
    assert abs(step - 8.95e12) < 0.01e12
    fwd = flops.block_attention_fwd(cell.config, cell.traffic)
    bwd = flops.block_attention_bwd(cell.config, cell.traffic)
    assert fwd["flops"] == 4 * 32 * 4 * 128 * 16_793_600
    assert bwd["flops"] == 2 * fwd["flops"] and bwd["bytes"] > fwd["bytes"]
    assert flops.expected_pairs_a_layer(cell.config, cell.traffic) == 8192
    assert flops.moe_experts(cell.config, cell.traffic, {}) is None
    counted = flops.moe_experts(cell.config, cell.traffic,
                                {"moe_pairs_per_step": 4 * 8192})
    assert counted["flops"] == 6 * 4 * 8192 * 3 * 2048 * 768


def test_scoped_readers_give_none_without_a_trace_or_a_count(cell):
    m = Measurement(config=cell.config, traffic=cell.traffic, chips=1,
                    peaks=manifest.load_peaks("TPU v5 lite"), window_s=1.0,
                    spans=[], counters={"steps_per_dispatch": 8})
    assert scoped.scope_roofline(
        m, "experts", "benchmark.flops_sdar_moe:moe_experts") is None
    assert scoped.scope_share_of_busy(m, "route") is None
    assert scoped.largest_over_mean(m, "moe_expert_rows") is None
    m.counters["moe_expert_rows"] = [1.0, 3.0, 2.0, 2.0]
    assert scoped.largest_over_mean(m, "moe_expert_rows") == 1.5


def test_rehearsal_walks_the_cell(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("JAX_PLATFORMS", None)
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--rehearse", "--seconds", "2", "--seed",
         "3000000019"], env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-2000:]
    lines = [json.loads(l) for l in run.stdout.splitlines()
             if l.startswith("{")]
    assert lines[-1]["rehearsal"] is True and lines[-1]["failed"] == 0
    info = lines[1]
    assert info["counters"]["window_compiles"] == 0
    assert info["counters"]["steps_per_dispatch"] == 8
    assert len(info["counters"]["moe_expert_rows"]) == 2 * 4
    checks = info["checks"]
    assert len(checks["blocks"]) == 2 and "fp8_would_fail" in checks
    first = checks["first_dispatch"]
    assert first["steps"] == 8 and len(first["leaves"]) == 27
    # the toy widths' bf16 stream rounds as coarsely as a layer adds, so
    # the block limits fail here; the first dispatch's state is held
    assert first["grad_rel_err"] <= checks["tol"]["grad"]
    assert first["update_rel_err"] <= checks["tol"]["update"]
    assert checks["first_loss"]["rel_err"] <= checks["tol"]["loss"]
    # the zoo's router starts balanced: a pair a row and layer
    assert abs(info["counters"]["moe_pairs_per_step"] - 2 * 256) < 26
    readings = next(l["readings"] for l in lines if "readings" in l)
    assert "rehearsal:moe_load_imbalance" in readings
