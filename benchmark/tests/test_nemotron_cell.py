"""The cell ``nemotron3_nano_30b_a3b-train-b1-l4096``: its manifest
entries load, no width differs from the published config, its arithmetic
is ISSUE 33's, the scope readers read what they are given, and
``--rehearse`` walks its control flow on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import flops_nemotron_h as flops
from benchmark import manifest
from benchmark.measure import Measurement
from benchmark.readers import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "nemotron3_nano_30b_a3b-train-b1-l4096"
NEW_METRICS = {"ssm_mixer_share", "ssm_scan_fwd_roofline",
               "ssm_scan_bwd_roofline", "causal_attention_fwd_roofline",
               "causal_attention_bwd_roofline", "moe_layer_share",
               "moe_layer_experts_roofline", "moe_layer_route_share",
               "moe_layer_load_imbalance"}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(ROOT, CELL)


def test_the_cell_loads_with_its_nine_metrics(cell):
    assert cell.chips == 1
    assert cell.traffic["kind"] == "train_fit_causal_tokens"
    names = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= names
    # the other decoder's and the LSTM's metrics keep their own cells
    assert not names & {"moe_experts_roofline", "block_attention_fwd_roofline",
                        "lstm_fwd_roofline", "moe_load_imbalance"}
    for m in cell.per_layer:
        if m["name"] in NEW_METRICS:
            assert m["reader"].startswith("benchmark.readers.scope")
            assert callable(manifest.resolve(m["reader"]))
    assert {m["name"] for m in cell.end_to_end} == {"train_examples_per_s",
                                                    "setup_s"}


def test_no_width_differs_from_the_published_config(cell):
    published = {
        "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
        "chunk_size": 128, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
        "n_shared_experts": 1, "norm_eps": 1e-05, "expand": 2}
    for key, value in published.items():
        assert cell.config[key] == value, key
    kw = cell.config["kwargs"]
    assert (kw["hidden"], kw["mamba_heads"], kw["mamba_head_dim"],
            kw["state_size"], kw["n_groups"], kw["conv_kernel"], kw["chunk"],
            kw["n_heads"], kw["n_kv_heads"], kw["head_dim"],
            kw["expert_width"], kw["shared_width"], kw["experts_per_token"],
            kw["n_experts"], kw["routed_scale"]) == (
                2688, 64, 64, 128, 8, 4, 128, 32, 2, 128, 1856, 3712, 6, 128,
                2.5)
    assert cell.config["reduced"] == ["num_hidden_layers",
                                      "n_routed_experts", "vocab_size"]
    assert (cell.config["num_hidden_layers"], cell.config["n_routed_experts"],
            cell.config["vocab_size"]) == (9, 8, 16384)
    assert cell.config["published"]["num_hidden_layers"] == 52
    # the first nine layers of the published pattern, as published
    assert cell.config["published"]["hybrid_override_pattern"].startswith(
        kw["pattern"])
    assert cell.config["hybrid_override_pattern"] == kw["pattern"]
    by_part = cell.config["widths"]["parameters_by_part"]
    assert (4 * by_part["mamba_a_layer"] + by_part["attention_a_layer"]
            + 4 * (by_part["router_a_layer"] + by_part["shared_expert_a_layer"]
                   + by_part["routed_experts_a_layer"]
                   + by_part["expert_layer_norm"])
            + by_part["embedding"] + by_part["head"] + by_part["final_norm"]
            ) == cell.config["widths"]["parameters"] == 666_962_944


def test_required_work_is_the_issues(cell):
    """8.4 TFLOP a step, 1,536 pairs a layer, 3.4 MFLOP of recurrence a
    position and layer, the visible pairs of a causal mask."""
    step = flops.train_step(cell.config, cell.traffic)["flops"]
    assert abs(step - 8.4e12) < 0.02e12
    assert flops.expected_pairs_a_layer(cell.config, cell.traffic) == 1536
    fwd = flops.ssm_scan_fwd(cell.config, cell.traffic)
    bwd = flops.ssm_scan_bwd(cell.config, cell.traffic)
    a_position = 2 * (8 * 128 * 128 + 64 * 128 * 64 + 2 * 64 * 64 * 128)
    assert fwd["flops"] == 4 * 4096 * a_position == bwd["flops"] // 2
    assert abs(a_position - 3.4e6) < 0.02e6 and bwd["bytes"] > fwd["bytes"]
    attn = flops.causal_attention_fwd(cell.config, cell.traffic)
    assert attn["flops"] == 4 * 128 * 32 * (4096 * 4097 // 2)
    assert flops.causal_attention_bwd(cell.config, cell.traffic)[
        "flops"] == 2 * attn["flops"]
    # the routed experts' own roofline: the pairs the run counted, six
    # products of 2,688 x 1,856 a pair, two matrices an expert
    assert flops.routed_experts(cell.config, cell.traffic, {}) is None
    routed = flops.routed_experts(cell.config, cell.traffic,
                                  {"moe_pairs_per_step": 4 * 1536})
    assert routed["flops"] == 6 * 4 * 1536 * 2 * 2688 * 1856
    assert routed["bytes"] == (4 * 8 * 2 * 2688 * 1856 * (2 * 2 + 4)
                               + 4 * 1536 * 2688 * (3 * 2 + 2 * 4))


def test_scope_readers_give_none_without_a_trace(cell):
    m = Measurement(config=cell.config, traffic=cell.traffic, chips=1,
                    peaks=manifest.load_peaks("TPU v5 lite"), window_s=1.0,
                    spans=[], counters={"steps_per_dispatch": 8})
    assert scopes.share_of_busy(m, ["mamba"], ["mamba", "attn"]) is None
    assert scopes.roofline(
        m, ["ssm_scan"], "benchmark.flops_nemotron_h:ssm_scan_fwd",
        ["forward"]) is None


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
    counters = info["counters"]
    assert counters["window_compiles"] == 0
    assert counters["steps_per_dispatch"] == 8
    # two expert layers of four held experts in the rehearsal's pattern
    assert len(counters["moe_expert_rows"]) == 2 * 4
    # some executor of each was traced; which one decides nothing
    for op in ("causal_attention", "ssm_scan", "grouped_matmul"):
        assert sum(counters[f"{op}_calls_by_backend"].values()) > 0, op
    checks = info["checks"]
    assert [b["kind"] for b in checks["blocks"]] == list("MEM*E")
    assert set(checks["block_fp8_reading"]) == set("ME*")
    first = checks["first_dispatch"]
    assert first["steps"] == 8 and "fp8_would_fail" in checks
    # the toy widths' bf16 stream rounds as coarsely as a layer adds, so
    # the block limits mean little here; the first dispatch's state and
    # the loss are held
    assert first["grad_rel_err"] <= checks["tol"]["grad"]
    assert first["update_rel_err"] <= checks["tol"]["update"]
    assert checks["first_loss"]["rel_err"] <= checks["tol"]["loss"]
    assert checks["loss"]["window_last"] < checks["loss"]["first"]
