"""The cell ``keye_vl2_30b_a3b-train-b1-l8192``: its manifest entries
load, no width differs from the published config, its parameter count,
FLOPs and bytes are ISSUE 43's, the yardsticks count the selected pairs,
and ``--rehearse`` walks its control flow on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import flops_keye_vl2_moe as flops
from benchmark import manifest
from benchmark.measure import Measurement
from benchmark.reference import keye_vl2_moe as ref
from benchmark.runners import train_fit_sparse_tokens as runner

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "keye_vl2_30b_a3b-train-b1-l8192"
NEW_METRICS = {"dsa_share", "dsa_select_share", "dsa_indexer_roofline",
               "dsa_core_fwd_roofline", "dsa_core_bwd_roofline",
               "keye_experts_roofline", "keye_moe_route_share",
               "keye_moe_load_imbalance"}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(ROOT, CELL)


def test_the_cell_loads_with_its_eight_metrics(cell):
    assert cell.chips == 1
    assert cell.traffic["kind"] == "train_fit_sparse_tokens"
    assert (cell.traffic["batch"], cell.traffic["seq_len"],
            cell.traffic["ring_batches"], cell.traffic["warmup_steps"]) == (
                1, 8192, 2, 16)
    names = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= names
    assert not names & {"moe_experts_roofline", "gqa64_core_fwd_roofline",
                        "causal_attention_fwd_roofline", "mla_share"}
    for m in cell.per_layer:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_examples_per_s"
            assert m["unit"] == "%" or m["name"] == "keye_moe_load_imbalance"
            assert callable(manifest.resolve(m["reader"]))
            cost = m["args"].get("cost")
            assert cost is None or callable(manifest.resolve(cost))
    assert {m["name"] for m in cell.end_to_end} == {"train_examples_per_s",
                                                    "setup_s"}
    listed = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in listed["per_layer"]:
        if m["name"] not in NEW_METRICS:
            assert CELL not in m.get("workloads", [])
    assert [c["reduced"] for c in listed["configs"]
            if c["name"] == "keye_vl2_30b_a3b"] == [[
                "num_hidden_layers", "num_experts", "vocab_size"]]
    assert cell.config["required_kernels"] == {
        "dl4j_sparse_attention_calls_total": "pallas",
        "dl4j_dsa_select_calls_total": "pallas",
        "dl4j_moe_grouped_matmul_calls_total": "pallas"}


def test_no_width_differs_from_the_published_config(cell):
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "KeyeVL2", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    for key, value in published.items():
        assert cell.config[key] == value, key
    kw = cell.config["kwargs"]
    sa_config = cell.config["sa_config"]
    assert (kw["hidden"], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"],
            kw["expert_width"], kw["experts_per_token"], kw["n_experts"],
            kw["index_heads"], kw["index_head_dim"], kw["index_topk"],
            kw["eps"], kw["rope_theta"]) == (
                2048, 32, 4, 128, 768, 8, 128, sa_config["indexer_num_heads"],
                sa_config["indexer_head_dim"], sa_config["topk"], 1e-6, 1e7)
    assert cell.config["reduced"] == ["num_hidden_layers", "num_experts",
                                      "vocab_size"]
    assert (cell.config["num_hidden_layers"], cell.config["num_experts"],
            cell.config["vocab_size"]) == (4, 16, 18992)
    assert (kw["n_layers"], kw["experts_held"], kw["vocab_size"]) == (
        4, 16, 18992)
    counts = cell.config["published"]
    assert {k: counts[k] for k in cell.config["reduced"]} == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    assert 8 * 18992 == 151936 and 8 * 16 == 128


def test_the_parameters_are_the_issues(cell):
    part = cell.config["widths"]["parameters_by_part"]
    assert part["attention_a_layer"] == (2 * 2048 * 4096 + 2 * 2048 * 512
                                         + 2 * 128) == 18_874_624
    assert part["indexer_a_layer"] == (2048 * 1024 + 2048 * 64 + 2 * 64
                                       + 2048 * 16) == 2_261_120
    assert part["router_a_layer"] == 2048 * 128
    assert part["routed_experts_a_layer"] == 16 * 3 * 2048 * 768
    layer = sum(part[k] for k in ("attention_a_layer", "indexer_a_layer",
                                  "router_a_layer", "routed_experts_a_layer",
                                  "norms_a_layer"))
    assert layer == 96_899_456
    assert part["embedding"] == part["head"] == 18992 * 2048
    total = 4 * layer + part["embedding"] + part["head"] + part["final_norm"]
    assert total == cell.config["widths"]["parameters"] == 465_391_104
    # 16 bytes a parameter: float32 weights, gradients and Adam's moments
    assert abs(16 * total / 1e9 - 7.45) < 0.01


def test_the_yardsticks_count_the_selected_pairs(cell):
    config, traffic = cell.config, cell.traffic
    selected = flops.selected_pairs_a_sequence(8192, 2048)
    visible = 8192 * 8193 // 2
    assert selected == 14_681_088 and visible == 33_558_528
    assert abs(selected / visible - 0.437) < 0.001
    assert flops.selected_pairs_a_sequence(1000, 2048) == 1000 * 1001 // 2
    fwd = flops.dsa_core_fwd(config, traffic)
    bwd = flops.dsa_core_bwd(config, traffic)
    assert fwd["flops"] == 4 * 32 * 4 * 128 * selected
    assert bwd["flops"] == 2 * fwd["flops"]
    # 240.5 and 481 GFLOP a layer
    assert abs(fwd["flops"] / 4 - 240.5e9) < 0.1e9
    assert abs(bwd["flops"] / 4 - 481.0e9) < 0.1e9
    words = 8192 * 8192 // 8
    assert fwd["bytes"] == 4 * (8192 * ((2 * 32 + 2 * 4) * 128 * 2 + 32 * 4)
                                + words)
    assert bwd["bytes"] == 4 * (8192 * ((4 * 32 + 4 * 4) * 128 * 2 + 32 * 4)
                                + words)
    index = flops.dsa_indexer(config, traffic)
    assert index["flops"] == 4 * 2 * 16 * 64 * visible
    assert abs(index["flops"] / 4 - 68.7e9) < 0.05e9
    assert index["bytes"] == 4 * (8192 * ((16 * 64 + 64) * 2 + 4 * 16 + 4)
                                  + words)
    # bound by FLOPs, all three
    for need in (fwd, bwd, index):
        assert need["flops"] / 197e12 > need["bytes"] / 819e9
    step = flops.train_step(config, traffic)["flops"]
    macs = flops.macs_a_row(config, traffic)
    assert macs == 4 * (2 * 2048 * 128 * 36 + 2048 * (1024 + 64 + 16)
                        + 2048 * 128 + 8 * 16 / 128 * 3 * 2048 * 768
                        ) + 2048 * 18992
    assert step == 6 * 8192 * macs + 4 * (12 * 128 * 32 * selected
                                          + 2 * 1024 * visible
                                          + 4 * 1024 * selected)
    assert flops.expected_pairs_a_layer(config, traffic) == 8192
    # the experts over the pairs a run counted
    assert flops.keye_experts(config, traffic, {}) is None
    routed = flops.keye_experts(config, traffic,
                                {"moe_pairs_per_step": 4 * 8192})
    assert routed["flops"] == 6 * 4 * 8192 * 3 * 2048 * 768
    assert routed["bytes"] == (4 * 16 * 3 * 2048 * 768 * (2 * 2 + 4)
                               + 4 * 8192 * 2048 * (3 * 2 + 2 * 4))


def test_the_rings_two_slices_are_one_sequences(cell):
    ring = runner.make_ring(cell.config, cell.traffic, 3000000019)
    again = runner.make_ring(cell.config, cell.traffic, 3000000019)
    assert len(ring) == 2
    for ds, same in zip(ring, again):
        assert ds.features.shape == ds.labels.shape == (1, 8192)
        assert ds.features.dtype == ds.labels.dtype == np.int32
        np.testing.assert_array_equal(ds.features, same.features)
        np.testing.assert_array_equal(ds.features[:, 1:], ds.labels[:, :-1])
        assert 0 <= ds.labels.min() and ds.labels.max() < 18992
    assert not np.array_equal(ring[0].features, ring[1].features)


def test_scope_readers_give_none_without_a_trace(cell):
    m = Measurement(config=cell.config, traffic=cell.traffic, chips=1,
                    peaks=manifest.load_peaks("TPU v5 lite"), window_s=1.0,
                    spans=[], counters={"steps_per_dispatch": 8})
    for metric in cell.per_layer:
        if metric["name"] in NEW_METRICS - {"keye_moe_load_imbalance"}:
            assert manifest.resolve(metric["reader"])(
                m, **metric["args"]) is None


def test_the_reference_names_its_kind_and_reads_none_of_the_program():
    assert ref.KINDS == ("sparse_experts",)
    assert ref.kind_of({"W": 0}) is None and ref.kind_of({"g": 0}) is None
    assert ref.kind_of({"Wq": 0, "W_IQ": 0, "Wr": 0}) == "sparse_experts"
    source = open(ref.__file__).read()
    assert "deeplearning4j_tpu" not in source.split('"""', 2)[2]


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
    calls = counters["kernel_calls_by_backend"]
    # 256 rows, heads of 64: the interpreter ran every kernel
    for metric, backend in manifest.load_cell(ROOT, CELL).config[
            "required_kernels"].items():
        assert set(calls[metric]) == {backend}, metric
    # 256 rows of a top 64 and one tile pair a sequence at this size
    pairs = sum(min(t + 1, 64) for t in range(256))
    assert counters["dsa_selected_pairs"] == {"layer_1": pairs,
                                              "layer_2": pairs}
    assert counters["dsa_tiles_walked_skipped"] == {"layer_1": [1, 0],
                                                    "layer_2": [1, 0]}
    checks = info["checks"]
    assert [b["kind"] for b in checks["blocks"]] == ["sparse_experts"] * 2
    selection = checks["selection"]
    assert [r["layer"] for r in selection["layers"]] == ["layer_1",
                                                         "layer_2"]
    assert all(r["rows_count_ok"] for r in selection["layers"])
    assert all(r["moved_by_first_dispatch"] >= 0
               for r in selection["layers"])
    # what the check has to refuse, it refuses
    assert set(selection["refused_on_first_layer"]) == {
        "fp8_indexer", "at_random", "by_position"}
    assert min(selection["refused_on_first_layer"].values()) > 1.0
    first = checks["first_loss"]
    assert first["rel_err"] <= checks["tol"]["loss"]
    ce, index = first["lm_and_indexer"]["reference"]
    assert abs(ce + index - first["reference"]) <= 1e-5 * first["reference"]
    assert index > 0
    leaves = checks["first_dispatch"]["leaves"]
    assert leaves["layer_1.W_IQ"]["kind"] == "indexer"
    assert leaves["layer_1.Wg"]["kind"] == "experts"
    assert leaves["layer_1.Wr"]["kind"] == "router"
    assert leaves["layer_1.Wq"]["kind"] == "plain"
    assert leaves["layer_1.Wq"]["grad_selection_moved"] >= 0
    now = checks["first_dispatch"]
    assert now["grad_rel_err_beyond_selection_move"] == max(
        v["grad"] - v["grad_selection_moved"] for v in leaves.values()
        if v["kind"] == "plain")
    assert checks["loss"]["window_last"] < checks["loss"][
        "first_on_last_batch"]
