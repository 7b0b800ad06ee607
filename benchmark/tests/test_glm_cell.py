"""The cell ``glm4_7_flash-train-b1-l4096``: its manifest entries load,
no width differs from the published config, its arithmetic is ISSUE
37's, the ring's three slices are one sequence's, and ``--rehearse``
walks its control flow on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import flops_glm4_moe_lite as flops
from benchmark import manifest
from benchmark.measure import Measurement
from benchmark.readers import scopes
from benchmark.runners import train_fit_mtp_tokens as runner

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "glm4_7_flash-train-b1-l4096"
NEW_METRICS = {"mla_share", "mla_core_fwd_roofline", "mla_core_bwd_roofline",
               "mla_projections_roofline", "mtp_share",
               "gated_experts_roofline", "gated_moe_route_share",
               "gated_moe_load_imbalance"}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(ROOT, CELL)


def test_the_cell_loads_with_its_eight_metrics(cell):
    assert cell.chips == 1
    assert cell.traffic["kind"] == "train_fit_mtp_tokens"
    assert cell.traffic["mtp_weight"] == cell.config["kwargs"]["mtp_weight"]
    names = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= names
    # the other decoders' and the LSTM's metrics keep their own cells
    assert not names & {"moe_experts_roofline", "moe_layer_experts_roofline",
                        "causal_attention_fwd_roofline", "ssm_mixer_share",
                        "block_attention_fwd_roofline", "lstm_fwd_roofline"}
    for m in cell.per_layer:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_examples_per_s"
            assert m["reader"].startswith("benchmark.readers.scope")
            assert callable(manifest.resolve(m["reader"]))
            cost = m["args"].get("cost")
            assert cost is None or callable(manifest.resolve(cost))
    assert {m["name"] for m in cell.end_to_end} == {"train_examples_per_s",
                                                    "setup_s"}
    # no metric that was there changed its list
    listed = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in listed["per_layer"]:
        if m["name"] not in NEW_METRICS:
            assert CELL not in m.get("workloads", [])


def test_no_width_differs_from_the_published_config(cell):
    published = {
        "hidden_size": 2048, "intermediate_size": 10240,
        "moe_intermediate_size": 1536, "num_attention_heads": 20,
        "num_key_value_heads": 20, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
        "num_experts_per_tok": 4, "routed_scaling_factor": 1.8,
        "n_shared_experts": 1, "first_k_dense_replace": 1,
        "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-05,
        "rope_theta": 1000000, "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "topk_method": "noaux_tc",
        "max_position_embeddings": 202752, "tie_word_embeddings": False}
    for key, value in published.items():
        assert cell.config[key] == value, key
    kw = cell.config["kwargs"]
    assert (kw["hidden"], kw["n_heads"], kw["q_rank"], kw["kv_rank"],
            kw["nope_dim"], kw["rope_dim"], kw["v_dim"], kw["mlp_width"],
            kw["expert_width"], kw["shared_width"], kw["experts_per_token"],
            kw["n_experts"], kw["routed_scale"], kw["first_dense"],
            kw["mtp_modules"], kw["mtp_weight"]) == (
                2048, 20, 768, 512, 192, 64, 256, 10240, 1536, 1536, 4, 64,
                1.8, 1, 1, 0.3)
    assert cell.config["reduced"] == ["num_hidden_layers",
                                      "n_routed_experts", "vocab_size"]
    assert (cell.config["num_hidden_layers"], cell.config["n_routed_experts"],
            cell.config["vocab_size"]) == (5, 8, 19360)
    assert (kw["n_layers"], kw["experts_held"], kw["vocab_size"]) == (
        5, 8, 19360)
    assert cell.config["published"] == {
        "num_hidden_layers": 47, "n_routed_experts": 64,
        "vocab_size": 154880}
    assert 8 * 19360 == 154880
    part = cell.config["widths"]["parameters_by_part"]
    attention = part["attention_a_layer"]
    assert attention == (2048 * 768 + 768 * 20 * 256 + 2048 * 576
                         + 512 * 20 * 448 + 20 * 256 * 2048
                         + 2048 + 768 + 512) == 21_761_280
    an_expert_layer = (attention + part["ffn_norm_a_layer"]
                       + part["router_a_layer"]
                       + part["shared_expert_a_layer"]
                       + part["routed_experts_a_layer"])
    assert an_expert_layer == 106_829_056
    dense_layer = attention + part["ffn_norm_a_layer"] + part["dense_mlp"]
    assert dense_layer == 84_677_888
    module = an_expert_layer + part["module_eh_proj"] + part["module_norms"]
    assert module == 115_223_808
    assert (dense_layer + 4 * an_expert_layer + module + part["embedding"]
            + part["head"] + part["final_norm"]
            ) == cell.config["widths"]["parameters"] == 706_518_528


def test_required_work_is_the_issues(cell):
    """352.6 M multiply-adds a row, 1.18e13 FLOPs a step of which the
    six attention cores are 3.09e12, 2,048 pairs a layer."""
    assert flops.macs_a_row(cell.config, cell.traffic) == 352_583_680
    step = flops.train_step(cell.config, cell.traffic)["flops"]
    pairs = 4096 * 4097 // 2
    cores = 6 * 12 * 256 * 20 * pairs
    assert step == 6 * 4096 * 352_583_680 + cores
    assert abs(step - 1.18e13) < 0.01e13 and abs(cores - 3.09e12) < 0.01e12
    assert flops.expected_pairs_a_layer(cell.config, cell.traffic) == 2048
    fwd = flops.mla_core_fwd(cell.config, cell.traffic)
    bwd = flops.mla_core_bwd(cell.config, cell.traffic)
    assert fwd["flops"] == 6 * 4 * 256 * 20 * pairs == bwd["flops"] // 2
    # q, k, v and the output of 20 heads of 256 in bf16, a statistic
    assert fwd["bytes"] == 6 * 4096 * (4 * 20 * 256 * 2 + 20 * 4)
    assert bwd["bytes"] == 6 * 4096 * (8 * 20 * 256 * 2 + 20 * 4)
    projections = flops.mla_projections(cell.config, cell.traffic)
    assert projections["flops"] == 6 * 6 * 4096 * (21_761_280 - 3328)
    # latent attention is 53% of the required work, the cores 26%
    assert abs((projections["flops"] + cores) / step - 0.53) < 0.01
    assert abs(cores / step - 0.26) < 0.01
    # the routed experts' own roofline: the pairs the run counted, nine
    # products of 2,048 x 1,536 a pair, three matrices an expert
    assert flops.gated_experts(cell.config, cell.traffic, {}) is None
    routed = flops.gated_experts(cell.config, cell.traffic,
                                 {"moe_pairs_per_step": 5 * 2048})
    assert routed["flops"] == 6 * 5 * 2048 * 3 * 2048 * 1536
    assert routed["bytes"] == (5 * 8 * 3 * 2048 * 1536 * (2 * 2 + 4)
                               + 5 * 2048 * 2048 * (3 * 2 + 2 * 4))
    assert abs(routed["flops"] / step - 0.05) < 0.005


def test_the_rings_three_slices_are_one_sequences(cell):
    ring = runner.make_ring(cell.config, cell.traffic, 3000000019)
    again = runner.make_ring(cell.config, cell.traffic, 3000000019)
    assert len(ring) == 2
    for ds, same in zip(ring, again):
        assert ds.features.shape == (1, 4096) and ds.labels.shape == (
            1, 2, 4096)
        assert ds.features.dtype == ds.labels.dtype == np.int32
        assert ds.labels_mask is None
        np.testing.assert_array_equal(ds.features, same.features)
        np.testing.assert_array_equal(ds.labels, same.labels)
        # ids 0..4095, 1..4096 and 2..4097 of one draw of 4,098
        np.testing.assert_array_equal(ds.features[:, 1:],
                                      ds.labels[:, 0, :-1])
        np.testing.assert_array_equal(ds.labels[:, 0, 1:],
                                      ds.labels[:, 1, :-1])
        assert 0 <= ds.labels.min() and ds.labels.max() < 19360
    assert not np.array_equal(ring[0].features, ring[1].features)
    other = runner.make_ring(cell.config, cell.traffic, 7)
    assert not np.array_equal(ring[0].features, other[0].features)


def test_scope_readers_give_none_without_a_trace(cell):
    m = Measurement(config=cell.config, traffic=cell.traffic, chips=1,
                    peaks=manifest.load_peaks("TPU v5 lite"), window_s=1.0,
                    spans=[], counters={"steps_per_dispatch": 8})
    for metric in cell.per_layer:
        if metric["name"] in NEW_METRICS - {"gated_moe_load_imbalance"}:
            assert manifest.resolve(metric["reader"])(
                m, **metric["args"]) is None
    assert scopes.share_of_busy(m, ["mtp"], ["mtp"]) is None


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
    # two expert layers and the module's, four held experts each
    assert len(counters["moe_expert_rows"]) == 3 * 4
    assert len(counters["moe_pairs_per_layer"]) == 3
    for op in ("causal_attention", "grouped_matmul"):
        assert sum(counters[f"{op}_calls_by_backend"].values()) > 0, op
    assert counters["mla_layers_traced"] >= 4
    checks = info["checks"]
    assert [b["kind"] for b in checks["blocks"]] == list("DEEM")
    assert set(checks["block_fp8_reading"]) == set("DEM")
    first = checks["first_dispatch"]
    assert first["steps"] == 8 and "fp8_would_fail" in checks
    # the toy widths' bf16 stream rounds as coarsely as an expert layer
    # adds (a layer of 0.02-normal matrices 64 wide adds a hundredth of
    # its input), so the expert and module limits mean little here, as
    # in the other two decoder cells' rehearsals; the dense layer, both
    # logit arrays, both losses and the first dispatch's state are held
    assert checks["blocks"][0]["ok"]
    for z in ("logits", "module_logits"):
        assert checks[z]["rel_err"] <= checks["tol"]["logits"]
        assert checks[z]["rel_err_all_rows"] <= checks["tol"][
            "logits_all_rows"]
    assert first["grad_rel_err"] <= checks["tol"]["grad"]
    assert first["grad_rel_err_shared"] <= checks["tol"]["grad"]
    assert first["update_rel_err"] <= checks["tol"]["update"]
    both = checks["first_loss"]["main_and_mtp"]
    assert max(both["rel_err"]) <= checks["tol"]["loss"]
    assert abs(both["system"][0] + 0.3 * both["system"][1]
               - checks["first_loss"]["system"]) < 1e-4
    assert checks["loss"]["window_last"] < checks["loss"][
        "first_on_last_batch"]
    assert len(checks["loss"]["window_last_main_and_mtp"]) == 2
