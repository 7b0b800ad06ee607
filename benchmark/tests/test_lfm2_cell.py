"""The cell ``lfm2_24b_a2b-train-b1-l8192``: its manifest entries load,
no width differs from the published config, its arithmetic is ISSUE
39's, the ring's two slices are one sequence's, and ``--rehearse`` walks
its control flow on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import flops_lfm2_moe as flops
from benchmark import manifest
from benchmark.measure import Measurement
from benchmark.reference import lfm2_moe as ref
from benchmark.runners import train_fit_decoder_tokens as runner

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "lfm2_24b_a2b-train-b1-l8192"
NEW_METRICS = {"shortconv_share", "shortconv_fwd_roofline",
               "shortconv_bwd_roofline", "shortconv_projections_roofline",
               "gqa64_core_fwd_roofline", "gqa64_core_bwd_roofline",
               "lfm2_experts_roofline", "lfm2_moe_route_share",
               "lfm2_moe_load_imbalance"}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(ROOT, CELL)


def test_the_cell_loads_with_its_nine_metrics(cell):
    assert cell.chips == 1
    assert cell.traffic["kind"] == "train_fit_decoder_tokens"
    assert (cell.traffic["batch"], cell.traffic["seq_len"],
            cell.traffic["ring_batches"], cell.traffic["warmup_steps"]) == (
                1, 8192, 2, 16)
    names = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= names
    # the other decoders' and the LSTM's metrics keep their own cells
    assert not names & {"moe_experts_roofline", "gated_experts_roofline",
                        "causal_attention_fwd_roofline", "ssm_mixer_share",
                        "mla_share", "lstm_fwd_roofline"}
    for m in cell.per_layer:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_examples_per_s"
            assert m["reader"].startswith("benchmark.readers.scope")
            assert callable(manifest.resolve(m["reader"]))
            cost = m["args"].get("cost")
            assert cost is None or callable(manifest.resolve(cost))
            assert m["unit"] == "%" or m["name"] == "lfm2_moe_load_imbalance"
    assert {m["name"] for m in cell.end_to_end} == {"train_examples_per_s",
                                                    "setup_s"}
    # no metric that was there changed its list
    listed = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in listed["per_layer"]:
        if m["name"] not in NEW_METRICS:
            assert CELL not in m.get("workloads", [])
    assert [c["reduced"] for c in listed["configs"]
            if c["name"] == "lfm2_24b_a2b"] == [[
                "num_hidden_layers", "num_dense_layers", "num_experts",
                "vocab_size"]]
    # what ``correct`` requires on a TPU is named by the configuration
    assert cell.config["required_kernels"] == {
        "dl4j_short_conv_calls_total": "pallas",
        "dl4j_causal_attention_calls_total": "pallas",
        "dl4j_moe_grouped_matmul_calls_total": "pallas"}


def test_no_width_differs_from_the_published_config(cell):
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True}
    for key, value in published.items():
        assert cell.config[key] == value, key
    kw = cell.config["kwargs"]
    assert (kw["hidden"], kw["conv_kernel"], kw["n_heads"], kw["n_kv_heads"],
            kw["head_dim"], kw["mlp_width"], kw["expert_width"],
            kw["experts_per_token"], kw["n_experts"], kw["routed_scale"],
            kw["router_eps"], kw["eps"], kw["rope_theta"]) == (
                2048, 3, 32, 8, 64, 11776, 1536, 4, 64, 1.0, 1e-6, 1e-5, 1e6)
    assert kw["head_dim"] * kw["n_heads"] == kw["hidden"]
    assert cell.config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                      "num_experts", "vocab_size"]
    assert (cell.config["num_hidden_layers"], cell.config["num_dense_layers"],
            cell.config["num_experts"], cell.config["vocab_size"]) == (
                5, 1, 8, 8192)
    assert cell.config["layer_types"] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert (kw["pattern"], kw["n_dense"], kw["experts_held"],
            kw["vocab_size"]) == ("caccc", 1, 8, 8192)
    # layers 1..5 of the published pattern: one dense layer, one period
    from deeplearning4j_tpu.zoo.models import LFM2_MOE_PATTERN
    assert LFM2_MOE_PATTERN[1:6] == kw["pattern"]
    assert [t[0] for t in cell.config["layer_types"]] == [
        {"c": "c", "a": "f"}[k] for k in kw["pattern"]]
    published_counts = cell.config["published"]
    assert {k: published_counts[k] for k in cell.config["reduced"]} == {
        "num_hidden_layers": 40, "num_dense_layers": 2, "num_experts": 64,
        "vocab_size": 65536}
    assert 8 * 8192 == 65536
    part = cell.config["widths"]["parameters_by_part"]
    operator = part["conv_operator_a_layer"]
    assert operator == 2048 * 6144 + 2048 * 2048 + 2048 * 3 + 2048 \
        == 16_785_408
    attention = part["attention_a_layer"]
    assert attention == (2 * 2048 * 2048 + 2 * 2048 * 512 + 2048 + 2 * 64
                         ) == 10_487_936
    ffn = (part["ffn_norm_a_layer"] + part["router_a_layer"]
           + part["routed_experts_a_layer"])
    assert part["routed_experts_a_layer"] == 8 * 3 * 2048 * 1536
    assert operator + ffn == 92_416_000
    assert attention + ffn == 86_118_528
    dense_layer = operator + part["ffn_norm_a_layer"] + part["dense_mlp"]
    assert dense_layer == 89_139_200
    assert (dense_layer + attention + ffn + 3 * (operator + ffn)
            + part["embedding_and_head"] + part["final_norm"]
            ) == cell.config["widths"]["parameters"] == 469_284_992


def test_required_work_is_the_issues(cell):
    """202.9 M multiply-adds a row with the core (405.8 M FLOPs forward),
    9.97e12 FLOPs a step, 4,096 pairs a layer."""
    macs = flops.macs_a_row(cell.config, cell.traffic)
    assert macs == (4 * 16_777_216 + 10_485_760 + 3 * 2048 * 11776
                    + 4 * (131_072 + 0.5 * 9_437_184) + 2048 * 8192
                    ) == 186_122_240
    pairs = 8192 * 8193 // 2
    step = flops.train_step(cell.config, cell.traffic)["flops"]
    cores = 12 * 64 * 32 * pairs
    assert step == 6 * 8192 * 186_122_240 + cores
    assert abs(step - 9.97e12) < 0.01e12
    forward_a_row = (2 * macs * 8192 + cores / 3) / 8192
    assert abs(forward_a_row - 405.8e6) < 0.05e6
    assert flops.expected_pairs_a_layer(cell.config, cell.traffic) == 4096
    # the shares ISSUE 39 gives of a row's forward work
    shares = {"projections": 2 * 4 * 16_777_216, "dense": 2 * 72_351_744,
              "core": cores / 3 / 8192, "attention": 2 * 10_485_760,
              "experts": 2 * 4 * 0.5 * 9_437_184, "head": 2 * 16_777_216}
    for name, want in {"projections": 0.331, "dense": 0.357, "core": 0.083,
                       "attention": 0.052, "experts": 0.093,
                       "head": 0.083}.items():
        assert abs(shares[name] / forward_a_row - want) < 0.001, name
    fwd = flops.shortconv_fwd(cell.config, cell.traffic)
    bwd = flops.shortconv_bwd(cell.config, cell.traffic)
    assert fwd["bytes"] == 4 * 8192 * 4 * 2048 * 2
    assert bwd["bytes"] == 4 * 8192 * 7 * 2048 * 2
    assert fwd["flops"] == 4 * 8192 * 2048 * (2 * 3 + 2) == bwd["flops"] // 2
    # bound by bytes: 0.16 and 0.28 ms a layer at 819 GB/s
    assert abs(fwd["bytes"] / 4 / 819e9 - 0.16e-3) < 0.005e-3
    assert abs(bwd["bytes"] / 4 / 819e9 - 0.28e-3) < 0.007e-3
    assert fwd["flops"] / 197e12 < fwd["bytes"] / 819e9 / 50
    projections = flops.shortconv_projections(cell.config, cell.traffic)
    assert projections["flops"] == 6 * 4 * 8192 * 16_777_216
    assert projections["bytes"] == 4 * (16_777_216 * 8
                                        + 3 * 8192 * 6 * 2048 * 2)
    core_fwd = flops.gqa64_core_fwd(cell.config, cell.traffic)
    core_bwd = flops.gqa64_core_bwd(cell.config, cell.traffic)
    assert core_fwd["flops"] == 4 * 64 * 32 * pairs == core_bwd["flops"] // 2
    # q and the output of 32 heads, k and v of 8, of 64 in bf16; a statistic
    assert core_fwd["bytes"] == 8192 * ((2 * 32 + 2 * 8) * 64 * 2 + 32 * 4)
    assert core_bwd["bytes"] == 8192 * ((4 * 32 + 4 * 8) * 64 * 2 + 32 * 4)
    assert flops.lfm2_experts(cell.config, cell.traffic, {}) is None
    routed = flops.lfm2_experts(cell.config, cell.traffic,
                                {"moe_pairs_per_step": 4 * 4096})
    assert routed["flops"] == 6 * 4 * 4096 * 3 * 2048 * 1536
    assert routed["bytes"] == (4 * 8 * 3 * 2048 * 1536 * (2 * 2 + 4)
                               + 4 * 4096 * 2048 * (3 * 2 + 2 * 4))
    assert abs(routed["flops"] / step - 0.093) < 0.001


def test_the_rings_two_slices_are_one_sequences(cell):
    ring = runner.make_ring(cell.config, cell.traffic, 3000000019)
    again = runner.make_ring(cell.config, cell.traffic, 3000000019)
    assert len(ring) == 2
    for ds, same in zip(ring, again):
        assert ds.features.shape == ds.labels.shape == (1, 8192)
        assert ds.features.dtype == ds.labels.dtype == np.int32
        assert ds.labels_mask is None
        np.testing.assert_array_equal(ds.features, same.features)
        np.testing.assert_array_equal(ds.labels, same.labels)
        # ids 0..8191 and 1..8192 of one draw of 8,193
        np.testing.assert_array_equal(ds.features[:, 1:], ds.labels[:, :-1])
        assert 0 <= ds.labels.min() and ds.labels.max() < 8192
    assert not np.array_equal(ring[0].features, ring[1].features)
    other = runner.make_ring(cell.config, cell.traffic, 7)
    assert not np.array_equal(ring[0].features, other[0].features)


def test_scope_readers_give_none_without_a_trace(cell):
    m = Measurement(config=cell.config, traffic=cell.traffic, chips=1,
                    peaks=manifest.load_peaks("TPU v5 lite"), window_s=1.0,
                    spans=[], counters={"steps_per_dispatch": 8})
    for metric in cell.per_layer:
        if metric["name"] in NEW_METRICS - {"lfm2_moe_load_imbalance"}:
            assert manifest.resolve(metric["reader"])(
                m, **metric["args"]) is None


def test_the_reference_names_its_kinds_and_reads_none_of_the_program():
    assert ref.KINDS == ("conv_dense", "attn_dense", "conv_experts",
                         "attn_experts")
    assert ref.kind_of({"W": 0}) is None and ref.kind_of({"g": 0}) is None
    assert ref.kind_of({"W_in": 0, "Wg": 0}) == "conv_dense"
    assert ref.kind_of({"Wq": 0, "Wr": 0}) == "attn_experts"
    source = open(ref.__file__).read()
    assert "deeplearning4j_tpu" not in source.split('"""', 2)[2]


def test_required_kernels_hold_each_counter_to_one_backend(monkeypatch):
    calls = {"a_total": {"pallas": 3.0}, "b_total": {"pallas": 1.0,
                                                     "xla": 1.0}}
    monkeypatch.setattr(runner, "_calls_by_backend", calls.__getitem__)
    assert runner._kernels_traced({"a_total": "pallas"}) == (
        {"a_total": {"pallas": 3.0}}, True)
    assert runner._kernels_traced({"a_total": "pallas",
                                   "b_total": "pallas"})[1] is False
    assert runner._kernels_traced({"a_total": "xla"})[1] is False


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
    assert lines[-1]["correct"] is True
    info = lines[1]
    counters = info["counters"]
    assert counters["window_compiles"] == 0
    assert counters["steps_per_dispatch"] == 8
    # three expert layers, four held experts each
    assert len(counters["moe_expert_rows"]) == 3 * 4
    assert len(counters["moe_pairs_per_layer"]) == 3
    calls = counters["kernel_calls_by_backend"]
    assert set(calls) == set(manifest.load_cell(ROOT, CELL).config[
        "required_kernels"])
    for metric, by_backend in calls.items():
        assert sum(by_backend.values()) > 0, metric
    # 128 columns are whole lane tiles: the interpreter ran the kernels
    assert set(calls["dl4j_short_conv_calls_total"]) == {"pallas"}
    checks = info["checks"]
    assert [b["kind"] for b in checks["blocks"]] == [
        "conv_dense", "attn_experts", "conv_experts", "conv_experts"]
    assert set(checks["block_fp8_reading"]) == {
        "conv_dense", "attn_experts", "conv_experts"}
    for b in checks["blocks"]:
        assert b["finite"] and b["rel_err"] <= checks["tol"]["block"]
    first = checks["first_dispatch"]
    assert first["steps"] == 8 and "fp8_would_fail" in checks
    assert checks["logits"]["rel_err"] <= checks["tol"]["logits"]
    assert checks["logits"]["rel_err_all_rows"] <= checks["tol"][
        "logits_all_rows"]
    assert checks["first_loss"]["rel_err"] <= checks["tol"]["loss"]
    assert first["grad_rel_err"] <= checks["tol"]["grad"]
    # the tied embedding holds both users' gradients
    assert first["grad_rel_err_tied"] <= checks["tol"]["grad"]
    assert first["leaves"]["layer_0.W"]["grad"] == first["grad_rel_err_tied"]
    assert not [k for k in first["leaves"] if k.startswith("layer_6")]
    assert first["update_rel_err"] <= checks["tol"]["update"]
    # the dense layer's matrices are no routed experts'
    assert first["leaves"]["layer_1.Wg"]["kind"] == "plain"
    assert first["leaves"]["layer_2.Wg"]["kind"] == "experts"
    assert first["leaves"]["layer_2.Wr"]["kind"] == "router"
    assert checks["loss"]["window_last"] < checks["loss"][
        "first_on_last_batch"]
