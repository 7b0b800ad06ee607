"""The manifest loader: the repo's own manifest loads, a missing file is
refused by name, and a later PR's additions need no edit of a file that
is there."""

import json
import os
import shutil

import pytest

from benchmark import manifest
from conftest import ROOT

BENCH = os.path.join(ROOT, "benchmark")


def _names():
    return manifest.workload_names(ROOT)


def test_every_cell_of_the_repo_loads():
    for name in _names():
        cell = manifest.load_cell(ROOT, name)
        assert cell.chips in (1, 4)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer and all(":" in m["reader"]
                                      for m in cell.per_layer)
        assert os.path.isfile(os.path.join(
            BENCH, "runners", cell.traffic["kind"] + ".py"))


def test_unknown_workload_is_refused():
    with pytest.raises(manifest.ManifestError, match="no workload"):
        manifest.load_cell(ROOT, "no-such-cell")


@pytest.fixture
def copy(tmp_path):
    """A copy of BENCHMARK.json and benchmark/ that a test may add to."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    return tmp_path


def _load(copy, name):
    return manifest.load_cell(str(copy), name,
                              bench_dir=str(copy / "benchmark"))


@pytest.mark.parametrize("victim,what", [
    ("configs/char_rnn.json", "config 'char_rnn'"),
    ("traffic/fit-b256-t1024.json", "traffic 'fit-b256-t1024'"),
    ("layer_metrics/lstm_kernel_share.json",
     "per-layer metric 'lstm_kernel_share'"),
])
def test_a_missing_file_is_refused_by_name(copy, victim, what):
    os.remove(copy / "benchmark" / victim)
    with pytest.raises(manifest.ManifestError, match=what):
        _load(copy, "char_rnn-train-b256-t1024")


def test_a_traffic_kind_without_a_runner_is_refused(copy):
    path = copy / "benchmark" / "traffic" / "fit-b256.json"
    path.write_text(json.dumps({**json.loads(path.read_text()),
                                "kind": "no_such_runner"}))
    with pytest.raises(manifest.ManifestError, match="no_such_runner"):
        _load(copy, "resnet50-train-b256")


def test_a_later_pr_adds_files_and_entries_only(copy):
    """A third configuration, a new traffic mix, a fifth cell (the repo
    has fewer: one more than it has) and a new per-layer metric with a
    reader of its own: new files and new BENCHMARK.json entries."""
    before = {p: (copy / "benchmark" / p).read_bytes()
              for p in ("manifest.py", "run.py", "runners/train_fit.py",
                        "configs/char_rnn.json", "traffic/fit-b256.json")}
    bench = copy / "benchmark"
    config = json.loads((bench / "configs" / "char_rnn.json").read_text())
    config["name"] = "char_rnn_3x512"
    config["kwargs"]["n_layers"] = 3
    (bench / "configs" / "char_rnn_3x512.json").write_text(json.dumps(config))
    traffic = json.loads((bench / "traffic" / "fit-b256-t1024.json").read_text())
    traffic["batch"] = 32
    (bench / "traffic" / "fit-b32-t1024.json").write_text(json.dumps(traffic))
    (bench / "readers" / "later.py").write_text(
        "def score_sync_count(m):\n"
        "    return sum(1 for s in m.spans if s.name == 'score_sync')\n")
    (bench / "layer_metrics" / "fit_score_syncs.json").write_text(json.dumps(
        {"reader": "benchmark.readers.later:score_sync_count", "args": {}}))
    doc = json.loads((copy / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "char_rnn_3x512", "source": "https://arxiv.org/abs/1308.0850",
        "file": "benchmark/configs/char_rnn_3x512.json", "reduced": [],
        "why": "a third LSTM layer"})
    doc["workloads"].append({
        "name": "char_rnn_3x512-train-b32", "config": "char_rnn_3x512",
        "traffic": "fit-b32-t1024", "chips": 1, "why": "launch-bound batch"})
    doc["per_layer"].append({
        "name": "fit_score_syncs", "unit": "spans", "better": "lower",
        "source": "program_span", "layer": "fit_loop",
        "moves": "train_examples_per_s",
        "workloads": ["char_rnn_3x512-train-b32"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(doc))

    cell = _load(copy, "char_rnn_3x512-train-b32")
    assert cell.config["kwargs"]["n_layers"] == 3
    assert cell.traffic["batch"] == 32
    names = [m["name"] for m in cell.per_layer]
    assert "fit_score_syncs" in names and "collective_exposed_ms" not in names
    assert "fit_score_syncs" not in [
        m["name"] for m in _load(copy, "resnet50-train-b256").per_layer]
    for p, content in before.items():
        assert (bench / p).read_bytes() == content


def test_a_metric_that_moves_nothing_the_cell_reports_is_refused(copy):
    doc = json.loads((copy / "BENCHMARK.json").read_text())
    doc["per_layer"][0]["moves"] = "decode_tokens_per_s"
    (copy / "BENCHMARK.json").write_text(json.dumps(doc))
    with pytest.raises(manifest.ManifestError, match="decode_tokens_per_s"):
        _load(copy, "resnet50-train-b256")


def test_a_layer_name_the_driver_would_refuse_is_refused(copy):
    doc = json.loads((copy / "BENCHMARK.json").read_text())
    doc["per_layer"][0]["layer"] = "fit loop"
    (copy / "BENCHMARK.json").write_text(json.dumps(doc))
    with pytest.raises(manifest.ManifestError, match="fit loop"):
        _load(copy, "resnet50-train-b256")


def test_peaks_know_the_v5e_and_refuse_the_unknown():
    peaks = manifest.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(manifest.UnknownDevice, match="TPU v9"):
        manifest.load_peaks("TPU v9")
    with pytest.raises(manifest.UnknownDevice):
        manifest.load_peaks("cpu")
