"""The set-up readers: on a hand-made stage account each returns its
number with the owners ``none`` and ``opindex_lookup`` left out, nothing
when the program keeps no account, and in a rehearsal the four stages
sum to no more than the run's ``setup_s``."""

import json
import sys

import pytest
from conftest import ROOT
from test_cli import CELL, _run
from test_readers import Span, _m

from benchmark import manifest
from benchmark.readers import setup

ACCOUNT = {
    "seconds": {
        "trace": {"net_init": 0.25, "device_step": 1.5, "none": 40.0},
        "lower": {"device_step": 2.0, "flops_derive": 0.5,
                  "opindex_lookup": 20.0},
        "cache_load": {"net_init": 1.0, "forward": 0.75, "device_step": 3.0,
                       "none": 9.0, "opindex_lookup": 4.0},
    },
    "programs": {
        "trace": {"net_init": 80, "device_step": 3, "none": 12},
        "lower": {"net_init": 80, "forward": 1, "device_step": 3,
                  "flops_derive": 1, "none": 12, "opindex_lookup": 1},
    },
    "spans": {"net_init": {"seconds": 2.5, "count": 1},
              "flops_derive": {"seconds": 0.625, "count": 1},
              "opindex_lookup": {"seconds": 27.0, "count": 1}},
}

BY_PROGRAM = [
    {"program": "reference", "owner": "none", "seconds": 49.0},
    {"program": "multi", "owner": "opindex_lookup", "seconds": 24.0},
    {"program": "multi", "owner": "device_step", "seconds": 6.0,
     "trace": 1.5, "lower": 1.5, "cache_load": 3.0},
    {"program": "step_fn", "owner": "flops_derive", "seconds": 0.5},
]

SEVEN = {
    "setup_trace_s": 1.75,
    "setup_lower_s": 2.5,
    "setup_cache_load_s": 4.75,
    "setup_backend_compile_s": 0.0,     # a warm run: the stage was not seen
    "setup_programs": 85,
    "setup_init_s": 2.5,
    "setup_flops_derive_s": 0.625,
}


@pytest.fixture
def program(monkeypatch):
    """A program whose account is ACCOUNT; ``taken`` counts the reads."""
    from deeplearning4j_tpu.observability import metrics as obs
    taken = []
    monkeypatch.setattr(obs, "stage_snapshot",
                        lambda: taken.append(1) or ACCOUNT)
    monkeypatch.setattr(obs, "largest_programs", lambda n: BY_PROGRAM[:n])
    return taken


def _metric(name):
    cell = manifest.load_cell(ROOT, CELL)
    return next(m for m in cell.per_layer if m["name"] == name)


@pytest.mark.parametrize("name", sorted(SEVEN))
def test_each_reader_on_a_hand_made_account(name, program, monkeypatch):
    metric = _metric(name)
    assert (metric["layer"], metric["moves"], metric["better"]) == (
        "setup", "setup_s", "lower")
    assert "workloads" not in metric        # every cell reports setup_s
    reader = manifest.resolve(metric["reader"])
    assert reader(_m(), **metric["args"]) == pytest.approx(SEVEN[name])
    # a program that keeps no account: nothing to read, and no error
    from deeplearning4j_tpu.observability import metrics as obs
    monkeypatch.delattr(obs, "stage_snapshot")
    assert reader(_m(), **metric["args"]) is None


def test_a_span_the_run_never_opened_reads_zero(program):
    assert setup.span_seconds(_m(), "forward") == 0.0


def test_the_account_is_taken_from_the_program_once(program):
    m = _m(spans=[Span("device_step", 0, 9e6, {"steps": 8}),
                  Span("xla_compile", 1e6, 7e6, {"program": "multi"})])
    assert setup.stage_seconds(m, "trace") == pytest.approx(1.75)
    assert setup.stage_programs(m, "lower") == 85
    assert len(program) == 1
    # a recompile in the window is named, where window_compiles counts it
    assert m.notes["setup_account"]["made_in_window"] == [
        {"span": "xla_compile", "program": "multi", "parent": None,
         "seconds": 7.0}]
    digest = m.notes["setup_account"]
    # what the seven leave out is printed, not lost
    assert digest["left_out"]["opindex_lookup"]["seconds"] == {
        "lower": 20.0, "cache_load": 4.0}
    assert digest["left_out"]["none"]["programs"] == {"trace": 12,
                                                      "lower": 12}
    assert set(digest["seconds_by_owner"]) == {
        "net_init", "forward", "device_step", "flops_derive"}
    assert [p["program"] for p in digest["largest_programs"]] == [
        "multi", "step_fn"]
    json.dumps(m.notes)


def test_a_program_without_the_account_reads_nothing(monkeypatch):
    from deeplearning4j_tpu.observability import metrics as obs
    monkeypatch.delattr(obs, "stage_snapshot")
    m = _m()
    assert setup.stage_seconds(m, "trace") is None
    assert setup.span_seconds(m, "net_init") is None
    assert m.notes == {}
    monkeypatch.setitem(sys.modules,
                        "deeplearning4j_tpu.observability.metrics", None)
    assert setup.stage_programs(_m(), "lower") is None


def test_rehearsal_prints_all_seven_and_they_fit_inside_setup_s():
    p = _run(ROOT, "--workload", CELL, "--seed", "2147483659",
             "--seconds", "2", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()]
    readings = next(ln["readings"] for ln in lines
                    if ln.get("rehearsal") and "readings" in ln)
    setup_s = next(ln["setup_s"] for ln in lines if "setup_phases_s" in ln)
    counters = next(ln["counters"] for ln in lines if "counters" in ln)
    for name in SEVEN:
        assert f"rehearsal:{name}" in readings, name
    stages = [readings[f"rehearsal:setup_{s}_s"] for s in
              ("trace", "lower", "cache_load", "backend_compile")]
    assert all(s >= 0 for s in stages) and sum(stages) > 0
    assert sum(stages) <= setup_s
    # the two compile stages are the runner's counter, less what set-up
    # makes outside every span
    made = stages[2] + stages[3]
    assert made <= counters["setup_compile_s"] + 1e-6
    assert made >= 0.8 * counters["setup_compile_s"]
    assert readings["rehearsal:setup_programs"] >= 20
    assert 0 < readings["rehearsal:setup_init_s"] < setup_s
    assert 0 < readings["rehearsal:setup_flops_derive_s"] < setup_s
    notes = next(ln["notes"] for ln in lines if "notes" in ln)
    largest = notes["setup_account"]["largest_programs"]
    assert {"multi", "apply_fn"} <= {p["program"] for p in largest}
