"""readers/opindex.py on a hand-made reduction and a hand-made index:
the classes partition the busy time, and what the index lacks lands in
step_unplaced_share."""

import pytest

from benchmark import xplane
from benchmark.measure import Measurement
from benchmark.readers import opindex as readers
from benchmark.readers import trace

STEP = "jit(multi)/while/body/closed_call/"
INDEX = {
    "fusion.1": {"opcode": "fusion", "op_name": STEP + "jvp(conv1)/add",
                 "inner": [("convolution", STEP + "jvp(conv1)/conv_general_dilated"),
                           ("add", STEP + "jvp(bn1)/add")]},
    "convert_reduce_fusion.2": {
        "opcode": "fusion", "op_name": STEP + "transpose(jvp(bn1))/reduce_sum",
        "inner": [("convolution", STEP + "transpose(jvp(conv1))/conv_general_dilated"),
                  ("reduce", STEP + "transpose(jvp(bn1))/reduce_sum")]},
    "subtract_fusion.3": {"opcode": "fusion", "op_name": STEP + "update/sub",
                          "inner": [("subtract", STEP + "update/sub")]},
    "dynamic-slice.4": {"opcode": "dynamic-slice", "inner": [],
                        "op_name": "jit(multi)/while/body/dynamic_slice"},
    "reduce.5": {"opcode": "reduce", "op_name": STEP + "jvp(loss)/reduce_sum",
                 "inner": []},
    "all-reduce.6": {"opcode": "all-reduce", "inner": [],
                     "op_name": STEP + "transpose(jvp(conv1))/conv_general_dilated"},
    "all-reduce-start.7": {"opcode": "all-reduce-start", "inner": [],
                           "op_name": STEP + "jvp(bn1)/reduce_sum"},
    "all-reduce-done.7": {"opcode": "all-reduce-done", "inner": [],
                          "op_name": STEP + "jvp(bn1)/reduce_sum"},
    "copy.8": {"opcode": "copy", "op_name": "", "inner": []},
}


def _event(start, end, name):
    return xplane.Event(start, end, f"%{name} = f32[8]{{0}} something(%x)")


def _measurement():
    leaves = [_event(0.0, 2.0, "fusion.1"),                 # forward, conv
              _event(2.0, 5.0, "convert_reduce_fusion.2"),  # backward, conv
              _event(5.0, 6.0, "subtract_fusion.3"),        # update
              _event(6.0, 6.5, "dynamic-slice.4"),          # input
              _event(6.5, 6.75, "reduce.5"),                # loss
              _event(6.75, 7.0, "fusion.99"),               # not in the index
              _event(7.0, 7.25, "copy.8"),                  # no name anywhere
              _event(7.25, 7.5, "all-reduce.6"),            # a gradient
              _event(7.5, 7.55, "all-reduce-start.7"),      # a statistic,
              _event(7.7, 7.75, "all-reduce-done.7"),       # async
              _event(8.5, 9.5, "concatenate.1")]            # another program
    modules = [xplane.Event(0.0, 8.0, "jit_multi(123)"),
               xplane.Event(8.4, 9.6, "jit_concatenate(7)")]
    device = xplane.DeviceReduction(
        name="/device:TPU:0", window=(0.0, 10.0), executions=1,
        leaves=[(e, e.end - e.start) for e in leaves],
        busy=xplane.merge([(e.start, e.end) for e in leaves]),
        modules=xplane.merge([(e.start, e.end) for e in modules]),
        module_events=modules,
        async_events=[_event(7.5, 7.75, "all-reduce-start.7")])
    return Measurement(
        config={}, traffic={}, chips=1, peaks=None, window_s=10.0, spans=[],
        counters={"steps_per_dispatch": 2},
        trace=xplane.Reduction("jit_multi", [device], {}))


@pytest.fixture
def program_index(monkeypatch):
    from deeplearning4j_tpu.observability import opindex
    monkeypatch.setattr(opindex, "lookup", lambda module: {
        "jit_multi": INDEX}.get(module))
    monkeypatch.setattr(opindex, "_scopes", opindex._scopes | {"conv1", "bn1"})
    return opindex


def test_the_classes_partition_the_busy_time(program_index):
    m = _measurement()
    ms = lambda *phases: readers.phase_ms_per_step(m, list(phases))
    assert ms("forward", "loss") == pytest.approx(1e3 * (2.0 + 0.25 + 0.1) / 2)
    assert ms("backward") == pytest.approx(1e3 * (3.0 + 0.25) / 2)
    assert ms("update") == pytest.approx(500.0)
    assert readers.fit_input_ms_per_step(m) == pytest.approx(1e3 * 1.5 / 2)
    unplaced_ms = 1e3 * 0.5 / 2
    assert readers.unplaced_share(m) == pytest.approx(
        100 * 0.5 / m.trace.busy_s)
    assert (ms("forward", "loss") + ms("backward") + ms("update")
            + readers.fit_input_ms_per_step(m) + unplaced_ms
            == pytest.approx(trace.busy_ms_per_step(m)))
    assert readers.convolution_share(m) == pytest.approx(
        100 * 5.0 / m.trace.busy_s)


def test_collectives_by_purpose(program_index):
    m = _measurement()
    assert readers.collective_ms_per_step(m, "gradient") == pytest.approx(125.0)
    # start op, done op and the async pair are one interval, start..done
    assert readers.collective_ms_per_step(m, "statistic") == pytest.approx(125.0)
    assert m.notes["collectives_per_step"] == {"gradient": 0.5,
                                               "statistic": 0.5}
    top = m.notes["device_ms_by_layer"][0]
    assert top[:2] == ["conv1", "backward"] and top[2] == pytest.approx(1625.0)
    assert top[3] == "conv_general_dilated"
    assert m.notes["device_ms_by_primitive"][0] == [
        "backward", "conv_general_dilated", pytest.approx(1625.0)]
    assert m.notes["opindex_build_s"] >= 0


def test_without_an_index_every_reader_returns_nothing(monkeypatch):
    from deeplearning4j_tpu.observability import opindex
    monkeypatch.setattr(opindex, "lookup", lambda module: None)
    m = _measurement()
    assert readers.phase_ms_per_step(m, ["forward"]) is None
    assert readers.fit_input_ms_per_step(m) is None
    assert readers.unplaced_share(m) is None
    assert readers.convolution_share(m) is None
    assert readers.collective_ms_per_step(m, "gradient") is None
    assert not m.notes
    m.trace = None
    assert readers.phase_ms_per_step(m, ["forward"]) is None


def test_a_program_without_the_module_returns_nothing(monkeypatch):
    """The parent commit has no observability/opindex.py."""
    import sys
    monkeypatch.setitem(sys.modules,
                        "deeplearning4j_tpu.observability.opindex", None)
    m = _measurement()
    assert readers.unplaced_share(m) is None and not m.notes


def test_the_metric_files_load():
    import os

    from benchmark import manifest
    root = os.path.dirname(manifest.BENCH_DIR)
    names = {"resnet50-train-dp4": 8, "resnet50-train-b256": 6,
             "char_rnn-train-b256-t1024": 5}
    for cell, expected in names.items():
        metrics = [m for m in manifest.load_cell(root, cell).per_layer
                   if m["reader"].startswith("benchmark.readers.opindex:")]
        assert len(metrics) == expected
        for m in metrics:
            assert callable(manifest.resolve(m["reader"]))
