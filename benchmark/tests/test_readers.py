"""Readers on a hand-made Measurement: each returns its number, and
nothing when there is nothing to read."""

from collections import namedtuple

import pytest

from benchmark.measure import Measurement
from benchmark.readers import counters, spans, trace

Span = namedtuple("Span", "name ts_us dur_us attrs")


def _m(**kw):
    base = dict(config={}, traffic={}, chips=1, peaks=None, window_s=2.0,
                spans=[], counters={})
    return Measurement(**{**base, **kw})


def test_span_readers():
    m = _m(spans=[Span("data_wait", 0, 100_000, None),
                  Span("data_wait", 5e5, 300_000, None),
                  Span("device_step", 1e5, 10, {"steps": 8}),
                  Span("device_step", 2e5, 10, None)])
    assert spans.share_of_window(m, "data_wait") == pytest.approx(20.0)
    assert spans.share_of_window(m, "score_sync") is None
    assert spans.mean_steps_attr(m, "device_step") == 4.5
    assert spans.mean_steps_attr(m, "host_dispatch") is None


def test_counter_readers():
    m = _m(counters={"window_compiles": 0, "on": 95.0, "off": 100.0})
    assert counters.counter(m, "window_compiles") == 0
    assert counters.counter(m, "absent") is None
    assert counters.slowdown_share(m, "on", "off") == pytest.approx(5.0)
    assert counters.slowdown_share(m, "on", "absent") is None


def test_trace_readers_without_a_trace_return_nothing():
    m = _m(counters={"steps_per_dispatch": 8})
    assert trace.busy_ms_per_step(m) is None
    assert trace.mfu_required(m) is None
    assert trace.op_share_of_busy(m, "x") is None
    assert trace.roofline(m, "x", "benchmark.flops:lstm_fwd_kernels_step") is None
    assert trace.collective_exposed_ms_per_step(m) is None


def test_trace_readers_on_the_recorded_trace():
    from benchmark import manifest, xplane
    from test_xplane import KERNEL, load_fixture

    peaks = manifest.load_peaks("TPU v5 lite")
    config = {"kwargs": {"vocab_size": 80, "hidden": 512, "n_layers": 2},
              "compute_itemsize": 2,
              "required_flops": "benchmark.flops:char_rnn_train_step"}
    m = _m(config=config, traffic={"batch": 256, "seq_len": 64}, peaks=peaks,
           counters={"steps_per_dispatch": 8},
           trace=xplane.reduce(load_fixture()))
    # 24 steps in the analysed window
    assert trace.busy_ms_per_step(m) == pytest.approx(56.988 / 24, rel=1e-3)
    flops = 324.0016e9
    assert trace.mfu_required(m) == pytest.approx(
        100 * flops * 24 / (0.056988 * 197e12), rel=1e-3)
    fwd = trace.roofline(m, r"^%jvp_[\w.]* = .*" + KERNEL,
                         "benchmark.flops:lstm_fwd_kernels_step")
    assert 85 < fwd < 100
    note = next(iter(m.notes.values()))
    assert note["bound"] == "hbm_bytes"
    assert 55 < trace.op_share_of_busy(m, KERNEL) < 65


def test_a_stall_moves_the_whole_window_rate_and_not_the_median_rate():
    from benchmark.runners.train_fit import _median_rate, _rate

    # 21 barriers, 8 steps of 0.1 s each between two; then one interval
    # stalls by 1.5 s
    clean = [(0.8 * i, 8 * i) for i in range(21)]
    stalled = [(t + (1.5 if i > 12 else 0.0), s)
               for i, (t, s) in enumerate(clean)]
    assert _rate(clean, 256) == pytest.approx(2560.0)
    assert _median_rate(clean, 256) == pytest.approx(2560.0)
    assert _rate(stalled, 256) == pytest.approx(2560.0 * 16 / 17.5)
    assert _median_rate(stalled, 256) == pytest.approx(2560.0)
    assert _median_rate(clean[:1], 256) is None
    m = _m(counters={"rate_whole_window": _rate(stalled, 256),
                     "rate_median_dispatch": _median_rate(stalled, 256)})
    assert counters.slowdown_share(m, "rate_whole_window",
                                   "rate_median_dispatch") == pytest.approx(
        100 * 1.5 / 17.5)
