"""The trace reduction, on a trace recorded on a v5e (PR 22) and on
hand-made events.

The fixture is four chunks of 8 ``zoo.char_rnn`` steps (b=256, t=64)
through ``net.fit``; between the third and the fourth the host slept
20 ms inside a span named ``bench_idle_probe``. The run's spans are kept
beside the trace with their unix times, and are laid on the trace the way
the runner does it: ``Trace.place``."""

import json
import os

import numpy as np
import pytest

from benchmark import xplane
from benchmark.xplane import DevicePlane, Event, Trace

FIXTURES = os.path.join(os.path.dirname(__file__), "..", "fixtures")
FIXTURE = os.path.join(FIXTURES, "char_rnn_b256_t64.xplane.pb.gz")
KERNEL = 'custom_call_target="tpu_custom_call"'


def load_fixture() -> xplane.Trace:
    """The recorded trace with the recorded spans placed on it."""
    trace = xplane.load(FIXTURE)
    with open(os.path.join(FIXTURES, "char_rnn_b256_t64.spans.json")) as f:
        for thread, name, start_unix, duration_s in json.load(f)["spans"]:
            trace.place(thread, name, start_unix, duration_s)
    return trace


@pytest.fixture(scope="module")
def reduction():
    return xplane.reduce(load_fixture())


def test_spans_are_placed_by_their_unix_times():
    bare, trace = xplane.load(FIXTURE), load_fixture()
    assert bare.host == {} and bare.start_unix > 1.79e9
    (spans,) = trace.host.values()
    assert len(spans) == 47
    probe = next(e for e in spans if e.name == "bench_idle_probe")
    # seconds since the profile began, like the device events
    assert 0 < probe.start < 1 and probe.end - probe.start > 0.020
    ends = [ev.end for ev in trace.devices[0].modules]
    assert min(ends) < probe.start < max(ends)


def test_window_is_second_to_last_run_of_the_dominant_program(reduction):
    assert reduction.dominant_module == "jit_multi"
    assert reduction.executions == 3          # four ran, the first is dropped
    assert reduction.window_s == pytest.approx(0.091876, rel=1e-3)


def test_busy_is_the_union_of_leaf_ops(reduction):
    # by hand from the same file: the three whiles' leaves, the stacking
    # programs between them; the whiles themselves must not count
    assert reduction.busy_s == pytest.approx(0.056988, rel=1e-3)
    assert 0.37 < 1 - reduction.busy_s / reduction.window_s < 0.39
    assert not any(" while(" in e.name for d in reduction.devices
                   for e, _ in d.leaves)


def test_gaps_go_to_the_host_span_that_overlaps_them(reduction):
    gaps = dict(reduction.gaps_by_cause(20))
    assert gaps["host: bench_idle_probe"] == pytest.approx(0.020256, rel=1e-3)
    assert max(gaps, key=gaps.get) == "host: bench_idle_probe"
    assert gaps["in jit_multi"] < 0.001       # bubbles inside the program
    idle = reduction.window_s - reduction.busy_s
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)


def test_kernels_are_matched_by_pattern(reduction):
    # 3 chunks x 8 steps x (2 forward + 2 backward) Pallas calls
    assert sum(KERNEL in e.name for e, _ in reduction.devices[0].leaves) == 96
    fwd = reduction.op_seconds(r"^%jvp_[\w.]* = .*" + KERNEL)
    bwd = reduction.op_seconds(r"^%transpose_jvp_[\w.]* = .*" + KERNEL)
    assert fwd + bwd == pytest.approx(reduction.op_seconds(KERNEL))
    assert 0.55 < reduction.op_seconds(KERNEL) / reduction.busy_s < 0.65
    assert reduction.op_seconds("no op has this name") is None
    name, seconds = reduction.top_ops(1)[0]
    assert name.startswith("%transpose_jvp") and "tpu_custom_call" in name
    assert reduction.collective_exposed_s() is None


def test_merge_complement_overlap():
    m = xplane.merge([(5, 6), (0, 2), (1, 3), (3, 4), (9, 9)])
    np.testing.assert_array_equal(m, [[0, 4], [5, 6]])
    np.testing.assert_array_equal(xplane.complement(m, -1, 7),
                                  [[-1, 0], [4, 5], [6, 7]])
    assert xplane.overlap(m, 3.5, 5.5) == 1.0
    assert xplane.length(xplane.merge([])) == 0.0


def test_self_time_takes_children_out():
    events = [Event(0, 10, "%while.1 = () while()"), Event(1, 4, "a"),
              Event(5, 9, "b"), Event(11, 12, "c")]
    got = {e.name[:6]: (s, leaf) for e, s, leaf in xplane.self_times(events)}
    assert got == {"%while": (3, False), "a": (3, True), "b": (4, True),
                   "c": (1, True)}


def _plane(name, ops, async_ops=()):
    runs = [Event(10 * i, 10 * i + 10, "jit_step(123)") for i in range(3)]
    return DevicePlane(name, modules=runs, ops=list(ops),
                       async_ops=list(async_ops))


def test_collective_time_not_covered_by_compute_is_exposed():
    ar = "%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %x)"
    start = "%all-reduce-start.2 = f32[8]{0} all-reduce-start(f32[8]{0} %y)"
    ops = [Event(10, 14, "%fusion.1 = f32[8]{0} fusion()"),
           Event(14, 16, ar),                       # blocking: 2 exposed
           Event(20, 23, "%fusion.2 = f32[8]{0} fusion()"),
           Event(25, 26, "%fusion.3 = f32[8]{0} fusion()")]
    in_flight = [Event(21, 25, start)]              # 2 of its 4 under fusion.2
    red = xplane.reduce(Trace([_plane("/device:TPU:0", ops, in_flight),
                               _plane("/device:TPU:1", ops)], {}))
    assert red.executions == 2 and red.window_s == 20
    # chip 0: 2 + 2 exposed; chip 1: 2; averaged over the chips
    assert red.collective_exposed_s() == pytest.approx(3.0)
    assert red.busy_s == pytest.approx(10.0)


def test_too_short_a_trace_reduces_to_nothing():
    assert xplane.reduce(Trace([], {})) is None
    two = DevicePlane("/device:TPU:0",
                      modules=[Event(0, 1, "m(1)"), Event(2, 3, "m(1)")],
                      ops=[Event(0, 1, "x")])
    assert xplane.reduce(Trace([two], {})) is None
