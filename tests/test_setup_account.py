"""The stage account (observability/metrics.py) and the spans with a
cause (observability/trace.py): every second jax spends making a program
is booked once, to a stage (trace, lower, cache_load, compile), to the
span that caused it and to the program; a steady dispatch pays nothing."""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import Dense, Output
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import Sgd
from deeplearning4j_tpu.observability import goodput
from deeplearning4j_tpu.observability import metrics as obs
from deeplearning4j_tpu.observability.goodput import RunReport
from deeplearning4j_tpu.observability.trace import (
    Tracer, current_span, set_tracer)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
XLA_SPANS = ("xla_trace", "xla_lower", "xla_cache_load", "xla_compile")


@pytest.fixture()
def tracer():
    tr = Tracer()
    previous = set_tracer(tr)
    try:
        yield tr
    finally:
        set_tracer(previous)


def _conf(seed=3, n_in=6, hidden=5):
    return (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1))
            .list()
            .layer(Dense(n_in=n_in, n_out=hidden, activation="relu"))
            .layer(Output(n_out=3, loss="mcxent", activation="softmax"))
            .build())


def _batch(n, n_in=6, seed=0):
    rng = np.random.default_rng(seed)
    return DataSet(rng.normal(0, 1, (n, n_in)).astype(np.float32),
                   np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)])


def _total(delta, name="seconds", owners=None):
    """``{stage: value}`` of a delta, over ``owners`` (all when None)."""
    return {stage: sum(v for o, v in by_owner.items()
                       if owners is None or o in owners)
            for stage, by_owner in delta[name].items()}


# ------------------------------------------------------------- the account
def test_inner_jits_are_a_union_not_a_sum(tracer):
    """An inner ``jit`` of a traced function reports a trace of its own:
    the outer interval holds it, so tracing is booked once."""
    calls = 12

    @jax.jit
    def inner_of_union(x):
        return jnp.tanh(x) * 2.0

    @jax.jit
    def outer_of_union(x):
        for _ in range(calls):
            x = inner_of_union(x) + 1.0
        return x

    x = jnp.ones((7,))     # made before the baseline: its own programs
    tracer.clear()
    heard = []

    def listen(event, value, **kw):
        if event.endswith("jaxpr_trace_duration"):
            heard.append(kw.get("fun_name"))

    base = obs.stage_snapshot()
    jax.monitoring.register_scalar_listener(listen)
    try:
        t0 = time.time()
        outer_of_union(x).block_until_ready()
        wall = time.time() - t0
    finally:
        jax.monitoring.unregister_scalar_listener(listen)
    made = obs.stage_delta(base)
    # jax sent a trace event for the outer program and for an inner one
    assert "outer_of_union" in heard and "inner_of_union" in heard
    assert _total(made, "programs") == {"trace": 1, "lower": 1, "compile": 1}
    seconds = _total(made)
    assert 0 < seconds["trace"] <= wall
    assert sum(seconds.values()) <= wall
    spans = [s for s in tracer.spans() if s.name in XLA_SPANS]
    assert sorted(s.name for s in spans) == [
        "xla_compile", "xla_lower", "xla_trace"]
    assert {s.attrs["program"] for s in spans} == {"outer_of_union"}
    mine = [p for p in obs.largest_programs(10_000)
            if p["program"] == "outer_of_union"]
    assert len(mine) == 1 and mine[0]["owner"] == "none"
    assert mine[0]["trace"] == pytest.approx(seconds["trace"])
    assert mine[0]["lower"] > 0 and mine[0]["compile"] > 0
    assert not any(p["program"] == "inner_of_union"
                   for p in obs.largest_programs(10_000))


def test_a_compile_inside_a_trace_is_booked_once(tracer):
    """An eager op on concrete values while a function is traced compiles
    there and then: its seconds are the compile's, not the tracing's too."""
    @jax.jit
    def traced_with_eager(x):
        with jax.ensure_compile_time_eval():
            k = jnp.cumsum(jnp.arange(11.0))[-1]    # compiles while tracing
        return x * k

    x = jnp.ones((3,))
    tracer.clear()
    base = obs.stage_snapshot()
    t0 = time.time()
    traced_with_eager(x).block_until_ready()
    wall = time.time() - t0
    made = obs.stage_delta(base)
    assert _total(made, "programs")["compile"] >= 2     # the eager op's too
    assert sum(_total(made).values()) <= wall
    outer = [s for s in tracer.spans() if s.name == "xla_trace"]
    assert [s.attrs["program"] for s in outer] == ["traced_with_eager"]
    inside = [s for s in tracer.spans() if s.name == "xla_compile"
              and s.ts_us < outer[0].ts_us + outer[0].dur_us]
    assert inside, "no compile was recorded inside the outer trace"
    booked = _total(made)["trace"]
    assert booked <= outer[0].dur_us * 1e-6 - sum(
        s.dur_us for s in inside) * 1e-6 + 1e-3


def test_stages_go_to_the_span_that_caused_them(tracer):
    base = obs.stage_snapshot()
    # a width no other test's net has: a worker that has built a 6 -> 5
    # -> 3 net before (tests/test_opindex.py does) holds its init's
    # programs, and net_init would compile nothing
    net = MultiLayerNetwork(_conf(hidden=11)).init()
    net.output(_batch(4).features)
    net.fit(_batch(16).features, _batch(16).labels, epochs=1, batch_size=4,
            multi_step=2)
    before_bare_jit = obs.stage_delta(base)
    jax.jit(lambda a: a * 5.0 - 1.0)(jnp.ones((9,))).block_until_ready()
    made = obs.stage_delta(base)
    # everything the net made has an owner (init's key included); what is
    # under "none" is the user's own jit
    assert not any(by_owner.get("none")
                   for by_owner in before_bare_jit["programs"].values())
    for owner in ("net_init", "forward", "device_step", "none"):
        assert made["seconds"]["compile"].get(owner, 0) > 0, owner
        assert made["programs"]["lower"].get(owner, 0) >= 1, owner
    # flops_derive reads the count from the scan the chunked path has
    # dispatched: it lowers and compiles nothing, and never builds the
    # single step
    assert not made["programs"]["lower"].get("flops_derive")
    assert not made["programs"]["compile"].get("flops_derive")
    assert net._train_step is None
    assert made["programs"]["lower"]["forward"] == 1
    for name in ("net_init", "forward", "flops_derive"):
        assert made["spans"][name]["count"] == 1
        under = sum(by_owner.get(name, 0)
                    for by_owner in made["seconds"].values())
        assert under <= made["spans"][name]["seconds"]
        # flops_derive holds at most jax's trace-cache lookup of the scan
        assert under > 0 or name == "flops_derive"
    by_name = {}
    for s in tracer.spans():
        by_name.setdefault(s.name, []).append(s)
    # host_dispatch stacks the chunk and splits the rng: programs too
    assert {s.parent for s in by_name["xla_compile"]} == {
        "net_init", "forward", "host_dispatch", "device_step", None}
    step = [s for s in by_name["xla_compile"] if s.parent == "device_step"]
    assert "multi" in [s.attrs["program"] for s in step]
    derived = {(s.name, s.attrs["program"]) for s in tracer.spans()
               if s.name in XLA_SPANS and s.parent == "flops_derive"}
    assert derived <= {("xla_trace", "multi")}, derived
    assert by_name["net_init"][0].parent is None
    # a stage span lies inside the span that caused it, on the same clock
    init = by_name["net_init"][0]
    for s in tracer.spans():
        if s.name in XLA_SPANS and s.parent == "net_init":
            assert init.ts_us - 2e3 <= s.ts_us
            assert s.ts_us + s.dur_us <= init.ts_us + init.dur_us + 2e3
    top = obs.largest_programs(10_000)
    assert any(p["program"] == "multi" and p["owner"] == "device_step"
               and p["trace"] > 0 and p["lower"] > 0 and p["compile"] > 0
               for p in top)
    assert top == sorted(top, key=lambda p: -p["seconds"])
    assert obs.largest_programs(3) == top[:3]


def test_a_stage_on_another_thread_takes_that_threads_span(tracer):
    seen = {}
    x = jnp.ones((13,))
    tracer.clear()

    def worker():
        with tracer.span("worker_span"):
            seen["inside"] = current_span()
            jax.jit(lambda a: a / 7.0 + 2.0)(x)
        seen["after"] = current_span()

    base = obs.stage_snapshot()
    with tracer.span("main_span"):
        t = threading.Thread(target=worker, name="stage-worker")
        t.start()
        t.join(timeout=120)
        assert not t.is_alive()
        assert current_span() == "main_span"
    made = obs.stage_delta(base)
    assert seen == {"inside": "worker_span", "after": None}
    assert made["programs"]["compile"].get("worker_span") == 1
    assert "main_span" not in made["programs"]["compile"]
    compiled = [s for s in tracer.spans() if s.name == "xla_compile"]
    assert [(s.parent, s.thread) for s in compiled] == [
        ("worker_span", "stage-worker")]


_CACHE_SCRIPT = """
    import json, sys
    import jax.numpy as jnp
    sys.path.insert(0, {repo!r})
    from deeplearning4j_tpu.compilecache import ensure_configured
    from deeplearning4j_tpu.observability import metrics as obs
    from tests.test_setup_account import _batch, _conf
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    assert ensure_configured()
    base = obs.stage_snapshot()
    net = MultiLayerNetwork(_conf()).init()
    net.output(_batch(4).features)
    net.fit_batch(_batch(8))
    made = obs.stage_delta(base)
    print(json.dumps({{"made": made, "compile": obs.compile_delta(
        {{"count": 0, "seconds": 0.0}})}}))
"""


def test_a_second_build_loads_from_the_persistent_cache(tmp_path):
    """The pattern of test_coldstart.py: the same programs built by two
    processes over one cache directory."""
    script = tmp_path / "build.py"
    script.write_text(textwrap.dedent(_CACHE_SCRIPT.format(repo=_REPO)))

    def build():
        out = subprocess.run(
            [sys.executable, str(script)], capture_output=True, text=True,
            timeout=600, cwd=_REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold, warm = build(), build()
    assert _total(cold["made"])["compile"] > 0
    assert "cache_load" not in cold["made"]["seconds"]
    assert "compile" not in warm["made"]["seconds"]
    loaded = _total(warm["made"], "programs")["cache_load"]
    assert loaded == _total(cold["made"], "programs")["compile"]
    assert loaded == warm["compile"]["cache_hits"]
    # cache_load is the whole backend_compile_duration of a program that hit
    assert _total(warm["made"])["cache_load"] == pytest.approx(
        warm["compile"]["seconds"], abs=1e-4)
    for owner in ("net_init", "forward", "device_step"):
        assert warm["made"]["seconds"]["cache_load"][owner] > 0


# ------------------------------------------------------ the steady hot path
def _warm_net(steps=3):
    net = MultiLayerNetwork(_conf(seed=11)).init()
    net.set_listeners()
    for i in range(steps):
        net.fit_batch(_batch(8, seed=i))
    return net


def test_a_steady_dispatch_adds_no_stage_event_and_no_span():
    net = _warm_net()
    events = []

    def listen(event, *a, **kw):
        events.append(event)

    tr = Tracer()
    previous = set_tracer(tr)
    jax.monitoring.register_scalar_listener(listen)
    jax.monitoring.register_event_duration_secs_listener(listen)
    base = obs.stage_snapshot()
    try:
        x = np.concatenate([_batch(8, seed=s).features for s in range(10)])
        y = np.concatenate([_batch(8, seed=s).labels for s in range(10)])
        net.fit(x, y, epochs=2, batch_size=8, async_prefetch=False)
    finally:
        jax.monitoring.unregister_scalar_listener(listen)
        jax.monitoring.unregister_event_duration_listener(listen)
        set_tracer(previous)
    assert events == []
    made = obs.stage_delta(base)
    assert sum(_total(made, "programs").values()) == 0
    assert sum(_total(made).values()) == 0
    counts = {}
    for s in tr.spans():
        counts[s.name] = counts.get(s.name, 0) + 1
    counts.pop("run_start", None)
    # the four spans a dispatch has, and the end-of-epoch data_wait
    assert counts == {"data_wait": 22, "host_dispatch": 20,
                      "device_step": 20}
    assert all(s.parent is None for s in tr.spans())
    assert not any(net.last_run_report.xla_stage_seconds.values())


def test_a_recompile_in_steady_state_names_the_step_and_its_cause(tracer):
    net = _warm_net()
    tracer.clear()
    net.fit_batch(_batch(5))            # a new batch shape
    made = [s for s in tracer.spans()
            if s.name in ("xla_compile", "xla_cache_load")]
    assert len(made) == 1
    assert made[0].attrs == {"program": "step_fn"}
    assert made[0].parent == "device_step"
    step = [s for s in tracer.spans() if s.name == "device_step"][0]
    assert step.ts_us <= made[0].ts_us + 2e3
    assert made[0].dur_us <= step.dur_us + 2e3
    traced = [s for s in tracer.spans() if s.name == "xla_trace"]
    assert ("step_fn", "device_step") in {
        (s.attrs["program"], s.parent) for s in traced}


# ------------------------------------------------- the books that read them
def test_nested_stage_spans_are_reported_and_not_attributed(tracer):
    prev = goodput._ENABLED
    goodput.set_enabled(True)
    try:
        ledger = goodput.start_run("fit")
        t = time.perf_counter()
        tracer.record("xla_compile", t + 0.01, t + 0.09,
                      {"program": "step_fn"}, parent="device_step")
        tracer.record("device_step", t, t + 0.1)
        tracer.record("xla_lower", t + 0.11, t + 0.12,
                      {"program": "step_fn"}, parent="flops_derive")
        tracer.record("flops_derive", t + 0.1, t + 0.13)
        report = goodput.end_run(ledger)
    finally:
        goodput._ENABLED = prev
    assert report.attributed_s == pytest.approx(0.13)
    assert report.phases["xla_compile"]["seconds"] == pytest.approx(0.08)
    assert report.phases["xla_lower"]["count"] == 1
    assert not goodput.FIT_EXCLUSIVE & set(XLA_SPANS)
    assert not goodput.SUPERVISOR_EXCLUSIVE & set(XLA_SPANS)


def test_run_report_carries_the_runs_own_stage_seconds(tracer):
    jax.jit(lambda a: a * 11.0)(jnp.ones((17,)))    # before the run
    net = MultiLayerNetwork(_conf(seed=21)).init()
    base = obs.stage_snapshot()
    net.fit(_batch(16).features, _batch(16).labels, epochs=1, batch_size=8)
    made = _total(obs.stage_delta(base))
    report = net.last_run_report
    assert set(report.xla_stage_seconds) == {"trace", "lower", "compile"}
    for stage, seconds in report.xla_stage_seconds.items():
        assert seconds == pytest.approx(made[stage], abs=1e-5)
    assert report.xla_stage_seconds["compile"] == pytest.approx(
        report.compile_seconds, abs=1e-5)
    again = RunReport.from_json(report.to_json())
    assert again.xla_stage_seconds == report.xla_stage_seconds
    assert RunReport(kind="fit").xla_stage_seconds == {}


def test_the_registry_shows_both_families_by_stage_and_owner(tracer):
    reg = obs.MetricsRegistry()
    previous = obs.set_registry(reg)
    try:
        obs.install_runtime_metrics(reg)
        x = jnp.ones((23,))
        with tracer.span("scrape_owner"):
            jax.jit(lambda a: a - 19.0)(x)
        text = reg.render_prometheus()
    finally:
        obs.set_registry(previous)
    labels = '{owner="scrape_owner",stage="%s"}'
    for stage in ("trace", "lower", "compile"):
        line = [ln for ln in text.splitlines() if ln.startswith(
            "dl4j_xla_stage_programs_total" + labels % stage)]
        assert line and float(line[0].split()[-1]) == 1
        line = [ln for ln in text.splitlines() if ln.startswith(
            "dl4j_xla_stage_seconds_total" + labels % stage)]
        assert line and float(line[0].split()[-1]) > 0
    assert "# TYPE dl4j_xla_stage_seconds_total counter" in text


# --------------------------------------------------------------- the spans
def test_a_span_knows_the_span_that_caused_it(tracer, tmp_path):
    with tracer.span("outer", job="a"):
        with tracer.span("middle"):
            with tracer.program_span("inner") as sp:
                sp.set(parse_s=0.25)
                assert current_span() == "inner"
            assert current_span() == "middle"
        tracer.record("told", 0.0, 0.001, parent="outer")
    assert current_span() is None
    tracer.record("untold", 0.0, 0.001)
    spans = {s.name: s for s in tracer.spans()}
    assert {n: s.parent for n, s in spans.items()} == {
        "outer": None, "middle": "outer", "inner": "middle",
        "told": "outer", "untold": None}
    assert spans["inner"].attrs == {"parse_s": 0.25}
    assert spans["inner"].to_dict()["parent"] == "middle"
    assert "parent" not in spans["outer"].to_dict()
    events = {e["name"]: e for e in tracer.to_chrome_trace()["traceEvents"]
              if e["ph"] == "X"}
    assert events["inner"]["args"] == {"parse_s": 0.25, "parent": "middle"}
    assert events["outer"]["args"] == {"job": "a"}
    assert "args" not in events["untold"]
    lines = [json.loads(ln) for ln in open(
        tracer.export_jsonl(str(tmp_path / "spans.jsonl")))]
    assert {d["name"]: d.get("parent") for d in lines}["middle"] == "outer"


def test_a_new_input_shape_is_a_first_forward_too(tracer):
    net = MultiLayerNetwork(_conf(seed=31)).init()
    net.output(_batch(4).features)
    tracer.clear()
    base = obs.stage_snapshot()
    net.output(_batch(4).features)          # steady: no span, no program
    assert tracer.spans() == []
    net.output(_batch(9).features)          # retraces the same entry
    made = obs.stage_delta(base)
    assert {o: n for o, n in made["programs"]["compile"].items() if n} == {
        "forward": 1}
    assert made["spans"]["forward"]["count"] == 1
    assert [s.name for s in tracer.spans() if s.parent is None] == ["forward"]


def test_a_graphs_eager_walk_is_owned_by_forward(tracer):
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    conf = (NeuralNetConfiguration.builder().seed(5).updater(Sgd(0.1))
            .graph_builder().add_inputs("in")
            .add_layer("h", Dense(n_in=6, n_out=7, activation="tanh"), "in")
            .add_layer("out", Output(n_in=7, n_out=3, loss="mcxent",
                                     activation="softmax"), "h")
            .set_outputs("out").build())
    base = obs.stage_snapshot()
    net = ComputationGraph(conf).init()
    acts = net.feed_forward(_batch(4).features)
    net.output(_batch(4).features)
    made = obs.stage_delta(base)
    assert set(acts) >= {"h", "out"}
    for stage, by_owner in made["programs"].items():
        owners = {o for o, n in by_owner.items() if n}
        assert owners <= {"net_init", "forward"}, (stage, by_owner)
    assert made["programs"]["compile"]["forward"] >= 2
    assert made["spans"]["forward"]["count"] == 1   # output's first call


def test_a_generators_span_closed_under_a_consumers_leaves_no_stale_name(
        tracer):
    """A generator yields inside its span and finishes while the
    consumer's span is open: the spans close out of order, and each is
    taken off the stack by itself."""
    def producer():
        with tracer.span("produce"):
            yield current_span()
            yield current_span()

    gen = producer()
    assert next(gen) == "produce"
    with tracer.span("consume"):
        assert current_span() == "consume"
        assert next(gen) == "consume"   # innermost on this thread
        with pytest.raises(StopIteration):
            next(gen)                   # "produce" closes under "consume"
        assert current_span() == "consume"
        with tracer.span("later"):
            pass
    assert current_span() is None
    with tracer.span("after"):
        pass
    parents = {s.name: s.parent for s in tracer.spans()}
    assert parents == {"produce": None, "consume": "produce",
                       "later": "consume", "after": None}


def test_a_span_closed_on_another_thread_leaves_its_own_threads_stack(
        tracer):
    ctx = tracer.span("handed_over")
    ctx.__enter__()
    assert current_span() == "handed_over"
    seen = {}

    def closer():
        with tracer.span("closers_own"):
            ctx.__exit__(None, None, None)
            seen["inside"] = current_span()
        seen["after"] = current_span()

    t = threading.Thread(target=closer)
    t.start()
    t.join(timeout=60)
    assert seen == {"inside": "closers_own", "after": None}
    assert current_span() is None       # not left open here for good


def test_a_failing_span_restores_its_parent(tracer):
    with tracer.span("survivor"):
        with pytest.raises(ValueError):
            with tracer.span("doomed"):
                raise ValueError("boom")
        assert current_span() == "survivor"
    assert current_span() is None


def test_program_span_totals_reach_the_account(tracer):
    base = obs.stage_snapshot()
    for _ in range(3):
        with tracer.program_span("opindex_lookup", module="jit_x"):
            time.sleep(0.002)
    disabled = Tracer(enabled=False)
    with disabled.program_span("opindex_lookup") as sp:
        sp.set(parse_s=1.0)             # a no-op, not an error
    made = obs.stage_delta(base)["spans"]["opindex_lookup"]
    assert made["count"] == 3 and 0.006 <= made["seconds"] < 1.0
    recorded = [s for s in tracer.spans() if s.name == "opindex_lookup"]
    assert sum(s.dur_us for s in recorded) * 1e-6 == pytest.approx(
        made["seconds"], abs=1e-3)


def test_record_unix_places_an_interval_on_the_tracers_clock(tracer):
    start = time.time()
    with tracer.span("around"):
        time.sleep(0.01)
    tracer.record_unix("placed", start, start + 0.005, {"program": "p"},
                       parent="around")
    around, placed = tracer.spans()
    assert placed.parent == "around" and placed.attrs == {"program": "p"}
    assert placed.dur_us == pytest.approx(5e3, abs=1.0)
    assert abs(placed.ts_us - around.ts_us) < 5e3   # the two clocks agree


@pytest.mark.parametrize("names, lanes", [
    (("a", "a"), 1),            # one thread
    (("a", "b"), 2),            # an ident handed on to the next thread
    (("a", "b", "a"), 2),
])
def test_chrome_lanes_are_keyed_by_ident_and_name(names, lanes):
    tr = Tracer()
    for i, name in enumerate(names):
        tr.record("work", i * 0.01, i * 0.01 + 0.005, tid=4242, thread=name)
    tr.record("other", 0.0, 0.001, tid=7, thread="main")
    doc = tr.to_chrome_trace()
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert sorted(e["args"]["name"] for e in meta) == sorted(
        set(names) | {"main"})
    assert len({e["tid"] for e in meta}) == lanes + 1
    by_lane = {e["tid"]: e["args"]["name"] for e in meta}
    for e, name in zip([e for e in doc["traceEvents"]
                        if e["ph"] == "X" and e["name"] == "work"], names):
        assert by_lane[e["tid"]] == name
