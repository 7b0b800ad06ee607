"""Goodput & efficiency attribution engine tests: the per-run wall-time
ledger (EfficiencyLedger / RunReport), zero-wiring live MFU gauges from
the dispatched step's cost model, padding-waste accounting (serving
bucket ladder + datapipe bucket_batch), tracer drop counters, the memory
watermark, and the scripts/check_budgets.py CI gate (including a
demonstrable failure on a violated budget)."""

import json
import os
import sys
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.observability import goodput
from deeplearning4j_tpu.observability.goodput import (
    RunReport,
    end_run,
    start_run,
)
from deeplearning4j_tpu.observability.metrics import (
    MetricsRegistry,
    install_runtime_metrics,
    memory_watermark_bytes,
    set_registry,
)
from deeplearning4j_tpu.observability.trace import Tracer, set_tracer

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"))

import check_budgets  # noqa: E402  (scripts/check_budgets.py)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def fresh_obs():
    """Fresh registry + tracer, goodput force-enabled; restores all
    process-global observability state afterwards."""
    reg = MetricsRegistry()
    prev_reg = set_registry(reg)
    tr = Tracer(enabled=True)
    prev_tr = set_tracer(tr)
    prev_enabled = goodput._ENABLED
    prev_last = goodput._LAST_REPORT
    goodput.set_enabled(True)
    try:
        yield reg, tr
    finally:
        set_registry(prev_reg)
        set_tracer(prev_tr)
        goodput._ENABLED = prev_enabled
        with goodput._lock:
            goodput._LAST_REPORT = prev_last


def _family_value(text: str, name: str) -> float:
    """First sample value of a Prometheus family, labelled or not."""
    for line in text.splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            return float(line.split()[-1])
    raise AssertionError(f"{name} not in exposition:\n{text}")


def _mlp(n_in=16, hidden=32, n_out=3):
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import Dense, Output
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().seed(1).list()
            .layer(Dense(n_in=n_in, n_out=hidden, activation="tanh"))
            .layer(Output(n_out=n_out, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _graph(n_in=16, hidden=32, n_out=3):
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import Dense, Output
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    conf = (NeuralNetConfiguration.builder().seed(1).graph_builder()
            .add_inputs("in")
            .add_layer("h", Dense(n_in=n_in, n_out=hidden, activation="tanh"),
                       "in")
            .add_layer("out", Output(n_in=hidden, n_out=n_out,
                                     activation="softmax", loss="mcxent"),
                       "h")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


def _xy(n=64, n_in=16, n_out=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, n_in)).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, n)]
    return x, y


# ------------------------------------------------------------- RunReport


def test_run_report_json_round_trip(tmp_path):
    rep = RunReport(kind="fit", status="completed", wall_s=2.5, steps=10,
                    phases={"device_step": {"seconds": 1.5, "count": 10}},
                    attributed_s=2.4, untracked_s=0.1, device_s=1.5,
                    goodput_fraction=0.6, flops_per_step=1e6,
                    flops_per_second=4e6, mfu=0.04, peak_flops=1e8,
                    compile_count=1, compile_seconds=0.3,
                    device_memory_peak_bytes=1234.0,
                    padding={"serving_bucket": {
                        "real": 3, "padded": 1, "waste_fraction": 0.25}},
                    trace_dropped_spans=2)
    clone = RunReport.from_json(rep.to_json())
    assert clone == rep
    path = tmp_path / "rr.json"
    rep.save(str(path))
    assert RunReport.load(str(path)) == rep
    # unknown keys from a future schema are dropped, not fatal
    d = rep.to_dict()
    d["from_the_future"] = 42
    assert RunReport.from_dict(d) == rep


def test_ledger_manual_feed_and_end_run_idempotent(fresh_obs):
    reg, tr = fresh_obs
    ledger = start_run("fit")
    with tr.span("device_step"):
        pass
    with tr.span("data_wait"):
        pass
    with tr.span("unrelated_phase"):
        pass
    ledger.observe_steps(3)
    ledger.record_padding("src", real=6, padded=2)
    rep = end_run(ledger)
    assert rep is not None and rep.kind == "fit"
    assert rep.steps == 3
    assert set(rep.phases) == {"device_step", "data_wait",
                               "unrelated_phase"}
    # only the exclusive phases count as attributed; device_step alone
    # feeds the goodput numerator
    assert rep.attributed_s == pytest.approx(
        rep.phases["device_step"]["seconds"]
        + rep.phases["data_wait"]["seconds"])
    assert rep.device_s == pytest.approx(
        rep.phases["device_step"]["seconds"])
    assert rep.padding == {"src": {"real": 6, "padded": 2,
                                   "waste_fraction": 0.25}}
    assert goodput.last_report() is rep
    # closing again is a no-op, not a second report
    assert end_run(ledger) is None
    # spans after close no longer feed the ledger
    with tr.span("device_step"):
        pass
    assert rep.phases["device_step"]["count"] == 1


def test_disabled_engine_returns_null_ledger(fresh_obs):
    goodput.set_enabled(False)
    ledger = start_run("fit")
    ledger.observe_steps(5)  # all no-ops
    assert ledger.closed
    assert end_run(ledger) is None


# -------------------------------------------------- fit integration


@pytest.mark.parametrize("multi_step", ["auto", 8])
def test_fit_publishes_live_goodput_and_mfu_gauges(fresh_obs, monkeypatch,
                                                   multi_step):
    """A plain net.fit on a zoo model publishes dl4j_mfu /
    dl4j_goodput_fraction / dl4j_flops_per_second with no manual FLOPs
    wiring — the acceptance criterion of the goodput engine. The CPU
    resolves "auto" to per-batch dispatch; 8 takes the chunked path."""
    from deeplearning4j_tpu import zoo

    monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "1e12")
    reg, tr = fresh_obs
    net = zoo.mnist_mlp()
    x, y = _xy(n=64, n_in=784, n_out=10)
    net.fit(x, y, epochs=2, batch_size=8, multi_step=multi_step)

    rep = net.last_run_report
    assert rep is not None and rep.status == "completed"
    assert rep.kind == "fit" and rep.steps == 16
    # FLOPs were auto-derived from the dispatched step's cost model
    assert net.flops_per_step and net.flops_per_step > 0
    assert rep.flops_per_step == pytest.approx(net.flops_per_step)
    assert rep.flops_per_second and rep.flops_per_second > 0
    assert rep.mfu is not None and 0 < rep.mfu <= 1.0
    assert rep.goodput_fraction is not None and 0 < rep.goodput_fraction <= 1
    assert rep.peak_flops == pytest.approx(1e12)
    assert rep.compile_count >= 1
    assert rep.device_memory_peak_bytes  # CPU falls back to host VmHWM

    text = reg.render_prometheus()
    assert 'dl4j_goodput_fraction{run="fit"}' in text
    assert 'dl4j_mfu{run="fit"}' in text
    assert 'dl4j_flops_per_second{run="fit"}' in text
    assert 'dl4j_run_wall_seconds{run="fit"}' in text
    assert ('dl4j_goodput_phase_seconds{phase="device_step",run="fit"}'
            in text)


def test_fit_ledger_sums_to_wall_within_5pct(fresh_obs):
    """The exclusive-phase invariant: data_wait + host_dispatch +
    device_step + score_sync on the fit thread account for the run's
    wall clock within +/-5% (enough steps to amortize startup)."""
    reg, tr = fresh_obs
    # wide enough that device_step dominates per-step Python overhead,
    # long enough (80 steps) that one-time startup amortizes
    net = _mlp(n_in=64, hidden=256)
    x, y = _xy(n=640, n_in=64)
    net.fit(x, y, epochs=4, batch_size=32)
    rep = net.last_run_report
    assert rep.steps == 80
    ratio = rep.attributed_s / rep.wall_s
    assert 0.95 <= ratio <= 1.05, f"attributed/wall = {ratio:.4f}"
    assert rep.untracked_s == pytest.approx(
        max(0.0, rep.wall_s - rep.attributed_s))


def test_pipelined_fit_ledger_holds_invariant(fresh_obs):
    """Same invariant on the pipelined path (multi_step chunking +
    device prefetch). Regression, twice: the chunked dispatcher sliced
    the stacked device arrays when handing shapes to the FLOPs
    derivation, and later the chunk's scores for ``score_value``, each
    paying first-call XLA compiles outside any span (attributed/wall
    ~0.88: the test was red for that, not for a loaded host). The books
    say why when the ratio falls: the stage account names every program
    the run made, and none was made outside a span; the nested
    ``xla_*`` spans are reported and not attributed."""
    from deeplearning4j_tpu.observability import metrics as obs

    reg, tr = fresh_obs
    net = _mlp(n_in=64, hidden=256)
    x, y = _xy(n=640, n_in=64)
    stages0 = obs.stage_snapshot()
    net.fit(ArrayDataSetIterator(x, y, batch_size=32, drop_last=True),
            epochs=4, multi_step=8, device_prefetch=True)
    rep = net.last_run_report
    assert rep.steps == 80
    assert rep.flops_per_step  # derivation still ran on the chunked path
    made = obs.stage_delta(stages0)["seconds"]
    assert made["compile"]["device_step"] > 0
    unowned = {stage: owners["none"] for stage, owners in made.items()
               if owners.get("none")}
    assert not unowned, f"programs made outside any span: {unowned}"
    exclusive = sum(rep.phases[p]["seconds"] for p in goodput.FIT_EXCLUSIVE
                    if p in rep.phases)
    assert rep.attributed_s == pytest.approx(exclusive)
    assert rep.phases["xla_compile"]["seconds"] > 0     # reported ...
    assert rep.xla_stage_seconds["compile"] == pytest.approx(
        rep.compile_seconds, abs=1e-5)
    ratio = rep.attributed_s / rep.wall_s
    assert 0.93 <= ratio <= 1.05, f"attributed/wall = {ratio:.4f}"


def test_fit_steps_count_k_per_chunked_dispatch(fresh_obs):
    """Under multi_step scan chunking one dispatch advances k
    iterations; the steps counter (and the ledger) must count k per
    dispatch, not 1."""
    reg, tr = fresh_obs
    install_runtime_metrics(reg)
    net = _mlp()
    x, y = _xy(n=64)

    def steps_total():
        return _family_value(reg.render_prometheus(),
                             "dl4j_fit_steps_total")

    before = steps_total()
    net.fit(x, y, epochs=1, batch_size=8, multi_step=4)  # 2 dispatches
    assert steps_total() == before + 8
    assert net.last_run_report.steps == 8
    assert net.iteration == 8


@pytest.mark.parametrize("make", [_mlp, _graph], ids=["mlp", "graph"])
def test_chunked_fit_reads_flops_from_the_dispatched_scan(fresh_obs, make):
    """The chunked path derives per-step FLOPs from the scan it has just
    dispatched: it never builds the single step, the derivation lowers
    and compiles nothing (jax's caches hand back the dispatch's own
    lowering and executable; the one interval booked to it, if any, is
    jax's trace-cache lookup of the scan), and the count is one step's,
    because XLA costs a ``while`` body once. Should a later XLA count
    every trip, the last check says so."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.observability import metrics as obs

    reg, tr = fresh_obs
    # wide enough that the scan body's rng split is far under 1e-3 of
    # the step's FLOPs
    net = make(n_in=64, hidden=256)
    x, y = _xy(n=512, n_in=64)
    stages0 = obs.stage_snapshot()
    net.fit(ArrayDataSetIterator(x, y, batch_size=32, drop_last=True),
            epochs=1, multi_step=8)
    made = obs.stage_delta(stages0)
    assert net.iteration == 16
    assert net._train_step is None
    for kind in ("seconds", "programs"):
        booked = {stage: owners["flops_derive"]
                  for stage, owners in made[kind].items()
                  if stage != "trace" and owners.get("flops_derive")}
        assert not booked, f"{kind} made under flops_derive: {booked}"
    assert made["programs"].get("trace", {}).get("flops_derive", 0) <= 1
    under =[(s.name, (s.attrs or {}).get("program")) for s in tr.spans()
             if s.parent == "flops_derive"]
    assert set(under) <= {("xla_trace", "multi")}, under
    assert made["spans"]["flops_derive"]["count"] == 1
    single = net.step_cost_analysis(DataSet(x[:32], y[:32]))["flops"]
    assert net.flops_per_step == pytest.approx(single, rel=1e-3)


def test_chunked_fit_auto_flops_off_derives_nothing(fresh_obs, monkeypatch):
    """DL4J_TPU_AUTO_FLOPS=0 on the chunked path: no count, no span."""
    from deeplearning4j_tpu.observability import metrics as obs

    monkeypatch.setenv("DL4J_TPU_AUTO_FLOPS", "0")
    reg, tr = fresh_obs
    net = _mlp()
    x, y = _xy(n=128)
    stages0 = obs.stage_snapshot()
    net.fit(ArrayDataSetIterator(x, y, batch_size=8, drop_last=True),
            epochs=1, multi_step=8)
    assert net.iteration == 16
    assert getattr(net, "flops_per_step", None) is None
    assert net.last_run_report.flops_per_step is None
    assert not [s for s in tr.spans() if s.name == "flops_derive"]
    spans = obs.stage_delta(stages0)["spans"]
    assert spans.get("flops_derive", {}).get("count", 0) == 0


def test_fit_batch_repeated_counts_n_steps(fresh_obs):
    from deeplearning4j_tpu.datasets.dataset import DataSet

    reg, tr = fresh_obs
    net = _mlp()
    x, y = _xy(n=8)
    ledger = start_run("fit", net=net)
    net.fit_batch_repeated(DataSet(x, y), 5)
    rep = end_run(ledger)
    assert rep.steps == 5


def test_graph_fit_produces_report(fresh_obs, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "1e12")
    net = _graph()
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator

    x, y = _xy(n=32)
    batches = [DataSet(x[i:i + 8], y[i:i + 8]) for i in range(0, 32, 8)]
    net.fit(ListDataSetIterator(batches), epochs=1)
    rep = net.last_run_report
    assert rep is not None and rep.kind == "fit" and rep.steps == 4
    assert rep.flops_per_step and rep.flops_per_step > 0
    assert rep.mfu is not None


def test_run_report_dir_env_writes_artifact(fresh_obs, tmp_path,
                                            monkeypatch):
    monkeypatch.setenv("DL4J_TPU_RUN_REPORT_DIR", str(tmp_path))
    net = _mlp()
    x, y = _xy(n=16)
    net.fit(x, y, epochs=1, batch_size=8)
    files = list(tmp_path.glob("run_report_fit_*.json"))
    assert len(files) == 1
    rep = RunReport.load(str(files[0]))
    assert rep.kind == "fit" and rep.steps == 2


def test_resilient_fit_result_carries_report(fresh_obs, tmp_path):
    net = _mlp()
    x, y = _xy(n=32)
    res = net.resilient_fit(x, y, checkpoint_dir=str(tmp_path), epochs=1,
                            batch_size=8, checkpoint_every_steps=2)
    assert res.status == "completed"
    assert res.report is not None and res.report.kind == "resilient_fit"
    assert res.report.steps >= 4
    # the supervisor also drops the artifact next to the checkpoints
    on_disk = RunReport.load(str(tmp_path / "run_report.json"))
    assert on_disk.kind == "resilient_fit"
    assert on_disk.steps == res.report.steps
    # checkpoint_* phases are part of the supervisor's exclusive set
    assert any(p.startswith("checkpoint") for p in on_disk.phases)


# --------------------------------------------------- padding accounting


def test_serving_bucket_padding_waste(fresh_obs):
    """3 rows into the min-2 power-of-two ladder -> bucket 4, 1 padded
    row, waste fraction 1/4 — in the stats snapshot, the Prometheus
    exposition, and the server's drain RunReport."""
    from deeplearning4j_tpu.serving import serve

    reg, tr = fresh_obs
    server = serve(_mlp(n_in=4), port=0, batch_window_ms=0.0)
    try:
        req = urllib.request.Request(
            server.url + "/predict",
            data=json.dumps({"features": np.zeros((3, 4)).tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=30).read()
        snap = server.metrics()
        assert snap["padded_rows_total"] == 1
        assert snap["padding_waste_fraction"] == pytest.approx(0.25)
        text = reg.render_prometheus()
        assert _family_value(text, "dl4j_serving_padded_rows_total") == 1
        assert _family_value(
            text, "dl4j_serving_padding_waste_fraction") == 0.25
    finally:
        server.stop()
    rep = server.run_report
    assert rep is not None and rep.kind == "serving"
    assert rep.padding["serving_bucket"] == {
        "real": 3, "padded": 1, "waste_fraction": 0.25}
    assert rep.device_s > 0  # device_compute spans attributed


def test_bucket_batch_stage_cell_accounting(fresh_obs):
    """Crafted ladder arithmetic: lengths 3 and 5 on a [4, 8] ladder
    collate into a 4-bucket and an 8-bucket batch; padded cells are
    b*bucket - real per collate."""
    from deeplearning4j_tpu import datapipe

    ledger = start_run("fit")
    recs = [(np.ones((3, 2), np.float32),),
            (np.ones((5, 2), np.float32),)]
    pipe = datapipe.from_records(recs).bucket_batch(1, ladder=[4, 8])
    batches = list(pipe)
    assert len(batches) == 2
    stage = pipe.tail
    assert stage.cells_real == 3 + 5
    assert stage.cells_padded == (1 * 4 - 3) + (1 * 8 - 5)
    rep = end_run(ledger)
    assert rep.padding["datapipe_bucket_batch"] == {
        "real": 8, "padded": 4, "waste_fraction": pytest.approx(1 / 3)}


# ------------------------------------------- tracer drops + watermark


def test_tracer_counts_drops_per_name_and_stamps_chrome_trace():
    tr = Tracer(capacity=4)
    for _ in range(7):
        tr.record("evicted", 0.0, 0.001)
    for _ in range(4):
        tr.record("survivor", 0.0, 0.001)
    # 7 evicted + 4 survivor through a 4-slot ring: the first 7 pushed
    # out are all "evicted" spans
    assert tr.dropped == 7
    assert tr.dropped_spans() == {"evicted": 7}
    doc = tr.to_chrome_trace()
    assert doc["otherData"]["dropped_spans_total"] == 7
    assert doc["otherData"]["dropped_spans_by_name"] == {"evicted": 7}

    sampled = Tracer(sample_every=4)
    for _ in range(8):
        with sampled.span("s"):
            pass
    assert sampled.dropped_spans() == {"s": 6}
    # clear() resets the per-name ledger with the ring
    sampled.clear()
    assert sampled.dropped_spans() == {}


def test_trace_dropped_spans_metric_family(fresh_obs):
    reg, tr = fresh_obs
    install_runtime_metrics(reg)
    small = Tracer(capacity=2)
    prev = set_tracer(small)
    try:
        for _ in range(5):
            small.record("hot_phase", 0.0, 0.001)
        text = reg.render_prometheus()
    finally:
        set_tracer(prev)
    assert "dl4j_trace_dropped_spans_total 3" in text
    assert 'dl4j_trace_dropped_spans_total{span="hot_phase"} 3' in text


def test_memory_watermark_gauge_and_fallback(fresh_obs):
    reg, tr = fresh_obs
    install_runtime_metrics(reg)
    # CPU: no device memory_stats -> host VmHWM high-water fallback
    wm = memory_watermark_bytes()
    assert wm is not None and wm > 0
    assert "dl4j_device_memory_peak_bytes{" in reg.render_prometheus()


def test_memory_watermarks_keep_arrays_and_reserved_apart(monkeypatch):
    """The arrays-only peak keeps its key and meaning; the largest
    in_use + reserved of one sample sits beside it."""
    import jax

    from deeplearning4j_tpu.observability import metrics

    class FakeChip:
        platform, id = "tpu", 7
        samples = [{"peak_bytes_in_use": 5e9, "bytes_in_use": 4e9,
                    "bytes_reserved": 9e9},
                   {"peak_bytes_in_use": 6e9, "bytes_in_use": 6e9,
                    "bytes_reserved": 1e9}]

        def memory_stats(self):     # the program ends: scratch is freed
            return self.samples.pop(0) if len(self.samples) > 1 \
                else self.samples[0]

    monkeypatch.setattr(jax, "local_devices", lambda: [FakeChip()])
    monkeypatch.setattr(metrics, "_MEM_PEAK", {})
    metrics.update_memory_watermark()
    peaks = metrics.memory_watermarks()
    assert peaks == {"tpu:7": 6e9, "tpu:7+reserved": 13e9}
    assert metrics.memory_watermark_bytes() == 6e9


# ---------------------------------------------------- listener + UI


def test_performance_listener_report_mfu_resolves_derived_flops():
    from deeplearning4j_tpu.optimize.listeners import PerformanceListener

    class FakeNet:
        flops_per_step = 2.5e6

    auto = PerformanceListener(report_mfu=True)
    assert auto._resolve_flops(FakeNet()) == pytest.approx(2.5e6)
    explicit = PerformanceListener(flops_per_step=1e6)
    assert explicit._resolve_flops(FakeNet()) == pytest.approx(1e6)
    off = PerformanceListener()
    assert off._resolve_flops(FakeNet()) is None


def test_goodput_families_scraped_on_both_servers(fresh_obs):
    """The new dl4j_goodput_* / dl4j_mfu families ride the unified
    registry, so both HTTP servers expose them on /metrics."""
    from deeplearning4j_tpu.serving import serve
    from deeplearning4j_tpu.ui import UIServer

    reg, tr = fresh_obs
    net = _mlp(n_in=4)
    x, y = _xy(n=16, n_in=4)
    net.fit(x, y, epochs=1, batch_size=8)

    def prom(url):
        req = urllib.request.Request(url)
        req.add_header("Accept", "text/plain")
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.read().decode()

    ui = UIServer(port=0)
    try:
        base = ui.url.rstrip("/")
        text = prom(base + "/metrics")
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            snap = json.loads(r.read().decode())
    finally:
        ui.stop()
    assert 'dl4j_goodput_fraction{run="fit"}' in text
    assert "dl4j_goodput_phase_seconds{" in text
    assert "dl4j_run_wall_seconds" in text
    # JSON snapshot view carries the same families
    assert "dl4j_goodput_fraction" in snap
    assert "dl4j_goodput_phase_seconds" in snap

    # a running ModelServer opens its own ledger, so its scrape reports
    # the live serving run (innermost ledger wins)
    server = serve(net, port=0)
    try:
        text = prom(server.url + "/metrics")
    finally:
        server.stop()
    assert 'dl4j_goodput_fraction{run="serving"}' in text
    assert 'dl4j_run_wall_seconds{run="serving"}' in text


def test_ui_server_goodput_endpoint(fresh_obs):
    from deeplearning4j_tpu.ui import UIServer

    reg, tr = fresh_obs
    net = _mlp()
    x, y = _xy(n=16)
    net.fit(x, y, epochs=1, batch_size=8)
    server = UIServer(port=0)
    try:
        with urllib.request.urlopen(server.url.rstrip("/") + "/api/goodput",
                                    timeout=30) as r:
            snap = json.loads(r.read().decode())
    finally:
        server.stop()
    assert snap["source"] == "last_report"
    assert snap["kind"] == "fit" and snap["steps"] == 2
    assert "phases" in snap and "goodput_fraction" in snap


# ------------------------------------------------------- budget gate


def test_check_report_min_max_and_derived_fields():
    report = {"kind": "fit", "wall_s": 10.0, "untracked_s": 1.0,
              "attributed_s": 9.0, "goodput_fraction": 0.5,
              "compile_count": 3, "mfu": None,
              "padding": {"a": {"waste_fraction": 0.1},
                          "b": {"waste_fraction": 0.4}}}
    ok = check_budgets.check_report(report, {
        "min_goodput_fraction": 0.4, "max_compile_count": 5,
        "max_untracked_fraction": 0.2, "min_attributed_fraction": 0.8,
        "max_padding_waste_fraction": 0.5,
        "min_mfu": 0.9,           # null in report -> skipped, not failed
        "min_not_a_field": 1.0,   # absent -> skipped
        "_comment": "ignored"})
    assert ok == []
    bad = check_budgets.check_report(report, {
        "min_goodput_fraction": 0.6,          # 0.5 < 0.6
        "max_compile_count": 2,               # 3 > 2
        "max_padding_waste_fraction": 0.3})   # worst source 0.4 > 0.3
    assert len(bad) == 3
    assert any("goodput_fraction" in v and "below" in v for v in bad)
    assert any("compile_count" in v and "above" in v for v in bad)
    assert any("padding_waste_fraction" in v for v in bad)


def test_check_budgets_cli_gates_a_real_fit_report(fresh_obs, tmp_path,
                                                  capsys):
    """End-to-end CI gate on a tiny-model fit: the committed
    BUDGETS.json passes, and a violated budget demonstrably fails."""
    net = _mlp()
    x, y = _xy(n=96)
    net.fit(x, y, epochs=2, batch_size=8)
    report_path = tmp_path / "run_report.json"
    net.last_run_report.save(str(report_path))

    # the committed budgets hold for the real run
    rc = check_budgets.main(["--report", str(report_path)])
    assert rc == 0
    assert "budgets OK [fit]" in capsys.readouterr().out

    # a violated budget fails with a nonzero exit + a named violation
    broken = tmp_path / "broken_budgets.json"
    broken.write_text(json.dumps(
        {"fit": {"min_goodput_fraction": 2.0, "max_compile_count": 0}}))
    rc = check_budgets.main(["--report", str(report_path),
                             "--budgets", str(broken)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "BUDGET VIOLATION [fit]" in out
    assert "goodput_fraction" in out and "compile_count" in out

    # unknown section -> usage error, not a silent pass
    assert check_budgets.main(["--report", str(report_path),
                               "--section", "nope"]) == 2


def test_bench_exposes_goodput_overhead_config():
    import bench

    assert "goodput_overhead" in bench._CONFIGS
    assert callable(bench.bench_goodput_overhead)


@pytest.mark.slow
def test_goodput_overhead_under_guard():
    import bench

    out = bench.bench_goodput_overhead(batch=256, n_batches=16, epochs=3)
    assert out["steps_per_sec_ledger_off"] > 0
    assert out["steps_per_sec_ledger_on"] > 0
    assert isinstance(out["overhead_ok"], bool)
    # the acceptance bar is <3%; allow CI noise headroom here, the
    # strict number is checked in the bench run recorded in PERF.md
    assert out["overhead_pct"] < 10.0
