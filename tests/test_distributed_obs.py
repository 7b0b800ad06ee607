"""Cross-process observability plane tests (observability/distributed.py
+ flightrec.py): process identity & env seeding, the canonical
sample-key escaping pin, metrics federation merge semantics (counter
sum / gauge last-write / histogram bucket add) under concurrent pushes,
the health scoreboard, trace-context propagation through /predict, the
crash flight recorder (direct + through the supervisor's fault paths),
the UIServer aggregator endpoints, RunReport identity stamping and the
check_budgets --fleet CI gate."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.core import DtypePolicy
from deeplearning4j_tpu.nn.conf.layers import Dense, Output
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.observability import distributed as dist
from deeplearning4j_tpu.observability import flightrec, goodput
from deeplearning4j_tpu.observability.distributed import (
    TRACE_HEADER,
    MetricsFederation,
    bump_incarnation,
    export_snapshot,
    get_identity,
    new_trace_id,
    reset_identity,
    set_identity,
    stamp_run_marker,
)
from deeplearning4j_tpu.observability.flightrec import (
    FlightRecorder,
    install_flight_recorder,
    uninstall_flight_recorder,
)
from deeplearning4j_tpu.observability.metrics import (
    MetricsRegistry,
    install_runtime_metrics,
    sample_key,
    set_registry,
)
from deeplearning4j_tpu.observability.trace import Tracer, set_tracer

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"))

import check_budgets  # noqa: E402  (scripts/check_budgets.py)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

F64 = DtypePolicy(param_dtype="float64", compute_dtype="float64")


@pytest.fixture()
def fresh_identity(monkeypatch):
    """Identity rebuilt from a scrubbed environment; restored after."""
    for var in ("DL4J_TPU_RUN_ID", "DL4J_TPU_INSTANCE",
                "DL4J_TPU_INCARNATION"):
        monkeypatch.delenv(var, raising=False)
    reset_identity()
    yield monkeypatch
    reset_identity()


@pytest.fixture()
def fresh_obs():
    """Fresh registry + tracer; process globals restored after."""
    reg = MetricsRegistry()
    prev_reg = set_registry(reg)
    tr = Tracer(enabled=True)
    prev_tr = set_tracer(tr)
    try:
        yield reg, tr
    finally:
        set_registry(prev_reg)
        set_tracer(prev_tr)


def _mlp():
    conf = (NeuralNetConfiguration.builder().seed(1).dtype(F64).list()
            .layer(Dense(n_in=4, n_out=8, activation="tanh"))
            .layer(Output(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _wire_snapshot(tag, families, health=None, snap_time=None):
    """Hand-built federation wire snapshot (the documented format —
    building it by hand pins the schema a third-party pusher targets)."""
    return {"schema": 1,
            "identity": {"tag": tag, "instance": tag},
            "time": time.time() if snap_time is None else snap_time,
            "families": families,
            "health": health or {}}


def _fam(name, kind, samples):
    return {"name": name, "kind": kind, "help": "",
            "samples": [{"labels": s[0], "suffix": s[1], "value": s[2]}
                        for s in samples]}


# ---------------------------------------------------------------- identity

def test_identity_reads_env_and_resets(fresh_identity):
    mp = fresh_identity
    mp.setenv("DL4J_TPU_RUN_ID", "run-abc")
    mp.setenv("DL4J_TPU_INSTANCE", "worker-7")
    mp.setenv("DL4J_TPU_INCARNATION", "2")
    reset_identity()
    ident = get_identity()
    assert ident.run_id == "run-abc"
    assert ident.instance == "worker-7"
    assert ident.incarnation == 2
    assert ident.pid == os.getpid()
    assert ident.tag == "worker-7-i2"
    # cached: same object until reset
    assert get_identity() is ident
    # default path: generated run_id, host-pid instance, incarnation 0
    mp.delenv("DL4J_TPU_RUN_ID")
    mp.delenv("DL4J_TPU_INSTANCE")
    mp.delenv("DL4J_TPU_INCARNATION")
    reset_identity()
    d = get_identity()
    assert len(d.run_id) == 12 and d.incarnation == 0
    assert d.tag == d.instance and str(os.getpid()) in d.instance
    labels = d.labels()
    assert labels["run_id"] == d.run_id and labels["pid"] == str(os.getpid())


def test_bump_incarnation_changes_tag_not_instance(fresh_identity):
    set_identity(instance="w0", run_id="r", incarnation=0)
    assert get_identity().tag == "w0"
    bump_incarnation()
    ident = get_identity()
    assert ident.instance == "w0" and ident.incarnation == 1
    assert ident.tag == "w0-i1"
    bump_incarnation()
    assert get_identity().tag == "w0-i2"


def test_run_marker_span_carries_identity(fresh_identity, fresh_obs):
    _, tr = fresh_obs
    set_identity(instance="w3", run_id="runx", incarnation=1)
    stamp_run_marker("fit")
    spans = tr.spans()
    assert [s.name for s in spans] == ["run_start"]
    attrs = spans[0].attrs
    assert attrs["kind"] == "fit" and attrs["run_id"] == "runx"
    assert attrs["instance"] == "w3" and attrs["incarnation"] == 1


def test_chrome_trace_stamps_identity_in_other_data(fresh_identity,
                                                    fresh_obs):
    _, tr = fresh_obs
    set_identity(instance="w9", run_id="runy", incarnation=0)
    with tr.span("a"):
        pass
    doc = tr.to_chrome_trace()
    ident = doc["otherData"]["identity"]
    assert ident["instance"] == "w9" and ident["run_id"] == "runy"
    # the metadata-event contract is untouched: M events stay thread_name
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert meta and all(e["name"] == "thread_name" for e in meta)


# ------------------------------------------------- canonical sample keys

def test_sample_key_matches_exposition_series_exactly(fresh_obs):
    reg, _ = fresh_obs
    nasty = 'a"b\\c\nd'
    reg.counter("dl4j_esc_total", "h",
                labelnames=("v",)).labels(v=nasty).inc(1)
    fam = reg.collect()[0]
    s = fam.samples[0]
    key = sample_key(fam.name, s.labels, s.suffix)
    # the JSON wire key IS the exposition series string: the rendered
    # text must contain exactly `<key> <value>` — one encoding, two views
    assert f"{key} 1" in reg.render_prometheus().splitlines()
    assert key == 'dl4j_esc_total{v="a\\"b\\\\c\\nd"}'
    snap = export_snapshot(reg)
    keys = [smp["key"] for f in snap["families"] for smp in f["samples"]]
    assert key in keys


def test_export_snapshot_wire_format(fresh_identity, fresh_obs):
    reg, _ = fresh_obs
    set_identity(instance="w1", run_id="rr", incarnation=0)
    reg.counter("dl4j_a_total", "h").inc(3)
    reg.histogram("dl4j_lat_seconds", "h", buckets=(0.1, 1.0)).observe(0.5)
    snap = export_snapshot(reg, health={"batcher_healthy": True})
    assert snap["schema"] == dist.SNAPSHOT_SCHEMA_VERSION
    assert snap["identity"]["tag"] == "w1"
    assert snap["health"] == {"batcher_healthy": True}
    fams = {f["name"]: f for f in snap["families"]}
    assert fams["dl4j_a_total"]["kind"] == "counter"
    suffixes = {s["suffix"] for s in fams["dl4j_lat_seconds"]["samples"]}
    assert {"_bucket", "_sum", "_count"} <= suffixes
    # round-trips through JSON (what push_snapshot puts on the wire)
    assert json.loads(json.dumps(snap)) == snap


# ------------------------------------------------------------- federation

def test_federation_merge_counter_gauge_histogram():
    fed = MetricsFederation()
    fed.ingest(_wire_snapshot("w0", [
        _fam("dl4j_steps_total", "counter", [({}, "", 10)]),
        _fam("dl4j_queue_depth", "gauge", [({}, "", 3)]),
        _fam("dl4j_lat", "histogram",
             [({"le": "1"}, "_bucket", 2), ({"le": "+Inf"}, "_bucket", 5),
              ({}, "_sum", 7.5), ({}, "_count", 5)]),
    ]))
    fed.ingest(_wire_snapshot("w1", [
        _fam("dl4j_steps_total", "counter", [({}, "", 32)]),
        _fam("dl4j_queue_depth", "gauge", [({}, "", 9)]),
        _fam("dl4j_lat", "histogram",
             [({"le": "1"}, "_bucket", 1), ({"le": "+Inf"}, "_bucket", 2),
              ({}, "_sum", 3.5), ({}, "_count", 2)]),
    ]))
    assert fed.instance_tags() == ["w0", "w1"]
    text = fed.render_prometheus()
    # every sample re-labeled per instance + one fleet rollup per series
    assert 'dl4j_steps_total{instance="w0"} 10' in text
    assert 'dl4j_steps_total{instance="w1"} 32' in text
    assert 'dl4j_steps_total{instance="fleet"} 42' in text
    # gauge rollup: last write (w1 pushed later) — NOT the sum
    assert 'dl4j_queue_depth{instance="fleet"} 9' in text
    # histogram buckets/sum/count add across instances
    assert 'dl4j_lat_bucket{instance="fleet",le="1"} 3' in text
    assert 'dl4j_lat_bucket{instance="fleet",le="+Inf"} 7' in text
    assert 'dl4j_lat_sum{instance="fleet"} 11' in text
    assert 'dl4j_lat_count{instance="fleet"} 7' in text
    # a re-push wholly replaces that instance (counters don't double)
    fed.ingest(_wire_snapshot("w0", [
        _fam("dl4j_steps_total", "counter", [({}, "", 11)])]))
    text = fed.render_prometheus()
    assert 'dl4j_steps_total{instance="fleet"} 43' in text


def test_federation_gauge_last_write_follows_repush_order():
    fed = MetricsFederation()
    fed.ingest(_wire_snapshot("w1", [
        _fam("dl4j_g", "gauge", [({}, "", 100)])]))
    fed.ingest(_wire_snapshot("w0", [
        _fam("dl4j_g", "gauge", [({}, "", 1)])]))
    assert 'dl4j_g{instance="fleet"} 1' in fed.render_prometheus()
    # w1 pushes again: it becomes the most recent writer
    fed.ingest(_wire_snapshot("w1", [
        _fam("dl4j_g", "gauge", [({}, "", 50)])]))
    assert 'dl4j_g{instance="fleet"} 50' in fed.render_prometheus()


def test_federation_kind_conflict_first_writer_wins():
    fed = MetricsFederation()
    fed.ingest(_wire_snapshot("w0", [
        _fam("dl4j_x", "counter", [({}, "", 5)])]))
    fed.ingest(_wire_snapshot("w1", [
        _fam("dl4j_x", "gauge", [({}, "", 7)])]))
    text = fed.render_prometheus()
    assert "# TYPE dl4j_x counter" in text
    assert 'dl4j_x{instance="w0"} 5' in text
    # the conflicting family is skipped, not merged in under a new kind
    assert 'instance="w1"' not in text
    assert 'dl4j_x{instance="fleet"} 5' in text


def test_federation_rejects_malformed_and_strips_instance_label():
    fed = MetricsFederation()
    with pytest.raises(ValueError):
        fed.ingest({"no": "families"})
    with pytest.raises(ValueError):
        fed.ingest({"families": [], "identity": {}})
    # a pusher's own instance label can't spoof another member's series
    fed.ingest(_wire_snapshot("w0", [
        _fam("dl4j_c_total", "counter", [({"instance": "evil"}, "", 4)])]))
    text = fed.render_prometheus()
    assert 'dl4j_c_total{instance="w0"} 4' in text
    assert "evil" not in text


def test_federation_concurrent_pushes_merge_consistently():
    fed = MetricsFederation()
    n_workers, pushes = 8, 25

    def pusher(i):
        for k in range(pushes):
            fed.ingest(_wire_snapshot(f"w{i}", [
                _fam("dl4j_steps_total", "counter", [({}, "", k + 1)]),
                _fam("dl4j_g", "gauge", [({}, "", i)]),
            ]))

    threads = [threading.Thread(target=pusher, args=(i,))
               for i in range(n_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert fed.instance_count() == n_workers
    text = fed.render_prometheus()
    # last push per instance won: every member shows its final counter,
    # and the fleet rollup is the sum of those latest values
    for i in range(n_workers):
        assert f'dl4j_steps_total{{instance="w{i}"}} {pushes}' in text
    assert (f'dl4j_steps_total{{instance="fleet"}} '
            f'{n_workers * pushes}') in text
    # the gauge rollup equals SOME member's value (a consistent last
    # write), never a blend
    fleet_g = [line for line in text.splitlines()
               if line.startswith('dl4j_g{instance="fleet"}')]
    assert len(fleet_g) == 1
    assert float(fleet_g[0].split()[-1]) in set(range(n_workers))


def test_federation_local_registry_folds_in(fresh_obs):
    reg, _ = fresh_obs
    reg.counter("dl4j_steps_total", "h").inc(5)
    fed = MetricsFederation()
    fed.ingest(_wire_snapshot("w0", [
        _fam("dl4j_steps_total", "counter", [({}, "", 7)])]))
    text = fed.render_prometheus(local=("agg", reg.collect()))
    assert 'dl4j_steps_total{instance="agg"} 5' in text
    assert 'dl4j_steps_total{instance="w0"} 7' in text
    assert 'dl4j_steps_total{instance="fleet"} 12' in text


# ------------------------------------------------------- health scoreboard

def test_health_scoreboard_staleness_and_readiness():
    # evict_after_factor=None: this test pins the stale-but-listed
    # semantics; auto-eviction has its own test below
    fed = MetricsFederation(stale_after_s=15.0, evict_after_factor=None)
    now = time.time()
    hb = [_fam("dl4j_heartbeat_timestamp_seconds", "gauge",
               [({}, "", now)])]
    hb_old = [_fam("dl4j_heartbeat_timestamp_seconds", "gauge",
                   [({}, "", now - 120)])]
    fed.ingest(_wire_snapshot("fresh", hb + [
        _fam("dl4j_fit_steps_total", "counter", [({}, "", 4)]),
        _fam("dl4j_serving_queue_depth", "gauge", [({}, "", 2)])],
        health={"batcher_healthy": True}))
    fed.ingest(_wire_snapshot("stale", hb_old, health={"healthy": True}))
    fed.ingest(_wire_snapshot("sick", hb, health={"batcher_healthy": False}))
    rows = {r["instance"]: r for r in fed.health()}
    assert rows["fresh"]["live"] and rows["fresh"]["ready"]
    assert rows["fresh"]["queue_depth"] == 2
    assert rows["fresh"]["steps_total"] == 4
    # heartbeat 120s older than its own snapshot time -> stale
    assert not rows["stale"]["live"] and not rows["stale"]["ready"]
    assert rows["stale"]["heartbeat_age_s"] >= 120
    # fresh heartbeat but self-reported unhealthy -> live, NOT ready
    assert rows["sick"]["live"] and not rows["sick"]["ready"]
    payload = fed.fleet_payload()
    assert payload["live"] == 2 and payload["ready"] == 1
    assert payload["stale_after_s"] == 15.0


def test_health_auto_evicts_dead_instances():
    """An instance whose heartbeat age blows past
    ``evict_after_factor * stale_after_s`` vanishes from the scoreboard
    entirely (a shrunken fleet must not list dead processes forever);
    one merely past ``stale_after_s`` stays, flagged not-live."""
    now = time.time()
    fed = MetricsFederation(stale_after_s=10.0, evict_after_factor=4.0)
    hb = lambda age: [_fam(  # noqa: E731
        "dl4j_heartbeat_timestamp_seconds", "gauge", [({}, "", now - age)])]
    fed.ingest(_wire_snapshot("fresh", hb(0)))
    fed.ingest(_wire_snapshot("wobbling", hb(20)))   # stale, not dead
    fed.ingest(_wire_snapshot("departed", hb(120)))  # past 4 x 10s
    rows = {r["instance"]: r for r in fed.health()}
    assert set(rows) == {"fresh", "wobbling"}
    assert rows["fresh"]["live"] and not rows["wobbling"]["live"]
    assert fed.instance_tags() == ["fresh", "wobbling"]
    assert fed.auto_evicted_total == 1
    payload = fed.fleet_payload()
    assert payload["auto_evicted_total"] == 1
    assert payload["evict_after_factor"] == 4.0
    # a fresh push re-admits the departed instance (it came back)
    fed.ingest(_wire_snapshot("departed", hb(0)))
    assert "departed" in {r["instance"] for r in fed.health()}
    # explicit drop() still works alongside auto-eviction
    fed.drop("departed")
    assert "departed" not in fed.instance_tags()


def test_health_progress_age_tracks_step_changes():
    fed = MetricsFederation()
    steps = lambda n: [_fam("dl4j_fit_steps_total", "counter",  # noqa: E731
                            [({}, "", n)])]
    fed.ingest(_wire_snapshot("w0", steps(5)))
    t0 = {r["instance"]: r for r in fed.health()}["w0"]
    time.sleep(0.05)
    # same step count on the next push: progress age keeps growing
    fed.ingest(_wire_snapshot("w0", steps(5)))
    t1 = {r["instance"]: r for r in fed.health()}["w0"]
    assert t1["last_progress_age_s"] >= t0["last_progress_age_s"] + 0.04
    assert t1["pushes"] == 2
    # progress: the age resets
    fed.ingest(_wire_snapshot("w0", steps(6)))
    t2 = {r["instance"]: r for r in fed.health()}["w0"]
    assert t2["last_progress_age_s"] < t1["last_progress_age_s"]


# --------------------------------------------------- UIServer aggregator

def test_ui_server_metrics_push_fleet_and_merged_view(fresh_identity,
                                                      fresh_obs):
    from deeplearning4j_tpu.ui.server import UIServer
    set_identity(instance="agg-host", run_id="ragg", incarnation=0)
    server = UIServer(port=0)
    base = server.url.rstrip("/")
    try:
        # before any push: /api/fleet is an empty scoreboard
        with urllib.request.urlopen(base + "/api/fleet", timeout=5) as r:
            empty = json.loads(r.read())
        assert empty["instances"] == [] and empty["live"] == 0

        now = time.time()
        snap = _wire_snapshot("pushed-worker", [
            _fam("dl4j_fit_steps_total", "counter", [({}, "", 21)]),
            _fam("dl4j_heartbeat_timestamp_seconds", "gauge",
                 [({}, "", now)])],
            health={"batcher_healthy": True})
        req = urllib.request.Request(
            base + "/api/metrics_push", data=json.dumps(snap).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=5) as r:
            reply = json.loads(r.read())
        assert reply == {"status": "ok", "instance": "pushed-worker",
                         "instances": 1}

        # merged Prometheus view: pushed series + the aggregator's own
        # registry folded in, plus fleet rollups
        req = urllib.request.Request(base + "/metrics",
                                     headers={"Accept": "text/plain"})
        with urllib.request.urlopen(req, timeout=5) as r:
            text = r.read().decode()
        assert 'dl4j_fit_steps_total{instance="pushed-worker"} 21' in text
        assert 'instance="fleet"' in text
        assert 'instance="agg-host"' in text

        with urllib.request.urlopen(base + "/api/fleet", timeout=5) as r:
            fleet = json.loads(r.read())
        rows = {r_["instance"]: r_ for r_ in fleet["instances"]}
        assert rows["pushed-worker"]["live"]
        assert rows["pushed-worker"]["ready"]
        assert rows["pushed-worker"]["steps_total"] == 21

        # the pull seam: /metrics?format=snapshot serves the wire form
        with urllib.request.urlopen(base + "/metrics?format=snapshot",
                                    timeout=5) as r:
            wire = json.loads(r.read())
        assert wire["schema"] == 1
        assert wire["identity"]["instance"] == "agg-host"

        # malformed push: 400, server stays up
        req = urllib.request.Request(
            base + "/api/metrics_push", data=b'{"no": "families"}',
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=5)
        assert exc.value.code == 400
    finally:
        server.stop()


# ------------------------------------------------- trace-id propagation

def test_predict_trace_id_echo_and_span_stamping(fresh_identity,
                                                 fresh_obs):
    from deeplearning4j_tpu.serving import serve
    _, tr = fresh_obs
    net = _mlp()
    server = serve(net, port=0)
    try:
        x = np.random.default_rng(0).normal(size=(2, 4))
        trace_id = new_trace_id()
        req = urllib.request.Request(
            server.url + "/predict",
            data=json.dumps({"features": x.tolist()}).encode(),
            headers={"Content-Type": "application/json",
                     TRACE_HEADER: trace_id})
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.headers.get(TRACE_HEADER) == trace_id
            preds = json.loads(resp.read())["predictions"]
        assert np.asarray(preds).shape == (2, 3)
        # the id rode into the batcher's spans (queue_wait/batch_assembly
        # /device_compute all carry trace_ids)
        deadline = time.time() + 5
        stamped = {}
        while time.time() < deadline:
            stamped = {s.name: s.attrs.get("trace_ids")
                       for s in tr.spans()
                       if s.attrs and s.attrs.get("trace_ids")}
            if "device_compute" in stamped:
                break
            time.sleep(0.01)
        assert trace_id in stamped.get("device_compute", ())
        assert trace_id in stamped.get("batch_assembly", ())

        # no header -> the server mints one and still echoes it
        req = urllib.request.Request(
            server.url + "/predict",
            data=json.dumps({"features": x.tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            minted = resp.headers.get(TRACE_HEADER)
        assert minted and len(minted) == 16 and minted != trace_id

        # error replies carry the echo too (the id must survive failure
        # — that's when you need the correlation most)
        bad = urllib.request.Request(
            server.url + "/predict", data=b'{"bogus": 1}',
            headers={"Content-Type": "application/json",
                     TRACE_HEADER: "deadbeefdeadbeef"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(bad, timeout=30)
        assert exc.value.headers.get(TRACE_HEADER) == "deadbeefdeadbeef"
    finally:
        server.stop()


# ----------------------------------------------------- flight recorder

def test_flight_recorder_flush_schema_and_atomicity(fresh_identity,
                                                    fresh_obs, tmp_path):
    _, tr = fresh_obs
    set_identity(instance="box-test", run_id="rfr", incarnation=1)
    rec = FlightRecorder(dir=str(tmp_path), capacity=4)
    rec.install()
    try:
        for i in range(10):  # ring: only the newest 4 survive
            with tr.span("step", i=i):
                pass
        rec.record_event("rollback", step=7, detail="nan at 7")
        try:
            raise ValueError("boom")
        except ValueError as e:
            path = rec.flush("nan_rollback", exc=e)
    finally:
        rec.uninstall()
    assert path == str(tmp_path / "flight_box-test-i1.json")
    assert rec.last_path == path
    assert not list(tmp_path.glob("*.tmp.*"))  # atomic: no torn temps
    with open(path) as f:
        doc = json.load(f)
    assert doc["schema"] == flightrec.FLIGHT_SCHEMA_VERSION
    assert doc["reason"] == "nan_rollback"
    assert doc["identity"]["instance"] == "box-test"
    assert doc["identity"]["incarnation"] == 1
    assert doc["exception"]["type"] == "ValueError"
    assert "boom" in doc["exception"]["message"]
    assert [s["attrs"]["i"] for s in doc["spans"]] == [6, 7, 8, 9]
    assert doc["events"][0]["kind"] == "rollback"
    assert doc["events"][0]["step"] == 7
    assert isinstance(doc["metrics"], dict)
    # a second flush overwrites in place (same tag -> same path)
    assert rec.flush("sigterm") == path


def test_flight_recorder_excepthook_chains(fresh_identity, fresh_obs,
                                           tmp_path):
    set_identity(instance="hook", run_id="r", incarnation=0)
    rec = FlightRecorder(dir=str(tmp_path))
    seen = []
    prev_hook = sys.excepthook
    sys.excepthook = lambda *a: seen.append(a)
    try:
        rec.install()
        try:
            raise RuntimeError("unhandled")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())
    finally:
        rec.uninstall()
        sys.excepthook = prev_hook
    # the box flushed AND the previous hook still ran
    assert len(seen) == 1 and seen[0][0] is RuntimeError
    with open(tmp_path / "flight_hook.json") as f:
        doc = json.load(f)
    assert doc["reason"] == "unhandled_exception"
    assert doc["exception"]["type"] == "RuntimeError"


@pytest.fixture()
def flight_module_state():
    """Isolate the process-global recorder around supervisor tests."""
    uninstall_flight_recorder()
    yield
    uninstall_flight_recorder()


def _fit_data(seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(32, 5))
    y = np.eye(3)[rng.integers(0, 3, 32)]
    return DataSet(x, y)


def _fit_net(seed=3):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .dtype(F64).list()
            .layer(Dense(n_in=5, n_out=7, activation="tanh"))
            .layer(Output(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def test_supervisor_crash_leaves_flight_artifact(fresh_identity, fresh_obs,
                                                 flight_module_state,
                                                 tmp_path):
    from deeplearning4j_tpu.resilience import (FaultInjector, InjectedCrash,
                                               resilient_fit)
    set_identity(instance="chaos-w", run_id="rc", incarnation=0)
    inj = FaultInjector().crash_during_save(1)
    net = _fit_net()
    with pytest.raises(InjectedCrash), inj.installed():
        resilient_fit(net, _fit_data(), checkpoint_dir=str(tmp_path),
                      epochs=10, checkpoint_every_steps=3, injector=inj)
    path = tmp_path / "flight_chaos-w.json"
    assert path.exists()
    with open(path) as f:
        doc = json.load(f)
    assert doc["schema"] == 1 and doc["reason"] == "exception"
    assert doc["exception"]["type"] == "InjectedCrash"
    assert doc["identity"]["instance"] == "chaos-w"
    # the box saw the supervisor's recovery events on the way down
    assert any(e["kind"] == "checkpoint" for e in doc["events"])


def test_supervisor_preemption_leaves_flight_artifact(fresh_identity,
                                                      fresh_obs,
                                                      flight_module_state,
                                                      tmp_path):
    from deeplearning4j_tpu.resilience import FaultInjector, resilient_fit
    set_identity(instance="preempt-w", run_id="rp", incarnation=0)
    inj = FaultInjector().preempt_at_step(4)
    net = _fit_net()
    res = resilient_fit(net, _fit_data(), checkpoint_dir=str(tmp_path),
                        epochs=10, checkpoint_every_steps=3, injector=inj)
    assert res.status == "preempted"
    with open(tmp_path / "flight_preempt-w.json") as f:
        doc = json.load(f)
    assert doc["reason"] == "preemption" and doc["exception"] is None
    assert any(e["kind"] == "preempt" for e in doc["events"])


# ------------------------------------------- runtime identity metrics

def test_runtime_metrics_carry_identity_gauges(fresh_identity, fresh_obs):
    reg, _ = fresh_obs
    set_identity(instance="m-w", run_id="rm", incarnation=2)
    install_runtime_metrics(reg)
    before = time.time()
    text = reg.render_prometheus()
    assert "dl4j_process_start_time_seconds" in text
    hb = [line for line in text.splitlines()
          if line.startswith("dl4j_heartbeat_timestamp_seconds ")]
    assert len(hb) == 1
    # the heartbeat is stamped at render time — a fresh render moves it
    assert before <= float(hb[0].split()[-1]) <= time.time()
    assert ('dl4j_instance_info{incarnation="2",instance="m-w",'
            f'pid="{os.getpid()}",run_id="rm"}} 1' in text)


def test_run_report_identity_stamped_and_roundtrip(fresh_identity,
                                                   fresh_obs):
    set_identity(instance="rep-w", run_id="rrep", incarnation=3)
    prev_enabled = goodput._ENABLED
    goodput.set_enabled(True)
    try:
        ledger = goodput.start_run("fit")
        report = goodput.end_run(ledger)
    finally:
        goodput._ENABLED = prev_enabled
    assert report.run_id == "rrep"
    assert report.instance == "rep-w" and report.incarnation == 3
    d = report.to_dict()
    assert d["run_id"] == "rrep"
    back = goodput.RunReport.from_dict(d)
    assert back.instance == "rep-w" and back.incarnation == 3
    # pre-identity reports (no run_id keys) still load
    legacy = {k: v for k, v in d.items()
              if k not in ("run_id", "instance", "incarnation")}
    old = goodput.RunReport.from_dict(legacy)
    assert old.run_id is None and old.kind == "fit"


# --------------------------------------------------- check_budgets --fleet

def _fleet_payload(hb_age=0.5, live=2, ready=2):
    return {"time": time.time(), "live": live, "ready": ready,
            "stale_after_s": 15.0,
            "instances": [
                {"instance": "w0", "live": True, "ready": True,
                 "heartbeat_age_s": hb_age, "pushes": 3},
                {"instance": "w1", "live": True, "ready": True,
                 "heartbeat_age_s": 0.2, "pushes": 2}]}


def test_check_budgets_fleet_gate(tmp_path, capsys):
    budgets = {"fleet": {"max_heartbeat_age_s": 15.0, "min_live": 1,
                         "min_ready": 1}}
    bpath = tmp_path / "budgets.json"
    bpath.write_text(json.dumps(budgets))
    ok = tmp_path / "fleet_ok.json"
    ok.write_text(json.dumps(_fleet_payload()))
    assert check_budgets.main(["--fleet", str(ok),
                               "--budgets", str(bpath)]) == 0
    assert "budgets OK [fleet]" in capsys.readouterr().out

    # ONE stale member violates — the bound is per instance, no averaging
    bad = tmp_path / "fleet_bad.json"
    bad.write_text(json.dumps(_fleet_payload(hb_age=120.0)))
    assert check_budgets.main(["--fleet", str(bad),
                               "--budgets", str(bpath)]) == 1
    out = capsys.readouterr().out
    assert "instance 'w0'" in out and "heartbeat_age_s" in out

    # rollup bound: a fleet with nothing ready fails min_ready
    none_ready = tmp_path / "fleet_none_ready.json"
    none_ready.write_text(json.dumps(_fleet_payload(ready=0)))
    assert check_budgets.main(["--fleet", str(none_ready),
                               "--budgets", str(bpath)]) == 1
    assert "fleet ready" in capsys.readouterr().out


def test_fleet_section_committed_in_budgets_json():
    with open(os.path.join(_REPO, "BUDGETS.json")) as f:
        budgets = json.load(f)
    assert "fleet" in budgets
    assert budgets["fleet"]["max_heartbeat_age_s"] > 0
    assert budgets["identity_overhead"]["max_overhead_pct"] <= 1.0


# ------------------------------------------------------ e2e (slow tier)

@pytest.mark.slow
def test_fleet_demo_subprocess_slow(tmp_path):
    """The acceptance demo, end to end: 2 real worker processes push to
    the aggregator; the script's own asserts check the merged exposition
    and scoreboard, and the saved payload passes the fleet budget gate."""
    out = tmp_path / "fleet.json"
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "fleet_demo.py"),
         "--workers", "2", "--steps", "3", "--out", str(out)],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout
    with open(out) as f:
        fleet = json.load(f)
    assert len(fleet["instances"]) >= 2
    assert check_budgets.main(["--fleet", str(out)]) == 0
