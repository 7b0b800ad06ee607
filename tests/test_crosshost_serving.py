"""Cross-host serving federation tests (serving/router.py +
compilecache shared-dir backend): least-loaded routing with eviction +
in-flight retry, session-affine decode with bit-identical cross-host
failover, global backpressure aggregation, degraded router health, the
concurrent-configure race on a shared cache dir, the heartbeat-push
retry schedule, and the cross_host_serving budget gate (including a
demonstrable failure)."""

import json
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from deeplearning4j_tpu.compilecache import cache as ccache
from deeplearning4j_tpu.observability import distributed as dist
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from deeplearning4j_tpu.serving import (DecodeEngine, FrontDoorRouter,
                                        ModelServer, NoHostsError)
from deeplearning4j_tpu.serving.router import BACKEND_HEADER

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "scripts"))

import check_budgets  # noqa: E402  (scripts/check_budgets.py)


@pytest.fixture(autouse=True)
def _cache_off_after_each_test():
    """configure() flips process-global jax config; always turn the
    knob back off (see test_coldstart.py for the XLA segfault story)."""
    yield
    ccache.deactivate()


def _mlp(seed: int = 1):
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import Dense, Output
    conf = (NeuralNetConfiguration.builder().seed(seed).list()
            .layer(Dense(n_in=6, n_out=8, activation="relu"))
            .layer(Output(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    return MultiLayerNetwork(conf).init()


def _post(url, path, obj, timeout=60.0):
    req = urllib.request.Request(
        url.rstrip("/") + path, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read()), dict(resp.headers)


def _free_dead_port():
    """A port that was just free — connecting to it gets RST, the
    connection-level death the router must treat as eviction."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------- shared cache backend
def test_atomic_publish_and_shared_meta(tmp_path):
    d = str(tmp_path)
    path = ccache.atomic_publish(d, "entry.json", {"k": [1, 2]})
    assert json.load(open(path)) == {"k": [1, 2]}
    # no partial-write debris next to the published file
    assert not [f for f in os.listdir(d) if f.endswith(".tmp")]


def test_configure_stamps_meta_and_reconfigure_is_idempotent(tmp_path):
    d = str(tmp_path / "shared-cache")
    r1 = ccache.configure(d)
    meta = ccache.shared_meta(d)
    assert meta is not None and meta["schema"] == ccache.META_SCHEMA_VERSION
    ccache.deactivate()
    r2 = ccache.configure(d)           # second host, same mount
    assert r1 == r2
    assert ccache.shared_meta(d) == meta   # not re-stamped
    assert not [f for f in os.listdir(d) if f.endswith(".tmp")]


def test_concurrent_configure_same_dir_threads(tmp_path):
    """The satellite race pin, in-process: N concurrent configure()
    calls against one shared dir must leave exactly one valid meta and
    zero partial entries."""
    d = str(tmp_path / "raced-cache")
    barrier = threading.Barrier(8)
    metas, errors = [], []

    def worker():
        try:
            barrier.wait(timeout=30)
            os.makedirs(d, exist_ok=True)
            ccache._stamp_shared_dir(d)
            metas.append(ccache.shared_meta(d))
        except Exception as e:   # pragma: no cover - the failure mode
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors
    # every racer read a COMPLETE meta (atomic publish: no torn reads)
    assert all(m is not None and m["schema"] == ccache.META_SCHEMA_VERSION
               for m in metas)
    assert not [f for f in os.listdir(d) if f.endswith(".tmp")]


@pytest.mark.slow
def test_concurrent_configure_cross_process(tmp_path):
    """The same race across REAL processes (the NFS/GCS-mount story):
    3 hosts configure the same dir at once; all succeed, one valid
    meta, no debris."""
    d = str(tmp_path / "xproc-cache")
    code = ("import sys\n"
            "from deeplearning4j_tpu.compilecache import cache as c\n"
            f"print(c.configure({d!r}))\n")
    procs = [subprocess.Popen(
        [sys.executable, "-c", code], cwd=_REPO,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}) for _ in range(3)]
    outs = [p.communicate(timeout=300) for p in procs]
    assert all(p.returncode == 0 for p in procs), \
        [o[1][-500:] for o in outs]
    resolved = {o[0].strip() for o in outs}
    assert len(resolved) == 1
    meta = ccache.shared_meta(d)
    assert meta is not None and meta["schema"] == ccache.META_SCHEMA_VERSION
    assert not [f for f in os.listdir(d) if f.endswith(".tmp")]


# --------------------------------------------------- push retry schedule
def test_push_snapshot_retry_backoff_schedule_pinned():
    """attempts=3 against a dead target: exactly 2 sleeps, jittered
    exponential — s0 in [0.2, 0.3), s1 in [0.4, 0.6) (initial 0.2,
    factor 2, jitter 0.5), then the final failure raises."""
    sleeps = []
    with pytest.raises(OSError):
        dist.push_snapshot("http://127.0.0.1:1/api/metrics_push",
                           MetricsRegistry(), {}, timeout=0.2,
                           attempts=3, sleep_fn=sleeps.append)
    assert len(sleeps) == 2
    assert 0.2 <= sleeps[0] <= 0.3
    assert 0.4 <= sleeps[1] <= 0.6


def test_heartbeat_pusher_retries_on_by_default_and_never_raises():
    p = dist.HeartbeatPusher("http://127.0.0.1:1/api/metrics_push",
                             interval_s=0.1, timeout=0.2,
                             backoff_initial_s=0.0)
    assert p.attempts == 3   # the federation-push retry satellite
    assert p.push_once() is False       # swallowed, counted
    assert p.pushes_failed == 1
    assert p.last_error is not None


# ------------------------------------------------------------ router core
def test_router_routes_predict_bit_identical_and_spreads():
    net = _mlp()
    srvs = [ModelServer(net, port=0, replicas=1, max_batch=8,
                        max_queue=64, warmup=False).start()
            for _ in range(2)]
    router = FrontDoorRouter([s.url for s in srvs]).start()
    try:
        x = np.random.default_rng(0).normal(size=(2, 6)).astype(np.float32)
        ref = np.asarray(net.output(x))
        backends = set()
        for _ in range(8):
            st, out, hdrs = _post(router.url, "/predict",
                                  {"features": x.tolist()})
            assert st == 200
            assert np.array_equal(
                np.asarray(out["predictions"], np.float32), ref)
            backends.add(hdrs[BACKEND_HEADER])
        # round-robin on score ties spreads across both hosts
        assert backends == {s.url for s in srvs}
        code, hz = router.healthz()
        assert (code, hz["status"]) == (200, "ok")
        assert len(router.route_table()) == 2
    finally:
        router.stop()
        for s in srvs:
            s.stop()


def test_router_evicts_dead_host_retries_in_flight_and_degrades():
    net = _mlp()
    srv = ModelServer(net, port=0, replicas=1, max_batch=8,
                      max_queue=64, warmup=False).start()
    router = FrontDoorRouter().start()
    dead = router.add_host(f"http://127.0.0.1:{_free_dead_port()}")
    router.add_host(srv.url)
    try:
        x = np.random.default_rng(0).normal(size=(1, 6)).astype(np.float32)
        ref = np.asarray(net.output(x))
        # drive until the dead host gets picked (RR ties): every reply
        # must still be 200 — the in-flight request is retried on the
        # survivor, the client never sees the dead host
        for _ in range(4):
            st, out, hdrs = _post(router.url, "/predict",
                                  {"features": x.tolist()})
            assert st == 200
            assert hdrs[BACKEND_HEADER] == srv.url
            assert np.array_equal(
                np.asarray(out["predictions"], np.float32), ref)
        d = router.describe()
        assert d["evicted_total"] == 1
        assert d["retried_total"] >= 1
        assert dead.status == "dead"
        code, hz = router.healthz()
        assert (code, hz["status"]) == (200, "degraded")
    finally:
        router.stop()
        srv.stop()


def test_router_no_hosts_503_and_unhealthy():
    router = FrontDoorRouter().start()
    router.add_host(f"http://127.0.0.1:{_free_dead_port()}")
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(router.url, "/predict", {"features": [[0.0] * 6]})
        assert e.value.code == 503
        code, hz = router.healthz()
        assert code == 503 and hz["status"] == "unhealthy"
        # raw NoHostsError surfaces when the router has NO hosts at all
        empty = FrontDoorRouter()
        with pytest.raises(NoHostsError):
            empty.handle_predict(b"{}", "t")
    finally:
        router.stop()


class _Overloaded503(BaseHTTPRequestHandler):
    retry_after = "2.5"

    def log_message(self, *a):
        pass

    def do_POST(self):  # noqa: N802
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        body = b'{"error": "queue full"}'
        self.send_response(503)
        self.send_header("Retry-After", self.retry_after)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def test_router_global_backpressure_min_retry_after():
    """Every host 503s: the router sheds with Retry-After = the MINIMUM
    of the per-host derived values (soonest expected headroom)."""
    class _Fast(_Overloaded503):
        retry_after = "0.7"

    servers = []
    for handler in (_Overloaded503, _Fast):
        hs = ThreadingHTTPServer(("127.0.0.1", 0), handler)
        threading.Thread(target=hs.serve_forever, daemon=True).start()
        servers.append(hs)
    router = FrontDoorRouter(
        [f"http://127.0.0.1:{s.server_address[1]}" for s in servers]
    ).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(router.url, "/predict", {"features": [[0.0] * 6]})
        assert e.value.code == 503
        assert float(e.value.headers["Retry-After"]) == 0.7
        assert router.describe()["shed_total"] == 1
        # both hosts stay LIVE: overload is backpressure, not death
        assert all(h.status == "live" for h in router.hosts)
    finally:
        router.stop()
        for s in servers:
            s.shutdown()
            s.server_close()


def test_router_api_fleet_carries_routing_table_over_http():
    router = FrontDoorRouter().start()
    router.add_host("http://127.0.0.1:1")
    try:
        with urllib.request.urlopen(router.url + "/api/fleet",
                                    timeout=10) as resp:
            payload = json.loads(resp.read())
        assert "routing" in payload and "router" in payload
        assert payload["routing"][0]["url"] == "http://127.0.0.1:1"
        assert "requests_total" in payload["router"]
    finally:
        router.stop()


# -------------------------------------------------- cross-host decode
def _tiny_gpt():
    from deeplearning4j_tpu.zoo import gpt_mini
    return gpt_mini(vocab_size=13, width=16, n_layers=1, n_heads=2,
                    max_len=32, max_cache_len=32)


def _ref_stream(prompt, n_tokens, vocab=13):
    """Sequential rnn_time_step greedy reference on a fresh
    same-seeded net — the bit-identity oracle."""
    net = _tiny_gpt()
    net.rnn_clear_previous_state()
    logits = None
    for tok in prompt:
        oh = np.zeros((1, 1, vocab), np.float32)
        oh[0, 0, tok] = 1.0
        logits = np.asarray(net.rnn_time_step(oh))[0, -1]
    toks = []
    for _ in range(n_tokens):
        nxt = int(np.argmax(logits))
        toks.append(nxt)
        oh = np.zeros((1, 1, vocab), np.float32)
        oh[0, 0, nxt] = 1.0
        logits = np.asarray(net.rnn_time_step(oh))[0, -1]
    return toks


def test_decode_failover_bit_identical_reprefill_on_survivor():
    """Kill the pinned host mid-session: the router re-pins, the
    survivor re-prefills from the router-held token history, and the
    finished stream matches the sequential reference bit for bit.
    Each engine gets its OWN same-seeded net: StreamingKVForward owns
    the net's streaming flags, so two engines must not share one."""
    servers = [ModelServer(_tiny_gpt(), port=0, replicas=1, warmup=False,
                           decode_engine=DecodeEngine(
                               _tiny_gpt(), n_pages=16, page_tokens=8)
                           ).start() for _ in range(2)]
    router = FrontDoorRouter().start()
    handles = {s.url: router.add_host(s.url) for s in servers}
    prompt, n_tokens = [1, 4, 7], 6
    try:
        st, out, _ = _post(router.url, "/decode",
                           {"op": "prefill", "sid": "s1", "ids": prompt})
        assert st == 200
        logits = np.asarray(out["logits"], np.float32)
        toks, recovered = [], 0
        for i in range(n_tokens):
            nxt = int(np.argmax(logits))
            toks.append(nxt)
            st, out, _ = _post(router.url, "/decode",
                               {"op": "step", "sid": "s1", "token": nxt})
            assert st == 200
            recovered += bool(out.get("recovered"))
            logits = np.asarray(out["logits"], np.float32)
            if i == 1:
                # kill the pinned host: stop it AND drop the router's
                # pooled keep-alive connections, so the next proxy sees
                # a refused connect (in one process, handler threads
                # outlive httpd.shutdown(); across machines SIGKILL
                # does both — crosshost_serve_bench covers that arm)
                pinned = router._affinity["s1"]
                next(s for s in servers
                     if s.url == pinned.base_url).stop()
                pinned.close()
        assert toks == _ref_stream(prompt, n_tokens)
        assert recovered == 1                 # survivor re-prefilled
        d = router.describe()
        assert d["failovers_total"] == 1
        assert d["evicted_total"] == 1
        assert d["affinity_hits"] >= n_tokens - 1
        code, hz = router.healthz()
        assert (code, hz["status"]) == (200, "degraded")
        st, out, _ = _post(router.url, "/decode",
                           {"op": "close", "sid": "s1"})
        assert st == 200 and out["closed"] is True
    finally:
        router.stop()
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass


def test_decode_failover_with_shared_pages_and_fleetwide_close():
    """The PR 16 sharing interaction with failover: two sessions carry
    the SAME prompt, so whichever host serves both shares their prefix
    pages (refcounted). Killing a pinned host mid-stream must still
    recover bit-identically — the survivor re-prefills from the
    router-held history and re-adopts whatever pages its peer already
    published there — and the router's broadcast close must release
    every session's pool pages on every live host."""
    servers = [ModelServer(_tiny_gpt(), port=0, replicas=1, warmup=False,
                           decode_engine=DecodeEngine(
                               _tiny_gpt(), n_pages=16, page_tokens=8)
                           ).start() for _ in range(2)]
    router = FrontDoorRouter().start()
    for s in servers:
        router.add_host(s.url)
    prompt, n_tokens = [1, 4, 7, 2, 9, 5, 11, 3, 8, 6], 6
    ref = _ref_stream(prompt, n_tokens)
    sids = ["sh1", "sh2"]
    try:
        logits = {}
        for sid in sids:
            st, out, _ = _post(router.url, "/decode",
                               {"op": "prefill", "sid": sid,
                                "ids": prompt})
            assert st == 200
            logits[sid] = np.asarray(out["logits"], np.float32)
        toks = {sid: [] for sid in sids}
        killed = None
        for i in range(n_tokens):
            for sid in sids:
                nxt = int(np.argmax(logits[sid]))
                toks[sid].append(nxt)
                st, out, _ = _post(router.url, "/decode",
                                   {"op": "step", "sid": sid,
                                    "token": nxt})
                assert st == 200
                logits[sid] = np.asarray(out["logits"], np.float32)
            if i == 1:
                pinned = router._affinity[sids[0]]
                killed = next(s for s in servers
                              if s.url == pinned.base_url)
                killed.stop()
                pinned.close()
        for sid in sids:
            assert toks[sid] == ref, sid
        assert router.describe()["failovers_total"] >= 1
        survivor = next(s for s in servers if s is not killed)
        # the survivor shared the identical sessions' pages: both ran
        # there after the kill, with one prompt-page chain between them
        d = survivor.metrics()["decode"]
        assert d["sessions_live"] == 2 and d["shared_pages"] >= 1
        assert d["dedup_ratio"] > 1.0
        for sid in sids:
            st, out, _ = _post(router.url, "/decode",
                               {"op": "close", "sid": sid})
            assert st == 200 and out["closed"] is True
        # fleet-wide release: no sessions, no pages, empty shared store
        d = survivor.metrics()["decode"]
        assert d["sessions_live"] == 0 and d["pages_used"] == 0
        assert d["store_pages"] == 0
    finally:
        router.stop()
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass


def test_decode_generate_op_full_stream_with_speculation():
    """The multi-token "generate" wire op: the host runs the whole
    greedy loop (so speculation's launch savings survive the wire
    instead of paying one HTTP round-trip per token), and the router
    keeps canonical history — appending only confirmed tokens, so a
    later generate can omit ids entirely."""
    server = ModelServer(_tiny_gpt(), port=0, replicas=1, warmup=False,
                         decode_engine=DecodeEngine(
                             _tiny_gpt(), n_pages=16, page_tokens=8,
                             speculative=2, draft_net=_tiny_gpt())
                         ).start()
    router = FrontDoorRouter().start()
    router.add_host(server.url)
    prompt = [2, 5, 9]
    ref8 = _ref_stream(prompt, 8)
    try:
        st, out, _ = _post(router.url, "/decode",
                           {"op": "generate", "sid": "g1", "ids": prompt,
                            "n_tokens": 6})
        assert st == 200
        # same-seeded draft -> full accepts, and still the exact stream
        assert out["tokens"] == ref8[:6]
        assert out["speculative"] is True
        assert router._history["g1"] == prompt + ref8[:6]
        # ids omitted: the router supplies its held history, and greedy
        # determinism makes the continuation the 8-token stream's tail
        st, out2, _ = _post(router.url, "/decode",
                            {"op": "generate", "sid": "g1",
                             "n_tokens": 2})
        assert st == 200
        assert out2["tokens"] == ref8[6:]
        # an unknown session with no ids and no history is the client's
        # error, not a routing failure
        st, _, _hdrs = router.handle_decode(
            {"op": "generate", "sid": "ghost", "n_tokens": 2}, "t")
        assert st == 400
    finally:
        router.stop()
        server.stop()


def test_decode_step_unknown_session_404_and_bad_op_400():
    router = FrontDoorRouter().start()
    try:
        st, out, _hdrs = router.handle_decode(
            {"op": "step", "sid": "ghost", "token": 1}, "t")
        assert st == 404
        st, out, _hdrs = router.handle_decode({"op": "nope"}, "t")
        assert st == 400
    finally:
        router.stop()


# ------------------------------------------------------- launcher wiring
def test_fleet_launcher_exports_shared_cache_env(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    from deeplearning4j_tpu.resilience.launcher import FleetLauncher
    lead = FleetLauncher(lambda size, rank, coord: ["true"],
                         compile_cache_dir="/mnt/shared/xla")
    env = lead._worker_env(2, 0, 0)
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/mnt/shared/xla"
    # an inherited value pins the cache: the launcher never overrides it
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/pinned/outside")
    assert lead._worker_env(2, 0, 0)[
        "JAX_COMPILATION_CACHE_DIR"] == "/pinned/outside"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    # unset -> absent, so workers compile cold
    off = FleetLauncher(lambda size, rank, coord: ["true"])
    assert "JAX_COMPILATION_CACHE_DIR" not in off._worker_env(2, 0, 0)


# ----------------------------------------------------------- budget gate
def test_crosshost_budget_gate_on_committed_artifact():
    art = os.path.join(_REPO, "CROSSHOST_SERVE_r01.json")
    assert os.path.exists(art), "bench artifact must be committed"
    assert check_budgets.main(["--bench", art]) == 0


def test_crosshost_budget_gate_fails_on_doctored_bound(tmp_path, capsys):
    art = json.load(open(os.path.join(_REPO, "CROSSHOST_SERVE_r01.json")))
    art["second_host_fresh_compiles"] = 7   # warm boot that compiled
    bad = tmp_path / "doctored.json"
    bad.write_text(json.dumps(art))
    assert check_budgets.main(["--bench", str(bad)]) == 1
    assert "BUDGET VIOLATION" in capsys.readouterr().out
