"""Cold-start & compile-time engine tests (compilecache/): persistent
XLA cache knob + hit/miss counters, AOT precompile artifacts and their
boot-time manifest validation, the trace-driven schedule autotuner, the
warm-up skip semantics, the per-run compile-delta seam, and the
cold_start budget gate (including a demonstrable failure)."""

import json
import os
import subprocess
import sys
import warnings

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.compilecache import autotune as at
from deeplearning4j_tpu.compilecache import cache as ccache
from deeplearning4j_tpu.compilecache import manifest as man
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import Dense, Output
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import metrics as obs
from deeplearning4j_tpu.observability.goodput import RunReport
from deeplearning4j_tpu.serving.batcher import bucket_ladder
from deeplearning4j_tpu.serving.server import ModelServer

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "scripts"))

import check_budgets  # noqa: E402  (scripts/check_budgets.py)


@pytest.fixture(autouse=True)
def _cache_off_after_each_test():
    """configure() flips process-global jax config (cache dir + zeroed
    floors). Left on, every later test's compiles would run through the
    persistent cache's serialize/deserialize path against a pytest tmp
    dir — observed to segfault XLA deep into the suite. Always turn the
    knob back off."""
    yield
    ccache.deactivate()


def _mlp(seed: int = 7):
    conf = (NeuralNetConfiguration.builder().seed(seed).list()
            .layer(Dense(n_in=4, n_out=8, activation="tanh"))
            .layer(Output(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


# ------------------------------------------------------------ bucket ladder
def test_bucket_ladder_powers_of_two_capped():
    assert bucket_ladder(2, 8) == [2, 4, 8]
    assert bucket_ladder(2, 64) == [2, 4, 8, 16, 32, 64]
    assert bucket_ladder(1, 1) == [1]
    # non-power-of-two cap: last rung is the cap itself, never above it
    assert bucket_ladder(2, 6) == [2, 4, 6]


# -------------------------------------------------------- warm-up skip pin
def test_warm_skips_buckets_already_seen_and_returns_compiled():
    net = _mlp()
    server = ModelServer(net, port=0, max_batch=8, warmup=False)
    try:
        mb = server._batcher
        assert mb.warm([(4,)]) == [2, 4, 8]       # cold: full ladder
        assert mb.warm([(4,)]) == []              # all seen: no work
        assert server.shapes_seen == {2, 4, 8}
        # explicit skip override: a pre-warm snapshot re-runs the ladder
        assert mb.warm([(4,)], skip=set()) == [2, 4, 8]
    finally:
        server._fleet.stop()


def test_warm_compile_count_pinned_via_compile_delta():
    net = _mlp(seed=11)
    server = ModelServer(net, port=0, max_batch=8, warmup=False)
    try:
        snap = obs.compile_snapshot()
        server._fleet.warm([(4,)])
        first = obs.compile_delta(snap)["count"]
        assert first == 3  # one XLA compile per ladder bucket, exactly
        snap2 = obs.compile_snapshot()
        server._fleet.warm([(4,)])
        assert obs.compile_delta(snap2)["count"] == 0  # skip = no compiles
    finally:
        server._fleet.stop()


# ------------------------------------------------- compile-delta seam pin
def test_compile_snapshot_delta_scopes_sequential_runs():
    import jax
    import jax.numpy as jnp

    snap = obs.compile_snapshot()
    assert set(snap) == {"count", "seconds", "cache_hits", "cache_misses"}
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    f(jnp.ones((5,))).block_until_ready()
    d1 = obs.compile_delta(snap)
    assert d1["count"] >= 1 and d1["seconds"] > 0
    # second run of the SAME executable: in-process jit cache, no compile
    snap2 = obs.compile_snapshot()
    f(jnp.ones((5,))).block_until_ready()
    assert obs.compile_delta(snap2)["count"] == 0
    # a pre-PR-10 baseline (no cache keys) still subtracts clean
    assert obs.compile_delta({"count": 0, "seconds": 0.0})["count"] >= 1


def test_run_report_carries_cache_and_coldstart_fields():
    fields = RunReport.__dataclass_fields__
    for f in ("xla_cache_hits", "xla_cache_misses", "cold_start_s",
              "warmup_s"):
        assert f in fields
    rep = RunReport(kind="serving", wall_s=1.0)
    d = rep.to_dict()
    assert d["xla_cache_hits"] == 0 and d["cold_start_s"] is None
    rep.cold_start_s = 2.5
    assert rep.to_dict()["cold_start_s"] == 2.5


# -------------------------------------------------------- cache configure
def test_configure_explicit_dir_and_idempotence(tmp_path, monkeypatch):
    monkeypatch.delenv(ccache.ENV_VAR, raising=False)
    assert ccache.configure(None) is None     # no library default dir
    target = str(tmp_path / "xla-cache")
    got = ccache.configure(target)
    assert got == os.path.abspath(target) and os.path.isdir(got)
    assert ccache.cache_dir() == got
    assert jax.config.jax_compilation_cache_dir == got
    # reconfiguring is allowed, and idempotent per dir
    other = str(tmp_path / "other")
    assert ccache.configure(other) == os.path.abspath(other)
    assert ccache.configure(other) == os.path.abspath(other)


def test_env_var_exported_after_jax_import_is_loud(tmp_path, monkeypatch):
    # jax latched its (unset) variable at import: a late export names a
    # dir jax will never write, so configure must not report it active
    monkeypatch.delenv(ccache.ENV_VAR, raising=False)
    monkeypatch.setenv(ccache.ENV_VAR, str(tmp_path / "late"))
    with pytest.raises(RuntimeError, match="before the process imports"):
        ccache.configure(None)
    assert not (tmp_path / "late").exists()


def test_jax_compilation_cache_dir_pins_the_cache(tmp_path):
    """The placement contract, in a real process: with jax's own
    variable exported the cache IS that dir — an explicit
    compile_cache_dir= is ignored with one log line, jax's config is
    left as jax read it, and entries land nowhere else."""
    pinned, other = str(tmp_path / "pinned"), str(tmp_path / "other")
    code = (
        "import logging, os, jax, jax.numpy as jnp\n"
        "logging.basicConfig()\n"
        "from deeplearning4j_tpu.compilecache import cache as c\n"
        f"assert c.configure({other!r}) == {pinned!r}\n"
        f"assert c.configure({other!r}) == {pinned!r}\n"
        f"assert jax.config.jax_compilation_cache_dir == {pinned!r}\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(4)).block_until_ready()\n"
        "c.deactivate()\n"
        f"assert jax.config.jax_compilation_cache_dir == {pinned!r}\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=_REPO, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", ccache.ENV_VAR: pinned})
    assert proc.returncode == 0, proc.stderr[-800:]
    assert proc.stderr.count("ignored") == 1, proc.stderr[-800:]
    assert any(f.endswith("-cache") for f in os.listdir(pinned))
    assert not os.path.exists(other)


# ----------------------------------------------------- manifest validation
def _serving_entry():
    return {"row_shapes": [[4]], "ladder": [2, 4, 8], "max_batch": 8,
            "min_batch": 2, "compute_dtype": "float32", "mesh_axes": None}


def test_manifest_round_trip_and_validation(tmp_path):
    net = _mlp()
    m = man.build(net, serving=_serving_entry())
    assert m["schema_version"] == man.SCHEMA_VERSION
    assert m["model"]["fingerprint"] == man.model_fingerprint(net)
    path = man.save(m, str(tmp_path))
    assert os.path.basename(path) == man.MANIFEST_NAME
    loaded = man.load(path)
    assert man.validate_serving(
        loaded, net, row_shapes=[(4,)], ladder=[2, 4, 8], max_batch=8,
        min_batch=2, compute_dtype="float32") == []
    # drifted config: every mismatch is named
    mis = man.validate_serving(
        loaded, net, row_shapes=[(4,)], ladder=[2, 4, 8, 16], max_batch=16,
        min_batch=2, compute_dtype="float32")
    assert any("max_batch" in s for s in mis)
    # a different model fingerprints differently
    assert man.model_fingerprint(_mlp(seed=99)) == man.model_fingerprint(
        _mlp(seed=100))  # same architecture => same HLO => same print
    wide = (NeuralNetConfiguration.builder().seed(7).list()
            .layer(Dense(n_in=4, n_out=16, activation="tanh"))
            .layer(Output(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    assert man.model_fingerprint(
        MultiLayerNetwork(wide).init()) != man.model_fingerprint(net)


def test_server_accepts_matching_manifest_and_warns_on_mismatch(tmp_path):
    net = _mlp()
    path = man.save(man.build(net, serving=_serving_entry()), str(tmp_path))
    server = ModelServer(net, port=0, max_batch=8, aot_manifest=path)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a match must NOT warn
        server.start()
    try:
        assert server.aot_manifest_ok is True
    finally:
        server.stop()
    # same manifest, drifted boot config -> RuntimeWarning + lazy fallback
    server2 = ModelServer(net, port=0, max_batch=16, aot_manifest=path)
    with pytest.warns(RuntimeWarning, match="falling back to lazy"):
        server2.start()
    try:
        assert server2.aot_manifest_ok is False
        out = server2.predict(np.zeros((3, 4), np.float32))  # still serves
        assert np.asarray(out).shape == (3, 3)
    finally:
        server2.stop()


# ------------------------------------------------------------- precompile
def test_precompile_serving_and_fit_populate_cache(tmp_path):
    from deeplearning4j_tpu.compilecache.precompile import (precompile_fit,
                                                            precompile_serving)
    cache = str(tmp_path / "cache")
    net = _mlp(seed=13)
    snap = obs.compile_snapshot()
    entry = precompile_serving(net, cache_dir=cache, max_batch=8)
    assert entry["ladder"] == [2, 4, 8]
    assert entry["row_shapes"] == [[4]]
    d = obs.compile_delta(snap)
    assert d["count"] == 3
    assert d["cache_misses"] == 3  # fresh compiles written INTO the cache
    assert len(os.listdir(cache)) >= 3
    train = precompile_fit(net, cache_dir=cache, batch=16)
    assert train == {"kind": "train_step", "net": "MultiLayerNetwork",
                     "batch": 16, "row_shapes": [[4]]}


# ---------------------------------------------------------------- autotune
def _trace_results(arrivals, max_batch=1024, window_ms=2.0):
    return {"trace": {"arrivals": arrivals, "concurrency": 8},
            "metrics": {"device_ms_by_bucket": {"2": 1.0, "4": 1.2,
                                                "8": 1.6},
                        "batch_size_hist": {"2": 50, "4": 30, "8": 20}},
            "max_batch": max_batch, "batch_window_ms": window_ms}


def test_autotune_beats_or_ties_default_on_deterministic_trace():
    arrivals = [(i * 0.002, 1) for i in range(400)]  # steady 500 req/s
    rep = at.autotune(_trace_results(arrivals))
    assert rep["config"] == "serving_autotune"
    assert rep["objective_ratio"] <= 1.0  # default is a grid point
    assert rep["tuned"]["objective"] <= rep["default"]["objective"]
    # the report is loadable as boot knobs
    cfg = at.load_tuned(rep)
    assert cfg["max_batch"] == rep["tuned"]["max_batch"]
    # grid rows are sorted best-first and carry the searched knobs
    assert rep["grid"][0] == rep["tuned"]
    with pytest.raises(ValueError):
        at.load_tuned({"schema_version": 1})
    with pytest.raises(ValueError, match="rerun"):
        at.extract_trace({"metrics": {}})


def test_simulator_respects_linger_and_padding_semantics():
    svc = lambda bucket: 1.0  # noqa: E731 — flat 1 ms service
    # two arrivals inside one linger window coalesce into one bucket-2
    # launch AT the deadline (the window is waited out)
    out = at.simulate([(0.0, 1), (0.001, 1)], max_batch=8,
                      batch_window_ms=4.0, min_batch=2, service_ms=svc)
    assert out["padding_waste_fraction"] == 0.0
    assert out["p99_ms"] == pytest.approx(5.0, abs=0.2)  # 4 linger + 1 svc
    # zero window: each arrival pads its own min bucket, no linger wait
    out0 = at.simulate([(0.0, 1), (0.01, 1)], max_batch=8,
                       batch_window_ms=0.0, min_batch=2, service_ms=svc)
    assert out0["padding_waste_fraction"] == 0.5
    assert out0["p99_ms"] == pytest.approx(1.0, abs=0.2)
    # a full bucket launches NOW, not at the window deadline
    full = at.simulate([(0.0, 4), (0.0005, 4)], max_batch=8,
                       batch_window_ms=50.0, min_batch=2, service_ms=svc)
    assert full["p99_ms"] < 10.0


def test_server_boots_with_tuning_report(tmp_path):
    rep = at.autotune(_trace_results([(i * 0.002, 1) for i in range(100)]))
    path = tmp_path / "tuning.json"
    path.write_text(json.dumps(rep))
    net = _mlp()
    server = ModelServer(net, port=0, warmup=False,
                         tuning_report=str(path))
    try:
        assert server.tuned_config == at.load_tuned(rep)
        assert server._batcher.max_batch == rep["tuned"]["max_batch"]
        assert server._batcher.batch_window_ms == \
            rep["tuned"]["batch_window_ms"]
    finally:
        server._fleet.stop()


# ------------------------------------------------------------ budget gate
def test_committed_coldstart_artifact_passes_budgets():
    artifact = os.path.join(_REPO, "COLDSTART_r01.json")
    assert os.path.exists(artifact), "COLDSTART_r01.json not committed"
    with open(artifact) as f:
        rep = json.load(f)
    assert rep["config"] == "cold_start"
    # the headline claims, straight off the committed artifact
    assert rep["warm_cache_misses"] == 0
    assert rep["warm_compile_seconds_ratio"] <= 0.5
    assert rep["steady_state_compiles"] == 0
    assert rep["autotuned_objective_ratio"] <= 1.0
    assert check_budgets.main(["--bench", artifact]) == 0


def test_cold_start_budget_demonstrably_fails(tmp_path, capsys):
    with open(os.path.join(_REPO, "BUDGETS.json")) as f:
        section = json.load(f)["cold_start"]
    # a boot that recompiled everything despite a warm cache
    bad = {"config": "cold_start", "cold_start_s": 5.0,
           "warm_cold_start_s": 5.0, "warm_boot_compile_count": 6,
           "warm_compile_seconds_ratio": 0.98, "warm_cache_misses": 6,
           "steady_state_compiles": 2, "autotuned_objective_ratio": 1.4}
    violations = check_budgets.check_report(bad, section)
    assert len(violations) >= 4
    assert any("warm_cache_misses" in v for v in violations)
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(bad))
    assert check_budgets.main(["--bench", str(path)]) == 1
    assert "BUDGET VIOLATION" in capsys.readouterr().out


# --------------------------------------------- subprocess cache round-trip
@pytest.mark.slow
def test_warm_boot_subprocess_round_trip(tmp_path):
    """Boot A (fresh process) populates the persistent cache; boot B
    (another fresh process, same dir) serves the same ladder with ZERO
    cache misses, zero fresh compiles, and zero steady-state compiles —
    the tentpole's end-to-end claim, un-fakeable across processes."""
    cache = str(tmp_path / "xla-cache")
    script = os.path.join(_REPO, "scripts", "coldstart_bench.py")

    def boot():
        out = subprocess.run(
            [sys.executable, script, "--child", "--cache-dir", cache,
             "--hidden", "32", "--depth", "2", "--max-batch", "4"],
            capture_output=True, text=True, timeout=600, cwd=_REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    a = boot()
    assert a["cache_misses"] >= 2          # cold: ladder written to disk
    assert a["steady_state_compiles"] == 0  # warm-up covered the ladder
    b = boot()
    assert b["cache_misses"] == 0
    assert b["fresh_compiles"] == 0
    assert b["steady_state_compiles"] == 0
    assert b["cache_hits"] >= a["cache_misses"]
    assert b["compile_seconds"] < a["compile_seconds"]


# --------------------------------------------- serve_bench trace plumbing
@pytest.mark.slow
def test_serve_bench_embeds_trace_and_coldstart_summary():
    import serve_bench

    report = serve_bench.bench_serving(
        concurrencies=(4,), requests_per_client=4, max_batch=8,
        batch_window_ms=1.0, hidden=32, depth=2)
    assert report["trace"]["concurrency"] == 4
    assert len(report["trace"]["arrivals"]) == 16
    assert all(len(a) == 2 for a in report["trace"]["arrivals"])
    summary = report["summary"]
    assert summary["cold_start_s"] is not None
    assert summary["warmup_s"] is not None and summary["warmup_s"] > 0
    assert report["run_report"]["warmup_s"] == summary["warmup_s"]
    # the bench file is directly autotunable
    tuned = at.autotune(report)
    assert tuned["objective_ratio"] <= 1.0
