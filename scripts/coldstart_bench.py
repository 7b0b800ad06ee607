"""Cold-start benchmark: time-to-first-reply and compile cost across
three boot arms, plus the autotuned-vs-default serving schedule.

**CPU control-flow drill (ROADMAP D3).** This script defaults
``JAX_PLATFORMS`` to ``cpu`` and starts several device-owning child
processes, which one TPU chip cannot host (a chip belongs to one
process). Its counts hold on any backend; its timings are CPU
wall-clock and say nothing about a TPU. The chip check is
``chip_smoke.py``.

Each arm boots a FRESH python process (``--child`` mode) that builds
the bench MLP, starts a warmed ``ModelServer``, fires one /predict,
then replays the bucket ladder to count steady-state compiles:

- ``cold`` — empty persistent cache: every ladder bucket misses and
  pays a real XLA compile.
- ``warm`` — same cache dir, second boot: every lookup hits, the boot
  compiles ~nothing (``cache_misses == 0``, ``compile_seconds`` a
  fraction of cold's).
- ``aot``  — a cache populated by ``scripts/precompile.py`` before the
  first boot ever runs, manifest-validated at boot: the deploy-time
  story (never pay the compile online at all).

The autotune section replays a ``serve_bench --out`` trace through
``scripts/autotune_serving.py`` and reports the tuned config's
objective vs the default's (<= 1.0 by construction).

Output (``--out COLDSTART_r01.json``) carries ``"config":
"cold_start"`` with the gated numbers top-level, so
``scripts/check_budgets.py --bench COLDSTART_r01.json`` applies the
BUDGETS.json ``cold_start`` section directly.

Run: ``python scripts/coldstart_bench.py --out COLDSTART_r01.json``
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


# ------------------------------------------------------------------- child
def child_main(args) -> int:
    """One boot measurement in a pristine process: start a warmed
    server against ``--cache-dir``, reply once, replay the ladder,
    print one JSON dict on stdout."""
    import numpy as np

    from deeplearning4j_tpu.observability import metrics as obs
    from deeplearning4j_tpu.serving.server import ModelServer
    from serve_bench import _serving_mlp

    net = _serving_mlp(args.hidden, args.depth)
    server = ModelServer(net, port=0, max_batch=args.max_batch,
                         compile_cache_dir=args.cache_dir).start()
    try:
        rng = np.random.default_rng(0)
        server.predict(rng.normal(size=(1, 64)).astype(np.float32))
        ttfr = server.stats.first_reply_unix - obs.process_start_unix()
        boot = obs.compile_snapshot()
        # steady state: traffic over every ladder bucket (odd sizes so
        # each pads up) must compile nothing — the warm-up already ran
        # every shape this server will ever execute
        b = 1
        while b <= args.max_batch:
            server.predict(rng.normal(size=(b, 64)).astype(np.float32))
            b *= 2
        steady = obs.compile_delta(boot)
    finally:
        server.stop()
    rep = server.run_report
    print(json.dumps({
        "time_to_first_reply_s": round(ttfr, 3),
        "cold_start_s": rep.cold_start_s,
        "warmup_s": rep.warmup_s,
        "compile_count": rep.compile_count,
        # backend_compile_duration fires on cache HITS too (it times the
        # retrieve-or-compile), so fresh XLA compiles = events - hits
        "fresh_compiles": rep.compile_count - rep.xla_cache_hits,
        "compile_seconds": rep.compile_seconds,
        "cache_hits": rep.xla_cache_hits,
        "cache_misses": rep.xla_cache_misses,
        "steady_state_compiles": steady["count"],
        "aot_manifest_ok": server.aot_manifest_ok,
    }))
    return 0


# ------------------------------------------------------------------ parent
def _run_child(cache_dir: str, args) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--cache-dir", cache_dir, "--hidden", str(args.hidden),
           "--depth", str(args.depth), "--max-batch", str(args.max_batch)]
    env = {**os.environ, "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS",
                                                         "cpu")}
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=_REPO, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"child boot failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def _run_autotune(args) -> dict:
    """serve_bench (trace-capturing) + autotune_serving, both in this
    process; returns the report's receipt fields."""
    from deeplearning4j_tpu.compilecache import autotune as at
    from serve_bench import bench_serving

    results = bench_serving(concurrencies=(16,), requests_per_client=10,
                            max_batch=args.max_batch, batch_window_ms=2.0,
                            hidden=args.hidden, depth=args.depth)
    report = at.autotune(results)
    return {"default": report["default"], "tuned": report["tuned"],
            "objective_ratio": report["objective_ratio"],
            "trace_requests": report["trace"]["requests"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--cache-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--skip-autotune", action="store_true",
                    help="skip the serve_bench replay section")
    ap.add_argument("--out", default=None,
                    help="write the artifact here (check_budgets gates "
                         "it via --bench)")
    args = ap.parse_args(argv)
    if args.child:
        return child_main(args)

    report: dict = {"config": "cold_start",
                    "model": f"serving_mlp 64-{args.hidden}x{args.depth}-10",
                    "max_batch": args.max_batch,
                    "created_unix": round(time.time(), 3)}

    with tempfile.TemporaryDirectory(prefix="dl4j_coldstart_") as tmp:
        cache = os.path.join(tmp, "xla-cache")
        print("== arm: cold (empty cache) ==", file=sys.stderr)
        report["cold"] = _run_child(cache, args)
        print("== arm: warm (same cache, new process) ==", file=sys.stderr)
        report["warm"] = _run_child(cache, args)

        aot_cache = os.path.join(tmp, "xla-cache-aot")
        print("== arm: aot (precompile, then first boot) ==",
              file=sys.stderr)
        pre = subprocess.run(
            [sys.executable, os.path.join(_REPO, "scripts", "precompile.py"),
             "--cache-dir", aot_cache, "--hidden", str(args.hidden),
             "--depth", str(args.depth), "--max-batch", str(args.max_batch)],
            capture_output=True, text=True, timeout=900, cwd=_REPO,
            env={**os.environ,
                 "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")})
        if pre.returncode != 0:
            raise RuntimeError(f"precompile failed:\n{pre.stderr[-2000:]}")
        report["precompile"] = json.loads(pre.stdout)
        report["aot"] = _run_child(aot_cache, args)

    if not args.skip_autotune:
        print("== autotune: serve_bench trace replay ==", file=sys.stderr)
        report["autotune"] = _run_autotune(args)

    cold, warm, aot = report["cold"], report["warm"], report["aot"]
    # gated scalars, top-level so check_budgets' generic resolver sees
    # them (BUDGETS.json "cold_start" section)
    report.update({
        "cold_start_s": cold["time_to_first_reply_s"],
        "warm_cold_start_s": warm["time_to_first_reply_s"],
        "warm_boot_compile_count": warm["fresh_compiles"],
        "warm_compile_seconds_ratio": round(
            warm["compile_seconds"] / cold["compile_seconds"], 4)
        if cold["compile_seconds"] else None,
        "warm_cache_misses": warm["cache_misses"],
        "aot_cache_misses": aot["cache_misses"],
        "aot_manifest_ok": bool(aot.get("aot_manifest_ok")),
        "steady_state_compiles": max(cold["steady_state_compiles"],
                                     warm["steady_state_compiles"],
                                     aot["steady_state_compiles"]),
    })
    if "autotune" in report:
        report["autotuned_objective_ratio"] = \
            report["autotune"]["objective_ratio"]

    print(json.dumps(report, indent=2))
    if args.out:
        tmp_path = args.out + ".tmp"
        with open(tmp_path, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        os.replace(tmp_path, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
