"""SLO-aware traffic engine benchmark: open-loop flash-crowd load
through the scheduling core, with the autoscaler closing the loop.

**CPU control-flow drill (ROADMAP D3).** This script defaults
``JAX_PLATFORMS`` to ``cpu`` and starts several device-owning child
processes, which one TPU chip cannot host (a chip belongs to one
process). Its counts hold on any backend; its timings are CPU
wall-clock and say nothing about a TPU. The chip check is
``chip_smoke.py``.

The receipt behind BUDGETS.json ``traffic`` (TRAFFIC_r01.json). One
topology, one storyline — a parent-process ``FrontDoorRouter``
(front-door SchedulingCore: tenant quotas + deadline sheds) over REAL
child ``ModelServer`` processes (``--child-host`` mode, the
crosshost_serve_bench pattern), each host running the same scheduling
core against its own queue (class watermarks: batch sheds at 50%,
interactive at 100%):

- **calibrate**: a short closed-loop probe through the router at 1
  host measures the sustainable rows/sec the open-loop phases are
  scaled against (open-loop load is meaningless without the capacity
  it is a multiple of).
- **open-loop flash crowd**: ``scheduling.loadgen.TrafficModel``
  materializes a seeded arrival trace — diurnal base load, then a
  flash crowd offering >= 2x the measured sustainable rate — with
  heavy-tailed row counts, mixed tenants (one tenant quota-capped at
  the front door) and mixed classes carrying their deadline headers.
  ``OpenLoopRunner`` fires every arrival at its appointed offset and
  NEVER waits for completions: when the fleet falls behind, requests
  pile up exactly as at a real front door. The gates: interactive
  p99 stays within its deadline and its SLO attainment beats batch
  (batch sheds first — per-class 503s with X-DL4J-Shed-Class prove
  it), and the capped tenant's flood quota-sheds without starving the
  others.
- **closed-loop autoscaler**: an ``Autoscaler`` watches the router's
  live federation gauges (pushed queue depth / derived retry-after);
  when the flash crowd breaches its thresholds it spawns host 1 as a
  real subprocess WARM off the shared compile-cache dir (gated: 0
  fresh compiles on scale-up) and registers it through the router's
  own ``POST /api/hosts`` verb. ``last_reaction_s`` — first breached
  observation to capacity live — is the gated reaction time.

The receipt also publishes the attainment-vs-offered-load curve
(per-bucket offered rows/sec and per-class attainment) so the shed
order is visible over time, not just in aggregate.

Run: ``python scripts/traffic_bench.py --out TRAFFIC_r01.json`` then
``python scripts/check_budgets.py --bench TRAFFIC_r01.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------- child
def child_main(args) -> int:
    """One serving host in a pristine process: warmed ModelServer
    against the SHARED compile cache (scheduler on by default — class
    watermarks enforce batch-first shedding at this queue), heartbeats
    pushed to the router, simulated device patched in AFTER warm-up so
    the ready line's compile counts measure real XLA work."""
    import numpy as np

    from deeplearning4j_tpu.observability import metrics as obs
    from deeplearning4j_tpu.serving.server import ModelServer
    from serve_bench import _serving_mlp

    net = _serving_mlp(args.hidden, args.depth)
    server = ModelServer(net, port=0, max_batch=args.max_batch,
                         batch_window_ms=1.0, max_queue=args.max_queue,
                         compile_cache_dir=args.cache_dir,
                         push_url=args.push_url or None,
                         push_interval_s=0.5).start()
    snap = obs.compile_snapshot()
    boot = {"ready": True, "port": server.port, "url": server.url,
            "pid": os.getpid(),
            "compile_count": snap["count"],
            "cache_hits": snap["cache_hits"],
            "cache_misses": snap["cache_misses"],
            "fresh_compiles": snap["count"] - snap["cache_hits"]}

    real = server._device_forward

    def simulated(feats, _real=real):
        out = _real(feats)
        np.asarray(out)
        time.sleep(args.device_sim_ms / 1000.0)
        return out

    for rep in server.fleet.replicas:
        rep.batcher._forward = simulated

    print(json.dumps(boot), flush=True)
    try:
        for _ in sys.stdin:
            pass
    except Exception:
        pass
    server.stop()
    return 0


# ------------------------------------------------------------------ parent
def spawn_host(idx: int, cache_dir: str, push_url: str, run_id: str,
               args, timeout_s: float = 900.0) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--child-host",
           "--cache-dir", cache_dir, "--push-url", push_url or "",
           "--hidden", str(args.hidden), "--depth", str(args.depth),
           "--max-batch", str(args.max_batch),
           "--max-queue", str(args.max_queue),
           "--device-sim-ms", str(args.device_sim_ms)]
    env = {**os.environ,
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
           "DL4J_TPU_RUN_ID": run_id,
           "DL4J_TPU_INSTANCE": f"host{idx}"}
    proc = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            cwd=_REPO, env=env)
    deadline = time.monotonic() + timeout_s
    line = proc.stdout.readline()
    while line and not line.startswith("{"):
        line = proc.stdout.readline()
        if time.monotonic() > deadline:
            break
    if not line:
        proc.kill()
        err = proc.stderr.read()
        raise RuntimeError(f"host{idx} died before ready:\n{err[-2000:]}")
    boot = json.loads(line)
    return {"proc": proc, "url": boot["url"], "port": boot["port"],
            "boot": boot}


def stop_host(host: dict) -> None:
    proc = host["proc"]
    if proc.poll() is None:
        try:
            proc.stdin.close()
        except Exception:
            pass
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)


def _post_json(url: str, path: str, obj: dict, headers=None,
               timeout: float = 60.0):
    """POST returning (status, body, reply headers) — 503 and friends
    come back as data, not exceptions (the open-loop runner records
    them as outcomes)."""
    import urllib.error
    import urllib.request
    req = urllib.request.Request(
        url.rstrip("/") + path, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read() or b"{}"), \
                dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


# ------------------------------------------------------------ calibration
def calibrate(router_url: str, bodies: dict, rows: int = 4,
              threads: int = 8, seconds: float = 5.0) -> float:
    """Closed-loop probe: the sustainable rows/sec the open-loop
    phases are multiples of. Closed loop by design — it can never
    overload, so it finds the knee, not the cliff."""
    import urllib.request
    stop_at = time.monotonic() + seconds
    counts = [0] * threads

    def worker(i: int):
        while time.monotonic() < stop_at:
            req = urllib.request.Request(
                router_url + "/predict", data=bodies[rows],
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    resp.read()
                    if resp.status == 200:
                        counts[i] += rows
            except Exception:
                pass

    t0 = time.monotonic()
    ts = [threading.Thread(target=worker, args=(i,), daemon=True)
          for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=seconds + 60)
    return sum(counts) / (time.monotonic() - t0)


def _curve(rows, flash_start, duration, bucket_s=10.0):
    """Offered-load vs attainment over time — the published curve."""
    from deeplearning4j_tpu.scheduling.loadgen import attainment
    out = []
    t = 0.0
    while t < duration:
        w = (t, min(t + bucket_s, duration))
        sel = [r for r in rows if w[0] <= r["t"] < w[1]]
        point = {"t0": w[0], "t1": w[1],
                 "offered_req": len(sel),
                 "offered_rows_per_sec": round(
                     sum(r["rows"] for r in sel) / (w[1] - w[0]), 2),
                 "in_flash": w[0] >= flash_start}
        for k in ("interactive", "batch", "best_effort"):
            a = attainment(rows, k, window=w)
            point[f"attainment_{k}"] = a["attainment"]
            point[f"shed_{k}"] = sum(
                1 for r in sel if r["class"] == k and r["status"] == 503)
        out.append(point)
        t += bucket_s
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child-host", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--cache-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--push-url", default="", help=argparse.SUPPRESS)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--depth", type=int, default=2)
    # per-host ceiling = max_batch / device_sim_ms ~= 114 rows/s: small
    # enough that the shared-core client tier can offer 2.3x it, big
    # enough that the queue dynamics are real
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--device-sim-ms", type=float, default=70.0)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--duration", type=float, default=90.0,
                    help="open-loop trace length (s)")
    ap.add_argument("--flash-start", type=float, default=12.0)
    ap.add_argument("--base-frac", type=float, default=0.45,
                    help="base offered rows/s as a fraction of "
                         "sustainable")
    ap.add_argument("--flash-target", type=float, default=2.3,
                    help="flash offered rows/s over sustainable "
                         "(gate: >= 2.0)")
    ap.add_argument("--interactive-deadline-ms", type=float,
                    default=2500.0)
    ap.add_argument("--batch-deadline-ms", type=float, default=10000.0)
    ap.add_argument("--out", default=None,
                    help="artifact path (check_budgets --bench gates it)")
    args = ap.parse_args(argv)
    if args.child_host:
        return child_main(args)

    import numpy as np

    from deeplearning4j_tpu.compilecache import atomic_publish
    from deeplearning4j_tpu.scheduling import core as sched_core
    from deeplearning4j_tpu.scheduling.autoscaler import (Autoscaler,
                                                          fleet_signals)
    from deeplearning4j_tpu.scheduling.loadgen import (OpenLoopRunner,
                                                       TrafficModel,
                                                       attainment)
    from deeplearning4j_tpu.serving import FrontDoorRouter

    report: dict = {
        "config": "traffic",
        "model": f"serving_mlp 64-{args.hidden}x{args.depth}-10",
        "device_sim_ms": args.device_sim_ms,
        "max_batch": args.max_batch, "max_queue": args.max_queue,
        "seed": args.seed, "duration_s": args.duration,
        "created_unix": round(time.time(), 3),
    }

    # request bodies per row count, built once (the open-loop hot path
    # must not spend its dispatch budget on json)
    rng = np.random.default_rng(args.seed)
    bodies = {r: json.dumps(
        {"features": rng.normal(size=(r, 64)).astype(np.float32).tolist()}
    ).encode() for r in range(1, 9)}

    run_id = f"traffic-{os.getpid()}"
    # the front door: tenant 'scraper' is quota-capped HERE (2 rows/s,
    # burst 8) — its flood must shed without touching a backend
    router = FrontDoorRouter(
        stale_after_s=3.0,
        scheduler=sched_core.SchedulingCore(
            quotas={"scraper": (2.0, 8.0)})).start()
    push_url = router.url + "/api/metrics_push"
    hosts = []
    scaler = None
    try:
        with tempfile.TemporaryDirectory(prefix="dl4j_traffic_") as tmp:
            cache = os.path.join(tmp, "shared-xla-cache")

            print("== host 0: cold boot (populates the shared cache) ==",
                  file=sys.stderr)
            h0 = spawn_host(0, cache, push_url, run_id, args)
            hosts.append(h0)
            router.add_host(h0["url"])
            time.sleep(1.5)   # first heartbeats land

            print("== calibrate: closed-loop sustainable rows/sec ==",
                  file=sys.stderr)
            sustainable = calibrate(router.url, bodies)
            report["sustainable_rows_per_sec"] = round(sustainable, 2)
            print(f"   sustainable ~= {sustainable:.1f} rows/s",
                  file=sys.stderr)

            # ---- the arrival trace: scale request rate so offered
            # rows/s hits the base/flash targets (row counts are
            # heavy-tailed, so measure the trace's own mean)
            flash_dur = args.duration - args.flash_start
            mix = dict(class_mix={"interactive": 0.35, "batch": 0.5,
                                  "best_effort": 0.15},
                       tenants={"acme": 0.5, "globex": 0.35,
                                "scraper": 0.15},
                       deadlines_ms={
                           "interactive": args.interactive_deadline_ms,
                           "batch": args.batch_deadline_ms},
                       pareto_alpha=1.6, max_rows=8,
                       session_fraction=0.2, think_s=2.0)
            probe = TrafficModel(seed=args.seed, duration_s=60.0,
                                 base_rps=20.0, **mix).arrivals()
            mean_rows = sum(a.rows for a in probe) / max(1, len(probe))
            base_rps = args.base_frac * sustainable / mean_rows
            mult = args.flash_target / args.base_frac
            model = TrafficModel(
                seed=args.seed, duration_s=args.duration,
                base_rps=base_rps, diurnal_amplitude=0.25,
                diurnal_period_s=60.0,
                flash_crowds=[(args.flash_start, flash_dur, mult)],
                **mix)
            arrivals = model.arrivals()
            flash_w = (args.flash_start, args.duration)
            flash_rows = sum(a.rows for a in arrivals
                             if flash_w[0] <= a.t < flash_w[1])
            report.update({
                "arrivals_total": len(arrivals),
                "mean_rows_per_request": round(mean_rows, 3),
                "offered_base_rows_per_sec": round(
                    base_rps * mean_rows, 2),
                "offered_flash_rows_per_sec": round(
                    flash_rows / flash_dur, 2),
                "offered_over_sustainable": round(
                    flash_rows / flash_dur / sustainable, 3),
            })
            print(f"   trace: {len(arrivals)} arrivals, flash offers "
                  f"{report['offered_over_sustainable']}x sustainable",
                  file=sys.stderr)

            # ---- the autoscaler: breach -> spawn host 1 warm off the
            # shared cache -> register via POST /api/hosts (the verb)
            def scale_up() -> bool:
                if len(hosts) >= 2:
                    return False
                try:
                    h = spawn_host(len(hosts), cache, push_url, run_id,
                                   args)
                except Exception as e:
                    print(f"   scale-up spawn failed: {e}",
                          file=sys.stderr)
                    return False
                hosts.append(h)
                st, body, _ = _post_json(router.url, "/api/hosts",
                                         {"url": h["url"],
                                          "action": "add"})
                print(f"   scale-up: {h['url']} added "
                      f"(fresh_compiles="
                      f"{h['boot']['fresh_compiles']})", file=sys.stderr)
                return st == 200 and body.get("added")

            scaler = Autoscaler(
                signals_fn=lambda: fleet_signals(router),
                up=scale_up, min_size=1, max_size=2,
                up_queue_depth=args.max_queue * 0.3,
                up_retry_after_s=0.5,
                breach_n=3, up_cooldown_s=120.0, interval_s=0.5)
            scaler.start()

            # ---- the open-loop run
            import urllib.error
            import urllib.request

            def submit(a):
                req = urllib.request.Request(
                    router.url + "/predict", data=bodies[a.rows],
                    headers={"Content-Type": "application/json",
                             **a.headers()})
                try:
                    with urllib.request.urlopen(req, timeout=60) as r:
                        r.read()
                        status, hdrs = r.status, r.headers
                except urllib.error.HTTPError as e:
                    e.read()
                    status, hdrs = e.code, e.headers
                return {"status": status,
                        "shed_class": hdrs.get(
                            sched_core.SHED_CLASS_HEADER)}

            print("== open-loop run (base, then flash crowd) ==",
                  file=sys.stderr)
            runner = OpenLoopRunner(submit, arrivals, max_workers=96)
            rows = runner.run()
            scaler.stop()

            # ---- attainment + receipts
            att = {k: attainment(rows, k, window=flash_w)
                   for k in ("interactive", "batch", "best_effort")}
            report["attainment_flash"] = att
            report["attainment_full"] = {
                k: attainment(rows, k)
                for k in ("interactive", "batch", "best_effort")}
            report["curve"] = _curve(rows, args.flash_start,
                                     args.duration)
            sched_snap = router.scheduler.snapshot()
            auto_snap = scaler.snapshot()
            report["router"] = router.describe()
            report["autoscaler"] = auto_snap
            report["hosts"] = {f"host{i}": h["boot"]
                               for i, h in enumerate(hosts)}
            errors = sum(1 for r in rows if r["error"])
            sheds = sum(1 for r in rows if r["status"] == 503)
            batch_sheds = sum(1 for r in rows
                              if r["status"] == 503
                              and r["shed_class"] == "batch")
            interactive_sheds = sum(1 for r in rows
                                    if r["status"] == 503
                                    and r["shed_class"] == "interactive")
            quota_sheds = sum(
                n for key, n in sched_snap["shed_by_reason"].items()
                if key.endswith("/quota"))
            scraper = [r for r in rows if r["tenant"] == "scraper"]
            others_ok = [r for r in rows if r["tenant"] != "scraper"
                         and r["status"] == 200]
            report.update({
                "connection_errors": errors,
                "sheds_total": sheds,
                "batch_sheds": batch_sheds,
                "interactive_sheds": interactive_sheds,
                "quota_sheds": quota_sheds,
                "scraper_offered": len(scraper),
                "scraper_served": sum(1 for r in scraper
                                      if r["status"] == 200),
                "other_tenants_served": len(others_ok),
                # ---- gated scalars (BUDGETS.json "traffic") ----
                "attainment_interactive":
                    att["interactive"]["attainment"],
                "attainment_batch": att["batch"]["attainment"],
                "attainment_gap": round(
                    (att["interactive"]["attainment"] or 0.0)
                    - (att["batch"]["attainment"] or 0.0), 4),
                "interactive_p99_ms": att["interactive"]["p99_ms"],
                "scale_ups_total": auto_snap["scale_ups_total"],
                "scaleup_reaction_s": auto_snap["last_reaction_s"],
                "scaleup_fresh_compiles": (
                    hosts[1]["boot"]["fresh_compiles"]
                    if len(hosts) > 1 else None),
                "hosts_after": len(hosts),
            })
    finally:
        if scaler is not None:
            scaler.stop()
        for h in hosts:
            try:
                stop_host(h)
            except Exception:
                pass
        router.stop()

    print(json.dumps({k: v for k, v in report.items()
                      if k not in ("curve",)}, indent=1))
    if args.out:
        out = os.path.abspath(args.out)
        atomic_publish(os.path.dirname(out), os.path.basename(out),
                       report)
        print(f"wrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
