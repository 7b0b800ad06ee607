"""Trace-stitching + SLO receipt: a stitched cross-process request
waterfall and SLO attainment over the same federated load.

**CPU control-flow drill (ROADMAP D3).** This script defaults
``JAX_PLATFORMS`` to ``cpu`` and starts several device-owning child
processes, which one TPU chip cannot host (a chip belongs to one
process). Its counts hold on any backend; its timings are CPU
wall-clock and say nothing about a TPU. The chip check is
``chip_smoke.py``.

The receipt behind BUDGETS.json ``slo`` (TRACE_SLO_r01.json). One
topology — a parent-process ``FrontDoorRouter`` federating 2 child
``ModelServer`` processes (``--child-host`` mode), each pushing
metrics snapshots WITH request-scoped span batches to the router —
two arms:

- **stitched waterfall (with failover)**: one decode session runs
  through the router under ONE client-minted ``X-DL4J-Trace-Id``;
  mid-stream the bench SIGKILLs the pinned host, so the survivor's
  re-prefill recovery spans join the same trace. The router's
  ``GET /api/trace/<id>`` must return a waterfall whose spans come
  from >= 3 instances (router + both hosts), carry derived
  ``network`` gap segments, and whose per-hop windows sum to the
  client-observed latency within ``max_waterfall_latency_gap_pct`` —
  the proof that the queue/device/network attribution adds up to what
  the client actually waited. The stream itself must stay
  bit-identical to the sequential reference (tracing changes nothing).
- **SLO attainment**: closed-loop /predict load through the router;
  the router's ``SLOEngine`` folds the hosts' pushed serving counters
  into its sliding windows and ``/api/fleet`` reports availability
  attainment / burn-rate over exactly that load.

Run: ``python scripts/trace_slo_bench.py --out TRACE_SLO_r01.json``
then ``python scripts/check_budgets.py --bench TRACE_SLO_r01.json``.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------- child
def child_main(args) -> int:
    """One serving host (crosshost_serve_bench child pattern): warmed
    ModelServer with a gpt_mini DecodeEngine, heartbeats + span batches
    pushed to the router. Decode ops are padded with a GIL-released
    sleep standing in for the device, so the waterfall's per-hop
    windows are dominated by modeled device time, not stack overhead
    (the same reason crosshost_serve_bench pads /predict)."""
    from crosshost_serve_bench import DECODE_CFG
    from deeplearning4j_tpu.serving import DecodeEngine
    from deeplearning4j_tpu.serving.server import ModelServer
    from deeplearning4j_tpu.zoo import gpt_mini
    from serve_bench import _serving_mlp

    net = _serving_mlp(args.hidden, args.depth)
    engine = DecodeEngine(gpt_mini(**DECODE_CFG), n_pages=64,
                          page_tokens=8)
    server = ModelServer(net, port=0, max_batch=args.max_batch,
                         batch_window_ms=1.0, max_queue=4096,
                         compile_cache_dir=args.cache_dir,
                         decode_engine=engine,
                         push_url=args.push_url or None,
                         push_interval_s=0.4).start()
    engine.warm()

    sim_s = args.device_sim_ms / 1000.0
    real_prefill, real_step = engine.prefill, engine.step

    def slow_prefill(sid, ids, trace_id=None):
        out = real_prefill(sid, ids, trace_id=trace_id)
        time.sleep(sim_s)
        return out

    def slow_step(sid, token, trace_id=None):
        out = real_step(sid, token, trace_id=trace_id)
        time.sleep(sim_s)
        return out

    engine.prefill, engine.step = slow_prefill, slow_step

    print(json.dumps({"ready": True, "port": server.port,
                      "url": server.url, "pid": os.getpid()}),
          flush=True)
    try:
        for _ in sys.stdin:   # parent closes stdin (or SIGKILLs us)
            pass
    except Exception:
        pass
    server.stop()
    return 0


# ------------------------------------------------------------------ parent
def spawn_host(idx: int, cache_dir: str, push_url: str, run_id: str,
               args, timeout_s: float = 900.0) -> dict:
    import subprocess

    cmd = [sys.executable, os.path.abspath(__file__), "--child-host",
           "--cache-dir", cache_dir, "--push-url", push_url or "",
           "--hidden", str(args.hidden), "--depth", str(args.depth),
           "--max-batch", str(args.max_batch),
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


class _Client:
    """Keep-alive client to the router: latency measured tightly
    around request/response, so the client-observed total and the
    router's hop windows disagree only by loopback + handler parse."""

    def __init__(self, host: str, port: int, timeout_s: float = 300.0):
        self.conn = http.client.HTTPConnection(host, port,
                                               timeout=timeout_s)

    def post(self, path: str, obj: dict, trace_id: str = None):
        from deeplearning4j_tpu.observability.distributed import (
            TRACE_HEADER)
        body = json.dumps(obj).encode()
        hdrs = {"Content-Type": "application/json"}
        if trace_id:
            hdrs[TRACE_HEADER] = trace_id
        t0 = time.perf_counter()
        self.conn.request("POST", path, body, hdrs)
        resp = self.conn.getresponse()
        data = resp.read()
        ms = (time.perf_counter() - t0) * 1e3
        return resp.status, json.loads(data or b"{}"), ms

    def close(self):
        self.conn.close()


def stitched_waterfall_arm(router, hosts, args) -> dict:
    """One traced decode session through the router, SIGKILLing the
    pinned host mid-stream; harvest /api/trace/<id> and compare its
    hop windows against the client-observed latency."""
    import numpy as np

    from crosshost_serve_bench import (DECODE_CFG, kill_host,
                                       reference_streams, _get)
    from deeplearning4j_tpu.observability.distributed import new_trace_id

    n_tokens = args.gen_tokens
    kill_after = max(1, n_tokens * 2 // 3)
    rng = np.random.default_rng(11)
    prompt = [int(t) for t in
              rng.integers(1, DECODE_CFG["vocab_size"], size=4)]
    ref = reference_streams([prompt], n_tokens)[0]

    tid = new_trace_id()
    cli = _Client(router.host, router.port)
    sid = "traced-s0"
    client_ms = 0.0
    recovered = 0
    killed = None
    try:
        st, out, ms = cli.post("/decode", {"op": "prefill", "sid": sid,
                                           "ids": prompt}, tid)
        assert st == 200, (st, out)
        client_ms += ms
        logits = np.asarray(out["logits"], np.float32)
        toks = []
        for t in range(n_tokens):
            nxt = int(np.argmax(logits))
            toks.append(nxt)
            if t == kill_after:
                # let the pinned host's span pushes land, then kill it:
                # the tail of the stream fails over and the survivor's
                # recovery spans join the SAME trace
                time.sleep(1.2)
                pinned_urls = {h.base_url
                               for h in router._affinity.values()}
                victim = next((h for h in hosts
                               if h["url"].rstrip("/") in pinned_urls),
                              hosts[0])
                kill_host(victim)
                killed = victim["url"]
            st, out, ms = cli.post("/decode", {"op": "step", "sid": sid,
                                               "token": nxt}, tid)
            assert st == 200, (st, out)
            client_ms += ms
            if out.get("recovered"):
                recovered += 1
            logits = np.asarray(out["logits"], np.float32)
        st, out, ms = cli.post("/decode", {"op": "close", "sid": sid},
                               tid)
        client_ms += ms
    finally:
        cli.close()

    # survivor span batches ride 0.4s heartbeats: poll until the trace
    # shows handler spans from both hosts (or give up after 15s)
    wf = {}
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        code, wf = _get(router.url, f"/api/trace/{tid}")
        insts = {s["instance"] for s in wf.get("segments", ())
                 if s["instance"] not in ("wire",)}
        if code == 200 and len(insts) >= 3:
            break
        time.sleep(0.5)

    segs = wf.get("segments", [])
    insts = sorted({s["instance"] for s in segs
                    if s["instance"] != "wire"})
    summary = wf.get("summary_ms", {})
    hop_ms = summary.get("router_proxy", 0.0)
    gap_pct = (abs(client_ms - hop_ms) / client_ms * 100.0
               if client_ms else None)
    survivor_insts = {s["instance"] for s in segs
                      if s["name"] == "decode_prefill"}
    return {
        "trace_id": tid,
        "tokens": n_tokens,
        "kill_after_tokens": kill_after,
        "killed_host": killed,
        "failover_recoveries": recovered,
        "bit_identical": int(toks == ref),
        "client_ms": round(client_ms, 3),
        "hop_ms": round(hop_ms, 3),
        "latency_gap_pct": round(gap_pct, 3) if gap_pct is not None
        else None,
        "instances": insts,
        "network_segments": sum(1 for s in segs
                                if s["name"] == "network"),
        "summary_ms": summary,
        "recovery_prefill_instances": sorted(survivor_insts),
        "waterfall": wf,
    }


def slo_arm(router, args) -> dict:
    """Closed-loop /predict load through the router, then the router's
    own SLO report over the hosts' pushed counters."""
    import numpy as np

    from crosshost_serve_bench import _get
    from serve_bench import _serving_mlp, run_load

    net = _serving_mlp(args.hidden, args.depth)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 64)).astype(np.float32)
    reference = np.asarray(net.output(x))

    # baseline ingest (counter deltas need two sightings per source)
    _get(router.url, "/api/fleet")
    load = run_load(router.port, x, reference, args.clients,
                    args.requests)
    if "error" in load:
        raise RuntimeError(f"predict load failed: {load['error']}")
    # let the post-load pushes land, folding the load's counters into
    # the engine's windows across a couple of polls
    slo = {}
    for _ in range(4):
        time.sleep(0.7)
        code, fleet = _get(router.url, "/api/fleet")
        slo = fleet.get("slo") or {}
        att = ((slo.get("slos") or {}).get("availability")
               or {}).get("attainment")
        if att is not None:
            break
    return {"load": {k: load.get(k) for k in
                     ("rows_per_sec", "p50_ms", "p99_ms", "errors",
                      "bit_identical")},
            "slo": slo}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child-host", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--cache-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--push-url", default="", help=argparse.SUPPRESS)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--max-batch", type=int, default=8)
    # decode ops padded to ~40ms so per-hop windows dominate the
    # client-observed latency (the gap bound measures attribution, not
    # loopback noise)
    ap.add_argument("--device-sim-ms", type=float, default=40.0)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--requests", type=int, default=15,
                    help="predict requests per client (SLO arm)")
    ap.add_argument("--gen-tokens", type=int, default=15)
    ap.add_argument("--out", default=None,
                    help="artifact path (check_budgets --bench gates it)")
    args = ap.parse_args(argv)
    if args.child_host:
        return child_main(args)

    from crosshost_serve_bench import kill_host
    from deeplearning4j_tpu.compilecache import atomic_publish
    from deeplearning4j_tpu.serving import FrontDoorRouter

    report: dict = {
        "config": "slo",
        "model": f"serving_mlp 64-{args.hidden}x{args.depth}-10 "
                 f"+ gpt_mini decode",
        "device_sim_ms": args.device_sim_ms,
        "clients": args.clients,
        "created_unix": round(time.time(), 3),
    }
    run_id = f"traceslo-{os.getpid()}"
    router = FrontDoorRouter(stale_after_s=5.0).start()
    push_url = router.url + "/api/metrics_push"
    hosts = []
    try:
        with tempfile.TemporaryDirectory(prefix="dl4j_traceslo_") as tmp:
            cache = os.path.join(tmp, "shared-xla-cache")
            for i in range(2):
                print(f"== host {i}: boot ==", file=sys.stderr)
                h = spawn_host(i, cache, push_url, run_id, args)
                hosts.append(h)
                router.add_host(h["url"])
            time.sleep(1.0)   # first pushes land

            print("== SLO arm: /predict load through the router ==",
                  file=sys.stderr)
            report["slo_arm"] = slo_arm(router, args)

            print("== waterfall arm: traced decode + failover ==",
                  file=sys.stderr)
            report["waterfall_arm"] = stitched_waterfall_arm(
                router, hosts, args)
            report["trace_store"] = router.trace_store.describe()
    finally:
        for h in hosts:
            try:
                kill_host(h)
            except Exception:
                pass
        router.stop()

    wfa = report["waterfall_arm"]
    slos = (report["slo_arm"]["slo"].get("slos") or {})
    avail = slos.get("availability") or {}
    # gated scalars, top-level so check_budgets' generic resolver sees
    # them (BUDGETS.json "slo" section)
    report.update({
        "stitched_instances": len(wfa["instances"]),
        "waterfall_latency_gap_pct": wfa["latency_gap_pct"],
        "waterfall_network_segments": wfa["network_segments"],
        "failover_trace_stitched":
            int(bool(wfa["recovery_prefill_instances"])
                and wfa["failover_recoveries"] >= 1),
        "decode_bit_identical": wfa["bit_identical"],
        "slo_availability_attainment": avail.get("attainment"),
        "slo_availability_burn_rate": avail.get("burn_rate"),
    })

    print(json.dumps({k: v for k, v in report.items()
                      if k != "waterfall_arm"}, indent=1))
    print(json.dumps({k: v for k, v in wfa.items()
                      if k != "waterfall"}, indent=1))
    if args.out:
        out = os.path.abspath(args.out)
        atomic_publish(os.path.dirname(out), os.path.basename(out),
                       report)
        print(f"wrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
