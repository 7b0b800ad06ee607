"""Cross-host serving federation benchmark: rows/sec-vs-hosts through
one FrontDoorRouter, warm-boot compile counts off the shared cache, and
bit-identical decode failover across REAL host processes.

**CPU control-flow drill (ROADMAP D3).** This script defaults
``JAX_PLATFORMS`` to ``cpu`` and starts several device-owning child
processes, which one TPU chip cannot host (a chip belongs to one
process). Its counts hold on any backend; its timings are CPU
wall-clock and say nothing about a TPU. The chip check is
``chip_smoke.py``.

The receipt behind BUDGETS.json ``cross_host_serving``
(CROSSHOST_SERVE_r01.json). Four arms, one topology — a parent-process
``FrontDoorRouter`` federating 2 child ``ModelServer`` processes
(``--child-host`` mode), every host a real subprocess with its own
/predict + /decode, pushing heartbeats to the router:

- **warm boot**: both hosts share one persistent-compile-cache dir
  (``compile_cache_dir=`` semantics); host 0 pays the fresh XLA
  compiles, host 1 must boot with ``fresh_compiles == 0`` — the PR 10
  cold/warm arms measured ACROSS hosts instead of across boots.
- **scaling**: closed-loop /predict load through the router at 1 host,
  then again after host 1 joins live (``add_host`` mid-run): the gated
  ``host_scaling_ratio`` is rows/sec(2 hosts) / rows/sec(1 host)
  through the SAME front door. Hosts simulate the accelerator exactly
  like ``serve_bench --fleet``: real (tiny) forward for row
  correctness, then a GIL-released sleep standing in for the device —
  so N host processes model N accelerator hosts on this CPU box.
- **decode failover**: sessionful greedy decode through the router's
  session-affine /decode; mid-generation the bench SIGKILLs the host
  holding the pinned sessions. The router evicts it on the connection
  error and re-pins to the survivor, whose DecodeEngine re-prefills
  from the router-held token history — every completed stream must
  match the sequential ``rnn_time_step`` reference bit for bit.
- **degraded health**: router /healthz must read ``ok`` with both
  hosts live and ``degraded`` (still 200) after the kill.

Run: ``python scripts/crosshost_serve_bench.py --out
CROSSHOST_SERVE_r01.json`` then ``python scripts/check_budgets.py
--bench CROSSHOST_SERVE_r01.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# decode model config — shared by every host AND the parent's reference
# net, so all processes compile identical programs and produce
# identical logits (gpt_mini is seed-deterministic)
DECODE_CFG = dict(vocab_size=31, width=32, n_layers=2, n_heads=2,
                  max_len=96, max_cache_len=96)


# ------------------------------------------------------------------- child
def child_main(args) -> int:
    """One serving host in a pristine process: warmed ModelServer
    (predict MLP + gpt_mini DecodeEngine) against the SHARED compile
    cache, heartbeats pushed to the router, simulated device patched in
    AFTER warm-up (so every warm-up compile is real). Prints one ready
    JSON line, then serves until stdin closes (or SIGKILL)."""
    import numpy as np

    from deeplearning4j_tpu.observability import metrics as obs
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
                         push_interval_s=0.5).start()
    engine.warm()
    snap = obs.compile_snapshot()
    # backend_compile_duration fires on cache hits too (it times the
    # retrieve-or-compile), so fresh XLA compiles = events - hits
    boot = {"ready": True, "port": server.port, "url": server.url,
            "pid": os.getpid(),
            "compile_count": snap["count"],
            "cache_hits": snap["cache_hits"],
            "cache_misses": snap["cache_misses"],
            "fresh_compiles": snap["count"] - snap["cache_hits"]}

    # the simulated accelerator (serve_bench.bench_fleet pattern): the
    # real forward keeps rows bit-identical, the GIL-released sleep is
    # the device executing the bucket — patched AFTER warm-up so the
    # compile counts above measure real XLA work
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
        for _ in sys.stdin:   # parent closes stdin (or SIGKILLs us)
            pass
    except Exception:
        pass
    server.stop()
    return 0


# ------------------------------------------------------------------ parent
def spawn_host(idx: int, cache_dir: str, push_url: str, run_id: str,
               args, timeout_s: float = 900.0) -> dict:
    """Launch one ``--child-host`` process and block for its ready
    line. Returns {proc, url, port, boot} — ``boot`` carries the
    compile receipts."""
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
        line = proc.stdout.readline()   # skip any stray warnings
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
            proc.stdin.close()   # EOF -> graceful server.stop()
        except Exception:
            pass
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)


def kill_host(host: dict) -> None:
    """SIGKILL — the host-death arm. No drain, no goodbye: pooled
    router connections see RST, exactly like a crashed machine."""
    proc = host["proc"]
    if proc.poll() is None:
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)


def _post(url: str, path: str, obj: dict, timeout: float = 120.0):
    import urllib.request
    req = urllib.request.Request(
        url.rstrip("/") + path, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _get(url: str, path: str, timeout: float = 30.0):
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(url.rstrip("/") + path,
                                    timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def reference_streams(prompts, n_tokens: int):
    """Per-session greedy reference: the sequential ``rnn_time_step``
    path on a fresh same-config net — the bit-identity oracle for
    every routed (and failed-over) decode stream."""
    import numpy as np

    from deeplearning4j_tpu.zoo import gpt_mini

    net = gpt_mini(**DECODE_CFG)
    v = DECODE_CFG["vocab_size"]

    def one_hot(tok):
        oh = np.zeros((1, 1, v), np.float32)
        oh[0, 0, tok] = 1.0
        return oh

    streams = []
    for ids in prompts:
        net.rnn_clear_previous_state()
        logits = None
        for tok in ids:
            logits = np.asarray(net.rnn_time_step(one_hot(tok)))[0, -1]
        toks = []
        for _ in range(n_tokens):
            nxt = int(np.argmax(logits))
            toks.append(nxt)
            logits = np.asarray(net.rnn_time_step(one_hot(nxt)))[0, -1]
        streams.append(toks)
    return streams


def decode_failover_arm(router, hosts, n_sessions: int = 6,
                        kill_after: int = None,
                        n_tokens: int = 18) -> dict:
    """Greedy-decode ``n_sessions`` concurrent sessions through the
    router; after every session has ``kill_after`` tokens, SIGKILL one
    host that holds pinned sessions; finish the streams on the
    survivor(s). Returns the bit-identity and affinity receipts."""
    import numpy as np

    if kill_after is None:
        # kill with a real post-kill tail: ~2/3 through the stream
        kill_after = max(1, n_tokens * 2 // 3)
    rng = np.random.default_rng(7)
    v = DECODE_CFG["vocab_size"]
    prompts = [[int(t) for t in rng.integers(1, v, size=4)]
               for _ in range(n_sessions)]
    refs = reference_streams(prompts, n_tokens)

    results = [None] * n_sessions
    recovered = [0] * n_sessions
    barrier = threading.Barrier(n_sessions + 1)

    def session(i: int):
        sid = f"bench-s{i}"
        st, out = _post(router.url, "/decode",
                        {"op": "prefill", "sid": sid, "ids": prompts[i]})
        assert st == 200, (st, out)
        logits = np.asarray(out["logits"], np.float32)
        toks = []
        for t in range(n_tokens):
            nxt = int(np.argmax(logits))
            toks.append(nxt)
            st, out = _post(router.url, "/decode",
                            {"op": "step", "sid": sid, "token": nxt})
            assert st == 200, (st, out)
            if out.get("recovered"):
                recovered[i] += 1
            logits = np.asarray(out["logits"], np.float32)
            if t + 1 == kill_after:
                barrier.wait(timeout=600)   # all sessions mid-stream
                barrier.wait(timeout=600)   # ...until the kill landed
        _post(router.url, "/decode", {"op": "close", "sid": sid})
        results[i] = toks

    threads = [threading.Thread(target=session, args=(i,), daemon=True)
               for i in range(n_sessions)]
    for t in threads:
        t.start()
    barrier.wait(timeout=600)
    # kill a host that actually holds pinned sessions (affinity spreads
    # them; either way at least one host carries some)
    pinned_urls = {h.base_url for h in router._affinity.values()}
    victim = next((h for h in hosts
                   if h["url"].rstrip("/") in pinned_urls), hosts[0])
    kill_host(victim)
    barrier.wait(timeout=600)
    for t in threads:
        t.join(timeout=600)

    done = [r for r in results if r is not None]
    identical = sum(1 for r, ref in zip(results, refs) if r == ref)
    d = router.describe()
    hits, misses = d["affinity_hits"], d["affinity_misses"]
    return {
        "sessions": n_sessions,
        "tokens_per_session": n_tokens,
        "kill_after_tokens": kill_after,
        "killed_host": victim["url"],
        "sessions_completed": len(done),
        "sessions_bit_identical": identical,
        "failover_bit_identical": round(identical / n_sessions, 4),
        "failover_recoveries": sum(recovered),
        "failovers_total": d["failovers_total"],
        "session_affinity_hit_rate": round(hits / (hits + misses), 4)
        if hits + misses else None,
        "affinity_hits": hits, "affinity_misses": misses,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child-host", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--cache-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--push-url", default="", help=argparse.SUPPRESS)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--depth", type=int, default=2)
    # sized so the HOST tier is the bottleneck even on a 1-core box:
    # per-host capacity = max_batch/device_sim_ms = 160 rows/s, well
    # under what the shared-core client+router tier can push (~550+),
    # so the 1->2 host ratio measures host scaling, not the generator
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--device-sim-ms", type=float, default=70.0)
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--requests", type=int, default=30,
                    help="requests per client per load phase")
    ap.add_argument("--sessions", type=int, default=6)
    ap.add_argument("--gen-tokens", type=int, default=18)
    ap.add_argument("--out", default=None,
                    help="artifact path (check_budgets --bench gates it)")
    args = ap.parse_args(argv)
    if args.child_host:
        return child_main(args)

    import numpy as np

    from deeplearning4j_tpu.compilecache import atomic_publish
    from deeplearning4j_tpu.serving import FrontDoorRouter
    from serve_bench import _serving_mlp, run_load

    report: dict = {
        "config": "cross_host_serving",
        "model": f"serving_mlp 64-{args.hidden}x{args.depth}-10 "
                 f"+ gpt_mini decode",
        "device_sim_ms": args.device_sim_ms,
        "max_batch": args.max_batch, "clients": args.clients,
        "created_unix": round(time.time(), 3),
    }
    # the /predict bit-identity reference (children build the SAME
    # seed-deterministic MLP)
    net = _serving_mlp(args.hidden, args.depth)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 64)).astype(np.float32)
    reference = np.asarray(net.output(x))

    run_id = f"crosshost-{os.getpid()}"
    router = FrontDoorRouter(stale_after_s=3.0).start()
    push_url = router.url + "/api/metrics_push"
    hosts = []
    try:
        with tempfile.TemporaryDirectory(
                prefix="dl4j_crosshost_") as tmp:
            cache = os.path.join(tmp, "shared-xla-cache")

            print("== host 0: cold boot (fresh compiles) ==",
                  file=sys.stderr)
            h0 = spawn_host(0, cache, push_url, run_id, args)
            hosts.append(h0)
            print("== host 1: warm boot off host 0's cache ==",
                  file=sys.stderr)
            h1 = spawn_host(1, cache, push_url, run_id, args)
            hosts.append(h1)
            report["hosts"] = {"host0": h0["boot"], "host1": h1["boot"]}

            print("== scaling: load at 1 host, then 2, same router ==",
                  file=sys.stderr)
            router.add_host(h0["url"])
            r1 = run_load(router.port, x, reference, args.clients,
                          args.requests)
            if "error" in r1:
                raise RuntimeError(f"1-host load failed: {r1['error']}")
            router.add_host(h1["url"])
            time.sleep(1.0)   # let host1's first pushes land
            r2 = run_load(router.port, x, reference, args.clients,
                          args.requests)
            if "error" in r2:
                raise RuntimeError(f"2-host load failed: {r2['error']}")
            report["scaling"] = {"hosts1": r1, "hosts2": r2}

            code, hz = _get(router.url, "/healthz")
            report["healthz_both_live"] = {"code": code,
                                           "status": hz["status"]}

            print("== decode failover: SIGKILL mid-generation ==",
                  file=sys.stderr)
            report["decode_failover"] = decode_failover_arm(
                router, hosts, n_sessions=args.sessions,
                n_tokens=args.gen_tokens)

            code, hz = _get(router.url, "/healthz")
            report["healthz_after_kill"] = {"code": code,
                                            "status": hz["status"]}
            report["router"] = router.describe()
            report["routing_table"] = router.route_table()
    finally:
        for h in hosts:
            try:
                kill_host(h)
            except Exception:
                pass
        router.stop()

    fo = report["decode_failover"]
    # gated scalars, top-level so check_budgets' generic resolver sees
    # them (BUDGETS.json "cross_host_serving" section)
    report.update({
        "host_scaling_ratio": round(
            report["scaling"]["hosts2"]["rows_per_sec"]
            / report["scaling"]["hosts1"]["rows_per_sec"], 3),
        "second_host_fresh_compiles":
            report["hosts"]["host1"]["fresh_compiles"],
        "second_host_cache_misses":
            report["hosts"]["host1"]["cache_misses"],
        "first_host_fresh_compiles":
            report["hosts"]["host0"]["fresh_compiles"],
        "session_affinity_hit_rate": fo["session_affinity_hit_rate"],
        "failover_bit_identical": fo["failover_bit_identical"],
        "failover_recoveries": fo["failover_recoveries"],
        "predict_bit_identical":
            int(report["scaling"]["hosts1"]["bit_identical"]
                and report["scaling"]["hosts2"]["bit_identical"]),
        "healthz_degraded_after_kill":
            int(report["healthz_after_kill"]["status"] == "degraded"),
    })

    print(json.dumps(report, indent=1))
    if args.out:
        out = os.path.abspath(args.out)
        atomic_publish(os.path.dirname(out), os.path.basename(out),
                       report)
        print(f"wrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
