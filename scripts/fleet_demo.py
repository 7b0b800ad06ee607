"""Fleet observability demo: 3 worker processes, one merged view.

**CPU control-flow drill (ROADMAP D3).** This script defaults
``JAX_PLATFORMS`` to ``cpu`` and starts several device-owning child
processes, which one TPU chip cannot host (a chip belongs to one
process). Its counts hold on any backend; its timings are CPU
wall-clock and say nothing about a TPU. The chip check is
``chip_smoke.py``.

Proves the cross-process observability plane end to end:

1. The parent starts a UIServer (the aggregator) on an ephemeral port.
2. It spawns ``--workers`` child processes (this script with
   ``--worker``), all sharing one ``DL4J_TPU_RUN_ID`` but each with its
   own ``DL4J_TPU_INSTANCE``. Every worker trains a tiny MLP for
   ``--steps`` steps and pushes ``export_snapshot()`` (full-fidelity
   metric families + identity + health) to the aggregator's
   ``POST /api/metrics_push`` — once mid-fit, once at exit.
3. The parent then fetches:
   - ``GET /metrics`` (``Accept: text/plain``) — ONE merged Prometheus
     exposition: every child sample labeled ``instance="worker-N"``,
     the aggregator folded in as its own instance, and a fleet rollup
     sample per series (``instance="fleet"``: counters summed, gauges
     last-write);
   - ``GET /api/fleet`` — the health scoreboard (liveness from
     heartbeat age, readiness, queue depth, step progress).
4. It ASSERTS the merge is correct — per-instance ``dl4j_fit_steps_total``
   samples exist for every worker and the fleet rollup equals their sum
   — and that every worker scores live on the scoreboard.

``--out fleet.json`` saves the scoreboard payload;
``scripts/check_budgets.py --fleet fleet.json`` gates it in CI
(``max_heartbeat_age_s``, ``min_live``).

Run: ``python scripts/fleet_demo.py`` (CPU, ~30s — dominated by three
XLA compiles of the tiny net). The pytest variant is the slow-marked
``tests/test_distributed_obs.py::test_fleet_demo_subprocess_slow``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ----------------------------------------------------------------- worker
def build_net(seed: int):
    import numpy as np

    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import Dense, Output
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Adam
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .list()
            .layer(Dense(n_in=12, n_out=16, activation="tanh"))
            .layer(Output(n_out=4, activation="softmax", loss="mcxent"))
            .build())
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(96, 12)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 96)]
    return MultiLayerNetwork(conf).init(), x, y


def run_worker(args) -> int:
    """One fleet member: tiny fit + snapshot pushes to the aggregator."""
    from deeplearning4j_tpu.observability import distributed as dist
    from deeplearning4j_tpu.observability import metrics as om
    om.install_runtime_metrics()
    ident = dist.get_identity()
    net, x, y = build_net(seed=17 + args.seed_offset)
    epochs = max(1, args.steps // (len(x) // 32))
    net.fit(x, y, epochs=epochs, batch_size=32)
    # push AFTER the fit so the snapshot carries real step counters;
    # a second push proves last-write-wins replacement at the aggregator.
    # attempts=5: an aggregator mid-restart costs a delayed heartbeat,
    # not a permanently dropped worker
    for _ in range(2):
        reply = dist.push_snapshot(args.push, health={"healthy": True},
                                   attempts=5)
        time.sleep(0.05)
    print(f"[worker {ident.instance}] pushed "
          f"(aggregator sees {reply['instances']} instance(s))")
    return 0


# ----------------------------------------------------------------- parent
def _fetch(url: str, accept: str = None) -> bytes:
    req = urllib.request.Request(
        url, headers={"Accept": accept} if accept else {})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.read()


def _series_values(exposition: str, family: str) -> dict:
    """{instance: value} for one family's plain (suffix-less) samples."""
    out = {}
    pat = re.compile(
        rf'^{family}\{{([^}}]*)\}} ([^\s]+)$', re.M)
    for labels, value in pat.findall(exposition):
        m = re.search(r'instance="([^"]*)"', labels)
        if m:
            out[m.group(1)] = float(value)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--steps", type=int, default=6,
                    help="fit steps per worker (default 6)")
    ap.add_argument("--out", default=None,
                    help="write the /api/fleet payload here (feed to "
                         "check_budgets.py --fleet)")
    # worker mode (internal): spawned by the parent
    ap.add_argument("--worker", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--push", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--seed-offset", type=int, default=0,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.worker is not None:
        return run_worker(args)

    from deeplearning4j_tpu.observability import distributed as dist
    from deeplearning4j_tpu.ui.server import UIServer

    run_id = dist.get_identity().run_id
    ui = UIServer(port=0)
    push_url = f"{ui.url.rstrip('/')}/api/metrics_push"
    print(f"[fleet] run_id {run_id}; aggregator at {ui.url} "
          f"(push endpoint {push_url})")

    procs = []
    for i in range(args.workers):
        env = dict(os.environ)
        env["DL4J_TPU_RUN_ID"] = run_id
        env["DL4J_TPU_INSTANCE"] = f"worker-{i}"
        env.setdefault("JAX_PLATFORMS", "cpu")
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--worker", f"worker-{i}", "--push", push_url,
             "--steps", str(args.steps), "--seed-offset", str(i)],
            env=env, cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))))
    rcs = [p.wait(timeout=300) for p in procs]
    if any(rcs):
        print(f"[fleet] FAIL — worker exit codes {rcs}")
        return 1

    # ---- merged Prometheus exposition -------------------------------
    text = _fetch(f"{ui.url.rstrip('/')}/metrics",
                  accept="text/plain").decode()
    steps = _series_values(text, "dl4j_fit_steps_total")
    expected = {f"worker-{i}" for i in range(args.workers)}
    missing = expected - set(steps)
    assert not missing, f"no per-instance samples for {sorted(missing)}"
    worker_sum = sum(v for k, v in steps.items() if k in expected)
    # the fleet rollup also folds in the aggregator's own (0-step)
    # counter; for counters the rollup is the plain sum
    rollup = steps.get("fleet")
    total = sum(v for k, v in steps.items() if k != "fleet")
    assert rollup is not None and abs(rollup - total) < 1e-9, (
        f"fleet rollup {rollup} != sum {total}")
    hb = _series_values(text, "dl4j_heartbeat_timestamp_seconds")
    assert expected <= set(hb), "workers missing heartbeat samples"
    print(f"[fleet] merged exposition: {len(text.splitlines())} lines, "
          f"per-instance steps {{" + ", ".join(
              f"{k}: {int(v)}" for k, v in sorted(steps.items())) + "}")
    for line in text.splitlines():
        if line.startswith("dl4j_fit_steps_total"):
            print("         " + line)

    # ---- health scoreboard ------------------------------------------
    fleet = json.loads(_fetch(f"{ui.url.rstrip('/')}/api/fleet"))
    by_tag = {r["instance"]: r for r in fleet["instances"]}
    assert expected <= set(by_tag), by_tag.keys()
    stale = [t for t in expected if not by_tag[t]["live"]]
    assert not stale, f"workers scored stale: {stale}"
    print(f"[fleet] scoreboard: {fleet['ready']}/{len(fleet['instances'])} "
          "ready — " + "  ".join(
              f"{t}: hb_age={by_tag[t]['heartbeat_age_s']}s "
              f"steps={by_tag[t]['steps_total']}"
              for t in sorted(expected)))

    if args.out:
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(fleet, f, indent=2)
        os.replace(tmp, args.out)
        print(f"[fleet] scoreboard saved to {args.out} "
              "(gate: scripts/check_budgets.py --fleet)")

    ui.stop()
    print(f"\n[verdict] PASS — {args.workers} workers, one merged "
          "exposition with per-instance labels + correct fleet rollup, "
          "all members live on the scoreboard")
    return 0


if __name__ == "__main__":
    sys.exit(main())
