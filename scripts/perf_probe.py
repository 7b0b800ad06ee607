"""Perf probe: honest step timing + XLA cost breakdown for one bench config.

Usage: python scripts/perf_probe.py resnet50 --batch 256 [--image 224]
Prints a JSON line with step_ms (min-of-k, window>=min_ms), examples/sec,
MFU from XLA cost analysis, and the top HLO categories from the compiled
module's cost analysis.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from bench import (bench_goodput_overhead, bench_host_loop,
                   bench_input_pipeline, bench_mixed_precision,
                   bench_trace_overhead, calibrated_step_time)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config", choices=["resnet50", "lenet", "char_rnn",
                                       "mnist_mlp", "resnet18", "host_loop",
                                       "trace_overhead", "goodput_overhead",
                                       "input_pipeline", "mixed_precision",
                                       "serving", "transformer",
                                       "speculative"])
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--epochs", type=int, default=4,
                    help="host_loop: timed fit epochs")
    ap.add_argument("--n-batches", type=int, default=32,
                    help="host_loop: minibatches per epoch")
    ap.add_argument("--f32", action="store_true")
    ap.add_argument("--breakdown", action="store_true")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="record the probe run in the span tracer and "
                    "export a Chrome trace-event file (open in Perfetto "
                    "or chrome://tracing)")
    ap.add_argument("--serving-results", metavar="RESULTS.json", default=None,
                    help="serving config: summarize an existing "
                    "serve_bench.py --out file instead of re-running the "
                    "load generator")
    args = ap.parse_args()

    tracer = None
    if args.trace:
        from deeplearning4j_tpu.observability.trace import Tracer, set_tracer
        tracer = Tracer(enabled=True)
        set_tracer(tracer)

    def finish(out):
        if tracer is not None:
            tracer.export_chrome_trace(args.trace)
            out["trace_file"] = args.trace
            out["trace_spans"] = len(tracer.spans())
        print(json.dumps(out))

    if args.config == "trace_overhead":
        # tracer on/off steps-per-sec guard (< 3% is the acceptance bar);
        # bench_trace_overhead manages its own tracers, so --trace here
        # only captures whatever the surrounding process recorded
        batch = args.batch if args.batch != 256 else 1024
        out = {"config": "trace_overhead"}
        out.update(bench_trace_overhead(
            batch=batch, n_batches=args.n_batches, epochs=args.epochs))
        finish(out)
        return

    if args.config == "goodput_overhead":
        # ledger on/off steps-per-sec guard: tracer stays ON in both
        # arms so the number isolates the goodput sink + FLOPs
        # derivation, not the span tracer itself (< 3% budget)
        batch = args.batch if args.batch != 256 else 1024
        out = {"config": "goodput_overhead"}
        out.update(bench_goodput_overhead(
            batch=batch, n_batches=args.n_batches, epochs=args.epochs))
        finish(out)
        return

    if args.config == "serving":
        # the serving round: either summarize a serve_bench.py --out
        # results file (--serving-results) or run the quick load
        # generator inline; the headline is the "summary" rollup
        # (p50/p99, rows/sec, coalesce ratio, padding-waste fraction)
        out = {"config": "serving"}
        if args.serving_results:
            with open(args.serving_results) as f:
                rep = json.load(f)
            out["results_file"] = args.serving_results
        else:
            from serve_bench import bench_serving
            rep = bench_serving(concurrencies=(16,), requests_per_client=10)
        out["model"] = rep.get("model")
        out.update(rep.get("summary") or {})
        for k, v in rep.items():
            if k.startswith("speedup_"):
                out[k] = v
        if rep.get("run_report"):
            rr = rep["run_report"]
            out["goodput_fraction"] = rr.get("goodput_fraction")
            out["device_s"] = rr.get("device_s")
        finish(out)
        return

    if args.config == "transformer" and args.serving_results:
        # summarize an existing serve_bench.py --decode --out receipt
        # (TRANSFORMER_r01.json) — the decode-serving half of the
        # transformer round; without --serving-results this config falls
        # through to the gpt_mini training-step probe below
        out = {"config": "transformer"}
        with open(args.serving_results) as f:
            rep = json.load(f)
        out["results_file"] = args.serving_results
        for k in ("model", "decode_tokens_per_sec", "inter_token_p50_ms",
                  "inter_token_p99_ms", "decode_bit_identical",
                  "kv_pool_occupancy", "kv_evictions", "reprefills",
                  "affinity_hit_rate", "train_mfu", "train_tokens_per_sec"):
            if k in rep:
                out[k] = rep[k]
        finish(out)
        return

    if args.config == "speculative":
        # speculative decode probe: either summarize an existing
        # serve_bench.py --decode --speculative --out receipt
        # (TRANSFORMER_r03.json) or run the bench.py fast entry inline
        # (draft-on vs draft-off tokens/sec on copy-task-trained nets)
        out = {"config": "speculative"}
        if args.serving_results:
            with open(args.serving_results) as f:
                rep = json.load(f)
            out["results_file"] = args.serving_results
        else:
            from bench import run_config
            rep = run_config("speculative")
        for k in ("model", "draft_model", "decode_tokens_per_sec",
                  "spec_off_tokens_per_sec", "spec_speedup_vs_off",
                  "spec_accept_tokens_per_step", "spec_rounds",
                  "spec_proposed", "spec_accepted", "spec_rejected",
                  "spec_bit_identical", "compile_delta_after_warm"):
            if k in rep:
                out[k] = rep[k]
        finish(out)
        return

    if args.config == "input_pipeline":
        # the datapipe round: records/sec + stall fraction through a
        # shuffle/batch/prefetch pipeline vs the bare in-memory gather,
        # and the pipeline's metrics/spans overhead (< 3% budget)
        batch = args.batch if args.batch != 256 else 1024
        out = {"config": "input_pipeline"}
        out.update(bench_input_pipeline(
            batch=batch, n_batches=args.n_batches, epochs=args.epochs))
        finish(out)
        return

    if args.config == "mixed_precision":
        # the precision round: lenet trained + served under the f32 vs
        # bf16 dtype policies — steps/sec and serving rows/sec ratios
        # (bench.bench_mixed_precision; PRECISION.md, PERF.md §10)
        out = {"config": "mixed_precision"}
        out.update(bench_mixed_precision(batch=args.batch))
        finish(out)
        return

    if args.config == "host_loop":
        # the fit-loop round: steps/sec through net.fit with the device
        # step subtracted (bench.bench_host_loop) — probes the host
        # dispatch path the async runtime pipelines, not the XLA step
        batch = args.batch if args.batch != 256 else 1024
        out = {"config": "host_loop"}
        out.update(bench_host_loop(batch=batch, n_batches=args.n_batches,
                                   epochs=args.epochs))
        finish(out)
        return

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import zoo
    from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet

    rng = np.random.default_rng(0)
    dtype = zoo.F32 if args.f32 else None
    is_graph = False

    if args.config == "resnet50":
        net = zoo.resnet50(image_size=args.image, dtype=dtype)
        x = rng.normal(size=(args.batch, args.image, args.image, 3)).astype(np.float32)
        y = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, args.batch)]
        is_graph = True
    elif args.config == "resnet18":
        net = zoo.resnet18(image_size=args.image, dtype=dtype)
        x = rng.normal(size=(args.batch, args.image, args.image, 3)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, args.batch)]
        is_graph = True
    elif args.config == "lenet":
        net = zoo.lenet(dtype=dtype)
        x = rng.normal(size=(args.batch, 28, 28, 1)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, args.batch)]
    elif args.config == "mnist_mlp":
        net = zoo.mnist_mlp(dtype=dtype)
        x = rng.normal(size=(args.batch, 784)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, args.batch)]
    elif args.config == "transformer":
        # gpt_mini training step (the bench.py `transformer` shape);
        # --seq sets the window, default batch drops to 8
        b = args.batch if args.batch != 256 else 8
        t = args.seq if args.seq != 64 else 128
        args.batch = b
        net = zoo.gpt_mini(vocab_size=80, width=256, n_layers=4,
                           n_heads=4, max_len=t, dtype=dtype)
        ids = rng.integers(0, 80, (b, t))
        x = np.eye(80, dtype=np.float32)[ids]
        y = np.eye(80, dtype=np.float32)[rng.integers(0, 80, (b, t))]
    else:
        net = zoo.char_rnn(vocab_size=80, hidden=args.hidden, n_layers=2,
                           dtype=dtype)
        ids = rng.integers(0, 80, (args.batch, args.seq))
        x = np.eye(80, dtype=np.float32)[ids]
        y = np.eye(80, dtype=np.float32)[rng.integers(0, 80, (args.batch, args.seq))]

    xd, yd = jnp.asarray(x), jnp.asarray(y)
    ds = MultiDataSet([xd], [yd]) if is_graph else DataSet(xd, yd)

    t0 = time.perf_counter()
    sec_per_step, n = calibrated_step_time(net, ds, min_window_s=0.2, scan0=10)
    total = time.perf_counter() - t0

    out = {
        "config": args.config,
        "batch": args.batch,
        "step_ms": round(1000 * sec_per_step, 3),
        "examples_per_sec": round(args.batch / sec_per_step, 1),
        "scan_len": n,
        "bench_wall_s": round(total, 1),
    }
    if args.config in ("char_rnn", "transformer"):
        out["tokens_per_sec"] = round(
            args.batch * x.shape[1] / sec_per_step, 1)

    # cost analysis of the single fused step
    try:
        it = jnp.asarray(0, jnp.int32)
        k = jax.random.PRNGKey(0)
        if is_graph:
            sargs = (net.params, net.state, net.opt_state, it,
                     {net.conf.network_inputs[0]: xd}, [yd], {}, None, k)
        else:
            sargs = (net.params, net.state, net.opt_state, it, xd, yd,
                     None, None, k)
        compiled = net._train_step.lower(*sargs).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0))
        bytes_ = float(cost.get("bytes accessed", 0.0))
        out["step_gflops"] = round(flops / 1e9, 2)
        out["step_gbytes"] = round(bytes_ / 1e9, 3)
        from deeplearning4j_tpu.utils.perf import peak_flops
        peak = peak_flops(jax.devices()[0])
        if peak and sec_per_step > 0:
            out["mfu"] = round(flops / sec_per_step / peak, 4)
            out["achieved_tflops"] = round(flops / sec_per_step / 1e12, 1)
            out["hbm_gb_per_s"] = round(bytes_ / sec_per_step / 1e9, 1)
        mem = compiled.memory_analysis()
        if mem is not None:
            out["peak_mem_gb"] = round(
                (getattr(mem, "temp_size_in_bytes", 0)
                 + getattr(mem, "argument_size_in_bytes", 0)
                 + getattr(mem, "output_size_in_bytes", 0)) / 1e9, 2)
    except Exception as e:
        out["cost_error"] = repr(e)

    finish(out)


if __name__ == "__main__":
    main()
