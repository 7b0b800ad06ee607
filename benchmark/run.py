"""One cell, one run.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell by name from ``BENCHMARK.json`` (``benchmark/manifest.py``),
checks that JAX sees a TPU with at least the chips the cell asks for,
hands the cell to the runner its traffic file names, and prints the
result. Every line but the last is free-form JSON for people; the last
line of stdout is the contract's:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}[, "breakdown": {...}]}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, taken
with the profiler off. With ``--trace 1`` the last ``trace_seconds`` of
the window run under the JAX profiler and the metrics are the cell's
per-layer metrics, each computed by the reader its file names.

Exit codes: 0 a result was printed and it is correct; 1 the run failed
or its result is not correct (nothing is printed as a result when there
is none); 2 the manifest is wrong; 3 no TPU, or fewer chips than the
cell needs; 4 the program under test is not beside the benchmark.

``--rehearse`` walks the same control flow on the CPU at the tiny sizes
the data files give under ``"rehearsal"`` (Pallas in interpret mode, as
many virtual devices as the cell has chips). It says so on an earlier
line and in the last, and reports no metric: a CPU number is never
written under a device metric's name. ``--self-test`` runs
``benchmark/tests``.

Two clocks start in this file. ``startup_s`` runs from its first line to
the moment ``jax.devices()`` has answered: the interpreter, importing
jax, and the runtime bringing the chip up. That is the machine's, not
the program's, and it drifts by seconds between sets of runs of the same
code (PERF.md section 2), so it is printed beside the result
(``startup_s``, a key the driver ignores) and kept out of ``setup_s``,
where it would hide what the program adds or saves. ``setup_s`` runs
from that moment to the start of the measured window: importing the
program, its compile cache, building the net from the seed, the ring,
the warm-up, and compilation where the cache misses.
"""

from __future__ import annotations

import time

_T0 = time.time()       # start-up is counted from here

import argparse         # noqa: E402
import json             # noqa: E402
import os               # noqa: E402
import sys              # noqa: E402
from dataclasses import dataclass   # noqa: E402
from importlib import import_module  # noqa: E402
from typing import Optional         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


@dataclass
class RunContext:
    root: str
    cell: object            # manifest.Cell: name, chips, config, traffic
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t0: float               # unix time at which set-up began
    peaks: Optional[dict]


def say(**line) -> None:
    print(json.dumps(line), flush=True)


def _fail(code: int, message: str) -> int:
    print(f"benchmark: {message}", file=sys.stderr, flush=True)
    return code


def _apply_rehearsal(config: dict, traffic: dict) -> None:
    for key, override in config.get("rehearsal", {}).items():
        config[key] = {**config[key], **override}
    traffic.update(traffic.get("rehearsal", {}))


def _device_block(jax, memory_peak: int) -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": memory_peak}


def _read_layer_metrics(cell, measurement) -> dict:
    from benchmark.manifest import resolve

    metrics = {}
    for m in cell.per_layer:
        value = resolve(m["reader"])(measurement, **m["args"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--self-test", action="store_true")
    args = ap.parse_args(argv)

    if args.self_test:
        import pytest
        return int(pytest.main([os.path.join(ROOT, "benchmark", "tests"),
                                "-q", "-p", "no:cacheprovider"]))

    from benchmark import manifest
    try:
        if not args.workload:
            raise manifest.ManifestError(
                "--workload is required; BENCHMARK.json has "
                f"{manifest.workload_names(ROOT)}")
        cell = manifest.load_cell(ROOT, args.workload)
    except manifest.ManifestError as e:
        return _fail(2, str(e))
    if not os.path.isdir(os.path.join(ROOT, "deeplearning4j_tpu")):
        return _fail(4, f"no deeplearning4j_tpu package in {ROOT}: the "
                        "benchmark measures the program beside it")

    # before jax is imported: where the compile cache lives, and for a
    # rehearsal the CPU stand-ins for the chip
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DL4J_TPU_PALLAS_INTERPRET"] = "1"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")
        _apply_rehearsal(cell.config, cell.traffic)

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return _fail(3, f"jax found no device: {e}")
    dev = devices[0]
    peaks = None
    if not args.rehearse:
        if dev.platform != "tpu":
            return _fail(3, f"jax found platform {dev.platform!r} "
                            f"({dev.device_kind}), not a TPU")
        if os.environ.get("DL4J_TPU_PALLAS_INTERPRET") == "1":
            return _fail(3, "DL4J_TPU_PALLAS_INTERPRET=1: the kernels "
                            "would not be compiled")
        try:
            peaks = manifest.load_peaks(dev.device_kind)
        except manifest.UnknownDevice as e:
            return _fail(3, str(e))
    if len(devices) < cell.chips:
        return _fail(3, f"cell {cell.name!r} needs {cell.chips} chips, "
                        f"jax found {len(devices)}")
    t_live = time.time()    # the chip is up: set-up is counted from here
    startup_s = t_live - _T0

    # the persistent cache has to be on before the first net is built, or
    # the sub-second init programs are never cached (PR 21's rule)
    from deeplearning4j_tpu.compilecache import ensure_configured
    cache_dir = ensure_configured()
    seconds = args.seconds if args.seconds is not None else cell.run_seconds
    say(cell=cell.name, seed=args.seed, seconds=seconds, trace=args.trace,
        rehearsal=args.rehearse, jax=jax.__version__,
        compile_cache_dir=cache_dir, startup_s=startup_s)

    ctx = RunContext(
        root=ROOT, cell=cell, seed=args.seed,
        seconds=float(seconds), trace=bool(args.trace),
        rehearse=args.rehearse, t0=t_live, peaks=peaks)
    runner = import_module(f"benchmark.runners.{cell.traffic['kind']}")
    result = runner.run(ctx)
    say(**result["info"])

    measurement = result["measurement"]
    layer_metrics = _read_layer_metrics(cell, measurement)
    if measurement.notes:
        say(notes=measurement.notes)
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {},
            "device": _device_block(jax, result["memory_peak_bytes"])}
    if args.rehearse:
        # readings of a CPU run, under names no device metric has
        say(rehearsal=True, readings={
            f"rehearsal:{k}": v["value"] for k, v in layer_metrics.items()})
        line["rehearsal"] = True
        # at toy widths the bf16 tolerances mean little (batch norm over
        # 16 values): a rehearsal passes when the control flow completed
        print(json.dumps(line), flush=True)
        return 0
    elif args.trace:
        reduction = measurement.trace
        if reduction is None or reduction.busy_s <= 0:
            return _fail(1, "the profiler's trace holds no device plane "
                            "with a program that ran three times")
        line["metrics"] = layer_metrics
        line["device"].update(busy_s=reduction.busy_s,
                              window_s=reduction.window_s)
        line["breakdown"] = {"device_ops": reduction.top_ops(10),
                             "idle_gaps": reduction.gaps_by_cause(10)}
    else:
        for m in cell.end_to_end:
            value = result["end_to_end"].get(m["name"])
            if value is None:
                return _fail(1, f"the run produced no {m['name']}")
            line["metrics"][m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
    line["startup_s"] = startup_s
    print(json.dumps(line), flush=True)
    return 0 if result["correct"] and not result["failed"] else 1


if __name__ == "__main__":
    sys.exit(main())
