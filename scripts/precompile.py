"""Build-time AOT precompile: populate a persistent XLA compilation
cache with every executable a deploy will need, plus a schema'd
manifest the server validates at boot.

Runs ``lower().compile()`` / the server's own warm-up seam over:

- the serving bucket ladder (every power-of-two bucket up to
  ``--max-batch``, through the same ``ReplicaSet.warm`` path a live
  boot uses — identical HLO, identical cache keys), and
- the net's jitted train step at ``--train-batch`` (``--train``).

The artifacts land in ``--cache-dir`` (the dir you point
``JAX_COMPILATION_CACHE_DIR`` / ``ModelServer(compile_cache_dir=...)`` at)
next to ``aot_manifest.json`` describing exactly what was compiled —
shapes, dtypes, ladder, mesh axes, model fingerprint. A later boot
whose config drifted from the manifest warns and falls back to lazy
compile instead of silently recompiling everything.

The model here is the serve_bench MLP (same ``--hidden`` / ``--depth``
knobs); real deployments import :mod:`deeplearning4j_tpu.compilecache.
precompile` and call ``precompile_serving`` / ``precompile_fit`` on
their own net.

Run: ``python scripts/precompile.py --cache-dir /var/cache/dl4j-xla``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cache-dir", required=True,
                    help="persistent compilation cache dir to populate")
    ap.add_argument("--max-batch", type=int, default=64,
                    help="serving bucket ladder cap (powers of two up "
                         "to this are compiled)")
    ap.add_argument("--hidden", type=int, default=4096)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--compute-dtype", default=None,
                    help="serving compute dtype override (e.g. bfloat16)")
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--train", action="store_true",
                    help="also AOT-compile the train step")
    ap.add_argument("--train-batch", type=int, default=32)
    args = ap.parse_args(argv)

    from deeplearning4j_tpu.compilecache import manifest as man
    from deeplearning4j_tpu.compilecache.precompile import (precompile_fit,
                                                            precompile_serving)
    from deeplearning4j_tpu.observability import metrics as obs
    from serve_bench import _serving_mlp

    net = _serving_mlp(args.hidden, args.depth)
    snap0 = obs.compile_snapshot()
    t0 = time.perf_counter()
    serving = precompile_serving(net, cache_dir=args.cache_dir,
                                 max_batch=args.max_batch,
                                 compute_dtype=args.compute_dtype,
                                 replicas=args.replicas)
    train = []
    if args.train:
        train.append(precompile_fit(net, cache_dir=args.cache_dir,
                                    batch=args.train_batch))
    wall = time.perf_counter() - t0
    manifest = man.build(net, serving=serving, train=train)
    path = man.save(manifest, args.cache_dir)
    delta = obs.compile_delta(snap0)
    print(json.dumps({
        "cache_dir": os.path.abspath(args.cache_dir),
        "manifest": path,
        "precompile_wall_s": round(wall, 3),
        "compiled": delta["count"],
        "compile_seconds": delta["seconds"],
        "cache_hits": delta["cache_hits"],
        "cache_misses": delta["cache_misses"],
        "serving": serving,
        "train": train,
    }, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
