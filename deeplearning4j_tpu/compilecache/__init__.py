"""Cold-start & compile-time engine (ROADMAP item 4, TVM grounding:
compilation artifacts and schedule choices are managed, measured state
— not boot-time side effects).

Three layers:

- :mod:`cache` — the persistent XLA compilation cache as a first-class
  knob: ``configure(dir)`` / jax's own ``JAX_COMPILATION_CACHE_DIR``
  (which overrides any dir passed in code) wire the cache through ``ModelServer``/``serve()``/
  ``fit``/``resilient_fit``; hit/miss traffic lands in
  ``dl4j_xla_cache_hits_total`` / ``_misses_total`` and on RunReport.
  The dir may be a SHARED mount (NFS/GCS-style): ``configure`` stamps
  it with an atomically-published marker and is concurrent-configure
  safe across processes, so a whole fleet warm-boots from one host's
  compiles (SERVING.md "Cross-host federation").
- :mod:`manifest` + :mod:`precompile` — AOT ``lower().compile()`` of
  the serving bucket ladder and both nets' train steps at BUILD time
  (scripts/precompile.py), persisting executables into the cache dir
  with a schema'd JSON manifest the server validates at boot; a
  mismatch warns and falls back to lazy compile.
- :mod:`autotune` — replay a ``serve_bench --out`` traffic trace
  offline and search the (bucket ladder, linger window) space for the
  config minimizing p99 x padding waste; the server loads the winning
  config via ``tuning_report=``.
"""

from deeplearning4j_tpu.compilecache.cache import (ENV_VAR, META_NAME,
                                                   atomic_publish, cache_dir,
                                                   configure, deactivate,
                                                   ensure_configured,
                                                   shared_meta)

__all__ = ["ENV_VAR", "META_NAME", "cache_dir", "configure", "deactivate",
           "ensure_configured", "atomic_publish", "shared_meta"]
