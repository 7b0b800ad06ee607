"""Persistent XLA compilation cache as a first-class runtime knob.

jax has had an on-disk compilation cache for years
(``jax_compilation_cache_dir``), but as shipped it is a config flag
buried behind two more flags that silently disable it for small
programs: entries are skipped below a 1-second compile-time floor and a
minimum serialized size. A CI-sized model compiles in milliseconds, so
the stock defaults cache *nothing* and every boot stays cold. This
module owns the knob:

- :func:`configure` points jax at a cache dir AND zeroes both floors,
  so every executable — tiny CI ladder buckets included — persists.
- **``JAX_COMPILATION_CACHE_DIR`` wins.** jax reads its own variable at
  import; where it is set, the cache IS that directory: no code path
  here sets another, and an explicit ``compile_cache_dir=`` argument is
  ignored with one log line (an operator who placed the cache from
  outside must find every entry there). Where it is unset, the dir
  comes from the explicit argument, and with neither the cache stays
  off — the library has no default dir, so a test run compiles cold.
  The entry programs (``chip_smoke.py``, ``bench.py``) export the
  variable themselves, to a fixed ``<checkout>/.jax_cache``, before
  jax is imported. Reconfiguration mid-process works (jax latches its
  cache handle on first use; we reset it).
- Hit/miss traffic is observable: jax emits
  ``/jax/compilation_cache/cache_hits`` / ``cache_misses`` monitoring
  events only while a cache is active, and observability.metrics folds
  them into ``dl4j_xla_cache_hits_total`` / ``_misses_total`` plus the
  RunReport ``xla_cache_hits``/``xla_cache_misses`` fields. A warm boot
  of an unchanged server therefore *proves* itself: misses == 0 and the
  run's ``compile_count`` ~ 0 (cache hits skip ``backend_compile``, the
  event the compile counter rides).

The cache key is the HLO module + compile options, so it is shared by
lazy jit, warm-up ladders and AOT ``lower().compile()`` — precompiling
at build time (compilecache.precompile) and serving later from the
same dir hit the identical entries.

**Shared-directory backend (cross-host).** The same dir can be a
mounted NFS/GCS-style path shared by a whole serving fleet: host A's
warm-up compiles become host B's cache hits, so only the FIRST host of
a fleet ever pays a fresh compile (measured by
``scripts/crosshost_serve_bench.py``; SERVING.md "Cross-host
federation"). What makes the dir safe to share:

- jax's file-system cache already publishes each entry via its own
  tmp+rename, so a reader never sees a partial executable;
- :func:`configure` stamps the dir with an atomically-published
  ``dl4j_cache_meta.json`` marker (:func:`atomic_publish`: unique tmp
  name per process/thread + ``os.replace``) recording schema and first
  writer — N processes configuring the same dir concurrently race
  benignly: every writer replaces a COMPLETE file, the first valid
  marker is kept, and no ``*.tmp`` turds survive;
- re-configure is idempotent per resolved dir, cross-process included
  (pinned by ``tests/test_crosshost_serving.py``).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import uuid
from typing import Optional

logger = logging.getLogger(__name__)

#: jax's own variable, consulted by :func:`configure` (fit /
#: resilient_fit / serving all reach it) — export it and every run in
#: the process, and every child it spawns, shares that one persistent
#: cache; it overrides any dir passed in code
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the shared-dir marker :func:`configure` publishes atomically — its
#: presence (and valid JSON-ness) is the "this dir is a dl4j compile
#: cache" handshake between hosts sharing the mount
META_NAME = "dl4j_cache_meta.json"
META_SCHEMA_VERSION = 1

_lock = threading.Lock()
_configured: Optional[str] = None
_ignored: set = set()  # explicit dirs already reported as overridden


def atomic_publish(directory: str, name: str, payload: dict) -> str:
    """Write ``payload`` as JSON to ``directory/name`` via the
    tmp+rename protocol shared dirs require: serialize to a tmp file
    whose name is unique per process/thread (pid + uuid — two hosts on
    one NFS mount never collide), fsync, then ``os.replace`` onto the
    final name. A concurrent reader sees either the old complete file
    or the new complete file, never a torn write; a concurrent writer
    just wins or loses the whole rename. Returns the final path."""
    final = os.path.join(directory, name)
    tmp = os.path.join(
        directory, f".{name}.{os.getpid()}.{uuid.uuid4().hex[:8]}.tmp")
    try:
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, final)
    finally:
        # a crash between write and replace must not leave tmp litter
        # for the next configure to trip over
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass
    return final


def shared_meta(path: Optional[str] = None) -> Optional[dict]:
    """The shared-dir marker of ``path`` (default: the active cache
    dir), or None when the dir is unstamped/unreadable."""
    d = path or _configured
    if not d:
        return None
    try:
        with open(os.path.join(d, META_NAME)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _stamp_shared_dir(resolved: str) -> None:
    """Publish the ``dl4j_cache_meta.json`` marker if the dir doesn't
    already carry a valid one. Concurrent-configure safe: losers of the
    publish race overwrite with an equivalent complete marker; an
    existing valid marker is left untouched (idempotent re-configure —
    the first writer's identity stays recorded); a corrupt marker is
    replaced. Never raises — a read-only shared mount still serves
    hits, it just stays unstamped."""
    if shared_meta(resolved) is not None:
        return
    try:
        from deeplearning4j_tpu.observability.distributed import \
            get_identity
        created_by = get_identity().tag
    except Exception:
        created_by = f"pid-{os.getpid()}"
    import time
    try:
        atomic_publish(resolved, META_NAME, {
            "schema": META_SCHEMA_VERSION,
            "created_unix": round(time.time(), 3),
            "created_by": created_by,
        })
    except OSError:
        pass


def cache_dir() -> Optional[str]:
    """The active persistent-cache directory, or None when cold."""
    return _configured


def _pinned_dir(path: Optional[str]) -> Optional[str]:
    """The dir ``$JAX_COMPILATION_CACHE_DIR`` pins the cache to (None
    when unset). jax latched the variable into its config at import, so
    a value exported later would name a dir jax never writes — raise
    rather than report a cache that is not there."""
    pinned = os.environ.get(ENV_VAR)
    if not pinned:
        return None
    import jax
    if jax.config.jax_compilation_cache_dir != pinned:
        raise RuntimeError(
            f"{ENV_VAR}={pinned!r} was exported after jax was imported "
            f"(jax holds {jax.config.jax_compilation_cache_dir!r}); set "
            "it in the environment before the process imports jax")
    pinned = os.path.abspath(pinned)
    if path and os.path.abspath(path) != pinned and path not in _ignored:
        _ignored.add(path)
        logger.warning("compile_cache_dir=%r ignored: %s pins the cache "
                       "to %r", path, ENV_VAR, pinned)
    return pinned


def configure(path: Optional[str] = None) -> Optional[str]:
    """Activate the persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when that is set (*path* is then
    ignored, with one log line, and jax's dir is left exactly as jax
    read it), else at *path*. Idempotent per dir; switching dirs
    mid-process resets jax's latched cache handle so the new dir takes
    effect. Returns the active dir (None when neither source names one
    — the knob stays off, nothing changes).

    Also installs the compile/cache-event listener so hit/miss counters
    are live even before the first ``install_runtime_metrics`` call.
    """
    global _configured
    pinned = _pinned_dir(path)
    resolved = pinned or (os.path.abspath(path) if path else None)
    if not resolved:
        return _configured
    with _lock:
        if _configured == resolved:
            return _configured
        os.makedirs(resolved, exist_ok=True)
        _stamp_shared_dir(resolved)
        import jax
        # stock floors (1s compile time, min serialized bytes) exist to
        # keep huge fleets from caching trivia; here they would skip
        # every CI-sized program — zero both so the cache is honest at
        # any model size (jax reads them per write: no reset needed)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        if not pinned:
            jax.config.update("jax_compilation_cache_dir", resolved)
            # jax latches its cache handle on first compile; without a
            # reset, configuring after any jit ran would silently keep
            # the old (or no) cache
            from jax._src import compilation_cache as _cc
            _cc.reset_cache()
        from deeplearning4j_tpu.observability.metrics import \
            _ensure_compile_listener
        _ensure_compile_listener()
        _configured = resolved
    return _configured


def deactivate() -> None:
    """Turn the persistent cache back off: restore jax's stock floors
    and, unless ``$JAX_COMPILATION_CACHE_DIR`` pins it, unset the dir
    and drop the latched cache handle so later compiles run cold again.
    Process-global, like :func:`configure` — meant for tear-down
    (tests, embedding hosts), not the serving hot path."""
    global _configured
    with _lock:
        if _configured is None:
            return
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        if not os.environ.get(ENV_VAR):
            jax.config.update("jax_compilation_cache_dir", None)
            from jax._src import compilation_cache as _cc
            _cc.reset_cache()
        _configured = None


def ensure_configured() -> Optional[str]:
    """Env-driven activation: a no-op unless
    ``JAX_COMPILATION_CACHE_DIR`` is set (or :func:`configure` already
    ran). The fit loops call this at run start, so exporting jax's own
    variable turns on warm boots across the whole stack."""
    return configure(None)
