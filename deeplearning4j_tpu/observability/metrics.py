"""Cross-runtime metrics registry with Prometheus text exposition.

PRs 1-3 left four telemetry islands: ServingStats (JSON snapshot),
ResilienceStats (counters), TrainingStatsCollector (phase events) and
StatsListener (UI reports). This module is the single registry they all
feed, rendered two ways: the existing JSON snapshots (unchanged, for
back-compat) and Prometheus text exposition for scrapers.

Two kinds of participants:

- **Direct instruments** — ``registry.counter(...)``/``gauge``/
  ``histogram`` families with ``.labels(...)`` children, owned by the
  registry. Used for the runtime metrics that exist nowhere else
  (XLA compile count/seconds, device memory, steps/sec, dispatch lag).
- **Collectors** — callables registered with ``register_collector``
  that return metric families at render time. ServingStats and
  ResilienceStats keep their own lock-guarded counters (their JSON
  snapshots and tests stay untouched) and attach a collector view, so
  there is one source of truth and zero double bookkeeping.

Naming follows Prometheus conventions: ``dl4j_`` prefix, ``_total``
suffix on counters, base units (seconds, bytes).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from deeplearning4j_tpu.observability import trace as _trace

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricFamily", "MetricsRegistry",
    "Sample", "get_registry", "set_registry", "sample_key",
    "install_runtime_metrics", "observe_step", "observe_dispatch_lag",
    "wants_prometheus", "PROMETHEUS_CONTENT_TYPE",
]

PROMETHEUS_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


def wants_prometheus(accept: str, query: str = "") -> bool:
    """/metrics content negotiation: Prometheus text when the client
    asks for it (scrapers send ``Accept: text/plain`` or an openmetrics
    type, or ``?format=prometheus`` forces it); JSON otherwise — the
    pre-existing payload stays the default for ``Accept: */*``."""
    if "format=prometheus" in (query or ""):
        return True
    a = (accept or "").lower()
    return "text/plain" in a or "openmetrics" in a

_VALID_KINDS = ("counter", "gauge", "histogram")

DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                   1.0, 2.5, 5.0, 10.0, float("inf"))


def _escape_label_value(v: str) -> str:
    # Exposition-format escaping: backslash, double-quote, newline.
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _escape_help(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace("\n", "\\n")


def sample_key(name: str, labels: Optional[Dict[str, str]] = None,
               suffix: str = "") -> str:
    """The canonical identity of one sample: exactly the series string
    the exposition format renders (`name{k="escaped"}`), labels sorted,
    values exposition-escaped. Both the Prometheus renderer and the
    federation JSON wire format key samples by this, so a label value
    containing `"` or a newline can never be encoded two different ways
    on the two paths."""
    if not labels:
        return f"{name}{suffix}"
    inner = ",".join(f'{k}="{_escape_label_value(v)}"'
                     for k, v in sorted(labels.items()))
    return f"{name}{suffix}{{{inner}}}"


def _fmt_value(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    if v == float("-inf"):
        return "-Inf"
    if v != v:  # NaN
        return "NaN"
    if isinstance(v, float) and v.is_integer():
        return str(int(v))
    return repr(float(v)) if isinstance(v, float) else str(v)


class Sample(Tuple):
    """(suffix, labels, value) — suffix is appended to the family name
    ("" for the plain sample, "_bucket"/"_sum"/"_count" for histograms)."""

    def __new__(cls, suffix: str, labels: Dict[str, str], value: float):
        return super().__new__(cls, (suffix, labels, value))

    @property
    def suffix(self):
        return self[0]

    @property
    def labels(self):
        return self[1]

    @property
    def value(self):
        return self[2]


class MetricFamily:
    """One named metric + HELP/TYPE + its samples. Collectors return
    lists of these; direct instruments render themselves into these."""

    def __init__(self, name: str, kind: str, help: str,
                 samples: Optional[List[Sample]] = None):
        if kind not in _VALID_KINDS:
            raise ValueError(f"metric kind must be one of {_VALID_KINDS}")
        self.name = name
        self.kind = kind
        self.help = help
        self.samples: List[Sample] = samples if samples is not None else []

    def add(self, value: float, labels: Optional[Dict[str, str]] = None,
            suffix: str = ""):
        self.samples.append(Sample(suffix, labels or {}, value))
        return self

    def render(self) -> str:
        lines = [f"# HELP {self.name} {_escape_help(self.help)}",
                 f"# TYPE {self.name} {self.kind}"]
        for s in self.samples:
            lines.append(f"{sample_key(self.name, s.labels, s.suffix)} "
                         f"{_fmt_value(s.value)}")
        return "\n".join(lines)

    def to_json(self):
        if len(self.samples) == 1 and not self.samples[0].labels \
                and not self.samples[0].suffix:
            return self.samples[0].value
        return [{"labels": s.labels, "value": s.value,
                 **({"suffix": s.suffix} if s.suffix else {})}
                for s in self.samples]


class _Child:
    """One labeled child of a family; value updates are lock-guarded by
    the owning registry's lock (coarse, but these are cold-ish paths —
    the span tracer owns the per-step hot path)."""

    __slots__ = ("_lock", "_value")

    def __init__(self, lock):
        self._lock = lock
        self._value = 0.0


class _CounterChild(_Child):
    def inc(self, amount: float = 1.0):
        if amount < 0:
            raise ValueError("counters only go up")
        with self._lock:
            self._value += amount

    @property
    def value(self):
        return self._value


class _GaugeChild(_Child):
    __slots__ = ("_fn",)

    def __init__(self, lock):
        super().__init__(lock)
        self._fn = None

    def set(self, value: float):
        with self._lock:
            self._value = float(value)

    def inc(self, amount: float = 1.0):
        with self._lock:
            self._value += amount

    def dec(self, amount: float = 1.0):
        self.inc(-amount)

    def set_function(self, fn: Callable[[], float]):
        """Lazily evaluated at render time (queue depths, clock-derived
        rates)."""
        self._fn = fn

    @property
    def value(self):
        if self._fn is not None:
            try:
                return float(self._fn())
            except Exception:
                return float("nan")
        return self._value


class _HistogramChild:
    __slots__ = ("_lock", "_buckets", "_counts", "_sum", "_count")

    def __init__(self, lock, buckets):
        self._lock = lock
        self._buckets = buckets
        self._counts = [0] * len(buckets)
        self._sum = 0.0
        self._count = 0

    def observe(self, value: float):
        with self._lock:
            self._sum += value
            self._count += 1
            # per-bucket counts; collect() accumulates into the
            # cumulative le-series the exposition format wants
            for i, b in enumerate(self._buckets):
                if value <= b:
                    self._counts[i] += 1
                    break


class _Family:
    def __init__(self, registry, name, kind, help, labelnames, buckets=None):
        self._registry = registry
        self.name = name
        self.kind = kind
        self.help = help
        self.labelnames = tuple(labelnames)
        self.buckets = buckets
        self._children: Dict[Tuple[str, ...], object] = {}
        # Label-less families get one implicit child so counter.inc()
        # works without .labels().
        if not self.labelnames:
            self._children[()] = self._make_child()

    def _make_child(self):
        lock = self._registry._lock
        if self.kind == "counter":
            return _CounterChild(lock)
        if self.kind == "gauge":
            return _GaugeChild(lock)
        return _HistogramChild(lock, self.buckets)

    def labels(self, **kv):
        if set(kv) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: expected labels {self.labelnames}, "
                f"got {tuple(kv)}")
        key = tuple(str(kv[n]) for n in self.labelnames)
        with self._registry._lock:
            child = self._children.get(key)
            if child is None:
                child = self._make_child()
                self._children[key] = child
        return child

    # label-less convenience passthroughs
    def inc(self, amount: float = 1.0):
        self._children[()].inc(amount)

    def set(self, value: float):
        self._children[()].set(value)

    def set_function(self, fn):
        self._children[()].set_function(fn)

    def observe(self, value: float):
        self._children[()].observe(value)

    @property
    def value(self):
        return self._children[()].value

    def collect(self) -> MetricFamily:
        fam = MetricFamily(self.name, self.kind, self.help)
        with self._registry._lock:
            items = list(self._children.items())
        for key, child in items:
            labels = dict(zip(self.labelnames, key))
            if self.kind == "histogram":
                cumulative = 0
                for b, c in zip(child._buckets, child._counts):
                    cumulative += c
                    fam.add(cumulative,
                            {**labels, "le": _fmt_value(b)}, "_bucket")
                fam.add(child._sum, labels, "_sum")
                fam.add(child._count, labels, "_count")
            else:
                fam.add(child.value, labels)
        return fam


# Public aliases so isinstance/typing reads naturally downstream.
Counter = Gauge = Histogram = _Family


class MetricsRegistry:
    """The central registry: direct instrument families + render-time
    collectors, rendered as Prometheus text or a JSON snapshot."""

    def __init__(self):
        self._lock = threading.RLock()
        self._families: Dict[str, _Family] = {}
        self._collectors: List[Callable[[], Sequence[MetricFamily]]] = []

    # ----------------------------------------------------------- instruments
    def _family(self, name, kind, help, labelnames, buckets=None):
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if fam.kind != kind or fam.labelnames != tuple(labelnames):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{fam.kind}{fam.labelnames}")
                return fam
            fam = _Family(self, name, kind, help, labelnames, buckets)
            self._families[name] = fam
            return fam

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()) -> _Family:
        return self._family(name, "counter", help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()) -> _Family:
        return self._family(name, "gauge", help, labelnames)

    def histogram(self, name: str, help: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> _Family:
        buckets = tuple(buckets)
        if not buckets or buckets[-1] != float("inf"):
            buckets = buckets + (float("inf"),)
        return self._family(name, "histogram", help, labelnames, buckets)

    # ------------------------------------------------------------ collectors
    def register_collector(self, fn: Callable[[], Sequence[MetricFamily]]):
        with self._lock:
            if fn not in self._collectors:
                self._collectors.append(fn)
        return fn

    def unregister_collector(self, fn):
        with self._lock:
            try:
                self._collectors.remove(fn)
            except ValueError:
                pass

    # -------------------------------------------------------------- renderers
    def collect(self) -> List[MetricFamily]:
        with self._lock:
            families = list(self._families.values())
            collectors = list(self._collectors)
        out = [f.collect() for f in families]
        for fn in collectors:
            try:
                out.extend(fn())
            except Exception:
                # A broken collector must not take down the scrape
                # endpoint; its series simply go missing.
                continue
        return out

    def render_prometheus(self) -> str:
        return "\n".join(f.render() for f in self.collect()) + "\n"

    def snapshot(self) -> dict:
        """JSON view: {name: value | [{labels, value}...]}."""
        return {f.name: f.to_json() for f in self.collect()}


# --------------------------------------------------------------------------
# process-global registry
# --------------------------------------------------------------------------

_GLOBAL = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return _GLOBAL


def set_registry(registry: MetricsRegistry) -> MetricsRegistry:
    """Swap the process-global registry (tests). Returns the previous
    one. Runtime metrics (compile/memory/steps) re-install themselves
    into the new registry on next touch."""
    global _GLOBAL, _RUNTIME_INSTALLED_ON
    prev, _GLOBAL = _GLOBAL, registry
    with _runtime_lock:
        _RUNTIME_INSTALLED_ON = None
    return prev


# --------------------------------------------------------------------------
# runtime metrics: XLA compile events, device memory, async-loop rates
# --------------------------------------------------------------------------
#
# Compile accounting rides jax.monitoring's event-duration stream:
# every backend compile fires '/jax/core/compile/backend_compile_duration'
# (a user-visible jit may fire several — internal jits count too, which
# is exactly what a "are we recompiling?" alarm wants). The listener is
# registered once per process; jax.monitoring has no unregister API.

_runtime_lock = threading.Lock()
# Stamped at module import — the standard Prometheus process-identity
# anchor; the federation's health scoreboard keys heartbeat age off the
# companion dl4j_heartbeat_timestamp_seconds rendered per scrape.
_PROCESS_START_TIME = time.time()
_COMPILE = {"count": 0, "seconds": 0.0}
# persistent-compilation-cache traffic (compilecache/): hits are
# executables deserialized from the cache dir instead of compiled,
# misses are fresh compiles written INTO the cache. Both stay 0 when no
# cache dir is configured — jax only emits the events while a cache is
# active, which is exactly the "is the knob on and working" signal.
_CACHE = {"hits": 0, "misses": 0}
_COMPILE_LISTENER_ON = False
_RUNTIME_INSTALLED_ON: Optional[MetricsRegistry] = None
_STEPS = {"count": 0.0, "per_sec": 0.0, "dispatch_lag_s": 0.0}
# memory high-water marks, updated on every watermark sample
# (render-time scrape, observe_rate, goodput run start/end — never on
# the per-step hot path): device key -> peak bytes_in_use seen (arrays),
# and device key + RESERVED_SUFFIX -> largest bytes_in_use +
# bytes_reserved of one sample (arrays and the running program's scratch)
_MEM_PEAK: dict = {}
RESERVED_SUFFIX = "+reserved"


# The stage account: every second jax spends making a program, booked
# to a stage, to the span that caused it and to the program. jax wraps
# tracing, lowering and backend compilation in one context manager
# (jax/_src/dispatch.py log_elapsed_time) that sends a scalar event with
# the unix start time on entry and a duration event on exit, both on the
# calling thread, with the program's name as fun_name.
_STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # "cache_load" instead where a /cache_hits event came inside it
    "/jax/core/compile/backend_compile_duration": "compile",
}
_STAGE_SECONDS: Dict[Tuple[str, str], float] = {}     # (stage, owner)
_STAGE_PROGRAMS: Dict[Tuple[str, str], int] = {}      # (stage, owner)
_PROGRAM_SECONDS: Dict[Tuple[str, str], dict] = {}    # (program, owner)
_PROGRAM_SPANS: Dict[str, list] = {}    # span name -> [seconds, count]


class _OpenStages(threading.local):
    """The stage intervals open on this thread that will be booked:
    ``stack`` holds ``[stage, program, start_unix, compile seconds
    inside, cache hit]``, at most one trace or lowering (the outermost)
    and one backend compile inside it; ``nested`` counts the tracings
    and lowerings open inside the outermost one."""

    def __init__(self):
        self.stack = []
        self.nested = 0


_OPEN_STAGES = _OpenStages()


def _on_jax_scalar(event: str, value, **kw):
    stage = _STAGE_OF_EVENT.get(event)
    if stage is None:
        return
    open_ = _OPEN_STAGES
    if open_.stack and stage != "compile":
        # an inner jit of a traced function reports a trace of its own,
        # and a lowering rule traces: the outermost interval holds both
        open_.nested += 1
    else:
        open_.stack.append([stage, kw.get("fun_name", ""), value, 0.0, False])


def _on_jax_event_duration(event: str, duration: float, **kw):
    stage = _STAGE_OF_EVENT.get(event)
    if stage is None:
        return
    open_ = _OPEN_STAGES
    stack = open_.stack
    if stage == "compile":
        with _runtime_lock:
            _COMPILE["count"] += 1
            _COMPILE["seconds"] += duration
    elif open_.nested:
        open_.nested -= 1
        return
    if stack and stack[-1][0] == stage:
        _, program, start, inside, hit = stack.pop()
    else:   # the listener came on inside this interval
        program, start, inside, hit = (
            kw.get("fun_name", ""), time.time() - duration, 0.0, False)
    if stack:
        # a compile while something is traced (an eager op on concrete
        # values): its seconds are the compile's, not the tracing's too
        stack[0][3] += duration
    if hit:
        stage = "cache_load"
    if program.endswith(")"):   # "jit(multi)" where tracing said "multi"
        program = program[program.find("(") + 1:-1]
    seconds = max(0.0, duration - inside)
    owner = _trace.current_span()
    key = (stage, owner or "none")
    with _runtime_lock:
        _STAGE_SECONDS[key] = _STAGE_SECONDS.get(key, 0.0) + seconds
        _STAGE_PROGRAMS[key] = _STAGE_PROGRAMS.get(key, 0) + 1
        by_stage = _PROGRAM_SECONDS.setdefault((program, key[1]), {})
        by_stage[stage] = by_stage.get(stage, 0.0) + seconds
    _trace.get_tracer().record_unix(
        "xla_" + stage, start, start + duration, {"program": program},
        parent=owner)


def _on_jax_event(event: str, **kw):
    # persistent-cache traffic: '/jax/compilation_cache/cache_hits' per
    # executable deserialized from disk, '.../cache_misses' per fresh
    # compile written into an ACTIVE cache (no cache dir -> no events)
    if event.endswith("/cache_hits"):
        with _runtime_lock:
            _CACHE["hits"] += 1
        stack = _OPEN_STAGES.stack
        if stack and stack[-1][0] == "compile":
            stack[-1][4] = True
    elif event.endswith("/cache_misses"):
        with _runtime_lock:
            _CACHE["misses"] += 1


def observe_program_span(name: str, seconds: float) -> None:
    """A set-up span closed (``Tracer.program_span``): its seconds join
    the account, beside the stage seconds booked to it as owner."""
    with _runtime_lock:
        ent = _PROGRAM_SPANS.setdefault(name, [0.0, 0])
        ent[0] += seconds
        ent[1] += 1


def _ensure_compile_listener():
    global _COMPILE_LISTENER_ON
    if _COMPILE_LISTENER_ON:
        return
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(
            _on_jax_event_duration)
        monitoring.register_event_listener(_on_jax_event)
        monitoring.register_scalar_listener(_on_jax_scalar)
        _COMPILE_LISTENER_ON = True
    except Exception:
        pass


def _runtime_collector() -> List[MetricFamily]:
    with _runtime_lock:
        compile_count = _COMPILE["count"]
        compile_secs = _COMPILE["seconds"]
        cache_hits = _CACHE["hits"]
        cache_misses = _CACHE["misses"]
        steps = dict(_STEPS)
        stage_seconds = dict(_STAGE_SECONDS)
        stage_programs = dict(_STAGE_PROGRAMS)
    fams = [
        MetricFamily("dl4j_xla_compile_total", "counter",
                     "XLA backend compiles observed via jax.monitoring"
                     ).add(compile_count),
        MetricFamily("dl4j_xla_compile_seconds_total", "counter",
                     "Cumulative XLA backend compile wall-clock seconds"
                     ).add(compile_secs),
        MetricFamily("dl4j_xla_cache_hits_total", "counter",
                     "Executables loaded from the persistent compilation "
                     "cache instead of compiled (0 when no cache dir is "
                     "configured — see compilecache.configure)"
                     ).add(cache_hits),
        MetricFamily("dl4j_xla_cache_misses_total", "counter",
                     "Fresh compiles written into the active persistent "
                     "compilation cache").add(cache_misses),
        _stage_family("dl4j_xla_stage_seconds_total", stage_seconds,
                      "Seconds jax spent making programs, by stage (trace, "
                      "lower, cache_load: a backend compile the persistent "
                      "cache held, compile: one it did not) and by owner: "
                      "the innermost span open on the thread, or none. "
                      "Each second is booked once: the outermost tracing "
                      "or lowering holds what nests in it, less a compile"),
        _stage_family("dl4j_xla_stage_programs_total", stage_programs,
                      "Outermost intervals of each stage, by owner: for "
                      "lower, cache_load and compile one a program"),
        MetricFamily("dl4j_fit_steps_total", "counter",
                     "Training steps dispatched by the fit loop"
                     ).add(steps["count"]),
        MetricFamily("dl4j_fit_steps_per_second", "gauge",
                     "Recent fit-loop dispatch rate (steps/sec)"
                     ).add(steps["per_sec"]),
        MetricFamily("dl4j_fit_dispatch_lag_seconds", "gauge",
                     "Last observed host->device dispatch lag (time the "
                     "host waited on device results at a sync point)"
                     ).add(steps["dispatch_lag_s"]),
        MetricFamily("dl4j_process_start_time_seconds", "gauge",
                     "Unix time the observability runtime was imported "
                     "(standard process-identity family)"
                     ).add(_PROCESS_START_TIME),
        MetricFamily("dl4j_heartbeat_timestamp_seconds", "gauge",
                     "Unix time of this render — liveness heartbeat; the "
                     "fleet scoreboard derives heartbeat age from it"
                     ).add(time.time()),
    ]
    try:
        from deeplearning4j_tpu.observability.distributed import get_identity
        fams.append(MetricFamily(
            "dl4j_instance_info", "gauge",
            "Process identity as labels (run_id/instance/incarnation/"
            "pid); always 1").add(1.0, get_identity().labels()))
    except Exception:
        pass
    mem = MetricFamily(
        "dl4j_device_memory_bytes", "gauge",
        "Per-device memory from jax.local_devices()[i].memory_stats(); "
        "backends that do not report (e.g. CPU) fall back to one "
        "process-wide kind=\"host_rss_bytes\" sample")
    reported = False
    try:
        import jax
        for d in jax.local_devices():
            stats = d.memory_stats()
            if not stats:
                continue
            dev = f"{d.platform}:{d.id}"
            for key in ("bytes_in_use", "peak_bytes_in_use",
                        "bytes_limit", "bytes_reserved"):
                if key in stats:
                    mem.add(stats[key], {"device": dev, "kind": key})
                    reported = True
    except Exception:
        pass
    if not reported:
        rss = _host_rss_bytes()
        if rss is not None:
            mem.add(rss, {"device": "process", "kind": "host_rss_bytes"})
    if mem.samples:
        fams.append(mem)
    peaks = memory_watermarks()
    if peaks:
        peak_fam = MetricFamily(
            "dl4j_device_memory_peak_bytes", "gauge",
            "High-water memory mark per device: max peak_bytes_in_use "
            "from Device.memory_stats() across watermark samples "
            "(arrays), and under <device>+reserved the largest "
            "bytes_in_use + bytes_reserved of one sample (arrays and "
            "program scratch); CPU falls back to the process VmHWM RSS "
            "high-water mark")
        for dev, v in sorted(peaks.items()):
            peak_fam.add(v, {"device": dev})
        fams.append(peak_fam)
    fams.extend(_trace_drop_families())
    return fams


def _stage_family(name: str, table: dict, help: str) -> MetricFamily:
    fam = MetricFamily(name, "counter", help)
    for (stage, owner), value in sorted(table.items()):
        fam.add(value, {"stage": stage, "owner": owner})
    return fam


def _trace_drop_families() -> List[MetricFamily]:
    """dl4j_trace_dropped_spans_total: ring-buffer data loss made
    visible — per evicted/sampled span name, plus the process total."""
    try:
        from deeplearning4j_tpu.observability.trace import get_tracer
        tracer = get_tracer()
        total = tracer.dropped
        by_name = tracer.dropped_spans()
    except Exception:
        return []
    if not total and not by_name:
        return []
    fam = MetricFamily(
        "dl4j_trace_dropped_spans_total", "counter",
        "Spans lost to tracer ring eviction or sampling, by span name "
        "(the 'total' label-less sample is the process-wide count)")
    fam.add(total)
    for name, n in sorted(by_name.items()):
        fam.add(n, {"span": name})
    return [fam]


def _host_rss_bytes() -> Optional[float]:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1]) * 1024.0
    except OSError:
        return None
    return None


def _host_hwm_bytes() -> Optional[float]:
    """Kernel-tracked RSS high-water mark (VmHWM) — the honest host
    watermark, no sampling cadence required."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return float(line.split()[1]) * 1024.0
    except OSError:
        return None
    return None


def update_memory_watermark() -> None:
    """Fold the current device memory state into the high-water table.
    Called at scrape time, epoch boundaries and goodput run start/end —
    deliberately NOT per-step (a /proc read per step would eat the
    trace-overhead budget)."""
    import jax

    try:
        devices = jax.local_devices()
    except RuntimeError:
        # no backend for THIS process (e.g. a router beside the process
        # that owns the chip): the host number below is the honest one
        devices = []
    reported = False
    # past that, no blanket except: a backend that cannot report (CPU)
    # returns None, and a device this process owns that RAISES must not
    # be silently replaced by the host-RSS fallback
    for d in devices:
        stats = d.memory_stats()
        if not stats:
            continue
        peak = stats.get("peak_bytes_in_use", stats.get("bytes_in_use"))
        if peak is None:
            continue
        dev = f"{d.platform}:{d.id}"
        # the runtime keeps program scratch in a book of its own; the two
        # peak at different moments, so their sum is taken per sample
        held = stats.get("bytes_in_use", 0) + stats.get("bytes_reserved", 0)
        with _runtime_lock:
            if peak > _MEM_PEAK.get(dev, 0.0):
                _MEM_PEAK[dev] = float(peak)
            if "bytes_reserved" in stats and held > _MEM_PEAK.get(
                    dev + RESERVED_SUFFIX, 0.0):
                _MEM_PEAK[dev + RESERVED_SUFFIX] = float(held)
        reported = True
    if reported:
        return
    hwm = _host_hwm_bytes() or _host_rss_bytes()
    if hwm is not None:
        with _runtime_lock:
            if hwm > _MEM_PEAK.get("process", 0.0):
                _MEM_PEAK["process"] = float(hwm)


def memory_watermarks() -> Dict[str, float]:
    """High-water mark per source, sampled now. ``"<platform>:<id>"``
    is the arrays-only peak (``peak_bytes_in_use`` of
    ``Device.memory_stats()``: parameters, optimizer state, staged
    batches). ``"<platform>:<id>+reserved"`` is the largest
    ``bytes_in_use + bytes_reserved`` of one sample: arrays and the
    scratch of the program that was running (activations, temporaries),
    which is most of a training step's memory; a peak between two
    samples is missed. The single ``"process"`` key is the host-RSS
    fallback of a backend that reports nothing."""
    update_memory_watermark()
    with _runtime_lock:
        return dict(_MEM_PEAK)


def memory_watermark_bytes() -> Optional[float]:
    """The single-number memory watermark (max across devices, arrays
    only) the RunReport records. Samples current state first."""
    peaks = [v for k, v in memory_watermarks().items()
             if not k.endswith(RESERVED_SUFFIX)]
    return max(peaks) if peaks else None


def install_runtime_metrics(
        registry: Optional[MetricsRegistry] = None) -> MetricsRegistry:
    """Idempotently attach the runtime collector (compile count/seconds,
    device memory, steps/sec, dispatch lag) + the jax.monitoring compile
    listener to *registry* (default: the global one). Called by the fit
    loops and both servers, so any surfaced registry carries these."""
    global _RUNTIME_INSTALLED_ON
    reg = registry or get_registry()
    _ensure_compile_listener()
    with _runtime_lock:
        if _RUNTIME_INSTALLED_ON is reg:
            return reg
        _RUNTIME_INSTALLED_ON = reg
    reg.register_collector(_runtime_collector)
    try:  # the goodput gauges ride along wherever runtime metrics go
        from deeplearning4j_tpu.observability.goodput import goodput_collector
        reg.register_collector(goodput_collector)
    except Exception:
        pass
    return reg


def observe_step(n: int = 1, wall_s: Optional[float] = None):
    """Fit loops report dispatched steps; steps/sec derives from the
    wall-clock the caller measured for those n steps."""
    with _runtime_lock:
        _STEPS["count"] += n
        if wall_s and wall_s > 0:
            _STEPS["per_sec"] = n / wall_s


def observe_rate(n: int, wall_s: Optional[float]):
    """Update the steps/sec gauge WITHOUT advancing steps_total — the
    fit loops count steps per dispatch (k per lax.scan chunk) via
    goodput.observe_steps and report the epoch-level rate here."""
    with _runtime_lock:
        if wall_s and wall_s > 0:
            _STEPS["per_sec"] = n / wall_s


def observe_dispatch_lag(seconds: float):
    """Record the latest host->device sync wait (e.g. a score_sync)."""
    with _runtime_lock:
        _STEPS["dispatch_lag_s"] = float(seconds)


def compile_stats() -> dict:
    with _runtime_lock:
        return dict(_COMPILE)


def cache_stats() -> dict:
    """Persistent-compilation-cache traffic since process start:
    ``{"hits", "misses"}``. Both 0 unless a cache dir is configured
    (compilecache.configure / JAX_COMPILATION_CACHE_DIR) — jax only emits
    the hit/miss events while a cache is active."""
    with _runtime_lock:
        return dict(_CACHE)


def compile_snapshot() -> dict:
    """Baseline snapshot for :func:`compile_delta` — the documented
    per-run seam over the process-global compile/cache counters.

    ``_COMPILE`` and ``_CACHE`` are process-cumulative (jax.monitoring
    has no unregister, and a counter that resets under a live scrape
    would corrupt Prometheus rate()). Run-scoped numbers — what the
    goodput ledger puts in a RunReport — must therefore be DELTAS:
    snapshot at run start, subtract at run end. Nested or sequential
    ledgers each take their own snapshot, so two fits in one process
    report their own compiles, not each other's.

    Taking a snapshot also installs the jax.monitoring listener: a
    baseline is always taken BEFORE the compiles it scopes, so the
    events land in the counters even when nothing else wired metrics."""
    _ensure_compile_listener()
    with _runtime_lock:
        return {"count": _COMPILE["count"], "seconds": _COMPILE["seconds"],
                "cache_hits": _CACHE["hits"],
                "cache_misses": _CACHE["misses"]}


def compile_delta(baseline: dict) -> dict:
    """Compile/cache activity since *baseline* (a
    :func:`compile_snapshot`). Missing baseline keys count from 0, so a
    pre-PR-10 snapshot ({"count", "seconds"}) still subtracts clean."""
    now = compile_snapshot()
    return {k: (round(now[k] - baseline.get(k, 0), 6)
                if k == "seconds" else now[k] - baseline.get(k, 0))
            for k in now}


def stage_snapshot() -> dict:
    """The stage account since process start, and the baseline of
    :func:`stage_delta`: ``seconds`` and ``programs`` as ``{stage:
    {owner: value}}``, and ``spans`` as ``{name: {"seconds", "count"}}``
    for the set-up spans (``Tracer.program_span``). Installs the
    listener, as :func:`compile_snapshot` does."""
    _ensure_compile_listener()
    out = {"seconds": {}, "programs": {}}
    with _runtime_lock:
        for name, table in (("seconds", _STAGE_SECONDS),
                            ("programs", _STAGE_PROGRAMS)):
            for (stage, owner), value in table.items():
                out[name].setdefault(stage, {})[owner] = value
        out["spans"] = {name: {"seconds": ent[0], "count": ent[1]}
                        for name, ent in _PROGRAM_SPANS.items()}
    return out


def largest_programs(n: int = 16) -> List[dict]:
    """The ``n`` largest (program, owner) pairs of the stage account by
    seconds, each ``{"program", "owner", "seconds", <stage>: seconds}``:
    which program, without a label whose values nobody bounds."""
    with _runtime_lock:
        programs = [{"program": program, "owner": owner,
                     "seconds": sum(by_stage.values()), **by_stage}
                    for (program, owner), by_stage
                    in _PROGRAM_SECONDS.items()]
    programs.sort(key=lambda p: -p["seconds"])
    return programs[:n]


def stage_delta(baseline: dict) -> dict:
    """Stage seconds, programs and set-up span totals since *baseline* (a
    :func:`stage_snapshot`), in the snapshot's shape. A run's own share
    of the process-cumulative account."""
    now = stage_snapshot()
    out = {}
    for name in ("seconds", "programs"):
        out[name] = {
            stage: {owner: value - baseline.get(name, {}).get(stage, {})
                    .get(owner, 0) for owner, value in owners.items()}
            for stage, owners in now[name].items()}
    before = baseline.get("spans", {})
    out["spans"] = {
        name: {k: ent[k] - before.get(name, {}).get(k, 0) for k in ent}
        for name, ent in now["spans"].items()}
    return out


def process_start_unix() -> float:
    """Unix time this PROCESS started (kernel starttime via /proc, so
    it predates every import) — the cold-start clock's zero. Falls back
    to the module-import stamp where /proc is unavailable."""
    try:
        with open("/proc/self/stat") as f:
            after_comm = f.read().rsplit(")", 1)[1].split()
        ticks = float(after_comm[19])  # field 22: starttime
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except Exception:
        return _PROCESS_START_TIME


def _monotonic() -> float:
    return time.perf_counter()
