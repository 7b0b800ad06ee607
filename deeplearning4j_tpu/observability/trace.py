"""Structured span tracing: the "where did step N spend its time" core.

The reference dedicates a module tier (deeplearning4j-ui-parent, ~25k
LoC) to stats collection and timeline export; TensorFlow (arXiv:
1605.08695 §9) treats tracing as a first-class runtime subsystem. After
PRs 1-3 this framework runs real concurrency — a pipelined fit loop, a
MicroBatcher device thread, an async checkpoint writer — and a span
tracer is the only honest way to see them against each other.

Design constraints, in order:

1. **Hot-path overhead**: recording one span is two ``perf_counter``
   calls plus one append into a bounded ring, under one uncontended
   lock — no allocation of dicts/strings beyond the tuple, no I/O, no
   device sync. The ``trace_overhead`` bench entry holds the fit-loop
   regression under 3% at default sampling; ``Tracer.disabled`` spans
   cost one attribute read.
2. **Thread lanes**: every span records its thread id + name, so the
   Chrome-trace export renders the fit loop, the ``microbatcher-device``
   thread and the ``dl4j-ckpt-writer`` thread as separate lanes in
   Perfetto / ``chrome://tracing``.
3. **XLA correlation**: with ``annotate=True`` each span is also wrapped
   in ``jax.profiler.TraceAnnotation``, so the same names appear inside
   device profiles captured by ``ProfilerListener`` — one taxonomy
   across host timeline and XLA trace.

Span taxonomy (OBSERVABILITY.md has the full table):

- fit loop (both nets): ``data_wait``, ``host_dispatch``,
  ``device_step``, ``score_sync``
- serving (MicroBatcher): ``queue_wait``, ``batch_assembly``,
  ``device_compute``
- resilience supervisor: ``checkpoint_snapshot``, ``checkpoint_write``,
  ``checkpoint_barrier``, ``rollback``, ``restore``
- distributed phases (parallel/stats.py): ``fit``, ``average``,
  ``checkpoint_barrier`` (the TrainingStatsCollector feeds the same
  tracer, so Spark-tier phases land in the same timeline)
- set-up (``Tracer.program_span``): ``net_init``, ``forward``,
  ``flops_derive``, ``opindex_lookup``; and under whichever span caused
  them ``xla_trace``, ``xla_lower``, ``xla_cache_load``,
  ``xla_compile``, one per program, recorded by the stage account of
  observability/metrics.py with the program's name

Every span carries ``parent``: the name of the span that was open on
the same thread when it began (None at the top).
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import List, NamedTuple, Optional, Sequence

__all__ = [
    "Span", "Tracer", "get_tracer", "set_tracer", "span", "trace_span",
    "trace_timeline_component", "current_span",
]


class Span(NamedTuple):
    """One completed span. Times are microseconds since the tracer's
    epoch (``perf_counter`` based — monotonic, comparable across threads
    of one process)."""
    name: str
    ts_us: float
    dur_us: float
    tid: int
    thread: str
    attrs: Optional[dict]
    parent: Optional[str] = None    # the span open on this thread at its start

    def to_dict(self) -> dict:
        d = {"name": self.name, "ts_us": round(self.ts_us, 3),
             "dur_us": round(self.dur_us, 3), "tid": self.tid,
             "thread": self.thread}
        if self.attrs:
            d["attrs"] = self.attrs
        if self.parent:
            d["parent"] = self.parent
        return d


class _Open(threading.local):
    """The spans open on this thread (any tracer), innermost last."""

    def __init__(self):
        self.stack = []


_OPEN = _Open()


def current_span() -> Optional[str]:
    """Name of the innermost span open on the calling thread, or None:
    the parent of a span that begins now, and the owner the stage
    account (observability/metrics.py) books a trace or a compile to."""
    stack = _OPEN.stack
    return stack[-1]._name if stack else None


class _SpanCtx:
    """Hand-rolled context manager: ~2x cheaper than
    ``@contextmanager`` on the per-step hot path."""

    __slots__ = ("_tracer", "_name", "_attrs", "_t0", "_ann", "_parent",
                 "_stack")

    def __init__(self, tracer: "Tracer", name: str, attrs):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._ann = None

    def set(self, **attrs):
        """Attributes known only inside the span (a part's own seconds)."""
        self._attrs = {**(self._attrs or {}), **attrs}

    def __enter__(self):
        if self._tracer.annotate:
            import jax
            self._ann = jax.profiler.TraceAnnotation(self._name)
            self._ann.__enter__()
        stack = self._stack = _OPEN.stack
        self._parent = stack[-1]._name if stack else None
        stack.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        stack = self._stack     # of the thread that opened it
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            # closed out of order: a generator that yielded inside the
            # span and finished under a consumer's, or another thread
            stack.remove(self)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer._record(self._name, self._t0, t1, self._attrs,
                             parent=self._parent)
        return False


class _ProgramSpanCtx(_SpanCtx):
    """A span round set-up work (``Tracer.program_span``): the stage
    account keeps its seconds too, since the ring it is recorded on may
    be gone by the time anyone asks what set-up cost."""

    __slots__ = ()

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0
        from deeplearning4j_tpu.observability.metrics import (
            observe_program_span)
        observe_program_span(self._name, seconds)
        return _SpanCtx.__exit__(self, *exc)


class _NullCtx:
    """Returned by a disabled tracer — a shared no-op (no allocation)."""

    __slots__ = ()

    def set(self, **attrs):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullCtx()


class Tracer:
    """Thread-safe bounded-ring span recorder.

    - ``capacity``: ring size (oldest spans evicted — a dashboard wants
      the recent window, not since-boot history; export what you need
      before it scrolls off).
    - ``sample_every``: keep 1 of every N occurrences *per span name*
      (N=1, the default, records everything — the fit-loop overhead
      budget already clears 3% unsampled; raise it for pathological
      span rates).
    - ``annotate``: additionally wrap each span in
      ``jax.profiler.TraceAnnotation`` so names appear in XLA/Perfetto
      device profiles (off by default: TraceMe has its own cost and is
      only useful while a profiler trace is recording).
    """

    def __init__(self, capacity: int = 65536, enabled: bool = True,
                 annotate: bool = False, sample_every: int = 1):
        self.capacity = int(capacity)
        self.enabled = bool(enabled)
        self.annotate = bool(annotate)
        self.sample_every = max(1, int(sample_every))
        self._ring: deque[Span] = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._epoch = time.perf_counter()
        self._seen: dict = {}       # name -> occurrence count (sampling)
        self.dropped = 0            # spans evicted or sampled away
        self._dropped_by_name: dict = {}  # name -> drop count
        self._sinks: list = []      # fns called with each recorded Span

    # ------------------------------------------------------------ recording
    def span(self, name: str, **attrs):
        """Context manager timing one span: ``with tracer.span("x"): ...``"""
        if not self.enabled:
            return _NULL
        return _SpanCtx(self, name, attrs or None)

    def program_span(self, name: str, **attrs):
        """``span`` for set-up work that makes programs (``net_init``,
        ``forward``, ``flops_derive``, ``opindex_lookup``): its seconds
        are also added to the stage account's span totals
        (``metrics.stage_snapshot()["spans"]``)."""
        if not self.enabled:
            return _NULL
        return _ProgramSpanCtx(self, name, attrs or None)

    def record(self, name: str, t0: float, t1: float, attrs: dict = None,
               tid: int = None, thread: str = None, parent: str = None):
        """Record an explicitly-timed span (``perf_counter`` endpoints) —
        for spans whose start lives on another thread (e.g. a serving
        ticket's ``queue_wait`` measured from its submit timestamp).
        ``parent`` names the span that caused it, where the caller knows."""
        if self.enabled:
            self._record(name, t0, t1, attrs, tid, thread, parent)

    def record_unix(self, name: str, start_unix: float, end_unix: float,
                    attrs: dict = None, parent: str = None):
        """``record`` for an interval timed on the unix clock (jax's
        monitoring events), placed on this tracer's clock through
        ``epoch_unix``."""
        t0 = start_unix - self.epoch_unix() + self._epoch
        self.record(name, t0, t0 + (end_unix - start_unix), attrs,
                    parent=parent)

    def _record(self, name, t0, t1, attrs, tid=None, thread=None,
                parent=None):
        if tid is None:
            t = threading.current_thread()
            tid, thread = t.ident or 0, t.name
        with self._lock:
            if self.sample_every > 1:
                seen = self._seen.get(name, 0)
                self._seen[name] = seen + 1
                if seen % self.sample_every:
                    self.dropped += 1
                    self._dropped_by_name[name] = \
                        self._dropped_by_name.get(name, 0) + 1
                    return
            if len(self._ring) == self.capacity:
                # ring eviction loses the OLDEST span — count its name,
                # not the incoming one, so the drop table says which
                # phase's history actually scrolled off
                self.dropped += 1
                evicted = self._ring[0].name
                self._dropped_by_name[evicted] = \
                    self._dropped_by_name.get(evicted, 0) + 1
            span = Span(
                name, (t0 - self._epoch) * 1e6, (t1 - t0) * 1e6,
                tid, thread or "", attrs, parent)
            self._ring.append(span)
            sinks = self._sinks
        for sink in sinks:
            try:
                sink(span)
            except Exception:
                pass  # a broken sink must never break the hot path

    # ---------------------------------------------------------------- sinks
    def add_sink(self, fn) -> None:
        """Register a callable invoked with every recorded Span (outside
        the ring lock; exceptions swallowed). Sinks see spans even when
        the ring later evicts them — the goodput ledger's feed."""
        with self._lock:
            if fn not in self._sinks:
                # copy-on-write: _record iterates a snapshot lock-free
                self._sinks = self._sinks + [fn]

    def remove_sink(self, fn) -> None:
        with self._lock:
            if fn in self._sinks:
                self._sinks = [s for s in self._sinks if s is not fn]

    # -------------------------------------------------------------- control
    def enable(self):
        self.enabled = True
        return self

    def disable(self):
        self.enabled = False
        return self

    def clear(self):
        with self._lock:
            self._ring.clear()
            self._seen.clear()
            self.dropped = 0
            self._dropped_by_name = {}

    # ---------------------------------------------------------------- clocks
    def epoch_unix(self) -> float:
        """Approximate unix time of the tracer's perf_counter epoch —
        the anchor that converts ``Span.ts_us`` (µs since epoch,
        monotonic, per-process) into wall-clock time so spans pushed
        from different processes can be laid on one timeline. Computed
        fresh per call from the current clock pair; the residual error
        is the clock-read skew (µs), far below the network gaps the
        cross-process waterfall resolves."""
        return time.time() - (time.perf_counter() - self._epoch)

    # ------------------------------------------------------------ drop stats
    def dropped_spans(self) -> dict:
        """Per-name dropped-span counts (ring eviction counts the
        evicted span's name; sampling counts the sampled-away name)."""
        with self._lock:
            return dict(self._dropped_by_name)

    # --------------------------------------------------------------- export
    def spans(self) -> List[Span]:
        """Snapshot of the ring (oldest first). Taken under the lock —
        recorder threads may keep appending while the caller iterates
        the returned list safely."""
        with self._lock:
            return list(self._ring)

    def to_chrome_trace(self) -> dict:
        """Chrome trace-event JSON (the ``{"traceEvents": [...]}`` form
        Perfetto and ``chrome://tracing`` load): one ``ph: "X"`` complete
        event per span, one ``ph: "M"`` thread_name metadata event per
        thread so lanes are labeled. Events are sorted by ``ts``."""
        spans = self.spans()
        pid = os.getpid()
        events = []
        # a lane is a thread ident AND a name: a thread that ends hands
        # its ident to the next one started, which is another lane
        lanes: dict = {}    # (ident, name) -> the export's tid
        first: dict = {}    # ident -> the first name seen under it
        spare = max((s.tid for s in spans), default=0)
        for s in spans:
            lane = lanes.get((s.tid, s.thread))
            if lane is None:
                lane = s.tid
                if first.setdefault(s.tid, s.thread) != s.thread:
                    lane = spare = spare + 1
                lanes[(s.tid, s.thread)] = lane
            ev = {"ph": "X", "name": s.name, "cat": "dl4j_tpu",
                  "pid": pid, "tid": lane,
                  "ts": round(s.ts_us, 3), "dur": round(s.dur_us, 3)}
            args = dict(s.attrs) if s.attrs else {}
            if s.parent:
                args["parent"] = s.parent
            if args:
                ev["args"] = args
            events.append(ev)
        events.sort(key=lambda e: e["ts"])
        meta = [{"ph": "M", "name": "thread_name", "pid": pid, "tid": lane,
                 "args": {"name": name or f"thread-{tid}"}}
                for (tid, name), lane in sorted(lanes.items())]
        out = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        dropped = self.dropped_spans()
        if self.dropped or dropped:
            # stamp data loss into the artifact: a timeline missing its
            # oldest spans should say so rather than look complete
            out["otherData"] = {
                "dropped_spans_total": self.dropped,
                "dropped_spans_by_name": dropped,
            }
        try:
            # identity rides in otherData (NOT a metadata event — lanes
            # stay thread_name-only) so exports from different fleet
            # members can be attributed and merged after the fact
            from deeplearning4j_tpu.observability.distributed import \
                get_identity
            out.setdefault("otherData", {})["identity"] = \
                get_identity().to_dict()
        except Exception:
            pass
        return out

    def export_chrome_trace(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path

    def export_jsonl(self, path: str) -> str:
        """One span per line — the grep/pandas-friendly raw form."""
        with open(path, "w") as f:
            for s in self.spans():
                f.write(json.dumps(s.to_dict()) + "\n")
        return path

    # ------------------------------------------------------------- analysis
    def totals_ms(self) -> dict:
        """Total recorded wall-clock per span name, in ms (the quick
        "what dominates" table)."""
        out: dict = {}
        for s in self.spans():
            out[s.name] = out.get(s.name, 0.0) + s.dur_us / 1000.0
        return out


# --------------------------------------------------------------------------
# process-global tracer (the one every runtime feeds by default)
# --------------------------------------------------------------------------

def _env_default() -> Tracer:
    """DL4J_TPU_TRACE=0 disables span recording process-wide;
    DL4J_TPU_TRACE_SAMPLE=N sets the default sampling."""
    enabled = os.environ.get("DL4J_TPU_TRACE", "1") != "0"
    sample = int(os.environ.get("DL4J_TPU_TRACE_SAMPLE", "1"))
    return Tracer(enabled=enabled, sample_every=sample)


_GLOBAL = _env_default()


def get_tracer() -> Tracer:
    return _GLOBAL


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-global tracer (tests, custom capacities).
    Returns the previous one so callers can restore it."""
    global _GLOBAL
    prev, _GLOBAL = _GLOBAL, tracer
    return prev


def span(name: str, **attrs):
    """``with span("data_wait"): ...`` against the global tracer."""
    return _GLOBAL.span(name, **attrs)


def trace_span(name: str):
    """Decorator form: ``@trace_span("checkpoint_write")``."""
    def deco(fn):
        import functools

        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with _GLOBAL.span(name):
                return fn(*a, **kw)
        return wrapped
    return deco


# --------------------------------------------------------------------------
# timeline rendering (the ChartTimeline tier the Spark stats export uses)
# --------------------------------------------------------------------------

_PALETTE = ("#1f77b4", "#ff7f0e", "#2ca02c", "#d62728", "#9467bd",
            "#8c564b", "#e377c2", "#7f7f7f", "#bcbd22", "#17becf")


def span_color(name: str) -> str:
    """Stable span-name -> color (shared by the dashboard JS panel and
    the exported HTML timeline)."""
    return _PALETTE[hash(name) % len(_PALETTE)]


def trace_timeline_component(spans: Sequence[Span],
                             title: str = "Runtime trace"):
    """Per-thread lanes of colored span bars through the same
    ``ChartTimeline`` component the Spark phase timeline renders with
    (parallel/stats.py timeline_component is the phase-tier sibling)."""
    from deeplearning4j_tpu.ui.components import ChartTimeline, Style

    by_thread: dict = {}
    for s in spans:
        by_thread.setdefault(s.thread or f"thread-{s.tid}", []).append(s)
    chart = ChartTimeline(title, Style(
        width=760, height=max(120, 46 + 34 * len(by_thread))),
        xlabel="seconds")
    for name in sorted(by_thread):
        entries = [(s.ts_us / 1e6, (s.ts_us + s.dur_us) / 1e6, s.name,
                    span_color(s.name))
                   for s in sorted(by_thread[name], key=lambda s: s.ts_us)]
        chart.add_lane(name, entries)
    return chart


def export_trace_html(spans: Sequence[Span], path: str,
                      title: str = "Runtime trace") -> None:
    """Standalone HTML timeline (StatsUtils.exportStatsAsHtml parity for
    the span tier)."""
    from deeplearning4j_tpu.ui.components import render_components_to_file

    render_components_to_file([trace_timeline_component(spans)], path, title)
