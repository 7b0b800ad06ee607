"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

What a TPU trace holds (looked at by hand on a v5e, jax 0.9, PR 22): one
plane per chip named ``/device:TPU:<n>`` with the lines ``XLA Modules``
(one event per program execution, e.g. ``jit_multi(<fingerprint>)``),
``XLA Ops`` (one event per HLO instruction run, named by its whole HLO
text, a ``while`` or ``conditional`` enclosing its body's events) and
``Async XLA Ops`` (DMA and collectives in flight, overlapping the ops).
Device events are timed in nanoseconds since the profile began, and the
plane ``Task Environment`` gives that moment on the unix clock
(``profile_start_time``). The host plane is not read (the runner turns
the profiler's host tracer off, PERF.md Findings PR 22): what the host
was doing comes from the program's own spans, laid on the same timeline
by their unix times (``Trace.place``).

Definitions used by every metric built on this file:

- *leaf op*: an ``XLA Ops`` event that encloses no other event. Only
  leaves count as the device doing something: a ``while`` spans its
  body's bubbles too.
- *busy*: the union of the leaf ops' intervals.
- *analysed window* of a chip: from the start of the second execution of
  the dominant program (the module with the most device time) to the end
  of its last execution. The first execution is dropped because the trace
  usually begins inside it, and starting the profiler stalls the host.
- *gap*: a maximal interval of the window with no leaf op. A gap inside a
  module execution is the program's own bubble; a gap between executions
  is shared out among the host spans that overlap it.
"""

from __future__ import annotations

import gzip
import re
from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"^%[\w.-]*(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)")
_OPCODE = re.compile(r"[\]})]\s([a-z][a-z0-9_-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclass(frozen=True)
class Event:
    start: float        # seconds since the trace began
    end: float
    name: str


@dataclass
class DevicePlane:
    name: str
    modules: list = field(default_factory=list)
    ops: list = field(default_factory=list)
    async_ops: list = field(default_factory=list)


@dataclass
class Trace:
    devices: list       # DevicePlane, sorted by name
    host: dict          # thread name -> [Event]: the spans placed so far
    start_unix: float = 0.0     # unix seconds at which the profile began

    def place(self, thread: str, name: str, start_unix: float,
              duration_s: float) -> None:
        """Add a host span timed on the unix clock."""
        start = start_unix - self.start_unix
        self.host.setdefault(thread, []).append(
            Event(start, start + duration_s, name))


def _events(line) -> list:
    return [Event(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                  e.name) for e in line.events]


def load(path: str) -> Trace:
    """The device planes of ``path`` (``.xplane.pb`` or ``.xplane.pb.gz``)
    and the unix time at which the profile began."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, start_unix = [], 0.0
    for plane in data.planes:
        if plane.name == "Task Environment":
            start_unix = dict(plane.stats).get("profile_start_time", 0) * 1e-9
        if DEVICE_PLANE.match(plane.name):
            dev = DevicePlane(plane.name)
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules = _events(line)
                elif line.name == "XLA Ops":
                    dev.ops = _events(line)
                elif line.name == "Async XLA Ops":
                    dev.async_ops = _events(line)
            devices.append(dev)
    devices.sort(key=lambda d: d.name)
    return Trace(devices, {}, start_unix)


# ------------------------------------------------------------- intervals
def merge(intervals: Sequence) -> np.ndarray:
    """Union of ``(start, end)`` pairs as a sorted ``[n, 2]`` array of
    disjoint intervals."""
    a = np.asarray([(s, e) for s, e in intervals if e > s], np.float64)
    if a.size == 0:
        return np.zeros((0, 2))
    a = a[np.argsort(a[:, 0], kind="stable")]
    reach = np.maximum.accumulate(a[:, 1])
    first = np.ones(len(a), bool)
    first[1:] = a[1:, 0] > reach[:-1]
    starts = a[first, 0]
    ends = np.append(reach[:-1][first[1:]], reach[-1])
    return np.stack([starts, ends], axis=1)


def clip(merged: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(merged) == 0:
        return merged
    out = np.clip(merged, lo, hi)
    return out[out[:, 1] > out[:, 0]]


def length(merged: np.ndarray) -> float:
    return float((merged[:, 1] - merged[:, 0]).sum()) if len(merged) else 0.0


def complement(merged: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The parts of ``[lo, hi]`` that ``merged`` (disjoint, sorted) leaves
    uncovered."""
    m = clip(merged, lo, hi)
    edges = np.concatenate([[lo], m.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def overlap(merged: np.ndarray, lo: float, hi: float) -> float:
    return length(clip(merged, lo, hi))


# ------------------------------------------------------------------- ops
def self_times(events: Sequence[Event]) -> list:
    """``(event, self_seconds, is_leaf)`` per event of one line: self time
    is the event's duration less that of the events directly inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].end))
    self_s = [e.end - e.start for e in events]
    leaf = [True] * len(events)
    stack: list = []
    for i in order:
        e = events[i]
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= events[stack[-1]].end:
            parent = stack[-1]
            self_s[parent] -= e.end - e.start
            leaf[parent] = False
        stack.append(i)
    return [(events[i], max(self_s[i], 0.0), leaf[i])
            for i in range(len(events))]


def op_label(hlo_text: str, width: int = 96) -> str:
    """``%name opcode [custom-call target]`` from an op's HLO text, the
    name without its numeric suffix: XLA names a fusion after what it
    fuses, so ``%convert_reduce_fusion.199`` and ``.196`` are one family,
    and a ResNet step has hundreds of ops of a few families."""
    name = re.sub(r"\.\d+$", "", hlo_text.split(" = ", 1)[0])
    m = _OPCODE.search(hlo_text)
    label = f"{name} {m.group(1)}" if m else name
    t = _TARGET.search(hlo_text)
    if t:
        label += f" {t.group(1)}"
    return label[:width]


def module_label(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


# -------------------------------------------------------------- reduction
@dataclass
class DeviceReduction:
    name: str
    window: tuple               # (start, end) seconds
    executions: int             # dominant-program executions in the window
    leaves: list                # [(Event, self_seconds)] inside the window
    busy: np.ndarray            # merged leaf intervals, clipped to the window
    modules: np.ndarray         # merged module intervals, clipped
    module_events: list         # every program execution touching the window
    async_events: list

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        return length(self.busy)

    @property
    def gaps(self) -> np.ndarray:
        return complement(self.busy, *self.window)


@dataclass
class Reduction:
    dominant_module: str
    devices: list               # DeviceReduction
    host: dict

    # -- the numbers the contract's ``device`` block carries
    @property
    def window_s(self) -> float:
        return float(np.mean([d.window_s for d in self.devices]))

    @property
    def busy_s(self) -> float:
        return float(np.mean([d.busy_s for d in self.devices]))

    @property
    def executions(self) -> int:
        return min(d.executions for d in self.devices)

    def op_seconds(self, pattern: str) -> Optional[float]:
        """Self time of the leaf ops whose HLO text matches ``pattern``,
        averaged over the chips; None when nothing matches."""
        rx = re.compile(pattern)
        per_dev = [[s for e, s in d.leaves if rx.search(e.name)]
                   for d in self.devices]
        if not any(per_dev):
            return None
        return float(np.mean([sum(found) for found in per_dev]))

    def top_ops(self, n: int = 10) -> list:
        total: dict = {}
        for d in self.devices:
            for e, s in d.leaves:
                key = op_label(e.name)
                total[key] = total.get(key, 0.0) + s / len(self.devices)
        return sorted(([k, v] for k, v in total.items()),
                      key=lambda kv: -kv[1])[:n]

    def gaps_by_cause(self, n: int = 10) -> list:
        """Idle seconds by cause, averaged over the chips: idle time inside
        a program execution goes to ``in <module>``, the rest to the host
        spans that overlap it (``host: <span>``), what no span covers to
        ``host: no span``."""
        spans = _main_thread_spans(self.host)
        total: dict = {}

        def add(key, seconds):
            if seconds > 0:
                total[key] = total.get(key, 0.0) + seconds / len(self.devices)

        for d in self.devices:
            for ev in d.module_events:
                lo, hi = max(ev.start, d.window[0]), min(ev.end, d.window[1])
                add(f"in {module_label(ev.name)}",
                    (hi - lo) - overlap(d.busy, lo, hi))
            gaps = d.gaps
            for lo, hi in complement(d.modules, *d.window):
                for glo, ghi in clip(gaps, lo, hi):
                    seen = 0.0
                    for sp in spans:
                        ov = min(ghi, sp.end) - max(glo, sp.start)
                        if ov > 0:
                            add(f"host: {sp.name}", ov)
                            seen += ov
                    add("host: no span", (ghi - glo) - seen)
        return sorted(([k, v] for k, v in total.items()),
                      key=lambda kv: -kv[1])[:n]

    def collective_exposed_s(self) -> Optional[float]:
        """Seconds, averaged over the chips, inside a collective (an op or
        an async start..done pair) during which no other leaf op ran on
        that chip. None when the trace holds no collective."""
        found, per_dev = False, []
        for d in self.devices:
            coll = [(e.start, e.end) for e, _ in d.leaves
                    if COLLECTIVE.match(e.name)]
            coll += [(e.start, e.end) for e in d.async_events
                     if COLLECTIVE.match(e.name)]
            if not coll:
                per_dev.append(0.0)
                continue
            found = True
            compute = merge([(e.start, e.end) for e, _ in d.leaves
                             if not COLLECTIVE.match(e.name)])
            exposed = 0.0
            for lo, hi in clip(merge(coll), *d.window):
                exposed += (hi - lo) - overlap(compute, lo, hi)
            per_dev.append(exposed)
        return float(np.mean(per_dev)) if found else None


def _main_thread_spans(host: dict) -> list:
    """Spans of the thread that recorded most of them (the fit loop's
    own): spans of two threads would overlap and count twice."""
    if not host:
        return []
    return max(host.values(), key=len)


def reduce(trace: Trace) -> Optional[Reduction]:
    """None when the trace holds no device plane with a program that ran
    at least three times (one dropped, two to span a window)."""
    planes = [d for d in trace.devices if d.modules and d.ops]
    if not planes:
        return None
    by_module: dict = {}
    for ev in planes[0].modules:
        by_module[ev.name] = by_module.get(ev.name, 0.0) + ev.end - ev.start
    dominant = max(by_module, key=by_module.get)
    out = []
    for d in planes:
        runs = sorted((ev for ev in d.modules if ev.name == dominant),
                      key=lambda ev: ev.start)
        if len(runs) < 3:
            return None
        lo, hi = runs[1].start, runs[-1].end
        leaves = [(e, s) for e, s, leaf in self_times(d.ops)
                  if leaf and e.end > lo and e.start < hi]
        busy = clip(merge([(e.start, e.end) for e, _ in leaves]), lo, hi)
        out.append(DeviceReduction(
            name=d.name, window=(lo, hi), executions=len(runs) - 1,
            leaves=leaves, busy=busy,
            modules=clip(merge([(ev.start, ev.end) for ev in d.modules]),
                         lo, hi),
            module_events=[ev for ev in d.modules
                           if ev.end > lo and ev.start < hi],
            async_events=[e for e in d.async_ops
                          if e.end > lo and e.start < hi]))
    return Reduction(module_label(dominant), out, trace.host)
