"""Per-layer metrics that say which layer and phase the device time
belongs to.

The device trace names an op by its HLO instruction and nothing else.
The program under test keeps an index of the step program it dispatched
(``deeplearning4j_tpu/observability/opindex.py``): these readers call
``opindex.lookup(m.trace.dominant_module)`` in the run's own process,
after the window, and ``opindex.place`` on the entry of each leaf op of
the reduced trace. The lookup compiles the registered step program again
(a load from the persistent cache) and parses its text; what that cost is
left in ``m.notes["opindex_build_s"]``. A program without that module (a
parent commit), or without a registration for the dominant module, gives
None for every metric here, and the line leaves them out.

All times are per optimizer step (``readers/trace.py:_steps``), mean
over the chips, of leaf ops clipped to the analysed window: the six
classes below partition the leaves, so forward + loss, backward, update,
input and unplaced sum to ``step_device_ms``.
"""

from __future__ import annotations

import time
from importlib import import_module

import numpy as np

from benchmark import xplane
from benchmark.readers.trace import _steps

_last = [None, None]        # the trace last tabulated, and its table


def _instruction(hlo_text: str) -> str:
    return hlo_text.split(" = ", 1)[0].lstrip("%")


class _Table:
    """Seconds per chip by (phase, layer, primitive), and what else the
    metrics need, from one pass over the leaves."""

    def __init__(self, trace, index, opindex):
        chips = len(trace.devices)
        placed: dict = {}       # instruction -> (place, has a convolution)

        def where(hlo_text):
            name = _instruction(hlo_text)
            if name not in placed:
                entry = index.get(name)
                placed[name] = (opindex.place(entry),
                                opindex.contains(entry, "convolution"))
            return placed[name]

        self.rows: dict = {}    # (phase, layer, primitive) -> seconds
        self.other_programs = 0.0
        self.convolution = 0.0
        self.collectives = {}   # purpose -> [seconds, count] per chip
        for d in trace.devices:
            lo, hi = d.window
            own = xplane.merge(
                [(ev.start, ev.end) for ev in d.module_events
                 if xplane.module_label(ev.name) == trace.dominant_module])
            # a leaf is the dominant program's when it starts inside one
            # of that program's executions
            starts = np.asarray([e.start for e, _ in d.leaves])
            run = np.searchsorted(own[:, 0], starts, side="right") - 1
            inside = (run >= 0) & (
                starts < np.append(own[:, 1], 0.0)[np.maximum(run, 0)])
            intervals: dict = {}    # purpose -> [(start, end, counts)]
            for (e, _), mine in zip(d.leaves, inside):
                seconds = (min(e.end, hi) - max(e.start, lo)) / chips
                if not mine:
                    self.other_programs += seconds
                    continue
                place, conv = where(e.name)
                self.rows[place] = self.rows.get(place, 0.0) + seconds
                if conv:
                    self.convolution += seconds
                if xplane.COLLECTIVE.match(e.name):
                    # a start op and its done op are one collective
                    done = _instruction(e.name).split(".")[0].endswith("-done")
                    intervals.setdefault(_purpose(place), []).append(
                        (e.start, e.end, not done))
            for e in d.async_events:
                if xplane.COLLECTIVE.match(e.name):
                    intervals.setdefault(_purpose(where(e.name)[0]), []).append(
                        (e.start, e.end, False))
            for purpose, found in intervals.items():
                total = self.collectives.setdefault(purpose, [0.0, 0.0])
                total[0] += xplane.length(xplane.clip(xplane.merge(
                    [(s, t) for s, t, _ in found]), lo, hi)) / chips
                total[1] += sum(counts for _, _, counts in found) / chips

    def phase_s(self, *phases) -> float:
        return sum(s for (phase, _, _), s in self.rows.items()
                   if phase in phases)


def _purpose(where) -> str:
    """What a collective is for, from where it was placed."""
    return {"backward": "gradient", "update": "gradient",
            "forward": "statistic"}.get(where[0], "other")


def _table(m):
    """The run's table, or None when there is no trace, no steps, no
    index module in the program or no index for the dominant module."""
    if m.trace is None or not _steps(m):
        return None
    if _last[0] is not m.trace:
        _last[:] = [m.trace, None]
        try:
            opindex = import_module(
                "deeplearning4j_tpu.observability.opindex")
        except ImportError:
            return None
        t0 = time.perf_counter()
        index = opindex.lookup(m.trace.dominant_module)
        build_s = time.perf_counter() - t0
        if index is not None:
            _last[1] = _Table(m.trace, index, opindex)
            _notes(m, _last[1], build_s)
    return _last[1]


def _notes(m, table, build_s: float) -> None:
    steps = _steps(m)
    by_layer: dict = {}
    for (phase, layer, primitive), s in table.rows.items():
        row = by_layer.setdefault((layer, phase), {})
        row[primitive] = row.get(primitive, 0.0) + s
    largest = sorted(by_layer.items(), key=lambda kv: -sum(kv[1].values()))
    m.notes["device_ms_by_layer"] = [
        [layer, phase, 1e3 * sum(row.values()) / steps,
         max(row, key=row.get)] for (layer, phase), row in largest[:15]]
    by_primitive: dict = {}
    for (phase, _, primitive), s in table.rows.items():
        by_primitive[phase, primitive] = by_primitive.get(
            (phase, primitive), 0.0) + s
    m.notes["device_ms_by_primitive"] = [
        [phase, primitive, 1e3 * s / steps] for (phase, primitive), s
        in sorted(by_primitive.items(), key=lambda kv: -kv[1])[:12]]
    m.notes["opindex_build_s"] = build_s
    if table.collectives:
        m.notes["collectives_per_step"] = {
            purpose: count / steps
            for purpose, (_, count) in table.collectives.items()}


def phase_ms_per_step(m, phases: list):
    """Leaf time of the dominant program placed in one of ``phases``."""
    table = _table(m)
    return None if table is None else (
        1e3 * table.phase_s(*phases) / _steps(m))


def fit_input_ms_per_step(m):
    """Device time of the fit loop itself: ops of the dominant program
    that are no layer's and not the update's (slicing the chunk, the
    converts, the rng split), and every other program in the window
    (stacking the next chunk, slicing the scores)."""
    table = _table(m)
    return None if table is None else 1e3 * (
        table.phase_s("input") + table.other_programs) / _steps(m)


def unplaced_share(m):
    """Busy time whose instruction the index lacks or cannot place."""
    table = _table(m)
    if table is None or not m.trace.busy_s:
        return None
    return 100.0 * table.phase_s("unplaced") / m.trace.busy_s


def convolution_share(m):
    """Busy share of ops that are, or fuse, a ``convolution``. On a TPU
    a dense layer's matmul is one too."""
    table = _table(m)
    if table is None or not m.trace.busy_s:
        return None
    return 100.0 * table.convolution / m.trace.busy_s


def collective_ms_per_step(m, purpose: str):
    """Time inside collectives (a leaf op, or an async start..done pair,
    merged) of one purpose: ``gradient`` (placed backward or update) or
    ``statistic`` (placed forward: batch-norm moments). None when the
    trace holds no collective at all."""
    table = _table(m)
    if table is None or not table.collectives:
        return None
    return 1e3 * table.collectives.get(purpose, [0.0])[0] / _steps(m)
