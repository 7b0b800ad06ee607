"""Per-layer metrics of device ops selected by named scopes that the
metric's own file lists (``readers/scoped.py`` reads one scope of a
module constant; PERF.md section 7 asks a ``benchmark`` issue to fold
the two into one).

A metric's ``args`` give ``scopes``, the scopes whose time it sums, and
may give ``among``, the scopes an op is placed among (``scopes`` where
left out): ``opindex.place(entry, scopes=among)`` gives an op the
innermost of ``among`` on its path. So a scope is counted with what lies
under its inner scopes by leaving those out of ``among``, and apart from
them by listing them. Times are of leaf ops inside executions of the
dominant program, clipped to the analysed window, mean over the chips,
as ``readers/scoped.py`` takes them. A program without the op index or
with no time under the scopes gives None, and the line leaves the metric
out.
"""

from __future__ import annotations

from importlib import import_module

import numpy as np

from benchmark import xplane
from benchmark.manifest import resolve
from benchmark.readers.trace import _steps

_tabulated: dict = {}       # among -> (trace, {(phase, scope): seconds})


def _seconds(m, among: tuple):
    """{(phase, scope): seconds a chip} of the run's trace with every op
    placed among ``among``, or None."""
    if m.trace is None or not _steps(m):
        return None
    if among in _tabulated and _tabulated[among][0] is m.trace:
        return _tabulated[among][1]
    _tabulated[among] = (m.trace, None)
    try:
        opindex = import_module("deeplearning4j_tpu.observability.opindex")
    except ImportError:
        return None
    index = opindex.lookup(m.trace.dominant_module)
    if index is None:
        return None
    placed: dict = {}
    total: dict = {}
    chips = len(m.trace.devices)
    for d in m.trace.devices:
        lo, hi = d.window
        own = xplane.merge(
            [(ev.start, ev.end) for ev in d.module_events
             if xplane.module_label(ev.name) == m.trace.dominant_module])
        if not len(own):
            continue
        starts = np.asarray([e.start for e, _ in d.leaves])
        run = np.searchsorted(own[:, 0], starts, side="right") - 1
        inside = (run >= 0) & (starts < own[np.maximum(run, 0), 1])
        for (e, _), mine in zip(d.leaves, inside):
            if not mine:
                continue
            name = e.name.split(" = ", 1)[0].lstrip("%")
            if name not in placed:
                placed[name] = opindex.place(index.get(name),
                                             scopes=among)[:2]
            total[placed[name]] = total.get(placed[name], 0.0) + (
                min(e.end, hi) - max(e.start, lo)) / chips
    _tabulated[among] = (m.trace, total)
    m.notes["device_ms_by_scope:" + "+".join(among)] = sorted(
        ([phase, scope, 1e3 * s / _steps(m)]
         for (phase, scope), s in total.items() if scope),
        key=lambda row: -row[2])
    return total


def _under(m, scopes, among, phases):
    total = _seconds(m, tuple(among or scopes))
    if total is None:
        return None
    return sum(s for (phase, scope), s in total.items()
               if scope in scopes and (phases is None or phase in phases)
               ) or None


def share_of_busy(m, scopes: list, among: list = None):
    """Share of the device's busy time under ``scopes``, summed."""
    seconds = _under(m, scopes, among, None)
    if not seconds or not m.trace.busy_s:
        return None
    return 100.0 * seconds / m.trace.busy_s


def roofline(m, scopes: list, cost: str, phases: list = None,
             among: list = None):
    """The least time the work under ``scopes`` (in ``phases``, all when
    None) could take for one step, the larger of FLOPs over peak FLOP/s
    and bytes over peak bytes/s of ``cost(config, traffic, counters)``,
    over the time its ops took."""
    seconds = _under(m, scopes, among, phases)
    need = resolve(cost)(m.config, m.traffic, m.counters)
    if not seconds or not need:
        return None
    by_flops = need["flops"] / m.peaks["bf16_flops_per_s"]
    by_bytes = need["bytes"] / m.peaks["hbm_bytes_per_s"]
    steps = _steps(m)
    m.notes[f"roofline:{'+'.join(scopes)}:{'+'.join(phases or ['all'])}"] = {
        "bound": "flops" if by_flops >= by_bytes else "hbm_bytes",
        "least_ms_per_step": 1e3 * max(by_flops, by_bytes),
        "ms_per_step": 1e3 * seconds / steps}
    return 100.0 * max(by_flops, by_bytes) * steps / seconds
