"""Per-layer metrics of device ops selected by the named scope the
program ran them under, whatever implements them: a Pallas kernel, a
fusion or a loop body's product all carry the scope in their ``op_name``.

The program's op index (``deeplearning4j_tpu/observability/opindex.py``)
gives every instruction of the dispatched step program its ``op_name``;
``opindex.place(entry, scopes=SCOPES)`` reads the phase (forward or
backward) and the innermost of ``SCOPES`` on the path. A program without
the index, without a registration for the dominant module, or with no
time under a scope gives None, and the line leaves the metric out.

Times are of leaf ops inside executions of the dominant program,
clipped to the analysed window, mean over the chips (as
``readers/opindex.py`` takes them).
"""

from __future__ import annotations

from importlib import import_module

import numpy as np

from benchmark import xplane
from benchmark.manifest import resolve
from benchmark.readers.trace import _steps

SCOPES = ("attn", "block_attention", "route", "experts")

_last = [None, None]        # the trace last tabulated, and its seconds


def _seconds(m):
    """{(phase, scope): seconds a chip} of the run's trace, or None."""
    if m.trace is None or not _steps(m):
        return None
    if _last[0] is m.trace:
        return _last[1]
    _last[:] = [m.trace, None]
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
                phase, scope, _ = opindex.place(index.get(name),
                                                scopes=SCOPES)
                placed[name] = (phase, scope)
            key = placed[name]
            total[key] = total.get(key, 0.0) + (
                min(e.end, hi) - max(e.start, lo)) / chips
    _last[1] = total
    m.notes["device_ms_by_scope"] = sorted(
        ([phase, scope, 1e3 * s / _steps(m)]
         for (phase, scope), s in total.items() if scope),
        key=lambda row: -row[2])
    return total


def _under(m, scope: str, phases):
    total = _seconds(m)
    if total is None:
        return None
    return sum(s for (phase, sc), s in total.items()
               if sc == scope and (phases is None or phase in phases)) or None


def scope_roofline(m, scope: str, cost: str, phases=None):
    """The least time the work under ``scope`` (in ``phases``, all when
    None) could take for one step, the larger of FLOPs over peak FLOP/s
    and bytes over peak bytes/s of ``cost(config, traffic, counters)``,
    over the time its ops took. None when the cost function has nothing
    to count from."""
    seconds = _under(m, scope, phases)
    need = resolve(cost)(m.config, m.traffic, m.counters)
    if not seconds or not need:
        return None
    by_flops = need["flops"] / m.peaks["bf16_flops_per_s"]
    by_bytes = need["bytes"] / m.peaks["hbm_bytes_per_s"]
    steps = _steps(m)
    m.notes[f"roofline:{scope}:{'+'.join(phases or ['all'])}"] = {
        "bound": "flops" if by_flops >= by_bytes else "hbm_bytes",
        "least_ms_per_step": 1e3 * max(by_flops, by_bytes),
        "ms_per_step": 1e3 * seconds / steps}
    return 100.0 * max(by_flops, by_bytes) * steps / seconds


def scope_share_of_busy(m, scope: str):
    seconds = _under(m, scope, None)
    if not seconds or not m.trace.busy_s:
        return None
    return 100.0 * seconds / m.trace.busy_s


def largest_over_mean(m, key: str):
    """Largest over mean of the counts ``m.counters[key]`` (a list)."""
    counts = m.counters.get(key)
    if not counts or not np.mean(counts):
        return None
    return float(np.max(counts) / np.mean(counts))
