"""Per-layer metrics read from the device trace (``benchmark/xplane.py``).

Every reader returns None when the run has no reduced trace, so a run
that could not be traced leaves these metrics out of its line.
"""

from __future__ import annotations

from benchmark.manifest import resolve


def _steps(m):
    """Optimizer steps inside the analysed window of the trace."""
    per_dispatch = m.counters.get("steps_per_dispatch")
    if m.trace is None or not per_dispatch:
        return None
    return m.trace.executions * per_dispatch


def _cost(m, function: str) -> dict:
    return resolve(function)(m.config, m.traffic)


def busy_ms_per_step(m):
    steps = _steps(m)
    return None if not steps else 1e3 * m.trace.busy_s / steps


def mfu_required(m):
    """Required FLOPs of a step (``required_flops`` of the configuration)
    over what the chips could do in the time they were busy with it."""
    steps = _steps(m)
    if not steps or not m.trace.busy_s:
        return None
    flops = _cost(m, m.config["required_flops"])["flops"]
    peak = m.peaks["bf16_flops_per_s"] * m.chips
    return 100.0 * flops * steps / (m.trace.busy_s * peak)


def op_share_of_busy(m, pattern: str):
    if m.trace is None or not m.trace.busy_s:
        return None
    seconds = m.trace.op_seconds(pattern)
    return None if seconds is None else 100.0 * seconds / m.trace.busy_s


def roofline(m, pattern: str, cost: str):
    """The least time the matched ops could take for one step, the larger
    of FLOPs over peak FLOP/s and bytes over peak bytes/s of ``cost``,
    over the time they took. Which of the two binds is left in
    ``m.notes``."""
    steps = _steps(m)
    seconds = None if m.trace is None else m.trace.op_seconds(pattern)
    if not steps or not seconds:
        return None
    need = _cost(m, cost)
    by_flops = need["flops"] / m.peaks["bf16_flops_per_s"]
    by_bytes = need["bytes"] / m.peaks["hbm_bytes_per_s"]
    m.notes[f"roofline:{pattern}"] = {
        "bound": "flops" if by_flops >= by_bytes else "hbm_bytes",
        "least_ms_per_step": 1e3 * max(by_flops, by_bytes),
        "ms_per_step": 1e3 * seconds / steps}
    return 100.0 * max(by_flops, by_bytes) * steps / seconds


def collective_exposed_ms_per_step(m):
    steps = _steps(m)
    if not steps:
        return None
    exposed = m.trace.collective_exposed_s()
    return None if exposed is None else 1e3 * exposed / steps
