"""Per-layer metrics that are counts or readings the run took itself."""

from __future__ import annotations


def counter(m, key: str):
    """``m.counters[key]`` as it is; None when the run did not take it."""
    return m.counters.get(key)


def slowdown_share(m, on: str, off: str):
    """``1 - on/off`` in percent, for two rates of one run (the part of
    the window with the profiler on against the part with it off)."""
    a, b = m.counters.get(on), m.counters.get(off)
    if not a or not b:
        return None
    return 100.0 * (1.0 - a / b)
