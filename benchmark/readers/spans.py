"""Per-layer metrics read from the program's own spans
(``observability/trace.py``), over the whole measured window.

A reader takes the run's ``Measurement`` (see ``benchmark/measure.py``)
and the ``args`` of its metric file, and returns a number, or None when
it found nothing to read.
"""

from __future__ import annotations


def share_of_window(m, span: str):
    """Summed duration of ``span`` over the window, in percent. The fit
    loop's spans do not nest, so a span's duration is its self time."""
    durations = [s.dur_us for s in m.spans if s.name == span]
    if not durations or not m.window_s:
        return None
    return 100.0 * sum(durations) * 1e-6 / m.window_s


def mean_steps_attr(m, span: str):
    """Mean of the ``steps`` attribute over the ``span``s in the window
    (a span without it covers one step): optimizer steps per dispatch."""
    steps = [(s.attrs or {}).get("steps", 1) for s in m.spans
             if s.name == span]
    return sum(steps) / len(steps) if steps else None
