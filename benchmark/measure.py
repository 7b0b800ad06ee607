"""What one run hands to the per-layer readers."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional


@dataclass
class Measurement:
    config: dict                    # the cell's configuration file
    traffic: dict                   # the cell's traffic file
    chips: int
    peaks: Optional[dict]           # this device's row of peaks.json
    window_s: float                 # measured window, first to last barrier
    spans: list                     # the program's spans inside the window
    counters: dict                  # counts and readings the runner took
    trace: object = None            # xplane.Reduction of the traced part
    notes: dict = field(default_factory=dict)   # free-form, printed early
