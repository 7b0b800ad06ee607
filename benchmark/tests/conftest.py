"""Tests of the benchmark's own arithmetic. Not part of tier-1: run them
with ``python3 benchmark/run.py --self-test`` or
``python3 -m pytest benchmark/tests -q -p no:cacheprovider``."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
