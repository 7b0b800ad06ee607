"""Test configuration: run on a virtual 8-device CPU mesh so sharding tests
exercise real multi-device semantics without TPU hardware, and enable x64
so gradient checks can run in float64 like the reference's
(double-precision) checks.

The platform is forced through jax.config, not only JAX_PLATFORMS, so the
suite stays on the CPU even on a machine that holds a chip (no backend
has been initialized yet at conftest time, so the switch takes effect).
``DL4J_TPU_TESTS=1`` leaves the platform alone and runs ONLY the
TPU-gated modules — the on-chip line is
``DL4J_TPU_TESTS=1 python -m pytest tests/test_backend_equivalence.py
tests/test_tpu_numerics.py -q``; ``chip_smoke.py`` is the end-to-end
chip check.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

_TPU_MODE = os.environ.get("DL4J_TPU_TESTS", "0") == "1"

if not _TPU_MODE:
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

# Lock-order detection is on by default under pytest (ANALYSIS.md):
# every threading.Lock/RLock the suite allocates is instrumented, the
# cross-thread acquisition-order graph accumulates over the whole run,
# and the session fails if it ends with a cycle (a would-be deadlock
# some interleaving will eventually hit). DL4J_TPU_LOCK_CHECK=0 opts
# out. Installed at conftest import time — before any module under test
# allocates a lock.
os.environ.setdefault("DL4J_TPU_LOCK_CHECK", "1")
from deeplearning4j_tpu.analysis import lockorder as _lockorder  # noqa: E402

_lockorder.maybe_install()

# Modules meaningful against the real accelerator (no x64 dependence).
# DL4J_TPU_TESTS=1 runs ONLY these — the rest of the suite assumes the
# x64 CPU configuration (f64 gradient checks, tight f64 tolerances) and
# would spuriously fail without it.
_TPU_MODULES = {"test_backend_equivalence.py", "test_tpu_numerics.py"}


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long end-to-end runs (chaos training, full recovery "
        "matrices) excluded from the tier-1 `-m 'not slow'` sweep")


def pytest_collection_modifyitems(config, items):
    if not _TPU_MODE:
        return
    import pytest
    skip = pytest.mark.skip(
        reason="DL4J_TPU_TESTS=1 runs only the TPU-gated modules; the rest "
               "of the suite requires the x64 CPU configuration")
    for item in items:
        if os.path.basename(str(item.fspath)) not in _TPU_MODULES:
            item.add_marker(skip)


def pytest_sessionfinish(session, exitstatus):
    """The lock-order gate: a cycle accumulated anywhere in the run is a
    would-be deadlock — report it and fail the session even when every
    individual test passed. (Tests that build cycles on purpose use
    private LockOrderGraphs via lockorder.instrument(graph=...), which
    never touch the global graph checked here.)"""
    if not _lockorder.installed():
        return
    findings = _lockorder.get_graph().findings()
    if not findings:
        return
    print("\n" + "=" * 24, "lock-order cycles (DL4J-L001)", "=" * 24)
    for f in findings:
        print(f)
    print("cross-thread lock acquisition-order cycle(s) detected — "
          "see ANALYSIS.md")
    import pytest
    session.exitstatus = pytest.ExitCode.TESTS_FAILED
