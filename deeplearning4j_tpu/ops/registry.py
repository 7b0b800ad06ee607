"""Op-lowering registry — the TPU-native analogue of the reference's cuDNN
Helper seam.

In the reference, layers reflectively load a per-layer ``*Helper`` and route
forward/backward through cuDNN when present
(nn/layers/convolution/ConvolutionLayer.java:69-76, :274-275;
nn/layers/normalization/BatchNormalization.java:53-60). Here the same seam is
an explicit registry: every hot op has an ``xla`` implementation (jax.numpy /
lax — what XLA lowers and fuses) and may have a ``pallas`` override (a
hand-written TPU kernel) that is used when enabled. The backend-equivalence
test harness (tests/test_backend_equivalence.py, the CuDNNGradientChecks
analogue from SURVEY.md §4) asserts pallas == xla on identical inputs.

Usage:
    @ops.register("conv2d", backend="xla")
    def conv2d_xla(...): ...

    impl = ops.get("conv2d")          # resolves preference order
    y = impl(x, w, ...)
"""

from __future__ import annotations

import os
import threading

_LOCK = threading.Lock()
_IMPLS: dict[str, dict[str, callable]] = {}

# Preference order; "pallas" first means use the hand kernel when one exists.
_DEFAULT_ORDER = ("pallas", "xla")
_order = list(_DEFAULT_ORDER)


def pallas_interpret() -> bool:
    """Shared interpret-mode switch for every pallas backend (set
    DL4J_TPU_PALLAS_INTERPRET=1 to run the hand kernels through the
    Pallas interpreter off-TPU — how the equivalence tests exercise them
    on CPU)."""
    return os.environ.get("DL4J_TPU_PALLAS_INTERPRET", "0") == "1"


def register(name: str, backend: str = "xla"):
    def deco(fn):
        with _LOCK:
            _IMPLS.setdefault(name, {})[backend] = fn
        return fn

    return deco


def get(name: str, backend: str | None = None):
    impls = _IMPLS.get(name)
    if not impls:
        raise KeyError(f"No implementation registered for op '{name}'")
    if backend is not None:
        return impls[backend]
    for b in _order:
        if b in impls:
            return impls[b]
    raise KeyError(
        f"Op '{name}' has no implementation in preferred backends {_order}; "
        f"registered: {sorted(impls)}")


def backends(name: str):
    return sorted(_IMPLS.get(name, {}))


def available_ops():
    return sorted(_IMPLS)


def set_preference(order):
    """Set global backend preference order, e.g. ("xla",) to disable pallas."""
    global _order
    with _LOCK:
        _order = list(order)


class use_backend:
    """Context manager pinning the preference order (for equivalence tests)."""

    def __init__(self, *order):
        self.order = order

    def __enter__(self):
        self.prev = list(_order)
        set_preference(self.order)
        return self

    def __exit__(self, *exc):
        set_preference(self.prev)
        return False
