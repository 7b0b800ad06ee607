"""Shared perf accounting: device peak FLOP/s table + XLA cost-model
extraction. Single source of truth for bench.py, PerformanceListener, and
the networks' ``step_cost_analysis`` (SURVEY.md §5.1)."""

from __future__ import annotations

import os

# bf16 matmul peak FLOP/s by device kind prefix (public spec numbers)
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e: 197 TFLOP/s bf16
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v6": 918e12,        # trillium
}


def peak_flops(device) -> float | None:
    """Peak FLOP/s for the MFU denominator. The DL4J_TPU_PEAK_FLOPS env
    override wins over the table — it is the only way to get an MFU
    number on devices without an honest spec entry (CPU), and lets TPU
    users pin the f32 vs bf16 peak they are actually comparing against."""
    override = os.environ.get("DL4J_TPU_PEAK_FLOPS")
    if override:
        try:
            return float(override)
        except ValueError:
            pass
    kind = getattr(device, "device_kind", "")
    for prefix, peak in PEAK_FLOPS.items():
        if kind.startswith(prefix):
            return peak
    return None


def _lower(jitted_step, args):
    if not hasattr(jitted_step, "lower"):
        raise NotImplementedError(
            "cost analysis needs a plain jitted step (meshed nets wrap it)")
    return jitted_step.lower(*args)


def _cost_numbers(cost) -> dict:
    cost = cost or {}
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0))}


def xla_step_cost(jitted_step, *args) -> dict:
    """Cost-model numbers for one compiled call of ``jitted_step(*args)``:
    {"flops", "bytes_accessed"}. Right after a dispatch of the step with
    arguments of the same avals and shardings this costs a cache lookup:
    jax hands back the lowering and the executable the dispatch made.
    Raises NotImplementedError for wrapped (non-jit) steps such as the
    meshed trainers."""
    return _cost_numbers(
        _lower(jitted_step, args).compile().cost_analysis())
