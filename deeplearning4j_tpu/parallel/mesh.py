"""Device-mesh helpers.

The mesh is the TPU-native replacement for the reference's device topology
handling (ParallelWrapper's AffinityManager thread->device pinning,
ParallelWrapper.java:352): axes are logical ('data', 'model', ...) and XLA
maps collectives onto ICI rings.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh


def make_mesh(axes: dict[str, int] | None = None, devices=None) -> Mesh:
    """Build a Mesh from {axis_name: size}. Default: all local devices on one
    'data' axis (pure data parallelism, the reference's only strategy)."""
    devices = list(jax.devices()) if devices is None else list(devices)
    if axes is None:
        axes = {"data": len(devices)}
    sizes = list(axes.values())
    total = int(np.prod(sizes))
    if total > len(devices):
        raise ValueError(
            f"Mesh needs {total} devices but only {len(devices)} available")
    dev_array = np.asarray(devices[:total]).reshape(sizes)
    return Mesh(dev_array, tuple(axes.keys()))


def compat_shard_map(f, *, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with per-shard replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
