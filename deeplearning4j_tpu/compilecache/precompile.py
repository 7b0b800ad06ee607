"""Build-time AOT compilation: run every executable a deploy will need
BEFORE the deploy, persisting into the compilation cache.

Two surfaces:

- :func:`precompile_serving` — the serving bucket ladder, through the
  SAME seam the server warms lazily (``ReplicaSet.warm`` over a
  ``ModelServer`` built with ``warmup=False``): identical forward,
  identical shapes, identical HLO, so the cache entries written here
  are byte-for-byte the ones a later boot looks up. Covers replicated,
  bf16-shadow and mesh tensor-parallel forwards because it goes through
  the server's own construction path rather than re-deriving it.
- :func:`precompile_fit` — both nets' jitted train step via explicit
  AOT ``step.lower(*args).compile()`` on zero-filled arrays of the
  training batch shape. Lowering + compiling never executes the step
  (params are untouched; donation only applies at execution), and the
  AOT path routes through the same ``compile_or_get_cached`` as jit, so
  a later ``fit`` of the same shapes boots warm.

Both return manifest entry dicts; ``scripts/precompile.py`` assembles
them into the schema'd artifact (compilecache.manifest) next to the
cache dir.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from deeplearning4j_tpu.compilecache import cache as _cache
from deeplearning4j_tpu.compilecache import manifest as _manifest
from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet


def precompile_serving(net, *, cache_dir: str, max_batch: int = 1024,
                       min_batch: Optional[int] = None,
                       input_shapes=None, compute_dtype=None,
                       replicas: int = 1, mesh=None,
                       model_axis: str = "model", data_axis=None,
                       tp_rules=None) -> dict:
    """AOT-compile the serving bucket ladder into *cache_dir* and return
    the manifest ``serving`` entry. Raises ValueError when the row
    shapes can't be inferred and ``input_shapes`` wasn't given."""
    from deeplearning4j_tpu.serving.batcher import bucket_ladder
    from deeplearning4j_tpu.serving.server import ModelServer

    _cache.configure(cache_dir)
    server = ModelServer(net, port=0, max_batch=max_batch, warmup=False,
                         input_shapes=input_shapes,
                         compute_dtype=compute_dtype, replicas=replicas,
                         mesh=mesh, model_axis=model_axis,
                         data_axis=data_axis, tp_rules=tp_rules)
    try:
        shapes = server._infer_row_shapes()
        if shapes is None:
            raise ValueError(
                "cannot infer serving row shapes from the model "
                "configuration — pass input_shapes explicitly")
        mb = server._batcher
        server._fleet.warm(shapes)
        return {
            "row_shapes": [list(s) for s in shapes],
            "ladder": bucket_ladder(mb.min_batch, mb.max_batch),
            "max_batch": int(mb.max_batch),
            "min_batch": int(mb.min_batch),
            "compute_dtype": server.serving_compute_dtype,
            "mesh_axes": _manifest._mesh_axes(mesh),
        }
    finally:
        server._fleet.stop()


def precompile_fit(net, *, cache_dir: str, batch: int = 32,
                   input_shapes=None) -> dict:
    """AOT-compile the net's train step for one training batch shape
    into *cache_dir* (``lower().compile()``, no execution) and return
    the manifest ``train`` entry. Works for MultiLayerNetwork and
    ComputationGraph with feed-forward output heads; ``input_shapes``
    overrides per-input row shapes when inference can't derive them."""
    import jax
    import jax.numpy as jnp

    _cache.configure(cache_dir)
    if net.params is None:
        net.init()
    step = net._build_train_step()
    row_shapes = input_shapes or _infer_row_shapes(net)
    if row_shapes is None:
        raise ValueError(
            "cannot infer training input shapes — pass input_shapes")
    zeros = net._batch_args(_dummy_batch(net, batch, row_shapes))
    lowered = step.lower(net.params, net.state, net.opt_state,
                         jnp.asarray(0, jnp.int32), *zeros,
                         jax.random.PRNGKey(0))
    lowered.compile()
    return {
        "kind": "train_step",
        "net": type(net).__name__,
        "batch": int(batch),
        "row_shapes": [list(s) for s in row_shapes],
    }


def _dummy_batch(net, batch: int, row_shapes, label_rows=None,
                 fill: float = 0.0):
    """A constant minibatch of the net's training shapes (label rows
    default to every output head's ``n_out``). A DataSet suits both nets;
    only a graph takes several inputs or heads."""
    label_rows = label_rows or [(n,) for n in _output_widths(net)]
    xs = [np.full((batch,) + tuple(s), fill, np.float32) for s in row_shapes]
    ys = [np.full((batch,) + tuple(s), fill, np.float32) for s in label_rows]
    if len(xs) == len(ys) == 1:
        return DataSet(xs[0], ys[0])
    return MultiDataSet(xs, ys)


def _infer_row_shapes(net) -> Optional[list]:
    """Per-input row shapes via the server's inference (one code path
    for both precompile surfaces — serving and fit must agree on what
    the model eats)."""
    from deeplearning4j_tpu.serving.server import ModelServer
    probe = ModelServer.__new__(ModelServer)
    probe.input_shapes = None
    probe.net = net
    probe._is_graph = hasattr(net, "conf") and hasattr(
        net.conf, "network_inputs")
    return probe._infer_row_shapes()


def _output_widths(net) -> List[int]:
    """n_out of every output head (label widths for the dummy batch)."""
    if hasattr(net.conf, "network_outputs"):
        return [int(net._resolved_confs[name].n_out)
                for name in net.conf.network_outputs]
    return [int(net._resolved_confs[-1].n_out)]
