"""DataSet pre-processors: what a data pipeline does to a minibatch on
the host before the net sees it (parity: ND4J's DataSetPreProcessor,
which an iterator applies to every DataSet it hands out).

``PreProcessingIterator(base, pre_processor)`` applies one to every
minibatch of ``base``.
"""

from __future__ import annotations

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import DataSetIterator
from deeplearning4j_tpu.observability.trace import get_tracer


class BlockDiffusionPreProcessor:
    """The noising step of block-diffusion training (SDAR; PERF.md
    section 4). From the ids ``x0`` ``[b, L]`` of a minibatch it draws a
    noise level ``t ~ U(0, 1]`` for every block of ``block_len`` tokens,
    replaces each token of the block by ``mask_id`` with probability
    ``t``, and returns the DataSet the decoder trains on:

    - features ``int32 [b, 2L]``: the noised ids, then the clean ids;
    - labels ``int32 [b, L]``: the clean ids;
    - labels mask ``float32 [b, L]``: ``1 / t`` of its block where the
      token was replaced and 0 elsewhere, the linear schedule's weight.

    Seeded and stateful: the n-th call after construction (or
    ``reset()``) draws the same noise. The net stays deterministic, so a
    reference can be given the very batch the net was."""

    def __init__(self, block_len: int, mask_id: int, seed: int = 0):
        self.block_len = int(block_len)
        self.mask_id = int(mask_id)
        self.seed = int(seed)
        self.reset()

    def reset(self):
        self._rng = np.random.default_rng(self.seed)

    def pre_process(self, ds: DataSet) -> DataSet:
        with get_tracer().span("block_diffusion_noise"):
            x0 = np.asarray(ds.features)
            if x0.ndim != 2 or not np.issubdtype(x0.dtype, np.integer):
                raise TypeError(
                    "BlockDiffusionPreProcessor takes integer ids [b, L], "
                    f"got {x0.dtype}{x0.shape}")
            b, length = x0.shape
            if length % self.block_len:
                raise ValueError(
                    f"sequences of {length} ids are not whole blocks of "
                    f"{self.block_len}")
            x0 = x0.astype(np.int32)
            # U(0, 1]: a block is never given weight 1/0
            t = 1.0 - self._rng.random((b, length // self.block_len))
            t = np.repeat(t, self.block_len, axis=1)
            masked = self._rng.random((b, length)) < t
            xt = np.where(masked, np.int32(self.mask_id), x0)
            weights = np.where(masked, 1.0 / t, 0.0).astype(np.float32)
            return DataSet(np.concatenate([xt, x0], axis=1), x0,
                           labels_mask=weights)


class PreProcessingIterator(DataSetIterator):
    """``base`` with ``pre_processor.pre_process`` applied to every
    minibatch (DataSetIterator.setPreProcessor parity)."""

    def __init__(self, base: DataSetIterator, pre_processor):
        self.base = base
        self.pre_processor = pre_processor

    def __iter__(self):
        for ds in self.base:
            yield self.pre_processor.pre_process(ds)

    def reset(self):
        self.base.reset()
        reset = getattr(self.pre_processor, "reset", None)
        if reset is not None:
            reset()

    @property
    def batch_size(self):
        return self.base.batch_size
