"""Configs of a modern decoder on the net's own path: integer token ids
in, RMS norm, rotary positions, grouped-query heads with per-head
query/key norm, routed gated experts of which this chip holds a share,
and a head with integer labels and per-token weights.

Layout as the recurrent family: ``[batch, time, features]``, but the
first layer takes ``[batch, time]`` integer ids (``InputType.recurrent(
vocab)`` states the vocabulary). These layers train and answer
``net.output``; they carry no streaming state, and ``rnn_time_step``
raises on them (serving them is ROADMAP Queue 2 item 10).

The attention here runs under the block-diffusion mask
(ops/attention.py): a batch row holds a noised copy and a clean copy of
one sequence, ``2 * seq`` ids, both at positions ``0..seq-1``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from deeplearning4j_tpu.nn.conf.layers import register_layer
from deeplearning4j_tpu.nn.conf.layers_recurrent import BaseRecurrentConfig


@dataclass(frozen=True)
class _WidthPreserving(BaseRecurrentConfig):
    layer_type = "base_width_preserving"

    def with_n_in(self, input_type):
        c = super().with_n_in(input_type)
        return c if c.n_out is not None else c.replace(n_out=c.n_in)


@register_layer
@dataclass(frozen=True)
class TokenEmbedding(BaseRecurrentConfig):
    """Integer ids ``[b, t]`` -> ``[b, t, n_out]``: one gather from a
    table of ``n_in`` rows (the vocabulary held here). No positions: the
    decoder blocks rotate them in."""

    layer_type = "token_embedding"

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import TokenEmbeddingLayer
        return TokenEmbeddingLayer(self, input_type, global_conf, policy)


@register_layer
@dataclass(frozen=True)
class RmsNorm(_WidthPreserving):
    """``x / sqrt(mean(x^2) + eps) * weight`` over the feature axis,
    statistics in float32."""

    layer_type = "rms_norm"
    eps: float = 1e-6

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import RmsNormLayer
        return RmsNormLayer(self, input_type, global_conf, policy)


@register_layer
@dataclass(frozen=True)
class RoutedExperts(_WidthPreserving):
    """Pre-norm routed experts with a residual: ``a + sum over the
    chosen experts held here of c_e down_e(silu(gate_e w) * up_e w)``,
    ``w = RMSNorm(a)``. The router scores all ``n_experts``, keeps
    ``experts_per_token`` and renormalises their weights; this layer
    holds experts ``first_expert .. first_expert + experts_held - 1``
    and leaves out what the others would add (their chips add it in a
    deployment). No pair is dropped whatever the load."""

    layer_type = "routed_experts"
    n_experts: int = 8
    experts_per_token: int = 2
    expert_width: int = 64
    experts_held: Optional[int] = None      # None: all of them
    first_expert: int = 0
    eps: float = 1e-6

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import RoutedExpertsLayer
        return RoutedExpertsLayer(self, input_type, global_conf, policy)


@register_layer
@dataclass(frozen=True)
class MoeDecoderBlock(RoutedExperts):
    """One decoder layer: pre-norm grouped-query attention under the
    block-diffusion mask with a residual (``n_heads`` query heads reading
    ``n_kv_heads`` key/value heads of ``head_dim``, RMS norm on every
    query and key head, rotary positions over the whole head), then the
    routed experts above."""

    layer_type = "moe_decoder_block"
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    rope_theta: float = 1e6
    block_len: int = 4

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import MoeDecoderBlockLayer
        return MoeDecoderBlockLayer(self, input_type, global_conf, policy)


@register_layer
@dataclass(frozen=True)
class TokenOutput(BaseRecurrentConfig):
    """Head over the first half of the rows (the noised copy): logits
    ``[b, t/2, n_out]`` without bias. Labels are integer ids ``[b, t/2]``
    and the labels mask holds a weight per token; the loss is the
    weighted cross-entropy summed and divided by ``b * t/2`` (every
    token, not the mask's sum: the block-diffusion objective)."""

    layer_type = "token_output"

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import TokenOutputLayer
        return TokenOutputLayer(self, input_type, global_conf, policy)
