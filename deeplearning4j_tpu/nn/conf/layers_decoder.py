"""Configs of a modern decoder on the net's own path: integer token ids
in, RMS norm, rotary positions, grouped-query heads with per-head
query/key norm, routed experts of which this chip holds a share, a
state-space mixer, and a head with integer labels and per-token weights.

Two decoders are built of them (PERF.md section 4 has both models):

- a block-diffusion decoder (``zoo.sdar_moe``): ``TokenEmbedding``,
  ``MoeDecoderBlock`` (attention under the block-diffusion mask, then a
  softmax router over gated silu experts), ``RmsNorm``, ``TokenOutput``
  over the noised half of the rows. A batch row holds a noised copy and
  a clean copy of one sequence, ``2 * seq`` ids, both at positions
  ``0..seq-1`` (ops/attention.py has the mask).
- a causal hybrid decoder (``zoo.nemotron_h``): ``TokenEmbedding``, then
  one mixer a layer, each with its own pre-norm and residual, in the
  order of a pattern string: ``Mamba2Mixer``, ``CausalAttention``,
  ``RoutedExperts`` (a sigmoid router with a correction bias over
  ``relu2`` experts without a gate, and a shared expert every row
  takes); ``RmsNorm``, ``TokenOutput`` over every row.

Layout as the recurrent family: ``[batch, time, features]``, but the
first layer takes ``[batch, time]`` integer ids (``InputType.recurrent(
vocab)`` states the vocabulary). These layers train and answer
``net.output``; they carry no streaming state, and ``rnn_time_step``
raises on them (serving them is ROADMAP Queue 2 items 9 and 10).
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
    chosen experts held here of c_e down_e(h_e(w))``, ``w = RMSNorm(a)``.
    The router scores all ``n_experts`` and keeps ``experts_per_token``;
    this layer holds experts ``first_expert .. first_expert +
    experts_held - 1`` and leaves out what the others would add (their
    chips add it in a deployment). No pair is dropped whatever the load.

    ``router``: ``"softmax"`` (the block-diffusion decoder's) takes the
    largest of ``softmax(W_r w)`` and renormalises them; ``"sigmoid"``
    (the hybrid decoder's) scores ``s = sigmoid(W_r w)``, chooses the
    largest of ``s + bias`` (``router_bias`` in the layer's state: a
    buffer no gradient reaches, zero at init) and weighs a chosen expert
    by ``s`` without the bias, renormalised. Either way the weights are
    multiplied by ``routed_scale``.
    ``expert_form``: ``"gated_silu"``, ``h = silu(gate w) * up w``, three
    matrices an expert (``Wg``, ``Wu``, ``Wd``); or ``"relu2"``, ``h =
    max(up w, 0)^2``, two (``Wu``, ``Wd``).
    ``shared_width`` > 0 adds a shared ``relu2`` expert of that width,
    ``down_s(max(up_s w, 0)^2)`` (``Ws_u``, ``Ws_d``), that every row
    takes, whichever experts it chose and wherever they are held."""

    layer_type = "routed_experts"
    n_experts: int = 8
    experts_per_token: int = 2
    expert_width: int = 64
    experts_held: Optional[int] = None      # None: all of them
    first_expert: int = 0
    eps: float = 1e-6
    router: str = "softmax"
    routed_scale: float = 1.0
    expert_form: str = "gated_silu"
    shared_width: int = 0

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
class CausalAttention(_WidthPreserving):
    """Pre-norm causal grouped-query attention with a residual: ``x +
    W_o concat_h(softmax_{j <= i}(q_h,i . k_g(h),j / sqrt(head_dim))
    v_g(h))`` of ``u = RMSNorm(x)``, ``n_heads`` query heads reading
    ``n_kv_heads`` key/value heads, no bias, no rotation and no other
    positional term (a hybrid decoder's state-space layers carry
    position). The projections are ``MoeDecoderBlock``'s, without its
    per-head norms."""

    layer_type = "causal_attention"
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    eps: float = 1e-5

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import CausalAttentionLayer
        return CausalAttentionLayer(self, input_type, global_conf, policy)


@register_layer
@dataclass(frozen=True)
class Mamba2Mixer(_WidthPreserving):
    """Pre-norm Mamba-2 mixer with a residual (Dao and Gu 2024): ``u =
    RMSNorm(x)`` is projected to a gate ``z`` and ``x`` (``n_heads *
    head_dim`` each), ``B`` and ``C`` (``n_groups * state_size`` each)
    and ``dt`` (``n_heads``); ``x``, ``B``, ``C`` pass a depthwise
    causal convolution of ``conv_kernel`` positions and a silu; the
    selective recurrence ``S_t = exp(dt A) S_{t-1} + dt x (x) B``, ``y =
    S C + D x`` runs with a ``[head_dim, state_size]`` state a head in
    chunks of ``chunk`` positions (ops/ssm.py has the equations);
    ``GroupRMSNorm(y * silu(z))`` over ``n_groups`` groups, then the
    output projection. The sequence must be whole chunks."""

    layer_type = "mamba2_mixer"
    n_heads: int = 4
    head_dim: int = 8
    n_groups: int = 2
    state_size: int = 16
    conv_kernel: int = 4
    chunk: int = 128
    eps: float = 1e-5
    # dt_bias is the inverse softplus of dt ~ logU[dt_min, dt_max],
    # floored at dt_floor
    dt_min: float = 1e-3
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import Mamba2MixerLayer
        return Mamba2MixerLayer(self, input_type, global_conf, policy)


@register_layer
@dataclass(frozen=True)
class TokenOutput(BaseRecurrentConfig):
    """Logits without bias, integer labels, a weight per token in the
    labels mask (none: every weight 1); the loss is the weighted
    cross-entropy summed and divided by the number of labels (every
    token, not the mask's sum). ``causal`` False (the block-diffusion
    decoder): the head reads the first half of the rows, the noised
    copy, logits ``[b, t/2, n_out]`` and labels ``[b, t/2]``. ``causal``
    True (the hybrid decoder): every row, logits ``[b, t, n_out]`` and
    labels ``[b, t]``, the next token."""

    layer_type = "token_output"
    causal: bool = False

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import TokenOutputLayer
        return TokenOutputLayer(self, input_type, global_conf, policy)
