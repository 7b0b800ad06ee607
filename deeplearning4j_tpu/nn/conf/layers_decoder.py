"""Configs of a modern decoder on the net's own path: integer token ids
in, RMS norm, rotary positions, grouped-query heads with per-head
query/key norm, routed experts of which this chip holds a share, a
state-space mixer, a gated short convolution, and a head with integer
labels and per-token weights.

Five decoders are built of them (PERF.md section 4 has the models):

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
- a causal latent-attention decoder (``zoo.glm4_moe_lite``):
  ``TokenEmbedding``, ``LatentDenseBlock`` for the leading layers (latent
  attention, then a dense gated silu MLP), ``LatentMoeBlock`` for the
  rest (latent attention, then a sigmoid router with a correction bias
  over gated silu experts and a shared gated silu expert), and
  ``MtpTokenOutput``: the final norm, the head, and a multi-token-
  prediction module that reads the embedding's matrix and the head's
  own (``RmsNorm`` and ``TokenOutput(causal=True)`` without the module).
- a causal short-convolution / attention decoder (``zoo.lfm2_moe``):
  ``TokenEmbedding``, then one operator and one feed-forward a layer in
  the order of a pattern string: the operator a gated short convolution
  (``ShortConv*Block``) or causal grouped-query attention with head
  norms and rotation (``Causal*Block``), the feed-forward a dense gated
  silu MLP in the leading layers (``*DenseBlock``) and sigmoid-routed
  gated silu experts without a shared one in the rest (``*MoeBlock``);
  ``RmsNorm`` and ``TokenOutput(causal=True, tied_to=<the embedding>)``,
  a head that reads the embedding's own matrix.
- a causal sparse-attention decoder (``zoo.keye_vl2_moe``):
  ``TokenEmbedding``, ``SparseMoeBlock`` (grouped-query attention over
  the keys a learned indexer selects for each row, then a softmax router
  over gated silu experts), ``RmsNorm``, ``TokenOutput(causal=True)``.

Which decoder uses what:

    ======================  ==========  ==========  ==============  ==========  ==========
                            sdar_moe    nemotron_h  glm4_moe_lite   lfm2_moe    keye_vl2
    ======================  ==========  ==========  ==============  ==========  ==========
    TokenEmbedding          x           x           x (two users)   x (tied)    x
    RmsNorm                 x           x           without module  x           x
    TokenOutput             causal=F    causal=T    without module  tied_to     causal=T
    MoeDecoderBlock         x
    CausalAttention                     x
    Mamba2Mixer                         x
    RoutedExperts           (in block)  x           (in blocks)     (in blocks) (in block)
      router                softmax     sigmoid     sigmoid         sigmoid     softmax
      router_eps            1e-20       1e-20       1e-20           1e-6        1e-20
      expert_form           gated_silu  relu2       gated_silu      gated_silu  gated_silu
      shared expert         none        relu2       gated_silu      none        none
    LatentDenseBlock                                x
    LatentMoeBlock                                  x
    MtpTokenOutput                                  with module
    ShortConvDenseBlock                                             x
    ShortConvMoeBlock                                               x
    CausalDenseBlock                                                (pattern)
    CausalMoeBlock                                                  x
    SparseMoeBlock                                                              x
    ops/attention.py        block_diff  causal      causal          causal, 64  (bodies)
    ops/sparse_attention.py                                                     x
    ops/grouped.py          kernels     chunk loop  kernels, 2 sl.  kernels, 2 sl. kernels
    ops/ssm.py                          x
    ops/shortconv.py                                                x
    ======================  ==========  ==========  ==============  ==========  ==========

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
    by ``s`` without the bias, renormalised: ``s_e / (sum of the chosen
    + router_eps)``. Either way the weights are multiplied by
    ``routed_scale``.
    ``expert_form``: ``"gated_silu"``, ``h = silu(gate w) * up w``, three
    matrices an expert (``Wg``, ``Wu``, ``Wd``); or ``"relu2"``, ``h =
    max(up w, 0)^2``, two (``Wu``, ``Wd``).
    ``shared_width`` > 0 adds a shared expert of that width and of the
    routed experts' form (``Ws_u``, ``Ws_d``, and ``Ws_g`` where gated)
    that every row takes, whichever experts it chose and wherever they
    are held."""

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
    router_eps: float = 1e-20   # in the sigmoid router's denominator

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import RoutedExpertsLayer
        return RoutedExpertsLayer(self, input_type, global_conf, policy)


@dataclass(frozen=True)
class _RotatedHeads:
    """Fields of a causal grouped-query attention with head norms and
    rotation, for ``u = RMSNorm(x)`` and positions ``p = 0..t-1``:

        q_h = R_p RMSNorm_q(W_q,h u)   k_g = R_p RMSNorm_k(W_k,g u)   v_g = W_v,g u
        x + W_o concat_h(softmax_{j <= i}(q_h,i . k_g(h),j / sqrt(head_dim)) v_g(h),j)

    ``n_heads`` query heads read ``n_kv_heads`` key/value heads of
    ``head_dim``; the head norms are over the head's columns, and
    ``R_p`` rotates the whole head (column ``i`` with ``i + head_dim /
    2``). ``MoeDecoderBlock`` has these heads under the block-diffusion
    mask (both halves of its rows at ``0..t/2-1``)."""

    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    rope_theta: float = 1e6


@register_layer
@dataclass(frozen=True)
class MoeDecoderBlock(_RotatedHeads, RoutedExperts):
    """One decoder layer: pre-norm grouped-query attention with head
    norms and rotation under the block-diffusion mask with a residual,
    then the routed experts above."""

    layer_type = "moe_decoder_block"
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


@dataclass(frozen=True)
class _LatentAttention:
    """Fields of a latent attention (DeepSeek-V2's multi-head latent
    attention, arXiv 2405.04434 section 2.1), for ``u = RMSNorm(x)``:

        c_q  = RMSNorm(W_dq u)  [q_rank]      q_h = W_uq,h c_q = [q_nope,h | q_rope,h]
        [c_kv | k_rope] = W_dkv u             c_kv <- RMSNorm(c_kv)  [kv_rank]
        [k_nope,h | v_h] = W_ukv,h c_kv
        q_h = [q_nope,h | R_p q_rope,h]       k_h = [k_nope,h | R_p k_rope]
        x + W_o concat_h(softmax_{j <= i}(q_h,i . k_h,j / sqrt(nope_dim + rope_dim)) v_h,j)

    ``R_p`` rotates the ``rope_dim`` columns alone (column ``i`` with ``i
    + rope_dim / 2``) by the row's position, 0 for the first row; one
    ``k_rope`` serves all heads. ``v_dim`` must equal ``nope_dim +
    rope_dim`` (ops/attention.py takes one head size)."""

    n_heads: int = 4
    q_rank: int = 24
    kv_rank: int = 16
    nope_dim: int = 24
    rope_dim: int = 8
    v_dim: int = 32
    rope_theta: float = 1e6


@register_layer
@dataclass(frozen=True)
class LatentMoeBlock(_LatentAttention, RoutedExperts):
    """One decoder layer: pre-norm latent attention with a residual,
    then the routed experts above."""

    layer_type = "latent_moe_block"

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import LatentMoeBlockLayer
        return LatentMoeBlockLayer(self, input_type, global_conf, policy)


@dataclass(frozen=True)
class _DenseMlp:
    """Fields of the feed-forward of a leading dense layer: ``a + W_d
    (silu(W_g w) * W_u w)``, ``w = RMSNorm(a)``, of ``mlp_width``."""

    mlp_width: int = 128
    eps: float = 1e-5


@register_layer
@dataclass(frozen=True)
class LatentDenseBlock(_LatentAttention, _DenseMlp, _WidthPreserving):
    """One decoder layer: pre-norm latent attention with a residual,
    then a pre-norm dense gated silu MLP with one."""

    layer_type = "latent_dense_block"

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import LatentDenseBlockLayer
        return LatentDenseBlockLayer(self, input_type, global_conf, policy)


@register_layer
@dataclass(frozen=True)
class CausalMoeBlock(_RotatedHeads, RoutedExperts):
    """One decoder layer: pre-norm causal grouped-query attention with
    head norms and rotation and a residual, then the routed experts
    above."""

    layer_type = "causal_moe_block"

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import CausalMoeBlockLayer
        return CausalMoeBlockLayer(self, input_type, global_conf, policy)


@register_layer
@dataclass(frozen=True)
class SparseMoeBlock(_RotatedHeads, RoutedExperts):
    """One decoder layer: ``CausalMoeBlock``'s, whose attention takes for
    each row only the ``index_topk`` earlier keys a learned indexer
    scores highest (DeepSeek Sparse Attention). For ``u = RMSNorm(x)``,
    row ``t`` at position ``t``, ``R_t`` the rotation over the whole
    head:

        qI_t,j = R_t (W_IQ u_t)_j                 j = 1..index_heads, index_head_dim each
        kI_s   = R_s LayerNorm(W_IK u_s)          index_head_dim, one key head
        w_t,j  = (W_w u_t)_j / sqrt(index_heads * index_head_dim)
        I_t,s  = sum_j w_t,j relu(qI_t,j . kI_s)     s <= t, float32
        S_t    = the index_topk largest I_t,s (all t + 1 keys where t < index_topk),
                 ties to the lower s, shared by every head
        x + W_o concat_h(softmax_{s in S_t}(q_h,t . k_g(h),s / sqrt(head_dim)) v_g(h),s)

    then the routed experts. The indexer reads ``u`` without its
    gradient, and trains on a loss of its own that the layer hands the
    net (``MultiLayerNetwork._loss`` adds it): ``L_I = mean_t sum_{s in
    S_t} p_t,s (log p_t,s - log softmax_{S_t}(I_t)_s)``, ``p`` the
    attention's probabilities averaged over the heads, without a
    gradient. So the indexer's leaves learn from ``L_I`` alone and every
    other leaf from the data loss alone."""

    layer_type = "sparse_moe_block"
    index_heads: int = 4
    index_head_dim: int = 16
    index_topk: int = 16

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import SparseMoeBlockLayer
        return SparseMoeBlockLayer(self, input_type, global_conf, policy)


@register_layer
@dataclass(frozen=True)
class CausalDenseBlock(_RotatedHeads, _DenseMlp, _WidthPreserving):
    """One decoder layer: the attention of ``CausalMoeBlock``, then a
    pre-norm dense gated silu MLP with a residual."""

    layer_type = "causal_dense_block"

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import CausalDenseBlockLayer
        return CausalDenseBlockLayer(self, input_type, global_conf, policy)


@dataclass(frozen=True)
class _ShortConv:
    """Fields of a gated short-convolution operator (the LFM2 family's
    ``conv`` layers), for ``u = RMSNorm(x)`` of width ``d``:

        [B | C | x~] = W_in u                     [3 d], in this order
        g_t = B_t * x~_t
        s_t,c = sum_{k < K} w_c,k g_{t-K+1+k, c}   (zeros before position 0)
        x + W_out (C_t * s_t)

    a depthwise causal filter of ``conv_kernel`` positions, no bias, no
    activation (ops/shortconv.py computes ``C * conv(B * x~)`` in one
    pass over ``W_in``'s output as it lies)."""

    conv_kernel: int = 3


@register_layer
@dataclass(frozen=True)
class ShortConvMoeBlock(_ShortConv, RoutedExperts):
    """One decoder layer: the pre-norm short-convolution operator with a
    residual, then the routed experts above."""

    layer_type = "short_conv_moe_block"

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import (
            ShortConvMoeBlockLayer)
        return ShortConvMoeBlockLayer(self, input_type, global_conf, policy)


@register_layer
@dataclass(frozen=True)
class ShortConvDenseBlock(_ShortConv, _DenseMlp, _WidthPreserving):
    """One decoder layer: the pre-norm short-convolution operator with a
    residual, then a pre-norm dense gated silu MLP with one."""

    layer_type = "short_conv_dense_block"

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import (
            ShortConvDenseBlockLayer)
        return ShortConvDenseBlockLayer(self, input_type, global_conf,
                                        policy)


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
    labels ``[b, t]``, the next token. ``tied_to`` names the net's
    ``TokenEmbedding``: the head then has no matrix of its own and its
    logits are ``rows E^T`` of that layer's ``W`` ``[n_out, width]``,
    one stored leaf whose gradient is the gather's and the product's,
    summed."""

    layer_type = "token_output"
    causal: bool = False
    tied_to: Optional[str] = None

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import TokenOutputLayer
        return TokenOutputLayer(self, input_type, global_conf, policy)


@register_layer
@dataclass(frozen=True)
class MtpTokenOutput(LatentMoeBlock):
    """The head of a decoder trained to predict two tokens a row
    (DeepSeek-V3's multi-token prediction, arXiv 2412.19437 section
    2.2, one module): for ``h`` the last block's output and integer
    labels ``[b, 2, t]``, row ``i`` holding tokens ``i + 1`` and ``i +
    2``,

        logits  = W RMSNorm_f(h)                              L_main = nll(logits, labels[:, 0])
        h'      = W_eh [RMSNorm_e(Emb(labels[:, 0])) ; RMSNorm_h(h)]
        g       = one LatentMoeBlock (this conf's fields) on h', positions 0..t-1
        logits' = W RMSNorm_s(g)                              L_mtp  = nll(logits', labels[:, 1])
        loss    = L_main + mtp_weight * L_mtp

    ``Emb`` is the matrix ``W`` of the layer named ``embedding``, the
    net's ``TokenEmbedding``: one stored leaf with two users, whose
    gradient is the sum of both (so is the head's ``W``, used twice
    here). ``net.output`` answers ``logits`` alone; the two losses of
    the last step ride in the layer's state (``mtp_loss``). A labels
    mask, where given, is ``[b, 2, t]`` too. ``n_out`` is the hidden
    width, as for every block; the logits are ``vocab_size`` wide."""

    layer_type = "mtp_token_output"
    vocab_size: int = 256
    mtp_weight: float = 0.3
    embedding: str = "layer_0"

    def get_output_type(self, input_type):
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        return InputType.recurrent(
            self.vocab_size, None if input_type is None
            else input_type.timesteps)

    def make_layer(self, input_type, global_conf, policy):
        from deeplearning4j_tpu.nn.layers.decoder import MtpTokenOutputLayer
        return MtpTokenOutputLayer(self, input_type, global_conf, policy)
