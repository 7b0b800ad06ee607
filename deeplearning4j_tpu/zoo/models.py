"""Canonical model builders for the baseline configs (BASELINE.md #1-#3).

These are the TPU-native renderings of the reference's flagship example
nets: LeNet on MNIST (MultiLayerNetwork.fit path,
deeplearning4j-nn/.../MultiLayerNetwork.java:947), ResNet-v1 bottleneck
graphs (ComputationGraph.fit path, ComputationGraph.java:701 + the
CudnnConvolutionHelper.java:49 conv stack), and a GravesLSTM char-RNN
(LSTMHelpers.java:57,271). All convs are NHWC (TPU-preferred layout; the
lowering handles it — the reference is NCHW at the API only).

By default conv/LSTM models use bf16 compute with f32 master params — the
MXU-native dtype policy.
"""

from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.core import DtypePolicy
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (ActivationLayer, Dense,
                                               Output)
from deeplearning4j_tpu.nn.conf.layers_conv import (
    BatchNorm,
    Convolution2D,
    GlobalPooling,
    Subsampling,
)
from deeplearning4j_tpu.nn.conf.layers_recurrent import GravesLSTM, RnnOutput
from deeplearning4j_tpu.nn.conf.vertices import ElementWiseVertex
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import Adam, Nesterovs
from deeplearning4j_tpu.observability.trace import get_tracer

BF16 = DtypePolicy(param_dtype="float32", compute_dtype="bfloat16")
F32 = DtypePolicy(param_dtype="float32", compute_dtype="float32")
# f16 compute implies dynamic loss scaling (DtypePolicy loss_scale="auto"
# resolves to dynamic for float16) — see PRECISION.md
F16 = DtypePolicy(param_dtype="float32", compute_dtype="float16")


def mnist_mlp(seed: int = 42, dtype: Optional[DtypePolicy] = None
              ) -> MultiLayerNetwork:
    """784-256-128-10 MLP (the round-1 smoke/bench model)."""
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Adam(1e-3)).activation("relu")
            .dtype(dtype or F32)
            .list()
            .layer(Dense(n_out=256))
            .layer(Dense(n_out=128))
            .layer(Output(n_out=10, loss="mcxent", activation="softmax"))
            .set_input_type(InputType.feed_forward(784))
            .build())
    return MultiLayerNetwork(conf).init()


def lenet(seed: int = 42, n_classes: int = 10,
          dtype: Optional[DtypePolicy] = None) -> MultiLayerNetwork:
    """LeNet MNIST (baseline config #1): conv5x5x20 -> maxpool2 ->
    conv5x5x50 -> maxpool2 -> dense500 -> softmax (the canonical DL4J
    LeNet example topology)."""
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Nesterovs(0.01, 0.9)).activation("relu")
            .dtype(dtype or BF16)
            .list()
            .layer(Convolution2D(n_out=20, kernel=(5, 5), stride=(1, 1),
                                 activation="identity"))
            .layer(Subsampling(kernel=(2, 2), stride=(2, 2), pooling="max"))
            .layer(Convolution2D(n_out=50, kernel=(5, 5), stride=(1, 1),
                                 activation="identity"))
            .layer(Subsampling(kernel=(2, 2), stride=(2, 2), pooling="max"))
            .layer(Dense(n_out=500, activation="relu"))
            .layer(Output(n_out=n_classes, loss="mcxent",
                          activation="softmax"))
            .set_input_type(InputType.convolutional(28, 28, 1))
            .build())
    return MultiLayerNetwork(conf).init()


def _conv_bn(g, name: str, n_out: int, kernel, stride, inputs: str,
             activation: str = "relu"):
    g.add_layer(f"{name}_conv",
                Convolution2D(n_out=n_out, kernel=kernel, stride=stride,
                              mode="same", has_bias=False,
                              activation="identity"),
                inputs)
    # activation must be EXPLICIT identity: a bare BatchNorm() inherits
    # the global default activation (sigmoid, reference parity), which
    # would squash every BN output — the round-1..3 zoo had exactly that
    # bug, silently training (and benchmarking) a sigmoid-gated ResNet
    g.add_layer(f"{name}_bn", BatchNorm(activation="identity"),
                f"{name}_conv")
    if activation != "identity":
        g.add_layer(f"{name}_act", ActivationLayer(activation=activation),
                    f"{name}_bn")
        return f"{name}_act"
    return f"{name}_bn"


def _bottleneck(g, name: str, inputs: str, filters: int, stride: int,
                project: bool) -> str:
    """ResNet-v1 bottleneck: 1x1 (reduce) -> 3x3 -> 1x1 (expand, x4), with
    an identity or projection shortcut."""
    x = _conv_bn(g, f"{name}_a", filters, (1, 1), (stride, stride), inputs)
    x = _conv_bn(g, f"{name}_b", filters, (3, 3), (1, 1), x)
    x = _conv_bn(g, f"{name}_c", filters * 4, (1, 1), (1, 1), x,
                 activation="identity")
    if project:
        shortcut = _conv_bn(g, f"{name}_proj", filters * 4, (1, 1),
                            (stride, stride), inputs, activation="identity")
    else:
        shortcut = inputs
    g.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), x, shortcut)
    g.add_layer(f"{name}_out", ActivationLayer(activation="relu"),
                f"{name}_add")
    return f"{name}_out"


def _basic_block(g, name: str, inputs: str, filters: int, stride: int,
                 project: bool) -> str:
    """ResNet-v1 basic block (3x3 -> 3x3) for ResNet-18/34."""
    x = _conv_bn(g, f"{name}_a", filters, (3, 3), (stride, stride), inputs)
    x = _conv_bn(g, f"{name}_b", filters, (3, 3), (1, 1), x,
                 activation="identity")
    if project:
        shortcut = _conv_bn(g, f"{name}_proj", filters, (1, 1),
                            (stride, stride), inputs, activation="identity")
    else:
        shortcut = inputs
    g.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), x, shortcut)
    g.add_layer(f"{name}_out", ActivationLayer(activation="relu"),
                f"{name}_add")
    return f"{name}_out"


def _resnet(stage_blocks, block_fn, bottleneck: bool, *, image_size: int,
            n_classes: int, seed: int, dtype: Optional[DtypePolicy],
            updater=None) -> ComputationGraph:
    g = (NeuralNetConfiguration.builder()
         .seed(seed).updater(updater or Nesterovs(0.1, 0.9))
         .dtype(dtype or BF16)
         .graph_builder()
         .add_inputs("img"))
    x = _conv_bn(g, "stem", 64, (7, 7), (2, 2), "img")
    g.add_layer("stem_pool",
                Subsampling(kernel=(3, 3), stride=(2, 2), pooling="max",
                            mode="same"),
                x)
    x = "stem_pool"
    filters = 64
    in_ch = 64
    for stage, n_blocks in enumerate(stage_blocks):
        out_ch = filters * 4 if bottleneck else filters
        for b in range(n_blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            # projection shortcut only where shapes change (canonical
            # ResNet: identity everywhere else)
            project = b == 0 and (stride != 1 or in_ch != out_ch)
            x = block_fn(g, f"s{stage}b{b}", x, filters, stride, project)
            in_ch = out_ch
        filters *= 2
    g.add_layer("head_pool", GlobalPooling(pooling="avg"), x)
    g.add_layer("fc", Output(n_out=n_classes, loss="mcxent",
                             activation="softmax"), "head_pool")
    conf = (g.set_outputs("fc")
            .set_input_types(InputType.convolutional(image_size, image_size,
                                                     3))
            .build())
    return ComputationGraph(conf).init()



def resnet50(seed: int = 42, n_classes: int = 1000, image_size: int = 224,
             dtype: Optional[DtypePolicy] = None,
             updater=None) -> ComputationGraph:
    """ResNet-50 v1 (baseline config #2): bottleneck stages [3, 4, 6, 3]."""
    return _resnet([3, 4, 6, 3], _bottleneck, True, image_size=image_size,
                   n_classes=n_classes, seed=seed, dtype=dtype,
                   updater=updater)


def resnet18(seed: int = 42, n_classes: int = 10, image_size: int = 32,
             dtype: Optional[DtypePolicy] = None,
             updater=None) -> ComputationGraph:
    """ResNet-18 (baseline config #5's CIFAR-10 model): basic-block stages
    [2, 2, 2, 2]; defaults sized for CIFAR."""
    return _resnet([2, 2, 2, 2], _basic_block, False, image_size=image_size,
                   n_classes=n_classes, seed=seed, dtype=dtype,
                   updater=updater)


def char_rnn(vocab_size: int = 80, hidden: int = 512, n_layers: int = 2,
             seed: int = 42, dtype: Optional[DtypePolicy] = None
             ) -> MultiLayerNetwork:
    """GravesLSTM char-RNN (baseline config #3): stacked LSTMs ->
    per-timestep softmax (the reference's LSTMHelpers example shape)."""
    b = (NeuralNetConfiguration.builder()
         .seed(seed).updater(Adam(2e-3)).dtype(dtype or BF16)
         .list())
    for _ in range(n_layers):
        b = b.layer(GravesLSTM(n_out=hidden, activation="tanh"))
    conf = (b.layer(RnnOutput(n_out=vocab_size, loss="mcxent",
                              activation="softmax"))
            .set_input_type(InputType.recurrent(vocab_size))
            .build())
    return MultiLayerNetwork(conf).init()


def gpt_mini(vocab_size: int = 80, width: int = 256, n_layers: int = 4,
             n_heads: int = 4, max_len: int = 256,
             max_cache_len: Optional[int] = None, seed: int = 42,
             dtype: Optional[DtypePolicy] = None) -> MultiLayerNetwork:
    """GPT-style decoder-only LM (ROADMAP item 1's workload): one-hot
    tokens -> GptEmbedding (learned positions) -> ``n_layers`` pre-LN
    TransformerBlocks -> streaming-exact softmax head. Serving decode
    carries a fixed-extent KV cache of ``max_cache_len`` (defaults to
    ``max_len``) per block — see nn/layers/attention.py for the decode
    bit-identity contract."""
    from deeplearning4j_tpu.nn.conf.layers_attention import (
        GptEmbedding, GptOutput, TransformerBlock)
    cache = int(max_cache_len or max_len)
    b = (NeuralNetConfiguration.builder()
         .seed(seed).updater(Adam(3e-4)).dtype(dtype or BF16)
         .list()
         .layer(GptEmbedding(n_out=width, max_len=max_len)))
    for _ in range(n_layers):
        b = b.layer(TransformerBlock(n_heads=n_heads, activation="gelu",
                                     max_cache_len=cache))
    conf = (b.layer(GptOutput(n_out=vocab_size, loss="mcxent",
                              activation="softmax"))
            .set_input_type(InputType.recurrent(vocab_size))
            .build())
    return MultiLayerNetwork(conf).init()


def sdar_moe(seed: int = 42, n_layers: int = 48, n_experts: int = 128,
             experts_held: Optional[int] = None, first_expert: int = 0,
             vocab_size: int = 151_936, hidden: int = 2048,
             n_heads: int = 32, n_kv_heads: int = 4, head_dim: int = 128,
             expert_width: int = 768, experts_per_token: int = 8,
             rope_theta: float = 1e6, eps: float = 1e-6,
             block_len: int = 4, learning_rate: float = 1e-5,
             dtype: Optional[DtypePolicy] = None) -> MultiLayerNetwork:
    """SDAR-30B-A3B-Chat (``model_type`` sdar_moe; the defaults are its
    published config.json): a decoder of RMS-normed grouped-query
    attention with per-head query/key norm and rotary positions, and
    routed SwiGLU experts, trained by diffusion over blocks of
    ``block_len`` tokens. A batch row is the noised copy and the clean
    copy of one sequence (``datasets.BlockDiffusionPreProcessor`` makes
    it), integer ids in and integer labels with a weight per token out.

    ``experts_held`` and ``first_expert`` give this chip's share of the
    experts under expert parallelism (the router still scores all
    ``n_experts``); ``vocab_size`` is the slice of the vocabulary held
    here, its last id the ``[MASK]`` token.

    Init: normal(0, 0.02), the family's ``initializer_range``, with two
    departures, both for every seed alike. The embedding rows are
    normal(0, 1): at 0.02 the first layer's attention output is 12 times
    its input, every row's router sees the same average of values, and
    one expert takes nearly every row. And where the experts are shared
    out, a router starts balanced between the shares: the columns of
    every share of ``experts_held`` experts start as copies of the first
    share's, so a row's ``n_experts / experts_held`` best experts are
    one a share, and every chip is given exactly one pair a row until
    training moves the columns apart. A trained router is balanced by
    its auxiliary loss; a seeded one gives a chip anything from 0.7 to
    1.3 of its share, because a quarter of a block-diffusion batch's
    rows are the same ``[MASK]`` token and take the same experts
    (PERF.md, Findings PR 31)."""
    from deeplearning4j_tpu.nn.conf.layers_decoder import (
        MoeDecoderBlock, RmsNorm, TokenEmbedding, TokenOutput)
    held = n_experts if experts_held is None else experts_held
    if n_experts % held:
        raise ValueError(
            f"sdar_moe: shares of {held} experts do not divide {n_experts}")
    b = (NeuralNetConfiguration.builder()
         .seed(seed).updater(Adam(learning_rate)).dtype(dtype or BF16)
         .weight_init({"type": "normal", "mean": 0.0, "std": 0.02})
         .list()
         .layer(TokenEmbedding(n_out=hidden, weight_init={
             "type": "normal", "mean": 0.0, "std": 1.0})))
    for _ in range(n_layers):
        b = b.layer(MoeDecoderBlock(
            n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
            rope_theta=rope_theta, block_len=block_len, eps=eps,
            n_experts=n_experts, experts_per_token=experts_per_token,
            expert_width=expert_width, experts_held=experts_held,
            first_expert=first_expert))
    conf = (b.layer(RmsNorm(eps=eps))
            .layer(TokenOutput(n_out=vocab_size, activation="identity"))
            .set_input_type(InputType.recurrent(vocab_size))
            .build())
    net = MultiLayerNetwork(conf).init()
    for p in net.params.values():
        if "Wr" in p:
            # on the host: the v5e's compiler aborts on some programs that
            # move 16 of 128 columns (IsFusibleUnalignedDUS, PR 31)
            first = np.asarray(p["Wr"])[:, :held]
            p["Wr"] = jnp.asarray(np.tile(first, (1, n_experts // held)))
    return net


NEMOTRON_H_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def nemotron_h(seed: int = 42, pattern: str = NEMOTRON_H_PATTERN,
               n_experts: int = 128, experts_held: Optional[int] = None,
               first_expert: int = 0, vocab_size: int = 131_072,
               hidden: int = 2688, mamba_heads: int = 64,
               mamba_head_dim: int = 64, n_groups: int = 8,
               state_size: int = 128, conv_kernel: int = 4,
               chunk: int = 128, n_heads: int = 32, n_kv_heads: int = 2,
               head_dim: int = 128, expert_width: int = 1856,
               shared_width: int = 3712, experts_per_token: int = 6,
               routed_scale: float = 2.5, eps: float = 1e-5,
               residual_depth: int = 52, learning_rate: float = 1e-5,
               dtype: Optional[DtypePolicy] = None) -> MultiLayerNetwork:
    """NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type`` nemotron_h; the
    defaults are its published config.json): a causal decoder of one
    mixer a layer, each with a pre-norm and a residual, in the order of
    ``pattern``: ``M`` a Mamba-2 mixer, ``*`` causal grouped-query
    attention without rotation, ``E`` routed ``relu2`` experts under a
    sigmoid router with a correction bias, renormalised and scaled,
    beside a shared expert. Integer ids in, the next token as integer
    labels out.

    ``experts_held`` and ``first_expert`` give this chip's share of the
    routed experts under expert parallelism (the router still scores all
    ``n_experts``; the shared expert is whole on every chip);
    ``vocab_size`` is the slice of the vocabulary held here.

    Init, for every seed alike: matrices normal(0, 0.02) (the family's
    ``initializer_range``), every mixer's output matrix divided by
    ``sqrt(residual_depth)`` (``rescale_prenorm_residual`` at the
    published depth, whatever ``pattern`` keeps of it), norm weights 1,
    and the mixer's own ``A_log``, ``D``, ``dt_bias`` and convolution
    (nn/layers/decoder.py ``Mamba2MixerLayer``). The embedding rows are
    normal(0, 1), as ``sdar_moe``'s and for its reason: at 0.02 the
    first mixer's output is several times its input, and rows that
    share a history reach the first router looking alike."""
    from deeplearning4j_tpu.nn.conf.layers_decoder import (
        CausalAttention, Mamba2Mixer, RmsNorm, RoutedExperts,
        TokenEmbedding, TokenOutput)
    kinds = {
        "M": lambda: Mamba2Mixer(
            n_heads=mamba_heads, head_dim=mamba_head_dim, n_groups=n_groups,
            state_size=state_size, conv_kernel=conv_kernel, chunk=chunk,
            eps=eps),
        "*": lambda: CausalAttention(
            n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
            eps=eps),
        "E": lambda: RoutedExperts(
            n_experts=n_experts, experts_per_token=experts_per_token,
            expert_width=expert_width, experts_held=experts_held,
            first_expert=first_expert, eps=eps, router="sigmoid",
            routed_scale=routed_scale, expert_form="relu2",
            shared_width=shared_width),
    }
    unknown = sorted(set(pattern) - set(kinds))
    if unknown or not pattern:
        raise ValueError(
            f"nemotron_h: pattern {pattern!r} may hold 'M' (Mamba-2), '*' "
            f"(attention) and 'E' (experts); it holds {unknown}")
    b = (NeuralNetConfiguration.builder()
         .seed(seed).updater(Adam(learning_rate)).dtype(dtype or BF16)
         .weight_init({"type": "normal", "mean": 0.0, "std": 0.02})
         .list()
         .layer(TokenEmbedding(n_out=hidden, weight_init={
             "type": "normal", "mean": 0.0, "std": 1.0})))
    for kind in pattern:
        b = b.layer(kinds[kind]())
    conf = (b.layer(RmsNorm(eps=eps))
            .layer(TokenOutput(n_out=vocab_size, activation="identity",
                               causal=True))
            .set_input_type(InputType.recurrent(vocab_size))
            .build())
    net = MultiLayerNetwork(conf).init()
    shrink = 1.0 / math.sqrt(residual_depth)
    with get_tracer().program_span("net_init"):     # eager, as init is
        for p in net.params.values():
            for leaf in ("W_out", "Wo", "Wd", "Ws_d"):
                if leaf in p:
                    p[leaf] = p[leaf] * shrink
    return net


def glm4_moe_lite(seed: int = 42, n_layers: int = 47, first_dense: int = 1,
                  n_experts: int = 64, experts_held: Optional[int] = None,
                  first_expert: int = 0, vocab_size: int = 154_880,
                  hidden: int = 2048, n_heads: int = 20, q_rank: int = 768,
                  kv_rank: int = 512, nope_dim: int = 192,
                  rope_dim: int = 64, v_dim: int = 256,
                  mlp_width: int = 10_240, expert_width: int = 1536,
                  shared_width: int = 1536, experts_per_token: int = 4,
                  routed_scale: float = 1.8, rope_theta: float = 1e6,
                  eps: float = 1e-5, mtp_modules: int = 1,
                  mtp_weight: float = 0.3, learning_rate: float = 1e-5,
                  dtype: Optional[DtypePolicy] = None) -> MultiLayerNetwork:
    """GLM-4.7-Flash (``model_type`` glm4_moe_lite; the defaults are its
    published config.json): a causal decoder of ``n_layers`` layers of
    latent attention (queries and keys/values expanded from compressed
    rows, rotary positions over ``rope_dim`` columns of a head), the
    first ``first_dense`` followed by a dense gated silu MLP and the
    others by routed gated silu experts under a sigmoid router with a
    correction bias, renormalised and scaled, beside a shared expert;
    and ``mtp_modules`` (0 or 1) multi-token-prediction module, one more
    expert layer that predicts the token after the next through the
    model's own embedding and head. Integer ids in; integer labels
    ``[b, 2, t]`` out (the next token and the one after; ``[b, t]``
    without the module).

    ``experts_held`` and ``first_expert`` give this chip's share of the
    routed experts under expert parallelism (the router still scores all
    ``n_experts``; attention, the dense layers and the shared expert are
    whole on every chip); ``vocab_size`` is the slice of the vocabulary
    held here.

    Init, for every seed alike: matrices normal(0, 0.02) (the family's
    ``initializer_range``), norm weights 1, router bias 0, and the
    embedding rows normal(0, 1) as the other two decoders' (every block
    norms its input, so the scale only sets how much of the stream the
    first layers replace; PERF.md Findings PR 37 has the pairs a layer
    is given at both scales)."""
    from deeplearning4j_tpu.nn.conf.layers_decoder import (
        LatentDenseBlock, LatentMoeBlock, MtpTokenOutput, RmsNorm,
        TokenEmbedding, TokenOutput)
    if mtp_modules not in (0, 1):
        raise ValueError(
            f"glm4_moe_lite: {mtp_modules} prediction modules; the output "
            "layer holds one or none")
    if not 0 <= first_dense <= n_layers:
        raise ValueError(
            f"glm4_moe_lite: {first_dense} dense layers among {n_layers}")
    attention = dict(n_heads=n_heads, q_rank=q_rank, kv_rank=kv_rank,
                     nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
                     rope_theta=rope_theta, eps=eps)
    experts = dict(attention, n_experts=n_experts,
                   experts_per_token=experts_per_token,
                   expert_width=expert_width, experts_held=experts_held,
                   first_expert=first_expert, router="sigmoid",
                   routed_scale=routed_scale, expert_form="gated_silu",
                   shared_width=shared_width)
    b = (NeuralNetConfiguration.builder()
         .seed(seed).updater(Adam(learning_rate)).dtype(dtype or BF16)
         .weight_init({"type": "normal", "mean": 0.0, "std": 0.02})
         .list()
         .layer(TokenEmbedding(n_out=hidden, weight_init={
             "type": "normal", "mean": 0.0, "std": 1.0})))
    for i in range(n_layers):
        b = b.layer(LatentDenseBlock(mlp_width=mlp_width, **attention)
                    if i < first_dense else LatentMoeBlock(**experts))
    if mtp_modules:
        b = b.layer(MtpTokenOutput(
            vocab_size=vocab_size, mtp_weight=mtp_weight,
            embedding="layer_0", activation="identity", **experts))
    else:
        b = b.layer(RmsNorm(eps=eps)).layer(TokenOutput(
            n_out=vocab_size, activation="identity", causal=True))
    return MultiLayerNetwork(
        b.set_input_type(InputType.recurrent(vocab_size)).build()).init()


LFM2_MOE_PATTERN = "cc" + "accc" * 9 + "ac"


def lfm2_moe(seed: int = 42, pattern: str = LFM2_MOE_PATTERN,
             n_dense: int = 2, n_experts: int = 64,
             experts_held: Optional[int] = None, first_expert: int = 0,
             vocab_size: int = 65_536, hidden: int = 2048,
             conv_kernel: int = 3, n_heads: int = 32, n_kv_heads: int = 8,
             head_dim: int = 64, mlp_width: int = 11_776,
             expert_width: int = 1536, experts_per_token: int = 4,
             routed_scale: float = 1.0, router_eps: float = 1e-6,
             rope_theta: float = 1e6, eps: float = 1e-5,
             learning_rate: float = 1e-5,
             dtype: Optional[DtypePolicy] = None) -> MultiLayerNetwork:
    """LFM2-24B-A2B (``model_type`` lfm2_moe; the defaults are its
    published config.json): a causal decoder of one operator and one
    feed-forward a layer, each pre-normed with a residual. The operator
    follows ``pattern``: ``c`` a gated short convolution (``C *
    conv_K(B * x~)`` between two projections, a depthwise causal filter
    of ``conv_kernel`` positions), ``a`` causal grouped-query attention
    with an RMS norm on every query and key head and rotary positions
    over the whole head. The feed-forward follows the layer's index:
    layers ``0..n_dense-1`` a dense gated silu MLP of ``mlp_width``, the
    rest routed gated silu experts under a sigmoid router with a
    correction bias, renormalised (``router_eps`` in the denominator), no
    shared expert. The head is the embedding's own matrix
    (``TokenOutput(tied_to=...)``): one stored leaf. Integer ids in, the
    next token as integer labels out.

    ``experts_held`` and ``first_expert`` give this chip's share of the
    routed experts under expert parallelism (the router still scores all
    ``n_experts``; operators and dense layers are whole on every chip);
    ``vocab_size`` is the slice of the vocabulary held here.

    Init, for every seed alike: matrices normal(0, 0.02) (the family's
    ``initializer_range``), the embedding among them: tied, it is the
    head too, and rows of normal(0, 1) would start the logits at a
    spread of 45 (PERF.md, Findings PR 39, has the pairs a layer is
    given at both scales); norm weights 1, router bias 0, the filter
    uniform(+-1/sqrt(conv_kernel)) as the state-space mixer's."""
    from deeplearning4j_tpu.nn.conf.layers_decoder import (
        CausalDenseBlock, CausalMoeBlock, RmsNorm, ShortConvDenseBlock,
        ShortConvMoeBlock, TokenEmbedding, TokenOutput)
    unknown = sorted(set(pattern) - set("ca"))
    if unknown or not pattern:
        raise ValueError(
            f"lfm2_moe: pattern {pattern!r} may hold 'c' (short "
            f"convolution) and 'a' (attention); it holds {unknown}")
    if not 0 <= n_dense <= len(pattern):
        raise ValueError(
            f"lfm2_moe: {n_dense} dense layers among {len(pattern)}")
    operators = {
        "c": dict(conv_kernel=conv_kernel),
        "a": dict(n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
                  rope_theta=rope_theta)}
    blocks = {("c", True): ShortConvDenseBlock, ("a", True): CausalDenseBlock,
              ("c", False): ShortConvMoeBlock, ("a", False): CausalMoeBlock}
    dense = dict(mlp_width=mlp_width, eps=eps)
    experts = dict(n_experts=n_experts, experts_per_token=experts_per_token,
                   expert_width=expert_width, experts_held=experts_held,
                   first_expert=first_expert, eps=eps, router="sigmoid",
                   routed_scale=routed_scale, router_eps=router_eps,
                   expert_form="gated_silu")
    b = (NeuralNetConfiguration.builder()
         .seed(seed).updater(Adam(learning_rate)).dtype(dtype or BF16)
         .weight_init({"type": "normal", "mean": 0.0, "std": 0.02})
         .list()
         .layer(TokenEmbedding(n_out=hidden)))
    for i, kind in enumerate(pattern):
        b = b.layer(blocks[kind, i < n_dense](
            **operators[kind], **(dense if i < n_dense else experts)))
    conf = (b.layer(RmsNorm(eps=eps))
            .layer(TokenOutput(n_out=vocab_size, activation="identity",
                               causal=True, tied_to="layer_0"))
            .set_input_type(InputType.recurrent(vocab_size))
            .build())
    return MultiLayerNetwork(conf).init()


def keye_vl2_moe(seed: int = 42, n_layers: int = 48, n_experts: int = 128,
                 experts_held: Optional[int] = None, first_expert: int = 0,
                 vocab_size: int = 151_936, hidden: int = 2048,
                 n_heads: int = 32, n_kv_heads: int = 4, head_dim: int = 128,
                 expert_width: int = 768, experts_per_token: int = 8,
                 index_heads: int = 16, index_head_dim: int = 64,
                 index_topk: int = 2048, rope_theta: float = 1e7,
                 eps: float = 1e-6, learning_rate: float = 1e-5,
                 dtype: Optional[DtypePolicy] = None) -> MultiLayerNetwork:
    """The language model of Keye-VL-2.0-30B-A3B (``model_type``
    KeyeVL2; the defaults are its published config.json, ``sa_config``
    among them): a causal decoder of RMS-normed grouped-query attention
    with per-head query/key norm and rotary positions, whose keys a
    learned indexer chooses for each row (the ``index_topk`` of all
    earlier keys it scores highest), and softmax-routed SwiGLU experts
    renormalised over the ``experts_per_token`` chosen, no shared one.
    Integer ids in, the next token as integer labels out; the indexer's
    own loss joins the data loss (``SparseMoeBlock``).

    ``experts_held`` and ``first_expert`` give this chip's share of the
    experts under expert parallelism (the router still scores all
    ``n_experts``); ``vocab_size`` is the slice of the vocabulary held
    here; the head is untied.

    Init, for every seed alike: matrices normal(0, 0.02), the family's
    ``initializer_range``; the embedding rows normal(0, 1), as the other
    three softmax- or sigmoid-routed decoders here seed them (at 0.02
    the first attention's output outweighs its input and every row's
    router sees much the same average, PERF.md Findings PR 31); the
    router column by column, with no balanced start (uniform ids and no
    ``[MASK]`` rows, PR 33's finding); norm weights 1, the indexer's key
    norm's bias 0."""
    from deeplearning4j_tpu.nn.conf.layers_decoder import (
        RmsNorm, SparseMoeBlock, TokenEmbedding, TokenOutput)
    b = (NeuralNetConfiguration.builder()
         .seed(seed).updater(Adam(learning_rate)).dtype(dtype or BF16)
         .weight_init({"type": "normal", "mean": 0.0, "std": 0.02})
         .list()
         .layer(TokenEmbedding(n_out=hidden, weight_init={
             "type": "normal", "mean": 0.0, "std": 1.0})))
    for _ in range(n_layers):
        b = b.layer(SparseMoeBlock(
            n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
            rope_theta=rope_theta, eps=eps, index_heads=index_heads,
            index_head_dim=index_head_dim, index_topk=index_topk,
            n_experts=n_experts, experts_per_token=experts_per_token,
            expert_width=expert_width, experts_held=experts_held,
            first_expert=first_expert))
    conf = (b.layer(RmsNorm(eps=eps))
            .layer(TokenOutput(n_out=vocab_size, activation="identity",
                               causal=True))
            .set_input_type(InputType.recurrent(vocab_size))
            .build())
    return MultiLayerNetwork(conf).init()


def gpt_mini_draft(vocab_size: int = 80, width: int = 128,
                   n_layers: int = 2, n_heads: int = 2, max_len: int = 256,
                   max_cache_len: Optional[int] = None, seed: int = 43,
                   dtype: Optional[DtypePolicy] = None) -> MultiLayerNetwork:
    """Draft-sized companion to ``gpt_mini`` for speculative decode
    (serving/decode.py): the SAME vocab/tokenizer contract — acceptance
    is exact argmax match against the target, so the two nets must index
    the same token space — at half the width and depth, so a draft
    forward costs a fraction of a target forward. Pass the target's
    ``vocab_size``/``max_cache_len`` when building the pair; the decode
    engine rejects a vocab mismatch at construction."""
    return gpt_mini(vocab_size=vocab_size, width=width, n_layers=n_layers,
                    n_heads=n_heads, max_len=max_len,
                    max_cache_len=max_cache_len, seed=seed, dtype=dtype)


def gpt_mini_tp_rules():
    """Tensor-parallel placement for ``gpt_mini`` (regex form,
    parallel/tensor.py match semantics, first match wins): column-parallel
    QKV + MLP up-projection (last axis on "model"), row-parallel output
    projection + MLP down-projection (first axis on "model"); embeddings
    and the LM head shard column-wise; norms/biases replicate via the
    default rule."""
    from jax.sharding import PartitionSpec as P
    return [
        (r"\['W[qkv]'\]", P(None, "model")),
        (r"\['W1'\]", P(None, "model")),
        (r"\['Wo'\]", P("model", None)),
        (r"\['W2'\]", P("model", None)),
        (r"\['W(tok|pos)'\]", P(None, "model")),
    ]


def vgg16(seed: int = 42, n_classes: int = 1000, image_size: int = 224,
          dtype: Optional[DtypePolicy] = None,
          updater=None) -> MultiLayerNetwork:
    """VGG-16 (TrainedModels.java VGG16 parity: the reference ships the
    architecture + preprocessing for its pretrained zoo entry
    deeplearning4j-modelimport/.../trainedmodels/TrainedModels.java).
    Pretrained ImageNet weights enter through the Keras importer
    (modelimport/keras.py) — this builder provides the canonical
    architecture; ``vgg16_preprocess`` the matching input pipeline."""
    b = (NeuralNetConfiguration.builder()
         .seed(seed).updater(updater or Nesterovs(0.01, 0.9))
         .dtype(dtype or BF16).activation("relu")
         .list())
    blocks = [(2, 64), (2, 128), (3, 256), (3, 512), (3, 512)]
    for n_convs, ch in blocks:
        for _ in range(n_convs):
            b = b.layer(Convolution2D(n_out=ch, kernel=(3, 3), mode="same",
                                      activation="relu"))
        b = b.layer(Subsampling(kernel=(2, 2), stride=(2, 2),
                                pooling="max"))
    conf = (b.layer(Dense(n_out=4096, activation="relu"))
            .layer(Dense(n_out=4096, activation="relu"))
            .layer(Output(n_out=n_classes, loss="mcxent",
                          activation="softmax"))
            .set_input_type(InputType.convolutional(image_size, image_size,
                                                    3))
            .build())
    return MultiLayerNetwork(conf).init()


# VGG16 per-channel ImageNet means, RGB order (TrainedModels.java
# VGG16.getPreProcessor parity: subtract these from RGB inputs)
VGG16_MEAN_RGB = (123.68, 116.779, 103.939)


def vgg16_preprocess(images):
    """[b, h, w, 3] RGB uint8/float -> mean-subtracted float32 (the
    reference's VGG16 pre-processor semantics, NHWC)."""
    import numpy as np
    x = np.asarray(images, np.float32)
    return x - np.asarray(VGG16_MEAN_RGB, np.float32)
