"""Operations and bytes the algorithm needs, computed from shapes.

These are the yardstick for `step_mfu_required` and the `*_roofline`
metrics: what a forward and backward pass *requires*, never what a
compiler emitted. XLA's cost model is not used: it counts 0 for a
`tpu_custom_call` (so the Pallas LSTM's recurrent matmuls vanish) and it
counts recomputation.

Conventions: one multiply-add is 2 FLOPs; training costs 3x the forward
matmul/convolution FLOPs (forward, gradient w.r.t. input, gradient w.r.t.
weight), less the first layer's input gradient, which nothing needs;
elementwise work (batch norm, activations, pooling, the optimizer) is not
counted, which is the usual MFU convention.

Every public function takes ``(config, traffic)``: the two data files of
a cell, as dicts.
"""

from __future__ import annotations

RESNET50_STAGES = (3, 4, 6, 3)


def _same_out(size: int, stride: int) -> int:
    return -(-size // stride)


def _train_flops(forward_macs: int, first_layer_macs: int) -> int:
    return 2 * (3 * forward_macs - first_layer_macs)


def resnet50_stem_macs(image_size: int = 224) -> int:
    out = _same_out(image_size, 2)
    return out * out * 7 * 7 * 3 * 64


def resnet50_forward_macs(image_size: int = 224, n_classes: int = 1000) -> int:
    """Multiply-adds of one image's forward pass through ResNet-50 v1
    (He et al. 2015, Table 1, 50-layer column): 7x7/2 stem, 3x3/2 max
    pool, bottleneck stages [3, 4, 6, 3] with the stride on the first
    1x1 and a projection shortcut where the shape changes, global
    average pool, dense to ``n_classes``. All convolutions pad "same"."""
    macs = 0

    def conv(size, k, c_in, c_out, stride):
        out = _same_out(size, stride)
        nonlocal macs
        macs += out * out * k * k * c_in * c_out
        return out

    size = conv(image_size, 7, 3, 64, 2)
    size = _same_out(size, 2)                      # max pool 3x3/2
    c_in, filters = 64, 64
    for stage, n_blocks in enumerate(RESNET50_STAGES):
        for block in range(n_blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            if block == 0:                         # projection shortcut
                conv(size, 1, c_in, filters * 4, stride)
            out = conv(size, 1, c_in, filters, stride)
            conv(out, 3, filters, filters, 1)
            conv(out, 1, filters, filters * 4, 1)
            size, c_in = out, filters * 4
        filters *= 2
    return macs + c_in * n_classes


def resnet50_train_step(config: dict, traffic: dict) -> dict:
    kw = config["kwargs"]
    macs = resnet50_forward_macs(kw["image_size"], kw["n_classes"])
    stem = resnet50_stem_macs(kw["image_size"])
    return {"flops": _train_flops(macs, stem) * traffic["batch"]}


def lstm_stack_forward_macs_per_char(vocab: int, hidden: int,
                                     n_layers: int) -> int:
    """One character through ``n_layers`` LSTMs of ``hidden`` cells and a
    dense softmax head: per layer the input projection [n_in, 4n] and the
    recurrent matmul [n, 4n]. A one-hot input row is still multiplied
    densely, so layer 0 counts ``vocab * 4n``. Peepholes are elementwise
    and not counted."""
    macs, n_in = 0, vocab
    for _ in range(n_layers):
        macs += n_in * 4 * hidden + hidden * 4 * hidden
        n_in = hidden
    return macs + hidden * vocab


def _lstm_sizes(config: dict, traffic: dict):
    kw = config["kwargs"]
    chars = traffic["batch"] * traffic["seq_len"]
    return kw["vocab_size"], kw["hidden"], kw["n_layers"], chars


def char_rnn_train_step(config: dict, traffic: dict) -> dict:
    vocab, hidden, layers, chars = _lstm_sizes(config, traffic)
    macs = lstm_stack_forward_macs_per_char(vocab, hidden, layers)
    return {"flops": _train_flops(macs, vocab * 4 * hidden) * chars}


# The Pallas LSTM kernels (ops/lstm.py) run the time loop only: the input
# projection is a matmul outside them. Per (timestep, row) in the compute
# dtype of ``itemsize`` bytes, with n = hidden:
#   forward  reads xz [4n], writes y [n], gates [4n], h_prev [n], c_prev [n]
#            and multiplies h [n] by Wh [n, 4n];
#   backward reads gates [4n], h_prev [n], c_prev [n], dy [n], writes dxz
#            [4n], and multiplies dz [4n] by Wh^T and h_prev^T by dz.
# Wh is read once per call; the [b, 1] mask column and the [b, n] carries
# are left out (under 1% of the streamed bytes at n = 512).
def _lstm_kernel(config, traffic, matmuls: int) -> dict:
    _, hidden, layers, chars = _lstm_sizes(config, traffic)
    itemsize = config["compute_itemsize"]
    per_row_bytes = 11 * hidden * itemsize
    weight_bytes = hidden * 4 * hidden * itemsize
    return {
        "flops": layers * matmuls * 2 * hidden * 4 * hidden * chars,
        "bytes": layers * (per_row_bytes * chars + weight_bytes),
    }


def lstm_fwd_kernels_step(config: dict, traffic: dict) -> dict:
    """All forward LSTM kernel calls of one training step."""
    return _lstm_kernel(config, traffic, matmuls=1)


def lstm_bwd_kernels_step(config: dict, traffic: dict) -> dict:
    """All backward LSTM kernel calls of one training step."""
    return _lstm_kernel(config, traffic, matmuls=2)
