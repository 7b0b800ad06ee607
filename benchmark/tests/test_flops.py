"""Required FLOPs and bytes against counts made by hand."""

import pytest

from benchmark import flops

RESNET = {"kwargs": {"image_size": 224, "n_classes": 1000}}
CHAR = {"kwargs": {"vocab_size": 80, "hidden": 512, "n_layers": 2},
        "compute_itemsize": 2}


def test_resnet50_forward_is_the_papers_3_8_gmac():
    macs = flops.resnet50_forward_macs()
    # He et al. 2015, Table 1: 3.8e9 multiply-adds for the 50-layer net
    assert 3.8e9 < macs < 3.9e9
    assert flops.resnet50_stem_macs() == 112 * 112 * 49 * 3 * 64
    # by hand: the last stage's second block, 7x7 map
    block = 49 * (2048 * 512 + 9 * 512 * 512 + 512 * 2048)
    assert block == 218_365_952


def test_resnet50_step_is_close_to_the_compiled_cost_model():
    step = flops.resnet50_train_step(RESNET, {"batch": 256})["flops"]
    # PR 21 read 5.80e12 from the compiled step at b=256 (it also leaves
    # out the stem's input gradient)
    assert step == pytest.approx(5.80e12, rel=0.02)
    assert step == 2 * 256 * (3 * flops.resnet50_forward_macs()
                              - flops.resnet50_stem_macs())


def test_lstm_stack_is_6_70_mflop_a_character():
    macs = flops.lstm_stack_forward_macs_per_char(80, 512, 2)
    assert macs == (80 * 2048 + 512 * 2048) + 2 * 512 * 2048 + 512 * 80
    assert 2 * macs == 6_701_056
    step = flops.char_rnn_train_step(CHAR, {"batch": 256, "seq_len": 64})
    assert step["flops"] == 2 * (3 * macs - 80 * 2048) * 256 * 64


def test_lstm_kernels_count_only_the_time_loop():
    traffic = {"batch": 256, "seq_len": 64}
    fwd = flops.lstm_fwd_kernels_step(CHAR, traffic)
    bwd = flops.lstm_bwd_kernels_step(CHAR, traffic)
    chars = 256 * 64
    assert fwd["flops"] == 2 * (2 * 512 * 2048) * chars    # h @ Wh, 2 layers
    assert bwd["flops"] == 2 * fwd["flops"]                # dz Wh^T, h^T dz
    per_row = (4 + 1 + 4 + 1 + 1) * 512 * 2                # xz,y,G,h,c bf16
    assert fwd["bytes"] == 2 * (per_row * chars + 512 * 2048 * 2)
    assert bwd["bytes"] == fwd["bytes"]
