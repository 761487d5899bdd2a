"""FLOP and byte counts against values worked by hand."""

import json
import os

from benchmark import correct

HERE = os.path.dirname(os.path.abspath(__file__))


def test_one_resnet_bottleneck():
    f = correct.load_module("flops/resnet50-imagenet.py")
    # res2_0 at 56x56, 64 -> 64 -> 64 -> 256, with a 64 -> 256 projection:
    # 3136 positions x (64*64 + 64*64*9 + 64*256 + 64*256) weights
    assert f.bottleneck_macs(56, 64, 64, True) == 3136 * (4096 + 36864 + 16384 + 16384)
    # res2_1: input 256 wide, no projection
    assert f.bottleneck_macs(56, 256, 64, False) == 3136 * (16384 + 36864 + 16384)
    # the stem: 112x112 positions x 64 x 3 x 7 x 7
    assert f.conv_macs(112, 64, 3, 7) == 12544 * 9408


def test_resnet50_whole():
    f = correct.load_module("flops/resnet50-imagenet.py")
    a = {"depth": 50, "img_size": 224, "num_classes": 1000}
    macs = f.forward_macs_per_image(a)
    assert 3.8e9 < macs < 3.9e9          # the familiar 3.86 G multiply-adds
    assert f.train_flops_per_step(a, {"image": (256, 150528)}) == 6 * 256 * macs


def test_one_gru_step():
    k = correct.load_module("kernels/gru.py")
    # one position, width 512: h U_zr is 512 x 1024, (r*h) U_c is 512 x 512
    flops, bytes_ = k.forward(1, 1, 512, 2)
    assert flops == 2 * (512 * 1024 + 512 * 512)
    # in: 3*512 pre-projected + weights 3*512*512, out: 512, all bf16; mask f32
    assert bytes_ == 2 * (1536 + 512 + 786432) + 4
    flops_b, _ = k.backward(1, 1, 512, 2)
    assert flops_b == 2 * flops
    sec, bound = k.least_seconds(*k.forward(512, 32, 512, 2),
                                 {"bf16_flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and abs(sec - 2 * 512 * 32 * 786432 / 197e12) < 1e-12


def test_nmt_step_by_hand():
    f = correct.load_module("flops/nmt-gru-attention-30k.py")
    a = {"word_vector_dim": 2, "encoder_size": 3, "decoder_size": 5,
         "trg_dict_dim": 7}
    B, Ts, Tt = 1, 4, 6
    enc = 2 * Ts * (2 * 9 + 27)
    per_tick = Ts * 5 + Ts * 6 + 6 * 15 + 75
    want = enc + Ts * 6 * 5 + 3 * 5 + Tt * 2 * 15 + Tt * per_tick + Tt * 5 * 7
    assert f.forward_macs(a, B, Ts, Tt) == want
    assert f.train_flops_per_step(a, {"src": (1, 4), "trg": (1, 6)}) == 6 * want


def test_peaks_have_a_source():
    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as fh:
        peaks = json.load(fh)
    assert "819 GB/s" in peaks["source"]
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
