"""The kernel-roofline reader on hand-made events, and the two feed-split
metrics on a hand-made histogram delta."""

import json
import os

import pytest

from benchmark import correct

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def metric(name):
    with open(os.path.join(os.path.dirname(HERE), "metrics", name + ".json")) as f:
        spec = json.load(f)
    return correct.load_module(f"readers/{spec['reader']}.py"), spec["args"]


def ctx(events, peak=PEAK):
    return {"raw": {"devices": {0: events, 1: [("fused_gru_fwd.9 custom-call", 0, 1)]},
                    "phases": []},
            "peak": peak, "shape": {"src": (512, 32), "trg": (512, 32)},
            "config": {"encoder_size": 512}}


def test_roofline_share_by_hand():
    reader, args = metric("gru_kernel_roofline.tokens")
    # B512 T32 H512 in bf16: forward 2*512*32*3*512^2 = 25.77 GFLOP, over
    # 197 TFLOP/s = 130.8 us (compute-bound: its 68.7 MB take 83.9 us of
    # HBM); backward twice the FLOPs = 261.6 us
    fwd = 2 * 512 * 32 * 3 * 512 * 512 / 197e12
    assert fwd == pytest.approx(130.8e-6, rel=1e-3)
    events = [
        ("jvp_fused_gru_fwd_.2 custom-call", 0, 200_000),
        ("shard_map_jvp_fused_gru_fwd_.3 custom-call", 300_000, 250_000),
        ("transpose_jvp_fused_gru_bwd__.2 custom-call", 600_000, 300_000),
        ("shard_map_transpose_jvp_fused_gru_bwd__.3 custom-call", 950_000, 350_000),
        ("fusion.311 fusion", 1_400_000, 4_300_000),       # ignored
        ("custom-call.7 custom-call", 6_000_000, 50_000),  # XLA's own: ignored
        ("jvp_fused_lstm_fwd_.1 custom-call", 7_000_000, 90_000),  # another kernel
    ]
    got = reader.read(ctx(events), **args)
    want = 100 * (2 * fwd + 2 * 2 * fwd) / 1100e-6
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(71.35, abs=0.01)


def test_nothing_to_read_is_none_not_zero():
    reader, args = metric("gru_kernel_roofline.tokens")
    # the parent's names (PR 24's trace): no kernel name in them
    old = [("jvp__.2 custom-call", 0, 210_000),
           ("transpose_jvp___.3 custom-call", 300_000, 311_000)]
    assert reader.read(ctx(old), **args) is None
    assert reader.read(ctx([]), **args) is None
    named = [("jvp_fused_gru_fwd_.2 custom-call", 0, 200_000)]
    assert reader.read(ctx(named, peak=None), **args) is None   # a rehearsal
    assert reader.read({**ctx(named), "raw": {"devices": {}, "phases": []}},
                       **args) is None


@pytest.mark.parametrize("suffix", ["images", "tokens"])
def test_feed_split_reads_the_programs_phases(suffix):
    phases = {"feed": (17.8, 100), "feed_convert": (12.5, 100),
              "feed_h2d": (5.0, 100), "dispatch": (0.4, 99), "compile": (0, 0)}
    for name, want in (("feed_convert_ms", 125.0), ("feed_h2d_ms", 50.0)):
        reader, args = metric(f"{name}.{suffix}")
        assert reader.read({"phases": phases}, **args) == pytest.approx(want)
        # a program without the phase (the parent): left out, not 0
        assert reader.read({"phases": {"feed": (17.8, 100)}}, **args) is None


def test_benchmark_json_lists_the_new_metrics():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for suffix, cell in (("images", "resnet50-train-b256"),
                         ("tokens", "nmt-train-b512")):
        for name in ("feed_convert_ms", "feed_h2d_ms"):
            m = layer[f"{name}.{suffix}"]
            assert m["workloads"] == [cell] and m["layer"] == "feeder / prefetch"
    roof = layer["gru_kernel_roofline.tokens"]
    assert roof["workloads"] == ["nmt-train-b512"] and roof["unit"] == "%"
    assert roof["layer"] == "kernels" and roof["source"] == "device_trace"
