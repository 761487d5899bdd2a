"""The LFM2-8B-A1B configuration's pieces on the CPU: hand-worked FLOP and
byte counts (the model, the gated short convolution, causal GQA at heads of
64), the roofline reader on the two new counts, the configuration's file held
to the catalog row, and the rehearsal cell `rehearsal-lfm2` through the whole
harness, planted faults and the fp8 control coming out not correct.
"""

import io
import json
import os
from contextlib import redirect_stderr, redirect_stdout

import jax.numpy as jnp
import pytest

from benchmark import correct, run, traffic

BENCH = run.HERE
CONFIG = "lfm2-8b-a1b-ep4"
CELL = "lfm2-ep4-train-s8192"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the catalog row's `config` (model-configs guide, architectures.jsonl)
TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "conv", "full_attention", "conv",
         "conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "full_attention", "conv", "conv"]
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


# ---- counts worked by hand --------------------------------------------------

TINY = {"vocab_size": 7, "hidden_size": 4, "intermediate_size": 9,
        "moe_intermediate_size": 5, "num_attention_heads": 2,
        "num_key_value_heads": 1, "conv_L_cache": 3,
        "layer_types": ["conv", "conv", "full_attention", "conv"],
        "layers_held": [0, 2, 3], "num_dense_layers": 2, "num_experts": 8,
        "num_experts_per_tok": 2, "experts_held": 4}


def test_flops_by_hand():
    f = correct.load_module(f"flops/{CONFIG}.py")
    T = 4
    m = f.forward_macs_per_token(TINY, T)
    # blocks 0 (conv, dense) and 3 (conv, MoE): W_in 4 x 12, 3 taps of 4,
    # W_out 4 x 4
    assert m["conv"] == 2 * (48 + 12 + 16)
    # block 2: q and o 4 x (2 heads of 2), k and v 4 x (1 head of 2)
    assert m["attention_projections"] == 16 + 16 + 8 + 8
    # a token sees (4 + 1) / 2 keys on average, 2 heads x (2 + 2) a pair
    assert m["attention_scores"] == 2 * 4 * 2.5
    assert m["dense_mlp"] == 3 * 4 * 9
    # two MoE blocks: router 4 x 8; 2 choices x 4/8 held x 3 x 4 x 5
    assert m["moe_router"] == 2 * 32 and m["moe_routed"] == 2 * 60
    assert m["head"] == 28
    assert f.train_flops_per_step(TINY, {"ids": (3, T)}) \
        == 6 * 3 * T * sum(m.values())


def test_flops_at_the_cells_size():
    f = correct.load_module(f"flops/{CONFIG}.py")
    a = load("configs", CONFIG)["model"]["args"]
    m = {k: v / 1e6 for k, v in f.forward_macs_per_token(a, 8192).items()}
    assert round(sum(m.values()), 1) == 216.3            # M multiply-adds
    assert round(m["conv"], 1) == 67.1 and round(m["head"], 1) == 33.6
    assert round(m["moe_routed"], 1) == 44.0 and round(m["dense_mlp"], 1) == 44.0
    assert round(m["attention_scores"], 1) == 16.8
    assert round(m["attention_projections"], 1) == 10.5
    step = f.train_flops_per_step(a, {"ids": (2, 8192)})
    assert 21.2e12 < step < 21.4e12


def test_short_conv_counts_by_hand():
    k = correct.load_module("kernels/short_conv.py")
    # one row of 4 positions, 2 channels, 3 taps, bf16
    assert k.forward(1, 4, 2, 3, 2) == (2 * 4 * 2 * 4, 4 * 4 * 2 * 2)
    # reads 3 D + D, writes 3 D a position; the taps' float32 gradient
    assert k.backward(1, 4, 2, 3, 2) == (4 * 4 * 2 * 4, 4 * 7 * 2 * 2 + 2 * 3 * 4)
    fwd, bound = k.least_seconds(*k.forward(2, 8192, 2048, 3, 2), PEAK)
    bwd, bound_b = k.least_seconds(*k.backward(2, 8192, 2048, 3, 2), PEAK)
    assert bound == bound_b == "memory"
    # 268 MB and 470 MB at 819 GB/s
    assert round(1e3 * fwd, 3) == 0.328 and round(1e3 * bwd, 3) == 0.574


def test_gqa_attention_counts_by_hand():
    k = correct.load_module("kernels/gqa_attn.py")
    assert [k.kept_pairs(L) for L in (1, 4, 8192)] == [1, 10, 8192 * 8193 // 2]
    # one row of 4 positions, 2 query heads of 4 = 8 / 2 on 1 key/value head
    flops, bytes_ = k.forward(1, 4, 2, 1, 8, 2)
    assert flops == 2 * 2 * 10 * (4 + 4)
    # q and o 4 x 2 x 4 each, k and v 4 x 1 x 4 each; two bytes
    assert bytes_ == 2 * (2 * 32 + 2 * 16)
    assert k.backward(1, 4, 2, 1, 8, 2) == (2 * flops, 2 * bytes_)
    fwd, bound = k.least_seconds(*k.forward(2, 8192, 32, 8, 2048, 2), PEAK)
    bwd, _ = k.least_seconds(*k.backward(2, 8192, 32, 8, 2048, 2), PEAK)
    # 64 + 64 multiply-adds a pair and head, as published (not the 128 lanes
    # a head goes in as)
    assert bound == "compute"
    assert round(1e3 * fwd, 2) == 2.79 and round(1e3 * bwd, 2) == 5.58


@pytest.mark.parametrize("metric,counts,layers,names", [
    ("short_conv_kernel_roofline.tokens", "short_conv", 4,
     ("jvp_short_conv_fwd_.3", "transpose_short_conv_bwd_.7")),
    ("gqa_attn_kernel_roofline.tokens", "gqa_attn", 1,
     ("jvp_flash_attn_fwd_.3", "transpose_jvp_flash_attn_bwd__.7")),
])
def test_roofline_reader_prices_the_steps_need_from_the_new_counts(
        metric, counts, layers, names):
    reader = correct.load_module("readers/kernel_need_roofline.py")
    spec = load("metrics", metric)
    config = load("configs", CONFIG)
    a = config["model"]["args"]
    k = correct.load_module(f"kernels/{counts}.py")
    shape = tuple(2 if s == "itemsize" else {"B": 2, "L": 8192}.get(s, a.get(s))
                  for s in spec["args"]["shape"])
    assert spec["args"]["layers"] != "num_hidden_layers"
    assert a[spec["args"]["layers"]] == layers
    need = 2 * layers * sum(k.least_seconds(*fn(*shape), PEAK)[0]
                            for fn in (k.forward, k.backward))   # 2 steps
    ctx = {"peak": PEAK, "config": config, "shape": {"ids": [2, 8192]},
           "cell": {"trace_steps": 2}}

    def read(events):
        return reader.read(dict(ctx, raw={"devices": {0: events}}),
                           **spec["args"])

    by_row = [(names[0], 0, 1e9 * need / 10)] * 20 + [("fusion.1", 0, 5e6)] \
        + [(names[1], 0, 1e9 * need / 10)] * 20
    assert read(by_row) == pytest.approx(25.0)
    # a program without these kernels (the parent) has no such event
    assert read([("while.3", 0, 1e6)]) is None
    # neither metric reads the other's events
    other = ("flash_attn" if counts == "short_conv" else "short_conv")
    assert not any(other in n for n in spec["args"]["calls"])


# ---- the configuration's file ------------------------------------------------

def test_config_holds_the_catalog_rows_numbers_but_the_three_reduced():
    c = load("configs", CONFIG)
    a = c["model"]["args"]
    reduced = {"num_hidden_layers": 5, "vocab_size": 16384}
    for key, want in CATALOG.items():
        assert key in c, key
        assert c[key] == reduced.get(key, want), key
    assert (c["experts_held"], c["first_expert"]) == (8, 0)
    assert c["layers_held"] == a["layers_held"] == [0, 2, 3, 4, 5]
    assert len(c["reduced"]) == 3
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "num_experts",
                "num_experts_per_tok", "routed_scaling_factor", "conv_L_cache",
                "layer_types", "num_dense_layers", "norm_eps", "rope_theta",
                "num_hidden_layers", "vocab_size", "experts_held"):
        assert a[key] == c[key], key
    kinds = [a["layer_types"][l] for l in a["layers_held"]]
    assert kinds.count("conv") == a["conv_layers"] == 4
    assert kinds.count("full_attention") == a["attention_layers"] == 1
    assert a["bias_update_rate"] == c["assumed"]["bias_update_rate"] == 0.001
    assert a["seq_len"] == 8192
    for key in ("published", "deployment", "assumed", "departures"):
        assert c[key], key
    ref = correct.load_module(c["reference"])
    table = ref.param_table(a)
    n = sum(int(jnp.prod(jnp.asarray(shape))) for shape, _ in table.values())
    assert n == 507_820_288
    assert len(ref.static_names(a)) == 4


def test_benchmark_json_lists_the_cell_and_its_metrics():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = load("workloads", CELL)
    entry = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert {k: entry[k] for k in ("config", "traffic", "chips", "why")} \
        == {k: cell[k] for k in ("config", "traffic", "chips", "why")}
    assert bench["workloads"][-1]["name"] == CELL
    conf = bench["configs"][-1]
    assert conf["name"] == CONFIG and len(conf["why"]) <= 200
    assert conf["reduced"] == ["num_hidden_layers", "vocab_size", "experts_held"]
    assert conf["source"] == load("configs", CONFIG)["source"]
    layer = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "short_conv_kernel_roofline.tokens", "gqa_attn_kernel_roofline.tokens"]
    for name in ("short_conv_kernel_roofline.tokens",
                 "gqa_attn_kernel_roofline.tokens"):
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["moves"] == "train_tokens_per_s"
    for name in ("attn_kernel_roofline.tokens", "mla_attn_kernel_roofline.tokens",
                 "gdn_kernel_roofline.tokens", "gru_kernel_roofline.tokens"):
        assert CELL not in layer[name]["workloads"], name
    for name in ("dispatch_ms", "step_ms_p95", "data_wait_share",
                 "train_step_mfu", "device_idle_share", "feed_convert_ms",
                 "feed_h2d_ms"):
        assert layer[name + ".tokens"]["workloads"][-1] == CELL, name
    # the four build-stage metrics keep the five cells their own test pins
    for name in ("step_build_s", "step_trace_s", "step_lower_s",
                 "step_backend_compile_s"):
        assert CELL not in layer[name]["workloads"], name
    rate = {m["name"]: m for m in bench["end_to_end"]}["train_tokens_per_s"]
    assert rate["workloads"][-1] == CELL


def test_the_cells_file_and_its_traffic():
    cell, config = load("workloads", CELL), load("configs", CONFIG)
    mix = traffic.load(cell["traffic"])
    assert cell["reports"] == ["train_tokens_per_s", "setup_s"]
    assert cell["config"] == CONFIG and len(cell["why"]) <= 200
    assert cell["chips"] == 1 and cell["trace_steps"] == 4
    assert cell["min_kernel_calls"] == 24
    assert cell["limits"] and all(v < 1.0 for v in cell["limits"].values())
    rows, work = traffic.pool(dict(mix, pool_batches=1),
                              config["model"]["args"], 2 ** 31 + 5)[0]
    assert work == 2 * 8192 and len(rows) == 2 and mix["pool_batches"] == 8
    for ids, nxt in rows:
        assert len(ids) == len(nxt) == 8192 and ids[1:] == nxt[:-1]
        assert 2 <= min(ids[1:]) and max(ids) < 16384


# ---- the rehearsal cell through the whole harness -----------------------------

def run_cell(argv):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        run.main(argv)
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


ARGS = ["--workload", "rehearsal-lfm2", "--seed", "3800000019", "--seconds", "1"]


def test_rehearsal_cell_sound_run():
    res, err = run_cell(ARGS[:-1] + ["3", "--trace", "1"])
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    assert res["device"]["platform"] == "cpu"
    # the XLA forms run here: no kernel event, so the rooflines are left out
    assert "short_conv_kernel_roofline.tokens" not in res["metrics"]
    assert "gqa_attn_kernel_roofline.tokens" not in res["metrics"]
    assert {"dispatch_ms.tokens", "data_wait_share.tokens"} <= set(res["metrics"])
    assert err.strip().splitlines()[-1].startswith("correct True")


def test_rehearsal_cell_state_handed_back_unchanged(monkeypatch):
    from paddle_tpu.trainer import trainer as tr

    real = tr.make_train_step

    def broken(loss, optimizer, static, lr_mults=None, evaluators=None,
               donate=True, **kw):
        step = real(loss, optimizer, static, lr_mults, evaluators, False, **kw)

        def same_state(params, opt_state, rng, feeds):
            _, _, cost, metrics = step(params, opt_state, rng, feeds)
            return params, opt_state, cost, metrics

        same_state.lower = step.lower
        return same_state

    monkeypatch.setattr(tr, "make_train_step", broken)
    res, _ = run_cell(ARGS + ["--trace", "0"])
    assert res["correct"] is False
    assert res["compared"]["delta_gap"]["value"] == pytest.approx(1.0)


def test_rehearsal_cell_half_of_the_batch_left_out(monkeypatch):
    from paddle_tpu.trainer.feeder import DataFeeder

    real = DataFeeder.__call__
    monkeypatch.setattr(DataFeeder, "__call__",
                        lambda self, batch: real(self, batch[:len(batch) // 2]))
    res, _ = run_cell(ARGS + ["--trace", "0"])
    assert res["correct"] is False


def test_fp8_control_is_further_from_the_reference_than_the_program():
    """The reference with every product's operands rounded to fp8 against
    the reference itself on the rehearsal cell's first steps; the expert
    biases are compared as the moving statistics of batch norm are."""
    cell, config, mix, _, _ = run.load_cell("rehearsal-lfm2")
    batches = [rows for rows, _ in traffic.pool(
        dict(mix, pool_batches=correct.STEPS), config["model"]["args"], 7)]
    static = set(correct.load_module(config["reference"]).static_names(
        config["model"]["args"]))
    ref = correct.reference_steps(config, batches, 7)
    fp8 = correct.reference_steps(config, batches, 7, rounding="fp8")
    numbers = correct.compare(fp8, ref, static)
    assert numbers["grad_gap_median"][0] > 5e-3
    assert "stat_gap_median" in numbers
    same = correct.compare(ref, ref, static)
    assert same["stat_gap_median"][0] == 0.0 and same["delta_gap"][0] == 0.0
    assert all(ref["delta"][k] > 0 for k in static)
