"""The Kimi-VL configuration's pieces on the CPU: hand-worked FLOP and byte
counts, the roofline reader on the new count, the configuration's file held
to the catalog row, and the rehearsal cell `rehearsal-kimivl` through the
whole harness, planted faults and the fp8 control coming out not correct.
"""

import io
import json
import os
from contextlib import redirect_stderr, redirect_stdout

import jax.numpy as jnp
import pytest

from benchmark import correct, run, traffic

BENCH = run.HERE
CONFIG = "kimi-vl-a3b-ep8"
CELL = "kimivl-ep8-train-s8192"
# the catalog row's `config` (model-configs guide, architectures.jsonl)
CATALOG = {
    "vocab_size": 163840, "max_position_embeddings": 131072,
    "hidden_size": 2048, "intermediate_size": 11264,
    "moe_intermediate_size": 1408, "num_hidden_layers": 27,
    "num_attention_heads": 16, "n_shared_experts": 2, "n_routed_experts": 64,
    "ep_size": 1, "routed_scaling_factor": 2.446, "kv_lora_rank": 512,
    "q_lora_rank": None, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "num_experts_per_tok": 6, "moe_layer_freq": 1,
    "first_k_dense_replace": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "seq_aux": True, "num_key_value_heads": 16,
    "hidden_act": "silu", "rms_norm_eps": 1e-05, "rope_theta": 800000,
    "rope_scaling": None, "attention_bias": False,
    "tie_word_embeddings": False}


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


# ---- counts worked by hand --------------------------------------------------

TINY = {"vocab_size": 7, "hidden_size": 2, "intermediate_size": 9,
        "moe_intermediate_size": 5, "num_hidden_layers": 3,
        "num_attention_heads": 4, "n_shared_experts": 2,
        "n_routed_experts": 8, "kv_lora_rank": 3, "qk_nope_head_dim": 3,
        "qk_rope_head_dim": 2, "v_head_dim": 4, "num_experts_per_tok": 2,
        "first_k_dense_replace": 1, "experts_held": 4}


def test_flops_by_hand():
    f = correct.load_module(f"flops/{CONFIG}.py")
    T = 4
    m = f.forward_macs_per_token(TINY, T)
    # q 2 x 4 x 5, the latent and the shared rotary key 2 x (3 + 2), the
    # decompression 3 x 4 x (3 + 4), o 4 x 4 x 2; three layers
    assert m["attention_projections"] == 3 * (40 + 10 + 84 + 32)
    # a token sees (4 + 1) / 2 keys on average, 4 heads x (5 + 4) a pair
    assert m["attention_scores"] == 3 * 4 * 9 * 2.5
    assert m["dense_mlp"] == 3 * 2 * 9
    # two MoE layers: router 2 x 8 and one shared MLP of 2 x 5, no gate
    assert m["moe_router_shared"] == 2 * (16 + 3 * 2 * 10)
    # 2 choices x 4/8 held x three products of 2 x 5
    assert m["moe_routed"] == 2 * 1 * 30
    assert m["head"] == 14
    assert f.train_flops_per_step(TINY, {"ids": (3, T)}) \
        == 6 * 3 * T * sum(m.values())


def test_flops_at_the_cells_size():
    f = correct.load_module(f"flops/{CONFIG}.py")
    a = load("configs", CONFIG)["model"]["args"]
    m = {k: 2 * v / 1e6 for k, v in f.forward_macs_per_token(a, 8192).items()}
    assert round(sum(m.values())) == 761                  # MFLOP a token
    assert round(m["attention_scores"] / 5, 1) == 41.9
    assert round(m["attention_projections"] / 5, 1) == 27.5
    assert round(m["moe_routed"] / 4, 1) == 13.0
    assert round((m["moe_routed"] + m["moe_router_shared"]) / 4, 1) == 47.8
    assert round(m["dense_mlp"]) == 138 and round(m["head"]) == 84
    step = f.train_flops_per_step(a, {"ids": (2, 8192)})
    assert 37.3e12 < step < 37.5e12


def test_attention_counts_by_hand():
    k = correct.load_module("kernels/mla_attn.py")
    assert [k.kept_pairs(L) for L in (1, 4, 8192)] == [1, 10, 8192 * 8193 // 2]
    # one row of 4 positions, 2 heads of 3 + 2 : 4, bf16: 10 kept pairs
    flops, bytes_ = k.forward(1, 4, 2, 3, 2, 4, 2)
    assert flops == 2 * 2 * 10 * (5 + 4)
    # q and k 4 x 2 x 5 each, v and o 4 x 2 x 4 each; two bytes
    assert bytes_ == 2 * (2 * 40 + 2 * 32)
    flops_b, bytes_b = k.backward(1, 4, 2, 3, 2, 4, 2)
    assert flops_b == 2 * flops and bytes_b == 2 * bytes_
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    fwd, bound = k.least_seconds(*k.forward(1, 8192, 16, 128, 64, 128, 2), peak)
    bwd, _ = k.least_seconds(*k.backward(1, 8192, 16, 128, 64, 128, 2), peak)
    # 192 + 128 multiply-adds a pair and head, whatever a head is padded to
    assert bound == "compute"
    assert round(1e3 * fwd, 2) == 1.74 and round(1e3 * bwd, 2) == 3.49


def test_roofline_reader_prices_the_steps_need_from_the_new_count():
    reader = correct.load_module("readers/kernel_need_roofline.py")
    spec = load("metrics", "mla_attn_kernel_roofline.tokens")
    config = load("configs", CONFIG)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    k = correct.load_module("kernels/mla_attn.py")
    shape = (2, 8192, 16, 128, 64, 128, 2)
    need = 2 * 5 * sum(k.least_seconds(*fn(*shape), peak)[0]
                       for fn in (k.forward, k.backward))   # 2 steps, 5 layers
    ctx = {"peak": peak, "config": config, "shape": {"ids": [2, 8192]},
           "cell": {"trace_steps": 2}}

    def read(events):
        return reader.read(dict(ctx, raw={"devices": {0: events}}),
                           **spec["args"])

    by_row = [("jvp_flash_attn_fwd_.3", 0, 1e9 * need / 10)] * 20 \
        + [("fusion.1", 0, 5e6)] \
        + [("transpose_jvp_flash_attn_bwd__.7", 0, 1e9 * need / 10)] * 20
    assert read(by_row) == pytest.approx(25.0)
    # a program without these kernels (the parent) has no such event
    assert read([("while.3", 0, 1e6)]) is None


# ---- the configuration's file ------------------------------------------------

def test_config_holds_the_catalog_rows_numbers_but_the_three_reduced():
    c = load("configs", CONFIG)
    a = c["model"]["args"]
    reduced = {"num_hidden_layers": 5, "vocab_size": 20480}
    for key, want in CATALOG.items():
        assert key in c, key
        assert c[key] == reduced.get(key, want), key
    assert (c["experts_held"], c["first_expert"]) == (8, 0)
    assert len(c["reduced"]) == 3
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "n_shared_experts", "n_routed_experts",
                "routed_scaling_factor", "kv_lora_rank", "qk_rope_head_dim",
                "qk_nope_head_dim", "v_head_dim", "num_experts_per_tok",
                "first_k_dense_replace", "rms_norm_eps", "rope_theta",
                "num_hidden_layers", "vocab_size", "experts_held"):
        assert a[key] == c[key], key
    assert a["bias_update_rate"] == c["assumed"]["bias_update_rate"] == 0.001
    assert a["seq_len"] == 8192
    for key in ("published", "deployment", "assumed", "departures"):
        assert c[key], key
    ref = correct.load_module(c["reference"])
    table = ref.param_table(a)
    n = sum(int(jnp.prod(jnp.asarray(shape))) for shape, _ in table.values())
    assert n == 568_484_352 + 4 * 64
    assert len(ref.static_names(a)) == 4
    assert table["_k_emb.w0"][1] == ("normal", ref.EMBEDDING_START)


def test_benchmark_json_lists_the_cell_and_its_metrics():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = load("workloads", CELL)
    entry = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert {k: entry[k] for k in ("config", "traffic", "chips", "why")} \
        == {k: cell[k] for k in ("config", "traffic", "chips", "why")}
    assert bench["workloads"][-1]["name"] == CELL
    conf = bench["configs"][-1]
    assert conf["name"] == CONFIG
    assert conf["reduced"] == ["num_hidden_layers", "vocab_size", "experts_held"]
    assert conf["source"] == load("configs", CONFIG)["source"]
    layer = {m["name"]: m for m in bench["per_layer"]}
    assert bench["per_layer"][-1]["name"] == "mla_attn_kernel_roofline.tokens"
    assert layer["mla_attn_kernel_roofline.tokens"]["workloads"] == [CELL]
    assert CELL not in layer["attn_kernel_roofline.tokens"]["workloads"]
    for name in ("dispatch_ms", "step_ms_p95", "data_wait_share",
                 "train_step_mfu", "device_idle_share", "feed_convert_ms",
                 "feed_h2d_ms"):
        assert layer[name + ".tokens"]["workloads"][-1] == CELL, name
    rate = {m["name"]: m for m in bench["end_to_end"]}["train_tokens_per_s"]
    assert rate["workloads"][-1] == CELL


def test_the_cells_file_and_its_traffic():
    cell, config = load("workloads", CELL), load("configs", CONFIG)
    mix = traffic.load(cell["traffic"])
    assert cell["reports"] == ["train_tokens_per_s", "setup_s"]
    assert cell["config"] == CONFIG and len(cell["why"]) <= 200
    assert cell["min_kernel_calls"] == 10 and cell["chips"] == 1
    assert cell["trace_steps"] == 4
    assert cell["limits"] and all(v < 1.0 for v in cell["limits"].values())
    four = traffic.load("lm4096-b4")
    assert mix == dict(four, batch=2, columns=[dict(four["columns"][0],
                                                    lengths=[8192, 8192])])
    rows, work = traffic.pool(dict(mix, pool_batches=1),
                              config["model"]["args"], 2 ** 31 + 5)[0]
    assert work == 2 * 8192 and len(rows) == 2 and mix["pool_batches"] == 8
    for ids, nxt in rows:
        assert len(ids) == len(nxt) == 8192 and ids[1:] == nxt[:-1]
        assert 2 <= min(ids[1:]) and max(ids) < 20480


# ---- the rehearsal cell through the whole harness -----------------------------

def run_cell(argv):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        run.main(argv)
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


ARGS = ["--workload", "rehearsal-kimivl", "--seed", "3400000019", "--seconds", "1"]


def test_rehearsal_cell_sound_run():
    res, err = run_cell(ARGS[:-1] + ["3", "--trace", "1"])
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    assert res["device"]["platform"] == "cpu"
    # the tiles run in XLA here: no kernel event, so the roofline is left out
    assert "mla_attn_kernel_roofline.tokens" not in res["metrics"]
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
    the reference itself on the rehearsal cell's first steps; the selection
    biases are compared as the moving statistics of batch norm are."""
    cell, config, mix, _, _ = run.load_cell("rehearsal-kimivl")
    batches = [rows for rows, _ in traffic.pool(
        dict(mix, pool_batches=correct.STEPS), config["model"]["args"], 7)]
    static = set(correct.load_module(config["reference"]).static_names(
        config["model"]["args"]))
    ref = correct.reference_steps(config, batches, 7)
    fp8 = correct.reference_steps(config, batches, 7, rounding="fp8")
    numbers = correct.compare(fp8, ref, static)
    assert numbers["grad_gap_median"][0] > 5e-3
    assert numbers["loss_gap"][0] > 1e-4
    assert "stat_gap_median" in numbers
    same = correct.compare(ref, ref, static)
    assert same["stat_gap_median"][0] == 0.0 and same["delta_gap"][0] == 0.0
    assert all(ref["delta"][k] > 0 for k in static)
