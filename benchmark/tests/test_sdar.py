"""The SDAR configuration's pieces on the CPU: hand-worked FLOP and byte
counts, the roofline reader, the configuration's file, and the rehearsal
cell `rehearsal-sdar` through the whole harness, planted faults and the fp8
control coming out not correct.
"""

import io
import json
import os
from contextlib import redirect_stderr, redirect_stdout

import jax.numpy as jnp
import pytest

from benchmark import correct, run, traffic

BENCH = run.HERE
CONFIG = "sdar-30b-a3b-ep8"
CELL = "sdar-ep8-train-s8192"


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


# ---- counts worked by hand --------------------------------------------------

TINY = {"vocab_size": 7, "hidden_size": 2, "num_hidden_layers": 3,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 3,
        "moe_intermediate_size": 5, "num_experts": 8,
        "num_experts_per_tok": 2, "experts_held": 4, "block_length": 2}


def test_kept_pairs_by_hand():
    k = correct.load_module("kernels/flash_attn.py")
    f = correct.load_module(f"flops/{CONFIG}.py")
    # L 4, blocks of 2: a noised token sees its block (2) and the clean
    # blocks before it (0 or 2); a clean token its block and those before
    # (2 or 4): 2 x (2 + 4) + 2 x (2 + 4 + ...) = 4 x 2 + (0 + 0 + 2 + 2)
    # + (2 + 2 + 4 + 4) = 24 = 4^2 + 2 x 2^2
    assert k.kept_pairs(4, 2) == f.kept_pairs(4, 2) == 24
    # a short last block: L 5 in blocks of 2 is blocks of 2, 2, 1
    assert k.kept_pairs(5, 2) == f.kept_pairs(5, 2) == 25 + 4 + 4 + 1
    # the cell: a quarter of the square and a sliver
    assert k.kept_pairs(8192, 4) == 8192 ** 2 + 8192 * 4


def test_flops_by_hand():
    f = correct.load_module(f"flops/{CONFIG}.py")
    L = 4
    m = f.forward_macs_per_row(TINY, L)
    # q and o 2 x 12 each, k and v 2 x 6 each, at 8 positions, 3 layers
    assert m["attention_projections"] == 3 * 8 * (24 + 24 + 12 + 12)
    # 24 kept pairs x 4 heads x (q.k and p v: 2 x 3)
    assert m["attention_scores"] == 3 * 4 * 24 * 6
    # router 2 x 8, routed 2 choices x 4/8 held x 3 products x 2 x 5: at 8
    # positions in two blocks, at 4 in the last
    assert m["moe"] == (2 * 8 + 4) * (16 + 30)
    assert m["head"] == 4 * 14
    assert f.train_flops_per_step(TINY, {"ids": (3, L)}) \
        == 6 * 3 * sum(m.values())


def test_flops_at_the_cells_size():
    f = correct.load_module(f"flops/{CONFIG}.py")
    a = load("configs", CONFIG)["model"]["args"]
    m = f.forward_macs_per_row(a, 8192)
    tflop = {k: 2 * 2 * v / 1e12 for k, v in m.items()}     # two rows
    assert round(tflop["attention_scores"], 1) == 8.8
    assert round(tflop["attention_projections"], 1) == 4.9
    assert round(tflop["moe"], 1) == 1.1
    assert round(tflop["head"], 1) == 1.3
    step = f.train_flops_per_step(a, {"ids": (2, 8192)})
    assert 48.4e12 < step < 48.6e12


def test_attention_counts_by_hand():
    k = correct.load_module("kernels/flash_attn.py")
    # one row, L 4 in blocks of 2, 4 : 2 heads of 3, bf16: 24 kept pairs
    flops, bytes_ = k.forward(1, 4, 2, 4, 2, 3, 2)
    assert flops == 2 * 4 * 24 * 2 * 3
    # q and o: 8 positions x 4 x 3; k and v: 8 x 2 x 3; two bytes each
    assert bytes_ == 2 * (2 * 96 + 2 * 48)
    flops_b, bytes_b = k.backward(1, 4, 2, 4, 2, 3, 2)
    assert flops_b == 2 * flops
    # reads q k v o do, writes dq dk dv
    assert bytes_b == 2 * (4 * 96 + 4 * 48)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    sec, bound = k.least_seconds(*k.forward(2, 8192, 4, 32, 4, 128, 2), peak)
    # 2.2 TFLOP over 0.6 GB: compute-bound on a v5e
    assert bound == "compute"
    assert abs(sec - 2 * 2 * 32 * (8192 ** 2 + 32768) * 256 / 197e12) < 1e-12


def test_roofline_reader_prices_the_steps_need_not_the_calls():
    reader = correct.load_module("readers/kernel_need_roofline.py")
    spec = load("metrics", "attn_kernel_roofline.tokens")
    config = load("configs", CONFIG)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    k = correct.load_module("kernels/flash_attn.py")
    shape = (2, 8192, 4, 32, 4, 128, 2)
    fwd, _ = k.least_seconds(*k.forward(*shape), peak)
    bwd, _ = k.least_seconds(*k.backward(*shape), peak)
    need = 2 * 4 * (fwd + bwd)                      # two traced steps, 4 layers
    ctx = {"peak": peak, "config": config, "shape": {"ids": [2, 8192]},
           "cell": {"trace_steps": 2}}

    def read(events):
        return reader.read(dict(ctx, raw={"devices": {0: events}}),
                           **spec["args"])

    # a row a launch, the forward launch made again: the time counts, the
    # need does not grow
    by_row = [("jvp_flash_attn_fwd_.3", 0, 1e9 * need / 4)] * 8 \
        + [("fusion.1", 0, 5e6)] \
        + [("transpose_jvp_flash_attn_bwd__.7", 0, 1e9 * need / 4)] * 4
    assert read(by_row) == pytest.approx(100 / 3)
    whole = [("flash_attn_fwd.1", 0, 2e9 * need),
             ("flash_attn_bwd_dq.1", 0, 1e9 * need)]
    assert read(whole) == pytest.approx(100 / 3)
    # a program without these kernels has no such event: nothing, not 0
    assert read([("while.3", 0, 1e6)]) is None
    assert reader.read(dict(ctx, peak=None, raw={"devices": {0: whole}}),
                       **spec["args"]) is None


# ---- the configuration's file ------------------------------------------------

def test_config_keeps_every_published_width():
    c = load("configs", CONFIG)
    a = c["model"]["args"]
    for key, want in {"hidden_size": 2048, "num_attention_heads": 32,
                      "num_key_value_heads": 4, "head_dim": 128,
                      "moe_intermediate_size": 768, "num_experts": 128,
                      "num_experts_per_tok": 8, "rope_theta": 1000000}.items():
        assert c[key] == want and a[key] == want, key
    assert (a["num_hidden_layers"], a["experts_held"], a["vocab_size"]) \
        == (4, 16, 18992) and len(c["reduced"]) == 3
    assert c["intermediate_size"] == 6144 and c["max_window_layers"] == 48
    for key in ("published", "deployment", "assumed", "departures"):
        assert c[key], key
    assert c["assumed"]["block_length"] == a["block_length"] == 4
    assert c["assumed"]["mask_token_id"] == a["mask_token_id"] == 1
    assert c["optimizer"]["learning_rate"] == 1e-6
    assert not any("shared" in k for k in a)
    ref = correct.load_module(c["reference"])
    n = sum(int(jnp.prod(jnp.asarray(shape)))
            for shape, _ in ref.param_table(a).values())
    assert n == 456_346_624


def test_benchmark_json_lists_the_cell_and_its_metrics():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = load("workloads", CELL)
    entry = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert {k: entry[k] for k in ("config", "traffic", "chips", "why")} \
        == {k: cell[k] for k in ("config", "traffic", "chips", "why")}
    conf = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert conf["reduced"] == ["num_hidden_layers", "vocab_size", "experts_held"]
    layer = {m["name"]: m for m in bench["per_layer"]}
    assert layer["attn_kernel_roofline.tokens"]["workloads"] == [CELL]
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
    assert cell["min_kernel_calls"] == 8 and cell["chips"] == 1
    # sharp scores: the precision hardly moves the gradient's or the loss's
    # gap (limits_why), so the limits are on the parameters' change alone
    assert set(cell["limits"]) == {"delta_gap", "delta_gap_median"}
    assert all(v < 1.0 for v in cell["limits"].values())
    rows, work = traffic.pool(dict(mix, pool_batches=1),
                              config["model"]["args"], 2 ** 31 + 5)[0]
    assert work == 2 * 8192 and len(rows) == 2
    for ids, u, t in rows:
        assert len(ids) == 8192 and u.shape == (8192,) and t.shape == (2048,)
        assert 2 <= min(ids) and max(ids) < 18992
        # on the grid the configuration's schedule assumes
        assert ((u + 0.5) * 256 % 1 == 0).all() and -0.5 <= u.min() and u.max() < 0.5
        assert ((t + 0.5) * 256 % 1 == 0).all()


# ---- the rehearsal cell through the whole harness -----------------------------

def run_cell(argv):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        run.main(argv)
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


ARGS = ["--workload", "rehearsal-sdar", "--seed", "3200000019", "--seconds", "1"]


def test_rehearsal_cell_sound_run():
    res, err = run_cell(ARGS[:-1] + ["3", "--trace", "1"])
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    assert res["device"]["platform"] == "cpu"
    # the tiles run in XLA here: no kernel event, so the roofline is left out
    assert "attn_kernel_roofline.tokens" not in res["metrics"]
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
    the reference itself, beside the program's own gap, on the rehearsal
    cell's first steps: the control reads the larger gradient gap."""
    cell, config, mix, _, _ = run.load_cell("rehearsal-sdar")
    batches = [rows for rows, _ in traffic.pool(
        dict(mix, pool_batches=correct.STEPS), config["model"]["args"], 7)]
    ref = correct.reference_steps(config, batches, 7)
    fp8 = correct.reference_steps(config, batches, 7, rounding="fp8")
    numbers = correct.compare(fp8, ref)
    assert numbers["grad_gap_median"][0] > 5e-3
    assert numbers["loss_gap"][0] > 1e-4
