"""The Qwen3-Next configuration's pieces on the CPU: hand-worked FLOP and
byte counts, the plain reference against the system at a tiny size, and the
rehearsal cell `rehearsal-qwen3next` through the whole harness, planted
faults coming out not correct.
"""

import io
import json
import os
from contextlib import redirect_stderr, redirect_stdout

import jax
import jax.numpy as jnp
import pytest

from benchmark import correct, program, run, traffic

BENCH = run.HERE
CONFIG = "qwen3-next-80b-a3b-ep32"


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


# ---- counts worked by hand --------------------------------------------------

TINY = {"vocab_size": 7, "hidden_size": 2, "num_hidden_layers": 4,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 3,
        "full_attention_interval": 4, "linear_num_key_heads": 1,
        "linear_num_value_heads": 2, "linear_key_head_dim": 3,
        "linear_value_head_dim": 5, "linear_conv_kernel_dim": 4,
        "moe_intermediate_size": 3, "shared_expert_intermediate_size": 5,
        "num_experts": 8, "num_experts_per_tok": 2, "experts_held": 4}


def test_flops_by_hand():
    f = correct.load_module(f"flops/{CONFIG}.py")
    T = 4
    m = f.forward_macs_per_token(TINY, T)
    # DeltaNet: x W_qkvz 2 x (3 + 3 + 10 + 10), x W_ba 2 x 4, conv 16 channels
    # x 4 taps, o W_out 10 x 2; three such layers
    assert m["gdn_projections"] == 3 * (52 + 8 + 64 + 20)
    # the rule: 2 value heads x 3 products x 3 x 5
    assert m["gdn_rule"] == 3 * 2 * 3 * 15
    # attention: q with its gate 2 x 12, k and v 2 x 3 each, o 6 x 2
    assert m["attention_projections"] == 24 + 6 + 6 + 12
    # scores and weighted sum: 2 x heads 2 x width 3 x (4 + 1) / 2 keys
    assert m["attention_scores"] == 2 * 2 * 3 * 2.5
    # router 2 x 8, shared gate 2, shared expert 3 x 2 x 5; four layers
    assert m["moe_router_shared"] == 4 * (16 + 2 + 30)
    # routed: 2 choices x 4/8 held x 3 products x 2 x 3
    assert m["moe_routed"] == 4 * (2 * 0.5 * 18)
    assert m["head"] == 14
    assert f.train_flops_per_step(TINY, {"ids": (3, T), "next_ids": (3, T)}) \
        == 6 * 3 * T * sum(m.values())


def test_flops_at_the_cells_size():
    f = correct.load_module(f"flops/{CONFIG}.py")
    a = load("configs", CONFIG)["model"]["args"]
    m = f.forward_macs_per_token(a, 4096)
    mflop = {k: 2 * v / 1e6 for k, v in m.items()}
    assert round(mflop["gdn_projections"] / 3, 1) == 67.4
    assert round(mflop["gdn_rule"] / 3, 2) == 3.15
    assert round(mflop["attention_projections"], 1) == 54.5
    assert round(mflop["attention_scores"], 1) == 33.6
    assert round(mflop["moe_routed"], 2) == 7.86
    assert round(mflop["head"], 1) == 77.8
    step = f.train_flops_per_step(a, {"ids": (4, 4096), "next_ids": (4, 4096)})
    assert 20.5e12 < step < 20.7e12


def test_state_pass_counts_by_hand():
    k = correct.load_module("kernels/gdn.py")
    # one row, one chunk of 2 tokens, key width 3, value width 5, bf16
    flops, bytes_ = k.forward(1, 1, 2, 3, 5, 2)
    # W S, Q~ S, K~^T Vn: 2 x 3 x 5 each; Aqk Vn: 2 x 2 x 5
    assert flops == 2 * (3 * 30 + 20)
    # in: W, Q~, K~ (2 x 3), U (2 x 5), Aqk (2 x 2), the decay (4 bytes);
    # out: O (2 x 5)
    assert bytes_ == 2 * (18 + 10 + 4 + 10) + 4
    flops_b, bytes_b = k.backward(1, 1, 2, 3, 5, 2)
    assert flops_b == 2 * (5 * 30 + 3 * 20)
    # in: the forward's inputs and dO; out: a gradient for each input
    assert bytes_b == 2 * (2 * (18 + 10 + 4) + 2 * 10) + 8
    sec, bound = k.least_seconds(*k.forward(32, 64, 64, 128, 128, 2),
                                 {"bf16_flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9})
    # 64 x 128 tiles: 104 KB a chunk for 7.3 MFLOP, memory-bound on a v5e
    assert bound == "memory"
    assert abs(sec - 32 * 64 * (2 * (3 * 8192 + 2 * 8192 + 4096) + 4) / 819e9) < 1e-12


def test_roofline_reader_prices_the_steps_need_not_the_calls():
    reader = correct.load_module("readers/gdn_kernel_roofline.py")
    spec = load("metrics", "gdn_kernel_roofline.tokens")
    config = load("configs", CONFIG)
    assert config["assumed"]["chunk_tokens"] == 64
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    k = correct.load_module("kernels/gdn.py")
    # 4 rows x 32 value heads, 4096 / 64 chunks; 3 of the 4 layers are DeltaNet
    shape = (4 * 32, 64, 64, 128, 128, 2)
    fwd, _ = k.least_seconds(*k.forward(*shape), peak)
    bwd, _ = k.least_seconds(*k.backward(*shape), peak)
    need = 2 * 3 * (fwd + bwd)                      # two traced steps
    ctx = {"peak": peak, "config": config, "shape": {"ids": [4, 4096]},
           "cell": {"trace_steps": 2}}

    def read(events):
        return reader.read(dict(ctx, raw={"devices": {0: events}}),
                           **spec["args"])

    # a row a call, the forward pass made again for the backward pass: the
    # time counts, the need does not grow
    by_row = [("jvp_gdn_chunk_fwd_.3", 0, 1e9 * need / 4)] * 8 \
        + [("fusion.1", 0, 5e6)] \
        + [("transpose_jvp_gdn_chunk_bwd__.7", 0, 1e9 * need / 4)] * 4
    assert read(by_row) == pytest.approx(100 / 3)
    # the whole batch a call, in the same seconds: the same share
    whole = [("gdn_chunk_fwd.1", 0, 2e9 * need), ("gdn_chunk_bwd.1", 0, 1e9 * need)]
    assert read(whole) == pytest.approx(100 / 3)
    # a program that takes the scan has no such event: nothing, not 0
    assert read([("while.3", 0, 1e6)]) is None
    assert reader.read(dict(ctx, peak=None, raw={"devices": {0: whole}}),
                       **spec["args"]) is None


# ---- the configuration's file ------------------------------------------------

def test_config_keeps_every_published_width():
    c = load("configs", CONFIG)
    a = c["model"]["args"]
    for key, want in {"hidden_size": 2048, "num_attention_heads": 16,
                      "num_key_value_heads": 2, "head_dim": 256,
                      "partial_rotary_factor": 0.25,
                      "linear_num_key_heads": 16, "linear_num_value_heads": 32,
                      "linear_key_head_dim": 128, "linear_value_head_dim": 128,
                      "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512,
                      "num_experts": 512, "num_experts_per_tok": 10,
                      "shared_expert_intermediate_size": 512}.items():
        assert c[key] == want and a[key] == want, key
    assert (a["num_hidden_layers"], a["experts_held"], a["vocab_size"]) \
        == (4, 16, 18992) and len(c["reduced"]) == 3
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["configs"] if e["name"] == CONFIG][0]
    assert entry["source"] == c["source"] and entry["file"].endswith(CONFIG + ".json")
    ref = correct.load_module(c["reference"])
    n = sum(int(jnp.prod(jnp.asarray(shape)))
            for shape, _ in ref.param_table(a).values())
    assert n == 424_340_544


# ---- the reference against the system, tiny ----------------------------------

@pytest.mark.parametrize("seed", [11, 4000000011])
def test_system_against_reference(seed):
    """Through the harness's own pieces in the cell's precision (bf16
    compute): inside the rehearsal cell's limits."""
    cell = load("workloads", "rehearsal-qwen3next")
    config = load("configs", "rehearsal-qwen3next")
    pool = traffic.pool(traffic.load(cell["traffic"]), config["model"]["args"], seed)
    table = correct.load_module(config["reference"]).param_table(
        config["model"]["args"])
    trainer, static = program.build_trainer(
        config, cell, correct.init_params(table, seed))
    batches = [pool[i][0] for i in range(3)]
    _, prog = run.first_steps(config, trainer, static, batches, seed)
    ref = correct.reference_steps(config, batches, seed)
    ok, rows = correct.judge(correct.compare(prog, ref, static), cell["limits"])
    assert ok, rows
    for fault in ("half_batch", "state_unchanged"):
        planted = correct.reference_steps(config, batches, seed, fault=fault)
        assert not correct.judge(correct.compare(planted, ref), cell["limits"])[0]


def test_system_against_reference_float32():
    """The same steps in float32 agree far inside what bf16 leaves: the gaps
    of the rehearsal cell are the precision's, not the program's."""
    cell = dict(load("workloads", "rehearsal-qwen3next"), trainer_kwargs={})
    config = load("configs", "rehearsal-qwen3next")
    seed = 5
    pool = traffic.pool(traffic.load(cell["traffic"]), config["model"]["args"], seed)
    table = correct.load_module(config["reference"]).param_table(
        config["model"]["args"])
    with jax.default_matmul_precision("highest"):
        trainer, static = program.build_trainer(
            config, cell, correct.init_params(table, seed))
        batches = [pool[i][0] for i in range(3)]
        _, prog = run.first_steps(config, trainer, static, batches, seed)
    ref = correct.reference_steps(config, batches, seed)
    nums = correct.compare(prog, ref, static)
    assert nums["loss_gap"][0] < 1e-5, nums
    assert nums["grad_gap"][0] < 2e-3 and nums["grad_gap_median"][0] < 1e-4, nums
    assert nums["delta_gap"][0] < 2e-2, nums


# ---- the rehearsal cell through the whole harness ------------------------------

def run_cell(argv):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        run.main(argv)
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


ARGS = ["--workload", "rehearsal-qwen3next", "--seed", "3000000019",
        "--seconds", "1"]


def test_rehearsal_cell_sound_run():
    res, err = run_cell(ARGS[:-1] + ["3", "--trace", "1"])
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    assert res["device"]["platform"] == "cpu"
    # the scan runs here: no kernel event, so the roofline is left out
    assert "gdn_kernel_roofline.tokens" not in res["metrics"]
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


def test_reference_reading_reads_the_control_and_the_faults(monkeypatch, capsys):
    """benchmark/reference_reading.py: the readings limits.py cannot make at
    the cell's size, here on the rehearsal cell."""
    from benchmark import reference_reading

    monkeypatch.setattr("sys.argv", [
        "reference_reading.py", "--workload", "rehearsal-qwen3next",
        "--seeds", "3000000019", "--who", "fp8,half_batch,state_unchanged"])
    reference_reading.main()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l["who"] for l in lines] == ["fp8", "half_batch", "state_unchanged"]
    limits = load("workloads", "rehearsal-qwen3next")["limits"]
    for l in lines[1:]:
        assert any(l[n] > lim for n, lim in limits.items()), l
    assert lines[2]["delta_gap"] == pytest.approx(1.0)
    assert lines[2]["grad_gap"] == 0
    # one state alive at a time, the same readings as the harness's own fault
    cell, config, mix, _, _ = run.load_cell("rehearsal-qwen3next")
    batches = [rows for rows, _ in traffic.pool(
        dict(mix, pool_batches=correct.STEPS), config["model"]["args"], 7)]
    lean = reference_reading.state_unchanged(config, batches, 7)
    full = correct.reference_steps(config, batches, 7, fault="state_unchanged")
    assert lean["loss"] == pytest.approx(full["loss"], rel=1e-6)
    assert lean["grad"] == pytest.approx(full["grad"], rel=1e-6)
    assert set(lean["delta"]) == set(full["delta"])
    assert all(v == 0 for v in lean["delta"].values())


def test_the_cell_reads_its_metrics_and_not_the_grus():
    cell, config, mix, layer, e2e = run.load_cell("qwen3next-ep32-train-s4096")
    assert e2e == ["train_tokens_per_s", "setup_s"]
    names = {m["name"] for m in layer}
    assert names == {"dispatch_ms.tokens", "step_ms_p95.tokens",
                     "data_wait_share.tokens", "train_step_mfu.tokens",
                     "device_idle_share.tokens", "feed_convert_ms.tokens",
                     "feed_h2d_ms.tokens", "gdn_kernel_roofline.tokens"}
    assert mix["batch"] == 4 and mix["columns"][0]["lengths"] == [4096, 4096]
    rows, work = traffic.pool(dict(mix, pool_batches=1),
                              config["model"]["args"], 1)[0]
    assert work == 4 * 4096 and all(len(r[0]) == len(r[1]) == 4096 for r in rows)
    assert max(max(r[0]) for r in rows) < 18992
