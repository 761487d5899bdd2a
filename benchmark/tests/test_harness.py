"""The harness end to end on the CPU rehearsal cell, its refusals, the control
and the planted faults.

`run_cell` skips nothing but the look for a chip (a rehearsal cell is allowed
the CPU) and drives the whole of a run in this process. The faults are
planted in the program underneath it: a step that returns its state
unchanged, and half of the batch left out with the mean taken over the rest.
(The exchange between chips belongs to a four-chip cell, which this
benchmark does not have yet; no token or answer is produced in training.)
"""

import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stderr, redirect_stdout

import pytest

from benchmark import correct, run, traffic

ROOT = run.ROOT


def run_cell(argv):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        run.main(argv)
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


ARGS = ["--workload", "rehearsal-nmt", "--seed", "3000000019", "--seconds", "0.5"]


def test_sound_run_is_correct_and_line_is_whole():
    res, err = run_cell(ARGS + ["--trace", "0"])
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 3
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in res["compared"].values())
    assert err.strip().splitlines()[-1].startswith("correct True")
    assert "compared loss_gap" in err


def test_traced_run_reports_per_layer_metrics_and_no_device_number():
    res, _ = run_cell(ARGS[:-1] + ["2", "--trace", "1"])
    assert res["correct"] is True
    # histograms and the host clock are there; the CPU has no TPU plane, so the
    # idle share and the share of a peak are left out, never reported as 0
    assert {"dispatch_ms.tokens", "data_wait_share.tokens",
            "step_ms_p95.tokens"} <= set(res["metrics"])
    assert "device_idle_share.tokens" not in res["metrics"]
    assert "train_step_mfu.tokens" not in res["metrics"]
    assert "busy_s" not in res["device"]
    assert not os.path.exists(os.path.join(ROOT, ".bench_trace", "rehearsal-nmt"))


def test_step_that_returns_its_state_unchanged(monkeypatch):
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
    res, err = run_cell(ARGS + ["--trace", "0"])
    assert res["correct"] is False
    assert res["compared"]["delta_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(monkeypatch):
    from paddle_tpu.trainer.feeder import DataFeeder

    real = DataFeeder.__call__
    monkeypatch.setattr(DataFeeder, "__call__",
                        lambda self, batch: real(self, batch[:len(batch) // 2]))
    res, _ = run_cell(ARGS + ["--trace", "0"])
    assert res["correct"] is False
    assert res["compared"]["loss_gap"]["value"] > res["compared"]["loss_gap"]["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_comes_out_not_correct(seed):
    """The reference in the program's place, in fp8: the rehearsal cell's
    limits fail it, as the chip cells' limits fail it at their own size."""
    with open(os.path.join(run.HERE, "workloads", "rehearsal-nmt.json")) as f:
        cell = json.load(f)
    with open(os.path.join(run.HERE, "configs", "rehearsal-nmt.json")) as f:
        config = json.load(f)
    pool = traffic.pool(traffic.load(cell["traffic"]), config["model"]["args"], seed)
    batches = [pool[i][0] for i in range(3)]
    ref = correct.reference_steps(config, batches, seed)
    control = correct.reference_steps(config, batches, seed, rounding=config["precision"]["control"])
    ok, rows = correct.judge(correct.compare(control, ref), cell["limits"])
    assert not ok, rows
    for fault in ("half_batch", "state_unchanged"):
        planted = correct.reference_steps(config, batches, seed, fault=fault)
        assert not correct.judge(correct.compare(planted, ref), cell["limits"])[0]


def test_a_listed_cell_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "nmt-train-b512",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "not a TPU" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_unknown_device_kind_is_an_error(monkeypatch):
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda: [Fake()])
    with pytest.raises(SystemExit, match="peaks.json"):
        run.check_device({"chips": 1})


def test_fewer_chips_than_the_cell_asks_for(monkeypatch):
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [Fake()])
    with pytest.raises(SystemExit, match="4 chips"):
        run.check_device({"chips": 4})


def test_a_cell_reads_only_its_own_metrics():
    cell, config, mix, layer, e2e = run.load_cell("nmt-train-b512")
    assert not cell.get("rehearsal") and e2e == ["train_tokens_per_s", "setup_s"]
    assert {m["name"] for m in layer} >= {"train_step_mfu.tokens",
                                          "device_idle_share.tokens"}
    assert all(m["name"].endswith(".tokens") for m in layer)
    for m in layer:                       # every metric has its reader
        with open(os.path.join(run.HERE, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert hasattr(correct.load_module(f"readers/{spec['reader']}.py"), "read")
