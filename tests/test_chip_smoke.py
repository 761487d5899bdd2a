"""chip_smoke.py off the chip: it must refuse to run, and its phases must be
sound at tiny widths — the rehearsal that costs no chip time. Plus the
compile-cache placement every device process shares (paddle.compile_cache).

The rehearsals are steered from here (tiny config dicts, the platform gate
of chip_smoke.enter_child not entered), never by an option of the script.
"""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU_TAGS = {"platform": "cpu", "device_kind": "cpu", "device_count": 1,
            "compile_cache_dir": None}


def test_refuses_to_run_on_the_cpu():
    """The no-fallback pin: held to the CPU, the script exits non-zero
    before any phase ran and prints no result line."""
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and '"phase"' not in r.stdout, r.stdout
    assert "not a TPU" in r.stderr
    assert not os.path.exists(chip_smoke.WORK)      # cleaned up after itself


def test_a_child_that_dies_ends_the_script(tmp_path, monkeypatch):
    """Any phase's child failing — here: an unknown phase name, which
    raises in the child as a killed one would return non-zero — exits the
    parent with a non-zero code instead of moving on."""
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path))
    with pytest.raises(SystemExit) as e:
        chip_smoke.run_child("no_such_phase")
    assert e.value.code not in (0, None)


def test_unknown_option_is_refused():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py"),
                        "--cpu"], capture_output=True, text=True, timeout=60)
    assert r.returncode != 0 and '"ok"' not in r.stdout


def test_train_phase_rehearsal_tiny(tmp_path, capsys):
    """phase_train end to end on the CPU at tiny widths: SGD.train through
    the pipelined loop with named feeding, the cost checks, the step
    lowering (no kernel expected here: the scan path), fused-vs-scan parity
    with the kernels interpreted, checkpoint round trip, paddle.infer."""
    cfg = dict(chip_smoke.TRAIN, vocab=200, width=128, batch=16, n_batches=4,
               max_len=8, cpu_cost_per_token=None, min_kernel_calls=0,
               gru_parity=(8, 128, 6), lstm_parity=(8, 128, 6),
               interpret=True, sync_probe=(64, 4))
    result = chip_smoke.phase_train(cfg, CPU_TAGS, str(tmp_path))
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert all(l["platform"] == "cpu" and l["device_kind"] == "cpu"
               for l in lines)
    train = lines[0]
    assert train["what"] == "SGD.train" and train["batches"] == 4
    assert train["tpu_custom_calls_in_step"] == 0
    assert train["cost_per_token"][-1] < train["cost_per_token"][0]
    parity = [l for l in lines if l["what"] == "fused kernel vs scan path"]
    assert [(p["kernel"], p["dtype"]) for p in parity] == [
        ("fused_gru", "float32"), ("fused_gru", "bfloat16"),
        ("fused_lstm", "float32"), ("fused_lstm", "bfloat16")]
    assert all(p["ok"] for p in parity)
    assert os.listdir(tmp_path) == []               # the checkpoint is gone


@pytest.mark.slow
def test_cpu_reference_of_the_full_width_run():
    """Re-derives TRAIN["cpu_cost_per_token"], the trajectory chip_smoke.py
    holds the chip to: the full-width NMT through the same public loop,
    here on the CPU (~1.5 min). Re-record the constant from this test's
    failure message when jax moves initial values again."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.core.topology import Topology

    cfg = chip_smoke.TRAIN
    cost = chip_smoke.nmt_cost(cfg)
    trainer = paddle.SGD(cost, paddle.parameters_create(Topology(cost)),
                         optimizer.Adam(cfg["lr"]), mixed_precision=True)
    costs, _, _ = chip_smoke.run_trainer(trainer, chip_smoke.nmt_reader(cfg),
                                         chip_smoke.NMT_FEEDING)
    got = chip_smoke.cost_per_token(costs, chip_smoke.nmt_reader(cfg)())
    assert got == pytest.approx(cfg["cpu_cost_per_token"], rel=1e-3), got


def test_parity_check_catches_a_wrong_kernel(monkeypatch):
    """The fused-vs-scan comparison is not vacuous: a kernel path that is
    1% off fails it."""
    real = chip_smoke._fused_path
    monkeypatch.setattr(chip_smoke, "_fused_path",
                        lambda *a: real(*a) * 1.01)
    res = chip_smoke.kernel_parity("gru", (8, 128, 6), "float32", True, 0)
    assert not res["ok"], res


_CACHE_PROBE = ("import jax, paddle_tpu; d = paddle_tpu.compile_cache(); "
                "print(repr((d, jax.config.jax_compilation_cache_dir)))")


def _cache_probe(env):
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    return eval(r.stdout.strip().splitlines()[-1])


def _env_without(*names, **extra):
    env = {k: v for k, v in os.environ.items() if k not in names}
    env.update(extra)
    return env


def test_compile_cache_follows_the_variable(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it, the function names
    the same directory and sets no other in code."""
    where = str(tmp_path / "cache")
    used, configured = _cache_probe(_env_without(
        "JAX_PLATFORMS", JAX_COMPILATION_CACHE_DIR=where))
    assert used == where and configured == where


def test_compile_cache_fixed_path_in_the_checkout():
    """Variable unset: one fixed directory inside the checkout, the same
    in every process (the path is part of the cache key). A process held
    to the CPU — this suite — gets no persistent cache at all."""
    env = _env_without("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")
    first, second = _cache_probe(env), _cache_probe(env)
    assert first == second == (os.path.join(REPO, ".jax_cache"),) * 2
    held = _cache_probe(_env_without("JAX_COMPILATION_CACHE_DIR",
                                     JAX_PLATFORMS="cpu"))
    assert held == (None, None)
