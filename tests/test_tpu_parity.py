"""Layer equivalence harness (SURVEY §4 carry-over (1)(2); the
Compare2Function analog, paddle/function/FunctionTest.h:1-60).

In-suite: every catalog case compares op-by-op CPU-interpreter execution
against the jit-compiled program (compiled-CPU here; the same harness
binary runs against the real chip). The subprocess test re-runs the
whole catalog WITHOUT the suite's CPU pin, so on the bench host it
executes compiled-TPU vs interpreter-CPU — the first suite path that
touches the actual device.
"""

import os
import subprocess
import sys

import pytest

from tools.tpu_parity import CASES, run_case

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("case", CASES, ids=[c.name for c in CASES])
def test_interpreter_vs_compiled(case):
    run_case(case)


def test_catalog_covers_major_layer_families():
    """The catalog must keep touching the core layer families as the
    registry grows (a shrunken catalog silently weakens the harness)."""
    import paddle_tpu  # noqa: F401  (fills the registry)
    from paddle_tpu.core.layer import LAYER_REGISTRY

    assert len(LAYER_REGISTRY._entries) >= 95
    assert len(CASES) >= 15


@pytest.mark.slow
def test_on_real_device_when_present():
    """Re-exec the harness without the suite's CPU pin: on a machine
    with a TPU this compiles every case for the chip and compares
    against the CPU interpreter — the reference's CPU-vs-GPU
    Compare2Function run.

    The accelerator platform comes from the launch environment's
    JAX_PLATFORMS; we append ',cpu' so the reference backend exists
    beside it. With no platform configured the harness still runs
    compiled-CPU vs interpreter-CPU.
    """
    env = dict(os.environ)
    launch_platform = env.get("JAX_PLATFORMS", "")
    if launch_platform and "cpu" not in launch_platform:
        env["JAX_PLATFORMS"] = f"{launch_platform},cpu"
    else:
        env.pop("JAX_PLATFORMS", None)
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count"))
    env["PYTHONPATH"] = (env.get("PYTHONPATH", "") + os.pathsep + REPO) \
        .strip(os.pathsep)
    # fast smoke subset: full catalog compile on a real chip is minutes
    subset = ["fc", "conv_pool_bn", "lstm", "embedding_pool"]
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpu_parity.py"),
         *subset],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert f"{len(subset)}/{len(subset)} cases passed" in r.stdout
