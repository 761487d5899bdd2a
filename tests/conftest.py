"""Test config: force an 8-device CPU platform so multi-chip sharding tests
run without TPU hardware (SURVEY §4 carry-over item 3)."""

import os

# Tests run on a virtual 8-device CPU mesh whatever the launch environment
# selected: the variable is set for child processes, the jax config (below)
# for this one. The run on a real chip is `python chip_smoke.py`.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import sys

# repo root on sys.path: test modules import the repo-level tools/
# package (e.g. tools.tpu_parity), which a bare `pytest` invocation does
# not put on the path
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running (device compile) tests")
    config.addinivalue_line(
        "markers", "quick: fast-tier tests (CI gate, `-m quick` < ~5 min)")
    config.addinivalue_line(
        "markers", "chaos: fault-injection / crash-recovery tests. The "
        "deterministic single-process ones stay in the tier-1 `not slow` "
        "set; multiprocess kill tests are additionally marked slow")


# Modules dominated by end-to-end acceptance runs / native toolchain /
# convergence training — excluded from the `-m quick` CI gate tier
# (VERDICT r2 weak-item #9). Everything else is marked quick.
_SLOW_MODULES = {
    "test_config_parser",   # reference-demo acceptance trains (~5 min)
    "test_trainer_mnist",   # convergence training
    "test_seq2seq",         # NMT beam-search end-to-end
    "test_flagship",        # ResNet-50 trace
    "test_elastic",         # kill/rejoin with real processes + TTLs
    "test_capi",            # C compiler + embedded CPython
    "test_native",          # native toolchain builds
    "test_cluster_launch",  # process fan-out
    "test_datasets",        # dataset loaders
    "test_tpu_parity",      # 23-case parity catalog
    "test_multihost",       # two-process jax.distributed bootstrap
    "test_gan",             # adversarial two-trainer acceptance
}


def pytest_collection_modifyitems(config, items):
    import pytest as _pytest

    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        if mod not in _SLOW_MODULES and "slow" not in item.keywords:
            item.add_marker(_pytest.mark.quick)


@pytest.fixture
def rng():
    import jax
    return jax.random.PRNGKey(0)
