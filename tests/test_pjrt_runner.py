"""PJRT C API runner (VERDICT r4 item 5, full-graph half): the native
library (pjrt_runner.cc, pure C++ — no Python, no JAX) loads a PJRT
plugin .so, compiles the bundle's exported StableHLO, and executes it.

These tests cover what needs no plugin: the build, the ABI surface and
the failure paths. Loading libtpu.so (dlopen + version negotiation)
lives in tests/test_chip_compile.py — the one test file that may load
the TPU library — and the end-to-end serve on a chip (C++ dlopen ->
PJRT_Client_Create -> PJRT_Client_Compile -> Execute, checked against
the JAX forward) is chip_smoke.py's serve phase.
"""

import os
import subprocess

import pytest

from paddle_tpu import native

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "paddle_tpu", "native")


@pytest.fixture(scope="session")
def pjrt_build():
    native.build("pjrt")        # raises with the compiler's output


def test_runner_is_python_free(pjrt_build):
    r = subprocess.run(
        ["ldd", os.path.join(NATIVE, "libpaddle_tpu_pjrt.so")],
        capture_output=True, text=True)
    assert "python" not in r.stdout.lower()


def test_nary_abi_surface(pjrt_build):
    """The r15 n-ary typed ABI (capi.h): execute_n / num_outputs are
    exported next to the legacy 1xf32 shim, and the null-handle paths
    answer without a live plugin."""
    import ctypes

    lib = ctypes.CDLL(os.path.join(NATIVE, "libpaddle_tpu_pjrt.so"))
    for sym in ("ptpu_pjrt_create_opts", "ptpu_pjrt_execute_n",
                "ptpu_pjrt_num_outputs", "ptpu_pjrt_execute",
                "ptpu_pjrt_device_count", "ptpu_pjrt_last_error"):
        assert getattr(lib, sym) is not None
    lib.ptpu_pjrt_num_outputs.restype = ctypes.c_int
    lib.ptpu_pjrt_num_outputs.argtypes = [ctypes.c_void_p]
    assert lib.ptpu_pjrt_num_outputs(None) == -1
    lib.ptpu_pjrt_device_count.restype = ctypes.c_int
    lib.ptpu_pjrt_device_count.argtypes = [ctypes.c_void_p]
    assert lib.ptpu_pjrt_device_count(None) == -1
    lib.ptpu_pjrt_execute_n.restype = ctypes.c_int
    lib.ptpu_pjrt_execute_n.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                        ctypes.c_int32, ctypes.c_void_p,
                                        ctypes.c_int32]
    assert lib.ptpu_pjrt_execute_n(None, None, 0, None, 0) == -1
    lib.ptpu_pjrt_last_error.restype = ctypes.c_char_p
    assert b"null runner" in lib.ptpu_pjrt_last_error()


def test_missing_plugin_fails_cleanly(pjrt_build):
    with pytest.raises(RuntimeError, match="dlopen"):
        native.PjrtRunner("/nonexistent-plugin.so")


def test_failed_build_raises_with_the_tools_output():
    """native.build never returns quietly from a failed make."""
    with pytest.raises(RuntimeError, match="no_such_target"):
        native.build("no_such_target")


@pytest.mark.parametrize("target", ["pjrt", "serving"])
def test_no_pjrt_header_no_binary(tmp_path, target):
    """Without the PJRT C API header the build FAILS (the header is a
    prerequisite, so nothing is compiled or overwritten): there is no
    daemon variant that cannot reach a device."""
    r = subprocess.run(["make", "-C", NATIVE, "-B", target,
                        f"PJRT_INC={tmp_path}"],
                       capture_output=True, text=True)
    assert r.returncode != 0
    assert "PJRT C API header not found" in r.stdout + r.stderr
