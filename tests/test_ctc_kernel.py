"""Pallas CTC forward-backward kernel (VERDICT r4 item 4): parity with
the lax.scan recursion (layers/crf_ctc.ctc_nll), finite-difference check
in f64 interpret mode, and edge cases. Silicon parity + the T-sweep
timing table: tools/ctc_bench.py (r5 figures in layers/crf_ctc.py,
not re-measured).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.ctc import ctc_nll_pallas
from paddle_tpu.layers.crf_ctc import ctc_nll


def _case(B=4, T=13, C=11, U=5, seed=0):
    r = np.random.RandomState(seed)
    logits = jnp.asarray(r.randn(B, T, C), jnp.float32)
    labels = jnp.asarray(r.randint(1, C, (B, U)), jnp.int32)
    lens = r.randint(max(2 * U + 1, 2), T + 1, B)
    lens[0] = T
    ulens = r.randint(1, U + 1, B)
    ulens[0] = U
    im = jnp.asarray((np.arange(T)[None] < lens[:, None]).astype(np.float32))
    lm = jnp.asarray((np.arange(U)[None] < ulens[:, None]).astype(np.float32))
    return logits, labels, im, lm


def test_pallas_matches_scan_values_and_grads():
    logits, labels, im, lm = _case()
    want = ctc_nll(logits, labels, im, lm)
    got = ctc_nll_pallas(logits, labels, im, lm, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    g1 = jax.grad(lambda l: ctc_nll(l, labels, im, lm).sum())(logits)
    g2 = jax.grad(lambda l: ctc_nll_pallas(l, labels, im, lm,
                                           interpret=True).sum())(logits)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g1),
                               rtol=1e-4, atol=1e-5)


def test_pallas_repeated_labels():
    """Repeated labels disable the skip transition (the can_skip rule)."""
    r = np.random.RandomState(1)
    logits = jnp.asarray(r.randn(2, 12, 6), jnp.float32)
    labels = jnp.asarray([[2, 2, 3], [4, 4, 4]], jnp.int32)
    im = jnp.ones((2, 12), jnp.float32)
    lm = jnp.ones((2, 3), jnp.float32)
    want = ctc_nll(logits, labels, im, lm)
    got = ctc_nll_pallas(logits, labels, im, lm, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pallas_empty_label():
    """ulen == 0: the all-blank path only (slen == 1)."""
    r = np.random.RandomState(2)
    logits = jnp.asarray(r.randn(2, 9, 5), jnp.float32)
    labels = jnp.asarray([[1, 2], [0, 0]], jnp.int32)
    im = jnp.ones((2, 9), jnp.float32)
    lm = jnp.asarray([[1.0, 1.0], [0.0, 0.0]])
    want = ctc_nll(logits, labels, im, lm)
    got = ctc_nll_pallas(logits, labels, im, lm, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pallas_fd_check_f64():
    """The VERDICT acceptance: FD-checked in interpret mode f64."""
    jax.config.update("jax_enable_x64", True)
    try:
        r = np.random.RandomState(3)
        B, T, C, U = 2, 9, 6, 3
        logits = jnp.asarray(r.randn(B, T, C), jnp.float64)
        labels = jnp.asarray(r.randint(1, C, (B, U)), jnp.int32)
        im = jnp.asarray((np.arange(T)[None] <
                          np.array([[9], [7]])).astype(np.float64))
        lm = jnp.ones((B, U), jnp.float64)

        def f(l):
            return ctc_nll_pallas(l, labels, im, lm, interpret=True).sum()

        g = np.asarray(jax.grad(f)(logits))
        eps = 1e-6
        r2 = np.random.RandomState(4)
        for _ in range(12):
            b, t, c = (r2.randint(B), r2.randint(T), r2.randint(C))
            e = jnp.zeros_like(logits).at[b, t, c].set(eps)
            fd = (float(f(logits + e)) - float(f(logits - e))) / (2 * eps)
            assert abs(fd - g[b, t, c]) < 1e-5 * max(1.0, abs(fd)), \
                (b, t, c, fd, g[b, t, c])
    finally:
        jax.config.update("jax_enable_x64", False)


def test_layer_impl_switch():
    """The ctc layer picks scan on CPU and exposes the force switch."""
    from paddle_tpu.layers import crf_ctc as mod

    assert not mod._ctc_use_pallas()          # CPU test suite
    old = mod.CTC_IMPL
    try:
        mod.CTC_IMPL = "pallas"
        assert mod._ctc_use_pallas()
        mod.CTC_IMPL = "scan"
        assert not mod._ctc_use_pallas()
    finally:
        mod.CTC_IMPL = old
