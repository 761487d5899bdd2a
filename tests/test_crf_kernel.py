"""Pallas CRF forward-backward kernel (VERDICT r4 item 4): parity with
the lax.scan recursion, f64 FD check in interpret mode, padding paths.
On-chip parity + the T-sweep timing table: tools/ctc_bench.py (r5
figures in layers/crf_ctc.py, not re-measured).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.layers.crf_ctc as cc


def _case(B=4, T=13, L=7, seed=0):
    r = np.random.RandomState(seed)
    emit = jnp.asarray(r.randn(B, T, L), jnp.float32)
    labels = jnp.asarray(r.randint(0, L, (B, T)), jnp.int32)
    lens = r.randint(2, T + 1, B)
    lens[0] = T
    mask = jnp.asarray((np.arange(T)[None] < lens[:, None])
                       .astype(np.float32))
    w = jnp.asarray(r.randn(L + 2, L) * 0.5, jnp.float32)
    return emit, labels, mask, w


def test_logz_matches_scan_values_and_grads():
    emit, labels, mask, w = _case()
    want = cc.crf_logz_scan(emit, mask, w)
    got = cc.crf_logz_pallas(emit, mask, w, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # non-uniform (and negative) cotangents exercise the in-kernel
    # ct-weighted pairwise accumulator
    ct = jnp.asarray([1.0, -2.0, 0.5, 3.0])
    g1 = jax.grad(lambda e, w: (cc.crf_logz_scan(e, mask, w) * ct).sum(),
                  argnums=(0, 1))(emit, w)
    g2 = jax.grad(lambda e, w: (cc.crf_logz_pallas(e, mask, w, True)
                                * ct).sum(), argnums=(0, 1))(emit, w)
    for n, a, b in zip(("demit", "dw"), g1, g2):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-5, err_msg=n)


def test_dtrans_with_disfavored_transitions():
    """Peaked alphas + a strongly NEGATIVE transition forced by the
    emissions: the pairwise factor's exponent goes positive (bounded by
    -trans), which a 0-capped clip silently truncated (r5 review
    finding) — d_trans must match scan exactly anyway."""
    B, T, L = 2, 6, 4
    r = np.random.RandomState(7)
    emit = jnp.asarray(r.randn(B, T, L) * 0.3, jnp.float32)
    emit = emit.at[:, :, 0].add(6.0)          # alphas peak on state 0
    emit = emit.at[:, 3, 1].add(14.0)         # ...but t=3 forces state 1
    mask = jnp.ones((B, T), jnp.float32)
    w = jnp.asarray(r.randn(L + 2, L) * 0.2, jnp.float32)
    w = w.at[2 + 0, 1].set(-6.0)              # trans[0 -> 1] strongly neg
    g1 = jax.grad(lambda w: cc.crf_logz_scan(emit, mask, w).sum())(w)
    g2 = jax.grad(lambda w: cc.crf_logz_pallas(emit, mask, w,
                                               interpret=True).sum())(w)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g1),
                               rtol=1e-4, atol=1e-5)


def test_crf_nll_kernel_and_scan_parity():
    """The layer's nll with the kernel's log partition (kernels.crf.crf_logz,
    time-major, the way crf_logz_pallas feeds it) equals the scan path's."""
    from paddle_tpu.kernels.crf import crf_logz

    emit, labels, mask, w = _case(seed=1)
    want = cc.crf_nll(emit, labels, mask, w)        # scan: the CPU backend
    start, end, trans = cc._crf_pieces(w)
    logz = crf_logz(jnp.swapaxes(emit, 0, 1), jnp.swapaxes(mask, 0, 1),
                    start, end, trans, True)
    got = logz - cc._crf_gold_score(emit, labels, mask, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend,no_jit,T", [
    ("cpu", False, 2048), ("tpu", True, 2048), ("tpu", False, 255)],
    ids=["backend", "disable_jit", "short"])
def test_crf_use_pallas_is_the_only_selector(monkeypatch, backend, no_jit, T):
    """The scan runs wherever the kernel cannot or should not: off the TPU
    backend, under jax_disable_jit, and below the measured crossover
    (`_CRF_PALLAS_MIN_T`). Nothing a user sets overrides that."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert cc._crf_use_pallas(2048) is True         # the control
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with jax.disable_jit(no_jit):
        assert cc._crf_use_pallas(T) is False


def test_fd_check_f64():
    """The VERDICT acceptance: FD-checked in interpret mode f64."""
    jax.config.update("jax_enable_x64", True)
    try:
        r = np.random.RandomState(3)
        B, T, L = 2, 9, 5
        emit = jnp.asarray(r.randn(B, T, L), jnp.float64)
        mask = jnp.asarray((np.arange(T)[None] <
                            np.array([[9], [6]])).astype(np.float64))
        w = jnp.asarray(r.randn(L + 2, L) * 0.5, jnp.float64)

        def f(e, w):
            return cc.crf_logz_pallas(e, mask, w, interpret=True).sum()

        ge, gw = jax.grad(f, argnums=(0, 1))(emit, w)
        ge, gw = np.asarray(ge), np.asarray(gw)
        eps = 1e-6
        r2 = np.random.RandomState(4)
        for _ in range(8):
            b, t, l = r2.randint(B), r2.randint(T), r2.randint(L)
            d = jnp.zeros_like(emit).at[b, t, l].set(eps)
            fd = (float(f(emit + d, w)) - float(f(emit - d, w))) / (2 * eps)
            assert abs(fd - ge[b, t, l]) < 1e-5 * max(1.0, abs(fd))
        for _ in range(8):
            i, j = r2.randint(L + 2), r2.randint(L)
            d = jnp.zeros_like(w).at[i, j].set(eps)
            fd = (float(f(emit, w + d)) - float(f(emit, w - d))) / (2 * eps)
            assert abs(fd - gw[i, j]) < 1e-5 * max(1.0, abs(fd)), \
                (i, j, fd, gw[i, j])
    finally:
        jax.config.update("jax_enable_x64", False)


def test_trans_bound_warns_eagerly():
    """Round-5 advisor finding: the backward clips pairwise-marginal
    exponents at +/-80, exact only for max |trans| < 80. The public
    crf_logz API documents the bound and warns on a concrete violation;
    compliant calls and NEG lane-padding sentinels stay silent."""
    import warnings

    from paddle_tpu.kernels.crf import NEG as KNEG, crf_logz

    T, B, L = 4, 2, 3
    r = np.random.RandomState(1)
    em = jnp.asarray(r.randn(T, B, L), jnp.float32)
    mask = jnp.ones((T, B), jnp.float32)
    start = jnp.zeros(L)
    end = jnp.zeros(L)
    ok = jnp.asarray(r.randn(L, L), jnp.float32)

    with warnings.catch_warnings():
        warnings.simplefilter("error")          # any warning -> failure
        crf_logz(em, mask, start, end, ok, True)
        # NEG-padded dead states (crf_logz_pallas lane padding) are
        # sentinels, not violations
        crf_logz(em, mask, start, end,
                 ok.at[-1, :].set(KNEG), True)

    bad = ok.at[0, 1].set(-120.0)
    with pytest.warns(RuntimeWarning, match=r"\|trans\|"):
        crf_logz(em, mask, start, end, bad, True)
    # traced calls skip the check (documented bound instead of a
    # host sync inside jit)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        jax.jit(lambda w: crf_logz(em, mask, start, end, w, True))(bad)
