"""Fused vocab-projection + softmax-xent kernel (kernels/vocab_xent.py):
values/grads match the materializing baseline exactly; silicon timing is
a measured WASH at NMT shapes (documented in the module docstring; r5,
not re-measured), so the kernel is a library function, not wired into any layer path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.vocab_xent import vocab_xent


def _case(N=37, D=16, V=300, seed=0):
    r = np.random.RandomState(seed)
    x = jnp.asarray(r.randn(N, D), jnp.float32)
    w = jnp.asarray(r.randn(D, V) * 0.1, jnp.float32)
    b = jnp.asarray(r.randn(V) * 0.1, jnp.float32)
    lab = jnp.asarray(r.randint(0, V, N), jnp.float32)
    return x, w, b, lab


def _ref(x, w, b, lab):
    logits = x @ w + b
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, lab.astype(jnp.int32)[:, None],
                               1)[:, 0]
    return lse - gold


def test_values_and_grads_match_baseline():
    x, w, b, lab = _case()
    want = _ref(x, w, b, lab)
    got = vocab_xent(x, w, b, lab, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    ct = jnp.asarray(np.random.RandomState(1).randn(x.shape[0]),
                     jnp.float32)
    g1 = jax.grad(lambda x, w, b: (_ref(x, w, b, lab) * ct).sum(),
                  argnums=(0, 1, 2))(x, w, b)
    g2 = jax.grad(lambda x, w, b: (vocab_xent(x, w, b, lab, True)
                                   * ct).sum(), argnums=(0, 1, 2))(x, w, b)
    for n, a, g in zip(("dx", "dw", "db"), g1, g2):
        np.testing.assert_allclose(np.asarray(g), np.asarray(a),
                                   rtol=1e-4, atol=1e-5, err_msg=n)


def test_aligned_shapes_no_padding_path():
    x, w, b, lab = _case(N=256, D=8, V=2048, seed=2)
    want = _ref(x, w, b, lab)
    got = vocab_xent(x, w, b, lab, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fd_check_f64():
    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        r = np.random.RandomState(3)
        N, D, V = 5, 4, 9
        x = jnp.asarray(r.randn(N, D), jnp.float64)
        w = jnp.asarray(r.randn(D, V) * 0.3, jnp.float64)
        b = jnp.asarray(r.randn(V) * 0.3, jnp.float64)
        lab = jnp.asarray(r.randint(0, V, N), jnp.float64)

        def f(w):
            return vocab_xent(x, w, b, lab, True).sum()

        g = np.asarray(jax.grad(f)(w))
        eps = 1e-6
        for _ in range(8):
            i, j = r.randint(D), r.randint(V)
            d = jnp.zeros_like(w).at[i, j].set(eps)
            fd = (float(f(w + d)) - float(f(w - d))) / (2 * eps)
            assert abs(fd - g[i, j]) < 1e-5 * max(1.0, abs(fd)), \
                (i, j, fd, g[i, j])
    finally:
        jax.config.update("jax_enable_x64", prev_x64)
