"""Pallas kernel parity tests (run in interpreter mode on the CPU suite;
the same kernels compile for TPU — the Compare2Function-style check that
the hand-fused kernel matches the layer-registry reference semantics)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import activation as am
from paddle_tpu.kernels.lstm import fused_lstm, fused_lstm_supported
from paddle_tpu.layers.recurrent import lstm_cell

TANH = am.resolve("tanh")


def _scan_ref(x4, W, b, mask):
    B, T, H4 = x4.shape
    H = H4 // 4
    h = jnp.zeros((B, H))
    c = jnp.zeros((B, H))
    hs, cs = [], []
    for t in range(T):
        hn, cn = lstm_cell(x4[:, t], h, c, W, b, TANH, TANH, H)
        m = mask[:, t][:, None]
        h = m * hn + (1 - m) * h
        c = m * cn + (1 - m) * c
        hs.append(h)
        cs.append(c)
    return jnp.stack(hs, 1), jnp.stack(cs, 1)


def _data(B, T, H, seed):
    r = np.random.RandomState(seed)
    x4 = jnp.asarray(r.randn(B, T, 4 * H) * 0.3, jnp.float32)
    W = jnp.asarray(r.randn(H, 4 * H) * 0.1, jnp.float32)
    b = jnp.asarray(r.randn(7 * H) * 0.1, jnp.float32)
    mask = np.ones((B, T), np.float32)
    mask[1, T // 2:] = 0
    return x4, W, b, jnp.asarray(mask)


def test_fused_lstm_supported():
    assert fused_lstm_supported(64, 512)
    assert not fused_lstm_supported(64, 100)
    assert not fused_lstm_supported(3, 128)


@pytest.mark.parametrize("T", [5, 6, 7])
def test_fused_lstm_grad_short_sequences(T):
    """T below the backward chunk size: the backward grid used to truncate
    and silently drop timesteps (NaN dx4)."""
    B, H = 8, 128
    x4, W, b, mask = _data(B, T, H, T)

    def loss_ref(x4, W, b):
        hs, _ = _scan_ref(x4, W, b, mask)
        return (hs ** 2).sum()

    def loss_fused(x4, W, b):
        hs, _ = fused_lstm(x4, W, b, mask, None, True)
        return (hs ** 2).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x4, W, b)
    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x4, W, b)
    for name, a, b_ in zip(("dx4", "dW", "db"), gr, gf):
        assert np.isfinite(np.asarray(b_)).all(), name
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("B,T,H", [(8, 5, 128), (8, 13, 128), (4, 24, 256)])
def test_fused_lstm_forward_parity(B, T, H):
    x4, W, b, mask = _data(B, T, H, B + T)
    hs_r, cs_r = _scan_ref(x4, W, b, mask)
    hs_f, cs_f = fused_lstm(x4, W, b, mask, None, True)
    np.testing.assert_allclose(np.asarray(hs_f), np.asarray(hs_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cs_f), np.asarray(cs_r),
                               rtol=1e-5, atol=1e-5)


def test_fused_lstm_grad_parity():
    B, T, H = 8, 13, 128
    x4, W, b, mask = _data(B, T, H, 0)

    def loss_ref(x4, W, b):
        hs, cs = _scan_ref(x4, W, b, mask)
        return (hs ** 2).sum() + 0.5 * (cs ** 2).sum()

    def loss_fused(x4, W, b):
        hs, cs = fused_lstm(x4, W, b, mask, None, True)
        return (hs ** 2).sum() + 0.5 * (cs ** 2).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x4, W, b)
    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x4, W, b)
    for name, a, b_ in zip(("dx4", "dW", "db"), gr, gf):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_fused_lstm_split_bwd_grad_parity(monkeypatch):
    """The split backward (no in-kernel dW — the h=1280 VMEM-gate path,
    VERDICT r4 item 6) produces identical grads to the scan reference."""
    import paddle_tpu.kernels.lstm as lstm_mod

    monkeypatch.setattr(lstm_mod, "_FORCE_SPLIT_BWD", True)
    B, T, H = 8, 13, 128
    x4, W, b, mask = _data(B, T, H, 3)

    def loss_ref(x4, W, b):
        hs, cs = _scan_ref(x4, W, b, mask)
        return (hs ** 2).sum() + 0.5 * (cs ** 2).sum()

    def loss_fused(x4, W, b):
        hs, cs = fused_lstm(x4, W, b, mask, None, True)
        return (hs ** 2).sum() + 0.5 * (cs ** 2).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x4, W, b)
    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x4, W, b)
    for name, a, b_ in zip(("dx4", "dW", "db"), gr, gf):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_fused_lstm_supported_covers_h1280():
    """h=1280/bs=64 — the r4 VMEM-gate fallback case — is now fused via
    the split backward."""
    assert fused_lstm_supported(64, 1280)


def _maxpool_vjp(v_flat, H, k, s, p, C):
    """(output, cotangent -> input gradient as [B, H, H, C]) of the ``pool``
    layer's max path at one geometry."""
    from paddle_tpu import data_type, layer, pooling
    from paddle_tpu.core.arg import Arg
    from paddle_tpu.core.layer import layer_name_scope
    from paddle_tpu.core.topology import Topology

    with layer_name_scope():
        x = layer.data(name="x", type=data_type.dense_vector(C * H * H),
                       height=H, width=H)
        out = layer.img_pool(input=x, pool_size=k, stride=s, padding=p,
                             num_channels=C, pool_type=pooling.Max())
    topo = Topology(out)
    y, vjp = jax.vjp(
        lambda v: topo.forward({}, {"x": Arg(v)})[out.name].value,
        jnp.asarray(v_flat))

    def grad(cot):
        (g,) = vjp(jnp.asarray(cot))
        return np.asarray(g).reshape(-1, C, H, H).transpose(0, 2, 3, 1)

    return np.asarray(y), grad


@pytest.mark.parametrize("H,k,s,p", [(13, 3, 2, 1), (12, 2, 2, 0),
                                     (14, 3, 3, 1)])
def test_maxpool_backward_matches_numpy(H, k, s, p):
    """The ``pool`` layer's max path (lax.reduce_window; backward = XLA's
    select-and-scatter) against a NumPy loop: the window's maximum takes
    the window's cotangent, across paddings and the ceil-mode overhang."""
    B, C = 2, 8
    r = np.random.RandomState(0)
    v = r.randn(B, C * H * H).astype(np.float32)
    y, grad = _maxpool_vjp(v, H, k, s, p, C)
    g = r.randn(*y.shape).astype(np.float32)
    img = v.reshape(B, C, H, H).transpose(0, 2, 3, 1)
    want = np.zeros_like(img)
    for oy in range(g.shape[1]):
        for ox in range(g.shape[2]):
            y0, x0 = max(oy * s - p, 0), max(ox * s - p, 0)
            y1, x1 = min(oy * s - p + k, H), min(ox * s - p + k, H)
            if y0 >= y1 or x0 >= x1:
                continue            # window lies wholly in the padding
            win = img[:, y0:y1, x0:x1, :].reshape(B, -1, C)
            top = win.argmax(axis=1)                        # [B, C]
            iy, ix = y0 + top // (x1 - x0), x0 + top % (x1 - x0)
            for b in range(B):
                want[b, iy[b], ix[b], np.arange(C)] += g[b, oy, ox]
    np.testing.assert_allclose(grad(g), want, rtol=1e-6, atol=1e-6)


def test_maxpool_backward_on_ties_has_one_winner():
    """Post-ReLU feature maps tie at 0.0 all the time: every element of a
    window is a maximum, and exactly one of them receives the window's
    whole cotangent (what the reference's max-pool backward does too)."""
    B, C, H, k = 2, 4, 8, 2
    y, grad = _maxpool_vjp(np.zeros((B, C * H * H), np.float32),
                           H, k, k, 0, C)
    got = grad(np.full(y.shape, 3.0, np.float32))
    win = got.reshape(B, H // k, k, H // k, k, C).transpose(0, 1, 3, 5, 2, 4)
    win = win.reshape(B, H // k, H // k, C, k * k)
    np.testing.assert_array_equal(win.sum(-1), 3.0)
    np.testing.assert_array_equal((win != 0).sum(-1), 1)


# --- the kernel layer's structure: one path per kernel site ----------------

#: every module under paddle_tpu/kernels/ that holds a pl.pallas_call. Wiring
#: or deleting the one exception has to touch this list.
_KERNEL_MODULES = ["crf", "flash_attn", "gdn", "gru", "head_norm_rotary",
                   "lstm", "moe_grouped", "short_conv", "vocab_xent"]
_UNWIRED = {"vocab_xent": "ROADMAP S3 decides: measure at the NMT cell's "
                          "shape, then wire or delete"}


def _pkg_sources(sub):
    import os

    import paddle_tpu

    root = os.path.join(os.path.dirname(paddle_tpu.__file__), sub)
    out = {}
    for fn in sorted(os.listdir(root)):
        if fn.endswith(".py"):
            with open(os.path.join(root, fn)) as f:
                out[fn[:-3]] = f.read()
    return out


@pytest.mark.parametrize("mod", _KERNEL_MODULES)
def test_kernel_module_is_reached_from_a_layer(mod):
    """A Mosaic kernel in the tree is on a layer's path, chosen by the one
    predicate (take_pallas) and called through call_kernel (which shard_maps
    it under a data-parallel trainer): no kernel kept beside the path."""
    import re

    kernels = _pkg_sources("kernels")
    assert _KERNEL_MODULES == sorted(
        m for m, src in kernels.items() if "pl.pallas_call(" in src)
    uses = re.compile(r"paddle_tpu\.kernels\.%s\b|"
                      r"from paddle_tpu\.kernels import [^\n]*\b%s\b"
                      % (mod, mod))
    layers = _pkg_sources("layers")
    users = [m for m, src in layers.items() if uses.search(src)]
    if mod in _UNWIRED:
        assert not users, f"{mod} is wired now: drop it from _UNWIRED"
        return
    assert users, f"no module under paddle_tpu/layers/ imports kernels.{mod}"
    for user in users:
        src = layers[user] + kernels[mod]
        assert "take_pallas(" in src and "call_kernel(" in src, (mod, user)
