"""Fused GRU Pallas kernel vs the layer-registry gru_cell reference
(kernels/gru.py; interpreter mode on the CPU suite, compiles for TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import activation as am
from paddle_tpu.kernels.gru import fused_gru, fused_gru_supported
from paddle_tpu.layers.recurrent import gru_cell

SIG = am.resolve("sigmoid")
TANH = am.resolve("tanh")


def _scan_ref(x3, Wg, Wc, b, mask):
    B, T, H3 = x3.shape
    H = H3 // 3
    h = jnp.zeros((B, H))
    hs = []
    for t in range(T):
        hn = gru_cell(x3[:, t], h, Wg, Wc, b, SIG, TANH, H)
        m = mask[:, t][:, None]
        h = m * hn + (1 - m) * h
        hs.append(h)
    return jnp.stack(hs, 1)


def _data(B, T, H, seed=0):
    r = np.random.RandomState(seed)
    x3 = jnp.asarray(r.randn(B, T, 3 * H) * 0.3, jnp.float32)
    Wg = jnp.asarray(r.randn(H, 2 * H) * 0.1, jnp.float32)
    Wc = jnp.asarray(r.randn(H, H) * 0.1, jnp.float32)
    b = jnp.asarray(r.randn(3 * H) * 0.1, jnp.float32)
    mask = np.ones((B, T), np.float32)
    mask[1, T // 2:] = 0                  # ragged batch member
    return x3, Wg, Wc, b, jnp.asarray(mask)


def test_supported_gate():
    assert fused_gru_supported(64, 512)
    assert not fused_gru_supported(63, 512)
    assert not fused_gru_supported(64, 300)
    assert not fused_gru_supported(256, 2560)   # VMEM blow


@pytest.mark.parametrize("B,T,H", [(8, 12, 128), (16, 7, 128), (8, 3, 256)])
def test_forward_parity(B, T, H):
    x3, Wg, Wc, b, mask = _data(B, T, H)
    want = _scan_ref(x3, Wg, Wc, b, mask)
    got = fused_gru(x3, Wg, Wc, b, mask, None, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_grad_parity():
    B, T, H = 8, 10, 128
    x3, Wg, Wc, b, mask = _data(B, T, H, seed=3)
    cot = jnp.asarray(np.random.RandomState(9).randn(B, T, H), jnp.float32)

    # compare on mask-multiplied outputs both ways (padded steps of the
    # fused path hold carried state, the scan ref ditto — masking makes
    # the comparison exact)
    def loss_ref2(args):
        x3, Wg, Wc, b = args
        return jnp.sum(_scan_ref(x3, Wg, Wc, b, mask)
                       * mask[..., None] * cot)

    def loss_fused2(args):
        x3, Wg, Wc, b = args
        return jnp.sum(fused_gru(x3, Wg, Wc, b, mask, None, True)
                       * mask[..., None] * cot)

    g_ref = jax.grad(loss_ref2)((x3, Wg, Wc, b))
    g_fus = jax.grad(loss_fused2)((x3, Wg, Wc, b))
    for a, bb, name in zip(g_ref, g_fus, ["dx3", "dWg", "dWc", "db"]):
        np.testing.assert_allclose(np.asarray(bb), np.asarray(a),
                                   rtol=3e-4, atol=3e-5, err_msg=name)


def test_layer_path_uses_scan_equivalence():
    """The gated_recurrent layer's scan path == fused kernel, incl.
    reverse, via the public layer API on CPU (kernel in interpret)."""
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.arg import Arg
    from paddle_tpu.core.topology import Topology

    B, T, H = 4, 6, 128
    r = np.random.RandomState(1)
    for reverse in (False, True):
        x = layer.data(name="x",
                       type=data_type.dense_vector_sequence(3 * H))
        g = layer.Layer(type="gated_recurrent", inputs=[x], name="g",
                        reverse=reverse, param_attrs=[layer.ParamAttr(),
                                                      layer.ParamAttr()])
        topo = Topology(g)
        params = topo.init_params(jax.random.PRNGKey(0))
        v = jnp.asarray(r.randn(B, T, 3 * H) * 0.3, jnp.float32)
        mask = np.ones((B, T), np.float32)
        mask[0, 4:] = 0
        outs = topo.forward(params, {"x": Arg(v, jnp.asarray(mask))})
        got = np.asarray(outs["g"].value)

        base = [k for k in params if k.endswith(".w0")][0][:-3]
        Wg, Wc = params[base + ".w0"], params[base + ".w1"]
        b = params.get(base + ".wbias")
        vv, mm = v, jnp.asarray(mask)
        if reverse:
            vv, mm = jnp.flip(vv, 1), jnp.flip(mm, 1)
        want = np.asarray(fused_gru(vv, Wg, Wc,
                                    b if b is not None
                                    else jnp.zeros(3 * H), mm, None, True))
        if reverse:
            want = want[:, ::-1]
        want = want * mask[..., None]
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_batch_sharded_call_matches_single_device():
    """The route a kernel takes inside DataParallelTrainer's GSPMD step
    (kernels/_pallas_util.call_kernel under batch_sharded_kernels: one
    shard_map over the batch, weights whole on every shard): same values
    and same gradients — the weight gradients summed over the shards —
    as the plain call."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.kernels._pallas_util import (batch_sharded_kernels,
                                                 batch_shards, call_kernel)
    from paddle_tpu.parallel import make_mesh

    B, T, H = 32, 6, 128
    x3, Wg, Wc, b, mask = _data(B, T, H, seed=5)
    cot = jnp.asarray(np.random.RandomState(6).randn(B, T, H), jnp.float32)

    def loss(x3, Wg, Wc, b, mask):
        hs = call_kernel(
            lambda x3, Wg, Wc, b, mask: fused_gru(x3, Wg, Wc, b, mask, None,
                                                  True),
            (x3, Wg, Wc, b, mask), batch_argnums=(0, 4))
        return jnp.sum(hs * mask[..., None] * cot)

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))
    want = jax.jit(grad)(x3, Wg, Wc, b, mask)

    mesh = make_mesh(data=4, devices=jax.devices()[:4])
    assert batch_shards() == 1

    def sharded(*args):
        with batch_sharded_kernels(mesh, "data"):
            assert batch_shards() == 4
            return grad(*args)

    batch = NamedSharding(mesh, P("data"))
    got = jax.jit(sharded)(jax.device_put(x3, batch), Wg, Wc, b,
                           jax.device_put(mask, batch))
    assert len(got[1][0].sharding.device_set) == 4
    for a, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)
