"""Flagship smoke tests: the driver entry points must trace and run.

Round-1 regression (VERDICT r1 #1-#3): entry() and the dry run crashed at
trace time because img_pool silently dropped ceil_mode and models
hand-threaded shapes. These tests pin the fix.
"""

import jax
import numpy as np
import pytest


def test_pool_ceil_vs_floor_shapes():
    from paddle_tpu import layer, pooling, data_type

    img = layer.data(name="img", type=data_type.dense_vector(64 * 112 * 112),
                     shape=(64, 112, 112))
    ceil = layer.img_pool(input=img, pool_size=3, stride=2, padding=1,
                          pool_type=pooling.Max(), ceil_mode=True)
    floor = layer.img_pool(input=img, pool_size=3, stride=2, padding=1,
                           pool_type=pooling.Max(), ceil_mode=False)
    assert ceil.out_info().shape == (64, 57, 57)
    assert floor.out_info().shape == (64, 56, 56)


@pytest.mark.parametrize("ceil_mode", [True, False])
def test_pool_forward_shape_matches_infer(ceil_mode):
    from paddle_tpu import layer, pooling, data_type
    from paddle_tpu.core.topology import Topology

    img = layer.data(name="img", type=data_type.dense_vector(4 * 11 * 11),
                     shape=(4, 11, 11))
    p = layer.img_pool(input=img, pool_size=3, stride=2, padding=1,
                       pool_type=pooling.Max(), ceil_mode=ceil_mode)
    topo = Topology(p)
    x = np.random.RandomState(0).rand(2, 4 * 11 * 11).astype(np.float32)
    out = topo.forward({}, {"img": x})[p.name].value
    # image layers carry 4D NHWC internally; info.shape stays logical
    # (C, H, W)
    c, oh, ow = topo.info(p).shape
    assert out.shape[1:] == (oh, ow, c)
    assert int(np.prod(out.shape[1:])) == topo.info(p).size


def test_resnet50_infer_shapes():
    """ResNet-50 graph builds and inference agrees at every stage."""
    from paddle_tpu.models.resnet import resnet_cost
    from paddle_tpu.core.topology import Topology

    img, lab, out, cost = resnet_cost(depth=50, img_size=224)
    topo = Topology(cost)
    assert topo.info(out).size == 1000
    # standard ResNet-50 stage sizes (floor-mode pool1)
    assert topo.info(topo.layer_map["res_pool1"]).shape == (64, 56, 56)
    assert topo.info(topo.layer_map["res4_0_sum"]).shape[0] == 1024
    assert topo.info(topo.layer_map["res_avgpool"]).shape == (2048, 1, 1)


def test_graft_entry_traces():
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.eval_shape(fn, *args)
    assert out.shape == (4, 100)


def test_dryrun_multichip_in_process():
    import __graft_entry__ as g

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh from conftest")
    g._dryrun_multichip_impl(8)


def _bf16_train_step(topo, cost, opt):
    """The step SGD(mixed_precision=True) jits: bf16 compute, f32 masters."""
    import jax.numpy as jnp
    from paddle_tpu.trainer.trainer import make_train_step

    return make_train_step(topo.loss_fn(cost, compute_dtype=jnp.bfloat16),
                           opt, topo.static_map())


def test_smallnet_train_step_runs():
    """The mixed-precision train step of the small image model runs end
    to end (VERDICT r1 #1)."""
    from paddle_tpu import optimizer
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.models.image_bench import smallnet_mnist_cifar
    import jax.numpy as jnp

    img, lab, out, cost = smallnet_mnist_cifar()
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = optimizer.Momentum(learning_rate=0.01, momentum=0.9)
    opt_state = opt.init(params)
    step = _bf16_train_step(topo, cost, opt)
    r = np.random.RandomState(0)
    feeds = {"image": jnp.asarray(r.rand(8, 3 * 32 * 32), jnp.float32),
             "label": jnp.asarray(r.randint(0, 10, (8, 1)), jnp.int32)}
    p2, o2, c, _metrics = step(params, opt_state, jax.random.PRNGKey(1),
                               feeds)
    assert np.isfinite(float(c))


def test_batch_norm_after_conv_without_num_channels():
    """Per-channel BN params inferred from the conv output shape (r2
    regression: 4D carry broke the channel fallback)."""
    from paddle_tpu import layer, data_type, activation
    from paddle_tpu.core.topology import Topology

    img = layer.data(name="im", type=data_type.dense_vector(3 * 16 * 16),
                     shape=(3, 16, 16))
    c = layer.img_conv(input=img, filter_size=3, num_filters=8, padding=1,
                       act=activation.Linear(), bias_attr=False)
    bn = layer.batch_norm(input=c, act=activation.Relu())
    topo = Topology(bn)
    params = topo.init_params(jax.random.PRNGKey(0))
    pname = [p for p in params if p.endswith(".w0") and "batch_norm" in p]
    assert params[pname[0]].shape == (8,), params[pname[0]].shape
    x = np.random.RandomState(0).rand(2, 3 * 16 * 16).astype(np.float32)
    out = topo.forward(params, {"im": x}, training=True)[bn.name].value
    assert out.shape == (2, 16, 16, 8)  # carried NHWC


def test_nhwc_carry_matches_nchw_reference():
    """The carried-NHWC image pipeline must be numerically identical to a
    direct NCHW computation with the same OIHW weights (layout refactor
    guard): conv(+bias) -> max pool -> fc over CHW-flat."""
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu import layer, data_type, activation, pooling
    from paddle_tpu.core.topology import Topology

    c_in, h_in, nf = 3, 8, 4
    img = layer.data(name="im2",
                     type=data_type.dense_vector(c_in * h_in * h_in),
                     shape=(c_in, h_in, h_in))
    cv = layer.img_conv(input=img, filter_size=3, num_filters=nf, padding=1,
                        act=activation.Linear())
    pl = layer.img_pool(input=cv, pool_size=2, stride=2,
                        pool_type=pooling.Max(), ceil_mode=False)
    fc = layer.fc(input=pl, size=5, act=activation.Linear(), name="fc",
                  bias_attr=False)
    topo = Topology(fc)
    params = topo.init_params(jax.random.PRNGKey(4))
    x = np.random.RandomState(1).rand(2, c_in * h_in * h_in) \
        .astype(np.float32)
    got = np.asarray(topo.forward(params, {"im2": x})["fc"].value)

    wname = [k for k in params if k.endswith(".w0") and "conv" in k][0]
    bname = [k for k in params if k.endswith(".wbias") and "conv" in k][0]
    fcw = params[[k for k in params if k.startswith("_fc")][0]]
    v = jnp.asarray(x).reshape(2, c_in, h_in, h_in)
    ref = lax.conv_general_dilated(
        v, params[wname], (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    ref = ref + params[bname][None, :, None, None]
    ref = lax.reduce_window(ref, -jnp.inf, lax.max, (1, 1, 2, 2),
                            (1, 1, 2, 2), ((0, 0),) * 4)
    ref = ref.reshape(2, -1) @ fcw           # CHW-flat fc contract
    np.testing.assert_allclose(got, np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("model,size", [("alexnet", 227),
                                        ("googlenet", 224), ("vgg", 224)])
def test_benchmark_model_suite_traces(model, size):
    """Every reference benchmark model builds and its train step traces
    (benchmark/paddle/image + rnn parity: alexnet/googlenet/vgg)."""
    import jax.numpy as jnp
    from paddle_tpu import optimizer
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.models import image_bench

    img, lab, out, cost = getattr(image_bench, model)()
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = optimizer.Momentum(learning_rate=0.01)
    step = _bf16_train_step(topo, cost, opt)
    feeds = {"image": jnp.zeros((2, 3 * size * size), jnp.float32),
             "label": jnp.zeros((2, 1), jnp.int32)}
    shapes = jax.eval_shape(step, params, opt.init(params),
                            jax.random.PRNGKey(0), feeds)
    assert shapes[2].shape == ()  # scalar cost
