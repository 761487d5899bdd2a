"""Registry-sweep gradient checks.

The reference's workhorse test covers ~every registered layer type with
finite differences (paddle/gserver/tests/test_LayerGrad.cpp via
LayerGradUtil.h:299-307 testLayerGrad). This sweep enforces the same
contract structurally: every type in LAYER_REGISTRY must either have a
builder here (-> its parameters AND float inputs are finite-difference
checked in f64) or an entry in SKIP with a stated reason.

A new layer type that is registered without being added to either table
fails `test_registry_fully_covered`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import activation, data_type, layer, pooling
from paddle_tpu.attr import ParamAttr
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.layer import LAYER_REGISTRY, Layer
from paddle_tpu.core.topology import Topology

EPS = 1e-5
RTOL = 2e-2
ATOL = 1e-6
B = 3


@pytest.fixture(autouse=True)
def _f64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


# --- feed helpers ---------------------------------------------------------

def _vec(d, seed=0, b=B):
    return np.random.RandomState(seed).randn(b, d) * 0.5


def _img(c, h, w, seed=0, b=B):
    return np.random.RandomState(seed).randn(b, c * h * w) * 0.5


def _seq(t, d, seed=0, b=B, ragged=True):
    r = np.random.RandomState(seed)
    v = r.randn(b, t, d) * 0.5
    m = np.ones((b, t))
    if ragged and t > 2 and b > 1:
        m[0, -1] = 0
        m[1, -2:] = 0
    return Arg(jnp.asarray(v * m[..., None]), jnp.asarray(m))


def _ids(t, vocab, seed=0, b=B):
    r = np.random.RandomState(seed)
    m = np.ones((b, t))
    if t > 2 and b > 1:
        m[0, -1] = 0
    return Arg(jnp.asarray(r.randint(0, vocab, (b, t)), jnp.int32),
               jnp.asarray(m))


def _lab(classes, seed=1, b=B):
    return np.random.RandomState(seed).randint(
        0, classes, (b, 1)).astype(np.int32)


def _data(name, d, shape=None):
    return layer.data(name=name, type=data_type.dense_vector(d), shape=shape)


def _data_seq(name, d):
    return layer.data(name=name, type=data_type.dense_vector_sequence(d))


def _data_ids(name, vocab):
    return layer.data(name=name, type=data_type.integer_value_sequence(vocab))


# --- the generic FD harness ----------------------------------------------

def sweep_check(out_layer, feeds, rng_needed=False, max_coords=4,
                rtol=RTOL, extra_outputs=(), nondiff_feeds=()):
    """FD-check d(projected scalar)/d(param) for every float parameter and
    d/d(feed) for every float feed value. ``nondiff_feeds`` names float
    feeds that carry discrete control data (slice offsets, selection
    indices) — perturbing those steps the output discontinuously."""
    topo = Topology([out_layer, *extra_outputs])
    params = topo.init_params(jax.random.PRNGKey(0))
    params = {k: v.astype(jnp.float64) if v.dtype == jnp.float32 else v
              for k, v in params.items()}
    static = topo.static_map()
    rng = jax.random.PRNGKey(7) if rng_needed else None

    # split feeds into differentiable float values and fixed structure
    fvals, fixed = {}, {}
    for k, v in feeds.items():
        a = v if isinstance(v, Arg) else Arg(jnp.asarray(v))
        val = jnp.asarray(a.value)
        if jnp.issubdtype(val.dtype, jnp.floating) and k not in nondiff_feeds:
            fvals[k] = val.astype(jnp.float64)
            fixed[k] = (None, a.mask, a.seg_ids)
        else:
            fixed[k] = (val, a.mask, a.seg_ids)

    def assemble(fvals):
        fd = {}
        for k, (val, mask, seg) in fixed.items():
            fd[k] = Arg(fvals[k] if val is None else val, mask, seg)
        return fd

    # one eager forward to size the projection vector
    out0 = topo.forward(params, assemble(fvals), training=True,
                        rng=rng)[out_layer.name]
    proj = jnp.asarray(np.random.RandomState(99).randn(*out0.value.shape))

    def scalar(params, fvals):
        outs = topo.forward(params, assemble(fvals), training=True, rng=rng)
        o = outs[out_layer.name]
        w = proj
        if o.mask is not None and o.value.ndim == 3:
            w = w * o.mask[..., None]
        return jnp.sum(o.value * w)

    scalar_j = jax.jit(scalar)
    g_params, g_feeds = jax.jit(jax.grad(scalar, argnums=(0, 1)))(params, fvals)

    def check(name, base, g, sub):
        flat = np.asarray(base, np.float64).ravel()
        ga = np.asarray(g, np.float64).ravel()
        idxs = np.random.RandomState(5).choice(
            flat.size, size=min(max_coords, flat.size), replace=False)
        for i in idxs:
            pp = flat.copy(); pp[i] += EPS
            pm = flat.copy(); pm[i] -= EPS
            fd = (float(scalar_j(*sub(pp.reshape(base.shape))))
                  - float(scalar_j(*sub(pm.reshape(base.shape))))) / (2 * EPS)
            an = ga[i]
            assert abs(fd - an) <= ATOL + rtol * max(abs(fd), abs(an)), \
                f"{name}[{i}]: analytic {an} vs fd {fd}"

    n_checked = 0
    for name, p in params.items():
        if static.get(name) or not jnp.issubdtype(p.dtype, jnp.floating):
            continue
        check(f"param {name}", p, g_params[name],
              lambda arr, n=name: ({**params, n: jnp.asarray(arr)}, fvals))
        n_checked += 1
    for name, v in fvals.items():
        check(f"feed {name}", v, g_feeds[name],
              lambda arr, n=name: (params, {**fvals, n: jnp.asarray(arr)}))
        n_checked += 1
    assert n_checked > 0, "sweep case checked nothing"


# --- skip list (explicit, with reasons) ----------------------------------

SKIP = {
    "data": "feed pseudo-layer; never computed (topology feeds it)",
    "print": "printer: identity passthrough for logging only",
    "priorbox": "constant output (anchor boxes); no gradient path",
    "maxid": "discrete argmax output; non-differentiable by design",
    "sampling_id": "discrete sampled ids; non-differentiable by design",
    "eos_id": "discrete indicator output; non-differentiable by design",
    "crf_decoding": "discrete viterbi decode; crf cost is checked instead",
    "detection_output": "discrete NMS box selection; multibox_loss is the "
                        "trainable path (itself skipped: box matching is "
                        "piecewise constant)",
    "multibox_loss": "discrete bipartite box matching makes FD ill-posed; "
                     "forward covered in tests/test_detection_evaluators.py",
    "kmax_seq_score": "discrete top-k index output",
    "memory": "recurrent-group plumbing; grads covered end-to-end in "
              "tests/test_recurrent_group.py",
    "step_input": "recurrent-group plumbing (see memory)",
    "get_output": "recurrent-group plumbing (see memory)",
    "beam_search": "generation-only machinery (no training gradient); "
                   "covered in tests/test_recurrent_group.py",
    "recurrent_layer_group": "grad-checked end-to-end in "
                             "tests/test_recurrent_group.py test_*grad*",
    "gru_step": "step layer inside recurrent groups; group grads covered "
                "in tests/test_recurrent_group.py",
    "lstm_step": "step layer inside recurrent groups (see gru_step)",
    "cross_entropy_over_beam": "operates on beam-search path structures; "
                               "covered in tests/test_recurrent_group.py",
    "crf_error": "discrete viterbi decode output (like crf_decoding); "
                 "the crf cost layer's exact DP gradient is checked",
    "lambda_cost": "NDCG pair weights are piecewise-constant in the scores "
                   "(sort-based), so FD at a point is ill-posed; forward "
                   "tested in tests/test_network_compare.py",
    "auc-validation": "constant-zero output by design (reference backward "
                      "is a no-op); metric path covered in "
                      "tests/test_validation_layers.py",
    "pnpair-validation": "constant-zero output by design (see "
                         "auc-validation); tests/test_validation_layers.py",
    "block_diffusion_noise": "discrete output (ids with the mask id put "
                             "in); checked in tests/test_sdar.py",
    "block_diffusion_weights": "piecewise constant in its inputs (1 / t "
                               "where v < t); checked in tests/test_sdar.py",
}


# --- builders: one minimal config per registered type --------------------

def _simple_cls(out):
    lab = layer.data(name="y", type=data_type.integer_value(3))
    return layer.classification_cost(input=out, label=lab, name="cost")


BUILD = {}


def build(name):
    def deco(fn):
        BUILD[name] = fn
        return fn
    return deco


@build("fc")
def _b_fc():
    x = _data("x", 6)
    return (layer.fc(input=x, size=4, act=activation.Tanh()),
            {"x": _vec(6)})


@build("mkldnn_fc")
def _b_mkldnn_fc():
    x = _data("x", 6)
    return (Layer(type="mkldnn_fc", inputs=[x], size=4,
                  act=activation.Tanh(), param_attrs=[ParamAttr()]),
            {"x": _vec(6)})


@build("selective_fc")
def _b_selective_fc():
    x = _data("x", 6)
    sel = layer.data(name="sel", type=data_type.sparse_binary_vector(5, max_ids=2))
    return (layer.selective_fc(input=x, select=sel, size=5,
                               act=activation.Tanh()),
            {"x": _vec(6),
             "sel": Arg(jnp.asarray([[0, 2], [1, 3], [4, 0]], jnp.int32))})


@build("embedding")
def _b_embedding():
    ids = _data_ids("ids", 12)
    return layer.embedding(input=ids, size=5), {"ids": _ids(4, 12)}


@build("agent")
def _b_agent():
    x = _data_seq("x", 4)
    return (Layer(type="agent", inputs=[x]), {"x": _seq(3, 4)})


@build("gather_agent")
def _b_gather_agent():
    a, b = _data_seq("a", 4), _data_seq("b", 4)
    return (Layer(type="gather_agent", inputs=[a, b]),
            {"a": _seq(3, 4), "b": _seq(2, 4, 1)})


@build("scatter_agent")
def _b_scatter_agent():
    x = _data_seq("x", 4)
    return (Layer(type="scatter_agent", inputs=[x]), {"x": _seq(3, 4)})


@build("addto")
def _b_addto():
    a, b = _data("a", 5), _data("b", 5)
    return (layer.addto(input=[a, b], act=activation.Tanh()),
            {"a": _vec(5), "b": _vec(5, 1)})


@build("concat")
def _b_concat():
    a, b = _data("a", 4), _data("b", 3)
    return layer.concat(input=[a, b]), {"a": _vec(4), "b": _vec(3, 1)}


@build("concat2")
def _b_concat2():
    a, b = _data_seq("a", 3), _data_seq("b", 2)
    return (layer.concat2(input=[a, b]) if hasattr(layer, "concat2")
            else Layer(type="concat2", inputs=[a, b]),
            {"a": _seq(4, 3), "b": _seq(4, 2, 1)})


@build("tensor")
def _b_tensor():
    a, b = _data("a", 3), _data("b", 4)
    return (layer.tensor(a=a, b=b, size=2, act=activation.Tanh()),
            {"a": _vec(3), "b": _vec(4, 1)})


@build("mixed")
def _b_mixed():
    a, b = _data("a", 4), _data("b", 5)
    return (layer.mixed(size=6, input=[
        layer.full_matrix_projection(input=a),
        layer.trans_full_matrix_projection(
            input=layer.fc(input=b, size=6, act=activation.Linear())),
    ], act=activation.Tanh()), {"a": _vec(4), "b": _vec(5, 1)})


@build("exconv")
def _b_exconv():
    x = _data("x", 3 * 8 * 8, shape=(3, 8, 8))
    return (layer.img_conv(input=x, filter_size=3, num_filters=4, stride=1,
                           padding=1, act=activation.Tanh()),
            {"x": _img(3, 8, 8)})


@build("cudnn_conv")
def _b_cudnn_conv():
    # stride-2 tiny-C geometry: exercises the space-to-depth rewrite
    x = _data("x", 3 * 8 * 8, shape=(3, 8, 8))
    return (Layer(type="cudnn_conv", inputs=[x], num_filters=4,
                  filter_size=3, stride=2, padding=1, num_channels=3,
                  act=activation.Tanh(), param_attrs=[ParamAttr()]),
            {"x": _img(3, 8, 8)})


@build("mkldnn_conv")
def _b_mkldnn_conv():
    x = _data("x", 2 * 6 * 6, shape=(2, 6, 6))
    return (Layer(type="mkldnn_conv", inputs=[x], num_filters=3,
                  filter_size=3, stride=1, padding=1, num_channels=2,
                  act=activation.Tanh(), param_attrs=[ParamAttr()]),
            {"x": _img(2, 6, 6)})


@build("exconvt")
def _b_exconvt():
    # two stacked deconvs cover both geometries: DCGAN k4/p1/s2
    # (k != 2p+1 — the lax.conv_transpose pad correction) and the
    # k3/p1/s1 identity case (k == 2p+1)
    x = _data("x", 3 * 5 * 5, shape=(3, 5, 5))
    up = layer.img_conv(input=x, filter_size=4, num_filters=2, stride=2,
                        padding=1, act=activation.Tanh(), trans=True)
    return (layer.img_conv(input=up, filter_size=3, num_filters=2, stride=1,
                           padding=1, act=activation.Tanh(), trans=True,
                           num_channels=2),
            {"x": _img(3, 5, 5)})


@build("cudnn_convt")
def _b_cudnn_convt():
    x = _data("x", 2 * 4 * 4, shape=(2, 4, 4))
    return (Layer(type="cudnn_convt", inputs=[x], num_filters=2,
                  filter_size=3, stride=1, padding=1, num_channels=2,
                  transposed=True, act=activation.Tanh(),
                  param_attrs=[ParamAttr()]),
            {"x": _img(2, 4, 4)})


@build("conv3d")
def _b_conv3d():
    x = _data("x", 2 * 4 * 4 * 4)
    return (layer.img_conv3d(input=x, filter_size=3, num_filters=2,
                             stride=1, padding=1, num_channels=2,
                             img_size_z=4, img_size_y=4, img_size=4,
                             act=activation.Tanh()),
            {"x": _img(2, 4, 4 * 4)})


@build("deconv3d")
def _b_deconv3d():
    x = _data("x", 2 * 3 * 3 * 3)
    return (layer.img_conv3d(input=x, filter_size=3, num_filters=2,
                             stride=1, padding=1, num_channels=2,
                             img_size_z=3, img_size_y=3, img_size=3,
                             act=activation.Tanh(), trans=True),
            {"x": _img(2, 3, 3 * 3)})


@build("pool")
def _b_pool():
    x = _data("x", 2 * 6 * 6, shape=(2, 6, 6))
    return (layer.img_pool(input=x, pool_size=2, stride=2,
                           pool_type=pooling.Avg()),
            {"x": _img(2, 6, 6)})


@build("mkldnn_pool")
def _b_mkldnn_pool():
    x = _data("x", 2 * 4 * 4, shape=(2, 4, 4))
    return (Layer(type="mkldnn_pool", inputs=[x], pool_size=2, stride=2,
                  pool_type="avg", num_channels=2),
            {"x": _img(2, 4, 4)})


@build("pool3d")
def _b_pool3d():
    x = _data("x", 2 * 4 * 4 * 4)
    return (layer.img_pool3d(input=x, pool_size=2, stride=2,
                             num_channels=2, img_size_z=4, img_size_y=4,
                             img_size=4, pool_type=pooling.Avg()),
            {"x": _img(2, 4, 4 * 4)})


@build("spp")
def _b_spp():
    x = _data("x", 2 * 6 * 6, shape=(2, 6, 6))
    return (layer.spp(input=x, num_channels=2, pyramid_height=2,
                      img_size=6, img_size_y=6, pool_type=pooling.Avg()),
            {"x": _img(2, 6, 6)})


@build("maxout")
def _b_maxout():
    x = _data("x", 4 * 4 * 4, shape=(4, 4, 4))
    return (layer.maxout(input=x, groups=2, num_channels=4),
            {"x": _img(4, 4, 4)})


@build("blockexpand")
def _b_blockexpand():
    x = _data("x", 2 * 4 * 4, shape=(2, 4, 4))
    return (layer.block_expand(input=x, num_channels=2, block_x=2, block_y=2,
                               stride_x=2, stride_y=2, img_size_y=4,
                               img_size_x=4),
            {"x": _img(2, 4, 4)})


@build("conv_shift")
def _b_conv_shift():
    a, b = _data("a", 6), _data("b", 3)
    return layer.conv_shift(a=a, b=b), {"a": _vec(6), "b": _vec(3, 1)}


@build("row_conv")
def _b_row_conv():
    x = _data_seq("x", 4)
    return layer.row_conv(input=x, context_len=2), {"x": _seq(5, 4)}


@build("batch_norm")
def _b_batch_norm():
    x = _data("x", 6)
    return (layer.batch_norm(input=x, act=activation.Tanh()),
            {"x": _vec(6, b=6)})


@build("cudnn_batch_norm")
def _b_cudnn_batch_norm():
    x = _data("x", 6)
    return (Layer(type="cudnn_batch_norm", inputs=[x],
                  act=activation.Tanh(), param_attrs=[ParamAttr()]),
            {"x": _vec(6, b=6)})


@build("mkldnn_batch_norm")
def _b_mkldnn_batch_norm():
    x = _data("x", 6)
    return (Layer(type="mkldnn_batch_norm", inputs=[x],
                  act=activation.Tanh(), param_attrs=[ParamAttr()]),
            {"x": _vec(6, b=6)})


@build("data_norm")
def _b_data_norm():
    x = _data("x", 5)
    return layer.data_norm(input=x), {"x": _vec(5)}


@build("norm")
def _b_norm():
    x = _data("x", 3 * 4 * 4, shape=(3, 4, 4))
    return (layer.img_cmrnorm(input=x, size=3, num_channels=3),
            {"x": _img(3, 4, 4)})


@build("cross-channel-norm")
def _b_ccn():
    x = _data("x", 3 * 4 * 4, shape=(3, 4, 4))
    return (layer.cross_channel_norm(input=x, num_channels=3),
            {"x": _img(3, 4, 4)})


@build("sum_to_one_norm")
def _b_sum_to_one():
    x = _data("x", 5)
    return (layer.sum_to_one_norm(input=x),
            {"x": np.abs(_vec(5)) + 0.5})


@build("row_l2_norm")
def _b_row_l2():
    x = _data("x", 5)
    return layer.row_l2_norm(input=x), {"x": _vec(5) + 0.1}


@build("lstmemory")
def _b_lstm():
    x = _data_seq("x", 3)
    proj = layer.fc(input=x, size=4 * 4, act=activation.Linear())
    return layer.lstmemory(input=proj), {"x": _seq(4, 3)}


@build("gated_recurrent")
def _b_gru():
    x = _data_seq("x", 3)
    proj = layer.fc(input=x, size=3 * 4, act=activation.Linear())
    return layer.grumemory(input=proj), {"x": _seq(4, 3)}


@build("recurrent")
def _b_recurrent():
    x = _data_seq("x", 4)
    return layer.recurrent(input=x, act=activation.Tanh()), {"x": _seq(4, 4)}


@build("mdlstmemory")
def _b_mdlstm():
    x = _data_seq("x", 10)
    return (Layer(type="mdlstmemory", inputs=[x],
                  param_attrs=[ParamAttr()]),
            {"x": _seq(4, 10)})


@build("expand")
def _b_expand():
    v = _data("v", 4)
    tmpl = _data_seq("t", 2)
    return (layer.expand(input=v, expand_as=tmpl),
            {"v": _vec(4), "t": _seq(3, 2)})


@build("featmap_expand")
def _b_featmap_expand():
    x = _data_seq("x", 3)
    return (Layer(type="featmap_expand", inputs=[x], num_filters=2),
            {"x": _seq(3, 3)})


@build("average")
def _b_avg_pool():
    x = _data_seq("x", 4)
    return (layer.pooling(input=x, pooling_type=pooling.Avg()),
            {"x": _seq(4, 4)})


@build("max")
def _b_max_pool():
    x = _data_seq("x", 4)
    return (layer.pooling(input=x, pooling_type=pooling.Max()),
            {"x": _seq(4, 4)})


@build("seqlastins")
def _b_last_seq():
    x = _data_seq("x", 4)
    return layer.last_seq(input=x), {"x": _seq(4, 4)}


@build("seqconcat")
def _b_seqconcat():
    a, b = _data_seq("a", 3), _data_seq("b", 3)
    return layer.seq_concat(a=a, b=b), {"a": _seq(3, 3), "b": _seq(2, 3, 1)}


@build("seqreshape")
def _b_seqreshape():
    x = _data_seq("x", 4)
    return (layer.seq_reshape(input=x, reshape_size=2),
            {"x": _seq(4, 4, ragged=False)})


@build("seq_slice")
def _b_seq_slice():
    x = _data_seq("x", 3)
    starts = layer.data(name="st", type=data_type.dense_vector(1))
    return (layer.seq_slice(input=x, starts=starts),
            {"x": _seq(5, 3),
             "st": Arg(jnp.asarray([[1.0], [0.0], [2.0]]))},
            {"nondiff_feeds": ("st",)})


@build("subseq")
def _b_subseq():
    x = _data_seq("x", 3)
    off = layer.data(name="off", type=data_type.dense_vector(1))
    sz = layer.data(name="sz", type=data_type.dense_vector(1))
    return (layer.sub_seq(input=x, offsets=off, sizes=sz),
            {"x": _seq(5, 3),
             "off": Arg(jnp.asarray([[1.0], [0.0], [2.0]])),
             "sz": Arg(jnp.asarray([[2.0], [3.0], [2.0]]))},
            {"nondiff_feeds": ("off", "sz")})


@build("sub_nested_seq")
def _b_sub_nested():
    x = layer.data(name="x",
                   type=data_type.dense_vector_sub_sequence(3))
    sel = layer.data(name="sel", type=data_type.dense_vector(2))
    r = np.random.RandomState(0)
    v = r.randn(B, 6, 3) * 0.5
    mask = np.ones((B, 6))
    seg = np.tile(np.array([0, 0, 1, 1, 2, 2]), (B, 1))
    return (layer.sub_nested_seq(input=x, selected_indices=sel),
            {"x": Arg(jnp.asarray(v), jnp.asarray(mask),
                      jnp.asarray(seg, jnp.int32)),
             "sel": Arg(jnp.asarray([[0.0, 1.0], [1.0, 2.0], [0.0, 2.0]]))},
            {"nondiff_feeds": ("sel",)})


@build("interpolation")
def _b_interpolation():
    w = _data("w", 1)
    a, b = _data("a", 4), _data("b", 4)
    return (layer.interpolation(input=[a, b], weight=w),
            {"w": np.random.RandomState(3).rand(B, 1) * 0.8 + 0.1,
             "a": _vec(4), "b": _vec(4, 1)})


@build("power")
def _b_power():
    w = _data("w", 1)
    x = _data("x", 4)
    return (layer.power(input=x, weight=w),
            {"w": np.random.RandomState(3).rand(B, 1) + 0.5,
             "x": np.abs(_vec(4)) + 0.5})


@build("scaling")
def _b_scaling():
    w = _data("w", 1)
    x = _data("x", 4)
    return (layer.scaling(input=x, weight=w),
            {"w": _vec(1, 3), "x": _vec(4)})


@build("slope_intercept")
def _b_slope_intercept():
    x = _data("x", 4)
    return (layer.slope_intercept(input=x, slope=1.7, intercept=0.3),
            {"x": _vec(4)})


@build("scale_shift")
def _b_scale_shift():
    x = _data("x", 4)
    return layer.scale_shift(input=x), {"x": _vec(4)}


@build("clip")
def _b_clip():
    x = _data("x", 4)
    return (layer.clip(input=x, min=-5.0, max=5.0), {"x": _vec(4)})


@build("prelu")
def _b_prelu():
    x = _data("x", 4)
    return layer.prelu(input=x), {"x": _vec(4) + 0.3}


@build("multiplex")
def _b_multiplex():
    idx = layer.data(name="idx", type=data_type.integer_value(2))
    a, b = _data("a", 4), _data("b", 4)
    return (layer.multiplex(input=[idx, a, b]),
            {"idx": _lab(2), "a": _vec(4), "b": _vec(4, 1)})


@build("convex_comb")
def _b_convex_comb():
    w = _data("w", 2)
    x = _data("x", 8)
    return (layer.convex_comb(input=x, weights=w, size=4),
            {"w": np.random.RandomState(3).rand(B, 2), "x": _vec(8)})


@build("out_prod")
def _b_out_prod():
    a, b = _data("a", 3), _data("b", 4)
    return layer.out_prod(a=a, b=b), {"a": _vec(3), "b": _vec(4, 1)}


@build("cos")
def _b_cos():
    a, b = _data("a", 4), _data("b", 4)
    return layer.cos_sim(a=a, b=b), {"a": _vec(4), "b": _vec(4, 1)}


@build("cos_vm")
def _b_cos_vm():
    a = _data("a", 4)
    b = _data("b", 8)
    return (layer.cos_sim_vm(vec=a, mat=b),
            {"a": _vec(4), "b": _vec(8, 1)})


@build("trans")
def _b_trans():
    x = _data("x", 9)   # [B=3, 9]... trans operates on the batch matrix
    return layer.trans(input=x), {"x": _vec(9, b=9)}


@build("rotate")
def _b_rotate():
    x = _data("x", 3 * 4)
    return (layer.rotate(input=x, height=3, width=4),
            {"x": _img(1, 3, 4)})


@build("resize")
def _b_resize():
    x = _data("x", 6)
    return layer.resize(input=x, size=9), {"x": _vec(6, b=6)}


@build("switch_order")
def _b_switch_order():
    x = _data("x", 2 * 3 * 4, shape=(2, 3, 4))
    return (layer.switch_order(input=x, reshape_axis=2),
            {"x": _img(2, 3, 4)})


@build("crop")
def _b_crop():
    x = _data("x", 3 * 5 * 5, shape=(3, 5, 5))
    return (layer.crop(input=x, shape_in=(3, 5, 5), shape_out=(3, 3, 3),
                       offset=(0, 1, 1)),
            {"x": _img(3, 5, 5)})


@build("pad")
def _b_pad():
    x = _data("x", 2 * 3 * 3, shape=(2, 3, 3))
    return (layer.pad(input=x, pad_c=(1, 1), pad_h=(0, 1), pad_w=(1, 0),
                      shape_in=(2, 3, 3)),
            {"x": _img(2, 3, 3)})


@build("bilinear_interp")
def _b_bilinear():
    x = _data("x", 2 * 4 * 4, shape=(2, 4, 4))
    return (layer.bilinear_interp(input=x, out_size_x=6, out_size_y=6,
                                  num_channels=2, in_size_x=4, in_size_y=4),
            {"x": _img(2, 4, 4)})


@build("hsigmoid")
def _b_hsigmoid():
    x = _data("x", 5)
    lab = layer.data(name="y", type=data_type.integer_value(6))
    return (layer.hsigmoid(input=x, label=lab, num_classes=6),
            {"x": _vec(5), "y": _lab(6)})


@build("nce")
def _b_nce():
    x = _data("x", 5)
    lab = layer.data(name="y", type=data_type.integer_value(8))
    return (layer.nce(input=x, label=lab, num_classes=8, num_neg_samples=3),
            {"x": _vec(5), "y": _lab(8)}, {"rng_needed": True})


@build("multi_head_attention")
def _b_mha():
    q = _data_seq("q", 8)
    return (layer.multi_head_attention(query=q, size=8, num_heads=2),
            {"q": _seq(4, 8)})


@build("rms_norm")
def _b_rms_norm():
    x = _data_seq("x", 6)
    return layer.rms_norm(input=x), {"x": _seq(4, 6)}


@build("gated_attention")
def _b_gated_attention():
    x = _data_seq("x", 6)
    return (layer.gated_attention(input=x, num_heads=4, num_kv_heads=2,
                                  head_dim=4, rotary_dim=2),
            {"x": _seq(5, 6, ragged=False)})


@build("gqa_attention")
def _b_gqa_attention():
    # 3 tokens in blocks of 2 (a short last block), noised and clean halves
    x = _data_seq("x", 6)
    return (layer.gqa_attention(input=x, num_heads=4, num_kv_heads=2,
                                head_dim=4, mask=("block_diffusion", 3, 2)),
            {"x": _seq(6, 6, ragged=False)})


@build("mla_attention")
def _b_mla_attention():
    # query/key heads of 4 + 2 beside value heads of 3, a latent of 5
    x = _data_seq("x", 6)
    return (layer.mla_attention(input=x, num_heads=2, qk_nope_head_dim=4,
                                qk_rope_head_dim=2, v_head_dim=3,
                                kv_lora_rank=5, mask=("causal", None)),
            {"x": _seq(5, 6, ragged=False)})


@build("short_conv")
def _b_short_conv():
    # 3 taps over 5 positions: the first two see zeros before the row
    x = _data_seq("x", 6)
    return (layer.short_conv(input=x, conv_kernel=3),
            {"x": _seq(5, 6, ragged=False)})


@build("gated_mlp")
def _b_gated_mlp():
    x = _data_seq("x", 6)
    return layer.gated_mlp(input=x, size=5), {"x": _seq(4, 6)}


@build("noised_half")
def _b_noised_half():
    x = _data_seq("x", 6)
    return layer.noised_half(x), {"x": _seq(6, 6)}


@build("gated_delta_net")
def _b_gated_delta_net():
    x = _data_seq("x", 6)
    return (layer.gated_delta_net(input=x, num_k_heads=2, num_v_heads=4,
                                  head_k_dim=4, head_v_dim=3, chunk=4),
            {"x": _seq(6, 6, ragged=False)})


@build("moe_ffn")
def _b_moe_ffn():
    # 2 of the 6 experts are held: the rest of a token's top 3 is left out
    x = _data_seq("x", 6)
    return (layer.moe_ffn(input=x, num_experts=6, top_k=3, expert_size=5,
                          shared_size=4, experts_held=2, first_expert=1,
                          tile=4),
            {"x": _seq(5, 6)})


@build("crf")
def _b_crf():
    x = _data_seq("x", 3)
    lab = _data_ids("y", 3)
    emit = layer.fc(input=x, size=3, act=activation.Linear())
    return (layer.crf(input=emit, label=lab, size=3),
            {"x": _seq(4, 3), "y": _ids(4, 3, 2)})


@build("ctc")
def _b_ctc():
    x = _data_seq("x", 5)
    lab = _data_ids("y", 4)
    emit = layer.fc(input=x, size=5, act=activation.Linear())
    return (layer.ctc(input=emit, label=lab, size=5),
            {"x": _seq(6, 5), "y": Arg(jnp.asarray([[1, 2], [3, 1], [2, 2]],
                                                   jnp.int32),
                                       jnp.ones((3, 2)))})


@build("warp_ctc")
def _b_warp_ctc():
    x = _data_seq("x", 5)
    lab = _data_ids("y", 4)
    emit = layer.fc(input=x, size=5, act=activation.Linear())
    return (layer.warp_ctc(input=emit, label=lab, size=5),
            {"x": _seq(6, 5), "y": Arg(jnp.asarray([[1, 2], [3, 1], [2, 2]],
                                                   jnp.int32),
                                       jnp.ones((3, 2)))})


# --- cost layers ----------------------------------------------------------

@build("multi-class-cross-entropy")
def _b_xent():
    x = _data("x", 4)
    out = layer.fc(input=x, size=3, act=activation.Softmax())
    return _simple_cls(out), {"x": _vec(4), "y": _lab(3)}


@build("softmax_with_cross_entropy")
def _b_fused_xent():
    x = _data("x", 4)
    out = layer.fc(input=x, size=3, act=activation.Linear())
    lab = layer.data(name="y", type=data_type.integer_value(3))
    return (Layer(type="softmax_with_cross_entropy", inputs=[out, lab]),
            {"x": _vec(4), "y": _lab(3)})


@build("multi_class_cross_entropy_with_selfnorm")
def _b_selfnorm():
    x = _data("x", 4)
    out = layer.fc(input=x, size=3, act=activation.Softmax())
    lab = layer.data(name="y", type=data_type.integer_value(3))
    return (layer.cross_entropy_with_selfnorm_cost(input=out, label=lab),
            {"x": _vec(4), "y": _lab(3)})


@build("soft_binary_class_cross_entropy")
def _b_soft_bce():
    x = _data("x", 4)
    out = layer.fc(input=x, size=3, act=activation.Sigmoid())
    t = _data("t", 3)
    return (layer.soft_binary_class_cross_entropy_cost(input=out, label=t),
            {"x": _vec(4), "t": np.random.RandomState(2).rand(B, 3)})


@build("multi_binary_label_cross_entropy")
def _b_multi_bce():
    x = _data("x", 4)
    out = layer.fc(input=x, size=5, act=activation.Sigmoid())
    lab = layer.data(name="y",
                     type=data_type.sparse_binary_vector(5, max_ids=2))
    return (layer.multi_binary_label_cross_entropy_cost(input=out, label=lab),
            {"x": _vec(4),
             "y": Arg(jnp.asarray([[0, 2], [1, -1], [3, 4]], jnp.int32))})


@build("square_error")
def _b_mse():
    x = _data("x", 4)
    out = layer.fc(input=x, size=3, act=activation.Linear())
    t = _data("t", 3)
    return (layer.square_error_cost(input=out, label=t),
            {"x": _vec(4), "t": _vec(3, 2)})


@build("smooth_l1")
def _b_smooth_l1():
    x = _data("x", 4)
    out = layer.fc(input=x, size=3, act=activation.Linear())
    t = _data("t", 3)
    # keep |diff| away from the |d|=1 kink for well-posed FD
    return (layer.smooth_l1_cost(input=out, label=t),
            {"x": _vec(4) * 0.1, "t": _vec(3, 2) * 0.1})


@build("huber_regression")
def _b_huber_reg():
    x = _data("x", 4)
    out = layer.fc(input=x, size=3, act=activation.Linear())
    t = _data("t", 3)
    return (layer.huber_regression_cost(input=out, label=t),
            {"x": _vec(4) * 0.1, "t": _vec(3, 2) * 0.1})


@build("huber_classification")
def _b_huber_cls():
    x = _data("x", 4)
    out = layer.fc(input=x, size=1, act=activation.Linear())
    lab = layer.data(name="y", type=data_type.integer_value(2))
    return (layer.huber_classification_cost(input=out, label=lab),
            {"x": _vec(4) * 0.3, "y": _lab(2)})


@build("rank-cost")
def _b_rank():
    a, b = _data("a", 4), _data("b", 4)
    left = layer.fc(input=a, size=1, act=activation.Linear())
    right = layer.fc(input=b, size=1, act=activation.Linear())
    lab = _data("t", 1)
    return (layer.rank_cost(left=left, right=right, label=lab),
            {"a": _vec(4), "b": _vec(4, 1),
             "t": np.random.RandomState(2).rand(B, 1)})


@build("sum_cost")
def _b_sum_cost():
    x = _data("x", 4)
    out = layer.fc(input=x, size=3, act=activation.Tanh())
    return layer.sum_cost(input=out), {"x": _vec(4)}


# --- the sweep ------------------------------------------------------------

ALL_TYPES = sorted(LAYER_REGISTRY.keys()
                   if hasattr(LAYER_REGISTRY, "keys")
                   else LAYER_REGISTRY.names())


def test_registry_fully_covered():
    missing = [t for t in ALL_TYPES if t not in BUILD and t not in SKIP]
    assert not missing, \
        f"registered layer types with neither a gradcheck builder nor a " \
        f"skip reason: {missing}"
    stale = [t for t in list(BUILD) + list(SKIP) if t not in ALL_TYPES]
    assert not stale, f"builders/skips for unregistered types: {stale}"


@pytest.mark.parametrize("ltype", [t for t in ALL_TYPES if t in BUILD])
def test_layer_grad(ltype):
    built = BUILD[ltype]()
    out, feeds = built[0], built[1]
    kwargs = built[2] if len(built) > 2 else {}
    sweep_check(out, feeds, **kwargs)


def test_deconv_autoencoder_geometry_and_cost_boundary():
    """k4/p1/s2 deconv (k != 2p+1: the lax.conv_transpose pad correction)
    reconstructs the input geometry, and a carried-NHWC conv output feeds
    a cost layer directly (flattened at the boundary)."""
    from paddle_tpu import activation

    img = _data("x", 1 * 8 * 8, shape=(1, 8, 8))
    enc = layer.img_conv(input=img, filter_size=4, num_filters=4, stride=2,
                         padding=1, act=activation.Relu())
    dec = layer.img_conv(input=enc, filter_size=4, num_filters=1, stride=2,
                         padding=1, act=activation.Linear(), trans=True,
                         num_channels=4, name="dec_ae")
    tgt = _data("t", 64)
    cost = layer.square_error_cost(input=dec, label=tgt)
    topo = Topology(cost)
    assert topo.info("dec_ae").shape == (1, 8, 8)
    p = topo.init_params(jax.random.PRNGKey(0))
    x = _vec(64, b=4)
    x32 = x.astype(np.float32)
    out = topo.forward(p, {"x": x32, "t": x32})[cost.name].value
    assert out.shape == (4, 1) and np.isfinite(np.asarray(out)).all()
    sweep_check(cost, {"x": x, "t": _vec(64, 1, b=4)})
