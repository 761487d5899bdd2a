"""Public layer API.

Analog of paddle.v2.layer (python/paddle/v2/layer.py auto-wrapping the v1
DSL python/paddle/trainer_config_helpers/layers.py ~100 wrappers). Each
function builds a graph node (paddle_tpu.core.layer.Layer); nothing
executes until a Topology compiles the graph into a jitted XLA program.

Projections for ``mixed`` return spec dicts, mirroring
full_matrix_projection / table_projection / ... (config_parser.py:488-764).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Union

import paddle_tpu.layers  # noqa: F401  (registers every layer type)
from paddle_tpu import activation as _act
from paddle_tpu.attr import ExtraAttr, ParamAttr, to_param_attr
from paddle_tpu.core.layer import Layer
from paddle_tpu import pooling as _pooling

__all__ = [
    "data", "fc", "embedding", "concat", "addto", "mixed", "dropout",
    "classification_cost", "cross_entropy_cost", "cross_entropy_with_selfnorm_cost",
    "square_error_cost", "regression_cost", "smooth_l1_cost", "huber_regression_cost",
    "huber_classification_cost", "rank_cost", "lambda_cost", "sum_cost",
    "multi_binary_label_cross_entropy_cost", "soft_binary_class_cross_entropy_cost",
    "cross_entropy_over_beam",
    "img_conv", "img_pool", "img_conv3d", "img_pool3d", "spp", "maxout",
    "block_expand", "conv_shift", "row_conv", "bilinear_interp", "pad", "crop",
    "batch_norm", "data_norm", "img_cmrnorm", "cross_channel_norm",
    "sum_to_one_norm", "row_l2_norm",
    "lstmemory", "grumemory", "recurrent", "lstm_step", "gru_step",
    "pooling", "last_seq", "first_seq", "expand", "seq_concat", "seq_reshape",
    "seq_slice", "sub_seq", "sub_nested_seq", "kmax_seq_score", "eos",
    "get_output", "max_id", "sampling_id", "multiplex",
    "slope_intercept", "scaling", "interpolation", "power", "cos_sim",
    "cos_sim_vm", "out_prod", "trans", "rotate", "resize", "clip",
    "tensor", "convex_comb", "scale_shift", "prelu",
    "hsigmoid", "nce", "selective_fc", "print_layer",
    "switch_order", "concat2",
    "full_matrix_projection", "trans_full_matrix_projection",
    "identity_projection", "dotmul_projection", "scaling_projection",
    "table_projection", "context_projection", "slice_projection",
    "dotmul_operator", "conv_operator",
    "AggregateLevel", "ExpandLevel",
]


def _as_list(x) -> List[Layer]:
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


class AggregateLevel:
    TO_NO_SEQUENCE = "to_no_sequence"
    TO_SEQUENCE = "to_sequence"
    EACH_TIMESTEP = "to_no_sequence"   # legacy alias
    EACH_SEQUENCE = "to_sequence"


class ExpandLevel:
    FROM_NO_SEQUENCE = "from_no_sequence"
    FROM_SEQUENCE = "from_sequence"


# --- inputs ---------------------------------------------------------------

def data(name: str, type=None, shape=None, **kw):
    """paddle.v2.layer.data analog; ``type`` is a paddle_tpu.data_type."""
    return Layer("data", [], name=name, size=getattr(type, "dim", None),
                 input_type=type, shape=shape, **kw)


# --- core -----------------------------------------------------------------

def fc(input, size, act=None, name=None, param_attr=None, bias_attr=None,
       layer_attr=None):
    ins = _as_list(input)
    pattrs = param_attr if isinstance(param_attr, (list, tuple)) else \
        [param_attr] * len(ins)
    return Layer("fc", ins, name=name, size=size,
                 act=act or _act.Tanh(),
                 param_attrs=[to_param_attr(a) for a in pattrs],
                 bias_attr=bias_attr, extra=layer_attr)


def embedding(input, size, name=None, param_attr=None, layer_attr=None):
    return Layer("embedding", _as_list(input), name=name, size=size,
                 param_attrs=[to_param_attr(param_attr)], extra=layer_attr)


def concat(input, name=None, act=None, layer_attr=None, bias_attr=None):
    return Layer("concat", _as_list(input), name=name, act=act,
                 bias_attr=bias_attr, extra=layer_attr)


def addto(input, name=None, act=None, bias_attr=False, layer_attr=None):
    return Layer("addto", _as_list(input), name=name, act=act,
                 bias_attr=bias_attr, extra=layer_attr)


def dropout(input, dropout_rate, name=None):
    return Layer("addto", _as_list(input), name=name, bias_attr=False,
                 extra=ExtraAttr(drop_rate=dropout_rate))


class MixedLayerBuilder:
    """`with mixed_layer() as m: m += proj` context-manager form (the v1
    DSL MixedLayerType, trainer_config_helpers/layers.py mixed_layer).
    After the with-block the builder delegates every attribute to the
    built Layer, so it drops into downstream graph construction
    (`mu + sigma`, inputs of other layers) like a Layer."""

    def __init__(self, **kw):
        self._kw = kw
        self._projs = []
        self._layer = None

    def __enter__(self):
        return self

    def __iadd__(self, proj):
        self._projs.append(proj)
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            self._layer = mixed(input=self._projs, **self._kw)
        return False

    def _built(self):
        lay = object.__getattribute__(self, "_layer")
        if lay is None:
            raise TypeError(
                "mixed_layer builder is not usable yet: the layer exists "
                "only after the with-block closes")
        return lay

    def __getattr__(self, k):
        lay = object.__getattribute__(self, "_layer")
        if lay is None:
            raise AttributeError(
                f"mixed_layer builder has no {k!r}: the layer exists only "
                "after the with-block closes")
        return getattr(lay, k)

    # implicit special-method lookup bypasses __getattr__, so the
    # arithmetic core.Layer supports must be spelled out here
    def __add__(self, other):
        return self._built() + other

    def __radd__(self, other):
        return self._built() + other

    def __sub__(self, other):
        return self._built() - other

    def __rsub__(self, other):
        return self._built().__rsub__(other)

    def __mul__(self, other):
        return self._built() * other

    __rmul__ = __mul__

    def __neg__(self):
        return -self._built()


def mixed(size=None, input=None, name=None, act=None, bias_attr=False,
          layer_attr=None):
    """mixed_layer: sums applied projections and operators. ``input`` is a
    list of specs from *_projection() / *_operator(). Operators (dotmul_op,
    conv_op) consume two graph inputs each; projections consume one.
    With ``input=None`` returns the context-manager builder form
    (``with mixed_layer() as m: m += projection``)."""
    if input is None:
        return MixedLayerBuilder(size=size, name=name, act=act,
                                 bias_attr=bias_attr, layer_attr=layer_attr)
    projs = _as_list(input)
    ins, specs = [], []
    for p in projs:
        q = dict(p)
        if q["kind"] == "dotmul_op":
            ins += [q.pop("a"), q.pop("b")]
            q["n_in"] = 2
        elif q["kind"] == "conv_op":
            ins += [q.pop("img"), q.pop("filter")]
            q["n_in"] = 2
        else:
            ins.append(q.pop("input"))
            q["n_in"] = 1
        specs.append(q)
    return Layer("mixed", ins, name=name, size=size, act=act,
                 bias_attr=bias_attr, extra=layer_attr, projections=specs)


def dotmul_operator(a, b, scale=1.0):
    """Elementwise-product operator for mixed: scale * a .* b
    (reference DotMulOperator, config_parser.py dotmul_operator)."""
    return {"kind": "dotmul_op", "a": a, "b": b, "scale": scale}


def conv_operator(img, filter, filter_size, num_filters, num_channels=None,
                  stride=1, padding=0, filter_size_y=None, stride_y=None,
                  padding_y=None, trans=False):
    """Convolution operator for mixed: conv(img, per-sample filters from
    the ``filter`` layer) — reference ConvOperator, where the second input
    supplies the kernel values sample by sample."""
    from paddle_tpu.utils.error import enforce
    enforce(not trans, "conv_operator: transposed mode is not supported")
    return {"kind": "conv_op", "img": img, "filter": filter,
            "filter_size": filter_size,
            "filter_size_y": filter_size_y or filter_size,
            "num_filters": num_filters, "num_channels": num_channels,
            "stride": stride, "stride_y": stride_y or stride,
            "padding": padding,
            "padding_y": padding_y if padding_y is not None else padding}


# --- projections ----------------------------------------------------------

def full_matrix_projection(input, size=None, param_attr=None):
    # size=None: inferred from the enclosing mixed layer's size (the
    # reference's size=0 default, config_parser fills it in)
    return {"kind": "full_matrix", "input": input, "size": size,
            "attr": to_param_attr(param_attr)}


def trans_full_matrix_projection(input, size=None, param_attr=None):
    return {"kind": "trans_full_matrix", "input": input, "size": size,
            "attr": to_param_attr(param_attr)}


def identity_projection(input, offset=None, size=None):
    if offset is None:
        return {"kind": "identity", "input": input}
    return {"kind": "identity_offset", "input": input, "offset": offset,
            "size": size}


def slice_projection(input, slices):
    return {"kind": "slice", "input": input, "slices": list(slices)}


def dotmul_projection(input, param_attr=None):
    return {"kind": "dotmul", "input": input, "attr": to_param_attr(param_attr)}


def scaling_projection(input, param_attr=None):
    return {"kind": "scaling", "input": input, "attr": to_param_attr(param_attr)}


def table_projection(input, size=None, param_attr=None):
    # size=None defers to the enclosing mixed layer (reference size=0)
    return {"kind": "table", "input": input, "size": size,
            "attr": to_param_attr(param_attr)}


def context_projection(input, context_len, context_start=None,
                       padding_attr=False):
    start = context_start if context_start is not None else -(context_len // 2)
    return {"kind": "context", "input": input, "context_len": context_len,
            "context_start": start}


# --- costs ----------------------------------------------------------------

def classification_cost(input, label, name=None, weight=None, evaluator=None,
                        layer_attr=None):
    """softmax output + cross-entropy, fused (the reference wires a softmax
    fc output into multi-class-cross-entropy; we use the fused stable form
    when the input activation is softmax). ``weight``: a layer giving each
    step's cost a weight before the row's sum."""
    ins = [input, label] + ([weight] if weight is not None else [])
    if input.act is not None and input.act.name == "softmax":
        # refuse double-softmax: fuse by using the raw logits path is not
        # possible post-hoc, so use prob-form xent (reference behavior).
        return Layer("multi-class-cross-entropy", ins, name=name,
                     extra=layer_attr)
    return Layer("softmax_with_cross_entropy", ins, name=name,
                 extra=layer_attr)


def cross_entropy_cost(input, label, name=None, coeff=1.0, weight=None,
                       layer_attr=None):
    return Layer("multi-class-cross-entropy", [input, label], name=name,
                 coeff=coeff, extra=layer_attr)


def cross_entropy_with_selfnorm_cost(input, label, name=None,
                                     softmax_selfnorm_alpha=0.1, layer_attr=None):
    return Layer("multi_class_cross_entropy_with_selfnorm", [input, label],
                 name=name, softmax_selfnorm_alpha=softmax_selfnorm_alpha,
                 extra=layer_attr)


def square_error_cost(input, label, name=None, coeff=1.0, layer_attr=None):
    return Layer("square_error", [input, label], name=name, extra=layer_attr)


regression_cost = square_error_cost


def smooth_l1_cost(input, label, name=None, coeff=1.0, layer_attr=None):
    return Layer("smooth_l1", [input, label], name=name, extra=layer_attr)


def huber_regression_cost(input, label, name=None, delta=1.0, coeff=1.0,
                          layer_attr=None):
    return Layer("huber_regression", [input, label], name=name, delta=delta,
                 extra=layer_attr)


def huber_classification_cost(input, label, name=None, coeff=1.0,
                              layer_attr=None):
    return Layer("huber_classification", [input, label], name=name,
                 extra=layer_attr)


def rank_cost(left, right, label, weight=None, name=None, coeff=1.0,
              layer_attr=None):
    return Layer("rank-cost", [left, right, label], name=name, extra=layer_attr)


def lambda_cost(input, score, name=None, NDCG_num=5, max_sort_size=-1,
                layer_attr=None):
    return Layer("lambda_cost", [input, score], name=name, NDCG_num=NDCG_num,
                 extra=layer_attr)


def sum_cost(input, name=None, layer_attr=None):
    return Layer("sum_cost", _as_list(input), name=name, extra=layer_attr)


def multi_binary_label_cross_entropy_cost(input, label, name=None, coeff=1.0,
                                          layer_attr=None):
    return Layer("multi_binary_label_cross_entropy", [input, label], name=name,
                 extra=layer_attr)


def soft_binary_class_cross_entropy_cost(input, label, name=None, coeff=1.0,
                                         layer_attr=None):
    return Layer("soft_binary_class_cross_entropy", [input, label], name=name,
                 extra=layer_attr)


def cross_entropy_over_beam(input, name=None):
    return Layer("cross_entropy_over_beam", _as_list(input), name=name)


# --- image ----------------------------------------------------------------

def img_conv(input, filter_size, num_filters, name=None, num_channels=None,
             stride=1, padding=0, groups=1, act=None, bias_attr=None,
             param_attr=None, shared_biases=True, layer_attr=None,
             filter_size_y=None, stride_y=None, padding_y=None,
             trans=False, img_size=None, img_size_y=None):
    type_name = "exconvt" if trans else "exconv"
    return Layer(type_name, _as_list(input), name=name,
                 num_filters=num_filters, num_channels=num_channels,
                 filter_size=filter_size, filter_size_y=filter_size_y or filter_size,
                 stride=stride, stride_y=stride_y or stride,
                 padding=padding, padding_y=padding_y if padding_y is not None else padding,
                 groups=groups, shared_biases=shared_biases,
                 img_size=img_size, img_size_y=img_size_y,
                 transposed=trans, act=act or _act.Relu(),
                 param_attrs=[to_param_attr(param_attr)], bias_attr=bias_attr,
                 extra=layer_attr)


def img_pool(input, pool_size, name=None, num_channels=None, pool_type=None,
             stride=1, padding=0, layer_attr=None, pool_size_y=None,
             stride_y=None, padding_y=None, img_size=None, img_size_y=None,
             ceil_mode=True, exclude_mode=None):
    pt = _pooling.resolve(pool_type)
    return Layer("pool", _as_list(input), name=name, num_channels=num_channels,
                 pool_size=pool_size, pool_size_y=pool_size_y,
                 stride=stride, stride_y=stride_y,
                 padding=padding, padding_y=padding_y,
                 pool_type=pt.name, img_size=img_size, img_size_y=img_size_y,
                 ceil_mode=ceil_mode,
                 exclude_mode=exclude_mode if exclude_mode is not None else True,
                 extra=layer_attr)


def img_conv3d(input, filter_size, num_filters, name=None, num_channels=None,
               stride=1, padding=0, act=None, bias_attr=None, param_attr=None,
               img_size=None, img_size_y=None, img_size_z=None, trans=False,
               layer_attr=None):
    return Layer("deconv3d" if trans else "conv3d", _as_list(input), name=name,
                 num_filters=num_filters, num_channels=num_channels,
                 filter_size=filter_size, stride=stride, padding=padding,
                 img_size=img_size, img_size_y=img_size_y, img_size_z=img_size_z,
                 transposed=trans, act=act or _act.Relu(),
                 param_attrs=[to_param_attr(param_attr)], bias_attr=bias_attr,
                 extra=layer_attr)


def img_pool3d(input, pool_size, name=None, num_channels=None, pool_type=None,
               stride=1, padding=0, img_size=None, img_size_y=None,
               img_size_z=None, layer_attr=None):
    pt = _pooling.resolve(pool_type)
    return Layer("pool3d", _as_list(input), name=name, num_channels=num_channels,
                 pool_size=pool_size, stride=stride, padding=padding,
                 pool_type=pt.name, img_size=img_size, img_size_y=img_size_y,
                 img_size_z=img_size_z, extra=layer_attr)


def spp(input, name=None, num_channels=None, pool_type=None, pyramid_height=3,
        img_size=None, img_size_y=None, layer_attr=None):
    pt = _pooling.resolve(pool_type)
    return Layer("spp", _as_list(input), name=name, num_channels=num_channels,
                 pool_type=pt.name, pyramid_height=pyramid_height,
                 img_size=img_size, img_size_y=img_size_y, extra=layer_attr)


def maxout(input, groups, num_channels=None, name=None, img_size=None,
           img_size_y=None, layer_attr=None):
    return Layer("maxout", _as_list(input), name=name, groups=groups,
                 num_channels=num_channels, img_size=img_size,
                 img_size_y=img_size_y, extra=layer_attr)


def block_expand(input, block_x, block_y, stride_x=1, stride_y=1,
                 padding_x=0, padding_y=0, num_channels=None, name=None,
                 img_size_x=None, img_size_y=None, layer_attr=None):
    return Layer("blockexpand", _as_list(input), name=name,
                 block_x=block_x, block_y=block_y, stride_x=stride_x,
                 stride_y=stride_y, padding_x=padding_x, padding_y=padding_y,
                 num_channels=num_channels, img_size_x=img_size_x,
                 img_size_y=img_size_y, extra=layer_attr)


def conv_shift(a, b, name=None, layer_attr=None):
    return Layer("conv_shift", [a, b], name=name, extra=layer_attr)


def row_conv(input, context_len, act=None, name=None, param_attr=None,
             layer_attr=None):
    return Layer("row_conv", _as_list(input), name=name, context_len=context_len,
                 act=act, param_attrs=[to_param_attr(param_attr)],
                 extra=layer_attr)


def bilinear_interp(input, out_size_x, out_size_y, num_channels=None,
                    in_size_x=None, in_size_y=None, name=None, layer_attr=None):
    return Layer("bilinear_interp", _as_list(input), name=name,
                 out_size_x=out_size_x, out_size_y=out_size_y,
                 in_size_x=in_size_x, in_size_y=in_size_y,
                 num_channels=num_channels, extra=layer_attr)


def pad(input, pad_c=None, pad_h=None, pad_w=None, shape_in=None, name=None,
        layer_attr=None):
    return Layer("pad", _as_list(input), name=name, pad_c=pad_c or (0, 0),
                 pad_h=pad_h or (0, 0), pad_w=pad_w or (0, 0),
                 shape_in=shape_in, extra=layer_attr)


def crop(input, shape_in, shape_out, offset=(0, 0, 0), name=None, layer_attr=None):
    return Layer("crop", _as_list(input), name=name, shape_in=shape_in,
                 shape_out=shape_out, offset=offset, extra=layer_attr)


# --- norm -----------------------------------------------------------------

def batch_norm(input, act=None, name=None, num_channels=None, bias_attr=None,
               param_attr=None, layer_attr=None, batch_norm_type=None,
               moving_average_fraction=0.9, use_global_stats=None,
               epsilon=1e-5):
    return Layer("batch_norm", _as_list(input), name=name,
                 num_channels=num_channels, act=act,
                 moving_average_fraction=moving_average_fraction,
                 use_global_stats=bool(use_global_stats),
                 epsilon=epsilon,
                 param_attrs=[to_param_attr(param_attr)] if param_attr else [],
                 bias_attr=bias_attr, extra=layer_attr)


def switch_order(input, name=None, reshape_axis=None, act=None,
                 layer_attr=None):
    """SwitchOrderLayer (paddle/gserver/layers/SwitchOrderLayer.cpp):
    NCHW -> NHWC permutation."""
    return Layer("switch_order", [input], name=name, act=act,
                 reshape_axis=reshape_axis)


def concat2(input, name=None, act=None, layer_attr=None):
    """ConcatenateLayer2 (paddle/gserver/layers/ConcatenateLayer.cpp)."""
    return Layer("concat2", _as_list(input), name=name, act=act)


def data_norm(input, name=None, data_norm_strategy="z-score", layer_attr=None):
    return Layer("data_norm", _as_list(input), name=name,
                 data_norm_strategy=data_norm_strategy, extra=layer_attr)


def img_cmrnorm(input, size=5, scale=0.0001, power=0.75, num_channels=None,
                name=None, img_size=None, img_size_y=None, layer_attr=None):
    return Layer("norm", _as_list(input), name=name, norm_size=size,
                 scale=scale, power=power, num_channels=num_channels,
                 img_size=img_size, img_size_y=img_size_y, extra=layer_attr)


def cross_channel_norm(input, num_channels=None, name=None, param_attr=None):
    return Layer("cross-channel-norm", _as_list(input), name=name,
                 num_channels=num_channels)


def sum_to_one_norm(input, name=None, layer_attr=None):
    return Layer("sum_to_one_norm", _as_list(input), name=name, extra=layer_attr)


def row_l2_norm(input, name=None, layer_attr=None):
    return Layer("row_l2_norm", _as_list(input), name=name, extra=layer_attr)


# --- recurrent ------------------------------------------------------------

def lstmemory(input, name=None, reverse=False, act=None, gate_act=None,
              state_act=None, bias_attr=None, param_attr=None, layer_attr=None):
    return Layer("lstmemory", _as_list(input), name=name, reverse=reverse,
                 active_type="tanh" if act is None else _act.resolve(act).name,
                 active_state_type="tanh" if state_act is None else _act.resolve(state_act).name,
                 active_gate_type="sigmoid" if gate_act is None else _act.resolve(gate_act).name,
                 param_attrs=[to_param_attr(param_attr)], bias_attr=bias_attr,
                 extra=layer_attr)


def grumemory(input, name=None, reverse=False, act=None, gate_act=None,
              bias_attr=None, param_attr=None, layer_attr=None):
    return Layer("gated_recurrent", _as_list(input), name=name, reverse=reverse,
                 active_type="tanh" if act is None else _act.resolve(act).name,
                 active_gate_type="sigmoid" if gate_act is None else _act.resolve(gate_act).name,
                 param_attrs=[to_param_attr(param_attr)], bias_attr=bias_attr,
                 extra=layer_attr)


def recurrent(input, name=None, reverse=False, act=None, bias_attr=None,
              param_attr=None, layer_attr=None):
    return Layer("recurrent", _as_list(input), name=name, reverse=reverse,
                 active_type="tanh" if act is None else _act.resolve(act).name,
                 param_attrs=[to_param_attr(param_attr)], bias_attr=bias_attr,
                 extra=layer_attr)


def lstm_step(input, state, size=None, hidden=None, act=None, gate_act=None,
              state_act=None, name=None, bias_attr=None, param_attr=None,
              layer_attr=None):
    ins = [input, state] + ([hidden] if hidden is not None else [])
    return Layer("lstm_step", ins, name=name, size=size,
                 active_type=_act.resolve(act).name if act else "tanh",
                 active_state_type=_act.resolve(state_act).name if state_act
                 else "tanh",
                 active_gate_type=_act.resolve(gate_act).name if gate_act
                 else "sigmoid",
                 param_attrs=[to_param_attr(param_attr)], bias_attr=bias_attr,
                 extra=layer_attr)


def gru_step(input, output_mem, size=None, act=None, gate_act=None, name=None,
             bias_attr=None, param_attr=None, layer_attr=None):
    return Layer("gru_step", [input, output_mem], name=name, size=size,
                 active_type=_act.resolve(act).name if act else "tanh",
                 active_gate_type=_act.resolve(gate_act).name if gate_act
                 else "sigmoid",
                 param_attrs=[to_param_attr(param_attr)], bias_attr=bias_attr,
                 extra=layer_attr)


# --- sequence -------------------------------------------------------------

def pooling(input, pooling_type=None, name=None, agg_level=None, layer_attr=None):
    pt = _pooling.resolve(pooling_type)
    level = agg_level or AggregateLevel.TO_NO_SEQUENCE
    if pt.name == "max":
        return Layer("max", _as_list(input), name=name, agg_level=level,
                     extra=layer_attr)
    strategy = {"average": "average", "sum": "sum",
                "squarerootn": "squarerootn"}[pt.name]
    return Layer("average", _as_list(input), name=name, agg_level=level,
                 average_strategy=strategy, extra=layer_attr)


def last_seq(input, name=None, agg_level=None, layer_attr=None):
    return Layer("seqlastins", _as_list(input), name=name,
                 agg_level=agg_level or AggregateLevel.TO_NO_SEQUENCE,
                 select_first=False, extra=layer_attr)


def first_seq(input, name=None, agg_level=None, layer_attr=None):
    return Layer("seqlastins", _as_list(input), name=name,
                 agg_level=agg_level or AggregateLevel.TO_NO_SEQUENCE,
                 select_first=True, extra=layer_attr)


def expand(input, expand_as, name=None, expand_level=None, layer_attr=None):
    return Layer("expand", [input, expand_as], name=name, extra=layer_attr)


def seq_concat(a, b, name=None, layer_attr=None):
    return Layer("seqconcat", [a, b], name=name, extra=layer_attr)


def seq_reshape(input, reshape_size, name=None, act=None, bias_attr=False,
                layer_attr=None):
    return Layer("seqreshape", _as_list(input), name=name, size=reshape_size,
                 act=act, extra=layer_attr)


def seq_slice(input, starts=None, ends=None, name=None):
    ins = [input] + [x for x in (starts, ends) if x is not None]
    return Layer("seq_slice", ins, name=name)


def sub_seq(input, offsets, sizes, name=None):
    return Layer("subseq", [input, offsets, sizes], name=name)


def sub_nested_seq(input, selected_indices, name=None):
    return Layer("sub_nested_seq", [input, selected_indices], name=name)


def kmax_seq_score(input, beam_size=1, name=None):
    return Layer("kmax_seq_score", _as_list(input), name=name, beam_size=beam_size)


def eos(input, eos_id, name=None, layer_attr=None):
    return Layer("eos_id", _as_list(input), name=name, eos_id=eos_id,
                 extra=layer_attr)


def get_output(input, arg_name="value", name=None, layer_attr=None):
    return Layer("get_output", _as_list(input), name=name, arg_name=arg_name,
                 extra=layer_attr)


def max_id(input, name=None, layer_attr=None):
    return Layer("maxid", _as_list(input), name=name, extra=layer_attr)


def sampling_id(input, name=None, layer_attr=None):
    return Layer("sampling_id", _as_list(input), name=name, extra=layer_attr)


def multiplex(input, name=None, layer_attr=None):
    return Layer("multiplex", _as_list(input), name=name, extra=layer_attr)


# --- math -----------------------------------------------------------------

def slope_intercept(input, slope=1.0, intercept=0.0, name=None, layer_attr=None):
    return Layer("slope_intercept", _as_list(input), name=name, slope=slope,
                 intercept=intercept, extra=layer_attr)


def scaling(input, weight, name=None, layer_attr=None):
    return Layer("scaling", [weight, input], name=name, extra=layer_attr)


def interpolation(input, weight, name=None, layer_attr=None):
    ins = _as_list(input)
    return Layer("interpolation", [weight] + ins, name=name, extra=layer_attr)


def power(input, weight, name=None, layer_attr=None):
    return Layer("power", [weight, input], name=name, extra=layer_attr)


def cos_sim(a, b, scale=1.0, size=1, name=None, layer_attr=None):
    if size > 1:
        return Layer("cos_vm", [a, b], name=name, cos_scale=scale,
                     extra=layer_attr)
    return Layer("cos", [a, b], name=name, cos_scale=scale, extra=layer_attr)


def cos_sim_vm(vec, mat, scale=1.0, name=None, layer_attr=None):
    return Layer("cos_vm", [vec, mat], name=name, cos_scale=scale,
                 extra=layer_attr)


def out_prod(a, b, name=None, layer_attr=None):
    return Layer("out_prod", [a, b], name=name, extra=layer_attr)


def trans(input, name=None, height=None, layer_attr=None):
    return Layer("trans", _as_list(input), name=name, height=height,
                 extra=layer_attr)


def rotate(input, height, width=None, name=None, layer_attr=None):
    return Layer("rotate", _as_list(input), name=name, height=height,
                 width=width, extra=layer_attr)


def resize(input, size, name=None, layer_attr=None):
    return Layer("resize", _as_list(input), name=name, size=size,
                 extra=layer_attr)


def clip(input, min, max, name=None):
    return Layer("clip", _as_list(input), name=name, min=min, max=max)


def tensor(a, b, size, act=None, name=None, param_attr=None, bias_attr=None,
           layer_attr=None):
    return Layer("tensor", [a, b], name=name, size=size, act=act,
                 param_attrs=[to_param_attr(param_attr)], bias_attr=bias_attr,
                 extra=layer_attr)


def convex_comb(input, weights, size, softmax_weights=False, name=None):
    return Layer("convex_comb", [weights, input], name=name, size=size,
                 softmax_weights=softmax_weights)


def scale_shift(input, name=None, param_attr=None, bias_attr=None):
    return Layer("scale_shift", _as_list(input), name=name,
                 param_attrs=[to_param_attr(param_attr)], bias_attr=bias_attr)


def prelu(input, name=None, partial_sum=1, param_attr=None, layer_attr=None):
    return Layer("prelu", _as_list(input), name=name, partial_sum=partial_sum,
                 param_attrs=[to_param_attr(param_attr)], extra=layer_attr)


# --- big-softmax alternatives / misc -------------------------------------

def hsigmoid(input, label, num_classes, name=None, bias_attr=None,
             param_attr=None, layer_attr=None):
    ins = _as_list(input) + [label]
    pattrs = param_attr if isinstance(param_attr, (list, tuple)) else \
        [param_attr] * (len(ins) - 1)
    return Layer("hsigmoid", ins, name=name, num_classes=num_classes,
                 param_attrs=[to_param_attr(a) for a in pattrs],
                 bias_attr=bias_attr, extra=layer_attr)


def nce(input, label, num_classes, num_neg_samples=10, neg_distribution=None,
        name=None, bias_attr=None, param_attr=None, layer_attr=None):
    ins = _as_list(input) + [label]
    pattrs = param_attr if isinstance(param_attr, (list, tuple)) else \
        [param_attr] * (len(ins) - 1)
    return Layer("nce", ins, name=name, num_classes=num_classes,
                 num_neg_samples=num_neg_samples,
                 param_attrs=[to_param_attr(a) for a in pattrs],
                 bias_attr=bias_attr, extra=layer_attr)


def selective_fc(input, select, size, act=None, name=None, param_attr=None,
                 bias_attr=None, pass_generation=False, layer_attr=None,
                 select_is_id_list=False, gather_min_c=None,
                 weight_transposed=False, select_unique=False,
                 compact_output=False):
    """``select_is_id_list=True`` forces id-list interpretation of the
    select input even when its width equals ``size`` (the reference's
    has_selected_colums semantics — a full-coverage candidate list would
    otherwise parse as a dense 0/1 selection matrix). ``gather_min_c``
    overrides the measured gather-vs-dense crossover (layers/misc.py).
    ``compact_output=True`` returns the [..., K] candidate-space scores
    instead of scattering to [..., size] — the compact-K decode
    handshake (layers/misc.py, docs/decode.md)."""
    ins = _as_list(input) + [select]
    pattrs = param_attr if isinstance(param_attr, (list, tuple)) else \
        [param_attr] * (len(ins) - 1)
    return Layer("selective_fc", ins, name=name, size=size, act=act,
                 selection_pass_generation=pass_generation,
                 select_is_id_list=select_is_id_list,
                 gather_min_c=gather_min_c,
                 weight_transposed=weight_transposed,
                 select_unique=select_unique,
                 compact_output=compact_output,
                 param_attrs=[to_param_attr(a) for a in pattrs],
                 bias_attr=bias_attr, extra=layer_attr)


def print_layer(input, format="{}", name=None):
    return Layer("print", _as_list(input), name=name, format=format)


def crf(input, label, size=None, weight=None, param_attr=None, name=None,
        coeff=1.0, layer_attr=None):
    """Linear-chain CRF cost (crf_layer)."""
    ins = [input, label] + ([weight] if weight is not None else [])
    return Layer("crf", ins, name=name, size=size or input.size, coeff=coeff,
                 param_attrs=[to_param_attr(param_attr)], extra=layer_attr)


def crf_decoding(input, size=None, label=None, param_attr=None, name=None,
                 layer_attr=None):
    ins = [input] + ([label] if label is not None else [])
    return Layer("crf_decoding", ins, name=name, size=size or input.size,
                 param_attrs=[to_param_attr(param_attr)], extra=layer_attr)


def ctc(input, label, size=None, name=None, norm_by_times=False, blank=None,
        layer_attr=None):
    return Layer("ctc", [input, label], name=name, size=size,
                 norm_by_times=norm_by_times,
                 blank=blank if blank is not None else 0, extra=layer_attr)


def warp_ctc(input, label, size=None, name=None, norm_by_times=False,
             blank=0, layer_attr=None):
    return Layer("warp_ctc", [input, label], name=name, size=size,
                 norm_by_times=norm_by_times, blank=blank, extra=layer_attr)


__all__ += ["crf", "crf_decoding", "ctc", "warp_ctc"]


def multi_head_attention(query, key_value=None, size=None, num_heads=8,
                         causal=False, seq_parallel=None, name=None,
                         param_attr=None, bias_attr=None, layer_attr=None):
    """Multi-head attention (beyond-parity; seq_parallel='ring'|'ulysses'
    shards long sequences over the mesh 'sp' axis)."""
    ins = [query] + ([key_value] if key_value is not None else [])
    return Layer("multi_head_attention", ins, name=name, size=size,
                 num_heads=num_heads, causal=causal, seq_parallel=seq_parallel,
                 param_attrs=[to_param_attr(param_attr)], bias_attr=bias_attr,
                 extra=layer_attr)


__all__ += ["multi_head_attention"]


# --- decoder-only blocks (docs/qwen3_next.md) -----------------------------

def rms_norm(input, eps=1e-6, zero_centered=True, name=None, param_attr=None,
             layer_attr=None):
    """x * rsqrt(mean(x^2) + eps) * (1 + w) over the features, w from 0;
    with ``zero_centered=False`` ... * w, w from 1."""
    return Layer("rms_norm", [input], name=name, eps=eps,
                 zero_centered=zero_centered,
                 param_attrs=[to_param_attr(param_attr)], extra=layer_attr)


def gated_attention(input, num_heads, num_kv_heads, head_dim, rotary_dim,
                    rope_theta=10000.0, eps=1e-6, scope=None, name=None,
                    param_attr=None, layer_attr=None):
    """Causal grouped-query self-attention with per-head q/k RMS norm,
    rotary positions on the first ``rotary_dim`` of each head and a sigmoid
    output gate; the scores tile by tile under the causal rule
    (kernels/flash_attn.py), a row's forward launch kept for its backward
    pass."""
    return Layer("gated_attention", [input], name=name, num_heads=num_heads,
                 num_kv_heads=num_kv_heads, head_dim=head_dim,
                 rotary_dim=rotary_dim, rope_theta=rope_theta, eps=eps,
                 scope=scope,
                 param_attrs=[to_param_attr(param_attr)], extra=layer_attr)


def gated_delta_net(input, num_k_heads, num_v_heads, head_k_dim, head_v_dim,
                    conv_kernel=4, eps=1e-6, chunk=64, scope=None, name=None,
                    param_attr=None, layer_attr=None):
    """Gated DeltaNet linear-attention mixer (the chunked gated delta rule
    behind a causal depthwise convolution)."""
    return Layer("gated_delta_net", [input], name=name,
                 num_k_heads=num_k_heads, num_v_heads=num_v_heads,
                 head_k_dim=head_k_dim, head_v_dim=head_v_dim,
                 conv_kernel=conv_kernel, eps=eps, chunk=chunk, scope=scope,
                 param_attrs=[to_param_attr(param_attr)], extra=layer_attr)


def short_conv(input, conv_kernel=3, scope=None, name=None, param_attr=None,
               layer_attr=None):
    """The gated short convolution (LFM2's conv mixer): [B ; C ; x~] = x W_in,
    y = (C * causal depthwise conv over time of B * x~, ``conv_kernel``
    taps, zeros before the row) W_out; no bias, no activation."""
    return Layer("short_conv", [input], name=name, conv_kernel=conv_kernel,
                 scope=scope, param_attrs=[to_param_attr(param_attr)],
                 extra=layer_attr)


def gqa_attention(input, num_heads, num_kv_heads, head_dim, mask,
                  rope_theta=10000.0, eps=1e-6, scope=None, name=None,
                  param_attr=None, layer_attr=None):
    """Grouped-query self-attention with per-head q/k RMS norm (weight w,
    from 1), rotary positions on the whole head and no gate, under a
    structured mask the layer knows by rule: ``mask=("block_diffusion", L,
    b)`` for rows of 2L positions [noised ; clean] in blocks of b tokens,
    ``mask=("causal", L)`` for rows of L positions. Only the tiles the rule
    keeps are computed."""
    return Layer("gqa_attention", [input], name=name, num_heads=num_heads,
                 num_kv_heads=num_kv_heads, head_dim=head_dim,
                 rope_theta=rope_theta, eps=eps, mask=tuple(mask), scope=scope,
                 param_attrs=[to_param_attr(param_attr)], extra=layer_attr)


def mla_attention(input, num_heads, qk_nope_head_dim, qk_rope_head_dim,
                  v_head_dim, kv_lora_rank, mask, rope_theta=10000.0, eps=1e-6,
                  scope=None, name=None, param_attr=None, layer_attr=None):
    """Multi-head latent attention in its training (decompressed) form: keys
    and values come from a normed low-rank latent of ``kv_lora_rank``; a
    head's query and key are a part without positions (``qk_nope_head_dim``)
    beside a rotary part (``qk_rope_head_dim``) whose key all heads share;
    the value head is ``v_head_dim``. No query latent, no bias.
    ``mask=("causal", L)`` for rows of L positions."""
    return Layer("mla_attention", [input], name=name, num_heads=num_heads,
                 qk_nope_head_dim=qk_nope_head_dim,
                 qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
                 kv_lora_rank=kv_lora_rank, rope_theta=rope_theta, eps=eps,
                 mask=tuple(mask), scope=scope,
                 param_attrs=[to_param_attr(param_attr)], extra=layer_attr)


def gated_mlp(input, size, scope=None, name=None, param_attr=None,
              layer_attr=None):
    """(silu(x Wg) * (x Wu)) Wd of width ``size``, no bias: a decoder's
    dense feed-forward layer."""
    return Layer("gated_mlp", [input], name=name, width=size, scope=scope,
                 param_attrs=[to_param_attr(param_attr)], extra=layer_attr)


def block_diffusion_noise(ids, v, t, block, mask_id, scope=None, name=None):
    """(the 2L ids [xt ; x0], the loss weight of each of the L noised
    positions) of a block-diffusion training row: token i of ``ids`` is
    replaced by ``mask_id`` where v_i < t of its block; its weight is then
    1 / t, and 0 anywhere else. The clean half carries no loss."""
    noised = Layer("block_diffusion_noise", [ids, v, t], name=name,
                   block=block, mask_id=mask_id, scope=scope)
    weights = Layer("block_diffusion_weights", [ids, v, t], block=block,
                    name=None if name is None else name + "_weights")
    return noised, weights


def noised_half(input, name=None):
    """The first L of a sequence of 2L positions [noised ; clean]."""
    return Layer("noised_half", [input], name=name)


def moe_ffn(input, num_experts, top_k, expert_size, shared_size=None,
            experts_held=None, first_expert=0, tile=256, score="softmax",
            selection_bias=False, bias_rate=1e-3, route_scale=1.0,
            shared_gate=True, route_norm_eps=0.0, scope=None, name=None,
            param_attr=None, layer_attr=None):
    """Top-k mixture of gated-MLP experts, with a shared expert of
    ``shared_size`` where one is given (behind a sigmoid gate unless
    ``shared_gate=False``). The router is over all ``num_experts``, by
    ``score`` ("softmax" or "sigmoid"); the layer holds (and computes) the
    experts [first_expert, first_expert + experts_held) only. With
    ``selection_bias`` the top k are chosen by score + bias, the weights
    staying the scores' own; the bias is state that moves by ``bias_rate``
    against each expert's load after every step. The renormalised weights
    (over their sum + ``route_norm_eps``) are scaled by ``route_scale``."""
    return Layer("moe_ffn", [input], name=name, num_experts=num_experts,
                 top_k=top_k, expert_size=expert_size, shared_size=shared_size,
                 experts_held=experts_held or num_experts,
                 first_expert=first_expert, tile=tile, score=score,
                 selection_bias=selection_bias, bias_rate=bias_rate,
                 route_scale=route_scale, shared_gate=shared_gate,
                 route_norm_eps=route_norm_eps, scope=scope,
                 param_attrs=[to_param_attr(param_attr)], extra=layer_attr)


__all__ += ["rms_norm", "gated_attention", "gated_delta_net", "moe_ffn",
            "gqa_attention", "block_diffusion_noise", "noised_half",
            "mla_attention", "gated_mlp", "short_conv"]


# --- detection (SSD) ------------------------------------------------------

def priorbox(input, image=None, min_size=None, max_size=None,
             aspect_ratio=None, variance=None, feat_h=None, feat_w=None,
             img_h=1.0, img_w=1.0, name=None):
    ins = [input] + ([image] if image is not None else [])
    return Layer("priorbox", ins, name=name, min_size=min_size or [],
                 max_size=max_size or [], aspect_ratio=aspect_ratio or [],
                 variance=variance or [0.1, 0.1, 0.2, 0.2],
                 feat_h=feat_h, feat_w=feat_w, img_h=img_h, img_w=img_w)


def multibox_loss(priorbox, label, loc_pred, conf_pred, num_classes,
                  overlap_threshold=0.5, neg_pos_ratio=3.0, name=None):
    return Layer("multibox_loss", [priorbox, label, loc_pred, conf_pred],
                 name=name, num_classes=num_classes,
                 overlap_threshold=overlap_threshold,
                 neg_pos_ratio=neg_pos_ratio)


def detection_output(priorbox, loc_pred, conf_pred, num_classes,
                     nms_threshold=0.45, nms_top_k=400, keep_top_k=100,
                     confidence_threshold=0.01, name=None):
    return Layer("detection_output", [priorbox, loc_pred, conf_pred],
                 name=name, num_classes=num_classes,
                 nms_threshold=nms_threshold, nms_top_k=nms_top_k,
                 keep_top_k=keep_top_k,
                 confidence_threshold=confidence_threshold)


__all__ += ["priorbox", "multibox_loss", "detection_output"]


# --- recurrent group / generation ----------------------------------------

from paddle_tpu.layers.recurrent_group import (   # noqa: E402
    BeamSearchControlCallbacks, GeneratedInput, StaticInput,
    SubsequenceInput, beam_search, memory, recurrent_group)

__all__ += ["recurrent_group", "memory", "StaticInput", "GeneratedInput",
            "SubsequenceInput", "BeamSearchControlCallbacks", "beam_search"]
