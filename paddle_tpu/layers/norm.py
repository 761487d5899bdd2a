"""Normalisation layers.

Analogs of paddle/gserver/layers/{BatchNormalizationLayer,
CudnnBatchNormLayer,BatchNormBaseLayer,DataNormLayer,NormLayer
(cross-map response norm),CrossChannelNormLayer,SumToOneNormLayer}.cpp.

Batch-norm running stats are handled functionally: the moving mean/var are
*parameters* updated by the trainer via the aux-state mechanism (the
reference stores them in the same Parameter slots, ParameterConfig
is_static moving averages) — on TPU we return batch stats via ctx.extras
and let the train step fold the EMA update into the jitted program, so the
whole thing stays one XLA computation.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from paddle_tpu.attr import ParamAttr
from paddle_tpu.core.arg import Arg, ArgInfo
from paddle_tpu.core.layer import ParamSpec, register_layer
from paddle_tpu.utils.error import enforce


def _bn_params(cfg, in_infos):
    c = cfg.attr("num_channels")
    if c is None:
        info = in_infos[0]
        # image inputs (C,H,W shape known) normalise per channel
        # (reference BatchNormBaseLayer channels_); plain feature vectors
        # normalise per feature
        c = info.shape[0] if (info.shape is not None
                              and len(info.shape) == 3) else info.size
    one = ParamAttr(initial_strategy="constant", initial_value=1.0)
    zero = ParamAttr(initial_strategy="zero")
    return {
        "w0": ParamSpec((c,), cfg.param_attr(0) if cfg.param_attrs else one, fan_in=c),
        "wbias": ParamSpec((c,), cfg.bias_param_attr() or zero, fan_in=c, is_bias=True),
        # moving statistics; excluded from gradient updates by the trainer
        # (aux param convention: suffix .wmean/.wvar, is_static)
        "wmean": ParamSpec((c,), ParamAttr(initial_strategy="zero", is_static=True),
                           fan_in=c),
        "wvar": ParamSpec((c,), ParamAttr(initial_strategy="constant",
                                          initial_value=1.0, is_static=True),
                          fan_in=c),
    }


def _bn_infer(cfg, in_infos):
    return in_infos[0]


@register_layer("batch_norm", infer=_bn_infer, params=_bn_params)
def _batch_norm(cfg, params, ins, ctx):
    # channel count comes from the parameter shape — the one place
    # guaranteed consistent with _bn_params for 4D/flat/image inputs
    c = params["w0"].shape[0]
    eps = cfg.attr("epsilon", 1e-5)
    momentum = cfg.attr("moving_average_fraction", 0.9)
    v = ins[0].value
    orig_shape = v.shape
    img = v.ndim == 4 or (v.ndim == 2 and (v.shape[-1] % c == 0)
                          and v.shape[-1] != c)
    if v.ndim == 4:                               # [B, H, W, C] carried 4D
        x = v
        axes = (0, 1, 2)
    elif img:
        x = v.reshape(v.shape[0], c, -1)          # [B, C, HW]
        axes = (0, 2)
    else:
        x = v
        axes = tuple(range(x.ndim - 1))
    shape = [1] * x.ndim
    # channel axis: 1 for the flat CHW view, last for NHWC-4D and vectors
    ax = 1 if (img and v.ndim != 4) else x.ndim - 1
    shape[ax] = c
    use_global = (not ctx.training) or cfg.attr("use_global_stats", False)
    if use_global:
        mean, var = params["wmean"], params["wvar"]
    else:
        # statistics always accumulate in fp32 (mixed-precision safe: bf16
        # sums lose precision at B*H*W scale)
        # promote, don't hard-cast: f64 checkgrad runs this graph in double
        xs = x.astype(jnp.promote_types(x.dtype, jnp.float32))
        mask = ins[0].mask
        if mask is not None and not img and x.ndim == 3:
            # ragged [B,T,D] sequences: weight stats by the padding mask so
            # padded positions bias neither the normalisation nor the EMA
            w = mask[..., None].astype(jnp.float32)
            denom = jnp.maximum(w.sum(axis=(0, 1)), 1.0)
            mean = (xs * w).sum(axis=(0, 1)) / denom
            var = (jnp.square(xs - mean) * w).sum(axis=(0, 1)) / denom
        else:
            # single-pass stats: E[x^2] - E[x]^2 lets XLA fuse both
            # reductions into ONE read of the activation (jnp.var's
            # two-pass form re-reads it; measured ~10% on the BN-heavy
            # ResNet step; a shifted variant defeats the fusion).
            # Conditioning envelope: with fp32 accumulation the relative
            # variance error is ~(1 + mean^2/var) * 2^-24 — exact enough
            # for |mean|/std up to ~1000, far beyond what batch-norm
            # inputs (zero-mean-init conv outputs) reach; inputs with
            # extreme offsets should go through data_norm first.
            mean = xs.mean(axis=axes)
            var = jnp.maximum((xs * xs).mean(axis=axes) - mean * mean, 0.0)
        # EMA update folded into the jitted step via ctx.extras
        with jax.named_scope("aux_update"):
            ctx.extras.setdefault("batch_stats", {})[cfg.name] = {
                "wmean": momentum * params["wmean"] + (1 - momentum) * mean,
                "wvar": momentum * params["wvar"] + (1 - momentum) * var,
            }
    mean_b, var_b = mean.reshape(shape), var.reshape(shape)
    g, b = params["w0"].reshape(shape), params["wbias"].reshape(shape)
    # fold to per-channel scale/shift in f32, then apply in the input
    # dtype: `(x - mean_f32) * ...` would promote the whole [B,H,W,C]
    # elementwise chain to f32 — under bf16 mixed precision XLA then
    # materialises f32 activations in the backward remat chain (profiled
    # 1.15 GB moved per 56x56 stage fusion vs ~0.3 GB of bf16 operands,
    # r3, retired setup). Per-channel math stays f32/f64; only the big
    # elementwise apply runs in x.dtype (the standard mixed-precision BN).
    inv = jax.lax.rsqrt(var_b + eps) * g
    scale = inv.astype(x.dtype)
    shift = (b - mean_b * inv).astype(x.dtype)
    y = x * scale + shift
    return Arg(y.reshape(orig_shape), ins[0].mask, ins[0].seg_ids)


@register_layer("cudnn_batch_norm", infer=_bn_infer, params=_bn_params)
def _cudnn_batch_norm(cfg, params, ins, ctx):
    return _batch_norm(cfg, params, ins, ctx)


@register_layer("mkldnn_batch_norm", infer=_bn_infer, params=_bn_params)
def _mkldnn_batch_norm(cfg, params, ins, ctx):
    return _batch_norm(cfg, params, ins, ctx)


def _data_norm_params(cfg, in_infos):
    d = in_infos[0].size
    st = ParamAttr(is_static=True)
    return {"wmin": ParamSpec((d,), st, fan_in=d),
            "wmax": ParamSpec((d,), ParamAttr(initial_strategy="constant",
                                              initial_value=1.0, is_static=True), fan_in=d),
            "wmean": ParamSpec((d,), st, fan_in=d),
            "wstd": ParamSpec((d,), ParamAttr(initial_strategy="constant",
                                              initial_value=1.0, is_static=True), fan_in=d)}


@register_layer("data_norm", params=_data_norm_params)
def _data_norm(cfg, params, ins, ctx):
    """DataNormLayer: z-score / min-max / decimal-scaling using precomputed
    stats carried as static parameters."""
    strat = cfg.attr("data_norm_strategy", "z-score")
    v = ins[0].value
    if strat == "min-max":
        rng = jnp.maximum(params["wmax"] - params["wmin"], 1e-8)
        return ins[0].with_value((v - params["wmin"]) / rng)
    if strat == "decimal-scaling":
        return ins[0].with_value(v / jnp.maximum(params["wmax"], 1e-8))
    return ins[0].with_value((v - params["wmean"]) / jnp.maximum(params["wstd"], 1e-8))


@register_layer("norm")
def _cmr_norm(cfg, params, ins, ctx):
    """NormLayer cmrnorm-projection: local response norm across channel maps
    (paddle/function/CrossMapNormalOp)."""
    c = cfg.attr("num_channels")
    size = cfg.attr("norm_size", 5)
    scale = cfg.attr("scale", 0.0001)
    power = cfg.attr("power", 0.75)
    h = cfg.attr("img_size_y") or cfg.attr("img_size")
    w = cfg.attr("img_size") or h
    if ins[0].value.ndim == 4:                    # carried NHWC
        h, w, c = ins[0].value.shape[1:]
    elif h is None and c:
        from paddle_tpu.layers.conv import _square_side
        h = w = _square_side(ins[0].value.shape[-1], c)
    enforce(c is not None and h is not None,
            f"cmrnorm layer {cfg.name}: specify num_channels/img_size")
    from paddle_tpu.layers.conv import as_nhwc
    v = as_nhwc(ins[0].value, c, h, w)
    sq = jnp.square(v)
    half = size // 2
    # sum over channel window via padded cumulative trick (channel = last)
    padded = jnp.pad(sq, ((0, 0), (0, 0), (0, 0), (half, size - 1 - half)))
    acc = sum(padded[..., i:i + c] for i in range(size))
    denom = jnp.power(1.0 + scale * acc, power)
    from paddle_tpu.layers.conv import flat_from_nhwc
    # flat CHW out (status quo ante): cmrnorm feeds flat-only consumers
    # in reference configs; conv/pool re-lift to NHWC cheaply
    return Arg(flat_from_nhwc(v / denom))


@register_layer("cross-channel-norm")
def _cross_channel_norm(cfg, params, ins, ctx):
    """CrossChannelNormLayer: L2-normalise across channels at each pixel
    with learned per-channel scale (SSD)."""
    c = cfg.attr("num_channels")
    v = ins[0].value
    if v.ndim == 4:                               # carried NHWC: C is last
        norm = jnp.sqrt(jnp.square(v).sum(axis=-1, keepdims=True) + 1e-10)
        return Arg(v / norm, ins[0].mask)
    x = v.reshape(v.shape[0], c, -1)
    norm = jnp.sqrt(jnp.square(x).sum(axis=1, keepdims=True) + 1e-10)
    y = x / norm
    return Arg(y.reshape(v.shape), ins[0].mask)


# --- RMS norm --------------------------------------------------------------

def const_init(attr: ParamAttr, value: float) -> ParamAttr:
    """``attr`` starting at the constant ``value`` unless it names an
    initialisation of its own (norm weights, decay rates)."""
    if attr.initial_strategy is not None or attr.initial_std is not None \
            or attr.initial_mean is not None or attr.initial_max is not None:
        return attr
    return dataclasses.replace(attr, initial_strategy="constant",
                               initial_value=float(value))


def rms_normalize(x, eps):
    """x * rsqrt(mean(x^2) + eps) over the last axis, statistics in float32
    (wider under an f64 gradient check), in x's dtype."""
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype)


def _rms_params(cfg, in_infos):
    d = in_infos[0].size
    start = 0.0 if cfg.attr("zero_centered", True) else 1.0
    return {"w0": ParamSpec((d,), const_init(cfg.param_attr(0), start),
                            fan_in=d)}


@register_layer("rms_norm", params=_rms_params)
def _rms_norm_forward(cfg, params, ins, ctx):
    """RMS norm over the feature axis: zero-centred,
    x * rsqrt(mean(x^2) + eps) * (1 + w) with w starting at 0, or with
    ``zero_centered=False`` the plain form, ... * w with w starting at 1."""
    x = ins[0].value
    y = rms_normalize(x, cfg.attr("eps", 1e-6))
    w = params["w0"]
    scale = 1 + w if cfg.attr("zero_centered", True) else w
    return ins[0].with_value(y * scale.astype(x.dtype))
