"""The gated short convolution, LFM2's conv mixer (docs/lfm2.md).

    [B ; C ; x~] = x W_in                       W_in [d, 3 d], no bias
    v = B * x~
    c_t = sum_j w[:, j] v_{t - (K - 1) + j}     depthwise, causal, zeros before
                                                the row, no bias, no activation
    out = (C * c) W_out                         W_out [d, d], no bias

``layers/delta_net.causal_conv`` at K taps without its SiLU. On the TPU the
three middle lines are one Mosaic launch each way over the in-projection's
own [T, 3 d] (``kernels/short_conv.py``); elsewhere, or past the launches'
gate, they are XLA's. The mixer's activations are made again in the backward
pass from its input: what a layer keeps is its input alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.layer import ParamSpec, register_layer
from paddle_tpu.kernels import short_conv as kernels
from paddle_tpu.utils.error import enforce


def _params(cfg, in_infos):
    d, K = in_infos[0].size, cfg.attr("conv_kernel", 3)
    a = cfg.param_attr(0)
    return {"in_proj": ParamSpec((d, 3 * d), a, fan_in=d),
            "conv": ParamSpec((d, K), a, fan_in=K),
            "out_proj": ParamSpec((d, d), a, fan_in=d)}


@register_layer("short_conv", params=_params)
def _short_conv_forward(cfg, params, ins, ctx):
    enforce(not getattr(ctx, "packed", False),
            f"short_conv {cfg.name}: packed rows need a segment reset in the "
            "convolution, which this layer lacks")
    x = ins[0].value
    _, T, d = x.shape
    K = cfg.attr("conv_kernel", 3)
    conv = kernels.gated_short_conv if kernels.taken(cfg.name, T, d, K,
                                                     x.dtype) \
        else kernels.short_conv_xla

    def mixer(x, p):
        bcx = jnp.matmul(x, p["in_proj"])
        return jnp.matmul(conv(bcx, p["conv"]), p["out_proj"])

    return ins[0].with_value(jax.checkpoint(mixer)(x, params))
