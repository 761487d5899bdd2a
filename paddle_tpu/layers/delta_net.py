"""Gated DeltaNet: a linear-attention sequence mixer (arXiv:2412.06464, as
Qwen3-Next configures it; docs/qwen3_next.md).

    [q, k, v, z] = split(x W_qkvz);  [b, a] = split(x W_ba)
    [q, k, v] = silu(causal depthwise conv over time, kernel K, zeros left)
    q, k L2-normalised per head, repeated to the value heads, q / sqrt(dk)
    beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)   (float32)
    o = gated delta rule(q, k, v, g, beta)          (kernels/gdn.py, chunked)
    out = (o * rsqrt(mean(o^2) + eps) * w_g * silu(z)) W_out
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.layer import ParamSpec, register_layer
from paddle_tpu.kernels import gdn
from paddle_tpu.layers.attention import rows_one_at_a_time
from paddle_tpu.layers.norm import const_init, rms_normalize
from paddle_tpu.utils.error import enforce


def _dims(cfg):
    return (cfg.attr("num_k_heads"), cfg.attr("num_v_heads"),
            cfg.attr("head_k_dim"), cfg.attr("head_v_dim"))


def _gdn_params(cfg, in_infos):
    d = in_infos[0].size
    Hk, Hv, dk, dv = _dims(cfg)
    K = cfg.attr("conv_kernel", 4)
    a = cfg.param_attr(0)
    return {
        "wqkvz": ParamSpec((d, 2 * Hk * dk + 2 * Hv * dv), a, fan_in=d),
        "wba": ParamSpec((d, 2 * Hv), a, fan_in=d),
        "conv": ParamSpec((2 * Hk * dk + Hv * dv, K), a, fan_in=K),
        "a_log": ParamSpec((Hv,), const_init(a, 0.0), fan_in=1),
        "dt_bias": ParamSpec((Hv,), const_init(a, 1.0), fan_in=1),
        "norm": ParamSpec((dv,), const_init(a, 1.0), fan_in=dv),
        "wout": ParamSpec((Hv * dv, d), a, fan_in=Hv * dv),
    }


def causal_conv(x, w):
    """Depthwise causal convolution over time: x [B, T, C], w [C, K];
    out_t = sum_j w[:, j] x_{t - (K - 1) + j}, zeros before the row."""
    K, T = w.shape[1], x.shape[1]
    padded = jnp.pad(x, [(0, 0), (K - 1, 0), (0, 0)])
    return sum(padded[:, j:j + T] * w[:, j].astype(x.dtype) for j in range(K))


def _l2(x):
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    y = xf * jax.lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + 1e-6)
    return y.astype(x.dtype)


@register_layer("gated_delta_net", params=_gdn_params)
def _gated_delta_net_forward(cfg, params, ins, ctx):
    enforce(not getattr(ctx, "packed", False),
            f"gated_delta_net {cfg.name}: packed rows need segment resets in "
            "the convolution and the state pass, which this layer lacks")
    x = ins[0].value
    B, T, _ = x.shape
    Hk, Hv, dk, dv = _dims(cfg)
    nk, nv = Hk * dk, Hv * dv
    chunk = cfg.attr("chunk", gdn.CHUNK)
    state_pass = gdn.pick_state_pass(cfg.name, dk, dv, chunk, x.dtype)

    def mixer(x, p):
        """One row [T, d]."""
        f32 = jnp.promote_types(x.dtype, jnp.float32)
        mixed = jnp.matmul(x, p["wqkvz"])
        qkv, z = mixed[..., :2 * nk + nv], mixed[..., 2 * nk + nv:]
        ba = jnp.matmul(x, p["wba"]).astype(f32)
        b, a = ba[..., :Hv], ba[..., Hv:]
        qkv = jax.nn.silu(causal_conv(qkv[None], p["conv"]))
        q = _l2(qkv[..., :nk].reshape(1, T, Hk, dk))
        k = _l2(qkv[..., nk:2 * nk].reshape(1, T, Hk, dk))
        v = qkv[..., 2 * nk:].reshape(1, T, Hv, dv)
        q = jnp.repeat(q, Hv // Hk, axis=2) * (dk ** -0.5)
        k = jnp.repeat(k, Hv // Hk, axis=2)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(p["a_log"].astype(f32)) * jax.nn.softplus(
            a + p["dt_bias"].astype(f32))
        o = gdn.gated_delta_rule(q, k, v, g[None], beta[None], chunk,
                                 state_pass)[0]
        o = rms_normalize(o, cfg.attr("eps", 1e-6)) * p["norm"].astype(x.dtype)
        o = o * jax.nn.silu(z.reshape(T, Hv, dv))
        return jnp.matmul(o.reshape(T, nv), p["wout"])

    out = rows_one_at_a_time(mixer, x, params)
    return ins[0].with_value(out)
