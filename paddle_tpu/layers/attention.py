"""Multi-head attention layers.

Beyond-parity extension (the 2017 reference builds attention only from
mixed-layer primitives — simple_attention; SURVEY §5.7 notes CP/ring
attention as the TPU-era extension). The layer integrates with the
sequence-parallel backends in paddle_tpu.parallel.ring_attention: set
``seq_parallel='ring'|'ulysses'`` and provide a mesh (via ctx.mesh /
trainer) to shard long sequences over the 'sp' axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.arg import Arg, ArgInfo
from paddle_tpu.core.layer import ParamSpec, register_layer
from paddle_tpu.layers.norm import const_init, rms_normalize
from paddle_tpu.utils.error import enforce


def _mha_infer(cfg, in_infos):
    return ArgInfo(size=cfg.size or in_infos[0].size, is_seq=True)


def _mha_params(cfg, in_infos):
    d_model = cfg.size or in_infos[0].size
    d_in = in_infos[0].size
    d_kv = in_infos[1].size if len(in_infos) > 1 else d_in
    specs = {
        "wq": ParamSpec((d_in, d_model), cfg.param_attr(0), fan_in=d_in),
        "wk": ParamSpec((d_kv, d_model), cfg.param_attr(0), fan_in=d_kv),
        "wv": ParamSpec((d_kv, d_model), cfg.param_attr(0), fan_in=d_kv),
        "wo": ParamSpec((d_model, d_model), cfg.param_attr(0), fan_in=d_model),
    }
    battr = cfg.bias_param_attr()
    if battr is not None:
        specs["wbias"] = ParamSpec((d_model,), battr, fan_in=d_model,
                                   is_bias=True)
    return specs


@register_layer("multi_head_attention", infer=_mha_infer, params=_mha_params)
def _mha_forward(cfg, params, ins, ctx):
    """Input 0: query seq [B,T,Dq]; optional input 1: key/value seq.
    num_heads required; causal for decoder self-attention."""
    q_in = ins[0]
    kv_in = ins[1] if len(ins) > 1 else ins[0]
    H = cfg.attr("num_heads")
    causal = cfg.attr("causal", False)
    backend = cfg.attr("seq_parallel")       # None | 'ring' | 'ulysses'
    d_model = params["wq"].shape[1]
    enforce(d_model % H == 0, "d_model must divide num_heads")
    Dh = d_model // H
    B, T = q_in.value.shape[:2]

    q = jnp.matmul(q_in.value, params["wq"]).reshape(B, T, H, Dh)
    Tk = kv_in.value.shape[1]
    k = jnp.matmul(kv_in.value, params["wk"]).reshape(B, Tk, H, Dh)
    v = jnp.matmul(kv_in.value, params["wv"]).reshape(B, Tk, H, Dh)

    # packed rows (docs/packing.md): a block-diagonal segment mask keeps
    # every query inside its own packed sequence — composed with the
    # causal mask, and subsuming the key-padding mask (padding carries
    # seg_id -1, which no valid query matches)
    packed = getattr(ctx, "packed", False)
    seg_q = q_in.seg_ids if packed else None
    seg_kv = kv_in.seg_ids if packed else None
    if packed:
        enforce(seg_q is not None and seg_kv is not None,
                f"multi_head_attention {cfg.name}: packed feeds need "
                "seg_ids on both the query and key/value sequences")

    if backend in ("ring", "ulysses") and ctx.mesh is not None and \
            "sp" in ctx.mesh.axis_names and ctx.mesh.shape["sp"] > 1:
        from paddle_tpu.parallel.ring_attention import (ring_attention,
                                                        ulysses_attention)
        fn = ring_attention if backend == "ring" else ulysses_attention
        o = fn(q, k, v, ctx.mesh, axis_name="sp", causal=causal,
               seg_q=seg_q, seg_kv=seg_kv)
    else:
        from paddle_tpu.parallel.ring_attention import reference_attention
        if seg_q is not None:
            # block-diagonal segment mask composed with causal inside
            # reference_attention — the same masked path the sp backends
            # reproduce shard-wise
            o = reference_attention(q, k, v, causal=causal, seg_q=seg_q,
                                    seg_kv=seg_kv)
        # mask padding keys
        elif kv_in.mask is not None:
            k = k * kv_in.mask[..., None, None]
            big_neg_bias = (1.0 - kv_in.mask)[:, None, None, :] * -1e30
            # accumulate scores at >= f32 without DOWNcasting wider
            # inputs: forcing f32 under the f64 gradcheck made finite
            # differences drown in f32 rounding noise
            s = jnp.einsum("bqhd,bkhd->bqhk", q, k,
                           preferred_element_type=jnp.promote_types(
                               q.dtype, jnp.float32)) * (Dh ** -0.5)
            s = s + jnp.moveaxis(big_neg_bias, 1, 2)
            if causal:
                pos_q, pos_k = jnp.arange(T), jnp.arange(Tk)
                s = jnp.where((pos_q[:, None] >= pos_k[None, :])[None, :, None, :],
                              s, -1e30)
            a = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            o = jnp.einsum("bqhk,bkhd->bqhd", a, v)
        else:
            o = reference_attention(q, k, v, causal=causal)

    out = jnp.matmul(o.reshape(B, T, d_model), params["wo"])
    if "wbias" in params:
        out = out + params["wbias"]
    if q_in.mask is not None:
        out = out * q_in.mask[..., None].astype(out.dtype)
    return Arg(out, q_in.mask, q_in.seg_ids)


# --- gated grouped-query attention ------------------------------------------

def _gattn_params(cfg, in_infos):
    d = in_infos[0].size
    H, Hkv, D = cfg.attr("num_heads"), cfg.attr("num_kv_heads"), cfg.attr("head_dim")
    a = cfg.param_attr(0)
    return {
        "wq": ParamSpec((d, H * 2 * D), a, fan_in=d),
        "wk": ParamSpec((d, Hkv * D), a, fan_in=d),
        "wv": ParamSpec((d, Hkv * D), a, fan_in=d),
        "wo": ParamSpec((H * D, d), a, fan_in=H * D),
        "q_norm": ParamSpec((D,), const_init(a, 0.0), fan_in=D),
        "k_norm": ParamSpec((D,), const_init(a, 0.0), fan_in=D),
    }


def rotary(x, theta, rot):
    """Rotate-half rotary positions 0..T-1 on the first ``rot`` of the last
    axis of x [B, T, heads, D]; angles in float32."""
    T, half = x.shape[1], rot // 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    xr, xp = x[..., :rot], x[..., rot:]
    turned = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    out = xr * cos.astype(x.dtype) + turned * sin.astype(x.dtype)
    return jnp.concatenate([out, xp], -1)


def rows_one_at_a_time(mixer, x, params, kept=("gdn_T",)):
    """``mixer(row [T, d], params)`` over the rows of x [B, T, d], one after
    the other, each row's forward computed again in its backward pass: what
    is alive at once is one row's activations, not the batch's (a row of
    4096 tokens fills the MXU on its own). The one thing kept from a row's
    forward is what the mixer names as ``kept`` says: ``gdn_T``
    (kernels/gdn.py: the delta rule's chunk inverses), ``flash_attn_o`` /
    ``flash_attn_lse`` (kernels/flash_attn.py: what the forward launch
    made)."""
    keep = jax.checkpoint_policies.save_only_these_names(*kept)
    return jax.lax.map(
        jax.checkpoint(lambda row: mixer(row, params), policy=keep), x)


@register_layer("gated_attention", params=_gattn_params)
def _gated_attention_forward(cfg, params, ins, ctx):
    """Causal grouped-query self-attention with per-head q/k RMS norm
    (1 + w), partial rotary positions and a sigmoid output gate read from
    the query projection. No bias. The scores are computed tile by tile
    under the rule ``("causal", T)`` (kernels/flash_attn.py). Padding
    follows the real tokens of a row, so causality keeps it out of them."""
    from paddle_tpu.kernels import flash_attn

    enforce(not getattr(ctx, "packed", False),
            f"gated_attention {cfg.name}: packed rows need a segment mask "
            "this layer does not have")
    x = ins[0].value
    B, T, _ = x.shape
    H, Hkv, D = cfg.attr("num_heads"), cfg.attr("num_kv_heads"), cfg.attr("head_dim")
    eps, rot = cfg.attr("eps", 1e-6), cfg.attr("rotary_dim")

    def mixer(x, p):
        """One row [T, d]."""
        qg = jnp.matmul(x, p["wq"]).reshape(1, T, H, 2 * D)
        q, gate = qg[..., :D], qg[..., D:]
        k = jnp.matmul(x, p["wk"]).reshape(1, T, Hkv, D)
        v = jnp.matmul(x, p["wv"]).reshape(1, T, Hkv * D)
        # 1 / sqrt(D) rides on the query's norm weight: a power of two at
        # D = 256, so no rounding moves
        q = rms_normalize(q, eps) * ((1 + p["q_norm"]) * D ** -0.5).astype(x.dtype)
        k = rms_normalize(k, eps) * (1 + p["k_norm"]).astype(x.dtype)
        q = rotary(q, cfg.attr("rope_theta"), rot)
        k = rotary(k, cfg.attr("rope_theta"), rot)
        o = flash_attn.attention(cfg.name, q.reshape(1, T, H * D),
                                 k.reshape(1, T, Hkv * D), v, ("causal", T),
                                 Hkv)
        o = o[0] * jax.nn.sigmoid(gate.reshape(T, H * D))
        return jnp.matmul(o, p["wo"])

    # a row's backward pass computes its projections, norms, rotary and gate
    # again from the layer's input; what the kernels' forward launch made of
    # the row it keeps
    out = rows_one_at_a_time(mixer, x, params,
                             kept=("flash_attn_o", "flash_attn_lse"))
    return ins[0].with_value(out)


# --- grouped-query attention under a structured mask -------------------------

def _gqa_params(cfg, in_infos):
    d = in_infos[0].size
    H, Hkv, D = cfg.attr("num_heads"), cfg.attr("num_kv_heads"), cfg.attr("head_dim")
    a = cfg.param_attr(0)
    return {
        "wq": ParamSpec((d, H * D), a, fan_in=d),
        "wk": ParamSpec((d, Hkv * D), a, fan_in=d),
        "wv": ParamSpec((d, Hkv * D), a, fan_in=d),
        "wo": ParamSpec((H * D, d), a, fan_in=H * D),
        "q_norm": ParamSpec((D,), const_init(a, 1.0), fan_in=D),
        "k_norm": ParamSpec((D,), const_init(a, 1.0), fan_in=D),
    }


def rotary_at(x, pos, theta):
    """Rotate-half rotary on the whole last axis of x [B, T, heads, D], at
    the given position of each of the T steps; angles in float32."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.asarray(pos, jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    turned = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos.astype(x.dtype) + turned * sin.astype(x.dtype)


def _head_norm(x, w, eps, scale=1.0):
    """x * rsqrt(mean(x^2) + eps) * w * scale over the head, in float32,
    rounded once to x's dtype."""
    f32 = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(f32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (w.astype(f32) * scale)).astype(x.dtype)


@register_layer("gqa_attention", params=_gqa_params)
def _gqa_attention_forward(cfg, params, ins, ctx):
    """Grouped-query self-attention as SDAR's blocks (docs/sdar.md) and
    LFM2's attention blocks (docs/lfm2.md, heads of 64 under the causal
    rule) have it: per-head RMS norm of q and k with a learned weight w,
    rotate-half rotary on the whole head at the position the mask's rule
    gives each step, softmax(q k^T / sqrt(D)) under the rule's mask, no
    gate, no bias. The rule (``mask``) is known at trace time and the scores
    are computed tile by tile where it keeps something
    (kernels/flash_attn.py). On the TPU,
    where a head is one group of 128 lanes, the norm and rotary of q and of k
    are one Mosaic launch each way, ``head_norm_rotary_fwd`` /
    ``head_norm_rotary_bwd`` (kernels/head_norm_rotary.py), on the
    projection as the matmul laid it out, [T, heads * D]; elsewhere they are
    ``_head_norm`` and ``rotary_at`` on [1, T, heads, D]. A row's padding is
    computed as tokens: the rule is over whole rows."""
    from paddle_tpu.kernels import flash_attn, head_norm_rotary

    enforce(not getattr(ctx, "packed", False),
            f"gqa_attention {cfg.name}: packed rows need a segment rule "
            "beside the mask's, which this layer does not have")
    x = ins[0].value
    B, T, _ = x.shape
    H, Hkv, D = cfg.attr("num_heads"), cfg.attr("num_kv_heads"), cfg.attr("head_dim")
    eps, theta, rule = cfg.attr("eps", 1e-6), cfg.attr("rope_theta"), cfg.attr("mask")
    if rule == ("causal", None):         # rows of any one length
        rule = ("causal", T)
    pos = flash_attn.positions(rule, T)
    one_pass = head_norm_rotary.taken(cfg.name, T, H * D, D, D, x.dtype)
    if one_pass:
        # once a layer, outside the rows' loop
        cos, sin = head_norm_rotary.tables(pos, theta, D, x.dtype)

    def mixer(x, p):
        """One row [T, d]."""
        # 1 / sqrt(D) rides on the query's norm weight: one rounding
        if one_pass:
            q = head_norm_rotary.normed_rotated(
                jnp.matmul(x, p["wq"]), p["q_norm"], cos, sin, eps, D ** -0.5)
            k = head_norm_rotary.normed_rotated(
                jnp.matmul(x, p["wk"]), p["k_norm"], cos, sin, eps)
            v = jnp.matmul(x, p["wv"])[None]
        else:
            q = jnp.matmul(x, p["wq"]).reshape(1, T, H, D)
            k = jnp.matmul(x, p["wk"]).reshape(1, T, Hkv, D)
            v = jnp.matmul(x, p["wv"])[None]
            q = rotary_at(_head_norm(q, p["q_norm"], eps, D ** -0.5), pos, theta)
            k = rotary_at(_head_norm(k, p["k_norm"], eps), pos, theta)
            q, k = q.reshape(1, T, H * D), k.reshape(1, T, Hkv * D)
        o = flash_attn.attention(cfg.name, q, k, v, rule, Hkv)
        return jnp.matmul(o[0], p["wo"])

    # a row's backward pass computes its projections, norms and rotary again
    # from the layer's input; what the kernels' forward launch made of the
    # row it keeps
    out = rows_one_at_a_time(mixer, x, params,
                             kept=("flash_attn_o", "flash_attn_lse"))
    return ins[0].with_value(out)


# --- multi-head latent attention, training form -------------------------------

def _mla_params(cfg, in_infos):
    d, H, r = in_infos[0].size, cfg.attr("num_heads"), cfg.attr("kv_lora_rank")
    Dn, Dr, Dv = (cfg.attr("qk_nope_head_dim"), cfg.attr("qk_rope_head_dim"),
                  cfg.attr("v_head_dim"))
    a = cfg.param_attr(0)
    return {
        "wq": ParamSpec((d, H * (Dn + Dr)), a, fan_in=d),
        "wkva": ParamSpec((d, r + Dr), a, fan_in=d),
        "kv_norm": ParamSpec((r,), const_init(a, 1.0), fan_in=r),
        "wkvb": ParamSpec((r, H * (Dn + Dv)), a, fan_in=r),
        "wo": ParamSpec((H * Dv, d), a, fan_in=H * Dv),
    }


@register_layer("mla_attention", params=_mla_params)
def _mla_attention_forward(cfg, params, ins, ctx):
    """Multi-head latent attention as DeepSeek-V3-style decoders train it
    (docs/kimi_vl.md), the decompressed form:

        q = x Wq -> [T, H, Dn + Dr] = [q_nope ; q_rope]      (no query latent)
        [c ; k_r] = x Wkva -> c [T, r], k_r [T, Dr]
        [k_nope ; v] = rms_norm(c; w_kv) Wkvb -> [T, H, Dn + Dv]
        rotate-half rotary on q_rope and on k_r, which ALL heads share
        k_h = [k_nope_h ; k_r];  o_h = softmax(q_h k_h^T / sqrt(Dn + Dr)) v_h
        out = concat(o_h) Wo

    under the mask's rule, tile by tile where it keeps something
    (kernels/flash_attn.py, head sizes Dn + Dr : Dv). No bias. The absorbed
    form over a latent cache is generation's, which this layer does not
    have."""
    from paddle_tpu.kernels import flash_attn, head_norm_rotary

    enforce(not getattr(ctx, "packed", False),
            f"mla_attention {cfg.name}: packed rows need a segment rule "
            "beside the mask's, which this layer does not have")
    x = ins[0].value
    B, T, _ = x.shape
    H, r = cfg.attr("num_heads"), cfg.attr("kv_lora_rank")
    Dn, Dr, Dv = (cfg.attr("qk_nope_head_dim"), cfg.attr("qk_rope_head_dim"),
                  cfg.attr("v_head_dim"))
    eps, theta, rule = cfg.attr("eps", 1e-6), cfg.attr("rope_theta"), cfg.attr("mask")
    if rule == ("causal", None):         # rows of any one length
        rule = ("causal", T)
    pos = flash_attn.positions(rule, T)
    f32 = jnp.promote_types(x.dtype, jnp.float32)
    # rotary is on Dr of a head's Dn + Dr lanes and the norm is the latent's:
    # outside head_norm_rotary's gate, which the log then says as
    # gqa_attention's does; both stay `_head_norm` and `rotary_at`
    head_norm_rotary.taken(cfg.name, T, H * (Dn + Dr), Dn + Dr, Dr, x.dtype)

    def mixer(x, p):
        """One row [T, d]."""
        # 1 / sqrt(Dn + Dr) goes into q before its one rounding
        q = (jnp.matmul(x, p["wq"], preferred_element_type=f32)
             * (Dn + Dr) ** -0.5).astype(x.dtype).reshape(1, T, H, Dn + Dr)
        ckr = jnp.matmul(x, p["wkva"])
        kv = jnp.matmul(_head_norm(ckr[:, :r], p["kv_norm"], eps),
                        p["wkvb"]).reshape(1, T, H, Dn + Dv)
        k_r = rotary_at(ckr[:, r:].reshape(1, T, 1, Dr), pos, theta)
        q = jnp.concatenate(
            [q[..., :Dn], rotary_at(q[..., Dn:], pos, theta)], -1)
        k = jnp.concatenate(
            [kv[..., :Dn], jnp.broadcast_to(k_r, (1, T, H, Dr))], -1)
        o = flash_attn.attention(
            cfg.name, q.reshape(1, T, H * (Dn + Dr)),
            k.reshape(1, T, H * (Dn + Dr)),
            kv[..., Dn:].reshape(1, T, H * Dv), rule, H)
        return jnp.matmul(o[0], p["wo"])

    out = rows_one_at_a_time(mixer, x, params,
                             kept=("flash_attn_o", "flash_attn_lse"))
    return ins[0].with_value(out)
