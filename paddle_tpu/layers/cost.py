"""Cost layers.

Analog of paddle/gserver/layers/CostLayer.cpp: multi-class cross-entropy
(+selfnorm), soft binary cross-entropy, square error, huber regression /
classification, rank cost, lambda cost, multi-binary-label cross-entropy,
smooth-l1, sum_cost; plus the fused softmax+cross-entropy classification
path (the reference special-cases `multi-class-cross-entropy` after a
softmax output — on TPU we fuse via log_softmax for numerical stability,
like operators/softmax_with_cross_entropy).

Every cost layer outputs per-sample cost [B, 1]; sequence costs sum over
valid (mask=1) timesteps first, matching the reference's per-sequence
aggregation of ragged costs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.arg import Arg, ArgInfo
from paddle_tpu.core.layer import register_layer
from paddle_tpu.utils.error import enforce


def _cost_infer(cfg, in_infos):
    return ArgInfo(size=1)


def _f32up(x):
    """Upcast low-precision (bf16/f16) loss inputs to f32, preserving
    f64 — checkgrad (--job=checkgrad) runs this same graph in double and
    a hard f32 cast would floor the finite-difference at fp32 ulps."""
    return x.astype(jnp.promote_types(x.dtype, jnp.float32))


COST_TYPES = set()


def is_cost_type(layer_type: str) -> bool:
    """True for layer types registered through register_cost (the exact
    'is this output a training cost' test the CLI needs for multi-output
    configs)."""
    return layer_type in COST_TYPES


def register_cost(name):
    """register_layer specialised for cost layers: applies the layer's
    ``coeff`` attribute (reference CostLayer coeff_ scaling) to the
    per-sample cost so weighted multi-cost objectives match.

    Sequence packing (docs/packing.md): masked per-step reductions are
    segment-additive, so a packed row's [B, 1] cost is exactly the sum of
    its sequences' costs — the VALUES need no change. What does change is
    the sample count: the wrapper publishes the batch's packed-sequence
    count into ``ctx.extras['<name>#n_seq']`` so Topology.loss_fn divides
    by sequences, not rows, and the packed loss matches the unpacked loss
    over the same samples."""
    COST_TYPES.add(name)
    def deco(fn):
        def wrapped(cfg, params, ins, ctx):
            from paddle_tpu.layers.conv import image_flat

            # cost layers consume flat matrices (reference CostLayer):
            # flatten carried-NHWC image values back to CHW order at this
            # boundary, like fc does
            ins = [a.with_value(image_flat(a.value))
                   if getattr(a.value, "ndim", 0) == 4 else a for a in ins]
            out = fn(cfg, params, ins, ctx)
            coeff = cfg.attr("coeff", 1.0)
            if coeff != 1.0:
                out = out.with_value(out.value * coeff)
            if getattr(ctx, "packed", False):
                seg = next((a.seg_ids for a in ins
                            if a.seg_ids is not None), None)
                if seg is not None:
                    from paddle_tpu.core.arg import packed_segment_count
                    ctx.extras[f"{cfg.name}#n_seq"] = \
                        packed_segment_count(seg)
            return out
        wrapped.__name__ = fn.__name__
        register_layer(name, infer=_cost_infer)(wrapped)
        return wrapped
    return deco


def _weighted(cost, ins):
    """Per-step costs times a third input's per-step weights (float32
    [B, T] or [B, T, 1]), where the layer was given one."""
    if len(ins) < 3:
        return cost
    w = _f32up(ins[2].value)
    return cost * (w[..., 0] if w.ndim == cost.ndim + 1 else w)


def _reduce_seq(cost, mask):
    """[B, T] per-step costs -> [B] via masked sum."""
    if mask is not None:
        cost = cost * mask
        return cost.sum(axis=-1)
    return cost


def _stable_nll(logits, ids):
    """-log_softmax(logits)[label] as lse - gathered-logit, upcasting
    INSIDE each consumer so no f32 copy of the [B(,T),V] logits ever
    materialises (the converts fuse into the reduce / the gather)."""
    lse = jax.nn.logsumexp(_f32up(logits), axis=-1)
    l_lab = _f32up(jnp.take_along_axis(
        logits, ids[..., None], axis=-1)[..., 0])
    return lse - l_lab


@register_cost("multi-class-cross-entropy")
def _xent_forward(cfg, params, ins, ctx):
    """Input 0: probability distribution (post-softmax); input 1: int labels;
    an optional input 2: a weight for each step's cost.
    When the producing layer stashed pre-softmax logits (core/layer.py
    Layer.forward), compute the numerically-stable fused log-softmax form
    directly from them — XLA then dead-code-eliminates the softmax if the
    probs have no other consumer (the softmax_with_cross_entropy_op
    fusion). Otherwise take probs and guard with clip (reference
    CostLayer.cpp oneHotCrossEntropy)."""
    probs, label = ins[0], ins[1]
    ids = label.value.astype(jnp.int32)
    if ids.ndim == probs.value.ndim:  # [B(,T),1] -> [B(,T)]
        ids = ids[..., 0]
    logits = ctx.extras.get(f"{cfg.inputs[0].name}#logits") \
        if cfg.inputs else None
    if logits is not None and logits.value.shape == probs.value.shape:
        cost = _reduce_seq(_weighted(_stable_nll(logits.value, ids), ins),
                           probs.mask)
        return Arg(cost[:, None])
    # gather FIRST, then upcast/clip/log on the [B(,T)] gathered vector —
    # upcasting the whole [B,T,V] prob tensor materialises a V-sized f32
    # array (at V=30k that is a 921MB HBM pass per step; r4 profile)
    p_lab = jnp.take_along_axis(probs.value, ids[..., None], axis=-1)[..., 0]
    nll = -jnp.log(jnp.clip(_f32up(p_lab), 1e-10, 1.0))
    cost = _reduce_seq(_weighted(nll, ins), probs.mask)
    return Arg(cost[:, None])


@register_cost("softmax_with_cross_entropy")
def _fused_xent_forward(cfg, params, ins, ctx):
    """Fused logits->xent (operators/softmax_with_cross_entropy_op analog):
    numerically stable lse - gathered-logit, single pass, no V-sized f32
    materialisation — the TPU-preferred path (shared _stable_nll)."""
    logits, label = ins[0], ins[1]
    ids = label.value.astype(jnp.int32)
    if ids.ndim == logits.value.ndim:
        ids = ids[..., 0]
    cost = _reduce_seq(_weighted(_stable_nll(logits.value, ids), ins),
                       logits.mask)
    return Arg(cost[:, None])


@register_cost("multi_class_cross_entropy_with_selfnorm")
def _xent_selfnorm_forward(cfg, params, ins, ctx):
    """CostLayer.cpp MultiClassCrossEntropyWithSelfNorm: xent on
    self-normalised probs + alpha * ln(Z)^2."""
    probs, label = ins[0], ins[1]
    alpha = cfg.attr("softmax_selfnorm_alpha", 0.1)
    p = jnp.clip(probs.value, 1e-10, None)
    z = p.sum(axis=-1, keepdims=True)
    pn = p / z
    ids = label.value.astype(jnp.int32)
    if ids.ndim == p.ndim:
        ids = ids[..., 0]
    nll = -jnp.log(jnp.take_along_axis(pn, ids[..., None], axis=-1))[..., 0]
    nll = nll + alpha * jnp.square(jnp.log(z[..., 0]))
    return Arg(_reduce_seq(nll, probs.mask)[:, None])


@register_cost("soft_binary_class_cross_entropy")
def _soft_bce_forward(cfg, params, ins, ctx):
    p = jnp.clip(ins[0].value, 1e-7, 1 - 1e-7)
    t = ins[1].value
    ce = -(t * jnp.log(p) + (1 - t) * jnp.log(1 - p)).sum(axis=-1)
    return Arg(_reduce_seq(ce, ins[0].mask)[:, None])


@register_cost("multi_binary_label_cross_entropy")
def _multi_bce_forward(cfg, params, ins, ctx):
    """Labels arrive as padded id lists (sparse_binary_vector analog):
    ids [B, K] with -1 padding, scattered to a dense multi-hot target."""
    p = jnp.clip(ins[0].value, 1e-7, 1 - 1e-7)
    ids = ins[1].value.astype(jnp.int32)
    if ids.ndim == p.ndim and ids.shape[-1] == p.shape[-1]:
        t = ids.astype(p.dtype)  # already dense multi-hot
    else:
        valid = (ids >= 0)
        oh = jax.nn.one_hot(jnp.clip(ids, 0, p.shape[-1] - 1), p.shape[-1])
        t = (oh * valid[..., None]).sum(axis=-2)
        t = jnp.clip(t, 0.0, 1.0)
    ce = -(t * jnp.log(p) + (1 - t) * jnp.log(1 - p)).sum(axis=-1)
    return Arg(_reduce_seq(ce, ins[0].mask)[:, None])


@register_cost("square_error")
def _mse_forward(cfg, params, ins, ctx):
    d = ins[0].value - ins[1].value
    cost = 0.5 * jnp.square(d).sum(axis=-1)
    return Arg(_reduce_seq(cost, ins[0].mask)[:, None])


@register_cost("smooth_l1")
def _smooth_l1_forward(cfg, params, ins, ctx):
    """SmoothL1Cost (CostLayer.cpp): 0.5 d^2 if |d|<1 else |d|-0.5."""
    d = ins[0].value - ins[1].value
    ad = jnp.abs(d)
    per = jnp.where(ad < 1.0, 0.5 * d * d, ad - 0.5).sum(axis=-1)
    return Arg(_reduce_seq(per, ins[0].mask)[:, None])


@register_cost("huber_regression")
def _huber_reg_forward(cfg, params, ins, ctx):
    delta = cfg.attr("delta", 1.0)
    d = jnp.abs(ins[0].value - ins[1].value)
    per = jnp.where(d <= delta, 0.5 * d * d, delta * (d - 0.5 * delta)).sum(axis=-1)
    return Arg(_reduce_seq(per, ins[0].mask)[:, None])


@register_cost("huber_classification")
def _huber_cls_forward(cfg, params, ins, ctx):
    """HuberTwoClassification: labels {0,1} -> y in {-1,+1};
    cost = 0 if y*f>1; (1-y*f)^2 if -1<=y*f<=1; -4*y*f otherwise."""
    f = _f32up(ins[0].value)[..., 0]
    y = ins[1].value.astype(f.dtype)
    if y.ndim > f.ndim:
        y = y[..., 0]
    y = 2.0 * y - 1.0
    a = y * f
    per = jnp.where(a > 1.0, 0.0, jnp.where(a >= -1.0, jnp.square(1.0 - a), -4.0 * a))
    return Arg(_reduce_seq(per, ins[0].mask)[:, None])


@register_cost("rank-cost")
def _rank_cost_forward(cfg, params, ins, ctx):
    """RankingCost (CostLayer.cpp): pairwise logistic loss on score diff
    o = o1 - o2, label in [0,1]: C = -t*o + log(1+exp(o))."""
    o = _f32up(ins[0].value)[..., 0] - _f32up(ins[1].value)[..., 0]
    t = ins[2].value.astype(o.dtype)
    if t.ndim > o.ndim:
        t = t[..., 0]
    per = -t * o + jnp.logaddexp(0.0, o)
    return Arg(per[:, None])


@register_cost("lambda_cost")
def _lambda_cost_forward(cfg, params, ins, ctx):
    """LambdaRank NDCG-weighted pairwise cost over a sequence of scores
    (CostLayer.cpp LambdaCost). Inputs: score seq [B,T,1], relevance seq
    [B,T,1]. Static-shape rewrite: all T^2 pairs weighted by |delta NDCG|."""
    ndcg_num = cfg.attr("NDCG_num", 5)
    score = ins[0].value[..., 0]       # [B, T]
    rel = ins[1].value[..., 0]         # [B, T]
    mask = ins[0].mask if ins[0].mask is not None else jnp.ones_like(score)
    g = (jnp.power(2.0, rel) - 1.0) * mask
    # ideal DCG from top-NDCG_num relevances
    sorted_g = -jnp.sort(-g, axis=-1)
    pos = jnp.arange(score.shape[-1])
    disc = jnp.where(pos < ndcg_num, 1.0 / jnp.log2(pos + 2.0), 0.0)
    idcg = (sorted_g * disc).sum(-1, keepdims=True)  # [B,1]
    idcg = jnp.maximum(idcg, 1e-5)
    sdiff = score[:, :, None] - score[:, None, :]           # [B,T,T]
    gdiff = g[:, :, None] - g[:, None, :]
    pair_mask = (mask[:, :, None] * mask[:, None, :]) * (gdiff > 0)
    lam = jnp.abs(gdiff) / idcg[..., None]
    per = (pair_mask * lam * jnp.logaddexp(0.0, -sdiff)).sum((-1, -2))
    return Arg(per[:, None])


@register_cost("sum_cost")
def _sum_cost_forward(cfg, params, ins, ctx):
    v = ins[0].value
    per = v.reshape(v.shape[0], -1).sum(axis=-1) if ins[0].mask is None else \
        _reduce_seq(v.sum(axis=-1), ins[0].mask)
    return Arg(per[:, None])


@register_cost("cross_entropy_over_beam")
def _xent_over_beam_forward(cfg, params, ins, ctx):
    """CrossEntropyOverBeam (reference cross_entropy_over_beam): softmax over
    beam candidate scores, NLL of the gold candidate index.
    Inputs: scores [B, beam], gold index [B]."""
    scores, gold = ins[0], ins[1]
    logp = jax.nn.log_softmax(scores.value, axis=-1)
    ids = gold.value.astype(jnp.int32)
    if ids.ndim > 1:
        ids = ids[..., 0]
    per = -jnp.take_along_axis(logp, ids[:, None], axis=-1)[:, 0]
    return Arg(per[:, None])


# --- validation layers (ValidationLayer.h:60,88) --------------------------
# The reference implements auc-validation / pnpair-validation as layers
# that accumulate AUC / pos-neg-pair statistics during forward and print
# at pass end, with a no-op backward (ValidationLayer.cpp:39-54). The
# TPU-native split: the layer itself contributes a constant zero "cost"
# (so configs that list it as an output train unchanged — autodiff of a
# constant is the reference's empty backward), and the metric
# accumulation rides the evaluator protocol — the trainer auto-attaches
# the matching evaluator over this layer's inputs
# (trainer/trainer.py auto_validation_evaluators; the config DSL table is
# python/paddle/trainer/config_parser.py:2639-2651 define_cost rows).

def _validation_infer(cfg, in_infos):
    return ArgInfo(size=1)


@register_layer("auc-validation", infer=_validation_infer)
def _auc_validation(cfg, params, ins, ctx):
    """AucValidation (ValidationLayer.cpp:43-115): inputs (output, label
    [, weight]); forward feeds a last-column-auc evaluator, output is an
    inert zero cost."""
    enforce(2 <= len(ins) <= 3,
            f"auc-validation layer {cfg.name} takes (output, label"
            f"[, weight]), got {len(ins)} inputs")
    return Arg(jnp.zeros((ins[0].value.shape[0], 1), jnp.float32))


@register_layer("pnpair-validation", infer=_validation_infer)
def _pnpair_validation(cfg, params, ins, ctx):
    """PnpairValidation (ValidationLayer.cpp:118-166): inputs (output,
    label, query-info[, weight]); forward feeds a pnpair evaluator,
    output is an inert zero cost."""
    enforce(3 <= len(ins) <= 4,
            f"pnpair-validation layer {cfg.name} takes (output, label, "
            f"info[, weight]), got {len(ins)} inputs")
    return Arg(jnp.zeros((ins[0].value.shape[0], 1), jnp.float32))
