"""Linear-chain CRF and CTC losses + decoders.

Analogs of paddle/gserver/layers/{CRFLayer,CRFDecodingLayer,
LinearChainCRF,CTCLayer,WarpCTCLayer}.cpp. The reference implements the
forward-backward recursions as hand-written CPU loops (LinearChainCRF.cpp)
and links warp-ctc CUDA for GPU; here both dynamic programs are a
``lax.scan`` recursion in log space (fully differentiable — autodiff
yields the posterior-marginal gradients the reference derives by hand).
The CRF partition function also has a Pallas forward-backward kernel
(kernels/crf.py) with the time loop fused in-kernel and an EXPLICIT
marginal backward pass — the long-sequence path on TPU, chosen by
``_crf_use_pallas``. Both are masked for padding.

CRF parameter layout (LinearChainCRF.cpp parity): w is (L+2) x L —
row 0 = start weights a, row 1 = end weights b, rows 2.. = transition
matrix w[i,j] = score(tag i -> tag j).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.arg import Arg, ArgInfo
from paddle_tpu.core.layer import ParamSpec, register_layer
from paddle_tpu.utils.error import enforce

NEG = -1e30


def _crf_params(cfg, in_infos):
    L = cfg.size or in_infos[0].size
    return {"w0": ParamSpec((L + 2, L), cfg.param_attr(0), fan_in=L)}


def _crf_pieces(w):
    return w[0], w[1], w[2:]          # start, end, trans [L, L]


# The Pallas forward-backward kernel (kernels/crf.py) computes the
# partition function on the TPU backend for LONG sequences, the lax.scan
# recursion elsewhere. Crossover on a v5e (B=32, L=64, fwd+bwd): T=128
# scan wins 1.2x, T=512 pallas 1.2x, T=2048 pallas 3.7x — threshold at
# 256 (tools/ctc_bench.py; r5, not re-measured).
_CRF_PALLAS_MIN_T = 256


def _crf_use_pallas(T):
    from paddle_tpu.kernels._pallas_util import take_pallas

    if jax.config.jax_disable_jit:
        return take_pallas("crf", "crf_logz", False,
                           "jax_disable_jit: interpreter/reference mode")
    if T < _CRF_PALLAS_MIN_T:
        return take_pallas("crf", "crf_logz", False,
                           f"T={T} < {_CRF_PALLAS_MIN_T}, scan wins there")
    return take_pallas("crf", "crf_logz")


def _crf_gold_score(emit, labels, mask, w):
    """Score of the gold path (shared by both logZ implementations)."""
    start, end, trans = _crf_pieces(w)
    lengths = mask.sum(-1).astype(jnp.int32)
    lab = labels.astype(jnp.int32)
    first = jnp.take_along_axis(emit[:, 0], lab[:, :1], axis=-1)[:, 0] + start[lab[:, 0]]
    emit_t = jnp.take_along_axis(emit, lab[..., None], axis=-1)[..., 0]  # [B,T]
    emit_sum = (emit_t * mask)[:, 1:].sum(-1)
    tr = trans[lab[:, :-1], lab[:, 1:]]                      # [B, T-1]
    tr_sum = (tr * mask[:, 1:]).sum(-1)
    last_idx = jnp.maximum(lengths - 1, 0)
    last_lab = jnp.take_along_axis(lab, last_idx[:, None], axis=1)[:, 0]
    return first + emit_sum + tr_sum + end[last_lab]


def crf_logz_scan(emit, mask, w):
    """[B] log partition function via the lax.scan alpha recursion."""
    start, end, trans = _crf_pieces(w)

    alpha0 = start[None, :] + emit[:, 0]                     # [B, L]

    def alpha_step(alpha, xm):
        e_t, m_t = xm
        nxt = jax.nn.logsumexp(alpha[:, :, None] + trans[None, :, :], axis=1) + e_t
        alpha = m_t[:, None] * nxt + (1 - m_t[:, None]) * alpha
        return alpha, None

    eT = jnp.swapaxes(emit, 0, 1)[1:]                        # [T-1, B, L]
    mT = jnp.swapaxes(mask, 0, 1)[1:]
    alpha, _ = jax.lax.scan(alpha_step, alpha0, (eT, mT))
    return jax.nn.logsumexp(alpha + end[None, :], axis=-1)   # [B]


def crf_logz_pallas(emit, mask, w, interpret=False):
    """[B] log partition via the Pallas forward-backward kernel
    (kernels/crf.py) with lane/sublane padding: L pads with NEG
    start/end/trans (dead states), B pads with zero-mask rows."""
    from paddle_tpu.kernels.crf import crf_logz

    start, end, trans = _crf_pieces(w)
    B0, T, L0 = emit.shape
    L = L0 if interpret else -(-L0 // 128) * 128
    B = B0 if interpret else -(-B0 // 8) * 8
    if L != L0:
        emit = jnp.pad(emit, ((0, 0), (0, 0), (0, L - L0)))
        start = jnp.pad(start, (0, L - L0), constant_values=NEG)
        end = jnp.pad(end, (0, L - L0), constant_values=NEG)
        trans = jnp.pad(trans, ((0, L - L0), (0, L - L0)),
                        constant_values=NEG)
    if B != B0:
        emit = jnp.pad(emit, ((0, B - B0), (0, 0), (0, 0)))
        mask = jnp.pad(mask, ((0, B - B0), (0, 0)))
    logz = crf_logz(jnp.swapaxes(emit, 0, 1),
                    jnp.swapaxes(mask, 0, 1).astype(emit.dtype),
                    start, end, trans, interpret)
    return logz[:B0]


def crf_nll(emit, labels, mask, w):
    """Negative log-likelihood of label paths under a linear-chain CRF.

    emit: [B, T, L] unary scores; labels: [B, T] int; mask: [B, T].
    Returns [B] costs. (LinearChainCRF::forward parity.)"""
    if _crf_use_pallas(emit.shape[1]):
        from paddle_tpu.kernels._pallas_util import call_kernel

        logZ = call_kernel(crf_logz_pallas, (emit, mask, w),
                           batch_argnums=(0, 1))
    else:
        logZ = crf_logz_scan(emit, mask, w)
    return logZ - _crf_gold_score(emit, labels, mask, w)


def crf_decode(emit, mask, w):
    """Viterbi decode -> ([B, T] best tags, [B] best scores)
    (LinearChainCRF::decode parity)."""
    mask = mask.astype(emit.dtype)   # mixed mask dtype would split the
    start, end, trans = _crf_pieces(w)   # scan carry between f32/f64
    B, T, L = emit.shape
    delta0 = start[None, :] + emit[:, 0]

    def vit_step(delta, xm):
        e_t, m_t = xm
        cand = delta[:, :, None] + trans[None, :, :]          # [B, L, L]
        best = cand.max(axis=1) + e_t
        bp = cand.argmax(axis=1)
        delta_new = m_t[:, None] * best + (1 - m_t[:, None]) * delta
        bp = jnp.where(m_t[:, None] > 0, bp,
                       jnp.broadcast_to(jnp.arange(L)[None, :], bp.shape))
        return delta_new, bp

    eT = jnp.swapaxes(emit, 0, 1)[1:]
    mT = jnp.swapaxes(mask, 0, 1)[1:]
    delta, bps = jax.lax.scan(vit_step, delta0, (eT, mT))     # bps [T-1, B, L]
    final = delta + end[None, :]
    last = final.argmax(axis=-1)                              # [B]
    score = final.max(axis=-1)

    def back_step(tag, bp):
        prev = jnp.take_along_axis(bp, tag[:, None], axis=-1)[:, 0]
        return prev, tag

    # processing bps[i] (transition into step i+1) emits tags[i+1]; the
    # final carry after the reverse scan is tags[0]
    first, tags_rest = jax.lax.scan(back_step, last, bps, reverse=True)
    tags = jnp.concatenate([first[:, None],
                            jnp.swapaxes(tags_rest, 0, 1)], axis=1)  # [B, T]
    return tags, score


def _crf_infer(cfg, in_infos):
    return ArgInfo(size=1)


@register_layer("crf", infer=_crf_infer, params=_crf_params)
def _crf_layer(cfg, params, ins, ctx):
    """CRFLayer: cost = NLL of the gold tag sequence. Inputs: emissions
    sequence [B,T,L], label sequence [B,T]."""
    enforce(not getattr(ctx, "packed", False),
            f"crf layer {cfg.name}: packed sequence rows are not supported "
            "(the chain would score transitions across packed boundaries); "
            "feed this model unpacked")
    emit, label = ins[0], ins[1]
    enforce(emit.mask is not None, "crf needs sequence input")
    ids = label.value.astype(jnp.int32)
    if ids.ndim == 3:
        ids = ids[..., 0]
    nll = crf_nll(emit.value, ids, emit.mask, params["w0"])
    coeff = cfg.attr("coeff", 1.0)
    return Arg((nll * coeff)[:, None])


def _crf_dec_infer(cfg, in_infos):
    return ArgInfo(size=1, is_seq=True, dtype=jnp.int32)


def _step_tag_errors(tags, label_value, mask):
    """[B,T] 0/1 per-step viterbi-vs-gold errors, masked (shared by
    crf_decoding's label mode and crf_error)."""
    lab = label_value.astype(jnp.int32)
    if lab.ndim == 3:
        lab = lab[..., 0]
    return (tags != lab).astype(jnp.float32) * mask


@register_layer("crf_decoding", infer=_crf_dec_infer, params=_crf_params)
def _crf_decoding_layer(cfg, params, ins, ctx):
    """CRFDecodingLayer: Viterbi tags; with a label input, emits 0/1
    per-step error indicators instead (reference semantics)."""
    enforce(not getattr(ctx, "packed", False),
            f"crf_decoding layer {cfg.name}: packed sequence rows are not "
            "supported (viterbi would score transitions across packed "
            "boundaries); feed this model unpacked")
    emit = ins[0]
    tags, score = crf_decode(emit.value, emit.mask, params["w0"])
    ctx.extras[f"{cfg.name}:score"] = score
    if len(ins) > 1:
        err = _step_tag_errors(tags, ins[1].value, emit.mask)
        return Arg(err[..., None], emit.mask)
    return Arg(tags[..., None].astype(jnp.int32), emit.mask)


# --- CTC ------------------------------------------------------------------

def ctc_nll(logits, labels, in_mask, label_mask, blank=0):
    """CTC negative log-likelihood via the alpha recursion in log space.

    logits: [B, T, C] (unnormalised); labels: [B, U] int (no blanks);
    in_mask: [B, T]; label_mask: [B, U]. Returns [B].
    (CTCLayer/LinearChainCTC parity; warp-ctc semantics, blank id
    configurable — the reference's warp_ctc uses blank=0.)"""
    logp = jax.nn.log_softmax(logits, axis=-1)
    B, T, C = logp.shape
    U = labels.shape[1]
    S = 2 * U + 1
    lab = labels.astype(jnp.int32)
    # extended sequence: blank l1 blank l2 ... blank
    ext = jnp.full((B, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(lab)
    # positions beyond 2*len(label)+1 are invalid
    ulen = label_mask.sum(-1).astype(jnp.int32)
    slen = 2 * ulen + 1
    pos = jnp.arange(S)[None, :]
    ext_ok = (pos < slen[:, None])

    # can-skip: ext[s] != blank and ext[s] != ext[s-2]
    ext_prev2 = jnp.pad(ext, ((0, 0), (2, 0)), constant_values=-1)[:, :S]
    can_skip = (ext != blank) & (ext != ext_prev2)

    def emit_at(t):
        return jnp.take_along_axis(logp[:, t], ext, axis=-1)  # [B, S]

    alpha0 = jnp.full((B, S), NEG)
    alpha0 = alpha0.at[:, 0].set(logp[:, 0, blank])
    alpha0 = alpha0.at[:, 1].set(
        jnp.take_along_axis(logp[:, 0], ext[:, 1:2], axis=-1)[:, 0])
    alpha0 = jnp.where(ext_ok, alpha0, NEG)

    logp_T = jnp.swapaxes(logp, 0, 1)                          # [T, B, C]
    m_T = jnp.swapaxes(in_mask, 0, 1)                          # [T, B]

    def step(alpha, xm):
        lp_t, m_t = xm
        em = jnp.take_along_axis(lp_t, ext, axis=-1)           # [B, S]
        a1 = jnp.pad(alpha, ((0, 0), (1, 0)), constant_values=NEG)[:, :S]
        a2 = jnp.pad(alpha, ((0, 0), (2, 0)), constant_values=NEG)[:, :S]
        a2 = jnp.where(can_skip, a2, NEG)
        nxt = jnp.logaddexp(jnp.logaddexp(alpha, a1), a2) + em
        nxt = jnp.where(ext_ok, nxt, NEG)
        alpha = m_t[:, None] * nxt + (1 - m_t[:, None]) * alpha
        return alpha, None

    alpha, _ = jax.lax.scan(step, alpha0, (logp_T[1:], m_T[1:]))
    # NLL = -log(alpha[S-1] + alpha[S-2]) at the last valid position;
    # when slen < 2 (empty label: the all-blank path only) there is no
    # second terminal state — masking last2 avoids double-counting the
    # blank path (exactly log 2 of spurious likelihood otherwise)
    last = jnp.take_along_axis(alpha, jnp.maximum(slen - 1, 0)[:, None], axis=-1)[:, 0]
    last2 = jnp.take_along_axis(alpha, jnp.maximum(slen - 2, 0)[:, None], axis=-1)[:, 0]
    last2 = jnp.where(slen >= 2, last2, NEG)
    return -jnp.logaddexp(last, last2)


def _ctc_infer(cfg, in_infos):
    return ArgInfo(size=1)


@register_layer("ctc", infer=_ctc_infer)
def _ctc_layer(cfg, params, ins, ctx):
    """CTCLayer: input 0 = frame logits/probs seq [B,T,C]; input 1 = label
    id seq [B,U]. norm_by_times divides by sequence length (reference
    flag)."""
    enforce(not getattr(ctx, "packed", False),
            f"ctc layer {cfg.name}: packed sequence rows are not supported "
            "(the alpha recursion would align the concatenation of several "
            "sequences as one); feed this model unpacked")
    x, lab = ins[0], ins[1]
    enforce(x.mask is not None and lab.mask is not None,
            "ctc needs sequence inputs")
    blank = cfg.attr("blank", 0)
    ids = lab.value.astype(jnp.int32)
    if ids.ndim == 3:
        ids = ids[..., 0]
    nll = ctc_nll(x.value, ids, x.mask, lab.mask, blank)
    if cfg.attr("norm_by_times", False):
        nll = nll / jnp.maximum(x.mask.sum(-1), 1.0)
    coeff = cfg.attr("coeff", 1.0)
    return Arg((nll * coeff)[:, None])


@register_layer("warp_ctc", infer=_ctc_infer)
def _warp_ctc_layer(cfg, params, ins, ctx):
    """WarpCTCLayer: identical math on TPU (warp-ctc was a CUDA-side
    optimisation); kept as a distinct type for config parity — the
    reference's test_WarpCTCLayer asserts ctc == warp_ctc, which holds
    trivially here."""
    return _ctc_layer(cfg, params, ins, ctx)


def ctc_greedy_decode(logits, mask, blank=0):
    """Best-path decode: argmax per frame, collapse repeats, drop blanks.
    Returns dense ids [B, T] right-padded with -1 + validity mask."""
    ids = jnp.argmax(logits, axis=-1)                         # [B, T]
    prev = jnp.pad(ids, ((0, 0), (1, 0)), constant_values=-1)[:, :-1]
    keep = (ids != blank) & (ids != prev) & (mask > 0)
    order = jnp.argsort(~keep, axis=1, stable=True)
    compact = jnp.take_along_axis(jnp.where(keep, ids, -1), order, axis=1)
    out_mask = jnp.take_along_axis(keep.astype(jnp.float32), order, axis=1)
    return compact, out_mask


def _crf_err_infer(cfg, in_infos):
    return ArgInfo(size=1)


@register_layer("crf_error", infer=_crf_err_infer, params=_crf_params)
def _crf_error_layer(cfg, params, ins, ctx):
    """CRFDecodingLayer's error mode as its own registered type
    (REGISTER_LAYER(crf_error), reference Layer registry): viterbi-decode
    and emit the per-SEQUENCE mean tag error [B,1] against the label
    input — the chunk-error building block."""
    enforce(not getattr(ctx, "packed", False),
            f"crf_error layer {cfg.name}: packed sequence rows are not "
            "supported (viterbi would score transitions across packed "
            "boundaries); feed this model unpacked")
    emit, label = ins[0], ins[1]
    enforce(emit.mask is not None, "crf_error needs sequence input")
    tags, _score = crf_decode(emit.value, emit.mask, params["w0"])
    wrong = _step_tag_errors(tags, label.value, emit.mask)
    denom = jnp.maximum(emit.mask.sum(axis=-1), 1.0)
    return Arg((wrong.sum(axis=-1) / denom)[:, None])
