"""Pallas CTC forward-backward kernel.

TPU-native analog of warp-ctc's fused alpha/beta kernels
(paddle/cuda/src/hl_warpctc_wrap.cc wraps them for the reference;
WarpCTCLayer.cpp consumes): the whole time recursion runs in one kernel
with the [B, S] state resident in VMEM, T streamed in chunks — the
lax.scan formulation (layers/crf_ctc.py ctc_nll) pays a per-step
dispatch + HBM round trip that dominates at long T.

Decomposition: the class-axis gather (logp at the extended blank-
interleaved label sequence) happens OUTSIDE the kernel — autodiff
scatters cotangents back into the [B, T, C] logits through the
take_along_axis vjp, so the kernel sees only [T, B, S] gathered
emissions. Inside, custom-vjp forward-backward:

  forward : alpha recursion (3-term banded logaddexp), stash alphas,
            per-sequence log-likelihood off the stash
  backward: beta recursion in reverse + EXPLICIT posterior marginals
            d nll / d emit[t, s] = -exp(alpha + beta - ll)
            (the marginal form is numerically tighter than autodiff
            back through the logaddexp chain — the r4 parity gap of
            1.22e-3 came from exactly that chain)

Masked timesteps carry state in both directions, so padded batches are
exact. S (= 2U+1) pads to the lane width with -inf alpha; padded slots
produce exp() = 0 contributions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels._pallas_util import (NEG, compiler_params as
                                             _compiler_params, pad_T as
                                             _pad_T, round_up)

_CHUNK = 8


def _shift_right(x, k, fill):
    """x[..., s] -> x[..., s-k] (x shifted right along the last axis)."""
    pad = jnp.full(x.shape[:-1] + (k,), fill, x.dtype)
    return jnp.concatenate([pad, x[..., :-k]], axis=-1)


def _shift_left(x, k, fill):
    pad = jnp.full(x.shape[:-1] + (k,), fill, x.dtype)
    return jnp.concatenate([x[..., k:], pad], axis=-1)


def _fwd_kernel(em_ref, m_ref, skip_ref, ok_ref, alpha0_ref,
                alphas_ref, a_scr, *, C: int):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _():
        a_scr[:] = alpha0_ref[:]

    skip = skip_ref[:]                       # [B, S] 1.0 where s->s+2 legal
    ok = ok_ref[:]                           # [B, S] 1.0 inside 2*ulen+1
    a = a_scr[:]
    dt = a.dtype
    for k in range(C):
        t_global = s * C + k                 # dynamic (s is program_id)

        def step(a):
            em = em_ref[k].astype(dt)
            a1 = _shift_right(a, 1, NEG)
            a2 = jnp.where(skip > 0, _shift_right(a, 2, NEG), NEG)
            mx = jnp.maximum(jnp.maximum(a, a1), a2)
            mx_s = jnp.maximum(mx, -1e29)    # keep exp() finite on -inf rows
            nxt = mx + jnp.log(jnp.exp(a - mx_s) + jnp.exp(a1 - mx_s)
                               + jnp.exp(a2 - mx_s)) + em
            # all-dead states give log(0) = -inf; keep everything finite
            # (the mask-carry multiplies by 0, and 0 * -inf = NaN)
            return jnp.where(ok > 0, jnp.maximum(nxt, NEG), NEG)

        # t=0 is the initial alpha itself (alpha0 includes emission)
        a_new = step(a)
        m = m_ref[k].astype(dt)              # [B, 1]
        first = (t_global == 0).astype(dt)
        keep_prev = jnp.maximum(1.0 - m, first)   # masked OR t==0: carry
        a = keep_prev * a + (1.0 - keep_prev) * a_new
        alphas_ref[k] = a
    a_scr[:] = a


def _bwd_kernel(em_ref, m_ref, skip_ref, ok_ref, beta_init_ref,
                alphas_ref, ll_ref, demit_ref, b_scr, *, C: int):
    s = pl.program_id(0)                     # s=0 is the LAST chunk

    @pl.when(s == 0)
    def _():
        b_scr[:] = beta_init_ref[:]

    skip = skip_ref[:]
    ok = ok_ref[:]
    ll = ll_ref[:]                           # [B, 1]
    beta = b_scr[:]
    dt = beta.dtype
    for k in reversed(range(C)):
        m = m_ref[k].astype(dt)
        # beta here = log P(emissions t+1.. | state at t); at t the
        # posterior marginal is alpha_t + beta_t - ll
        alpha_t = alphas_ref[k]
        post = jnp.exp(jnp.clip(alpha_t + beta - ll, -80.0, 0.0))
        demit_ref[k] = -(post * m).astype(demit_ref.dtype)

        # recurse: beta_{t-1}[s] = LSE over next states {s, s+1, s+2}
        # of beta_t[s'] + em_t[s']  (em_t = emission at this t)
        em = em_ref[k].astype(dt)
        be = jnp.where(ok > 0, beta + em, NEG)
        b1 = _shift_left(be, 1, NEG)
        # s -> s+2 only when the TARGET can skip
        b2 = _shift_left(jnp.where(skip > 0, be, NEG), 2, NEG)
        mx = jnp.maximum(jnp.maximum(be, b1), b2)
        mx_s = jnp.maximum(mx, -1e29)
        prev = mx + jnp.log(jnp.exp(be - mx_s) + jnp.exp(b1 - mx_s)
                            + jnp.exp(b2 - mx_s))
        prev = jnp.where(ok > 0, jnp.maximum(prev, NEG), NEG)
        beta = m * prev + (1.0 - m) * beta
    b_scr[:] = beta


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ctc_fb(em, mask_tb, skip, ok, beta_init, interpret=False):
    """[B] negative log-likelihood from gathered extended emissions.

    em        [T, B, S] log p at extended labels (time-major)
    mask_tb   [T, B]    1.0 valid timestep
    skip      [B, S]    1.0 where the s-2 -> s transition is legal
    ok        [B, S]    1.0 inside the sequence's 2*ulen+1 states
    beta_init [B, S]    0.0 at the two terminal states, -inf elsewhere
    """
    nll, _ = _ctc_fb_fwd(em, mask_tb, skip, ok, beta_init, interpret)
    return nll


def _alphas(em, mask_tb, skip, ok, interpret):
    T, B, S = em.shape
    dt = jnp.promote_types(em.dtype, jnp.float32)   # f64 under x64 FD
    Tp = round_up(T, _CHUNK)
    em_p = _pad_T(em, Tp)
    m_p = _pad_T(mask_tb[..., None].astype(dt), Tp)
    # alpha0: emissions of the first frame at states 0 and 1
    a0 = jnp.where((jnp.arange(S)[None, :] < 2) & (ok > 0),
                   em[0].astype(dt), NEG)
    kernel = functools.partial(_fwd_kernel, C=_CHUNK)
    alphas = pl.pallas_call(
        kernel,
        name="ctc_fwd",
        grid=(Tp // _CHUNK,),
        in_specs=[
            pl.BlockSpec((_CHUNK, B, S), lambda s: (s, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_CHUNK, B, 1), lambda s: (s, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((B, S), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((B, S), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((B, S), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_CHUNK, B, S), lambda s: (s, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Tp, B, S), dt),
        scratch_shapes=[pltpu.VMEM((B, S), dt)],
        interpret=interpret,
        **_compiler_params(interpret),
    )(em_p, m_p, skip.astype(dt), ok.astype(dt), a0)
    return alphas, em_p, m_p


def _ctc_fb_fwd(em, mask_tb, skip, ok, beta_init, interpret):
    T, B, S = em.shape
    alphas, em_p, m_p = _alphas(em, mask_tb, skip, ok, interpret)
    # ll off the LAST VALID alpha: masked steps carry, so row T-1 holds it
    a_last = alphas[T - 1]                              # [B, S]
    terminal = jnp.where(beta_init > NEG / 2, a_last, NEG)
    mx = jnp.max(terminal, axis=-1, keepdims=True)
    mx_s = jnp.maximum(mx, -1e29)
    ll = (mx + jnp.log(jnp.exp(terminal - mx_s).sum(-1, keepdims=True)))
    nll = -ll[:, 0]
    return nll, (T, em_p, m_p, mask_tb, skip, ok, beta_init, alphas, ll)


def _ctc_fb_bwd(interpret, res, ct):
    T, em_p, m_p, mask_tb, skip, ok, beta_init, alphas, ll = res
    Tp, B, S = em_p.shape
    dt = alphas.dtype
    kernel = functools.partial(_bwd_kernel, C=_CHUNK)
    NC = Tp // _CHUNK
    rev = lambda s: (NC - 1 - s, 0, 0)
    demit = pl.pallas_call(
        kernel,
        name="ctc_bwd",
        grid=(NC,),
        in_specs=[
            pl.BlockSpec((_CHUNK, B, S), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((_CHUNK, B, 1), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((B, S), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((B, S), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((B, S), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_CHUNK, B, S), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((B, 1), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_CHUNK, B, S), rev,
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Tp, B, S), dt),
        scratch_shapes=[pltpu.VMEM((B, S), dt)],
        interpret=interpret,
        **_compiler_params(interpret),
    )(em_p, m_p, skip.astype(dt), ok.astype(dt),
      beta_init.astype(dt), alphas, ll)
    # d nll = ct * demit (ct is [B]); slice padding back off
    g = demit[:T] * ct[None, :, None]
    # cotangents carry each PRIMAL input's dtype (see crf.py note)
    return (g.astype(em_p.dtype), jnp.zeros((T, B), mask_tb.dtype),
            jnp.zeros_like(skip), jnp.zeros_like(ok),
            jnp.zeros_like(beta_init))


ctc_fb.defvjp(_ctc_fb_fwd, _ctc_fb_bwd)


def ctc_nll_pallas(logits, labels, in_mask, label_mask, blank=0,
                   interpret=False):
    """Drop-in for layers/crf_ctc.ctc_nll via the Pallas kernel.

    logits [B, T, C]; labels [B, U]; in_mask [B, T]; label_mask [B, U].
    Returns [B] NLL. The gather into the extended sequence and the
    log-softmax stay outside the kernel (autodiff routes the marginals
    back through them).
    """
    logp = jax.nn.log_softmax(logits, axis=-1)
    B0 = logits.shape[0]
    # sublane-pad B for the TPU kernel; dummy rows carry zero masks and
    # are sliced back off
    if not interpret and B0 % 8 != 0:
        Bp = -(-B0 // 8) * 8
        logp = jnp.pad(logp, ((0, Bp - B0), (0, 0), (0, 0)))
        labels = jnp.pad(labels, ((0, Bp - B0), (0, 0)))
        in_mask = jnp.pad(in_mask, ((0, Bp - B0), (0, 0)))
        label_mask = jnp.pad(label_mask, ((0, Bp - B0), (0, 0)))
    B, T, C = logp.shape
    U = labels.shape[1]
    S = 2 * U + 1
    # lane-pad S for the TPU kernel; padded states are never ok
    S_pad = S if interpret else round_up(S, 128)
    lab = labels.astype(jnp.int32)
    ext = jnp.full((B, S_pad), blank, jnp.int32)
    ext = ext.at[:, 1:S:2].set(lab)
    ulen = label_mask.sum(-1).astype(jnp.int32)
    slen = 2 * ulen + 1
    pos = jnp.arange(S_pad)[None, :]
    ok = pos < slen[:, None]
    ext_prev2 = jnp.pad(ext, ((0, 0), (2, 0)), constant_values=-1)[:, :S_pad]
    skip = (ext != blank) & (ext != ext_prev2) & ok
    # gather: [B, T, S_pad] emissions at extended labels -> time-major
    idx = jnp.broadcast_to(ext[:, None, :], (B, T, S_pad))
    em = jnp.take_along_axis(logp, idx, axis=-1)
    em = jnp.swapaxes(em, 0, 1)                          # [T, B, S]
    beta_init = jnp.where(
        (pos == jnp.maximum(slen - 1, 0)[:, None]) |
        ((pos == jnp.maximum(slen - 2, 0)[:, None]) & (slen >= 2)[:, None]),
        0.0, NEG)
    # float carriers: custom_vjp wants float cotangents for every input
    nll = ctc_fb(em, jnp.swapaxes(in_mask, 0, 1).astype(logp.dtype),
                 skip.astype(logp.dtype), ok.astype(logp.dtype),
                 beta_init.astype(logp.dtype), interpret)
    return nll[:B0]
