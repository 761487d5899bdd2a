"""Pallas linear-chain CRF forward-backward kernel.

TPU-native analog of the reference's hand-written forward/backward
recursions (paddle/gserver/layers/LinearChainCRF.cpp:28-180 calcAlpha/
calcBeta/grad): the whole time loop runs in one kernel with the [B, L]
state and the [L, L] transition matrix resident in VMEM.

The per-step LSE-over-transitions is phrased as an MXU matmul of
bounded exponentials (factor out the per-row max so every exp() <= 1):

    alpha_t = log( exp(alpha_{t-1} - mx_b) @ exp(trans - mt) )
              + mx_b + mt + emit_t

and the backward computes EXPLICIT posterior marginals — unary for
d emit (and d start / d end), pairwise for d trans, where the pairwise
sum over (t, b) is itself one MXU matmul per step of two bounded
exponential factors:

    dtrans = exp(trans) * sum_t  exp(alpha_{t-1} - s_b)^T
                               @ exp(emit_t + beta_t - logZ + s_b)

with s_b = max_i alpha_{t-1}[b, i] (first factor <= 1; the second's
exponent is bounded by -min trans — see the in-kernel clip note).

Masked timesteps carry both recursions, so padded batches are exact.
The NLL's gold-path score half stays in plain jnp (cheap gathers,
autodiff exact) — only the partition function runs here.
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


def _fwd_kernel(em_ref, m_ref, trans_ref, a0_ref, alphas_ref, a_scr,
                *, C: int):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _():
        a_scr[:] = a0_ref[:]

    trans = trans_ref[:].astype(a_scr.dtype)
    mt = jnp.max(trans)
    etr = jnp.exp(trans - mt)
    a = a_scr[:]
    dt = a.dtype
    for k in range(C):
        t_global = s * C + k

        em = em_ref[k].astype(dt)
        mx = jnp.max(a, axis=-1, keepdims=True)              # [B, 1]
        prod = jax.lax.dot_general(jnp.exp(a - mx), etr,
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=dt,
                                   precision=jax.lax.Precision.HIGHEST)
        # floor prod at a NORMAL f32 (the TPU flushes subnormals: a
        # 1e-38 floor becomes log(0) = -inf, and the blend below would
        # produce 0 * inf = NaN — the r5 silicon bug)
        nxt = jnp.log(jnp.maximum(prod, 1e-30)) + mx + mt + em
        m = m_ref[k].astype(dt)
        first = (t_global == 0).astype(dt)
        keep_prev = jnp.maximum(1.0 - m, first)              # t=0: a0 IS alpha_0
        a = jnp.where(keep_prev > 0, a, nxt)    # select, not blend: inf-safe
        alphas_ref[k] = a
    a_scr[:] = a


def _bwd_kernel(em_ref, m_ref, trans_ref, end_ref, logz_ref, ct_ref,
                alphas_ref, alphas_prev_ref,
                demit_ref, acc_ref, b_scr, acc_scr, *, C: int):
    s = pl.program_id(0)                        # s=0 is the LAST chunk

    @pl.when(s == 0)
    def _():
        b_scr[:] = jnp.broadcast_to(end_ref[:], b_scr.shape)  # beta_{T-1}
        acc_scr[:] = jnp.zeros_like(acc_scr)

    trans = trans_ref[:].astype(b_scr.dtype)
    mt = jnp.max(trans)
    etr_T = jnp.exp(trans - mt).T               # for the beta recursion
    logz = logz_ref[:]                          # [B, 1]
    beta = b_scr[:]
    acc = acc_scr[:]
    dt = beta.dtype
    for k in reversed(range(C)):
        m = m_ref[k].astype(dt)
        em = em_ref[k].astype(dt)
        alpha_t = alphas_ref[k]
        # unary posterior at t (beta excludes em_t; alpha includes it)
        post = jnp.exp(jnp.clip(alpha_t + beta - logz, -80.0, 0.0))
        demit_ref[k] = (post * m).astype(demit_ref.dtype)

        # pairwise marginal accumulation (t>=1 transitions only). The
        # first factor's exponent is <= 0 by the s_b shift; the second's
        # is bounded by -trans[argmax_alpha, j] (the full marginal
        # alpha+trans+em+beta-logZ is <= 0, so em+beta-logZ+s_b <=
        # -trans at the max row) — POSITIVE for disfavored transitions,
        # so it must NOT be clamped at 0 (r5 review: a 0-cap truncated
        # d_trans to ~0 exactly where transitions are most negative).
        # +/-80 keeps exp() finite for any sane |trans| < 80.
        a_prev = alphas_prev_ref[k]             # alpha_{t-1}; NEG at t==0
        s_b = jnp.max(a_prev, axis=-1, keepdims=True)
        s_b = jnp.maximum(s_b, -1e29)
        ea = jnp.exp(a_prev - s_b) * m          # masked steps contribute 0
        # the [B] cotangent of logz rides the second factor (outside the
        # exp, so sign/scale are free)
        eb = jnp.exp(jnp.clip(em + beta - logz + s_b, -80.0, 80.0)) \
            * ct_ref[:].astype(dt)
        acc = acc + jax.lax.dot_general(ea, eb, (((0,), (0,)), ((), ())),
                                        preferred_element_type=dt,
                                        precision=jax.lax.Precision.HIGHEST)

        # beta_{t-1}[i] = LSE_j trans[i,j] + em_t[j] + beta_t[j]
        v = em + beta
        mx = jnp.max(v, axis=-1, keepdims=True)
        prod = jax.lax.dot_general(jnp.exp(v - mx), etr_T,
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=dt,
                                   precision=jax.lax.Precision.HIGHEST)
        prev = jnp.log(jnp.maximum(prod, 1e-30)) + mx + mt
        beta = jnp.where(m > 0, prev, beta)     # select, not blend: inf-safe
    b_scr[:] = beta
    acc_scr[:] = acc

    @pl.when(s == pl.num_programs(0) - 1)
    def _():
        acc_ref[:] = acc.astype(acc_ref.dtype)


_TRANS_BOUND = 80.0


def _check_trans_bound(trans):
    """Eager-path guard for the backward's exponent clip: the pairwise-
    marginal kernel bounds its exponents at +/-80 (see _bwd_kernel), which
    is exact only while every |trans| < 80. Warn when a CONCRETE
    transition matrix violates it; traced values (inside jit) skip the
    check — the bound is documented at the API instead. NEG-magnitude
    entries are lane-padding sentinels (crf_logz_pallas pads dead states
    with NEG; their marginals are exactly zero) and are ignored."""
    import warnings

    if isinstance(trans, jax.core.Tracer):
        return
    try:
        a = jnp.abs(trans)
        mx = float(jnp.max(jnp.where(a >= -NEG / 2, 0.0, a)))
    except Exception:
        return
    if mx >= _TRANS_BOUND:
        warnings.warn(
            f"crf_logz: max |trans| = {mx:.1f} >= {_TRANS_BOUND:.0f}; the "
            "backward's exponent clip truncates pairwise marginals beyond "
            "this bound, so d_trans may be inexact. Rescale or regularise "
            "the transition weights (|trans| < 80 is the supported range).",
            RuntimeWarning, stacklevel=3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def crf_logz(em, mask_tb, start, end, trans, interpret=False):
    """[B] log partition function of a linear-chain CRF.

    em [T, B, L] time-major emissions; mask_tb [T, B]; start/end [L];
    trans [L, L]. Differentiable in all float inputs via explicit
    forward-backward marginals.

    Numerical bound: the backward pass clips its pairwise-marginal
    exponents at +/-80 (see the in-kernel note in _bwd_kernel), which is
    exact only for ``max |trans| < 80`` — transition magnitudes at or
    beyond 80 silently truncate d_trans. Trained CRF transition weights
    sit orders of magnitude below this; a concrete (non-traced) call
    that violates the bound raises a RuntimeWarning.
    """
    _check_trans_bound(trans)
    logz, _ = _crf_fwd(em, mask_tb, start, end, trans, interpret)
    return logz


def _alpha_call(em, mask_tb, start, trans, interpret):
    T, B, L = em.shape
    dt = jnp.promote_types(em.dtype, jnp.float32)
    Tp = round_up(T, _CHUNK)
    em_p = _pad_T(em, Tp)
    m_p = _pad_T(mask_tb[..., None].astype(dt), Tp)
    a0 = (start[None, :] + em[0]).astype(dt)
    kernel = functools.partial(_fwd_kernel, C=_CHUNK)
    alphas = pl.pallas_call(
        kernel,
        name="crf_fwd",
        grid=(Tp // _CHUNK,),
        in_specs=[
            pl.BlockSpec((_CHUNK, B, L), lambda s: (s, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_CHUNK, B, 1), lambda s: (s, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((L, L), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((B, L), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_CHUNK, B, L), lambda s: (s, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Tp, B, L), dt),
        scratch_shapes=[pltpu.VMEM((B, L), dt)],
        interpret=interpret,
        **_compiler_params(interpret),
    )(em_p, m_p, trans.astype(dt), a0)
    return alphas, em_p, m_p


def _crf_fwd(em, mask_tb, start, end, trans, interpret):
    T, B, L = em.shape
    alphas, em_p, m_p = _alpha_call(em, mask_tb, start, trans, interpret)
    a_last = alphas[T - 1]
    terminal = a_last + end[None, :]
    mx = jnp.max(terminal, axis=-1, keepdims=True)
    logz = (mx + jnp.log(jnp.exp(terminal - mx).sum(-1, keepdims=True)))
    return logz[:, 0], (T, em_p, mask_tb, start, end, trans, alphas, logz,
                        m_p)


def _crf_bwd(interpret, res, ct):
    T, em_p, mask_tb, start, end, trans, alphas, logz, m_p = res
    Tp, B, L = em_p.shape
    dt = alphas.dtype
    NC = Tp // _CHUNK
    rev = lambda s: (NC - 1 - s, 0, 0)
    neg_row = jnp.full((1, B, L), NEG, dt)
    alphas_prev = jnp.concatenate([neg_row, alphas[:-1]], axis=0)
    kernel = functools.partial(_bwd_kernel, C=_CHUNK)
    demit, acc = pl.pallas_call(
        kernel,
        name="crf_bwd",
        grid=(NC,),
        in_specs=[
            pl.BlockSpec((_CHUNK, B, L), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((_CHUNK, B, 1), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((L, L), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, L), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((B, 1), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((B, 1), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_CHUNK, B, L), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((_CHUNK, B, L), rev, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((_CHUNK, B, L), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((L, L), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, B, L), dt),
            jax.ShapeDtypeStruct((L, L), dt),
        ],
        scratch_shapes=[pltpu.VMEM((B, L), dt), pltpu.VMEM((L, L), dt)],
        interpret=interpret,
        **_compiler_params(interpret),
    )(em_p, m_p, trans.astype(dt), end[None, :].astype(dt), logz,
      ct.astype(dt)[:, None], alphas, alphas_prev)
    # ct: [B] cotangent of logz (unary parts apply it outside; the
    # pairwise accumulator already carries it)
    ctb = ct[None, :, None]
    d_em = (demit[:T] * ctb).astype(em_p.dtype)
    # d start = unary posterior at t=0; d end = posterior at the last
    # valid step = exp(alpha_last + end - logz)
    d_start = (demit[0] * ct[:, None]).sum(0)
    a_last = alphas[T - 1]
    post_end = jnp.exp(jnp.clip(a_last + end[None, :] - logz, -80.0, 0.0))
    d_end = (post_end * ct[:, None]).sum(0)
    d_trans = (acc * jnp.exp(trans.astype(dt))).astype(trans.dtype)
    # cotangents must carry each PRIMAL input's dtype (bf16 emissions
    # with f32 weights otherwise crash the downstream add of tangents)
    return (d_em, jnp.zeros((T, B), mask_tb.dtype),
            d_start.astype(start.dtype), d_end.astype(end.dtype), d_trans)


crf_logz.defvjp(_crf_fwd, _crf_bwd)
