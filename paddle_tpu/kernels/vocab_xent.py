"""Fused vocab-projection + softmax cross-entropy Pallas kernel.

The NMT step's dominant cost is the [B*T, H] @ [H, V] vocab projection
plus its softmax-xent: even with the r4 DCE fusion (logits stay, probs
die), the [B*T, V] LOGITS still materialize in HBM (460 MB/step at
B*T=7680, V=30k bf16) and are re-read by the loss and the backward.
This kernel never materializes them — a flash-attention-style ONLINE
log-sum-exp over vocabulary chunks:

  fwd    : grid (rows, V) — logits chunk lives in VMEM only; running
           (max, sumexp) per row + one-hot gather of the gold logit;
           emits nll = lse - gold and lse (for the backward)
  bwd    : two kernels, each recomputing the chunk — dx with rows
           outer / V inner, dW/db with V outer / rows inner — so every
           accumulator spans only CONSECUTIVE grid steps (the
           guaranteed-VMEM-resident Pallas reduction pattern).

MEASURED OUTCOME (r5, v5e, NMT shapes N=7680 D=512 V=30k bf16): a WASH —
9.6-10.2 ms fwd+bwd for both this kernel and the XLA baseline
(projection + lse-gather xent), across two sessions. XLA's pipeline is
already at the same roofline; the flash-style recompute exactly offsets
the saved [N, V] materialization at this arithmetic intensity. Kept as
a correctness-proven (grads == baseline to 2e-7 on silicon) LIBRARY
function — not wired into any layer path — and a documented negative
result — the r4 DCE softmax fusion
remains the production path. Reference analog: the reference pays the
full materialization (fc + softmax + cross-entropy separate layers,
gserver/layers/CostLayer.cpp).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels._pallas_util import (NEG, compiler_params as
                                             _compiler_params, round_up)

_ROWS = 256          # rows per block (sublane multiple)
_VC = 2048           # vocab chunk (lane multiple)


def _chunk_logits(x_ref, w_ref, b_ref, vc, *, V, VC):
    acc_dt = b_ref.dtype        # the accumulate dtype rides the bias
    logits = jax.lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=acc_dt) + b_ref[0]
    col = vc * VC + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    return jnp.where(col < V, logits, NEG), col


def _fwd_kernel(x_ref, w_ref, b_ref, lab_ref, nll_ref, lse_ref,
                m_scr, l_scr, g_scr, *, V: int, VC: int):
    vc = pl.program_id(1)

    @pl.when(vc == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        g_scr[:] = jnp.zeros_like(g_scr)

    logits, col = _chunk_logits(x_ref, w_ref, b_ref, vc, V=V, VC=VC)
    m_prev = m_scr[:]                              # [R, 1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    l_scr[:] = l_scr[:] * jnp.exp(m_prev - m_new) + \
        jnp.exp(logits - m_new).sum(axis=-1, keepdims=True)
    m_scr[:] = m_new

    lab = lab_ref[:].astype(jnp.int32)             # [R, 1]
    oh = (col == lab).astype(logits.dtype)
    g_scr[:] = g_scr[:] + (logits * oh).sum(axis=-1, keepdims=True)

    @pl.when(vc == pl.num_programs(1) - 1)
    def _():
        lse = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))
        lse_ref[:] = lse
        nll_ref[:] = lse - g_scr[:]


def _dlog(x_ref, w_ref, b_ref, lab_ref, lse_ref, ct_ref, vc, *, V, VC):
    logits, col = _chunk_logits(x_ref, w_ref, b_ref, vc, V=V, VC=VC)
    p = jnp.exp(logits - lse_ref[:])
    oh = (col == lab_ref[:].astype(jnp.int32)).astype(logits.dtype)
    return (p - oh) * ct_ref[:]                    # [R, VC]


def _bwd_dx_kernel(x_ref, w_ref, b_ref, lab_ref, lse_ref, ct_ref,
                   dx_ref, dx_scr, *, V: int, VC: int):
    """dx backward: grid (rows outer, V inner) — the accumulator spans
    only CONSECUTIVE V steps, the guaranteed-VMEM-resident Pallas
    reduction pattern (an aliased-in/out dx variant measured the same
    and relied on revisit-refetch semantics that are NOT guaranteed for
    constant block indices — reverted after review)."""
    vc = pl.program_id(1)

    @pl.when(vc == 0)
    def _():
        dx_scr[:] = jnp.zeros_like(dx_scr)

    dlog = _dlog(x_ref, w_ref, b_ref, lab_ref, lse_ref, ct_ref, vc,
                 V=V, VC=VC)
    w = w_ref[:]
    dx_scr[:] = dx_scr[:] + jax.lax.dot_general(
        dlog.astype(w.dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=dx_scr.dtype)

    @pl.when(vc == pl.num_programs(1) - 1)
    def _():
        dx_ref[:] = dx_scr[:].astype(dx_ref.dtype)


def _bwd_dw_kernel(x_ref, w_ref, b_ref, lab_ref, lse_ref, ct_ref,
                   dw_ref, db_ref, dw_scr, db_scr, *, V: int, VC: int):
    """dW/db backward: grid (V outer, rows inner) — accumulators span
    consecutive row steps in VMEM."""
    vc = pl.program_id(0)
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _():
        dw_scr[:] = jnp.zeros_like(dw_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    dlog = _dlog(x_ref, w_ref, b_ref, lab_ref, lse_ref, ct_ref, vc,
                 V=V, VC=VC)
    x = x_ref[:]
    dw_scr[:] = dw_scr[:] + jax.lax.dot_general(
        x, dlog.astype(x.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=dw_scr.dtype)
    db_scr[:] = db_scr[:] + dlog.sum(axis=0, keepdims=True)

    @pl.when(r == pl.num_programs(1) - 1)
    def _():
        dw_ref[:] = dw_scr[:].astype(dw_ref.dtype)
        db_ref[:] = db_scr[:].astype(db_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def vocab_xent(x, w, b, labels, interpret=False):
    """Per-row softmax-xent NLL of x @ w + b against labels.

    x [N, D] (bf16/f32); w [D, V]; b [V]; labels [N] — a FLOAT carrier
    of integer ids (custom_vjp wants float cotangents; exact < 2^24).
    Returns nll [N] f32 without materializing the [N, V] logits.
    """
    nll, _ = _fwd(x, w, b, labels, interpret)
    return nll


def _pads(x, w, b, labels):
    N, D = x.shape
    V = w.shape[1]
    Np = round_up(N, _ROWS)
    Vp = round_up(V, _VC)
    if Np != N:
        x = jnp.pad(x, ((0, Np - N), (0, 0)))
        labels = jnp.pad(labels, (0, Np - N))
    if Vp != V:
        w = jnp.pad(w, ((0, 0), (0, Vp - V)))
        b = jnp.pad(b, (0, Vp - V))
    return x, w, b, labels, N, V, Np, Vp


def _row_spec():
    return pl.BlockSpec((_ROWS, 1), lambda r, v: (r, 0),
                        memory_space=pltpu.VMEM)


def _fwd(x, w, b, labels, interpret):
    x_p, w_p, b_p, lab_p, N, V, Np, Vp = _pads(x, w, b, labels)
    D = x.shape[1]
    dt = jnp.promote_types(x.dtype, jnp.float32)
    kernel = functools.partial(_fwd_kernel, V=V, VC=_VC)
    nll, lse = pl.pallas_call(
        kernel,
        name="vocab_xent_fwd",
        grid=(Np // _ROWS, Vp // _VC),
        in_specs=[
            pl.BlockSpec((_ROWS, D), lambda r, v: (r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((D, _VC), lambda r, v: (0, v),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _VC), lambda r, v: (0, v),
                         memory_space=pltpu.VMEM),
            _row_spec(),
        ],
        out_specs=[_row_spec(), _row_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((Np, 1), dt),
            jax.ShapeDtypeStruct((Np, 1), dt),
        ],
        scratch_shapes=[pltpu.VMEM((_ROWS, 1), dt)] * 3,
        interpret=interpret,
        **_compiler_params(interpret),
    )(x_p, w_p.astype(x.dtype), b_p.astype(dt)[None, :],
      lab_p.astype(dt)[:, None])
    # residuals carry the UNPADDED lse ([N], matching x/labels): _vjp_bwd
    # re-pads it with the +1e4 guard value, so padded rows' p underflows
    # to 0 instead of seeing the forward-computed lse of zero rows
    # (ADVICE r5 item 1 — the padded-length residual made the bwd re-pad
    # a shape-corrupting no-op)
    return nll[:N, 0], (x, w, b, labels, lse[:N, 0])


def _vjp_fwd(x, w, b, labels, interpret):
    return _fwd(x, w, b, labels, interpret)


def _vjp_bwd(interpret, res, ct):
    x, w, b, labels, lse = res
    x_p, w_p, b_p, lab_p, N, V, Np, Vp = _pads(x, w, b, labels)
    D = x.shape[1]
    dt = jnp.promote_types(x.dtype, jnp.float32)
    lab_col = lab_p.astype(dt)[:, None]
    # pad lse with +1e4 so padded rows' p = exp(b - 1e4) underflows to 0;
    # a zero (or forward-computed softmax-of-bias) lse on padded rows
    # would give p = exp(b - lse), and a bias >= ~88 then reaches
    # inf * 0 = NaN through dW/db. The residual lse is the UNPADDED [N]
    # (see _fwd), so this pad genuinely covers rows N..Np.
    lse_col = jnp.pad(lse, (0, Np - N), constant_values=1e4)[:, None]
    # padded rows must contribute nothing: zero cotangent kills dlog
    ct_col = jnp.pad(ct.astype(dt), (0, Np - N))[:, None]
    w_cast = w_p.astype(x.dtype)
    b_row = b_p.astype(dt)[None, :]

    common_specs = [
        pl.BlockSpec((_ROWS, D), None, memory_space=pltpu.VMEM),
        pl.BlockSpec((D, _VC), None, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, _VC), None, memory_space=pltpu.VMEM),
        pl.BlockSpec((_ROWS, 1), None, memory_space=pltpu.VMEM),
        pl.BlockSpec((_ROWS, 1), None, memory_space=pltpu.VMEM),
        pl.BlockSpec((_ROWS, 1), None, memory_space=pltpu.VMEM),
    ]

    def with_maps(maps):
        out = []
        for spec, m in zip(common_specs, maps):
            out.append(pl.BlockSpec(spec.block_shape, m,
                                    memory_space=pltpu.VMEM))
        return out

    rmap = lambda r, v: (r, 0)
    vmap_ = lambda r, v: (0, v)
    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, V=V, VC=_VC),
        name="vocab_xent_bwd_dx",
        grid=(Np // _ROWS, Vp // _VC),
        in_specs=with_maps([rmap, vmap_, vmap_, rmap, rmap, rmap]),
        out_specs=pl.BlockSpec((_ROWS, D), lambda r, v: (r, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Np, D), dt),
        scratch_shapes=[pltpu.VMEM((_ROWS, D), dt)],
        interpret=interpret,
        **_compiler_params(interpret),
    )(x_p, w_cast, b_row, lab_col, lse_col, ct_col)

    vr_r = lambda v, r: (r, 0)
    vr_v = lambda v, r: (0, v)
    dw, db = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, V=V, VC=_VC),
        name="vocab_xent_bwd_dw",
        grid=(Vp // _VC, Np // _ROWS),
        in_specs=with_maps([vr_r, vr_v, vr_v, vr_r, vr_r, vr_r]),
        out_specs=[
            pl.BlockSpec((D, _VC), lambda v, r: (0, v),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _VC), lambda v, r: (0, v),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((D, Vp), dt),
            jax.ShapeDtypeStruct((1, Vp), dt),
        ],
        scratch_shapes=[pltpu.VMEM((D, _VC), dt),
                        pltpu.VMEM((1, _VC), dt)],
        interpret=interpret,
        **_compiler_params(interpret),
    )(x_p, w_cast, b_row, lab_col, lse_col, ct_col)

    return (dx[:N].astype(x.dtype), dw[:, :V].astype(w.dtype),
            db[0, :V].astype(b.dtype), jnp.zeros_like(labels))


vocab_xent.defvjp(_vjp_fwd, _vjp_bwd)
