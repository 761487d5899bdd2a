"""Fused GRU recurrence as a Pallas TPU kernel.

Same design as kernels/lstm.py (the hl_gpu_lstm.cuh-style whole-loop
fusion, cuDNN-style activation stashing): the recurrent matrices stay
VMEM-resident across the scan, each timestep costs two MXU matmuls +
VPU gate math, and the backward kernel walks the grid in reverse
accumulating dWg/dWc/db in VMEM scratch. The lax.scan formulation
re-reads both weight matrices from HBM every tick and pays the scan's
dynamic-slice machinery — profiled on the NMT encoder (r4, not
re-measured).

Cell semantics match layers/recurrent.py gru_cell exactly (reference
GruCompute / GruLayer): gates [z, r] from x[:, :2H] + h@Wg, candidate
tanh(x[:, 2H:] + (r*h)@Wc), h' = z*h + (1-z)*c, mask-gated carry.

Sequence packing (docs/packing.md): like kernels/lstm.py, an optional
segment-start ``reset`` vector zeroes the h carry entering the first
valid step of each packed segment; ``reset=None`` traces the exact
pre-packing program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_CHUNK = 8
_CHUNK_BWD = 4


def _vmem_estimate_bytes(B: int, H: int) -> int:
    blk = _CHUNK_BWD * B * 3 * H * 2
    blocks = 9 * blk
    w = H * 3 * H * (2 + 4 + 4)     # Wg+Wc bf16 + dW f32 scratch + out
    return blocks + w


def fused_gru_supported(B: int, H: int) -> bool:
    return H % 128 == 0 and B % 8 == 0 and \
        _vmem_estimate_bytes(B, H) < 64 * 1024 * 1024


from paddle_tpu.kernels._pallas_util import (  # noqa: E402
    compiler_params as _compiler_params)


def _sig(x):
    return jax.nn.sigmoid(x)


def _cell_fwd(x3, h_prev, m, wg, wc, b, H):
    xf = x3.astype(jnp.float32)
    g = xf[:, :2 * H] + jnp.dot(h_prev.astype(wg.dtype), wg,
                                preferred_element_type=jnp.float32)
    g = g + b[:2 * H]
    z = _sig(g[:, :H])
    r = _sig(g[:, H:])
    rh = r * h_prev
    c = jnp.tanh(xf[:, 2 * H:] + jnp.dot(rh.astype(wc.dtype), wc,
                                         preferred_element_type=jnp.float32)
                 + b[2 * H:])
    h_new = z * h_prev + (1.0 - z) * c
    h = m * h_new + (1.0 - m) * h_prev
    return h, z, r, c


def _fwd_kernel(x3_ref, wg_ref, wc_ref, b_ref, m_ref, *rest, H: int, C: int,
                R: bool = False):
    if R:
        r_ref, hs_ref, gates_ref, h_scr = rest
    else:
        r_ref = None
        hs_ref, gates_ref, h_scr = rest
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _():
        h_scr[:] = jnp.zeros_like(h_scr)

    wg = wg_ref[:]
    wc = wc_ref[:]
    b = b_ref[0].astype(jnp.float32)
    h = h_scr[:]
    for k in range(C):
        m = m_ref[k].astype(jnp.float32)             # [B, 1]
        if R:
            # segment-start reset (reset <= mask): zero the carry where a
            # new packed sequence begins
            h = (1.0 - r_ref[k].astype(jnp.float32)) * h
        h, z, r, c = _cell_fwd(x3_ref[k], h, m, wg, wc, b, H)
        hs_ref[k] = h.astype(hs_ref.dtype)
        gates_ref[k] = jnp.concatenate([z, r, c], axis=-1).astype(
            gates_ref.dtype)
    h_scr[:] = h


def _bwd_kernel(wg_ref, wc_ref, m_ref, *rest, H: int, C: int,
                R: bool = False):
    # packed mode (R): hs_prev arrives pre-multiplied by (1-reset) — the
    # effective state the forward consumed — so cell-local grads and the
    # dW accumulations are unchanged; only the carry handed to step t-1
    # is gated by (1-reset) at the end of each step.
    if R:
        (r_ref, gates_ref, hs_prev_ref, ghs_ref,
         dx3_ref, dwg_ref, dwc_ref, db_ref,
         dh_scr, dwg_scr, dwc_scr, db_scr) = rest
    else:
        r_ref = None
        (gates_ref, hs_prev_ref, ghs_ref,
         dx3_ref, dwg_ref, dwc_ref, db_ref,
         dh_scr, dwg_scr, dwc_scr, db_scr) = rest
    s = pl.program_id(0)                             # s=0 is the LAST chunk

    @pl.when(s == 0)
    def _():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dwg_scr[:] = jnp.zeros_like(dwg_scr)
        dwc_scr[:] = jnp.zeros_like(dwc_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    wg = wg_ref[:]
    wc = wc_ref[:]
    dh = dh_scr[:]
    dwg_acc = dwg_scr[:]
    dwc_acc = dwc_scr[:]
    for k in reversed(range(C)):
        m = m_ref[k].astype(jnp.float32)
        dh_t = ghs_ref[k].astype(jnp.float32) + dh
        dh_new = m * dh_t
        dh_pass = (1.0 - m) * dh_t

        gates = gates_ref[k].astype(jnp.float32)
        z = gates[:, :H]
        r = gates[:, H:2 * H]
        c = gates[:, 2 * H:]
        h_prev = hs_prev_ref[k].astype(jnp.float32)

        dz = dh_new * (h_prev - c)
        dc_pre = dh_new * (1.0 - z) * (1.0 - c * c)
        drh = jax.lax.dot_general(
            dc_pre.astype(wc.dtype), wc, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dz_pre = dz * z * (1.0 - z)
        dr_pre = (drh * h_prev) * r * (1.0 - r)
        dg = jnp.concatenate([dz_pre, dr_pre], axis=-1)      # [B, 2H]
        dh = (dh_new * z + drh * r + dh_pass
              + jax.lax.dot_general(
                  dg.astype(wg.dtype), wg, (((1,), (1,)), ((), ())),
                  preferred_element_type=jnp.float32))
        if R:
            dh = (1.0 - r_ref[k].astype(jnp.float32)) * dh
        dwg_acc = dwg_acc + jax.lax.dot_general(
            h_prev.astype(wg.dtype), dg.astype(wg.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dwc_acc = dwc_acc + jax.lax.dot_general(
            (r * h_prev).astype(wc.dtype), dc_pre.astype(wc.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dpre3 = jnp.concatenate([dg, dc_pre], axis=-1)       # [B, 3H]
        db_scr[0:1, :] = db_scr[0:1, :] + dpre3.sum(axis=0, keepdims=True)
        dx3_ref[k] = dpre3.astype(dx3_ref.dtype)

    dh_scr[:] = dh
    dwg_scr[:] = dwg_acc
    dwc_scr[:] = dwc_acc

    @pl.when(s == pl.num_programs(0) - 1)
    def _():
        dwg_ref[:] = dwg_acc.astype(dwg_ref.dtype)
        dwc_ref[:] = dwc_acc.astype(dwc_ref.dtype)
        db_ref[:] = db_scr[:].astype(db_ref.dtype)


def _fwd_call(x3_tm, wg, wc, b, mask_tm, reset_tm, interpret):
    T, B, H3 = x3_tm.shape
    H = H3 // 3
    C = _CHUNK
    assert T % C == 0
    dt = x3_tm.dtype
    R = reset_tm is not None
    kernel = functools.partial(_fwd_kernel, H=H, C=C, R=R)
    maybe_reset = ([pl.BlockSpec((C, B, 1), lambda s: (s, 0, 0),
                                 memory_space=pltpu.VMEM)] if R else [])
    return pl.pallas_call(
        kernel,
        name="fused_gru_fwd",
        grid=(T // C,),
        in_specs=[
            pl.BlockSpec((C, B, H3), lambda s: (s, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((H, 2 * H), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((H, H), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 3 * H), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, 1), lambda s: (s, 0, 0),
                         memory_space=pltpu.VMEM),
            *maybe_reset,
        ],
        out_specs=[
            pl.BlockSpec((C, B, H), lambda s: (s, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, H3), lambda s: (s, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H), dt),             # hs
            jax.ShapeDtypeStruct((T, B, H3), dt),            # z|r|c stash
        ],
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32)],
        interpret=interpret,
        **_compiler_params(interpret),
    )(x3_tm, wg, wc, b, mask_tm, *([reset_tm] if R else []))


def _bwd_call(wg, wc, mask_tm, reset_tm, gates, hs_prev, g_hs, interpret):
    T, B, H3 = gates.shape
    H = H3 // 3
    C = _CHUNK_BWD
    assert T % C == 0
    NC = T // C
    dt = g_hs.dtype
    R = reset_tm is not None
    kernel = functools.partial(_bwd_kernel, H=H, C=C, R=R)
    rev = lambda s: (NC - 1 - s, 0, 0)
    maybe_reset = ([pl.BlockSpec((C, B, 1), rev, memory_space=pltpu.VMEM)]
                   if R else [])
    return pl.pallas_call(
        kernel,
        name="fused_gru_bwd",
        grid=(NC,),
        in_specs=[
            pl.BlockSpec((H, 2 * H), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((H, H), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, 1), rev, memory_space=pltpu.VMEM),
            *maybe_reset,
            pl.BlockSpec((C, B, H3), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((C, B, H3), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((H, 2 * H), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((H, H), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 3 * H), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H3), dt),            # dx3
            jax.ShapeDtypeStruct((H, 2 * H), wg.dtype),      # dWg
            jax.ShapeDtypeStruct((H, H), wc.dtype),          # dWc
            jax.ShapeDtypeStruct((1, 3 * H), jnp.float32),   # dbias
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((H, 2 * H), jnp.float32),
            pltpu.VMEM((H, H), jnp.float32),
            pltpu.VMEM((1, 3 * H), jnp.float32),
        ],
        interpret=interpret,
        **_compiler_params(interpret),
    )(wg, wc, mask_tm, *([reset_tm] if R else []), gates, hs_prev, g_hs)


def _pad_time(x_tm, T_pad):
    T = x_tm.shape[0]
    if T == T_pad:
        return x_tm
    pad = [(0, T_pad - T)] + [(0, 0)] * (x_tm.ndim - 1)
    return jnp.pad(x_tm, pad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def fused_gru(x3, wg, wc, bias, mask, reset=None, interpret=False):
    """Fused GRU over a padded batch.

    x3    [B, T, 3H]  pre-projected input ([z-gate | r-gate | candidate])
    wg    [H, 2H]     gate recurrent weights
    wc    [H, H]      candidate recurrent weights
    bias  [3H]        (pass zeros when bias-free)
    mask  [B, T]      1.0 valid / 0.0 padding
    reset [B, T]|None segment-start resets for packed rows (1.0 zeroes the
                      incoming h carry; reset <= mask). None = pre-packing
                      program, no reset refs traced.
    Returns hs [B, T, H] (not mask-multiplied — carries hold)."""
    return _fwd_res(x3, wg, wc, bias, mask, reset, interpret)[0]


def _fwd_res(x3, wg, wc, bias, mask, reset, interpret):
    B, T, H3 = x3.shape
    T_pad = -(-T // _CHUNK) * _CHUNK
    x3_tm = _pad_time(jnp.swapaxes(x3, 0, 1), T_pad)
    m_tm = _pad_time(jnp.swapaxes(mask, 0, 1)[..., None].astype(jnp.bfloat16),
                     T_pad)
    r_tm = None if reset is None else _pad_time(
        jnp.swapaxes(reset, 0, 1)[..., None].astype(jnp.bfloat16), T_pad)
    hs_tm, gates = _fwd_call(x3_tm, wg, wc, bias[None, :], m_tm, r_tm,
                             interpret)
    return jnp.swapaxes(hs_tm[:T], 0, 1), gates, hs_tm, m_tm, r_tm


def _fused_gru_fwd(x3, wg, wc, bias, mask, reset, interpret):
    hs, gates, hs_tm, m_tm, r_tm = _fwd_res(x3, wg, wc, bias, mask, reset,
                                            interpret)
    return hs, (wg, wc, bias, mask, reset, m_tm, r_tm, gates, hs_tm)


def _fused_gru_bwd(interpret, res, g_hs):
    wg, wc, bias, mask, reset, m_tm, r_tm, gates, hs_tm = res
    B, T = mask.shape
    T_pad = hs_tm.shape[0]
    zrow = jnp.zeros_like(hs_tm[:1])
    hs_prev = jnp.concatenate([zrow, hs_tm[:-1]], axis=0)
    if r_tm is not None:
        # effective prev state = what the forward cell consumed (packing)
        hs_prev = hs_prev * (1.0 - r_tm.astype(jnp.float32)).astype(
            hs_prev.dtype)
    g_hs_tm = _pad_time(jnp.swapaxes(g_hs, 0, 1).astype(hs_tm.dtype), T_pad)
    dx3_tm, dwg, dwc, db = _bwd_call(wg, wc, m_tm, r_tm, gates, hs_prev,
                                     g_hs_tm, interpret)
    dx3 = jnp.swapaxes(dx3_tm[:T], 0, 1).astype(hs_tm.dtype)
    dreset = None if reset is None else jnp.zeros_like(reset)
    return dx3, dwg.astype(wg.dtype), dwc.astype(wc.dtype), \
        db[0].astype(bias.dtype), jnp.zeros_like(mask), dreset


fused_gru.defvjp(_fused_gru_fwd, _fused_gru_bwd)
