"""Fused LSTM recurrence as a Pallas TPU kernel.

TPU-native analog of the reference's hand-fused CUDA LSTM
(paddle/cuda/src/hl_gpu_lstm.cuh, hl_lstm.h): the whole time loop runs in
ONE kernel with the recurrent weight matrix resident in VMEM, so each step
costs one MXU matmul + VPU gate math instead of an HBM weight refetch
(the `lax.scan` formulation re-reads W [H,4H] from HBM every timestep,
which is what made the scan path bandwidth-bound).

Layout: time-major [T, B, 4H] input blocks stream through a sequential
grid in chunks of C timesteps (the chunk amortises per-grid-step pipeline
overhead; the inner loop is unrolled straight-line code); h/c carries live
in fp32 VMEM scratch across grid steps. The backward pass is a second
Pallas kernel walking the grid in reverse, accumulating dW/db in VMEM
scratch (cuDNN-style: gate activations are stashed in the forward, so the
backward needs no recomputation matmul). Time is padded to a multiple of C
with zero mask — the mask-gated carry makes padding a no-op in both
directions.

Semantics match layers/recurrent.py lstm_cell exactly: gate order
[i, f, c, o], peephole biases packed at bias[4H:7H] (reference LstmLayer
bias layout), mask-gated carry for ragged batches.

Sequence packing (docs/packing.md): an optional segment-start ``reset``
vector [B, T] rides alongside ``mask`` — 1.0 at the first valid step of
each packed segment. The kernel zeroes the h/c carry entering such a
step, so a row holding several packed sequences never leaks state across
a sequence boundary. ``reset=None`` (the default) compiles the exact
pre-packing kernel: the reset refs and multiplies only exist in the
traced program when a reset vector is passed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_CHUNK = 8
# the backward holds ~2x the live blocks (gates, two shifted state views,
# two cotangents, dW scratch+out); a smaller chunk keeps it under the 16MB
# scoped-VMEM budget
_CHUNK_BWD = 4


def _vmem_estimate_bytes(B: int, H: int) -> int:
    """Backward working set WITH in-kernel dW accumulation: W + dW
    scratch+out + ~9 double-buffered [C, B, H..4H] blocks. The chip
    accepts a raised scoped-vmem limit (r4), but past ~90MB the compiler
    refuses or spills."""
    blk = _CHUNK_BWD * B * 4 * H * 2            # bf16 gate blocks
    blocks = 9 * blk                            # in/out streams (x2 buffer)
    w = H * 4 * H * (2 + 4 + 4)                 # W bf16 + dW f32 scr + out
    return blocks + w


def _vmem_estimate_nodw_bytes(B: int, H: int, C: int) -> int:
    """Backward working set of the split variant (_bwd_kernel_nodw) at
    time-chunk C: the dW/db accumulators leave VMEM entirely — dpre
    streams out and one XLA matmul over the stash computes dW/db
    afterwards (r5: this is what lets h=1280 run fused; the extra HBM
    pass over dpre + hs_prev is ~0.1 ms against an 18+ ms scan
    baseline)."""
    blk = C * B * 4 * H * 2
    blocks = 9 * blk
    return blocks + H * 4 * H * 2               # W bf16 only


def _split_bwd_chunk(B: int, H: int):
    """Largest backward time-chunk whose split working set fits; None if
    even C=1 does not (then lax.scan runs)."""
    for C in (_CHUNK_BWD, 2, 1):
        if _vmem_estimate_nodw_bytes(B, H, C) < 64 * 1024 * 1024:
            return C
    return None


def _vmem_estimate_fwd_bytes(B: int, H: int, C: int) -> int:
    """Forward working set at time-chunk C: W resident + double-buffered
    streams (x4 in, hs/cs/gates out, mask)."""
    streams = C * B * (4 * H + H + H + 4 * H + 1) * 2 * 2
    return streams + H * 4 * H * 2 + 2 * B * H * 4      # + h/c scratch


def _fwd_chunk(B: int, H: int):
    """Largest forward time-chunk that fits (h1280/bs256 at C=8 asks
    ~103MB — the compiler's stack-allocation OOM measured r5)."""
    for C in (_CHUNK, 4, 2, 1):
        if _vmem_estimate_fwd_bytes(B, H, C) < 64 * 1024 * 1024:
            return C
    return None


# test hook: force the split backward regardless of the VMEM estimate
_FORCE_SPLIT_BWD = False


def _use_in_kernel_dw(B: int, H: int) -> bool:
    if _FORCE_SPLIT_BWD:
        return False
    return _vmem_estimate_bytes(B, H) < 64 * 1024 * 1024


def fused_lstm_supported(B: int, H: int) -> bool:
    """MXU/VPU tiling wants lane dim % 128 and sublane % 8; the working
    set must fit the (raised) scoped-VMEM budget. Cells whose in-kernel
    dW accumulation would blow the budget (h=1280/bs=64 asks ~85MiB)
    take the split backward — with a shrinking time-chunk — instead of
    falling to lax.scan."""
    return H % 128 == 0 and B % 8 == 0 and \
        _split_bwd_chunk(B, H) is not None and \
        _fwd_chunk(B, H) is not None


from paddle_tpu.kernels._pallas_util import (  # noqa: E402
    compiler_params as _compiler_params)


def _sig(x):
    return jax.nn.sigmoid(x)


def _cell_fwd(x4, h_prev, c_prev, m, w, b, H):
    pre = x4.astype(jnp.float32) + jnp.dot(
        h_prev.astype(w.dtype), w, preferred_element_type=jnp.float32)
    pre = pre + b[:4 * H]
    pi, pf, po = b[4 * H:5 * H], b[5 * H:6 * H], b[6 * H:7 * H]
    i = _sig(pre[:, :H] + pi * c_prev)
    f = _sig(pre[:, H:2 * H] + pf * c_prev)
    g = jnp.tanh(pre[:, 2 * H:3 * H])
    c_new = f * c_prev + i * g
    o = _sig(pre[:, 3 * H:] + po * c_new)
    h_new = o * jnp.tanh(c_new)
    h = m * h_new + (1.0 - m) * h_prev
    c = m * c_new + (1.0 - m) * c_prev
    return h, c, i, f, g, o


def _fwd_kernel(x4_ref, w_ref, b_ref, m_ref, *rest, H: int, C: int,
                R: bool = False):
    if R:
        r_ref, hs_ref, cs_ref, gates_ref, h_scr, c_scr = rest
    else:
        r_ref = None
        hs_ref, cs_ref, gates_ref, h_scr, c_scr = rest
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _():
        h_scr[:] = jnp.zeros_like(h_scr)
        c_scr[:] = jnp.zeros_like(c_scr)

    w = w_ref[:]
    b = b_ref[0].astype(jnp.float32)
    h = h_scr[:]
    c = c_scr[:]
    for k in range(C):
        m = m_ref[k].astype(jnp.float32)            # [B, 1]
        if R:
            # segment-start reset: the carry entering this step is zeroed
            # where a new packed sequence begins (reset <= mask, so a
            # masked step never destroys the carry it must preserve)
            p = 1.0 - r_ref[k].astype(jnp.float32)
            h = p * h
            c = p * c
        h, c, i, f, g, o = _cell_fwd(x4_ref[k], h, c, m, w, b, H)
        hs_ref[k] = h.astype(hs_ref.dtype)
        cs_ref[k] = c.astype(cs_ref.dtype)
        gates_ref[k] = jnp.concatenate([i, f, g, o], axis=-1).astype(
            gates_ref.dtype)
    h_scr[:] = h
    c_scr[:] = c


def _bwd_kernel(w_ref, b_ref, m_ref, *rest, H: int, C: int,
                R: bool = False):
    # packed mode (R): cs_prev/hs_prev arrive pre-multiplied by (1-reset)
    # — the EFFECTIVE state the forward cell consumed — so the cell-local
    # grads and dW need no changes; only the carry handed to step t-1
    # must be gated by (1-reset) at the end of each step.
    if R:
        (r_ref, gates_ref, cs_ref, cs_prev_ref, hs_prev_ref, ghs_ref,
         gcs_ref, dx4_ref, dw_ref, db_ref,
         dh_scr, dc_scr, dw_scr, db_scr) = rest
    else:
        r_ref = None
        (gates_ref, cs_ref, cs_prev_ref, hs_prev_ref, ghs_ref, gcs_ref,
         dx4_ref, dw_ref, db_ref,
         dh_scr, dc_scr, dw_scr, db_scr) = rest
    s = pl.program_id(0)                            # s=0 is the LAST chunk

    @pl.when(s == 0)
    def _():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dc_scr[:] = jnp.zeros_like(dc_scr)
        dw_scr[:] = jnp.zeros_like(dw_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    w = w_ref[:]
    b = b_ref[0].astype(jnp.float32)
    pi, pf, po = b[4 * H:5 * H], b[5 * H:6 * H], b[6 * H:7 * H]
    dh = dh_scr[:]
    dc = dc_scr[:]
    dw_acc = dw_scr[:]
    for k in reversed(range(C)):
        m = m_ref[k].astype(jnp.float32)
        dh_t = ghs_ref[k].astype(jnp.float32) + dh
        dc_t = gcs_ref[k].astype(jnp.float32) + dc
        # forward gating: h_t = m*h_new + (1-m)*h_prev
        dh_new = m * dh_t
        dc_in = m * dc_t
        dh_pass = (1.0 - m) * dh_t
        dc_pass = (1.0 - m) * dc_t

        gates = gates_ref[k].astype(jnp.float32)
        i = gates[:, :H]
        f = gates[:, H:2 * H]
        g = gates[:, 2 * H:3 * H]
        o = gates[:, 3 * H:]
        c_new = cs_ref[k].astype(jnp.float32)       # valid where m==1
        c_prev = cs_prev_ref[k].astype(jnp.float32)  # zeros at t==0
        h_prev = hs_prev_ref[k].astype(jnp.float32)

        tanh_c = jnp.tanh(c_new)
        do_ = dh_new * tanh_c * o * (1.0 - o)
        dc_new = dh_new * o * (1.0 - tanh_c * tanh_c) + dc_in + do_ * po
        di_ = dc_new * g * i * (1.0 - i)
        df_ = dc_new * c_prev * f * (1.0 - f)
        dg_ = dc_new * i * (1.0 - g * g)
        dc = dc_new * f + di_ * pi + df_ * pf + dc_pass

        dpre = jnp.concatenate([di_, df_, dg_, do_], axis=-1)   # [B, 4H]
        dh = jax.lax.dot_general(
            dpre.astype(w.dtype), w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) + dh_pass
        if R:
            p = 1.0 - r_ref[k].astype(jnp.float32)
            dh = p * dh
            dc = p * dc
        # dW += h_prev^T @ dpre  (contract over batch)
        dw_acc = dw_acc + jax.lax.dot_general(
            h_prev.astype(w.dtype), dpre.astype(w.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        # bias grads accumulate row-sliced (1D concatenates with >1-tile
        # offsets are unsupported by Mosaic): row 0 = gate biases [4H],
        # rows 1-3 hold peephole grads in their first H lanes
        db_scr[0:1, :] = db_scr[0:1, :] + dpre.sum(axis=0, keepdims=True)
        db_scr[1:2, :H] = db_scr[1:2, :H] + \
            (di_ * c_prev).sum(axis=0, keepdims=True)
        db_scr[2:3, :H] = db_scr[2:3, :H] + \
            (df_ * c_prev).sum(axis=0, keepdims=True)
        db_scr[3:4, :H] = db_scr[3:4, :H] + \
            (do_ * c_new).sum(axis=0, keepdims=True)
        dx4_ref[k] = dpre.astype(dx4_ref.dtype)

    dh_scr[:] = dh
    dc_scr[:] = dc
    dw_scr[:] = dw_acc

    @pl.when(s == pl.num_programs(0) - 1)
    def _():
        dw_ref[:] = dw_acc.astype(dw_ref.dtype)
        db_ref[:] = db_scr[:].astype(db_ref.dtype)


def _bwd_kernel_nodw(w_ref, b_ref, m_ref, *rest, H: int, C: int,
                     R: bool = False):
    """Split backward: the dh/dc recurrence + dpre (=dx4) only. dW/db are
    computed OUTSIDE from the streamed dpre/hs_prev/cs arrays (one XLA
    matmul), so no [H,4H] f32 accumulator lives in VMEM — the variant
    that fits h=1280. Packed mode (R): see _bwd_kernel — cs_prev arrives
    effective, the outgoing carry is gated by (1-reset)."""
    if R:
        (r_ref, gates_ref, cs_ref, cs_prev_ref, ghs_ref, gcs_ref,
         dx4_ref, dh_scr, dc_scr) = rest
    else:
        r_ref = None
        (gates_ref, cs_ref, cs_prev_ref, ghs_ref, gcs_ref,
         dx4_ref, dh_scr, dc_scr) = rest
    s = pl.program_id(0)                            # s=0 is the LAST chunk

    @pl.when(s == 0)
    def _():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dc_scr[:] = jnp.zeros_like(dc_scr)

    w = w_ref[:]
    b = b_ref[0].astype(jnp.float32)
    pi, pf, po = b[4 * H:5 * H], b[5 * H:6 * H], b[6 * H:7 * H]
    dh = dh_scr[:]
    dc = dc_scr[:]
    for k in reversed(range(C)):
        m = m_ref[k].astype(jnp.float32)
        dh_t = ghs_ref[k].astype(jnp.float32) + dh
        dc_t = gcs_ref[k].astype(jnp.float32) + dc
        dh_new = m * dh_t
        dc_in = m * dc_t
        dh_pass = (1.0 - m) * dh_t
        dc_pass = (1.0 - m) * dc_t

        gates = gates_ref[k].astype(jnp.float32)
        i = gates[:, :H]
        f = gates[:, H:2 * H]
        g = gates[:, 2 * H:3 * H]
        o = gates[:, 3 * H:]
        c_new = cs_ref[k].astype(jnp.float32)
        c_prev = cs_prev_ref[k].astype(jnp.float32)

        tanh_c = jnp.tanh(c_new)
        do_ = dh_new * tanh_c * o * (1.0 - o)
        dc_new = dh_new * o * (1.0 - tanh_c * tanh_c) + dc_in + do_ * po
        di_ = dc_new * g * i * (1.0 - i)
        df_ = dc_new * c_prev * f * (1.0 - f)
        dg_ = dc_new * i * (1.0 - g * g)
        dc = dc_new * f + di_ * pi + df_ * pf + dc_pass

        dpre = jnp.concatenate([di_, df_, dg_, do_], axis=-1)   # [B, 4H]
        dh = jax.lax.dot_general(
            dpre.astype(w.dtype), w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) + dh_pass
        if R:
            p = 1.0 - r_ref[k].astype(jnp.float32)
            dh = p * dh
            dc = p * dc
        dx4_ref[k] = dpre.astype(dx4_ref.dtype)

    dh_scr[:] = dh
    dc_scr[:] = dc


def _bwd_call_nodw(w, b, mask_tm, reset_tm, gates, cs, cs_prev, g_hs, g_cs,
                   interpret):
    T, B, H4 = gates.shape
    H = H4 // 4
    C = _split_bwd_chunk(B, H) or _CHUNK_BWD
    assert T % C == 0, "caller pads T to a _CHUNK multiple"
    NC = T // C
    dt = g_hs.dtype
    R = reset_tm is not None
    kernel = functools.partial(_bwd_kernel_nodw, H=H, C=C, R=R)
    rev = lambda s: (NC - 1 - s, 0, 0)
    maybe_reset = ([pl.BlockSpec((C, B, 1), rev, memory_space=pltpu.VMEM)]
                   if R else [])
    return pl.pallas_call(
        kernel,
        name="fused_lstm_bwd_dx",
        grid=(NC,),
        in_specs=[
            pl.BlockSpec((H, H4), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 7 * H), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, 1), rev, memory_space=pltpu.VMEM),
            *maybe_reset,
            pl.BlockSpec((C, B, H4), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((C, B, H4), rev, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H4), dt),          # dx4 (=dpre)
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
        ],
        interpret=interpret,
        **_compiler_params(interpret),
    )(w, b, mask_tm, *([reset_tm] if R else []), gates, cs, cs_prev,
      g_hs, g_cs)


def _fwd_call(x4_tm, w, b, mask_tm, reset_tm, interpret):
    T, B, H4 = x4_tm.shape
    H = H4 // 4
    C = _fwd_chunk(B, H) or _CHUNK
    assert T % C == 0, "caller pads T to a _CHUNK multiple"
    dt = x4_tm.dtype
    R = reset_tm is not None
    kernel = functools.partial(_fwd_kernel, H=H, C=C, R=R)
    maybe_reset = ([pl.BlockSpec((C, B, 1), lambda s: (s, 0, 0),
                                 memory_space=pltpu.VMEM)] if R else [])
    return pl.pallas_call(
        kernel,
        name="fused_lstm_fwd",
        grid=(T // C,),
        in_specs=[
            pl.BlockSpec((C, B, H4), lambda s: (s, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((H, H4), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 7 * H), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, 1), lambda s: (s, 0, 0),
                         memory_space=pltpu.VMEM),
            *maybe_reset,
        ],
        out_specs=[
            pl.BlockSpec((C, B, H), lambda s: (s, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, H), lambda s: (s, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, H4), lambda s: (s, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H), dt),           # hs
            jax.ShapeDtypeStruct((T, B, H), dt),           # cs
            jax.ShapeDtypeStruct((T, B, H4), dt),          # gate acts
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
        ],
        interpret=interpret,
        **_compiler_params(interpret),
    )(x4_tm, w, b, mask_tm, *([reset_tm] if R else []))


def _bwd_call(w, b, mask_tm, reset_tm, gates, cs, cs_prev, hs_prev, g_hs,
              g_cs, interpret):
    T, B, H4 = gates.shape
    H = H4 // 4
    C = _CHUNK_BWD
    assert T % C == 0, "caller pads T to a _CHUNK multiple"
    NC = T // C
    dt = g_hs.dtype
    R = reset_tm is not None
    kernel = functools.partial(_bwd_kernel, H=H, C=C, R=R)
    rev = lambda s: (NC - 1 - s, 0, 0)
    maybe_reset = ([pl.BlockSpec((C, B, 1), rev, memory_space=pltpu.VMEM)]
                   if R else [])
    return pl.pallas_call(
        kernel,
        name="fused_lstm_bwd",
        grid=(NC,),
        in_specs=[
            pl.BlockSpec((H, H4), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 7 * H), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, 1), rev, memory_space=pltpu.VMEM),
            *maybe_reset,
            pl.BlockSpec((C, B, H4), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((C, B, H4), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((H, H4), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((8, H4), lambda s: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H4), dt),          # dx4
            jax.ShapeDtypeStruct((H, H4), w.dtype),        # dW
            jax.ShapeDtypeStruct((8, H4), jnp.float32),    # dbias rows
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((H, H4), jnp.float32),
            pltpu.VMEM((8, H4), jnp.float32),
        ],
        interpret=interpret,
        **_compiler_params(interpret),
    )(w, b, mask_tm, *([reset_tm] if R else []), gates, cs, cs_prev,
      hs_prev, g_hs, g_cs)


def _pad_time(x_tm, T_pad):
    T = x_tm.shape[0]
    if T == T_pad:
        return x_tm
    pad = [(0, T_pad - T)] + [(0, 0)] * (x_tm.ndim - 1)
    return jnp.pad(x_tm, pad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def fused_lstm(x4, w, bias, mask, reset=None, interpret=False):
    """Fused LSTM over a padded batch.

    x4    [B, T, 4H]  pre-projected input (i,f,c,o gate order)
    w     [H, 4H]     recurrent weights
    bias  [7H]        gate biases + peepholes (pass zeros when bias-free)
    mask  [B, T]      1.0 valid / 0.0 padding
    reset [B, T]|None segment-start resets for packed rows (1.0 zeroes the
                      incoming h/c carry at that step; must satisfy
                      reset <= mask). None = pre-packing program, no
                      reset refs traced.
    Returns (hs, cs): [B, T, H] each (not mask-multiplied — carries hold).
    """
    hs, cs = _fwd_res(x4, w, bias, mask, reset, interpret)[0:2]
    return hs, cs


def _reset_tm(reset, T_pad):
    if reset is None:
        return None
    return _pad_time(jnp.swapaxes(reset, 0, 1)[..., None]
                     .astype(jnp.bfloat16), T_pad)


def _fwd_res(x4, w, bias, mask, reset, interpret):
    B, T, H4 = x4.shape
    # always pad to a multiple of _CHUNK (>= _CHUNK) so both the forward
    # chunk and the smaller backward chunk tile T exactly — T in (C_bwd,
    # _CHUNK) used to truncate the backward grid and drop timesteps
    T_pad = -(-T // _CHUNK) * _CHUNK
    x4_tm = _pad_time(jnp.swapaxes(x4, 0, 1), T_pad)     # [Tp, B, 4H]
    m_tm = _pad_time(jnp.swapaxes(mask, 0, 1)[..., None].astype(jnp.bfloat16),
                     T_pad)                               # [Tp, B, 1]
    r_tm = _reset_tm(reset, T_pad)
    hs_tm, cs_tm, gates = _fwd_call(x4_tm, w, bias[None, :], m_tm, r_tm,
                                    interpret)
    return (jnp.swapaxes(hs_tm[:T], 0, 1), jnp.swapaxes(cs_tm[:T], 0, 1),
            gates, hs_tm, cs_tm, m_tm, r_tm)


def _fused_lstm_fwd(x4, w, bias, mask, reset, interpret):
    hs, cs, gates, hs_tm, cs_tm, m_tm, r_tm = _fwd_res(
        x4, w, bias, mask, reset, interpret)
    return (hs, cs), (w, bias, mask, reset, m_tm, r_tm, gates, hs_tm, cs_tm)


def _fused_lstm_bwd(interpret, res, cot):
    w, bias, mask, reset, m_tm, r_tm, gates, hs_tm, cs_tm = res
    g_hs, g_cs = cot
    B, T = mask.shape
    T_pad = hs_tm.shape[0]
    H = w.shape[0]
    # one-step-shifted state arrays give every chunk an aligned view of
    # h_{t-1}/c_{t-1} (row 0 = the zero initial state)
    zrow = jnp.zeros_like(hs_tm[:1])
    hs_prev = jnp.concatenate([zrow, hs_tm[:-1]], axis=0)
    cs_prev = jnp.concatenate([zrow, cs_tm[:-1]], axis=0)
    if r_tm is not None:
        # packed rows: the forward cell consumed (1-reset)*state — hand
        # the backward the same EFFECTIVE prev-state views so cell-local
        # grads (df_, peepholes) and dW see what the forward saw
        p_tm = (1.0 - r_tm.astype(jnp.float32)).astype(hs_prev.dtype)
        hs_prev = hs_prev * p_tm
        cs_prev = cs_prev * p_tm
    g_hs_tm = _pad_time(jnp.swapaxes(g_hs, 0, 1).astype(hs_tm.dtype), T_pad)
    g_cs_tm = _pad_time(jnp.swapaxes(g_cs, 0, 1).astype(hs_tm.dtype), T_pad)
    if _use_in_kernel_dw(B, H):
        dx4_tm, dw, db_rows = _bwd_call(w, bias[None, :], m_tm, r_tm, gates,
                                        cs_tm, cs_prev, hs_prev, g_hs_tm,
                                        g_cs_tm, interpret)
        db = jnp.concatenate([db_rows[0], db_rows[1, :H], db_rows[2, :H],
                              db_rows[3, :H]])
    else:
        # split backward (the h=1280 path): kernel streams dpre; dW/db
        # are one MXU matmul + reductions over the stash (dpre is zero
        # at masked/padded steps, so padding contributes nothing)
        (dx4_tm,) = _bwd_call_nodw(w, bias[None, :], m_tm, r_tm, gates,
                                   cs_tm, cs_prev, g_hs_tm, g_cs_tm,
                                   interpret)
        dpre = dx4_tm.reshape(T_pad * B, 4 * H)
        dw = jax.lax.dot_general(
            hs_prev.reshape(T_pad * B, H).astype(w.dtype),
            dpre.astype(w.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpre32 = dpre.astype(jnp.float32)
        cp = cs_prev.reshape(T_pad * B, H).astype(jnp.float32)
        cn = cs_tm.reshape(T_pad * B, H).astype(jnp.float32)
        db = jnp.concatenate([
            dpre32.sum(axis=0),
            (dpre32[:, :H] * cp).sum(axis=0),           # d peephole_i
            (dpre32[:, H:2 * H] * cp).sum(axis=0),      # d peephole_f
            (dpre32[:, 3 * H:] * cn).sum(axis=0),       # d peephole_o
        ])
    dx4 = jnp.swapaxes(dx4_tm[:T], 0, 1).astype(hs_tm.dtype)
    dreset = None if reset is None else jnp.zeros_like(reset)
    return dx4, dw.astype(w.dtype), db.astype(bias.dtype), \
        jnp.zeros_like(mask), dreset


fused_lstm.defvjp(_fused_lstm_fwd, _fused_lstm_bwd)
