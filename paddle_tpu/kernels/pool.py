"""Fused max-pool backward as a Pallas TPU kernel.

XLA lowers max-pool backward to `select-and-scatter`, which on the bench
chip runs at ~500 GB/s (vs ~700 for the surrounding fusions) and re-reads
the pooled output — 1.7 ms of the ResNet-50 step (r4, not re-measured). The
reference hand-writes the same kernel in CUDA for the same reason
(paddle/cuda/src/hl_cuda_cnn.cu hl_maxpool_backward: each input position
sums `outGrad * (in == out)` over the <=4 windows containing it). This is
that kernel, TPU-shaped:

- grid over batch; each program holds one [H, W, C] image in VMEM,
- the pooled maxima are recomputed IN-KERNEL from the VMEM-resident input
  (no HBM read of `y`), so HBM traffic is the floor: read x, read dy,
  write dx,
- the <=4-windows-per-input sum is vectorised by parity: even rows/cols
  see one window, odd see two (kernel 3, stride 2, symmetric pad 1).

Tie semantics match the reference CUDA kernel: every position equal to
the window max receives the full gradient (hl_maxpool_backward's
`in == out` test), a valid subgradient that differs from XLA's
first-match select-and-scatter only on exact ties.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def maxpool_3x3s2p1_supported(x_shape) -> bool:
    """NHWC, even H/W, and one image's buffers fit VMEM comfortably."""
    if len(x_shape) != 4:
        return False
    _, H, W, C = x_shape
    vmem_bytes = (2 * H * W * C + (H // 2) * (W // 2) * C) * 2 * 2
    return H % 2 == 0 and W % 2 == 0 and C % 64 == 0 and \
        vmem_bytes < 12 * 1024 * 1024


def _pool_fwd_raw(x):
    """reduce_window max, kernel 3 stride 2 symmetric pad 1 (img_pool
    geometry for the ResNet stem: 112 -> 56)."""
    return jax.lax.reduce_window(
        x, jnp.asarray(-jnp.inf, x.dtype), jax.lax.max,
        (1, 3, 3, 1), (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])


def _bwd_kernel(x_ref, dy_ref, dx_ref):
    """One image: dx[r,c] = sum over containing windows of
    dy[o,po] * (x[r,c] == max of window (o,po)).

    Internal math runs in f32: Mosaic (as of this chip's toolchain)
    rejects bf16 compares in the split [HO, WO, 2, C] layout
    (arith.cmpf on vector<...x2xbf16>); f32 compiles and the casts are
    free VPU ops against the HBM-bound roofline."""
    H, W, C = x_ref.shape[1], x_ref.shape[2], x_ref.shape[3]
    HO, WO = H // 2, W // 2
    x = x_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    neg = jnp.asarray(-jnp.inf, x.dtype)

    # recompute pooled maxima from VMEM: window (o,po) covers rows
    # 2o-1..2o+1, cols 2po-1..2po+1. Build the 3-row max at output rows
    # first, then the 3-col max.
    x2 = x.reshape(HO, 2, W, C)
    xe, xo = x2[:, 0], x2[:, 1]                    # even/odd input rows
    xo_up = jnp.concatenate([jnp.full((1, W, C), neg, x.dtype),
                             xo[:-1]], axis=0)     # row 2o-1
    rowmax = jnp.maximum(jnp.maximum(xe, xo), xo_up)   # [HO, W, C]
    r2 = rowmax.reshape(HO, WO, 2, C)
    re_, ro = r2[:, :, 0], r2[:, :, 1]             # even/odd cols
    ro_up = jnp.concatenate([jnp.full((HO, 1, C), neg, x.dtype),
                             ro[:, :-1]], axis=1)  # col 2po-1
    y = jnp.maximum(jnp.maximum(re_, ro), ro_up)   # [HO, WO, C]

    inf_row = jnp.full((1, WO, C), jnp.inf, x.dtype)
    zero_row = jnp.zeros((1, WO, C), dy.dtype)
    yD = jnp.concatenate([y[1:], inf_row], axis=0)        # window o+1
    dyD = jnp.concatenate([dy[1:], zero_row], axis=0)

    inf_col = jnp.full((HO, 1, C), jnp.inf, x.dtype)
    zero_col = jnp.zeros((HO, 1, C), dy.dtype)

    def row_terms(xrow_pairs, ys, ds):
        """Contribution of H-window stream (ys, ds) to the two column
        parities of input rows; xrow_pairs: [HO, W, C] of one row parity.
        Returns [HO, W, C]."""
        xp = xrow_pairs.reshape(HO, WO, 2, C)
        xce, xco = xp[:, :, 0], xp[:, :, 1]        # even/odd input cols
        # even col c=2j2: window j2 only
        t_e = ds * (xce == ys).astype(ds.dtype)
        # odd col c=2j2+1: windows j2 and j2+1
        ysR = jnp.concatenate([ys[:, 1:], inf_col], axis=1)
        dsR = jnp.concatenate([ds[:, 1:], zero_col], axis=1)
        t_o = (ds * (xco == ys).astype(ds.dtype)
               + dsR * (xco == ysR).astype(ds.dtype))
        return jnp.stack([t_e, t_o], axis=2).reshape(HO, W, C)

    # even input rows r=2i2: H-window i2 only
    dxe = row_terms(xe, y, dy)
    # odd input rows r=2i2+1: H-windows i2 and i2+1
    dxo = row_terms(xo, y, dy) + row_terms(xo, yD, dyD)
    dx_ref[0] = jnp.stack([dxe, dxo], axis=1).reshape(H, W, C).astype(
        dx_ref.dtype)


def _maxpool_bwd_pallas(x, dy, interpret=False):
    B, H, W, C = x.shape
    HO, WO = H // 2, W // 2
    kw = {}
    if not interpret:
        # the f32 working set exceeds the default 16M scoped-vmem budget;
        # the chip accepts a raised limit (measured r4)
        kw["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024)
    return pl.pallas_call(
        _bwd_kernel,
        name="maxpool_bwd",
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, W, C), lambda b: (b, 0, 0, 0)),
                  pl.BlockSpec((1, HO, WO, C), lambda b: (b, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, H, W, C), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, C), dy.dtype),
        interpret=interpret,
        **kw,
    )(x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def maxpool_3x3s2p1(x, interpret=False):
    """Max pool, kernel 3 / stride 2 / symmetric pad 1, NHWC — the
    ResNet-stem pool (models/resnet.py res_pool1) with a Pallas backward.
    Forward is XLA's reduce_window (already optimal); backward replaces
    select-and-scatter."""
    return _pool_fwd_raw(x)


def _mp_fwd(x, interpret):
    return _pool_fwd_raw(x), x


def _mp_bwd(interpret, x, g):
    return (_maxpool_bwd_pallas(x, g, interpret=interpret),)


maxpool_3x3s2p1.defvjp(_mp_fwd, _mp_bwd)
