"""Per-head RMS norm and rotate-half rotary of a projection, one pass each
way, in the layout the projection's matmul made (docs/sdar.md).

    xn = round(x * rsqrt(mean_head(x^2) + eps) * (w * scale))     float32, once
    y  = round(xn * cos + roll(xn, D / 2) * sin±)                 float32, once

over x [B, T, heads * D]: head h of a position is the lanes ``D h .. D h +
D - 1`` of its row, which for D = 128 is exactly one lane group, so nothing is
reshaped to ``[.., heads, D]`` and back (the copies XLA makes for that change
of tiling, and the float32 temporaries it keeps between the mean's reduce and
rotate-half's concatenate, are what the two launches replace). ``tables``
makes cos and sin once a layer from the rule's positions, rounded to x's
dtype as ``layers/attention.rotary_at`` rounds them, with rotate-half's sign
folded into sin (``sin±``: minus on the first half of the head), so that
"turned" is one lane roll by D / 2.

``head_norm_rotary_fwd``  grid (rows of B, blocks of ``block`` positions): a
    block [block, heads * D] of x in, the same of y out, the heads one after
    another as static lane slices of the whole block (on a v5e the launch
    runs at what HBM gives an elementwise pass, ~610 GB/s: a lane sum and a
    lane roll a register hide behind the block's DMA when the body takes the
    block whole, and do not when it takes 32 positions at a time).
``head_norm_rotary_bwd``  reads x and dy, writes dx, and sums dw [1, D] in
    float32 in a block that stays in VMEM over the whole (sequential) grid:

        dxn = dy * cos + roll(dy * sin±, D / 2)
        g   = dxn * w * scale
        dx  = r * (g - x r^2 mean_head(g x)),      r = rsqrt(mean_head(x^2) + eps)
        dw  = scale * sum over positions and heads of dxn * x * r

    The roundings of the forward pass are passed straight through, as
    autodiff passes an ``astype``.

Every rounding point is ``_head_norm``'s and ``rotary_at``'s or finer: the
norm rounds once to x's dtype; rotary's two products and their sum are made
in float32 and rounded once (in XLA each is rounded where XLA does not fuse
them). ``gate`` is the one gate: a head of exactly one lane group, rotary over
the whole head, bf16 or float32, rows that split into whole blocks whose
backward working set fits ``_VMEM_BUDGET``; ``taken`` adds the platform and
the layer's line in the log. Bytes a launch moves at the SDAR cell's shape are
in docs/sdar.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels._pallas_util import (VMEM_LIMIT_BYTES, call_kernel,
                                             compiler_params, take_pallas)

LANES = 128
# what the backward launch's blocks (x, dy, dx, each twice as the pipeline
# keeps the next one coming) may hold; a block past a few MB gains nothing
_VMEM_BUDGET = VMEM_LIMIT_BYTES // 4


def tables(pos, theta, D, dtype):
    """(cos, sin±) [T, D] of rotate-half rotary at the positions ``pos``,
    angles in float32, rounded to ``dtype``; sin± carries rotate-half's sign."""
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.asarray(pos, jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return (jnp.concatenate([cos, cos], -1).astype(dtype),
            jnp.concatenate([-sin, sin], -1).astype(dtype))


def bwd_vmem_bytes(block, width, dtype):
    """Bytes ``head_norm_rotary_bwd`` holds in VMEM at a block of ``block``
    positions of ``width`` lanes: x, dy and dx and the two tables, each
    twice."""
    return 2 * block * (3 * width + 2 * LANES) * jnp.dtype(dtype).itemsize


def block_rows(T, width, dtype):
    """The positions a grid step takes: the largest of 512, 256, .. 16 that
    divides T and fits the budget; None where none does."""
    for block in (512, 256, 128, 64, 32, 16):
        if T % block == 0 \
                and bwd_vmem_bytes(block, width, dtype) <= _VMEM_BUDGET:
            return block
    return None


def gate(T, width, D, rot, dtype):
    """(eligible, why not) for rows of T positions of ``width`` = heads * D
    lanes, rotary on the first ``rot`` of a head."""
    if D != LANES or dtype not in (jnp.bfloat16, jnp.float32):
        return False, (f"head size {D}, {jnp.dtype(dtype).name} is outside "
                       "the kernel's gate (a head is one group of 128 lanes)")
    if rot != D:
        return False, f"rotary on {rot} of a head's {D} is not the whole head"
    if block_rows(T, width, dtype) is None:
        return False, (f"a row of {T} positions of {width} lanes does not "
                       f"split into blocks of 16 positions or more "
                       f"under {_VMEM_BUDGET / 1e6:.1f} MB of VMEM")
    return True, ""


def taken(who, T, width, D, rot, dtype):
    """Whether the layer ``who`` takes the launches for projections of up to
    ``width`` lanes; the log says once a layer which form ran and why."""
    return take_pallas(who, "head_norm_rotary_fwd/bwd",
                       *gate(T, width, D, rot, dtype),
                       otherwise="_head_norm and rotary_at in XLA")


# ---- the Mosaic kernels ------------------------------------------------------

def _head_mean(v):
    """Mean over a head's lanes of v [block, D] float32, as [block, 1]."""
    return jnp.sum(v, axis=-1, keepdims=True) * (1.0 / v.shape[-1])


def _fwd_kernel(x_ref, cos_ref, sin_ref, w_ref, y_ref, *, heads, eps):
    f32, D = jnp.float32, LANES
    cos, sin, w = cos_ref[...].astype(f32), sin_ref[...].astype(f32), w_ref[...]
    for h in range(heads):
        lanes = slice(h * D, (h + 1) * D)
        xf = x_ref[0, :, lanes].astype(f32)
        r = jax.lax.rsqrt(_head_mean(xf * xf) + eps)
        xn = (xf * r * w).astype(y_ref.dtype).astype(f32)
        y_ref[0, :, lanes] = (
            xn * cos + pltpu.roll(xn, D // 2, 1) * sin).astype(y_ref.dtype)


def _bwd_kernel(x_ref, dy_ref, cos_ref, sin_ref, w_ref, dx_ref, dw_ref, *,
                heads, eps):
    f32, D = jnp.float32, LANES
    cos, sin, w = cos_ref[...].astype(f32), sin_ref[...].astype(f32), w_ref[...]

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw = jnp.zeros(cos.shape, f32)
    for h in range(heads):
        lanes = slice(h * D, (h + 1) * D)
        xf = x_ref[0, :, lanes].astype(f32)
        dy = dy_ref[0, :, lanes].astype(f32)
        r = jax.lax.rsqrt(_head_mean(xf * xf) + eps)
        xr = xf * r
        dxn = dy * cos + pltpu.roll(dy * sin, D // 2, 1)
        dw = dw + dxn * xr
        g = dxn * w
        # r * (g - x r^2 mean(g x)), with x r made once
        dx_ref[0, :, lanes] = (
            r * (g - xr * _head_mean(g * xr))).astype(dx_ref.dtype)
    dw_ref[...] += jnp.sum(dw, axis=0, keepdims=True)


def _specs(block, width):
    vm = pltpu.VMEM
    return {
        "x": pl.BlockSpec((1, block, width), lambda b, t: (b, t, 0),
                          memory_space=vm),
        "table": pl.BlockSpec((block, LANES), lambda b, t: (t, 0),
                              memory_space=vm),
        "w": pl.BlockSpec((1, LANES), lambda b, t: (0, 0), memory_space=vm),
    }


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _fwd_call(x, ws, cos, sin, eps, block, interpret):
    B, T, width = x.shape
    spec = _specs(block, width)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=width // LANES, eps=eps),
        name="head_norm_rotary_fwd", grid=(B, T // block),
        in_specs=[spec["x"], spec["table"], spec["table"], spec["w"]],
        out_specs=spec["x"],
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret, **compiler_params(interpret))(x, cos, sin, ws)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _bwd_call(x, dy, ws, cos, sin, eps, block, interpret):
    B, T, width = x.shape
    spec = _specs(block, width)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=width // LANES, eps=eps),
        name="head_norm_rotary_bwd", grid=(B, T // block),
        in_specs=[spec["x"], spec["x"], spec["table"], spec["table"],
                  spec["w"]],
        out_specs=[spec["x"], spec["w"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((1, LANES), jnp.float32)],
        interpret=interpret, **compiler_params(interpret))(x, dy, cos, sin, ws)


def _weight(w, scale):
    """w * scale as the kernels read it: float32 [1, D], ``_head_norm``'s
    product."""
    return (w.astype(jnp.float32) * scale)[None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def head_norm_rotary(x, w, cos, sin, eps, scale, block, interpret=False):
    """x [B, T, heads * 128], w [128], (cos, sin±) [T, 128] of ``tables`` ->
    the heads normed (weight w * scale) and rotated, [B, T, heads * 128], as
    ``head_norm_rotary_fwd`` / ``head_norm_rotary_bwd`` over blocks of
    ``block`` positions."""
    return _fwd_call(x, _weight(w, scale), cos, sin, eps, block, interpret)


def _vjp_fwd(x, w, cos, sin, eps, scale, block, interpret):
    y = _fwd_call(x, _weight(w, scale), cos, sin, eps, block, interpret)
    return y, (x, w, cos, sin)


def _vjp_bwd(eps, scale, block, interpret, res, dy):
    x, w, cos, sin = res
    dx, dw = _bwd_call(x, dy.astype(x.dtype), _weight(w, scale), cos, sin,
                       eps, block, interpret)
    return dx, (dw[0] * scale).astype(w.dtype), None, None


head_norm_rotary.defvjp(_vjp_fwd, _vjp_bwd)


def normed_rotated(x, w, cos, sin, eps, scale=1.0):
    """The layer's call where ``taken`` said so: one row's projection
    x [T, heads * 128] -> [1, T, heads * 128] as ``flash_attn.attention``
    reads it, per batch shard under a data-parallel trainer."""
    T, width = x.shape
    block = block_rows(T, width, x.dtype)
    return call_kernel(
        lambda x, w, cos, sin: head_norm_rotary(x, w, cos, sin, eps, scale,
                                                block),
        (x[None], w, cos, sin), (0,))
