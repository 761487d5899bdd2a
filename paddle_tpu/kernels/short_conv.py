"""The gated short convolution of LFM2's conv mixer, one pass each way over
the in-projection's own layout (docs/lfm2.md).

    [B ; C ; x~] = x W_in                   [T, 3 D], the layer's matmul
    v   = round(B * x~)
    c_t = round(sum_j w[:, j] v_{t - (K - 1) + j})    depthwise, causal,
                                            zeros before the row, no bias
    y   = round(C * c)

``short_conv_xla`` is the same in XLA (``layers/delta_net.causal_conv`` on
``B * x~``): the path off the TPU and what the tests pin the kernels to.

``short_conv_fwd``  grid (rows, channel blocks, row blocks): a [bt, bd]
    block of each of B, C and x~ is read as a column block of the [T, 3 D]
    projection (three BlockSpecs on one array: no slice, no transpose, nothing
    written between the matmul and the launch), and y's block written. A
    position's K - 1 predecessors are v's rows rolled down by 1 .. K - 1
    sublanes; the rows that roll in from the block before come from its last
    8 rows of v, CARRIED in VMEM from one row block to the next (zeros at a
    row's start), so the row blocks of a (row, channel block) run in order.
``short_conv_bwd``  grid (channel blocks, rows, row blocks, 3): reads B, C,
    x~, dy and computes, in float32,

        c   as forward (v's halo carried the same way)
        dC  = round(dy * c);  dc = round(dy * C)
        dv_s = round(sum_j w[:, j] dc_{s + K - 1 - j})     (zeros past the row)
        dB  = round(dv * x~); dx~ = round(dv * B)
        dw[:, j] = sum_s dc_{s + K - 1 - j} v_s            (float32)

    dc's first K - 1 rows of the NEXT row block come from a block of 16 rows
    of dy and C read beside the step's own. d[B ; C ; x~] goes into one
    [T, 3 D] array in the projection's layout: the last grid axis walks the
    three parts, the first step of a block computing all three into VMEM and
    each step writing one (the inputs' blocks do not move over that axis, so
    they are fetched once). dw [8, bd] (rows past K zero) stays in VMEM over
    the rows and row blocks of its channel block and leaves once.

``gate`` is the one gate: a channel count of whole lanes, rows of whole
16-row groups, a kernel of at most 9 taps, bf16 or float32; ``taken`` adds the
platform and says once a layer which path ran and why.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels._pallas_util import (VMEM_LIMIT_BYTES, call_kernel,
                                             take_pallas)

f32 = jnp.float32
# rows of the next row block the backward launch reads for dc's halo: one
# packed bf16 sublane group
HALO = 16
# v's rows carried from one row block to the next: one float32 sublane group
CARRY = 8


def short_conv_xla(bcx, w):
    """bcx [B, T, 3 D] = [B ; C ; x~], w [D, K] -> C * causal_conv(B * x~)."""
    from paddle_tpu.layers.delta_net import causal_conv

    D = w.shape[0]
    return bcx[..., D:2 * D] * causal_conv(bcx[..., :D] * bcx[..., 2 * D:], w)


def blocks(T, D):
    """(bt, bd): the largest of 512 .. 16 rows that divides T and of 512, 256,
    128 lanes that divides D; None where none does."""
    bt = next((b for b in (512, 256, 128, 64, 32, 16) if T % b == 0), None)
    bd = next((b for b in (512, 256, 128) if D % b == 0), None)
    return (bt, bd) if bt and bd else None


def gate(T, D, K, dtype):
    """(eligible, why not) for rows of T positions, D channels, K taps."""
    if dtype not in (jnp.bfloat16, jnp.float32) or not 2 <= K <= CARRY + 1:
        return False, (f"{K} taps in {jnp.dtype(dtype).name} are outside "
                       f"the kernels' gate (2 to {CARRY + 1}, bf16 or "
                       "float32)")
    if blocks(T, D) is None:
        return False, (f"rows of {T} positions of {D} channels do not split "
                       "into blocks of 16 rows and 128 lanes")
    return True, ""


def taken(who, T, D, K, dtype):
    """Whether the layer ``who`` takes the launches; the log says once a
    layer which form ran and why."""
    return take_pallas(who, "short_conv_fwd/bwd", *gate(T, D, K, dtype),
                       otherwise="the convolution in XLA")


# ---- the Mosaic kernels ------------------------------------------------------

def _row(block, i):
    """Row i of a [rows, bd] float32 block as [1, bd] (a select and a
    sublane sum: no slice at an unaligned sublane)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return jnp.sum(jnp.where(rows == i, block, 0.0), axis=0, keepdims=True)


def _taps(w_ref, K):
    w = w_ref[...]
    return [_row(w, j) for j in range(K)]


def _past(v, carry, s):
    """v_{t - s} of every row t of the block: v rolled down s sublanes, rows
    t < s from the block before (whose last 8 rows ``carry`` holds)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    out = pltpu.roll(v, s, 0)
    for i in range(s):
        out = jnp.where(rows == i, _row(carry, CARRY - s + i), out)
    return out


def _conv(v, carry, taps):
    """c = sum_j w_j v_{t - (K - 1) + j}, float32."""
    K = len(taps)
    c = taps[K - 1] * v
    for j in range(K - 1):
        c = c + taps[j] * _past(v, carry, K - 1 - j)
    return c


def _fwd_kernel(b_ref, c_ref, x_ref, w_ref, y_ref, carry_ref, *, K):
    @pl.when(pl.program_id(2) == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    dt = y_ref.dtype
    v = (b_ref[0] * x_ref[0]).astype(f32)
    c = _conv(v, carry_ref[...], _taps(w_ref, K)).astype(dt)
    y_ref[0] = c_ref[0] * c
    carry_ref[...] = v[v.shape[0] - CARRY:]


def _bwd_kernel(b_ref, c_ref, x_ref, dy_ref, dyn_ref, cn_ref, w_ref, o_ref,
                dw_ref, carry_ref, parts_ref, *, K):
    t, last = pl.program_id(2), pl.num_programs(2) - 1
    first = pl.program_id(3) == 0

    @pl.when((pl.program_id(1) == 0) & (t == 0) & first)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when((t == 0) & first)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    @pl.when(first)
    def _():
        dt = o_ref.dtype
        taps = _taps(w_ref, K)
        bv, cv, xv, dy = b_ref[0], c_ref[0], x_ref[0], dy_ref[0]
        v = (bv * xv).astype(f32)
        c = _conv(v, carry_ref[...], taps).astype(dt)
        carry_ref[...] = v[v.shape[0] - CARRY:]
        dc = (dy * cv).astype(f32)
        # dc of the next block's first rows; none past the row's end
        nxt = (dyn_ref[0] * cn_ref[0]).astype(f32) \
            * jnp.where(t == last, 0.0, 1.0)
        bt = dc.shape[0]
        rows = jax.lax.broadcasted_iota(jnp.int32, dc.shape, 0)
        dv = taps[K - 1] * dc
        sums = [jnp.sum(dc * v, axis=0, keepdims=True)]
        for u in range(1, K):            # dc_{s + u}
            ahead = pltpu.roll(dc, bt - u, 0)
            for i in range(u):
                ahead = jnp.where(rows == bt - u + i, _row(nxt, i), ahead)
            dv = dv + taps[K - 1 - u] * ahead
            sums.append(jnp.sum(ahead * v, axis=0, keepdims=True))
        dv = dv.astype(dt)
        parts_ref[0] = (dv * xv).astype(dt)
        parts_ref[1] = (dy * c).astype(dt)
        parts_ref[2] = (dv * bv).astype(dt)
        # sums[u] is dw[:, K - 1 - u]
        taps_rows = jax.lax.broadcasted_iota(jnp.int32, dw_ref.shape, 0)
        upd = jnp.zeros(dw_ref.shape, f32)
        for u, s in enumerate(sums):
            upd = jnp.where(taps_rows == K - 1 - u, s, upd)
        dw_ref[...] += upd

    o_ref[0] = parts_ref[pl.program_id(3)]


def _params(interpret, axes):
    """Every grid axis in order: v's halo is carried along the row blocks,
    and dw's block stays in VMEM over the rows of a channel block."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=VMEM_LIMIT_BYTES,
        dimension_semantics=("arbitrary",) * axes)}


def _taps_table(w):
    """w [D, K] -> [8, D] float32 rounded through w's compute type, rows past
    K zero: the taps as the kernels read them."""
    return jnp.pad(w.T.astype(f32), [(0, CARRY - w.shape[1]), (0, 0)])


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _fwd_call(bcx, taps, bt, bd, interpret):
    B, T, D3 = bcx.shape
    D, K = D3 // 3, taps.shape[1]
    nc = D // bd
    vm = pltpu.VMEM

    def part(p):
        return pl.BlockSpec((1, bt, bd), lambda b, c, t: (b, t, p * nc + c),
                            memory_space=vm)

    return pl.pallas_call(
        functools.partial(_fwd_kernel, K=K),
        name="short_conv_fwd", grid=(B, nc, T // bt),
        in_specs=[part(0), part(1), part(2),
                  pl.BlockSpec((CARRY, bd), lambda b, c, t: (0, c),
                               memory_space=vm)],
        out_specs=pl.BlockSpec((1, bt, bd), lambda b, c, t: (b, t, c),
                               memory_space=vm),
        out_shape=jax.ShapeDtypeStruct((B, T, D), bcx.dtype),
        scratch_shapes=[pltpu.VMEM((CARRY, bd), f32)],
        interpret=interpret, **_params(interpret, 3))(
            bcx, bcx, bcx, _taps_table(taps))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _bwd_call(bcx, dy, taps, bt, bd, interpret):
    """(d[B ; C ; x~] [B, T, 3 D], dw [D, K] float32)."""
    B, T, D3 = bcx.shape
    D, K = D3 // 3, taps.shape[1]
    nc, nt = D // bd, T // bt
    vm = pltpu.VMEM

    def part(p):
        return pl.BlockSpec((1, bt, bd),
                            lambda c, b, t, q: (b, t, p * nc + c),
                            memory_space=vm)

    def ahead(p):
        return pl.BlockSpec(
            (1, HALO, bd),
            lambda c, b, t, q: (b, jnp.minimum((t + 1) * (bt // HALO),
                                               T // HALO - 1), p * nc + c),
            memory_space=vm)

    dbcx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K),
        name="short_conv_bwd", grid=(nc, B, nt, 3),
        in_specs=[part(0), part(1), part(2), part(0), ahead(0), ahead(1),
                  pl.BlockSpec((CARRY, bd), lambda c, b, t, q: (0, c),
                               memory_space=vm)],
        out_specs=[pl.BlockSpec((1, bt, bd),
                                lambda c, b, t, q: (b, t, q * nc + c),
                                memory_space=vm),
                   pl.BlockSpec((CARRY, bd), lambda c, b, t, q: (0, c),
                                memory_space=vm)],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((CARRY, D), f32)],
        scratch_shapes=[pltpu.VMEM((CARRY, bd), f32),
                        pltpu.VMEM((3, bt, bd), bcx.dtype)],
        interpret=interpret, **_params(interpret, 4))(
            bcx, bcx, bcx, dy, dy, bcx, _taps_table(taps))
    return dbcx, dw[:K].T


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def short_conv(bcx, w, bt, bd, interpret=False):
    """bcx [B, T, 3 D] = [B ; C ; x~], w [D, K] -> y [B, T, D], as
    ``short_conv_fwd`` / ``short_conv_bwd`` over blocks of bt rows and bd
    channels."""
    return _fwd_call(bcx, w.astype(bcx.dtype), bt, bd, interpret)


def _vjp_fwd(bcx, w, bt, bd, interpret):
    return _fwd_call(bcx, w.astype(bcx.dtype), bt, bd, interpret), (bcx, w)


def _vjp_bwd(bt, bd, interpret, res, dy):
    bcx, w = res
    dbcx, dw = _bwd_call(bcx, dy.astype(bcx.dtype), w.astype(bcx.dtype), bt,
                         bd, interpret)
    return dbcx, dw.astype(w.dtype)


short_conv.defvjp(_vjp_fwd, _vjp_bwd)


def gated_short_conv(bcx, w):
    """The layer's call where ``taken`` said so, per batch shard under a
    data-parallel trainer."""
    _, T, D3 = bcx.shape
    bt, bd = blocks(T, D3 // 3)
    return call_kernel(lambda bcx, w: short_conv(bcx, w, bt, bd), (bcx, w),
                       (0,))
