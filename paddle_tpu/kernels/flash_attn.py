"""Grouped-query softmax attention under a structured mask known at trace
time, computed tile by tile and only where the mask keeps something
(docs/sdar.md).

    o_i = sum_j softmax_j(q_i . k_j  where keep(i, j)) v_j

q arrives scaled (the layer folds 1/sqrt(D) into the query). The head size of
q and k (Dk) may differ from that of v and o (Dv): latent attention's heads
are 192 : 128 (docs/kimi_vl.md), padded to 256 : 128 by the layer. The mask is
a RULE, not an array: ``("causal", L)`` over the L positions of a row,
keep(i, j) = j <= i, or ``("block_diffusion", L, b)`` over the 2L positions
``[noised ; clean]`` of a block-diffusion training row,

    keep(i, j) = (i <  L, j <  L, blk(j) == blk(i))      noised sees its block
               | (i <  L, j >= L, blk(j) <  blk(i))      and the clean past
               | (i >= L, j >= L, blk(j) <= blk(i))      clean is block-causal

with blk(i) = (i mod L) // b. ``mask_codes`` turns a rule into three small
integer vectors such that keep(i, j) = code[j] <= thr[i] or code[j] == eq[i];
``tile_plan`` classes every (query tile, key tile) of the square from them as
skipped (no pair kept), whole (every pair kept) or partial, and both
implementations walk that one plan:

``attention_tiles_xla``  one query tile at a time against its kept key tiles
    gathered side by side, plain XLA, each tile's scores computed again in
    the backward pass (the path everywhere but the TPU, and what the CPU
    tests pin the kernels to);
``flash_attention``      the Mosaic kernels ``flash_attn_fwd`` /
    ``flash_attn_bwd`` under a ``jax.custom_vjp``.

The kernels' grid is (batch, key/value heads, kept tiles): the plan's kept
tiles reach the index maps by scalar prefetch, so a skipped tile costs
nothing, not even a grid step. A grid step holds one query tile of the G
query heads that share a key/value head ([bq, G * Dk], read as the projection
stored it: no transpose) and one key and value tile [bk, Dk], [bk, Dv]; the G heads' chains
stand side by side in the body. Softmax is online, in float32; whole tiles
skip the mask's compares and selects. The forward pass keeps the
log-sum-exp [B, Hkv, T, G]; the backward pass is ONE walk of the same steps
(query tiles outermost): a tile's probabilities are computed once from the
log-sum-exp and feed all three gradients, five products a tile and head.
dQ accumulates in VMEM over a query tile's key tiles and leaves when the
query tile moves; dK and dV of the whole row of one key/value head stay in
VMEM ([Tp, Dk] and [Tp, Dv] float32, updated at the step's key rows) and leave once,
when the (row, key/value head) is done. That working set follows the row's
length, so ``kernel_gate`` admits the kernels only where
``bwd_vmem_bytes`` fits; a longer row takes the tiles in XLA. Bytes a
launch moves at the cells' shapes beside the benchmark's need are in
docs/sdar.md and docs/kimi_vl.md.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels._pallas_util import (NEG, VMEM_LIMIT_BYTES,
                                             call_kernel, log_once, round_up,
                                             take_pallas)
from paddle_tpu.kernels._pallas_util import nt as _nt, tn as _tn
from paddle_tpu.utils.error import enforce

SKIPPED, WHOLE, PARTIAL = 0, 1, 2
# the largest tile: [512, 512] float32 scores a head, G heads one after
# another, under the VMEM limit with every block double-buffered
TILE = 512
# a code no threshold reaches and no query asks for: padding keys
_NEVER = np.iinfo(np.int32).max


def mask_codes(rule, T, xp=np):
    """(thr [T], eq [T], code [T]) int32 of a rule over T positions:
    keep(i, j) = code[j] <= thr[i] or code[j] == eq[i]."""
    kind, L = rule[:2]
    enforce(kind in ("causal", "block_diffusion"),
            f"attention mask rule {kind!r} is not known")
    if kind == "causal":
        enforce(T == L, f"a causal mask over rows of {L} tokens needs {L} "
                f"positions, the layer got {T}")
        pos = xp.arange(T, dtype=xp.int32)
        return pos, xp.full((T,), -1, xp.int32), pos
    b = rule[2]
    enforce(T == 2 * L, f"a block_diffusion mask over rows of {L} tokens "
            f"needs 2 x {L} positions, the layer got {T}")
    nb = -(-L // b)
    pos = xp.arange(T, dtype=xp.int32)
    clean = pos >= L
    blk = xp.where(clean, pos - L, pos) // b
    thr = xp.where(clean, blk, blk - 1)
    eq = xp.where(clean, -1, nb + blk)
    code = xp.where(clean, blk, nb + blk)
    return thr.astype(xp.int32), eq.astype(xp.int32), code.astype(xp.int32)


def keep(thr_q, eq_q, code_k):
    """The element rule, on broadcastable integer arrays."""
    return (code_k <= thr_q) | (code_k == eq_q)


def positions(rule, T):
    """Rotary position of each of the T positions: i mod L."""
    return np.arange(T) % rule[1]


@functools.lru_cache(maxsize=None)
def tile_plan(rule, T, bq, bk):
    """Classes [ceil(T / bq), ceil(T / bk)] int8 of the square's tiles. A
    tile whose keys reach past T holds padding keys, which nothing keeps, so
    it is never whole (padding queries may be: their rows are cut off)."""
    thr, eq, code = mask_codes(rule, T)
    nq, nk = -(-T // bq), -(-T // bk)
    out = np.zeros((nq, nk), np.int8)
    for ki in range(nk):
        ks = np.sort(code[ki * bk:(ki + 1) * bk])
        for qi in range(nq):
            t, e = thr[qi * bq:(qi + 1) * bq], eq[qi * bq:(qi + 1) * bq]
            n = np.searchsorted(ks, t, "right") + np.where(
                e > t, np.searchsorted(ks, e, "right")
                - np.searchsorted(ks, e, "left"), 0)
            if n.max() > 0:
                out[qi, ki] = WHOLE if n.min() == bk else PARTIAL
    return out


def plan_counts(plan):
    """(kept, whole, partial, all) tiles of a plan."""
    whole, partial = int((plan == WHOLE).sum()), int((plan == PARTIAL).sum())
    return whole + partial, whole, partial, plan.size


def tile_sizes(T):
    """(bq, bk) from the row's length alone."""
    t = min(TILE, round_up(T, 128))
    return t, t


# ---- the tiles in XLA ------------------------------------------------------

def attention_tiles_xla(q, k, v, rule, bq, bk):
    """q [B, T, Hkv, G, Dk] (scaled), k [B, T, Hkv, Dk], v [B, T, Hkv, Dv]
    -> [B, T, Hkv, G, Dv]: one query tile at a time against the key tiles the plan keeps for it,
    scores [B, Hkv, G, bq, kept keys] in float32, computed again in the
    backward pass."""
    T = q.shape[1]
    plan = tile_plan(rule, T, bq, bk)
    thr, eq, code = mask_codes(rule, T)
    acc = jnp.promote_types(q.dtype, jnp.float32)

    @jax.checkpoint
    def one(qb, kb, vb, thr_q, eq_q, code_k):
        s = jnp.einsum("bqngd,bknd->bngqk", qb, kb, preferred_element_type=acc)
        kept = keep(thr_q[:, None], eq_q[:, None], code_k[None, :])
        a = jax.nn.softmax(jnp.where(kept, s, NEG), axis=-1)
        return jnp.einsum("bngqk,bknd->bqngd", a.astype(vb.dtype), vb)

    outs = []
    for qi in range(plan.shape[0]):
        rows = slice(qi * bq, min((qi + 1) * bq, T))
        cols = np.concatenate([np.arange(ki * bk, min((ki + 1) * bk, T))
                               for ki in np.nonzero(plan[qi])[0]])
        outs.append(one(q[:, rows], k[:, cols], v[:, cols], thr[rows],
                        eq[rows], code[cols]))
    return jnp.concatenate(outs, axis=1)


# ---- the Mosaic kernels ------------------------------------------------------

_FIRST, _LAST, _MASKED = 1, 2, 4


def _steps(plan):
    """The plan's kept tiles, each once, as the kernels' scalar prefetch:
    three int32 vectors (query tile, key tile, flags), query tiles
    outermost; _FIRST / _LAST mark where a query tile's accumulator starts
    and ends. Forward and backward launch walk the same steps."""
    qi, ki = np.nonzero(plan)
    flags = np.where(plan[qi, ki] == PARTIAL, _MASKED, 0)
    edge = np.flatnonzero(np.diff(qi)) + 1
    flags[np.concatenate([[0], edge])] |= _FIRST
    flags[np.concatenate([edge - 1, [len(qi) - 1]])] |= _LAST
    return qi.astype(np.int32), ki.astype(np.int32), flags.astype(np.int32)


def _column(block, g):
    """Column g of a [rows, G] block as [rows, 1], by a select and a lane
    reduction (no slice at a lane offset)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == g, block, 0.0), axis=1, keepdims=True)


def _columns(cols):
    """[rows, 1] columns side by side as one [rows, G] block."""
    G = len(cols)
    lane = jax.lax.broadcasted_iota(jnp.int32, (cols[0].shape[0], G), 1)
    out = jnp.zeros(lane.shape, cols[0].dtype)
    for g, c in enumerate(cols):
        out = jnp.where(lane == g, c, out)
    return out


def _either(flag, body):
    """``body(masked)`` under the step's class: a whole tile runs the copy
    without the mask's compares and selects."""
    pl.when((flag & _MASKED) != 0)(lambda: body(True))
    pl.when((flag & _MASKED) == 0)(lambda: body(False))


def _fwd_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, thr_ref, eq_ref,
                code_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, G, Dk, Dv):
    flag = fl_ref[pl.program_id(2)]

    @pl.when((flag & _FIRST) != 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(masked):
        k, v = k_ref[0], v_ref[0]
        if masked:
            kept = keep(thr_ref[...], eq_ref[...], code_ref[...])
        for g in range(G):
            cols = slice(g * Dv, (g + 1) * Dv)
            s = _nt(q_ref[0, :, g * Dk:(g + 1) * Dk], k)
            if masked:
                s = jnp.where(kept, s, NEG)
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            if masked:       # a row with nothing kept yet has m_new = NEG
                p = jnp.where(kept, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[g] = alpha * l_scr[g] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[:, cols] = alpha * acc_scr[:, cols] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[g] = m_new

    _either(flag, tile)

    @pl.when((flag & _LAST) != 0)
    def _():
        lse = []
        for g in range(G):
            cols = slice(g * Dv, (g + 1) * Dv)
            l = l_scr[g]
            l = jnp.where(l == 0.0, 1.0, l)      # padding queries keep nothing
            o_ref[0, :, cols] = (acc_scr[:, cols] / l).astype(o_ref.dtype)
            lse.append(m_scr[g] + jnp.log(l))
        lse_ref[0, 0] = _columns(lse)


def _bwd_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                dl_ref, thr_ref, eq_ref, code_ref, dq_ref, dk_ref, dv_ref,
                dq_scr, dk_scr, dv_scr, *, G, Dk, Dv):
    step = pl.program_id(2)
    flag, ki = fl_ref[step], ki_ref[step]

    @pl.when(step == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when((flag & _FIRST) != 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def tile(masked):
        k, v = k_ref[0], v_ref[0]
        lse, dl = lse_ref[0, 0], dl_ref[0, 0]
        if masked:
            kept = keep(thr_ref[...], eq_ref[...], code_ref[...])
        dk = dv = None
        for g in range(G):
            cols = slice(g * Dk, (g + 1) * Dk)
            q, do = q_ref[0, :, cols], do_ref[0, :, g * Dv:(g + 1) * Dv]
            p = jnp.exp(_nt(q, k) - _column(lse, g))
            if masked:
                p = jnp.where(kept, p, 0.0)
            ds = (p * (_nt(do, v) - _column(dl, g))).astype(k.dtype)
            dq_scr[:, cols] += jnp.dot(ds, k,
                                       preferred_element_type=jnp.float32)
            dv_g, dk_g = _tn(p.astype(do.dtype), do), _tn(ds, q)
            dv, dk = (dv_g, dk_g) if g == 0 else (dv + dv_g, dk + dk_g)
        # the G heads' sum, then one update of the resident pair's key rows
        dv_scr[ki] += dv
        dk_scr[ki] += dk

    _either(flag, tile)

    @pl.when((flag & _LAST) != 0)
    def _():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=VMEM_LIMIT_BYTES,
        dimension_semantics=("parallel", "parallel", "arbitrary"))}


def _specs(G, Dk, Dv, bq, bk, nk):
    """BlockSpecs by what a block follows: the step's query tile (``q``,
    ``dq`` of width G * Dk; ``o``, ``do`` of G * Dv) or its key tile (``k``,
    ``v``), read from the prefetched steps, or (``dk``, ``dv``) nothing but
    the (row, key/value head): such a block stays in VMEM for the whole
    walk and goes back to HBM once."""
    vm = pltpu.VMEM

    def query(width):
        return pl.BlockSpec((1, bq, width),
                            lambda b, n, s, qi, ki, fl: (b, qi[s], n),
                            memory_space=vm)

    def key(width):
        return pl.BlockSpec((1, bk, width),
                            lambda b, n, s, qi, ki, fl: (b, ki[s], n),
                            memory_space=vm)

    def row(width):
        return pl.BlockSpec((1, nk, bk, width),
                            lambda b, n, s, qi, ki, fl: (b, 0, 0, n),
                            memory_space=vm)

    return {
        "q": query(G * Dk), "o": query(G * Dv), "k": key(Dk), "v": key(Dv),
        "dk": row(Dk), "dv": row(Dv),
        "stat": pl.BlockSpec((1, 1, bq, G),
                             lambda b, n, s, qi, ki, fl: (b, n, qi[s], 0),
                             memory_space=vm),
        "qcode": pl.BlockSpec((bq, 1),
                              lambda b, n, s, qi, ki, fl: (qi[s], 0),
                              memory_space=vm),
        "kcode": pl.BlockSpec((1, bk),
                              lambda b, n, s, qi, ki, fl: (0, ki[s]),
                              memory_space=vm),
    }


def _codes(rule, T, Tp):
    """The rule's vectors as the kernels read them, padded to Tp positions:
    thr, eq [Tp, 1] (a padding query keeps nothing), code [1, Tp] (nothing
    keeps a padding key). Built in the program from iotas: no constant."""
    thr, eq, code = mask_codes(rule, T, jnp)
    pad = (0, Tp - T)
    return (jnp.pad(thr, pad, constant_values=-1)[:, None],
            jnp.pad(eq, pad, constant_values=-1)[:, None],
            jnp.pad(code, pad, constant_values=_NEVER)[None, :])


def _pad_rows(x, Tp):
    T = x.shape[1]
    return x if T == Tp else jnp.pad(x, [(0, 0), (0, Tp - T), (0, 0)])


def _head_sizes(q, k, v, Hkv):
    """(Dk, Dv, G) of q [.., H * Dk], k [.., Hkv * Dk], v [.., Hkv * Dv]."""
    return k.shape[-1] // Hkv, v.shape[-1] // Hkv, q.shape[-1] // k.shape[-1]


def _fwd_call(q, k, v, rule, Hkv, bq, bk, interpret):
    """(o [B, T, H * Dv], lse [B, Hkv, Tp, G] float32)."""
    B, T, _ = q.shape
    Dk, Dv, G = _head_sizes(q, k, v, Hkv)
    Tp = round_up(T, math.lcm(bq, bk))
    steps = _steps(tile_plan(rule, T, bq, bk))
    spec = _specs(G, Dk, Dv, bq, bk, Tp // bk)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, G=G, Dk=Dk, Dv=Dv),
        name="flash_attn_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, Hkv, len(steps[0])),
            in_specs=[spec["q"], spec["k"], spec["v"], spec["qcode"],
                      spec["qcode"], spec["kcode"]],
            out_specs=[spec["o"], spec["stat"]],
            scratch_shapes=[pltpu.VMEM((G, bq, 1), jnp.float32),
                            pltpu.VMEM((G, bq, 1), jnp.float32),
                            pltpu.VMEM((bq, G * Dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, Tp, G * Hkv * Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, Tp, G), jnp.float32)],
        interpret=interpret, **_params(interpret))(
            *steps, _pad_rows(q, Tp), _pad_rows(k, Tp), _pad_rows(v, Tp),
            *_codes(rule, T, Tp))
    return o[:, :T], lse


def _bwd_call(q, k, v, o, lse, do, rule, Hkv, bq, bk, interpret):
    B, T, _ = q.shape
    Dk, Dv, G = _head_sizes(q, k, v, Hkv)
    Tp = round_up(T, math.lcm(bq, bk))
    nk = Tp // bk
    steps = _steps(tile_plan(rule, T, bq, bk))
    # sum_d o do of every (position, head), as the log-sum-exp is laid out
    dl = jnp.sum((o.astype(jnp.float32) * do.astype(jnp.float32))
                 .reshape(B, T, Hkv, G, Dv), axis=-1)
    dl = jnp.pad(jnp.moveaxis(dl, 1, 2), [(0, 0), (0, 0), (0, Tp - T), (0, 0)])
    spec = _specs(G, Dk, Dv, bq, bk, nk)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, G=G, Dk=Dk, Dv=Dv),
        name="flash_attn_bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, Hkv, len(steps[0])),
            in_specs=[spec["q"], spec["k"], spec["v"], spec["o"],
                      spec["stat"], spec["stat"], spec["qcode"],
                      spec["qcode"], spec["kcode"]],
            out_specs=[spec["q"], spec["dk"], spec["dv"]],
            scratch_shapes=[pltpu.VMEM((bq, G * Dk), jnp.float32),
                            pltpu.VMEM((nk, bk, Dk), jnp.float32),
                            pltpu.VMEM((nk, bk, Dv), jnp.float32)]),
        # dk, dv as [B, key tiles, bk, Hkv * D]: the same bytes as
        # [B, Tp, Hkv * D], and a key tile's rows are one leading index
        out_shape=[jax.ShapeDtypeStruct((B, Tp) + q.shape[2:], q.dtype),
                   jax.ShapeDtypeStruct((B, nk, bk) + k.shape[2:], k.dtype),
                   jax.ShapeDtypeStruct((B, nk, bk) + v.shape[2:], v.dtype)],
        interpret=interpret, **_params(interpret))(
            *steps, _pad_rows(q, Tp), _pad_rows(k, Tp), _pad_rows(v, Tp),
            _pad_rows(do.astype(q.dtype), Tp), lse, dl, *_codes(rule, T, Tp))
    return (dq[:, :T], dk.reshape(B, Tp, -1)[:, :T],
            dv.reshape(B, Tp, -1)[:, :T])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, rule, Hkv, bq, bk, interpret=False):
    """q [B, T, H * Dk] (scaled; head h = n * G + g reads key/value head n),
    k [B, T, Hkv * Dk], v [B, T, Hkv * Dv] -> o [B, T, H * Dv], as
    ``flash_attn_fwd`` /
    ``flash_attn_bwd`` over the kept tiles of ``rule``."""
    return _fwd_call(q, k, v, rule, Hkv, bq, bk, interpret)[0]


def _flash_fwd(q, k, v, rule, Hkv, bq, bk, interpret):
    o, lse = _fwd_call(q, k, v, rule, Hkv, bq, bk, interpret)
    # what a layer's `jax.checkpoint` may keep, so that its backward pass
    # computes the projections again but not this launch
    o = checkpoint_name(o, "flash_attn_o")
    lse = checkpoint_name(lse, "flash_attn_lse")
    return o, (q, k, v, o, lse)


def _flash_bwd(rule, Hkv, bq, bk, interpret, res, do):
    return _bwd_call(*res, do, rule, Hkv, bq, bk, interpret)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def kernel_supported(D, dtype, Dv=None):
    """A value head of whole lanes (the q / k head goes in padded to them,
    `attention`), in bf16 or float32."""
    return (Dv or D) % 128 == 0 and dtype in (jnp.bfloat16, jnp.float32)


# what the backward launch may hold of VMEM_LIMIT_BYTES by the estimate
# below; the rest is Mosaic's own (spills, the products' staging)
_VMEM_BUDGET = VMEM_LIMIT_BYTES * 7 // 8


def bwd_vmem_bytes(T, D, G, dtype, Dv=None):
    """(all, dK/dV) bytes ``flash_attn_bwd`` holds in VMEM over a row of T
    positions at head sizes D (q, k) and Dv (v, o; D where not given): dK
    and dV of one key/value head, whole, in float32, and their result
    blocks; every tile block twice, as the pipeline keeps the next one
    coming; dQ's accumulator; the body's [bq, bk] float32 temporaries
    (scores, probabilities, dp, ds and their roundings)."""
    Dv = Dv or D
    bq, bk = tile_sizes(T)
    Tp, item = round_up(T, math.lcm(bq, bk)), jnp.dtype(dtype).itemsize
    pair = Tp * (D + Dv) * 4
    tiles = 2 * (bq * G * (2 * D + Dv) + bk * (D + Dv)) * item \
        + bq * G * D * 4
    stats = 2 * 2 * bq * round_up(G, 128) * 4
    return (pair + 2 * Tp * (D + Dv) * item + tiles + stats
            + 6 * bq * bk * 4, pair)


def kernel_gate(T, D, G, dtype, Dv=None):
    """(eligible, why not): the kernels cover value heads that fill the
    lanes in bf16 / float32, on rows whose dK / dV (the q / k head padded to
    whole lanes) fit in VMEM."""
    if not kernel_supported(D, dtype, Dv):
        sizes = D if Dv in (None, D) else f"{D} : {Dv}"
        return False, (f"head size {sizes}, {jnp.dtype(dtype).name} is "
                       "outside the kernel's gate")
    need = bwd_vmem_bytes(T, round_up(D, 128), G, dtype, Dv)[0]
    return need <= _VMEM_BUDGET, (
        f"over a row of {T} positions flash_attn_bwd would hold "
        f"{need / 1e6:.1f} MB in VMEM, dK and dV whole, against the "
        f"{_VMEM_BUDGET / 1e6:.1f} MB of the kernel's gate")


def _pad_heads(x, D, Dp):
    """[B, T, heads * D] -> [B, T, heads * Dp], zeros behind every head."""
    B, T, _ = x.shape
    x = jnp.pad(x.reshape(B, T, -1, D), [(0, 0)] * 3 + [(0, Dp - D)])
    return x.reshape(B, T, -1)


def attention(who, q, k, v, rule, Hkv):
    """The layer's call: q [B, T, H * Dk] (scaled), k [B, T, Hkv * Dk],
    v [B, T, Hkv * Dv] -> [B, T, H * Dv], by the Mosaic kernels on the TPU
    where their gate passes and by the same tiles in XLA elsewhere; the log
    says once a layer which, how much of the square the rule keeps and what
    the backward launch holds in VMEM."""
    B, T, _ = q.shape
    Dk, Dv, G = _head_sizes(q, k, v, Hkv)
    # the kernels read a head as whole lanes: a q / k head that is not
    # (latent attention's 192) goes in with zeros behind it, which add
    # nothing to a score
    Dp = round_up(Dk, 128)
    bq, bk = tile_sizes(T)
    kept, whole, partial, every = plan_counts(tile_plan(rule, T, bq, bk))
    pallas = take_pallas(who, "flash_attn_fwd/bwd",
                         *kernel_gate(T, Dk, G, q.dtype, Dv),
                         otherwise="the tiles in XLA")
    log_once(who, f"mask {rule}: {kept} of {every} tiles of {bq} x {bk} kept "
             f"({whole} whole, {partial} partial)")
    if pallas:
        need, pair = bwd_vmem_bytes(T, Dp, G, q.dtype, Dv)
        log_once(who, f"flash_attn_bwd: one walk of {kept} tiles, dK/dV "
                 f"{pair / 1e6:.1f} MB of {need / 1e6:.1f} MB in VMEM")
        if Dp != Dk:
            log_once(who, f"q and k heads of {Dk} go in as {Dp} lanes")
            q, k = _pad_heads(q, Dk, Dp), _pad_heads(k, Dk, Dp)
        return call_kernel(
            lambda q, k, v: flash_attention(q, k, v, rule, Hkv, bq, bk),
            (q, k, v), range(3))
    o = attention_tiles_xla(q.reshape(B, T, Hkv, G, Dk),
                            k.reshape(B, T, Hkv, Dk), v.reshape(B, T, Hkv, Dv),
                            rule, bq, bk)
    return o.reshape(B, T, Hkv * G * Dv)
