"""The gated delta rule in chunks (Gated DeltaNet, arXiv:2412.06464).

Per head, with state S [dk, dv] from zero:

    S <- exp(g_t) S;  S <- S + k_t (beta_t (v_t - S^T k_t))^T;  o_t = S^T q_t

computed C tokens at a time in the WY / UT-transform form. With gamma the
running sum of g inside a chunk and decay[i, j] = exp(gamma_i - gamma_j):

    inside a chunk (plain batched products, ``chunk_prepare``)
        A   = tril(beta K K^T * decay, -1)
        T   = (I + A)^-1
        U   = T (beta V)             W   = T (beta K exp(gamma))
        Aqk = tril(Q K^T * decay)    Q~  = Q exp(gamma)
        K~  = K exp(gamma_C - gamma) d   = exp(gamma_C)
    across chunks (the state pass, sequential)
        Vn  = U - W S;  O = Q~ S + Aqk Vn;  S <- d S + K~^T Vn

The state pass is the part that is a recurrence: ``state_pass_scan`` is its
``lax.scan`` form (the path everywhere but the TPU, and what the CPU tests
pin the kernels to); ``state_pass_kernel`` runs it as the Mosaic kernels
``gdn_chunk_fwd`` / ``gdn_chunk_bwd`` under a ``jax.custom_vjp``.

Their grid is (row groups, chunk groups): a grid step holds R (batch x head)
rows x Kc chunks of each, blocks [R, Kc, C, .], with S (backward: dS)
[R, dk, dv] float32 resident in VMEM over a row group's chunks. A
``fori_loop`` walks the Kc chunks (backward: groups and chunks in reverse);
in its body the R rows' chains stand side by side, independent, so that the
scheduler can interleave them on the MXUs (one row's chunk alone is three
products that wait on one another). R and Kc come from the shapes alone
(``state_pass_block``: divisors of BH and NC under ``_vmem_estimate_bytes``
against a quarter of ``VMEM_LIMIT_BYTES``, rows first, 1 x 1 where nothing
larger fits); the arithmetic of a chunk is the same in every block, bit for
bit. Bytes a chunk and launch, bf16 at C 64 and widths 128, beside the
benchmark's need of 90,116 forward + 180,232 backward:

    gdn_chunk_fwd, primal    reads W U Q~ K~ Aqk d 74,240  writes O 16,384
    gdn_chunk_fwd, the rule  the same, and writes S0 (float32) 65,536
    gdn_chunk_bwd            reads those, S0 and dO 156,160  writes 74,240

The primal call (no gradient asked: the first pass under a row's
``jax.checkpoint``, by ``optimize_remat``) writes no S0; W, Q~, K~ and Aqk
are read once as ``chunk_prepare`` stored them, and the products that need
them turned contract over their first dimension inside the kernel (``_tn``).

g, gamma, the decay products, T and S are float32 (wider under an f64
gradient check); the products take their operands in the dtype q, k, v come
in (bf16 under the mixed-precision policy) and accumulate in float32.

T is ``unit_lower_inverse``: the diagonal blocks of LEAF x LEAF of every
matrix at once by substitution, then pairs of blocks merged by products of
float32 operands at ``Precision.HIGHEST`` (at default precision the TPU would
round them to bf16), as many levels as the chunk's size asks for. Every
product is of true inverses, whose entries stay bounded; a Neumann doubling
over the whole chunk is not (every key of a chunk the same, beta 0.99:
max|T| is 1, the powers of A reach 1e18, the result is off by 8.6e10). Its
backward rule needs only T, dA = -tril(T^T dT T^T, -1), and T is the one
value a row's ``jax.checkpoint`` keeps (``gdn_T``): the backward pass runs
``gdn_chunk_fwd`` again, as the benchmark's cell demands (9 Mosaic calls a
step) and because every chunk's starting state would be 1.6 GB a layer, but
inverts nothing again.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels._pallas_util import VMEM_LIMIT_BYTES, round_up
from paddle_tpu.kernels._pallas_util import nt as _nt, tn as _tn

CHUNK = 64


def _mm(a, b, dtype, acc):
    return jnp.matmul(a.astype(dtype), b.astype(dtype),
                      preferred_element_type=acc)


def _mm_f32(a, b):
    """A product of float32 (float64) operands kept at that precision: at
    default precision the TPU would round them to one bf16 pass."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


LEAF = 32


@jax.jit    # traced once for all the layers of a step
def _inverse_by_blocks(A):
    lead, C = A.shape[:-2], A.shape[-1]
    b = math.gcd(LEAF, C)
    nb = C // b
    # the diagonal blocks of b x b of every matrix at once, by substitution:
    # row r of (I + D)^-1 is e_r - D[r, :r] (rows before r). Laid out
    # [row, column, matrix]: the matrices side by side in the lanes (a minor
    # dimension of 32 would be padded to 128) and the row in the leading
    # dimension, where a loop's index costs nothing. A `fori_loop`, not a
    # Python loop: every pass over the step's jaxpr walks the b - 1 updates,
    # which written out cost the cell 2.3 s of set-up.
    blocks = A.reshape(lead + (nb, b, nb, b))
    D = jnp.stack([blocks[..., m, :, m, :] for m in range(nb)], axis=-3)
    D = jnp.moveaxis(D.reshape((-1, b, b)), 0, -1)

    def row(r, T):
        d = jax.lax.dynamic_index_in_dim(D, r, keepdims=False)
        new = jax.lax.dynamic_slice_in_dim(T, r, 1) - jnp.sum(
            d[:, None, :] * T, axis=0, keepdims=True)
        return jax.lax.dynamic_update_slice_in_dim(T, new, r, axis=0)

    T = jax.lax.fori_loop(1, b, row, jnp.broadcast_to(
        jnp.eye(b, dtype=A.dtype)[:, :, None], D.shape))
    T = jnp.moveaxis(T, -1, 0).reshape(lead + (nb, b, b))
    # back on the diagonal of [C, C], zeros elsewhere
    T = T[..., :, :, None, :] * jnp.eye(nb, dtype=A.dtype)[:, None, :, None]
    T = T.reshape(A.shape)
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    size = b
    while size < C:
        # T holds the inverses of the diagonal blocks of `size`; L is A's
        # block below the diagonal inside every pair of them, and
        # [[T11, 0], [-T22 A21 T11, T22]] = T - T L T for all pairs at once
        L = jnp.where((i // (2 * size) == j // (2 * size))
                      & (i // size != j // size), A, 0)
        T = T - _mm_f32(T, _mm_f32(L, T))
        size *= 2
    return T


@jax.custom_vjp
def unit_lower_inverse(A):
    """T = (I + A)^-1 for A [..., C, C] strictly lower triangular, float32
    (float64 under a gradient check), any C."""
    return _inverse_by_blocks(A)


def _unit_lower_inverse_fwd(A):
    # Named on the one value that result and residual both come from: under
    # `save_only_these_names("gdn_T")` (layers/attention.py
    # `rows_one_at_a_time`) the backward pass then finds T and computes
    # nothing of the inverse again. Kept with the matrix flattened: the TPU
    # pads a float32 [..., 64, 64] to 128 lanes, twice the bytes.
    T = _inverse_by_blocks(A)
    flat = checkpoint_name(T.reshape(A.shape[:-2] + (-1,)), "gdn_T")
    return flat.reshape(A.shape), flat


def _unit_lower_inverse_bwd(flat, dT):
    Tt = jnp.swapaxes(flat.reshape(dT.shape), -1, -2)
    return (-jnp.tril(_mm_f32(Tt, _mm_f32(dT, Tt)), -1),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def chunk_prepare(q, k, v, g, beta, chunk):
    """q, k [B, T, H, dk] (q already scaled), v [B, T, H, dv], g, beta
    [B, T, H] -> (W, U, Qt, Kt, Aqk, d) laid out [B*H, NC, C, .] (d:
    [B*H, NC, 1, 1]) for the state pass; T is padded to whole chunks with
    tokens that write nothing (k = 0, beta = 0, g = 0)."""
    B, T, H, dk = k.shape
    dv, C = v.shape[-1], chunk
    dtype = v.dtype
    acc = jnp.promote_types(dtype, jnp.float32)
    NC = -(-T // C)

    def lay(x):
        x = jnp.pad(x, [(0, 0), (0, NC * C - T)] + [(0, 0)] * (x.ndim - 2))
        x = jnp.moveaxis(x, 2, 1)                       # [B, H, T, ...]
        return x.reshape((B * H, NC, C) + x.shape[3:])

    q, k, v = lay(q), lay(k), lay(v)
    g, beta = lay(g.astype(acc)), lay(beta.astype(acc))
    gamma = jnp.cumsum(g, axis=-1)                      # [BH, NC, C]
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    diff = gamma[..., :, None] - gamma[..., None, :]
    decay = jnp.exp(jnp.where(i >= j, diff, -jnp.inf))  # 0 above the diagonal
    kb = k.astype(acc) * beta[..., None]
    kk = _mm(kb, jnp.swapaxes(k, -1, -2), dtype, acc)
    A = jnp.where(i > j, kk * decay, 0.0)
    with jax.named_scope("gdn_tinv"):
        Tm = unit_lower_inverse(A)
    U = _mm(Tm, v.astype(acc) * beta[..., None], dtype, acc)
    W = _mm(Tm, kb * jnp.exp(gamma)[..., None], dtype, acc)
    Aqk = _mm(q, jnp.swapaxes(k, -1, -2), dtype, acc) * decay
    Qt = q.astype(acc) * jnp.exp(gamma)[..., None]
    last = gamma[..., -1:]
    Kt = k.astype(acc) * jnp.exp(last - gamma)[..., None]
    d = jnp.exp(last)[..., None]                        # [BH, NC, 1, 1]
    return tuple(x.astype(dtype) for x in (W, U, Qt, Kt, Aqk)) + (d,)


def state_pass_scan(W, U, Qt, Kt, Aqk, d):
    """O [BH, NC, C, dv] from the chunk quantities, chunk after chunk."""
    dtype = U.dtype
    acc = jnp.promote_types(dtype, jnp.float32)
    BH, dk, dv = W.shape[0], W.shape[-1], U.shape[-1]

    def step(S, xs):
        w, u, qt, kt, aqk, dd = xs
        vn = u.astype(acc) - _mm(w, S, dtype, acc)
        o = _mm(qt, S, dtype, acc) + _mm(aqk, vn, dtype, acc)
        S = dd * S + _mm(jnp.swapaxes(kt, -1, -2), vn, dtype, acc)
        return S, o.astype(dtype)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (W, U, Qt, Kt, Aqk, d))
    _, O = jax.lax.scan(step, jnp.zeros((BH, dk, dv), acc), xs)
    return jnp.moveaxis(O, 0, 1)


def gated_delta_rule(q, k, v, g, beta, chunk=CHUNK, state_pass=state_pass_scan):
    """o [B, T, H, dv] of the recurrence in the module docstring."""
    B, T, H, _ = k.shape
    dv = v.shape[-1]
    O = state_pass(*chunk_prepare(q, k, v, g, beta, chunk))
    return jnp.moveaxis(O.reshape(B, H, -1, dv)[:, :, :T], 1, 2)


# ---- the state pass as Mosaic kernels --------------------------------------

def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _fwd_kernel(w_ref, u_ref, qt_ref, kt_ref, aqk_ref, d_ref, o_ref, *rest):
    # rest: (s0_ref, s_scr) in the custom rule's forward, (s_scr,) in the
    # primal call, whose chunks' starting states nothing would read
    s0_ref, s_scr = rest if len(rest) == 2 else (None, rest[0])
    R, Kc = u_ref.shape[:2]

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    dt = u_ref.dtype

    def chunk(c, carry):
        # the R rows' chains are independent: side by side in one basic
        # block, for the scheduler to interleave on the MXUs
        for r in range(R):
            S = s_scr[r]
            if s0_ref is not None:
                s0_ref[r, c] = S
            Sb = S.astype(dt)
            vn = u_ref[r, c].astype(jnp.float32) - _dot(w_ref[r, c], Sb)
            vnb = vn.astype(dt)
            o = _dot(qt_ref[r, c], Sb) + _dot(aqk_ref[r, c], vnb)
            o_ref[r, c] = o.astype(o_ref.dtype)
            s_scr[r] = d_ref[r, c] * S + _tn(kt_ref[r, c], vnb)
        return carry

    jax.lax.fori_loop(0, Kc, chunk, 0)


def _bwd_kernel(w_ref, u_ref, qt_ref, kt_ref, aqk_ref, d_ref, s0_ref, do_ref,
                dw_ref, du_ref, dqt_ref, dkt_ref, daqk_ref, dd_ref, ds_scr):
    R, Kc = u_ref.shape[:2]

    @pl.when(pl.program_id(1) == 0)          # the LAST chunks: grid runs back
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    dt = u_ref.dtype

    def chunk(i, carry):
        c = Kc - 1 - i                       # and so does the walk inside
        for r in range(R):
            w = w_ref[r, c]
            S = s0_ref[r, c]
            Sb = S.astype(dt)
            dS = ds_scr[r]                   # gradient of the state AFTER
            dSb = dS.astype(dt)
            do = do_ref[r, c]
            vnb = (u_ref[r, c].astype(jnp.float32) - _dot(w, Sb)).astype(dt)
            dvn = _tn(aqk_ref[r, c], do) + _dot(kt_ref[r, c], dSb)
            dvnb = dvn.astype(dt)
            du_ref[r, c] = dvnb
            daqk_ref[r, c] = _nt(do, vnb).astype(daqk_ref.dtype)
            dqt_ref[r, c] = _nt(do, Sb).astype(dqt_ref.dtype)
            dkt_ref[r, c] = _nt(vnb, dSb).astype(dkt_ref.dtype)
            dw_ref[r, c] = (-_nt(dvnb, Sb)).astype(dw_ref.dtype)
            dd_ref[r, c] = jnp.sum(dS * S, axis=0, keepdims=True)
            ds_scr[r] = d_ref[r, c] * dS + _tn(qt_ref[r, c], do) \
                - _tn(w, dvnb)
        return carry

    jax.lax.fori_loop(0, Kc, chunk, 0)


def _vmem_estimate_bytes(R, Kc, C, dk, dv, itemsize):
    """What a grid step of the backward call (the larger of the two) holds in
    VMEM: every block twice, as the pipeline keeps the next one coming, the
    minor dimension padded to the 128 lanes and a [1, dv] row to 8 sublanes;
    dS resident; and the float32 values of the R chains that are alive at
    once (S, dS, their products: six of [dk, dv] a row, reckoned wide)."""
    ldk, ldv, lC = (round_up(n, 128) for n in (dk, dv, C))
    row = 8 * ldv * 4                                   # d, dd
    ins = (3 * C * ldk + 2 * C * ldv + C * lC) * itemsize \
        + dk * ldv * 4 + row                            # W Q~ K~, U dO, Aqk, S0
    outs = (3 * C * ldk + C * ldv + C * lC) * itemsize + row
    return 2 * R * Kc * (ins + outs) + 7 * R * dk * ldv * 4


# rows side by side in a grid step: enough independent chains for the four
# MXUs, few enough that the unrolled body stays a basic block Mosaic
# schedules in seconds
MAX_ROWS = 8


def state_pass_block(BH, NC, C, dk, dv, itemsize):
    """(R, Kc): how many rows and how many chunks of each one grid step of
    ``gdn_chunk_fwd`` / ``gdn_chunk_bwd`` holds. From the shapes alone: R the
    largest divisor of BH up to MAX_ROWS, then Kc the largest divisor of NC,
    whose blocks ``_vmem_estimate_bytes`` puts under a quarter of
    VMEM_LIMIT_BYTES (the rest is Mosaic's own: spills, the products'
    staging); rows give way before chunks do, down to (1, 1)."""
    budget = VMEM_LIMIT_BYTES // 4
    fits = lambda R, Kc: _vmem_estimate_bytes(R, Kc, C, dk, dv, itemsize) \
        <= budget
    divisors = lambda n, most: [m for m in range(min(n, most), 0, -1)
                                if n % m == 0]
    for R in divisors(BH, MAX_ROWS):
        for Kc in divisors(NC, NC):
            if fits(R, Kc):
                return R, Kc
    return 1, 1


def _block(shape, block, rev_of=None):
    """A block of R rows x Kc chunks of an array [BH, NC, ...]; with
    ``rev_of`` (the number of chunk groups) the groups are walked back."""
    if rev_of is None:
        index = lambda b, c: (b, c, 0, 0)
    else:
        index = lambda b, c: (b, rev_of - 1 - c, 0, 0)
    return pl.BlockSpec(tuple(block) + tuple(shape[2:]), index,
                        memory_space=pltpu.VMEM)


def _params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=VMEM_LIMIT_BYTES,
        dimension_semantics=("parallel", "arbitrary"))}


def _dvec(d, dv):
    return jnp.broadcast_to(d.astype(jnp.float32), d.shape[:3] + (dv,))


def _fwd_call(W, U, Qt, Kt, Aqk, d, interpret, keep_s0):
    """O, and with ``keep_s0`` every chunk's starting state (float32)."""
    BH, NC, C, dk = W.shape
    dv = U.shape[-1]
    R, Kc = block = state_pass_block(BH, NC, C, dk, dv, U.dtype.itemsize)
    ins = (W, U, Qt, Kt, Aqk, _dvec(d, dv))
    outs = (jax.ShapeDtypeStruct((BH, NC, C, dv), U.dtype),)
    if keep_s0:
        outs += (jax.ShapeDtypeStruct((BH, NC, dk, dv), jnp.float32),)
    return pl.pallas_call(
        _fwd_kernel, name="gdn_chunk_fwd", grid=(BH // R, NC // Kc),
        in_specs=[_block(x.shape, block) for x in ins],
        out_specs=[_block(x.shape, block) for x in outs], out_shape=list(outs),
        scratch_shapes=[pltpu.VMEM((R, dk, dv), jnp.float32)],
        interpret=interpret, **_params(interpret))(*ins)


def _bwd_call(W, U, Qt, Kt, Aqk, d, S0, dO, interpret):
    BH, NC, C, dk = W.shape
    dv, dt = U.shape[-1], U.dtype
    R, Kc = block = state_pass_block(BH, NC, C, dk, dv, dt.itemsize)
    ins = (W, U, Qt, Kt, Aqk, _dvec(d, dv), S0, dO.astype(dt))
    outs = (jax.ShapeDtypeStruct(W.shape, dt), jax.ShapeDtypeStruct(U.shape, dt),
            jax.ShapeDtypeStruct(Qt.shape, dt), jax.ShapeDtypeStruct(Kt.shape, dt),
            jax.ShapeDtypeStruct(Aqk.shape, dt),
            jax.ShapeDtypeStruct((BH, NC, 1, dv), jnp.float32))
    groups = NC // Kc
    return pl.pallas_call(
        _bwd_kernel, name="gdn_chunk_bwd", grid=(BH // R, groups),
        in_specs=[_block(x.shape, block, rev_of=groups) for x in ins],
        out_specs=[_block(x.shape, block, rev_of=groups) for x in outs],
        out_shape=list(outs),
        scratch_shapes=[pltpu.VMEM((R, dk, dv), jnp.float32)],
        interpret=interpret, **_params(interpret))(*ins)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def state_pass_kernel(W, U, Qt, Kt, Aqk, d, interpret=False):
    """``state_pass_scan`` as the Mosaic kernels ``gdn_chunk_fwd`` /
    ``gdn_chunk_bwd``: grid (row groups, chunk groups), S (and in the
    backward pass its gradient) resident in VMEM over a row group's chunks.
    This, the primal call, writes O alone; the custom rule's forward also
    hands out every chunk's starting state for the backward call."""
    return _fwd_call(W, U, Qt, Kt, Aqk, d, interpret, keep_s0=False)[0]


def _state_pass_fwd(W, U, Qt, Kt, Aqk, d, interpret):
    O, S0 = _fwd_call(W, U, Qt, Kt, Aqk, d, interpret, keep_s0=True)
    return O, (W, U, Qt, Kt, Aqk, d, S0)


def _state_pass_bwd(interpret, res, dO):
    W, U, Qt, Kt, Aqk, d, S0 = res
    dW, dU, dQt, dKt, dAqk, dd = _bwd_call(W, U, Qt, Kt, Aqk, d, S0, dO,
                                           interpret)
    return dW, dU, dQt, dKt, dAqk, \
        jnp.sum(dd, axis=-1, keepdims=True).astype(d.dtype)


# optimize_remat: under a row's `jax.checkpoint` the first pass keeps no
# residual of this rule, and JAX then runs the primal call in its place
state_pass_kernel.defvjp(_state_pass_fwd, _state_pass_bwd, optimize_remat=True)


def kernel_supported(dk, dv, chunk, dtype):
    return dk % 128 == 0 and dv % 128 == 0 and chunk % 16 == 0 \
        and dtype in (jnp.bfloat16, jnp.float32)


def pick_state_pass(who, dk, dv, chunk, dtype):
    """The state pass a layer takes: the Mosaic kernels on the TPU where
    their gate passes, the scan elsewhere (and under a data-parallel GSPMD
    step, whose batch the row-by-row mixer does not hand to ``call_kernel``
    shard by shard). The decision's line in the log is followed by the block
    the kernels engaged, once the shapes are there: a 1 x 1 fallback shows."""
    from paddle_tpu.kernels._pallas_util import (batch_shards, call_kernel,
                                                 log_once, take_pallas)

    ok = kernel_supported(dk, dv, chunk, dtype)
    why = f"dk {dk}, dv {dv}, chunk {chunk}, {jnp.dtype(dtype).name} is " \
        "outside the kernel's gate" if not ok else \
        "the batch is sharded over a mesh"
    if not take_pallas(who, "gdn_chunk_fwd/bwd", ok and batch_shards() == 1,
                       why):
        return state_pass_scan

    def kernels(*xs):
        (BH, NC, C, _), U = xs[0].shape, xs[1]
        R, Kc = state_pass_block(BH, NC, C, dk, dv, U.dtype.itemsize)
        log_once(who, f"gdn_chunk_fwd/bwd take rows {R} x chunks {Kc} of "
                 f"{BH} x {NC} a grid step; the primal call writes no S0")
        return call_kernel(state_pass_kernel, xs, range(6))

    return kernels
