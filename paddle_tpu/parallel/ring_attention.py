"""Ring attention: sequence/context parallelism over the mesh 'sp' axis.

First-class long-context support (driver requirement; the 2017 reference
has no attention ops at all — SURVEY §5.7 — so this is the
beyond-parity extension that gives the rebuilt framework modern
long-sequence scaling). Design follows the ring-attention pattern from the
public literature (blockwise online-softmax accumulation while K/V blocks
rotate around the ICI ring via ``ppermute``): each device holds a T/P
slice of Q, K, V; P ring steps accumulate exact attention with O(T/P)
memory per chip, communication overlapped by XLA with the per-block
matmuls (MXU-bound for healthy block sizes).

Also provides ``ulysses_attention`` (all-to-all head-scatter sequence
parallelism): reshard [B, T/P, H, D] -> [B, T, H/P, D], run full attention
per head group locally, reshard back — cheaper for moderate T, head-count
divisible by P.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _online_block(q, k, v, o, m, l, q_pos, k_pos, causal, scale,
                  q_seg=None, k_seg=None):
    """One blockwise attention accumulation step (flash-style).

    q [B,Tq,H,D]; k,v [B,Tk,H,D]; o accum [B,Tq,H,D]; m,l [B,Tq,H].
    Scores in fp32 for numerical parity regardless of input dtype.
    q_seg/k_seg [B,Tq]/[B,Tk] (packed rows, docs/packing.md): scores
    between different segments are masked out, composing the
    block-diagonal packing mask with the causal mask."""
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = (q_pos[:, None] >= k_pos[None, :])          # [Tq, Tk]
        s = jnp.where(mask[None, :, None, :], s, -1e30)
    if q_seg is not None:
        allow = (q_seg[:, :, None] == k_seg[:, None, :])   # [B, Tq, Tk]
        s = jnp.where(allow[:, :, None, :], s, -1e30)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p_ = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + p_.sum(axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bqhk,bkhd->bqhd", p_.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return o_new, m_new, l_new


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                   axis_name: str = "sp", causal: bool = False,
                   scale: Optional[float] = None,
                   seg_q: Optional[jax.Array] = None,
                   seg_kv: Optional[jax.Array] = None) -> jax.Array:
    """Exact attention with Q/K/V sequence-sharded over ``axis_name``.

    q, k, v: [B, T, H, D] (global view; T sharded over the axis).
    seg_q/seg_kv: optional [B, T] packed-row segment ids (docs/packing.md),
    sharded like T — the K-side ids rotate around the ring with their K/V
    blocks, so every block applies the same block-diagonal segment mask a
    single-device attention would.
    Returns [B, T, H, D] with the same sharding.
    """
    D = q.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    segged = seg_q is not None

    def local(q, k, v, *segs):
        p = jax.lax.axis_size(axis_name)
        idx = jax.lax.axis_index(axis_name)
        B, Tq, H, Dh = q.shape
        Tk = k.shape[1]
        q_pos = idx * Tq + jnp.arange(Tq)
        sq, sk0 = segs if segged else (None, None)

        o = jnp.zeros((B, Tq, H, Dh), jnp.float32)
        m = jnp.full((B, Tq, H), -jnp.inf, jnp.float32)
        l = jnp.zeros((B, Tq, H), jnp.float32)

        def body(step, carry):
            o, m, l, k_cur, v_cur, sk_cur = carry
            src = (idx + step) % p           # which shard we hold this step
            k_pos = src * Tk + jnp.arange(Tk)
            o, m, l = _online_block(q, k_cur, v_cur, o, m, l, q_pos, k_pos,
                                    causal, scale, q_seg=sq, k_seg=sk_cur)
            # rotate K/V (and their segment ids) around the ring (ICI
            # neighbour exchange)
            perm = [(i, (i - 1) % p) for i in range(p)]
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            sk_nxt = jax.lax.ppermute(sk_cur, axis_name, perm) \
                if segged else sk_cur
            return o, m, l, k_nxt, v_nxt, sk_nxt

        sk_init = sk0 if segged else jnp.zeros((), jnp.int32)
        o, m, l, _, _, _ = jax.lax.fori_loop(0, p, body,
                                             (o, m, l, k, v, sk_init))
        return (o / jnp.maximum(l[..., None], 1e-20)).astype(q.dtype)

    spec = P(None, axis_name, None, None)
    seg_spec = P(None, axis_name)
    if segged:
        return shard_map(local, mesh=mesh,
                         in_specs=(spec, spec, spec, seg_spec, seg_spec),
                         out_specs=spec, check_vma=False)(q, k, v,
                                                          seg_q, seg_kv)
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                      axis_name: str = "sp", causal: bool = False,
                      scale: Optional[float] = None,
                      seg_q: Optional[jax.Array] = None,
                      seg_kv: Optional[jax.Array] = None) -> jax.Array:
    """DeepSpeed-Ulysses-style SP: all_to_all heads<->sequence, local full
    attention, all_to_all back. Requires H % axis_size == 0. seg_q/seg_kv
    ([B, T] packed-row segment ids sharded like T) are all-gathered to
    the full sequence — after the head scatter every device holds full-T
    scores, so the packing mask applies globally like the causal one."""
    D = q.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    segged = seg_q is not None

    def local(q, k, v, *segs):
        p = jax.lax.axis_size(axis_name)
        B, Tl, H, Dh = q.shape

        def scatter_heads(x):
            # [B, T/P, H, D] -> [B, T, H/P, D]
            x = x.reshape(B, Tl, p, H // p, Dh)
            x = jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                   tiled=False)
            return x.reshape(B, Tl * p, H // p, Dh)

        def gather_heads(x):
            # [B, T, H/P, D] -> [B, T/P, H, D]: received head chunks must be
            # merged chunk-major (concat_axis=2 -> [B, Tl, p, H/p, Dh]) so the
            # global head order is (source chunk, local head); concat_axis=3
            # would interleave head chunks whenever H/p > 1
            x = x.reshape(B, p, Tl, H // p, Dh)
            x = jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                   tiled=False)
            return x.reshape(B, Tl, H, Dh)

        qf, kf, vf = scatter_heads(q), scatter_heads(k), scatter_heads(v)
        T = qf.shape[1]
        s = jnp.einsum("bqhd,bkhd->bqhk", qf, kf,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            pos = jnp.arange(T)
            s = jnp.where((pos[:, None] >= pos[None, :])[None, :, None, :],
                          s, -1e30)
        if segged:
            sq, sk = segs
            # [B, T/P] shard -> full [B, T] (tiled=True concatenates the
            # gathered chunks along the sequence axis in ring order)
            sq = jax.lax.all_gather(sq, axis_name, axis=1, tiled=True)
            sk = jax.lax.all_gather(sk, axis_name, axis=1, tiled=True)
            s = jnp.where((sq[:, :, None] == sk[:, None, :])[:, :, None, :],
                          s, -1e30)
        a = jax.nn.softmax(s, axis=-1).astype(vf.dtype)
        of = jnp.einsum("bqhk,bkhd->bqhd", a, vf)
        return gather_heads(of)

    spec = P(None, axis_name, None, None)
    seg_spec = P(None, axis_name)
    if segged:
        return shard_map(local, mesh=mesh,
                         in_specs=(spec, spec, spec, seg_spec, seg_spec),
                         out_specs=spec, check_vma=False)(q, k, v,
                                                          seg_q, seg_kv)
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def reference_attention(q, k, v, causal=False, scale=None, seg_q=None,
                        seg_kv=None):
    """Single-device exact attention (numerical reference for tests).
    seg_q/seg_kv: optional [B, T] packed-row segment ids — scores across
    segments are masked (the packing block-diagonal mask)."""
    D = q.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        T, Tk = s.shape[1], s.shape[3]
        pos_q, pos_k = jnp.arange(T), jnp.arange(Tk)
        s = jnp.where((pos_q[:, None] >= pos_k[None, :])[None, :, None, :],
                      s, -1e30)
    if seg_q is not None:
        s = jnp.where((seg_q[:, :, None] == seg_kv[:, None, :])
                      [:, :, None, :], s, -1e30)
    a = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bqhk,bkhd->bqhd", a, v)
