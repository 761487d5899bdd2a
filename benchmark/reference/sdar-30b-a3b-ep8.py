"""Plain reference of one chip's share of SDAR-30B-A3B-Chat
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json)
under the block-diffusion training objective (BD3-LM, arXiv:2503.09573,
which SDAR's paper, arXiv:2510.06303, trains with): grouped-query attention
and a 128-expert top-8 MoE with no shared expert in every block, the chip
holding `experts_held` of the routed experts.

Straightforward jax.numpy in float32; the caller sets
`jax.default_matmul_precision("highest")`. No kernel, no tiles, no import of
the program: the model is written out from its equations, and the parameters
carry the names the program gives them so that the comparison can go leaf by
leaf. `x` is one row of the 2L positions, [2L, d].

    noising   a row of L clean ids x0, blocks of b tokens, blk(i) = i // b;
              t_k = 1/256 + (noise_t[k] + 0.5) for block k; token i is
              masked where mask_u[i] + 0.5 < t_blk(i) and is then replaced
              by the mask id, giving xt; the model reads [xt ; x0]
    positions pos(i) = i mod L, blk(i) = (i mod L) // b for i in [0, 2L)
    mask      M[i, j] = (i <  L and j <  L and blk(j) == blk(i))
                     or (i <  L and j >= L and blk(j) <  blk(i))
                     or (i >= L and j >= L and blk(j) <= blk(i))
    norm      n(x; w) = x * rsqrt(mean(x^2) + eps) * w
    block l   h = x + attn_l(n(x; w_in));  y = h + moe_l(n(h; w_post))
    attention q = x Wq, k = x Wk, v = x Wv per head; q, k normed over the head
              (weight w); rotate-half rotary on the whole head at pos(i);
              each key/value head serves H / Hkv query heads;
              a = softmax(q k^T / sqrt(D) where M); out = (a v) Wo
    MoE       p = softmax(x Wr) over all experts; the top k of p renormalised;
              routed = sum over the chosen experts THAT ARE HELD
              ([first, first + held)) of p_e (silu(x Wg_e) * (x Wu_e)) Wd_e;
              no shared expert; what the absent experts would add is left out
    head      n(.; w_final), untied [d, V], on the noised half only;
              cost = sum over a row's masked tokens of
              (1 / t_blk(i)) * -log softmax(logits_i)[x0_i] (no shift),
              averaged over the rows

Every matrix product with a weight, and the attention's two products, go
through `q` (benchmark/reference/lowprec.py): the identity for the reference,
a rounding for the control. A row's padding (none in the cell's traffic) is
computed as tokens of id 0 that carry no loss, as the program computes it.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERIES_PER_BLOCK = 512
# Where the per-head q and k norm weights start (the configuration's
# `assumed.init` says why): the scores' spread is its square.
QK_NORM_START = 3.0


def param_table(a):
    """name -> (shape, init): ("normal", std) or ("const", value)."""
    V, d, n = a["vocab_size"], a["hidden_size"], a.get("name", "s")
    H, Hkv, D = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    E, held, I = a["num_experts"], a["experts_held"], a["moe_intermediate_size"]

    def w(*shape, fan_in):
        return (tuple(shape), ("normal", 1.0 / math.sqrt(fan_in)))

    def const(v, *shape):
        return (tuple(shape), ("const", float(v)))

    t = {f"_{n}_emb.w0": w(V, d, fan_in=d), f"_{n}_head.w0": w(d, V, fan_in=d),
         f"_{n}_final_norm.w0": const(1, d)}
    for l in range(a["num_hidden_layers"]):
        b = f"_{n}_l{l}"
        t[f"{b}_in_norm.w0"] = const(1, d)
        t[f"{b}_post_norm.w0"] = const(1, d)
        t[f"{b}_attn.wq"] = w(d, H * D, fan_in=d)
        t[f"{b}_attn.wk"] = w(d, Hkv * D, fan_in=d)
        t[f"{b}_attn.wv"] = w(d, Hkv * D, fan_in=d)
        t[f"{b}_attn.wo"] = w(H * D, d, fan_in=H * D)
        t[f"{b}_attn.q_norm"] = const(QK_NORM_START, D)
        t[f"{b}_attn.k_norm"] = const(QK_NORM_START, D)
        t[f"{b}_moe.router"] = w(d, E, fan_in=d)
        t[f"{b}_moe.wg"] = w(held, d, I, fan_in=d)
        t[f"{b}_moe.wu"] = w(held, d, I, fan_in=d)
        t[f"{b}_moe.wd"] = w(held, I, d, fan_in=I)
    return t


def static_names(a):
    return ()


def pad(rows, a):
    """Rows of (ids, mask_u, noise_t) -> zero-padded ids with their 0/1
    mask, and the two noise columns as they are."""
    seqs = [r[0] for r in rows]
    T = max(len(s) for s in seqs)
    ids = np.zeros((len(seqs), T), np.int32)
    mask = np.zeros((len(seqs), T), np.float32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
        mask[i, :len(s)] = 1.0
    return {"ids": ids, "ids_mask": mask,
            "mask_u": np.stack([np.asarray(r[1], np.float32) for r in rows]),
            "noise_t": np.stack([np.asarray(r[2], np.float32) for r in rows])}


# ---- the objective's two pieces: the noised row and the mask ----------------

def noise(ids, real, mask_u, noise_t, a):
    """(the 2L ids [xt ; x0], the loss weight of each of the L tokens) of one
    row, token by token."""
    L, b = ids.shape[0], a["block_length"]
    t = (1.0 / 256 + (noise_t + 0.5))[jnp.arange(L) // b]      # of each token
    masked = (mask_u + 0.5 < t) & (real > 0)
    xt = jnp.where(masked, a["mask_token_id"], ids)
    return jnp.concatenate([xt, ids]), jnp.where(masked, 1.0 / t, 0.0)


def mask(i, j, L, b):
    """M[i, j] of the rule for index arrays i (queries) and j (keys),
    numpy or jax.numpy."""
    qn, kn = (i < L)[:, None], (j < L)[None, :]
    qb, kb = ((i % L) // b)[:, None], ((j % L) // b)[None, :]
    return (qn & kn & (kb == qb)) | (qn & ~kn & (kb < qb)) \
        | (~qn & ~kn & (kb <= qb))


def dense_mask(L, b):
    """M [2L, 2L] bool."""
    return mask(np.arange(2 * L), np.arange(2 * L), L, b)


# ---- the layers, one row [2L, d] at a time -----------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotary(x, pos, theta):
    """x [T, heads, D]: rotate-half on the whole head at positions pos [T]."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    turned = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + turned * sin


def _queries_per_block(L, b):
    """The most queries a block of the loop below may hold: a divisor of L
    made of whole diffusion blocks (so that a query's own block lies inside
    its query block), L itself where there is none."""
    fits = [n for n in range(b, min(L, QUERIES_PER_BLOCK) + 1, b) if L % n == 0]
    return max(fits) if fits else L


def attention(p, x, a, q):
    T = x.shape[0]
    L, b = T // 2, a["block_length"]
    H, Hkv, D = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    eps, G = a["rms_norm_eps"], H // Hkv
    pos = jnp.arange(T) % L
    qh = (q(x) @ q(p["wq"])).reshape(T, Hkv, G, D)
    k = (q(x) @ q(p["wk"])).reshape(T, Hkv, D)
    v = (q(x) @ q(p["wv"])).reshape(T, Hkv, D)
    qh = _rotary(rms_norm(qh.reshape(T, H, D), p["q_norm"], eps), pos,
                 a["rope_theta"]).reshape(T, Hkv, G, D)
    k = _rotary(rms_norm(k, p["k_norm"], eps), pos, a["rope_theta"])
    n = _queries_per_block(L, b)

    @jax.checkpoint
    def queries(start):
        """The n queries from `start` on against the keys the mask can give
        them: the noised positions of their own stretch of the row and every
        clean position (M is zero for them anywhere else)."""
        i = start + jnp.arange(n)
        j = jnp.concatenate([start % L + jnp.arange(n), L + jnp.arange(L)])
        s = jnp.einsum("tngd,snd->ngts", q(qh[i]), q(k[j])) / math.sqrt(D)
        att = jax.nn.softmax(jnp.where(mask(i, j, L, b), s, -1e30), axis=-1)
        return jnp.einsum("ngts,snd->tngd", q(att), q(v[j]))

    o = jax.lax.map(queries, jnp.arange(0, T, n)).reshape(T, H * D)
    return q(o) @ q(p["wo"])


def _expert(x, wg, wu, wd, q):
    return q(jax.nn.silu(q(x) @ q(wg)) * (q(x) @ q(wu))) @ q(wd)


def routed(p, x, a, q, first=None, held=None):
    """The routed part from the experts [first, first + held) of the layer's
    table (by default all it holds, which sit at a["first_expert"])."""
    table_first = a["first_expert"]
    first = table_first if first is None else first
    held = a["experts_held"] if held is None else held
    probs = jax.nn.softmax(q(x) @ q(p["router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, a["num_experts_per_tok"])
    top = top / jnp.sum(top, -1, keepdims=True)
    lo = first - table_first

    @jax.checkpoint       # an expert's products again in the backward pass
    def weighted(x, wg, wu, wd, w_e):
        return w_e[:, None] * _expert(x, wg, wu, wd, q)

    def one(y, held_expert):              # a loop over the experts held
        wg, wu, wd, e = held_expert
        w_e = jnp.sum(jnp.where(idx == e, top, 0.0), -1)
        return y + weighted(x, wg, wu, wd, w_e), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["wg"][lo:lo + held], p["wu"][lo:lo + held], p["wd"][lo:lo + held],
        jnp.arange(first, first + held)))
    return y


def moe_ffn(p, x, a, q):
    return routed(p, x, a, q)


# ---- the model ---------------------------------------------------------------

def _local(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def _block_params(p, a, l):
    b = f"_{a.get('name', 's')}_l{l}"
    return {"in_norm": p[f"{b}_in_norm.w0"], "post_norm": p[f"{b}_post_norm.w0"],
            "attn": _local(p, f"{b}_attn."), "moe": _local(p, f"{b}_moe.")}


def _block_names(a, l, tree):
    b = f"_{a.get('name', 's')}_l{l}"
    out = {f"{b}_in_norm.w0": tree["in_norm"],
           f"{b}_post_norm.w0": tree["post_norm"]}
    out.update({f"{b}_attn.{k}": v for k, v in tree["attn"].items()})
    out.update({f"{b}_moe.{k}": v for k, v in tree["moe"].items()})
    return out


def _block(pb, x, a, q):
    eps = a["rms_norm_eps"]
    h = x + attention(pb["attn"], rms_norm(x, pb["in_norm"], eps), a, q)
    return h + moe_ffn(pb["moe"], rms_norm(h, pb["post_norm"], eps), a, q)


def _head(ph, x, ids, weights, a, q):
    """One row's cost, from the noised half of the last block's output."""
    L = ids.shape[0]
    xn = rms_norm(x[:L], ph["final_norm"], a["rms_norm_eps"])
    logits = q(xn) @ q(ph["head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, ids[:, None], axis=-1)[:, 0]
    return jnp.sum((lse - picked) * weights)


def _head_params(p, a):
    n = a.get("name", "s")
    return {"final_norm": p[f"_{n}_final_norm.w0"], "head": p[f"_{n}_head.w0"]}


def _row_loss(p, ids, real, mask_u, noise_t, q, a):
    both, weights = noise(ids, real, mask_u, noise_t, a)
    x = p[f"_{a.get('name', 's')}_emb.w0"][both]
    for l in range(a["num_hidden_layers"]):
        x = _block(_block_params(p, a, l), x, a, q)
    return _head(_head_params(p, a), x, ids, weights, a, q)


def loss(p, b, q, a):
    """(cost, {}) of one padded batch; cost as the configuration defines it."""
    B = b["ids"].shape[0]
    rows = [_row_loss(p, b["ids"][r], b["ids_mask"][r], b["mask_u"][r],
                      b["noise_t"][r], q, a) for r in range(B)]
    return sum(rows) / B, {}


def _freeze(a):
    return tuple(sorted((k, v) for k, v in a.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=1)
def _programs(q, frozen):
    """The jitted parts of one rounding and set of arguments (the last one
    asked for: a loaded program takes device memory too)."""
    a = dict(frozen)
    block = jax.jit(lambda pb, x: _block(pb, x, a, q))
    head_vg = jax.jit(jax.value_and_grad(
        lambda ph, x, ids, w: _head(ph, x, ids, w, a, q), argnums=(0, 1)))
    block_vjp = jax.jit(lambda pb, x, ct: jax.vjp(
        lambda pb, x: _block(pb, x, a, q), pb, x)[1](ct))
    noised = jax.jit(lambda ids, real, u, t: noise(ids, real, u, t, a))
    return block, head_vg, block_vjp, noised


def value_and_grad(p, b, q, a):
    """((cost, {}), gradients) as jax.value_and_grad(loss, has_aux=True)
    gives them: one row at a time, block by block (each block's backward
    pass computes its forward again from the block's input). Not to be
    jitted as a whole."""
    block, head_vg, block_vjp, noised = _programs(q, _freeze(a))
    n_layers, B = a["num_hidden_layers"], b["ids"].shape[0]
    n = a.get("name", "s")
    emb_name = f"_{n}_emb.w0"
    add = jax.jit(lambda acc, g: jax.tree_util.tree_map(
        lambda u, v: u + v / B, acc, g), donate_argnums=0)
    scatter = jax.jit(lambda acc, ids, ct: acc.at[ids].add(ct / B),
                      donate_argnums=0)
    grads = {k: jnp.zeros_like(v) for k, v in p.items()}
    ph = _head_params(p, a)
    cost = 0.0

    def accumulate(part):                 # a part's gradients, then let go
        rest = {k: grads.pop(k) for k in part}
        grads.update(add(rest, part))

    for r in range(B):
        both, weights = noised(b["ids"][r], b["ids_mask"][r], b["mask_u"][r],
                               b["noise_t"][r])
        inputs, x = [], p[emb_name][both]
        for l in range(n_layers):
            inputs.append(x)
            x = block(_block_params(p, a, l), x)
        c, (g_head, ct) = head_vg(ph, x, b["ids"][r], weights)
        cost = cost + c / B
        accumulate({f"_{n}_final_norm.w0": g_head["final_norm"],
                    f"_{n}_head.w0": g_head["head"]})
        del g_head, x
        for l in reversed(range(n_layers)):
            g, ct = block_vjp(_block_params(p, a, l), inputs.pop(), ct)
            accumulate(_block_names(a, l, g))
            del g
        grads[emb_name] = scatter(grads[emb_name], both, ct)
        del ct
    return (cost, {}), grads
