"""Plain reference of one chip's share of LFM2-8B-A1B's decoder
(https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json, model
type lfm2_moe): gated short convolutions and grouped-query attention as
`layer_types` orders them, a dense gated MLP in the first `num_dense_layers`
blocks and a 32-expert sigmoid-routed MoE with a selection bias (no shared
expert) in the others, the chip holding `experts_held` of the routed experts
and the blocks `layers_held` of `layer_types`, and a head tied to the
embedding.

Straightforward jax.numpy in float32; the caller sets
`jax.default_matmul_precision("highest")`. No kernel, no tiles, no import of
the program: the model is written out from its equations, and the parameters
carry the names the program gives them so that the comparison can go leaf by
leaf. `x` is one row, [T, d]. Block i of this file is block layers_held[i]
of the published model, and its leaves carry that index.

    norm      n(x; w) = x * rsqrt(mean(x^2) + eps) * w
    block     h = x + mixer(n(x; w_in));  y = h + ffn(n(h; w_post));
              mixer: conv or attention by layer_types; ffn: the dense MLP in
              the first num_dense_layers published blocks, the MoE otherwise
    conv      [B ; C ; x~] = x W_in (W_in [d, 3d]); v = B * x~;
              c_t = sum_j w[:, j] v_{t - K + 1 + j} (zeros before the row);
              out = (C * c) W_out; no bias
    attention q = x Wq -> [T, H, D], k = x Wk, v = x Wv -> [T, Hkv, D],
              D = d / H; q, k each n(.; w_q / w_k) over a head; rotate-half
              rotary at position t on the whole head; head h attends with
              key/value head h // (H / Hkv): softmax(q k^T / sqrt(D) +
              causal) v; out = concat(o_h) Wo; no bias
    MLP       mlp(x; Wg, Wu, Wd) = (silu(x Wg) * (x Wu)) Wd
    MoE       s = sigmoid(x Wr) over all experts; the top k of s + b chosen;
              w = s_chosen / (sum(s_chosen) + 1e-6) * routed_scaling_factor;
              out = sum over the chosen experts THAT ARE HELD
              ([first, first + held)) of w_e mlp_e(x); what the absent
              experts would add is left out
    bias      b gets no gradient; after the step, from the step's counts c_e
              of (real token, chosen expert) pairs over ALL experts and both
              rows: b_e += gamma * sign(mean(c) - c_e)
    head      n(.; w_final), the embedding's table transposed (tied);
              cost = sum over a row's tokens of -log softmax(logits)[next id],
              averaged over the rows

Every matrix product with a weight, and the attention's two products, go
through `q` (benchmark/reference/lowprec.py): the identity for the reference,
a rounding for the control.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERIES_PER_BLOCK = 256
# Where the embedding's rows start: a component's standard deviation (the
# configuration's `assumed.init` says why); the table is the head's too
EMBEDDING_START = None        # 1 / sqrt(hidden)


def _held(a):
    return list(a["layers_held"])


def is_dense(a, i):
    return _held(a)[i] < a["num_dense_layers"]


def is_conv(a, i):
    return a["layer_types"][_held(a)[i]] == "conv"


def _prefix(a, i):
    return f"_{a.get('name', 'f')}_l{_held(a)[i]}"


def param_table(a):
    """name -> (shape, init): ("normal", std) or ("const", value)."""
    V, d, n = a["vocab_size"], a["hidden_size"], a.get("name", "f")
    H, Hkv = a["num_attention_heads"], a["num_key_value_heads"]
    D, K = d // H, a["conv_L_cache"]
    held, I, Id = a["experts_held"], a["moe_intermediate_size"], \
        a["intermediate_size"]

    def w(*shape, fan_in):
        return (tuple(shape), ("normal", 1.0 / math.sqrt(fan_in)))

    def const(v, *shape):
        return (tuple(shape), ("const", float(v)))

    start = EMBEDDING_START or 1.0 / math.sqrt(d)
    t = {f"_{n}_emb.w0": ((V, d), ("normal", start)),
         f"_{n}_final_norm.w0": const(1, d)}
    for i in range(a["num_hidden_layers"]):
        b = _prefix(a, i)
        t[f"{b}_in_norm.w0"] = const(1, d)
        t[f"{b}_post_norm.w0"] = const(1, d)
        if is_conv(a, i):
            t[f"{b}_conv.in_proj"] = w(d, 3 * d, fan_in=d)
            t[f"{b}_conv.conv"] = w(d, K, fan_in=K)
            t[f"{b}_conv.out_proj"] = w(d, d, fan_in=d)
        else:
            t[f"{b}_attn.wq"] = w(d, H * D, fan_in=d)
            t[f"{b}_attn.wk"] = w(d, Hkv * D, fan_in=d)
            t[f"{b}_attn.wv"] = w(d, Hkv * D, fan_in=d)
            t[f"{b}_attn.wo"] = w(H * D, d, fan_in=H * D)
            t[f"{b}_attn.q_norm"] = const(1, D)
            t[f"{b}_attn.k_norm"] = const(1, D)
        if is_dense(a, i):
            t[f"{b}_mlp.wg"] = w(d, Id, fan_in=d)
            t[f"{b}_mlp.wu"] = w(d, Id, fan_in=d)
            t[f"{b}_mlp.wd"] = w(Id, d, fan_in=Id)
            continue
        t[f"{b}_moe.router"] = w(d, a["num_experts"], fan_in=d)
        t[f"{b}_moe.bias"] = const(0, a["num_experts"])
        t[f"{b}_moe.wg"] = w(held, d, I, fan_in=d)
        t[f"{b}_moe.wu"] = w(held, d, I, fan_in=d)
        t[f"{b}_moe.wd"] = w(held, I, d, fan_in=I)
    return t


def static_names(a):
    """The selection biases: state a rule moves, not a gradient."""
    return tuple(f"{_prefix(a, i)}_moe.bias"
                 for i in range(a["num_hidden_layers"]) if not is_dense(a, i))


def pad(rows, a):
    """Rows of (ids, next_ids) -> zero-padded id arrays and one 0/1 mask."""
    out = {}
    for col, name in enumerate(("ids", "next_ids")):
        seqs = [r[col] for r in rows]
        T = max(len(s) for s in seqs)
        ids = np.zeros((len(seqs), T), np.int32)
        mask = np.zeros((len(seqs), T), np.float32)
        for i, s in enumerate(seqs):
            ids[i, :len(s)] = s
            mask[i, :len(s)] = 1.0
        out[name], out[name + "_mask"] = ids, mask
    assert (out["ids_mask"] == out["next_ids_mask"]).all()
    return out


# ---- the layers, one row [T, d] at a time -----------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def short_conv(p, x, a, q):
    T, d = x.shape
    K = a["conv_L_cache"]
    bcx = q(x) @ q(p["in_proj"])
    B, C, xt = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    v = jnp.pad(B * xt, [(K - 1, 0), (0, 0)])
    c = sum(p["conv"][:, j] * v[j:j + T] for j in range(K))
    return q(C * c) @ q(p["out_proj"])


def _rotary(x, theta):
    """x [T, heads, D]: rotate-half on the whole last axis at positions
    0..T-1."""
    T, D = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    turned = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + turned * sin


def _queries_per_block(T):
    return max(n for n in range(1, min(T, QUERIES_PER_BLOCK) + 1) if T % n == 0)


def attention(p, x, a, q):
    T, d = x.shape
    H, Hkv = a["num_attention_heads"], a["num_key_value_heads"]
    D, eps, theta = d // H, a["norm_eps"], a["rope_theta"]
    qh = rms_norm((q(x) @ q(p["wq"])).reshape(T, H, D), p["q_norm"], eps)
    k = rms_norm((q(x) @ q(p["wk"])).reshape(T, Hkv, D), p["k_norm"], eps)
    v = (q(x) @ q(p["wv"])).reshape(T, Hkv, D)
    qh, k = _rotary(qh, theta), _rotary(k, theta)
    # head h reads key/value head h // (H / Hkv)
    k, v = jnp.repeat(k, H // Hkv, axis=1), jnp.repeat(v, H // Hkv, axis=1)
    n = _queries_per_block(T)

    @jax.checkpoint
    def queries(start):
        i = start + jnp.arange(n)
        s = jnp.einsum("thd,shd->hts", q(qh[i]), q(k)) / math.sqrt(D)
        keep = i[:, None] >= jnp.arange(T)[None, :]
        att = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        return jnp.einsum("hts,shd->thd", q(att), q(v))

    o = jax.lax.map(queries, jnp.arange(0, T, n)).reshape(T, H * D)
    return q(o) @ q(p["wo"])


def mlp(x, wg, wu, wd, q):
    return q(jax.nn.silu(q(x) @ q(wg)) * (q(x) @ q(wu))) @ q(wd)


def route(p, x, a, q):
    """(idx [T, k], w [T, k]): the experts chosen by score + bias, weighted
    by their scores alone."""
    s = jax.nn.sigmoid(q(x) @ q(p["router"]))
    idx = jax.lax.top_k(s + p["bias"], a["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / (jnp.sum(w, -1, keepdims=True) + 1e-6) \
        * a["routed_scaling_factor"]


def moe_ffn(p, x, a, q, first=None, held=None):
    """The routed part from the experts [first, first + held) of the layer's
    table (by default all it holds, which sit at a["first_expert"])."""
    table_first = a["first_expert"]
    first = table_first if first is None else first
    held = a["experts_held"] if held is None else held
    idx, top = route(p, x, a, q)
    lo = first - table_first

    @jax.checkpoint       # an expert's products again in the backward pass
    def weighted(x, wg, wu, wd, w_e):
        return w_e[:, None] * mlp(x, wg, wu, wd, q)

    def one(y, held_expert):              # a loop over the experts held
        wg, wu, wd, e = held_expert
        w_e = jnp.sum(jnp.where(idx == e, top, 0.0), -1)
        return y + weighted(x, wg, wu, wd, w_e), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["wg"][lo:lo + held], p["wu"][lo:lo + held], p["wd"][lo:lo + held],
        jnp.arange(first, first + held)))
    return y


def pair_counts(p, x, real, a, q):
    """c [E]: the (real token, chosen expert) pairs of each expert."""
    idx = route(p, x, a, q)[0]
    hit = idx[..., None] == jnp.arange(a["num_experts"])
    return jnp.sum(hit * real[:, None, None], axis=(0, 1))


def next_bias(b, counts, a):
    return b + a["bias_update_rate"] * jnp.sign(jnp.mean(counts) - counts)


# ---- the model ---------------------------------------------------------------

def _local(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def _names(a, i):
    return ("conv" if is_conv(a, i) else "attn",
            "mlp" if is_dense(a, i) else "moe")


def _block_params(p, a, i):
    b, (mixer, ffn) = _prefix(a, i), _names(a, i)
    return {"in_norm": p[f"{b}_in_norm.w0"], "post_norm": p[f"{b}_post_norm.w0"],
            "mixer": _local(p, f"{b}_{mixer}."), "ffn": _local(p, f"{b}_{ffn}.")}


def _block_names(a, i, tree):
    b, (mixer, ffn) = _prefix(a, i), _names(a, i)
    out = {f"{b}_in_norm.w0": tree["in_norm"],
           f"{b}_post_norm.w0": tree["post_norm"]}
    out.update({f"{b}_{mixer}.{k}": v for k, v in tree["mixer"].items()})
    out.update({f"{b}_{ffn}.{k}": v for k, v in tree["ffn"].items()})
    return out


def _block(pb, x, real, a, q, dense):
    """(the block's output, the MoE's pair counts [E] or None); the mixer's
    leaves say which mixer it is."""
    eps = a["norm_eps"]
    mixer = short_conv if "in_proj" in pb["mixer"] else attention
    h = x + mixer(pb["mixer"], rms_norm(x, pb["in_norm"], eps), a, q)
    hn = rms_norm(h, pb["post_norm"], eps)
    if dense:
        f = pb["ffn"]
        return h + mlp(hn, f["wg"], f["wu"], f["wd"], q), None
    return (h + moe_ffn(pb["ffn"], hn, a, q),
            jax.lax.stop_gradient(pair_counts(pb["ffn"], hn, real, a, q)))


def _head(ph, x, next_ids, mask, a, q):
    """One row's cost: sum over its real tokens."""
    xn = rms_norm(x, ph["final_norm"], a["norm_eps"])
    logits = q(xn) @ q(ph["table"]).T
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, next_ids[:, None], axis=-1)[:, 0]
    return jnp.sum((lse - picked) * mask)


def _head_names(a):
    n = a.get("name", "f")
    return {"final_norm": f"_{n}_final_norm.w0", "table": f"_{n}_emb.w0"}


def _head_params(p, a):
    return {k: p[v] for k, v in _head_names(a).items()}


def _updated_biases(p, counts, a):
    """{leaf: its new value} from each MoE layer's counts over the batch."""
    return {f"{_prefix(a, i)}_moe.bias":
            next_bias(p[f"{_prefix(a, i)}_moe.bias"], c, a)
            for i, c in counts.items()}


def _row_loss(p, ids, next_ids, mask, q, a):
    x, counts = p[f"_{a.get('name', 'f')}_emb.w0"][ids], {}
    for i in range(a["num_hidden_layers"]):
        x, c = _block(_block_params(p, a, i), x, mask, a, q, is_dense(a, i))
        if c is not None:
            counts[i] = c
    return _head(_head_params(p, a), x, next_ids, mask, a, q), counts


def loss(p, b, q, a):
    """(cost, the updated selection biases) of one padded batch; cost as the
    configuration defines it."""
    B = b["ids"].shape[0]
    rows = [_row_loss(p, b["ids"][r], b["next_ids"][r], b["ids_mask"][r], q, a)
            for r in range(B)]
    counts = {i: sum(c[i] for _, c in rows) for i in rows[0][1]}
    return sum(c for c, _ in rows) / B, _updated_biases(p, counts, a)


def _freeze(a):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in a.items()
                        if isinstance(v, (int, float, str, bool, list))))


@functools.lru_cache(maxsize=1)
def _programs(q, frozen):
    """The jitted parts of one rounding and set of arguments (the last one
    asked for: a loaded program takes device memory too)."""
    a = dict(frozen)
    block = jax.jit(lambda pb, x, real, dense: _block(pb, x, real, a, q, dense),
                    static_argnames="dense")
    head_vg = jax.jit(jax.value_and_grad(
        lambda ph, x, nxt, m: _head(ph, x, nxt, m, a, q), argnums=(0, 1)))

    @functools.partial(jax.jit, static_argnames="dense")
    def block_vjp(pb, x, real, ct, dense):
        return jax.vjp(lambda pb, x: _block(pb, x, real, a, q, dense)[0],
                       pb, x)[1](ct)

    return block, head_vg, block_vjp


def value_and_grad(p, b, q, a):
    """((cost, the updated biases), gradients) as
    jax.value_and_grad(loss, has_aux=True) gives them: one row at a time,
    block by block (each block's backward pass computes its forward again
    from the block's input). Not to be jitted as a whole."""
    block, head_vg, block_vjp = _programs(q, _freeze(a))
    L, B = a["num_hidden_layers"], b["ids"].shape[0]
    emb_name = f"_{a.get('name', 'f')}_emb.w0"
    add = jax.jit(lambda acc, g: jax.tree_util.tree_map(
        lambda u, v: u + v / B, acc, g), donate_argnums=0)
    scatter = jax.jit(lambda acc, ids, ct: acc.at[ids].add(ct / B),
                      donate_argnums=0)
    grads = {k: jnp.zeros_like(v) for k, v in p.items()}
    ph = _head_params(p, a)
    cost, counts = 0.0, {}

    def accumulate(part):                 # a part's gradients, then let go
        rest = {k: grads.pop(k) for k in part}
        grads.update(add(rest, part))

    for r in range(B):
        ids, real = b["ids"][r], b["ids_mask"][r]
        inputs, x = [], p[emb_name][ids]
        for i in range(L):
            inputs.append(x)
            x, c = block(_block_params(p, a, i), x, real, dense=is_dense(a, i))
            if c is not None:
                counts[i] = counts.get(i, 0) + c
        c, (g_head, ct) = head_vg(ph, x, b["next_ids"][r], real)
        cost = cost + c / B
        accumulate({v: g_head[k] for k, v in _head_names(a).items()})
        del g_head, x
        for i in reversed(range(L)):
            g, ct = block_vjp(_block_params(p, a, i), inputs.pop(), real, ct,
                              dense=is_dense(a, i))
            accumulate(_block_names(a, i, g))
            del g
        grads[emb_name] = scatter(grads[emb_name], ids, ct)
        del ct
    return (cost, _updated_biases(p, counts, a)), grads
