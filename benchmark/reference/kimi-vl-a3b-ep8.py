"""Plain reference of one chip's share of Kimi-VL-A3B-Instruct's language
model (https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/config.json,
a DeepSeek-V3-style decoder): multi-head latent attention in every block, a
dense gated MLP in the first `first_k_dense_replace` blocks and a 64-expert
sigmoid-routed MoE with two shared experts and a selection bias in the others,
the chip holding `experts_held` of the routed experts.

Straightforward jax.numpy in float32; the caller sets
`jax.default_matmul_precision("highest")`. No kernel, no tiles, no import of
the program: the model is written out from its equations, and the parameters
carry the names the program gives them so that the comparison can go leaf by
leaf. `x` is one row, [T, d].

    norm      n(x; w) = x * rsqrt(mean(x^2) + eps) * w
    block l   h = x + attn_l(n(x; w_in));  y = h + ffn_l(n(h; w_post));
              ffn_l is the dense MLP where l < first_k_dense_replace, the MoE
              otherwise
    MLP       mlp(x; Wg, Wu, Wd) = (silu(x Wg) * (x Wu)) Wd
    attention q = x Wq -> [T, H, Dn + Dr] = [q_nope ; q_rope] (no query latent);
              [c ; k_r] = x Wkva, c [T, r], k_r [T, Dr];
              [k_nope ; v] = n(c; w_kv) Wkvb -> [T, H, Dn + Dv];
              rotate-half rotary at position t on q_rope and on k_r, which all
              heads share; k_h = [k_nope_h ; k_r];
              a = softmax(q_h k_h^T / sqrt(Dn + Dr) + causal); out = (a v) Wo
    MoE       s = sigmoid(x Wr) over all experts; the top k of s + b chosen;
              w = s_chosen / sum(s_chosen) * routed_scaling_factor;
              routed = sum over the chosen experts THAT ARE HELD
              ([first, first + held)) of w_e mlp_e(x); shared = mlp_s(x) of
              width n_shared_experts * moe_intermediate_size, no gate;
              out = routed + shared; what the absent experts would add is
              left out
    bias      b gets no gradient; after the step, from the step's counts c_e
              of (real token, chosen expert) pairs over ALL experts and both
              rows: b_e += gamma * sign(mean(c) - c_e)
    head      n(.; w_final), untied [d, V]; cost = sum over a row's tokens of
              -log softmax(logits)[next id], averaged over the rows

Every matrix product with a weight, and the attention's two products, go
through `q` (benchmark/reference/lowprec.py): the identity for the reference,
a rounding for the control.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERIES_PER_BLOCK = 512
# Where the embedding's rows start (the configuration's `assumed.init` says
# why): a component's standard deviation. The other decoder references start
# them at 1 / sqrt(hidden).
EMBEDDING_START = 1.0


def is_dense(a, l):
    return l < a["first_k_dense_replace"]


def param_table(a):
    """name -> (shape, init): ("normal", std) or ("const", value)."""
    V, d, n = a["vocab_size"], a["hidden_size"], a.get("name", "k")
    H, r = a["num_attention_heads"], a["kv_lora_rank"]
    Dn, Dr, Dv = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"]
    E, held, I = a["n_routed_experts"], a["experts_held"], a["moe_intermediate_size"]
    Is, Id = a["n_shared_experts"] * I, a["intermediate_size"]

    def w(*shape, fan_in):
        return (tuple(shape), ("normal", 1.0 / math.sqrt(fan_in)))

    def const(v, *shape):
        return (tuple(shape), ("const", float(v)))

    t = {f"_{n}_emb.w0": ((V, d), ("normal", EMBEDDING_START)),
         f"_{n}_head.w0": w(d, V, fan_in=d),
         f"_{n}_final_norm.w0": const(1, d)}
    for l in range(a["num_hidden_layers"]):
        b = f"_{n}_l{l}"
        t[f"{b}_in_norm.w0"] = const(1, d)
        t[f"{b}_post_norm.w0"] = const(1, d)
        t[f"{b}_attn.wq"] = w(d, H * (Dn + Dr), fan_in=d)
        t[f"{b}_attn.wkva"] = w(d, r + Dr, fan_in=d)
        t[f"{b}_attn.kv_norm"] = const(1, r)
        t[f"{b}_attn.wkvb"] = w(r, H * (Dn + Dv), fan_in=r)
        t[f"{b}_attn.wo"] = w(H * Dv, d, fan_in=H * Dv)
        if is_dense(a, l):
            t[f"{b}_mlp.wg"] = w(d, Id, fan_in=d)
            t[f"{b}_mlp.wu"] = w(d, Id, fan_in=d)
            t[f"{b}_mlp.wd"] = w(Id, d, fan_in=Id)
            continue
        t[f"{b}_moe.router"] = w(d, E, fan_in=d)
        t[f"{b}_moe.bias"] = const(0, E)
        t[f"{b}_moe.wg"] = w(held, d, I, fan_in=d)
        t[f"{b}_moe.wu"] = w(held, d, I, fan_in=d)
        t[f"{b}_moe.wd"] = w(held, I, d, fan_in=I)
        t[f"{b}_moe.shared_wg"] = w(d, Is, fan_in=d)
        t[f"{b}_moe.shared_wu"] = w(d, Is, fan_in=d)
        t[f"{b}_moe.shared_wd"] = w(Is, d, fan_in=Is)
    return t


def static_names(a):
    """The selection biases: state a rule moves, not a gradient."""
    return tuple(f"_{a.get('name', 'k')}_l{l}_moe.bias"
                 for l in range(a["num_hidden_layers"]) if not is_dense(a, l))


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
    T = x.shape[0]
    H, r = a["num_attention_heads"], a["kv_lora_rank"]
    Dn, Dr, Dv = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"]
    eps, theta = a["rms_norm_eps"], a["rope_theta"]
    qh = (q(x) @ q(p["wq"])).reshape(T, H, Dn + Dr)
    ckr = q(x) @ q(p["wkva"])
    kv = (q(rms_norm(ckr[:, :r], p["kv_norm"], eps)) @ q(p["wkvb"])) \
        .reshape(T, H, Dn + Dv)
    k_r = _rotary(ckr[:, None, r:], theta)                      # [T, 1, Dr]
    qh = jnp.concatenate([qh[..., :Dn], _rotary(qh[..., Dn:], theta)], -1)
    k = jnp.concatenate([kv[..., :Dn], jnp.broadcast_to(k_r, (T, H, Dr))], -1)
    v = kv[..., Dn:]
    n = _queries_per_block(T)

    @jax.checkpoint
    def queries(start):
        i = start + jnp.arange(n)
        s = jnp.einsum("thd,shd->hts", q(qh[i]), q(k)) / math.sqrt(Dn + Dr)
        keep = i[:, None] >= jnp.arange(T)[None, :]
        att = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        return jnp.einsum("hts,shd->thd", q(att), q(v))

    o = jax.lax.map(queries, jnp.arange(0, T, n)).reshape(T, H * Dv)
    return q(o) @ q(p["wo"])


def mlp(x, wg, wu, wd, q):
    return q(jax.nn.silu(q(x) @ q(wg)) * (q(x) @ q(wu))) @ q(wd)


def route(p, x, a, q):
    """(idx [T, k], w [T, k]): the experts chosen by score + bias, weighted
    by their scores alone."""
    s = jax.nn.sigmoid(q(x) @ q(p["router"]))
    idx = jax.lax.top_k(s + p["bias"], a["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / jnp.sum(w, -1, keepdims=True) * a["routed_scaling_factor"]


def routed(p, x, a, q, first=None, held=None):
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


def shared(p, x, a, q):
    return mlp(x, p["shared_wg"], p["shared_wu"], p["shared_wd"], q)


def pair_counts(p, x, real, a, q):
    """c [E]: the (real token, chosen expert) pairs of each expert."""
    idx = route(p, x, a, q)[0]
    hit = idx[..., None] == jnp.arange(a["n_routed_experts"])
    return jnp.sum(hit * real[:, None, None], axis=(0, 1))


def next_bias(b, counts, a):
    return b + a["bias_update_rate"] * jnp.sign(jnp.mean(counts) - counts)


def moe_ffn(p, x, a, q):
    return routed(p, x, a, q) + shared(p, x, a, q)


# ---- the model ---------------------------------------------------------------

def _local(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def _ffn_name(a, l):
    return "mlp" if is_dense(a, l) else "moe"


def _block_params(p, a, l):
    b = f"_{a.get('name', 'k')}_l{l}"
    return {"in_norm": p[f"{b}_in_norm.w0"], "post_norm": p[f"{b}_post_norm.w0"],
            "attn": _local(p, f"{b}_attn."),
            "ffn": _local(p, f"{b}_{_ffn_name(a, l)}.")}


def _block_names(a, l, tree):
    b = f"_{a.get('name', 'k')}_l{l}"
    out = {f"{b}_in_norm.w0": tree["in_norm"],
           f"{b}_post_norm.w0": tree["post_norm"]}
    out.update({f"{b}_attn.{k}": v for k, v in tree["attn"].items()})
    out.update({f"{b}_{_ffn_name(a, l)}.{k}": v for k, v in tree["ffn"].items()})
    return out


def _block(pb, x, real, a, q, dense):
    """(the block's output, the MoE's pair counts [E] or None)."""
    eps = a["rms_norm_eps"]
    h = x + attention(pb["attn"], rms_norm(x, pb["in_norm"], eps), a, q)
    hn = rms_norm(h, pb["post_norm"], eps)
    if dense:
        f = pb["ffn"]
        return h + mlp(hn, f["wg"], f["wu"], f["wd"], q), None
    return (h + moe_ffn(pb["ffn"], hn, a, q),
            jax.lax.stop_gradient(pair_counts(pb["ffn"], hn, real, a, q)))


def _head(ph, x, next_ids, mask, a, q):
    """One row's cost: sum over its real tokens."""
    xn = rms_norm(x, ph["final_norm"], a["rms_norm_eps"])
    logits = q(xn) @ q(ph["head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, next_ids[:, None], axis=-1)[:, 0]
    return jnp.sum((lse - picked) * mask)


def _head_params(p, a):
    n = a.get("name", "k")
    return {"final_norm": p[f"_{n}_final_norm.w0"], "head": p[f"_{n}_head.w0"]}


def _updated_biases(p, counts, a):
    """{leaf: its new value} from each MoE layer's counts over the batch."""
    n = a.get("name", "k")
    return {f"_{n}_l{l}_moe.bias":
            next_bias(p[f"_{n}_l{l}_moe.bias"], c, a) for l, c in counts.items()}


def _row_loss(p, ids, next_ids, mask, q, a):
    x, counts = p[f"_{a.get('name', 'k')}_emb.w0"][ids], {}
    for l in range(a["num_hidden_layers"]):
        x, c = _block(_block_params(p, a, l), x, mask, a, q, is_dense(a, l))
        if c is not None:
            counts[l] = c
    return _head(_head_params(p, a), x, next_ids, mask, a, q), counts


def loss(p, b, q, a):
    """(cost, the updated selection biases) of one padded batch; cost as the
    configuration defines it."""
    B = b["ids"].shape[0]
    rows = [_row_loss(p, b["ids"][r], b["next_ids"][r], b["ids_mask"][r], q, a)
            for r in range(B)]
    counts = {l: sum(c[l] for _, c in rows) for l in rows[0][1]}
    return sum(c for c, _ in rows) / B, _updated_biases(p, counts, a)


def _freeze(a):
    return tuple(sorted((k, v) for k, v in a.items()
                        if isinstance(v, (int, float, str, bool))))


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
    n = a.get("name", "k")
    emb_name = f"_{n}_emb.w0"
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
        for l in range(L):
            inputs.append(x)
            x, c = block(_block_params(p, a, l), x, real, dense=is_dense(a, l))
            if c is not None:
                counts[l] = counts.get(l, 0) + c
        c, (g_head, ct) = head_vg(ph, x, b["next_ids"][r], real)
        cost = cost + c / B
        accumulate({f"_{n}_final_norm.w0": g_head["final_norm"],
                    f"_{n}_head.w0": g_head["head"]})
        del g_head, x
        for l in reversed(range(L)):
            g, ct = block_vjp(_block_params(p, a, l), inputs.pop(), real, ct,
                              dense=is_dense(a, l))
            accumulate(_block_names(a, l, g))
            del g
        grads[emb_name] = scatter(grads[emb_name], ids, ct)
        del ct
    return (cost, _updated_biases(p, counts, a)), grads
