"""Plain reference of one chip's share of Qwen3-Next-80B-A3B
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json):
gated DeltaNet 3 : 1 gated attention, a 512-expert MoE with a shared expert
after every mixer, the chip holding `experts_held` of the routed experts.

Straightforward jax.numpy in float32; the caller sets
`jax.default_matmul_precision("highest")`. No kernel, no chunking of the
delta rule, no import of the program: the model is written out from its
equations, and the parameters carry the names the program gives them so
that the comparison can go leaf by leaf. `x` is one row, [T, d].

    norm      n(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)
    block l   h = x + mixer_l(n(x; w_in));  y = h + moe_l(n(h; w_post));
              mixer_l is gated attention where (l + 1) % interval == 0,
              gated DeltaNet otherwise
    head      n(.; w_final), untied [d, V]; cost = sum over a row's tokens of
              -log softmax(logits)[next id], averaged over the rows
    attention [q, gate] = split(x Wq) per head, k = x Wk, v = x Wv;
              q, k normed over the head (1 + w), rotary (rotate-half) on the
              first `rot` dimensions; each key/value head serves H / Hkv
              query heads; a = softmax(q k^T / sqrt(D) + causal);
              out = ((a v) * sigmoid(gate)) Wo
    DeltaNet  [q, k, v, z] = split(x W_qkvz), [b, a] = split(x W_ba);
              [q, k, v] = silu(causal depthwise conv, kernel K, zeros on the
              left); q, k L2-normalised, repeated to the value heads, q
              scaled by 1/sqrt(dk); beta = sigmoid(b),
              g = -exp(A_log) * softplus(a + dt_bias); per head, S from 0:
              S <- exp(g_t) S;  S <- S + k_t (beta_t (v_t - S^T k_t))^T;
              o_t = S^T q_t -- token by token;
              out = (o * rsqrt(mean(o^2) + eps) * w_g * silu(z)) W_out
    MoE       p = softmax(x Wr) over all experts; the top k of p renormalised;
              routed = sum over the chosen experts THAT ARE HELD
              ([first, first + held)) of p_e (silu(x Wg_e) * (x Wu_e)) Wd_e;
              shared = sigmoid(x w_sg) * expert_shared(x); what the absent
              experts would add is left out

Every matrix product with a weight, and the attention's two products, go
through `q` (benchmark/reference/lowprec.py): the identity for the reference,
a rounding for the control.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

TOKENS_PER_CHECKPOINT = 64


def _dims(a):
    Hk, Hv = a["linear_num_key_heads"], a["linear_num_value_heads"]
    dk, dv = a["linear_key_head_dim"], a["linear_value_head_dim"]
    return Hk, Hv, dk, dv


def is_attention(a, l):
    return (l + 1) % a["full_attention_interval"] == 0


def param_table(a):
    """name -> (shape, init): ("normal", std) or ("const", value)."""
    V, d, n = a["vocab_size"], a["hidden_size"], a.get("name", "q")
    H, Hkv, D = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    Hk, Hv, dk, dv = _dims(a)
    K = a["linear_conv_kernel_dim"]
    E, held = a["num_experts"], a["experts_held"]
    I, Is = a["moe_intermediate_size"], a["shared_expert_intermediate_size"]

    def w(*shape, fan_in):
        return (tuple(shape), ("normal", 1.0 / math.sqrt(fan_in)))

    def const(v, *shape):
        return (tuple(shape), ("const", float(v)))

    t = {f"_{n}_emb.w0": w(V, d, fan_in=d), f"_{n}_head.w0": w(d, V, fan_in=d),
         f"_{n}_final_norm.w0": const(0, d)}
    for l in range(a["num_hidden_layers"]):
        b = f"_{n}_l{l}"
        t[f"{b}_in_norm.w0"] = const(0, d)
        t[f"{b}_post_norm.w0"] = const(0, d)
        if is_attention(a, l):
            t[f"{b}_attn.wq"] = w(d, H * 2 * D, fan_in=d)
            t[f"{b}_attn.wk"] = w(d, Hkv * D, fan_in=d)
            t[f"{b}_attn.wv"] = w(d, Hkv * D, fan_in=d)
            t[f"{b}_attn.wo"] = w(H * D, d, fan_in=H * D)
            t[f"{b}_attn.q_norm"] = const(0, D)
            t[f"{b}_attn.k_norm"] = const(0, D)
        else:
            t[f"{b}_gdn.wqkvz"] = w(d, 2 * Hk * dk + 2 * Hv * dv, fan_in=d)
            t[f"{b}_gdn.wba"] = w(d, 2 * Hv, fan_in=d)
            t[f"{b}_gdn.conv"] = w(2 * Hk * dk + Hv * dv, K, fan_in=K)
            t[f"{b}_gdn.a_log"] = const(0, Hv)
            t[f"{b}_gdn.dt_bias"] = const(1, Hv)
            t[f"{b}_gdn.norm"] = const(1, dv)
            t[f"{b}_gdn.wout"] = w(Hv * dv, d, fan_in=Hv * dv)
        t[f"{b}_moe.router"] = w(d, E, fan_in=d)
        t[f"{b}_moe.wg"] = w(held, d, I, fan_in=d)
        t[f"{b}_moe.wu"] = w(held, d, I, fan_in=d)
        t[f"{b}_moe.wd"] = w(held, I, d, fan_in=I)
        t[f"{b}_moe.shared_gate"] = w(d, 1, fan_in=d)
        t[f"{b}_moe.shared_wg"] = w(d, Is, fan_in=d)
        t[f"{b}_moe.shared_wu"] = w(d, Is, fan_in=d)
        t[f"{b}_moe.shared_wd"] = w(Is, d, fan_in=Is)
    return t


def static_names(a):
    return ()


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


# ---- the layers, one row [T, d] at a time ---------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def _rotary(x, theta, rot):
    """x [T, heads, D]: rotate-half on the first `rot` dimensions."""
    T, half = x.shape[0], rot // 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    xr, xp = x[..., :rot], x[..., rot:]
    turned = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([xr * cos + turned * sin, xp], -1)


def gated_attention(p, x, a, q):
    T = x.shape[0]
    H, Hkv, D = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    rot, eps = int(D * a["partial_rotary_factor"]), a["rms_norm_eps"]
    qg = (q(x) @ q(p["wq"])).reshape(T, H, 2 * D)
    qh, gate = qg[..., :D], qg[..., D:]
    k = (q(x) @ q(p["wk"])).reshape(T, Hkv, D)
    v = (q(x) @ q(p["wv"])).reshape(T, Hkv, D)
    qh = _rotary(rms_norm(qh, p["q_norm"], eps), a["rope_theta"], rot)
    k = _rotary(rms_norm(k, p["k_norm"], eps), a["rope_theta"], rot)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    G = H // Hkv
    outs = []
    for n in range(Hkv):                  # one softmax per block of G heads
        s = jnp.einsum("tgd,sd->gts", q(qh[:, n * G:(n + 1) * G]), q(k[:, n]))
        s = jnp.where(causal[None], s / math.sqrt(D), -1e30)
        att = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("gts,sd->tgd", q(att), q(v[:, n])))
    o = jnp.concatenate(outs, 1).reshape(T, H * D)
    o = o * jax.nn.sigmoid(gate.reshape(T, H * D))
    return q(o) @ q(p["wo"])


def delta_rule(qh, k, v, g, beta):
    """The recurrence itself, token by token: qh, k [T, H, dk], v [T, H, dv],
    g, beta [T, H] -> o [T, H, dv]. The state after every
    TOKENS_PER_CHECKPOINT tokens is kept for the backward pass, the others
    are computed again."""
    T, H, dk = k.shape
    dv, n = v.shape[-1], TOKENS_PER_CHECKPOINT
    Tp = -(-T // n) * n

    def chunks(x):        # padding tokens write nothing: k = 0, beta = 0, g = 0
        x = jnp.pad(x, [(0, Tp - T)] + [(0, 0)] * (x.ndim - 1))
        return x.reshape((Tp // n, n) + x.shape[1:])

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, None, None] * S
        read = jnp.einsum("hk,hkv->hv", k_t, S)
        S = S + k_t[:, :, None] * (b_t[:, None] * (v_t - read))[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    @jax.checkpoint
    def chunk(S, xs):
        return jax.lax.scan(token, S, xs, unroll=8)

    _, o = jax.lax.scan(chunk, jnp.zeros((H, dk, dv), jnp.float32),
                        tuple(chunks(x) for x in (qh, k, v, g, beta)))
    return o.reshape(Tp, H, dv)[:T]


def gated_delta_net(p, x, a, q):
    T = x.shape[0]
    Hk, Hv, dk, dv = _dims(a)
    K, eps = a["linear_conv_kernel_dim"], a["rms_norm_eps"]
    nk, nv = Hk * dk, Hv * dv
    mixed = q(x) @ q(p["wqkvz"])
    qkv, z = mixed[:, :2 * nk + nv], mixed[:, 2 * nk + nv:]
    ba = q(x) @ q(p["wba"])
    b, a_ = ba[:, :Hv], ba[:, Hv:]
    padded = jnp.pad(qkv, [(K - 1, 0), (0, 0)])
    conv = sum(padded[j:j + T] * p["conv"][:, j] for j in range(K))
    qkv = jax.nn.silu(conv)
    qh = qkv[:, :nk].reshape(T, Hk, dk)
    k = qkv[:, nk:2 * nk].reshape(T, Hk, dk)
    v = qkv[:, 2 * nk:].reshape(T, Hv, dv)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    qh = jnp.repeat(l2(qh), Hv // Hk, axis=1) / math.sqrt(dk)
    k = jnp.repeat(l2(k), Hv // Hk, axis=1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(a_ + p["dt_bias"])
    o = delta_rule(qh, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * p["norm"]
    o = o * jax.nn.silu(z.reshape(T, Hv, dv))
    return q(o.reshape(T, nv)) @ q(p["wout"])


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

    def one(y, held_expert):              # a loop over the experts held
        wg, wu, wd, e = held_expert
        w_e = jnp.sum(jnp.where(idx == e, top, 0.0), -1)
        return y + w_e[:, None] * _expert(x, wg, wu, wd, q), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["wg"][lo:lo + held], p["wu"][lo:lo + held], p["wd"][lo:lo + held],
        jnp.arange(first, first + held)))
    return y


def shared(p, x, a, q):
    gate = jax.nn.sigmoid(q(x) @ q(p["shared_gate"]))
    return gate * _expert(x, p["shared_wg"], p["shared_wu"], p["shared_wd"], q)


def moe_ffn(p, x, a, q):
    return routed(p, x, a, q) + shared(p, x, a, q)


# ---- the model -------------------------------------------------------------

def _local(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def _block_params(p, a, l):
    n = a.get("name", "q")
    b = f"_{n}_l{l}"
    mixer = "attn" if is_attention(a, l) else "gdn"
    return {"in_norm": p[f"{b}_in_norm.w0"], "post_norm": p[f"{b}_post_norm.w0"],
            "mixer": _local(p, f"{b}_{mixer}."), "moe": _local(p, f"{b}_moe.")}


def _block_names(a, l, tree):
    n = a.get("name", "q")
    b = f"_{n}_l{l}"
    mixer = "attn" if is_attention(a, l) else "gdn"
    out = {f"{b}_in_norm.w0": tree["in_norm"],
           f"{b}_post_norm.w0": tree["post_norm"]}
    out.update({f"{b}_{mixer}.{k}": v for k, v in tree["mixer"].items()})
    out.update({f"{b}_moe.{k}": v for k, v in tree["moe"].items()})
    return out


def _block(pb, x, a, q, attention):
    mixer = gated_attention if attention else gated_delta_net
    eps = a["rms_norm_eps"]
    h = x + mixer(pb["mixer"], rms_norm(x, pb["in_norm"], eps), a, q)
    return h + moe_ffn(pb["moe"], rms_norm(h, pb["post_norm"], eps), a, q)


def _head(ph, x, next_ids, mask, a, q):
    """One row's cost: sum over its real tokens."""
    xn = rms_norm(x, ph["final_norm"], a["rms_norm_eps"])
    logits = q(xn) @ q(ph["head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, next_ids[:, None], axis=-1)[:, 0]
    return jnp.sum((lse - picked) * mask)


def _head_params(p, a):
    n = a.get("name", "q")
    return {"final_norm": p[f"_{n}_final_norm.w0"], "head": p[f"_{n}_head.w0"]}


def _row_loss(p, ids, next_ids, mask, q, a):
    x = p[f"_{a.get('name', 'q')}_emb.w0"][ids]
    for l in range(a["num_hidden_layers"]):
        x = _block(_block_params(p, a, l), x, a, q, is_attention(a, l))
    return _head(_head_params(p, a), x, next_ids, mask, a, q)


def loss(p, b, q, a):
    """(cost, {}) of one padded batch; cost as the configuration defines it."""
    B = b["ids"].shape[0]
    rows = [_row_loss(p, b["ids"][r], b["next_ids"][r], b["ids_mask"][r], q, a)
            for r in range(B)]
    return sum(rows) / B, {}


def _freeze(a):
    return tuple(sorted((k, v) for k, v in a.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=1)
def _programs(q, frozen):
    """The jitted parts of one rounding and set of arguments (the last one
    asked for: a loaded program takes device memory too)."""
    a = dict(frozen)
    block = jax.jit(lambda pb, x, attention: _block(pb, x, a, q, attention),
                    static_argnames="attention")
    head_vg = jax.jit(jax.value_and_grad(
        lambda ph, x, nxt, m: _head(ph, x, nxt, m, a, q), argnums=(0, 1)))

    @functools.partial(jax.jit, static_argnames="attention")
    def block_vjp(pb, x, ct, attention):
        return jax.vjp(lambda pb, x: _block(pb, x, a, q, attention), pb, x)[1](ct)

    return block, head_vg, block_vjp


def value_and_grad(p, b, q, a):
    """((cost, {}), gradients) as jax.value_and_grad(loss, has_aux=True)
    gives them: one row at a time, block by block (each block's backward
    pass computes its forward again from the block's input). Not to be
    jitted as a whole."""
    block, head_vg, block_vjp = _programs(q, _freeze(a))
    L, B = a["num_hidden_layers"], b["ids"].shape[0]
    emb_name = f"_{a.get('name', 'q')}_emb.w0"
    add = jax.jit(lambda acc, g: jax.tree_util.tree_map(
        lambda u, v: u + v / B, acc, g), donate_argnums=0)
    scatter = jax.jit(lambda acc, ids, ct: acc.at[ids].add(ct / B),
                      donate_argnums=0)
    grads = {k: jnp.zeros_like(v) for k, v in p.items()}
    ph = _head_params(p, a)
    n = a.get("name", "q")
    cost = 0.0

    def accumulate(part):                 # a part's gradients, then let go
        rest = {k: grads.pop(k) for k in part}
        grads.update(add(rest, part))

    for r in range(B):
        ids = b["ids"][r]
        inputs, x = [], p[emb_name][ids]
        for l in range(L):
            inputs.append(x)
            x = block(_block_params(p, a, l), x, attention=is_attention(a, l))
        c, (g_head, ct) = head_vg(ph, x, b["next_ids"][r], b["ids_mask"][r])
        cost = cost + c / B
        accumulate({f"_{n}_final_norm.w0": g_head["final_norm"],
                    f"_{n}_head.w0": g_head["head"]})
        del g_head, x
        for l in reversed(range(L)):
            g, ct = block_vjp(_block_params(p, a, l), inputs.pop(), ct,
                              attention=is_attention(a, l))
            accumulate(_block_names(a, l, g))
            del g
        grads[emb_name] = scatter(grads[emb_name], ids, ct)
        del ct
    return (cost, {}), grads
