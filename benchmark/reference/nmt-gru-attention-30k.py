"""Plain reference of the GRU-attention NMT (Bahdanau et al., arXiv:1409.0473,
as the PaddlePaddle book's machine-translation chapter configures it).

Straightforward jax.numpy in float32; the caller sets
`jax.default_matmul_precision("highest")`. No kernel, no scan-hoisting trick,
no import of the program: the model is written out from its equations, and
the parameters carry the names the configuration's file gives them so that
the comparison can go leaf by leaf.

    encoder   x = E_src[src];  each direction: x3 = x W_t, then the GRU
              z = sigmoid(x3_z + h U_z + b_z),  r = sigmoid(x3_r + h U_r + b_r)
              c = tanh(x3_c + (r*h) U_c + b_c),  h' = z*h + (1-z)*c
              run over real positions only (padding keeps h), backward
              direction from the last real token to the first
    decoder   h_0 = tanh(bwd[0] W_boot);  at each target position
              e_j = v . tanh(enc_j W_p + h),  a = softmax over real j,
              ctx = sum_j a_j enc_j,  x3 = ctx W_c + E_trg[trg_t] W_e,
              h = GRU(x3, h)
    output    logits = h W_o + b_o;  cost = sum over real target positions of
              -log softmax(logits)[next token], averaged over the sentences

Every matrix product goes through `q` (benchmark/reference/lowprec.py), which
is the identity for the reference and a rounding for the control.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

def param_table(a):
    """name -> (shape, init): ("normal", std) or ("const", value)."""
    V_s, V_t = a["src_dict_dim"], a["trg_dict_dim"]
    D, E, H = a["word_vector_dim"], a["encoder_size"], a["decoder_size"]

    def w(*shape, fan_in):
        return (tuple(shape), ("normal", 1.0 / math.sqrt(fan_in)))

    def zeros(*shape):
        return (tuple(shape), ("const", 0.0))

    t = {"_src_emb": w(V_s, D, fan_in=D), "_trg_emb": w(V_t, D, fan_in=D)}
    for d in ("fwd", "bwd"):
        t[f"_m_enc_{d}_transform.w0"] = w(D, 3 * E, fan_in=D)
        t[f"_m_enc_{d}.w0"] = w(E, 2 * E, fan_in=E)
        t[f"_m_enc_{d}.w1"] = w(E, E, fan_in=E)
        t[f"_m_enc_{d}.wbias"] = zeros(3 * E)
    t["_m_enc_proj.w0"] = w(2 * E, H, fan_in=2 * E)
    t["_m_boot.w0"] = w(E, H, fan_in=E)
    t["_m_attn_weight.w0"] = w(H, 1, fan_in=H)
    t["_m_dec_in.w0"] = w(2 * E, 3 * H, fan_in=2 * E)
    t["_m_dec_in.w1"] = w(D, 3 * H, fan_in=D)
    t["_m_dec.w0"] = w(H, 2 * H, fan_in=H)
    t["_m_dec.w1"] = w(H, H, fan_in=H)
    t["_m_dec.wbias"] = zeros(3 * H)
    t["_m_out.w0"] = w(H, V_t, fan_in=H)
    t["_m_out.wbias"] = zeros(V_t)
    return t


def static_names(a):
    return ()


def pad(rows, a):
    """Rows of (src, trg, trg_next) id lists -> zero-padded id arrays and 0/1
    masks. The width is the longest row's own: padding adds nothing."""
    out = {}
    for col, name in enumerate(("src", "trg", "trg_next")):
        seqs = [r[col] for r in rows]
        T = max(len(s) for s in seqs)
        ids = np.zeros((len(seqs), T), np.int32)
        mask = np.zeros((len(seqs), T), np.float32)
        for i, s in enumerate(seqs):
            ids[i, :len(s)] = s
            mask[i, :len(s)] = 1.0
        out[name], out[name + "_mask"] = ids, mask
    assert (out["trg_mask"] == out["trg_next_mask"]).all()
    return out


def _gru_cell(x3, h, Wg, Wc, b, n, q):
    g = x3[:, :2 * n] + q(h) @ q(Wg) + b[:2 * n]
    z, r = jax.nn.sigmoid(g[:, :n]), jax.nn.sigmoid(g[:, n:])
    c = jnp.tanh(x3[:, 2 * n:] + q(r * h) @ q(Wc) + b[2 * n:])
    return z * h + (1 - z) * c


def _gru(x3, mask, Wg, Wc, b, reverse, q):
    """x3 [B,T,3n], mask [B,T] -> hidden states [B,T,n], zero on padding."""
    n = Wc.shape[0]

    def step(h, xm):
        x, m = xm
        h = m[:, None] * _gru_cell(x, h, Wg, Wc, b, n, q) + (1 - m[:, None]) * h
        return h, h

    h0 = jnp.zeros((x3.shape[0], n), jnp.float32)
    _, hs = jax.lax.scan(step, h0, (jnp.swapaxes(x3, 0, 1), mask.T),
                         reverse=reverse)
    return jnp.swapaxes(hs, 0, 1) * mask[..., None]


def loss(p, b, q, a):
    """(cost, {}) of one padded batch; cost as the configuration defines it."""
    src_mask, trg_mask = b["src_mask"], b["trg_mask"]
    x = p["_src_emb"][b["src"]]
    enc = []
    for d in ("fwd", "bwd"):
        x3 = q(x) @ q(p[f"_m_enc_{d}_transform.w0"])
        enc.append(_gru(x3, src_mask, p[f"_m_enc_{d}.w0"], p[f"_m_enc_{d}.w1"],
                        p[f"_m_enc_{d}.wbias"], d == "bwd", q))
    bwd0 = enc[1][:, 0]
    enc = jnp.concatenate(enc, axis=-1)                      # [B,Ts,2E]
    enc_proj = q(enc) @ q(p["_m_enc_proj.w0"])               # [B,Ts,H]
    h0 = jnp.tanh(q(bwd0) @ q(p["_m_boot.w0"]))
    emb_proj = q(p["_trg_emb"][b["trg"]]) @ q(p["_m_dec_in.w1"])   # [B,Tt,3H]
    H = h0.shape[-1]

    def step(h, xm):
        e3, m = xm
        comb = jnp.tanh(enc_proj + h[:, None, :])
        score = (q(comb) @ q(p["_m_attn_weight.w0"]))[..., 0]       # [B,Ts]
        score = jnp.where(src_mask > 0, score, -1e30)
        att = jax.nn.softmax(score, axis=-1) * src_mask
        ctx = jnp.sum(enc * att[..., None], axis=1)                 # [B,2E]
        x3 = q(ctx) @ q(p["_m_dec_in.w0"]) + e3
        h_new = _gru_cell(x3, h, p["_m_dec.w0"], p["_m_dec.w1"],
                          p["_m_dec.wbias"], H, q)
        h = m[:, None] * h_new + (1 - m[:, None]) * h
        return h, h_new

    _, hs = jax.lax.scan(step, h0, (jnp.swapaxes(emb_proj, 0, 1), trg_mask.T))
    hs = jnp.swapaxes(hs, 0, 1) * trg_mask[..., None]              # [B,Tt,H]
    logits = q(hs) @ q(p["_m_out.w0"]) + p["_m_out.wbias"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, b["trg_next"][..., None], axis=-1)[..., 0]
    cost = jnp.sum((lse - picked) * trg_mask, axis=-1)
    return jnp.mean(cost), {}
