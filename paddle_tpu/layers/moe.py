"""Sparse mixture of experts, as one chip's share of an expert-parallel
layer (docs/qwen3_next.md), and the plain gated MLP an expert is.

    p = softmax(x Wr) over ALL num_experts (float32); the top k of p,
    renormalised to sum 1; routed = sum over the chosen experts e THAT THIS
    LAYER HOLDS, [first_expert, first_expert + experts_held), of
    p_e (silu(x Wg_e) * (x Wu_e)) Wd_e;
    shared = sigmoid(x w_sg) * (silu(x Wg_s) * (x Wu_s)) Wd_s;
    out = routed + shared; a layer built without a shared size has no
    shared expert: no ``shared_*`` leaf, no shared term

With ``score="sigmoid"`` p = sigmoid(x Wr); with ``selection_bias`` the top k
are chosen by p + b while the weights stay the chosen p, renormalised and
scaled by ``route_scale``; ``shared_gate=False`` leaves the shared expert's
gate out (docs/kimi_vl.md). b is state, not a parameter: no gradient reaches
it (it only decides an index), it starts at 0, and after every step
b_e += bias_rate * sign(mean(c) - c_e) from the step's own counts c_e of
(real token, chosen expert) pairs over all experts. It travels as batch
norm's moving statistics do: an ``is_static`` leaf whose new value goes under
``ctx.extras["batch_stats"]``.

What the absent experts would add is left out: the other shares of the layer
hold them, and on one chip nothing stands in for the exchange that would
bring their tokens here and send these results back.

Dispatch is drop-free with one compiled shape. The (token, chosen and held
expert) pairs are laid out expert by expert (a counting sort: a pair's place
is its expert's offset plus its rank among that expert's pairs), each
expert's run padded to whole tiles of ``tile`` rows, in a buffer sized for
the worst case (every token choosing min(k, held) held experts). The grouped
products walk only the tiles in use, a loop whose trip count is data; its
backward pass is written out under a custom_vjp (a loop with a
data-dependent trip count has no automatic transpose, and no activation is
kept: the forward is made again).

What a trip does has two forms, chosen by what the code observes
(``_grouped_products``: the platform and ``kernels/moe_grouped.chunk_plan``,
one gate on VMEM bytes from the shapes; no attribute, flag or environment
variable):

``grouped_ffn`` below, the TILE LOOP (off the TPU and past the gate, and what
    the tests pin the kernels to): a trip is one tile. Gather the tile's
    tokens, three products with the tile's expert, weighted scatter-add back;
    backward the same again and six products more. Every trip slices the
    expert's three matrices out of HBM and, backward, adds into three whole
    float32 expert matrices there, although the next tile almost always has
    the same expert; and XLA's scatter-add takes 0.30 us a row on a v5e.
``kernels/moe_grouped.grouped_ffn``, the CHUNKED KERNELS (on the TPU): a trip
    is a chunk of 16 tiles at hidden 2048. Their rows are gathered in XLA;
    one Mosaic launch walks them with each tile's expert as scalar prefetch,
    so an expert's weights are fetched when the expert CHANGES, its float32
    weight gradient stays in VMEM over the run of its tiles and is written
    once a (chunk, expert), and a tile's results are added into the tokens'
    float32 sums by the launch's own DMAs (a token's row is one 8 KB piece
    of the sum's layout). Every rounding point is the tile loop's. Bytes and
    times by cell: docs/qwen3_next.md, docs/sdar.md, docs/kimi_vl.md.

``dispatch_plan`` counts what either form does: ``paddle_moe_tiles_total``
(tiles in use, padding included) and ``paddle_moe_expert_fetches_total``
((trip, expert) runs: how often an expert's weights are fetched and its
gradient written); tiles over fetches is 1.0 for the tile loop.

Why not jax.lax.ragged_dot over the sorted rows: on the TPU its cost follows
the rows of the buffer, which are static, and not the group sizes, which are
data; a drop-free buffer is the worst case, 32 times the expected load at
16 of 512 experts held, and a choice among a few buffer sizes makes XLA plan
the largest one's temporaries (docs/qwen3_next.md has the table PR 27
measured). It also leaves the rows past the last group undefined, in its lhs
gradient too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.attr import ParamAttr
from paddle_tpu.core.layer import (ParamSpec, register_layer,
                                   register_step_stats)
from paddle_tpu.kernels import moe_grouped
from paddle_tpu.kernels._pallas_util import (batch_shards, call_kernel,
                                             log_once, take_pallas)
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.utils.error import enforce

STATS = ("held", "elsewhere", "load_max_over_mean", "dropped", "tiles",
         "fetches")

_M_TOKENS = obs_metrics.counter(
    "paddle_moe_tokens_total",
    "(token, chosen expert) pairs a moe_ffn layer routed, by where the "
    "expert lives: held (computed by this layer) or elsewhere (an expert "
    "of another share; its contribution is left out here)",
    labels=("layer", "result"))
_M_LOAD = obs_metrics.gauge(
    "paddle_moe_expert_load_max_over_mean",
    "Pairs of the busiest held expert over the mean of the held experts, "
    "in the last drained step", labels=("layer",))
_M_DROPPED = obs_metrics.counter(
    "paddle_moe_dropped_total",
    "Held (token, expert) pairs the dispatch buffer had no row for; the "
    "buffer is sized for the worst case, so this stays 0")
_M_TILES = obs_metrics.counter(
    "paddle_moe_tiles_total",
    "Tiles of the dispatch buffer a moe_ffn layer's grouped products walked "
    "(each expert's run padded to whole tiles); times the tile's rows over "
    "the held pairs is what the padding costs", labels=("layer",))
_M_FETCHES = obs_metrics.counter(
    "paddle_moe_expert_fetches_total",
    "Runs of one expert's tiles inside one trip of the grouped products' "
    "loop: how often an expert's weights were fetched and its weight "
    "gradient written. Tiles over fetches is 1 for the tile loop and the "
    "mean run a chunked launch keeps an expert on the chip for",
    labels=("layer",))
_M_BIAS = obs_metrics.gauge(
    "paddle_moe_selection_bias_max_abs",
    "Largest magnitude of a moe_ffn layer's selection bias after the last "
    "drained step's update (layers with selection_bias only)",
    labels=("layer",))


@register_step_stats("moe_ffn")
def _publish_stats(lname, vec):
    """One drained step's STATS vector of one layer into the counters; a
    layer with a selection bias appends its largest magnitude."""
    held, elsewhere, load, dropped, tiles, fetches = (
        float(v) for v in vec[:len(STATS)])
    _M_TOKENS.labels(layer=lname, result="held").inc(held)
    _M_TOKENS.labels(layer=lname, result="elsewhere").inc(elsewhere)
    _M_LOAD.labels(layer=lname).set(load)
    _M_DROPPED.inc(dropped)
    _M_TILES.labels(layer=lname).inc(tiles)
    _M_FETCHES.labels(layer=lname).inc(fetches)
    if len(vec) > len(STATS):
        _M_BIAS.labels(layer=lname).set(float(vec[len(STATS)]))


def _moe_params(cfg, in_infos):
    d = in_infos[0].size
    E, held = cfg.attr("num_experts"), cfg.attr("experts_held")
    I, Is = cfg.attr("expert_size"), cfg.attr("shared_size")
    a = cfg.param_attr(0)
    specs = {
        "router": ParamSpec((d, E), a, fan_in=d),
        "wg": ParamSpec((held, d, I), a, fan_in=d),
        "wu": ParamSpec((held, d, I), a, fan_in=d),
        "wd": ParamSpec((held, I, d), a, fan_in=I),
    }
    if Is is not None:
        if cfg.attr("shared_gate", True):
            specs["shared_gate"] = ParamSpec((d, 1), a, fan_in=d)
        specs.update(_mlp_specs(d, Is, a, "shared_"))
    if cfg.attr("selection_bias", False):
        # state that the layer's own rule moves; excluded from gradient
        # updates by the trainer, as batch norm's moving statistics are
        specs["bias"] = ParamSpec(
            (E,), ParamAttr(initial_strategy="zero", is_static=True), fan_in=E)
    return specs


def _mlp_specs(d, width, a, prefix=""):
    return {prefix + "wg": ParamSpec((d, width), a, fan_in=d),
            prefix + "wu": ParamSpec((d, width), a, fan_in=d),
            prefix + "wd": ParamSpec((width, d), a, fan_in=width)}


def _gated_hidden(x, wg, wu):
    return jax.nn.silu(jnp.matmul(x, wg)) * jnp.matmul(x, wu)


def _gated_mlp(x, wg, wu, wd):
    """(silu(x Wg) * (x Wu)) Wd."""
    return jnp.matmul(_gated_hidden(x, wg, wu), wd)


def _acc(dtype):
    return jnp.promote_types(dtype, jnp.float32)


def _tile_rows(rows, t, tile):
    return jax.lax.dynamic_slice_in_dim(rows, t * tile, tile)


def _tile_forward(x, wg, wu, wd, tok, e):
    """One tile's tokens through expert e: (xt, a, b, h, y), y in float32."""
    acc = _acc(x.dtype)
    xt = jnp.take(x, tok, axis=0, mode="clip")
    a = jnp.dot(xt, wg[e], preferred_element_type=acc)
    b = jnp.dot(xt, wu[e], preferred_element_type=acc)
    h = (jax.nn.silu(a) * b).astype(x.dtype)
    return xt, a, b, h, jnp.dot(h, wd[e], preferred_element_type=acc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def grouped_ffn(x, wg, wu, wd, row_w, row_tok, tile_expert, n_tiles, tile):
    """sum over rows r of row_w[r] * expert(x[row_tok[r]]) scattered to
    token row_tok[r]: x [N, d]; wg, wu [G, d, I]; wd [G, I, d]; row_w, row_tok
    [R] (padding rows: token N, weight 0); tile_expert [R / tile]; only the
    first n_tiles tiles hold rows. Returns [N, d] in x's dtype."""
    return _grouped_fwd(x, wg, wu, wd, row_w, row_tok, tile_expert, n_tiles,
                        tile)[0]


def _grouped_fwd(x, wg, wu, wd, row_w, row_tok, tile_expert, n_tiles, tile):
    acc = _acc(x.dtype)

    def body(t, y):
        tok, w = _tile_rows(row_tok, t, tile), _tile_rows(row_w, t, tile)
        yt = _tile_forward(x, wg, wu, wd, tok, tile_expert[t])[-1]
        return y.at[tok].add(w[:, None].astype(acc) * yt, mode="drop")

    with jax.named_scope("moe_grouped_ffn_fwd"):
        y = jax.lax.fori_loop(0, n_tiles, body, jnp.zeros(x.shape, acc))
    return y.astype(x.dtype), (x, wg, wu, wd, row_w, row_tok, tile_expert,
                               n_tiles)


def _grouped_bwd(tile, res, dy):
    x, wg, wu, wd, row_w, row_tok, tile_expert, n_tiles = res
    acc = _acc(x.dtype)

    def body(t, carry):
        dx, dwg, dwu, dwd, drow = carry
        e = tile_expert[t]
        tok, w = _tile_rows(row_tok, t, tile), _tile_rows(row_w, t, tile)
        xt, a, b, h, yt = _tile_forward(x, wg, wu, wd, tok, e)
        dyt = jnp.take(dy, tok, axis=0, mode="fill", fill_value=0).astype(acc)
        drow = jax.lax.dynamic_update_slice_in_dim(
            drow, jnp.sum(dyt * yt, -1), t * tile, 0)
        dyw = (dyt * w[:, None].astype(acc)).astype(x.dtype)
        dh = jnp.dot(dyw, wd[e].T, preferred_element_type=acc)
        sig = jax.nn.sigmoid(a)
        da = (dh * b * sig * (1 + a * (1 - sig))).astype(x.dtype)
        db = (dh * jax.nn.silu(a)).astype(x.dtype)
        dxt = jnp.dot(da, wg[e].T, preferred_element_type=acc) \
            + jnp.dot(db, wu[e].T, preferred_element_type=acc)
        dx = dx.at[tok].add(dxt, mode="drop")
        dwg = dwg.at[e].add(jnp.dot(xt.T, da, preferred_element_type=acc))
        dwu = dwu.at[e].add(jnp.dot(xt.T, db, preferred_element_type=acc))
        dwd = dwd.at[e].add(jnp.dot(h.T, dyw, preferred_element_type=acc))
        return dx, dwg, dwu, dwd, drow

    init = (jnp.zeros(x.shape, acc), jnp.zeros(wg.shape, acc),
            jnp.zeros(wu.shape, acc), jnp.zeros(wd.shape, acc),
            jnp.zeros(row_w.shape, acc))
    with jax.named_scope("moe_grouped_ffn_bwd"):
        dx, dwg, dwu, dwd, drow = jax.lax.fori_loop(0, n_tiles, body, init)
    return (dx.astype(x.dtype), dwg.astype(wg.dtype), dwu.astype(wu.dtype),
            dwd.astype(wd.dtype), drow.astype(row_w.dtype), None, None, None)


grouped_ffn.defvjp(_grouped_fwd, _grouped_bwd)


def _grouped_products(who, d, I, tile, dtype):
    """(the layer's grouped products, the tiles a trip of theirs takes):
    the chunked Mosaic kernels on the TPU where their gate passes, the tile
    loop above elsewhere; the log says once a layer which, and what the
    backward launch holds in VMEM."""
    plan, why = moe_grouped.chunk_plan(d, I, tile, dtype)
    if plan is not None and batch_shards() > 1:
        plan, why = None, "the routed rows are not split by the batch"
    if not take_pallas(who, "moe_grouped_fwd/bwd", plan is not None, why,
                       otherwise="the tile loop"):
        return lambda *args: grouped_ffn(*args, tile), 1
    chunk, buffers = plan
    need, grads = moe_grouped.bwd_vmem_bytes(d, I, tile, dtype, buffers)
    log_once(who, f"moe_grouped_fwd/bwd take chunks of {chunk} tiles of "
             f"{tile} rows; an expert's weights in {buffers} buffer"
             f"{'s' if buffers > 1 else ''}, its gradients {grads / 1e6:.1f} "
             f"MB of {need / 1e6:.1f} MB in VMEM")

    def grouped(*args):
        return call_kernel(
            lambda *a: moe_grouped.grouped_ffn(*a, tile, chunk, buffers),
            args, (0,))

    return grouped, chunk


def dispatch_plan(idx, top, valid, first, held, tile, chunk=1):
    """Where each (token, chosen expert) pair goes. idx, top [N, k]: the
    chosen experts and their renormalised weights; valid [N] bool (real
    tokens); chunk: the tiles one trip of the grouped products takes.
    Returns (row_w, row_tok, tile_expert, n_tiles, stats): the buffer's
    rows, each tile's expert, the tiles in use, and the STATS vector
    (float32)."""
    N, k = idx.shape
    R = -(-N * min(k, held) // tile) * tile + held * tile
    local = idx - first
    here = (local >= 0) & (local < held) & valid[:, None]
    local = jnp.where(here, local, held).reshape(-1)              # [N * k]
    onehot = (local[:, None] == jnp.arange(held)[None, :]).astype(jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                               jnp.minimum(local, held - 1)[:, None], 1)[:, 0]
    sizes = jnp.sum(onehot, axis=0)                               # [held]
    tiles = -(-sizes // tile)
    tile_end = jnp.cumsum(tiles)
    offset = (tile_end - tiles) * tile
    place = jnp.where(local < held,
                      offset[jnp.minimum(local, held - 1)] + rank, R)
    token = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
    row_tok = jnp.full((R,), N, jnp.int32).at[place].set(token, mode="drop")
    row_w = jnp.zeros((R,), top.dtype).at[place].set(top.reshape(-1),
                                                     mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(R // tile), side="right"),
        held - 1).astype(jnp.int32)
    n_held = jnp.sum(sizes)
    n_real = jnp.sum(valid.astype(jnp.int32)) * k
    f32 = jnp.float32
    mean = jnp.maximum(n_held.astype(f32) / held, 1e-9)
    stats = jnp.stack([n_held.astype(f32), (n_real - n_held).astype(f32),
                       jnp.max(sizes).astype(f32) / mean,
                       jnp.sum((place >= R) & (local < held)).astype(f32)])
    n_tiles = tile_end[-1]
    # a trip fetches an expert where its first tile starts: at the trip's
    # first tile and wherever the expert differs from the tile before
    t = jnp.arange(R // tile)
    fetch = (t < n_tiles) & ((t % chunk == 0)
                             | (tile_expert != jnp.roll(tile_expert, 1)))
    stats = jnp.concatenate([stats, jnp.stack(
        [n_tiles.astype(f32), jnp.sum(fetch).astype(f32)])])
    return row_w, row_tok, tile_expert, n_tiles, stats


def route(x, p, cfg, valid):
    """(idx, top [N, k], new bias or None): the chosen experts of every
    token of x [N, d], their weights, and what the selection bias becomes
    after this step."""
    k, acc, score = cfg.attr("top_k"), _acc(x.dtype), cfg.attr("score", "softmax")
    enforce(score in ("softmax", "sigmoid"),
            f"moe_ffn {cfg.name}: score {score!r} is neither softmax nor sigmoid")
    logits = jnp.matmul(x, p["router"]).astype(acc)
    probs = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    if "bias" in p:
        # the bias decides WHICH experts; the weights are the scores' own
        idx = jax.lax.top_k(probs + p["bias"].astype(acc), k)[1]
        top = jnp.take_along_axis(probs, idx, axis=-1)
    else:
        top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, -1, keepdims=True)
    scale = cfg.attr("route_scale", 1.0)
    if scale != 1.0:
        top = top * scale
    if "bias" not in p:
        return idx, top, None
    with jax.named_scope("aux_update"):
        hit = (idx[..., None] == jnp.arange(p["bias"].shape[0])) \
            & valid[:, None, None]
        counts = jnp.sum(hit, axis=(0, 1), dtype=jnp.float32)
        bias = p["bias"] + cfg.attr("bias_rate", 1e-3) * jnp.sign(
            jnp.mean(counts) - counts).astype(p["bias"].dtype)
    return idx, top, bias


@register_layer("moe_ffn", params=_moe_params)
def _moe_ffn_forward(cfg, params, ins, ctx):
    x_in = ins[0].value
    d = x_in.shape[-1]
    held = cfg.attr("experts_held")
    first, tile = cfg.attr("first_expert", 0), cfg.attr("tile", 256)
    valid = jnp.ones(x_in.shape[:-1], bool) if ins[0].mask is None \
        else ins[0].mask > 0
    grouped, chunk = _grouped_products(cfg.name, d, cfg.attr("expert_size"),
                                       tile, x_in.dtype)

    def moe(x, valid, p):
        x = x.reshape(-1, d)
        acc = _acc(x.dtype)
        idx, top, bias = route(x, p, cfg, valid.reshape(-1))
        row_w, row_tok, tile_expert, n_tiles, stats = dispatch_plan(
            idx, top, valid.reshape(-1), first, held, tile, chunk)
        out = grouped(x, p["wg"], p["wu"], p["wd"], row_w, row_tok,
                      tile_expert, n_tiles)
        if bias is not None:
            stats = jnp.concatenate([stats, jnp.max(jnp.abs(bias))[None]])
        if "shared_wg" in p:
            # two spellings of one MLP: the gated one keeps the order of
            # operations the Qwen3-Next cell's step was lowered with
            if "shared_gate" in p:
                gate = jax.nn.sigmoid(
                    jnp.matmul(x, p["shared_gate"]).astype(acc))
                h = _gated_hidden(x, p["shared_wg"], p["shared_wu"])
                shared = gate.astype(x.dtype) * jnp.matmul(h, p["shared_wd"])
            else:
                shared = _gated_mlp(x, p["shared_wg"], p["shared_wu"],
                                    p["shared_wd"])
            out = out + shared
        return out.reshape(x_in.shape), stats, bias

    out, stats, bias = jax.checkpoint(moe)(x_in, valid, params)
    ctx.extras.setdefault("step_stats", {}).setdefault(
        "moe_ffn", {})[cfg.name] = jax.lax.stop_gradient(stats)
    if bias is not None and ctx.training:
        ctx.extras.setdefault("batch_stats", {})[cfg.name] = {
            "bias": jax.lax.stop_gradient(bias)}
    return ins[0].with_value(out)


# --- the plain gated MLP ------------------------------------------------------

def _gated_mlp_params(cfg, in_infos):
    return _mlp_specs(in_infos[0].size, cfg.attr("width"), cfg.param_attr(0))


@register_layer("gated_mlp", params=_gated_mlp_params)
def _gated_mlp_forward(cfg, params, ins, ctx):
    """(silu(x Wg) * (x Wu)) Wd of width ``width``, no bias: a decoder's dense
    feed-forward layer. Its two wide activations are computed again in the
    backward pass, as an expert's are."""
    out = jax.checkpoint(_gated_mlp)(
        ins[0].value, params["wg"], params["wu"], params["wd"])
    return ins[0].with_value(out)
