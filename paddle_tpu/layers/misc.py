"""Misc layers: hierarchical sigmoid, NCE, selective fc, printers.

Analogs of paddle/gserver/layers/{HierarchicalSigmoidLayer,NCELayer,
SelectiveFullyConnectedLayer,PrintLayer}.cpp.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.arg import Arg, ArgInfo
from paddle_tpu.core.layer import ParamSpec, register_layer
from paddle_tpu.utils.error import enforce


def _cost_infer(cfg, in_infos):
    return ArgInfo(size=1)


def _hsig_params(cfg, in_infos):
    num_classes = cfg.attr("num_classes")
    code_len = num_classes - 1
    specs = {}
    # one weight per non-label input, like the reference's per-input weights
    for i, info in enumerate(in_infos[:-1]):
        specs[f"w{i}"] = ParamSpec((code_len, info.size), cfg.param_attr(i),
                                   fan_in=info.size)
    battr = cfg.bias_param_attr()
    if battr is not None:
        specs["wbias"] = ParamSpec((code_len,), battr, fan_in=code_len, is_bias=True)
    return specs


@register_layer("hsigmoid", infer=_cost_infer, params=_hsig_params)
def _hsigmoid(cfg, params, ins, ctx):
    """HierarchicalSigmoidLayer: complete-binary-tree Huffman-style code
    over num_classes leaves (code of class c = bits of c+num_classes walking
    up, reference MultiBinaryLabelCode). Cost formulation, used as an
    output-cost layer."""
    num_classes = cfg.attr("num_classes")
    code_len = int(jnp.ceil(jnp.log2(num_classes))) if False else (num_classes - 1).bit_length()
    label = ins[-1].value.astype(jnp.int32)
    if label.ndim > 1:
        label = label[..., 0]
    B = label.shape[0]
    # per-sample code: node indices + bits walking the implicit tree
    codes = label + num_classes                     # [B]
    exps = jnp.arange(code_len)
    walked = codes[:, None] >> exps[None, :]        # [B, L] node path (reversed)
    node_idx = (walked >> 1) - 1                    # parent node ids
    bits = (walked & 1).astype(jnp.float32)
    valid = (walked > 1).astype(jnp.float32)
    node_idx = jnp.clip(node_idx, 0, num_classes - 2)
    # sum_i x_i @ W_i[node] (+ bias[node]) per path node
    pre = jnp.zeros((B, code_len))
    for i, a in enumerate(ins[:-1]):
        W = params[f"w{i}"]                          # [code_len_param, D]
        Wsel = W[node_idx]                           # [B, L, D]
        pre = pre + jnp.einsum("bld,bd->bl", Wsel, a.value)
    if "wbias" in params:
        pre = pre + params["wbias"][node_idx]
    # cost = -sum log sigmoid((1-2bit)*pre)  (binary code cross-entropy)
    sign = 1.0 - 2.0 * bits
    cost = -(jax.nn.log_sigmoid(sign * pre) * valid).sum(-1)
    return Arg(cost[:, None])


def _nce_params(cfg, in_infos):
    num_classes = cfg.attr("num_classes")
    specs = {}
    for i, info in enumerate(in_infos[:-1]):
        specs[f"w{i}"] = ParamSpec((num_classes, info.size), cfg.param_attr(i),
                                   fan_in=info.size)
    battr = cfg.bias_param_attr()
    if battr is not None:
        specs["wbias"] = ParamSpec((num_classes,), battr, fan_in=num_classes,
                                   is_bias=True)
    return specs


@register_layer("nce", infer=_cost_infer, params=_nce_params)
def _nce(cfg, params, ins, ctx):
    """NCELayer: noise-contrastive estimation cost with uniform (or given)
    noise distribution, num_neg_samples per example. Samples are drawn
    inside the jitted program (ctx.rng), unlike the reference's CPU-side
    sampler — keeps the whole step on-device."""
    num_classes = cfg.attr("num_classes")
    k = cfg.attr("num_neg_samples", 10)
    label = ins[-1].value.astype(jnp.int32)
    if label.ndim > 1:
        label = label[..., 0]
    B = label.shape[0]
    key = ctx.rng(cfg.name)
    neg = jax.random.randint(key, (B, k), 0, num_classes)
    samples = jnp.concatenate([label[:, None], neg], axis=1)   # [B, 1+k]
    logits = jnp.zeros((B, 1 + k))
    for i, a in enumerate(ins[:-1]):
        W = params[f"w{i}"]                                    # [C, D]
        Wsel = W[samples]                                      # [B,1+k,D]
        logits = logits + jnp.einsum("bkd,bd->bk", Wsel, a.value)
    if "wbias" in params:
        logits = logits + params["wbias"][samples]
    # P_noise uniform = 1/num_classes; logit correction log(k * Pn)
    log_kpn = jnp.log(k / num_classes)
    delta = logits - log_kpn
    labels01 = jnp.concatenate([jnp.ones((B, 1)), jnp.zeros((B, k))], axis=1)
    cost = -(labels01 * jax.nn.log_sigmoid(delta)
             + (1 - labels01) * jax.nn.log_sigmoid(-delta)).sum(-1)
    return Arg(cost[:, None])


def _selfc_infer(cfg, in_infos):
    # compact_output: the layer's output lives in CANDIDATE space — one
    # score per selection slot ([..., K]), never scattered to [..., C]
    size = in_infos[-1].size if cfg.attr("compact_output") else cfg.size
    return ArgInfo(size=size,
                   is_seq=any(i.is_seq for i in in_infos[:-1]),
                   is_nested=any(i.is_nested for i in in_infos[:-1]))


def _selfc_params(cfg, in_infos):
    specs = {}
    # weight_transposed stores (in, out) — fc's layout — so a selective
    # vocab projection can SHARE an fc layer's parameters by name (the
    # beam-decode wiring in networks.gru_encoder_decoder names its
    # selective projection like the training fc; checkpoints port
    # between modes with no transpose step)
    transposed = bool(cfg.attr("weight_transposed", False))
    for i, info in enumerate(in_infos[:-1]):
        shape = (info.size, cfg.size) if transposed else (cfg.size, info.size)
        specs[f"w{i}"] = ParamSpec(shape, cfg.param_attr(i),
                                   fan_in=info.size)
    battr = cfg.bias_param_attr()
    if battr is not None:
        specs["wbias"] = ParamSpec((cfg.size,), battr, fan_in=cfg.size, is_bias=True)
    return specs


# Two crossover regimes, both measured end-to-end (train-step harness):
# - PLAIN autodiff (no sparse_update / plain jax.grad): the gather
#   path's dW is a dense [C, D] zero-init + scatter-add and loses to the
#   dense mask through C=1M (36.3 vs 10.9 ms at 1M; r5, not
#   re-measured) — conservative crossover stays 2M.
# - SPARSE dW (weight has sparse_update=True and the step runs through
#   make_train_step's tangent-slot protocol): dW is a (rows, values)
#   SparseRowGrad applied per-row by the optimizer — no [C, D] buffer
#   anywhere — and the end-to-end train-step crossover drops well below
#   1M (r6, a CPU round: gather+sparse-dW beat dense-mask at every
#   measured C from 65k up; not measured on the chip, ROADMAP S2, so
#   256k is kept as the conservative committed default).
# The layer picks the regime at trace time (the sparse protocol
# announces itself via ctx.sparse_collect/sparse_tangents); a per-layer
# ``gather_min_c`` cfg overrides both — the selective-decode wiring
# (networks.gru_encoder_decoder) sets it explicitly because generation
# is forward-only (no dW at all) and gather wins as soon as K << C.
_SELFC_GATHER_MIN_C = 1 << 21
_SELFC_GATHER_MIN_C_SPARSE = 1 << 18


@register_layer("selective_fc", infer=_selfc_infer, params=_selfc_params)
def _selective_fc(cfg, params, ins, ctx):
    """SelectiveFullyConnectedLayer (SelectiveFullyConnectedLayer.cpp):
    fc over the full output set, but only rows selected by the last input
    (id list, -1 padded) are kept — non-selected outputs are masked to
    -inf (softmax) / 0.

    Two paths, crossover measured on a v5e (r4, not re-measured): the
    dense matmul + mask wins through ~100k outputs (the MXU eats the
    matmul; masking is one fused elementwise), while at NCE/hsigmoid-
    scale vocabs (>=256k) the reference's reason for existing kicks in —
    gather the K selected weight rows, compute [B,K] products, scatter
    into the dense output (weight grads become scatter-adds, so backward
    is sparse too).

    With ``sparse_update=True`` on the weight attr and a train step built
    by make_train_step, the gather path's dW never exists densely: the
    step hands this layer a zero tangent slot per weight
    (ctx.sparse_tangents[pname], shape [N, K, D]); the layer adds it to
    the gathered rows and stop-gradients the table, so the step's
    jax.grad w.r.t. the slot IS the per-row dW. Touched row ids (dead
    slots -1) are reported through ctx.extras['sparse_rows'][pname] and
    the optimizer applies (rows, values) directly (sparse_grad.py).

    cfg knobs: ``select_is_id_list=True`` forces id-list interpretation
    even when K == C (a full-coverage candidate list would otherwise
    parse as a dense 0/1 selection matrix); ``gather_min_c`` overrides
    the measured crossover constants below; ``compact_output=True``
    keeps the result in CANDIDATE space — the layer returns the [..., K]
    per-slot scores (dead slots, i.e. -1 pads and non-first duplicates,
    filled with ``fill``) instead of scattering into [..., C], and
    reports the per-slot vocab ids through
    ``ctx.extras['selfc_compact'][layer_name]`` (dead slots -1) so a
    downstream consumer (the compact-K beam-search path,
    layers/recurrent_group.py) can map winners back to vocab ids without
    re-deriving the selection. Compact mode always takes the gather path
    (a scatter would defeat its purpose) and implies id-list
    interpretation."""
    sel = ins[-1].value.astype(jnp.int32)     # [..., K] ids or dense [..., C]
    C = cfg.size
    pass_gen = cfg.attr("selection_pass_generation", False)
    fill = 0.0 if pass_gen else -1e30
    compact = bool(cfg.attr("compact_output", False))
    id_list = compact or bool(cfg.attr("select_is_id_list", False)) \
        or sel.shape[-1] != C
    mask = next((a.mask for a in ins[:-1] if a.mask is not None), None)
    seg = next((a.seg_ids for a in ins[:-1] if a.seg_ids is not None), None)
    x_ndim = max(a.value.ndim for a in ins[:-1])
    if sel.ndim == x_ndim - 1:
        # per-batch selection applied to a sequence input: every timestep
        # keeps the same rows (the reference's per-sample selCols)
        T = next(a.value.shape[1] for a in ins[:-1] if a.value.ndim == x_ndim)
        sel = jnp.broadcast_to(sel[:, None, :], (sel.shape[0], T,
                                                 sel.shape[-1]))
    # sparse-dW protocol active? (make_train_step announces itself via
    # the collect/tangent dicts; the weight must opt in via sparse_update)
    sparse_proto = (ctx.sparse_collect is not None
                    or ctx.sparse_tangents is not None)
    sparse_w = [cfg.param_attr(i).sparse_update
                for i in range(len(ins) - 1)]
    min_c = cfg.attr("gather_min_c")
    if min_c is None:
        min_c = (_SELFC_GATHER_MIN_C_SPARSE
                 if sparse_proto and all(sparse_w) else _SELFC_GATHER_MIN_C)
    # gather path handles any leading dims ([B,K] batches and [B,T,K]
    # sequence selections — beam-search generation is the 3D consumer)
    # by flattening to rows
    if id_list and (compact or C >= min_c) \
            and all(a.value.ndim == sel.ndim for a in ins[:-1]):
        lead, K = sel.shape[:-1], sel.shape[-1]
        sel2 = sel.reshape(-1, K)
        N = sel2.shape[0]
        valid = sel2 >= 0
        # a duplicated id inside one row would double-count weight/bias
        # grads (each duplicate slot gathers the full output cotangent in
        # the scatter vjp); only the first occurrence scatters into a real
        # output, the rest ride to the scratch column. Sort-based first-
        # occurrence test: O(K log K) per row, not the O(K^2) pairwise
        # compare (NCE-scale selection lists make K big).
        # select_unique=True skips the per-call sort for callers that
        # GUARANTEE unique ids per row (the decode wiring: candidate
        # vocab lists are unique by construction, and the sort would
        # otherwise run every beam tick)
        if cfg.attr("select_unique", False):
            first = jnp.ones((N, K), bool)
        else:
            order = jnp.argsort(sel2, axis=-1, stable=True)
            ss = jnp.take_along_axis(sel2, order, axis=-1)
            dup_sorted = jnp.concatenate(
                [jnp.zeros((N, 1), bool), ss[:, 1:] == ss[:, :-1]], axis=-1)
            rows_k = jnp.broadcast_to(jnp.arange(N)[:, None], (N, K))
            first = ~jnp.zeros((N, K), bool).at[rows_k, order].set(dup_sorted)
        idx = jnp.clip(sel2, 0, C - 1)
        # row ids as the OPTIMIZER will consume them: dead slots (pads and
        # in-row duplicate tails, whose cotangents are zero — they feed
        # the dropped scratch column) are -1
        grad_rows = jnp.where(valid & first, sel2, -1)
        y = None
        transposed = bool(cfg.attr("weight_transposed", False))
        for i, a in enumerate(ins[:-1]):
            x = a.value.reshape(N, a.value.shape[-1])
            if transposed:
                # fc-layout (in, out) table: transpose THEN row-gather —
                # the transpose is loop-invariant, so inside a decode
                # scan XLA hoists it out and every tick does contiguous
                # row gathers (a per-tick column gather strides the full
                # vocab row pitch — measured 2.3x slower end-to-end).
                # Decode-portability mode is forward-only: sparse-row dW
                # indexes axis 0, so the two knobs don't compose.
                enforce(not sparse_w[i],
                        "selective_fc: weight_transposed does not compose "
                        "with sparse_update (row grads index axis 0)")
                wk = jnp.swapaxes(params[f"w{i}"], 0, 1)[idx]  # [N, K, D]
                t = jnp.einsum("nd,nkd->nk", x, wk)
                y = t if y is None else y + t
                continue
            W = params[f"w{i}"]
            pname = ctx.layer_param_names.get(f"w{i}")
            if sparse_w[i] and pname is not None \
                    and ctx.sparse_collect is not None:
                # discovery trace: announce the tangent-slot shape
                prev = ctx.sparse_collect.get(pname)
                slot = ((N, K, W.shape[-1]), W.dtype)
                enforce(prev is None or prev == slot,
                        f"sparse param {pname} reached by two selective_fc "
                        "gathers with different slot shapes — sparse-row "
                        "grads need one consumer per table")
                ctx.sparse_collect[pname] = slot
            tang = (ctx.sparse_tangents.get(pname)
                    if sparse_w[i] and pname is not None
                    and ctx.sparse_tangents is not None else None)
            if tang is not None:
                # the table itself is stop-gradiented: the step computes
                # dW as d/d tang (shape [N, K, D]) and pairs it with
                # grad_rows — the dense [C, D] dW never exists
                wk = jax.lax.stop_gradient(W)[idx] + tang
                srows = ctx.extras.setdefault("sparse_rows", {})
                enforce(pname not in srows,
                        f"sparse param {pname} gathered twice in one "
                        "forward — sparse-row grads need one consumer")
                srows[pname] = grad_rows
            else:
                wk = W[idx]                           # [N, K, D] row gather
            t = jnp.einsum("nd,nkd->nk", x, wk)
            y = t if y is None else y + t
        if "wbias" in params:
            y = y + params["wbias"][idx]
        if compact:
            # candidate-space result: dead slots (pads, non-first
            # duplicates) are filled so a softmax gives them zero mass —
            # identical values, slot for slot, to what the scatter below
            # would place at their vocab columns
            ctx.extras.setdefault("selfc_compact", {})[cfg.name] = \
                grad_rows.reshape(*lead, K)
            yk = jnp.where(valid & first, y, fill)
            return Arg(yk.reshape(*lead, K), mask, seg)
        # padded (-1) and duplicate slots scatter into a scratch column C,
        # never into a real output (idx clip would alias them onto id 0);
        # the dropped column also zeroes their gradients
        idx_sc = jnp.where(valid & first, idx, C)
        out = jnp.full((N, C + 1), fill, y.dtype)
        rows = jnp.broadcast_to(jnp.arange(N)[:, None], (N, K))
        out = out.at[rows, idx_sc].set(y)[:, :C]
        return Arg(out.reshape(*lead, C), mask, seg)
    enforce(not compact,
            f"selective_fc {cfg.name!r}: compact_output requires the "
            "gather path (selection rank must match the input rank)")
    out = None
    for i, a in enumerate(ins[:-1]):
        w = params[f"w{i}"]
        if not cfg.attr("weight_transposed", False):
            w = w.T
        t = jnp.matmul(a.value, w)
        out = t if out is None else out + t
    if "wbias" in params:
        out = out + params["wbias"]
    if not id_list:
        keep = sel > 0
    else:
        oh = jax.nn.one_hot(jnp.clip(sel, 0, C - 1), C, dtype=bool)
        keep = (oh & (sel >= 0)[..., None]).any(axis=-2)
    return Arg(jnp.where(keep, out, fill), mask, seg)


@register_layer("print")
def _print_layer(cfg, params, ins, ctx):
    """PrintLayer: debug-print layer values. Uses jax.debug.print so it
    works under jit (host callback), then passes input through."""
    fmt = cfg.attr("format", "{}")
    jax.debug.print(cfg.name + ": " + fmt, ins[0].value)
    return ins[0]


# --- switch_order / concat2 (v1 parity; SwitchOrderLayer.cpp,
# ConcatenateLayer2 in SequenceConcatLayer.cpp) ----------------------------

def _switch_order_infer(cfg, in_infos):
    info = in_infos[0]
    if info.shape is not None and len(info.shape) == 3:
        c, h, w = info.shape
        return info.replace(shape=(h, w, c))
    return info


@register_layer("switch_order", infer=_switch_order_infer)
def _switch_order(cfg, params, ins, ctx):
    """SwitchOrderLayer: NCHW -> NHWC dimension permutation (the reference
    uses it to feed channel-last consumers). reshape_axis splits the
    output into [batch, prod(dims[:axis]), prod(dims[axis:])]."""
    a = ins[0]
    v = a.value
    if v.ndim == 2:
        shape = cfg.inputs[0].out_info().shape
        if shape is not None and len(shape) == 3:
            v = jnp.transpose(v.reshape(v.shape[0], *shape),
                              (0, 2, 3, 1))  # flat CHW -> NHWC
    # carried 4D images are already NHWC — exactly this layer's output
    reshape_axis = cfg.attr("reshape_axis")
    if reshape_axis:
        lead = 1
        for d in v.shape[1:1 + int(reshape_axis)]:
            lead *= d
        return Arg(v.reshape(v.shape[0], lead, -1), a.mask, a.seg_ids)
    if v.ndim == 4:
        # flatten HERE in HWC order: returning carried-4D would make the
        # downstream CHW-flatten boundary silently undo the permutation
        v = v.reshape(v.shape[0], -1)
    return Arg(v, a.mask, a.seg_ids)


def _concat2_infer(cfg, in_infos):
    size = sum(i.size for i in in_infos)
    return in_infos[0].replace(size=size, shape=None)


@register_layer("concat2", infer=_concat2_infer)
def _concat2(cfg, params, ins, ctx):
    """ConcatenateLayer2: per-input-slice concatenation; on this framework
    identical to flat feature concat (projections are composed upstream
    via mixed/full_matrix_projection instead)."""
    from paddle_tpu.layers.conv import image_flat

    mask = next((a.mask for a in ins if a.mask is not None), None)
    # flatten only carried images — 3-D sequence values pass through so
    # the [B, T] mask stays aligned
    vals = [image_flat(a.value) if a.value.ndim == 4 else a.value
            for a in ins]
    return Arg(jnp.concatenate(vals, axis=-1), mask)
