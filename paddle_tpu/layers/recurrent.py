"""Recurrent layers: simple RNN, LSTM, GRU (full-sequence fused forms).

Analogs of paddle/gserver/layers/{RecurrentLayer,LstmLayer,GruLayer}.cpp and
the fused CUDA recurrences hl_gpu_lstm.cuh / hl_gpu_gru.cuh. The reference
re-packs ragged batches per timestep with SequenceToBatch
(SequenceToBatch.cpp); on TPU the batch is already padded+masked, so each
layer is one ``lax.scan`` over time with mask-gated state carry — XLA keeps
the per-step GEMMs on the MXU and the gate math fused.

Like the reference, the time-varying *input* projection is expected to be
pre-computed by the layer below (fc/mixed producing 4*size for LSTM,
3*size for GRU), so the scan body contains only the [size, k*size]
recurrent matmul — the same split the hand-fused CUDA kernels use.

Gate order: LSTM [i, f, c, o]; GRU [z(update), r(reset), c(candidate)].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.attr import ParamAttr
from paddle_tpu.core.arg import Arg, ArgInfo, segment_start_resets
from paddle_tpu.core.layer import ParamSpec, register_layer
from paddle_tpu import activation as act_mod
from paddle_tpu.utils.error import enforce


def _scan_time(fn, init, xs_time_major, reverse=False):
    # unroll amortises TPU loop-iteration overhead across steps; the body
    # is a small [B,H]x[H,kH] matmul so overhead would otherwise dominate
    return jax.lax.scan(fn, init, xs_time_major, reverse=reverse, unroll=8)


def _to_time_major(v):
    return jnp.swapaxes(v, 0, 1)


def _packed_resets(a, ctx, reverse):
    """Segment-start reset vector [B, T] for a packed input, else None.
    Packed rows hold several sequences back to back (docs/packing.md);
    the carry entering the first step of each segment (last step under
    ``reverse`` — that is where a reverse scan's carry enters) is zeroed
    so state never crosses a sequence boundary. Unpacked/nested inputs
    return None and trace the exact pre-packing program.

    Under a packed feed a sequence input MUST still carry seg_ids —
    seg_ids propagation is opt-in per layer, and a time-preserving
    layer that dropped them would otherwise fail OPEN here (no resets,
    state silently leaking across packed boundaries). Refuse loudly
    instead, like attention does."""
    if not getattr(ctx, "packed", False):
        return None
    if a.mask is not None:
        enforce(a.seg_ids is not None,
                "recurrent layer over a packed feed lost its seg_ids "
                "(an upstream layer dropped them); packed rows without "
                "segment resets would leak state across sequence "
                "boundaries — feed this model unpacked or keep seg_ids "
                "propagating through every time-preserving layer")
        return segment_start_resets(a.seg_ids, a.mask, reverse=reverse)
    return None


# --- simple recurrent ----------------------------------------------------

def _recurrent_infer(cfg, in_infos):
    return ArgInfo(size=in_infos[0].size, is_seq=True)


def _recurrent_params(cfg, in_infos):
    n = in_infos[0].size
    specs = {"w0": ParamSpec((n, n), cfg.param_attr(0), fan_in=n)}
    battr = cfg.bias_param_attr()
    if battr is not None:
        specs["wbias"] = ParamSpec((n,), battr, fan_in=n, is_bias=True)
    return specs


@register_layer("recurrent", infer=_recurrent_infer, params=_recurrent_params)
def _recurrent(cfg, params, ins, ctx):
    a = ins[0]
    act = act_mod.resolve(cfg.attr("active_type", "tanh"))
    reverse = cfg.attr("reverse", False)
    W = params["w0"]
    b = params.get("wbias", 0.0)
    xs = _to_time_major(a.value)                  # [T, B, D]
    # mask blends are exact in any float dtype; casting keeps the scan
    # carry in the compute dtype under mixed precision
    ms = _to_time_major(a.mask.astype(a.value.dtype))[..., None]
    reset = _packed_resets(a, ctx, reverse)
    h0 = jnp.zeros((a.value.shape[0], W.shape[0]), a.value.dtype)

    if reset is not None:
        rs = _to_time_major(reset.astype(a.value.dtype))[..., None]

        def step_packed(h, xmr):
            x, m, r = xmr
            h = (1 - r) * h               # cut the carry at segment starts
            h_new = act.apply(x + jnp.matmul(h, W) + b)
            h = m * h_new + (1 - m) * h
            return h, h

        _, hs = _scan_time(step_packed, h0, (xs, ms, rs), reverse=reverse)
        out = jnp.swapaxes(hs, 0, 1)
        return Arg(out * a.mask[..., None].astype(out.dtype), a.mask,
                   a.seg_ids)

    def step(h, xm):
        x, m = xm
        h_new = act.apply(x + jnp.matmul(h, W) + b)
        h = m * h_new + (1 - m) * h
        return h, h

    _, hs = _scan_time(step, h0, (xs, ms), reverse=reverse)
    out = jnp.swapaxes(hs, 0, 1)
    return Arg(out * a.mask[..., None].astype(out.dtype), a.mask, a.seg_ids)


# --- LSTM ----------------------------------------------------------------

def _lstm_infer(cfg, in_infos):
    enforce(in_infos[0].size % 4 == 0, "lstmemory input must be 4*size (pre-projected)")
    return ArgInfo(size=in_infos[0].size // 4, is_seq=True)


def _lstm_params(cfg, in_infos):
    n = in_infos[0].size // 4
    specs = {"w0": ParamSpec((n, 4 * n), cfg.param_attr(0), fan_in=n)}
    battr = cfg.bias_param_attr()
    if battr is not None:
        # bias holds gate biases + 3 peephole vectors, 7*size total —
        # same packing as the reference LstmLayer bias parameter.
        specs["wbias"] = ParamSpec((7 * n,), battr, fan_in=n, is_bias=True)
    return specs


def lstm_cell(x4, h_prev, c_prev, W, bias, out_act, state_act, n,
              gate_act=None):
    """One LSTM step; x4 [B, 4n] pre-projected input. gate_act defaults to
    sigmoid (reference LstmLayer active_gate_type)."""
    gate = gate_act.apply if gate_act is not None else jax.nn.sigmoid
    pre = x4 + jnp.matmul(h_prev, W)
    if bias is not None:
        pre = pre + bias[:4 * n]
    i_, f_, c_, o_ = jnp.split(pre, 4, axis=-1)
    if bias is not None:
        pi, pf, po = bias[4 * n:5 * n], bias[5 * n:6 * n], bias[6 * n:7 * n]
        i_ = i_ + pi * c_prev
        f_ = f_ + pf * c_prev
    i = gate(i_)
    f = gate(f_)
    c_new = f * c_prev + i * state_act.apply(c_)
    if bias is not None:
        o_ = o_ + po * c_new
    o = gate(o_)
    h_new = o * out_act.apply(c_new)
    return h_new, c_new


def _default_lstm_acts(cfg):
    return (cfg.attr("active_type", "tanh") == "tanh"
            and cfg.attr("active_state_type", "tanh") == "tanh"
            and cfg.attr("active_gate_type", "sigmoid") == "sigmoid")


def _default_gru_acts(cfg):
    return (cfg.attr("active_type", "tanh") == "tanh"
            and cfg.attr("active_gate_type", "sigmoid") == "sigmoid")


def _take_fused(cfg, kernel, default_acts, supported, B, n):
    """Whether this recurrent layer runs its fused Pallas kernel
    (kernels/_pallas_util.take_pallas decides and logs). ``supported`` is
    the kernel's own gate, asked about the batch one shard sees."""
    from paddle_tpu.kernels._pallas_util import batch_shards, take_pallas

    if not default_acts:
        return take_pallas(cfg.name, kernel, False,
                           "the kernel hardcodes sigmoid/tanh")
    shards = batch_shards()
    if B % shards or not supported(B // shards, n):
        return take_pallas(cfg.name, kernel, False,
                           f"B={B} over {shards} shard(s), H={n}: outside "
                           "the kernel's gate (B%8, H%128, VMEM estimate)")
    return take_pallas(cfg.name, kernel)


@register_layer("lstmemory", infer=_lstm_infer, params=_lstm_params)
def _lstmemory(cfg, params, ins, ctx):
    a = ins[0]
    n = a.value.shape[-1] // 4
    reverse = cfg.attr("reverse", False)
    out_act = act_mod.resolve(cfg.attr("active_type", "tanh"))
    state_act = act_mod.resolve(cfg.attr("active_state_type", "tanh"))
    gate_act = act_mod.resolve(cfg.attr("active_gate_type", "sigmoid"))
    W = params["w0"]
    bias = params.get("wbias")
    B = a.value.shape[0]

    # fused Pallas path (hl_gpu_lstm.cuh analog): one kernel for the whole
    # recurrence with W resident in VMEM — the scan path refetches W from
    # HBM every timestep and is bandwidth-bound
    from paddle_tpu.kernels._pallas_util import call_kernel
    from paddle_tpu.kernels.lstm import fused_lstm, fused_lstm_supported

    reset = _packed_resets(a, ctx, reverse)
    if _take_fused(cfg, "fused_lstm", _default_lstm_acts(cfg),
                   fused_lstm_supported, B, n):
        x4 = a.value
        mask = a.mask if a.mask is not None else \
            jnp.ones(x4.shape[:2], jnp.float32)
        if reverse:
            # the kernel always runs forward over flipped inputs; the
            # reverse-direction resets (segment ENDS) flip along with
            # them into forward-direction segment starts
            x4 = jnp.flip(x4, axis=1)
            mask = jnp.flip(mask, axis=1)
            if reset is not None:
                reset = jnp.flip(reset, axis=1)
        b7 = bias if bias is not None else jnp.zeros((7 * n,), x4.dtype)
        hs_b, cs_b = call_kernel(fused_lstm, (x4, W, b7, mask, reset),
                                 batch_argnums=(0, 3, 4))
        if reverse:
            hs_b = jnp.flip(hs_b, axis=1)
            cs_b = jnp.flip(cs_b, axis=1)
        mm = a.mask[..., None].astype(hs_b.dtype) if a.mask is not None \
            else 1.0
        ctx.extras[f"{cfg.name}:state"] = Arg(cs_b * mm, a.mask)
        return Arg(hs_b * mm, a.mask, a.seg_ids)

    xs = _to_time_major(a.value)
    ms = _to_time_major(a.mask.astype(a.value.dtype))[..., None]
    h0 = jnp.zeros((B, n), a.value.dtype)
    c0 = jnp.zeros((B, n), a.value.dtype)

    if reset is not None:
        rs = _to_time_major(reset.astype(a.value.dtype))[..., None]

        def step_packed(carry, xmr):
            h, c = carry
            x, m, r = xmr
            h = (1 - r) * h               # cut the carry at segment starts
            c = (1 - r) * c
            h_new, c_new = lstm_cell(x, h, c, W, bias, out_act, state_act,
                                     n, gate_act)
            h = m * h_new + (1 - m) * h
            c = m * c_new + (1 - m) * c
            return (h, c), (h, c)

        (_, _), (hs, cs) = _scan_time(step_packed, (h0, c0), (xs, ms, rs),
                                      reverse=reverse)
    else:
        def step(carry, xm):
            h, c = carry
            x, m = xm
            h_new, c_new = lstm_cell(x, h, c, W, bias, out_act, state_act, n,
                                     gate_act)
            h = m * h_new + (1 - m) * h
            c = m * c_new + (1 - m) * c
            return (h, c), (h, c)

        (_, _), (hs, cs) = _scan_time(step, (h0, c0), (xs, ms),
                                      reverse=reverse)
    mm = a.mask[..., None].astype(a.value.dtype)
    out = jnp.swapaxes(hs, 0, 1) * mm
    ctx.extras[f"{cfg.name}:state"] = Arg(jnp.swapaxes(cs, 0, 1) * mm, a.mask)
    return Arg(out, a.mask, a.seg_ids)


# --- GRU -----------------------------------------------------------------

def _gru_infer(cfg, in_infos):
    enforce(in_infos[0].size % 3 == 0, "gated_recurrent input must be 3*size")
    return ArgInfo(size=in_infos[0].size // 3, is_seq=True)


def _gru_params(cfg, in_infos):
    n = in_infos[0].size // 3
    specs = {
        "w0": ParamSpec((n, 2 * n), cfg.param_attr(0), fan_in=n),   # gates
        "w1": ParamSpec((n, n), cfg.param_attr(1), fan_in=n),       # candidate
    }
    battr = cfg.bias_param_attr()
    if battr is not None:
        specs["wbias"] = ParamSpec((3 * n,), battr, fan_in=n, is_bias=True)
    return specs


def gru_cell(x3, h_prev, Wg, Wc, bias, gate_act, candidate_act, n):
    xg, xc = x3[..., :2 * n], x3[..., 2 * n:]
    g = xg + jnp.matmul(h_prev, Wg)
    if bias is not None:
        g = g + bias[:2 * n]
    z = jax.nn.sigmoid(g[..., :n])
    r = jax.nn.sigmoid(g[..., n:])
    c = xc + jnp.matmul(r * h_prev, Wc)
    if bias is not None:
        c = c + bias[2 * n:]
    c = candidate_act.apply(c)
    # reference GruLayer: h = z * h_prev + (1 - z) * candidate
    return z * h_prev + (1 - z) * c


@register_layer("gated_recurrent", infer=_gru_infer, params=_gru_params)
def _gated_recurrent(cfg, params, ins, ctx):
    a = ins[0]
    n = a.value.shape[-1] // 3
    reverse = cfg.attr("reverse", False)
    gate_act = act_mod.resolve(cfg.attr("active_gate_type", "sigmoid"))
    cand_act = act_mod.resolve(cfg.attr("active_type", "tanh"))
    Wg, Wc = params["w0"], params["w1"]
    bias = params.get("wbias")

    # fused Pallas path (kernels/gru.py; same design as the LSTM kernel):
    # default activations only — the kernel hardcodes sigmoid/tanh
    from paddle_tpu.kernels._pallas_util import call_kernel
    from paddle_tpu.kernels.gru import fused_gru, fused_gru_supported

    B = a.value.shape[0]
    reset = _packed_resets(a, ctx, reverse)
    if _take_fused(cfg, "fused_gru", _default_gru_acts(cfg),
                   fused_gru_supported, B, n):
        x3 = a.value
        mask = a.mask if a.mask is not None else \
            jnp.ones(x3.shape[:2], jnp.float32)
        if reverse:
            x3 = jnp.flip(x3, axis=1)
            mask = jnp.flip(mask, axis=1)
            if reset is not None:
                reset = jnp.flip(reset, axis=1)
        b3 = bias if bias is not None else jnp.zeros((3 * n,), x3.dtype)
        hs = call_kernel(fused_gru, (x3, Wg, Wc, b3, mask, reset),
                         batch_argnums=(0, 4, 5))
        if reverse:
            hs = jnp.flip(hs, axis=1)
        if a.mask is not None:
            hs = hs * a.mask[..., None].astype(hs.dtype)
        return Arg(hs, a.mask, a.seg_ids)

    xs = _to_time_major(a.value)
    ms = _to_time_major(a.mask.astype(a.value.dtype))[..., None]
    h0 = jnp.zeros((a.value.shape[0], n), a.value.dtype)

    if reset is not None:
        rs = _to_time_major(reset.astype(a.value.dtype))[..., None]

        def step_packed(h, xmr):
            x, m, r = xmr
            h = (1 - r) * h               # cut the carry at segment starts
            h_new = gru_cell(x, h, Wg, Wc, bias, gate_act, cand_act, n)
            h = m * h_new + (1 - m) * h
            return h, h

        _, hs = _scan_time(step_packed, h0, (xs, ms, rs), reverse=reverse)
        out = jnp.swapaxes(hs, 0, 1) * a.mask[..., None].astype(a.value.dtype)
        return Arg(out, a.mask, a.seg_ids)

    def step(h, xm):
        x, m = xm
        h_new = gru_cell(x, h, Wg, Wc, bias, gate_act, cand_act, n)
        h = m * h_new + (1 - m) * h
        return h, h

    _, hs = _scan_time(step, h0, (xs, ms), reverse=reverse)
    out = jnp.swapaxes(hs, 0, 1) * a.mask[..., None].astype(a.value.dtype)
    return Arg(out, a.mask, a.seg_ids)


# --- single-step cells (for recurrent groups / generation) ---------------

def _lstm_step_infer(cfg, in_infos):
    return ArgInfo(size=cfg.size)


def _lstm_step_params(cfg, in_infos):
    n = cfg.size
    specs = {"w0": ParamSpec((n, 4 * n), cfg.param_attr(0), fan_in=n)}
    battr = cfg.bias_param_attr()
    if battr is not None:
        specs["wbias"] = ParamSpec((7 * n,), battr, fan_in=n, is_bias=True)
    return specs


@register_layer("lstm_step", infer=_lstm_step_infer, params=_lstm_step_params)
def _lstm_step(cfg, params, ins, ctx):
    """One LSTM step: in0 = pre-projected input [B, 4n], in1 = prev cell
    state [B, n]. Output = hidden; new cell state published as
    '<name>:state' (get_output arg_name='state' taps it)."""
    n = cfg.size
    x4, c_prev = ins[0].value, ins[1].value
    # h_prev is recovered from the output gate path in the reference; here
    # the recurrent group passes h via the boot/memory mechanism in x4.
    h_prev = ins[2].value if len(ins) > 2 else jnp.zeros_like(c_prev)
    out_act = act_mod.resolve(cfg.attr("active_type", "tanh"))
    state_act = act_mod.resolve(cfg.attr("active_state_type", "tanh"))
    h, c = lstm_cell(x4, h_prev, c_prev, params["w0"], params.get("wbias"),
                     out_act, state_act, n)
    ctx.extras[f"{cfg.name}:state"] = Arg(c)
    return Arg(h)


def _gru_step_infer(cfg, in_infos):
    return ArgInfo(size=cfg.size)


def _gru_step_params(cfg, in_infos):
    n = cfg.size
    specs = {"w0": ParamSpec((n, 2 * n), cfg.param_attr(0), fan_in=n),
             "w1": ParamSpec((n, n), cfg.param_attr(1), fan_in=n)}
    battr = cfg.bias_param_attr()
    if battr is not None:
        specs["wbias"] = ParamSpec((3 * n,), battr, fan_in=n, is_bias=True)
    return specs


@register_layer("gru_step", infer=_gru_step_infer, params=_gru_step_params)
def _gru_step(cfg, params, ins, ctx):
    """One GRU step: in0 = pre-projected [B, 3n], in1 = prev hidden [B, n]."""
    n = cfg.size
    x3, h_prev = ins[0].value, ins[1].value
    gate_act = act_mod.resolve(cfg.attr("active_gate_type", "sigmoid"))
    cand_act = act_mod.resolve(cfg.attr("active_type", "tanh"))
    h = gru_cell(x3, h_prev, params["w0"], params["w1"], params.get("wbias"),
                 gate_act, cand_act, n)
    return Arg(h)


# --- mdlstm (2-D LSTM over feature maps) ---------------------------------

def _mdlstm_infer(cfg, in_infos):
    enforce(in_infos[0].size % 5 == 0, "mdlstmemory input must be 5*size")
    return ArgInfo(size=in_infos[0].size // 5, is_seq=in_infos[0].is_seq)


def _mdlstm_params(cfg, in_infos):
    n = in_infos[0].size // 5
    # ONE shared recurrent matrix applied to every spatial predecessor
    # (MDLstmLayer.cpp:228 CHECK_EQ(n*n*(3+numDims)) with numDims=2), and
    # a (5+2*numDims)*n = 9n bias laid out
    # [localBias 5n | checkIg n | checkFg 2n | checkOg n]
    # (MDLstmLayer.cpp:232,279-282) — the check* blocks are the peephole
    # weights.
    specs = {"w0": ParamSpec((n, 5 * n), cfg.param_attr(0), fan_in=n)}
    battr = cfg.bias_param_attr()
    if battr is not None:
        specs["wbias"] = ParamSpec((9 * n,), battr, fan_in=n, is_bias=True)
    return specs


def _mdlstm_bias_blocks(bias, n, dtype):
    """Split the 9n reference bias into (localBias[5n], checkIg, checkFg0,
    checkFg1, checkOg); zeros when the layer has no bias."""
    if bias is None:
        z = jnp.zeros((n,), dtype)
        return jnp.zeros((5 * n,), dtype), z, z, z, z
    return (bias[:5 * n], bias[5 * n:6 * n], bias[6 * n:7 * n],
            bias[7 * n:8 * n], bias[8 * n:9 * n])


@register_layer("mdlstmemory", infer=_mdlstm_infer, params=_mdlstm_params)
def _mdlstmemory(cfg, params, ins, ctx):
    """MDLstmLayer (multi-dimensional LSTM, MDLstmLayer.cpp): true 2-D
    wavefront with reference parameter parity. The input sequence
    [B, T, 5n] is a row-major H x W grid (attrs ``mdlstm_height``/
    ``mdlstm_width``; default W=1 degenerates to a 1-D chain, matching
    variable-length sequence use).

    Gate blocks are the reference's order (MDLstmLayer.cpp:176
    "IG Layer: (Input, InputGate, ForgetGates, OutputGate)"), one shared
    recurrent matrix W multiplies every predecessor's output
    (forwardOneSequence, MDLstmLayer.cpp:558-565), and the 9n bias carries
    the peephole blocks (checkIg/checkFg/checkOg, applied in
    forwardGate2OutputSequence, MDLstmLayer.cpp:489-547):

        pre(i,j) = x(i,j) + (h(i-1,j) + h(i,j-1)) @ W + localBias
        [g | ig | f0 | f1 | og] = split(pre)
        ig += (c(i-1,j) + c(i,j-1)) * checkIg
        f0 += c(i-1,j) * checkFg0 ;  f1 += c(i,j-1) * checkFg1
        c(i,j) = sig(f0)*c(i-1,j) + sig(f1)*c(i,j-1) + sig(ig)*tanh(g)
        og += c(i,j) * checkOg
        h(i,j) = sig(og) * tanh(c(i,j))

    Zero boundary states make the "only when the predecessor exists"
    guards implicit: a missing neighbour contributes 0 to pre, to the
    peepholes, and to c.

    Scheduling: ``lax.scan`` over the H+W-1 anti-diagonals — every cell on
    a diagonal is independent, so each tick is one batched [B*H, n]x[n,5n]
    matmul on the MXU (the TPU-native form of the reference's wavefront
    loop; the shared weight lets both predecessors ride one matmul).
    ``reverse_x``/``reverse_y`` attrs flip the scan direction per
    dimension (the reference's 4 scan directions).
    """
    a = ins[0]
    enforce(not getattr(ctx, "packed", False),
            f"mdlstmemory {cfg.name}: packed sequence rows are not "
            "supported (the 2-D wavefront has no segment-reset path); "
            "feed this model unpacked")
    B, T = a.value.shape[0], a.value.shape[1]
    n = a.value.shape[-1] // 5
    Hh, Ww = cfg.attr("mdlstm_height"), cfg.attr("mdlstm_width")
    if Hh is None and Ww is None:
        Hh, Ww = T, 1               # variable-length 1-D chain default
    elif Hh is None:
        Hh = T // max(Ww, 1)
    elif Ww is None:
        Ww = T // max(Hh, 1)
    enforce(Hh * Ww == T, f"mdlstmemory {cfg.name}: grid {Hh}x{Ww} != T={T}")
    Wrec = params["w0"]
    bias = params.get("wbias")
    local_b, check_ig, check_fg0, check_fg1, check_og = \
        _mdlstm_bias_blocks(bias, n, a.value.dtype)

    if Ww == 1 or Hh == 1:
        # degenerate 1-D chain: the wavefront's per-diagonal batched form
        # would be O(T^2) here (every tick computes all rows for one valid
        # cell); run the O(T) masked scan instead. Edge padding matches
        # the grid form (a frozen zero carry == reading a zeroed masked
        # neighbour); the off-chain forget gate sees the zero boundary.
        # the chain runs along dim 0 (height) when W==1, else dim 1 — the
        # active forget gate / checkFg block follows the dim index
        check_fg = check_fg0 if Ww == 1 else check_fg1
        rev = cfg.attr("reverse_y") if Ww == 1 else cfg.attr("reverse_x")
        xs = _to_time_major(a.value)
        ms = (_to_time_major(a.mask.astype(a.value.dtype))[..., None]
              if a.mask is not None
              else jnp.ones(xs.shape[:2] + (1,), a.value.dtype))
        h0 = jnp.zeros((B, n), a.value.dtype)
        c0 = jnp.zeros_like(h0)

        def chain_step(carry, xm):
            h, c = carry
            x, m = xm
            pre = x + jnp.matmul(h, Wrec) + local_b
            g_, ig_, f0_, f1_, og_ = jnp.split(pre, 5, axis=-1)
            f_on = (f0_ if Ww == 1 else f1_) + c * check_fg
            ig_ = ig_ + c * check_ig
            c_new = (jax.nn.sigmoid(f_on) * c
                     + jax.nn.sigmoid(ig_) * jnp.tanh(g_))
            og_ = og_ + c_new * check_og
            h_new = jax.nn.sigmoid(og_) * jnp.tanh(c_new)
            # masked cells do not update state (grid-form parity)
            h2 = m * h_new + (1 - m) * h
            c2 = m * c_new + (1 - m) * c
            return (h2, c2), h2

        _, hs = _scan_time(chain_step, (h0, c0), (xs, ms),
                           reverse=bool(rev))
        out = jnp.swapaxes(hs, 0, 1)
        if a.mask is not None:
            out = out * a.mask[..., None].astype(out.dtype)
        return Arg(out, a.mask, a.seg_ids)
    x = a.value.reshape(B, Hh, Ww, 5 * n)
    # ragged grids: masked (padded) cells never update h/c, so their
    # stored state stays the zero boundary value — successors of padding
    # see the same zeros a grid edge provides (matters under reverse_*,
    # where flipping moves the padding ahead of the valid cells)
    mgrid = (a.mask.reshape(B, Hh, Ww) if a.mask is not None
             else jnp.ones((B, Hh, Ww), x.dtype))
    if cfg.attr("reverse_y"):
        x = jnp.flip(x, axis=1)
        mgrid = jnp.flip(mgrid, axis=1)
    if cfg.attr("reverse_x"):
        x = jnp.flip(x, axis=2)
        mgrid = jnp.flip(mgrid, axis=2)

    ii = jnp.arange(Hh)
    h_grid0 = jnp.zeros((B, Hh, Ww, n), a.value.dtype)
    c_grid0 = jnp.zeros_like(h_grid0)

    def tick(carry, d):
        h_grid, c_grid = carry
        jj = d - ii                                   # col per row on diag d
        valid = (jj >= 0) & (jj < Ww)
        jc = jnp.clip(jj, 0, Ww - 1)
        x_d = x[:, ii, jc]                            # [B, H, 5n]
        up_i = jnp.clip(ii - 1, 0, Hh - 1)
        h_up = jnp.where((ii > 0)[None, :, None], h_grid[:, up_i, jc], 0.0)
        c_up = jnp.where((ii > 0)[None, :, None], c_grid[:, up_i, jc], 0.0)
        jl = jnp.clip(jc - 1, 0, Ww - 1)
        left_ok = (jj > 0) & valid
        h_left = jnp.where(left_ok[None, :, None], h_grid[:, ii, jl], 0.0)
        c_left = jnp.where(left_ok[None, :, None], c_grid[:, ii, jl], 0.0)
        pre = x_d + jnp.matmul(h_up + h_left, Wrec) + local_b
        g_, ig_, f0_, f1_, og_ = jnp.split(pre, 5, axis=-1)
        ig_ = ig_ + (c_up + c_left) * check_ig
        f0_ = f0_ + c_up * check_fg0
        f1_ = f1_ + c_left * check_fg1
        c_new = (jax.nn.sigmoid(f0_) * c_up + jax.nn.sigmoid(f1_) * c_left
                 + jax.nn.sigmoid(ig_) * jnp.tanh(g_))
        og_ = og_ + c_new * check_og
        h_new = jax.nn.sigmoid(og_) * jnp.tanh(c_new)
        m_d = mgrid[:, ii, jc]                        # [B, H] cell mask
        keep = valid[None, :, None] & (m_d[..., None] > 0)
        h_grid = h_grid.at[:, ii, jc].set(
            jnp.where(keep, h_new, h_grid[:, ii, jc]))
        c_grid = c_grid.at[:, ii, jc].set(
            jnp.where(keep, c_new, c_grid[:, ii, jc]))
        return (h_grid, c_grid), None

    (h_grid, _), _ = jax.lax.scan(tick, (h_grid0, c_grid0),
                                  jnp.arange(Hh + Ww - 1))
    if cfg.attr("reverse_x"):
        h_grid = jnp.flip(h_grid, axis=2)
    if cfg.attr("reverse_y"):
        h_grid = jnp.flip(h_grid, axis=1)
    out = h_grid.reshape(B, T, n)
    if a.mask is not None:
        out = out * a.mask[..., None].astype(out.dtype)
    return Arg(out, a.mask, a.seg_ids)
