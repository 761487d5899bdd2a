"""Analytic model-FLOP accounting for MFU reporting.

``topology_fwd_flops`` walks the layer graph and sums the matmul work
(2 * positions * weight-elements per consumed weight — the standard
dense-layer FLOP count), ``train_flops`` applies the usual 3x
forward-multiplier (backward = ~2x forward for matmul-dominated nets),
and ``device_peak_flops`` looks up the chip's published peak so
mfu = achieved / peak.

Deliberately approximate where it does not matter: elementwise work
(activations, norms, masks, optimizer update) and embedding gathers are
omitted — on the book's models they are <2% of the matmul work.
Layer types with no entry below contribute zero; the per-type accounting
is the audit trail.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


# published dense peak (bf16 FLOP/s) per device kind; mfu is None on
# platforms without a published figure (e.g. the CPU test mesh)
_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6e": 918e12,
    "TPU v6 lite": 918e12,
}


def device_peak_flops(device=None) -> Optional[float]:
    import jax

    d = device or jax.devices()[0]
    kind = getattr(d, "device_kind", "")
    for name, peak in _PEAK_FLOPS.items():
        if kind.lower().startswith(name.lower()):
            return peak
    return None


def _weight_numels(topo, lname) -> int:
    """Total elements of the non-bias weights a layer consumes."""
    specs = topo.param_specs()
    total = 0
    for suffix, pname in topo._layer_params[lname].items():
        if suffix == "wbias":
            continue
        total += int(np.prod(specs[pname].shape))
    return total


def _selective_fc_numel(topo, l) -> int:
    """Effective per-position weight elements of a selective_fc,
    mirroring the layer's own path choice (layers/misc.py): the gather
    path (compact_output, or id-list selection above the gather_min_c
    crossover) multiplies only the K selected rows per position — K*D
    instead of C*D; the dense-mask fallback pays the full matmul."""
    from paddle_tpu.layers.misc import (_SELFC_GATHER_MIN_C,
                                        _SELFC_GATHER_MIN_C_SPARSE)

    numel = _weight_numels(topo, l.name)
    C = l.size
    K = topo.info(l.inputs[-1].name).size
    id_list = bool(l.attr("select_is_id_list")) or K != C
    min_c = l.attr("gather_min_c")
    if min_c is None:
        sparse = all(l.param_attr(i).sparse_update
                     for i in range(len(l.inputs) - 1))
        min_c = _SELFC_GATHER_MIN_C_SPARSE if sparse else _SELFC_GATHER_MIN_C
    gather = bool(l.attr("compact_output")) or (id_list and C >= min_c)
    if gather and K < C:
        numel = numel * K // C      # exact: every weight carries factor C
    return numel


def _beam_inner_numel(l) -> int:
    """Per-tick, per-hypothesis matmul weight elements of a beam_search
    layer's step sub-network. selective_fc projections count in candidate
    space (K rows per position) — the compact-K decode accounting."""
    itopo = l.attr("inner").topology
    total = 0
    for il in itopo.layers:
        if il.type == "selective_fc":
            total += _selective_fc_numel(itopo, il)
        else:
            total += _weight_numels(itopo, il.name)
    return total


def _numel(shapes, *suffixes) -> int:
    return sum(int(np.prod(shapes[s])) for s in suffixes)


def _gated_attention_flops(l, shapes, T) -> float:
    """Per row: the four projections, and the causal scores and weighted
    sum (each query against the keys at or before it)."""
    H, D = l.attr("num_heads"), l.attr("head_dim")
    proj = 2.0 * T * _numel(shapes, "wq", "wk", "wv", "wo")
    return proj + 2 * 2.0 * H * D * T * (T + 1) / 2


def _gqa_attention_flops(l, shapes, T) -> float:
    """Per row of the 2L positions the layer's mask rule is over (whatever
    ``T`` the caller counts the other layers at): the four projections, and
    the scores and weighted sum of the pairs the block-diffusion rule keeps,
    L^2 + sum of the squared block sizes of the 4 L^2."""
    H, D = l.attr("num_heads"), l.attr("head_dim")
    _, L, b = l.attr("mask")
    kept = L * L + (L // b) * b * b + (L % b) ** 2
    proj = 2.0 * 2 * L * _numel(shapes, "wq", "wk", "wv", "wo")
    return proj + 2 * 2.0 * H * D * kept


def _mla_attention_flops(l, shapes, T) -> float:
    """Per row: the query, latent, decompression and output projections, and
    the causal scores (heads of nope + rope) and weighted sum (value
    heads), each query against the keys at or before it."""
    H = l.attr("num_heads")
    Dqk = l.attr("qk_nope_head_dim") + l.attr("qk_rope_head_dim")
    proj = 2.0 * T * _numel(shapes, "wq", "wkva", "wkvb", "wo")
    return proj + 2.0 * H * (Dqk + l.attr("v_head_dim")) * T * (T + 1) / 2


def _gated_delta_net_flops(l, shapes, T) -> float:
    """Per row: the projections, the depthwise convolution, and the delta
    rule as its recurrence defines it (three dk x dv matrix-vector products
    a token and value head: read, write, query), not as the chunked form
    computes it."""
    Hv = l.attr("num_v_heads")
    dk, dv = l.attr("head_k_dim"), l.attr("head_v_dim")
    proj = 2.0 * T * _numel(shapes, "wqkvz", "wba", "wout", "conv")
    return proj + 2.0 * T * Hv * 3 * dk * dv


def _moe_ffn_flops(l, shapes, T) -> float:
    """Per row: the router over all experts, the shared expert and its
    gate where the layer has one, and the routed experts a token reaches HERE: of its top_k choices
    the share experts_held / num_experts in expectation, three d x I
    products each. Not all the weights held."""
    E, held, k = l.attr("num_experts"), l.attr("experts_held"), l.attr("top_k")
    dense = _numel(shapes, *(s for s in shapes
                             if s == "router" or s.startswith("shared_")))
    one_expert = _numel(shapes, "wg", "wu", "wd") / held
    return 2.0 * T * (dense + k * held / E * one_expert)


# per-row forward FLOPs of the decoder-block layers, whose work is not
# "positions x weights held": (layer, {suffix: shape}, seq_len) -> FLOPs
_DECODER_FLOPS = {
    "rms_norm": lambda l, shapes, T: 0.0,        # elementwise
    "gated_attention": _gated_attention_flops,
    "gqa_attention": _gqa_attention_flops,
    "mla_attention": _mla_attention_flops,
    "gated_delta_net": _gated_delta_net_flops,
    "moe_ffn": _moe_ffn_flops,
}


def layer_fwd_flops(topo, l, batch: int, seq_len: int = 1,
                    decode_ticks: Optional[int] = None) -> float:
    """Forward multiply-add FLOPs ONE layer contributes to a batch — the
    per-layer term :func:`topology_fwd_flops` sums, exposed on its own so
    the pipeline stage balancer (parallel/topo_pipeline.py) and the PP
    accounting tool can price per-stage compute with the same audit
    trail the MFU gauges use."""
    if l.type == "embedding":
        # table lookup, not a matmul — the docstring's "embedding
        # gathers are omitted" made concrete (pricing the [V, D]
        # table as a dense multiply would swamp real decode work)
        return 0.0
    if l.type in _DECODER_FLOPS:
        specs = topo.param_specs()
        shapes = {sfx: specs[pn].shape
                  for sfx, pn in topo._layer_params[l.name].items()}
        return float(batch * _DECODER_FLOPS[l.type](l, shapes, seq_len))
    numel = _weight_numels(topo, l.name)
    if numel == 0 and l.type not in ("recurrent_layer_group",
                                     "beam_search"):
        return 0.0
    info = topo.info(l.name)
    if l.type in ("exconv", "exconvt", "cudnn_conv", "cudnn_convt",
                  "mkldnn_conv", "conv3d", "deconv3d"):
        # out_info.shape = (C, H', W'[, ...]): spatial positions
        spatial = int(np.prod(info.shape[1:]))
        return 2.0 * batch * spatial * numel
    if l.type == "beam_search":
        beam = l.attr("beam_size", 1)
        ticks = decode_ticks if decode_ticks is not None \
            else l.attr("max_length", 25)
        return 2.0 * batch * beam * ticks * _beam_inner_numel(l)
    if l.type == "recurrent_layer_group":
        inner = l.attr("inner")
        inner_numel = sum(
            int(np.prod(s.shape))
            for n, s in inner.topology.param_specs().items()
            if not s.is_bias)
        return 2.0 * batch * seq_len * inner_numel
    if l.type == "selective_fc":
        pos = batch * seq_len if info.is_seq else batch
        return 2.0 * pos * _selective_fc_numel(topo, l)
    if l.type in ("lstmemory", "grumemory", "recurrent"):
        # recurrent weight applied once per tick
        return 2.0 * batch * seq_len * numel
    if info.is_seq:
        return 2.0 * batch * seq_len * numel
    return 2.0 * batch * numel


def topology_fwd_flops(topo, batch: int, seq_len: int = 1,
                       decode_ticks: Optional[int] = None) -> float:
    """Forward multiply-add FLOPs of one batch through the topology.

    Per layer: 2 * positions * weight_elements, where positions is the
    number of independent output rows the weight multiplies — batch for
    plain layers, batch*T for sequence layers, H'*W'*batch for convs
    (the weight slides over the output plane), batch*T for the matmuls
    inside recurrent cells (gate transform applied per tick), and
    batch*beam*ticks for beam_search generation (``decode_ticks``
    overrides the static max_length when the early-exit loop actually
    ran fewer ticks). selective_fc layers on the gather path count K
    selected rows per position, so compact-K decode FLOPs reflect the
    candidate-space work (top-k / softmax / gathers are non-matmul and
    omitted like all elementwise work).
    """
    return float(sum(layer_fwd_flops(topo, l, batch, seq_len, decode_ticks)
                     for l in topo.layers))


def train_flops(topo, batch: int, seq_len: int = 1) -> float:
    """fwd + bwd ~= 3x fwd for matmul-dominated nets (dX and dW each
    re-run the forward's contraction)."""
    return 3.0 * topology_fwd_flops(topo, batch, seq_len)


def mfu(flops_per_sec: float, device=None) -> Optional[float]:
    peak = device_peak_flops(device)
    if not peak:
        return None
    return flops_per_sec / peak
