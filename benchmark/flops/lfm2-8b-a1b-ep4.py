"""Model FLOPs of one training step of the LFM2-8B-A1B decoder's share, from
the configuration's shapes.

Counted: the multiply-adds of every matrix product, 2 FLOPs each, forward
once and backward twice: 3 x forward, nothing recomputed. All B x T positions
of the padded batch count, as the step computes them. Per token, over the
blocks `layers_held` names of `layer_types`:

  conv        each conv block: x W_in (d x 3d), the depthwise convolution
              (d x K taps) and c W_out (d x d).
  attention   each attention block: x Wq, x Wk, x Wv (H and Hkv heads of
              D = d / H), o Wo, and the causal scores and weighted sum: each
              query against the (T + 1) / 2 keys at or before it on average,
              D multiply-adds a score and D a weighted value, AS PUBLISHED
              (64; lanes an implementation pads a head with are not counted).
  dense MLP   the blocks before `num_dense_layers`: three d x I_dense products.
  MoE         the other blocks: the router over ALL experts, and the routed
              experts a token reaches ON THIS CHIP: of its k choices the share
              held / E in expectation (the selection bias keeps the loads
              level, so the expectation is the count), three d x I products
              each. Not the weights held.
  head        the [d, V] product with the tied table.

Not counted: the embedding look-up, norms, rotary, the gates' products
(B * x~, C * c), sigmoid, top-k, dispatch, the bias rule, the optimizer.
"""


def _kinds(a):
    return [(a["layer_types"][l], l < a["num_dense_layers"])
            for l in a["layers_held"]]


def forward_macs_per_token(a, T):
    """{part: multiply-adds a token, forward} at row length T."""
    d, V, K = a["hidden_size"], a["vocab_size"], a["conv_L_cache"]
    H, Hkv = a["num_attention_heads"], a["num_key_value_heads"]
    D = d // H
    E, k, held = a["num_experts"], a["num_experts_per_tok"], a["experts_held"]
    kinds = _kinds(a)
    convs = sum(kind == "conv" for kind, _ in kinds)
    attns = sum(kind == "full_attention" for kind, _ in kinds)
    dense = sum(is_dense for _, is_dense in kinds)
    return {"conv": convs * (3 * d * d + d * K + d * d),
            "attention_projections": attns * (2 * d * H * D
                                              + 2 * d * Hkv * D),
            "attention_scores": attns * H * 2 * D * (T + 1) / 2,
            "dense_mlp": dense * 3 * d * a["intermediate_size"],
            "moe_router": (len(kinds) - dense) * d * E,
            "moe_routed": (len(kinds) - dense) * k * held / E * 3 * d
            * a["moe_intermediate_size"],
            "head": d * V}


def train_flops_per_step(a, feeds):
    """`feeds`: {feed name: padded shape} as the step saw them."""
    B, T = feeds["ids"][:2]
    return 3 * 2 * B * T * sum(forward_macs_per_token(a, T).values())
