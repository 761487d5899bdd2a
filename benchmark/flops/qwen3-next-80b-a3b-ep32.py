"""Model FLOPs of one training step of the Qwen3-Next share, from the
configuration's shapes.

Counted: the multiply-adds of every matrix product, 2 FLOPs each, forward
once and backward twice: 3 x forward, nothing recomputed. All B x T positions
of the padded batch count, as the step computes them. Per token:

  DeltaNet layer   x W_qkvz, x W_ba, the depthwise convolution (K taps a
                   channel), o W_out, and the delta rule AS ITS RECURRENCE
                   DEFINES IT: per value head three dk x dv matrix-vector
                   products a token (read S^T k, write k delta^T, query S^T q).
                   What the chunked form spends beyond that (the products
                   inside a chunk, the triangular inverse) is the
                   implementation's and is not counted.
  attention layer  the q (with its gate), k, v and o projections, and the
                   causal scores and weighted sum: each query against the
                   (T + 1) / 2 keys at or before it on average.
  MoE              the router over ALL experts, the shared expert and its
                   gate, and the routed experts a token reaches ON THIS CHIP:
                   of its k choices the share held / E in expectation (ids and
                   weights are random, so the expectation is the count), three
                   d x I products each. Not the weights held.
  head             the [d, V] projection.

Not counted: the embedding look-up, norms, rotary, gates, softmax, top-k,
dispatch, the optimizer. (The arithmetic of paddle_tpu/flops.py
`layer_fwd_flops` for these layer types, written out per product and kept
here so that no later PR can move it.)
"""


def forward_macs_per_token(a, T):
    """{part: multiply-adds a token, forward} at row length T."""
    d, V = a["hidden_size"], a["vocab_size"]
    H, Hkv, D = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    Hk, Hv = a["linear_num_key_heads"], a["linear_num_value_heads"]
    dk, dv = a["linear_key_head_dim"], a["linear_value_head_dim"]
    K = a["linear_conv_kernel_dim"]
    E, k, held = a["num_experts"], a["num_experts_per_tok"], a["experts_held"]
    I, Is = a["moe_intermediate_size"], a["shared_expert_intermediate_size"]
    L = a["num_hidden_layers"]
    n_attn = sum(1 for l in range(L)
                 if (l + 1) % a["full_attention_interval"] == 0)
    conv_channels = 2 * Hk * dk + Hv * dv
    gdn = (d * (2 * Hk * dk + 2 * Hv * dv) + d * 2 * Hv + conv_channels * K
           + Hv * dv * d)
    rule = Hv * 3 * dk * dv
    attn = d * H * 2 * D + 2 * d * Hkv * D + H * D * d
    scores = 2 * H * D * (T + 1) / 2
    moe_dense = d * E + d + 3 * d * Is
    moe_routed = k * held / E * 3 * d * I
    return {"gdn_projections": (L - n_attn) * gdn, "gdn_rule": (L - n_attn) * rule,
            "attention_projections": n_attn * attn,
            "attention_scores": n_attn * scores,
            "moe_router_shared": L * moe_dense, "moe_routed": L * moe_routed,
            "head": d * V}


def train_flops_per_step(a, feeds):
    """`feeds`: {feed name: padded shape} as the step saw them."""
    B, T = feeds["ids"][:2]
    return 3 * 2 * B * T * sum(forward_macs_per_token(a, T).values())
