"""Model FLOPs of one training step of the SDAR share under the
block-diffusion objective, from the configuration's shapes.

Counted: the multiply-adds of every matrix product, 2 FLOPs each, forward
once and backward twice: 3 x forward, nothing recomputed. A row of L clean
tokens is computed at 2L positions ([noised ; clean]), because the objective
says so: the count is of positions, not of clean tokens. Per row:

  attention   the q, k, v and o projections at all 2L positions, and the
              scores and the weighted sum of the (query, key) pairs THE MASK
              KEEPS: L^2 + the sum of the squared block sizes (L^2 + L b
              where b divides L) of the 4 L^2 of the square, never the
              square and never the tiles an implementation rounds them up to.
  MoE         the router over ALL experts and the routed experts a position
              reaches ON THIS CHIP: of its k choices the share held / E in
              expectation (ids and weights are random, so the expectation is
              the count; the cut's 8 x 16 / 128 is one held pair a position),
              three d x I products each. At all 2L positions but in the last
              block, whose MoE is counted on the noised half: its clean half
              feeds no loss and the program does not run it. No shared expert.
  head        the [d, V] projection on the noised half, L positions.

The last block's attention is counted whole, as the program computes it
(its clean queries feed no loss either; what they cost is the same pairs).
Not counted: the embedding look-up, norms, rotary, softmax, top-k, dispatch,
the noising, the optimizer.
"""


from benchmark import correct

kept_pairs = correct.load_module("kernels/flash_attn.py").kept_pairs


def forward_macs_per_row(a, L):
    """{part: multiply-adds a row of L clean tokens, forward}."""
    d, V, n = a["hidden_size"], a["vocab_size"], a["num_hidden_layers"]
    H, Hkv, D = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    E, k, held = a["num_experts"], a["num_experts_per_tok"], a["experts_held"]
    I = a["moe_intermediate_size"]
    proj = 2 * d * H * D + 2 * d * Hkv * D
    moe = d * E + k * held / E * 3 * d * I
    return {"attention_projections": n * 2 * L * proj,
            "attention_scores": n * H * kept_pairs(L, a["block_length"]) * 2 * D,
            "moe": ((n - 1) * 2 * L + L) * moe,
            "head": L * d * V}


def train_flops_per_step(a, feeds):
    """`feeds`: {feed name: padded shape} as the step saw them."""
    B, L = feeds["ids"][:2]
    return 3 * 2 * B * sum(forward_macs_per_row(a, L).values())
