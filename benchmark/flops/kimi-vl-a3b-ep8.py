"""Model FLOPs of one training step of the Kimi-VL language model's share,
from the configuration's shapes.

Counted: the multiply-adds of every matrix product, 2 FLOPs each, forward
once and backward twice: 3 x forward, nothing recomputed. All B x T positions
of the padded batch count, as the step computes them. Per token:

  attention   every layer: x Wq (H heads of Dn + Dr), x Wkva (the latent r and
              the shared rotary key Dr), the decompression c Wkvb (H heads of
              Dn + Dv), o Wo, and the causal scores and weighted sum: each
              query against the (T + 1) / 2 keys at or before it on average,
              Dn + Dr multiply-adds a score AS PUBLISHED (192; lanes an
              implementation pads a head with are not counted) and Dv a
              weighted value.
  dense MLP   the first `first_k_dense_replace` layers: three d x I_dense
              products.
  MoE         the other layers: the router over ALL experts, the shared
              experts (one MLP of n_shared x I, no gate), and the routed
              experts a token reaches ON THIS CHIP: of its k choices the share
              held / E in expectation (the selection bias keeps the loads
              level, so the expectation is the count), three d x I products
              each. Not the weights held.
  head        the [d, V] projection.

Not counted: the embedding look-up, norms, rotary, sigmoid, top-k, dispatch,
the bias rule, the optimizer.
"""


def forward_macs_per_token(a, T):
    """{part: multiply-adds a token, forward} at row length T."""
    d, V, L = a["hidden_size"], a["vocab_size"], a["num_hidden_layers"]
    H, r = a["num_attention_heads"], a["kv_lora_rank"]
    Dn, Dr, Dv = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"]
    E, k, held = a["n_routed_experts"], a["num_experts_per_tok"], a["experts_held"]
    I, Is = a["moe_intermediate_size"], a["n_shared_experts"] * a["moe_intermediate_size"]
    n_dense = min(a["first_k_dense_replace"], L)
    proj = d * H * (Dn + Dr) + d * (r + Dr) + r * H * (Dn + Dv) + H * Dv * d
    scores = H * (Dn + Dr + Dv) * (T + 1) / 2
    return {"attention_projections": L * proj, "attention_scores": L * scores,
            "dense_mlp": n_dense * 3 * d * a["intermediate_size"],
            "moe_router_shared": (L - n_dense) * (d * E + 3 * d * Is),
            "moe_routed": (L - n_dense) * k * held / E * 3 * d * I,
            "head": d * V}


def train_flops_per_step(a, feeds):
    """`feeds`: {feed name: padded shape} as the step saw them."""
    B, T = feeds["ids"][:2]
    return 3 * 2 * B * T * sum(forward_macs_per_token(a, T).values())
