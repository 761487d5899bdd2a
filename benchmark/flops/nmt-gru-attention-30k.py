"""Model FLOPs of one NMT training step, from the configuration's shapes.

Counted: the multiply-adds of every matrix product, 2 FLOPs each, forward
once and backward twice: 3 x forward. Padded positions count as the model
computes them: the step multiplies all B x T positions of the padded batch, so
T is the padded length the step saw, not the mean sentence length. Not
counted: embedding look-ups, gate and attention elementwise math, softmax,
the optimizer, anything recomputed. (The arithmetic of paddle_tpu/flops.py
`layer_fwd_flops`, written out per product and kept here so that no later PR
can move it; the attention's weighted sum, which that file prices at zero, is
counted here as the batched matrix-vector product it is.)
"""


def forward_macs(a, B, Ts, Tt):
    D, E, H, V = (a["word_vector_dim"], a["encoder_size"], a["decoder_size"],
                  a["trg_dict_dim"])
    enc = 2 * B * Ts * (D * 3 * E + 3 * E * E)        # two directions
    enc_proj = B * Ts * 2 * E * H
    boot = B * E * H
    emb_proj = B * Tt * D * 3 * H
    per_tick = B * Ts * H + B * Ts * 2 * E + B * 2 * E * 3 * H + B * 3 * H * H
    out = B * Tt * H * V
    return enc + enc_proj + boot + emb_proj + Tt * per_tick + out


def train_flops_per_step(a, feeds):
    """`feeds`: {feed name: padded shape} as the step saw them."""
    (B, Ts), (_, Tt) = feeds["src"], feeds["trg"]
    return 3 * 2 * forward_macs(a, B, Ts, Tt)
