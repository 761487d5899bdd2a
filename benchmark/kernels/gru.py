"""What the fused-GRU recurrence needs, from its shapes: FLOPs and HBM bytes
of one forward call and one backward call over [B, T] positions of width H.

Forward, per position: h U_zr (H x 2H) and (r*h) U_c (H x H): 3 H^2
multiply-adds. Backward, per position: the two products' input gradients and
their weight gradients: 6 H^2 multiply-adds; recomputing the forward is not
counted. Bytes are the least traffic the recurrence needs: the pre-projected
input, the mask, the weights once, the hidden states out; backward reads
input, states and the incoming gradient and writes the input gradient and
the weight gradients. A stash of gates is the implementation's choice and is
not counted.
"""


def forward(B, T, H, itemsize):
    flops = 2 * B * T * 3 * H * H
    bytes_ = (B * T * 3 * H + B * T * H + 3 * H * H) * itemsize + B * T * 4
    return flops, bytes_


def backward(B, T, H, itemsize):
    flops = 2 * B * T * 6 * H * H
    bytes_ = (B * T * 3 * H + 2 * B * T * H + B * T * 3 * H + 2 * 3 * H * H) \
        * itemsize + B * T * 4
    return flops, bytes_


def least_seconds(flops, bytes_, peak):
    """(seconds, which bound) on a chip with the given peaks."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = bytes_ / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
