"""What causal multi-head latent attention needs in its training
(decompressed) form, from its shapes: FLOPs and HBM bytes of one layer's
forward pass and of its backward pass over B rows of L positions, H heads
whose query and key are Dn + Dr wide (a part without positions beside a
rotary part) and whose value is Dv wide.

Counted from the MASK'S KEPT PAIRS and from no tile size: position i keeps
key j <= i, L (L + 1) / 2 pairs of the L^2 of the square. Per kept pair and
head, forward: q . k (Dn + Dr multiply-adds, as published: 192, whatever an
implementation pads the head to) and p v (Dv). Backward: dV += p do and
dP = do . v (Dv each), dK += ds q and dQ += ds k (Dn + Dr each); the scores
computed again for it are the implementation's and are not counted. Bytes are
the least traffic with the keys decompressed per head: forward reads q, k, v
once and writes o; backward reads q, k, v, o, do and writes dq, dk, dv. The
log-sum-exp a forward pass keeps is the implementation's choice and is not
counted. The projections to and from the latent are matrix products outside
these kernels and are priced with the step (flops/kimi-vl-a3b-ep8.py).
"""


def kept_pairs(L):
    return L * (L + 1) // 2


def forward(B, L, H, Dn, Dr, Dv, itemsize):
    flops = 2 * B * H * kept_pairs(L) * (Dn + Dr + Dv)
    bytes_ = B * L * H * 2 * (Dn + Dr + Dv) * itemsize
    return flops, bytes_


def backward(B, L, H, Dn, Dr, Dv, itemsize):
    flops = 2 * B * H * kept_pairs(L) * 2 * (Dn + Dr + Dv)
    bytes_ = B * L * H * 4 * (Dn + Dr + Dv) * itemsize
    return flops, bytes_


def least_seconds(flops, bytes_, peak):
    """(seconds, which bound) on a chip with the given peaks."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = bytes_ / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
