"""What causal grouped-query attention needs, from its shapes: FLOPs and HBM
bytes of one layer's forward pass and of its backward pass over B rows of L
positions, H query heads sharing Hkv key/value heads of D = d / H.

Counted from the MASK'S KEPT PAIRS and from no tile size: position i keeps
key j <= i, L (L + 1) / 2 pairs of the L^2 of the square. Per kept pair and
query head, forward: q . k and p v (D multiply-adds each, AS PUBLISHED: 64,
whatever an implementation pads the head to). Backward: dV += p do,
dP = do . v, dK += ds q, dQ += ds k (4 D); the scores computed again for it
are the implementation's and are not counted. Bytes are the least traffic:
forward reads q, k, v once and writes o; backward reads q, k, v, o, do and
writes dq, dk, dv. The log-sum-exp a forward pass keeps is the
implementation's choice and is not counted.
"""


def kept_pairs(L):
    return L * (L + 1) // 2


def forward(B, L, H, Hkv, d, itemsize):
    D = d // H
    flops = 2 * B * H * kept_pairs(L) * 2 * D
    bytes_ = B * L * (2 * H * D + 2 * Hkv * D) * itemsize
    return flops, bytes_


def backward(B, L, H, Hkv, d, itemsize):
    D = d // H
    flops = 2 * B * H * kept_pairs(L) * 4 * D
    bytes_ = B * L * (4 * H * D + 4 * Hkv * D) * itemsize
    return flops, bytes_


def least_seconds(flops, bytes_, peak):
    """(seconds, which bound) on a chip with the given peaks."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = bytes_ / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
