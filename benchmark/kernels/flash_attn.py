"""What attention under the block-diffusion mask needs, from its shapes:
FLOPs and HBM bytes of one layer's forward pass and of its backward pass
over B rows of 2L positions ([noised ; clean]), blocks of b tokens, H query
heads sharing Hkv key/value heads of size D.

Counted from the MASK'S KEPT PAIRS and from no tile size. Position i keeps
key j where (i < L, j < L, blk(j) = blk(i)) or (i < L, j >= L,
blk(j) < blk(i)) or (i >= L, j >= L, blk(j) <= blk(i)), blk(i) = (i mod L)
// b. With block sizes s_0 .. s_n (all b but a shorter last one) that is
sum s_k^2 noised-noised pairs, sum_k s_k sum_{m<k} s_m noised-clean and
sum_k s_k sum_{m<=k} s_m clean-clean: L^2 + sum s_k^2 in all (L^2 + L b where
b divides L), of the 4 L^2 of the square.

Forward, per kept pair and query head: q . k (D multiply-adds) and p v (D).
Backward: dV += p do, dP = do . v, dK += ds q, dQ += ds k (4 D); the scores
computed again for it are the implementation's and are not counted. Bytes
are the least traffic: forward reads q, k, v once and writes o; backward
reads q, k, v, o, do and writes dq, dk, dv. The log-sum-exp a forward pass
keeps is the implementation's choice and is not counted.
"""


def kept_pairs(L, b):
    sizes = [b] * (L // b) + ([L % b] if L % b else [])
    return L * L + sum(s * s for s in sizes)


def forward(B, L, b, H, Hkv, D, itemsize):
    flops = 2 * B * H * kept_pairs(L, b) * 2 * D
    bytes_ = B * 2 * L * (2 * H * D + 2 * Hkv * D) * itemsize
    return flops, bytes_


def backward(B, L, b, H, Hkv, D, itemsize):
    flops = 2 * B * H * kept_pairs(L, b) * 4 * D
    bytes_ = B * 2 * L * (4 * H * D + 4 * Hkv * D) * itemsize
    return flops, bytes_


def least_seconds(flops, bytes_, peak):
    """(seconds, which bound) on a chip with the given peaks."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = bytes_ / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
