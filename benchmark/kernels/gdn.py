"""What the state pass of the chunked gated delta rule needs, from its
shapes: FLOPs and HBM bytes of one forward call and one backward call over
BH (batch x value heads) rows of NC chunks of C tokens, key width dk, value
width dv.

Forward, per chunk: W S (C x dk x dv), Q~ S (C x dk x dv), Aqk Vn
(C x C x dv), K~^T Vn (dk x C x dv) multiply-adds. Backward, per chunk: Vn
again (W S), then Aqk^T dO, K~ dS', dO Vn^T, dO S^T, Vn dS'^T, dVn S^T,
Q~^T dO, W^T dVn: five products of C x dk x dv, three of C x C x dv, and the
one recomputed is not counted. Bytes are the least traffic the pass needs:
forward reads W, Q~, K~ (C x dk), U (C x dv), Aqk (C x C) and the chunk's
decay and writes O (C x dv); backward reads those and dO, and writes the
gradient of each input. The states a forward call keeps for the backward
pass are the implementation's choice and are not counted.
"""


def forward(BH, NC, C, dk, dv, itemsize):
    flops = 2 * BH * NC * (3 * C * dk * dv + C * C * dv)
    bytes_ = BH * NC * ((3 * C * dk + 2 * C * dv + C * C) * itemsize + 4)
    return flops, bytes_


def backward(BH, NC, C, dk, dv, itemsize):
    flops = 2 * BH * NC * (5 * C * dk * dv + 3 * C * C * dv)
    bytes_ = BH * NC * ((2 * (3 * C * dk + C * dv + C * C) + 2 * C * dv)
                        * itemsize + 8)
    return flops, bytes_


def least_seconds(flops, bytes_, peak):
    """(seconds, which bound) on a chip with the given peaks."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = bytes_ / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
