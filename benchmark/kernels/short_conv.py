"""What LFM2's gated short convolution needs between its two projections,
from its shapes: FLOPs and HBM bytes of one layer's forward pass and of its
backward pass over B rows of L positions, D channels and K taps.

The work is elementwise, so the pass is bound by HBM's bytes. Forward reads
[B ; C ; x~] (3 D a position) and writes y = C * conv(B * x~) (D); backward
reads [B ; C ; x~] and dy (4 D) and writes d[B ; C ; x~] (3 D) and the taps'
float32 gradient (D K). A position's predecessors a convolution needs again,
and anything an implementation keeps or computes twice, are not counted.
FLOPs per position and channel: forward B * x~, K multiply-adds, C * c
(2 K + 2); backward dc and dC, the K multiply-adds of dv and those of dw, dB
and dx~ (4 K + 4).
"""


def forward(B, L, D, K, itemsize):
    return 2 * B * L * D * (K + 1), B * L * 4 * D * itemsize


def backward(B, L, D, K, itemsize):
    return 4 * B * L * D * (K + 1), B * L * 7 * D * itemsize + D * K * 4


def least_seconds(flops, bytes_, peak):
    """(seconds, which bound) on a chip with the given peaks."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = bytes_ / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
