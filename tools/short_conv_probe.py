"""python3 tools/short_conv_probe.py [BT:BD ...]
(on the chip: chiprun -- python3 tools/short_conv_probe.py 512:512 256:512)

Times `short_conv_fwd` / `short_conv_bwd` (paddle_tpu/kernels/short_conv.py)
alone at the LFM2 cell's shape (2 rows x 8192 positions, hidden 2048, 3 taps,
bf16: the in-projection's [2, 8192, 6144] in, [2, 8192, 2048] out) beside the
XLA form they replace (`short_conv_xla`: the column slices, B * x~,
`causal_conv`, C *, under plain autodiff, jitted alone), and the distance
between the two forms' results and gradients. One JSON line a reading: ms a
launch, the bytes a pass needs (benchmark/kernels/short_conv.py) over that
time as a share of HBM's 819 GB/s. With arguments, every block (rows :
channels of a grid step) named is timed; without, the one `blocks` chooses.
PROBE_TOKENS sets the row for the CPU (interpret mode).
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.kernels import short_conv as sc  # noqa: E402

B, T, D, K = 2, int(os.environ.get("PROBE_TOKENS", 8192)), 2048, 3
INTERPRET = jax.default_backend() != "tpu"
HBM = 819e9


def timed(f, *xs, reps=20):
    """Seconds a call, the calls queued one behind the other and waited for
    once: the host's dispatch hides behind the chip."""
    jax.block_until_ready(f(*xs))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        outs = [f(*xs) for _ in range(reps)]
        jax.block_until_ready(outs)
        best = min(best, (time.perf_counter() - t) / reps)
    return best


def pair(f, bcx, w, dy):
    """(y, d bcx, dw): the result too, so that the forward pass is made."""
    y, vjp = jax.vjp(f, bcx, w)
    return (y,) + vjp(dy)


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / np.abs(b).max())


def main():
    dtype = jnp.bfloat16
    item = jnp.dtype(dtype).itemsize
    blocks = [tuple(int(n) for n in a.split(":")) for a in sys.argv[1:]]
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    bcx = jax.random.normal(ks[0], (B, T, 3 * D)).astype(dtype)
    dy = jax.random.normal(ks[1], (B, T, D)).astype(dtype)
    w = (jax.random.normal(ks[2], (D, K)) / K ** 0.5).astype(dtype)
    need_f = B * T * 4 * D * item
    need_b = B * T * 7 * D * item
    out = {}
    for form, block in [("xla", None)] + [("kernels", b) for b in (
            blocks or [sc.blocks(T, D)])]:
        if form == "xla":
            f = sc.short_conv_xla
        else:
            f = (lambda bt, bd: lambda bcx, w: sc.short_conv(
                bcx, w, bt, bd, INTERPRET))(*block)
        fwd = jax.jit(f)
        both = jax.jit(lambda bcx, w, dy, f=f: pair(f, bcx, w, dy))
        try:
            t_f = timed(fwd, bcx, w)
            t_b = timed(both, bcx, w, dy) - t_f
        except Exception as e:       # a block Mosaic refuses: say so
            print(json.dumps({"block": block, "refused": str(e)[-400:]}),
                  flush=True)
            continue
        out[form] = both(bcx, w, dy)
        line = {"form": form, "block": block, "fwd_ms": 1e3 * t_f,
                "bwd_ms": 1e3 * t_b, "fwd_share_of_hbm": need_f / HBM / t_f,
                "bwd_share_of_hbm": need_b / HBM / t_b,
                "platform": jax.default_backend()}
        if form == "kernels":
            line["rel_err_y_dbcx_dw"] = [rel(a, b) for a, b in zip(
                out["kernels"], out["xla"])]
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
