"""python3 tools/head_norm_rotary_probe.py [BLOCK ...]
(on the chip: chiprun -- python3 tools/head_norm_rotary_probe.py 128 256 512)

Times `head_norm_rotary_fwd` / `head_norm_rotary_bwd`
(paddle_tpu/kernels/head_norm_rotary.py) alone at the SDAR cell's shapes (one
row of 2 x 8192 positions, bf16: q of 32 heads of 128, k of 4) beside the
lines they replace (`_head_norm` + `rotary_at` on `[1, T, heads, 128]` under
plain autodiff, jitted alone, with the reshapes from and to `[T, heads *
128]`), and the distance between the two forms' results and gradients. One
JSON line a reading: ms a launch, the bytes the pass needs (docs/sdar.md) over
that time as a share of HBM's 819 GB/s. With arguments, every block (the
positions of a grid step) named is timed; without, the one `block_rows`
chooses. PROBE_TOKENS sets the row for the CPU (interpret mode).
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

from paddle_tpu.kernels import head_norm_rotary as hnr  # noqa: E402
from paddle_tpu.layers.attention import _head_norm, rotary_at  # noqa: E402

T = 2 * int(os.environ.get("PROBE_TOKENS", 8192))
D, THETA, EPS = 128, 1e6, 1e-6
INTERPRET = jax.default_backend() != "tpu"
HBM = 819e9


def timed(f, *xs, reps=20):
    """Seconds a call, the calls queued one behind the other and waited for
    once: the host's dispatch (~0.4 ms a call here) hides behind the chip."""
    jax.block_until_ready(f(*xs))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        outs = [f(*xs) for _ in range(reps)]
        jax.block_until_ready(outs)
        best = min(best, (time.perf_counter() - t) / reps)
    return best


def forms(heads, scale, block, dtype):
    """The two forms by name: x [T, heads * D], w -> [1, T, heads * D]."""
    pos = np.arange(T) % (T // 2)
    cos, sin = hnr.tables(pos, THETA, D, dtype)

    def xla(x, w):
        y = rotary_at(_head_norm(x.reshape(1, T, heads, D), w, EPS, scale),
                      pos, THETA)
        return y.reshape(1, T, heads * D)

    def kernels(x, w):
        return hnr.head_norm_rotary(x[None], w, cos, sin, EPS, scale, block,
                                    INTERPRET)

    return {"xla": xla, "kernels": kernels}


def pair(f, x, w, dy):
    """(y, dx, dw): the result too, so that the forward pass is made."""
    y, vjp = jax.vjp(f, x, w)
    return (y,) + vjp(dy)


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / np.abs(b).max())


def main():
    dtype = jnp.bfloat16
    item = jnp.dtype(dtype).itemsize
    blocks = [int(a) for a in sys.argv[1:]]
    for who, heads, scale in (("q", 32, D ** -0.5), ("k", 4, 1.0)):
        width = heads * D
        ks = jax.random.split(jax.random.PRNGKey(heads), 3)
        x = jax.random.normal(ks[0], (T, width)).astype(dtype)
        dy = jax.random.normal(ks[1], (1, T, width)).astype(dtype)
        w = (3 + 0.1 * jax.random.normal(ks[2], (D,))).astype(dtype)
        # x and y (forward), x, dy and dx (backward), the two tables
        need_f = (2 * T * width + 2 * T * D) * item
        need_b = (3 * T * width + 2 * T * D) * item
        out = {}
        for form, block in [("xla", None)] + [("kernels", b) for b in (
                blocks or [hnr.block_rows(T, width, dtype)])]:
            f = forms(heads, scale, block, dtype)[form]
            fwd = jax.jit(f)
            both = jax.jit(lambda x, w, dy, f=f: pair(f, x, w, dy))
            try:
                t_f = timed(fwd, x, w)
                t_b = timed(both, x, w, dy) - t_f
            except Exception as e:       # a block Mosaic refuses: say so
                print(json.dumps({"who": who, "block": block,
                                  "refused": str(e)[-400:]}), flush=True)
                continue
            out[form] = both(x, w, dy)
            line = {"who": who, "heads": heads, "form": form, "block": block,
                    "fwd_ms": 1e3 * t_f, "bwd_ms": 1e3 * t_b,
                    "fwd_share_of_hbm": need_f / HBM / t_f,
                    "bwd_share_of_hbm": need_b / HBM / t_b,
                    "platform": jax.default_backend()}
            if form == "kernels":
                line["rel_err_y_dx_dw"] = [rel(a, b) for a, b in zip(
                    out["kernels"], out["xla"])]
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
