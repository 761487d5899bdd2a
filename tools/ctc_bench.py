"""CRF Pallas vs lax.scan on silicon: parity + the T-sweep timing table
that `layers/crf_ctc._CRF_PALLAS_MIN_T` is derived from.

Run on the TPU (default platform):  python tools/ctc_bench.py
Produced the r5 figures quoted in layers/crf_ctc.py (not re-measured).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu.layers.crf_ctc as cc


def _sync(x):
    return float(jnp.asarray(x).sum())     # sync by scalar fetch


def _time(f, *args, iters=30):
    _sync(f(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
    _sync(out)
    return (time.perf_counter() - t0) / iters * 1e3


def bench_crf(B=32, L=64):
    print(f"# CRF logZ fwd+bwd ms (B={B} L={L})", flush=True)
    print("| T | scan ms | pallas ms | speedup | grad maxdiff |")
    print("|---|---------|-----------|---------|--------------|")
    for T in (128, 512, 2048):
        r = np.random.RandomState(0)
        emit = jnp.asarray(r.randn(B, T, L), jnp.float32)
        lens = r.randint(2, T + 1, B)
        mask = jnp.asarray((np.arange(T)[None] < lens[:, None])
                           .astype(np.float32))
        w = jnp.asarray(r.randn(L + 2, L) * 0.5, jnp.float32)

        f_scan = jax.jit(jax.grad(
            lambda e, w: cc.crf_logz_scan(e, mask, w).sum(),
            argnums=(0, 1)))
        f_pal = jax.jit(jax.grad(
            lambda e, w: cc.crf_logz_pallas(e, mask, w).sum(),
            argnums=(0, 1)))
        g1 = f_scan(emit, w)
        g2 = f_pal(emit, w)
        diff = max(float(jnp.abs(a - b).max()) for a, b in zip(g1, g2))
        ms_scan = _time(lambda e: f_scan(e, w)[0], emit)
        ms_pal = _time(lambda e: f_pal(e, w)[0], emit)
        print(f"| {T} | {ms_scan:.2f} | {ms_pal:.2f} | "
              f"{ms_scan / ms_pal:.2f}x | {diff:.2e} |", flush=True)


if __name__ == "__main__":
    import paddle_tpu

    paddle_tpu.compile_cache()
    bench_crf()
