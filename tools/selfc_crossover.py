"""selective_fc dense-mask vs gather end-to-end crossover harness.

The r5 harness measured grad-wrt-params of the LAYER; this one measures
the full jitted TRAIN STEP (make_train_step: forward, backward,
optimizer apply) — the number that matters — for three configurations:

  dense   : dense matmul + mask, dense dW           (the r5 winner)
  gather  : row gather + scatter, dense dW          (the r5 loser)
  sparse  : row gather + scatter, SPARSE (rows, values) dW through the
            optimizer (ISSUE r6 tentpole — no [C, D] buffer anywhere)

Run:  python tools/selfc_crossover.py [--iters N] [--d DIM] [--points 2d|3d|both]
Prints one markdown table row per vocab size C.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import data_type, layer, optimizer
from paddle_tpu.attr import ParamAttr
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.topology import Topology
from paddle_tpu.trainer.trainer import make_train_step


def build(C, D, K, seq, sparse, gather):
    dt = (data_type.dense_vector_sequence if seq else data_type.dense_vector)
    x = layer.data(name="x", type=dt(D))
    s = layer.data(name="sel", type=dt(K))
    lab = layer.data(name="lab", type=dt(C))
    out = layer.Layer(type="selective_fc", inputs=[x, s], name="sf", size=C,
                      param_attrs=[ParamAttr(sparse_update=sparse)],
                      selection_pass_generation=True,
                      gather_min_c=1 if gather else 10**12)
    cost = layer.square_error_cost(input=out, label=lab, name="cost")
    return Topology(cost), cost


def measure(C, D, K, B, T=None, mode="dense", iters=5):
    seq = T is not None
    sparse = mode == "sparse"
    gather = mode in ("gather", "sparse")
    topo, cost = build(C, D, K, seq, sparse, gather)
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = optimizer.SGD(learning_rate=0.1)
    st = opt.init(params)
    step = make_train_step(topo.loss_fn(cost), opt, topo.static_map(),
                           donate=False)
    r = np.random.RandomState(0)
    lead = (B, T) if seq else (B,)
    mask = jnp.ones((B, T), jnp.float32) if seq else None
    feeds = {
        "x": Arg(jnp.asarray(r.randn(*lead, D), jnp.float32), mask),
        "sel": Arg(jnp.asarray(r.randint(0, C, (*lead, K)), jnp.int32), mask),
        "lab": Arg(jnp.asarray(r.randn(*lead, C), jnp.float32), mask),
    }
    rng = jax.random.PRNGKey(1)
    npar, nst, c, _ = step(params, st, rng, feeds)     # compile
    float(c)
    t0 = time.perf_counter()
    for i in range(iters):
        npar, nst, c, _ = step(npar, nst, jax.random.fold_in(rng, i), feeds)
    float(c)
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--d", type=int, default=512)
    ap.add_argument("--b", type=int, default=64)
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--points", default="both", choices=["2d", "3d", "both"])
    ap.add_argument("--cs", default="65536,131072,262144,524288,1048576")
    args = ap.parse_args()
    cs = [int(c) for c in args.cs.split(",")]
    dev = jax.devices()[0]
    print(f"platform: {dev.platform} ({getattr(dev, 'device_kind', '?')}), "
          f"D={args.d} K={args.k}")
    if args.points in ("2d", "both"):
        print(f"\n2D B={args.b}:\n| C | dense ms | gather(dense dW) ms | "
              "gather(sparse dW) ms |\n|---|---|---|---|")
        for C in cs:
            row = [f"{measure(C, args.d, args.k, args.b, None, m, args.iters):.2f}"
                   for m in ("dense", "gather", "sparse")]
            print(f"| {C} | " + " | ".join(row) + " |", flush=True)
    if args.points in ("3d", "both"):
        B, T = 20, 20
        print(f"\n3D B={B} T={T} (B*T={B*T}):\n| C | dense ms | "
              "gather(dense dW) ms | gather(sparse dW) ms |\n|---|---|---|---|")
        for C in cs:
            row = [f"{measure(C, args.d, args.k, B, T, m, args.iters):.2f}"
                   for m in ("dense", "gather", "sparse")]
            print(f"| {C} | " + " | ".join(row) + " |", flush=True)


if __name__ == "__main__":
    import paddle_tpu

    paddle_tpu.compile_cache()
    main()
