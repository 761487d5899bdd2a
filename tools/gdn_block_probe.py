"""python3 tools/gdn_block_probe.py [RxKc ...] [--other path/to/gdn.py]
(on the chip: chiprun -- python3 tools/gdn_block_probe.py 1x1 4x8 8x4)

Times the delta rule's three state-pass launches alone at the Qwen3-Next
cell's size (one row of the batch: 32 value heads x 64 chunks of 64 tokens,
widths 128, bf16): gdn_chunk_fwd without S0 (the primal call), with S0 (the
custom rule's forward) and gdn_chunk_bwd, each with the block that
paddle_tpu/kernels/gdn.py `state_pass_block` chooses and with every block
named on the command line, and checks that each block's results equal the
1 x 1 block's bit for bit. --other times another copy of the module (a
parent commit's, unpacked under .checkout/) beside it, whatever its `_fwd_call`
and `_bwd_call` take. One JSON line a reading; a step of the cell is 12
launches of each kind. PROBE_CHUNKS shrinks it for the CPU (interpret mode).
"""
import argparse
import importlib.util
import inspect
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.kernels import gdn  # noqa: E402

H, C, dk, dv = 32, 64, 128, 128
NC = int(os.environ.get("PROBE_CHUNKS", 64))
INTERPRET = jax.default_backend() != "tpu"


def inputs(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    T = NC * C
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (1, T, H, dk)))
    v = jax.random.normal(ks[2], (1, T, H, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (1, T, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, T, H)))
    bf = lambda x: x.astype(jnp.bfloat16)
    xs = jax.jit(lambda *a: gdn.chunk_prepare(*a, C))(bf(q), bf(k), bf(v), g, beta)
    dO = bf(jax.random.normal(ks[5], xs[1].shape))
    return xs, dO


def launches(module):
    """{name: jitted launch} of a copy of the module; S0 for the backward
    launch comes from its own forward."""
    takes = inspect.signature(module._fwd_call).parameters
    if "keep_s0" in takes:
        fwd = {"fwd": lambda *xs: module._fwd_call(*xs, INTERPRET, keep_s0=False),
               "fwd_s0": lambda *xs: module._fwd_call(*xs, INTERPRET, keep_s0=True)}
    else:
        fwd = {"fwd_s0": lambda *xs: module._fwd_call(*xs, INTERPRET)}
    out = {name: jax.jit(f) for name, f in fwd.items()}
    out["bwd"] = jax.jit(lambda *a: module._bwd_call(*a, INTERPRET))
    return out


def ms(f, args, n):
    jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / n


def read(module, xs, dO, n):
    fs = launches(module)
    S0 = fs["fwd_s0"](*xs)[1]
    args = {"fwd": xs, "fwd_s0": xs, "bwd": xs + (S0, dO)}
    results = {name: jax.tree.map(np.asarray, f(*args[name]))
               for name, f in fs.items()}
    return {name: ms(f, args[name], n) for name, f in fs.items()}, results


def same(a, b):
    return all(x.shape == y.shape and (x.view(np.uint8) == y.view(np.uint8)).all()
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("blocks", nargs="*", help="R x Kc pairs, as 4x8")
    ap.add_argument("--other", help="another copy of kernels/gdn.py to time")
    ap.add_argument("--repeat", type=int, default=3 if INTERPRET else 50)
    a = ap.parse_args()
    xs, dO = inputs()
    device = jax.devices()[0]
    say = lambda **kw: print(json.dumps(dict(
        kw, device=device.device_kind, rows=H, chunks=NC)), flush=True)

    chosen = gdn.state_pass_block(H, NC, C, dk, dv, 2)
    pairs = [(1, 1), chosen] + [tuple(int(n) for n in b.split("x"))
                                for b in a.blocks]
    choose, base = gdn.state_pass_block, None
    for pair in dict.fromkeys(pairs):
        gdn.state_pass_block = lambda *shape, pair=pair: pair
        try:
            took, results = read(gdn, xs, dO, a.repeat)
        except Exception as e:    # a block Mosaic refuses is a reading too
            say(block=pair, refused=str(e)[:300])
            continue
        base = base or results
        say(block=pair, chosen=pair == chosen, ms=took,
            ms_a_step=12 * sum(took.values()),
            equals_1x1={k: same(results[k], base[k]) for k in results},
            vmem_estimate=gdn._vmem_estimate_bytes(*pair, C, dk, dv, 2))
    gdn.state_pass_block = choose
    if a.other:
        spec = importlib.util.spec_from_file_location("other_gdn", a.other)
        other = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(other)
        took, results = read(other, xs, dO, a.repeat)
        say(other=a.other, ms=took,
            ms_a_step=12 * (2 * took["fwd_s0"] + took["bwd"]),
            equals_1x1={k: same(results[k], base[k]) for k in results},
            largest_gap={k: max(float(np.max(np.abs(
                x.astype(np.float32) - y.astype(np.float32))))
                for x, y in zip(jax.tree.leaves(results[k]),
                                jax.tree.leaves(base[k]))) for k in results})


if __name__ == "__main__":
    main()
