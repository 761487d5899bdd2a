"""python3 tools/moe_grouped_probe.py  (on the chip: chiprun -- python3 tools/moe_grouped_probe.py)

Times the routed part of moe_ffn alone, forward + backward, at the Qwen3-Next
cell's size (16,384 tokens x 2048, 16 of 512 experts held, top 10, bf16): the
tile loop of paddle_tpu/layers/moe.py against jax.lax.ragged_dot over the
rows sorted by expert, in buffers of several static sizes, at two loads of
the held experts. One JSON line a reading; docs/qwen3_next.md has the table
this printed on a v5e and what it taught. PROBE_TOKENS shrinks it for the CPU;
--forms keeps the forms whose name ends in one of the given words.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.layers import moe  # noqa: E402

N = int(os.environ.get("PROBE_TOKENS", 16384))
d, I, E, held, k, tile = 2048, 512, 512, 16, 10, 256
f32 = jnp.float32


def routed_tile(x, wg, wu, wd, idx, top, valid):
    row_w, row_tok, tile_expert, n_tiles, _ = moe.dispatch_plan(
        idx, top, valid, 0, held, tile)
    return moe.grouped_ffn(x, wg, wu, wd, row_w, row_tok, tile_expert,
                           n_tiles, tile)


def make_ragged(R, mask_lhs=True):
    """The same sum by three ragged_dot over the first R sorted pairs (held
    pairs first, expert by expert). ragged_dot leaves the rows past the last
    group undefined, in its result and in its lhs gradient: both are masked
    (mask_lhs=False shows what the second costs when forgotten)."""

    def routed(x, wg, wu, wd, idx, top, valid):
        here = (idx >= 0) & (idx < held) & valid[:, None]
        local = jnp.where(here, idx, held).reshape(-1)
        order = jnp.argsort(local, stable=True)[:R]
        sizes = jnp.bincount(local, length=held + 1)[:held].astype(jnp.int32)
        tok, live = order // k, local[order] < held
        w = jnp.where(live, top.reshape(-1)[order], 0)
        xs = x[tok]
        if mask_lhs:
            xs = jnp.where(live[:, None], xs, 0)
        a = jax.lax.ragged_dot(xs, wg, sizes, preferred_element_type=f32)
        b = jax.lax.ragged_dot(xs, wu, sizes, preferred_element_type=f32)
        h = (jax.nn.silu(a) * b).astype(x.dtype)
        y = jax.lax.ragged_dot(h, wd, sizes, preferred_element_type=f32)
        y = jnp.where(live[:, None], y * w[:, None], 0)
        return jnp.zeros(x.shape, f32).at[tok].add(y).astype(x.dtype)

    return routed


def bench(fn, args, r):
    def loss(x, wg, wu, wd, top, idx, valid):
        return jnp.sum(fn(x, wg, wu, wd, idx, top, valid).astype(f32) * r)

    g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))
    t0 = time.time()
    out = jax.block_until_ready(g(*args))
    first = time.time() - t0
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = jax.block_until_ready(g(*args))
        times.append(time.perf_counter() - t0)
    return out, sorted(times)[2] * 1e3, first


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--forms", default="", help="comma-separated words")
    ap.add_argument("--bonus", default="0,3",
                    help="what the held experts' logits are raised by")
    a = ap.parse_args()
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (N, d), jnp.bfloat16)
    wg, wu = ((jax.random.normal(key, (held, d, I), f32) / d ** 0.5
               ).astype(jnp.bfloat16) for key in ks[1:3])
    wd = (jax.random.normal(ks[3], (held, I, d), f32) / I ** 0.5
          ).astype(jnp.bfloat16)
    r = jax.random.normal(ks[4], (N, d), f32)
    valid = jnp.ones((N,), bool)
    forms = [("tile_loop", routed_tile)] + [
        (f"ragged_dot_rows_{R}", make_ragged(R))
        for R in (N * min(k, held), N * 4, N)] + [
        (f"ragged_dot_rows_{N}_lhs_unmasked", make_ragged(N, mask_lhs=False))]
    forms = [(n, fn) for n, fn in forms
             if n == "tile_loop" or any(n.endswith(w) for w in a.forms.split(","))]
    for bonus in (float(b) for b in a.bonus.split(",")):
        logits = jax.random.gumbel(ks[5], (N, E), f32) \
            + bonus * (jnp.arange(E) < held)
        top, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
        top = top / top.sum(-1, keepdims=True)
        pairs = int(jnp.sum(idx < held))
        print(json.dumps({"held_pairs": pairs, "share": pairs / (N * k)}),
              flush=True)
        args, ref = (x, wg, wu, wd, top, idx, valid), None
        for name, fn in forms:
            if name != "tile_loop" and int(name.split("_")[3]) < pairs:
                continue                      # the buffer does not hold them
            (val, grads), ms, first = bench(fn, args, r)
            rec = {"form": name, "ms_fwd_bwd": ms, "first_call_s": first,
                   "loss": float(val)}
            if ref is None:
                ref = grads
            else:
                rec.update({n: float(jnp.linalg.norm((g - g0).astype(f32))
                                     / jnp.linalg.norm(g0.astype(f32)))
                            for n, g, g0 in (("rel_dx", grads[0], ref[0]),
                                             ("rel_dwg", grads[1], ref[1]),
                                             ("rel_dwd", grads[3], ref[3]))})
            print(json.dumps(rec), flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"peak_bytes_in_use": stats.get("peak_bytes_in_use")}))


if __name__ == "__main__":
    main()
