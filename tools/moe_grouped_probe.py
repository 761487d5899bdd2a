"""python3 tools/moe_grouped_probe.py  (on the chip: chiprun -- python3 tools/moe_grouped_probe.py)

Times `grouped_ffn` alone, forward + backward, at the three MoE cells' shapes
(tokens a step, expert width, experts held of all, top k; bf16, hidden 2048,
tiles of 256 rows): the tile loop of paddle_tpu/layers/moe.py against the
chunked Mosaic kernels of paddle_tpu/kernels/moe_grouped.py, over one layer's
plan from uniform router logits (the held experts' logits raised by --bonus
for the Qwen3-Next cell's late, drifted load). One JSON line a reading: ms a
layer, us a held pair, the products' share of the MXU's pace, the bytes the
form moves beside the least bytes (every expert in use read once a pass and
its gradient written once), and the kernels' gradients beside the tile
loop's. `gathers_only` is the kernels' chunk loop with the launches taken
out: what the rows' gathers, which stay in XLA, cost. PROBE_TOKENS shrinks it for
the CPU (the kernels then run in interpret mode); --chunk / --buffers try
another plan than `chunk_plan`'s.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.kernels import moe_grouped  # noqa: E402
from paddle_tpu.layers import moe  # noqa: E402

d, tile = 2048, 256
f32 = jnp.float32
PEAK_FLOPS = 197e12
# cell: (positions a step, expert width, experts, held, top k, bonuses)
CELLS = {
    "sdar-ep8-train-s8192": (32768, 768, 128, 16, 8, (0.0,)),
    "kimivl-ep8-train-s8192": (16384, 1408, 64, 8, 6, (0.0,)),
    "qwen3next-ep32-train-s4096": (16384, 512, 512, 16, 10, (0.0, 3.0)),
}


def gathers_only(x, wg, wu, wd, row_w, row_tok, tile_expert, n_tiles, chunk):
    """The chunk loops of `moe_grouped.grouped_ffn`, forward and backward,
    with the launches taken out: what is left in XLA, the rows' gathers."""
    N = x.shape[0]
    _, _, n_chunks, of = moe_grouped._chunks(row_w, row_tok, tile_expert,
                                             n_tiles, tile, chunk, N)

    def fwd(c, s):
        tok = of(c)[0]
        xt = jnp.take(x, tok, axis=0, mode="clip")
        return s + jnp.sum(xt[:, :128].astype(f32))

    def bwd(c, s):
        tok = of(c)[0]
        xt = jnp.take(x, tok, axis=0, mode="clip")
        dyt = jnp.take(x, tok, axis=0, mode="fill", fill_value=0)
        return s + jnp.sum((xt[:, :128] + dyt[:, :128]).astype(f32))

    zero = jnp.zeros((), f32)
    return (jax.lax.fori_loop(0, n_chunks, fwd, zero)
            + jax.lax.fori_loop(0, n_chunks, bwd, zero))


def bench(fn, args):
    g = jax.jit(fn)
    t0 = time.time()
    out = jax.block_until_ready(g(*args))
    first = time.time() - t0
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = jax.block_until_ready(g(*args))
        times.append(time.perf_counter() - t0)
    return out, sorted(times)[2] * 1e3, first


def walk(tile_expert, n_tiles, chunk):
    """(experts in use, (chunk, expert) runs, runs that continue a chunk's
    last) by a NumPy walk of the tiles in use."""
    te = np.asarray(tile_expert)[:n_tiles]
    start = np.ones(n_tiles, bool)
    start[1:] = (te[1:] != te[:-1]) | (np.arange(1, n_tiles) % chunk == 0)
    carried = int(np.sum((np.arange(1, n_tiles) % chunk == 0)
                         & (te[1:] == te[:-1])))
    return len(set(te.tolist())), int(start.sum()), carried


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--forms", default="tile_loop,kernels,gathers_only")
    ap.add_argument("--chunk", type=int, default=0)
    ap.add_argument("--buffers", type=int, default=0)
    a = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    for cell in a.cells.split(","):
        N, I, E, held, k, bonuses = CELLS[cell]
        N = int(os.environ.get("PROBE_TOKENS", N))
        plan, why = moe_grouped.chunk_plan(d, I, tile, jnp.bfloat16)
        if plan is None:
            print(json.dumps({"cell": cell, "kernels": why}), flush=True)
        chunk, buffers, slices = plan or (1, 2, 1)
        chunk, buffers = a.chunk or chunk, a.buffers or buffers
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        x = jax.random.normal(ks[0], (N, d), jnp.bfloat16)
        wg, wu = ((jax.random.normal(key, (held, d, I), f32) / d ** 0.5
                   ).astype(jnp.bfloat16) for key in ks[1:3])
        wd = (jax.random.normal(ks[3], (held, I, d), f32) / I ** 0.5
              ).astype(jnp.bfloat16)
        r = jax.random.normal(ks[4], (N, d), f32)
        forms = {
            "tile_loop": lambda *p: moe.grouped_ffn(*p, tile),
            "kernels": lambda *p: moe_grouped.grouped_ffn(
                *p, tile, chunk, buffers, not on_tpu, slices),
        }
        for bonus in bonuses:
            logits = jax.random.gumbel(ks[5], (N, E), f32) \
                + bonus * (jnp.arange(E) < held)
            top, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
            top = top / top.sum(-1, keepdims=True)
            row_w, row_tok, tile_expert, n_tiles, stats = jax.jit(
                lambda idx, top: moe.dispatch_plan(
                    idx, top, jnp.ones((N,), bool), 0, held, tile))(idx, top)
            n_tiles, pairs = int(n_tiles), int(stats[0])
            used, runs, carried = walk(tile_expert, n_tiles, chunk)
            w_bytes, g_bytes = 3 * d * I * 2, 3 * d * I * 4
            # a tile's rows: x, dy gathered and read, y and dx written, read
            # and added into their float32 sums
            row_bytes = n_tiles * tile * d * (3 * 2 + 4 * 4 + 6 * 2 + 4 * 4)
            least = used * (2 * w_bytes + g_bytes) + row_bytes
            moved = {
                "tile_loop": n_tiles * (3 * w_bytes + 2 * g_bytes) + row_bytes,
                "kernels": runs * (2 * w_bytes + g_bytes) + carried * g_bytes
                + row_bytes}
            flops = n_tiles * tile * 24 * d * I
            print(json.dumps({
                "cell": cell, "bonus": bonus, "held_pairs": pairs,
                "share": pairs / (N * k), "tiles": n_tiles,
                "padding": n_tiles * tile / max(pairs, 1) - 1,
                "experts_in_use": used, "chunk": chunk, "buffers": buffers,
                "fetches": runs, "tiles_over_fetches": n_tiles / runs,
                "least_MB": least / 1e6}), flush=True)
            args, ref = (x, wg, wu, wd, row_w), None
            for name in a.forms.split(","):
                if name == "gathers_only":
                    _, ms, first = bench(
                        lambda *p: gathers_only(*p, row_tok, tile_expert,
                                             n_tiles, chunk), args)
                    print(json.dumps({"form": name, "ms_fwd_bwd": ms,
                                      "us_a_tile": ms * 1e3 / n_tiles,
                                      "first_call_s": first}), flush=True)
                    continue

                def loss(*p, fn=forms[name]):
                    return jnp.sum(fn(*p, row_tok, tile_expert, n_tiles)
                                   .astype(f32) * r)

                (val, grads), ms, first = bench(
                    jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)), args)
                rec = {"form": name, "ms_fwd_bwd": ms,
                       "us_a_held_pair": ms * 1e3 / pairs,
                       "us_a_tile": ms * 1e3 / n_tiles,
                       "mxu_pace_share": flops / PEAK_FLOPS / (ms * 1e-3),
                       "moved_MB": moved[name] / 1e6,
                       "moved_over_least": moved[name] / least,
                       "first_call_s": first, "loss": float(val)}
                if ref is None:
                    ref = grads
                else:
                    rec.update({
                        n: float(jnp.linalg.norm((g - g0).astype(f32))
                                 / jnp.linalg.norm(g0.astype(f32)))
                        for n, g, g0 in zip(("rel_dx", "rel_dwg", "rel_dwu",
                                             "rel_dwd", "rel_drow"),
                                            grads, ref)})
                print(json.dumps(rec), flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"peak_bytes_in_use": stats.get("peak_bytes_in_use")}))


if __name__ == "__main__":
    main()
