"""python3 tools/flash_attn_probe.py [TILE ...]
(on the chip: chiprun -- python3 tools/flash_attn_probe.py 256 512)

Times the block-diffusion attention kernels alone at the SDAR cell's size
(one layer: 2 rows x 2 x 8192 positions, 32 : 4 heads of 128, block 4,
bf16): flash_attn_fwd and the pair forward + flash_attn_bwd, with the tile
paddle_tpu/kernels/flash_attn.py `tile_sizes` chooses and with every tile
named on the command line, and checks the kernels' result and gradients
against the same tiles in XLA at a row short enough for them
(PROBE_CHECK_TOKENS, 1024). One JSON line a reading, with two shares at the
bf16 peak: of the benchmark's need (benchmark/kernels/flash_attn.py, priced
from the pairs the mask keeps) and of the MXU's pace for what the launch
really multiplies (its products a kept tile and head, read from the traced
kernel body, times the kept tiles' FLOPs). A launch near the MXU's pace and
far from the need makes too many products; one far from both is held by
something else (the softmax's passes over the scores). PROBE_TOKENS and
PROBE_ROWS set the timed row and the rows for the CPU (interpret mode) or a
longer row.
"""
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.kernels import flash_attn as fa  # noqa: E402

Hkv, G, D, BLOCK = 4, 8, 128, 4
B = int(os.environ.get("PROBE_ROWS", 2))
L = int(os.environ.get("PROBE_TOKENS", 8192))
L_CHECK = int(os.environ.get("PROBE_CHECK_TOKENS", 1024))
INTERPRET = jax.default_backend() != "tpu"
PEAK = 197e12


def need():
    spec = importlib.util.spec_from_file_location(
        "need", os.path.join(ROOT, "benchmark", "kernels", "flash_attn.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def inputs(L, dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    T = 2 * L
    q = jax.random.normal(ks[0], (B, T, Hkv * G * D)) * D ** -0.5
    k = jax.random.normal(ks[1], (B, T, Hkv * D))
    v = jax.random.normal(ks[2], (B, T, Hkv * D))
    do = jax.random.normal(ks[3], (B, T, Hkv * G * D))
    return tuple(x.astype(dtype) for x in (q, k, v, do))


def timed(f, *xs, reps=5):
    jax.block_until_ready(f(*xs))
    best = float("inf")
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(f(*xs))
        best = min(best, time.perf_counter() - t)
    return best


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in v if isinstance(v, (tuple, list)) else (v,):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def kernel_products(jaxpr, name, inside=False):
    """`dot_general` equations inside the bodies of every `pallas_call`
    named `name` of a jaxpr, through calls and branches."""
    n = 0
    for e in jaxpr.eqns:
        n += inside and e.primitive.name == "dot_general"
        here = inside or (e.primitive.name == "pallas_call"
                          and e.params.get("name") == name)
        n += sum(kernel_products(j, name, here) for j in _sub_jaxprs(e))
    return n


def products_a_tile(tile):
    """(forward, backward) `dot_general` a kept tile and grouped head, counted
    in the kernels' traced bodies (a body holds a copy for whole tiles and
    one for partial ones; a step runs one of them)."""
    q, k, v, do = (jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in inputs(L_CHECK))
    rule = ("block_diffusion", L_CHECK, BLOCK)
    jaxpr = jax.make_jaxpr(lambda q, k, v, do: jax.vjp(
        lambda *a: fa.flash_attention(*a, rule, Hkv, tile, tile, INTERPRET),
        q, k, v)[1](do))(q, k, v, do)
    return tuple(kernel_products(jaxpr.jaxpr, name) // (2 * G)
                 for name in ("flash_attn_fwd", "flash_attn_bwd"))


def check(tile):
    rule = ("block_diffusion", L_CHECK, BLOCK)
    q, k, v, do = inputs(L_CHECK)
    T = 2 * L_CHECK

    def xla(q, k, v):
        o = fa.attention_tiles_xla(
            q.reshape(B, T, Hkv, G, D), k.reshape(B, T, Hkv, D),
            v.reshape(B, T, Hkv, D), rule, tile, tile)
        return o.reshape(q.shape)

    def kernels(q, k, v):
        return fa.flash_attention(q, k, v, rule, Hkv, tile, tile, INTERPRET)

    out = {}
    for name, f in (("xla", xla), ("kernels", kernels)):
        o, vjp = jax.vjp(f, q, k, v)
        out[name] = (o,) + vjp(do)
    return [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
                  / jnp.max(jnp.abs(b.astype(jnp.float32))))
            for a, b in zip(out["kernels"], out["xla"])]


def main():
    tiles = [int(t) for t in sys.argv[1:]] or [fa.tile_sizes(2 * L)[0]]
    rule = ("block_diffusion", L, BLOCK)
    q, k, v, do = inputs(L)
    count = need()
    f_need = count.forward(B, L, BLOCK, Hkv * G, Hkv, D, 2)
    b_need = count.backward(B, L, BLOCK, Hkv * G, Hkv, D, 2)
    for tile in tiles:
        fwd = jax.jit(lambda q, k, v: fa.flash_attention(
            q, k, v, rule, Hkv, tile, tile, INTERPRET))
        both = jax.jit(lambda q, k, v, do: jax.vjp(
            lambda *a: fa.flash_attention(*a, rule, Hkv, tile, tile,
                                          INTERPRET), q, k, v)[1](do))
        try:
            t_f = timed(fwd, q, k, v)
            t_b = timed(both, q, k, v, do) - t_f
        except Exception as e:      # a tile Mosaic refuses: say so, go on
            print(json.dumps({"tile": tile, "refused": str(e)[-400:]}),
                  flush=True)
            continue
        kept = fa.plan_counts(fa.tile_plan(rule, 2 * L, tile, tile))
        n_f, n_b = products_a_tile(tile)
        # a product of a kept tile: [tile, D] x [D, tile], every head and row
        tile_flops = 2 * tile * tile * D * Hkv * G * B * kept[0]
        print(json.dumps({
            "tile": tile, "tiles_kept_whole_partial_all": kept,
            "fwd_ms": 1e3 * t_f, "bwd_ms": 1e3 * t_b,
            "fwd_share_of_need": f_need[0] / PEAK / t_f,
            "bwd_share_of_need": b_need[0] / PEAK / t_b,
            "fwd_products_a_tile": n_f, "bwd_products_a_tile": n_b,
            "fwd_tile_tflop": n_f * tile_flops / 1e12,
            "bwd_tile_tflop": n_b * tile_flops / 1e12,
            "fwd_share_of_mxu_pace": n_f * tile_flops / PEAK / t_f,
            "bwd_share_of_mxu_pace": n_b * tile_flops / PEAK / t_b,
            "rel_err_o_dq_dk_dv": check(tile),
            "platform": jax.default_backend()}), flush=True)


if __name__ == "__main__":
    main()
