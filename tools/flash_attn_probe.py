"""python3 tools/flash_attn_probe.py [TILE ...]
(on the chip: chiprun -- python3 tools/flash_attn_probe.py 256 512)

Times the attention kernels alone at a cell's size (one layer, bf16; PROBE_CELL
names the cell): `sdar`, the default: 2 rows x 2 x 8192 positions, 32 : 4 heads
of 128 under the block-diffusion mask in blocks of 4; `kimivl`: 2 rows x 8192
positions, 16 : 16 heads of 192 : 128 under the causal mask, the q / k heads
padded to 256 lanes as `attention` pads them. Timed: flash_attn_fwd and the pair forward + flash_attn_bwd, with the tile
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

BLOCK = 4
CELL = os.environ.get("PROBE_CELL", "sdar")
# per cell: key/value heads, query heads a key/value head, the q / k head as
# published and as it goes in, the value head, positions a row of L tokens
Hkv, G, D_PUB, D, Dv, ROW = {
    "sdar": (4, 8, 128, 128, 128, 2),
    "kimivl": (16, 1, 192, 256, 128, 1)}[CELL]
B = int(os.environ.get("PROBE_ROWS", 2))
L = int(os.environ.get("PROBE_TOKENS", 8192))
L_CHECK = int(os.environ.get("PROBE_CHECK_TOKENS", 1024))
INTERPRET = jax.default_backend() != "tpu"
PEAK = 197e12


def mask_rule(L):
    return ("causal", L) if CELL == "kimivl" else ("block_diffusion", L, BLOCK)


def need():
    """(forward, backward) (FLOPs, bytes) of the benchmark's count."""
    name = "mla_attn" if CELL == "kimivl" else "flash_attn"
    spec = importlib.util.spec_from_file_location(
        "need", os.path.join(ROOT, "benchmark", "kernels", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    dims = (B, L, Hkv * G, 128, 64, 128, 2) if CELL == "kimivl" \
        else (B, L, BLOCK, Hkv * G, Hkv, D, 2)
    return mod.forward(*dims), mod.backward(*dims)


def inputs(L, dtype=jnp.bfloat16, seed=0):
    """q, k with their published head size, zeros behind it where the probe
    pads; v, do with the value head's."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    T = ROW * L
    q = jax.random.normal(ks[0], (B, T, Hkv * G * D_PUB)) * D_PUB ** -0.5
    k = jax.random.normal(ks[1], (B, T, Hkv * D_PUB))
    v = jax.random.normal(ks[2], (B, T, Hkv * Dv))
    do = jax.random.normal(ks[3], (B, T, Hkv * G * Dv))
    if D != D_PUB:
        q, k = fa._pad_heads(q, D_PUB, D), fa._pad_heads(k, D_PUB, D)
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
    rule = mask_rule(L_CHECK)
    jaxpr = jax.make_jaxpr(lambda q, k, v, do: jax.vjp(
        lambda *a: fa.flash_attention(*a, rule, Hkv, tile, tile, INTERPRET),
        q, k, v)[1](do))(q, k, v, do)
    return tuple(kernel_products(jaxpr.jaxpr, name) // (2 * G)
                 for name in ("flash_attn_fwd", "flash_attn_bwd"))


def check(tile):
    rule = mask_rule(L_CHECK)
    q, k, v, do = inputs(L_CHECK)
    T = ROW * L_CHECK

    def xla(q, k, v):
        o = fa.attention_tiles_xla(
            q.reshape(B, T, Hkv, G, D), k.reshape(B, T, Hkv, D),
            v.reshape(B, T, Hkv, Dv), rule, tile, tile)
        return o.reshape(do.shape)

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
    tiles = [int(t) for t in sys.argv[1:]] or [fa.tile_sizes(ROW * L)[0]]
    rule = mask_rule(L)
    q, k, v, do = inputs(L)
    f_need, b_need = need()
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
        kept = fa.plan_counts(fa.tile_plan(rule, ROW * L, tile, tile))
        n_f, n_b = products_a_tile(tile)
        # a kept tile's products, every head and row: the forward's two are
        # D and Dv wide, the backward's five three times D and twice Dv
        per_width = 2 * tile * tile * Hkv * G * B * kept[0]
        f_flops = per_width * (D + Dv) * n_f / 2
        b_flops = per_width * (3 * D + 2 * Dv) * n_b / 5
        print(json.dumps({
            "cell": CELL, "head_q_k_v": [D_PUB, D, Dv],
            "tile": tile, "tiles_kept_whole_partial_all": kept,
            "fwd_ms": 1e3 * t_f, "bwd_ms": 1e3 * t_b,
            "fwd_share_of_need": f_need[0] / PEAK / t_f,
            "bwd_share_of_need": b_need[0] / PEAK / t_b,
            "fwd_products_a_tile": n_f, "bwd_products_a_tile": n_b,
            "fwd_tile_tflop": f_flops / 1e12, "bwd_tile_tflop": b_flops / 1e12,
            "fwd_share_of_mxu_pace": f_flops / PEAK / t_f,
            "bwd_share_of_mxu_pace": b_flops / PEAK / t_b,
            "rel_err_o_dq_dk_dv": check(tile),
            "platform": jax.default_backend()}), flush=True)


if __name__ == "__main__":
    main()
