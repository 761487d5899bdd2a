"""`kernels/moe_grouped.py` (the routed experts' products, a chunk of tiles a
Mosaic launch) pinned to the tile loop of `layers/moe.py` on the CPU, the
kernels in interpret mode; the gate; the two counters the chunks bring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import data_type, layer
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.topology import Topology
from paddle_tpu.kernels import moe_grouped
from paddle_tpu.layers import moe

D, TILE, CHUNK, HELD = 128, 16, 4, 4

# rows of each held expert, by what the plan then holds (tiles of 16 rows,
# chunks of 4 tiles)
PLANS = {
    # tiles 0 0 0 2 | 2 3 3 3 | 3 3: expert 1 holds no row, the runs of
    # experts 2 and 3 cross a chunk's edge, 10 tiles are not whole chunks,
    # three tiles end in padding rows
    "mixed": [40, 0, 17, 70],
    # tiles 0 0 1 1 | 2 2 2 2: whole chunks, whole tiles, a run that starts
    # at a chunk's first tile without continuing anything
    "whole": [32, 32, 64, 0],
    # expert 0 runs over two chunks' edges; the last chunk holds one tile more
    "long": [16 * 9, 0, 0, 5],
    # nothing routed here: no tile in use, no trip
    "none": [0, 0, 0, 0],
    # fewer tiles than a chunk
    "short": [3, 0, 20, 0],
}


def _plan(sizes, seed=0):
    """(idx, top, valid) of tokens that each choose one held expert (or
    none) and one expert of another share, such that held expert e gets
    sizes[e] rows; three tokens are padding."""
    rng = np.random.default_rng(seed)
    n = sum(sizes) + 9
    first = np.full(n, HELD + 1)
    first[rng.permutation(n - 3)[:sum(sizes)]] = np.repeat(np.arange(HELD),
                                                           sizes)
    idx = np.stack([first, np.full(n, HELD + 2)], 1)
    top = rng.uniform(0.2, 0.8, (n, 2)).astype(np.float32)
    valid = np.arange(n) < n - 3
    # the padding tokens' choices must not count: give them a held expert
    idx[~valid, 0] = 0
    return jnp.asarray(idx), jnp.asarray(top), jnp.asarray(valid)


def _walk(tile_expert, n_tiles, chunk):
    """(tiles in use, (chunk, expert) runs) by a NumPy walk."""
    te = np.asarray(tile_expert)[:n_tiles]
    runs = sum(1 for t in range(n_tiles)
               if t % chunk == 0 or te[t] != te[t - 1])
    return n_tiles, runs


def _weights(I, dtype, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return ((jax.random.normal(ks[0], (HELD, D, I)) / D ** .5).astype(dtype),
            (jax.random.normal(ks[1], (HELD, D, I)) / D ** .5).astype(dtype),
            (jax.random.normal(ks[2], (HELD, I, D)) / I ** .5).astype(dtype))


def _close(got, want, dtype):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.all(np.isfinite(got))
    # float32: the sums differ by the order a chunk's scatter-add takes its
    # rows in; bf16: by one rounding of the result beside that
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=ulp * max(np.abs(want).max(), 1e-6))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("I", [256, 384, 1408])
@pytest.mark.parametrize("plan", sorted(PLANS))
def test_the_kernels_match_the_tile_loop(plan, I, dtype):
    """Forward and all five gradients (x, wg, wu, wd, row_w) of the chunked
    kernels against the tile loop over one plan, at expert widths that are a
    power of two (256) and are not (3 x 128 and 11 x 128 lanes, as 768 and
    1408 are); the plan drops nothing and its two counters read what a walk
    of `tile_expert` gives."""
    sizes = PLANS[plan]
    idx, top, valid = _plan(sizes)
    N = idx.shape[0]
    x = jax.random.normal(jax.random.PRNGKey(2), (N, D)).astype(dtype)
    r = jax.random.normal(jax.random.PRNGKey(3), (N, D))
    wg, wu, wd = _weights(I, dtype)

    def loss(fn, chunk):
        def f(x, wg, wu, wd, top):
            row_w, row_tok, te, nt, stats = moe.dispatch_plan(
                idx, top, valid, 0, HELD, TILE, chunk)
            y = fn(x, wg, wu, wd, row_w, row_tok, te, nt)
            return jnp.sum(y.astype(jnp.float32) * r), (y, te, nt, stats)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))

    (_, (y0, te, nt, stats0)), g0 = loss(
        lambda *a: moe.grouped_ffn(*a, TILE), 1)(x, wg, wu, wd, top)
    (_, (y1, _, _, stats1)), g1 = loss(
        lambda *a: moe_grouped.grouped_ffn(*a, TILE, CHUNK, 2, True), CHUNK)(
            x, wg, wu, wd, top)
    nt = int(nt)
    assert nt == sum(-(-s // TILE) for s in sizes)
    _close(y1, y0, dtype)
    for got, want in zip(g1, g0):
        _close(got, want, dtype)
    if plan != "none":
        assert float(jnp.abs(g0[4]).max()) > 0      # row_w's gradient is live
    held, _, _, dropped, tiles, fetches = np.asarray(stats1).tolist()
    assert (held, dropped) == (sum(sizes), 0)
    assert (tiles, fetches) == _walk(te, nt, CHUNK)
    # the tile loop fetches an expert every tile
    assert np.asarray(stats0).tolist()[4:] == [nt, nt]


def test_one_buffer_for_the_weights_gives_the_same_numbers():
    """`pl.Buffered(1)` on the weights' blocks (what the gate gives a wide
    expert) changes when a block is fetched, not what is computed."""
    idx, top, valid = _plan(PLANS["mixed"])
    N = idx.shape[0]
    x = jax.random.normal(jax.random.PRNGKey(2), (N, D))
    wg, wu, wd = _weights(256, jnp.float32)
    row_w, row_tok, te, nt, _ = moe.dispatch_plan(idx, top, valid, 0, HELD,
                                                  TILE, CHUNK)

    def grads(buffers):
        return jax.grad(lambda *a: jnp.sum(moe_grouped.grouped_ffn(
            *a, row_tok, te, nt, TILE, CHUNK, buffers, True) ** 2),
            argnums=(0, 1, 2, 3, 4))(x, wg, wu, wd, row_w)

    for one, two in zip(grads(1), grads(2)):
        np.testing.assert_array_equal(np.asarray(one), np.asarray(two))


@pytest.mark.parametrize("d,I,tile,dtype,want", [
    (2048, 768, 256, jnp.bfloat16, (16, 2)),     # the SDAR cell's expert
    (2048, 512, 256, jnp.bfloat16, (16, 2)),     # the Qwen3-Next cell's
    (2048, 1408, 256, jnp.bfloat16, (16, 1)),    # Kimi-VL's: one buffer
    (2048, 768, 256, jnp.float32, (8, 2)),
    (1024, 512, 128, jnp.bfloat16, (64, 2)),
    (2048, 2816, 256, jnp.bfloat16, None),       # gradients past VMEM
    (2048, 768, 200, jnp.bfloat16, None),        # a tile off the sublanes
    (2048, 700, 256, jnp.bfloat16, None),        # a width off the lanes
    (1152, 768, 256, jnp.bfloat16, None),        # a token's row off the tiles
    (16, 12, 8, jnp.float32, None),              # the CPU tests' toy widths
])
def test_the_gate_follows_the_shapes(d, I, tile, dtype, want):
    plan, why = moe_grouped.chunk_plan(d, I, tile, dtype)
    assert plan == want, (plan, why)
    assert (why == "") == (want is not None)
    if want is not None:
        need, grads = moe_grouped.bwd_vmem_bytes(d, I, tile, dtype, want[1])
        assert grads == 3 * d * I * 4 < need <= moe_grouped._VMEM_BUDGET


def test_a_layer_takes_the_kernels_where_the_gate_passes(monkeypatch):
    """`moe_ffn` with the platform's answer taken out of `take_pallas` and
    the kernels in interpret mode: the layer's output, its gradients and its
    held pairs are the tile loop's, tiles over fetches rises above 1, and off
    the TPU the same layer logs the tile loop."""
    from paddle_tpu.kernels import _pallas_util

    d, T = 1024, 40
    x = layer.data(name="x", type=data_type.dense_vector_sequence(d))
    out = layer.moe_ffn(input=x, num_experts=4, top_k=2, expert_size=128,
                        experts_held=2, tile=16, name="l")
    topo = Topology(out)
    params = topo.init_params(jax.random.PRNGKey(0))
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, T, d))

    def run():
        def loss(params, xs):
            outs, ctx = topo.forward(params, {"x": Arg(xs, jnp.ones((2, T)))},
                                     training=True, return_ctx=True)
            return (jnp.sum(outs["l"].value ** 2),
                    ctx.extras["step_stats"]["moe_ffn"]["l"])
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            params, xs)

    (v0, stats0), g0 = run()
    assert stats0[4] == stats0[5] > 0
    lines = {line for who, line in _pallas_util._LOGGED_DECISIONS if who == "l"}
    assert any(line.startswith("the tile loop, not moe_grouped_fwd/bwd")
               for line in lines), lines

    monkeypatch.setattr(moe, "take_pallas",
                        lambda who, kernel, eligible=True, why_not="", **kw:
                        eligible)
    # chunks of 3 tiles, so that the 5 or so tiles in use take two trips
    monkeypatch.setattr(moe_grouped, "CHUNK_BYTES", 3 * 16 * d * 4)
    kernels = moe_grouped.grouped_ffn
    monkeypatch.setattr(moe_grouped, "grouped_ffn",
                        lambda *a: kernels(*a, True))
    (v1, stats1), g1 = run()
    np.testing.assert_allclose(float(v1), float(v0), rtol=1e-5)
    for got, want in zip(jax.tree_util.tree_leaves(g1),
                         jax.tree_util.tree_leaves(g0)):
        _close(got, want, jnp.float32)
    assert np.asarray(stats1[:5]).tolist() == np.asarray(stats0[:5]).tolist()
    assert 0 < stats1[5] < stats1[4]
