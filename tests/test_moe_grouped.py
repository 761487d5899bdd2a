"""`kernels/moe_grouped.py` (the routed experts' products, a chunk of tiles a
Mosaic launch) pinned to the tile loop of `layers/moe.py` on the CPU, the
kernels in interpret mode; the gate; the two counters the chunks bring; the
routing plan the layer's checkpoint keeps, made once a step."""

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
    _check_against_the_tile_loop(plan, I, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("I,slices", [(256, 2), (384, 3), (896, 7)])
@pytest.mark.parametrize("plan", ["mixed", "long", "short"])
def test_a_width_walked_in_slices_matches_the_tile_loop(plan, I, slices,
                                                        dtype):
    """The backward launch over the experts' width in slices of whole lanes
    (what `chunk_plan` gives an expert whose gradients do not fit whole, as
    LFM2's 1792 in 2 x 896): each slice's window of the three gradients,
    the slices' dx in one token sum and their d row_w summed are the tile
    loop's, over runs that cross a chunk's edge (the carried sums read back
    slice by slice)."""
    _check_against_the_tile_loop(plan, I, dtype, slices)


def _check_against_the_tile_loop(plan, I, dtype, slices=1):
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
        lambda *a: moe_grouped.grouped_ffn(*a, TILE, CHUNK, 2, True, slices),
        CHUNK)(x, wg, wu, wd, top)
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
    # the cells' experts keep the plan they had before a width could be
    # sliced: whole, in the same buffers
    (2048, 768, 256, jnp.bfloat16, (16, 2, 1)),  # the SDAR cell's expert
    (2048, 512, 256, jnp.bfloat16, (16, 2, 1)),  # the Qwen3-Next cell's
    (2048, 1408, 256, jnp.bfloat16, (16, 1, 1)),  # Kimi-VL's: one buffer
    (2048, 768, 256, jnp.float32, (8, 2, 1)),
    (1024, 512, 128, jnp.bfloat16, (64, 2, 1)),
    # gradients past VMEM whole: the fewest slices of whole lanes that fit
    (2048, 1792, 256, jnp.bfloat16, (16, 2, 2)),  # LFM2's: 2 x 896
    (2048, 2816, 256, jnp.bfloat16, (16, 1, 2)),  # 2 x 1408, one buffer
    (2048, 1792, 256, jnp.float32, (8, 1, 2)),
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
        chunk, buffers, slices = want
        need, grads = moe_grouped.bwd_vmem_bytes(d, I // slices, tile, dtype,
                                                 buffers)
        assert grads == 3 * d * I // slices * 4 < need \
            <= moe_grouped._VMEM_BUDGET
        if slices > 1:     # whole, one buffer, it would not fit
            assert moe_grouped.bwd_vmem_bytes(d, I, tile, dtype, 1)[0] \
                > moe_grouped._VMEM_BUDGET


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


# --- the routing plan kept across the layer's checkpoint ----------------------

# the layer's four forms, as the cells build them: SDAR's, Qwen3-Next's,
# Kimi-VL's, LFM2's
FORMS = {
    "softmax": dict(),
    "softmax_gated_shared": dict(shared_size=12),
    "sigmoid_bias_shared_scaled": dict(score="sigmoid", selection_bias=True,
                                       shared_size=12, shared_gate=False,
                                       route_scale=2.446),
    "sigmoid_bias_norm_eps": dict(score="sigmoid", selection_bias=True,
                                  route_norm_eps=1e-6),
}


def _form_layer(form, dtype=jnp.float32):
    """(topology, params, the loss's value_and_grad) of a small `moe_ffn` of
    one form: 8 experts, 4 held from the third, top 3, a padded row; a biased
    form starts from a bias that changes the choice."""
    x = layer.data(name="x", type=data_type.dense_vector_sequence(16))
    out = layer.moe_ffn(input=x, num_experts=8, top_k=3, expert_size=12,
                        experts_held=4, first_expert=2, tile=8, name="l",
                        **FORMS[form])
    topo = Topology(out)
    params = topo.init_params(jax.random.PRNGKey(0))
    if "_l.bias" in params:
        params["_l.bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(5),
                                                     (8,))
    params = {k: v if k == "_l.bias" else v.astype(dtype)
              for k, v in params.items()}
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 16)).astype(dtype)
    mask = jnp.ones((2, 24)).at[1, 19:].set(0)

    def loss(params, xs):
        outs, ctx = topo.forward(params, {"x": Arg(xs, mask)}, training=True,
                                 return_ctx=True)
        return (jnp.sum(outs["l"].value.astype(jnp.float32) ** 2),
                (ctx.extras["step_stats"],
                 ctx.extras.get("batch_stats", {})))

    return topo, params, xs, jax.value_and_grad(loss, argnums=(0, 1),
                                                has_aux=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_kept_plan_gives_the_same_numbers_to_the_bit(form, dtype,
                                                        monkeypatch):
    """The loss, every gradient, the step statistics and the new selection
    bias with the plan kept across the checkpoint are those of a plain
    `jax.checkpoint`, which makes the plan again in the backward pass."""
    _, params, xs, vg = _form_layer(form, dtype)
    kept = jax.jit(vg)(params, xs)
    monkeypatch.setattr(moe, "_KEEP_THE_PLAN", None)
    again = jax.jit(vg)(params, xs)
    leaves = jax.tree_util.tree_leaves(kept)
    assert len(leaves) == len(jax.tree_util.tree_leaves(again)) >= 7
    if "selection_bias" in FORMS[form]:
        assert float(jnp.abs(kept[0][1][1]["l"]["bias"]).max()) > 0
    for got, want in zip(leaves, jax.tree_util.tree_leaves(again)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _primitives(jaxpr, counts):
    """Every equation's primitive, sub-jaxprs (loops, calls, the checkpoint
    and its transpose) walked."""
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    _primitives(sub.jaxpr, counts)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    _primitives(sub, counts)
    return counts


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_plan_is_made_once_a_step(form):
    """In the jaxpr of the layer's value and gradients the plan's top_k, its
    two cumulative sums and its two scatters appear as often as in the
    forward pass alone: the backward pass makes none of them again (a plain
    checkpoint doubles each)."""
    import jax.extend  # noqa: F401  (jax.extend.core below)

    topo, params, xs, vg = _form_layer(form)

    def forward(params, xs):
        return topo.forward(params, {"x": Arg(xs, jnp.ones((2, 24)))},
                            training=True)["l"].value

    once = _primitives(jax.make_jaxpr(forward)(params, xs).jaxpr, {})
    step = _primitives(jax.make_jaxpr(vg)(params, xs).jaxpr, {})
    assert (once["top_k"], once["cumsum"], once["scatter"]) == (1, 2, 2)
    for prim in ("top_k", "cumsum", "scatter", "sort"):
        assert step.get(prim, 0) == once.get(prim, 0), (prim, step, once)


# the four cells' layers: (tokens a step, top k, experts, held, width,
# expert width, shared width, the form)
CELL_LAYERS = {
    "sdar": (32768, 8, 128, 16, 2048, 768, None, "softmax"),
    "qwen3next": (16384, 10, 512, 16, 2048, 512, 512,
                  "softmax_gated_shared"),
    "kimivl": (16384, 6, 64, 8, 2048, 1408, 2816,
               "sigmoid_bias_shared_scaled"),
    "lfm2": (16384, 4, 32, 8, 2048, 1792, None, "sigmoid_bias_norm_eps"),
}


@pytest.mark.parametrize("cell", sorted(CELL_LAYERS))
def test_the_checkpoint_keeps_the_plan_and_nothing_wide(cell):
    """What the layer's checkpoint keeps at a cell's own shapes, traced and
    never run: beside the layer's arguments only the routing plan, the bytes
    `plan_bytes` counts (what the log says), under 8 MB a layer, and no value
    with a dimension of the model's, an expert's or the shared expert's width
    or of the router's scores."""
    from jax._src.ad_checkpoint import saved_residuals

    from paddle_tpu.kernels import _pallas_util

    N, k, E, held, d, I, Is, form = CELL_LAYERS[cell]
    kw = {key: v for key, v in FORMS[form].items() if key != "shared_size"}
    x = layer.data(name="x", type=data_type.dense_vector_sequence(d))
    out = layer.moe_ffn(input=x, num_experts=E, top_k=k, expert_size=I,
                        shared_size=Is, experts_held=held, tile=256,
                        name="l_" + cell, **kw)
    topo = Topology(out)
    params = {name: jax.ShapeDtypeStruct(v.shape, jnp.float32 if
                                         name.endswith(".bias")
                                         else jnp.bfloat16)
              for name, v in jax.eval_shape(topo.init_params,
                                            jax.random.PRNGKey(0)).items()}
    xs = jax.ShapeDtypeStruct((2, N // 2, d), jnp.bfloat16)

    def forward(params, xs):
        return topo.forward(params, {"x": Arg(xs, jnp.ones((2, N // 2)))},
                            training=True)["l_" + cell].value

    kept = [aval for aval, why in saved_residuals(forward, params, xs)
            if not why.startswith("from the argument")]
    size = sum(a.size * a.dtype.itemsize for a in kept)
    assert size == moe.plan_bytes(N, k, held, 256) < 8e6, kept
    wide = {d, I, E} | ({Is} if Is else set())
    assert not [a for a in kept if wide & set(a.shape)], kept
    line = f"(idx, top, place, rows: {size / 1e6:.1f} MB)"
    assert any(who == "l_" + cell and line in text
               for who, text in _pallas_util._LOGGED_DECISIONS)
