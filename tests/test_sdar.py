"""The SDAR share (docs/sdar.md): the block-diffusion mask's rule and its
tile plan, the noising and its weights, grouped-query attention under the
rule, the MoE without a shared expert, and the whole tiny model through
`make_train_step`, held to the plain reference of the benchmark
(benchmark/reference/sdar-30b-a3b-ep8.py: float32, the mask dense, the
noising token by token, the MoE as a masked loop) on seeded weights.
CPU, tiny widths, float32 at `highest`.
"""

import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import data_type, layer
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.topology import Topology
from paddle_tpu.kernels import flash_attn
from paddle_tpu.models.text import qwen3_next_lm_cost, sdar_lm_cost

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARGS = dict(vocab_size=50, hidden_size=16, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            rope_theta=10000.0, moe_intermediate_size=12, num_experts=8,
            num_experts_per_tok=3, experts_held=4, first_expert=2,
            rms_norm_eps=1e-6, seq_len=20, block_length=4, mask_token_id=1)


def _load(rel):
    path = os.path.join(ROOT, rel)
    spec = importlib.util.spec_from_file_location(
        "ref_" + os.path.basename(path).replace("-", "_")[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/sdar-30b-a3b-ep8.py")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _ident(x):
    return x


def _normal(seed, *shape, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.max(np.abs(want))), 1e-6)
    assert float(np.max(np.abs(got - want))) <= tol * scale, \
        (float(np.max(np.abs(got - want))), scale)


def _seeded(table, seed):
    """Every leaf of a reference table from the seed; the constants are
    moved off their start so that their gradients are exercised."""
    out = {}
    for i, (name, (shape, (kind, v))) in enumerate(sorted(table.items())):
        noise = _normal(seed * 1000 + i, *shape)
        out[name] = v * noise if kind == "normal" else v + 0.1 * noise
    return out


def _token_by_token_mask(L, b):
    """The issue's rule, one (query, key) pair at a time."""
    M = np.zeros((2 * L, 2 * L), bool)
    for i in range(2 * L):
        for j in range(2 * L):
            bi, bj = (i % L) // b, (j % L) // b
            M[i, j] = (i < L and j < L and bj == bi) \
                or (i < L and j >= L and bj < bi) \
                or (i >= L and j >= L and bj <= bi)
    return M


# ---- (b) the mask's rule and its tiles ---------------------------------------

@pytest.mark.parametrize("b", [1, 4, 32])
@pytest.mark.parametrize("L,tile", [(40, 16), (72, 32), (64, 128)])
def test_mask_rule_and_tile_classes_match_the_dense_mask(ref, L, tile, b):
    """The rule's codes give the dense mask pair for pair (the reference's
    own dense mask is the same one), for L not a multiple of the tile; no
    kept pair lies in a skipped tile and no dropped pair in a whole one."""
    rule, T = ("block_diffusion", L, b), 2 * L
    M = _token_by_token_mask(L, b)
    assert np.array_equal(ref.dense_mask(L, b), M)
    thr, eq, code = flash_attn.mask_codes(rule, T)
    assert np.array_equal(flash_attn.keep(thr[:, None], eq[:, None],
                                          code[None, :]), M)
    assert list(flash_attn.positions(rule, T)) == [i % L for i in range(T)]
    plan = flash_attn.tile_plan(rule, T, tile, tile)
    kept = 0
    for qi in range(plan.shape[0]):
        for ki in range(plan.shape[1]):
            sub = M[qi * tile:(qi + 1) * tile, ki * tile:(ki + 1) * tile]
            if plan[qi, ki] == flash_attn.SKIPPED:
                assert not sub.any()
            elif plan[qi, ki] == flash_attn.WHOLE:
                assert sub.all() and sub.shape[1] == tile    # no padding key
            else:
                assert sub.any()
            kept += sub.sum() if plan[qi, ki] else 0
    # every kept pair is in a kept tile; the count is the closed form the
    # benchmark prices the kernels by
    count = _load("benchmark/kernels/flash_attn.py")
    assert kept == M.sum() == count.kept_pairs(L, b)
    n_kept, whole, partial, every = flash_attn.plan_counts(plan)
    assert n_kept == whole + partial <= every == plan.size


def test_at_the_cells_shape_a_quarter_of_the_square_is_kept():
    plan = flash_attn.tile_plan(("block_diffusion", 8192, 4), 16384,
                                *flash_attn.tile_sizes(16384))
    assert flash_attn.plan_counts(plan) == (288, 240, 48, 1024)


# ---- (f) the kernels in interpret mode against the tiles in XLA --------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("L,b,tile,B,Hkv,G", [
    (40, 4, 16, 2, 2, 2), (96, 32, 32, 2, 2, 2), (24, 1, 16, 2, 2, 2),
    # rows the tile does not divide: padding keys in the resident dK / dV
    (36, 4, 16, 2, 2, 2), (20, 4, 32, 1, 1, 4),
    # more rows and key/value heads: the pair starts from zero in each
    (40, 8, 16, 3, 3, 1)])
def test_flash_kernels_match_the_tiles_in_xla(L, b, tile, B, Hkv, G, dtype,
                                              tol):
    """flash_attn_fwd / flash_attn_bwd (interpret mode) against
    `attention_tiles_xla` and against plain attention under the dense mask:
    the output and the gradients of q, k and v."""
    D, T = 8, 2 * L
    rule = ("block_diffusion", L, b)
    q = (_normal(1, B, T, Hkv * G * D) * D ** -0.5).astype(dtype)
    k, v = (_normal(s, B, T, Hkv * D).astype(dtype) for s in (2, 3))
    proj = _normal(4, B, T, Hkv * G * D)
    M = _token_by_token_mask(L, b)

    def through(attend):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) * proj), argnums=(0, 1, 2))(
                q, k, v)

    def tiles(q, k, v):
        return flash_attn.attention_tiles_xla(
            q.reshape(B, T, Hkv, G, D), k.reshape(B, T, Hkv, D),
            v.reshape(B, T, Hkv, D), rule, tile, tile).reshape(q.shape)

    def dense(q, k, v):
        s = jnp.einsum("bqngd,bknd->bngqk", q.reshape(B, T, Hkv, G, D),
                       k.reshape(B, T, Hkv, D)).astype(jnp.float32)
        a = jax.nn.softmax(jnp.where(M, s, -1e30), -1).astype(v.dtype)
        return jnp.einsum("bngqk,bknd->bqngd", a,
                          v.reshape(B, T, Hkv, D)).reshape(q.shape)

    want = through(dense)
    for got in (through(tiles), through(lambda q, k, v: flash_attn.flash_attention(
            q, k, v, rule, Hkv, tile, tile, True))):
        _close(got[0], want[0], tol)
        for a, w in zip(got[1], want[1]):
            assert a.dtype == w.dtype and a.shape == w.shape
            _close(a.astype(jnp.float32), w.astype(jnp.float32), tol)


@pytest.mark.parametrize("L,b,tile", [(8192, 4, 512), (40, 4, 16),
                                      (36, 4, 16), (96, 32, 32)])
def test_the_backward_launch_walks_every_kept_tile_once(L, b, tile):
    """Forward and backward launch share one list of steps: every kept tile
    of the plan exactly once (288 at the cell's shape), query tiles
    outermost, each query tile's run opened and closed once."""
    plan = flash_attn.tile_plan(("block_diffusion", L, b), 2 * L, tile, tile)
    qi, ki, fl = flash_attn._steps(plan)
    kept = flash_attn.plan_counts(plan)[0]
    assert len(qi) == len(ki) == len(fl) == kept
    if L == 8192:
        assert kept == 288
    assert sorted(zip(qi.tolist(), ki.tolist())) == \
        [tuple(t) for t in np.argwhere(plan != flash_attn.SKIPPED).tolist()]
    assert np.all(np.diff(qi) >= 0)
    first = (fl & flash_attn._FIRST) != 0
    last = (fl & flash_attn._LAST) != 0
    assert first.sum() == last.sum() == len(set(qi.tolist()))
    assert np.array_equal((fl & flash_attn._MASKED) != 0,
                          plan[qi, ki] == flash_attn.PARTIAL)


@pytest.mark.parametrize("G", [1, 2, 4])
def test_a_tile_costs_two_products_forward_and_five_backward(G):
    """The traced kernel bodies (a copy for whole tiles, one for partial
    ones): q k^T and p v a head forward; q k^T, do v^T, p^T do, ds^T q and
    ds k a head backward, the probabilities made once."""
    probe = _load("tools/flash_attn_probe.py")
    L, tile, Hkv, D = 32, 16, 2, 8
    q = jax.ShapeDtypeStruct((1, 2 * L, Hkv * G * D), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 2 * L, Hkv * D), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v, do: jax.vjp(
        lambda *a: flash_attn.flash_attention(
            *a, ("block_diffusion", L, 4), Hkv, tile, tile, True),
        q, k, v)[1](do))(q, k, k, q).jaxpr
    assert probe.kernel_products(jaxpr, "flash_attn_fwd") == 2 * 2 * G
    assert probe.kernel_products(jaxpr, "flash_attn_bwd") == 2 * 5 * G


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_the_vmem_gate_follows_the_rows_length(dtype, monkeypatch, caplog):
    """dK and dV of a key/value head stay in VMEM over the whole row, so the
    gate is an estimate from the shapes: the cell's row passes in both
    dtypes, a row too long takes the tiles in XLA and the log says why."""
    import logging

    from paddle_tpu.kernels import _pallas_util

    T, D, G = 16384, 128, 8
    need, pair = flash_attn.bwd_vmem_bytes(T, D, G, dtype)
    assert pair == 2 * T * D * 4 and pair < need <= flash_attn._VMEM_BUDGET
    assert flash_attn.kernel_gate(T, D, G, dtype)[0]
    assert flash_attn._VMEM_BUDGET < _pallas_util.VMEM_LIMIT_BYTES
    # the estimate grows with the row, and somewhere stops fitting
    needs = [flash_attn.bwd_vmem_bytes(t, D, G, dtype)[0]
             for t in (4096, 16384, 32768, 65536, 131072)]
    assert needs == sorted(needs)
    long_row = 131072
    ok, why = flash_attn.kernel_gate(long_row, D, G, dtype)
    assert not ok and f"over a row of {long_row} positions" in why \
        and "in VMEM" in why

    # the layer reads that gate: a short row whose estimate is made not to
    # fit takes the tiles in XLA, and says so once
    monkeypatch.setattr(_pallas_util, "_LOGGED_DECISIONS", set())
    monkeypatch.setattr(flash_attn, "_VMEM_BUDGET", 1000)
    q = _normal(1, 1, 80, 2 * 128).astype(dtype)
    with caplog.at_level(logging.INFO, logger="paddle_tpu"):
        o = flash_attn.attention("l7", q, q[..., :128], q[..., :128],
                                 ("block_diffusion", 40, 4), 1)
    assert o.shape == q.shape and o.dtype == q.dtype
    lines = [r.getMessage() for r in caplog.records if "l7" in r.getMessage()]
    assert len(lines) == 2 and lines[0].startswith(
        "l7: the tiles in XLA, not flash_attn_fwd/bwd (over a row of 80 "
        "positions flash_attn_bwd would hold ") \
        and "MB in VMEM, dK and dV whole, against the" in lines[0], lines


def test_the_layers_log_says_what_the_backward_launch_holds(monkeypatch,
                                                            caplog):
    """Where the kernels are taken the layer's log gains, once, the form of
    the backward launch and what of the estimate is the resident pair."""
    import logging

    from paddle_tpu.kernels import _pallas_util

    monkeypatch.setattr(_pallas_util, "_LOGGED_DECISIONS", set())
    monkeypatch.setattr(flash_attn, "take_pallas",
                        lambda who, kernel, eligible=True, why_not="", **kw:
                        eligible)
    monkeypatch.setattr(flash_attn, "flash_attention",
                        lambda q, k, v, *a: q)      # nothing runs here
    q = jax.ShapeDtypeStruct((1, 16384, 8 * 4 * 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 16384, 4 * 128), jnp.bfloat16)
    with caplog.at_level(logging.INFO, logger="paddle_tpu"):
        for _ in range(2):
            jax.eval_shape(lambda q, k: flash_attn.attention(
                "l5", q, k, k, ("block_diffusion", 8192, 4), 4), q, k)
    lines = [r.getMessage() for r in caplog.records if "l5" in r.getMessage()]
    assert lines == [
        "l5: mask ('block_diffusion', 8192, 4): 288 of 1024 tiles of 512 x "
        "512 kept (240 whole, 48 partial)",
        "l5: flash_attn_bwd: one walk of 288 tiles, dK/dV 16.8 MB of 49.8 "
        "MB in VMEM"], lines


def test_kernel_gate_and_the_layers_line_in_the_log(monkeypatch, caplog):
    """Held to the CPU the layer takes the tiles in XLA and says so once,
    with the tiles kept / whole / partial of the square."""
    import logging

    from paddle_tpu.kernels import _pallas_util

    assert flash_attn.kernel_supported(128, jnp.bfloat16)
    # heads of 64 go in padded to 128 lanes since PR 38; 96 does not
    assert flash_attn.kernel_supported(64, jnp.bfloat16)
    assert not flash_attn.kernel_supported(96, jnp.bfloat16)
    monkeypatch.setattr(_pallas_util, "_LOGGED_DECISIONS", set())
    q = _normal(1, 1, 80, 32)
    with caplog.at_level(logging.INFO, logger="paddle_tpu"):
        for _ in range(2):
            flash_attn.attention("l9", q, q[..., :16], q[..., :16],
                                 ("block_diffusion", 40, 4), 2)
    lines = [r.getMessage() for r in caplog.records if "l9" in r.getMessage()]
    assert lines == [
        "l9: the tiles in XLA, not flash_attn_fwd/bwd (head size 8, float32 "
        "is outside the kernel's gate)",
        "l9: mask ('block_diffusion', 40, 4): 1 of 1 tiles of 128 x 128 kept "
        "(0 whole, 1 partial)"], lines


# ---- (c) the noising -----------------------------------------------------------

def _noise_layers(L, b, mask_id=1):
    ids = layer.data(name="ids", type=data_type.integer_value_sequence(50))
    v = layer.data(name="v", type=data_type.dense_vector(L))
    t = layer.data(name="t", type=data_type.dense_vector(-(-L // b)))
    return layer.block_diffusion_noise(ids, v, t, block=b, mask_id=mask_id,
                                       name="n")


def test_noising_masks_where_v_is_under_the_blocks_level():
    L, b = 10, 4
    both, weights = _noise_layers(L, b)
    ids = np.arange(2, 2 + L)[None]
    v = np.asarray([[.1, .6, .4, .9, .2, .2, .2, .2, .99, .5]], np.float32)
    t = np.asarray([[.5, .25, 1.0]], np.float32)
    feeds = {"ids": Arg(jnp.asarray(ids), jnp.ones((1, L))),
             "v": Arg(jnp.asarray(v)), "t": Arg(jnp.asarray(t))}
    outs, ctx = Topology([both, weights]).forward(feeds=feeds, params={},
                                                  training=True, return_ctx=True)
    masked = [True, False, True, False, True, True, True, True, True, True]
    level = [.5] * 4 + [.25] * 4 + [1.0] * 2
    want_w = [1 / l if m else 0.0 for m, l in zip(masked, level)]
    got = np.asarray(outs["n"].value)[0]
    assert got[:L].tolist() == [1 if m else i for m, i in zip(masked, ids[0])]
    assert got[L:].tolist() == ids[0].tolist()
    np.testing.assert_allclose(np.asarray(outs["n_weights"].value)[0],
                               want_w, rtol=1e-6)
    assert np.asarray(outs["n"].mask).tolist() == [[1.0] * (2 * L)]
    stats = np.asarray(ctx.extras["step_stats"]["block_diffusion_noise"]["n"])
    assert stats.tolist() == [sum(masked), 2 * L]


def _rows(seed, lens, a, no_mask_row=None):
    """Rows as the benchmark's traffic makes them: ids, mask_u and noise_t in
    [-0.5, 0.5) on a grid of 1/256."""
    rng = np.random.default_rng(seed)
    L, nb = a["seq_len"], -(-a["seq_len"] // a["block_length"])
    rows = []
    for r, n in enumerate(lens):
        u = rng.integers(0, 256, L) / 256.0 - 0.5
        t = rng.integers(0, 256, nb) / 256.0 - 0.5
        if r == no_mask_row:        # t = 1/256 everywhere, every u above it
            u, t = np.full(L, 0.25), np.full(nb, -0.5)
        rows.append((rng.integers(2, a["vocab_size"], n).tolist(),
                     u.astype(np.float32), t.astype(np.float32)))
    return rows


def _feeds(ref, rows, a):
    b = {k: jnp.asarray(v) for k, v in ref.pad(rows, a).items()}
    return b, {"ids": Arg(b["ids"], b["ids_mask"]),
               "mask_u": Arg(b["mask_u"]), "noise_t": Arg(b["noise_t"])}


def test_a_row_with_no_masked_token_costs_nothing(ref):
    topo = Topology(sdar_lm_cost(**ARGS))
    p = _seeded(ref.param_table(ARGS), 5)
    rows = _rows(1, [ARGS["seq_len"]], ARGS, no_mask_row=0)
    _, feeds = _feeds(ref, rows, ARGS)
    (c, _), g = jax.value_and_grad(lambda p: topo.loss_fn()(p, feeds),
                                   has_aux=True)(p)
    assert float(c) == 0.0
    assert all(np.all(np.isfinite(np.asarray(v))) for v in g.values())
    assert all(float(jnp.max(jnp.abs(v))) == 0.0 for v in g.values())


# ---- (e) the MoE without a shared expert ----------------------------------------

def test_moe_without_a_shared_size_has_no_shared_leaf(ref):
    a = ARGS
    x = layer.data(name="x", type=data_type.dense_vector_sequence(16))
    out = layer.moe_ffn(input=x, num_experts=8, top_k=3, expert_size=12,
                        experts_held=4, first_expert=2, tile=8, name="l")
    topo = Topology(out)
    assert sorted(topo.param_specs()) == ["_l.router", "_l.wd", "_l.wg", "_l.wu"]
    params = {k: _normal(i, *s.shape, scale=0.4)
              for i, (k, s) in enumerate(sorted(topo.param_specs().items()))}
    xs = _normal(9, 2, 21, 16)
    y = topo.forward(params, {"x": Arg(xs, jnp.ones((2, 21)))},
                     training=True)["l"].value
    p = {k.split(".", 1)[1]: v for k, v in params.items()}
    _close(y, jnp.stack([ref.moe_ffn(p, xs[r], a, _ident) for r in range(2)]))


QWEN_ARGS = dict(vocab_size=50, hidden_size=16, num_hidden_layers=4,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=8,
                 partial_rotary_factor=0.5, rope_theta=10000.0,
                 full_attention_interval=4, linear_num_key_heads=2,
                 linear_num_value_heads=4, linear_key_head_dim=8,
                 linear_value_head_dim=8, linear_conv_kernel_dim=4,
                 moe_intermediate_size=12, shared_expert_intermediate_size=12,
                 num_experts=8, num_experts_per_tok=3, experts_held=4,
                 first_expert=2, rms_norm_eps=1e-6)
# sha256 of the lowered train step below, by this test's own code: the
# Qwen3-Next cell's step must not lower differently. Recorded at 96b24b7
# (fb70acdc...) and held through PR 34; PR 35 appended two entries to a
# `moe_ffn` layer's step statistics (tiles, expert fetches:
# `layers/moe.py` STATS), which the step hands out, and the hash moved for
# them alone: with the five lines of `dispatch_plan` that count and append
# them taken out, the step lowers to fb70acdc... again (off the TPU the
# layer takes the tile loop, which PR 35 did not touch)
# It moved again (from 6215b9cd...) when `moe_ffn`'s checkpoint came to keep
# the routing plan: the checkpoint's policy, the plan's unique places
# (`dispatch_plan`) and the plan's `checkpoint_name`s, which lower to nothing
# but renumber the module's private functions; with the three taken out the
# step lowers to 6215b9cd... again, and this test's SDAR twin to ee60a363...
# It moved a third time (from e9054c00...) when `gated_attention` came to
# compute its scores by `flash_attn.attention` under the causal rule, keeping
# the forward launch's o / lse across the row's checkpoint, in place of
# checkpointed XLA blocks of 512 queries: with that layer's forward taken back
# to its earlier form (the `query_block` argument gone from `layer.py` or
# not), the step lowers to e9054c00... again
QWEN_STEP_SHA256 = "4677066a4375d5f1559bdfc87e5893b8082f8aa5cf078065d83e72c35f4ce1de"


def test_the_qwen3_next_step_lowers_as_it_did():
    """`moe_ffn` gained an optional shared expert, `rms_norm` a second form
    and `classification_cost` a weight (PR 32), then a sigmoid score, a
    selection bias, a weight scale and a shared expert without its gate
    (PR 34): a model that uses none of them lowers to the text it lowered
    to before them."""
    from paddle_tpu.trainer.trainer import make_train_step

    topo = Topology(qwen3_next_lm_cost(**QWEN_ARGS))
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = paddle.optimizer.Adam(learning_rate=3e-4)
    step = make_train_step(topo.loss_fn(compute_dtype=jnp.bfloat16), opt,
                           topo.static_map())
    ids = jnp.zeros((2, 24), jnp.int32)
    feeds = {"ids": Arg(ids, jnp.ones((2, 24))),
             "next_ids": Arg(ids, jnp.ones((2, 24)))}
    text = jax.jit(step).lower(params, opt.init(params), jax.random.PRNGKey(1),
                               feeds).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == QWEN_STEP_SHA256


# sha256 of this cell's own step, and of the kernels' launches as traced, at
# the parent commit of PR 34 (b3f4740), by the two tests' own code: `moe_ffn`
# and `flash_attn` changed for Kimi-VL (docs/kimi_vl.md), the SDAR cell's
# step and launches must not. The step's hash moved at PR 35 (from
# 7a485160...) for the two entries appended to `moe_ffn`'s step statistics
# alone, as the Qwen3-Next step's above did, by the same check, and again
# (from ee60a363...) for the routing plan kept across `moe_ffn`'s checkpoint,
# as the Qwen3-Next step's did
SDAR_STEP_SHA256 = "da73df3694f4d935f65d0691f781bf6e874ac1a922bafd8e5d83dc78d82a9133"
SDAR_LAUNCHES_SHA256 = "ff794b75612227160d059cd2d1c96ef506f65c0c410a28e0780a56f12f3edc92"


def test_the_sdar_step_lowers_as_it_did():
    from paddle_tpu.trainer.trainer import make_train_step

    topo = Topology(sdar_lm_cost(**ARGS))
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = paddle.optimizer.Adam(learning_rate=3e-4)
    step = make_train_step(topo.loss_fn(compute_dtype=jnp.bfloat16), opt,
                           topo.static_map())
    ids = jnp.zeros((2, 20), jnp.int32)
    feeds = {"ids": Arg(ids, jnp.ones((2, 20))),
             "mask_u": Arg(jnp.zeros((2, 20))),
             "noise_t": Arg(jnp.zeros((2, 5)))}
    text = jax.jit(step).lower(params, opt.init(params), jax.random.PRNGKey(1),
                               feeds).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == SDAR_STEP_SHA256


def test_the_sdar_launches_trace_as_they_did():
    """`flash_attn_fwd` / `flash_attn_bwd` at equal head sizes under the
    block-diffusion rule, with grouped heads: grid, block specs, scratch and
    both bodies, as `jax.make_jaxpr` prints them."""
    rule = ("block_diffusion", 256, 4)

    def f(q, k, v):
        o = flash_attn.flash_attention(q, k, v, rule, 2, 128, 128)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    q = jax.ShapeDtypeStruct((1, 512, 4 * 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 512, 2 * 128), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, k))
    assert hashlib.sha256(text.encode()).hexdigest() == SDAR_LAUNCHES_SHA256


# ---- (d) the shares add up --------------------------------------------------------

def test_eight_shares_make_the_uncut_layer(ref):
    """128 experts in 8 shares of 16, top 8, as the configuration cuts them
    (toy widths): each share's layer holds 16 experts and routes over all
    128; the shares' routed parts add up to the reference's layer that holds
    all 128. There is no shared expert to count once."""
    a = dict(ARGS, num_experts=128, num_experts_per_tok=8, experts_held=128,
             first_expert=0)
    d, T = a["hidden_size"], 40
    table = {k.split("_moe.")[1]: v for k, v in ref.param_table(a).items()
             if k.startswith("_s_l0_moe.")}
    full = {k: _normal(i, *shape, scale=0.4)
            for i, (k, (shape, _)) in enumerate(sorted(table.items()))}
    x = _normal(5, 1, T, d)
    want = ref.moe_ffn(full, x[0], a, _ident)
    total, pairs = 0.0, 0
    for s in range(8):
        inp = layer.data(name="x", type=data_type.dense_vector_sequence(d))
        out = layer.moe_ffn(
            input=inp, num_experts=128, top_k=8,
            expert_size=a["moe_intermediate_size"], experts_held=16,
            first_expert=16 * s, tile=8, name="l")
        mine = {"_l." + k: (v[16 * s:16 * s + 16] if k != "router" else v)
                for k, v in full.items()}
        outs, ctx = Topology(out).forward(
            mine, {"x": Arg(x, jnp.ones((1, T)))}, training=True,
            return_ctx=True)
        part = outs["l"].value[0]
        _close(part, ref.routed(full, x[0], a, _ident, first=16 * s, held=16))
        total = total + part
        held, elsewhere, _, dropped = np.asarray(
            ctx.extras["step_stats"]["moe_ffn"]["l"])[:4]
        assert held + elsewhere == T * 8 and dropped == 0
        pairs += held
    assert pairs == T * 8
    _close(total, want)


# ---- the attention layer ---------------------------------------------------------

def test_attention_layer_matches_the_reference(ref):
    a, L = ARGS, ARGS["seq_len"]
    x = layer.data(name="x", type=data_type.dense_vector_sequence(16))
    out = layer.gqa_attention(
        input=x, num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=10000.0,
        mask=("block_diffusion", L, a["block_length"]), name="l")
    topo = Topology(out)
    B, T, d = 2, 2 * L, 16
    params = {k: _normal(i, *s.shape, scale=0.3) + (1.0 if "norm" in k else 0.0)
              for i, (k, s) in enumerate(sorted(topo.param_specs().items()))}
    xs, proj = _normal(77, B, T, d), _normal(78, B, T, d)

    def prog(params, x):
        y = topo.forward(params, {"x": Arg(x, jnp.ones((B, T)))},
                         training=True)["l"].value
        return jnp.sum(y * proj), y

    def plain(params, x):
        p = {k.split(".", 1)[1]: v for k, v in params.items()}
        y = jnp.stack([ref.attention(p, x[r], a, _ident) for r in range(B)])
        return jnp.sum(y * proj), y

    (_, y), g = jax.value_and_grad(prog, argnums=(0, 1), has_aux=True)(params, xs)
    (_, y_ref), g_ref = jax.value_and_grad(plain, argnums=(0, 1),
                                           has_aux=True)(params, xs)
    _close(y, y_ref)
    _close(g[1], g_ref[1])
    for k in params:
        _close(g[0][k], g_ref[0][k])
    with pytest.raises(paddle.utils.error.Error, match="needs 2 x"):
        topo.forward(params, {"x": Arg(xs[:, :30], jnp.ones((B, 30)))},
                     training=True)


# ---- (a) the whole tiny model -----------------------------------------------------

def test_model_declares_the_references_leaves(ref):
    topo = Topology(sdar_lm_cost(**ARGS))
    table = ref.param_table(ARGS)
    assert {k: tuple(s.shape) for k, s in topo.param_specs().items()} \
        == {k: tuple(shape) for k, (shape, _) in table.items()}
    assert not any("shared" in k for k in table)
    mine = topo.init_params(jax.random.PRNGKey(0))
    for k, (shape, (kind, v)) in table.items():
        if kind != "const":
            continue
        if k.endswith(("q_norm", "k_norm")):
            # the layer starts them at 1; the benchmark's weights start them
            # where its configuration assumes (sharp scores, balanced routing)
            assert v == ref.QK_NORM_START and np.all(np.asarray(mine[k]) == 1), k
        else:
            assert np.all(np.asarray(mine[k]) == v), k


def test_model_loss_and_every_gradient_match_the_reference(ref):
    topo = Topology(sdar_lm_cost(**ARGS))
    p = _seeded(ref.param_table(ARGS), 3)
    b, feeds = _feeds(ref, _rows(0, [ARGS["seq_len"]] * 3, ARGS), ARGS)
    loss = topo.loss_fn()
    (c, _), g = jax.jit(jax.value_and_grad(
        lambda p: loss(p, feeds), has_aux=True))(p)
    (c_ref, _), g_ref = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, b, _ident, ARGS), has_aux=True))(p)
    (c_blk, _), g_blk = ref.value_and_grad(p, b, _ident, ARGS)
    assert float(c_ref) > 1.0
    assert abs(float(c) - float(c_ref)) <= 1e-5 * abs(float(c_ref))
    assert abs(float(c_blk) - float(c_ref)) <= 1e-5 * abs(float(c_ref))
    assert set(g) == set(g_ref) == set(g_blk)
    for k in g_ref:
        _close(g[k], g_ref[k], 5e-4)
        _close(g_blk[k], g_ref[k], 5e-5)


def test_three_adam_steps_through_make_train_step_match_the_reference(ref):
    """The loss of each of three steps and every leaf after them, the
    program's `make_train_step` with its Adam against the reference's
    block-by-block gradients with the benchmark's plain Adam."""
    from paddle_tpu.trainer.trainer import make_train_step

    optim = _load("benchmark/reference/optim.py")
    spec = {"kind": "adam", "learning_rate": 1e-3, "beta1": 0.9,
            "beta2": 0.95, "epsilon": 1e-8}
    topo = Topology(sdar_lm_cost(**ARGS))
    opt = paddle.optimizer.Adam(learning_rate=1e-3, beta1=0.9, beta2=0.95,
                                epsilon=1e-8)
    step = jax.jit(make_train_step(topo.loss_fn(), opt, topo.static_map()))
    p = p_start = _seeded(ref.param_table(ARGS), 7)
    p_ref, s_ref, state = dict(p), optim.init(spec, p), opt.init(p)
    for t in range(1, 4):
        b, feeds = _feeds(ref, _rows(t, [ARGS["seq_len"]] * 2, ARGS), ARGS)
        out = step(p, state, jax.random.PRNGKey(t), feeds)
        p, state, cost = out[0], out[1], out[2]
        (c_ref, _), g_ref = ref.value_and_grad(p_ref, b, _ident, ARGS)
        p_ref, s_ref = optim.update(spec, t, p_ref, g_ref, s_ref)
        assert abs(float(cost) - float(c_ref)) <= 2e-5 * abs(float(c_ref))
    # Adam divides a gradient by its own size: where one is round-off its
    # sign decides a whole step of 1e-3, so a leaf may hold a few such
    # elements; every other one agrees to a hundredth of a step
    for k in p_ref:
        off = np.abs(np.asarray(p[k]) - np.asarray(p_ref[k])) > 1e-5
        assert off.sum() <= max(2, 0.005 * off.size), (k, off.sum(), off.size)
        assert float(jnp.linalg.norm(p_ref[k] - p_start[k])) > 0, k


# ---- through the public trainer, with the counters --------------------------------

def test_trains_through_sgd_and_fills_the_diffusion_counters():
    from paddle_tpu.observability import metrics as obs_metrics

    # the feeder pads rows to a power of two: a window of 16 stays 16
    a = dict(ARGS, first_expert=0, seq_len=16)
    cost = sdar_lm_cost(**a)
    params = paddle.parameters.create(cost)
    trainer = paddle.SGD(cost, params,
                         paddle.optimizer.Adam(learning_rate=3e-3),
                         mixed_precision=True)
    rows = _rows(0, [a["seq_len"]] * 4, a)
    costs = []

    def snap(name):
        fam = obs_metrics.default_registry.snapshot().get(name, {"series": {}})
        return sum(fam["series"].values())

    before = {n: snap(n) for n in ("paddle_diffusion_masked_tokens_total",
                                   "paddle_diffusion_positions_total")}
    trainer.train(lambda: iter([rows] * 10), num_passes=1,
                  event_handler=lambda ev: costs.append(ev.cost)
                  if isinstance(ev, paddle.event.EndIteration) else None,
                  feeding={"ids": 0, "mask_u": 1, "noise_t": 2})
    assert len(costs) == 10 and np.all(np.isfinite(costs))
    assert costs[-1] < costs[0]
    masked = sum(int(np.sum((u + 0.5) < np.repeat(t + 0.5 + 1 / 256,
                                                  a["block_length"])[:len(u)]))
                 for _, u, t in rows)
    assert snap("paddle_diffusion_masked_tokens_total") \
        - before["paddle_diffusion_masked_tokens_total"] == 10 * masked
    assert snap("paddle_diffusion_positions_total") \
        - before["paddle_diffusion_positions_total"] == 10 * 4 * 2 * a["seq_len"]
    assert snap("paddle_moe_dropped_total") == 0
