"""The Kimi-VL language model's share (docs/kimi_vl.md): the causal rule and
its tile plan, the attention kernels at head sizes that differ between q/k
and v, multi-head latent attention, the dense gated MLP, the sigmoid-routed
MoE with its selection bias (state a rule moves, not a gradient), and the
whole tiny model through `make_train_step` and `SGD.train`, held to the plain
reference of the benchmark (benchmark/reference/kimi-vl-a3b-ep8.py: float32,
the mask dense, the MoE as a masked loop) on seeded weights. CPU, tiny widths
that keep what the real ones have: a q/k head (8 + 4) wider than the value
head (8), a leading dense layer, fewer experts held than routed over.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import data_type, flops, layer
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.layer import LAYER_REGISTRY
from paddle_tpu.core.topology import Topology
from paddle_tpu.kernels import flash_attn
from paddle_tpu.models.text import kimi_vl_lm_cost

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARGS = dict(vocab_size=50, hidden_size=16, intermediate_size=24,
            moe_intermediate_size=12, num_hidden_layers=3,
            num_attention_heads=4, n_shared_experts=2, n_routed_experts=8,
            routed_scaling_factor=2.446, kv_lora_rank=10, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, num_experts_per_tok=3,
            first_k_dense_replace=1, rope_theta=10000.0, rms_norm_eps=1e-5,
            experts_held=4, first_expert=2, bias_update_rate=1e-3,
            seq_len=None)
GAMMA = ARGS["bias_update_rate"]


def _load(rel):
    path = os.path.join(ROOT, rel)
    spec = importlib.util.spec_from_file_location(
        "ref_" + os.path.basename(path).replace("-", "_")[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/kimi-vl-a3b-ep8.py")


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
    moved off their start so that their gradients are exercised, but for the
    selection biases, which start level as the rule leaves them."""
    out = {}
    for i, (name, (shape, (kind, v))) in enumerate(sorted(table.items())):
        noise = _normal(seed * 1000 + i, *shape)
        if name.endswith(".bias"):
            out[name] = 0.01 * jnp.round(noise)
        else:
            out[name] = v * noise if kind == "normal" else v + 0.1 * noise
    return out


def _moe_layer(a, held=None, first=None, name="l"):
    x = layer.data(name="x", type=data_type.dense_vector_sequence(
        a["hidden_size"]))
    return layer.moe_ffn(
        input=x, num_experts=a["n_routed_experts"],
        top_k=a["num_experts_per_tok"], expert_size=a["moe_intermediate_size"],
        shared_size=a["n_shared_experts"] * a["moe_intermediate_size"],
        experts_held=a["experts_held"] if held is None else held,
        first_expert=a["first_expert"] if first is None else first,
        score="sigmoid", selection_bias=True,
        bias_rate=a["bias_update_rate"],
        route_scale=a["routed_scaling_factor"], shared_gate=False, tile=8,
        name=name)


# ---- the causal rule and its tiles ------------------------------------------

@pytest.mark.parametrize("L,tile", [(40, 16), (72, 32), (64, 128), (33, 8)])
def test_causal_rule_and_tile_classes_match_the_dense_mask(L, tile):
    rule = ("causal", L)
    M = np.tril(np.ones((L, L), bool))
    thr, eq, code = flash_attn.mask_codes(rule, L)
    assert np.array_equal(flash_attn.keep(thr[:, None], eq[:, None],
                                          code[None, :]), M)
    assert list(flash_attn.positions(rule, L)) == list(range(L))
    plan = flash_attn.tile_plan(rule, L, tile, tile)
    kept = 0
    for qi in range(plan.shape[0]):
        for ki in range(plan.shape[1]):
            sub = M[qi * tile:(qi + 1) * tile, ki * tile:(ki + 1) * tile]
            if plan[qi, ki] == flash_attn.SKIPPED:
                assert not sub.any()
            elif plan[qi, ki] == flash_attn.WHOLE:
                assert sub.all() and sub.shape[1] == tile
            else:
                assert sub.any()
            kept += sub.sum() if plan[qi, ki] else 0
    count = _load("benchmark/kernels/mla_attn.py")
    assert kept == M.sum() == count.kept_pairs(L)


def test_at_the_cells_shape_the_lower_triangle_of_tiles_is_kept():
    plan = flash_attn.tile_plan(("causal", 8192), 8192,
                                *flash_attn.tile_sizes(8192))
    assert flash_attn.plan_counts(plan) == (136, 120, 16, 256)


def test_rules_are_checked():
    with pytest.raises(paddle.utils.error.Error, match="needs 40 positions"):
        flash_attn.mask_codes(("causal", 40), 41)
    with pytest.raises(paddle.utils.error.Error, match="is not known"):
        flash_attn.mask_codes(("window", 40), 40)


# ---- the kernels in interpret mode, head sizes Dk != Dv ----------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("L,tile,B,Hkv,G,Dk,Dv", [
    (40, 16, 2, 2, 1, 12, 8),      # the layer's case: G = 1, 12 : 8
    (36, 16, 1, 2, 2, 12, 8),      # a row the tile does not divide, G = 2
    (24, 32, 2, 2, 1, 8, 16),      # one tile; the value head the wider one
    (32, 16, 1, 3, 1, 8, 8)])      # equal sizes under the causal rule
def test_flash_kernels_under_the_causal_rule_and_unequal_heads(
        L, tile, B, Hkv, G, Dk, Dv, dtype, tol):
    """flash_attn_fwd / flash_attn_bwd (interpret mode) against
    `attention_tiles_xla` and against a dense causal softmax: the output and
    the gradients of q, k and v."""
    rule = ("causal", L)
    q = (_normal(1, B, L, Hkv * G * Dk) * Dk ** -0.5).astype(dtype)
    k = _normal(2, B, L, Hkv * Dk).astype(dtype)
    v = _normal(3, B, L, Hkv * Dv).astype(dtype)
    proj = _normal(4, B, L, Hkv * G * Dv)
    M = np.tril(np.ones((L, L), bool))

    def through(attend):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) * proj), argnums=(0, 1, 2))(
                q, k, v)

    def tiles(q, k, v):
        return flash_attn.attention_tiles_xla(
            q.reshape(B, L, Hkv, G, Dk), k.reshape(B, L, Hkv, Dk),
            v.reshape(B, L, Hkv, Dv), rule, tile, tile).reshape(proj.shape)

    def dense(q, k, v):
        s = jnp.einsum("bqngd,bknd->bngqk", q.reshape(B, L, Hkv, G, Dk),
                       k.reshape(B, L, Hkv, Dk)).astype(jnp.float32)
        a = jax.nn.softmax(jnp.where(M, s, -1e30), -1).astype(v.dtype)
        return jnp.einsum("bngqk,bknd->bqngd", a,
                          v.reshape(B, L, Hkv, Dv)).reshape(proj.shape)

    want = through(dense)
    for got in (through(tiles), through(lambda q, k, v: flash_attn.flash_attention(
            q, k, v, rule, Hkv, tile, tile, True))):
        _close(got[0], want[0], tol)
        for a, w in zip(got[1], want[1]):
            assert a.dtype == w.dtype and a.shape == w.shape
            _close(a.astype(jnp.float32), w.astype(jnp.float32), tol)


def test_heads_padded_to_whole_lanes_change_nothing():
    """What `attention` does to a q / k head that is not whole lanes before
    the kernels launch: zeros behind every head, which no score sees."""
    B, L, H, Dk, Dv, Dp = 1, 24, 2, 12, 8, 16
    rule = ("causal", L)
    q, k = _normal(1, B, L, H * Dk) * Dk ** -0.5, _normal(2, B, L, H * Dk)
    v = _normal(3, B, L, H * Dv)
    padded = flash_attn._pad_heads(q, Dk, Dp)
    assert padded.shape == (B, L, H * Dp)
    assert np.array_equal(padded.reshape(B, L, H, Dp)[..., :Dk],
                          q.reshape(B, L, H, Dk))
    assert float(jnp.abs(padded.reshape(B, L, H, Dp)[..., Dk:]).max()) == 0

    def loss(q, k, v, pad):
        if pad:
            q, k = flash_attn._pad_heads(q, Dk, Dp), flash_attn._pad_heads(k, Dk, Dp)
        return jnp.sum(flash_attn.flash_attention(q, k, v, rule, H, 16, 16,
                                                  True) ** 2)

    want = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v, False)
    got = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v, True)
    _close(got[0], want[0], 1e-6)
    for a, w in zip(got[1], want[1]):
        _close(a, w, 1e-5)


def test_the_gate_prices_both_head_sizes(monkeypatch, caplog):
    """192 : 128 goes in as 256 : 128; the backward launch's working set
    follows both sizes; a value head that is not whole lanes takes the tiles
    in XLA, and the log says why."""
    import logging

    both, pair = flash_attn.bwd_vmem_bytes(8192, 256, 1, jnp.bfloat16, 128)
    assert pair == 8192 * (256 + 128) * 4
    assert flash_attn.bwd_vmem_bytes(8192, 128, 1, jnp.bfloat16)[0] < both \
        < flash_attn.bwd_vmem_bytes(8192, 256, 1, jnp.bfloat16)[0]
    assert flash_attn.kernel_gate(8192, 256, 1, jnp.bfloat16, 128)[0]
    ok, why = flash_attn.kernel_gate(8192, 256, 1, jnp.bfloat16, 96)
    assert not ok and "256 : 96" in why
    # the SDAR cell's row, as before
    assert flash_attn.bwd_vmem_bytes(16384, 128, 8, jnp.bfloat16) \
        == flash_attn.bwd_vmem_bytes(16384, 128, 8, jnp.bfloat16, 128)

    seen = {}

    def fake_call(fn, args, batched):
        seen["shapes"] = [a.shape for a in args]
        return jnp.zeros(args[0].shape[:2] + (16 * 128,), args[0].dtype)

    monkeypatch.setattr(flash_attn, "take_pallas",
                        lambda who, what, ok, why, otherwise: ok)
    monkeypatch.setattr(flash_attn, "call_kernel", fake_call)
    q = jnp.zeros((1, 1024, 16 * 192), jnp.bfloat16)
    v = jnp.zeros((1, 1024, 16 * 128), jnp.bfloat16)
    with caplog.at_level(logging.INFO, logger="paddle_tpu"):
        flash_attn.attention("padl", q, q, v, ("causal", 1024), 16)
    assert seen["shapes"] == [(1, 1024, 16 * 256), (1, 1024, 16 * 256),
                              (1, 1024, 16 * 128)]
    lines = [r.getMessage() for r in caplog.records if "padl" in r.getMessage()]
    assert any("mask ('causal', 1024): 3 of 4 tiles of 512 x 512 kept "
               "(1 whole, 2 partial)" in m for m in lines), lines
    assert any("q and k heads of 192 go in as 256 lanes" in m for m in lines)


# ---- each layer against the reference's function ------------------------------

def _check_layer(out, ref_fn, x, static=()):
    topo = Topology(out)
    B, T, d = x.shape
    params = {k: _normal(i, *s.shape, scale=0.3) + (1.0 if "norm" in k else 0.0)
              for i, (k, s) in enumerate(sorted(topo.param_specs().items()))}
    for k in static:
        params[k] = 0.02 * jnp.round(_normal(9, *params[k].shape))
    proj = _normal(78, B, T, d)

    def prog(params, x):
        y = topo.forward(params, {"x": Arg(x, jnp.ones((B, T)))},
                         training=True)["l"].value
        return jnp.sum(y * proj), y

    def plain(params, x):
        p = {k.split(".", 1)[1]: v for k, v in params.items()}
        y = jnp.stack([ref_fn(p, x[r]) for r in range(B)])
        return jnp.sum(y * proj), y

    (_, y), g = jax.value_and_grad(prog, argnums=(0, 1), has_aux=True)(params, x)
    (_, y_ref), g_ref = jax.value_and_grad(plain, argnums=(0, 1),
                                           has_aux=True)(params, x)
    _close(y, y_ref)
    _close(g[1], g_ref[1])
    for k in params:
        _close(g[0][k], g_ref[0][k])
    return topo, params, g[0]


def test_mla_attention_matches_the_reference(ref):
    a = ARGS
    x = layer.data(name="x", type=data_type.dense_vector_sequence(16))
    out = layer.mla_attention(
        input=x, num_heads=4, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, kv_lora_rank=10, rope_theta=a["rope_theta"],
        eps=a["rms_norm_eps"], mask=("causal", 21), name="l")
    topo, params, _ = _check_layer(
        out, lambda p, row: ref.attention(p, row, a, _ident),
        _normal(77, 2, 21, 16))
    assert {k: tuple(s.shape) for k, s in topo.param_specs().items()} == {
        "_l.wq": (16, 48), "_l.wkva": (16, 14), "_l.kv_norm": (10,),
        "_l.wkvb": (10, 64), "_l.wo": (32, 16)}
    with pytest.raises(paddle.utils.error.Error, match="needs 21 positions"):
        topo.forward(params, {"x": Arg(_normal(1, 2, 30, 16), jnp.ones((2, 30)))},
                     training=True)


def test_gated_mlp_matches_the_reference(ref):
    x = layer.data(name="x", type=data_type.dense_vector_sequence(16))
    out = layer.gated_mlp(input=x, size=24, name="l")
    topo, _, _ = _check_layer(
        out, lambda p, row: ref.mlp(row, p["wg"], p["wu"], p["wd"], _ident),
        _normal(5, 2, 9, 16))
    assert sorted(topo.param_specs()) == ["_l.wd", "_l.wg", "_l.wu"]


def test_sigmoid_moe_with_a_selection_bias_matches_the_reference(ref):
    """The layer's output and every gradient; the bias decides the choice
    (with it off the output differs), gets no gradient, and is static."""
    a = ARGS
    topo, params, g = _check_layer(
        _moe_layer(a), lambda p, row: ref.moe_ffn(p, row, a, _ident),
        _normal(9, 2, 21, 16), static=("_l.bias",))
    assert sorted(topo.param_specs()) == [
        "_l.bias", "_l.router", "_l.shared_wd", "_l.shared_wg", "_l.shared_wu",
        "_l.wd", "_l.wg", "_l.wu"]
    assert topo.static_map()["_l.bias"] and not topo.static_map()["_l.router"]
    assert float(jnp.abs(g["_l.bias"]).max()) == 0.0
    assert float(jnp.abs(params["_l.bias"]).max()) > 0
    x = Arg(_normal(9, 2, 21, 16), jnp.ones((2, 21)))
    with_bias = topo.forward(params, {"x": x}, training=True)["l"].value
    level = dict(params, **{"_l.bias": jnp.zeros_like(params["_l.bias"])})
    without = topo.forward(level, {"x": x}, training=True)["l"].value
    assert float(jnp.abs(with_bias - without).max()) > 1e-3


def test_the_bias_moves_by_gamma_against_the_load_and_skips_padding(ref):
    a = dict(ARGS, experts_held=8, first_expert=0)
    topo = Topology(_moe_layer(a))
    params = {k: _normal(i, *s.shape, scale=0.5)
              for i, (k, s) in enumerate(sorted(topo.param_specs().items()))}
    params["_l.bias"] = jnp.zeros((8,))
    T = 12
    xs = _normal(2, 2, T, 16)
    mask = jnp.asarray([[1.0] * T, [1.0] * 5 + [0.0] * (T - 5)])
    _, ctx = topo.forward(params, {"x": Arg(xs, mask)}, training=True,
                          return_ctx=True)
    new = np.asarray(ctx.extras["batch_stats"]["l"]["bias"])
    p = {k.split(".", 1)[1]: v for k, v in params.items()}
    counts = sum(np.asarray(ref.pair_counts(p, xs[r], mask[r], a, _ident))
                 for r in range(2))
    assert counts.sum() == (T + 5) * a["num_experts_per_tok"]
    assert np.array_equal(new, GAMMA * np.sign(counts.mean() - counts)
                          .astype(np.float32))
    assert set(np.round(np.abs(new) / GAMMA).tolist()) <= {0.0, 1.0}
    assert np.array_equal(new, np.asarray(
        ref.next_bias(p["bias"], jnp.asarray(counts), a)))
    stats = np.asarray(ctx.extras["step_stats"]["moe_ffn"]["l"])
    held, elsewhere, _, dropped, _, _, bias_max = stats
    assert (held, elsewhere, dropped) == ((T + 5) * 3, 0, 0)
    assert bias_max == np.float32(GAMMA)
    # outside training nothing is handed to the trainer
    _, ctx = topo.forward(params, {"x": Arg(xs, mask)}, training=False,
                          return_ctx=True)
    assert "batch_stats" not in ctx.extras


def test_the_rule_levels_a_skewed_router():
    """Repeated on one batch, the rule moves pairs from the busiest experts
    to the idlest: the largest load over the mean falls."""
    a = dict(ARGS, experts_held=8, first_expert=0, bias_update_rate=0.05)
    topo = Topology(_moe_layer(a))
    params = {k: _normal(i, *s.shape, scale=0.5)
              for i, (k, s) in enumerate(sorted(topo.param_specs().items()))}
    params["_l.router"] = params["_l.router"].at[:, 0].add(1.5)   # a favourite
    params["_l.bias"] = jnp.zeros((8,))
    x = Arg(_normal(3, 4, 32, 16), jnp.ones((4, 32)))

    @jax.jit
    def step(params):
        _, ctx = topo.forward(params, {"x": x}, training=True, return_ctx=True)
        return (ctx.extras["batch_stats"]["l"]["bias"],
                ctx.extras["step_stats"]["moe_ffn"]["l"][2])

    loads = []
    for _ in range(12):
        params["_l.bias"], load = step(params)
        loads.append(float(load))
    assert loads[-1] < loads[0] - 0.05, loads


# ---- the shares add up ------------------------------------------------------------

def test_eight_shares_and_the_shared_expert_once_make_the_uncut_layer(ref):
    """64 experts in 8 shares of 8, top 6, as the configuration cuts them
    (toy widths): each share's layer holds 8 experts and routes over all 64
    with the same bias; the shares' routed parts and ONE shared part add up
    to the reference's layer that holds all 64."""
    a = dict(ARGS, n_routed_experts=64, num_experts_per_tok=6, experts_held=64,
             first_expert=0)
    d, T = a["hidden_size"], 40
    table = {k.split("_moe.")[1]: v for k, v in ref.param_table(a).items()
             if k.startswith("_k_l1_moe.")}
    full = {k: _normal(i, *shape, scale=0.4)
            for i, (k, (shape, _)) in enumerate(sorted(table.items()))}
    full["bias"] = 0.05 * jnp.round(_normal(3, 64))
    x = _normal(5, 1, T, d)
    want = ref.moe_ffn(full, x[0], a, _ident)
    total, pairs = ref.shared(full, x[0], a, _ident), 0
    for s in range(8):
        mine = {"_l." + k: (v[8 * s:8 * s + 8] if k in ("wg", "wu", "wd") else v)
                for k, v in full.items()}
        outs, ctx = Topology(_moe_layer(a, held=8, first=8 * s)).forward(
            mine, {"x": Arg(x, jnp.ones((1, T)))}, training=True,
            return_ctx=True)
        part = outs["l"].value[0] - ref.shared(full, x[0], a, _ident)
        _close(part, ref.routed(full, x[0], a, _ident, first=8 * s, held=8))
        total = total + part
        held, elsewhere, _, dropped = np.asarray(
            ctx.extras["step_stats"]["moe_ffn"]["l"])[:4]
        assert held + elsewhere == T * 6 and dropped == 0
        pairs += held
        # every share counts ALL experts' pairs of its own tokens alike
        assert np.array_equal(
            np.asarray(ctx.extras["batch_stats"]["l"]["bias"]),
            np.asarray(ref.next_bias(full["bias"], ref.pair_counts(
                full, x[0], jnp.ones((T,)), a, _ident), a)))
    assert pairs == T * 6
    _close(total, want)


# ---- the whole tiny model ---------------------------------------------------------

def _batch(ref, lens, seed=0):
    rng = np.random.default_rng(seed)
    rows = [(rng.integers(2, ARGS["vocab_size"], n).tolist(),
             rng.integers(2, ARGS["vocab_size"], n).tolist()) for n in lens]
    b = {k: jnp.asarray(v) for k, v in ref.pad(rows, ARGS).items()}
    feeds = {"ids": Arg(b["ids"], b["ids_mask"]),
             "next_ids": Arg(b["next_ids"], b["next_ids_mask"])}
    return rows, b, feeds


def test_model_declares_the_references_leaves(ref):
    topo = Topology(kimi_vl_lm_cost(**ARGS))
    table = ref.param_table(ARGS)
    assert {k: tuple(s.shape) for k, s in topo.param_specs().items()} \
        == {k: tuple(shape) for k, (shape, _) in table.items()}
    static = {k for k, v in topo.static_map().items() if v}
    assert static == set(ref.static_names(ARGS)) \
        == {"_k_l1_moe.bias", "_k_l2_moe.bias"}
    assert "_k_l0_mlp.wg" in table and "_k_l0_moe.router" not in table
    mine = topo.init_params(jax.random.PRNGKey(0))
    for k, (shape, (kind, v)) in table.items():
        if kind == "const":
            assert np.all(np.asarray(mine[k]) == v), k
    # the benchmark's weights start the embedding's rows where its
    # configuration assumes; the layer's own default is untouched
    assert table["_k_emb.w0"][1] == ("normal", ref.EMBEDDING_START)


def test_model_loss_every_gradient_and_the_new_bias_match_the_reference(ref):
    topo = Topology(kimi_vl_lm_cost(**ARGS))
    p = _seeded(ref.param_table(ARGS), 3)
    _, b, feeds = _batch(ref, [70, 64, 33])
    loss = topo.loss_fn()
    (c, (_, aux)), g = jax.jit(jax.value_and_grad(
        lambda p: loss(p, feeds), has_aux=True))(p)
    (c_ref, aux_ref), g_ref = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, b, _ident, ARGS), has_aux=True))(p)
    (c_blk, aux_blk), g_blk = ref.value_and_grad(p, b, _ident, ARGS)
    assert float(c_ref) > 1.0
    assert abs(float(c) - float(c_ref)) <= 1e-5 * abs(float(c_ref))
    assert abs(float(c_blk) - float(c_ref)) <= 1e-5 * abs(float(c_ref))
    assert set(g) == set(g_ref) == set(g_blk)
    for k in g_ref:
        _close(g[k], g_ref[k], 5e-4)
        _close(g_blk[k], g_ref[k], 5e-5)
    assert set(aux) == set(aux_ref) == set(aux_blk) == set(ref.static_names(ARGS))
    for k in aux_ref:
        assert float(jnp.abs(g[k]).max()) == 0.0
        moved = np.asarray(aux_ref[k]) - np.asarray(p[k])
        assert np.any(moved != 0)
        assert np.allclose(np.abs(moved)[moved != 0], GAMMA, rtol=1e-4)
        assert np.array_equal(np.asarray(aux[k]), np.asarray(aux_ref[k])), k
        assert np.array_equal(np.asarray(aux_blk[k]), np.asarray(aux_ref[k])), k


def test_three_adam_steps_through_make_train_step_match_the_reference(ref):
    """The loss of each of three steps and every leaf after them, the
    selection biases among them: the program's `make_train_step` with its
    Adam against the reference's block-by-block gradients with the
    benchmark's plain Adam, the biases set from the reference's `aux` as
    benchmark/correct.py sets them."""
    from paddle_tpu.trainer.trainer import make_train_step

    optim = _load("benchmark/reference/optim.py")
    spec = {"kind": "adam", "learning_rate": 1e-3, "beta1": 0.9,
            "beta2": 0.95, "epsilon": 1e-8}
    topo = Topology(kimi_vl_lm_cost(**ARGS))
    opt = paddle.optimizer.Adam(learning_rate=1e-3, beta1=0.9, beta2=0.95,
                                epsilon=1e-8)
    static = set(ref.static_names(ARGS))
    step = jax.jit(make_train_step(topo.loss_fn(), opt, topo.static_map()))
    p = p_start = _seeded(ref.param_table(ARGS), 7)
    p_ref, s_ref, state = dict(p), optim.init(spec, p), opt.init(p)
    for t in range(1, 4):
        _, b, feeds = _batch(ref, [40, 40], seed=t)
        out = step(p, state, jax.random.PRNGKey(t), feeds)
        p, state, cost = out[0], out[1], out[2]
        (c_ref, aux), g_ref = ref.value_and_grad(p_ref, b, _ident, ARGS)
        p_ref, s_ref = optim.update(spec, t, p_ref, g_ref, s_ref, static)
        p_ref.update(aux)
        assert abs(float(cost) - float(c_ref)) <= 2e-5 * abs(float(c_ref))
    for k in p_ref:
        if k in static:
            assert np.array_equal(np.asarray(p[k]), np.asarray(p_ref[k])), k
            steps = (np.asarray(p[k]) - np.asarray(p_start[k])) / GAMMA
            assert np.abs(steps).max() <= 3 + 1e-3 and np.abs(steps).max() >= 1
            continue
        off = np.abs(np.asarray(p[k]) - np.asarray(p_ref[k])) > 1e-5
        assert off.sum() <= max(2, 0.005 * off.size), (k, off.sum(), off.size)
        assert float(jnp.linalg.norm(p_ref[k] - p_start[k])) > 0, k


# ---- through the public trainer, with the counters --------------------------------

def test_trains_through_sgd_moves_the_bias_and_fills_the_gauge():
    from paddle_tpu.observability import metrics as obs_metrics

    cost = kimi_vl_lm_cost(**dict(ARGS, first_expert=0))
    params = paddle.parameters.create(cost)
    trainer = paddle.SGD(cost, params,
                         paddle.optimizer.Adam(learning_rate=3e-3),
                         mixed_precision=True)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(2, 50, 24).tolist() for _ in range(4)]
    rows = [([0] + s, s + [1]) for s in seqs]
    costs = []

    def tokens():
        fam = obs_metrics.default_registry.snapshot()
        return {tuple(sorted(dict(k).items())): v for k, v in
                fam.get("paddle_moe_tokens_total", {"series": {}})["series"].items()}

    before = tokens()
    trainer.train(lambda: iter([rows] * 12), num_passes=1,
                  event_handler=lambda ev: costs.append(ev.cost)
                  if isinstance(ev, paddle.event.EndIteration) else None,
                  feeding={"ids": 0, "next_ids": 1})
    after = tokens()
    assert len(costs) == 12 and np.all(np.isfinite(costs))
    assert costs[-1] < costs[0]
    key = lambda result: (("layer", "k_l1_moe"), ("result", result))
    held = after[key("held")] - before.get(key("held"), 0)
    elsewhere = after[key("elsewhere")] - before.get(key("elsewhere"), 0)
    assert held + elsewhere == 12 * 4 * 25 * ARGS["num_experts_per_tok"]
    assert 0 < held < held + elsewhere
    snap = obs_metrics.default_registry.snapshot()
    assert sum(snap["paddle_moe_dropped_total"]["series"].values()) == 0
    gauge = {dict(k)["layer"]: v for k, v in
             snap["paddle_moe_selection_bias_max_abs"]["series"].items()}
    assert set(gauge) >= {"k_l1_moe", "k_l2_moe"}
    for lname in ("k_l1_moe", "k_l2_moe"):
        b = np.asarray(trainer.parameters.as_dict()[f"_{lname}.bias"])
        assert b.dtype == np.float32          # static leaves stay float32
        assert 0 < np.abs(b).max() <= 12 * GAMMA + 1e-6
        assert abs(gauge[lname] - np.abs(b).max()) < 1e-7
        # every move is a whole number of steps of gamma
        assert np.allclose(b / GAMMA, np.round(b / GAMMA), atol=1e-3)


# ---- registry and FLOP pricing ------------------------------------------------------

def test_the_new_types_are_registered():
    for t in ("mla_attention", "gated_mlp", "moe_ffn"):
        assert LAYER_REGISTRY.get(t) is not None


def test_flops_price_the_new_layers_by_the_work_done_here():
    a = dict(ARGS, first_expert=0, seq_len=10)
    topo = Topology(kimi_vl_lm_cost(**a))
    T, d, H = 10, 16, 4
    E, k, held, I = 8, 3, a["experts_held"], a["moe_intermediate_size"]
    by = {l.name: flops.layer_fwd_flops(topo, l, 1, T) for l in topo.layers}
    proj = d * H * 12 + d * (10 + 4) + 10 * H * 16 + H * 8 * d
    assert by["k_l0_attn"] == 2.0 * T * proj + 2.0 * H * (12 + 8) * T * (T + 1) / 2
    assert by["k_l0_mlp"] == 2.0 * T * 3 * d * a["intermediate_size"]
    # router over all 8, two shared experts as one MLP, no gate, 3 x 4/8
    # routed experts; the bias is no product
    assert by["k_l1_moe"] == 2.0 * T * (d * E + 3 * d * 2 * I
                                        + k * held / E * 3 * d * I)
    count = _load("benchmark/flops/kimi-vl-a3b-ep8.py")
    mine = sum(by.values())
    theirs = 2 * T * sum(count.forward_macs_per_token(a, T).values())
    assert abs(mine - theirs) <= 1e-9 * theirs
