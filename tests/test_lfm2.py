"""LFM2-8B-A1B's decoder share (docs/lfm2.md): the gated short convolution
(the layer, and `short_conv_fwd/bwd` in interpret mode against its XLA
form), grouped-query attention at heads of 64 under the causal rule (the
flash kernels with q, k and v padded to 128 lanes), the sigmoid-routed MoE
with an expert bias and its weights over their sum + 1e-6, the four shares
of a block adding up to the uncut block, and the whole tiny model through
`make_train_step` and `SGD.train`, held to the plain reference of the
benchmark (benchmark/reference/lfm2-8b-a1b-ep4.py: float32, the convolution
as a sum of shifted rows, the mask dense, the MoE as a masked loop) on seeded
weights. CPU, tiny widths that keep what the real ones have: conv and
attention blocks in the published order with one left out, leading dense
blocks, fewer experts held than routed over, a head tied to the embedding.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import activation as act
from paddle_tpu import data_type, flops, layer
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.layer import LAYER_REGISTRY
from paddle_tpu.core.topology import Topology
from paddle_tpu.kernels import flash_attn, short_conv
from paddle_tpu.models.text import lfm2_moe_lm_cost

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv"]
ARGS = dict(vocab_size=50, hidden_size=16, intermediate_size=24,
            moe_intermediate_size=12, num_hidden_layers=5,
            num_attention_heads=4, num_key_value_heads=2, layer_types=TYPES,
            layers_held=[0, 2, 3, 4, 5], num_dense_layers=2, num_experts=8,
            num_experts_per_tok=3, routed_scaling_factor=1.0, conv_L_cache=3,
            rope_theta=10000.0, norm_eps=1e-5, experts_held=4, first_expert=2,
            bias_update_rate=1e-3, conv_layers=4, attention_layers=1,
            seq_len=None, name="f")
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
    return _load("benchmark/reference/lfm2-8b-a1b-ep4.py")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _ident(x):
    return x


def _normal(seed, *shape, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
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


# ---- the gated short convolution --------------------------------------------

def _conv_inputs(B, T, D, K, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, T, 3 * D)).astype(dtype),
            jax.random.normal(ks[1], (D, K)) / K ** 0.5,
            jax.random.normal(ks[2], (B, T, D)).astype(dtype))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("T,D,K,bt,bd", [
    (48, 256, 3, 16, 128),     # the cell's taps; 3 row blocks, 2 lane blocks
    (32, 128, 2, 16, 128),
    (64, 384, 4, 32, 128),     # 3 lane blocks, a halo of 3 rows
    (16, 128, 3, 16, 128),     # one row block: the halo is the row's start
])
def test_short_conv_kernels_match_the_xla_form(T, D, K, bt, bd, dtype, tol):
    """`short_conv_fwd` / `short_conv_bwd` (interpret mode) against
    `short_conv_xla` in float32 on the same (rounded) inputs: y, d[B ; C ;
    x~] in the projection's layout, and the taps' gradient summed over rows
    and row blocks (in float32 by the kernel; XLA's form in bf16 sums it in
    bf16 and is itself off by more than a rounding)."""
    bcx, w, dy = _conv_inputs(2, T, D, K, dtype)
    w = w.astype(dtype).astype(jnp.float32)
    got_y, vjp = jax.vjp(lambda b, w: short_conv.short_conv(b, w, bt, bd, True),
                         bcx, w)
    f32 = jnp.float32
    want_y, want_vjp = jax.vjp(short_conv.short_conv_xla, bcx.astype(f32), w)
    assert got_y.dtype == dtype and got_y.shape == (2, T, D)
    _close(got_y, want_y, tol)
    for got, want in zip(vjp(dy), want_vjp(dy.astype(f32))):
        assert got.shape == want.shape
        _close(got, want, tol)


def test_short_conv_gate_and_blocks():
    assert short_conv.blocks(8192, 2048) == (512, 512)
    assert short_conv.blocks(48, 384) == (16, 128)
    assert short_conv.gate(8192, 2048, 3, jnp.bfloat16) == (True, "")
    for args in ((8192, 2000, 3, jnp.bfloat16), (40, 2048, 3, jnp.bfloat16),
                 (8192, 2048, 12, jnp.bfloat16), (8192, 2048, 3, jnp.float16)):
        ok, why = short_conv.gate(*args)
        assert not ok and why, args


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


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_short_conv_layer_matches_the_reference(ref, monkeypatch, kernels):
    """The layer, its input's and every weight's gradient, against the
    reference's sum of shifted rows: in XLA, and with the launches taken (in
    interpret mode) where the widths are whole lanes."""
    d, T = (128, 32) if kernels else (16, 21)
    if kernels:
        real = short_conv.short_conv
        monkeypatch.setattr(short_conv, "take_pallas",
                            lambda who, kernel, eligible=True, why_not="",
                            **kw: eligible)
        monkeypatch.setattr(short_conv, "short_conv",
                            lambda b, w, bt, bd: real(b, w, bt, bd, True))
    x = layer.data(name="x", type=data_type.dense_vector_sequence(d))
    out = layer.short_conv(input=x, conv_kernel=3, name="l")
    topo, _, _ = _check_layer(
        out, lambda p, row: ref.short_conv(p, row, ARGS, _ident),
        _normal(77, 2, T, d))
    assert {k: tuple(s.shape) for k, s in topo.param_specs().items()} == {
        "_l.in_proj": (d, 3 * d), "_l.conv": (d, 3), "_l.out_proj": (d, d)}


# ---- attention at heads of 64 ----------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bf16"])
def test_flash_kernels_at_heads_of_64_under_the_causal_rule(monkeypatch, dtype,
                                                            tol):
    """`flash_attn.attention` with the launches taken (interpret mode) at
    q / k / v heads of 64: each goes in padded to 128 lanes and the output's
    zero lanes are cut off; output and gradients of q, k, v against
    `attention_tiles_xla`, over several tiles of the causal square."""
    real = flash_attn.flash_attention
    monkeypatch.setattr(flash_attn, "take_pallas",
                        lambda who, kernel, eligible=True, why_not="", **kw:
                        eligible)
    monkeypatch.setattr(flash_attn, "tile_sizes", lambda T: (16, 16))
    monkeypatch.setattr(flash_attn, "flash_attention",
                        lambda *a: real(*a, True))
    B, L, Hkv, G, D = 2, 40, 2, 2, 64
    rule = ("causal", L)
    q = (_normal(1, B, L, Hkv * G * D) * D ** -0.5).astype(dtype)
    k = _normal(2, B, L, Hkv * D).astype(dtype)
    v = _normal(3, B, L, Hkv * D).astype(dtype)
    proj = _normal(4, B, L, Hkv * G * D)

    def through(attend):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) * proj), argnums=(0, 1, 2))(
                q, k, v)

    def tiles(q, k, v):
        return flash_attn.attention_tiles_xla(
            q.reshape(B, L, Hkv, G, D), k.reshape(B, L, Hkv, D),
            v.reshape(B, L, Hkv, D), rule, 16, 16).reshape(proj.shape)

    want = through(tiles)
    got = through(lambda q, k, v: flash_attn.attention("h64", q, k, v, rule,
                                                       Hkv))
    _close(got[0], want[0], tol)
    for a, w in zip(got[1], want[1]):
        assert a.dtype == w.dtype and a.shape == w.shape
        _close(a, w, tol)


# the gate's answers at the cells' shapes, by the parent of PR 38 (1571fd4)
GATE_AT_THE_PARENT = {
    (16384, 128, 8, "bfloat16", None): 49.8, (8192, 192, 1, "bfloat16", 128): 35.1,
    (16384, 128, 8, "float32", None): 73.4, (8192, 192, 1, "float32", 128): 49.8}


def test_the_gate_admits_heads_of_64_and_keeps_the_cells_answers():
    """Heads of 64 go in as 128 lanes under the gate, priced as such; the
    SDAR and Kimi-VL cells' shapes get the answers they got before."""
    for (T, D, G, dtype, Dv), mb in GATE_AT_THE_PARENT.items():
        assert flash_attn.kernel_gate(T, D, G, jnp.dtype(dtype), Dv) == (
            True, f"over a row of {T} positions flash_attn_bwd would hold "
            f"{mb} MB in VMEM, dK and dV whole, against the 88.1 MB of the "
            "kernel's gate")
    ok, why = flash_attn.kernel_gate(8192, 64, 4, jnp.bfloat16)
    assert ok and "28.8 MB" in why, why
    for D, Dv in ((96, None), (64, 32), (256, 96)):
        assert not flash_attn.kernel_gate(8192, D, 4, jnp.bfloat16, Dv)[0]


def test_gqa_attention_at_head_64_matches_the_reference(ref):
    a = ARGS
    x = layer.data(name="x", type=data_type.dense_vector_sequence(16))
    out = layer.gqa_attention(input=x, num_heads=4, num_kv_heads=2,
                              head_dim=4, rope_theta=a["rope_theta"],
                              eps=a["norm_eps"], mask=("causal", None),
                              name="l")
    topo, _, _ = _check_layer(
        out, lambda p, row: ref.attention(p, row, a, _ident),
        _normal(7, 2, 21, 16))
    assert {k: tuple(s.shape) for k, s in topo.param_specs().items()} == {
        "_l.wq": (16, 16), "_l.wk": (16, 8), "_l.wv": (16, 8),
        "_l.wo": (16, 16), "_l.q_norm": (4,), "_l.k_norm": (4,)}


# ---- the MoE -----------------------------------------------------------------------

def _moe_layer(a, held=None, first=None, name="l", x=None):
    if x is None:
        x = layer.data(name="x", type=data_type.dense_vector_sequence(
            a["hidden_size"]))
    return layer.moe_ffn(
        input=x, num_experts=a["num_experts"], top_k=a["num_experts_per_tok"],
        expert_size=a["moe_intermediate_size"],
        experts_held=a["experts_held"] if held is None else held,
        first_expert=a["first_expert"] if first is None else first,
        score="sigmoid", selection_bias=True, bias_rate=a["bias_update_rate"],
        route_scale=a["routed_scaling_factor"], route_norm_eps=1e-6, tile=8,
        name=name)


def test_sigmoid_moe_with_an_expert_bias_matches_the_reference(ref):
    """No shared expert: the layer is the routed part alone; its weights are
    the chosen scores over their sum + 1e-6."""
    a = ARGS
    topo, params, g = _check_layer(
        _moe_layer(a), lambda p, row: ref.moe_ffn(p, row, a, _ident),
        _normal(9, 2, 21, 16), static=("_l.bias",))
    assert sorted(topo.param_specs()) == [
        "_l.bias", "_l.router", "_l.wd", "_l.wg", "_l.wu"]
    assert float(jnp.abs(g["_l.bias"]).max()) == 0.0
    # the 1e-6 is in the program's weights as in the reference's
    x = _normal(9, 21, 16)
    p = {k.split(".", 1)[1]: v for k, v in params.items()}
    idx, w = ref.route(p, x, a, _ident)
    s = jax.nn.sigmoid(x @ p["router"])
    chosen = jnp.take_along_axis(s, idx, -1)
    _close(w, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), 1e-7)


# ---- the shares add up --------------------------------------------------------------

def test_four_shares_with_the_mixer_once_make_the_uncut_block(ref):
    """One conv block with its MoE as the configuration cuts it (toy widths,
    32 experts in 4 shares of 8, top 4): every share computes the same mixer
    and residual h, routes over all 32 with the same bias and computes its 8
    experts; h once plus the four shares' routed parts is the reference's
    block that holds all 32."""
    a = dict(ARGS, num_experts=32, num_experts_per_tok=4, experts_held=32,
             first_expert=0)
    d, T = a["hidden_size"], 40
    x = layer.data(name="x", type=data_type.dense_vector_sequence(d))
    normed = layer.rms_norm(input=x, eps=a["norm_eps"], zero_centered=False,
                            name="in")
    mixed = layer.short_conv(input=normed, conv_kernel=3, name="mix")
    h = layer.addto(input=[x, mixed], act=act.Linear(), bias_attr=False,
                    name="h")
    post = layer.rms_norm(input=h, eps=a["norm_eps"], zero_centered=False,
                          name="post")
    full = _seeded({k: v for k, v in ref.param_table(a).items()
                    if k.startswith("_f_l3_")}, 5)
    full["_f_l3_moe.bias"] = 0.05 * jnp.round(_normal(3, 32))
    pb = ref._block_params(full, a, 2)            # published block 3
    assert set(pb["mixer"]) == {"in_proj", "conv", "out_proj"}
    xs = _normal(6, 1, T, d)
    want, counts = ref._block(pb, xs[0], jnp.ones((T,)), a, _ident, False)
    hs, total, pairs = [], 0.0, 0
    for s in range(4):
        share = dict(a, experts_held=8, first_expert=8 * s)
        moe = _moe_layer(share, name="moe", x=post)
        out = layer.addto(input=[h, moe], act=act.Linear(), bias_attr=False,
                          name="out")
        mine = {"_in.w0": pb["in_norm"], "_post.w0": pb["post_norm"],
                **{f"_mix.{k}": v for k, v in pb["mixer"].items()},
                **{f"_moe.{k}": v[8 * s:8 * s + 8] if k in ("wg", "wu", "wd")
                   else v for k, v in pb["ffn"].items()}}
        outs, ctx = Topology([out, h]).forward(
            mine, {"x": Arg(xs, jnp.ones((1, T)))}, training=True,
            return_ctx=True)
        hs.append(outs["h"].value[0])
        total = total + (outs["out"].value[0] - hs[-1])
        held, elsewhere, _, dropped = np.asarray(
            ctx.extras["step_stats"]["moe_ffn"]["moe"])[:4]
        assert held + elsewhere == T * 4 and dropped == 0
        pairs += held
        # every share counts ALL experts' pairs of its own tokens alike
        assert np.array_equal(
            np.asarray(ctx.extras["batch_stats"]["moe"]["bias"]),
            np.asarray(ref.next_bias(pb["ffn"]["bias"], counts, a)))
    assert all(np.array_equal(np.asarray(hs[0]), np.asarray(h)) for h in hs)
    assert pairs == T * 4
    _close(hs[0] + total, want)


# ---- the whole tiny model -------------------------------------------------------------

def _batch(ref, lens, seed=0):
    rng = np.random.default_rng(seed)
    rows = [(rng.integers(2, ARGS["vocab_size"], n).tolist(),
             rng.integers(2, ARGS["vocab_size"], n).tolist()) for n in lens]
    b = {k: jnp.asarray(v) for k, v in ref.pad(rows, ARGS).items()}
    feeds = {"ids": Arg(b["ids"], b["ids_mask"]),
             "next_ids": Arg(b["next_ids"], b["next_ids_mask"])}
    return rows, b, feeds


def test_model_declares_the_references_leaves(ref):
    topo = Topology(lfm2_moe_lm_cost(**ARGS))
    table = ref.param_table(ARGS)
    assert {k: tuple(s.shape) for k, s in topo.param_specs().items()} \
        == {k: tuple(shape) for k, (shape, _) in table.items()}
    static = {k for k, v in topo.static_map().items() if v}
    assert static == set(ref.static_names(ARGS)) == {
        f"_f_l{l}_moe.bias" for l in (2, 3, 4, 5)}
    # the published blocks held, by index: block 1 is another stage's
    assert "_f_l0_mlp.wg" in table and "_f_l0_conv.in_proj" in table
    assert "_f_l2_attn.wq" in table and "_f_l2_moe.router" in table
    assert not any(k.startswith("_f_l1_") for k in table)
    # one table: the embedding's, which the head reads transposed
    assert not any("head" in k for k in table)
    assert table["_f_emb.w0"][0] == (50, 16)


def test_model_loss_every_gradient_and_the_new_bias_match_the_reference(ref):
    topo = Topology(lfm2_moe_lm_cost(**ARGS))
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
    expert biases among them: the program's `make_train_step` with its Adam
    against the reference's block-by-block gradients with the benchmark's
    plain Adam, the biases set from the reference's `aux` as
    benchmark/correct.py sets them."""
    from paddle_tpu.trainer.trainer import make_train_step

    optim = _load("benchmark/reference/optim.py")
    spec = {"kind": "adam", "learning_rate": 1e-3, "beta1": 0.9,
            "beta2": 0.95, "epsilon": 1e-8}
    topo = Topology(lfm2_moe_lm_cost(**ARGS))
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


def test_trains_through_sgd_moves_the_bias_and_fills_the_gauge():
    from paddle_tpu.observability import metrics as obs_metrics

    cost = lfm2_moe_lm_cost(**dict(ARGS, first_expert=0))
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
    key = lambda result: (("layer", "f_l2_moe"), ("result", result))
    held = after[key("held")] - before.get(key("held"), 0)
    elsewhere = after[key("elsewhere")] - before.get(key("elsewhere"), 0)
    assert held + elsewhere == 12 * 4 * 25 * ARGS["num_experts_per_tok"]
    assert 0 < held < held + elsewhere
    snap = obs_metrics.default_registry.snapshot()
    assert sum(snap["paddle_moe_dropped_total"]["series"].values()) == 0
    gauge = {dict(k)["layer"]: v for k, v in
             snap["paddle_moe_selection_bias_max_abs"]["series"].items()}
    for lname in ("f_l2_moe", "f_l5_moe"):
        b = np.asarray(trainer.parameters.as_dict()[f"_{lname}.bias"])
        assert b.dtype == np.float32          # static leaves stay float32
        assert 0 < np.abs(b).max() <= 12 * GAMMA + 1e-6
        assert abs(gauge[lname] - np.abs(b).max()) < 1e-7


# ---- the builder, the registry and FLOP pricing ------------------------------------------

def test_the_builder_checks_the_mixers_it_is_told_of():
    with pytest.raises(paddle.utils.error.Error, match="4 conv mixers, the arguments say 3"):
        lfm2_moe_lm_cost(**dict(ARGS, conv_layers=3))
    with pytest.raises(paddle.utils.error.Error, match="full_attention"):
        lfm2_moe_lm_cost(**dict(ARGS, attention_layers=2))
    with pytest.raises(paddle.utils.error.Error, match="layers_held"):
        lfm2_moe_lm_cost(**dict(ARGS, layers_held=[0, 2, 3, 4, 9]))


def test_the_new_type_is_registered():
    assert LAYER_REGISTRY.get("short_conv") is not None


def test_flops_price_the_new_layers_by_the_work_done_here():
    a = dict(ARGS, first_expert=0, seq_len=10)
    topo = Topology(lfm2_moe_lm_cost(**a))
    T, d, H = 10, 16, 4
    E, k, held, I = 8, 3, a["experts_held"], a["moe_intermediate_size"]
    by = {l.name: flops.layer_fwd_flops(topo, l, 1, T) for l in topo.layers}
    assert by["f_l0_conv"] == 2.0 * T * (3 * d * d + 3 * d + d * d)
    assert by["f_l2_attn"] == 2.0 * T * (2 * d * d + 2 * d * 8) \
        + 2 * 2.0 * H * 4 * T * (T + 1) / 2
    assert by["f_l0_mlp"] == 2.0 * T * 3 * d * a["intermediate_size"]
    assert by["f_l3_moe"] == 2.0 * T * (d * E + k * held / E * 3 * d * I)
    assert by["f_head"] == 2.0 * T * d * a["vocab_size"]
    count = _load("benchmark/flops/lfm2-8b-a1b-ep4.py")
    mine = sum(by.values())
    theirs = 2 * T * sum(count.forward_macs_per_token(a, T).values())
    assert abs(mine - theirs) <= 1e-9 * theirs
