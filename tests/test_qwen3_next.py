"""The Qwen3-Next layers (rms_norm, gated_attention, gated_delta_net, moe_ffn),
the chunked delta rule and the whole tiny model, held to the plain reference
of the benchmark (benchmark/reference/qwen3-next-80b-a3b-ep32.py: float32,
the delta rule token by token, the MoE as a masked loop) on seeded weights.
CPU, tiny widths, float32 at `highest`.
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
from paddle_tpu.kernels import gdn
from paddle_tpu.models.text import qwen3_next_lm_cost

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARGS = dict(vocab_size=50, hidden_size=16, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            partial_rotary_factor=0.5, rope_theta=10000.0,
            full_attention_interval=4, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=8, linear_conv_kernel_dim=4,
            moe_intermediate_size=12, shared_expert_intermediate_size=12,
            num_experts=8, num_experts_per_tok=3, experts_held=4,
            first_expert=2, rms_norm_eps=1e-6)


def _load(rel):
    path = os.path.join(ROOT, rel)
    spec = importlib.util.spec_from_file_location(
        "ref_" + os.path.basename(path).replace("-", "_")[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/qwen3-next-80b-a3b-ep32.py")


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


# ---- each layer against the reference's function of the same name ---------

def _layer_case(name, a, chunk=8):
    """(layer built on a dense sequence input "x", the reference function as
    f(params by suffix, x [T, d]))."""
    x = layer.data(name="x", type=data_type.dense_vector_sequence(
        a["hidden_size"]))
    if name == "rms_norm":
        return layer.rms_norm(input=x, eps=a["rms_norm_eps"], name="l"), \
            lambda ref, p, row: ref.rms_norm(row, p["w0"], a["rms_norm_eps"])
    if name == "gated_attention":
        return layer.gated_attention(
            input=x, num_heads=a["num_attention_heads"],
            num_kv_heads=a["num_key_value_heads"], head_dim=a["head_dim"],
            rotary_dim=int(a["head_dim"] * a["partial_rotary_factor"]),
            rope_theta=a["rope_theta"], name="l"), \
            lambda ref, p, row: ref.gated_attention(p, row, a, _ident)
    if name == "gated_delta_net":
        return layer.gated_delta_net(
            input=x, num_k_heads=a["linear_num_key_heads"],
            num_v_heads=a["linear_num_value_heads"],
            head_k_dim=a["linear_key_head_dim"],
            head_v_dim=a["linear_value_head_dim"],
            conv_kernel=a["linear_conv_kernel_dim"], chunk=chunk, name="l"), \
            lambda ref, p, row: ref.gated_delta_net(p, row, a, _ident)
    return layer.moe_ffn(
        input=x, num_experts=a["num_experts"], top_k=a["num_experts_per_tok"],
        expert_size=a["moe_intermediate_size"],
        shared_size=a["shared_expert_intermediate_size"],
        experts_held=a["experts_held"], first_expert=a["first_expert"],
        tile=8, name="l"), \
        lambda ref, p, row: ref.moe_ffn(p, row, a, _ident)


def _check_layer_against_the_reference(ref, name, x, chunk=8):
    """Output and every gradient of the layer on x [B, T, d]."""
    out, ref_fn = _layer_case(name, ARGS, chunk)
    topo = Topology(out)
    B, T, d = x.shape
    params = {k: _normal(i, *s.shape, scale=0.3) + (1.0 if k.endswith(".norm") else 0.0)
              for i, (k, s) in enumerate(sorted(topo.param_specs().items()))}
    proj = _normal(78, B, T, d)

    def prog(params, x):
        y = topo.forward(params, {"x": Arg(x, jnp.ones((B, T)))},
                         training=True)["l"].value
        return jnp.sum(y * proj), y

    def plain(params, x):
        p = {k.split(".", 1)[1]: v for k, v in params.items()}
        y = jnp.stack([ref_fn(ref, p, x[r]) for r in range(B)])
        return jnp.sum(y * proj), y

    (_, y), g = jax.value_and_grad(prog, argnums=(0, 1), has_aux=True)(params, x)
    (_, y_ref), g_ref = jax.value_and_grad(plain, argnums=(0, 1),
                                           has_aux=True)(params, x)
    _close(y, y_ref)
    _close(g[1], g_ref[1])
    for k in params:
        _close(g[0][k], g_ref[0][k])


# a row of 640 positions is two query tiles of 512 (`flash_attn.tile_sizes`),
# the second holding 128 positions (the kernels pad it to 512)
@pytest.mark.parametrize("name,T", [
    *(pytest.param(n, 21, id=n) for n in ("rms_norm", "gated_attention",
                                          "gated_delta_net", "moe_ffn")),
    pytest.param("gated_attention", 640, id="gated_attention-640")])
def test_layer_matches_the_reference(ref, name, T):
    _check_layer_against_the_reference(
        ref, name, _normal(77, 2, T, ARGS["hidden_size"]))


# ---- the chunked delta rule against the token-by-token form ----------------

def _rule_inputs(B, T, H, dk, dv, seed=0):
    q = _normal(seed, B, T, H, dk)
    k = _normal(seed + 1, B, T, H, dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = _normal(seed + 2, B, T, H, dv)
    g = -jax.nn.softplus(_normal(seed + 3, B, T, H))
    beta = jax.nn.sigmoid(_normal(seed + 4, B, T, H))
    return q, k, v, g, beta


@pytest.mark.parametrize("chunk,T", [(4, 10), (64, 70), (64, 64), (8, 3)])
def test_chunked_rule_matches_token_by_token(ref, chunk, T):
    ins = _rule_inputs(2, T, 3, 8, 6)
    proj = _normal(9, 2, T, 3, 6)

    def chunked(*ins):
        return jnp.sum(gdn.gated_delta_rule(*ins, chunk=chunk) * proj)

    def tokens(*ins):
        o = jnp.stack([ref.delta_rule(*(x[r] for x in ins)) for r in range(2)])
        return jnp.sum(o * proj)

    v, g = jax.value_and_grad(chunked, argnums=(0, 1, 2, 3, 4))(*ins)
    v_ref, g_ref = jax.value_and_grad(tokens, argnums=(0, 1, 2, 3, 4))(*ins)
    _close(v, v_ref)
    for a, b in zip(g, g_ref):
        _close(a, b)


# ---- the chunk inverse T = (I + A)^-1 ---------------------------------------

def _chunk_A(kind, C, dtype, seed=0):
    """A [2, 3, C, C] as `chunk_prepare` makes it. "layer": unit keys, beta
    from a sigmoid, decays from g. "one_key": every key of a chunk the same,
    beta 0.99, g 0 (a run of one repeated token, or padding): max|T| is 1,
    but the powers A^k reach ~1e18 before they cancel."""
    lead = (2, 3)
    if kind == "layer":
        k = _normal(seed, *lead, C, 8)
        beta = jax.nn.sigmoid(_normal(seed + 1, *lead, C))
        g = -jax.nn.softplus(_normal(seed + 2, *lead, C))
    else:
        k = jnp.broadcast_to(_normal(seed, *lead, 1, 8), lead + (C, 8))
        beta, g = jnp.full(lead + (C,), 0.99), jnp.zeros(lead + (C,))
    k, beta, g = (x.astype(dtype) for x in (k, beta, g))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    gamma = jnp.cumsum(g, axis=-1)
    decay = jnp.exp(gamma[..., :, None] - gamma[..., None, :])
    kk = jnp.matmul(k * beta[..., None], jnp.swapaxes(k, -1, -2))
    return jnp.tril(kk * decay, -1)


def _by_the_solve(A):
    eye = jnp.eye(A.shape[-1], dtype=A.dtype)
    return jax.scipy.linalg.solve_triangular(
        A + eye, jnp.broadcast_to(eye, A.shape), lower=True,
        unit_diagonal=True)


def _whole_chunk_neumann(A):
    """(I - A)(I + A^2)(I + A^4)...: what the block form must not become."""
    T, P, k = jnp.eye(A.shape[-1], dtype=A.dtype) - A, A, 2
    while k < A.shape[-1]:
        P = jnp.matmul(P, P)
        T, k = T + jnp.matmul(T, P), 2 * k
    return T


@pytest.mark.parametrize("kind", ["layer", "one_key"])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6), ("float64", 1e-12)])
@pytest.mark.parametrize("C", [16, 64, 128])
def test_unit_lower_inverse_matches_the_solve(C, dtype, tol, kind):
    """Value and gradient against `solve_triangular` and autodiff through
    it. float32 at 2e-6 of max|T| needs the merges' products at HIGHEST; the
    one-key case is what a whole-chunk Neumann doubling fails by ten orders
    of magnitude while max|T| stays 1."""
    with jax.enable_x64(dtype == "float64"):
        A = _chunk_A(kind, C, jnp.dtype(dtype))
        proj = _normal(7, *A.shape).astype(A.dtype)
        T, want = gdn.unit_lower_inverse(A), _by_the_solve(A)
        dA = jax.grad(lambda A: jnp.sum(gdn.unit_lower_inverse(A) * proj))(A)
        dA_want = jax.grad(lambda A: jnp.sum(_by_the_solve(A) * proj))(A)
        assert T.dtype == A.dtype and dA.dtype == A.dtype
        _close(T, want, tol)
        # autodiff through the solve leaves a cotangent above the diagonal,
        # where A is zero by construction; the rule returns none there
        _close(dA, jnp.tril(dA_want, -1), 10 * tol)
        if kind == "one_key" and C >= 64 and dtype == "float32":
            off = jnp.max(jnp.abs(_whole_chunk_neumann(A) - want))
            assert float(jnp.max(jnp.abs(want))) < 1.01 and float(off) > 1e3


def test_layer_on_a_row_of_one_repeated_id_matches_the_reference(ref):
    """Every token the same: after the convolution's first three positions
    every key of a chunk is the same vector, the case above inside the
    layer, at the cell's chunk of 64."""
    d = ARGS["hidden_size"]
    _check_layer_against_the_reference(
        ref, "gated_delta_net",
        jnp.broadcast_to(_normal(77, 1, 1, d), (1, 150, d)), chunk=64)


def test_backward_pass_finds_T_and_inverts_nothing_again(monkeypatch, capsys):
    """The row's `jax.checkpoint` keeps the chunk inverses and nothing else
    of the mixer: T is among the saved residuals, and the backward pass
    holds the rule's own two products at HIGHEST and none of the forward
    merge's two. With the policy taken away T is gone from the residuals
    and the forward's products are back, so this can fail."""
    from paddle_tpu.layers import attention

    out, _ = _layer_case("gated_delta_net", ARGS, chunk=64)
    topo = Topology(out)
    T, d = 128, ARGS["hidden_size"]
    params = {k: _normal(i, *s.shape, scale=0.3)
              for i, (k, s) in enumerate(sorted(topo.param_specs().items()))}
    x = _normal(77, 1, T, d)
    kept = f"f32[{ARGS['linear_num_value_heads']},{T // 64},{64 * 64}]"

    def f(params, x):
        return jnp.sum(topo.forward(params, {"x": Arg(x, jnp.ones((1, T)))},
                                    training=True)["l"].value ** 2)

    def saved_and_products():
        capsys.readouterr()
        jax.ad_checkpoint.print_saved_residuals(f, params, x)
        saved = capsys.readouterr().out
        # outside this file's "highest" default, only the inverse's own
        # products say HIGHEST
        with jax.default_matmul_precision("default"):
            _, pull = jax.vjp(f, params, x)
            backward = str(jax.make_jaxpr(pull)(jnp.float32(1.0)))
        return saved, backward.count("Precision.HIGHEST, Precision.HIGHEST")

    # the one row as a plain call, so that the checkpoint's own residuals
    # are what is listed, not a scan's
    monkeypatch.setattr(attention.jax.lax, "map",
                        lambda f, xs: jnp.stack([f(x) for x in xs]))
    saved, products = saved_and_products()
    assert kept in saved and "chunk_prepare" in saved, saved
    assert products == 2

    monkeypatch.setattr(attention.jax.checkpoint_policies,
                        "save_only_these_names",
                        lambda *names: jax.checkpoint_policies.nothing_saveable)
    saved, products = saved_and_products()
    assert kept not in saved, saved
    assert products == 4


def _limit_engaging(block, dtype, C=16, dk=128, dv=128):
    """The VMEM limit under which `state_pass_block` engages `block` (it
    budgets a quarter of the limit); None: the limit as it is."""
    if block is None:
        return gdn.VMEM_LIMIT_BYTES
    if block == (1, 1):
        return 0                    # nothing fits: the 1 x 1 fallback
    return 4 * gdn._vmem_estimate_bytes(*block, C, dk, dv,
                                        jnp.dtype(dtype).itemsize)


# (dtype, tolerance, B x H rows, tokens, the block a grid step holds). Chunks
# of 16 tokens: 40 tokens are 3 chunks, 96 are 6. With the limit as it is a
# grid step holds all there is; a tight one leaves rows 2 x chunks 3 of 4 x 6
# (the budget has no room for 4 rows, nor for 2 x 6: the chunks a step holds
# divide NC), and none at all leaves 1 x 1.
@pytest.mark.parametrize("dtype,tol,H,T,block", [
    (jnp.float32, 1e-5, 2, 40, None),
    (jnp.bfloat16, 2e-2, 2, 40, None),
    (jnp.float32, 1e-5, 4, 96, None),
    (jnp.float32, 1e-5, 4, 96, (2, 3)),
    (jnp.float32, 1e-5, 4, 96, (4, 1)),
    (jnp.float32, 1e-5, 4, 96, (1, 1)),
    (jnp.float32, 1e-5, 2, 40, (1, 3)),
    (jnp.bfloat16, 2e-2, 4, 96, (2, 3)),
], ids=lambda x: getattr(x, "__name__", str(x)).replace(" ", ""))
def test_state_pass_kernels_match_the_scan(dtype, tol, H, T, block,
                                           monkeypatch):
    """gdn_chunk_fwd / gdn_chunk_bwd in interpret mode against the lax.scan
    form, values and every input's gradient (float32: the same arithmetic;
    bfloat16: the hand-written backward pass rounds at other points than the
    scan's transpose), in blocks of several rows and chunks a grid step, and
    equal BIT FOR BIT to the kernels that take one row's one chunk a step:
    the blocking moves no arithmetic."""
    ins = _rule_inputs(1, T, H, 128, 128)
    ins = tuple(x.astype(dtype) for x in ins[:3]) + ins[3:]
    xs = gdn.chunk_prepare(*ins, 16)
    BH, NC = xs[0].shape[:2]
    proj = _normal(9, *xs[1].shape)

    def through(state_pass):
        return jax.value_and_grad(lambda *xs: jnp.sum(
            state_pass(*xs).astype(jnp.float32) * proj),
            argnums=tuple(range(6)))(*xs)

    def kernels(block):
        monkeypatch.setattr(gdn, "VMEM_LIMIT_BYTES",
                            _limit_engaging(block, dtype))
        engaged = gdn.state_pass_block(BH, NC, 16, 128, 128,
                                       jnp.dtype(dtype).itemsize)
        assert engaged == (block or (BH, NC)), engaged
        return through(lambda *xs: gdn.state_pass_kernel(*xs, True)), \
            gdn.state_pass_kernel(*xs, True)

    (v, g), primal = kernels(block)
    v_ref, g_ref = through(gdn.state_pass_scan)
    _close(v, v_ref, tol)
    for a, b in zip(g, g_ref):
        assert a.dtype == b.dtype and a.shape == b.shape
        _close(a.astype(jnp.float32), b.astype(jnp.float32), tol)
    (v1, g1), primal1 = kernels((1, 1))
    for a, b in zip((v, primal) + g, (v1, primal1) + g1):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


def _pallas_calls(jaxpr):
    """Every pallas_call equation in a jaxpr, however deep."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


def test_the_primal_call_writes_no_s0():
    """Asked for no gradient, `state_pass_kernel` is one gdn_chunk_fwd with
    one result, O. The custom rule's forward has two (O and every chunk's
    starting state, float32), which the backward call reads. Under a row's
    `jax.checkpoint` the first pass keeps nothing of the rule, so it is the
    primal call there too (`optimize_remat`), and the pass made again for
    the gradient is the one with S0."""
    ins = _rule_inputs(1, 32, 2, 128, 128)
    xs = gdn.chunk_prepare(*ins, 16)
    S0 = ((2, 2, 128, 128), jnp.float32)

    def results(fn):
        calls = _pallas_calls(jax.make_jaxpr(fn)(*xs).jaxpr)
        return [(eqn.params["name"],
                 [(v.aval.shape, v.aval.dtype) for v in eqn.outvars])
                for eqn in calls]

    O = (xs[1].shape, xs[1].dtype)
    kernel = lambda *xs: gdn.state_pass_kernel(*xs, True)
    loss = lambda *xs: jnp.sum(kernel(*xs))
    assert results(kernel) == [("gdn_chunk_fwd", [O])]
    fwd, bwd = results(jax.grad(loss, argnums=tuple(range(6))))
    assert fwd == ("gdn_chunk_fwd", [O, S0])
    assert bwd[0] == "gdn_chunk_bwd" and len(bwd[1]) == 6
    kept = jax.checkpoint(
        loss, policy=jax.checkpoint_policies.save_only_these_names("gdn_T"))
    first, again, back = results(jax.grad(kept, argnums=tuple(range(6))))
    assert first == ("gdn_chunk_fwd", [O]), first
    assert again == fwd and back == bwd


def test_state_pass_block_at_the_cells_shape():
    """The block comes from the shapes and the VMEM estimate alone. At the
    Qwen3-Next cell's shape (32 rows of 64 chunks of 64 tokens, widths 128)
    a grid step holds several rows and chunks, they divide the array, and
    what they need stays under a quarter of the limit the calls are compiled
    with; a shape with nothing to divide, or no room, runs 1 x 1."""
    for itemsize in (2, 4):
        R, Kc = gdn.state_pass_block(32, 64, 64, 128, 128, itemsize)
        assert R * Kc > 1 and 32 % R == 0 and 64 % Kc == 0, (R, Kc)
        need = gdn._vmem_estimate_bytes(R, Kc, 64, 128, 128, itemsize)
        assert need <= gdn.VMEM_LIMIT_BYTES // 4, need
        # the estimate is no less than the blocks themselves, twice
        per = ((3 * 128 + 2 * 128 + 64) * 64 * itemsize + 128 * 128 * 4) \
            + (3 * 128 + 128 + 64) * 64 * itemsize
        assert need >= 2 * R * Kc * per
    assert gdn.state_pass_block(32, 64, 64, 128, 128, 2) == (8, 4)
    assert gdn.state_pass_block(32, 64, 64, 128, 128, 4) == (8, 2)
    assert gdn.state_pass_block(7, 13, 64, 128, 128, 2) == (7, 1)
    assert gdn.state_pass_block(1, 1, 64, 128, 128, 2) == (1, 1)
    assert gdn.state_pass_block(3, 5, 16, 128, 128, 4) == (3, 5)
    # rows give way before chunks do
    assert gdn.state_pass_block(32, 64, 64, 1024, 1024, 4)[0] < 8


def test_kernel_gate():
    assert gdn.kernel_supported(128, 128, 64, jnp.bfloat16)
    assert not gdn.kernel_supported(8, 128, 64, jnp.bfloat16)
    assert not gdn.kernel_supported(128, 128, 4, jnp.float32)
    # held to the CPU here, every layer takes the scan
    assert gdn.pick_state_pass("t", 128, 128, 64, jnp.bfloat16) \
        is gdn.state_pass_scan


def test_the_engaged_block_is_in_the_log(monkeypatch, caplog):
    """Where the layer takes the kernels, its decision line is followed, once
    a layer, by the block a grid step holds, from the function that computes
    it: a fallback to 1 x 1 shows in every run's log."""
    import logging

    from paddle_tpu.kernels import _pallas_util

    monkeypatch.setattr(_pallas_util, "take_pallas",
                        lambda who, kernel, eligible=True, why_not="": eligible)
    calls = []
    monkeypatch.setattr(_pallas_util, "call_kernel",
                        lambda fn, xs, batch: calls.append(fn) or xs[1])
    monkeypatch.setattr(_pallas_util, "_LOGGED_DECISIONS", set())
    xs = gdn.chunk_prepare(*_rule_inputs(1, 40, 2, 128, 128), 16)
    with caplog.at_level(logging.INFO, logger="paddle_tpu"):
        state_pass = gdn.pick_state_pass("l7", 128, 128, 16, jnp.float32)
        state_pass(*xs), state_pass(*xs)
        monkeypatch.setattr(gdn, "VMEM_LIMIT_BYTES", 0)
        state_pass(*xs)
    lines = [r.getMessage() for r in caplog.records if "l7" in r.getMessage()]
    assert calls == [gdn.state_pass_kernel] * 3
    assert lines == [
        "l7: gdn_chunk_fwd/bwd take rows 2 x chunks 3 of 2 x 3 a grid step; "
        "the primal call writes no S0",
        "l7: gdn_chunk_fwd/bwd take rows 1 x chunks 1 of 2 x 3 a grid step; "
        "the primal call writes no S0"], lines


# ---- the whole tiny model ---------------------------------------------------

def _batch(ref, lens, seed=0):
    rng = np.random.default_rng(seed)
    rows = [(rng.integers(2, ARGS["vocab_size"], n).tolist(),
             rng.integers(2, ARGS["vocab_size"], n).tolist()) for n in lens]
    b = {k: jnp.asarray(v) for k, v in ref.pad(rows, ARGS).items()}
    feeds = {"ids": Arg(b["ids"], b["ids_mask"]),
             "next_ids": Arg(b["next_ids"], b["next_ids_mask"])}
    return rows, b, feeds


def test_model_declares_the_references_leaves(ref):
    topo = Topology(qwen3_next_lm_cost(**ARGS))
    table = ref.param_table(ARGS)
    assert {k: tuple(s.shape) for k, s in topo.param_specs().items()} \
        == {k: tuple(shape) for k, (shape, _) in table.items()}
    # the program's own initialisation starts the constants where the
    # reference's table does
    mine = topo.init_params(jax.random.PRNGKey(0))
    for k, (shape, (kind, v)) in table.items():
        if kind == "const":
            assert np.all(np.asarray(mine[k]) == v), k


def test_model_loss_and_every_gradient_match_the_reference(ref):
    topo = Topology(qwen3_next_lm_cost(**ARGS))
    p = _seeded(ref.param_table(ARGS), 3)
    _, b, feeds = _batch(ref, [70, 64, 33])
    loss = topo.loss_fn()
    (c, _), g = jax.jit(jax.value_and_grad(
        lambda p: loss(p, feeds), has_aux=True))(p)
    (c_ref, _), g_ref = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, b, _ident, ARGS), has_aux=True))(p)
    (c_blk, _), g_blk = ref.value_and_grad(p, b, _ident, ARGS)
    assert abs(float(c) - float(c_ref)) <= 1e-5 * abs(float(c_ref))
    assert abs(float(c_blk) - float(c_ref)) <= 1e-5 * abs(float(c_ref))
    assert set(g) == set(g_ref) == set(g_blk)
    for k in g_ref:
        _close(g[k], g_ref[k], 5e-4)
        _close(g_blk[k], g_ref[k], 5e-5)


def test_four_shares_and_one_shared_make_the_uncut_layer(ref):
    """8 experts in 4 shares of 2: each share's layer holds 2 experts and
    routes over all 8; the shares' routed parts and ONE shared part add up
    to the reference's layer that holds all 8."""
    a = dict(ARGS, experts_held=8, first_expert=0)
    d, T = a["hidden_size"], 40
    table = {k.split("_moe.")[1]: v for k, v in ref.param_table(a).items()
             if k.startswith("_q_l0_moe.")}
    full = {k: _normal(i, *shape, scale=0.4)
            for i, (k, (shape, _)) in enumerate(sorted(table.items()))}
    x = _normal(5, 1, T, d)
    want = ref.moe_ffn(full, x[0], a, _ident)
    total = ref.shared(full, x[0], a, _ident)
    for s in range(4):
        inp = layer.data(name="x", type=data_type.dense_vector_sequence(d))
        out = layer.moe_ffn(
            input=inp, num_experts=8, top_k=a["num_experts_per_tok"],
            expert_size=a["moe_intermediate_size"],
            shared_size=a["shared_expert_intermediate_size"],
            experts_held=2, first_expert=2 * s, tile=8, name="l")
        mine = {"_l." + k: (v[2 * s:2 * s + 2] if k in ("wg", "wu", "wd") else v)
                for k, v in full.items()}
        outs, ctx = Topology(out).forward(
            mine, {"x": Arg(x, jnp.ones((1, T)))}, training=True,
            return_ctx=True)
        part = outs["l"].value[0] - ref.shared(full, x[0], a, _ident)
        _close(part, ref.routed(full, x[0], a, _ident, first=2 * s, held=2))
        total = total + part
        held, elsewhere, _, dropped = np.asarray(
            ctx.extras["step_stats"]["moe_ffn"]["l"])[:4]
        assert held + elsewhere == T * a["num_experts_per_tok"]
        assert dropped == 0
    _close(total, want)


# sha256 of the lowered layer below at the parent commit of PR 34 (b3f4740),
# by this test's own code: `moe_ffn` learnt Kimi-VL's router there (a sigmoid
# score, a selection bias, a weight scale, a shared expert without its gate).
# It holds through PR 35: off the TPU the layer takes the tile loop, which the
# chunked kernels did not touch, and the two entries the step statistics
# gained do not reach this program (only the layer's value does). It moved
# (from de18d10b...) when the layer's checkpoint came to keep the routing
# plan: with the checkpoint's policy, the plan's unique places and its
# `checkpoint_name`s (nothing in the text but the numbering of private
# functions) taken out, the layer lowers to de18d10b... again
QWEN_MOE_SHA256 = "4d86b712578d967878741cb047cf412d8be2c728433a0f020341e7037845ef28"


def test_the_softmax_routed_gated_shared_layer_lowers_as_it_did():
    """The Qwen3-Next cell's `moe_ffn` (softmax router, no bias, a gated
    shared expert), forward and every gradient: the text it lowered to
    before the layer had another router."""
    import hashlib

    x = layer.data(name="x", type=data_type.dense_vector_sequence(16))
    out = layer.moe_ffn(input=x, num_experts=8, top_k=3, expert_size=12,
                        shared_size=12, experts_held=4, first_expert=2, tile=8,
                        name="l")
    topo = Topology(out)
    params = topo.init_params(jax.random.PRNGKey(0))
    assert "_l.bias" not in params and "_l.shared_gate" in params

    def loss(params, xs):
        y = topo.forward(params, {"x": Arg(xs, jnp.ones((2, 24)))},
                         training=True)["l"].value
        return jnp.sum(y ** 2)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, jnp.zeros((2, 24, 16))).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == QWEN_MOE_SHA256


def test_moe_counts_held_and_elsewhere_and_skips_padding():
    d, T = 16, 12
    inp = layer.data(name="x", type=data_type.dense_vector_sequence(d))
    out = layer.moe_ffn(input=inp, num_experts=8, top_k=2, expert_size=8,
                        shared_size=8, experts_held=8, tile=4, name="l")
    topo = Topology(out)
    params = topo.init_params(jax.random.PRNGKey(1))
    mask = jnp.asarray([[1.0] * T, [1.0] * 5 + [0.0] * (T - 5)])
    _, ctx = topo.forward(params, {"x": Arg(_normal(2, 2, T, d), mask)},
                          training=True, return_ctx=True)
    held, elsewhere, load, dropped, tiles, fetches = np.asarray(
        ctx.extras["step_stats"]["moe_ffn"]["l"])
    # every expert is held: every real token's two choices are computed here
    assert (held, elsewhere, dropped) == ((T + 5) * 2, 0, 0)
    assert load >= 1.0
    # tiles of 4 rows hold the 34 pairs, padding included; the tile loop
    # fetches an expert every tile
    assert (T + 5) * 2 / 4 <= tiles == fetches <= (T + 5) * 2 / 4 + 8


def test_grouped_ffn_walks_only_the_tiles_in_use():
    """The dispatch buffer is sized for the worst case; the tiles past the
    last expert's run hold no row and are not visited."""
    from paddle_tpu.layers import moe

    N, k, held, tile = 32, 2, 4, 8
    idx = jnp.tile(jnp.asarray([[0, 9]]), (N, 1))       # one held choice each
    top = jnp.full((N, k), 0.5)
    row_w, row_tok, tile_expert, n_tiles, stats = moe.dispatch_plan(
        idx, top, jnp.ones((N,), bool), 0, held, tile)
    assert row_tok.shape[0] == N * 2 + held * tile       # the worst case
    assert int(n_tiles) == N // tile                     # what is in use
    assert np.asarray(row_tok[:N]).tolist() == list(range(N))
    assert np.all(np.asarray(row_tok[N:]) == N)          # padding rows
    assert np.asarray(stats).tolist() == [N, N, 4.0, 0.0, N // tile, N // tile]


# ---- through the public trainer ---------------------------------------------

def test_trains_through_sgd_and_fills_the_moe_counters():
    from paddle_tpu.observability import metrics as obs_metrics

    cost = qwen3_next_lm_cost(**dict(ARGS, first_expert=0))
    params = paddle.parameters.create(cost)
    trainer = paddle.SGD(cost, params,
                         paddle.optimizer.Adam(learning_rate=3e-3),
                         mixed_precision=True)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(2, 50, 24).tolist() for _ in range(4)]
    rows = [([0] + s, s + [1]) for s in seqs]
    costs = []

    def snap():
        fam = obs_metrics.default_registry.snapshot()
        tok = {tuple(sorted(dict(k).items())): v for k, v in
               fam.get("paddle_moe_tokens_total", {"series": {}})["series"].items()}
        return tok, fam.get("paddle_moe_dropped_total")

    before, _ = snap()
    trainer.train(lambda: iter([rows] * 12), num_passes=1,
                  event_handler=lambda ev: costs.append(ev.cost)
                  if isinstance(ev, paddle.event.EndIteration) else None,
                  feeding={"ids": 0, "next_ids": 1})
    after, dropped = snap()
    assert len(costs) == 12 and np.all(np.isfinite(costs))
    assert costs[-1] < costs[0]
    key = lambda result: (("layer", "q_l0_moe"), ("result", result))
    held = after[key("held")] - before.get(key("held"), 0)
    elsewhere = after[key("elsewhere")] - before.get(key("elsewhere"), 0)
    assert held + elsewhere == 12 * 4 * 25 * ARGS["num_experts_per_tok"]
    assert 0 < held < held + elsewhere
    assert sum(dropped["series"].values()) == 0
    load = obs_metrics.default_registry.snapshot()[
        "paddle_moe_expert_load_max_over_mean"]["series"]
    assert all(v >= 1.0 for v in load.values()) and len(load) >= 4


def test_no_moe_layer_leaves_the_step_as_it_was():
    """A model without moe_ffn hands out no extra metric: the step's outputs
    are what they were (the NMT and ResNet cells' programs do not change)."""
    from paddle_tpu import activation
    from paddle_tpu.trainer.trainer import make_train_step

    img = layer.data(name="pixel", type=data_type.dense_vector(8))
    lab = layer.data(name="label", type=data_type.integer_value(3))
    cost = layer.classification_cost(
        input=layer.fc(input=img, size=3, act=activation.Softmax()), label=lab)
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = paddle.optimizer.Adam(learning_rate=1e-2)
    step = make_train_step(topo.loss_fn(cost), opt, topo.static_map())
    out = step(params, opt.init(params), jax.random.PRNGKey(1),
               {"pixel": Arg(jnp.zeros((4, 8))),
                "label": Arg(jnp.zeros((4, 1), jnp.int32))})
    assert out[3] == {}


# ---- registry and FLOP pricing ----------------------------------------------

def test_the_four_types_are_registered():
    for t in ("rms_norm", "gated_attention", "gated_delta_net", "moe_ffn"):
        assert LAYER_REGISTRY.get(t) is not None


def test_flops_price_the_new_layers_by_the_work_done_here():
    a = dict(ARGS, first_expert=0)
    topo = Topology(qwen3_next_lm_cost(**a))
    T = 10
    d, E, k, held = 16, a["num_experts"], 3, a["experts_held"]
    I = a["moe_intermediate_size"]
    by = {l.name: flops.layer_fwd_flops(topo, l, 1, T) for l in topo.layers}
    assert by["q_l0_in_norm"] == 0.0
    # router over all 8, shared gate, shared expert, 3 x 4/8 routed experts
    assert by["q_l0_moe"] == 2.0 * T * (d * E + d + 3 * d * I
                                        + k * held / E * 3 * d * I)
    # q (+ gate), k, v, o projections and the causal scores
    H, Hkv, D = 4, 2, 8
    assert by["q_l3_attn"] == 2.0 * T * (d * H * 2 * D + 2 * d * Hkv * D
                                         + H * D * d) \
        + 2 * 2.0 * H * D * T * (T + 1) / 2
    Hk, Hv, dk, dv, K = 2, 4, 8, 8, 4
    assert by["q_l0_gdn"] == 2.0 * T * (
        d * (2 * Hk * dk + 2 * Hv * dv) + d * 2 * Hv
        + (2 * Hk * dk + Hv * dv) * K + Hv * dv * d) + 2.0 * T * Hv * 3 * dk * dv
