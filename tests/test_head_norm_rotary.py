"""`kernels/head_norm_rotary.py` (q's and k's per-head RMS norm and rotary, one
Mosaic launch each way on `[T, heads * 128]`) pinned on the CPU, the kernels in
interpret mode, to the lines of `layers/attention.py` they stand for
(`rotary_at(_head_norm(x, w, eps, scale), pos, theta)` on `[1, T, heads, 128]`
under plain autodiff); the gate, the layer's line in the log, and the layer
with the launches taken."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import data_type, layer
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.topology import Topology
from paddle_tpu.kernels import _pallas_util, flash_attn
from paddle_tpu.kernels import head_norm_rotary as hnr
from paddle_tpu.layers.attention import _head_norm, rotary_at

D, THETA, EPS, L = 128, 1e6, 1e-6, 64
SHAPES = [(512, 4, jnp.bfloat16), (512, 1, jnp.bfloat16),
          (256, 2, jnp.float32)]
IDS = ["512x4-bf16", "512x1-bf16", "256x2-float32"]


def _f32(x):
    return np.asarray(x, np.float32)


def _inputs(T, heads, dtype, zero_head=False):
    """x as a projection gives it, the norm weight at 3 as the cell starts it,
    a cotangent; positions `i mod L`, block diffusion's."""
    ks = jax.random.split(jax.random.PRNGKey(T + heads), 3)
    x = jax.random.normal(ks[0], (T, heads * D))
    if zero_head:       # position 5, the last head: eps alone under the root
        x = x.at[5, -D:].set(0.0)
    w = 3.0 + 0.1 * jax.random.normal(ks[1], (D,))
    dy = jax.random.normal(ks[2], (1, T, heads * D))
    return x.astype(dtype), w.astype(dtype), dy.astype(dtype), np.arange(T) % L


def _forms(T, heads, dtype, scale, pos):
    cos, sin = hnr.tables(pos, THETA, D, dtype)
    block = hnr.block_rows(T, heads * D, dtype)

    def xla(x, w):
        y = rotary_at(_head_norm(x.reshape(1, T, heads, D), w, EPS, scale),
                      pos, THETA)
        return y.reshape(1, T, heads * D)

    def kernels(x, w):
        return hnr.head_norm_rotary(x[None], w, cos, sin, EPS, scale, block,
                                    True)

    return xla, kernels


def _ulp(dtype):
    return 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -22


@pytest.mark.parametrize("scale", [1.0, D ** -0.5], ids=["k", "q-scaled"])
@pytest.mark.parametrize("T,heads,dtype", SHAPES, ids=IDS)
def test_forward_is_the_xla_forms_within_an_ulp(T, heads, dtype, scale):
    x, w, _, pos = _inputs(T, heads, dtype)
    xla, kernels = _forms(T, heads, dtype, scale, pos)
    got, want = kernels(x, w), xla(x, w)
    assert got.shape == want.shape and got.dtype == want.dtype == dtype
    got, want = _f32(got), _f32(want)
    # rotary's two products and their sum round once here and three times in
    # XLA on the CPU: an ulp at the size of the head's entries
    top = np.abs(want).reshape(T, heads, D).max(-1, keepdims=True)
    err = np.abs(got - want).reshape(T, heads, D)
    assert np.all(err <= _ulp(dtype) * top), float((err / top).max())
    # against the norm's own rounding followed by rotary made in float32 and
    # rounded once (what XLA's fusion does on the chip): an ulp of the entry
    # where a rounding falls the other way, beside float32's own noise
    xn = _head_norm(x.reshape(1, T, heads, D), w, EPS, scale).astype(jnp.float32)
    cos, sin = (_f32(t)[None, :, None] for t in hnr.tables(pos, THETA, D, dtype))
    once = _f32((_f32(xn) * cos + np.roll(_f32(xn), D // 2, -1) * sin)
                .reshape(1, T, heads * D).astype(dtype))
    err = np.abs(got - once).reshape(T, heads, D)
    assert np.all(err <= _ulp(dtype) * np.abs(once).reshape(T, heads, D)
                  + 2.0 ** -22 * top)


@pytest.mark.parametrize("scale", [1.0, D ** -0.5], ids=["k", "q-scaled"])
@pytest.mark.parametrize("T,heads,dtype", SHAPES, ids=IDS)
def test_gradients_are_autodiffs_of_the_xla_form(T, heads, dtype, scale):
    x, w, dy, pos = _inputs(T, heads, dtype)
    xla, kernels = _forms(T, heads, dtype, scale, pos)
    (dx0, dw0), (dx1, dw1) = (jax.vjp(f, x, w)[1](dy) for f in (xla, kernels))
    assert dx1.dtype == dx0.dtype == dtype and dw1.dtype == dw0.dtype == dtype
    # float32: the order of the sums alone; bf16: XLA rounds dy cos, its
    # turned twin and their sum to bf16 on the way, the launch rounds dx once
    tol = 2.0 ** -6 if dtype == jnp.bfloat16 else 1e-5
    for got, want in ((dx1, dx0), (dw1, dw0)):
        got, want = _f32(got), _f32(want)
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=tol * np.abs(want).max())


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
def test_a_head_of_zeros_gives_finite_gradients(dtype):
    T, heads = 64, 2
    x, w, dy, pos = _inputs(T, heads, dtype, zero_head=True)
    xla, kernels = _forms(T, heads, dtype, 1.0, pos)
    y, vjp = jax.vjp(kernels, x, w)
    dx, dw = vjp(dy)
    assert np.all(_f32(y)[0, 5, -D:] == 0.0)
    assert np.all(np.isfinite(_f32(dx))) and np.all(np.isfinite(_f32(dw)))
    # under eps alone the head's gradient is dy's, normed by rsqrt(eps)
    dx0 = jax.vjp(xla, x, w)[1](dy)[0]
    got, want = _f32(dx)[5, -D:], _f32(dx0)[5, -D:]
    assert np.abs(want).max() > 100.0
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2.0 ** -6 * np.abs(want).max())


def test_tables_are_rotary_ats_with_the_sign_in_sin():
    pos = np.arange(40) % 7
    cos, sin = hnr.tables(pos, THETA, D, jnp.bfloat16)
    assert cos.shape == sin.shape == (40, D) and cos.dtype == jnp.bfloat16
    x = jnp.zeros((1, 40, 1, D), jnp.bfloat16).at[..., :D // 2].set(1.0)
    # ones on the first half: rotary_at gives cos there and sin behind it
    want = _f32(rotary_at(x, pos, THETA))[0, :, 0]
    np.testing.assert_array_equal(_f32(cos)[:, :D // 2], want[:, :D // 2])
    np.testing.assert_array_equal(_f32(sin)[:, D // 2:], want[:, D // 2:])
    np.testing.assert_array_equal(_f32(sin)[:, :D // 2],
                                  -_f32(sin)[:, D // 2:])


# ---- the gate, and the layer's line in the log ------------------------------

def test_the_gate_follows_the_head_the_rotary_and_the_block(monkeypatch):
    assert hnr.gate(16384, 4096, 128, 128, jnp.bfloat16) == (True, "")
    assert hnr.block_rows(16384, 4096, jnp.bfloat16) == 256
    assert hnr.block_rows(16384, 512, jnp.bfloat16) == 512
    assert hnr.block_rows(16384, 4096, jnp.float32) == 128
    assert hnr.bwd_vmem_bytes(256, 4096, jnp.bfloat16) \
        == 2 * 256 * (3 * 4096 + 2 * 128) * 2 <= hnr._VMEM_BUDGET \
        < _pallas_util.VMEM_LIMIT_BYTES
    ok, why = hnr.gate(16384, 2048, 64, 64, jnp.bfloat16)
    assert not ok and why.startswith("head size 64, bfloat16 is outside")
    ok, why = hnr.gate(16384, 4096, 128, 64, jnp.bfloat16)
    assert not ok and why == "rotary on 64 of a head's 128 is not the whole head"
    ok, why = hnr.gate(16384, 4096, 128, 128, jnp.float16)
    assert not ok and "float16" in why
    ok, why = hnr.gate(40, 512, 128, 128, jnp.float32)
    assert not ok and why.startswith("a row of 40 positions of 512 lanes")
    monkeypatch.setattr(hnr, "_VMEM_BUDGET", 1000)
    ok, why = hnr.gate(16384, 4096, 128, 128, jnp.bfloat16)
    assert not ok and "under 0.0 MB of VMEM" in why


def _gqa(T, heads, kv_heads, head_dim, name):
    x = layer.data(name="x", type=data_type.dense_vector_sequence(64))
    return Topology(layer.gqa_attention(
        input=x, num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
        rope_theta=THETA, mask=("block_diffusion", T // 2, 4), name=name))


def _mla(T, name):
    x = layer.data(name="x", type=data_type.dense_vector_sequence(64))
    return Topology(layer.mla_attention(
        input=x, num_heads=2, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, kv_lora_rank=32, rope_theta=THETA, mask=("causal", T),
        name=name))


@pytest.mark.parametrize("build,why", [
    (lambda T, name: _gqa(T, 2, 1, 64, name),
     "head size 64, float32 is outside the kernel's gate (a head is one "
     "group of 128 lanes)"),
    (lambda T, name: _gqa(T, 2, 1, 128, name), "backend is 'cpu'"),
    (_mla, "head size 24, float32 is outside the kernel's gate (a head is "
           "one group of 128 lanes)")], ids=["D64", "cpu", "mla"])
def test_elsewhere_the_layer_takes_the_xla_form_and_says_so_once(
        build, why, monkeypatch, caplog):
    monkeypatch.setattr(_pallas_util, "_LOGGED_DECISIONS", set())
    T, name = 64, "elsewhere"
    topo = build(T, name)
    params = topo.init_params(jax.random.PRNGKey(0))
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, T, 64))
    with caplog.at_level(logging.INFO, logger="paddle_tpu"):
        for _ in range(2):
            topo.forward(params, {"x": Arg(xs, jnp.ones((2, T)))},
                         training=True)
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith(name + ": _head_norm")]
    assert lines == [f"{name}: _head_norm and rotary_at in XLA, not "
                     f"head_norm_rotary_fwd/bwd ({why})"], lines


def test_a_partial_rotary_takes_the_xla_form_and_says_so_once(monkeypatch,
                                                              caplog):
    """Latent attention asks with its own shapes (rotary on 64 of a head's
    192 lanes; docs/kimi_vl.md), and a head of 128 rotated by half is
    outside the gate as well."""
    monkeypatch.setattr(_pallas_util, "_LOGGED_DECISIONS", set())
    with caplog.at_level(logging.INFO, logger="paddle_tpu"):
        for _ in range(2):
            assert not hnr.taken("half", 512, 4 * D, D, D // 2, jnp.bfloat16)
            assert not hnr.taken("mla", 512, 16 * 192, 192, 64, jnp.bfloat16)
    lines = [r.getMessage() for r in caplog.records]
    assert lines == [
        "half: _head_norm and rotary_at in XLA, not head_norm_rotary_fwd/bwd "
        "(rotary on 64 of a head's 128 is not the whole head)",
        "mla: _head_norm and rotary_at in XLA, not head_norm_rotary_fwd/bwd "
        "(head size 192, bfloat16 is outside the kernel's gate (a head is "
        "one group of 128 lanes))"], lines


def _taken(monkeypatch):
    """The platform's answer taken out of `take_pallas`, every launch in
    interpret mode."""
    for mod in (hnr, flash_attn):
        monkeypatch.setattr(mod, "take_pallas",
                            lambda who, kernel, eligible=True, why_not="",
                            **kw: eligible)
    norm, flash = hnr.head_norm_rotary, flash_attn.flash_attention
    monkeypatch.setattr(hnr, "head_norm_rotary", lambda *a: norm(*a, True))
    monkeypatch.setattr(flash_attn, "flash_attention",
                        lambda *a: flash(*a, True))


def test_a_layer_takes_the_launches_where_the_gate_passes(monkeypatch):
    """`gqa_attention` at heads of 128 with the launches taken against the
    same layer in XLA: the output and every gradient, the q and k norm
    weights' among them, and no `[.., heads, 128]` reshape left in the row."""
    T, H, Hkv = 64, 2, 1
    topo = _gqa(T, H, Hkv, D, "taken")
    params = topo.init_params(jax.random.PRNGKey(0))
    params = {k: v + 2.0 if k.endswith("_norm") else v
              for k, v in params.items()}
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, T, 64))

    def loss(params, xs):
        out = topo.forward(params, {"x": Arg(xs, jnp.ones((2, T)))},
                           training=True)["taken"].value
        return jnp.sum(out ** 2)

    run = jax.value_and_grad(loss, argnums=(0, 1))
    with jax.default_matmul_precision("highest"):
        v0, g0 = run(params, xs)
        _taken(monkeypatch)
        v1, g1 = run(params, xs)
        text = str(jax.make_jaxpr(run)(params, xs))
    np.testing.assert_allclose(float(v1), float(v0), rtol=1e-5)
    flat0, flat1 = (jax.tree_util.tree_leaves_with_path(g) for g in (g0, g1))
    assert len(flat0) == len(flat1) == 7
    for (path, want), (_, got) in zip(flat0, flat1):
        np.testing.assert_allclose(
            _f32(got), _f32(want), rtol=0,
            atol=2e-5 * np.abs(_f32(want)).max(), err_msg=str(path))
    assert text.count("head_norm_rotary_fwd") and \
        text.count("head_norm_rotary_bwd")
    assert f"{T},{H},{D}]" not in text and f"{T},{Hkv},{D}]" not in text


def test_under_a_data_parallel_trace_the_launch_goes_through_call_kernel(
        monkeypatch):
    seen = []

    def fake_call(fn, args, batched):
        seen.append(([a.shape for a in args], tuple(batched)))
        return args[0]

    monkeypatch.setattr(hnr, "call_kernel", fake_call)
    x = jnp.zeros((64, 2 * D), jnp.bfloat16)
    cos, sin = hnr.tables(np.arange(64), THETA, D, x.dtype)
    y = hnr.normed_rotated(x, jnp.ones((D,), x.dtype), cos, sin, EPS)
    assert y.shape == (1, 64, 2 * D)
    # the row is the batch's; the weight and the tables are whole on a shard
    assert seen == [([(1, 64, 2 * D), (D,), (64, D), (64, D)], (0,))]
