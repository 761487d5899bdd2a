"""The main path's Pallas kernels compile for the chip — checked without one.

The TPU's compiler is installed here and compiles for a v5e that is
DESCRIBED, not attached (jax.experimental.topologies): what it refuses — a
slice off the tiling, too much VMEM, a kernel it cannot partition — costs a
test run instead of chip time. Nothing executes: a pass says "compiles",
never "runs" or "is right" (chip_smoke.py says those, on the chip).

This is the ONE test file that loads the TPU library. The topology is
described inside a module-scoped fixture, after collection, never at
import: only one process at a time may hold libtpu, and under xdist every
worker imports every test file but only one runs this one. The libtpu
dlopen + PJRT version negotiation test (formerly tests/test_pjrt_runner.py)
lives here for the same reason. Compiles run in this process, with the
persistent compile cache off around them.
"""

import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile for the described device(s); returns (compiled, number of
    Mosaic kernels in the program)."""
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.output_size_in_bytes \
        + ma.temp_size_in_bytes < HBM_BYTES, ma
    return compiled, lowered.as_text().count("tpu_custom_call")


def _gru_args(B, H, T, dtype, batch_sh, repl_sh):
    return (_sds((B, T, 3 * H), dtype, batch_sh),
            _sds((H, 2 * H), dtype, repl_sh), _sds((H, H), dtype, repl_sh),
            _sds((3 * H,), dtype, repl_sh),
            _sds((B, T), jnp.float32, batch_sh))


def _lstm_args(B, H, T, dtype, sh):
    return (_sds((B, T, 4 * H), dtype, sh), _sds((H, 4 * H), dtype, sh),
            _sds((7 * H,), dtype, sh), _sds((B, T), jnp.float32, sh))


def _gru_train(x3, wg, wc, b, mask):
    from paddle_tpu.kernels.gru import fused_gru

    def loss(x3, wg, wc, b):
        return jnp.sum(fused_gru(x3, wg, wc, b, mask).astype(jnp.float32))

    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(x3, wg, wc, b)


def _lstm_train(x4, w, b, mask):
    from paddle_tpu.kernels.lstm import fused_lstm

    def loss(x4, w, b):
        hs, cs = fused_lstm(x4, w, b, mask)
        return jnp.sum(hs.astype(jnp.float32)) + jnp.sum(
            cs.astype(jnp.float32))

    return jax.value_and_grad(loss, argnums=(0, 1, 2))(x4, w, b)


# (B, H, T, dtype, f32 matmul precision): the NMT encoder in training
# (bf16) and as chip_smoke.py's parity check traces it (f32 at 'highest')
@pytest.mark.parametrize("B,H,T,dtype,precision", [
    (256, 512, 30, jnp.bfloat16, None),
    (256, 512, 30, jnp.float32, "highest"),
])
def test_fused_gru_train_compiles(one_chip, B, H, T, dtype, precision):
    with jax.default_matmul_precision(precision or "default"):
        _c, n = _compile(_gru_train,
                         *_gru_args(B, H, T, dtype, one_chip, one_chip))
    assert n == 2                                   # forward + backward


def _mosaic_instructions(compiled):
    """Names of the compiled program's Mosaic custom-call instructions:
    what the device trace shows as an op's name (benchmark/trace_reduce
    `short_name` keeps exactly this)."""
    return re.findall(r'^\s*%?([\w.\-]+) = [^\n]*custom-call\([^\n]*'
                      r'custom_call_target="tpu_custom_call"',
                      compiled.as_text(), re.M)


def test_gru_kernels_keep_their_names_in_the_compiled_step(one_chip):
    """`pl.pallas_call(name=...)` survives JAX's transform stack into the
    instruction names (`jvp_fused_gru_fwd_.N`, `transpose_jvp_fused_gru_
    bwd__.N`), at the NMT cell's size (B512 T32 H512, bf16): the kernel
    layer's roofline metric finds its kernels by these substrings."""
    compiled, n = _compile(_gru_train, *_gru_args(512, 512, 32, jnp.bfloat16,
                                                  one_chip, one_chip))
    names = _mosaic_instructions(compiled)
    assert n == 2 and len(names) == 2, names
    assert sum("fused_gru_fwd" in x for x in names) == 1, names
    assert sum("fused_gru_bwd" in x for x in names) == 1, names


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gdn_state_pass_train_compiles_and_keeps_its_names(one_chip, dtype):
    """The delta rule's state pass at the Qwen3-Next cell's size (one row:
    32 value heads, 64 chunks of 64 tokens, widths 128): forward and
    backward kernels, found in the compiled program by their names."""
    from paddle_tpu.kernels import gdn

    BH, NC, C, dk, dv = 32, 64, 64, 128, 128
    assert gdn.kernel_supported(dk, dv, C, dtype)
    R, Kc = gdn.state_pass_block(BH, NC, C, dk, dv, jnp.dtype(dtype).itemsize)
    assert R * Kc > 1, (R, Kc)      # several rows and chunks a grid step
    args = [_sds((BH, NC, C, n), dtype, one_chip) for n in (dk, dv, dk, dk, C)]
    args.append(_sds((BH, NC, 1, 1), jnp.float32, one_chip))

    def train(*xs):
        return jax.value_and_grad(lambda *xs: jnp.sum(
            gdn.state_pass_kernel(*xs).astype(jnp.float32)),
            argnums=tuple(range(6)))(*xs)

    compiled, n = _compile(train, *args)
    assert n == 2
    names = _mosaic_instructions(compiled)
    assert any("gdn_chunk_fwd" in x for x in names), names
    assert any("gdn_chunk_bwd" in x for x in names), names


@pytest.mark.parametrize("kind,dtype", [("gated_delta_net", jnp.bfloat16),
                                        ("gated_delta_net", jnp.float32),
                                        ("gated_attention", jnp.bfloat16)])
def test_qwen3_next_mixer_train_compiles_at_the_cells_size(one_chip, kind,
                                                           dtype, monkeypatch):
    """value_and_grad of one mixer layer as the Qwen3-Next cell runs it (4
    rows x 4096 x 2048, bf16, one row at a time). The DeltaNet layer: the
    chunk inverse is products (no `InvertDiagBlocksLowerTriangular` custom
    call, no triangular-solve), the backward pass runs `gdn_chunk_fwd` again
    (the cell demands 9 Mosaic calls a step) but finds T kept, flattened so
    that the four rows' stack is 134 MB and not padded to twice that. The
    kernels take the block `state_pass_block` chooses (bf16 and float32
    compile with it); the first pass's `gdn_chunk_fwd` has O as its one
    result, the pass made again for the gradient O and the float32 states.
    The attention layer shares `rows_one_at_a_time` and names nothing to
    keep: its program, and so its temporaries, are what they were before T
    was kept (parent commit, same compiler: 535,163,904 bytes)."""
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.arg import Arg
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.kernels import _pallas_util

    # held to the CPU, `take_pallas` would hand the layer the scan
    monkeypatch.setattr(_pallas_util, "take_pallas",
                        lambda who, kernel, eligible=True, why_not="": eligible)
    B, T, d = 4, 4096, 2048
    x = layer.data(name="x", type=data_type.dense_vector_sequence(d))
    if kind == "gated_delta_net":
        out = layer.gated_delta_net(
            input=x, num_k_heads=16, num_v_heads=32, head_k_dim=128,
            head_v_dim=128, conv_kernel=4, chunk=64, name="l")
    else:
        out = layer.gated_attention(
            input=x, num_heads=16, num_kv_heads=2, head_dim=256,
            rotary_dim=64, rope_theta=1e7, name="l")
    topo = Topology(out)
    params = {k: _sds(s.shape, dtype, one_chip)
              for k, s in topo.param_specs().items()}

    def loss(params, x):
        y = topo.forward(params, {"x": Arg(x, jnp.ones((B, T)))},
                         training=True)["l"].value
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled, _ = _compile(jax.value_and_grad(loss, argnums=(0, 1)), params,
                           _sds((B, T, d), dtype, one_chip))
    text, names = compiled.as_text(), _mosaic_instructions(compiled)
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"{kind} {jnp.dtype(dtype).name}: {temp:,} bytes of temporaries")
    assert "InvertDiagBlocks" not in text and "triangular-solve" not in text
    if kind == "gated_delta_net":
        assert sum("gdn_chunk_fwd" in n for n in names) == 2, names
        assert sum("gdn_chunk_bwd" in n for n in names) == 1, names
        assert "f32[4,32,64,4096]" in text          # the rows' kept T
        # what each gdn_chunk_fwd hands out: one of the two keeps no S0
        S0 = "f32[32,64,128,128]"
        results = sorted(S0 in line.split(" custom-call(")[0]
                         for line in text.splitlines()
                         if "tpu_custom_call" in line and "gdn_chunk_fwd" in line)
        assert results == [False, True], results
        if dtype == jnp.bfloat16:
            # the parent (one row's one chunk a grid step, S0 from both
            # forward calls, four transposes by XLA): 1,800,759,296; blocks
            # of 8 rows x 4 chunks, no S0 from the first: 1,632,672,768
            assert temp < 1_900_000_000, temp
    else:
        assert names == [] and temp == 535_163_904, (names, temp)


def _kernels_taken(monkeypatch, *modules):
    """Held to the CPU, `take_pallas` would hand a layer its XLA form."""
    for mod in modules:
        monkeypatch.setattr(mod, "take_pallas",
                            lambda who, kernel, eligible=True, why_not="",
                            **kw: eligible)


def _without_kernel_bodies(text):
    """A lowered program's text without its Mosaic launches' serialized
    bodies, which hold the source positions of every frame above the
    `pallas_call` (PERF.md section 6, PR 36)."""
    return re.sub(r'backend_config = "(?:[^"\\]|\\.)*"',
                  'backend_config = ""', text)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_sdar_attention_train_compiles_at_the_cells_size(one_chip, dtype,
                                                         monkeypatch):
    """value_and_grad of one attention layer as the SDAR cell runs it (2
    rows x 2 x 8192 positions x 2048, 32 : 4 heads of 128, the
    block-diffusion mask in blocks of 4, one row at a time): a row's
    backward pass keeps what `flash_attn_fwd` made and makes q's and k's
    norm and rotary again, so the compiled layer holds one forward and one
    backward flash launch, four `head_norm_rotary_fwd` and two
    `head_norm_rotary_bwd`, by their names, and nowhere a projection laid
    out again as `[.., heads, 128]`."""
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.arg import Arg
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.kernels import flash_attn, head_norm_rotary

    _kernels_taken(monkeypatch, flash_attn, head_norm_rotary)
    B, L, d = 2, 8192, 2048
    x = layer.data(name="x", type=data_type.dense_vector_sequence(d))
    out = layer.gqa_attention(input=x, num_heads=32, num_kv_heads=4,
                              head_dim=128, rope_theta=1e6,
                              mask=("block_diffusion", L, 4), name="l")
    topo = Topology(out)
    params = {k: _sds(s.shape, dtype, one_chip)
              for k, s in topo.param_specs().items()}

    def loss(params, x):
        y = topo.forward(params, {"x": Arg(x, jnp.ones((B, 2 * L)))},
                         training=True)["l"].value
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled, _ = _compile(jax.value_and_grad(loss, argnums=(0, 1)), params,
                           _sds((B, 2 * L, d), dtype, one_chip))
    names = _mosaic_instructions(compiled)
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"gqa_attention {jnp.dtype(dtype).name}: {temp:,} bytes of temporaries")
    assert len(names) == 8, names
    assert sum("flash_attn_fwd" in x for x in names) == 1, names
    assert sum("flash_attn_bwd" in x for x in names) == 1, names
    assert sum("head_norm_rotary_fwd" in x for x in names) == 4, names
    assert sum("head_norm_rotary_bwd" in x for x in names) == 2, names
    text = compiled.as_text()
    assert f"{2 * L},32,128]" not in text and f"{2 * L},4,128]" not in text


# sha256 of the Kimi-VL attention layer's lowered text below, the launches'
# bodies cut out, by this test's own code at the parent of PR 37 (f2d410a)
KIMI_VL_ATTENTION_SHA256 = {
    "bfloat16": "df4759c03a423b9c041ebd7c6ad2ca8a0202367af7ddc20ea4b98bbc6a0f8176",
    "float32": "855b0e393c3177a5a4618f9cdc2bf2a41b13de28e08905c795338126583c448f"}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kimi_vl_attention_train_compiles_at_the_cells_size(one_chip, dtype,
                                                            monkeypatch):
    """value_and_grad of one latent-attention layer as the Kimi-VL cell runs
    it (2 rows x 8192 positions x 2048, 16 heads of 192 : 128 that go in as
    256 : 128, the causal mask, one row at a time): one forward and one
    backward launch, by their names, taken by Mosaic at unequal head
    sizes."""
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.arg import Arg
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.kernels import flash_attn

    _kernels_taken(monkeypatch, flash_attn)
    B, L, d = 2, 8192, 2048
    x = layer.data(name="x", type=data_type.dense_vector_sequence(d))
    out = layer.mla_attention(
        input=x, num_heads=16, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, kv_lora_rank=512, rope_theta=800000, eps=1e-5,
        mask=("causal", L), name="l")
    topo = Topology(out)
    params = {k: _sds(s.shape, dtype, one_chip)
              for k, s in topo.param_specs().items()}

    def loss(params, x):
        y = topo.forward(params, {"x": Arg(x, jnp.ones((B, L)))},
                         training=True)["l"].value
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled, n = _compile(jax.value_and_grad(loss, argnums=(0, 1)), params,
                           _sds((B, L, d), dtype, one_chip))
    names = _mosaic_instructions(compiled)
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"mla_attention {jnp.dtype(dtype).name}: {temp:,} bytes of temporaries")
    assert n == 2 and len(names) == 2, names
    assert sum("flash_attn_fwd" in x for x in names) == 1, names
    assert sum("flash_attn_bwd" in x for x in names) == 1, names
    # the launches take heads of 256 lanes: 16 x 256 a position
    assert f"[1,{L},4096]" in compiled.as_text()
    # `_head_norm` and `rotary_at` serve this layer too, on a latent of 512
    # and heads of 64 lanes: outside `head_norm_rotary`'s gate, so the layer
    # lowers to the text it lowered to at the parent of PR 37 (f2d410a)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, _sds((B, L, d), dtype, one_chip)).as_text()
    assert hashlib.sha256(_without_kernel_bodies(text).encode()).hexdigest() \
        == KIMI_VL_ATTENTION_SHA256[jnp.dtype(dtype).name]


@pytest.mark.parametrize("cell,B,T,I,E,held,k,extra", [
    ("sdar", 2, 16384, 768, 128, 16, 8, {}),
    ("kimivl", 2, 8192, 1408, 64, 8, 6,
     dict(score="sigmoid", selection_bias=True, route_scale=2.446)),
    ("qwen3next", 4, 4096, 512, 512, 16, 10, {}),
])
def test_moe_ffn_train_compiles_at_the_cells_size(one_chip, monkeypatch, cell,
                                                  B, T, I, E, held, k, extra):
    """value_and_grad of one `moe_ffn` layer's routed part as the three MoE
    cells run it (hidden 2048, bf16, tiles of 256 rows, chunks of 16): the
    chunked grouped products are one `moe_grouped_fwd` and one
    `moe_grouped_bwd` launch (the forward made again by the layer's
    `jax.checkpoint` is dead code: the backward launch makes its own), taken
    by Mosaic with an expert's three float32 gradients resident in VMEM, at
    Kimi-VL's width with one buffer for the weights' blocks."""
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.arg import Arg
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.kernels import moe_grouped
    from paddle_tpu.layers import moe

    monkeypatch.setattr(moe, "take_pallas",
                        lambda who, kernel, eligible=True, why_not="", **kw:
                        eligible)
    d, dtype = 2048, jnp.bfloat16
    plan, why = moe_grouped.chunk_plan(d, I, 256, dtype)
    assert plan == (16, 1 if cell == "kimivl" else 2), (plan, why)
    x = layer.data(name="x", type=data_type.dense_vector_sequence(d))
    out = layer.moe_ffn(input=x, num_experts=E, top_k=k, expert_size=I,
                        experts_held=held, name="l", **extra)
    topo = Topology(out)
    params = {n: _sds(s.shape, jnp.float32 if n.endswith(".bias") else dtype,
                      one_chip)
              for n, s in topo.param_specs().items()}

    def loss(params, x):
        y = topo.forward(params, {"x": Arg(x, jnp.ones((B, T)))},
                         training=True)["l"].value
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled, n = _compile(jax.value_and_grad(loss, argnums=(0, 1)), params,
                           _sds((B, T, d), dtype, one_chip))
    names = _mosaic_instructions(compiled)
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"moe_ffn {cell}: {temp:,} bytes of temporaries")
    assert sum("moe_grouped_fwd" in x for x in names) == 1, names
    assert sum("moe_grouped_bwd" in x for x in names) == 1, names
    assert len(names) == 2, names


# the LSTM classifier (B64/H512/T100) and the split backward past the
# in-kernel-dW VMEM gate (H1280)
@pytest.mark.parametrize("B,H,T,dtype,precision", [
    (64, 512, 100, jnp.bfloat16, None),
    (64, 512, 100, jnp.float32, "highest"),
    (128, 1280, 100, jnp.bfloat16, None),
])
def test_fused_lstm_train_compiles(one_chip, B, H, T, dtype, precision):
    with jax.default_matmul_precision(precision or "default"):
        _c, n = _compile(_lstm_train, *_lstm_args(B, H, T, dtype, one_chip))
    assert n == 2


# forward only, f32, at the serving daemon's static batch: what the
# exported classifier / NMT whole-loop modules hold on a TPU host
@pytest.mark.parametrize("kind,B,H,T", [("lstm", 8, 512, 100),
                                        ("gru", 8, 512, 16)])
def test_fused_forward_at_serving_batch_compiles(one_chip, kind, B, H, T):
    from paddle_tpu.kernels.gru import fused_gru
    from paddle_tpu.kernels.lstm import fused_lstm

    if kind == "gru":
        fn, args = fused_gru, _gru_args(B, H, T, jnp.float32, one_chip,
                                        one_chip)
    else:
        fn, args = fused_lstm, _lstm_args(B, H, T, jnp.float32, one_chip)
    _c, n = _compile(lambda *a: fn(*a), *args)
    assert n == 1


@pytest.mark.parametrize("B,T,L", [(32, 2048, 64), (32, 512, 64),
                                   (64, 256, 10)])
def test_crf_logz_train_compiles(one_chip, B, T, L):
    from paddle_tpu.layers.crf_ctc import crf_logz_pallas

    def train(emit, mask, w):
        return jax.value_and_grad(
            lambda e, w: jnp.sum(crf_logz_pallas(e, mask, w)),
            argnums=(0, 1))(emit, w)

    _c, n = _compile(train, _sds((B, T, L), jnp.float32, one_chip),
                     _sds((B, T), jnp.float32, one_chip),
                     _sds((L + 2, L), jnp.float32, one_chip))
    assert n >= 2


def test_fused_gru_under_data_parallel_compiles(topo):
    """GSPMD cannot partition a Mosaic kernel; DataParallelTrainer's step
    wraps each in a shard_map over the batch (kernels/_pallas_util). The
    same jit + sharding-annotation program, on the four described chips."""
    from paddle_tpu.kernels._pallas_util import (batch_sharded_kernels,
                                                 call_kernel)
    from paddle_tpu.kernels.gru import fused_gru

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    batch, repl = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())

    def train(x3, wg, wc, b, mask):
        def loss(x3, wg, wc, b):
            hs = call_kernel(fused_gru, (x3, wg, wc, b, mask),
                             batch_argnums=(0, 4))
            return jnp.sum(hs.astype(jnp.float32))

        with batch_sharded_kernels(mesh, "data"):
            return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(
                x3, wg, wc, b)

    args = _gru_args(256, 512, 30, jnp.bfloat16, batch, repl)
    compiled, n = _compile(train, *args)
    assert n == 2
    # the shard_map wrapper adds to the names, the kernels' own stay
    names = _mosaic_instructions(compiled)
    assert any("fused_gru_fwd" in x for x in names) \
        and any("fused_gru_bwd" in x for x in names), names
    assert "all-reduce" in compiled.as_text()       # the weight gradients
    # and without the wrapper the partitioner refuses, which is why
    with pytest.raises(NotImplementedError, match="partitioned"):
        jax.jit(_gru_train).lower(*args)


def test_a_scope_around_a_mosaic_launch_changes_nothing_in_the_lowered_text(
        one_chip):
    """The layer graph's `jax.named_scope` (core/topology.py) is metadata on
    the TPU too: with and without it the lowered text is the same, the
    Mosaic kernel's serialized body in the custom call's `backend_config`
    included. (That body does hold the launch's source positions, so an
    edit that moves a line above a `pallas_call` changes the text, and with
    it the compile-cache key: PERF.md section 6, PR 36.)"""
    import contextlib

    from jax.experimental import pallas as pl

    def twice(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def layer(x, scoped):
        with (jax.named_scope("layer_a") if scoped
              else contextlib.nullcontext()):
            return pl.pallas_call(
                twice, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                name="twice")(x) + 1

    x = _sds((256, 256), jnp.float32, one_chip)
    plain, scoped = (
        jax.jit(lambda x, s=s: layer(x, s)).lower(x).as_text()
        for s in (False, True))
    assert "tpu_custom_call" in plain
    assert plain == scoped


def test_libtpu_api_negotiation():
    """libtpu.so exports GetPjrtApi and speaks the vendored header's PJRT
    API major version; on this chip-less host client creation then fails
    with the TPU runtime's own error (proving dlopen + version check +
    PJRT_Plugin_Initialize all ran). On a TPU host it succeeds."""
    import importlib.util

    from paddle_tpu import native

    spec = importlib.util.find_spec("libtpu")
    if spec is None:
        pytest.skip("no libtpu package installed")
    libtpu = os.path.join(list(spec.submodule_search_locations)[0],
                          "libtpu.so")
    native.build("pjrt")
    try:
        r = native.PjrtRunner(libtpu)
        assert r.device_count >= 1
        r.close()
    except RuntimeError as e:
        # past dlopen/dlsym/version checks, into the TPU runtime proper
        assert "dlopen" not in str(e) and "version mismatch" not in str(e), e
        assert "TPU" in str(e) or "device" in str(e), e
