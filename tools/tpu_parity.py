"""CPU-interpreter vs device-compiled equivalence harness.

The Compare2Function analog (paddle/function/FunctionTest.h:1-60 compares
every kernel's CPU and GPU implementations on random inputs; the
reference runs it per registered Function). Here the two "backends" are:

- reference: op-by-op eager evaluation pinned to the host CPU
  (``jax.disable_jit`` + ``jax.default_device(cpu)``) — the interpreter;
- candidate: the SAME program under ``jax.jit`` on the default device —
  the TPU chip where there is one, the compiled-CPU path in the
  CPU-pinned test suite.

Each case builds a small topology, runs forward on every output and the
gradient of a scalar loss w.r.t. every float parameter, and asserts
numerical agreement. ``jax.default_matmul_precision('highest')`` keeps
TPU matmuls in fp32 so tolerances stay tight.

Run standalone on a machine with a TPU:
    python tools/tpu_parity.py [case ...]
"""

from __future__ import annotations

import os
import sys
from typing import Callable, Dict, List, NamedTuple

import numpy as np

# standalone `python tools/tpu_parity.py` from anywhere: repo root on path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class Case(NamedTuple):
    name: str
    build: Callable  # () -> (topology, feeds: {name: np/Arg}, loss_out: str)
    rtol: float = 1e-4
    atol: float = 1e-5


def _r(seed):
    return np.random.RandomState(seed)


def _seq(B, T, D, seed, ragged=True):
    import jax.numpy as jnp

    from paddle_tpu.core.arg import Arg

    r = _r(seed)
    v = r.randn(B, T, D).astype(np.float32)
    m = np.ones((B, T), np.float32)
    if ragged and T > 2:
        m[0, -1] = 0
        if B > 1:
            m[1, -2:] = 0
    return Arg(jnp.asarray(v * m[..., None]), jnp.asarray(m))


def _ids(B, T, vocab, seed):
    import jax.numpy as jnp

    from paddle_tpu.core.arg import Arg

    r = _r(seed)
    ids = r.randint(0, vocab, (B, T)).astype(np.int32)
    m = np.ones((B, T), np.float32)
    if T > 2:
        m[0, -1] = 0
    return Arg(jnp.asarray(ids), jnp.asarray(m))


# --- case catalog ---------------------------------------------------------

def _case_fc():
    from paddle_tpu import activation, data_type, layer
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="x", type=data_type.dense_vector(16))
    h = layer.fc(input=x, size=24, act=activation.Relu())
    o = layer.fc(input=h, size=8, act=activation.Tanh(), name="o")
    return Topology(o), {"x": _r(0).rand(4, 16).astype(np.float32)}, "o"


def _case_mixed_projections():
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="x", type=data_type.dense_vector(12))
    m = layer.mixed(size=12, input=[
        layer.full_matrix_projection(x, size=12),
        layer.dotmul_projection(x),
        layer.identity_projection(x)], name="m", bias_attr=True)
    g = layer.mixed(size=12, input=[layer.dotmul_operator(a=m, b=x)],
                    name="g")
    return Topology(g), {"x": _r(1).rand(3, 12).astype(np.float32)}, "g"


def _case_conv_pool_bn():
    from paddle_tpu import activation, layer
    from paddle_tpu import data_type
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="img", type=data_type.dense_vector(3 * 8 * 8))
    c = layer.img_conv(input=x, filter_size=3, num_filters=4, num_channels=3,
                       padding=1, act=activation.Linear())
    b = layer.batch_norm(input=c, act=activation.Relu())
    p = layer.img_pool(input=b, pool_size=2, stride=2, name="p")
    return (Topology(p),
            {"img": _r(2).rand(2, 3 * 8 * 8).astype(np.float32)}, "p")


def _case_cmrnorm_maxout():
    from paddle_tpu import layer
    from paddle_tpu import data_type
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="img", type=data_type.dense_vector(4 * 6 * 6))
    n = layer.img_cmrnorm(input=x, size=3, num_channels=4)
    m = layer.maxout(input=n, groups=2, num_channels=4, name="m")
    return (Topology(m),
            {"img": _r(3).rand(2, 4 * 6 * 6).astype(np.float32)}, "m")


def _case_lstm():
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="s", type=data_type.dense_vector_sequence(16))
    l = layer.lstmemory(input=x, name="l")
    last = layer.last_seq(input=l, name="last")
    return Topology(last), {"s": _seq(3, 5, 16, 4)}, "last"


def _case_gru_reverse():
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="s", type=data_type.dense_vector_sequence(12))
    g = layer.grumemory(input=x, reverse=True, name="g")
    f = layer.first_seq(input=g, name="f")
    return Topology(f), {"s": _seq(2, 4, 12, 5)}, "f"


def _case_embedding_pool():
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.topology import Topology

    ids = layer.data(name="ids", type=data_type.integer_value_sequence(50))
    e = layer.embedding(input=ids, size=8)
    p = layer.pooling(input=e, name="p")
    return Topology(p), {"ids": _ids(3, 6, 50, 6)}, "p"


def _case_seq_ops():
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.topology import Topology

    a = layer.data(name="a", type=data_type.dense_vector_sequence(6))
    b = layer.data(name="b", type=data_type.dense_vector_sequence(6))
    sc = layer.seq_concat(a, b)
    rs = layer.seq_reshape(input=sc, reshape_size=12)
    ex = layer.expand(input=layer.last_seq(input=rs), expand_as=rs, name="e")
    return (Topology(ex),
            {"a": _seq(2, 3, 6, 7), "b": _seq(2, 3, 6, 8)}, "e")


def _case_cos_tensor():
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.topology import Topology

    a = layer.data(name="a", type=data_type.dense_vector(10))
    b = layer.data(name="b", type=data_type.dense_vector(10))
    cs = layer.cos_sim(a=a, b=b, name="cs")
    t = layer.tensor(a=a, b=b, size=4, name="t")
    o = layer.concat(input=[cs, t], name="o")
    return (Topology(o), {"a": _r(9).rand(3, 10).astype(np.float32),
                          "b": _r(10).rand(3, 10).astype(np.float32)}, "o")


def _case_elementwise():
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="x", type=data_type.dense_vector(8))
    s = layer.slope_intercept(input=x, slope=2.0, intercept=0.5)
    p = layer.power(input=layer.clip(input=s, min=0.1, max=3.0),
                    weight=layer.slope_intercept(input=x, slope=0.0,
                                                 intercept=2.0))
    sc = layer.scaling(input=p, weight=layer.slope_intercept(
        input=x, slope=0.0, intercept=0.5))
    o = layer.addto(input=[sc, x], name="o", bias_attr=False)
    return (Topology(o),
            {"x": _r(11).rand(2, 8).astype(np.float32) + 0.5}, "o")


def _case_crf():
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="s", type=data_type.dense_vector_sequence(5))
    lab = layer.data(name="lab", type=data_type.integer_value_sequence(5))
    feat = layer.fc(input=x, size=5, name="feat")
    crf = layer.crf(input=feat, label=lab, size=5, name="c")
    return (Topology(crf),
            {"s": _seq(2, 4, 5, 12, ragged=True),
             "lab": _ids(2, 4, 5, 13)}, "c")


def _case_block_expand_rowconv():
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="s", type=data_type.dense_vector_sequence(9))
    rc = layer.row_conv(input=x, context_len=3, name="rc")
    l = layer.last_seq(input=rc, name="l")
    return Topology(l), {"s": _seq(2, 5, 9, 14)}, "l"


def _case_recurrent_group():
    from paddle_tpu import data_type, layer
    from paddle_tpu import trainer_config_helpers as tch
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="s", type=data_type.dense_vector_sequence(12))

    def step(x_t):
        return tch.gru_unit(input=x_t, size=4, name="g")

    g = layer.recurrent_group(step=step, input=x)
    l = layer.last_seq(input=g, name="l")
    return Topology(l), {"s": _seq(2, 5, 12, 15)}, "l"


def _case_costs():
    from paddle_tpu import activation, data_type, layer
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="x", type=data_type.dense_vector(10))
    lab = layer.data(name="lab", type=data_type.integer_value(4))
    o = layer.fc(input=x, size=4, act=activation.Softmax())
    ce = layer.cross_entropy_cost(input=o, label=lab, name="ce")
    return (Topology(ce),
            {"x": _r(16).rand(4, 10).astype(np.float32),
             "lab": _r(17).randint(0, 4, (4, 1)).astype(np.int32)}, "ce")


def _case_hsigmoid_selective():
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="x", type=data_type.dense_vector(12))
    lab = layer.data(name="lab", type=data_type.integer_value(6))
    hs = layer.hsigmoid(input=x, label=lab, num_classes=6, name="hs")
    return (Topology(hs),
            {"x": _r(18).rand(3, 12).astype(np.float32),
             "lab": _r(19).randint(0, 6, (3, 1)).astype(np.int32)}, "hs")


def _case_pad_crop_resize():
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.topology import Topology

    from paddle_tpu import activation

    x = layer.data(name="img", type=data_type.dense_vector(2 * 5 * 5))
    p = layer.pad(input=x, pad_c=[0, 0], pad_h=[1, 1], pad_w=[1, 1],
                  shape_in=(2, 5, 5))
    t = layer.fc(input=layer.resize(input=p, size=2 * 7 * 7), size=6,
                 act=activation.Tanh(), name="t")
    return (Topology(t),
            {"img": _r(20).rand(3, 2 * 5 * 5).astype(np.float32)}, "t")


def _case_mha():
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="s", type=data_type.dense_vector_sequence(16))
    m = layer.multi_head_attention(query=x, size=16, num_heads=4, name="m")
    l = layer.last_seq(input=m, name="l")
    return Topology(l), {"s": _seq(2, 6, 16, 23)}, "l"


def _case_seq_slice_kmax():
    from paddle_tpu import activation, data_type, layer
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="s", type=data_type.dense_vector_sequence(5))
    scored = layer.fc(input=x, size=1, act=activation.Linear(), name="sc")
    k = layer.kmax_seq_score(input=scored, beam_size=2, name="k")
    sliced = layer.seq_slice(input=x, starts=None, ends=None, name="sl")
    pooled = layer.pooling(input=sliced, name="p")
    o = layer.concat(input=[layer.last_seq(input=x), pooled], name="o")
    # k (top-frame indices) compared as a second forward output
    return Topology([o, k]), {"s": _seq(2, 5, 5, 24)}, "o"


def _case_pad_crop_bilinear():
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="img", type=data_type.dense_vector(2 * 5 * 5))
    p = layer.pad(input=x, pad_c=[1, 0], pad_h=[1, 1], pad_w=[0, 1],
                  shape_in=(2, 5, 5))
    cr = layer.crop(input=p, shape_in=(3, 7, 6), shape_out=(2, 5, 5),
                    offset=(1, 1, 0))
    b = layer.bilinear_interp(input=cr, num_channels=2, in_size_x=5,
                              in_size_y=5, out_size_x=8, out_size_y=8,
                              name="b")
    return (Topology(b),
            {"img": _r(25).rand(2, 2 * 5 * 5).astype(np.float32)}, "b")


def _case_elementwise2():
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.topology import Topology

    a = layer.data(name="a", type=data_type.dense_vector(6))
    b = layer.data(name="b", type=data_type.dense_vector(6))
    w = layer.data(name="w", type=data_type.dense_vector(1))
    it = layer.interpolation(input=[a, b], weight=w)
    pr = layer.prelu(input=it, name="pr")
    op = layer.out_prod(a=layer.scale_shift(input=pr),
                        b=layer.slope_intercept(input=a, slope=0.5),
                        name="op")
    return (Topology(op),
            {"a": _r(26).rand(2, 6).astype(np.float32),
             "b": _r(27).rand(2, 6).astype(np.float32),
             "w": _r(28).rand(2, 1).astype(np.float32)}, "op")


def _case_costs2():
    from paddle_tpu import activation, data_type, layer
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="x", type=data_type.dense_vector(7))
    y = layer.data(name="y", type=data_type.dense_vector(3))
    lab = layer.data(name="lab", type=data_type.integer_value(2))
    o = layer.fc(input=x, size=3, act=activation.Linear())
    s = layer.smooth_l1_cost(input=o, label=y, name="s")
    h = layer.huber_regression_cost(input=o, label=y, name="h")
    r = layer.fc(input=x, size=1, act=activation.Linear())
    hc = layer.huber_classification_cost(input=r, label=lab, name="hc")
    tot = layer.concat(input=[s, h, hc], name="tot")
    return (Topology(tot),
            {"x": _r(29).rand(4, 7).astype(np.float32),
             "y": _r(30).rand(4, 3).astype(np.float32),
             "lab": _r(31).randint(0, 2, (4, 1)).astype(np.int32)}, "tot")


def _case_ctc():
    from paddle_tpu import activation, data_type, layer
    from paddle_tpu.core.topology import Topology

    V = 6  # vocab incl. blank
    x = layer.data(name="s", type=data_type.dense_vector_sequence(8))
    lab = layer.data(name="lab", type=data_type.integer_value_sequence(V))
    feat = layer.fc(input=x, size=V, act=activation.Linear())
    c = layer.ctc(input=feat, label=lab, size=V, name="c")
    return (Topology(c),
            {"s": _seq(2, 6, 8, 32, ragged=False),
             "lab": _ids(2, 3, V - 1, 33)}, "c")


def _case_conv3d():
    from paddle_tpu import data_type, layer
    from paddle_tpu.core.topology import Topology

    x = layer.data(name="v3", type=data_type.dense_vector(2 * 4 * 4 * 4))
    c = layer.img_conv3d(input=x, filter_size=3, num_filters=3,
                         num_channels=2, padding=1, stride=1,
                         img_size=4, img_size_y=4, img_size_z=4, name="c3")
    return (Topology(c),
            {"v3": _r(34).rand(2, 2 * 4 * 4 * 4).astype(np.float32)}, "c3")


CASES: List[Case] = [
    Case("fc", _case_fc),
    Case("mixed_projections", _case_mixed_projections),
    Case("conv_pool_bn", _case_conv_pool_bn, rtol=5e-4, atol=5e-5),
    Case("cmrnorm_maxout", _case_cmrnorm_maxout),
    Case("lstm", _case_lstm, rtol=5e-4, atol=5e-5),
    Case("gru_reverse", _case_gru_reverse, rtol=5e-4, atol=5e-5),
    Case("embedding_pool", _case_embedding_pool),
    Case("seq_ops", _case_seq_ops),
    Case("cos_tensor", _case_cos_tensor),
    Case("elementwise", _case_elementwise),
    Case("crf", _case_crf, rtol=5e-4, atol=5e-5),
    Case("block_expand_rowconv", _case_block_expand_rowconv),
    Case("recurrent_group", _case_recurrent_group, rtol=5e-4, atol=5e-5),
    Case("costs", _case_costs),
    Case("hsigmoid_selective", _case_hsigmoid_selective),
    Case("pad_crop_resize", _case_pad_crop_resize),
    Case("mha", _case_mha, rtol=5e-4, atol=5e-5),
    Case("seq_slice_kmax", _case_seq_slice_kmax),
    Case("pad_crop_bilinear", _case_pad_crop_bilinear),
    Case("elementwise2", _case_elementwise2),
    Case("costs2", _case_costs2),
    # CTC's long logsumexp chains accumulate ~1e-3 relative cross-device
    Case("ctc", _case_ctc, rtol=3e-3, atol=1e-3),
    Case("conv3d", _case_conv3d, rtol=5e-4, atol=5e-5),
]


def run_case(case: Case) -> Dict[str, float]:
    """Run one case on both backends; raises AssertionError on mismatch.
    Returns {'fwd_maxerr': .., 'grad_maxerr': ..}."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        topo, feeds, loss_out = case.build()
        params = topo.init_params(jax.random.PRNGKey(0))
        float_params = [k for k, v in params.items()
                        if jnp.issubdtype(jnp.asarray(v).dtype,
                                          jnp.floating)]
        out_names = [o.name for o in topo.outputs]

        def fwd(params, feeds):
            outs = topo.forward(params, feeds, training=False)
            return {n: outs[n].value for n in out_names}

        def loss(params, feeds):
            outs = topo.forward(params, feeds, training=False)
            v = outs[loss_out].value
            return (v.astype(jnp.float32) ** 2).mean()

        grad = jax.grad(lambda fp, rest, feeds: loss({**fp, **rest}, feeds))

        def split(params):
            fp = {k: params[k] for k in float_params}
            rest = {k: v for k, v in params.items() if k not in float_params}
            return fp, rest

        fp, rest = split(params)

        cpu = jax.devices("cpu")[0]
        # reference: op-by-op on host CPU (the interpreter)
        with jax.default_device(cpu), jax.disable_jit():
            ref_out = fwd(params, feeds)
            ref_grad = grad(fp, rest, feeds)
        # candidate: one compiled XLA program on the default device
        cand_out = jax.jit(fwd)(params, feeds)
        cand_grad = jax.jit(grad)(fp, rest, feeds)

        fwd_err = 0.0
        for n in out_names:
            a, b = np.asarray(ref_out[n]), np.asarray(cand_out[n])
            np.testing.assert_allclose(b, a, rtol=case.rtol, atol=case.atol,
                                       err_msg=f"{case.name}: output {n}")
            if a.size:
                fwd_err = max(fwd_err, float(np.max(np.abs(a - b))))
        grad_err = 0.0
        for k in float_params:
            a, b = np.asarray(ref_grad[k]), np.asarray(cand_grad[k])
            np.testing.assert_allclose(b, a, rtol=case.rtol,
                                       atol=max(case.atol, 1e-5),
                                       err_msg=f"{case.name}: grad {k}")
            if a.size:
                grad_err = max(grad_err, float(np.max(np.abs(a - b))))
        return {"fwd_maxerr": fwd_err, "grad_maxerr": grad_err}


def main(argv=None):
    import jax

    names = (argv or sys.argv[1:]) or [c.name for c in CASES]
    by_name = {c.name: c for c in CASES}
    unknown = [n for n in names if n not in by_name]
    if unknown:
        print(f"unknown case(s) {unknown}; known: {sorted(by_name)}")
        return 2
    dev = jax.devices()[0]
    print(f"device: {dev.platform} ({dev})")
    failed = []
    for n in names:
        try:
            errs = run_case(by_name[n])
            print(f"PASS {n}: fwd={errs['fwd_maxerr']:.2e} "
                  f"grad={errs['grad_maxerr']:.2e}")
        except Exception as e:  # a diverging/unlowerable case must not
            failed.append(n)    # abort the survey of the remaining ones
            print(f"FAIL {n}: {type(e).__name__}: {str(e)[:300]}")
    print(f"{len(names) - len(failed)}/{len(names)} cases passed")
    return 1 if failed else 0


if __name__ == "__main__":
    import paddle_tpu

    paddle_tpu.compile_cache()
    sys.exit(main())
