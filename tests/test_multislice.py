"""Multi-slice trainer tests (ISSUE 9, docs/multislice.md): hierarchical
ICI->DCN gradient reduction + ZeRO-1 optimizer-state sharding on the
2 x 4 slice x data mesh, on the forced-host 8-device CPU platform.

The load-bearing pins:
- ZeRO-sharded trajectory == replicated DataParallelTrainer trajectory
  (losses, final params, final CANONICAL optimizer state) for
  SGD/Momentum/Adam;
- the compiled step's reduction structure (two distinct stages under
  ``hierarchical``, reduce-scatter + shard-psum + all-gather under
  ``zero``) pinned in the jaxpr;
- per-chip optimizer-state bytes <= replicated / data_axis_size + O(1);
- snapshot round-trip through the canonical layout, including across a
  world-size change (the elastic-rescale half lives in
  test_multislice_elastic.py).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.trainer.event as v2_event
from paddle_tpu import activation, data_type, layer, optimizer
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.topology import Topology
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.parallel.dp import DataParallelTrainer
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.parallel.multislice import (MultiSliceTrainer,
                                            make_multislice_train_step,
                                            measure_collectives,
                                            per_chip_opt_bytes, zero_pack,
                                            zero_unpack)

DIM, CLASSES, N, BATCH = 8, 4, 64, 16


def _dataset(seed=0):
    rs = np.random.RandomState(seed)
    w = rs.randn(DIM, CLASSES)
    x = rs.randn(N, DIM).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.int64)
    return x, y


X, Y = _dataset()


def _sample_reader():
    for i in range(N):
        yield (X[i], int(Y[i]))


OPTS = {
    "sgd": lambda: optimizer.Momentum(learning_rate=0.05),
    "momentum": lambda: optimizer.Momentum(learning_rate=0.05, momentum=0.9),
    "adam": lambda: optimizer.Adam(learning_rate=1e-2),
}


def _make_trainer(cls, make_opt=None, mesh=None, with_eval=True, **kw):
    x = layer.data(name="x", type=data_type.dense_vector(DIM))
    y = layer.data(name="y", type=data_type.integer_value(CLASSES))
    h = layer.fc(input=x, size=16, act=activation.Relu(), name="h")
    out = layer.fc(input=h, size=CLASSES, act=activation.Softmax(),
                   name="out")
    cost = layer.classification_cost(input=out, label=y, name="cost")
    params = paddle.parameters_create(paddle.Topology(cost))
    from paddle_tpu import evaluator as ev
    evs = {"err": ev.classification_error(input="out", label="y")} \
        if with_eval else {}
    return cls(cost=cost, parameters=params,
               update_equation=(make_opt or OPTS["adam"])(),
               evaluators=evs, mesh=mesh, **kw)


def _run(trainer, passes=2):
    losses, errs = [], []

    def handler(e):
        if isinstance(e, v2_event.EndIteration):
            losses.append(e.cost)
            if "err" in e.metrics:
                errs.append(e.metrics["err"])

    trainer.train(paddle.batch(_sample_reader, BATCH), num_passes=passes,
                  event_handler=handler)
    return losses, errs


def _final(trainer):
    return {k: np.asarray(trainer.parameters.get(k))
            for k in trainer.parameters.names()}


def test_make_mesh_slice_axes():
    mesh = make_mesh(slice=2, data=4)
    assert dict(mesh.shape) == {"slice": 2, "data": 4}
    assert make_mesh(slice=1).shape == {"slice": 1, "data": 8}
    # default surface unchanged
    assert dict(make_mesh(data=4, model=2).shape) == {"data": 4, "model": 2}


@pytest.mark.parametrize("name", sorted(OPTS))
def test_zero_matches_replicated_dp(name):
    """THE acceptance pin: ZeRO-sharded hierarchical run == replicated
    DataParallelTrainer run — losses, evaluator values, final params AND
    final canonical optimizer state."""
    dp = _make_trainer(DataParallelTrainer, OPTS[name])
    dp_losses, dp_errs = _run(dp)

    ms = _make_trainer(MultiSliceTrainer, OPTS[name],
                       mesh=make_mesh(slice=2, data=4), zero=True)
    ms_losses, ms_errs = _run(ms)

    np.testing.assert_allclose(ms_losses, dp_losses, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(ms_errs, dp_errs, rtol=1e-6, atol=0)
    got, want = _final(ms), _final(dp)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-4, atol=1e-6)
    # canonical opt state matches the replicated trainer's slot for slot
    canon = ms._canonical_opt_state(ms._opt_state)
    for pname, slots in dp._opt_state.items():
        if pname.startswith("__"):
            np.testing.assert_allclose(np.asarray(canon[pname]),
                                       np.asarray(slots))
            continue
        for sname, v in slots.items():
            np.testing.assert_allclose(
                np.asarray(canon[pname][sname]), np.asarray(v),
                rtol=1e-4, atol=1e-6, err_msg=f"{pname}.{sname}")


def test_hierarchical_matches_flat():
    """The two reduction programs are numerically the same update."""
    a = _make_trainer(MultiSliceTrainer, mesh=make_mesh(slice=2, data=4),
                      zero=True, hierarchical=True)
    b = _make_trainer(MultiSliceTrainer, mesh=make_mesh(slice=2, data=4),
                      zero=True, hierarchical=False)
    la, _ = _run(a)
    lb, _ = _run(b)
    np.testing.assert_allclose(la, lb, rtol=2e-5, atol=1e-6)
    ga, gb = _final(a), _final(b)
    for k in ga:
        np.testing.assert_allclose(ga[k], gb[k], rtol=1e-4, atol=1e-6)


def _step_jaxpr(zero, hierarchical):
    x = layer.data(name="x", type=data_type.dense_vector(DIM))
    y = layer.data(name="y", type=data_type.integer_value(CLASSES))
    out = layer.fc(input=x, size=CLASSES, act=activation.Softmax(),
                   name="out")
    cost = layer.classification_cost(input=out, label=y, name="cost")
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    loss = topo.loss_fn(cost)
    opt = optimizer.Momentum(learning_rate=0.01, momentum=0.9)
    mesh = make_mesh(slice=2, data=4)
    state = opt.init(params)
    if zero:
        state = zero_pack(state, params, mesh)
    step = make_multislice_train_step(loss, opt, topo.static_map(),
                                      mesh=mesh, zero=zero,
                                      hierarchical=hierarchical,
                                      donate=False)
    feeds = {"x": Arg(jnp.zeros((16, DIM))),
             "y": Arg(jnp.zeros((16, 1), jnp.int32))}
    txt = str(jax.make_jaxpr(step)(params, state, jax.random.PRNGKey(0),
                                   feeds))
    return " ".join(txt.split())


def _collectives(flat_txt):
    return {
        "reduce_scatter": len(re.findall(r"reduce_scatter\[", flat_txt)),
        "psum_data": len(re.findall(r"psum\[\s*axes=\('data',\)", flat_txt)),
        "psum_slice": len(re.findall(r"psum\[\s*axes=\('slice',\)",
                                     flat_txt)),
        "psum_both": len(re.findall(r"psum\[\s*axes=\('slice', 'data'\)",
                                    flat_txt)),
        "all_gather": len(re.findall(r"all_gather\[", flat_txt)),
    }


# jax 0.9.0's lax.psum binds ONE psum equation per pytree leaf (it
# tree_maps the bind; older releases bound one multi-operand psum for the
# whole gradient dict), so a reduction over the grads of this model
# shows as N_GRAD_LEAVES equations; XLA's all-reduce combiner fuses
# them again below the jaxpr. The STAGES are what the pins hold.
N_GRAD_LEAVES = 2                               # w0, wbias


def test_jaxpr_hierarchical_zero_has_two_reduction_stages():
    """The compiled ZeRO step IS the SURVEY §5.8 program: per-param ICI
    reduce-scatter over 'data' (stage 1), a shard-sized psum per leaf
    over 'slice' (stage 2, the DCN hop at 1/N bytes), per-param ICI
    all-gather of the updated params, + the scalar cost reduction."""
    c = _collectives(_step_jaxpr(zero=True, hierarchical=True))
    assert c["reduce_scatter"] == N_GRAD_LEAVES, c
    assert c["psum_slice"] == N_GRAD_LEAVES, c  # DCN stage, 1/N shards
    assert c["all_gather"] == N_GRAD_LEAVES, c  # param re-replication
    assert c["psum_both"] == 1, c               # cost mean only
    assert c["psum_data"] == 0, c


def test_jaxpr_hierarchical_replicated_has_two_psums():
    c = _collectives(_step_jaxpr(zero=False, hierarchical=True))
    assert c["psum_data"] == N_GRAD_LEAVES, c   # ICI stage
    assert c["psum_slice"] == N_GRAD_LEAVES, c  # DCN stage
    assert c["psum_both"] == 1, c               # cost mean only
    assert c["reduce_scatter"] == 0 and c["all_gather"] == 0, c


def test_jaxpr_flat_has_single_spanning_allreduce():
    c = _collectives(_step_jaxpr(zero=False, hierarchical=False))
    assert c["psum_both"] == N_GRAD_LEAVES + 1, c   # grads + cost
    assert c["psum_data"] == 0 and c["psum_slice"] == 0, c
    assert c["reduce_scatter"] == 0, c


def test_zero_pack_roundtrip_any_world_size():
    """zero_pack o zero_unpack is the identity across DIFFERENT data-axis
    sizes — the property elastic rescale stands on."""
    params = {"w": jnp.asarray(np.random.RandomState(0)
                               .randn(7, 3).astype(np.float32)),
              "b": jnp.asarray(np.random.RandomState(1)
                               .randn(5).astype(np.float32))}
    opt = optimizer.Adam(learning_rate=1e-3)
    canon = opt.init(params)
    mesh24 = make_mesh(slice=2, data=4)
    mesh14 = make_mesh(slice=1, data=4, devices=jax.devices()[:4])
    z = zero_pack(canon, params, mesh24)
    # sharded leaves are flat and padded to a multiple of 4
    assert z["w"]["m"].shape == (24,) and z["b"]["m"].shape == (8,)
    back = zero_unpack(z, params)
    rez = zero_pack(back, params, mesh14)
    back2 = zero_unpack(rez, params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        canon, back2)


def test_per_chip_opt_bytes_drop():
    """Acceptance: ZeRO per-chip optimizer-state bytes <= replicated /
    data_axis_size + O(1) scalars, on the 2x4 mesh."""
    mesh = make_mesh(slice=2, data=4)
    x = layer.data(name="x", type=data_type.dense_vector(64))
    out = layer.fc(input=x, size=64, act=activation.Linear(), name="o")
    cost = layer.square_error_cost(
        input=out, label=layer.data(name="lab",
                                    type=data_type.dense_vector(64)))
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = optimizer.Adam(learning_rate=1e-3)
    canon = opt.init(params)
    repl = per_chip_opt_bytes(canon, mesh, zero=False)
    z = per_chip_opt_bytes(zero_pack(canon, params, mesh), mesh, zero=True)
    n = mesh.shape["data"]
    scalars = 64          # __step__ + per-param t slots + pad slack
    assert z <= repl / n + n * 4 * len(params) + scalars, (z, repl)
    assert z < repl / 2


def test_gauges_published():
    mesh = make_mesh(slice=2, data=4)
    t = _make_trainer(MultiSliceTrainer, mesh=mesh, zero=True)
    _run(t, passes=1)
    reg = obs_metrics.default_registry
    ici = reg.gauge("paddle_ici_allreduce_seconds").value
    dcn = reg.gauge("paddle_dcn_allreduce_seconds").value
    assert ici > 0 and dcn > 0
    zb = reg.gauge("paddle_opt_state_bytes",
                   labels=("layout",)).labels(layout="zero").value
    assert zb > 0
    canon = t._canonical_opt_state(t._opt_state)
    assert zb <= per_chip_opt_bytes(canon, mesh, zero=False)


def test_measure_collectives_returns_positive():
    ici, dcn = measure_collectives(make_mesh(slice=2, data=4),
                                   grad_bytes=1 << 16, iters=2)
    assert ici > 0 and dcn > 0


def test_snapshot_resume_same_world_exact(tmp_path):
    """r7 step snapshots under ZeRO: crash/resume at the SAME world size
    continues the exact trajectory (canonical layout round-trips through
    the in-loop shard layout)."""
    ref = _make_trainer(MultiSliceTrainer, mesh=make_mesh(slice=2, data=4))
    ref_losses, _ = _run(ref, passes=2)

    class _Crash(RuntimeError):
        pass

    seen = {"n": 0}

    def crash_handler(e):
        if isinstance(e, v2_event.EndIteration):
            seen["n"] += 1
            if seen["n"] >= 6:
                raise _Crash()

    snap = str(tmp_path / "snaps")
    t1 = _make_trainer(MultiSliceTrainer, mesh=make_mesh(slice=2, data=4))
    with pytest.raises(_Crash):
        t1.train(paddle.batch(_sample_reader, BATCH), num_passes=2,
                 event_handler=crash_handler, save_every_n_batches=2,
                 snapshot_dir=snap)
    from paddle_tpu.trainer.trainer import SGD as _SGD
    loaded, resume = _SGD.load_step_resume(snap)
    t2 = _make_trainer(MultiSliceTrainer, mesh=make_mesh(slice=2, data=4))
    for name in loaded.names():
        t2.parameters.set(name, loaded.get(name))
    tail = []

    def tail_handler(e):
        if isinstance(e, v2_event.EndIteration):
            tail.append(e.cost)

    t2.train(paddle.batch(_sample_reader, BATCH), num_passes=2,
             resume_state=resume, event_handler=tail_handler,
             save_every_n_batches=2, snapshot_dir=snap)
    np.testing.assert_allclose(tail, ref_losses[-len(tail):], rtol=1e-5,
                               atol=1e-6)
    got, want = _final(t2), _final(ref)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-4, atol=1e-6)


def test_batch_not_divisible_fails_clearly():
    t = _make_trainer(MultiSliceTrainer, mesh=make_mesh(slice=2, data=4))
    with pytest.raises(Exception, match="does not divide"):
        t.train(paddle.batch(_sample_reader, 12), num_passes=1)


def test_guards():
    mesh = make_mesh(slice=2, data=4)
    # global clipping under zero
    with pytest.raises(Exception, match="global_clipping"):
        _make_trainer(MultiSliceTrainer,
                      lambda: optimizer.Momentum(
                          learning_rate=0.1,
                          gradient_clipping_threshold=1.0,
                          global_clipping=True),
                      mesh=mesh, zero=True)
    # model_average under zero
    with pytest.raises(Exception, match="model_average"):
        _make_trainer(MultiSliceTrainer,
                      lambda: optimizer.Momentum(
                          learning_rate=0.1,
                          model_average=optimizer.ModelAverage()),
                      mesh=mesh, zero=True)
    # wrong mesh axes
    with pytest.raises(Exception, match="slice"):
        _make_trainer(MultiSliceTrainer, mesh=make_mesh(data=8, model=1))
    # batch_norm aux state
    x = layer.data(name="x", type=data_type.dense_vector(DIM))
    y = layer.data(name="y", type=data_type.integer_value(CLASSES))
    h = layer.fc(input=x, size=8, act=activation.Linear(), name="hb")
    bn = layer.batch_norm(input=h, act=activation.Relu(), name="bn")
    out = layer.fc(input=bn, size=CLASSES, act=activation.Softmax())
    cost = layer.classification_cost(input=out, label=y)
    params = paddle.parameters_create(paddle.Topology(cost))
    with pytest.raises(Exception, match="batch_norm"):
        MultiSliceTrainer(cost=cost, parameters=params,
                          update_equation=optimizer.Momentum(
                              learning_rate=0.1), mesh=mesh)


def test_per_value_clipping_and_regularization_supported():
    """The elementwise optimizer features ride the shard update
    unchanged — pin one combined run against replicated DP."""
    mk = lambda: optimizer.Momentum(  # noqa: E731
        learning_rate=0.05, momentum=0.9,
        gradient_clipping_threshold=0.5,
        regularization=optimizer.L2Regularization(1e-3))
    dp = _make_trainer(DataParallelTrainer, mk)
    dl, _ = _run(dp)
    ms = _make_trainer(MultiSliceTrainer, mk,
                       mesh=make_mesh(slice=2, data=4), zero=True)
    ml, _ = _run(ms)
    np.testing.assert_allclose(ml, dl, rtol=2e-5, atol=1e-6)


def test_zero_accounting_tool():
    """Acceptance: the accounting tool's bound holds for every optimizer
    — zero per-chip bytes <= replicated / N + O(1) — and the slot-ful
    optimizers actually drop ~Nx."""
    from tools import zero_accounting

    rep = zero_accounting.main(["--quick", "--json"])
    assert rep["data_axis"] == 4
    for name, r in rep["optimizers"].items():
        assert r["within_bound"], (name, r)
        if name != "sgd":        # plain SGD keeps no per-param slots
            assert r["drop"] >= 3.0, (name, r)
