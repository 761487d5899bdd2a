"""Pipelined train loop (ISSUE 5): `SGD.train(pipeline_depth=N)` overlaps
host read/feed/H2D with device compute while draining (cost, metrics)
device values in exact batch order — the pipelined trajectory must be
BIT-identical to the synchronous one (docs/pipeline.md).

Pins: final params / evaluator values / event sequence across depths
0/2/4 (incl. a mid-pass test boundary); snapshot/resume under
pipelining; preemption honored within depth-1 batches with exact
resume; a fault-injected reader raising inside the overlap window;
the jaxpr bit-identity acceptance; the new dispatch/drain phase split,
in-flight gauge, pad-fraction histogram and on-device param-stats dump.
"""

import logging

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import activation, data_type, evaluator, layer, optimizer
from paddle_tpu.distributed.faults import FaultError, FaultPlan, FaultSpec
from paddle_tpu.io import checkpoint
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.reader.decorator import checkpointable
from paddle_tpu.trainer import event as v2_event
from paddle_tpu.trainer.trainer import SGD
from paddle_tpu.utils.flags import FLAGS

DIM, CLASSES, N, BATCH = 8, 2, 64, 16     # 4 batches per pass


def _dataset(seed=0, n=N):
    rs = np.random.RandomState(seed)
    w = rs.randn(DIM, CLASSES)
    x = rs.randn(n, DIM).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.int64)
    return x, y


X, Y = _dataset()


def _sample_reader():
    for i in range(N):
        yield (X[i], int(Y[i]))


def _make_trainer(with_evaluator=True):
    x = layer.data(name="x", type=data_type.dense_vector(DIM))
    y = layer.data(name="y", type=data_type.integer_value(CLASSES))
    out = layer.fc(input=x, size=CLASSES, act=activation.Softmax(),
                   name="out")
    cost = layer.classification_cost(input=out, label=y, name="cost")
    params = paddle.parameters_create(paddle.Topology(cost))
    evs = ({"err": evaluator.classification_error(input=out, label=y)}
           if with_evaluator else {})
    return SGD(cost=cost, parameters=params,
               update_equation=optimizer.Adam(learning_rate=1e-2),
               evaluators=evs)


def _final(trainer):
    return {k: np.asarray(trainer.parameters.get(k))
            for k in trainer.parameters.names()}


def _trace_handler(events):
    def handler(ev):
        if isinstance(ev, v2_event.BeginIteration):
            events.append(("begin", ev.pass_id, ev.batch_id))
        elif isinstance(ev, v2_event.EndIteration):
            events.append(("end", ev.pass_id, ev.batch_id, float(ev.cost),
                           tuple(sorted((k, float(v))
                                        for k, v in ev.metrics.items()))))
        elif isinstance(ev, v2_event.TestResult):
            events.append(("test", float(ev.cost),
                           tuple(sorted((k, float(v))
                                        for k, v in ev.metrics.items()))))
        elif isinstance(ev, v2_event.EndPass):
            events.append(("endpass", ev.pass_id,
                           tuple(sorted((k, float(v))
                                        for k, v in ev.metrics.items()))))
    return handler


def _run(depth, num_passes=2, test_period=0):
    t = _make_trainer()
    events = []
    kw = {}
    if test_period:
        kw["test_reader"] = paddle.batch(_sample_reader, BATCH)
        FLAGS.set("test_period", test_period)
    try:
        t.train(paddle.batch(_sample_reader, BATCH), num_passes=num_passes,
                event_handler=_trace_handler(events),
                pipeline_depth=depth, **kw)
    finally:
        if test_period:
            FLAGS.set("test_period", 0)
    return _final(t), events


# --- THE acceptance pin: bit-identical trajectory --------------------------

def test_pipelined_bit_identical_to_sync():
    """depth 2 and 4 produce byte-identical final parameters, evaluator
    values, and the exact same event sequence (order AND values) as the
    synchronous depth-0 loop — pipelining only reorders WHEN host code
    runs, never what it computes."""
    p0, e0 = _run(0)
    p2, e2 = _run(2)
    p4, e4 = _run(4)
    assert e0 == e2 == e4
    assert any(ev[0] == "end" for ev in e0)
    for k in p0:
        np.testing.assert_array_equal(p0[k], p2[k])
        np.testing.assert_array_equal(p0[k], p4[k])


def test_pipelined_mid_pass_test_boundary_bit_identical():
    """--test_period boundaries drain the in-flight queue fully: the
    TestResult events land at the same position in the sequence with the
    same cost/metrics, and the trajectory stays bit-identical."""
    p0, e0 = _run(0, num_passes=1, test_period=2)
    p3, e3 = _run(3, num_passes=1, test_period=2)
    assert e0 == e3
    assert sum(1 for ev in e0 if ev[0] == "test") == 2
    for k in p0:
        np.testing.assert_array_equal(p0[k], p3[k])


def test_pipelined_snapshot_resume_bit_identical(tmp_path):
    """Mid-pass crash under pipelining: snapshots are written at fully
    drained boundaries, so a resumed run (itself pipelined) lands on the
    synchronous run's exact final parameters."""
    ref, _ = _run(0, num_passes=2)

    class _Crash(RuntimeError):
        pass

    state = {"n": 0}

    def crash_handler(ev):
        if isinstance(ev, v2_event.EndIteration):
            state["n"] += 1
            if state["n"] >= 6:
                raise _Crash("scripted crash after batch 6")

    snap = str(tmp_path / "snaps")
    t1 = _make_trainer()
    with pytest.raises(_Crash):
        t1.train(checkpointable(paddle.batch(_sample_reader, BATCH)),
                 num_passes=2, event_handler=crash_handler,
                 save_every_n_batches=2, snapshot_dir=snap,
                 pipeline_depth=2)

    found = SGD.load_step_resume(snap)
    assert found is not None
    loaded, resume = found
    assert resume["global_step"] >= 4        # lost at most save_every

    t2 = _make_trainer()
    for name in loaded.names():
        t2.parameters.set(name, loaded.get(name))
    t2.train(checkpointable(paddle.batch(_sample_reader, BATCH)),
             num_passes=2, resume_state=resume, save_every_n_batches=2,
             snapshot_dir=snap, pipeline_depth=4)
    got = _final(t2)
    for k in ref:
        np.testing.assert_array_equal(got[k], ref[k])
    assert checkpoint.list_step_snapshots(snap) == []


def test_pipelined_preemption_bounded_lag_exact_resume(tmp_path):
    """Preemption under pipelining is honored at a fully drained batch
    boundary at most depth-1 batches after the flag was raised; the
    snapshot is trajectory-exact, so the resumed run still matches the
    uninterrupted synchronous run bit for bit."""
    import threading

    ref, _ = _run(0, num_passes=1)
    snap = str(tmp_path / "snaps")
    depth = 2
    preempt = threading.Event()
    state = {"n": 0}

    def handler(ev):
        if isinstance(ev, v2_event.EndIteration):
            state["n"] += 1
            if state["n"] == 2:
                preempt.set()

    t1 = _make_trainer()
    t1.train(checkpointable(paddle.batch(_sample_reader, BATCH)),
             num_passes=1, event_handler=handler, save_every_n_batches=3,
             snapshot_dir=snap, preempt_event=preempt,
             pipeline_depth=depth)
    assert t1.preempted
    found = SGD.load_step_resume(snap)
    assert found is not None
    loaded, resume = found
    # flag raised at the drain of batch 2 (global step 2); honored within
    # the in-flight window
    assert 2 <= resume["global_step"] <= 2 + (depth - 1)

    t2 = _make_trainer()
    for name in loaded.names():
        t2.parameters.set(name, loaded.get(name))
    t2.train(checkpointable(paddle.batch(_sample_reader, BATCH)),
             num_passes=1, resume_state=resume, pipeline_depth=depth)
    got = _final(t2)
    for k in ref:
        np.testing.assert_array_equal(got[k], ref[k])


def test_reader_fault_inside_overlap_window_surfaces(tmp_path):
    """An r7 injected reader fault that fires while steps are in flight
    raises in the consumer (SGD.train's caller), and the snapshot written
    before the fault stays valid for resume."""
    snap = str(tmp_path / "snaps")
    plan = FaultPlan([FaultSpec("reader.next", "drop", at=3)])
    t = _make_trainer()
    with plan.installed():
        with pytest.raises(FaultError):
            t.train(checkpointable(paddle.batch(_sample_reader, BATCH)),
                    num_passes=1, save_every_n_batches=2, snapshot_dir=snap,
                    pipeline_depth=4)
    assert plan.fired() == [("reader.next", 3, "drop")]
    found = checkpoint.find_latest_step(snap)
    assert found is not None and found[0] == 2


# --- acceptance: pipelining changes no compiled program --------------------

def _tiny_step_jaxpr():
    from paddle_tpu.core.layer import layer_name_scope
    from paddle_tpu.trainer.trainer import make_train_step
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.arg import Arg
    from paddle_tpu.core.topology import Topology

    with layer_name_scope():
        img = layer.data(name="px", type=data_type.dense_vector(8))
        lab = layer.data(name="lb", type=data_type.integer_value(3))
        out = layer.fc(input=img, size=3, act=activation.Softmax())
        cost = layer.classification_cost(input=out, label=lab)
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = optimizer.Adam(learning_rate=1e-2)
    opt_state = opt.init(params)
    loss = topo.loss_fn(cost)
    step = make_train_step(loss, opt, topo.static_map(), jit_compile=False)
    feeds = {"px": Arg(jnp.zeros((4, 8), jnp.float32)),
             "lb": Arg(jnp.zeros((4, 1), jnp.int32))}
    return str(jax.make_jaxpr(step)(params, opt_state,
                                    jax.random.PRNGKey(1), feeds))


def test_pipelining_changes_no_jaxpr():
    """Pipelining is host-side orchestration only: the train-step program
    compiled under a deeply pipelined trainer is bit-identical to the one
    the synchronous loop runs (extends the r9 instrumentation pin)."""
    before = _tiny_step_jaxpr()
    _run(4, num_passes=1)                     # a pipelined run in between
    after = _tiny_step_jaxpr()
    assert before == after


# --- observability wiring --------------------------------------------------

def test_dispatch_drain_phases_and_inflight_gauge():
    reg = obs_metrics.default_registry
    hist = reg.histogram("paddle_train_step_seconds", labels=("phase",))
    before = {p: hist.labels(phase=p).count
              for p in ("data_wait", "feed", "feed_convert", "feed_h2d",
                        "compile", "dispatch", "drain")}
    _run(4, num_passes=1)
    got = {p: hist.labels(phase=p).count - before[p] for p in before}
    # a fresh trainer, one shape: its first step is the `compile` phase,
    # the other three are `dispatch`
    assert got.pop("compile") == 1 and got.pop("dispatch") == 3
    assert set(got.values()) == {4}, got
    # fully drained at exit
    assert reg.gauge("paddle_train_inflight_batches").value == 0
    assert reg.gauge("paddle_train_examples_per_sec").value > 0


def test_rate_gauges_skip_burst_drains():
    """Review pin: the back-to-back pops of a boundary/pass-end
    drain_all have microsecond inter-drain walls; publishing n/wall
    there would leave an absurd examples/sec spike as the scrape-visible
    value. With a ~2ms/batch reader the steady rate is bounded by
    BATCH/2ms; the final pass-end burst (depth 4 leaves 3 in flight)
    must not blow past it."""
    import time

    def slow_reader():
        def r():
            for i in range(0, N, BATCH):
                time.sleep(2e-3)
                yield [(X[j], int(Y[j])) for j in range(i, i + BATCH)]
        return r

    t = _make_trainer()
    t.train(slow_reader(), num_passes=1, pipeline_depth=4)
    rate = obs_metrics.default_registry.gauge(
        "paddle_train_examples_per_sec").value
    assert 0 < rate < BATCH / 2e-3 * 5, rate


def test_param_stats_dump_on_device(caplog):
    """show_parameter_stats_period under pipelining: the avg/max |value|
    dump still appears per period, computed by the jitted on-device
    reduction (only scalars are fetched), and the values match a host
    recomputation at the same boundary."""
    FLAGS.set("show_parameter_stats_period", 4)
    logged = {}

    def handler(ev):
        # batch 3 (global step 4) triggers the dump; its drain happens
        # before the next dispatch boundary, so the params at the END of
        # training pass 1 x 4 batches are exactly the dumped ones
        pass

    try:
        t = _make_trainer()
        with caplog.at_level(logging.INFO, logger="paddle_tpu"):
            t.train(paddle.batch(_sample_reader, BATCH), num_passes=1,
                    event_handler=handler, pipeline_depth=2)
        lines = [r.getMessage() for r in caplog.records
                 if "avg_abs" in r.getMessage()]
        assert lines, "no parameter-stats lines logged"
        # 4 batches, period 4 -> exactly one dump covering every param
        assert len(lines) == len(list(t.parameters.names()))
        # dump fired at the final batch: values must equal the final params
        for line in lines:
            pname = line.split()[1].rstrip(":")
            vals = np.abs(np.asarray(t.parameters.get(pname)))
            avg = float(line.split("avg_abs=")[1].split()[0])
            mx = float(line.split("max_abs=")[1].split()[0])
            assert avg == pytest.approx(float(vals.mean()), rel=1e-4)
            assert mx == pytest.approx(float(vals.max()), rel=1e-4)
    finally:
        FLAGS.set("show_parameter_stats_period", 0)


def test_feed_pad_fraction_histogram():
    """DataFeeder observes the power-of-two bucketing padding waste per
    feed slot (satellite: the v5e re-measure sees bucketing overhead
    alongside data-wait)."""
    from paddle_tpu.trainer.feeder import DataFeeder

    reg = obs_metrics.default_registry
    hist = reg.histogram("paddle_feed_pad_fraction",
                         labels=("feed", "packed"))
    child = hist.labels(feed="w", packed="0")
    before = (child.count, child.sum)
    feeder = DataFeeder([("w", data_type.integer_value_sequence(50))],
                        rotate_buffers=3)
    batch = [([1, 2, 3, 4, 5],), ([6, 7, 8],)]
    arg = feeder(batch)["w"]
    # max len 5 buckets to T=8; 8 real steps of 16 -> pad fraction 0.5
    assert arg.value.shape == (2, 8)
    assert child.count - before[0] == 1
    assert child.sum - before[1] == pytest.approx(0.5)
    # rotate_buffers is a no-op for a bare feeder (no pool): conversions
    # stay correct across consecutive calls
    arg2 = feeder(batch)["w"]
    np.testing.assert_array_equal(np.asarray(arg.value),
                                  np.asarray(arg2.value))


def test_feed_pool_kept_across_train_calls_bit_identical():
    """SGD.train assembles every batch in the trainer's own pool,
    rotated through ``depth`` generations, and keeps the pool across
    train() calls: the second call allocates nothing, and the
    trajectory is the one a trainer fed from fresh arrays gives, bit
    for bit, at any depth."""
    ctr = obs_metrics.default_registry.counter(
        "paddle_feed_buffer_total", labels=("feed", "result"))

    def allocated():
        return sum(ctr.labels(feed=f, result="allocated").value
                   for f in ("x", "y"))

    def run(depth, pooled):
        t = _make_trainer()
        if not pooled:
            t._feed_buffers = None          # bare feeder: fresh arrays
        t.train(paddle.batch(_sample_reader, BATCH), num_passes=1,
                pipeline_depth=depth)
        before = allocated()
        t.train(paddle.batch(_sample_reader, BATCH), num_passes=1,
                pipeline_depth=depth)
        return _final(t), allocated() - before

    ref, fresh_allocs = run(0, pooled=False)
    assert fresh_allocs == 2 * (N // BATCH)     # two arrays a batch
    for depth in (0, 3):
        got, allocs = run(depth, pooled=True)
        assert allocs == 0
        for k in ref:
            np.testing.assert_array_equal(got[k], ref[k])


def test_prefetch_latch_is_per_shape():
    """Review pin: a batch shape whose sharded device_put fails (e.g. a
    non-divisible tail batch) must not disable the prefetch for other
    shapes — the latch is keyed by batch size."""
    t = _make_trainer()
    from paddle_tpu.core.arg import Arg
    import jax.numpy as jnp

    good = {"x": Arg(jnp.zeros((16, 4)))}
    bad = {"x": Arg(jnp.zeros((3, 4)))}
    calls = []

    def fake_put(x, *a, **kw):
        b = next(iter(x.values())).value.shape[0]
        calls.append(b)
        if b == 3:
            raise ValueError("injected placement failure")
        return x

    import jax as _jax
    _jax_device_put = _jax.device_put
    _jax.device_put = fake_put
    try:
        t._device_put_feeds(bad)            # fails -> latches shape 3
        t._device_put_feeds(good)           # still prefetches
        t._device_put_feeds(bad)            # latched: no retry
    finally:
        _jax.device_put = _jax_device_put
    assert calls == [3, 16]
    assert t._prefetch_put_failed == {3}


def test_dp_pipelined_bit_identical():
    """DataParallelTrainer's sharding-aware device prefetch: pipelined
    DP training matches synchronous DP training bit for bit on the
    8-device test mesh."""
    from paddle_tpu.parallel.dp import DataParallelTrainer

    def run(depth):
        x = layer.data(name="x", type=data_type.dense_vector(DIM))
        y = layer.data(name="y", type=data_type.integer_value(CLASSES))
        out = layer.fc(input=x, size=CLASSES, act=activation.Softmax(),
                       name="out")
        cost = layer.classification_cost(input=out, label=y, name="cost")
        params = paddle.parameters_create(paddle.Topology(cost))
        t = DataParallelTrainer(cost=cost, parameters=params,
                                update_equation=optimizer.Adam(
                                    learning_rate=1e-2))
        t.train(paddle.batch(_sample_reader, BATCH), num_passes=1,
                pipeline_depth=depth)
        return _final(t)

    a, b = run(0), run(3)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
