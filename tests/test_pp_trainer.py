"""PipelineParallelTrainer (ISSUE 8): the heterogeneous GPipe pipeline
threaded through the r10 pipelined SGD train loop — one pipeline runtime,
with host feed overlapping the schedule's bubble (docs/pipeline.md,
"One pipeline").

Pins: PP training matches plain single-device SGD (allclose params,
identical event stream incl. evaluator values); host-overlapped depth 2
is BIT-identical to the synchronous depth-0 PP run; balanced stage
assignment is trajectory-equivalent to naive on the same stream (allclose
losses, identical evaluator totals); r7 snapshot/resume replays the
exact trajectory under the pipeline step; the paddle_pp_* gauges are
live."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import activation, data_type, evaluator, layer, optimizer
from paddle_tpu.io import checkpoint
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.parallel.pp import PipelineParallelTrainer
from paddle_tpu.reader.decorator import checkpointable
from paddle_tpu.trainer import event as v2_event
from paddle_tpu.trainer.trainer import SGD
from paddle_tpu.utils.error import Error

DIM, CLASSES, N, BATCH = 8, 4, 64, 16     # 4 batches per pass

rs = np.random.RandomState(0)
_W = rs.randn(DIM, CLASSES)
X = rs.randn(N, DIM).astype(np.float32)
Y = (X @ _W).argmax(1).astype(np.int64)


def _sample_reader():
    for i in range(N):
        yield (X[i], int(Y[i]))


def _build(trainer_cls=PipelineParallelTrainer, annotate=False, **kw):
    def _attr(d):
        return ({"layer_attr": paddle.attr.ExtraAttr(device=d)}
                if annotate else {})

    x = layer.data(name="x", type=data_type.dense_vector(DIM))
    y = layer.data(name="y", type=data_type.integer_value(CLASSES))
    h1 = layer.fc(input=x, size=32, act=activation.Relu(), name="h1",
                  **_attr(0))
    h2 = layer.fc(input=h1, size=24, act=activation.Relu(), name="h2",
                  **_attr(1))
    h3 = layer.fc(input=h2, size=16, act=activation.Relu(), name="h3",
                  **_attr(2))
    out = layer.fc(input=h3, size=CLASSES, act=activation.Softmax(),
                   name="out", **_attr(3))
    cost = layer.classification_cost(input=out, label=y, name="cost",
                                     **_attr(3))
    params = paddle.parameters_create(paddle.Topology(cost))
    evs = {"err": evaluator.classification_error(input=out, label=y)}
    return trainer_cls(cost=cost, parameters=params,
                       update_equation=optimizer.Adam(learning_rate=1e-2),
                       evaluators=evs, **kw)


def _final(t):
    return {k: np.asarray(t.parameters.get(k))
            for k in t.parameters.names()}


def _run(t, depth, num_passes=2, reader=None, **kw):
    events = []

    def handler(ev):
        if isinstance(ev, v2_event.EndIteration):
            events.append((ev.batch_id, round(float(ev.cost), 6),
                           tuple(sorted((k, round(float(v), 6))
                                        for k, v in ev.metrics.items()))))
        elif isinstance(ev, v2_event.EndPass):
            events.append(("endpass", ev.pass_id,
                           tuple(sorted((k, round(float(v), 6))
                                        for k, v in ev.metrics.items()))))

    t.train(reader or paddle.batch(_sample_reader, BATCH),
            num_passes=num_passes, event_handler=handler,
            pipeline_depth=depth, **kw)
    return _final(t), events


def test_pp_matches_plain_sgd():
    """THE unification pin: the stage-compiled pipeline step trains the
    same trajectory as plain SGD — event stream identical to 1e-6
    (costs, evaluator values, order) and final params allclose."""
    ref, ref_ev = _run(_build(SGD), 0)
    got, got_ev = _run(_build(num_stages=4, balance=True, num_micro=2), 0)
    assert ref_ev == got_ev
    assert any(e[0] == "endpass" for e in ref_ev)
    for k in ref:
        np.testing.assert_allclose(ref[k], got[k], rtol=2e-4, atol=2e-6,
                                   err_msg=k)


def test_pp_host_overlap_bit_identical():
    """Host-overlapped PP training (depth 2/4) is BIT-identical to the
    synchronous PP run: same events, byte-equal final params — the r10
    exact-drain guarantees hold for the pipeline-parallel step."""
    p0, e0 = _run(_build(num_stages=4, balance=True, num_micro=2), 0)
    p2, e2 = _run(_build(num_stages=4, balance=True, num_micro=2), 2)
    p4, e4 = _run(_build(num_stages=4, balance=True, num_micro=2), 4)
    assert e0 == e2 == e4
    for k in p0:
        np.testing.assert_array_equal(p0[k], p2[k])
        np.testing.assert_array_equal(p0[k], p4[k])


def test_pp_balanced_vs_naive_trajectory():
    """Balanced stage assignment vs the naive annotation-inherited one,
    same stream: allclose losses, identical evaluator totals (the stage
    split changes float summation order, never the math)."""
    pn, en = _run(_build(annotate=True, num_micro=2), 2)
    pb, eb = _run(_build(num_stages=4, balance=True, num_micro=2), 2)
    assert len(en) == len(eb)
    for a, b in zip(en, eb):
        if a[0] == "endpass":
            assert b[0] == "endpass" and a[2] == b[2]   # evaluator totals
        else:
            assert a[0] == b[0]
            assert a[1] == pytest.approx(b[1], rel=2e-4, abs=1e-6)
            assert a[2] == b[2]                         # per-batch metrics
    for k in pn:
        np.testing.assert_allclose(pn[k], pb[k], rtol=2e-3, atol=1e-5,
                                   err_msg=k)


def test_pp_snapshot_resume_exact(tmp_path):
    """r7 crash-safety through the pipeline step: params stay a plain
    dict, so step snapshots + resume replay the exact trajectory."""
    ref, _ = _run(_build(num_stages=4, balance=True, num_micro=2), 2)

    class _Crash(RuntimeError):
        pass

    state = {"n": 0}

    def crash_handler(ev):
        if isinstance(ev, v2_event.EndIteration):
            state["n"] += 1
            if state["n"] >= 6:
                raise _Crash("scripted crash after batch 6")

    snap = str(tmp_path / "snaps")
    t1 = _build(num_stages=4, balance=True, num_micro=2)
    with pytest.raises(_Crash):
        t1.train(checkpointable(paddle.batch(_sample_reader, BATCH)),
                 num_passes=2, event_handler=crash_handler,
                 save_every_n_batches=2, snapshot_dir=snap,
                 pipeline_depth=2)

    found = SGD.load_step_resume(snap)
    assert found is not None
    loaded, resume = found
    t2 = _build(num_stages=4, balance=True, num_micro=2)
    for name in loaded.names():
        t2.parameters.set(name, loaded.get(name))
    t2.train(checkpointable(paddle.batch(_sample_reader, BATCH)),
             num_passes=2, resume_state=resume, save_every_n_batches=2,
             snapshot_dir=snap, pipeline_depth=2)
    got = _final(t2)
    for k in ref:
        np.testing.assert_array_equal(got[k], ref[k])
    assert checkpoint.list_step_snapshots(snap) == []


def test_pp_gauges_live():
    """paddle_pp_stage_padding_fraction{kind} and
    paddle_pp_bubble_seconds are set by a PP run."""
    _run(_build(num_stages=4, balance=True, num_micro=2), 2)
    reg = obs_metrics.default_registry
    pad = reg.gauge("paddle_pp_stage_padding_fraction", labels=("kind",))
    for kind in ("param", "boundary"):
        assert 0.0 <= pad.labels(kind=kind).value < 1.0, kind
    assert reg.gauge("paddle_pp_bubble_seconds").value > 0.0


def test_pp_eval_input_pinned_to_last_stage():
    """The balancer plans around evaluator inputs: 'out' is pinned into
    the last stage so its full-batch output can ride back."""
    t = _build(num_stages=4, balance=True, num_micro=2)
    assert t._pt.stages["out"] == t._pt.S - 1
    assert t._pt.stages["cost"] == t._pt.S - 1
    assert t._eval_out_names == ("out",)


def test_pp_refuses_host_tables():
    t = _build(num_stages=4, balance=True, num_micro=2)
    with pytest.raises(Error):
        t.train(paddle.batch(_sample_reader, BATCH), num_passes=1,
                host_tables=["h1.w"])


def test_pp_batch_must_divide_microbatches():
    t = _build(num_stages=4, balance=True, num_micro=3)
    with pytest.raises(Error):
        t.train(paddle.batch(_sample_reader, BATCH), num_passes=1,
                pipeline_depth=0)
