"""End-to-end training slice (SURVEY §7 stage 4): MNIST-shaped FC model
through the full v2-API path — reader -> feeder -> jitted train step ->
events -> checkpoint. Mirrors paddle/trainer/tests/test_TrainerOnePass
one-pass convergence testing.
"""

import io

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import activation, data_type, evaluator, layer, optimizer
from paddle_tpu.dataset import synthetic


def build_model(dim=32, classes=4):
    img = layer.data(name="pixel", type=data_type.dense_vector(dim))
    lab = layer.data(name="label", type=data_type.integer_value(classes))
    h1 = layer.fc(input=img, size=32, act=activation.Relu())
    out = layer.fc(input=h1, size=classes, act=activation.Linear(), name="output")
    cost = layer.classification_cost(input=out, label=lab, name="cost")
    return img, lab, out, cost


def test_train_converges():
    img, lab, out, cost = build_model()
    topo_params = paddle.parameters_create(paddle.Topology(cost))
    trainer = paddle.SGD(
        cost=cost, parameters=topo_params,
        update_equation=optimizer.Adam(learning_rate=1e-2),
        evaluators={"classification_error":
                    evaluator.classification_error(input=out, label=lab)})
    reader = paddle.batch(synthetic.classification(32, 4, 512, seed=3), 64)
    costs = []

    def handler(ev):
        if isinstance(ev, paddle.event.EndPass):
            costs.append(ev.metrics.get("classification_error"))

    trainer.train(reader, num_passes=4, event_handler=handler)
    # synthetic linear data: should fit well within 4 passes
    assert costs[-1] < 0.15, f"error {costs} did not converge"


def test_train_then_infer_and_checkpoint():
    img, lab, out, cost = build_model()
    params = paddle.parameters_create(paddle.Topology(cost))
    trainer = paddle.SGD(cost=cost, parameters=params,
                         update_equation=optimizer.Momentum(
                             learning_rate=0.1, momentum=0.9))
    reader = paddle.batch(synthetic.classification(32, 4, 256, seed=5), 64)
    trainer.train(reader, num_passes=2)

    # inference path
    samples = [(s[0],) for s in list(synthetic.classification(32, 4, 8, seed=6)())]
    probs = paddle.infer(output_layer=out, parameters=trainer.parameters,
                         input=samples)
    assert probs.shape == (8, 4)

    # checkpoint tar round-trip produces identical inference
    buf = io.BytesIO()
    trainer.save_parameter_to_tar(buf)
    buf.seek(0)
    restored = paddle.Parameters.from_tar(buf)
    probs2 = paddle.infer(output_layer=out, parameters=restored, input=samples)
    np.testing.assert_allclose(np.asarray(probs), np.asarray(probs2), rtol=1e-5)


def test_test_method_reports_metrics():
    img, lab, out, cost = build_model()
    params = paddle.parameters_create(paddle.Topology(cost))
    trainer = paddle.SGD(cost=cost, parameters=params,
                         update_equation=optimizer.AdaGrad(learning_rate=0.05),
                         evaluators={"err": evaluator.classification_error(
                             input=out, label=lab)})
    reader = paddle.batch(synthetic.classification(32, 4, 256, seed=7), 64)
    trainer.train(reader, num_passes=2)
    result = trainer.test(paddle.batch(synthetic.classification(32, 4, 128, seed=8), 64))
    assert "err" in result.metrics
    assert 0.0 <= result.metrics["err"] <= 1.0


def test_optimizer_suite_one_step():
    """Every optimizer family performs a step without error and changes
    params (FirstOrderOptimizer.h parity smoke)."""
    from paddle_tpu import optimizer as opt
    import jax.numpy as jnp

    for make in (lambda: opt.Momentum(learning_rate=0.1),
                 lambda: opt.Momentum(learning_rate=0.1, momentum=0.9),
                 lambda: opt.Momentum(learning_rate=0.1, momentum=0.9, nesterov=True),
                 lambda: opt.AdaGrad(learning_rate=0.1),
                 lambda: opt.DecayedAdaGrad(learning_rate=0.1),
                 lambda: opt.AdaDelta(learning_rate=1.0),
                 lambda: opt.RMSProp(learning_rate=0.01),
                 lambda: opt.Adam(learning_rate=0.01),
                 lambda: opt.AdaMax(learning_rate=0.01)):
        o = make()
        params = {"w": jnp.ones((3, 3))}
        state = o.init(params)
        grads = {"w": jnp.full((3, 3), 0.5)}
        new_params, new_state = o.update(grads, state, params)
        assert not np.allclose(np.asarray(new_params["w"]), 1.0), type(o).__name__


def test_lr_schedules():
    from paddle_tpu.optimizer import lr_schedule
    f = lr_schedule(0.1, learning_rate_schedule="constant")
    assert float(f(100)) == pytest.approx(0.1)
    f = lr_schedule(0.1, 0.01, 0.5, "poly")
    assert float(f(0)) == pytest.approx(0.1)
    assert float(f(100)) < 0.1
    f = lr_schedule(0.1, 0.5, 10, "discexp")
    assert float(f(9)) == pytest.approx(0.1)
    assert float(f(10)) == pytest.approx(0.05)


def test_ctr_wide_deep_trains_on_sparse_inputs():
    """BASELINE acceptance config: CTR wide&deep with sparse-embedding
    inputs trains end-to-end (sparse ids -> EP-shardable tables)."""
    from paddle_tpu.models.text import ctr_wide_deep

    W, D, K = 500, 300, 8
    (wide_in, deep_in), lab, out, cost = ctr_wide_deep(
        wide_dim=W, deep_vocab=D, emb_dim=8, max_ids=K, hidden=32)
    params = paddle.parameters_create(paddle.Topology(cost))
    trainer = paddle.SGD(cost=cost, parameters=params,
                         update_equation=optimizer.Adam(learning_rate=5e-3),
                         evaluators={"err": evaluator.classification_error(
                             input=out, label=lab)})

    def reader():
        r = np.random.RandomState(0)
        for _ in range(256):
            wide = sorted(r.choice(W, size=K, replace=False))
            deep = sorted(r.choice(D, size=K, replace=False))
            # learnable signal: click iff enough low wide-ids
            click = int(sum(1 for i in wide if i < W // 2) > K // 2)
            yield wide, deep, click

    errs = []

    def handler(ev):
        if isinstance(ev, paddle.event.EndPass):
            errs.append(ev.metrics["err"])

    trainer.train(paddle.batch(reader, 32), num_passes=6,
                  event_handler=handler)
    assert errs[-1] < errs[0], errs
    assert errs[-1] < 0.35, errs


def test_test_period_runs_mid_pass_evaluation():
    """--test_period N: TestResult events fire every N batches mid-pass
    (reference periodic Tester mode), not only at pass end."""
    from paddle_tpu.utils.flags import FLAGS

    img = layer.data(name="x", type=data_type.dense_vector(6))
    lab = layer.data(name="y", type=data_type.integer_value(2))
    out = layer.fc(input=img, size=2, act=activation.Softmax())
    cost = layer.classification_cost(input=out, label=lab)
    params = paddle.parameters_create(paddle.Topology(cost))
    trainer = paddle.SGD(cost=cost, parameters=params,
                         update_equation=optimizer.Adam(learning_rate=1e-2))

    rng = np.random.RandomState(0)
    data = [(rng.rand(6).astype("float32"), int(rng.randint(2)))
            for _ in range(64)]

    def rd():
        yield from data

    results = []

    def handler(ev):
        if isinstance(ev, paddle.event.TestResult):
            results.append(ev)

    FLAGS.set("test_period", 2)
    try:
        trainer.train(paddle.batch(rd, 16), num_passes=1,
                      event_handler=handler,
                      test_reader=paddle.batch(rd, 16))
    finally:
        FLAGS.set("test_period", 0)
    # 4 batches/pass -> mid-pass tests at batches 2 and 4; the batch-4
    # test doubles as the end-of-pass test (no duplicate evaluation)
    assert len(results) == 2


def test_mid_pass_test_does_not_corrupt_train_metrics():
    """self.test() snapshots/restores shared evaluator accumulation."""
    from paddle_tpu.utils.flags import FLAGS

    img = layer.data(name="x", type=data_type.dense_vector(6))
    lab = layer.data(name="y", type=data_type.integer_value(2))
    out = layer.fc(input=img, size=2, act=activation.Softmax(), name="o")
    cost = layer.classification_cost(input=out, label=lab)
    params = paddle.parameters_create(paddle.Topology(cost))
    ev_err = evaluator.classification_error(input=out, label=lab)
    rng = np.random.RandomState(0)
    data = [(rng.rand(6).astype("float32"), int(rng.randint(2)))
            for _ in range(64)]

    def rd():
        yield from data

    def run(period):
        t = paddle.SGD(cost=cost, parameters=params,
                       update_equation=optimizer.Momentum(
                           learning_rate=0.0, momentum=0.0),  # frozen
                       evaluators={"err": ev_err})
        finals = []

        def h(ev):
            if isinstance(ev, paddle.event.EndPass):
                finals.append(ev.metrics["err"])

        FLAGS.set("test_period", period)
        try:
            t.train(paddle.batch(rd, 16), num_passes=1, event_handler=h,
                    test_reader=paddle.batch(rd[:0] if False else rd, 16))
        finally:
            FLAGS.set("test_period", 0)
        return finals[0]

    # frozen weights: pass-level train error must be identical whether or
    # not mid-pass tests interleave
    base = run(0)
    with_tests = run(1)
    assert base == pytest.approx(with_tests)
