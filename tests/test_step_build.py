"""What building a train step cost, by stage: the `paddle:compile` span hears
jax.monitoring's reports (trainer/trainer.py `_compile_phase`) and writes
paddle_train_step_seconds{phase=compile_trace|compile_lower|compile_backend}
and paddle_train_compile_cache_total{result}."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.trainer import trainer as trainer_mod

STAGES = ("compile_trace", "compile_lower", "compile_backend")
TRACE, LOWER, BACKEND = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration")


def _phases():
    fam = obs_metrics.default_registry.snapshot()["paddle_train_step_seconds"]
    return {dict(labels)["phase"]: (h["sum"], h["count"])
            for labels, h in fam["series"].items()}


def _cache():
    fam = obs_metrics.default_registry.snapshot().get(
        "paddle_train_compile_cache_total", {"series": {}})
    got = {dict(labels)["result"]: v for labels, v in fam["series"].items()}
    return got.get("hit", 0), got.get("miss", 0)


def _delta(after, before, phase):
    a, b = after.get(phase, (0.0, 0)), before.get(phase, (0.0, 0))
    return a[0] - b[0], a[1] - b[1]


def _train_once(width=24):
    x = paddle.layer.data(name="x", type=paddle.data_type.dense_vector(12))
    y = paddle.layer.data(name="y", type=paddle.data_type.integer_value(3))
    # named, so that a second build is the same program to the cache's key
    hid = paddle.layer.fc(input=x, size=width, act=paddle.activation.Tanh(),
                          name="hid")
    out = paddle.layer.fc(input=hid, size=3, act=paddle.activation.Softmax(),
                          name="out")
    cost = paddle.layer.classification_cost(input=out, label=y, name="cost")
    trainer = paddle.SGD(cost=cost, parameters=paddle.parameters.create(cost),
                         update_equation=paddle.optimizer.Momentum(
                             momentum=0.9, learning_rate=1e-2))
    rng = np.random.RandomState(0)
    rows = [(rng.randn(12).astype("float32"), int(rng.randint(3)))
            for _ in range(24)]
    trainer.train(paddle.batch(lambda: iter(rows), 8), num_passes=1,
                  feeding={"x": 0, "y": 1})
    return trainer


def test_stages_are_observed_inside_the_span_and_sum_to_no_more():
    before = _phases()
    _train_once()
    after = _phases()
    span, n = _delta(after, before, "compile")
    assert n == 1 and span > 0
    total = 0.0
    for stage in STAGES:
        secs, count = _delta(after, before, stage)
        assert count == 1 and secs > 0, stage
        total += secs
    assert total <= span
    # three batches, one shape: the two dispatches report nothing
    assert _delta(after, before, "dispatch")[1] == 2


def test_a_jit_outside_the_span_adds_nothing():
    before, cache = _phases(), _cache()
    jax.jit(lambda a: jnp.sin(a) @ a.T)(jnp.ones((5, 5))).block_until_ready()
    after = _phases()
    for stage in STAGES + ("compile",):
        assert _delta(after, before, stage) == (0.0, 0), stage
    assert _cache() == cache


def test_nested_reports_count_each_second_once():
    """An inner jit reports its trace before the outer one ends, inside it,
    and the lowering covers the trace's tail: each second is one stage's."""
    before = _phases()
    with trainer_mod._compile_phase(0, key="hand-made") as run:
        time.sleep(0.06)
        trainer_mod._on_compile_stage(TRACE, 0.01)      # the inner jit
        trainer_mod._on_compile_stage(TRACE, 0.05)      # the outer, around it
        trainer_mod._on_compile_stage(LOWER, 0.02)      # over its last 20 ms
        trainer_mod._on_compile_stage(BACKEND, 10.0)    # cut at the span
        trainer_mod._on_compile_stage("/jax/some/other_duration", 1.0)
    after = _phases()
    trace = _delta(after, before, "compile_trace")[0]
    lower = _delta(after, before, "compile_lower")[0]
    backend = _delta(after, before, "compile_backend")[0]
    assert backend == pytest.approx(run.seconds, abs=5e-3)
    # the backend's report covers all of the span: nothing is left for the
    # stages before it, and the three sum to the span, not to 10 s
    assert trace + lower + backend <= run.seconds + 1e-9
    with trainer_mod._compile_phase(0, key="hand-made") as run:
        time.sleep(0.06)
        trainer_mod._on_compile_stage(TRACE, 0.01)
        trainer_mod._on_compile_stage(TRACE, 0.05)
        trainer_mod._on_compile_stage(LOWER, 0.02)
    assert run.stage_seconds["compile_lower"] == pytest.approx(0.02, abs=2e-3)
    assert run.stage_seconds["compile_trace"] == pytest.approx(0.03, abs=2e-3)
    assert "compile_backend" not in run.stage_seconds
    assert run.summary().startswith("trace 0.03 s, lower 0.02 s")


def test_a_report_from_another_thread_is_not_the_steps():
    import threading

    with trainer_mod._compile_phase(0, key="hand-made") as run:
        t = threading.Thread(
            target=trainer_mod._on_compile_stage, args=(BACKEND, 0.001))
        t.start()
        t.join()
    assert run.stage_seconds == {} and run.summary() == "no stage reported"


def test_second_trainer_on_the_same_shapes_hits_the_persistent_cache(tmp_path):
    from jax._src import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        jax.config.update(names[0], str(tmp_path))
        jax.config.update(names[1], 0.0)
        jax.config.update(names[2], -1)
        compilation_cache.reset_cache()
        hit0, miss0 = _cache()
        _train_once(width=40)
        hit1, miss1 = _cache()
        assert (hit1 - hit0, miss1 - miss0) == (0, 1)
        _train_once(width=40)
        hit2, miss2 = _cache()
        assert (hit2 - hit1, miss2 - miss1) == (1, 0)
        text = obs_metrics.default_registry.to_prometheus()
        assert 'paddle_train_compile_cache_total{result="hit"}' in text
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()
