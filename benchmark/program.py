"""The only module of the benchmark that touches the program: it builds the
configuration's model and trainer by the dotted names the data files give,
drives the trainer's public loop, and reads the program's own counters.
Everything the yardstick decides (traffic, weights, reference, reduction,
limits) lives in the benchmark's other files.
"""

import importlib
import time

import numpy as np


def resolve(dotted):
    mod, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


def compile_cache():
    """The program fixes its compile-cache directory in code: JAX's own
    environment variable if set, else `.jax_cache` inside the checkout."""
    import jax
    import paddle_tpu

    path = paddle_tpu.compile_cache()
    # every program of a run is found again by the next run, small ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _optimizer(spec):
    from paddle_tpu import optimizer

    if spec["kind"] == "adam":
        return optimizer.Adam(beta1=spec["beta1"], beta2=spec["beta2"],
                              epsilon=spec["epsilon"],
                              learning_rate=spec["learning_rate"])
    if spec["kind"] == "momentum":
        reg = optimizer.L2Regularization(spec["l2"]) if spec.get("l2") else None
        return optimizer.Momentum(momentum=spec["momentum"],
                                  learning_rate=spec["learning_rate"],
                                  regularization=reg)
    raise ValueError(f"optimizer kind {spec['kind']!r} is not known")


def build_trainer(config, cell, params):
    """The trainer the cell names, on the benchmark's own weights. Fails if
    the program's parameters are not the leaves the reference declares."""
    from paddle_tpu.core.parameters import Parameters

    out = resolve(config["model"]["builder"])(**config["model"]["args"])
    idx = config["model"].get("cost_index")
    cost = out if idx is None else out[idx]
    trainer = resolve(cell["trainer"])(
        cost, Parameters.from_dict(params), _optimizer(config["optimizer"]),
        **cell.get("trainer_kwargs", {}))
    specs = {k: tuple(s.shape) for k, s in trainer.topology.param_specs().items()}
    mine = {k: tuple(v.shape) for k, v in params.items()}
    if specs != mine:
        diff = sorted(set(specs.items()) ^ set(mine.items()))
        raise SystemExit(f"benchmark: the program's parameters differ from "
                         f"the reference's table: {diff[:6]}")
    static = {k for k, v in trainer.topology.static_map().items() if v}
    return trainer, static


class Loop:
    """Drives `trainer.train` (the public loop, its default pipeline depth)
    over batches, and keeps what the event handler sees: a timestamp and the
    cost at every drained step."""

    def __init__(self, trainer, feeding, annotate=False):
        self.trainer, self.feeding = trainer, feeding
        self.annotate = annotate
        self.drained, self.costs = [], []
        self._span = None

    def _open(self, name):
        if self.annotate:
            import jax

            self._close()
            self._span = jax.profiler.TraceAnnotation("bench:" + name)
            self._span.__enter__()

    def _close(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def _handler(self, ev):
        import paddle_tpu as paddle

        if isinstance(ev, paddle.event.BeginIteration):
            self._open("drain")
        elif isinstance(ev, paddle.event.EndIteration):
            self._close()
            self.drained.append(time.perf_counter())
            self.costs.append(float(ev.cost))

    def run(self, batches):
        """`batches`: an iterator of row lists. Returns when the loop has
        drained its last step."""
        def reader():
            it = iter(batches)
            while True:
                self._open("data_wait")
                rows = next(it, None)
                if rows is None:
                    self._close()
                    return
                # from here to the drain the loop feeds and dispatches
                self._open("feed_dispatch")
                yield rows

        self.trainer.train(reader, num_passes=1, event_handler=self._handler,
                           feeding=self.feeding)
        self._close()


def phase_seconds():
    """{phase: (sum seconds, count)} of paddle_train_step_seconds so far."""
    from paddle_tpu.observability import metrics

    fam = metrics.default_registry.snapshot().get("paddle_train_step_seconds")
    out = {}
    for labels, h in (fam or {"series": {}})["series"].items():
        out[dict(labels)["phase"]] = (h["sum"], h["count"])
    return out


def optimizer_state(trainer):
    return trainer._opt_state


def parameters(trainer):
    return trainer.parameters.as_dict()


def compiled_step(trainer, rows, feeding):
    """The step the loop compiled, lowered again on one batch as the loop
    feeds it and compiled (a cache hit): returns (jax.stages.Compiled, the
    shape key the loop used)."""
    import jax
    from paddle_tpu.trainer.feeder import DataFeeder

    (key, fn), = trainer._step_fns.items()
    feeder = DataFeeder(trainer.topology.data_type(), feeding)
    feeds = trainer._device_put_feeds(trainer._prepare_feeds(feeder(rows)))
    if trainer._shape_key(feeds) != key:
        raise SystemExit(f"benchmark: the batch's shape {trainer._shape_key(feeds)} "
                         f"is not the compiled step's {key}")
    lowered = fn.lower(trainer.parameters.as_dict(), trainer._opt_state,
                       jax.random.PRNGKey(0), feeds)
    return lowered.compile(), key


def step_shapes(trainer):
    """Shape keys of every step the trainer has compiled."""
    return list(trainer._step_fns)
