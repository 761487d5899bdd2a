"""SGD trainer: the event-loop train driver.

Analog of python/paddle/v2/trainer.py:24 (SGD.train with
BeginPass/BeginIteration/EndIteration/EndPass events) and the C++
TrainerInternal::trainOneBatch protocol (TrainerInternal.cpp:66-172:
startBatch / forwardBackward / update / finishBatch).

On TPU the whole trainOneBatch body — forward, backward, optimizer update,
batch-norm stat EMA, metric computation — is ONE jitted XLA program
(``_train_step``); the reference's per-layer timers, update callbacks and
grad buffers all collapse into the compiled graph. Data parallelism is a
sharding annotation on the batch (see paddle_tpu.parallel), not a separate
MultiGradientMachine.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.arg import Arg
from paddle_tpu.core.layer import publish_step_stats
from paddle_tpu.core.parameters import Parameters
from paddle_tpu.core.topology import Topology
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.optimizer import Optimizer
from paddle_tpu.trainer import event as v2_event
from paddle_tpu.trainer.feeder import (DataFeeder, FeedBufferPool,
                                       resolve_pack_flags)
from paddle_tpu.utils import logger
from paddle_tpu.utils.error import enforce
from paddle_tpu.utils.flags import FLAGS
from paddle_tpu.utils.stat import timer_scope

# --- train-loop telemetry (host-side only: all of these time AROUND the
# jitted step, never inside it, so the compiled program is untouched —
# pinned by tests/test_observability.py jaxpr tests) ----------------------
_M_STEP_SECONDS = obs_metrics.histogram(
    "paddle_train_step_seconds",
    "Per-batch wall time by phase: data_wait (reader next), feed (batch "
    "in hand -> feeds ready for the step) with its children feed_convert "
    "(feeder stacking) and feed_h2d (device_put until the call returns), "
    "compile (build + first call of a new shape key; its count is the "
    "compile counter) and what JAX says of its stages while the span is "
    "open: compile_trace, compile_lower, compile_backend (XLA's compile "
    "on a cache miss, the load on a hit), dispatch (jitted step enqueue "
    "of a known shape), drain (blocked fetching that batch's cost)",
    labels=("phase",))
_M_BATCHES = obs_metrics.counter(
    "paddle_train_batches_total", "Batches trained by SGD.train")
_M_EXAMPLES = obs_metrics.counter(
    "paddle_train_examples_total", "Examples consumed by SGD.train")
_M_EXAMPLES_PER_SEC = obs_metrics.gauge(
    "paddle_train_examples_per_sec",
    "Examples/sec over the wall clock between consecutive steady-state "
    "drained batches (overlap-aware; the pre-pipeline "
    "n/(wait+feed+compute) double-counted once phases overlapped; "
    "back-to-back boundary drains don't update rate gauges)")
_M_INFLIGHT = obs_metrics.gauge(
    "paddle_train_inflight_batches",
    "Dispatched-but-undrained train steps (<= pipeline_depth - 1; 0 "
    "means the loop is running synchronously or fully drained)")
_M_TFLOPS = obs_metrics.gauge(
    "paddle_train_achieved_tflops_per_sec",
    "Analytic model TFLOP/s of the last compute phase (flops.py)")
_M_MFU = obs_metrics.gauge(
    "paddle_train_mfu",
    "Model FLOP utilization of the last step vs the chip's published "
    "peak (unset on platforms without one, e.g. the CPU test mesh)")
_M_SNAPSHOTS = obs_metrics.counter(
    "paddle_train_step_snapshots_total", "Mid-pass step snapshots written")
_M_PREEMPTIONS = obs_metrics.counter(
    "paddle_train_preemptions_total",
    "Preemption requests honored at a batch boundary")


class _phase(timer_scope):
    """One phase of the train loop, timed once and written three ways from
    this one call site: the span ``paddle:<phase>`` in the profiler's own
    trace (host line, the device's clock) and in the Chrome ``Tracer``
    when enabled, and ``paddle_train_step_seconds{phase}``. ``step`` is
    the global step of the batch the phase works on: the spans of one
    batch share it. A phase left by an exception (the reader's
    StopIteration) is not a completed phase: no observation."""

    __slots__ = ("_hist",)

    def __init__(self, phase, step, **args):
        super().__init__("paddle:" + phase, step=step, **args)
        self._hist = _M_STEP_SECONDS.labels(phase=phase)

    def __exit__(self, exc_type, exc, tb):
        super().__exit__(exc_type, exc, tb)
        if exc_type is None:
            self._hist.observe(self.seconds)
        return False


# --- what building a step cost, by stage -----------------------------------
# JAX reports each stage of a compilation through jax.monitoring when the
# stage ends, with its duration. While the calling thread is inside a
# `paddle:compile` span the listeners keep each report as an interval; the
# span's end turns them into paddle_train_step_seconds{phase=compile_*}.
# These three are durations told after the fact: no span of their own in
# the profile. Outside a span (another program's jit, another thread) a
# report is not the step's and is dropped. The listeners run only when JAX
# compiles: a step that is already compiled never reaches them.
_COMPILE_STAGES = {        # in the order a compilation goes through them
    "/jax/core/compile/jaxpr_trace_duration": "compile_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile_lower",
    # XLA's compile on a miss; the cache's retrieval and load on a hit
    "/jax/core/compile/backend_compile_duration": "compile_backend",
}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_M_COMPILE_CACHE = obs_metrics.counter(
    "paddle_train_compile_cache_total",
    "Persistent compile-cache lookups made while building a train step "
    "(inside a paddle:compile span), by result: hit (the executable was "
    "loaded) or miss (XLA compiled it)",
    labels=("result",))
_building = threading.local()


def _on_compile_stage(event, duration, **_):
    span = getattr(_building, "span", None)
    stage = _COMPILE_STAGES.get(event)
    if span is not None and stage is not None:
        end = time.perf_counter()
        span.stages[stage].append((end - duration, end))


def _on_cache_event(event, **_):
    span = getattr(_building, "span", None)
    result = _CACHE_EVENTS.get(event)
    if span is not None and result is not None:
        _M_COMPILE_CACHE.labels(result=result).inc()
        span.cache[result] += 1


jax.monitoring.register_event_duration_secs_listener(_on_compile_stage)
jax.monitoring.register_event_listener(_on_cache_event)


def _merged(intervals, lo, hi):
    """Sorted, disjoint [(start, end)] of the intervals' parts in [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class _compile_phase(_phase):
    """The `compile` phase, which also hears what JAX says of its stages.
    An inner jit (a kernel's launch wrapper) reports its trace before the
    outer one ends, inside it, and a lowering may trace again: a stage is
    the union of its reports, and a second that two stages cover counts
    for the later stage (backend, then lower, then trace), so the three
    never sum to more than the span."""

    __slots__ = ("stages", "cache", "stage_seconds")

    def __init__(self, step, **args):
        super().__init__("compile", step, **args)
        self.stages = {stage: [] for stage in _COMPILE_STAGES.values()}
        self.cache = {"hit": 0, "miss": 0}
        self.stage_seconds = {}

    def __enter__(self):
        super().__enter__()
        _building.span = self
        return self

    def __exit__(self, exc_type, exc, tb):
        _building.span = None
        super().__exit__(exc_type, exc, tb)
        if exc_type is None:
            lo, hi = self._t0, self._t0 + self.seconds
            taken, covered = [], 0.0
            for stage in reversed(_COMPILE_STAGES.values()):
                mine = _merged(self.stages[stage], lo, hi)
                taken = _merged(mine + taken, lo, hi)
                total = sum(e - s for s, e in taken)
                if mine:
                    self.stage_seconds[stage] = total - covered
                    _M_STEP_SECONDS.labels(phase=stage).observe(
                        total - covered)
                covered = total
        return False

    def summary(self):
        """`trace 1.2 s, lower 0.4 s, backend 8.0 s, cache hit` for the log."""
        parts = [f"{stage[len('compile_'):]} {self.stage_seconds[stage]:.2f} s"
                 for stage in _COMPILE_STAGES.values()
                 if stage in self.stage_seconds]
        parts += [f"cache {r}" + (f" x{n}" if n > 1 else "")
                  for r, n in self.cache.items() if n]
        return ", ".join(parts) or "no stage reported"


class _TimedBatches:
    """Iterator adapter: each ``next`` on the underlying reader is the
    ``data_wait`` phase of the batch it returns (``step_of()`` = that
    batch's global step at the dispatch frontier)."""

    __slots__ = ("_it", "_step_of")

    def __init__(self, it, step_of):
        self._it = it
        self._step_of = step_of

    def __iter__(self):
        return self

    def __next__(self):
        with _phase("data_wait", self._step_of()):
            return next(self._it)


class _InFlight:
    """One dispatched-but-undrained train step: the device values the
    drain side needs to fire batch N's events with exact numbers once
    the dispatch frontier has moved on. cost/metrics are step outputs —
    NOT part of the donated param/opt pytrees — so they stay valid while
    later steps consume (and invalidate) the params they came from."""

    __slots__ = ("batch_id", "step", "cost", "metrics", "n_examples",
                 "dispatch_s", "step_flops", "param_stats", "host_token",
                 "host_grads")

    def __init__(self, batch_id, step, cost, metrics, n_examples, dispatch_s,
                 step_flops, param_stats=None, host_token=None,
                 host_grads=None):
        self.batch_id = batch_id
        # global step of this batch: its drain span carries the number
        # its data_wait/feed/dispatch spans did
        self.step = step
        self.cost = cost
        self.metrics = metrics
        self.n_examples = n_examples
        self.dispatch_s = dispatch_s
        self.step_flops = step_flops
        self.param_stats = param_stats
        # host-resident tables (docs/embedding_cache.md): the staged
        # batch (unique-id map) and the step's [cache_rows, D] cache
        # gradients — flushed to the host store at drain, when the
        # fetch has forced the step to finish anyway
        self.host_token = host_token
        self.host_grads = host_grads


def _compute_metrics(evaluators, outs, loss, feeds):
    """Run every evaluator's device-side compute. Packed-aware evaluators
    (seq_classification_error, chunk, ctc_error) must NOT key on seg_ids
    presence alone — nested SUB_SEQUENCE feeds carry seg_ids too — so the
    harness stamps ``packed_feed`` from the topology's trace-time check
    (the same one that sets ctx.packed) before each compute."""
    fp = getattr(loss, "_feeds_packed", None)
    packed = bool(fp(feeds)) if fp is not None else False
    metrics = {}
    for name, ev in evaluators.items():
        ev.packed_feed = packed
        metrics[name] = ev.compute(outs)
    if "#step_stats" in outs:
        # operator counters, not an evaluator: the loop publishes them
        # where it drains the cost (core/layer.py publish_step_stats)
        metrics["#step_stats"] = outs["#step_stats"]
    return metrics


def make_train_step(loss, optimizer, static, lr_mults=None, evaluators=None,
                    donate=True, accum_steps=1, jit_compile=True,
                    host_tables=()):
    """Build THE jitted train step (TrainerInternal::trainOneBatch as one
    XLA program): forward+backward, optimizer update, batch-norm EMA
    fold-in, metrics.

    ``accum_steps > 1`` reproduces the reference's local gradient
    accumulation (``num_batches_per_send_parameter``,
    TrainerInternal.cpp:245-252 / RemoteParameterUpdater): gradients are
    summed across N consecutive batches and the optimizer applies ONE
    update from their mean — numerically the big-batch update. On TPU the
    accumulator lives in device memory inside the donated optimizer-state
    pytree and the N-way branch is a ``lax.cond`` in the compiled program,
    so accumulation costs no host round trip.

    Sparse-row gradients (the reference's SparseRowMatrix sgdUpdate /
    sparse_update story): when the loss was built by Topology.loss_fn over
    a model with sparse_update parameters consumed by a selective_fc
    gather (layers/misc.py), the step (a) runs ONE abstract discovery
    trace (jax.eval_shape — no runtime cost) to learn which tables get
    row-sparse grads this batch and the tangent-slot shapes, (b) excludes
    those tables from the dense grad tree and differentiates w.r.t. zero
    tangent slots added to the gathered rows instead, and (c) hands the
    optimizer ``SparseRowGrad(rows, values)`` leaves — the dense [C, D]
    gradient is never materialized anywhere in the compiled program.
    Caveat: a sparse-grad table must ONLY be consumed through sparse-
    aware gathers in that step; a second, dense use of the same shared
    parameter would contribute no gradient. Gradient accumulation
    (accum_steps > 1) keeps the dense path — the accumulator is a dense
    pytree.

    ``host_tables`` (docs/embedding_cache.md): parameter names whose
    entry in ``params`` is a compact [cache_rows, D] device row cache of
    a host-resident table, not the table itself. Their gradients — dense
    over the CACHE (XLA's gather-vjp scatter-add lands per-slot sums
    exactly) — are excluded from the device optimizer (the host store
    applies them per row with lazy catch-up) and returned as a fifth
    output ``{name: [cache_rows, D]}``. With host_tables empty the
    traced program and the 4-tuple return are bit-identical to before
    the feature existed (jaxpr-pinned).
    """
    evaluators = dict(evaluators or {})
    host_tables = tuple(host_tables)
    if host_tables and accum_steps > 1:
        raise NotImplementedError(
            "host-resident tables do not compose with gradient "
            "accumulation (accum_steps > 1): the dense accumulator would "
            "span cache generations whose slot->row maps differ")
    if host_tables and optimizer.clip_threshold and optimizer.global_clipping:
        raise NotImplementedError(
            "host-resident tables do not compose with global_clipping: "
            "cache grads are popped before the global-norm computation, "
            "so the table would train unclipped and every other param "
            "would see a different clip scale than HBM-resident training")
    if host_tables and optimizer.model_average is not None:
        raise NotImplementedError(
            "host-resident tables do not compose with model_average: the "
            "Polyak window has no slot for a table that never lives in "
            "device memory (per-batch cache slots cannot be averaged)")
    sparse_capable = getattr(loss, "_sparse_capable", False)

    def step(params, opt_state, rng, feeds):
        slots = {}
        if sparse_capable:
            jax.eval_shape(
                lambda p, r, f: loss(p, f, rng=r, training=True,
                                     sparse_collect=slots)[0],
                params, rng, feeds)
        if slots:
            from paddle_tpu.sparse_grad import SparseRowGrad

            tangents = {pn: jnp.zeros(shape, dt)
                        for pn, (shape, dt) in slots.items()}
            dense_p = {k: v for k, v in params.items() if k not in tangents}

            def split_loss(dp, tg):
                return loss({**dp, **{k: params[k] for k in tangents}},
                            feeds, rng=rng, training=True,
                            sparse_tangents=tg)

            (cost, (outs, aux)), (gd, gt) = jax.value_and_grad(
                split_loss, argnums=(0, 1), has_aux=True)(dense_p, tangents)
            aux = dict(aux)
            rows_map = aux.pop("__sparse_rows__")
            grads = dict(gd)
            for pn, vals in gt.items():
                rows = rows_map[pn].reshape(-1)
                grads[pn] = SparseRowGrad(
                    rows, vals.reshape(rows.shape[0], -1)
                    .astype(params[pn].dtype), params[pn].shape)
        else:
            (cost, (outs, aux)), grads = jax.value_and_grad(
                loss, has_aux=True)(params, feeds, rng=rng, training=True)
        host_grads = {hn: grads.pop(hn) for hn in host_tables
                      if hn in grads}
        with jax.named_scope("optimizer"):
            new_params, new_opt_state = optimizer.update(
                grads, opt_state, params, lr_mults, static)
        # batch-norm / static-state fold-in: assignments, no op; the values'
        # arithmetic is in their layers, under `<layer>/aux_update`
        for pname, val in aux.items():
            new_params[pname] = val
        metrics = _compute_metrics(evaluators, outs, loss, feeds)
        if host_tables:
            return new_params, new_opt_state, cost, metrics, host_grads
        return new_params, new_opt_state, cost, metrics

    if accum_steps > 1:
        def step(params, acc_state, rng, feeds):  # noqa: F811
            opt_state, acc, k = (acc_state["opt"], acc_state["acc"],
                                 acc_state["k"])
            (cost, (outs, aux)), grads = jax.value_and_grad(
                loss, has_aux=True)(params, feeds, rng=rng, training=True)
            acc = jax.tree_util.tree_map(jnp.add, acc, grads)
            k = k + 1

            def do_apply(operand):
                params, opt_state, acc = operand
                mean = jax.tree_util.tree_map(
                    lambda a: a / float(accum_steps), acc)
                with jax.named_scope("optimizer"):
                    new_params, new_opt = optimizer.update(
                        mean, opt_state, params, lr_mults, static)
                zero = jax.tree_util.tree_map(jnp.zeros_like, acc)
                return new_params, new_opt, zero, jnp.zeros((), jnp.int32)

            def do_skip(operand):
                params, opt_state, acc = operand
                return params, opt_state, acc, k

            new_params, new_opt, acc, k = jax.lax.cond(
                k >= accum_steps, do_apply, do_skip, (params, opt_state, acc))
            # batch-norm EMA still folds in every batch (forward-side stat)
            for pname, val in aux.items():
                new_params[pname] = val
            metrics = _compute_metrics(evaluators, outs, loss, feeds)
            return (new_params, {"opt": new_opt, "acc": acc, "k": k},
                    cost, metrics)

    if not jit_compile:
        return step     # raw body
    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def init_accum_state(opt_state, params):
    """Initial optimizer+accumulator state for accum_steps>1 train steps."""
    return {"opt": opt_state,
            "acc": jax.tree_util.tree_map(jnp.zeros_like, dict(params)),
            "k": jnp.zeros((), jnp.int32)}


class AsyncSGDUpdater:
    """Async-SGD with bounded staleness — the TPU-native analog of the
    reference pserver's async update path (ParameterServer2.cpp:457
    ``asyncSGD``, ``handleRequestSendParameter`` applying gradients in
    arrival order against the live parameter copy).

    Trainers there push gradients computed against a possibly-stale
    parameter snapshot; the server applies them immediately and discards
    gradients lagging more than ``async_lagged_grad_discard`` versions
    behind. Here the same protocol is host-side state around one jitted
    grad/update pair: ``push()`` computes gradients against the *current*
    snapshot and enqueues them tagged with the parameter version;
    ``apply()`` pops in arrival order, drops over-stale entries, and runs
    the optimizer update (bumping the version). Overlap comes from XLA's
    async dispatch — grads for batch t+1 compute while update t applies.
    """

    def __init__(self, loss, optimizer, params, opt_state, static=None,
                 lr_mults=None, max_lagged: int = 4, discard: bool = True):
        self.optimizer = optimizer
        self.params = dict(params)
        self.opt_state = opt_state
        self.version = 0
        self.max_lagged = max_lagged
        self.discard = discard
        self.num_discarded = 0
        self._push_count = 0
        from collections import deque
        self._pending = deque()

        def grad_fn(params, rng, feeds):
            (cost, (_outs, aux)), grads = jax.value_and_grad(
                loss, has_aux=True)(params, feeds, rng=rng, training=True)
            return grads, cost, aux

        def update_fn(grads, opt_state, params):
            return optimizer.update(grads, opt_state, params, lr_mults, static)

        self._grad_fn = jax.jit(grad_fn)
        self._update_fn = jax.jit(update_fn, donate_argnums=(1,))

    def push(self, feeds, rng=None) -> float:
        """Compute gradients against the current snapshot and enqueue."""
        if rng is None:
            # keyed by push count, not version: multiple pushes between
            # applies must not share dropout masks
            rng = jax.random.fold_in(jax.random.PRNGKey(0), self._push_count)
        self._push_count += 1
        grads, cost, aux = self._grad_fn(self.params, rng, feeds)
        self._pending.append((grads, aux, self.version))
        return float(cost)

    def apply(self) -> bool:
        """Apply the oldest pending gradient (arrival order). Returns False
        when nothing is pending or the gradient was discarded for
        exceeding the staleness bound."""
        if not self._pending:
            return False
        grads, aux, version = self._pending.popleft()
        if self.discard and self.version - version > self.max_lagged:
            self.num_discarded += 1
            return False
        self.params, self.opt_state = self._update_fn(
            grads, self.opt_state, self.params)
        for pname, val in aux.items():
            self.params[pname] = val
        self.version += 1
        return True

    def train_one_batch(self, feeds, rng=None) -> float:
        """Push + drain: the single-trainer degenerate case (== sync SGD)."""
        cost = self.push(feeds, rng)
        while self._pending:
            self.apply()
        return cost


class SGD:
    """paddle.v2.trainer.SGD analog."""

    def __init__(self, cost, parameters: Parameters, update_equation: Optimizer,
                 extra_layers: Optional[Sequence] = None, is_local: bool = True,
                 mesh=None, evaluators: Optional[Dict[str, object]] = None,
                 donate_params: bool = True, mixed_precision: bool = False,
                 num_batches_per_send_parameter: int = 1):
        self.topology = Topology(cost, extra_layers)
        self.cost_name = cost.name if hasattr(cost, "name") else cost
        self.parameters = parameters
        self.optimizer = update_equation
        self.mesh = mesh
        self.evaluators = dict(evaluators or {})
        # validation LAYERS imply evaluators (AucValidation/PnpairValidation
        # create their own, ValidationLayer.cpp:43-64); explicit
        # declarations win on name clashes
        from paddle_tpu.evaluator import auto_validation_evaluators
        for n, ev in auto_validation_evaluators(self.topology).items():
            self.evaluators.setdefault(n, ev)
        # mixed precision: bf16 compute, fp32 master weights (TPU-first
        # addition; the 2017 reference is fp32-only)
        self._loss = self.topology.loss_fn(
            cost, compute_dtype=jnp.bfloat16 if mixed_precision else None)
        self._static = self.topology.static_map()
        self._lr_mults = self.topology.lr_mults()
        self._opt_state = None
        self._step_fns: Dict[tuple, Callable] = {}
        self._test_fns: Dict[tuple, Callable] = {}
        # host buffers train()'s feeder assembles batches in; kept across
        # train() calls (like _step_fns) so a second call starts warm
        self._feed_buffers = FeedBufferPool()
        self._donate = donate_params
        self._batch_counter = 0
        # local gradient accumulation (num_batches_per_send_parameter,
        # TrainerInternal.cpp:245-252): N batches' grads -> one update
        self._accum_steps = max(1, int(num_batches_per_send_parameter))
        # analytic FLOPs per compiled shape key (for the MFU gauge);
        # None = model not priceable, computed once per key
        self._flops_cache: Dict[tuple, Optional[float]] = {}
        # jitted on-device |param| avg/max reduction for the
        # show_parameter_stats_period dump (built on first use)
        self._param_stats_fn: Optional[Callable] = None
        # per-shape latch: a failing prefetch device_put is warned about
        # once per batch shape and not retried every batch — keyed by
        # shape so a non-divisible tail batch doesn't disable the
        # prefetch for the full-size batches of later passes
        self._prefetch_put_failed: set = set()
        # host-resident embedding tables (docs/embedding_cache.md):
        # built lazily by train() from ParamAttr(host_resident=True) /
        # the host_table_min_rows threshold; () = every table in HBM
        self._host_rt = None
        self._host_tables: tuple = ()
        if FLAGS.get("debug_nans"):
            jax.config.update("jax_debug_nans", True)

    def _flops_for(self, key: tuple, feeds: Dict[str, Arg]):
        """Cached train FLOPs of one batch for this shape key (flops.py
        accounting); None when the topology can't be priced. Never lets a
        pricing failure touch the train loop."""
        if key in self._flops_cache:
            return self._flops_cache[key]
        try:
            from paddle_tpu.flops import train_flops

            batch, seq = 1, 1
            for v in feeds.values():
                shp = np.shape(v.value)
                if shp:
                    batch = int(shp[0])
                if v.mask is not None and len(shp) > 1:
                    seq = max(seq, int(shp[1]))
            val = train_flops(self.topology, batch, seq)
        except Exception:
            val = None
        self._flops_cache[key] = val
        return val

    def _flush_accum(self, params, acc_state):
        """Apply a pending partial accumulation (k < N tail batches)."""
        k = int(acc_state["k"])
        if k == 0:
            return params, acc_state
        mean = jax.tree_util.tree_map(lambda a: a / float(k),
                                      acc_state["acc"])
        new_params, new_opt = self.optimizer.update(
            mean, acc_state["opt"], params, self._lr_mults, self._static)
        zero = jax.tree_util.tree_map(jnp.zeros_like, acc_state["acc"])
        return new_params, {"opt": new_opt, "acc": zero,
                            "k": jnp.zeros((), jnp.int32)}

    # --- jitted step builders --------------------------------------------
    def _build_train_step(self):
        return make_train_step(self._loss, self.optimizer, self._static,
                               self._lr_mults, self.evaluators, self._donate,
                               accum_steps=self._accum_steps,
                               host_tables=self._host_tables)

    # --- optimizer-state layout hooks -------------------------------------
    # Subclasses whose in-loop optimizer state is laid out differently
    # from ``optimizer.init`` (MultiSliceTrainer's ZeRO shards,
    # docs/multislice.md) override these so r7 step snapshots always
    # carry the CANONICAL per-parameter layout — making a snapshot
    # loadable at any world size.
    def _init_opt_state(self, params):
        """Build the in-loop optimizer state for ``params``."""
        return self.optimizer.init(params)

    def _canonical_opt_state(self, opt_state):
        """In-loop layout -> canonical {param: {slot: array}} layout (the
        one ``optimizer.init`` produces), for snapshots."""
        return opt_state

    def _restore_opt_state(self, opt_state):
        """Canonical (host numpy) snapshot layout -> in-loop layout."""
        return jax.tree_util.tree_map(jnp.asarray, opt_state)

    def _snapshot_meta(self) -> dict:
        """Extra step-snapshot meta (subclasses: mesh shape etc.)."""
        return {}

    # --- host-resident tables (docs/embedding_cache.md) -------------------
    def _strip_host(self, params: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        """Drop host-table entries (they hold the per-batch [U, D] device
        cache, NOT the table) before syncing params back into
        self.parameters — the table's truth lives in the host store."""
        if not self._host_tables:
            return params
        return {k: v for k, v in params.items()
                if k not in self._host_tables}

    def _sync_host_tables_back(self):
        """Dense-backed host stores sync their trained rows into
        self.parameters at pass boundaries, so the v2 checkpoint flow
        (EndPass handlers saving parameters, Inference built from them)
        sees the trained table — not its initialization values. Lazy
        stores have no dense twin; their truth stays in the store (and
        in r7 step snapshots via state_dict())."""
        if self._host_rt is None:
            return
        for name, store in self._host_rt.tables.items():
            snap = getattr(store, "dense_snapshot", lambda: None)()
            if snap is not None:
                # set(), not update_from(): the latter replaces the whole
                # param dict (and the train loop's update_from calls strip
                # host names, so the entry must be re-inserted here)
                self.parameters.set(name, snap)

    def _host_cache_sharding(self):
        """Placement for the per-batch device row cache: None = default
        device (plain SGD). DataParallelTrainer overrides with a
        replicated mesh sharding — the cache's slot space is
        batch-derived, so vocab (EP) sharding cannot apply to it."""
        return None

    def _teardown_host_tables(self):
        """Undo a prior host-table run on this trainer: land every
        in-flight flush, sync dense-backed tables (rows + optimizer
        slots) back for the device path, stop the flush worker, and
        restore the host-mode compile state (static flags, cached step
        fns compiled for the 5-tuple host path). No-op when the feature
        was never on."""
        if self._host_rt is not None:
            self._host_rt.barrier()
            self._sync_host_tables_back()
            for pname, store in self._host_rt.tables.items():
                # hand the table's optimizer slots back to the device
                # path (they were an empty dict in host mode)
                snap = getattr(store, "dense_slot_snapshot",
                               lambda: None)()
                if snap is not None and self._opt_state is not None \
                        and pname in self._opt_state:
                    self._opt_state[pname] = {
                        k: jnp.asarray(v) for k, v in snap.items()}
            self._host_rt.close()
        if self._host_tables:
            orig = self.topology.static_map()
            for pname in self._host_tables:
                if pname in orig:
                    self._static[pname] = orig[pname]
                else:
                    self._static.pop(pname, None)
            self._step_fns.clear()
            self._test_fns.clear()
        self._host_rt = None
        self._host_tables = ()

    def _setup_host_tables(self, host_tables, host_cache_rows, host_store,
                           host_staleness, host_flush_inflight):
        """Resolve + build the host-table runtime for this train run.
        Returns the table names ('' tuple when the feature is off — the
        zero-cost default path)."""
        from paddle_tpu.host_table import build_runtime

        if host_tables is None:
            min_rows = int(FLAGS.get("host_table_min_rows", 0) or 0)
            host_tables = self.topology.host_param_names(min_rows)
        host_tables = tuple(sorted(host_tables))
        for pname in self.topology.host_param_names(0):
            # an attr-marked table was never materialized on device
            # (init_params skips it) — without host mode it has no
            # values anywhere; fail clearly, not with a KeyError deep
            # in forward
            enforce(pname in host_tables or pname in self.parameters,
                    f"table {pname!r} is ParamAttr(host_resident=True) "
                    "and was never materialized on device; it cannot "
                    "train with host mode disabled for it (include it "
                    "in host_tables or drop the attr)")
        if self._host_rt is not None and self._host_tables != host_tables:
            # a store without a dense twin (pserver-backed) cannot be
            # synced back into parameters — dropping it from host mode
            # (or rebuilding it without the factory) would abandon its
            # trained rows; refuse clearly instead of KeyError'ing later
            for pname, store in self._host_rt.tables.items():
                if getattr(store, "dense_snapshot", None) is not None:
                    continue
                enforce(pname in host_tables and callable(host_store),
                        f"host table {pname!r} is pserver-backed; its "
                        "rows live in the pserver process and cannot be "
                        "synced back into trainer parameters — keep it "
                        "in host_tables with the same host_store, or "
                        "checkpoint server-side first")
        if not host_tables:
            self._teardown_host_tables()
            return ()
        enforce_msg = ("host-resident tables are not supported under "
                       "multi-process data parallelism yet (each process "
                       "would need its own row-store shard)")
        if jax.process_count() > 1:
            raise NotImplementedError(enforce_msg)
        if host_cache_rows is None:
            host_cache_rows = int(FLAGS.get("host_cache_rows", 0) or 0)
        if host_staleness is None:
            host_staleness = "exact"
        if self._host_rt is not None and self._host_tables == host_tables:
            # resume into the existing runtime (the store holds the
            # trained rows) — but apply this call's knobs rather than
            # silently keeping the first call's sizing/semantics
            self._host_rt.reconfigure(cache_rows=host_cache_rows,
                                      staleness=host_staleness,
                                      flush_inflight=host_flush_inflight)
            return host_tables
        # a DIFFERENT table set than the previous run: tear the old
        # runtime down first (sync rows/slots back, restore static
        # flags, stop the worker) — else the dropped tables would stay
        # frozen behind stale _static=True flags
        self._teardown_host_tables()
        self._host_tables = host_tables
        self._host_rt = build_runtime(
            self.topology, self.optimizer, host_tables,
            parameters=self.parameters, cache_rows=host_cache_rows,
            staleness=host_staleness, flush_inflight=host_flush_inflight,
            store_factory=host_store if callable(host_store) else None,
            seed=FLAGS.get("seed", 1))
        # the cache is fed per batch and updated host-side: the device
        # optimizer must never touch it (its grads are popped anyway)
        for pname in host_tables:
            self._static[pname] = True
        # step fns compiled without the host_tables kwarg are stale
        self._step_fns.clear()
        self._test_fns.clear()
        return host_tables

    def _build_test_step(self):
        loss = self._loss
        evaluators = self.evaluators

        def test_step(params, feeds):
            cost, (outs, _aux) = loss(params, feeds, rng=None, training=False)
            metrics = _compute_metrics(evaluators, outs, loss, feeds)
            return cost, metrics

        return jax.jit(test_step)

    def _prepare_feeds(self, feeds: Dict[str, Arg]) -> Dict[str, Arg]:
        """Hook between the feeder and the jitted step — subclasses
        (DataParallelTrainer under multi-process) turn process-local host
        batches into global arrays."""
        return feeds

    def _prefetch_sharding(self):
        """Placement target for the feed prefetch: None = default
        device; a Sharding = place accordingly; False = skip the
        prefetch entirely (e.g. multi-process DP, where _prepare_feeds
        already built global device arrays)."""
        return None

    def _device_put_feeds(self, feeds: Dict[str, Arg]) -> Dict[str, Arg]:
        """Prefetch-to-device stage of the pipelined loop: start the H2D
        copy of a prepared batch NOW. jax.device_put is async, so batch
        N+1's transfer overlaps the compute of step N already enqueued —
        without it the copy happens lazily inside the next dispatch.
        Subclasses make it sharding-aware by overriding
        ``_prefetch_sharding`` (DataParallelTrainer places the batch
        over the mesh 'data' axis). A placement failure disables the
        prefetch for that batch SHAPE for the rest of the run (one
        warning, no per-batch retry; e.g. a non-divisible tail batch
        under DP) — the jit then transfers those lazily as before."""
        sharding = self._prefetch_sharding()
        if sharding is False:
            return feeds
        key = self._prefetch_latch_key(feeds)
        if key in self._prefetch_put_failed:
            return feeds
        try:
            if sharding is None:
                return jax.device_put(feeds)
            return jax.device_put(feeds, sharding)
        except Exception as e:
            self._prefetch_put_failed.add(key)
            logger.warning("feed prefetch disabled for batch size %s: "
                           "device_put failed (%s); falling back to "
                           "in-dispatch transfer", key, e)
            return feeds

    @staticmethod
    def _prefetch_latch_key(feeds: Dict[str, Arg]):
        """Latch key for prefetch failures: the batch (leading) dim —
        the axis whose divisibility/placement actually varies between
        batches of one run."""
        for a in feeds.values():
            shp = np.shape(getattr(a, "value", a))
            if shp:
                return int(shp[0])
        return 0

    def _param_stats(self, params):
        """Dispatch the on-device avg/max |value| reduction for the
        show_parameter_stats_period dump. The pre-pipeline dump pulled
        every FULL parameter to host with np.asarray mid-loop (a
        pipeline stall proportional to model size); this enqueues one
        tiny jitted program and only two scalars per parameter ever
        cross to host — fetched at drain time with the batch's cost."""
        if self._param_stats_fn is None:
            def stats(ps):
                return {k: (jnp.abs(v).mean(), jnp.abs(v).max())
                        for k, v in ps.items()}

            self._param_stats_fn = jax.jit(stats)
        return self._param_stats_fn(params)

    def _on_batch_drained(self, ent: "_InFlight", wall_s: float,
                          steady: bool):
        """Hook fired by the drain side once batch ``ent`` has been
        forced to completion (``wall_s`` = wall clock since the previous
        drain; ``steady`` False for burst drains at boundaries, same
        semantics as the rate gauges). Subclasses publish loop-shape
        telemetry here — e.g. the pipeline-parallel trainer's
        ``paddle_pp_bubble_seconds`` estimate — without touching the
        drain bookkeeping."""

    @staticmethod
    def _shape_key(feeds: Dict[str, Arg]) -> tuple:
        return tuple(sorted((k, tuple(np.shape(v.value)),
                             v.mask is not None) for k, v in feeds.items()))

    # --- crash-safe step snapshots ---------------------------------------
    def _save_step_snapshot(self, snapshot_dir, params, opt_state, rng,
                            pass_id, batch_id, reader, pass_cost,
                            pass_batches, keep):
        """Write save_dir/step-<global_step>: params + FULL in-loop
        optimizer state (incl. the gradient-accumulation wrapper) + a
        train_state pickle carrying everything replay needs — the RNG
        carry, evaluator partials, resumable reader position, and the
        running pass aggregates. All via the atomic writer, so a crash
        mid-snapshot leaves the previous snapshot loadable."""
        import copy

        from paddle_tpu.io import checkpoint as ckpt

        self.parameters.update_from(self._strip_host(params))
        host_opt = jax.tree_util.tree_map(lambda x: np.asarray(x),
                                          self._canonical_opt_state(opt_state))
        ev_states = {}
        for name, ev in self.evaluators.items():
            ev_states[name] = {
                k: (np.asarray(v) if isinstance(v, jax.Array)
                    else copy.deepcopy(v))
                for k, v in ev.__dict__.items()}
        reader_state = reader.state() if hasattr(reader, "state") else None
        train_state = {"rng": np.asarray(rng), "evaluators": ev_states,
                       "reader_state": reader_state,
                       "pass_cost": float(pass_cost),
                       "pass_batches": int(pass_batches)}
        if self._host_rt is not None:
            # host-resident tables: rows + per-row optimizer slots live
            # outside params — state_dict() barriers on the flush queue
            # first, so the snapshot carries every drained batch's update
            train_state["host_tables"] = self._host_rt.state_dict()
        meta = {"pass_id": int(pass_id), "batch_id": int(batch_id),
                "accum_steps": self._accum_steps, **self._snapshot_meta()}
        path = ckpt.save_step(snapshot_dir, self._batch_counter,
                              self.parameters, host_opt, meta, train_state,
                              keep=keep)
        _M_SNAPSHOTS.inc()
        logger.info("step snapshot %s (pass %d batch %d)", path, pass_id,
                    batch_id)
        return path

    @staticmethod
    def load_step_resume(save_dir):
        """Locate the newest VALID step snapshot under ``save_dir`` and
        unpack it into (Parameters, resume_state) for ``train(...,
        resume_state=...)`` — or None when no usable snapshot exists.
        Torn/corrupt snapshots are skipped with a warning (the loader
        never loads one)."""
        from paddle_tpu.io import checkpoint as ckpt

        found = ckpt.find_latest_step(save_dir)
        if found is None:
            return None
        step, path = found
        params, opt_state, meta = ckpt.load_checkpoint(path)
        ts = meta.get("train_state") or {}
        resume_state = {
            "pass_id": int(meta.get("pass_id", 0)),
            "batch_id": int(meta.get("batch_id", -1)),
            "global_step": int(meta.get("global_step", step)),
            "opt_state": opt_state,
            "rng": ts.get("rng"),
            "evaluators": ts.get("evaluators"),
            "reader_state": ts.get("reader_state"),
            "pass_cost": float(ts.get("pass_cost", 0.0)),
            "pass_batches": int(ts.get("pass_batches", 0)),
            "host_tables": ts.get("host_tables"),
            "path": path,
        }
        return params, resume_state

    # --- public API -------------------------------------------------------
    def train(self, reader, num_passes: int = 1, event_handler=None,
              feeding=None, test_reader=None, start_pass: int = 0,
              save_every_n_batches: int = 0, snapshot_dir: str = None,
              resume_state: dict = None, preempt_event=None,
              keep_snapshots: int = 3, pipeline_depth: Optional[int] = None,
              pack_sequences: Optional[bool] = None,
              pack_max_len: Optional[int] = None,
              bucket_rounding: Optional[int] = None,
              pack_row_rounding: Optional[int] = None,
              host_tables: Optional[Sequence[str]] = None,
              host_cache_rows: Optional[int] = None,
              host_store=None, host_staleness: Optional[str] = None,
              host_flush_inflight: int = 4,
              publish_every_n_batches: int = 0,
              publish_dir: Optional[str] = None,
              publish_url: Optional[str] = None,
              publisher=None, publish_topology=None,
              publish_rows_every_n_batches: int = 0):
        """``start_pass`` resumes pass numbering (reference --start_pass,
        ParamUtil.h:103-112) — the caller is responsible for having loaded
        the matching checkpoint into ``self.parameters``/``_opt_state``.

        Mid-pass crash safety (ISSUE 2): with ``save_every_n_batches > 0``
        and a ``snapshot_dir``, a step snapshot lands every N batches (and
        at preemption). ``resume_state`` (from ``load_step_resume``)
        restores params/optimizer/RNG/evaluators and the reader position
        so the replay continues the EXACT trajectory: a resumed run's
        final parameters match an uninterrupted run of the same seed.
        ``preempt_event`` (a threading.Event, set by e.g. a SIGTERM
        handler) requests snapshot-then-return at the next batch boundary;
        ``self.preempted`` reports it. On normal completion step snapshots
        are cleared — pass-level checkpoints are the durable artifacts.

        Pipelining (ISSUE 5, docs/pipeline.md): ``pipeline_depth`` (None
        -> the ``pipeline_depth`` flag, default 2) overlaps host feed
        with device compute — step N executes while batch N+1 is read,
        fed, and device_put. Up to depth-1 steps stay in flight; their
        (cost, metrics) device values drain in batch order, so events,
        evaluator accumulation, logs and snapshot/test/preemption
        boundaries see the exact synchronous trajectory (snapshot/test/
        preemption boundaries drain the queue fully first). 0/1 restore
        the strictly synchronous loop.

        The loop's feeder assembles every batch in host buffers this
        trainer keeps, rotating through ``depth`` generations (the drain
        bound above is what makes a generation free again by the time it
        comes round), so steady-state assembly allocates no host memory.
        A feed array handed to the step is therefore only valid until
        that step has drained (docs/pipeline.md "Host-buffer rotation").

        ``pack_sequences`` (None -> the ``pack_sequences`` flag, default
        off; docs/packing.md): the feeder packs several ragged samples
        per fixed row with seg_ids, and the segment-aware layer stack
        keeps every packed sequence isolated — same loss/evaluator
        trajectory as the padded feed over the same sample stream,
        without the padding compute. ``pack_max_len`` caps the packed
        row length; ``bucket_rounding`` rounds padded T to a multiple of
        N instead of the next power of two. All three fall back to the
        same-named flags, and mid-pass/end-of-pass ``test()`` evaluation
        reuses the training values so eval feeds compile the same
        shapes.

        Host-resident tables (ISSUE 7, docs/embedding_cache.md):
        ``host_tables`` (None -> ParamAttr(host_resident=True) tables
        plus the ``host_table_min_rows`` size threshold; [] disables)
        names embedding tables that live in a host-RAM/pserver
        HostRowStore instead of device memory. Each batch, the feed
        phase stages only the touched rows into a [host_cache_rows, D]
        device cache (overlapping the previous step's compute under
        pipelining), the compiled step sees ONLY the cache, and per-row
        gradients flush back to the store asynchronously (bounded by
        ``host_flush_inflight``) with lazy per-row optimizer catch-up.
        ``host_staleness="exact"`` (default) drains the pipeline on row
        conflicts so the trajectory matches HBM-resident training;
        "async" accepts up to depth-1 batches of row staleness (the
        reference async-pserver semantics). ``host_store`` may be a
        callable ``(pname, spec) -> store`` (e.g. a PServerRowStore
        factory) to back tables by a pserver process.

        Continuous train→serve publishing (ISSUE 12,
        docs/serving.md "Continuous publishing"): with
        ``publish_every_n_batches > 0`` the trainer drains the pipeline
        every N batches — exactly synchronous parameters, the r7
        snapshot discipline — and hands them to a
        :class:`paddle_tpu.serving_publisher.ContinuousPublisher`
        (``publisher=``, or one built from ``publish_dir`` /
        ``publish_url`` / ``publish_topology`` — the inference layer to
        serve; default the training topology). Publishing can NEVER
        stall or kill training: a NaN step is rejected by the
        validation gate, a daemon outage is a deadline-bounded retry
        then a deferred publish, and a daemon refusal rolls serving
        back to the previous known-good bundle.

        With host-resident tables, ``publish_rows_every_n_batches > 0``
        additionally streams rows dirtied since the last drain as
        ``/v1/rows`` deltas between full publish boundaries (ISSUE 19,
        docs/embedding_cache.md "Train -> serve row freshness") — a
        trained row reaches serving without waiting for (or paying for)
        a full bundle publish. The same never-stall rules apply."""
        if event_handler is None:
            event_handler = _default_event_handler
        self.preempted = False
        if pipeline_depth is None:
            pipeline_depth = FLAGS.get("pipeline_depth", 2)
        depth = max(1, int(pipeline_depth))
        pack_sequences, pack_max_len, bucket_rounding = resolve_pack_flags(
            pack_sequences, pack_max_len, bucket_rounding)
        feeder = DataFeeder(self.topology.data_type(), feeding,
                            buffers=self._feed_buffers,
                            rotate_buffers=depth,
                            pack_sequences=pack_sequences,
                            pack_max_len=pack_max_len,
                            bucket_rounding=bucket_rounding,
                            pack_row_rounding=pack_row_rounding)
        host_tables = self._setup_host_tables(
            host_tables, host_cache_rows, host_store, host_staleness,
            host_flush_inflight)
        if publisher is not None or publish_every_n_batches:
            from paddle_tpu.utils.error import enforce as _enforce

            _enforce(publish_every_n_batches > 0,
                     "publisher= given without publish_every_n_batches: "
                     "pass the publish cadence or the publisher never "
                     "fires")
        if publish_every_n_batches and publisher is None:
            from paddle_tpu.serving_publisher import ContinuousPublisher
            from paddle_tpu.utils.error import enforce as _enforce

            _enforce(publish_dir,
                     "publish_every_n_batches requires publish_dir "
                     "(where versioned bundles land)")
            publisher = ContinuousPublisher(
                publish_topology if publish_topology is not None
                else self.topology,
                publish_dir, publish_url=publish_url)
        publish_on = bool(publish_every_n_batches and publisher is not None)
        if publish_on and self._host_rt is not None \
                and hasattr(publisher, "host_tables") \
                and publisher.host_tables is None:
            # wire the trainer's live stores into the publisher: full
            # publishes spool them as __hostrows__/ sidecars and
            # publish_rows() streams their dirty rows as deltas
            publisher.host_tables = dict(self._host_rt.tables)
        if publish_rows_every_n_batches:
            from paddle_tpu.utils.error import enforce as _enforce

            _enforce(publish_on,
                     "publish_rows_every_n_batches needs a full-publish "
                     "cadence too (publish_every_n_batches + publisher/"
                     "publish_dir): row deltas extend a published "
                     "bundle's lineage")
        # latest drained batch's exact cost: the publisher's NaN-loss
        # gate reads it at each publish boundary
        last_cost_box = [None]
        params = {k: jnp.asarray(v) for k, v in self.parameters.as_dict().items()
                  if k not in self._host_tables}
        resume = dict(resume_state or {})
        resume_batch = int(resume.get("batch_id", -1)) if resume else -1
        if resume:
            start_pass = int(resume.get("pass_id", start_pass))
            self._batch_counter = int(resume.get("global_step",
                                                 self._batch_counter))
        if resume.get("opt_state") is not None:
            # the snapshot carries the CANONICAL layout; the hook maps it
            # into this trainer's in-loop layout — possibly resharding it
            # to a mesh the snapshot was not taken on (elastic rescale,
            # docs/multislice.md)
            opt_state = self._restore_opt_state(resume["opt_state"])
            self._opt_state = (opt_state["opt"]
                               if self._accum_steps > 1 and "opt" in opt_state
                               else opt_state)
        else:
            if self._opt_state is None:
                self._opt_state = self._init_opt_state(params)
            opt_state = self._opt_state
            if self._accum_steps > 1:
                opt_state = init_accum_state(opt_state, params)
        if self._host_tables and self._opt_state is not None:
            for pname in self._host_tables:
                prev = self._opt_state.get(pname)
                if prev:
                    # enabling host mode on a trainer with existing
                    # device optimizer state: hand the table's [V, D]
                    # slots (stamped current through now) to the store
                    # instead of silently discarding the momentum and
                    # carrying the full-size arrays through every step
                    store = (self._host_rt.tables.get(pname)
                             if self._host_rt else None)
                    seed = getattr(store, "seed_slots", None)
                    if seed is not None:
                        seed({k: np.asarray(v) for k, v in prev.items()},
                             t0=self._batch_counter)
                    else:
                        logger.warning(
                            "host table %s: existing device optimizer "
                            "slots cannot be seeded into this store "
                            "backing and are discarded", pname)
                # the cache entry needs a state key (update() walks
                # params), but its slots live in the host store — an
                # empty dict keeps the pytree shape-stable across cache
                # regrows
                self._opt_state[pname] = {}
        if resume.get("rng") is not None:
            rng = jnp.asarray(resume["rng"])
        else:
            rng = jax.random.PRNGKey(FLAGS.get("seed", 1))
        reader_restored = False
        if resume.get("reader_state") is not None \
                and hasattr(reader, "restore"):
            reader.restore(resume["reader_state"])
            reader_restored = True
        if resume.get("host_tables") is not None \
                and self._host_rt is not None:
            # restore the host store rows + per-row optimizer slots the
            # snapshot carried (r7 step granularity for tables that
            # never exist in params)
            self._host_rt.load_state(resume["host_tables"])
        train_fn = None
        log_period = FLAGS.get("log_period", 100)
        stats_period = FLAGS.get("show_parameter_stats_period", 0)
        test_period = FLAGS.get("test_period", 0)
        # dispatch-frontier global step: runs ahead of self._batch_counter
        # (which advances at drain) by the in-flight count; the two agree
        # at every fully-drained boundary
        disp_step = self._batch_counter

        for pass_id in range(start_pass, num_passes):
            resuming_here = bool(resume) and pass_id == start_pass \
                and resume_batch >= 0
            event_handler(v2_event.BeginPass(pass_id))
            if resuming_here and resume.get("evaluators"):
                for name, st in resume["evaluators"].items():
                    if name in self.evaluators:
                        self.evaluators[name].__dict__.clear()
                        self.evaluators[name].__dict__.update(st)
            else:
                for ev in self.evaluators.values():
                    ev.reset()
            pass_cost = resume.get("pass_cost", 0.0) if resuming_here else 0.0
            pass_batches = (resume.get("pass_batches", 0)
                            if resuming_here else 0)
            tested_at = None
            batch_start = resume_batch + 1 if resuming_here else 0
            batch_iter = reader()
            if resuming_here and batch_start > 0 and not reader_restored \
                    and not getattr(reader, "task_queue_backed", False):
                # plain (non-checkpointable, non-queue-backed) reader:
                # drain the already-trained prefix — replays input I/O but
                # no compute. A checkpointable reader skipped internally;
                # a task-queue-backed stream holds only unfinished work.
                for _ in range(batch_start):
                    if next(batch_iter, _DRAINED) is _DRAINED:
                        break
            snapshots_on = bool(save_every_n_batches and snapshot_dir)
            timed_iter = _TimedBatches(batch_iter, lambda: disp_step + 1)

            # --- drain side of the pipeline: fire batch N's events with
            # exact values once its dispatched step has (been forced to)
            # finish. Bookkeeping runs in batch order, lagging the
            # dispatch frontier by at most depth-1 batches.
            inflight: deque = deque()
            drain_clock = [time.perf_counter()]

            def drain_one(steady=True):
                nonlocal pass_cost, pass_batches
                ent = inflight.popleft()
                _M_INFLIGHT.set(len(inflight))
                if depth > 1:
                    # pipelined: Begin/End both fire at drain so the
                    # event SEQUENCE matches the synchronous loop; at
                    # depth<=1 Begin already fired pre-dispatch (exact
                    # legacy timing for handlers doing pre-batch setup)
                    event_handler(v2_event.BeginIteration(pass_id,
                                                          ent.batch_id))
                with _phase("drain", ent.step) as drain:
                    # the float() fetch forces the dispatched step to
                    # finish — everything enqueued through it has executed
                    cost = float(ent.cost)
                drain_s = drain.seconds
                _M_BATCHES.inc()
                now = time.perf_counter()
                wall_s = now - drain_clock[0]
                drain_clock[0] = now
                if ent.n_examples:
                    _M_EXAMPLES.inc(ent.n_examples)
                    # rate gauges only on steady-state drains: a
                    # boundary/pass-end drain_all() pops back-to-back, so
                    # its inter-drain wall is microseconds — publishing
                    # n/wall there would spike examples/sec and MFU to
                    # nonsense as the scrape-visible last value
                    if steady and wall_s > 0:
                        _M_EXAMPLES_PER_SEC.set(ent.n_examples / wall_s)
                if ent.step_flops and steady:
                    from paddle_tpu.flops import mfu as _mfu

                    # overlapped loop: wall clock between drains is the
                    # honest rate denominator (dispatch+drain undercounts
                    # device time once host work hides under it)
                    denom = wall_s if depth > 1 else ent.dispatch_s + drain_s
                    if denom > 0:
                        per_sec = ent.step_flops / denom
                        _M_TFLOPS.set(per_sec / 1e12)
                        m = _mfu(per_sec)
                        if m is not None:
                            _M_MFU.set(m)
                pass_cost += cost
                pass_batches += 1
                last_cost_box[0] = cost
                self._batch_counter += 1
                self._on_batch_drained(ent, wall_s, steady)
                if ent.host_grads is not None:
                    # host-resident tables: the cost fetch above forced
                    # this step to finish, so its cache-row gradients
                    # are ready — hand them to the bounded async flush
                    # queue tagged with the global step (drives the
                    # store-side lr schedule and catch-up gaps)
                    self._host_rt.flush_async(
                        ent.host_token,
                        {k: np.asarray(v)
                         for k, v in ent.host_grads.items()},
                        self._batch_counter)
                if "#step_stats" in ent.metrics:
                    publish_step_stats(ent.metrics["#step_stats"])
                result = {}
                for name, ev in self.evaluators.items():
                    ev.accumulate(ent.metrics[name])
                    result[name] = ev.value()
                event_handler(v2_event.EndIteration(pass_id, ent.batch_id,
                                                    cost, result))
                if log_period and (ent.batch_id + 1) % log_period == 0:
                    logger.info("pass %d batch %d cost=%.6f %s", pass_id,
                                ent.batch_id + 1, cost,
                                " ".join(f"{k}={v:.5f}"
                                         for k, v in result.items()))
                if ent.param_stats is not None:
                    # per-parameter telemetry (TrainerInternal.cpp:186-215
                    # show_parameter_stats_period): avg/max |value|,
                    # reduced on device at dispatch time — only scalars
                    # cross to host here
                    for pname in sorted(ent.param_stats):
                        avg, mx = ent.param_stats[pname]
                        logger.info("  param %s: avg_abs=%.6g max_abs=%.6g",
                                    pname, float(avg), float(mx))

            def drain_all():
                while inflight:
                    drain_one(steady=False)

            for batch_id, data_batch in enumerate(timed_iter,
                                                  start=batch_start):
                if depth <= 1:
                    event_handler(v2_event.BeginIteration(pass_id, batch_id))
                step = disp_step + 1
                staged = None
                with _phase("feed", step):
                    with _phase("feed_convert", step):
                        feeds = self._prepare_feeds(feeder(data_batch))
                    if self._host_rt is not None:
                        # host-resident tables: exact staleness drains
                        # the pipeline when this batch touches a row an
                        # in-flight batch also touched (its flush must
                        # land before the gather); then stage = touched
                        # -id extraction + slot remap + row gather —
                        # host work that overlaps step N's compute
                        if inflight and self._host_rt.peek_conflicts(feeds):
                            drain_all()
                        staged = self._host_rt.stage(
                            feeds, overlapped=bool(inflight))
                        feeds = staged.feeds
                    if depth > 1 or staged is not None:
                        # host time until the calls return: device_put is
                        # async, the copy itself lands under later spans
                        with _phase("feed_h2d", step):
                            if depth > 1:
                                # start the H2D copy now so it overlaps
                                # the still-executing previous step
                                feeds = self._device_put_feeds(feeds)
                            if staged is not None:
                                # the row cache rides the same async lane
                                sh = self._host_cache_sharding()
                                for pname, cache in staged.caches.items():
                                    params[pname] = (
                                        jax.device_put(cache) if sh is None
                                        else jax.device_put(cache, sh))
                key = self._shape_key(feeds)
                rng, step_rng = jax.random.split(rng)
                hgrads = None
                compiling = key not in self._step_fns
                if compiling:
                    # a new shape: building the step AND its first call
                    # (which traces and compiles) are one `compile` span;
                    # the count of {phase=compile} is the compile counter
                    logger.info("compiling train step for shapes %s", key)
                    run = _compile_phase(step, key=str(key))
                else:
                    # a StepTraceAnnotation (step_num): the profile groups
                    # the device's ops by the program's own steps
                    run = _phase("dispatch", step, step_num=step)
                with run:
                    if compiling:
                        self._step_fns[key] = self._build_train_step()
                    train_fn = self._step_fns[key]
                    # async dispatch: returns once enqueued; step N+1 can
                    # enqueue against step N's device-resident donated
                    # outputs without any host sync
                    out = train_fn(params, opt_state, step_rng, feeds)
                    if staged is not None:
                        params, opt_state, cost, metrics, hgrads = out
                        self._host_rt.mark_dispatched(staged)
                    else:
                        params, opt_state, cost, metrics = out
                    if depth <= 1:
                        # synchronous mode: the fetch forces the step to
                        # finish, so the span means executed, not
                        # enqueued (drain_one's float() is then a no-op)
                        cost = float(cost)
                if compiling:
                    logger.info("compiled train step in %.2f s: %s",
                                run.seconds, run.summary())
                dispatch_s = run.seconds
                disp_step += 1
                stats_dev = None
                if stats_period and disp_step % stats_period == 0:
                    stats_dev = self._param_stats(params)
                inflight.append(_InFlight(
                    batch_id, step, cost, metrics,
                    len(data_batch) if hasattr(data_batch, "__len__") else 0,
                    dispatch_s, self._flops_for(key, feeds), stats_dev,
                    host_token=staged, host_grads=hgrads))
                _M_INFLIGHT.set(len(inflight))
                while len(inflight) > depth - 1:
                    drain_one()
                # boundary triggers are decided at the dispatch frontier
                # (their conditions depend only on batch/step counters) and
                # drain the queue fully first, so each sees EXACTLY the
                # state the synchronous loop would have had at batch N
                if (test_period and test_reader is not None
                        and disp_step % test_period == 0):
                    # mid-pass evaluation (--test_period batches; the
                    # reference Tester's periodic mode, Trainer.h:43-132)
                    drain_all()
                    self.parameters.update_from(self._strip_host(params))
                    self._opt_state = (opt_state["opt"]
                                       if self._accum_steps > 1 else opt_state)
                    event_handler(self.test(
                        test_reader, feeding,
                        pack_sequences=pack_sequences,
                        pack_max_len=pack_max_len,
                        pack_row_rounding=pack_row_rounding,
                        bucket_rounding=bucket_rounding))
                    tested_at = self._batch_counter
                    # eval time must not pollute the next steady drain's
                    # rate-gauge wall interval
                    drain_clock[0] = time.perf_counter()
                wrote_snapshot = False
                if snapshots_on \
                        and (batch_id + 1) % save_every_n_batches == 0:
                    drain_all()
                    self._save_step_snapshot(
                        snapshot_dir, params, opt_state, rng, pass_id,
                        batch_id, reader, pass_cost, pass_batches,
                        keep_snapshots)
                    wrote_snapshot = True
                    drain_clock[0] = time.perf_counter()
                if publish_on \
                        and (batch_id + 1) % publish_every_n_batches == 0:
                    # publish boundary: drain first so the bundle holds
                    # EXACTLY the synchronous state at batch N (the r7
                    # snapshot discipline), then hand off. publish()
                    # never raises — a serving-side failure defers or
                    # rolls back, it never stalls this loop.
                    drain_all()
                    self.parameters.update_from(self._strip_host(params))
                    if self._host_rt is not None:
                        # host-resident tables: flush every drained
                        # batch's rows and re-enter them into
                        # parameters, or the bundle would serve stale
                        # embedding rows under fresh dense params
                        self._host_rt.barrier()
                        self._sync_host_tables_back()
                    res = publisher.publish(self.parameters,
                                            step=self._batch_counter,
                                            last_cost=last_cost_box[0])
                    if res.outcome != "published":
                        logger.warning(
                            "publish at step %d: %s (%s)",
                            self._batch_counter, res.outcome, res.detail)
                    drain_clock[0] = time.perf_counter()
                if publish_on and publish_rows_every_n_batches \
                        and (batch_id + 1) % publish_rows_every_n_batches \
                        == 0 \
                        and (publish_every_n_batches == 0
                             or (batch_id + 1) % publish_every_n_batches
                             != 0):
                    # row-delta boundary (skipped when it coincides with
                    # a full publish — the bundle already carries the
                    # rows): land in-flight store flushes, then stream
                    # the dirty rows. No pipeline drain — the store is
                    # the truth for these rows and barrier() makes it
                    # current through the last flushed batch.
                    if self._host_rt is not None:
                        self._host_rt.barrier()
                    res = publisher.publish_rows(step=self._batch_counter)
                    if res.outcome not in ("published", "skipped"):
                        logger.warning(
                            "row delta publish at step %d: %s (%s)",
                            self._batch_counter, res.outcome, res.detail)
                if preempt_event is not None and preempt_event.is_set():
                    # preemption (SIGTERM from the scheduler): snapshot at
                    # this batch boundary and hand control back — the
                    # restarted process resumes from here, losing nothing
                    drain_all()
                    if snapshots_on and not wrote_snapshot:
                        self._save_step_snapshot(
                            snapshot_dir, params, opt_state, rng, pass_id,
                            batch_id, reader, pass_cost, pass_batches,
                            keep_snapshots)
                    self.parameters.update_from(self._strip_host(params))
                    if self._host_rt is not None:
                        # the returned Parameters must carry the trained
                        # table, not lose it to the strip above
                        self._host_rt.barrier()
                        self._sync_host_tables_back()
                    self._opt_state = (opt_state["opt"]
                                       if self._accum_steps > 1 else opt_state)
                    self.preempted = True
                    _M_PREEMPTIONS.inc()
                    logger.warning(
                        "preempted at pass %d batch %d: %s, exiting train "
                        "loop", pass_id, batch_id,
                        "step snapshot written" if snapshots_on
                        else "NO snapshot (snapshots disabled) — mid-pass "
                             "progress is lost")
                    return self.parameters
            drain_all()
            if self._host_rt is not None:
                # pass boundary: every flushed row lands in the store
                # before checkpoints / EndPass handlers read state
                self._host_rt.barrier()
            # pass-end flush of a partial gradient accumulation (the
            # reference sends the pending accumulated grads at
            # finishTrainPass rather than dropping the tail batches)
            if self._accum_steps > 1:
                params, opt_state = self._flush_accum(params, opt_state)
            # sync back for checkpointing / events (host tables re-enter
            # parameters from the store — update_from strips them)
            self.parameters.update_from(self._strip_host(params))
            self._sync_host_tables_back()
            self._opt_state = (opt_state["opt"] if self._accum_steps > 1
                               else opt_state)
            result = {name: ev.value() for name, ev in self.evaluators.items()}
            if test_reader is not None and not (
                    tested_at == self._batch_counter
                    and self._accum_steps == 1):
                # skip only when a mid-pass test already evaluated these
                # exact weights (last batch hit test_period; accum>1 may
                # have flushed a pending update since)
                tr = self.test(test_reader, feeding,
                               pack_sequences=pack_sequences,
                               pack_max_len=pack_max_len,
                               pack_row_rounding=pack_row_rounding,
                               bucket_rounding=bucket_rounding)
                event_handler(tr)
            event_handler(v2_event.EndPass(pass_id, result))
        self.parameters.update_from(self._strip_host(params))
        self._sync_host_tables_back()
        self._opt_state = (opt_state["opt"] if self._accum_steps > 1
                           else opt_state)
        if save_every_n_batches and snapshot_dir:
            # training completed: step snapshots are recovery scratch, the
            # pass-level checkpoints are the durable artifacts — clearing
            # them keeps a rerun from "resuming" into a finished job
            from paddle_tpu.io import checkpoint as ckpt

            ckpt.clear_step_snapshots(snapshot_dir)
        return self.parameters

    def test(self, reader, feeding=None,
             pack_sequences: Optional[bool] = None,
             pack_max_len: Optional[int] = None,
             pack_row_rounding: Optional[int] = None,
             bucket_rounding: Optional[int] = None) -> "v2_event.TestResult":
        import copy

        pack_sequences, pack_max_len, bucket_rounding = resolve_pack_flags(
            pack_sequences, pack_max_len, bucket_rounding)
        feeder = DataFeeder(self.topology.data_type(), feeding,
                            pack_sequences=pack_sequences,
                            pack_max_len=pack_max_len,
                            bucket_rounding=bucket_rounding,
                            pack_row_rounding=pack_row_rounding)
        params = {k: jnp.asarray(v) for k, v in self.parameters.as_dict().items()
                  if k not in self._host_tables}
        if self._host_rt is not None:
            # eval sees every drained batch's row update
            self._host_rt.barrier()
        # Polyak-averaged apply window for evaluation (apply/restore
        # protocol, ParameterUpdaterBase.h:23)
        if self._opt_state is not None:
            params = {**params, **self.optimizer.apply_average(self._opt_state, params)}
        # evaluators are shared with the train loop; snapshot their
        # accumulation so a mid-pass test doesn't corrupt train metrics
        saved = {k: copy.deepcopy(v.__dict__)
                 for k, v in self.evaluators.items()}
        try:
            for ev in self.evaluators.values():
                ev.reset()
            total_cost, n = 0.0, 0
            for data_batch in reader():
                feeds = self._prepare_feeds(feeder(data_batch))
                if self._host_rt is not None:
                    # per-batch row cache for eval, same staging path as
                    # training (forward-only: nothing flushes back)
                    staged = self._host_rt.stage(feeds)
                    feeds = staged.feeds
                    params = {**params,
                              **{p: jnp.asarray(c)
                                 for p, c in staged.caches.items()}}
                key = self._shape_key(feeds)
                if key not in self._test_fns:
                    self._test_fns[key] = self._build_test_step()
                cost, metrics = self._test_fns[key](params, feeds)
                total_cost += float(cost)
                n += 1
                for name, ev in self.evaluators.items():
                    ev.accumulate(metrics[name])
            result = {name: ev.value() for name, ev in self.evaluators.items()}
        finally:
            for k, v in self.evaluators.items():
                v.__dict__.clear()
                v.__dict__.update(saved[k])
        return v2_event.TestResult(total_cost / max(n, 1), result)

    def averaged_parameters(self):
        """apply/restore window (ParameterUpdaterBase.h:23 apply()/
        restore()): a context manager that swaps the Polyak-averaged
        weights into ``self.parameters`` (e.g. for eval or checkpointing)
        and restores the live training weights on exit."""
        import contextlib

        @contextlib.contextmanager
        def _window():
            if self._opt_state is None or getattr(
                    self.optimizer, "model_average", None) is None:
                yield self.parameters
                return
            backup = {k: np.array(v)
                      for k, v in self.parameters.as_dict().items()}
            avg = self.optimizer.apply_average(self._opt_state, backup)
            self.parameters.update_from(
                {k: jnp.asarray(v) for k, v in avg.items()})
            try:
                yield self.parameters
            finally:
                self.parameters.update_from(backup)

        return _window()

    def save_parameter_to_tar(self, f):
        self.parameters.to_tar(f)


#: sentinel for draining exhausted readers on resume
_DRAINED = object()


def _default_event_handler(ev):
    if isinstance(ev, v2_event.EndPass):
        logger.info("Pass %d done. %s", ev.pass_id,
                    " ".join(f"{k}={v:.5f}" for k, v in ev.metrics.items()))
