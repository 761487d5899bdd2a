"""Multi-slice data parallelism: hierarchical ICI->DCN gradient
reduction + ZeRO-1 sharded optimizer state on a 2D slice x data mesh.

SURVEY §5.8 names this design for scaling past one pod: "pserver-side
optimizer ops become sharded optimizer states (ZeRO-style) updated
locally on each chip", with collectives hierarchical — ICI inside a
slice, DCN across slices (cf. Rajbhandari et al. 2020, ZeRO; GSPMD-style
spec-driven placement). The reference's sync pserver sharded dense
parameter BLOCKS over server processes (ParameterServer2.h:163-238) and
ran the optimizer server-side on each shard; here the same 1/N-state
idea lands on the chips themselves, and the cross-slice hop that used to
be trainer->pserver TCP is a DCN collective over 1/N-sized shards.

The compiled step (``make_multislice_train_step``) is an explicit
``shard_map`` program over the mesh ('slice', 'data'), so the two
reduction stages are visible primitives in the jaxpr (pinned by
tests/test_multislice.py), not an XLA planning artifact:

  hierarchical + zero   psum_scatter(g, 'data')  [ICI reduce-scatter]
                        psum(shard, 'slice')     [DCN, 1/N bytes]
                        local shard update, all_gather(p, 'data')  [ICI]
  hierarchical + repl   psum(g, 'data') then psum(g, 'slice')
  flat                  one psum over ('slice', 'data') — the baseline
                        a single cross-DCN all-reduce pays full bytes

ZeRO-1 layout: every param-shaped optimizer slot is flattened, padded to
a multiple of the data-axis size N, and sharded over 'data' (replicated
over 'slice' — each slice owns a full copy of the sharded state, the
slice-local update is identical everywhere after the DCN reduce). Step
snapshots store the CANONICAL per-parameter layout (``zero_unpack``), so
a snapshot taken on a 2x4 mesh resumes on 1x4 — or any other world size
— by repacking (elastic rescale, docs/multislice.md).
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core.arg import Arg, as_arg
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.trainer.trainer import SGD, _compute_metrics
from paddle_tpu.utils import logger
from paddle_tpu.utils.error import enforce

_M_ICI_ALLREDUCE = obs_metrics.gauge(
    "paddle_ici_allreduce_seconds",
    "Measured wall seconds of one gradient-sized all-reduce over the "
    "mesh 'data' axis (intra-slice ICI). Probed by MultiSliceTrainer at "
    "step-build time with a buffer matching the model's gradient bytes. "
    "NOTE on the CPU test mesh both axes ride host memory, so the "
    "ICI/DCN asymmetry only shows on real multi-slice hardware "
    "(ROADMAP v5e re-measure)")
_M_DCN_ALLREDUCE = obs_metrics.gauge(
    "paddle_dcn_allreduce_seconds",
    "Measured wall seconds of one all-reduce over the mesh 'slice' axis "
    "(cross-slice DCN) at the byte size that stage actually moves: "
    "full gradient bytes under replicated/flat reduction, 1/N shard "
    "bytes under hierarchical ZeRO (the point of reduce-scattering "
    "before the DCN hop)")
_M_OPT_BYTES = obs_metrics.gauge(
    "paddle_opt_state_bytes",
    "Per-chip optimizer-state bytes of the current training run, by "
    "layout (zero = 1/data-axis shard + replicated scalars)",
    labels=("layout",))


# --- ZeRO-1 state layout ---------------------------------------------------

def _chunks(shape, n: int):
    """(size, chunk, padded) for flatten-pad-shard over an axis of n."""
    size = int(np.prod(shape)) if shape else 1
    chunk = -(-size // n)                       # ceil
    return size, chunk, chunk * n


def _is_param_slot(v, pshape) -> bool:
    return hasattr(v, "shape") and tuple(v.shape) == tuple(pshape)


def zero_pack(opt_state: dict, params: Dict[str, jax.Array], mesh: Mesh,
              device_put: bool = True) -> dict:
    """Canonical optimizer state -> ZeRO-1 layout for ``mesh``: every
    param-shaped slot becomes a flat [N*chunk] array sharded over 'data'
    (zero-padded tail); scalar slots and '__step__' stay replicated.
    With ``device_put`` the sharded placement is applied eagerly (the
    jitted step's in_specs would otherwise reshard on first call)."""
    n = mesh.shape["data"]
    sh_data = NamedSharding(mesh, P("data"))
    sh_repl = NamedSharding(mesh, P())

    def put(x, sh):
        return jax.device_put(x, sh) if device_put else x

    out = {}
    for pname, slots in opt_state.items():
        if pname not in params:
            # reserved global entries ("__step__" etc. — NOT matched by
            # a name prefix: auto-named layers produce params like
            # '___fc_0__.w0'); replicate whatever structure they carry
            out[pname] = jax.tree_util.tree_map(
                lambda x: put(jnp.asarray(x), sh_repl), slots)
            continue
        pshape = params[pname].shape
        _size, _chunk, padded = _chunks(pshape, n)
        packed = {}
        for k, v in slots.items():
            if _is_param_slot(v, pshape):
                flat = jnp.ravel(jnp.asarray(v))
                flat = jnp.pad(flat, (0, padded - flat.shape[0]))
                packed[k] = put(flat, sh_data)
            else:
                enforce(not hasattr(v, "shape") or np.ndim(v) == 0,
                        f"optimizer slot {pname}.{k} is neither "
                        f"param-shaped nor scalar (shape "
                        f"{getattr(v, 'shape', None)}); the ZeRO-1 "
                        "layout cannot shard it")
                packed[k] = put(jnp.asarray(v), sh_repl)
        out[pname] = packed
    return out


def zero_unpack(opt_state: dict, params: Dict[str, jax.Array]) -> dict:
    """ZeRO-1 layout -> canonical per-parameter layout (drops the pad
    tail, restores the param shape). Inverse of ``zero_pack`` for any
    data-axis size — the world-size-portable snapshot form."""
    out = {}
    for pname, slots in opt_state.items():
        if pname not in params:
            out[pname] = slots
            continue
        pshape = tuple(params[pname].shape)
        size = int(np.prod(pshape)) if pshape else 1
        unpacked = {}
        for k, v in slots.items():
            if hasattr(v, "shape") and np.ndim(v) == 1:
                unpacked[k] = jnp.reshape(jnp.asarray(v)[:size], pshape)
            else:
                unpacked[k] = v
        out[pname] = unpacked
    return out


def per_chip_opt_bytes(opt_state: dict, mesh: Optional[Mesh] = None,
                       zero: bool = True) -> int:
    """Per-chip bytes of an optimizer state tree. For the ZeRO layout
    every ndim>=1 leaf is sharded over 'data' (count shard bytes); for
    the replicated layout every leaf is whole on every chip."""
    n = mesh.shape["data"] if (zero and mesh is not None) else 1
    total = 0
    for leaf in jax.tree_util.tree_leaves(opt_state):
        if not hasattr(leaf, "nbytes"):
            leaf = np.asarray(leaf)
        total += leaf.nbytes // n if (zero and np.ndim(leaf) >= 1) \
            else leaf.nbytes
    return int(total)


# --- collective probes -----------------------------------------------------

def measure_collectives(mesh: Mesh, grad_bytes: int, zero: bool = True,
                        iters: int = 5):
    """Time one gradient-sized all-reduce per mesh axis and publish the
    ICI/DCN gauges. The DCN probe uses the byte size that stage actually
    moves: full gradient bytes for replicated/flat reduction, the 1/N
    shard for hierarchical ZeRO. Returns (ici_s, dcn_s). On hardware
    this shows the ICI/DCN bandwidth asymmetry the hierarchical
    reduction exists for; on the CPU test mesh both are host memcpys
    (docs/multislice.md, ROADMAP v5e note)."""
    n = mesh.shape["data"]
    elems = max(1, int(grad_bytes) // 4)

    def probe(axis, size):
        x = jax.device_put(jnp.zeros((size,), jnp.float32),
                           NamedSharding(mesh, P()))
        fn = jax.jit(shard_map(lambda v: jax.lax.psum(v, axis), mesh=mesh,
                               in_specs=P(), out_specs=P(),
                               check_vma=False))
        fn(x).block_until_ready()            # compile
        secs = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn(x).block_until_ready()
            secs.append(time.perf_counter() - t0)
        secs.sort()
        return secs[len(secs) // 2]

    ici_s = probe("data", elems)
    dcn_s = probe("slice", max(1, elems // n) if zero else elems)
    _M_ICI_ALLREDUCE.set(ici_s)
    _M_DCN_ALLREDUCE.set(dcn_s)
    return ici_s, dcn_s


# --- the compiled step -----------------------------------------------------

def make_multislice_train_step(loss, optimizer, static, lr_mults=None,
                               evaluators=None, mesh: Mesh = None,
                               zero: bool = True, hierarchical: bool = True,
                               donate: bool = True, eval_out_names=()):
    """Build the jitted multi-slice train step: same
    ``(params, opt_state, rng, feeds) -> (params, opt_state, cost,
    metrics)`` contract as ``make_train_step``, but the body is a
    ``shard_map`` over the ('slice', 'data') mesh with the gradient
    reduction written as explicit collectives (module docstring shows
    the three reduction programs). ``opt_state`` must be in the matching
    layout: ``zero_pack`` output when ``zero``, canonical otherwise.

    Constraints (enforced with clear errors by MultiSliceTrainer):
    no batch-norm aux state, no sparse-row grads, no gradient
    accumulation; under ``zero`` additionally no global_clipping (the
    norm would need a cross-shard reduction) and no model_average (the
    Polyak window would need gathering on every eval)."""
    evaluators = dict(evaluators or {})
    S, N = mesh.shape["slice"], mesh.shape["data"]
    world = S * N
    eval_out_names = tuple(eval_out_names)

    def body(params, opt_state, rng, feeds):
        # per-device: feeds are this chip's batch shard; params and rng
        # replicated; opt_state the local 1/N shard (zero) or replicated
        lin = jax.lax.axis_index("slice") * N + jax.lax.axis_index("data")
        dev_rng = None if rng is None else jax.random.fold_in(rng, lin)
        (cost, (outs, _aux)), grads = jax.value_and_grad(
            loss, has_aux=True)(params, feeds, rng=dev_rng, training=True)

        if hierarchical:
            # stage 1 (ICI, intra-slice) then stage 2 (DCN, cross-slice)
            # as two distinct jaxpr-visible reductions
            if zero:
                def scatter(g):
                    size, chunk, padded = _chunks(g.shape, N)
                    flat = jnp.pad(jnp.ravel(g), (0, padded - size))
                    return jax.lax.psum_scatter(
                        flat, "data", scatter_dimension=0, tiled=True)

                gsh = {k: scatter(g) for k, g in grads.items()}
                gsh = jax.lax.psum(gsh, "slice")       # 1/N bytes on DCN
                gsh = {k: g / world for k, g in gsh.items()}
            else:
                grads = jax.lax.psum(grads, "data")
                grads = jax.lax.psum(grads, "slice")
                grads = {k: g / world for k, g in grads.items()}
        else:
            # flat baseline: ONE all-reduce spanning both axes — the
            # DCN hop moves full gradient bytes
            grads = jax.lax.psum(grads, ("slice", "data"))
            grads = {k: g / world for k, g in grads.items()}
            if zero:
                def shard_of(g):
                    size, chunk, padded = _chunks(g.shape, N)
                    flat = jnp.pad(jnp.ravel(g), (0, padded - size))
                    return jax.lax.dynamic_slice_in_dim(
                        flat, jax.lax.axis_index("data") * chunk, chunk)

                gsh = {k: shard_of(g) for k, g in grads.items()}

        if zero:
            # local update of the 1/N optimizer-state shard, then the
            # ICI all-gather that re-replicates the parameters
            idx = jax.lax.axis_index("data")

            def param_shard(p):
                size, chunk, padded = _chunks(p.shape, N)
                flat = jnp.pad(jnp.ravel(p), (0, padded - size))
                return jax.lax.dynamic_slice_in_dim(flat, idx * chunk, chunk)

            p_sh = {k: param_shard(p) for k, p in params.items()}
            new_p_sh, new_opt = optimizer.update(gsh, opt_state, p_sh,
                                                 lr_mults, static)

            def gather(name, psh):
                full = jax.lax.all_gather(psh, "data", axis=0, tiled=True)
                size = int(np.prod(params[name].shape)) \
                    if params[name].shape else 1
                return jnp.reshape(full[:size], params[name].shape)

            new_params = {k: gather(k, v) for k, v in new_p_sh.items()}
        else:
            new_params, new_opt = optimizer.update(grads, opt_state, params,
                                                   lr_mults, static)

        cost = jax.lax.psum(cost, ("slice", "data")) / world
        eouts = {n: outs[n] for n in eval_out_names}
        return new_params, new_opt, cost, eouts

    def step(params, opt_state, rng, feeds):
        fp = getattr(loss, "_feeds_packed", None)
        if fp is not None and fp(feeds):
            raise NotImplementedError(
                "packed feeds are not supported under MultiSliceTrainer: "
                "the per-shard packed-sequence counts would change the "
                "loss normalization vs the global batch")
        for fname, a in feeds.items():
            b = np.shape(a.value)[0] if np.shape(a.value) else 0
            enforce(b % world == 0,
                    f"feed {fname!r} batch {b} does not divide the "
                    f"{S}x{N} slice x data mesh ({world} chips); size "
                    "batches as a multiple of the world size (use "
                    "paddle.batch(..., drop_last=True) for the tail)")
        if zero:
            opt_specs = jax.tree_util.tree_map(
                lambda x: P("data") if np.ndim(x) >= 1 else P(), opt_state)
        else:
            opt_specs = jax.tree_util.tree_map(lambda x: P(), opt_state)
        batch = P(("slice", "data"))
        new_p, new_opt, cost, eouts = shard_map(
            body, mesh=mesh,
            in_specs=(P(), opt_specs, P(), batch),
            out_specs=(P(), opt_specs, P(), batch),
            check_vma=False)(params, opt_state, rng, feeds)
        outs = {k: as_arg(v) for k, v in feeds.items()}
        outs.update(eouts)
        metrics = _compute_metrics(evaluators, outs, loss, feeds)
        return new_p, new_opt, cost, metrics

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


# --- the trainer -----------------------------------------------------------

class MultiSliceTrainer(SGD):
    """SGD over a 2D slice x data mesh: hierarchical ICI->DCN gradient
    reduction, ZeRO-1 optimizer-state sharding over 'data', and
    world-size-portable step snapshots (docs/multislice.md).

    ``mesh`` must carry ('slice', 'data') axes (``make_mesh(slice=S,
    data=N)``); ``num_slices`` builds one over all visible devices.
    ``zero=False`` keeps the optimizer state replicated (the comparison
    baseline — same hierarchical reduction, N times the state bytes);
    ``hierarchical=False`` collapses the two reduction stages into one
    flat all-reduce spanning both axes (what plain DataParallelTrainer's
    GSPMD program does), as the comparison.

    Trajectory: a ZeRO run is allclose to the replicated DP run over
    the same batch stream — losses, final params, and (canonical)
    optimizer state — for every elementwise optimizer (SGD/Momentum/
    Adam/... pinned by tests/test_multislice.py). Models with dropout
    diverge by design: each chip folds its device index into the step
    RNG, where single-program DP draws one global mask.
    """

    def __init__(self, cost, parameters, update_equation,
                 mesh: Optional[Mesh] = None, num_slices: int = 1,
                 zero: bool = True, hierarchical: bool = True, **kw):
        if mesh is None:
            mesh = make_mesh(slice=num_slices)
        enforce("slice" in mesh.axis_names and "data" in mesh.axis_names,
                "MultiSliceTrainer needs a ('slice', 'data') mesh — build "
                "one with make_mesh(slice=S, data=N) (got axes "
                f"{tuple(mesh.axis_names)})")
        enforce(int(kw.pop("num_batches_per_send_parameter", 1)) == 1,
                "MultiSliceTrainer does not compose with gradient "
                "accumulation (the dense accumulator would need the ZeRO "
                "shard layout)")
        self.zero = bool(zero)
        self.hierarchical = bool(hierarchical)
        super().__init__(cost, parameters, update_equation, mesh=mesh, **kw)
        for l in self.topology.layers:
            enforce("batch_norm" not in l.type,
                    f"layer {l.name!r} ({l.type}) keeps batch-statistics "
                    "aux state; under shard_map its stats would be "
                    "per-shard, not global-batch — batch_norm models "
                    "cannot train multi-slice yet")
        enforce(not getattr(self._loss, "_sparse_capable", False),
                "sparse-row gradients (sparse_update tables) are not "
                "supported under MultiSliceTrainer yet — the touched-row "
                "sets differ per shard")
        if self.zero:
            enforce(not (self.optimizer.clip_threshold
                         and self.optimizer.global_clipping),
                    "global_clipping under ZeRO sharding would compute "
                    "the norm of each chip's 1/N shard, not the global "
                    "norm; use per-value clipping or zero=False")
            enforce(self.optimizer.model_average is None,
                    "model_average under ZeRO sharding has no gathered "
                    "Polyak window; use zero=False")
        self._probed = False

    # --- step build -------------------------------------------------------
    def _eval_out_names(self):
        """Non-feed layer outputs the evaluators read — the only loss
        outputs the shard_map body returns (batch-sharded); feeds are
        added back outside (same scheme as the PP trainer)."""
        feed_names = {l.name for l in self.topology.feed_layers}
        names = set()
        for ev in self.evaluators.values():
            for attr in ("input", "label", "weight", "info"):
                v = getattr(ev, attr, None)
                if isinstance(v, str) and v not in feed_names:
                    names.add(v)
        return tuple(sorted(names))

    def _build_train_step(self):
        if not self._probed:
            # gradient-sized ICI/DCN probe, once per trainer (the gauges
            # a v5e run reads for the real asymmetry; docs/multislice.md)
            grad_bytes = sum(
                int(np.prod(s.shape)) * 4
                for s in self.topology.param_specs().values())
            try:
                measure_collectives(self.mesh, grad_bytes, zero=self.zero)
            except Exception as e:          # never let the probe kill train
                logger.warning("collective probe failed: %s", e)
            self._probed = True
        return make_multislice_train_step(
            self._loss, self.optimizer, self._static, self._lr_mults,
            self.evaluators, mesh=self.mesh, zero=self.zero,
            hierarchical=self.hierarchical, donate=self._donate,
            eval_out_names=self._eval_out_names())

    # --- optimizer-state layout hooks (ZeRO <-> canonical) ----------------
    def _init_opt_state(self, params):
        state = self.optimizer.init(params)
        if self.zero:
            state = zero_pack(state, params, self.mesh)
        _M_OPT_BYTES.labels(
            layout="zero" if self.zero else "replicated").set(
            per_chip_opt_bytes(state, self.mesh, zero=self.zero))
        return state

    def _params_now(self):
        return {k: jnp.asarray(v) for k, v in
                self.parameters.as_dict().items()}

    def _canonical_opt_state(self, opt_state):
        if not self.zero:
            return opt_state
        return zero_unpack(opt_state, self._params_now())

    def _restore_opt_state(self, opt_state):
        state = jax.tree_util.tree_map(jnp.asarray, opt_state)
        if self.zero:
            # repack for THIS mesh — the snapshot may have been taken at
            # a different world size (elastic rescale)
            state = zero_pack(state, self._params_now(), self.mesh)
        _M_OPT_BYTES.labels(
            layout="zero" if self.zero else "replicated").set(
            per_chip_opt_bytes(state, self.mesh, zero=self.zero))
        return state

    def _snapshot_meta(self):
        return {"mesh_slice": int(self.mesh.shape["slice"]),
                "mesh_data": int(self.mesh.shape["data"]),
                "zero_opt_state": self.zero}

    # --- feed placement ---------------------------------------------------
    def _prepare_feeds(self, feeds):
        if jax.process_count() == 1:
            return feeds
        batch_sh = NamedSharding(self.mesh, P(("slice", "data")))
        return jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(
                batch_sh, np.asarray(x)), feeds)

    def _prefetch_sharding(self):
        if jax.process_count() > 1:
            return False
        return NamedSharding(self.mesh, P(("slice", "data")))

    def _setup_host_tables(self, host_tables, *rest):
        names = super()._setup_host_tables(host_tables, *rest)
        enforce(not names,
                "host-resident embedding tables do not compose with "
                "MultiSliceTrainer yet (the per-batch row cache has no "
                "slice-replicated flush path)")
        return names


# --- elastic coordination --------------------------------------------------

def elastic_train(make_trainer, reader, membership, snapshot_dir: str,
                  num_passes: int = 1, save_every_n_batches: int = 1,
                  event_handler=None, watch_poll: float = 0.05,
                  max_rescales: int = 8, **train_kw):
    """Elastic multi-slice training loop (docs/multislice.md).

    ``make_trainer(world_size)`` builds a MultiSliceTrainer sized to the
    currently-alive slice count (the caller maps seats to a mesh — e.g.
    2 slices -> make_mesh(slice=2), 1 -> slice=1 over half the chips).
    The coordinator then composes three existing mechanisms:

    - membership (``distributed.discovery.SliceMembership``): a slice
      that dies stops heartbeating; its seat lapses within one TTL and
      a watcher thread sees the world change;
    - the r7 preemption protocol: the watcher sets the trainer's
      ``preempt_event``, so training stops AT A BATCH BOUNDARY with a
      valid step snapshot on disk (nothing torn, nothing lost past the
      last save_every_n_batches window);
    - the r7 step-resume protocol + the ZeRO layout hooks: the newest
      snapshot (canonical optimizer-state layout) reloads into a NEW
      trainer at the new world size — ``_restore_opt_state`` repacks
      the shards for the new 'data' axis.

    Post-rescale, the loss trajectory is the fixed-size trajectory from
    the same snapshot (tests/test_multislice_elastic.py pins it): the
    global batch stream is world-size independent, only its sharding
    changes. With a master-attached reader the dead slice's leased
    tasks redeliver through the master's TTL (at-least-once), so no
    batch is lost to the rescale either.

    Returns the final trainer (its ``.parameters`` hold the result).
    """
    import threading

    enforce(save_every_n_batches >= 1 and snapshot_dir,
            "elastic_train needs step snapshots (they ARE the rescale "
            "mechanism): pass snapshot_dir and save_every_n_batches >= 1")
    rescales = 0
    while True:
        alive = membership.alive()
        world = len(alive)
        enforce(world >= 1, "no live slices in the membership registry")
        trainer = make_trainer(world)
        resume_state = None
        found = SGD.load_step_resume(snapshot_dir)
        if found is not None:
            loaded, resume_state = found
            for name in loaded.names():
                trainer.parameters.set(name, loaded.get(name))
            logger.info("elastic: world=%d resuming from %s (step %d)",
                        world, resume_state["path"],
                        resume_state["global_step"])
        stop = threading.Event()
        preempt = threading.Event()
        seen = {"alive": alive}

        def watch():
            while not stop.is_set():
                now = membership.watch_change(seen["alive"], timeout=0.5,
                                              poll=watch_poll)
                if now is not None:
                    seen["alive"] = now
                    logger.warning("elastic: membership changed to %s; "
                                   "preempting at next batch boundary", now)
                    preempt.set()
                    return

        watcher = threading.Thread(target=watch, daemon=True,
                                   name="elastic-membership-watch")
        watcher.start()
        try:
            trainer.train(reader, num_passes=num_passes,
                          event_handler=event_handler,
                          save_every_n_batches=save_every_n_batches,
                          snapshot_dir=snapshot_dir,
                          resume_state=resume_state,
                          preempt_event=preempt, **train_kw)
        finally:
            stop.set()
            watcher.join(timeout=2.0)
        if not trainer.preempted:
            return trainer
        rescales += 1
        enforce(rescales <= max_rescales,
                f"elastic_train rescaled {rescales} times without "
                "finishing; membership is flapping")
