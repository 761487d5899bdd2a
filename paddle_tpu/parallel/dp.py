"""Data-parallel trainer over a device mesh.

Replaces MultiGradientMachine + TrainerThread rings
(paddle/gserver/gradientmachines/MultiGradientMachine.h:44-98: per-thread
grad ring, value dispatch threads) AND the sync parameter server
(paddle/pserver/ParameterServer2.cpp addGradient/getParameter barriers):
with jit + shardings, the batch is split over the mesh 'data' axis,
XLA inserts the psum all-reduce over ICI for gradients, and parameters
stay replicated (or sharded, ZeRO-style, via param_spec overrides).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.core.arg import Arg
from paddle_tpu.trainer.trainer import SGD
from paddle_tpu.parallel.mesh import make_mesh


class DataParallelTrainer(SGD):
    """SGD whose jitted step shards the batch across mesh 'data'.

    The entire MultiGradientMachine machinery (grad collect threads, value
    dispatch, peer-to-peer copies) is expressed as in/out shardings; the
    gradient all-reduce is XLA's, riding ICI.
    """

    def __init__(self, cost, parameters, update_equation, mesh=None, **kw):
        mesh = mesh or make_mesh()
        super().__init__(cost, parameters, update_equation, mesh=mesh, **kw)

    def _batch_axes(self):
        """Mesh axes the batch dim shards over: plain 'data' on the
        default mesh; ('slice', 'data') on a 2D multi-slice mesh
        (docs/multislice.md) — there the whole mesh is data parallelism
        and XLA plans the (flat) gradient all-reduce over both axes."""
        if "slice" in self.mesh.axis_names:
            return ("slice", "data")
        return "data"

    def _prepare_feeds(self, feeds: Dict[str, Arg]) -> Dict[str, Arg]:
        """Multi-host DP: each process's feeder produces its LOCAL batch;
        assemble the global sharded array over the mesh (the reference's
        per-trainer data partitioning, trainer_id/num_gradient_servers —
        here jax.make_array_from_process_local_data over the 'data' axis).
        Single-process runs pass through untouched."""
        if jax.process_count() == 1:
            return feeds
        batch_sh = NamedSharding(self.mesh, P(self._batch_axes()))
        return jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(
                batch_sh, np.asarray(x)), feeds)

    def _prefetch_sharding(self):
        """Sharding-aware prefetch-to-device (pipelined loop,
        docs/pipeline.md): the async H2D copy lands the batch ALREADY
        laid out over the mesh 'data' axis, so the per-shard copies
        overlap the previous step's compute and the step's
        with_sharding_constraint becomes a no-op placement-wise.
        Multi-process runs skip the prefetch (False): _prepare_feeds
        already built global sharded device arrays. Placement failures
        (e.g. a non-divisible tail batch under drop_last=False) latch
        per batch shape in the base class, so full-size batches keep
        their overlap."""
        if jax.process_count() > 1:
            return False
        return NamedSharding(self.mesh, P(self._batch_axes()))

    def _host_cache_sharding(self):
        """Host-resident tables under single-process DP: the per-batch
        [U, D] row cache is REPLICATED over the mesh — its slot space is
        batch-derived, so the EP vocab sharding of sparse_update tables
        (sharding.ShardingRules.spec_for) cannot apply to it; every
        shard gathers its own batch rows from the same replicated cache
        and the cache-grad scatter-add all-reduces over ICI like any
        replicated parameter's gradient."""
        return NamedSharding(self.mesh, P())

    def _build_train_step(self):
        from paddle_tpu.kernels._pallas_util import batch_sharded_kernels

        step = super()._build_train_step()
        mesh = self.mesh
        batch_sh = NamedSharding(mesh, P(self._batch_axes()))
        repl = NamedSharding(mesh, P())

        def arg_sharding(a: Arg):
            return Arg(
                value=batch_sh,
                mask=batch_sh if a.mask is not None else None,
                seg_ids=batch_sh if a.seg_ids is not None else None)

        def sharded(params, opt_state, rng, feeds):
            feeds = {k: Arg(jax.lax.with_sharding_constraint(a.value, batch_sh),
                            None if a.mask is None else
                            jax.lax.with_sharding_constraint(a.mask, batch_sh),
                            None if a.seg_ids is None else
                            jax.lax.with_sharding_constraint(a.seg_ids, batch_sh))
                     for k, a in feeds.items()}
            # XLA partitions everything in this program but the Pallas
            # kernels, which run per batch shard (kernels/_pallas_util)
            with batch_sharded_kernels(mesh, self._batch_axes()):
                return step(params, opt_state, rng, feeds)

        return jax.jit(sharded)
