"""Pipeline parallelism (GPipe-style) over a mesh 'stage' axis.

TPU-native replacement for ParallelNeuralNetwork's per-layer device
pinning + input-ready semaphores (paddle/gserver/gradientmachines/
ParallelNeuralNetwork.cpp, Layer::waitInputValue): homogeneous blocks are
stacked on a 'stage' mesh axis; microbatches flow stage-to-stage via
``ppermute`` inside a differentiable ``lax.scan`` schedule (M + S - 1
ticks). Backward flows automatically (autodiff of ppermute is the reverse
permute), giving 1F1B-equivalent memory behaviour with remat applied to
the block fn.

``pipeline_schedule`` is THE schedule — one tick loop shared by the
homogeneous block pipeline here (:func:`gpipe`) and the heterogeneous
config-compiled pipeline (`topo_pipeline.PipelinedTopology.loss`), so
there is a single place where bubble structure, activity masking and
boundary movement are defined.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def schedule_ticks(num_micro: int, num_stages: int) -> int:
    """Ticks one schedule runs: M microbatches drain through S stages in
    M + S - 1 ticks; each device is busy in M of them, so the bubble
    fraction is (S - 1) / (M + S - 1) (the GPipe model)."""
    return num_micro + num_stages - 1


def pipeline_schedule(step_fn: Callable, emit_fn: Callable, zero, s,
                      num_micro: int, num_stages: int,
                      axis_name: str = "stage"):
    """Run the GPipe software-pipeline tick loop on one stage shard.

    Must be called inside ``shard_map`` over ``axis_name``; ``s`` is this
    shard's ``jax.lax.axis_index``. At tick ``t`` stage ``s`` processes
    microbatch ``mb = t - s`` when ``0 <= mb < M`` (``active``), its
    boundary output ``ppermute``s to stage ``s + 1``, and ``emit_fn``
    derives this tick's local emission (cost contribution, collected
    last-stage rows, ...).

      step_fn(mb, active, stage_in) -> (y, aux)
          y:   the boundary value handed to the next stage (same
               pytree/shape as ``zero``; masked to zeros when inactive
               before both emission and ppermute)
          aux: stage-local extras emit_fn may need (NOT permuted)
      emit_fn(mb, active, y, aux) -> per-tick emission pytree

    Returns the emissions stacked over ticks (leading dim M + S - 1).

    The emissions ride the scan's ``ys`` outputs and are reduced by the
    CALLER after the scan, never accumulated in the carry: the jax of
    r13 could not transpose, under shard_map, a scan whose carry mixes a
    ppermuted boundary with a locally-accumulated value (_SpecError; it
    blocked ``jax.grad`` of the heterogeneous pipeline until then). Not
    re-tried on jax 0.9.0; the ys form transposes on both.
    """
    fwd_perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    def tick(stage_in, t):
        mb = jnp.clip(t - s, 0, num_micro - 1)
        active = ((t - s) >= 0) & ((t - s) < num_micro)
        y, aux = step_fn(mb, active, stage_in)
        y = jax.tree_util.tree_map(
            lambda a: jnp.where(active, a, jnp.zeros_like(a)), y)
        out = emit_fn(mb, active, y, aux)
        nxt = jax.lax.ppermute(y, axis_name, fwd_perm)
        return nxt, out

    _, outs = jax.lax.scan(
        tick, zero, jnp.arange(schedule_ticks(num_micro, num_stages)))
    return outs


def gpipe(block_fn: Callable, stacked_params, xs: jax.Array, mesh: Mesh,
          axis_name: str = "stage", remat: bool = True) -> jax.Array:
    """Run microbatches through S pipeline stages.

    block_fn(params_slice, x) -> y with x/y the same shape (homogeneous
    stages, e.g. transformer blocks).
    stacked_params: pytree with leading dim S (sharded over axis_name).
    xs: [M, B, ...] microbatches (replicated).
    Returns [M, B, ...] outputs of the final stage (replicated).
    """
    fn = jax.checkpoint(block_fn) if remat else block_fn

    def local(params, xs):
        S = jax.lax.axis_size(axis_name)
        s = jax.lax.axis_index(axis_name)
        M = xs.shape[0]
        p_local = jax.tree_util.tree_map(lambda a: a[0], params)
        zero = jnp.zeros_like(xs[0])

        def step(mb, active, stage_in):
            x_in = jnp.where(s == 0, xs[mb], stage_in)
            return fn(p_local, x_in), ()

        def emit(mb, active, y, aux):
            # only the last stage's active outputs survive the psum
            return jnp.where(active & (s == S - 1), y, jnp.zeros_like(y))

        ticks_out = pipeline_schedule(step, emit, zero, s, M, S, axis_name)
        # the last stage runs microbatch mb at tick mb + S - 1, so its
        # collected rows are the static tail slice of the tick axis
        outs = ticks_out[S - 1:]
        # replicate the last stage's collected outputs to every stage
        return jax.lax.psum(outs, axis_name)

    param_specs = jax.tree_util.tree_map(
        lambda a: P(axis_name, *([None] * (a.ndim - 1))), stacked_params)
    return shard_map(local, mesh=mesh,
                     in_specs=(param_specs, P()), out_specs=P(),
                     check_vma=False)(stacked_params, xs)
