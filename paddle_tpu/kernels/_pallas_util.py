"""Shared constants/helpers for the Pallas TPU kernels (lstm/gru/crf/
ctc): one source of truth for the finite -inf stand-in, the raised
scoped-VMEM limit, and the time-padding helper, so the kernels cannot
drift apart on these numerics.
"""

from __future__ import annotations

import contextlib
import contextvars
import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from paddle_tpu.utils import logger

# finite stand-in for -inf in log space: real -inf turns arithmetic
# mask-blends into NaN (0 * -inf), and the TPU's subnormal flush makes
# log() hit -inf more easily than interpret mode (seen on a v5e at r5,
# not re-measured)
NEG = -1e30

# raise the 16MB default scoped-vmem limit: the chip accepts ~100MB
# (r4, not re-measured); kernels gate their working sets well under this
VMEM_LIMIT_BYTES = 96 * 1024 * 1024

# (who, line) already logged by log_once
_LOGGED_DECISIONS: set = set()


def log_once(who: str, line: str) -> None:
    """``who: line`` in the log, once per layer and line however often the
    layer is traced (a step traces a layer several times)."""
    if (who, line) not in _LOGGED_DECISIONS:
        _LOGGED_DECISIONS.add((who, line))
        logger.info("%s: %s", who, line)


def take_pallas(who: str, kernel: str, eligible: bool = True,
                why_not: str = "", otherwise: str = "lax.scan") -> bool:
    """THE predicate for "this layer runs its Pallas kernel". The caller
    says whether the kernel covers its configuration (``eligible``;
    ``why_not`` names what rules it out); this adds the platform — the
    Mosaic kernels compile for the ``tpu`` backend only — and logs, once
    per layer and decision at trace time, which implementation was
    taken and why, so a run that fell back to ``otherwise`` (the layer's
    other implementation, ``lax.scan`` for the recurrences) says so."""
    if not eligible:
        taken, why = False, why_not
    elif jax.default_backend() != "tpu":
        taken, why = False, f"backend is {jax.default_backend()!r}"
    else:
        taken, why = True, "backend is 'tpu' and the kernel's gate passes"
    log_once(who, (f"Pallas {kernel}" if taken else f"{otherwise}, not {kernel}")
             + f" ({why})")
    return taken


# (mesh, batch axes) while a data-parallel trainer traces its GSPMD step
_BATCH_MESH = contextvars.ContextVar("paddle_tpu_kernel_batch_mesh",
                                     default=None)


@contextlib.contextmanager
def batch_sharded_kernels(mesh, batch_axes):
    """Trace-time context of a GSPMD (jit + sharding annotations) program
    whose batch is split over ``batch_axes`` of ``mesh``. The partitioner
    refuses a Mosaic kernel ("cannot be automatically partitioned"), so
    inside this context ``call_kernel`` wraps each kernel in a shard_map
    over the batch. A program that is already one shard_map (the
    multi-slice step) does not enter it."""
    token = _BATCH_MESH.set((mesh, batch_axes))
    try:
        yield
    finally:
        _BATCH_MESH.reset(token)


def batch_shards() -> int:
    """How many ways ``call_kernel`` splits the batch here (1 outside
    ``batch_sharded_kernels``): kernels gate on the per-shard batch."""
    ctx = _BATCH_MESH.get()
    if ctx is None:
        return 1
    mesh, axes = ctx
    axes = (axes,) if isinstance(axes, str) else axes
    return math.prod(mesh.shape[a] for a in axes)


def call_kernel(fn, args, batch_argnums):
    """``fn(*args)`` for a kernel whose results and ``batch_argnums``
    arguments carry a leading batch dim and whose other arguments (the
    weights) are whole on every shard; runs per batch shard under
    ``batch_sharded_kernels`` (autodiff of the shard_map sums the weight
    gradients over the shards)."""
    ctx = _BATCH_MESH.get()
    if ctx is None:
        return fn(*args)
    mesh, axes = ctx
    batch = P(axes)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(batch if i in batch_argnums else P()
                       for i in range(len(args))),
        out_specs=batch, check_vma=False)(*args)


def compiler_params(interpret: bool) -> dict:
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=VMEM_LIMIT_BYTES)}


def pad_T(x: jax.Array, Tp: int) -> jax.Array:
    """Zero-pad the leading (time) axis to Tp rows."""
    if x.shape[0] == Tp:
        return x
    return jnp.pad(x, [(0, Tp - x.shape[0])] + [(0, 0)] * (x.ndim - 1))


def round_up(n: int, k: int) -> int:
    return -(-n // k) * k


def nt(a, b):
    """a [m, k] x b [n, k]^T -> [m, n], float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def tn(a, b):
    """a [k, m]^T x b [k, n] -> [m, n], float32: the operand is read as it
    is stored and turned inside the kernel, not by XLA in HBM before it."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
