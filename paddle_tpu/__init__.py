"""paddle_tpu — a TPU-native deep-learning framework.

A from-scratch rebuild of the capabilities of 2017-era PaddlePaddle
(reference: hshlpeter/Paddle), re-expressed idiomatically for TPUs:

- layer-graph model engine (analog of paddle/gserver) compiled to a single
  jitted XLA program instead of per-layer virtual dispatch,
- padded+masked / segment-id sequence representation instead of ragged
  ``Argument.sequenceStartPositions`` (XLA needs static shapes),
- ``jax.sharding`` meshes + ICI collectives instead of MultiGradientMachine
  thread rings and the C++/Go parameter servers,
- XLA / Pallas kernels instead of paddle/cuda + paddle/math,
- a functional optimizer suite mirroring paddle/parameter/FirstOrderOptimizer.h.

Public surface mirrors the reference's Python v2 API
(python/paddle/v2/__init__.py): ``layer``, ``activation``, ``optimizer``,
``trainer``, ``pooling``, ``attr``, ``networks``, ``evaluator``, ``reader``,
``dataset``, ``inference``, plus TPU-first additions under ``parallel``.
"""

from paddle_tpu import activation
from paddle_tpu import attr
from paddle_tpu import evaluator
from paddle_tpu import initializer
from paddle_tpu import layer
from paddle_tpu import networks
from paddle_tpu import optimizer
from paddle_tpu import pooling
from paddle_tpu import reader
from paddle_tpu import dataset
from paddle_tpu import parallel
from paddle_tpu import utils
from paddle_tpu.core.topology import Topology
from paddle_tpu.trainer import SGD
from paddle_tpu.trainer import event
from paddle_tpu.core import parameters
from paddle_tpu.core.parameters import Parameters, create as parameters_create
from paddle_tpu.inference import Inference, infer
from paddle_tpu import image
from paddle_tpu import plot
from paddle_tpu.version import __version__


def init(**kwargs):
    """Process-level initialisation (analog of paddle.init / initMain,
    reference paddle/trainer/TrainerMain.cpp:32 + paddle/utils/Util.cpp).

    Accepts reference gflags-style keywords (use_gpu, trainer_count, ...);
    on TPU these map to device selection and mesh defaults.
    """
    from paddle_tpu.utils import flags as _flags

    for k, v in kwargs.items():
        _flags.FLAGS.set_if_known(k, v)
    return _flags.FLAGS


def compile_cache():
    """Place JAX's persistent compilation cache for a process that will
    compile for the device (the CLI, chip_smoke.py's children, the tools
    that time on the chip). Returns the directory in use, or
    None when there is none.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, so this sets
    nothing — no code in the repo names another directory. Unset: one
    fixed directory inside the checkout (the path is part of the cache
    key, so it must not move between runs). A process held to the CPU
    (``JAX_PLATFORMS=cpu``: the test suite and its children) gets no
    persistent cache, so that tier-1 never depends on what a directory
    happens to hold."""
    import os

    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if jax.config.jax_platforms == "cpu":
        return None
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


batch = reader.minibatch_batch
