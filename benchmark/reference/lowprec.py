"""Operand roundings for the references' matmuls and convolutions.

`none` is the reference itself (float32 operands at `highest`). `fp8` is the
control of "How correct is decided": the nearest precision below the bf16 the
configurations state, e4m3 with a per-tensor scale, straight-through in the
backward pass, so the backward products see the rounded operands too. `bf16`
rounds operands to bfloat16, the configurations' stated compute type: read
beside the program (benchmark/limits.py --also bf16) it says how much of a gap
is that precision's own. The roundings are `lax.reduce_precision`: a
convert to a narrow type and back is something XLA may elide on the TPU
(xla_allow_excess_precision), and on the chip it did.
"""

import jax
import jax.numpy as jnp


def _ste(x, y):
    return x + jax.lax.stop_gradient(y - x)


def none(x):
    return x


def bf16(x):
    return _ste(x, jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7))


def fp8(x):
    """e4m3 (four exponent bits, three stored mantissa bits) with a per-tensor
    scale that puts the largest magnitude at 240, the format's largest
    normal number."""
    s = jnp.max(jnp.abs(x)) / 240.0 + 1e-30
    return _ste(x, jax.lax.reduce_precision(x / s, exponent_bits=4,
                                            mantissa_bits=3) * s)


BY_NAME = {"none": none, "bf16": bf16, "fp8": fp8}
