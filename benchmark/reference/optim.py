"""Plain optimizers for the references: the published update rules in
float32 jax.numpy, written from the papers and independent of the program.

`state` is a dict leaf-name -> dict of slots; `static` names leaves that no
gradient moves (batch-norm moving statistics: the model's `aux` sets them).
"""

import jax.numpy as jnp


def init(spec, params):
    kind = spec["kind"]
    if kind == "adam":
        return {k: {"m": jnp.zeros_like(p), "v": jnp.zeros_like(p)}
                for k, p in params.items()}
    if kind == "momentum":
        return {k: {"mom": jnp.zeros_like(p)} for k, p in params.items()}
    raise ValueError(f"no reference optimizer for kind {kind!r}")


def update(spec, t, params, grads, state, static=()):
    """One step (t counts from 1). Returns (new_params, new_state)."""
    kind = spec["kind"]
    lr = spec["learning_rate"]
    new_p, new_s = {}, {}
    for k, p in params.items():
        if k in static:
            new_p[k], new_s[k] = p, state[k]
            continue
        g = grads[k] + spec.get("l2", 0.0) * p
        if kind == "adam":
            b1, b2, eps = spec["beta1"], spec["beta2"], spec["epsilon"]
            m = b1 * state[k]["m"] + (1 - b1) * g
            v = b2 * state[k]["v"] + (1 - b2) * g * g
            mhat = m / (1 - b1 ** t)
            vhat = v / (1 - b2 ** t)
            new_p[k] = p - lr * mhat / (jnp.sqrt(vhat) + eps)
            new_s[k] = {"m": m, "v": v}
        else:
            mom = spec["momentum"] * state[k]["mom"] - lr * g
            new_p[k] = p + mom
            new_s[k] = {"mom": mom}
    return new_p, new_s
