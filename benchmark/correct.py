"""What decides `correct` for a training cell.

Set-up drives the trainer it will time through its first three steps, on
three batches that differ, through the window's own call. From those steps
this module keeps, per parameter leaf, the norm of the first gradient as the
optimizer got it (worked out from the optimizer's state after step one) and
the norm of the parameters' change after step three, and each step's loss.
Once the window has closed and the program's state is freed, the plain
reference (benchmark/reference/<config>.py, float32 at `highest`) makes the
same three steps from the same seed's weights and rows, and the numbers are
compared:

  loss_gap    worst |loss - ref| / |ref| over the three steps (loss1_gap:
              the first step's alone)
  grad_gap    worst leaf of |‖g‖ - ‖g_ref‖| / max(‖g_ref‖, median leaf ‖g_ref‖)
  delta_gap   the same of the change ‖p3 - p0‖, over the leaves whose
              reference gradient is at least a thousandth of the median
              leaf's (the others move by round-off alone under Adam)
  grad_gap_median, delta_gap_median   the median leaf's gap, not the worst's
  stat_gap_median   the median gap of the change of the leaves no gradient
              moves (batch-norm moving statistics), where the model has any

The gap is between the two norms, not the norm of a difference. A cell's file
names the numbers it holds and a limit for each, set from chip readings
(PERF.md section 2).
"""

import functools
import importlib.util
import os
import statistics

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
STEPS = 3


def load_module(rel_path):
    path = os.path.join(HERE, rel_path)
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path).replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _init_program(leaves):
    """One jitted program that makes every leaf of `leaves`
    ((name, shape, kind, value), ...) from a key."""
    import jax
    import jax.numpy as jnp

    def make(key):
        out = {}
        for i, (n, shape, kind, v) in enumerate(leaves):
            if kind == "const":
                out[n] = jnp.full(shape, v, jnp.float32)
            else:
                out[n] = v * jax.random.normal(jax.random.fold_in(key, i),
                                               shape, jnp.float32)
        return out

    return jax.jit(make)


def init_params(table, seed):
    """Every leaf from the seed, on the device, in one jitted call."""
    import jax

    leaves = tuple((n, tuple(table[n][0]), *table[n][1]) for n in sorted(table))
    # a seed may pass 2**31: fold it in as two 31-bit halves
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return _init_program(leaves)(key)


def leaf_norms(tree):
    """{leaf: float norm}, reduced on the device in one call."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})(tree)
    return {k: float(v) for k, v in norms.items()}


def diff_norms(a, b):
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k].astype(jnp.float32) - b[k].astype(jnp.float32)))) for k in a})(a, b)
    return {k: float(v) for k, v in norms.items()}


def first_gradient_norms(opt_spec, opt_state, leaves, static):
    """Per-leaf norm of the gradient the optimizer got at step one (its weight
    decay included), from its state after that step."""
    kind = opt_spec["kind"]
    if kind == "adam":
        scale, slot = 1.0 / (1.0 - opt_spec["beta1"]), "m"
    elif kind == "momentum":
        scale, slot = 1.0 / opt_spec["learning_rate"], "mom"
    else:
        raise ValueError(kind)
    tree = {k: opt_state[k][slot] for k in leaves if k not in static}
    return {k: v * scale for k, v in leaf_norms(tree).items()}


def reference_steps(config, batches, seed, rounding="none", fault=None):
    """The reference's three steps. Returns {"loss": [...], "grad": {leaf:
    norm}, "delta": {leaf: norm}}. `rounding` names the operand rounding
    (reference/lowprec.py); `fault` plants one of the faults of "How correct
    is decided" in the reference: "half_batch" or "state_unchanged"."""
    import jax
    import jax.numpy as jnp

    ref = load_module(config["reference"])
    optim = load_module("reference/optim.py")
    q = load_module("reference/lowprec.py").BY_NAME[rounding]
    args = config["model"]["args"]
    static = set(ref.static_names(args))
    spec = config["optimizer"]

    if hasattr(ref, "value_and_grad"):      # block by block, so that it fits
        def grads_of(p, b):
            return ref.value_and_grad(p, b, q, args)
    else:
        grads_of = jax.jit(lambda p, b: jax.value_and_grad(
            lambda p: ref.loss(p, b, q, args), has_aux=True)(p))

    def apply(p, s, g, aux, t):
        # the optimizer's gradient includes its weight decay
        gn = {k: jnp.sqrt(jnp.sum(jnp.square(g[k] + spec.get("l2", 0.0) * p[k])))
              for k in p if k not in static}
        new_p, new_s = optim.update(spec, t, p, g, s, static)
        new_p.update(aux)
        return new_p, new_s, gn

    apply = jax.jit(apply, static_argnums=4, donate_argnums=(0, 1))
    with jax.default_matmul_precision("highest"):
        p0 = init_params(ref.param_table(args), seed)
        p = init_params(ref.param_table(args), seed)
        s = optim.init(spec, p)
        losses, grad = [], None
        for t in range(1, STEPS + 1):
            rows = batches[t - 1]
            if fault == "half_batch":
                rows = rows[:len(rows) // 2]
            b = {k: jnp.asarray(v) for k, v in ref.pad(rows, args).items()}
            (loss, aux), g = grads_of(p, b)
            new_p, new_s, gn = apply(p, s, g, aux, t)
            del g, aux, b
            losses.append(float(loss))
            if t == 1:
                grad = {k: float(v) for k, v in gn.items()}
            if fault == "state_unchanged":
                # a step that hands back the state it was given
                p = init_params(ref.param_table(args), seed)
                s = optim.init(spec, p)
            else:
                p, s = new_p, new_s
        delta = diff_norms(p, p0)
    return {"loss": losses, "grad": grad, "delta": delta}


def _gaps(got, want, leaves):
    """(worst gap, its leaf, the median leaf's gap)."""
    med = statistics.median(want[k] for k in want)
    gaps = {k: abs(got[k] - want[k]) / max(want[k], med, 1e-30) for k in leaves}
    at = max(gaps, key=lambda k: gaps[k] if gaps[k] == gaps[k] else float("inf"))
    mid = statistics.median(float("inf") if g != g else g for g in gaps.values())
    return gaps[at], at, mid


def compare(prog, ref, static=()):
    """The numbers compared: {name: (value, worst leaf or step)}."""
    loss = [abs(a - b) / max(abs(b), 1e-30)
            for a, b in zip(prog["loss"], ref["loss"])]
    if len(prog["loss"]) != len(ref["loss"]) or not all(
            np.isfinite(prog["loss"])):
        loss = [float("inf")]
    worst_loss = max(loss)
    med_g = statistics.median(ref["grad"].values())
    moving = [k for k in ref["grad"] if ref["grad"][k] >= 1e-3 * med_g]
    # leaves no gradient moves (batch-norm statistics) change by the model's
    # own rule: they are compared with the rest
    moving += [k for k in ref["delta"] if k in static]
    g, g_at, g_mid = _gaps(prog["grad"], ref["grad"], list(ref["grad"]))
    d, d_at, d_mid = _gaps(prog["delta"], ref["delta"], moving)
    out = {"loss_gap": (worst_loss, f"step{loss.index(worst_loss) + 1}"),
           "loss1_gap": (loss[0], "step1"),
           "grad_gap": (g, g_at), "grad_gap_median": (g_mid, "median leaf"),
           "delta_gap": (d, d_at), "delta_gap_median": (d_mid, "median leaf")}
    held = [k for k in ref["delta"] if k in static]
    if held:
        out["stat_gap_median"] = (_gaps(prog["delta"], ref["delta"], held)[2],
                                  "median moving statistic")
    return out


def judge(numbers, limits):
    """(correct, [(name, value, limit)]): every number at or under its limit."""
    rows = [(n, numbers[n][0], limits[n]) for n in sorted(limits)]
    return all(v <= lim for _, v, lim in rows), rows
