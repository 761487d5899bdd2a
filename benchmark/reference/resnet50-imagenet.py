"""Plain reference of ResNet-50 for ImageNet (He et al., arXiv:1512.03385,
Table 1, 50-layer column; the downsampling stride on the first 1x1 of a stage,
as the reference tree's model_zoo resnet.py has it).

Straightforward jax.numpy / lax in float32 and NCHW; the caller sets
`jax.default_matmul_precision("highest")`. Batch normalisation uses the
batch's own mean and biased variance (eps 1e-5) and moves the running
statistics by 0.9 / 0.1.

`loss` is the whole model as one function. `value_and_grad` computes the same
loss and gradients block by block: forward keeping each block's input, then
each block's vector-Jacobian product in reverse order. That is the chain rule
written out, nothing else; it keeps float32 activations of the timed batch
inside one chip, and lets blocks of one shape share one compiled program (the
whole model at `highest` is a 236 MB executable, more than the compile cache
of the chip tool's machine holds). The tests hold the two to each other.

Every convolution and the final product go through `q`
(benchmark/reference/lowprec.py): identity for the reference, a rounding for
the control.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
BN_EPS, BN_KEEP = 1e-5, 0.9
BRANCHES = ("branch2a", "branch2b", "branch2c", "branch1")


def _blocks(a):
    """(name, c_in, c, stride, project) of every bottleneck, in order."""
    out, c_in = [], 64
    for stage, n in enumerate(STAGES[a["depth"]]):
        c = 64 * 2 ** stage
        for b in range(n):
            out.append((f"res{stage + 2}_{b}", c_in, c,
                        2 if (b == 0 and stage > 0) else 1, b == 0))
            c_in = 4 * c
    return out


def _convs(a):
    """(name, c_out, c_in, k) of every conv+bn, in network order."""
    out = [("res_conv1", 64, 3, 7)]
    for n, c_in, c, _, project in _blocks(a):
        out += [(f"{n}_branch2a", c, c_in, 1), (f"{n}_branch2b", c, c, 3),
                (f"{n}_branch2c", 4 * c, c, 1)]
        if project:
            out.append((f"{n}_branch1", 4 * c, c_in, 1))
    return out


def param_table(a):
    """name -> (shape, init): ("normal", std) or ("const", value)."""
    t = {}
    for name, co, ci, k in _convs(a):
        t[f"_{name}.w0"] = ((co, ci, k, k),
                            ("normal", 1.0 / math.sqrt(ci * k * k)))
        t[f"_{name}_bn.w0"] = ((co,), ("const", 1.0))
        t[f"_{name}_bn.wbias"] = ((co,), ("const", 0.0))
        t[f"_{name}_bn.wmean"] = ((co,), ("const", 0.0))
        t[f"_{name}_bn.wvar"] = ((co,), ("const", 1.0))
    t["_res_fc.w0"] = ((2048, a["num_classes"]),
                       ("normal", 1.0 / math.sqrt(2048)))
    t["_res_fc.wbias"] = ((a["num_classes"],), ("const", 0.0))
    return t


def static_names(a):
    return tuple(f"_{n}_bn.{s}" for n, *_ in _convs(a)
                 for s in ("wmean", "wvar"))


def pad(rows, a):
    """Rows of (flat CHW image, label) -> arrays; nothing to pad."""
    s = a["img_size"]
    return {"image": np.stack([r[0] for r in rows]).reshape(len(rows), 3, s, s)
            .astype(np.float32),
            "label": np.asarray([r[1] for r in rows], np.int32)}


def _conv_bn(p, x, stride, pad_, relu, q):
    """p: {"w", "scale", "shift", "mean", "var"} -> (y, (new mean, new var))."""
    y = lax.conv_general_dilated(q(x), q(p["w"]), (stride, stride),
                                 ((pad_, pad_), (pad_, pad_)),
                                 dimension_numbers=("NCHW", "OIHW", "NCHW"))
    mean = jnp.mean(y, axis=(0, 2, 3))
    var = jnp.mean(jnp.square(y - mean[None, :, None, None]), axis=(0, 2, 3))
    stats = (BN_KEEP * p["mean"] + (1 - BN_KEEP) * mean,
             BN_KEEP * p["var"] + (1 - BN_KEEP) * var)
    y = (y - mean[None, :, None, None]) * lax.rsqrt(var + BN_EPS)[None, :, None, None]
    y = y * p["scale"][None, :, None, None] + p["shift"][None, :, None, None]
    return (jax.nn.relu(y) if relu else y), stats


def _stem(p, x, q):
    y, stats = _conv_bn(p["conv1"], x, 2, 3, True, q)
    y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          ((0, 0), (0, 0), (1, 1), (1, 1)))
    return y, {"conv1": stats}


def _block(p, x, stride, q):
    """One bottleneck; p has "branch1" where the shortcut is a projection."""
    stats = {}
    y, stats["branch2a"] = _conv_bn(p["branch2a"], x, stride, 0, True, q)
    y, stats["branch2b"] = _conv_bn(p["branch2b"], y, 1, 1, True, q)
    y, stats["branch2c"] = _conv_bn(p["branch2c"], y, 1, 0, False, q)
    sc = x
    if "branch1" in p:
        sc, stats["branch1"] = _conv_bn(p["branch1"], x, stride, 0, False, q)
    return jax.nn.relu(y + sc), stats


def _head(p, x, label, q):
    x = jnp.mean(x, axis=(2, 3))                                  # [B,2048]
    logits = q(x) @ q(p["w"]) + p["b"]
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, label[:, None], axis=-1)[:, 0]
    return jnp.mean(nll)


def _conv_params(p, name):
    return {"w": p[f"_{name}.w0"], "scale": p[f"_{name}_bn.w0"],
            "shift": p[f"_{name}_bn.wbias"], "mean": p[f"_{name}_bn.wmean"],
            "var": p[f"_{name}_bn.wvar"]}


def _parts(p, a):
    """The model's parameters grouped by part, under part-local names, so
    that parts of one shape are one program: [(part name, {...})]."""
    parts = [("stem", {"conv1": _conv_params(p, "res_conv1")})]
    for n, _, _, _, project in _blocks(a):
        parts.append((n, {br: _conv_params(p, f"{n}_{br}") for br in BRANCHES
                          if br != "branch1" or project}))
    parts.append(("head", {"w": p["_res_fc.w0"], "b": p["_res_fc.wbias"]}))
    return parts


def _flat(part, tree, grads=False):
    """Part-local names back to the configuration's leaf names."""
    if part == "head":
        return {"_res_fc.w0": tree["w"], "_res_fc.wbias": tree["b"]}
    out = {}
    for br, v in tree.items():
        name = "res_conv1" if part == "stem" else f"{part}_{br}"
        if grads:
            out[f"_{name}.w0"] = v["w"]
            out[f"_{name}_bn.w0"], out[f"_{name}_bn.wbias"] = v["scale"], v["shift"]
        else:
            out[f"_{name}_bn.wmean"], out[f"_{name}_bn.wvar"] = v
    return out


def loss(p, b, q, a):
    """(cost, {moving statistic: new value}) of one batch."""
    parts = _parts(p, a)
    strides = [s for _, _, _, s, _ in _blocks(a)]
    x, stats = _stem(parts[0][1], b["image"], q)
    aux = _flat("stem", stats)
    for (name, pk), stride in zip(parts[1:-1], strides):
        x, stats = _block(pk, x, stride, q)
        aux.update(_flat(name, stats))
    return _head(parts[-1][1], x, b["label"], q), aux


@functools.lru_cache(maxsize=None)
def _programs(q):
    """The jitted parts, one set per rounding."""
    stem = jax.jit(functools.partial(_stem, q=q))
    block = jax.jit(functools.partial(_block, q=q), static_argnames="stride")
    head_vg = jax.jit(jax.value_and_grad(functools.partial(_head, q=q),
                                         argnums=(0, 1)))

    @functools.partial(jax.jit, static_argnames="stride")
    def block_vjp(pk, x, ct, stride):
        _, pull = jax.vjp(lambda pk, x: _block(pk, x, stride, q)[0], pk, x)
        return pull(ct)

    @jax.jit
    def stem_vjp(pk, x, ct):
        return jax.vjp(lambda pk: _stem(pk, x, q)[0], pk)[1](ct)[0]

    return stem, block, head_vg, block_vjp, stem_vjp


def value_and_grad(p, b, q, a):
    """((cost, aux), gradients) as jax.value_and_grad(loss, has_aux=True)
    gives them, block by block. Not to be jitted as a whole."""
    stem, block, head_vg, block_vjp, stem_vjp = _programs(q)
    parts = _parts(p, a)
    strides = [s for _, _, _, s, _ in _blocks(a)]
    inputs = [b["image"]]
    x, stats = stem(parts[0][1], b["image"])
    aux = _flat("stem", stats)
    for (name, pk), stride in zip(parts[1:-1], strides):
        inputs.append(x)
        x, stats = block(pk, x, stride=stride)
        aux.update(_flat(name, stats))
    cost, (g_head, ct) = head_vg(parts[-1][1], x, b["label"])
    grads = _flat("head", g_head)
    for (name, pk), stride in reversed(list(zip(parts[1:-1], strides))):
        g, ct = block_vjp(pk, inputs.pop(), ct, stride=stride)
        grads.update(_flat(name, g, grads=True))
    grads.update(_flat("stem", stem_vjp(parts[0][1], inputs.pop(), ct),
                       grads=True))
    for k in static_names(a):
        grads[k] = jnp.zeros_like(p[k])
    return (cost, aux), grads
