"""ResNet-50 perf sweep: measure step-time variants to find the >=1.0x
configuration (VERDICT r2 next-step #1).

Each variant builds the same jitted train step as bench.py and prints
ms/step + imgs/sec. Run: python tools/perf_sweep.py v1 v2 ...
Variants:
  base128     flat-CHW fp32 feed, bs=128 (the r2 configuration)
  base256     flat-CHW fp32 feed, bs=256
  nhwc128     NHWC 4-D fp32 feed, bs=128 (no per-step CHW->NHWC transpose)
  nhwc256     NHWC 4-D fp32 feed, bs=256
  nhwc256b    NHWC 4-D bf16 feed, bs=256 (halved input HBM traffic)
  nhwc512b    NHWC 4-D bf16 feed, bs=512
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from paddle_tpu import optimizer
from paddle_tpu.core.topology import Topology
from paddle_tpu.models.resnet import resnet_cost


def build_step():
    from paddle_tpu.trainer.trainer import make_train_step

    img, lab, out, cost = resnet_cost(depth=50, img_size=224)
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9)
    opt_state = opt.init(params)
    loss = topo.loss_fn(cost, compute_dtype=jnp.bfloat16)
    step = make_train_step(loss, opt, topo.static_map(), donate=True)
    return step, params, opt_state


def measure(step, params, opt_state, feeds, iters=20, prekeys=False):
    rng = jax.random.PRNGKey(0)
    params, opt_state, c, _ = step(params, opt_state, rng, feeds)
    float(c)
    if prekeys:
        # fold_in dispatches a tiny device op between step launches,
        # which can serialize with the step stream — precompute all
        # keys before the timed window
        keys = [jax.random.fold_in(rng, i) for i in range(iters)]
        jax.block_until_ready(keys)
    t0 = time.perf_counter()
    for i in range(iters):
        params, opt_state, c, _ = step(params, opt_state,
                                       keys[i] if prekeys else
                                       jax.random.fold_in(rng, i), feeds)
    float(c)
    return (time.perf_counter() - t0) / iters


def feeds_for(variant, batch):
    r = np.random.RandomState(0)
    lab = jnp.asarray(r.randint(0, 1000, (batch, 1)), jnp.int32)
    if variant.startswith("base"):
        img = jnp.asarray(r.rand(batch, 3 * 224 * 224), jnp.float32)
    else:
        dt = jnp.bfloat16 if variant.endswith("b") else jnp.float32
        img = jnp.asarray(r.rand(batch, 224, 224, 3), dt)
    return {"image": img, "label": lab}


VARIANTS = {
    "base128": ("base", 128), "base256": ("base", 256),
    "nhwc128": ("nhwc", 128), "nhwc256": ("nhwc", 256),
    "nhwc192b": ("nhwcb", 192), "nhwc224b": ("nhwcb", 224),
    "nhwc256b": ("nhwcb", 256), "nhwc384b": ("nhwcb", 384),
    "nhwc512b": ("nhwcb", 512),
}


def main():
    names = sys.argv[1:] or ["base128", "base256", "nhwc256b"]
    step, params0, opt0 = build_step()
    for name in names:
        if name.startswith("devloop"):
            measure_loop(steps_per_call=int(name[len("devloop"):] or 5))
            continue
        prekeys = name.endswith("+pk")
        kind, batch = VARIANTS[name[:-3] if prekeys else name]
        feeds = feeds_for(kind, batch)
        # fresh param/opt copies: step donates its inputs
        params = jax.tree_util.tree_map(jnp.copy, params0)
        opt_state = jax.tree_util.tree_map(jnp.copy, opt0)
        sec = measure(step, params, opt_state, feeds, prekeys=prekeys)
        print(f"{name}: {sec * 1e3:.2f} ms/step  "
              f"{batch / sec:.1f} imgs/sec", flush=True)


def measure_loop(batch=256, steps_per_call=5, calls=4):
    """Device-side lax.scan training loop (make_train_loop)."""
    import os
    os.environ["PADDLE_TPU_ALLOW_SCAN_LOOP"] = "1"   # sanctioned bench tool
    from paddle_tpu.trainer.trainer import make_train_loop
    from paddle_tpu.models.resnet import resnet_cost

    img, lab, out, cost = resnet_cost(depth=50, img_size=224)
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9)
    opt_state = opt.init(params)
    loss = topo.loss_fn(cost, compute_dtype=jnp.bfloat16)
    loop = make_train_loop(loss, opt, topo.static_map(), steps_per_call)
    r = np.random.RandomState(0)
    feeds = {"image": jnp.asarray(r.rand(batch, 224, 224, 3), jnp.bfloat16),
             "label": jnp.asarray(r.randint(0, 1000, (batch, 1)), jnp.int32)}
    rng = jax.random.PRNGKey(0)
    params, opt_state, c = loop(params, opt_state, rng, feeds)
    float(c)
    t0 = time.perf_counter()
    for i in range(calls):
        params, opt_state, c = loop(params, opt_state,
                                    jax.random.fold_in(rng, i), feeds)
    float(c)
    sec = (time.perf_counter() - t0) / (calls * steps_per_call)
    print(f"devloop{steps_per_call}: {sec * 1e3:.2f} ms/step  "
          f"{batch / sec:.1f} imgs/sec", flush=True)


if __name__ == "__main__":
    import paddle_tpu

    paddle_tpu.compile_cache()
    main()
