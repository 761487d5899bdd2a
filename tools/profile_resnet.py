"""Capture a device profile of the ResNet-50 train step and print the
per-op time table (VERDICT r2 next-step #1: 'persist the xplane or a
per-op table as an artifact').

Usage: python tools/profile_resnet.py [outdir] [batch]
Writes the raw xplane trace under outdir and prints the top ops by
self-time, parsed with the installed xprof/tensorboard-plugin-profile.
"""

import glob
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu import optimizer
from paddle_tpu.core.topology import Topology
from paddle_tpu.models.resnet import resnet_cost


def main():
    outdir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/resnet_profile"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    from paddle_tpu.trainer.trainer import make_train_step

    img, lab, out, cost = resnet_cost(depth=50, img_size=224)
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9)
    opt_state = opt.init(params)
    loss = topo.loss_fn(cost, compute_dtype=jnp.bfloat16)
    step = make_train_step(loss, opt, topo.static_map(), donate=True)
    r = np.random.RandomState(0)
    feeds = {"image": jnp.asarray(r.rand(batch, 224, 224, 3), jnp.bfloat16),
             "label": jnp.asarray(r.randint(0, 1000, (batch, 1)), jnp.int32)}
    rng = jax.random.PRNGKey(0)
    params, opt_state, c, _ = step(params, opt_state, rng, feeds)
    float(c)
    # 30 iters: the dispatch queue needs depth for steady state (r4:
    # 20 iters under-reported by ~3.5 ms/step, not re-measured); the
    # per-op self-times in the trace are per-execution and unaffected
    iters = 30
    t0 = time.perf_counter()
    with jax.profiler.trace(outdir):
        for i in range(iters):
            params, opt_state, c, _ = step(params, opt_state,
                                           jax.random.fold_in(rng, i), feeds)
        float(c)
    dt = (time.perf_counter() - t0) / iters
    print(f"measured {dt * 1e3:.2f} ms/step  {batch / dt:.1f} imgs/sec")

    xplanes = glob.glob(os.path.join(outdir, "**", "*.xplane.pb"),
                        recursive=True)
    print("xplane files:", xplanes)
    if not xplanes:
        return
    # xprof first: the tensorboard_plugin_profile converter in this image
    # dies on a protobuf version conflict (TypeError at import, not
    # ImportError)
    try:
        from xprof.convert import raw_to_tool_data
    except Exception:
        from tensorboard_plugin_profile.convert import raw_to_tool_data
    data, _ = raw_to_tool_data.xspace_to_tool_data(
        [xplanes[-1]], "framework_op_stats^", {})
    import csv
    import io
    # returns JSON or CSV depending on version; try CSV first
    try:
        rows = list(csv.reader(io.StringIO(data)))
        print("\n".join(",".join(r[:8]) for r in rows[:40]))
    except Exception:
        print(str(data)[:4000])


if __name__ == "__main__":
    import paddle_tpu

    paddle_tpu.compile_cache()
    main()
