"""ZeRO-1 optimizer-state memory accounting (ISSUE 9, docs/multislice.md).

Per-chip optimizer-state bytes of one model under the two layouts
MultiSliceTrainer supports on the 2x4 slice x data mesh:

- replicated:  every chip holds every slot whole (the r0-r13 trainer,
  and the reference's per-trainer full optimizer state before its
  pserver block-sharding, ParameterServer2.h:163-238);
- zero:        every param-shaped slot flattened, padded to a multiple
  of the data-axis size N and 1/N-sharded over 'data'
  (parallel/multislice.zero_pack) — scalar slots (Adam's t, __step__)
  stay replicated.

The acceptance bound printed per optimizer (and asserted by
tests/test_multislice.py::test_zero_accounting_tool):

    zero_per_chip <= replicated_per_chip / N + O(1) overhead

where the overhead is the replicated scalars plus <= N-1 pad elements
per slot.

Usage:  python tools/zero_accounting.py [--hidden 512] [--layers 3]
        [--quick] [--json]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import activation, data_type, layer, optimizer  # noqa: E402
from paddle_tpu.core.topology import Topology  # noqa: E402
from paddle_tpu.parallel.mesh import make_mesh  # noqa: E402
from paddle_tpu.parallel.multislice import (per_chip_opt_bytes,  # noqa: E402
                                            zero_pack)

OPTIMIZERS = {
    "sgd": lambda: optimizer.Momentum(learning_rate=0.1),
    "momentum": lambda: optimizer.Momentum(learning_rate=0.1, momentum=0.9),
    "adam": lambda: optimizer.Adam(learning_rate=1e-3),
    "adadelta": lambda: optimizer.AdaDelta(learning_rate=1.0),
    "rmsprop": lambda: optimizer.RMSProp(learning_rate=1e-3),
    "adamax": lambda: optimizer.AdaMax(learning_rate=1e-3),
}


def build_model(dim, hidden, layers, classes=16):
    x = layer.data(name="x", type=data_type.dense_vector(dim))
    y = layer.data(name="y", type=data_type.integer_value(classes))
    h = x
    for i in range(layers):
        h = layer.fc(input=h, size=hidden, act=activation.Relu(),
                     name=f"h{i}")
    out = layer.fc(input=h, size=classes, act=activation.Softmax(),
                   name="out")
    return layer.classification_cost(input=out, label=y, name="cost")


def account(hidden=512, layers=3, dim=512, slices=2, data=4):
    mesh = make_mesh(slice=slices, data=data)
    n = mesh.shape["data"]
    cost = build_model(dim, hidden, layers)
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    param_bytes = sum(int(np.asarray(p).nbytes) for p in params.values())
    n_slots = sum(int(np.prod(p.shape)) for p in params.values())
    rows = {}
    for name, make_opt in OPTIMIZERS.items():
        opt = make_opt()
        canon = opt.init(params)
        repl = per_chip_opt_bytes(canon, mesh, zero=False)
        z = per_chip_opt_bytes(zero_pack(canon, params, mesh), mesh,
                               zero=True)
        # O(1) overhead bound: replicated scalars (__step__ + per-param
        # t slots) + up to N-1 f32 pad elements per sharded slot
        n_sharded = sum(
            1 for pname, slots in canon.items()
            if pname in params        # reserved keys by membership, not
            for v in slots.values()   # prefix: '___fc_0__.w0' is a param
            if hasattr(v, "shape") and v.shape == params[pname].shape)
        overhead = 4 * (1 + len(params)) + 4 * (n - 1) * max(n_sharded, 1)
        rows[name] = {
            "replicated_per_chip_bytes": int(repl),
            "zero_per_chip_bytes": int(z),
            "drop": round(repl / max(z, 1), 2),
            "within_bound": bool(z <= repl / n + overhead),
        }
    return {"mesh": f"{slices}x{data} slice x data",
            "model": f"fc dim={dim} hidden={hidden} x{layers}",
            "param_bytes": param_bytes, "param_elements": n_slots,
            "data_axis": n, "optimizers": rows}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--quick", action="store_true",
                    help="tiny model (the tier-1 smoke configuration)")
    ap.add_argument("--json", action="store_true",
                    help="one JSON line instead of the table")
    args = ap.parse_args(argv)
    if args.quick:
        args.hidden, args.layers, args.dim = 32, 2, 32
    rep = account(hidden=args.hidden, layers=args.layers, dim=args.dim)
    if args.json:
        print(json.dumps(rep))
        return rep
    n = rep["data_axis"]
    print(f"# ZeRO-1 optimizer-state accounting — {rep['mesh']} mesh, "
          f"{rep['model']} ({rep['param_bytes'] / 1e6:.2f} MB params)\n")
    print(f"| optimizer | replicated/chip | zero/chip | drop | "
          f"<= repl/{n} + O(1) |")
    print("|---|---|---|---|---|")
    for name, r in rep["optimizers"].items():
        print(f"| {name} | {r['replicated_per_chip_bytes'] / 1e6:.3f} MB "
              f"| {r['zero_per_chip_bytes'] / 1e6:.3f} MB "
              f"| {r['drop']}x | {'yes' if r['within_bound'] else 'NO'} |")
    return rep


if __name__ == "__main__":
    main()
