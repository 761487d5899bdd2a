"""python3 benchmark/reference_reading.py --workload <cell> --seeds 1,2 --who fp8,half_batch,state_unchanged

Reads, on the chip, what the upper readings of a cell's limits are set from,
WITHOUT the program: for each seed the plain reference's three steps, and for
each name under --who the same steps of the reference with its operands
rounded (a name of reference/lowprec.py, e.g. the configuration's
precision.control) or with a fault planted ("half_batch",
"state_unchanged"), compared as benchmark/correct.py compares a run. One
JSON line per reading, in limits.py's form.

limits.py reads the same numbers beside the program's own, in one process.
This is for a configuration whose state is too large for that: the program's
trainer is never built here, and an unchanged state is planted by starting
every step from a fresh copy of the seed's state, where
correct.reference_steps(fault="state_unchanged") holds the old and the new
state at once (at qwen3-next-80b-a3b-ep32: 13.6 GB beside a block's 2.7 GB,
which the chip refuses). The lower readings come from `limits.py --control-seeds 0` and
from the `compared` lines of the cell's own runs.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import correct, run, traffic  # noqa: E402

FAULTS = ("half_batch", "state_unchanged")


def state_unchanged(config, batches, seed):
    """What correct.reference_steps(fault="state_unchanged") returns, with
    one state alive at a time."""
    import jax
    import jax.numpy as jnp

    ref = correct.load_module(config["reference"])
    q = correct.load_module("reference/lowprec.py").BY_NAME["none"]
    args, l2 = config["model"]["args"], config["optimizer"].get("l2", 0.0)
    static = set(ref.static_names(args))
    if hasattr(ref, "value_and_grad"):
        def grads_of(p, b):
            return ref.value_and_grad(p, b, q, args)
    else:
        grads_of = jax.jit(lambda p, b: jax.value_and_grad(
            lambda p: ref.loss(p, b, q, args), has_aux=True)(p))

    @jax.jit
    def norms(p, g):
        return {k: jnp.sqrt(jnp.sum(jnp.square(g[k] + l2 * p[k])))
                for k in p if k not in static}

    def fresh():
        return correct.init_params(ref.param_table(args), seed)

    with jax.default_matmul_precision("highest"):
        losses, grad = [], None
        for t in range(correct.STEPS):
            p = fresh()     # every step starts from the state it was given
            b = {k: jnp.asarray(v)
                 for k, v in ref.pad(batches[t], args).items()}
            (loss, _), g = grads_of(p, b)
            if t == 0:
                grad = {k: float(v) for k, v in norms(p, g).items()}
            losses.append(float(loss))
            del p, g, b
        delta = correct.diff_norms(fresh(), fresh())
    return {"loss": losses, "grad": grad, "delta": delta}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--who", required=True,
                    help="roundings of reference/lowprec.py and/or faults "
                         f"{FAULTS}, comma-separated")
    a = ap.parse_args()
    cell, config, mix, _, _ = run.load_cell(a.workload)
    run.check_device(cell)
    from benchmark import program

    program.compile_cache()
    ref = correct.load_module(config["reference"])
    args = config["model"]["args"]
    static = set(ref.static_names(args))
    for seed in (int(s) for s in a.seeds.split(",")):
        mix3 = dict(mix, pool_batches=correct.STEPS)
        batches = [rows for rows, _ in traffic.pool(mix3, args, seed)]
        want = correct.reference_steps(config, batches, seed)
        for who in a.who.split(","):
            if who == "state_unchanged":
                got = state_unchanged(config, batches, seed)
            elif who in FAULTS:
                got = correct.reference_steps(config, batches, seed, fault=who)
            else:
                got = correct.reference_steps(config, batches, seed,
                                              rounding=who)
            nums = correct.compare(got, want, static)
            print(json.dumps({"cell": a.workload, "seed": seed, "who": who,
                              **{k: v[0] for k, v in nums.items()},
                              "at": {k: v[1] for k, v in nums.items()},
                              "loss": got["loss"], "ref_loss": want["loss"]}),
                  flush=True)


if __name__ == "__main__":
    main()
