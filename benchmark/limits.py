"""python3 benchmark/limits.py --workload <cell> --seeds 1,2,3 [--control-seeds N]

Reads, in one process on the chip, what a cell's limits are set from: for
each seed the numbers of benchmark/correct.py for the program's own first
three steps (the lower readings), and for the first N seeds the same numbers
for the control (the reference in the program's place, in the precision the
configuration's file names under precision.control) and for the planted
faults (half of the batch left out; the state handed back unchanged). One
JSON line per reading. No window is measured: training's readings need none.
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import correct, run, traffic  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--also", default="",
                    help="further roundings of the reference to read, e.g. bf16")
    ap.add_argument("--leaves", type=int, default=0,
                    help="also print the N leaves with the widest gaps")
    ap.add_argument("--dump", default="",
                    help="append every reading's per-leaf norms to this file")
    a = ap.parse_args()
    cell, config, mix, _, _ = run.load_cell(a.workload)
    run.check_device(cell)
    from benchmark import program

    program.compile_cache()
    ref = correct.load_module(config["reference"])
    args = config["model"]["args"]
    for n, seed in enumerate(int(s) for s in a.seeds.split(",")):
        mix3 = dict(mix, pool_batches=correct.STEPS)
        batches = [rows for rows, _ in traffic.pool(mix3, args, seed)]
        trainer, static = program.build_trainer(
            config, cell, correct.init_params(ref.param_table(args), seed))
        loop, prog = run.first_steps(config, trainer, static, batches, seed)
        del trainer, loop
        gc.collect()
        want = correct.reference_steps(config, batches, seed)
        readings = {"program": prog}
        if n < a.control_seeds:
            readings["control"] = correct.reference_steps(
                config, batches, seed, rounding=config["precision"]["control"])
            for fault in ("half_batch", "state_unchanged"):
                readings[fault] = correct.reference_steps(
                    config, batches, seed, fault=fault)
            for rounding in filter(None, a.also.split(",")):
                readings[rounding] = correct.reference_steps(
                    config, batches, seed, rounding=rounding)
        for who, got in readings.items():
            nums = correct.compare(got, want, static)
            print(json.dumps({"cell": a.workload, "seed": seed, "who": who,
                              **{k: v[0] for k, v in nums.items()},
                              "at": {k: v[1] for k, v in nums.items()},
                              "loss": got["loss"], "ref_loss": want["loss"]}),
                  flush=True)
            if a.dump:
                with open(a.dump, "a") as f:
                    f.write(json.dumps({"seed": seed, "who": who, "got": got,
                                        "want": want}) + "\n")
            for kind in ("grad", "delta") if a.leaves else ():
                med = sorted(want[kind].values())[len(want[kind]) // 2]
                gaps = sorted(((abs(got[kind][k] - want[kind][k])
                                / max(want[kind][k], med), k)
                               for k in want[kind]), reverse=True)[:a.leaves]
                print(json.dumps({"who": who, "seed": seed, "kind": kind,
                                  "median_ref_norm": med, "leaves": [
                                      [k, g, got[kind][k], want[kind][k]]
                                      for g, k in gaps]}), flush=True)


if __name__ == "__main__":
    main()
