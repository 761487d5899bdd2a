"""python3 tools/moe_drift_probe.py --workload <cell> --seed <n> [--steps 40]
        [--learning-rate <x>]
(on the chip: chiprun -- python3 tools/moe_drift_probe.py --workload
sdar-ep8-train-s8192 --seed 3200000101)

How far the router drifts onto the experts a share holds inside a run, and
the step with it (PERF.md section 6, PR 27): builds a benchmark cell's
trainer as benchmark/run.py does (the benchmark's weights, traffic and
optimizer from the seed), drives `--steps` steps through the public loop and
reads, at every drained step, the drain-to-drain interval and
`paddle_moe_tokens_total{result}` summed over the layers. One JSON line: the
held share of pairs at step 4 and at the last step, their ratio, the median
interval of steps 4-8 and of the last five, both step by step from step 2
on, `paddle_moe_dropped_total`, the two gauges step by step (the
busiest held expert's load over the mean, the largest selection bias), and
from `paddle_moe_tiles_total` / `paddle_moe_expert_fetches_total` how far
the chunked grouped products engage: tiles over fetches (1.0 is the tile
loop) and the rows the tiles' padding adds to the held pairs, over the run
and at step 4 and the last step.
`--learning-rate` overrides the configuration's constant rate, to read the
drift at another one without editing a benchmark file. A rehearsal cell runs
on the CPU.
"""
import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import correct, program, run, traffic  # noqa: E402


def counters():
    from paddle_tpu.observability import metrics

    snap = metrics.default_registry.snapshot()
    by = {"held": 0.0, "elsewhere": 0.0}
    for labels, v in snap.get("paddle_moe_tokens_total",
                              {"series": {}})["series"].items():
        by[dict(labels)["result"]] += v
    dropped, tiles, fetches = (
        sum(snap.get(name, {"series": {}})["series"].values())
        for name in ("paddle_moe_dropped_total", "paddle_moe_tiles_total",
                     "paddle_moe_expert_fetches_total"))
    return by["held"], by["elsewhere"], dropped, tiles, fetches


def gauges():
    """(largest held-expert load over the mean, largest selection bias) over
    the layers at the last drained step; 0 where no layer publishes one."""
    from paddle_tpu.observability import metrics

    snap = metrics.default_registry.snapshot()
    return tuple(max(snap.get(name, {"series": {}})["series"].values(),
                     default=0.0)
                 for name in ("paddle_moe_expert_load_max_over_mean",
                              "paddle_moe_selection_bias_max_abs"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--learning-rate", type=float)
    a = ap.parse_args()
    # the cell's own files, whether BENCHMARK.json lists it or not
    cell = run.load_json("workloads", a.workload + ".json")
    config = run.load_json("configs", cell["config"] + ".json")
    mix = traffic.load(cell["traffic"])
    run.check_device(cell)
    if a.learning_rate is not None:
        config["optimizer"]["learning_rate"] = a.learning_rate
    program.compile_cache()
    args = config["model"]["args"]
    params = correct.init_params(
        correct.load_module(config["reference"]).param_table(args), a.seed)
    trainer, _ = program.build_trainer(config, cell, params)
    del params
    pool = traffic.pool(mix, args, a.seed)
    loop = program.Loop(trainer, config["feeding"])
    seen = []                     # (time, held, elsewhere) at every drain
    state = []                    # (load skew, largest bias) at every drain
    walked = []                   # (tiles, fetches, held) at every drain
    real = loop._handler

    def handler(ev):
        n = len(loop.drained)
        real(ev)
        if len(loop.drained) > n:
            c = counters()
            seen.append((loop.drained[-1],) + c[:2])
            walked.append(c[3:] + c[:1])
            state.append(gauges())

    loop._handler = handler
    loop.run(pool[i % len(pool)][0] for i in range(a.steps))
    share = [(h1 - h0) / max(h1 - h0 + e1 - e0, 1.0)
             for (_, h0, e0), (_, h1, e1) in zip(seen, seen[1:])]
    ms = [1e3 * (b[0] - a_[0]) for a_, b in zip(seen, seen[1:])]
    # share[i], ms[i] belong to step i + 2 (the first drain has no interval)
    step = [tuple(b - a_ for a_, b in zip(w0, w1))
            for w0, w1 in zip(walked, walked[1:])]
    tile_rows = args.get("tile", 256)

    def engaged(tiles, fetches, held):
        return {"tiles_over_fetches": round(tiles / max(fetches, 1.0), 3),
                "padding_over_held": round(
                    tiles * tile_rows / max(held, 1.0) - 1, 4)}

    print(json.dumps({
        "cell": a.workload, "seed": a.seed, "steps": a.steps,
        "learning_rate": config["optimizer"]["learning_rate"],
        "held_share_step4": share[2], "held_share_last": share[-1],
        "last_over_step4": share[-1] / share[2],
        "interval_ms_steps4to8": statistics.median(ms[2:7]),
        "interval_ms_last5": statistics.median(ms[-5:]),
        "held_share_by_step": [round(x, 5) for x in share],
        "interval_ms_by_step": [round(x, 1) for x in ms],
        "held_load_max_over_mean_by_step": [round(x[0], 3) for x in state[1:]],
        "selection_bias_max_abs_by_step": [round(x[1], 5) for x in state[1:]],
        "dropped": counters()[2],
        "tiles_a_step": statistics.mean(t for t, _, _ in step),
        "grouped_products": engaged(*(sum(x) for x in zip(*step))),
        "grouped_products_step4": engaged(*step[2]),
        "grouped_products_last": engaged(*step[-1]),
        "costs_first_last": [loop.costs[0], loop.costs[-1]]}), flush=True)


if __name__ == "__main__":
    main()
