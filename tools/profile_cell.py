"""python3 tools/profile_cell.py --workload <cell> --seed <n> [--steps <k>]
        [--keep-trace]
(on the chip: chiprun -- python3 tools/profile_cell.py --workload
kimivl-ep8-train-s8192 --seed 3600000101)

A benchmark cell's step, read by scope: builds the cell's trainer as
benchmark/run.py does (the benchmark's weights, traffic and optimizer from
the seed), drives its first three steps (which compile), then `--steps`
steps (the cell's `trace_steps` by default) through the public loop under
`jax.profiler.start_trace`, as an operator would, and reads the profile with
`paddle_tpu.observability.profile`. Prints the table (PERF.md section 5) and
one JSON line: the seconds the reader took and the file's bytes, the drain
intervals of the profiled steps, the device's milliseconds a run of the step,
busy / window / idle, the share of busy time without a scope, and what
building the step cost by stage (`paddle_train_step_seconds{phase=compile*}`,
`paddle_train_compile_cache_total`). Writes chiprun_out/profile/<cell>.json
(the whole reduction) and .txt (the table); with --keep-trace the
`.xplane.pb` too, gzipped. A rehearsal cell runs on the CPU (no device plane:
the table is empty, the JSON line holds the host's numbers).
"""
import argparse
import gzip
import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import correct, program, run, traffic  # noqa: E402


def build_cost():
    """{phase: seconds} of the compile phases, and the cache counter."""
    from paddle_tpu.observability import metrics

    snap = metrics.default_registry.snapshot()
    phases = {p: s for p, (s, _) in program.phase_seconds().items()
              if p.startswith("compile")}
    cache = {dict(labels)["result"]: v for labels, v in snap.get(
        "paddle_train_compile_cache_total", {"series": {}})["series"].items()}
    return phases, cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int)
    ap.add_argument("--keep-trace", action="store_true")
    a = ap.parse_args()
    cell = run.load_json("workloads", a.workload + ".json")
    config = run.load_json("configs", cell["config"] + ".json")
    mix = traffic.load(cell["traffic"])
    run.check_device(cell)
    cache_dir = program.compile_cache()
    args = config["model"]["args"]
    params = correct.init_params(
        correct.load_module(config["reference"]).param_table(args), a.seed)
    trainer, _ = program.build_trainer(config, cell, params)
    del params
    pool = traffic.pool(mix, args, a.seed)
    loop = program.Loop(trainer, config["feeding"])
    loop.run(rows for rows, _ in pool[:correct.STEPS])
    phases, cache = build_cost()

    import jax

    from paddle_tpu.observability import profile

    steps = a.steps or cell["trace_steps"]
    trace_dir = os.path.join(ROOT, ".bench_trace", "profile-" + a.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    n0 = len(loop.drained)
    jax.profiler.start_trace(trace_dir)
    loop.run(pool[(correct.STEPS + i) % len(pool)][0] for i in range(steps))
    jax.profiler.stop_trace()
    drained = loop.drained[n0:]
    intervals = [1e3 * (b - a_) for a_, b in zip(drained, drained[1:])]

    path = profile.find_xplane(trace_dir)
    t0 = time.perf_counter()
    red = profile.reduce(profile.load(path))
    read_s = time.perf_counter() - t0
    text = profile.render(red, os.path.relpath(path, ROOT))
    print(text, flush=True)
    out = os.path.join(ROOT, "chiprun_out", "profile")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, a.workload + ".txt"), "w") as f:
        f.write(text + "\n")
    with open(os.path.join(out, a.workload + ".json"), "w") as f:
        json.dump(red, f)
    if a.keep_trace:
        with open(path, "rb") as src, gzip.open(
                os.path.join(out, a.workload + ".xplane.pb.gz"), "wb") as dst:
            shutil.copyfileobj(src, dst)
    line = {"cell": a.workload, "seed": a.seed, "steps": steps,
            "profile_read_s": read_s, "trace_bytes": os.path.getsize(path),
            "profiled_interval_ms_p50":
                statistics.median(intervals) if intervals else None,
            "profiled_intervals_ms": [round(x, 2) for x in intervals],
            "compile_cache_dir": cache_dir,
            "build_s": phases, "compile_cache": cache}
    if red is not None:
        step = red["modules"][0] if red["modules"] else None
        line.update(
            busy_s=red["busy_s"], window_s=red["window_s"],
            idle_share=red["idle_share"], self_s=red["self_s"],
            unscoped_share=red["unscoped_share"],
            device_ms_a_run=1e3 * step["seconds"] / step["runs"] if step
            else None, runs=step["runs"] if step else None)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
