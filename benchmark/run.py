"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell, in this process, on the machine it is started on. Loads
the cell's data files by the names BENCHMARK.json gives, makes weights and
traffic from the seed, builds the trainer, drives it through its first three
steps (kept for the comparison, and the warm-up of the cell's one shape),
measures the public training loop for --seconds, reads the program's
counters (and with --trace 1 a profiler trace of some steps), frees the
program, runs the plain reference, and prints one JSON line.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for, when the device is not in peaks.json, or when the program
is not importable. A cell file marked "rehearsal" (which BENCHMARK.json may
not list) may run on the CPU: its line says platform cpu and carries no
device metric.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import correct, traffic  # noqa: E402

def note(**kv):
    """An earlier line of standard output: context, never the result."""
    print(json.dumps(kv), flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name):
    """(cell file, config file, traffic mix, the cell's per-layer metric
    names, its end-to-end metric names)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {w["name"]: w for w in bench["workloads"]}
    cell = load_json("workloads", name + ".json")
    if name in listed:
        if cell.get("rehearsal"):
            sys.exit(f"benchmark: {name} is listed in BENCHMARK.json and "
                     "marked a rehearsal; a listed cell runs on the chip only")
        entry = listed[name]
    elif cell.get("rehearsal"):
        entry = {"config": cell["config"], "traffic": cell["traffic"],
                 "chips": cell["chips"], "name": name}
    else:
        sys.exit(f"benchmark: {name} is not a cell of BENCHMARK.json")
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            sys.exit(f"benchmark: {name}: {key} differs between the cell's "
                     "file and BENCHMARK.json")

    def wanted(m, default_metric):
        if "workloads" in m:
            return name in m["workloads"]
        return default_metric in cell["reports"]

    e2e = [m["name"] for m in bench["end_to_end"]
           if m["name"] in cell["reports"]]
    layer = [m for m in bench["per_layer"] if wanted(m, m["moves"])]
    if cell.get("rehearsal"):
        layer = [m for m in bench["per_layer"]
                 if m["name"] in cell.get("rehearse_metrics", [])]
    return (cell, load_json("configs", cell["config"] + ".json"),
            traffic.load(cell["traffic"]), layer, e2e)


def check_device(cell):
    """The devices this run may use, and the chip's peaks; exits where the
    cell cannot be measured here."""
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind
    if cell.get("rehearsal"):
        return devs, None
    if devs[0].platform != "tpu":
        sys.exit(f"benchmark: JAX reports {devs[0].platform!r} ({kind}), not "
                 "a TPU; nothing was measured")
    if len(devs) < cell["chips"]:
        sys.exit(f"benchmark: the cell needs {cell['chips']} chips, JAX "
                 f"finds {len(devs)}")
    peaks = load_json("peaks.json")
    if kind not in peaks:
        sys.exit(f"benchmark: device kind {kind!r} is not in peaks.json; add "
                 "its published peaks with their source")
    return devs, peaks[kind]


def first_steps(config, trainer, static, batches, seed, annotate=False):
    """Drives the trainer through its first steps by the window's own call
    (one batch, then the rest, so that the optimizer's state after step one
    can be read) and returns (the loop, {"loss", "grad", "delta"})."""
    from benchmark import program

    table = correct.load_module(config["reference"]).param_table(
        config["model"]["args"])
    loop = program.Loop(trainer, config["feeding"], annotate=annotate)
    loop.run(batches[:1])
    p0 = correct.init_params(table, seed)
    grad = correct.first_gradient_norms(
        config["optimizer"], program.optimizer_state(trainer), p0, static)
    loop.run(batches[1:])
    delta = correct.diff_norms(program.parameters(trainer), p0)
    return loop, {"loss": list(loop.costs), "grad": grad, "delta": delta}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    cell, config, mix, layer_metrics, e2e = load_cell(a.workload)
    devs, peak = check_device(cell)
    import jax

    from benchmark import program

    t_import = time.perf_counter()
    cache_dir = program.compile_cache()
    ref = correct.load_module(config["reference"])
    model_args = config["model"]["args"]
    params = correct.init_params(ref.param_table(model_args), a.seed)
    jax.block_until_ready(params)
    t_init = time.perf_counter()
    trainer, static = program.build_trainer(config, cell, params)
    del params
    t_trainer = time.perf_counter()
    pool = traffic.pool(mix, model_args, a.seed)
    assert len(pool) >= correct.STEPS
    t_built = time.perf_counter()

    # ---- the first three steps: through the window's own call and feed ----
    batches = [rows for rows, _ in pool[:correct.STEPS]]
    loop, prog = first_steps(config, trainer, static, batches, a.seed,
                             annotate=bool(a.trace))
    shapes = program.step_shapes(trainer)
    t_warm = time.perf_counter()

    # what set-up built (modules, the pool's rows) leaves the collector's
    # sight, so that a full collection inside the window has little to scan
    gc.collect()
    gc.freeze()

    # ---- with --trace 1: some steps under the profiler, before the window,
    # through the same call; starting and stopping the profiler takes
    # seconds of host time, which must not fall into the window's counters
    trace_dir = os.path.join(ROOT, ".bench_trace", a.workload)
    if a.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        n_trace = cell["trace_steps"]
        jax.profiler.start_trace(trace_dir)
        loop.run(pool[(correct.STEPS + i) % len(pool)][0]
                 for i in range(n_trace))
        jax.profiler.stop_trace()
        loop.annotate = False

    # ---- the window ----
    n0 = len(loop.drained)
    work = []
    before = program.phase_seconds()
    t_start = time.perf_counter()
    deadline = t_start + a.seconds

    def window():
        i = correct.STEPS
        while time.perf_counter() < deadline:
            rows, w = pool[i % len(pool)]
            work.append(w)
            i += 1
            yield rows

    setup_s = t_start - T0
    loop.run(window())
    after = program.phase_seconds()
    drained = loop.drained[n0:]
    costs = loop.costs[n0:]
    window_s = drained[-1] - t_start
    intervals = [b - a_ for a_, b in zip(drained, drained[1:])]
    failed = sum(1 for c in costs if not (c == c and abs(c) != float("inf")))
    if len(program.step_shapes(trainer)) != 1 or \
            program.step_shapes(trainer) != shapes:
        sys.exit("benchmark: a second step shape compiled inside the run: "
                 f"{program.step_shapes(trainer)}")

    # ---- memory, and the step as compiled ----
    stats = [d.memory_stats() or {} for d in devs[:cell["chips"]]]
    # the runtime keeps a running program's temporaries apart from the live
    # buffers (`bytes_reserved`): a chip's peak is the two peaks together
    peak_in_use = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    memory_peak = max((s.get("peak_bytes_in_use", 0)
                       + s.get("peak_bytes_reserved", 0) for s in stats),
                      default=0)
    compiled, key = program.compiled_step(trainer, pool[0][0],
                                          config["feeding"])
    mem = compiled.memory_analysis()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    planned = {k: int(getattr(mem, k + "_size_in_bytes", 0) or 0)
               for k in ("argument", "output", "alias", "temp",
                         "generated_code")} if mem is not None else {}
    if n_kernels < cell.get("min_kernel_calls", 0):
        sys.exit(f"benchmark: the compiled step holds {n_kernels} "
                 f"tpu_custom_call, the cell needs {cell['min_kernel_calls']}:"
                 " a layer left its kernel")
    note(what="memory", peak_bytes_in_use=peak_in_use, compiled_step=planned,
         tpu_custom_calls_in_step=n_kernels, memory_stats=stats[0],
         step_shape_key=str(key))
    t_after = time.perf_counter()

    # ---- free the program, then the reference ----
    shape = {name: dims for name, dims, _ in key}     # as the step saw it
    flops_per_step = correct.load_module(config["flops"]).train_flops_per_step(
        model_args, shape)
    del compiled, trainer, loop.trainer
    gc.collect()
    ref_out = correct.reference_steps(config, batches, a.seed)
    numbers = correct.compare(prog, ref_out, static)
    ok, rows = correct.judge(numbers, cell["limits"])
    t_ref = time.perf_counter()

    # ---- metrics ----
    done_work = sum(work[:len(drained)])
    values = {"setup_s": (setup_s, "s")}
    rate_name, rate_unit = cell["rate"]["metric"], cell["rate"]["unit"]
    values[rate_name] = (done_work / window_s, rate_unit)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell["chips"], "memory_peak_bytes": memory_peak}
    result = {"correct": bool(ok and failed == 0), "attempted": len(work),
              "failed": failed + (len(work) - len(drained))}
    if a.trace:
        from benchmark import trace_reduce

        raw = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        red = trace_reduce.reduce(raw)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if red is None and not cell.get("rehearsal"):
            sys.exit("benchmark: the trace holds no device operation")
        phases = {k: (after[k][0] - before.get(k, (0, 0))[0],
                      after[k][1] - before.get(k, (0, 0))[1]) for k in after}
        ctx = {"phases": phases, "window_s": window_s, "steps": len(drained),
               "intervals": intervals, "flops_per_step": flops_per_step,
               "chips": cell["chips"], "peak": peak, "trace": red, "raw": raw,
               "cell": cell, "config": config, "shape": shape}
        metrics = {}
        for m in layer_metrics:
            spec = load_json("metrics", m["name"] + ".json")
            reader = correct.load_module(f"readers/{spec['reader']}.py")
            v = reader.read(ctx, **spec.get("args", {}))
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        if red is not None:
            device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    else:
        result["metrics"] = {k: {"value": values[k][0], "unit": values[k][1]}
                             for k in e2e}
    note(what="run", cell=a.workload, seed=a.seed, steps=len(drained),
         window_s=window_s, step_ms_mean=1e3 * window_s / max(len(drained), 1),
         step_ms_p50=1e3 * statistics.median(intervals) if intervals else None,
         interval_samples=len(intervals),
         longest_intervals_ms=[round(1e3 * x, 2) for x in
                               sorted(intervals, reverse=True)[:5]],
         work_per_step=work[0],
         flops_per_step=flops_per_step, shape=shape,
         rate={rate_name: values[rate_name][0]}, compile_cache_dir=cache_dir,
         losses_first_steps=prog["loss"], reference_losses=ref_out["loss"],
         split_s={"import": t_import - T0, "weights": t_init - t_import,
                  "build_trainer": t_trainer - t_init,
                  "traffic": t_built - t_trainer,
                  "first_steps": t_warm - t_built, "setup_s": setup_s,
                  "after_window": t_after - drained[-1],
                  "reference": t_ref - t_after})
    result["device"] = device
    result["compared"] = {n: {"value": v, "limit": lim, "worst": numbers[n][1]}
                          for n, v, lim in rows}
    print(json.dumps(result), flush=True)
    for n, v, lim in rows:
        print(f"compared {n} {v:.6g} limit {lim:.6g} at {numbers[n][1]}",
              file=sys.stderr)
    print(f"correct {result['correct']} failed_steps {failed}", file=sys.stderr,
          flush=True)


if __name__ == "__main__":
    main()
