"""The four step-building metrics (PR 36): the reader on a hand-made
registry, the metric files against BENCHMARK.json, and a CPU rehearsal cell
that prints them."""

import json
import os
import types

import pytest

from benchmark import correct, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAMES = {"step_build_s": "compile", "step_trace_s": "compile_trace",
         "step_lower_s": "compile_lower",
         "step_backend_compile_s": "compile_backend"}
CELLS = ["resnet50-train-b256", "nmt-train-b512", "qwen3next-ep32-train-s4096",
         "sdar-ep8-train-s8192", "kimivl-ep8-train-s8192"]


def _reader():
    return correct.load_module("readers/registry_phase_s.py")


def _registry(series):
    snap = {"paddle_train_step_seconds": {"type": "histogram",
                                          "series": series}}
    return types.SimpleNamespace(snapshot=lambda: snap)


def _hist(total, count):
    return {"sum": total, "count": count, "buckets": []}


def test_reader_sums_the_phases_and_leaves_an_empty_family_out(monkeypatch):
    from benchmark import program

    series = {(("phase", "compile"),): _hist(12.5, 2),
              (("phase", "compile_trace"),): _hist(1.25, 2),
              (("phase", "dispatch"),): _hist(99.0, 400),
              (("phase", "compile_lower"),): _hist(0.0, 0)}
    fake = {"pkg.mod.registry": _registry(series),
            "pkg.mod.empty": _registry({}),
            "pkg.mod.bare": types.SimpleNamespace(snapshot=lambda: {})}

    def resolve(dotted):
        if dotted not in fake:
            raise AttributeError(dotted)
        return fake[dotted]

    monkeypatch.setattr(program, "resolve", resolve)
    read = _reader().read
    fam = "paddle_train_step_seconds"
    assert read({}, "pkg.mod.registry", fam, ["compile"]) == 12.5
    assert read({}, "pkg.mod.registry", fam,
                ["compile", "compile_trace"]) == 13.75
    # a phase nothing observed, a phase that is not there, an empty family,
    # a registry without the family, a program without the registry
    assert read({}, "pkg.mod.registry", fam, ["compile_lower"]) is None
    assert read({}, "pkg.mod.registry", fam, ["compile_backend"]) is None
    assert read({}, "pkg.mod.empty", fam, ["compile"]) is None
    assert read({}, "pkg.mod.bare", fam, ["compile"]) is None
    assert read({}, "pkg.mod.gone", fam, ["compile"]) is None


@pytest.mark.parametrize("name", sorted(NAMES))
def test_metric_file_names_the_reader_and_benchmark_json_lists_the_cells(name):
    spec = run.load_json("metrics", name + ".json")
    assert spec["reader"] == "registry_phase_s"
    assert spec["args"] == {
        "registry": "paddle_tpu.observability.metrics.default_registry",
        "family": "paddle_train_step_seconds", "phases": [NAMES[name]]}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "s", "better": "lower",
                     "source": "program_counter", "layer": "step program",
                     "moves": "setup_s", "workloads": CELLS}
    assert [m["name"] for m in bench["per_layer"][-4:]] == [
        "step_build_s", "step_trace_s", "step_lower_s",
        "step_backend_compile_s"]


def test_rehearsal_cell_prints_the_four(capsys):
    run.main(["--workload", "rehearsal-nmt-build", "--seed", "3600000007",
              "--seconds", "1", "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == set(NAMES)
    assert all(v > 0 for v in got.values())
    assert got["step_trace_s"] + got["step_lower_s"] + \
        got["step_backend_compile_s"] <= got["step_build_s"]
    assert line["correct"] is True
