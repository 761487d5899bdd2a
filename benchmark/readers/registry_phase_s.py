"""Seconds the program has spent in the given phases of one of its histogram
families since the process began: the sums of the series whose `phase` label
is one of `phases`, read from the program's own registry. `registry` is a
dotted name (`paddle_tpu.observability.metrics.default_registry`), resolved
through `benchmark.program.resolve`, so program.py stays the only module that
imports the program. None where the registry, the family or every one of the
phases is missing (a program older than the phase: the metric is left out).

Cumulative is right here: every compilation of a run lies in set-up, before
the window, where `ctx["phases"]` (a difference over the window) reads zero.
"""


def read(ctx, registry, family, phases):
    from benchmark import program

    try:
        series = program.resolve(registry).snapshot()[family]["series"]
    except (ImportError, AttributeError, KeyError):
        return None
    found = [h["sum"] for labels, h in series.items()
             if dict(labels).get("phase") in phases and h["count"]]
    return sum(found) if found else None
