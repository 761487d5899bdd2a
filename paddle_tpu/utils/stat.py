"""Hierarchical wall-clock stats + profiler trace spans.

Analog of paddle/utils/Stat.h:114-246 (Stat/StatSet/TimerOnce,
REGISTER_TIMER_INFO) and the GPU-profiler bridge (Stat.cpp:155). On TPU the
profiler is jax.profiler: ``timer_scope`` records host wall-clock into the
global StatSet and opens a ``jax.profiler.TraceAnnotation`` (a TraceMe), so
a profile taken with ``jax.profiler.start_trace`` holds the span on a host
line, on the same clock as the device's ops. With no profiler session the
annotation is one atomic check.

The observability subsystem rides the same call: when a tracer is active
(observability.trace.enable), every ``timer_scope`` completion also lands
as a Chrome trace-event span via the ``set_trace_sink`` hook — StatSet
names, profiler spans and Chrome events are one vocabulary, written from
one call site.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional

#: observability hook: fn(name, start_perf_counter, duration_seconds,
#: args), installed by observability.trace when tracing is enabled. Kept
#: as a plain module global so the no-tracer hot path is one None check.
_trace_sink: Optional[Callable[[str, float, float, dict], None]] = None


def set_trace_sink(fn: Optional[Callable[[str, float, float, dict], None]]):
    """Install (or clear, with None) the span sink timer_scope feeds."""
    global _trace_sink
    _trace_sink = fn


class Stat:
    __slots__ = ("name", "total", "count", "max", "min", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.total = 0.0
        self.count = 0
        self.max = 0.0
        self.min = float("inf")
        # per-stat lock: add() races with buffered-reader fill threads and
        # the exporter's scrape thread (the old unlocked += lost updates)
        self._lock = threading.Lock()

    def add(self, seconds: float):
        with self._lock:
            self.total += seconds
            self.count += 1
            self.max = max(self.max, seconds)
            self.min = min(self.min, seconds)

    def peek(self):
        """Consistent (total, count, max, min) read."""
        with self._lock:
            return self.total, self.count, self.max, self.min

    def __repr__(self):
        total, count, mx, mn = self.peek()
        avg = total / count if count else 0.0
        mn = 0.0 if count == 0 else mn
        return (f"Stat={self.name:<30} total={total * 1e3:10.2f}ms "
                f"avg={avg * 1e3:8.3f}ms max={mx * 1e3:8.3f}ms "
                f"min={mn * 1e3:8.3f}ms count={count}")


class StatSet:
    def __init__(self):
        self._stats: Dict[str, Stat] = {}
        self._lock = threading.Lock()

    def get(self, name: str) -> Stat:
        with self._lock:
            st = self._stats.get(name)
            if st is None:
                st = self._stats[name] = Stat(name)
            return st

    def print_all_status(self, log=print):
        """globalStat.printAllStatus() analog."""
        with self._lock:
            stats = dict(self._stats)
        for name in sorted(stats):
            log(repr(stats[name]))

    def reset(self):
        with self._lock:
            self._stats.clear()

    def to_dict(self):
        with self._lock:
            stats = dict(self._stats)
        out = {}
        for n, s in stats.items():
            total, count, mx, mn = s.peek()
            out[n] = {"total_s": total, "count": count, "max_s": mx,
                      "min_s": 0.0 if count == 0 else mn}
        return out


global_stat = StatSet()


class timer_scope:
    """REGISTER_TIMER_INFO analog, as a context manager: host wall-clock
    stat + a span in the profiler's own trace (+ a Chrome trace span when
    observability tracing is enabled). ``args`` become the span's stats
    in the profile and the Chrome event's ``args``; an arg named
    ``step_num`` makes the span a ``StepTraceAnnotation``, which is what
    the profiler groups device ops by. ``seconds`` holds the duration
    after exit (one clock read per edge: callers that feed a histogram
    read it instead of timing the interval again)."""

    __slots__ = ("name", "args", "seconds", "_t0", "_ann")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args
        self.seconds = 0.0

    def __enter__(self):
        # jax at first use, not at module import: utils.stat stays
        # importable (and cheap) in processes that never touch a device
        from jax import profiler
        cls = (profiler.StepTraceAnnotation if "step_num" in self.args
               else profiler.TraceAnnotation)
        self._ann = cls(self.name, **self.args)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.seconds = dur = time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        global_stat.get(self.name).add(dur)
        sink = _trace_sink
        if sink is not None:
            sink(self.name, self._t0, dur, self.args)
        return False


def register_timer(name: str):
    """Decorator form of timer_scope (REGISTER_TIMER analog)."""
    def deco(fn):
        def wrapped(*a, **kw):
            with timer_scope(name):
                return fn(*a, **kw)
        wrapped.__name__ = getattr(fn, "__name__", name)
        return wrapped
    return deco
