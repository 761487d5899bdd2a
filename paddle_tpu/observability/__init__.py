"""Observability subsystem: metrics registry, trace spans, exporters.

Four small host-side modules (docs/observability.md is the catalog):

- ``metrics``  — thread-safe typed registry (Counter/Gauge/Histogram with
  fixed log-spaced buckets, optional labels), consistent snapshots,
  delta-since-last-scrape, Prometheus text + JSON exposition,
- ``trace``    — the cheap host-only span log (Chrome trace-event JSON),
  fed by utils.stat.timer_scope — the same call that writes each span
  into the profiler's own trace (jax.profiler.TraceAnnotation), where it
  lies beside the device's ops,
- ``exporter`` — opt-in background HTTP server (/metrics, /healthz,
  /trace) + periodic file exporter for headless runs,
- ``profile``  — the reader of a ``jax.profiler`` trace: device time by
  the scopes the program writes, idle gaps by ``paddle:`` span
  (``python -m paddle_tpu.observability.profile <dir>``; standard library
  only, and not imported here: ``-m`` runs it as a fresh module).

Instrumentation is host-side only: enabling any of it changes no jaxpr
(pinned by tests/test_observability.py).
"""

from paddle_tpu.observability import exporter, metrics, trace  # noqa: F401
from paddle_tpu.observability.metrics import (DEFAULT_BUCKETS,  # noqa: F401
                                              MetricsRegistry, counter,
                                              default_registry, gauge,
                                              histogram, log_buckets)
from paddle_tpu.observability.trace import (global_tracer, span)  # noqa: F401
from paddle_tpu.observability.exporter import (FileExporter,  # noqa: F401
                                               MetricsHTTPServer, configure,
                                               shutdown, start_file_exporter,
                                               start_http_server)
