"""Observability subsystem (metrics registry, trace spans, exporter) —
the r9 tentpole's test surface.

Pinned here:

- registry correctness under concurrent writers (counters and histograms
  lose no updates across racing threads),
- Prometheus text exposition golden (exact bytes for a fixed registry),
- delta-since-last-scrape semantics,
- /healthz + /metrics + /metrics.json + /trace served over a REAL socket,
- trace spans land as valid Chrome trace-event JSON, and legacy
  ``timer_scope`` names are subsumed into the same trace buffer,
- utils/stat thread-safety (the satellite fix: Stat.add was unlocked) and
  the previously-dead ``min`` field surfacing in repr/to_dict,
- the train loop's phases (trainer.py ``_phase``): one call site lands
  each ``paddle:`` span in the profiler's own trace (read back with
  ``ProfileData``), the step histogram (``feed_convert`` / ``feed_h2d`` /
  ``compile`` count, no ``compute``) and the Chrome tracer, with its step,
- every ``pl.pallas_call`` of paddle_tpu/kernels carries a literal,
  package-unique ``name=``,
- END-TO-END: a short SGD.train run reports nonzero data-wait and
  drain splits,
- ACCEPTANCE: instrumentation changes NO jaxpr (train and decode steps
  bit-identical with the exporter/tracer on vs off), and one scrape after
  a real fault-injected training run returns Prometheus text carrying
  step-time, data-wait, checkpoint-latency, and retry-counter series.
"""

import glob
import json
import os
import re
import socketserver
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.observability import exporter as obs_exporter
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import trace as obs_trace


# --- registry -------------------------------------------------------------

def test_counter_concurrent_writers():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("c_total", "c")
    lc = reg.counter("lc_total", "lc", labels=("who",))

    def work(i):
        child = lc.labels(who=f"w{i % 2}")
        for _ in range(5000):
            c.inc()
            child.inc(2)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8 * 5000
    assert lc.labels(who="w0").value == 4 * 5000 * 2
    assert lc.labels(who="w1").value == 4 * 5000 * 2


def test_histogram_concurrent_observers():
    reg = obs_metrics.MetricsRegistry()
    h = reg.histogram("h_seconds", "h", buckets=(0.01, 0.1, 1.0))
    vals = (0.005, 0.05, 0.5, 5.0)

    def work():
        for _ in range(2000):
            for v in vals:
                h.observe(v)

    threads = [threading.Thread(target=work) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    n = 6 * 2000
    assert h.count == n * 4
    snap = reg.snapshot()["h_seconds"]["series"][()]
    # one observation per bucket per round, including the overflow slot
    assert snap["buckets"] == [n, n, n, n]
    assert snap["sum"] == pytest.approx(n * sum(vals))


def test_counter_rejects_negative_and_type_clash():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("x_total", "x")
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        reg.gauge("x_total", "now a gauge")
    # get-or-create: same type + labels returns the SAME family
    assert reg.counter("x_total") is c
    # histogram bucket layouts are part of the identity too
    h = reg.histogram("h_seconds", "h", buckets=(0.1, 1.0))
    assert reg.histogram("h_seconds", buckets=(0.1, 1.0)) is h
    assert reg.histogram("h_seconds") is h      # None = accept existing
    with pytest.raises(ValueError):
        reg.histogram("h_seconds", buckets=(0.5, 5.0))


def test_configure_tears_down_on_partial_failure(tmp_path):
    """configure() must not leak a half-started egress: a bound port
    after the tracer enabled tears the trace sink back down and saves
    what was collected."""
    from paddle_tpu.utils import stat as stat_mod

    blocker = obs_exporter.start_http_server(port=0)
    try:
        with pytest.raises(OSError):
            obs_exporter.configure(metrics_port=blocker.port,
                                   trace_dir=str(tmp_path / "t"))
    finally:
        blocker.stop()
    assert not obs_trace.global_tracer.enabled
    assert stat_mod._trace_sink is None


def test_prometheus_exposition_golden():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("req_total", "requests", labels=("cmd",)) \
       .labels(cmd="GET").inc(3)
    reg.gauge("depth", "queue depth").set(5)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.01, 0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(50.0)
    expected = (
        "# HELP depth queue depth\n"
        "# TYPE depth gauge\n"
        "depth 5\n"
        "# HELP lat_seconds latency\n"
        "# TYPE lat_seconds histogram\n"
        'lat_seconds_bucket{le="0.01"} 0\n'
        'lat_seconds_bucket{le="0.1"} 1\n'
        'lat_seconds_bucket{le="1"} 2\n'
        'lat_seconds_bucket{le="+Inf"} 3\n'
        "lat_seconds_sum 50.55\n"
        "lat_seconds_count 3\n"
        "# HELP req_total requests\n"
        "# TYPE req_total counter\n"
        'req_total{cmd="GET"} 3\n'
    )
    assert reg.to_prometheus() == expected


def test_delta_since_last_scrape():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("c_total", "c")
    g = reg.gauge("g", "g")
    h = reg.histogram("h_seconds", "h", buckets=(1.0,))
    c.inc(5)
    g.set(10)
    h.observe(0.5)
    first = reg.delta()          # opens the window: full values
    assert first["c_total"]["series"][()] == 5
    c.inc(2)
    g.set(7)
    h.observe(0.25)
    h.observe(2.0)
    d = reg.delta()
    assert d["c_total"]["series"][()] == 2          # counters: difference
    assert d["g"]["series"][()] == 7                # gauges: current value
    hs = d["h_seconds"]["series"][()]
    assert hs["count"] == 2 and hs["buckets"] == [1, 1]
    assert hs["sum"] == pytest.approx(2.25)


def test_consistent_snapshot_under_writers():
    """A snapshot taken mid-storm is internally consistent: the paired
    counters only ever move together under the registry lock, so every
    cut must see them equal."""
    reg = obs_metrics.MetricsRegistry()
    a = reg.counter("a_total", "a")
    stop = threading.Event()

    def work():
        while not stop.is_set():
            a.inc(3)

    t = threading.Thread(target=work)
    t.start()
    try:
        for _ in range(200):
            snap = reg.snapshot()["a_total"]["series"]
            v = snap.get((), 0)
            assert v % 3 == 0, "snapshot observed a torn increment"
    finally:
        stop.set()
        t.join()


# --- exporter over a real socket ------------------------------------------

def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.read().decode()


def test_http_exporter_endpoints():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("served_total", "serves").inc(4)
    tracer = obs_trace.Tracer()
    tracer.enable()
    with tracer.span("unit_span"):
        pass
    tracer.disable()
    srv = obs_exporter.start_http_server(port=0, registry=reg,
                                         tracer=tracer)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        text = _get(base + "/metrics")
        assert "# TYPE served_total counter" in text
        assert "served_total 4" in text
        hz = json.loads(_get(base + "/healthz"))
        assert hz["status"] == "ok" and hz["uptime_s"] >= 0
        js = json.loads(_get(base + "/metrics.json"))
        assert js["served_total"]["series"][""] == 4
        tr = json.loads(_get(base + "/trace"))
        assert any(e["name"] == "unit_span" for e in tr["traceEvents"])
        with pytest.raises(urllib.error.HTTPError):
            _get(base + "/nope")
    finally:
        srv.stop()


def test_file_exporter_writes_snapshots(tmp_path):
    reg = obs_metrics.MetricsRegistry()
    reg.counter("fe_total", "fe").inc(9)
    path = tmp_path / "metrics.jsonl"
    fe = obs_exporter.FileExporter(str(path), interval=0.05, registry=reg)
    fe.start()
    import time
    time.sleep(0.12)
    fe.stop()
    lines = [line for line in path.read_text().splitlines() if line]
    assert len(lines) >= 2                      # periodic + final flush
    rec = json.loads(lines[-1])
    assert rec["metrics"]["fe_total"]["series"][""] == 9
    # the dump tool reads the same file
    from tools.metrics_dump import load_file
    assert load_file(str(path))["fe_total"]["series"][""] == 9


def test_metrics_dump_quick_smoke():
    from tools.metrics_dump import main
    assert main(["--quick"]) == 0


# --- trace ----------------------------------------------------------------

def test_trace_spans_are_valid_chrome_events(tmp_path):
    tracer = obs_trace.Tracer()
    tracer.enable(str(tmp_path))
    with tracer.span("outer", step=1):
        with tracer.span("inner"):
            pass
    tracer.add_instant("marker", {"why": "test"})
    path = tracer.save()
    tracer.disable()
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    names = [e["name"] for e in events]
    assert "outer" in names and "inner" in names and "marker" in names
    for e in events:
        assert isinstance(e["ts"], (int, float))
        assert e["ph"] in ("X", "i")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["dur"] >= 0
    outer = next(e for e in events if e["name"] == "outer")
    assert outer["args"] == {"step": 1}
    # spans nest on the same timeline: inner lies within outer
    inner = next(e for e in events if e["name"] == "inner")
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0


def test_timer_scope_names_subsumed_into_trace():
    """Legacy timer_scope/register_timer sites land in the tracer buffer
    (one namespace) and still feed global_stat."""
    from paddle_tpu.utils.stat import (global_stat, register_timer,
                                       timer_scope)

    tracer = obs_trace.global_tracer
    tracer.clear()
    tracer.enable()
    try:
        with timer_scope("legacy_scope", shard=3) as scope:
            pass
        assert scope.seconds > 0

        @register_timer("legacy_deco")
        def f():
            return 7

        assert f() == 7
        with obs_trace.span("new_span"):
            pass
    finally:
        tracer.disable()
    events = tracer.to_chrome_trace()["traceEvents"]
    names = [e["name"] for e in events]
    assert {"legacy_scope", "legacy_deco", "new_span"} <= set(names)
    # the sink passes a scope's args on to the Chrome event
    legacy = next(e for e in events if e["name"] == "legacy_scope")
    assert legacy["args"] == {"shard": 3}
    d = global_stat.to_dict()
    assert d["legacy_scope"]["count"] >= 1
    assert d["new_span"]["count"] >= 1
    tracer.clear()


# --- utils/stat satellites ------------------------------------------------

def test_stat_add_thread_safe_and_min_surfaced():
    from paddle_tpu.utils.stat import Stat, StatSet

    st = Stat("x")

    def work():
        for _ in range(5000):
            st.add(0.001)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert st.count == 20000                    # unlocked += lost updates
    assert st.total == pytest.approx(20.0, rel=1e-6)
    # the min field: dead in the seed (tracked, never shown)
    st2 = Stat("y")
    st2.add(0.5)
    st2.add(0.002)
    assert "min=" in repr(st2)
    ss = StatSet()
    ss.get("y").add(0.25)
    d = ss.to_dict()
    assert d["y"]["min_s"] == pytest.approx(0.25)
    # concurrent iteration vs insertion must not blow up (bounded key
    # set — the point is the race, not the scale)
    stop = threading.Event()

    def insert():
        i = 0
        while not stop.is_set():
            ss.get(f"k{i % 64}").add(0.001)
            i += 1

    t = threading.Thread(target=insert)
    t.start()
    try:
        for _ in range(20):
            ss.to_dict()
            ss.print_all_status(log=lambda *_: None)
    finally:
        stop.set()
        t.join()


# --- end-to-end through the trainer ---------------------------------------

def _tiny_trainer():
    import paddle_tpu as paddle
    from paddle_tpu import activation, data_type, layer, optimizer

    img = layer.data(name="pixel", type=data_type.dense_vector(8))
    lab = layer.data(name="label", type=data_type.integer_value(3))
    out = layer.fc(input=img, size=3, act=activation.Softmax())
    cost = layer.classification_cost(input=out, label=lab)
    params = paddle.parameters_create(paddle.Topology(cost))
    trainer = paddle.SGD(cost=cost, parameters=params,
                         update_equation=optimizer.Adam(learning_rate=1e-2))
    return trainer


def _tiny_reader(n=48, batch=8):
    import paddle_tpu as paddle
    from paddle_tpu.dataset import synthetic

    return paddle.batch(synthetic.classification(8, 3, n), batch)


def test_sgd_train_reports_data_wait_and_drain_split():
    """Tier-1 e2e (satellite): a short SGD.train run produces NONZERO
    data-wait and drain phase observations in the step histogram."""
    from paddle_tpu.reader.decorator import buffered

    reg = obs_metrics.default_registry
    step_hist = reg.histogram("paddle_train_step_seconds",
                              labels=("phase",))
    before = {p: (step_hist.labels(phase=p).count,
                  step_hist.labels(phase=p).sum)
              for p in ("data_wait", "drain")}
    trainer = _tiny_trainer()
    trainer.train(buffered(_tiny_reader(), 4, name="e2e"), num_passes=2)
    for phase in ("data_wait", "drain"):
        hist = step_hist.labels(phase=phase)
        assert hist.count - before[phase][0] == 12, phase
        assert hist.sum - before[phase][1] > 0, phase
    items = reg.counter("paddle_reader_items_total",
                        labels=("reader",)).labels(reader="e2e")
    assert items.value == 12
    assert reg.gauge("paddle_train_examples_per_sec").value > 0


# --- the loop's phases: one call site, three sinks ------------------------

PHASES = ("data_wait", "feed", "feed_convert", "feed_h2d", "compile",
          "dispatch", "drain")


@pytest.fixture(scope="module")
def phase_run(tmp_path_factory):
    """Three steps of SGD.train at depth 2 on a fresh trainer, under the
    profiler AND with the Chrome tracer on: what each of the three sinks
    of ``_phase`` holds afterwards."""
    from jax.profiler import ProfileData

    hist = obs_metrics.default_registry.histogram(
        "paddle_train_step_seconds", labels=("phase",))
    before = {p: hist.labels(phase=p).count for p in PHASES}
    trainer = _tiny_trainer()
    first = trainer._batch_counter + 1
    tracer = obs_trace.global_tracer
    tracer.clear()
    tracer.enable()
    prof_dir = str(tmp_path_factory.mktemp("phase_profile"))
    jax.profiler.start_trace(prof_dir)
    try:
        trainer.train(_tiny_reader(24, 8), num_passes=1, pipeline_depth=2)
    finally:
        jax.profiler.stop_trace()
        tracer.disable()
    chrome = tracer.to_chrome_trace()["traceEvents"]
    tracer.clear()
    (path,) = glob.glob(os.path.join(prof_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans = [(e.name[len("paddle:"):], e.start_ns,
                      e.start_ns + e.duration_ns, dict(e.stats))
                     for e in line.events if e.name.startswith("paddle:")]
            if spans:
                lines.append(spans)
    counts = {p: hist.labels(phase=p).count - before[p] for p in before}
    return {"lines": lines, "chrome": chrome, "counts": counts,
            "steps": [first, first + 1, first + 2]}


def test_phases_land_in_the_profilers_own_trace(phase_run):
    """The seven ``paddle:`` spans sit on ONE host line of the profile
    (so on the clock the device's ops are on), each with its batch's
    step; the feed's children lie inside it; a batch's drain carries the
    step of its dispatch; the one new shape compiled exactly once."""
    (spans,) = phase_run["lines"]
    steps = phase_run["steps"]
    by = {}
    for name, t0, t1, stats in spans:
        by.setdefault(name, {}).setdefault(stats["step"], []).append(
            (t0, t1, stats))
    for name in ("data_wait", "feed", "feed_convert", "feed_h2d", "drain"):
        for step in steps:
            assert len(by[name][step]) == 1, (name, step)
    # first step of a fresh trainer compiles; the others dispatch, as
    # step annotations (`step_num`) so the profile groups by them
    assert list(by["compile"]) == [steps[0]]
    ((_, _, stats),) = by["compile"][steps[0]]
    assert stats["key"] == str((("label", (8, 1), False),
                                ("pixel", (8, 8), False)))
    assert sorted(by["dispatch"]) == steps[1:]
    for step in steps[1:]:
        ((_, _, stats),) = by["dispatch"][step]
        assert stats["step_num"] == step
    for step in steps:
        ((f0, f1, _),) = by["feed"][step]
        for child in ("feed_convert", "feed_h2d"):
            ((c0, c1, _),) = by[child][step]
            assert f0 <= c0 <= c1 <= f1, (child, step)
        ((conv0, conv1, _),) = by["feed_convert"][step]
        ((h0, _, _),) = by["feed_h2d"][step]
        assert conv1 <= h0
        # the drain of a batch comes after its dispatch / compile
        run = by["compile" if step == steps[0] else "dispatch"][step]
        ((_, r1, _),) = run
        ((d0, _, _),) = by["drain"][step]
        assert r1 <= d0, step


def test_phase_histogram_has_the_split_and_the_compile_count(phase_run):
    counts = phase_run["counts"]
    assert counts["compile"] == 1 and counts["dispatch"] == 2
    for p in ("data_wait", "feed", "feed_convert", "feed_h2d", "drain"):
        assert counts[p] == 3, p
    text = obs_metrics.default_registry.to_prometheus()
    assert 'paddle_train_step_seconds_count{phase="compile"}' in text
    assert 'phase="compute"' not in text


def test_phases_land_in_the_chrome_tracer_with_their_step(phase_run):
    got = {}
    for e in phase_run["chrome"]:
        if e["name"].startswith("paddle:"):
            got.setdefault(e["name"][len("paddle:"):], []).append(
                e["args"]["step"])
    steps = phase_run["steps"]
    for p in ("feed", "feed_convert", "feed_h2d", "drain"):
        assert got[p] == steps, p
    # one more `next` on the reader than batches: the one that ended it
    assert got["data_wait"][:3] == steps
    assert got["compile"] == steps[:1] and got["dispatch"] == steps[1:]


def test_every_pallas_call_has_a_literal_unique_name():
    """The device trace names a Mosaic call after its ``name=`` (wrapped
    by JAX's transform stack): without one the kernel layer's metrics
    cannot find their kernels. Literal, so a reader can grep for it."""
    import ast

    kdir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "paddle_tpu", "kernels")
    names = []
    for fn in sorted(os.listdir(kdir)):
        if not fn.endswith(".py"):
            continue
        with open(os.path.join(kdir, fn)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "pallas_call":
                kw = {k.arg: k.value for k in node.keywords}
                assert isinstance(kw.get("name"), ast.Constant) \
                    and isinstance(kw["name"].value, str), \
                    f"{fn}:{node.lineno}: pallas_call without a literal name="
                names.append(kw["name"].value)
    assert len(names) >= 12
    assert len(set(names)) == len(names), sorted(names)
    assert {"fused_gru_fwd", "fused_gru_bwd"} <= set(names)


def test_every_metric_family_the_doc_names_is_registered():
    """docs/observability.md is the catalog: every `paddle_*` family it names
    is registered by the module that holds its name (imported here), or, for
    `paddle_serving_*`, written by the C++ daemon. A family whose only
    feeder left the tree has to leave the document with it."""
    import importlib
    import re

    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    with open(os.path.join(root, "docs", "observability.md")) as f:
        named = set(re.findall(r"`(paddle_[a-z0-9_]*[a-z0-9])\b", f.read()))
    named -= {"paddle_tpu", "paddle_tpu_serving"}
    assert len(named) > 90
    with open(os.path.join(root, "paddle_tpu", "native",
                           "serving_daemon.cc")) as f:
        daemon = f.read()
    sources = {}
    pkg = os.path.join(root, "paddle_tpu")
    for d, _dirs, files in os.walk(pkg):
        for fn in files:
            if fn.endswith(".py"):
                mod = os.path.relpath(os.path.join(d, fn), root)[:-3]
                with open(os.path.join(d, fn)) as f:
                    sources[mod.replace(os.sep, ".")] = f.read()
    served = {n for n in named if n.startswith("paddle_serving_")}
    for mod, src in sources.items():
        if any('"%s"' % name in src for name in named - served):
            importlib.import_module(mod[:-9] if mod.endswith(".__init__")
                                    else mod)
    registered = obs_metrics.default_registry.snapshot()
    missing = sorted(n for n in named - served if n not in registered) \
        + sorted(n for n in served if '"%s' % n not in daemon)
    assert not missing, missing


def test_moe_routing_counters_are_declared():
    """The moe_ffn layers' routing counts (operator-only: no benchmark
    metric reads them yet): pairs routed to experts held here / elsewhere,
    the held experts' load skew, and the drop counter that stays 0.
    tests/test_qwen3_next.py drives them through SGD.train."""
    import paddle_tpu.layers.moe  # noqa: F401  (declares them)

    snap = obs_metrics.default_registry.snapshot()
    assert snap["paddle_moe_tokens_total"]["type"] == "counter"
    assert snap["paddle_moe_expert_load_max_over_mean"]["type"] == "gauge"
    assert snap["paddle_moe_dropped_total"]["type"] == "counter"
    assert sum(snap["paddle_moe_dropped_total"]["series"].values()) == 0


# --- acceptance: jaxpr bit-identity + fault-injected scrape ---------------

def _train_step_jaxpr():
    """Jaxpr text of the tiny model's UNJITTED train-step body — the
    exact program make_train_step compiles."""
    from paddle_tpu import activation, data_type, layer, optimizer
    from paddle_tpu.core.arg import Arg
    from paddle_tpu.core.layer import layer_name_scope
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.trainer.trainer import make_train_step

    with layer_name_scope():
        img = layer.data(name="pixel", type=data_type.dense_vector(8))
        lab = layer.data(name="label", type=data_type.integer_value(3))
        out = layer.fc(input=img, size=3, act=activation.Softmax())
        cost = layer.classification_cost(input=out, label=lab)
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = optimizer.Adam(learning_rate=1e-2)
    opt_state = opt.init(params)
    loss = topo.loss_fn(cost)
    step = make_train_step(loss, opt, topo.static_map(), jit_compile=False)
    feeds = {"pixel": Arg(jnp.zeros((4, 8), jnp.float32)),
             "label": Arg(jnp.zeros((4, 1), jnp.int32))}
    jaxpr = jax.make_jaxpr(step)(params, opt_state,
                                 jax.random.PRNGKey(1), feeds)
    return str(jaxpr)


def _decode_jaxpr():
    """Jaxpr text of a tiny compact-K beam decode forward."""
    from paddle_tpu import data_type, layer, networks
    from paddle_tpu.core.arg import Arg
    from paddle_tpu.core.layer import layer_name_scope
    from paddle_tpu.core.topology import Topology

    with layer_name_scope():
        src = layer.data(name="src",
                         type=data_type.integer_value_sequence(16))
        gen = networks.gru_encoder_decoder(
            src_word_id=src, src_dict_dim=16, trg_dict_dim=16,
            word_vector_dim=8, encoder_size=8, decoder_size=8,
            is_generating=True, beam_size=2, max_length=4, name="obsg")
    topo = Topology(gen)
    params = topo.init_params(jax.random.PRNGKey(0))
    feeds = {"src": Arg(jnp.asarray([[3, 5, 2, 9]], jnp.int32),
                        jnp.ones((1, 4)))}
    jaxpr = jax.make_jaxpr(
        lambda p, f: topo.forward(p, f, return_ctx=True)[1]
        .extras[f"{gen.name}:ids"])(params, feeds)
    return str(jaxpr)


def test_instrumentation_changes_no_jaxpr():
    """THE no-overhead acceptance pin: with the exporter OFF the
    instrumented paths compile the same programs as with everything ON —
    train and decode jaxprs are bit-identical either way (all telemetry
    is host-side, timing AROUND jitted calls)."""
    train_off = _train_step_jaxpr()
    decode_off = _decode_jaxpr()
    srv = obs_exporter.start_http_server(port=0)
    tracer = obs_trace.global_tracer
    tracer.enable()
    try:
        # churn the registry while instrumented: a metrics-on environment
        obs_metrics.counter("jaxpr_pin_probe_total").inc()
        train_on = _train_step_jaxpr()
        decode_on = _decode_jaxpr()
    finally:
        tracer.disable()
        tracer.clear()
        srv.stop()
    assert train_on == train_off
    assert decode_on == decode_off


def test_retry_counter_counts_only_actual_retries():
    """An exhausted run of N attempts performed N-1 retries — the final
    failed attempt is not a retry (review finding: off-by-one skewed the
    retry-rate vs exhausted-rate relationship)."""
    from paddle_tpu.utils.retry import RetryError, RetryPolicy

    reg = obs_metrics.default_registry
    retries = reg.counter("paddle_retry_attempts_total",
                          labels=("policy",)).labels(policy="obs_test")
    exhausted = reg.counter("paddle_retry_exhausted_total",
                            labels=("policy",)).labels(policy="obs_test")

    def boom():
        raise ConnectionError("nope")

    policy = RetryPolicy(name="obs_test", max_attempts=3, base_delay=0.0,
                         deadline=None, sleep=lambda s: None)
    with pytest.raises(RetryError):
        policy.run(boom)
    assert retries.value == 2                   # 3 attempts, 2 retries
    assert exhausted.value == 1
    # single-attempt policy: zero retries
    policy1 = RetryPolicy(name="obs_test", max_attempts=1, base_delay=0.0,
                          deadline=None, sleep=lambda s: None)
    with pytest.raises(RetryError):
        policy1.run(boom)
    assert retries.value == 2
    assert exhausted.value == 2


def test_heartbeat_age_gauge_retired_on_stop(tmp_path):
    """stop_heartbeat removes the callback age gauge — a released lease
    must not keep reporting a climbing age (review finding)."""
    from paddle_tpu.distributed.discovery import DiscoveryRegistry

    reg = DiscoveryRegistry(str(tmp_path / "d"), ttl=5.0)
    reg.heartbeat("obs/test", "v")
    fam = obs_metrics.default_registry.gauge(
        "paddle_discovery_heartbeat_age_seconds", labels=("key",))
    snap = obs_metrics.default_registry.snapshot()
    assert (("key", "obs/test"),) in snap[
        "paddle_discovery_heartbeat_age_seconds"]["series"]
    assert fam.labels(key="obs/test").value < 5.0
    reg.stop_heartbeat("obs/test")
    snap = obs_metrics.default_registry.snapshot()
    assert (("key", "obs/test"),) not in snap[
        "paddle_discovery_heartbeat_age_seconds"]["series"]


def test_checkpoint_load_failure_counted(tmp_path):
    """A load that fails AFTER validation records op=load ok=false
    (review finding: the failure series could never be emitted)."""
    import os

    from paddle_tpu.io import checkpoint as ckpt

    reg = obs_metrics.default_registry
    load_fail = reg.counter("paddle_checkpoint_ops_total",
                            labels=("op", "ok")).labels(op="load",
                                                        ok="false")
    before = load_fail.value
    path = str(tmp_path / "bad")
    os.makedirs(path)
    with open(os.path.join(path, "params.tar"), "wb") as f:
        f.write(b"not a tar at all")
    with open(os.path.join(path, "meta.json"), "w") as f:
        f.write('{"format_version": 1}')
    with pytest.raises(ckpt.CheckpointError):
        ckpt.load_checkpoint(path)
    assert load_fail.value == before + 1


def test_master_connect_failure_counted():
    """An unreachable master counts into paddle_master_cmd_errors_total
    (review finding: connect-phase failures were outside the counter)."""
    import socket

    from paddle_tpu.distributed.master_client import MasterClient

    reg = obs_metrics.default_registry
    errs = reg.counter("paddle_master_cmd_errors_total",
                       labels=("cmd",)).labels(cmd="PING")
    before = errs.value
    # grab a port, close it: connection refused
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    client = MasterClient("127.0.0.1", port, timeout=2.0)
    with pytest.raises((ConnectionError, OSError)):
        client.ping()
    assert errs.value == before + 1


def test_cli_flags_trace_and_file_exporter(tmp_path, monkeypatch):
    """`paddle train --metrics_port 0 --trace_dir D --metrics_interval s`
    end-to-end through the real CLI: the run leaves a Perfetto-loadable
    trace and a metrics.jsonl whose last line carries the run's step
    series."""
    import os

    from paddle_tpu.cli import main as cli_main

    fixdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "fixtures", "demo_mnist")
    trace_dir = str(tmp_path / "obs")
    monkeypatch.chdir(fixdir)
    rc = cli_main(["train", "--config", "mini_mnist_conf.py",
                   "--num_passes", "1", "--metrics_port", "0",
                   "--trace_dir", trace_dir,
                   "--metrics_interval", "0.05"])
    assert rc == 0
    trace_path = os.path.join(trace_dir, f"trace-{os.getpid()}.json")
    with open(trace_path) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"paddle:data_wait", "paddle:feed", "paddle:feed_convert",
            "paddle:compile", "paddle:dispatch", "paddle:drain"} <= names
    from tools.metrics_dump import load_file
    snap = load_file(os.path.join(trace_dir, "metrics.jsonl"))
    series = snap["paddle_train_step_seconds"]["series"]
    assert series["phase=data_wait"]["count"] > 0
    assert series["phase=dispatch"]["count"] > 0
    assert series["phase=drain"]["count"] > 0
    assert "phase=compute" not in series


class _StubMasterHandler(socketserver.StreamRequestHandler):
    def handle(self):
        while True:
            line = self.rfile.readline()
            if not line:
                return
            if line.strip() == b"PING":
                self.wfile.write(b"PONG\n")
            else:
                self.wfile.write(b"ERR unknown\n")


def test_acceptance_fault_injected_run_scrape(tmp_path):
    """THE acceptance scrape: exporter on, fault injection enabled, one
    real short training run with step snapshots and a (stub) master
    behind the retrying elastic client — a single /metrics scrape then
    carries step-time, data-wait, checkpoint-latency, and retry-counter
    series."""
    from paddle_tpu.distributed import faults
    from paddle_tpu.distributed.discovery import DiscoveryRegistry
    from paddle_tpu.distributed.master_client import ElasticMasterClient
    from paddle_tpu.reader.decorator import checkpointable
    from paddle_tpu.utils.retry import RetryPolicy

    master = socketserver.ThreadingTCPServer(("127.0.0.1", 0),
                                             _StubMasterHandler)
    master.daemon_threads = True
    threading.Thread(target=master.serve_forever, daemon=True).start()
    registry = DiscoveryRegistry(str(tmp_path / "disc"), ttl=30.0)
    registry.put("master/addr",
                 f"127.0.0.1:{master.server_address[1]}")

    plan = faults.FaultPlan([
        # a data stall mid-epoch…
        faults.FaultSpec("reader.next", "delay", at=2, count=1,
                         seconds=0.002),
        # …and a dropped master command, forcing a real retry
        faults.FaultSpec("master.send", "drop", at=1, count=1),
    ])
    srv = obs_exporter.start_http_server(port=0)
    try:
        with plan.installed():
            trainer = _tiny_trainer()
            trainer.train(checkpointable(_tiny_reader(), seed=1),
                          num_passes=1, save_every_n_batches=2,
                          snapshot_dir=str(tmp_path / "snap"))
            client = ElasticMasterClient(
                registry, policy=RetryPolicy(
                    name="master", max_attempts=4, base_delay=0.0,
                    deadline=None, sleep=lambda s: None))
            assert client.ping()
            client.close()
        assert ("reader.next", 2, "delay") in plan.fired()
        assert ("master.send", 1, "drop") in plan.fired()
        text = _get(f"http://127.0.0.1:{srv.port}/metrics")
    finally:
        srv.stop()
        master.shutdown()
        master.server_close()
        registry.stop_all()

    # step-time + the data-wait/compute split
    assert "# TYPE paddle_train_step_seconds histogram" in text
    for phase in ("data_wait", "dispatch", "drain"):
        m = re.search(
            rf'paddle_train_step_seconds_count\{{phase="{phase}"\}} (\d+)',
            text)
        assert m and int(m.group(1)) > 0, phase
    # checkpoint latency from the snapshot writes of THIS run
    m = re.search(r'paddle_checkpoint_seconds_count\{op="save"\} (\d+)',
                  text)
    assert m and int(m.group(1)) > 0
    assert re.search(
        r'paddle_checkpoint_ops_total\{op="save",ok="true"\} [1-9]', text)
    # the injected master drop went through the unified retry policy
    m = re.search(r'paddle_retry_attempts_total\{policy="master"\} (\d+)',
                  text)
    assert m and int(m.group(1)) > 0
    assert re.search(r'paddle_master_cmd_errors_total\{cmd="PING"\} [1-9]',
                     text)
