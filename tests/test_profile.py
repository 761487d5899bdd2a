"""paddle_tpu/observability/profile.py: the wire reader against a trace
recorded on the chip (tests/data/scoped_step.xplane.pb, by
tests/data/record_scoped_step.py) and against what other readers make of the
same files, scope normalisation on the strings this JAX writes, self time
and gap attribution on hand-made events, and the scope sites themselves: a
layer graph's compiled step names every layer, and the scopes change nothing
but metadata."""

import contextlib
import hashlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import profile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SCOPED = os.path.join(HERE, "data", "scoped_step.xplane.pb")
SMALL = os.path.join(ROOT, "benchmark", "tests", "small_trace.xplane.pb")


# ---- the wire reader ----------------------------------------------------------

@pytest.fixture(scope="module")
def scoped():
    trace = profile.load(SCOPED)
    return trace, profile.reduce(trace)


def test_stdlib_only_at_import():
    """Nothing outside the standard library at module import: checked in a
    fresh interpreter that loads the file alone."""
    code = ("import importlib.util, sys; before = set(sys.modules); "
            "spec = importlib.util.spec_from_file_location('p', sys.argv[1]); "
            "m = importlib.util.module_from_spec(spec); "
            "spec.loader.exec_module(m); "
            "new = {n.split('.')[0] for n in set(sys.modules) - before}; "
            "print(sorted(new - set(sys.stdlib_module_names) - {'p'}))")
    out = subprocess.run([sys.executable, "-c", code, profile.__file__],
                         capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "[]"


def test_wire_reader_equals_xplane_pb2_on_the_chip_trace(scoped):
    """Events, durations and tf_op of device 0's `XLA Ops` line, and the
    `paddle:` spans, as tensorflow's generated reader gives them."""
    trace, _ = scoped
    dev = trace["devices"][0]
    assert len(dev["ops"]) > 100 and trace["spans"]
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception as e:                       # the cross-check only
        pytest.skip(f"xplane_pb2 does not import here: {e!r}")
    space = xplane_pb2.XSpace()
    with open(SCOPED, "rb") as f:
        space.ParseFromString(f.read())
    plane, = [p for p in space.planes if p.name == "/device:TPU:0"]
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    line, = [ln for ln in plane.lines if ln.name == "XLA Ops"]
    theirs = []
    for ev in line.events:
        meta = plane.event_metadata[ev.metadata_id]
        tf_op = [s.str_value for s in meta.stats
                 if stat_names[s.metadata_id] == "tf_op"]
        theirs.append((line.timestamp_ns * 1000 + ev.offset_ps,
                       ev.duration_ps, meta.name, tf_op[0] if tf_op else None))
    mine = [(s, d, dev["meta"][m]["name"], dev["meta"][m].get("tf_op"))
            for s, d, m in dev["ops"]]
    assert mine == theirs
    spans = []
    for p in space.planes:
        names = {k: v.name for k, v in p.stat_metadata.items()}
        for ln in p.lines:
            for ev in ln.events:
                name = p.event_metadata[ev.metadata_id].name
                if name.startswith("paddle:"):
                    step = [s.int64_value or s.uint64_value for s in ev.stats
                            if names[s.metadata_id] in ("step", "step_num")]
                    spans.append((name, ln.timestamp_ns * 1000 + ev.offset_ps,
                                  ev.duration_ps, step[0] if step else None))
    assert sorted(spans, key=lambda s: s[1]) == trace["spans"]


def test_busy_window_idle_equal_trace_reduce_to_the_digit():
    from benchmark import trace_reduce

    for path in (SMALL, SCOPED):
        theirs = trace_reduce.reduce(trace_reduce.load(path))
        mine = profile.reduce(profile.load(path))
        for key in ("busy_s", "window_s", "idle_share"):
            assert mine[key] == theirs[key], (path, key)
        assert mine["devices"] == theirs["devices"]
        assert sum(g["seconds"] for g in mine["gaps"]["by_span"]) == \
            pytest.approx(sum(v for _, v in theirs["idle_gaps"]), rel=1e-9)


def test_unscoped_trace_warns():
    """The benchmark's small trace is a bare jitted product: no scope, and
    the reader says so."""
    red = profile.reduce(profile.load(SMALL))
    assert red["unscoped_share"] == 1.0 and red["warnings"]
    assert [s["scope"] for s in red["scopes"]] == [profile.NONE]
    assert red["none_by_category"][0]["hlo_category"] == "convolution fusion"
    assert red["scopes"][0]["ops"][0]["flops"] == 17196646400


def test_chip_trace_by_scope(scoped):
    """The two layers, forward and backward, the optimizer and the casts are
    rows; self times sum to busy; the row loop's kernels are named."""
    _, red = scoped
    assert not red["warnings"]
    rows = {(s["scope"], s["direction"]): s for s in red["scopes"]}
    for key in (("attn", "fwd"), ("attn", "bwd"), ("out", "fwd"),
                ("out", "bwd"), ("cost", "bwd"), ("optimizer", "fwd"),
                ("precision_cast", "fwd")):
        assert key in rows, key
    # a launch's own scope (`attn/flash_attn_fwd`) is folded into the layer's
    assert not [k for k in rows if "flash_attn" in k[0]]
    assert "flash_attn_fwd" in rows[("attn", "fwd")]["kernels"]
    assert "flash_attn_bwd" in rows[("attn", "bwd")]["kernels"]
    for row in rows.values():
        assert sum(row["kernels"].values()) <= row["self_s"] + 1e-12
    assert red["self_s"] == pytest.approx(red["busy_s"], rel=5e-3)
    assert sum(s["self_s"] for s in red["scopes"]) == \
        pytest.approx(red["self_s"], rel=1e-9)
    step, = [m for m in red["modules"] if m["module"].startswith("jit_step")]
    assert step["runs"] == 5


def test_chip_trace_gaps_lie_in_the_loops_spans(scoped):
    trace, red = scoped
    names = {s[0] for s in trace["spans"]}
    assert {"paddle:feed", "paddle:feed_convert", "paddle:dispatch",
            "paddle:drain"} <= names
    assert all(s[3] is not None for s in trace["spans"])
    gaps = red["gaps"]
    assert gaps["count"] >= 1
    assert {g["span"] for g in gaps["by_span"]} <= names | {"unattributed"}
    assert gaps["total_s"] <= red["window_s"] - red["busy_s"] + 1e-9


def test_cli_prints_the_table_and_json():
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observability.profile", SCOPED],
        capture_output=True, text=True, check=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu")).stdout
    assert "self time by scope" in out and "idle gaps of device 0" in out
    assert re.search(r"^\s+attn\s+bwd\s", out, re.M)
    as_json = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observability.profile", SCOPED,
         "--json"], capture_output=True, text=True, check=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu")).stdout
    red = json.loads(as_json)
    assert red["file"] == SCOPED and red["scopes"]


def test_spans_of_a_cpu_profile_carry_their_step(tmp_path):
    """The host half alone: a CPU profile has no device plane, the loop's
    spans are read with their step as `jax.profiler.ProfileData` reads
    them."""
    trainer, reader, feeding = _tiny_trainer()
    trainer.train(reader, num_passes=1, feeding=feeding)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    trainer.train(reader, num_passes=1, feeding=feeding)
    jax.profiler.stop_trace()
    path = profile.find_xplane(str(tmp_path))
    trace = profile.load(path)
    assert trace["devices"] == {} and profile.reduce(trace) is None
    theirs = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("paddle:"):
                    stats = dict(e.stats)
                    theirs.append((e.name, stats.get("step")))
    assert sorted(theirs) == sorted((s[0], s[3]) for s in trace["spans"])
    assert {n for n, _ in theirs} >= {"paddle:feed", "paddle:dispatch",
                                      "paddle:drain"}


# ---- scope normalisation --------------------------------------------------------

SCOPE_CASES = [
    # as jax 0.9.0 writes them (a checkpointed layer mapped over rows)
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/layer_a/"
     "dot_general", ("layer_a", "bwd")),
    ("jit(step)/transpose(jvp(outer))/while/body/closed_call/layer_a/layer_a/"
     "checkpoint/rematted_computation/dot_general", ("outer/layer_a", "bwd")),
    ("jit(step)/jvp(outer)/while", ("outer", "fwd")),
    ("jit(step)/jvp(outer)/while/cond/lt", ("outer", "fwd")),
    ("jit(step)/transpose(jvp(outer))/while/body/closed_call/layer_a/"
     "layer_a/remat2", ("outer/layer_a", "bwd")),
    ("jit(step)/jvp(outer)/while/body/closed_call/layer_a/jit(inner)/sin",
     ("outer/layer_a", "fwd")),
    # a scope of several parts, written twice under a checkpoint's transpose
    ("jit(step)/transpose(jvp(qwen3next/l3/moe))/qwen3next/l3/moe/checkpoint/"
     "rematted_computation/moe_grouped_ffn_bwd/while/body/dot_general",
     ("qwen3next/l3/moe/moe_grouped_ffn_bwd", "bwd")),
    ("jit(step)/jvp(q/l0/mixer)/while/body/closed_call/gdn_tinv/while/body/"
     "mul", ("q/l0/mixer/gdn_tinv", "fwd")),
    ("jit(step)/jvp(a)/cond/branch_1_fun/b/add", ("a/b", "fwd")),
    ("jit(step)/vmap(jvp(a))/b/c/d/e/f/add", ("a/b/c/d/e", "fwd")),
    ("jit(step)/optimizer/mul", ("optimizer", "fwd")),
    ("jit(step)/jvp(a)/broadcast_in_dim;jit(step)/jvp(b)/mul", ("a", "fwd")),
    # XLA merged the like ops of two layers and joined their names
    ("jit(step)/jvp(k/l4/moe)/jit(searchsorted)/jit(step)/jvp(k/l3/moe)/"
     "jit(searchsorted)/vmap()/closed_call/while/body/closed_call/gather:",
     ("k/l4/moe", "fwd")),
    # the chip's tf_op is `<name>:<type>`
    ("jit(<lambda>)/dot_general:", (profile.NONE, "fwd")),
    ("jit(step)/jvp(fc:1)/dot_general:", ("fc:1", "fwd")),
    ("reduce_sum", (profile.NONE, "fwd")),
    ("", (profile.NONE, "fwd")),
    (None, (profile.NONE, "fwd")),
]


@pytest.mark.parametrize("op_name,want", SCOPE_CASES,
                         ids=[str(i) for i in range(len(SCOPE_CASES))])
def test_scope_of(op_name, want):
    assert profile.scope_of(op_name) == want


def _op_names(compiled):
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def test_scopes_of_a_compiled_step_on_real_strings():
    """Forward and backward ops of a layer under `jax.checkpoint` inside
    `lax.map` land in `<layer>` fwd / bwd, the update in `optimizer`, an
    unscoped op in `(none)`: on the strings this JAX writes."""
    def layer(x, w):
        def row(xr):
            return jnp.tanh(xr @ w)
        with jax.named_scope("layer_a"):
            return jax.lax.map(jax.checkpoint(row), x)

    def step(w, x):
        def loss(w):
            return (layer(x, w) ** 2).sum()
        value, grad = jax.value_and_grad(loss)(w)
        with jax.named_scope("optimizer"):
            w = w - 0.1 * grad
        return w, jnp.cos(value)

    compiled = jax.jit(step).lower(jnp.ones((8, 8)),
                                   jnp.ones((4, 3, 8))).compile()
    got = {}
    for name in _op_names(compiled):
        got.setdefault(profile.scope_of(name), set()).add(
            name.rsplit("/", 1)[-1])
    assert "dot_general" in got[("layer_a", "fwd")]
    assert "tanh" in got[("layer_a", "fwd")]
    assert "dot_general" in got[("layer_a", "bwd")]
    assert {"mul", "sub"} <= got[("optimizer", "fwd")]
    assert "cos" in got[(profile.NONE, "fwd")]
    assert set(got) <= {("layer_a", "fwd"), ("layer_a", "bwd"),
                        ("optimizer", "fwd"), (profile.NONE, "fwd"),
                        (profile.NONE, "bwd")}


# ---- self time and gaps on hand-made events -------------------------------------

def test_self_time_of_nested_events():
    """A loop [0, 100) holding a kernel [10, 40) and an op [40, 70) that
    itself holds [50, 60), then two siblings."""
    ops = [(0, 100, "loop"), (10, 30, "kernel"), (40, 30, "inner_loop"),
           (50, 10, "leaf"), (100, 20, "sibling"), (130, 5, "late")]
    got = dict((k, s) for s, k in profile.self_times(ops))
    assert got == {"loop": 40, "kernel": 30, "inner_loop": 20, "leaf": 10,
                   "sibling": 20, "late": 5}
    assert sum(got.values()) == 125          # the union of the intervals


def test_self_time_cuts_a_child_that_outlasts_its_parent():
    got = dict((k, s) for s, k in profile.self_times(
        [(0, 10, "parent"), (8, 5, "child")]))
    assert got == {"parent": 8, "child": 5}


def _reduced(ops, spans, meta=None):
    meta = meta or {}
    trace = {"devices": {0: {
        "ops": [(s * 1000, d * 1000, m) for s, d, m in ops], "modules": [],
        "meta": {m: dict({"name": f"%op.{m} = f32[] add()", "tf_op":
                          f"jit(step)/jvp(l{m})/add"}, **meta.get(m, {}))
                 for _, _, m in ops}}},
        "spans": [(n, s * 1000, d * 1000, step) for n, s, d, step in spans]}
    return profile.reduce(trace)


def test_gap_goes_to_the_innermost_span_that_covers_most_of_it():
    # ops at [0, 10us) and [200us, 210us): one gap of 190 us
    ops = [(0, 10_000, 1), (200_000, 10_000, 2)]
    feed = ("paddle:feed", 5_000, 190_000, 7)
    convert = ("paddle:feed_convert", 20_000, 150_000, 7)
    h2d = ("paddle:feed_h2d", 171_000, 20_000, 7)
    red = _reduced(ops, [feed, convert, h2d])
    (gap,) = red["gaps"]["longest"]
    assert (gap["span"], gap["step"]) == ("paddle:feed_convert", 7)
    assert gap["seconds"] == pytest.approx(190e-6)
    # neither child covers more than half: the parent's own time
    convert = ("paddle:feed_convert", 20_000, 80_000, 7)
    h2d = ("paddle:feed_h2d", 101_000, 80_000, 7)
    red = _reduced(ops, [feed, convert, h2d])
    assert red["gaps"]["longest"][0]["span"] == "paddle:feed"
    # no span touches it
    red = _reduced(ops, [("paddle:drain", 300_000, 10_000, 8)])
    assert red["gaps"]["by_span"] == [
        {"span": "unattributed", "seconds": pytest.approx(190e-6), "gaps": 1}]
    # a gap under the threshold is not a gap
    red = _reduced([(0, 10_000, 1), (40_000, 10_000, 2)], [feed])
    assert red["gaps"]["count"] == 0


def test_span_arguments_in_the_name():
    """A TraceMe nobody decoded carries its arguments in its name."""
    assert profile._span("paddle:feed#step=12,key=x#", 5, 7, {}) == \
        ("paddle:feed", 5, 7, 12)
    assert profile._span("paddle:dispatch", 5, 7, {"step_num": 3}) == \
        ("paddle:dispatch", 5, 7, 3)
    assert profile._span("paddle:drain", 5, 7, {}) == \
        ("paddle:drain", 5, 7, None)


def test_kernel_names():
    def meta(text):
        return {"name": text}
    assert profile.kernel_of(meta(
        "%transpose_jvp_flash_attn_bwd__.7 = (bf16[1]{0}) custom-call(x)")) \
        == "flash_attn_bwd"
    assert profile.kernel_of(meta(
        "%jvp_fused_gru_fwd_.3 = f32[8]{0} custom-call(f32[8]{0} %p)")) \
        == "fused_gru_fwd"
    assert profile.kernel_of(meta(
        "%moe_grouped_fwd.12 = f32[8]{0} custom-call(f32[8]{0} %p)")) \
        == "moe_grouped_fwd"
    assert profile.kernel_of(meta(
        "%custom-call.4 = f32[8]{0} custom-call(f32[8]{0} %p)")) is None
    assert profile.kernel_of(meta("%fusion.3 = f32[8]{0} fusion(%p)")) is None


# ---- the scope sites ------------------------------------------------------------

def _tiny_model():
    x = paddle.layer.data(name="x", type=paddle.data_type.dense_vector(16))
    y = paddle.layer.data(name="y", type=paddle.data_type.integer_value(4))
    hid = paddle.layer.fc(input=x, size=32, act=paddle.activation.Relu(),
                          name="hid")
    bn = paddle.layer.batch_norm(input=hid, name="bn")
    out = paddle.layer.fc(input=bn, size=4,
                          act=paddle.activation.Softmax(), name="out")
    return paddle.layer.classification_cost(input=out, label=y, name="cost")


def _tiny_trainer(**kw):
    cost = _tiny_model()
    trainer = paddle.SGD(cost=cost, parameters=paddle.parameters.create(cost),
                         update_equation=paddle.optimizer.Adam(
                             learning_rate=1e-3), **kw)
    rng = np.random.RandomState(0)
    rows = [(rng.randn(16).astype("float32"), int(rng.randint(4)))
            for _ in range(32)]
    return trainer, paddle.batch(lambda: iter(rows), 16), {"x": 0, "y": 1}


def _lowered_step(trainer, reader, feeding):
    from paddle_tpu.trainer.feeder import DataFeeder

    feeder = DataFeeder(trainer.topology.data_type(), feeding)
    feeds = trainer._device_put_feeds(
        trainer._prepare_feeds(feeder(next(iter(reader())))))
    params = trainer.parameters.as_dict()
    return trainer._build_train_step().lower(
        params, trainer._init_opt_state(params), jax.random.PRNGKey(0), feeds)


@pytest.mark.parametrize("mixed", [False, True], ids=["f32", "bf16"])
def test_compiled_step_names_every_layer_the_update_and_the_aux(mixed):
    trainer, reader, feeding = _tiny_trainer(mixed_precision=mixed)
    compiled = _lowered_step(trainer, reader, feeding).compile()
    scopes = {profile.scope_of(n)[0].split("/")[0]: n
              for n in _op_names(compiled)}
    for layer in trainer.topology.layers:
        if layer.type != "data":
            assert layer.scope in scopes, layer.name
    assert "optimizer" in scopes
    assert any(profile.scope_of(n)[0] == "bn/aux_update"
               for n in _op_names(compiled))
    assert ("precision_cast" in scopes) == mixed


def test_scopes_change_nothing_but_metadata(monkeypatch):
    """The lowered text without debug info is byte-identical with the scopes
    and without them (tests/test_sdar.py pins the decoder models' by
    sha256)."""
    def text():
        trainer, reader, feeding = _tiny_trainer(mixed_precision=True)
        return _lowered_step(trainer, reader, feeding).as_text()

    with_scopes = text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = text()
    assert "named_scope" not in with_scopes
    assert hashlib.sha256(with_scopes.encode()).hexdigest() == \
        hashlib.sha256(without.encode()).hexdigest()


def test_a_models_scope_attribute_is_the_layers_scope():
    x = paddle.layer.data(name="x", type=paddle.data_type.dense_vector(8))
    mlp = paddle.layer.gated_mlp(input=x, size=16, scope="m/l0/mlp",
                                 name="m_l0_mlp")
    assert mlp.scope == "m/l0/mlp" and x.scope == "x"
