"""Python-free serving daemon (r15, docs/serving.md): golden-parity
serving over the interp backend, continuous-batching decode scheduling,
/metrics + /healthz, and the ldd-clean guarantee.

The daemon is pure C++ (no libpython — pinned here via
tools/check_ldd_clean.py); Python only builds bundles, drives HTTP
requests and checks answers against the live topology.forward.
"""

import json
import os
import signal
import subprocess
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import activation, data_type, layer, pooling
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.topology import Topology
from paddle_tpu.io.merged_model import (export_forward_stablehlo_ex,
                                        stablehlo_meta, write_bundle)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "paddle_tpu", "native")
DAEMON = os.path.join(NATIVE, "paddle_tpu_serving")


@pytest.fixture(scope="session")
def serving_build():
    r = subprocess.run(["make", "-C", NATIVE, "serving"],
                       capture_output=True)
    if r.returncode != 0 or not os.path.exists(DAEMON):
        pytest.skip("serving daemon build unavailable")


class Daemon:
    def __init__(self, *flags, env=None):
        self.proc = subprocess.Popen(
            [DAEMON, "--port", "0", *flags],
            env=dict(os.environ, **env) if env else None,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        # host-table bundles log one line per table before the banner
        for _ in range(32):
            line = self.proc.stdout.readline()
            if "paddle_tpu_serving on port" in line:
                break
        assert "paddle_tpu_serving on port" in line, line
        self.port = int(line.split("port")[1].split()[0])
        # wait for readiness
        deadline = time.time() + 10
        while time.time() < deadline:
            try:
                if self.get("/healthz").startswith("ok"):
                    return
            except OSError:
                time.sleep(0.05)
        raise RuntimeError("daemon did not become healthy")

    def get(self, path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{self.port}{path}", timeout=30) as r:
            return r.read().decode()

    def post(self, path, obj, headers=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}",
            data=json.dumps(obj).encode(), headers=headers or {})
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    def stop(self):
        self.proc.send_signal(signal.SIGKILL)
        self.proc.wait()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.stop()


# --- toy decode twin (must match serving_daemon.cc ToyBackend) ------------

MASK64 = (1 << 64) - 1


def toy_decode(src, max_new, vocab=1000):
    d = 0
    for x in src:
        d = (d * 1000003 + (x & 0xFFFFFFFF)) & MASK64
    n = d % max_new + 1
    out = []
    for t in range(n):
        x = (d ^ ((t + 1) * 0x9E3779B97F4A7C15 & MASK64)) & MASK64
        out.append((x >> 17) % (vocab - 2) + 2)
    return out


# --- bundles ---------------------------------------------------------------

def _multi_input_bundle(path):
    ids = layer.data(name="ids", type=data_type.integer_value_sequence(50))
    den = layer.data(name="den", type=data_type.dense_vector(6))
    emb = layer.embedding(input=ids, size=12)
    pooled = layer.pooling(input=emb, pooling_type=pooling.Avg())
    h = layer.fc(input=[pooled, den], size=16, act=activation.Relu())
    o1 = layer.fc(input=h, size=5, act=activation.Softmax(), name="o1")
    o2 = layer.fc(input=h, size=3, act=activation.Tanh(), name="o2")
    topo = Topology([o1, o2])
    params = paddle.parameters_create(topo)
    shlo, reason = export_forward_stablehlo_ex(topo, params, seq_len=6)
    assert reason is None
    with open(path, "wb") as f:
        write_bundle(f, topo, params,
                     meta={"stablehlo": stablehlo_meta(shlo)})
    return topo, params


def test_ldd_clean_tier1(serving_build):
    """The daemon binary and libpaddle_tpu_pjrt.so link no libpython*
    (the acceptance pin; tools/check_ldd_clean.py is the CI surface)."""
    r = subprocess.run(
        ["python", os.path.join(REPO, "tools", "check_ldd_clean.py")],
        capture_output=True, text=True, timeout=600)
    if r.returncode == 2:
        pytest.skip(f"nothing checkable: {r.stdout}")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DIRTY" not in r.stdout


def test_selftest_smoke(serving_build):
    """`make serve-smoke` body: the daemon spawns itself, POSTs decode
    requests over loopback, scrapes /metrics — both scheduling modes."""
    for extra in ([], ["--drain_batch"]):
        r = subprocess.run([DAEMON, "--selftest", *extra],
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "SERVE-SMOKE-OK" in r.stdout


def test_daemon_serves_multi_input_bundle_golden(serving_build, tmp_path):
    """Multi-input (ids+mask + dense), multi-output bundle served from
    the C++ daemon matches the Python forward golden."""
    import jax.numpy as jnp

    bundle = str(tmp_path / "mi.ptpu")
    topo, params = _multi_input_bundle(bundle)
    r = np.random.RandomState(0)
    iv = r.randint(0, 50, (3, 6)).astype(np.int32)
    mk = np.ones((3, 6), np.float32)
    mk[1, 4:] = 0
    iv[1, 4:] = 0
    dv = r.rand(3, 6).astype(np.float32)
    with Daemon("--bundle", bundle) as d:
        resp = d.post("/v1/infer", {"inputs": {
            "ids": iv.tolist(), "ids:mask": mk.tolist(),
            "den": dv.tolist()}})
        sig = json.loads(d.get("/v1/signature"))
    pdict = {k: jnp.asarray(v) for k, v in params.as_dict().items()}
    want = topo.forward(pdict, {"ids": Arg(jnp.asarray(iv),
                                           jnp.asarray(mk)),
                                "den": Arg(jnp.asarray(dv))})
    for name in ("o1", "o2"):
        got = np.array(resp["outputs"][name]["data"], np.float32) \
            .reshape(resp["outputs"][name]["shape"])
        np.testing.assert_allclose(got, np.asarray(want[name].value),
                                   rtol=2e-5, atol=1e-6)
    assert [s["name"] for s in sig["inputs"]] == ["ids", "ids:mask", "den"]


def test_daemon_shared_engine_concurrent_sessions(serving_build, tmp_path):
    """The multi_thread capi analog: many concurrent /v1/infer sessions
    over ONE shared engine, every response exact."""
    import jax.numpy as jnp

    bundle = str(tmp_path / "mt.ptpu")
    topo, params = _multi_input_bundle(bundle)
    pdict = {k: jnp.asarray(v) for k, v in params.as_dict().items()}
    rng = np.random.RandomState(7)
    cases = []
    for _ in range(8):
        iv = rng.randint(0, 50, (2, 6)).astype(np.int32)
        mk = np.ones((2, 6), np.float32)
        dv = rng.rand(2, 6).astype(np.float32)
        want = topo.forward(pdict, {"ids": Arg(jnp.asarray(iv),
                                               jnp.asarray(mk)),
                                    "den": Arg(jnp.asarray(dv))})
        cases.append((iv, mk, dv, np.asarray(want["o1"].value)))
    errs = []
    with Daemon("--bundle", bundle, "--threads", "8") as d:
        def go(case):
            iv, mk, dv, want1 = case
            try:
                resp = d.post("/v1/infer", {"inputs": {
                    "ids": iv.tolist(), "ids:mask": mk.tolist(),
                    "den": dv.tolist()}})
                got = np.array(resp["outputs"]["o1"]["data"],
                               np.float32).reshape(want1.shape)
                np.testing.assert_allclose(got, want1, rtol=2e-5,
                                           atol=1e-6)
            except Exception as e:      # surfaced below
                errs.append(e)
        ts = [threading.Thread(target=go, args=(c,)) for c in cases * 3]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    assert not errs, errs[:2]


def test_decode_matches_python_twin_continuous(serving_build):
    """Continuous batching: a burst of concurrent decodes over few slots
    completes with outputs matching the deterministic twin, and at least
    one admission happened into a freed slot while others were live."""
    srcs = [[i + 1, i * 7 + 3] for i in range(10)]
    results = [None] * len(srcs)
    with Daemon("--backend", "toy", "--slots", "2", "--toy_tick_us",
                "2000", "--max_new_cap", "64") as d:
        def go(i):
            results[i] = d.post("/v1/decode",
                                {"src": srcs[i], "max_new": 32})
        ts = [threading.Thread(target=go, args=(i,))
              for i in range(len(srcs))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        metrics = d.get("/metrics")
    for i, r in enumerate(results):
        assert r["ids"] == toy_decode(srcs[i], 32), (i, r)
    assert any(r["continuous_admit"] for r in results)
    assert _metric(metrics, "paddle_serving_admitted_inflight_total") >= 1
    assert _metric(metrics, "paddle_serving_decode_completed_total") == \
        len(srcs)


def test_decode_drain_mode_same_outputs(serving_build):
    """--drain_batch (classic static batching) produces the SAME decode
    outputs — scheduling policy changes throughput, never results."""
    srcs = [[i + 1, i * 7 + 3] for i in range(6)]
    results = [None] * len(srcs)
    with Daemon("--backend", "toy", "--slots", "2", "--toy_tick_us",
                "1000", "--drain_batch", "--max_new_cap", "64") as d:
        def go(i):
            results[i] = d.post("/v1/decode",
                                {"src": srcs[i], "max_new": 32})
        ts = [threading.Thread(target=go, args=(i,))
              for i in range(len(srcs))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        metrics = d.get("/metrics")
    for i, r in enumerate(results):
        assert r["ids"] == toy_decode(srcs[i], 32), (i, r)
    # drain mode NEVER admits into a live batch
    assert not any(r["continuous_admit"] for r in results)
    assert _metric(metrics, "paddle_serving_admitted_inflight_total",
                   default=0.0) == 0


def _metric(text, name, default=None):
    for ln in text.splitlines():
        if ln.startswith(name + " ") or ln.startswith(name + "{"):
            return float(ln.split()[-1])
    if default is not None:
        return default
    raise AssertionError(f"metric {name} not found:\n{text}")


def test_metrics_exposition_format(serving_build):
    """/metrics parses as Prometheus text: TYPE lines, monotone
    cumulative histogram buckets ending at +Inf == _count."""
    with Daemon("--backend", "toy", "--slots", "2") as d:
        d.post("/v1/decode", {"src": [3, 4], "max_new": 8})
        text = d.get("/metrics")
    assert "# TYPE paddle_serving_requests_total counter" in text
    assert "# TYPE paddle_serving_request_seconds histogram" in text
    buckets = [float(ln.split()[-1]) for ln in text.splitlines()
               if ln.startswith("paddle_serving_request_seconds_bucket"
                                "{endpoint=\"decode\"")]
    assert buckets == sorted(buckets) and buckets[-1] >= 1
    count = _metric(text,
                    "paddle_serving_request_seconds_count"
                    "{endpoint=\"decode\"}")
    assert buckets[-1] == count
    # occupancy accounting identity: live_ticks <= ticks * slots
    ticks = _metric(text, "paddle_serving_decode_ticks_total")
    live = _metric(text, "paddle_serving_decode_slot_live_ticks_total")
    assert 0 < live <= ticks * 2


def test_infer_on_decode_only_daemon_is_400_not_crash(serving_build):
    """Post-review pin: /v1/infer against a toy (decode-only) daemon
    answers 400 — it used to feed a null engine into vector sizing and
    std::terminate the whole process (one stray request = DoS)."""
    with Daemon("--backend", "toy", "--slots", "2") as d:
        with pytest.raises(urllib.error.HTTPError) as ei:
            d.post("/v1/infer", {"inputs": {"x": [[1.0]]}})
        assert ei.value.code == 400
        assert "no infer backend" in ei.value.read().decode()
        # the daemon survived: decode still serves
        r = d.post("/v1/decode", {"src": [5, 9], "max_new": 8})
        assert r["ids"] == toy_decode([5, 9], 8)


def test_undersized_mask_is_clean_error(serving_build, tmp_path):
    """Post-review pin: a mask whose shape disagrees with its value
    feed's [B, T] answers 400 (was a heap out-of-bounds read in the
    pooling loop)."""
    bundle = str(tmp_path / "m.ptpu")
    _multi_input_bundle(bundle)
    with Daemon("--bundle", bundle) as d:
        iv = [[1, 2, 3, 4, 5, 6]] * 2        # [2, 6] ids
        dv = [[0.0] * 6] * 2
        for bad_mask in ([[1.0]] * 2,        # [2, 1]
                         [1.0, 1.0]):        # [2]
            with pytest.raises(urllib.error.HTTPError) as ei:
                d.post("/v1/infer", {"inputs": {
                    "ids": iv, "ids:mask": bad_mask, "den": dv}})
            assert ei.value.code == 400
            assert "mask" in ei.value.read().decode()


def test_daemon_error_paths(serving_build, tmp_path):
    bundle = str(tmp_path / "e.ptpu")
    _multi_input_bundle(bundle)
    with Daemon("--bundle", bundle) as d:
        # bad JSON body -> 400 with an error message
        with pytest.raises(urllib.error.HTTPError) as ei:
            d.post("/v1/infer", {"not_inputs": 1})
        assert ei.value.code == 400
        # decode without a decode backend -> clear 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            d.post("/v1/decode", {"src": [1, 2]})
        assert ei.value.code == 400
        assert "decode backend" in ei.value.read().decode()
        # unknown endpoint -> 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            d.get("/nope")
        assert ei.value.code == 404


def test_daemon_rejects_unservable_bundle(serving_build, tmp_path):
    """A bundle outside the interp subset (conv) with no usable backend
    fails at startup with the interp's reason — not at first request."""
    from paddle_tpu import networks

    img = layer.data(name="pixel", type=data_type.dense_vector(64))
    conv = networks.simple_img_conv_pool(
        input=img, filter_size=3, num_filters=4, num_channel=1,
        pool_size=2, pool_stride=2, act=activation.Relu())
    out = layer.fc(input=conv, size=10, act=activation.Softmax(),
                   name="out")
    topo = Topology(out)
    params = paddle.parameters_create(topo)
    bundle = str(tmp_path / "conv.ptpu")
    with open(bundle, "wb") as f:
        write_bundle(f, topo, params, meta={})
    r = subprocess.run([DAEMON, "--bundle", bundle, "--port", "0"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "unsupported layer type" in (r.stdout + r.stderr)


def test_decode_bundle_without_step_logs_fallback_reason(serving_build,
                                                         tmp_path):
    """Satellite (ISSUE 14): a generation bundle that carries
    meta.stablehlo_step_skip_reason makes the daemon LOG the recorded
    reason (drain-batch whole-loop fallback) at load — never a silent
    whole-loop-only bundle. On this plugin-less host the interp backend
    then refuses the beam layer, so startup still exits 1; on a PJRT
    host the same load proceeds into the drain-batch fallback."""
    import jax

    from paddle_tpu.core.parameters import Parameters
    from paddle_tpu.io.merged_model import (export_forward_stablehlo_ex,
                                            stablehlo_meta)
    from paddle_tpu.models.text import nmt_decode_topology

    gen = nmt_decode_topology(src_dict_dim=60, trg_dict_dim=60,
                              word_vector_dim=8, encoder_size=8,
                              decoder_size=8, beam_size=2, max_length=6,
                              cand_k=16, mode="compact", name="m")
    topo = Topology(gen)
    params = topo.init_params(jax.random.PRNGKey(0))
    P = Parameters.from_dict({k: np.asarray(v)
                              for k, v in params.items()})
    shlo, reason = export_forward_stablehlo_ex(topo, P, seq_len=5)
    assert reason is None, reason
    bundle = str(tmp_path / "gen_nostep.ptpu")
    with open(bundle, "wb") as f:
        write_bundle(f, topo, P, meta={
            "stablehlo": stablehlo_meta(shlo),
            "stablehlo_step_skip_reason":
                "beam-control callbacks cannot ride a compiled step "
                "module"})
    r = subprocess.run([DAEMON, "--bundle", bundle, "--port", "0"],
                       capture_output=True, text=True, timeout=120)
    out = r.stdout + r.stderr
    assert "decode step modules absent" in out, out
    assert "beam-control callbacks" in out
    assert "drain-batch" in out


def test_readyz_and_healthz_split(serving_build):
    """Liveness (/healthz) and readiness (/readyz) are separate
    endpoints: both ok on a fresh daemon (drain flips /readyz only —
    pinned in tests/test_serving_chaos.py). The ready body is JSON
    carrying bundle_version + backend kind (r21: the router and fleet
    publisher confirm reloads from it without a /metrics scrape);
    the 200 status stays the contract for bare old-style probes."""
    with Daemon("--backend", "toy", "--slots", "2") as d:
        assert d.get("/healthz").startswith("ok")
        rz = json.loads(d.get("/readyz"))
        assert rz["status"] == "ok"
        assert rz["backend"] == "toy"
        assert rz["bundle_version"] == 0    # toy serves no bundle


def test_readyz_json_tracks_reload_version(serving_build, tmp_path):
    """The /readyz bundle_version field is live: a hot-swap advances
    it — this is the field the fleet publisher's rolling confirm and
    the router read instead of scraping /metrics."""
    import numpy as np

    def bundle(path, scale, version):
        x = layer.data(name="x", type=data_type.dense_vector(4))
        out = layer.fc(input=x, size=3, act=activation.Softmax(),
                       name="out")
        topo = Topology(out)
        params = paddle.parameters_create(topo)
        for n in params.names():
            v = np.asarray(params.get(n))
            params.set(n, (v * scale).astype(v.dtype))
        with open(path, "wb") as f:
            write_bundle(f, topo, params, version=version)

    a, b = str(tmp_path / "a.ptpu"), str(tmp_path / "b.ptpu")
    bundle(a, 1.0, version=7)
    bundle(b, 2.0, version=8)
    with Daemon("--bundle", a) as d:
        rz = json.loads(d.get("/readyz"))
        assert rz["bundle_version"] == 7 and rz["backend"] == "interp"
        assert d.post("/v1/reload", {"bundle": b})["result"] == "ok"
        rz = json.loads(d.get("/readyz"))
        assert rz["bundle_version"] == 8


def test_request_body_cap_413(serving_build):
    """Hostile-client pin: a body past --max_body_bytes answers 413
    without reading (or buffering) the payload."""
    with Daemon("--backend", "toy", "--slots", "2",
                "--max_body_bytes", "1024") as d:
        big = {"src": list(range(2000)), "max_new": 8}
        with pytest.raises(urllib.error.HTTPError) as ei:
            d.post("/v1/decode", big)
        assert ei.value.code == 413
        assert "max_body_bytes" in ei.value.read().decode()
        # the daemon survived and still serves normal requests
        r = d.post("/v1/decode", {"src": [5, 9], "max_new": 8})
        assert r["ids"] == toy_decode([5, 9], 8)


def test_slow_client_408_cannot_pin_worker(serving_build):
    """Hostile-client pin: a socket that sends half a request and
    stalls gets 408 after --io_timeout_ms instead of pinning a worker
    thread forever."""
    import socket as socketlib

    with Daemon("--backend", "toy", "--slots", "2", "--threads", "2",
                "--io_timeout_ms", "300") as d:
        t0 = time.time()
        s = socketlib.create_connection(("127.0.0.1", d.port), timeout=10)
        s.sendall(b"POST /v1/decode HTTP/1.1\r\nContent-Length: 40\r\n"
                  b"\r\n{\"src\": [1")          # ...and stall mid-body
        resp = b""
        s.settimeout(10)
        try:
            while True:
                chunk = s.recv(4096)
                if not chunk:
                    break
                resp += chunk
        except OSError:
            pass
        s.close()
        assert b"408" in resp.split(b"\r\n", 1)[0], resp[:200]
        # bounded: the 408 came from --io_timeout_ms, not a 30s default
        assert time.time() - t0 < 5
        # with only 2 workers, both must be free again afterwards
        r = d.post("/v1/decode", {"src": [5, 9], "max_new": 8})
        assert r["ids"] == toy_decode([5, 9], 8)


def test_load_shed_503_retry_after_only_above_high_water(serving_build):
    """Satellite pin: 503 + Retry-After appears only above
    --queue_high_water, and paddle_serving_shed_total matches the count
    of shed responses exactly."""
    # one slot, slow ticks: the first request occupies the slot, the
    # next two queue up to the high-water mark, everything past it sheds
    with Daemon("--backend", "toy", "--slots", "1", "--toy_tick_us",
                "50000", "--max_new_cap", "64",
                "--queue_high_water", "2") as d:
        occupants = []
        ts = []
        for i in range(3):                    # 1 in slot + 2 queued
            # srcs chosen for long toy decodes (gen_len >= 24 ticks at
            # 50ms each) so the queue stays full while shedding is probed
            t = threading.Thread(target=lambda i=i: occupants.append(
                d.post("/v1/decode", {"src": [6 + i, 7], "max_new": 32})))
            t.start()
            ts.append(t)
            # wait until this request is genuinely in the slot/queue so
            # the fill order is deterministic
            deadline = time.time() + 10
            while time.time() < deadline:
                m = d.get("/metrics")
                depth = _metric(m, "paddle_serving_queue_depth",
                                default=0.0)
                live = _metric(m, "paddle_serving_slots_live",
                               default=0.0)
                if live + depth >= i + 1:
                    break
                time.sleep(0.01)
        # above the high-water mark: shed with Retry-After
        shed = 0
        for _ in range(3):
            with pytest.raises(urllib.error.HTTPError) as ei:
                d.post("/v1/decode", {"src": [5, 9], "max_new": 8})
            assert ei.value.code == 503
            assert ei.value.headers.get("Retry-After") == "1"
            assert "high-water" in ei.value.read().decode()
            shed += 1
        m = d.get("/metrics")
        assert _metric(m, "paddle_serving_shed_total") == shed
        for t in ts:
            t.join()
        # the admitted requests were untouched by the shedding
        assert len(occupants) == 3
        for r in occupants:
            assert r["ids"]
        # below the mark again: no shed, no Retry-After needed
        r = d.post("/v1/decode", {"src": [5, 9], "max_new": 8})
        assert r["ids"] == toy_decode([5, 9], 8)
        assert _metric(d.get("/metrics"),
                       "paddle_serving_shed_total") == shed


# --- quantized bundles (ISSUE 16, docs/serving.md "Quantized bundles") ----

def _quantized_bundles(tmp_path, batch_ladder=None):
    """One model, three precisions: the _multi_input_bundle topology
    merged at f32 / bf16 / int8 into sibling bundles sharing the SAME
    master params, so outputs are directly comparable."""
    from paddle_tpu import quant
    from paddle_tpu.core.parameters import Parameters

    ids = layer.data(name="ids", type=data_type.integer_value_sequence(50))
    den = layer.data(name="den", type=data_type.dense_vector(6))
    emb = layer.embedding(input=ids, size=12)
    pooled = layer.pooling(input=emb, pooling_type=pooling.Avg())
    h = layer.fc(input=[pooled, den], size=16, act=activation.Relu())
    o1 = layer.fc(input=h, size=5, act=activation.Softmax(), name="o1")
    o2 = layer.fc(input=h, size=3, act=activation.Tanh(), name="o2")
    topo = Topology([o1, o2])
    params = paddle.parameters_create(topo)
    pdict = {k: params.get(k) for k in params.names()}
    paths = {}
    for mode in ("f32", "bf16", "int8"):
        if mode == "f32":
            P, qmeta = params, None
        else:
            qd, qmeta = quant.quantize_params(topo, pdict, mode)
            P = Parameters.from_dict(qd)
        shlo, reason = export_forward_stablehlo_ex(topo, P, seq_len=6,
                                                   qmeta=qmeta,
                                                   batch_ladder=batch_ladder)
        assert reason is None, reason
        meta = {"stablehlo": stablehlo_meta(shlo)}
        if qmeta is not None:
            meta["quantize"] = qmeta
        paths[mode] = str(tmp_path / f"{mode}.ptpu")
        with open(paths[mode], "wb") as f:
            write_bundle(f, topo, P, meta=meta)
    return topo, params, paths


def _quant_feeds():
    r = np.random.RandomState(0)
    iv = r.randint(0, 50, (3, 6)).astype(np.int32)
    mk = np.ones((3, 6), np.float32)
    mk[1, 4:] = 0
    iv[1, 4:] = 0
    dv = r.rand(3, 6).astype(np.float32)
    return iv, mk, dv


def _f32_golden(topo, params, iv, mk, dv):
    import jax.numpy as jnp

    pdict = {k: jnp.asarray(v) for k, v in params.as_dict().items()}
    want = topo.forward(pdict, {"ids": Arg(jnp.asarray(iv),
                                           jnp.asarray(mk)),
                                "den": Arg(jnp.asarray(dv))})
    return {n: np.asarray(want[n].value) for n in ("o1", "o2")}


def test_daemon_quantized_golden_and_accounting(serving_build, tmp_path):
    """bf16 and int8 bundles served by the interp backend stay within
    the documented tolerance of the f32 python golden, and the byte
    accounting is visible everywhere: meta.param_bytes ->
    /v1/signature.{quantize,param_bytes} ->
    paddle_serving_param_bytes{dtype} gauges."""
    topo, params, paths = _quantized_bundles(tmp_path)
    iv, mk, dv = _quant_feeds()
    golden = _f32_golden(topo, params, iv, mk, dv)
    totals = {}
    for mode, tol in (("f32", 1e-5), ("bf16", 5e-3), ("int8", 2e-2)):
        with Daemon("--bundle", paths[mode], "--backend", "interp") as d:
            resp = d.post("/v1/infer", {"inputs": {
                "ids": iv.tolist(), "ids:mask": mk.tolist(),
                "den": dv.tolist()}})
            sig = json.loads(d.get("/v1/signature"))
            mtext = d.get("/metrics")
        for name in ("o1", "o2"):
            got = np.array(resp["outputs"][name]["data"], np.float32) \
                .reshape(resp["outputs"][name]["shape"])
            err = np.max(np.abs(got - golden[name]))
            assert err < tol, (mode, name, err)
        pb = sig["param_bytes"]
        totals[mode] = pb["total"]
        assert pb["total"] == sum(pb["by_dtype"].values())
        if mode == "f32":
            assert sig.get("quantize", "f32") == "f32"
            assert set(pb["by_dtype"]) == {"f32"}
        else:
            assert sig["quantize"]["mode"] == mode
            assert pb["by_dtype"][mode] > 0
            # biases (and int8 scale sidecars) remain f32
            assert pb["by_dtype"]["f32"] > 0
        for dt, v in pb["by_dtype"].items():
            assert _metric(
                mtext,
                'paddle_serving_param_bytes{dtype="%s"}' % dt) == v
        assert _metric(mtext, "paddle_serving_param_bytes_total") \
            == pb["total"]
    # the acceptance byte cut: ~2x bf16, ~4x int8 on the weight payload
    assert totals["bf16"] < totals["f32"] * 0.62
    assert totals["int8"] < totals["f32"] * 0.45


def test_daemon_quantized_golden_pjrt(serving_build, tmp_path):
    """Same golden over the PJRT backend where buildable: the exported
    module carries the dequant, so XLA serves the quantized bundle with
    no daemon-side special casing."""
    topo, params, paths = _quantized_bundles(tmp_path)
    iv, mk, dv = _quant_feeds()
    golden = _f32_golden(topo, params, iv, mk, dv)
    for mode, tol in (("bf16", 5e-3), ("int8", 2e-2)):
        try:
            d = Daemon("--bundle", paths[mode], "--backend", "pjrt")
        except AssertionError:
            pytest.skip("pjrt backend unavailable on this host")
        with d:
            resp = d.post("/v1/infer", {"inputs": {
                "ids": iv.tolist(), "ids:mask": mk.tolist(),
                "den": dv.tolist()}})
        for name in ("o1", "o2"):
            got = np.array(resp["outputs"][name]["data"], np.float32) \
                .reshape(resp["outputs"][name]["shape"])
            assert np.max(np.abs(got - golden[name])) < tol, (mode, name)


def test_daemon_reload_across_precisions(serving_build, tmp_path):
    """/v1/reload swaps an f32 daemon onto the int8 bundle: signature,
    gauges and served outputs all move to the new precision with no
    restart and no flag changes."""
    topo, params, paths = _quantized_bundles(tmp_path)
    iv, mk, dv = _quant_feeds()
    golden = _f32_golden(topo, params, iv, mk, dv)
    with Daemon("--bundle", paths["f32"], "--backend", "interp") as d:
        sig0 = json.loads(d.get("/v1/signature"))
        assert sig0.get("quantize", "f32") == "f32"
        r = d.post("/v1/reload", {"bundle": paths["int8"]})
        assert r.get("result") == "ok", r
        sig = json.loads(d.get("/v1/signature"))
        assert sig["quantize"]["mode"] == "int8"
        assert sig["param_bytes"]["total"] < \
            sig0["param_bytes"]["total"] * 0.45
        mtext = d.get("/metrics")
        assert _metric(
            mtext, 'paddle_serving_param_bytes{dtype="int8"}') \
            == sig["param_bytes"]["by_dtype"]["int8"]
        assert _metric(mtext, "paddle_serving_param_bytes_total") \
            == sig["param_bytes"]["total"]
        resp = d.post("/v1/infer", {"inputs": {
            "ids": iv.tolist(), "ids:mask": mk.tolist(),
            "den": dv.tolist()}})
        got = np.array(resp["outputs"]["o1"]["data"], np.float32) \
            .reshape(resp["outputs"]["o1"]["shape"])
        err = np.max(np.abs(got - golden["o1"]))
        # int8-quantized now: off the f32 exact path but within tol
        assert 1e-7 < err < 2e-2


def _poison_param_dtype(src, dst):
    """Rewrite one meta.quantize.param_dtypes entry to an unknown tag
    ('fp4'), leaving the param tar (and its crc) untouched."""
    import struct

    with open(src, "rb") as f:
        magic = f.read(8)
        (n,) = struct.unpack("<Q", f.read(8))
        cfg = json.loads(f.read(n).decode())
        rest = f.read()
    name = next(k for k, v in
                cfg["meta"]["quantize"]["param_dtypes"].items()
                if v == "int8")
    cfg["meta"]["quantize"]["param_dtypes"][name] = "fp4"
    blob = json.dumps(cfg).encode()
    with open(dst, "wb") as f:
        f.write(magic)
        f.write(struct.pack("<Q", len(blob)))
        f.write(blob)
        f.write(rest)
    return name


def test_daemon_fail_closed_unknown_param_dtype(serving_build, tmp_path):
    """Fail-closed pin: a bundle whose signature declares a param dtype
    this daemon does not know is REFUSED — at startup (exit nonzero,
    message naming the param) and on /v1/reload (409, old params keep
    serving byte-identically). Never reinterpret the bytes."""
    topo, params, paths = _quantized_bundles(tmp_path)
    bad = str(tmp_path / "fp4.ptpu")
    name = _poison_param_dtype(paths["int8"], bad)
    r = subprocess.run([DAEMON, "--port", "0", "--bundle", bad,
                        "--backend", "interp"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode != 0
    out = r.stdout + r.stderr
    assert name in out and "fp4" in out
    assert "refusing" in out.lower()

    iv, mk, dv = _quant_feeds()
    with Daemon("--bundle", paths["f32"], "--backend", "interp") as d:
        before = d.post("/v1/infer", {"inputs": {
            "ids": iv.tolist(), "ids:mask": mk.tolist(),
            "den": dv.tolist()}})
        with pytest.raises(urllib.error.HTTPError) as ei:
            d.post("/v1/reload", {"bundle": bad})
        assert ei.value.code == 409
        body = ei.value.read().decode()
        assert "fp4" in body
        sig = json.loads(d.get("/v1/signature"))
        # old f32 state still live
        assert sig.get("quantize", "f32") == "f32"
        after = d.post("/v1/infer", {"inputs": {
            "ids": iv.tolist(), "ids:mask": mk.tolist(),
            "den": dv.tolist()}})
        assert after["outputs"]["o1"]["data"] == \
            before["outputs"]["o1"]["data"]


def test_metrics_dump_url_against_daemon(serving_build, tmp_path):
    """tools/metrics_dump.py --url reads the daemon's /metrics.json
    (the C++ twin of the Python registry's to_json()): the full
    snapshot renders, and --prefix paddle_serving_param isolates the
    quantized byte gauges."""
    import io as _io

    from tools.metrics_dump import load_url, render

    _topo, _params, paths = _quantized_bundles(tmp_path)
    with Daemon("--bundle", paths["int8"], "--backend", "interp") as d:
        iv, mk, dv = _quant_feeds()
        d.post("/v1/infer", {"inputs": {
            "ids": iv.tolist(), "ids:mask": mk.tolist(),
            "den": dv.tolist()}})
        snap = load_url(f"http://127.0.0.1:{d.port}")
        sig = json.loads(d.get("/v1/signature"))
    buf = _io.StringIO()
    n = render(snap, out=buf, prefix="paddle_serving_param")
    text = buf.getvalue()
    assert n >= 4       # f32/bf16/int8 byte gauges + total + version
    assert 'paddle_serving_param_bytes' in text
    assert 'dtype="int8"' in text
    int8_bytes = sig["param_bytes"]["by_dtype"]["int8"]
    assert str(int8_bytes) in text or f"{int8_bytes:.6g}" in text
    # the unfiltered snapshot renders too (histograms included)
    buf2 = _io.StringIO()
    n2 = render(snap, out=buf2)
    assert n2 > n
    assert "paddle_serving_request_seconds" in buf2.getvalue()


# --- infer micro-batching + multi-model daemons (ISSUE 18,
#     docs/serving.md "Infer micro-batching" / "Multi-model daemons") ------

def _infer_body(iv, mk, dv):
    return {"inputs": {"ids": iv.tolist(), "ids:mask": mk.tolist(),
                       "den": dv.tolist()}}


def _row_requests(n=6, seed=5):
    """n single-row request bodies with distinct inputs — the CTR
    traffic shape the micro-batcher coalesces."""
    r = np.random.RandomState(seed)
    bodies = []
    for _ in range(n):
        iv = r.randint(0, 50, (1, 6)).astype(np.int32)
        mk = np.ones((1, 6), np.float32)
        dv = r.rand(1, 6).astype(np.float32)
        bodies.append(_infer_body(iv, mk, dv))
    return bodies


def _concurrent_posts(d, bodies, headers=None):
    out = [None] * len(bodies)
    errs = []

    def go(i):
        try:
            out[i] = d.post("/v1/infer", bodies[i],
                            headers=headers[i] if headers else None)
        except Exception as e:          # surfaced below
            errs.append(e)

    ts = [threading.Thread(target=go, args=(i,))
          for i in range(len(bodies))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs[:2]
    return out


def test_batched_infer_bit_identical_interp(serving_build, tmp_path):
    """Acceptance pin: responses gathered through the micro-batch
    window are BYTE-identical to per-request execution (same daemon
    flags minus --batch_window_ms) across f32/bf16/int8 bundles on the
    interp backend — batching is a scheduling change, never a numeric
    one. The window genuinely coalesced (fewer batches than requests)
    and the interp backend never pads (native n-ary batching)."""
    _topo, _params, paths = _quantized_bundles(tmp_path)
    bodies = _row_requests(6)
    for mode in ("f32", "bf16", "int8"):
        with Daemon("--bundle", paths[mode], "--backend", "interp") as s:
            ref = [s.post("/v1/infer", b) for b in bodies]
        with Daemon("--bundle", paths[mode], "--backend", "interp",
                    "--batch_window_ms", "120", "--batch_max", "64",
                    "--threads", "8") as d:
            got = _concurrent_posts(d, bodies)
            mtext = d.get("/metrics")
        for g, r in zip(got, ref):
            assert g["outputs"] == r["outputs"], mode
        batches = _metric(
            mtext, 'paddle_serving_batches_total{model="default"}')
        assert 1 <= batches < len(bodies), (mode, batches)
        assert _metric(
            mtext,
            'paddle_serving_batch_size_count{model="default"}') == batches
        assert _metric(
            mtext, 'paddle_serving_batch_pad_fraction_bucket'
                   '{model="default",le="0"}') == batches


def test_batched_infer_bit_identical_pjrt(serving_build, tmp_path):
    """Same acceptance pin over the PJRT backend where loadable: the
    batch ladder serves the gathered rows, and every scattered row is
    byte-identical to the solo-request answer."""
    _topo, _params, paths = _quantized_bundles(tmp_path,
                                               batch_ladder=[1, 2, 4])
    bodies = _row_requests(6)
    for mode in ("f32", "bf16", "int8"):
        try:
            s = Daemon("--bundle", paths[mode], "--backend", "pjrt")
        except AssertionError:
            pytest.skip("pjrt backend unavailable on this host")
        with s:
            ref = [s.post("/v1/infer", b) for b in bodies]
        with Daemon("--bundle", paths[mode], "--backend", "pjrt",
                    "--batch_window_ms", "120", "--threads", "8") as d:
            got = _concurrent_posts(d, bodies)
        for g, r in zip(got, ref):
            assert g["outputs"] == r["outputs"], mode


def test_batch_ladder_export_and_signature(serving_build, tmp_path):
    """merge-side ladder pins: --export_batch_ladder style rungs come
    back sorted + deduped in signature.batch_ladder, each rung lands as
    a batch-monomorphic module under meta (mlir_<platform>_b<N>_b64),
    and the daemon surfaces the ladder through /v1/signature."""
    ids = layer.data(name="ids", type=data_type.integer_value_sequence(50))
    den = layer.data(name="den", type=data_type.dense_vector(6))
    emb = layer.embedding(input=ids, size=12)
    pooled = layer.pooling(input=emb, pooling_type=pooling.Avg())
    o1 = layer.fc(input=[pooled, den], size=5,
                  act=activation.Softmax(), name="o1")
    topo = Topology([o1])
    params = paddle.parameters_create(topo)
    shlo, reason = export_forward_stablehlo_ex(
        topo, params, seq_len=6, batch_ladder=[4, 1, 2, 2])
    assert reason is None, reason
    assert shlo["signature"]["batch_ladder"] == [1, 2, 4]
    meta = stablehlo_meta(shlo)
    for n in (1, 2, 4):
        assert f"mlir_cpu_b{n}_b64" in meta, sorted(meta)
    bundle = str(tmp_path / "ladder.ptpu")
    with open(bundle, "wb") as f:
        write_bundle(f, topo, params, meta={"stablehlo": meta})
    with Daemon("--bundle", bundle) as d:
        sig = json.loads(d.get("/v1/signature"))
    assert sig.get("batch_ladder") == [1, 2, 4]


def test_batch_ladder_selection_pjrt(serving_build, tmp_path):
    """Rung-selection pin (PJRT hosts): a 3-row request on ladder
    [1,2,4] runs the b4 module — pad_fraction observes exactly 0.25,
    never a full-static-batch pad."""
    ids = layer.data(name="ids", type=data_type.integer_value_sequence(50))
    den = layer.data(name="den", type=data_type.dense_vector(6))
    emb = layer.embedding(input=ids, size=12)
    pooled = layer.pooling(input=emb, pooling_type=pooling.Avg())
    o1 = layer.fc(input=[pooled, den], size=5,
                  act=activation.Softmax(), name="o1")
    topo = Topology([o1])
    params = paddle.parameters_create(topo)
    shlo, reason = export_forward_stablehlo_ex(
        topo, params, seq_len=6, batch_ladder=[1, 2, 4])
    assert reason is None, reason
    bundle = str(tmp_path / "ladder_sel.ptpu")
    with open(bundle, "wb") as f:
        write_bundle(f, topo, params, meta={"stablehlo":
                                            stablehlo_meta(shlo)})
    try:
        d = Daemon("--bundle", bundle, "--backend", "pjrt",
                   "--batch_window_ms", "30")
    except AssertionError:
        pytest.skip("pjrt backend unavailable on this host")
    with d:
        r = np.random.RandomState(2)
        iv = r.randint(0, 50, (3, 6)).astype(np.int32)
        mk = np.ones((3, 6), np.float32)
        dv = r.rand(3, 6).astype(np.float32)
        resp = d.post("/v1/infer", _infer_body(iv, mk, dv))
        assert resp["outputs"]["o1"]["shape"] == [3, 5]
        mtext = d.get("/metrics")
    assert _metric(mtext, 'paddle_serving_batch_pad_fraction_bucket'
                          '{model="default",le="0.125"}') == 0
    assert _metric(mtext, 'paddle_serving_batch_pad_fraction_bucket'
                          '{model="default",le="0.25"}') == 1


def test_two_model_mixed_window_parity(serving_build, tmp_path):
    """Multi-bundle daemon: one gather window mixing requests for two
    models (f32 as 'a', int8 as 'b') keeps per-model batches separate —
    every scattered row byte-identical to that model's solo daemon,
    routing via both the "model" body field and the X-Model header,
    unknown model 404s, per-model metric twins live."""
    _topo, _params, paths = _quantized_bundles(tmp_path)
    bodies = _row_requests(6)
    refs = {}
    for m, p in (("a", paths["f32"]), ("b", paths["int8"])):
        with Daemon("--bundle", p, "--backend", "interp") as solo:
            refs[m] = [solo.post("/v1/infer", b) for b in bodies]
    with Daemon("--bundle", "a=" + paths["f32"],
                "--bundle", "b=" + paths["int8"],
                "--backend", "interp", "--batch_window_ms", "80",
                "--threads", "8") as d:
        mixed, headers = [], []
        for i, b in enumerate(bodies):
            if i % 2 == 0:              # body-field routing
                mixed.append(dict(b, model="a"))
                headers.append(None)
            else:                       # header routing
                mixed.append(b)
                headers.append({"X-Model": "b"})
        got = _concurrent_posts(d, mixed, headers=headers)
        mtext = d.get("/metrics")
        with pytest.raises(urllib.error.HTTPError) as ei:
            d.post("/v1/infer", dict(bodies[0], model="zzz"))
        assert ei.value.code == 404
        assert "unknown model" in ei.value.read().decode()
    for i in range(len(bodies)):
        m = "a" if i % 2 == 0 else "b"
        assert got[i]["outputs"] == refs[m][i]["outputs"], (i, m)
    assert _metric(mtext, 'paddle_serving_batches_total{model="a"}') >= 1
    assert _metric(mtext, 'paddle_serving_batches_total{model="b"}') >= 1
    # the default-model back-compat twin tracks model 'a' (first spec)
    assert _metric(mtext, "paddle_serving_param_version") == \
        _metric(mtext, 'paddle_serving_param_version{model="a"}')


def test_batch_deadline_504_inside_window(serving_build, tmp_path):
    """Deadline-aware gather: a request whose deadline expires inside a
    stalled window (batch.window fault) answers 504 WITHOUT stalling
    its batch-mates, and batch_expired_total counts it."""
    bundle = str(tmp_path / "dl.ptpu")
    _multi_input_bundle(bundle)
    bodies = _row_requests(2)
    with Daemon("--bundle", bundle, "--batch_window_ms", "50",
                "--threads", "4",
                env={"PTPU_SERVING_FAULTS": "batch.window@1:400"}) as d:
        res, errs = [None, None], [None, None]

        def go(i, body):
            try:
                res[i] = d.post("/v1/infer", body)
            except urllib.error.HTTPError as e:
                errs[i] = (e.code, e.read().decode())

        ts = [threading.Thread(target=go,
                               args=(0, dict(bodies[0], deadline_ms=100))),
              threading.Thread(target=go, args=(1, bodies[1]))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        mtext = d.get("/metrics")
    assert errs[0] is not None and errs[0][0] == 504, errs
    assert "gather window" in errs[0][1]
    assert res[1] is not None and "outputs" in res[1]
    assert _metric(
        mtext,
        'paddle_serving_batch_expired_total{model="default"}') == 1


def test_metrics_dump_batch_histograms(serving_build, tmp_path):
    """Satellite: tools/metrics_dump.py --url --prefix
    paddle_serving_batch renders the micro-batcher histograms' p50/p95
    from the C++ /metrics.json twin — the custom bucket bounds
    (batch-size powers of two, pad-fraction eighths) round-trip the
    JSON shape."""
    import io as _io

    from tools.metrics_dump import load_url, render

    bundle = str(tmp_path / "md.ptpu")
    _multi_input_bundle(bundle)
    with Daemon("--bundle", bundle, "--batch_window_ms", "40",
                "--threads", "6") as d:
        _concurrent_posts(d, _row_requests(4))
        snap = load_url(f"http://127.0.0.1:{d.port}")
    buf = _io.StringIO()
    n = render(snap, out=buf, prefix="paddle_serving_batch")
    text = buf.getvalue()
    assert n >= 4, text
    for fam in ("paddle_serving_batch_size",
                "paddle_serving_batch_window_wait_seconds",
                "paddle_serving_batch_pad_fraction",
                "paddle_serving_batches_total"):
        assert fam in text, text
    assert 'model="default"' in text
    for ln in text.splitlines():
        if " hist " in ln:
            assert "p50<=" in ln and "p95<=" in ln, ln
