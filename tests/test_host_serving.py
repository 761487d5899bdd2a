"""Serving the 100M-row model (ISSUE 19, docs/serving.md "Host-backed
tables"): the daemon's HostRowStore stages only a request's touched
rows from the ``__hostrows__/`` sidecar through a bounded LRU cache, so
a vocab of 100M serves inside a fixed footprint — and the /v1/rows
delta channel streams trained rows between full publishes.

Acceptance bar pinned here:
- a 100M-row lazy bundle serves /v1/infer (the same ldd-clean binary
  tests/test_serving_daemon.py::test_ldd_clean_tier1 pins) within
  ``--host_cache_rows``, bit-identical to a dense-served small-vocab
  twin on the same ids;
- a post-publish trained row is visible after ONE /v1/rows delta, no
  full republish;
- torn / regressing / wrong-lineage deltas 409 while the store keeps
  serving exactly what it served before;
- merge_model --no_host_sidecar records a stablehlo_skip_reason naming
  the table;
- tools/metrics_dump.py renders the paddle_serving_rowstore family
  with stage_seconds p50/p95.
"""

import io
import json
import os
import subprocess
import urllib.error

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import activation, data_type, layer, optimizer, pooling
from paddle_tpu.core.topology import Topology
from paddle_tpu.host_table import HostRowStore, write_row_delta
from paddle_tpu.io.merged_model import (export_forward_stablehlo_ex,
                                        read_bundle_meta, stablehlo_meta,
                                        write_bundle)

from test_serving_daemon import DAEMON, NATIVE, Daemon

BIG_VOCAB = 100_000_000
SMALL_VOCAB = 1000
D = 8
SEQ = 6


@pytest.fixture(scope="module")
def serving_build():
    r = subprocess.run(["make", "-C", NATIVE, "serving"],
                       capture_output=True)
    if r.returncode != 0 or not os.path.exists(DAEMON):
        pytest.skip("serving daemon build unavailable")


def _ctr_topo(vocab, host):
    """CTR-shaped servable topology: id sequence -> embedding (the
    100M-row table when ``host``) -> avg pool, + a dense feed, -> fc."""
    ids = layer.data(name="ids",
                     type=data_type.integer_value_sequence(vocab))
    den = layer.data(name="den", type=data_type.dense_vector(4))
    attr = paddle.attr.ParamAttr(name="_hemb", host_resident=host)
    emb = layer.embedding(input=ids, size=D, param_attr=attr)
    pooled = layer.pooling(input=emb, pooling_type=pooling.Avg())
    out = layer.fc(input=[pooled, den], size=4,
                   act=activation.Softmax(), name="out")
    return Topology([out])


@pytest.fixture(scope="module")
def bundles(tmp_path_factory):
    """(host_bundle, dense_bundle, table, store): a 100M-vocab lazy
    host-table bundle and its dense small-vocab twin, identical rows
    0..SMALL_VOCAB-1 and identical non-table parameters."""
    tmp = tmp_path_factory.mktemp("host_serving")
    rng = np.random.RandomState(0)
    table = (rng.randn(SMALL_VOCAB, D) * 0.1).astype(np.float32)

    topo_d = _ctr_topo(SMALL_VOCAB, host=False)
    params_d = paddle.parameters_create(topo_d)
    params_d["_hemb"] = table

    topo_h = _ctr_topo(BIG_VOCAB, host=True)
    params_h = paddle.parameters_create(topo_h)
    for n in params_h.names():
        params_h[n] = params_d[n]
    store = HostRowStore("_hemb", (BIG_VOCAB, D),
                         optimizer.SGD(learning_rate=0.1))
    for i in range(SMALL_VOCAB):
        store._rows[i] = table[i].copy()

    shlo, reason = export_forward_stablehlo_ex(
        topo_h, params_h, seq_len=SEQ, host_tables={"_hemb": 64})
    assert reason is None, reason
    host_bundle = str(tmp / "host.ptpu")
    with open(host_bundle, "wb") as f:
        write_bundle(f, topo_h, params_h,
                     meta={"stablehlo": stablehlo_meta(shlo)},
                     version=7, host_tables={"_hemb": store})

    dense_bundle = str(tmp / "dense.ptpu")
    with open(dense_bundle, "wb") as f:
        write_bundle(f, topo_d, params_d, version=7)
    return host_bundle, dense_bundle, table, store


def _infer(d, iv, mk, dv):
    resp = d.post("/v1/infer", {"inputs": {
        "ids": iv.tolist(), "ids:mask": mk.tolist(),
        "den": dv.tolist()}})
    o = resp["outputs"]["out"]
    return np.array(o["data"], np.float32).reshape(o["shape"])


def test_host_bundle_bit_identical_to_dense_twin(serving_build, bundles):
    """The acceptance bar's exactness half: the 100M-vocab bundle whose
    table exists ONLY as a row sidecar answers bit-identically to the
    dense-resident small-vocab twin on the same ids — row staging is a
    gather, not an approximation."""
    host_bundle, dense_bundle, _table, _store = bundles
    # the sidecar holds the rows that were written, never the vocabulary
    assert os.path.getsize(host_bundle) < 100 * SMALL_VOCAB * D * 4
    rng = np.random.RandomState(3)
    iv = rng.randint(0, SMALL_VOCAB, (4, SEQ)).astype(np.int32)
    mk = np.ones((4, SEQ), np.float32)
    mk[2, 3:] = 0
    iv[2, 3:] = 0
    dv = rng.rand(4, 4).astype(np.float32)
    with Daemon("--bundle", host_bundle, "--backend", "interp",
                "--host_cache_rows", "256") as d:
        sig = json.loads(d.get("/v1/signature"))
        assert sig["host_tables"]["_hemb"]["vocab"] == BIG_VOCAB
        assert sig["host_tables"]["_hemb"]["rows"] == SMALL_VOCAB
        got_host = _infer(d, iv, mk, dv)
    with Daemon("--bundle", dense_bundle, "--backend", "interp") as d:
        got_dense = _infer(d, iv, mk, dv)
    np.testing.assert_array_equal(got_host, got_dense)


def test_footprint_bounded_by_host_cache_rows(serving_build, bundles):
    """--host_cache_rows caps row residency: after touching far more
    distinct ids than the cap, resident_bytes stays <= cap * D * 4 and
    the staging metrics families are live."""
    host_bundle = bundles[0]
    cap = 8
    with Daemon("--bundle", host_bundle, "--backend", "interp",
                "--host_cache_rows", str(cap)) as d:
        rng = np.random.RandomState(5)
        for _ in range(6):
            iv = rng.choice(SMALL_VOCAB, (2, SEQ),
                            replace=False).astype(np.int32)
            mk = np.ones((2, SEQ), np.float32)
            dv = rng.rand(2, 4).astype(np.float32)
            _infer(d, iv, mk, dv)
        met = d.get("/metrics")
    resident = None
    for line in met.splitlines():
        if line.startswith("paddle_serving_rowstore_resident_bytes"):
            resident = float(line.rsplit(" ", 1)[1])
    assert resident is not None, met
    assert 0 < resident <= cap * D * 4
    for fam in ("paddle_serving_rowstore_hit_rate",
                "paddle_serving_rowstore_staged_rows",
                "paddle_serving_rowstore_stage_seconds"):
        assert fam in met, fam


def test_trained_row_visible_after_one_delta(serving_build, bundles,
                                             tmp_path):
    """The freshness half: train a row after the full publish, stream
    it with publish_rows(), and the very next /v1/infer serves it — no
    full republish. Exact against the updated dense math."""
    from paddle_tpu.serving_publisher import ContinuousPublisher

    host_bundle, _dense, table, store = bundles
    topo_h = _ctr_topo(BIG_VOCAB, host=True)
    params_h = paddle.parameters_create(topo_h)
    with Daemon("--bundle", host_bundle, "--backend", "interp") as d:
        pub = ContinuousPublisher(topo_h, str(tmp_path / "pub"),
                                  publish_url=f"http://127.0.0.1:{d.port}",
                                  host_tables={"_hemb": store})
        res = pub.publish(params_h, step=1)
        assert res.outcome == "published", (res.outcome, res.detail)

        iv = np.full((1, SEQ), 5, np.int32)
        mk = np.ones((1, SEQ), np.float32)
        dv = np.zeros((1, 4), np.float32)
        before = _infer(d, iv, mk, dv)

        # one "training step" on row 5, then exactly one delta
        store._rows[5] = (table[5] + 1.0).astype(np.float32)
        store.mark_dirty([5])
        res = pub.publish_rows(step=2)
        assert res.outcome == "published", (res.outcome, res.detail)
        assert "1 rows" in res.detail
        after = _infer(d, iv, mk, dv)
        assert not np.allclose(before, after)
    # restore the module-scoped store for later tests
    store._rows[5] = table[5].copy()
    store.drain_dirty()


def test_bad_deltas_409_store_keeps_serving(serving_build, bundles,
                                            tmp_path):
    """Torn, regressing, and wrong-lineage deltas are refused with 409
    and the store's answers are byte-for-byte what they were before."""
    host_bundle = bundles[0]

    def delta(name, base, seq, fill, corrupt=False):
        p = str(tmp_path / name)
        write_row_delta(p, "_hemb", base_version=base, delta_seq=seq,
                        vocab=BIG_VOCAB, width=D,
                        ids=np.array([9], np.int64),
                        rows=np.full((1, D), fill, np.float32))
        if corrupt:
            blob = bytearray(open(p, "rb").read())
            blob[-3] ^= 0xFF
            open(p, "wb").write(bytes(blob))
        return p

    iv = np.full((1, SEQ), 9, np.int32)
    mk = np.ones((1, SEQ), np.float32)
    dv = np.zeros((1, 4), np.float32)
    with Daemon("--bundle", host_bundle, "--backend", "interp") as d:
        r = d.post("/v1/rows", {"delta": delta("ok.d", 7, 1, 0.5)})
        assert r["result"] == "ok" and r["delta_seq"] == 1
        baseline = _infer(d, iv, mk, dv)
        for name, base, seq, corrupt, expect in (
                ("torn.d", 7, 2, True, "untouched"),     # payload crc
                ("regress.d", 7, 1, False, "regressed"),  # stale seq
                ("lineage.d", 99, 2, False, "lineage")):  # wrong base
            with pytest.raises(urllib.error.HTTPError) as ei:
                d.post("/v1/rows",
                       {"delta": delta(name, base, seq, 0.9, corrupt)})
            assert ei.value.code == 409, name
            body = json.loads(ei.value.read())
            assert expect in body["error"], body
            np.testing.assert_array_equal(
                _infer(d, iv, mk, dv), baseline)
        # the channel is not wedged: the next well-formed delta applies
        r = d.post("/v1/rows", {"delta": delta("next.d", 7, 2, 0.9)})
        assert r["delta_seq"] == 2
        assert not np.array_equal(_infer(d, iv, mk, dv), baseline)


def test_no_sidecar_skip_reason_names_table(tmp_path):
    """merge_model --no_host_sidecar (the pre-r23 legacy path) writes
    the bundle without the table and records WHY there is no
    Python-free export — naming the table."""
    from paddle_tpu.io.merged_model import merge_model

    conf = tmp_path / "host_conf.py"
    conf.write_text(
        "from paddle.trainer_config_helpers import *\n"
        "x = data_layer(name='x', size=16)\n"
        "h = fc_layer(input=x, size=8, param_attr=ParameterAttribute(\n"
        "    name='_big_fc', host_resident=True))\n"
        "outputs(fc_layer(input=h, size=4, act=SoftmaxActivation(),\n"
        "                 name='out'))\n")
    out = str(tmp_path / "legacy.ptpu")
    merge_model(config=str(conf), output=out, host_sidecar=False)
    meta = read_bundle_meta(out)
    assert "stablehlo" not in meta
    reason = meta["stablehlo_skip_reason"]
    assert "'_big_fc'" in reason
    assert "no_host_sidecar" in reason


def test_metrics_dump_renders_rowstore_family(serving_build, bundles):
    """tools/metrics_dump.py --url <daemon> --prefix
    paddle_serving_rowstore: the family renders with stage_seconds
    count/p50/p95 — the operator's one-liner for staging health."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(NATIVE), ".."))
    from tools import metrics_dump

    host_bundle = bundles[0]
    with Daemon("--bundle", host_bundle, "--backend", "interp") as d:
        iv = np.arange(SEQ, dtype=np.int32).reshape(1, SEQ)
        _infer(d, iv, np.ones((1, SEQ), np.float32),
               np.zeros((1, 4), np.float32))
        snap = metrics_dump.load_url(f"http://127.0.0.1:{d.port}")
    buf = io.StringIO()
    rows = metrics_dump.render(snap, out=buf,
                               prefix="paddle_serving_rowstore")
    text = buf.getvalue()
    assert rows >= 4, text
    stage = [ln for ln in text.splitlines()
             if ln.startswith("paddle_serving_rowstore_stage_seconds")]
    assert stage, text
    assert "p50<=" in stage[0] and "p95<=" in stage[0]
    assert all(ln.startswith("paddle_serving_rowstore")
               for ln in text.splitlines() if ln.strip())
