"""Export-parity coverage for the generalized StableHLO export (r15).

For each servable bundle shape (multi-input dense, ids+mask,
multi-output, non-sequence ids, while_loop beam decode) the test
round-trips export -> deserialize -> call and asserts the results match
the live ``topology.forward`` / decode goldens — plus the skip-reason
satellite: unservable topologies record WHY in the bundle meta instead
of silently omitting the artifact.
"""

import base64
import io as _io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import export as jax_export

import paddle_tpu as paddle
from paddle_tpu import activation, data_type, layer, pooling
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.parameters import Parameters
from paddle_tpu.core.topology import Topology
from paddle_tpu.io.merged_model import (export_decode_step_stablehlo_ex,
                                        export_forward_stablehlo,
                                        export_forward_stablehlo_ex,
                                        read_bundle, read_bundle_meta,
                                        stablehlo_meta,
                                        stablehlo_step_meta, write_bundle)
from paddle_tpu.step_decode import StepDecodeDriver


def _pdict(params):
    return {k: jnp.asarray(v) for k, v in params.as_dict().items()}


def _feeds_for(sig, arrays):
    """Order `arrays` {name: np array} by the signature's input list."""
    return [arrays[s["name"]] for s in sig["inputs"]]


@pytest.fixture
def multi_io_model():
    a = layer.data(name="a", type=data_type.dense_vector(8))
    b = layer.data(name="b", type=data_type.dense_vector(4))
    h = layer.fc(input=[a, b], size=16, act=activation.Relu())
    o1 = layer.fc(input=h, size=5, act=activation.Softmax(), name="o1")
    o2 = layer.fc(input=h, size=3, act=activation.Tanh(), name="o2")
    topo = Topology([o1, o2])
    return topo, paddle.parameters_create(topo)


def test_multi_input_multi_output_parity(multi_io_model):
    topo, params = multi_io_model
    shlo, reason = export_forward_stablehlo_ex(topo, params)
    assert reason is None and shlo is not None
    sig = shlo["signature"]
    assert [s["name"] for s in sig["inputs"]] == ["a", "b"]
    assert [s["name"] for s in sig["outputs"]] == ["o1", "o2"]
    assert sig["symbolic_batch"] is True
    assert "cpu" in shlo["modules"] and "tpu" in shlo["modules"]

    exp = jax_export.deserialize(shlo["artifact"])
    r = np.random.RandomState(0)
    # symbolic batch: a size the static_batch does not equal
    x1 = r.rand(3, 8).astype(np.float32)
    x2 = r.rand(3, 4).astype(np.float32)
    got = exp.call(x1, x2)
    want = topo.forward(_pdict(params), {"a": x1, "b": x2})
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(want["o1"].value),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[1]),
                               np.asarray(want["o2"].value),
                               rtol=1e-5, atol=1e-6)


def test_ids_mask_sequence_parity():
    ids = layer.data(name="ids", type=data_type.integer_value_sequence(50))
    emb = layer.embedding(input=ids, size=12)
    pooled = layer.pooling(input=emb, pooling_type=pooling.Avg())
    out = layer.fc(input=pooled, size=4, act=activation.Softmax(),
                   name="out")
    topo = Topology(out)
    params = paddle.parameters_create(topo)
    shlo, reason = export_forward_stablehlo_ex(topo, params, seq_len=6)
    assert reason is None
    sig = shlo["signature"]
    assert [(s["name"], s["dtype"]) for s in sig["inputs"]] == \
        [("ids", "i32"), ("ids:mask", "f32")]
    assert sig["inputs"][0]["shape"] == ["b", 6]

    exp = jax_export.deserialize(shlo["artifact"])
    r = np.random.RandomState(1)
    iv = r.randint(0, 50, (2, 6)).astype(np.int32)
    mk = np.ones((2, 6), np.float32)
    mk[1, 4:] = 0
    got = exp.call(iv, mk)
    want = topo.forward(_pdict(params),
                        {"ids": Arg(jnp.asarray(iv), jnp.asarray(mk))})
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want["out"].value),
                               rtol=1e-5, atol=1e-6)
    # per-feed seq_len dict: a feed missing from the dict falls back to
    # the default length instead of crashing (post-review pin)
    shlo2, r2 = export_forward_stablehlo_ex(topo, params,
                                            seq_len={"other": 9})
    assert r2 is None
    assert shlo2["signature"]["inputs"][0]["shape"] == ["b", 16]


def test_non_sequence_ids_parity():
    """integer_value (non-sequence) feeds export as [b, 1] i32 — the
    feeder's shape for plain id inputs."""
    wid = layer.data(name="wid", type=data_type.integer_value(40))
    emb = layer.embedding(input=wid, size=8)
    out = layer.fc(input=emb, size=3, act=activation.Softmax(), name="out")
    topo = Topology(out)
    params = paddle.parameters_create(topo)
    shlo, reason = export_forward_stablehlo_ex(topo, params)
    assert reason is None
    assert shlo["signature"]["inputs"][0] == {
        "feed": "wid", "role": "value", "name": "wid", "dtype": "i32",
        "shape": ["b", 1]}
    exp = jax_export.deserialize(shlo["artifact"])
    iv = np.arange(5, dtype=np.int32).reshape(5, 1)
    got = exp.call(iv)
    want = topo.forward(_pdict(params), {"wid": jnp.asarray(iv)})
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want["out"].value),
                               rtol=1e-5, atol=1e-6)


def test_while_loop_decode_exports_whole():
    """The compact-K beam decode (lax.while_loop early-exit inside)
    exports as ONE module: ids / scores / ticks land as typed results
    and match the live decode bit for bit."""
    from paddle_tpu.models.text import nmt_decode_topology

    V, K = 120, 16
    gen = nmt_decode_topology(src_dict_dim=V, trg_dict_dim=V,
                              word_vector_dim=8, encoder_size=8,
                              decoder_size=8, beam_size=2, max_length=6,
                              cand_k=K, mode="compact", name="m")
    topo = Topology(gen)
    params = topo.init_params(jax.random.PRNGKey(0))
    P = Parameters.from_dict({k: np.asarray(v) for k, v in params.items()})
    shlo, reason = export_forward_stablehlo_ex(topo, P, seq_len=5)
    assert reason is None, reason
    sig = shlo["signature"]
    out_names = [s["name"] for s in sig["outputs"]]
    assert "m_gen:ids" in out_names and "m_gen:scores" in out_names \
        and "m_gen:ticks" in out_names

    exp = jax_export.deserialize(shlo["artifact"])
    B = 3 if sig["symbolic_batch"] else sig["static_batch"]
    r = np.random.RandomState(0)
    src = r.randint(0, V, (B, 5)).astype(np.int32)
    mk = np.ones((B, 5), np.float32)
    cand = np.stack([r.choice(V, K, replace=False)
                     for _ in range(B)]).astype(np.int32)
    cand[~(cand == 1).any(1), 0] = 1          # eos in every row
    arrays = {"src": src, "src:mask": mk,
              "cand": cand.astype(np.float32)}  # declared dense_vector
    got = exp.call(*_feeds_for(sig, arrays))
    outs, ctx = topo.forward(
        params, {"src": Arg(jnp.asarray(src), jnp.asarray(mk)),
                 "cand": Arg(jnp.asarray(cand))}, return_ctx=True)
    by_name = dict(zip(out_names, got))
    np.testing.assert_array_equal(np.asarray(by_name["m_gen:ids"]),
                                  np.asarray(ctx.extras["m_gen:ids"]))
    np.testing.assert_allclose(np.asarray(by_name["m_gen:scores"]),
                               np.asarray(ctx.extras["m_gen:scores"]),
                               rtol=1e-5, atol=1e-5)
    assert int(by_name["m_gen:ticks"]) == int(ctx.extras["m_gen:ticks"])
    # the early-exit loop is in the module: a C-side PJRT host compiles
    # this bytes blob with no Python anywhere
    assert len(shlo["modules"].get("tpu", b"")) > 0


def test_skip_reason_sparse_input():
    sp = layer.data(name="sp",
                    type=data_type.sparse_binary_vector(100, max_ids=8))
    out = layer.fc(input=sp, size=4, act=activation.Softmax(), name="out")
    topo = Topology(out)
    params = paddle.parameters_create(topo)
    shlo, reason = export_forward_stablehlo_ex(topo, params)
    assert shlo is None and "sparse" in reason
    # back-compat wrapper still returns plain None
    assert export_forward_stablehlo(topo, params) is None


def test_skip_reason_params_too_large():
    big = layer.data(name="ids",
                     type=data_type.integer_value_sequence(600000))
    emb = layer.embedding(input=big, size=16)
    pooled = layer.pooling(input=emb, pooling_type=pooling.Avg())
    out = layer.fc(input=pooled, size=4, name="out")
    topo = Topology(out)
    params = paddle.parameters_create(topo)
    shlo, reason = export_forward_stablehlo_ex(topo, params)
    assert shlo is None and "too large" in reason


def test_bundle_meta_carries_signature_and_skip_reason(multi_io_model,
                                                      tmp_path):
    topo, params = multi_io_model
    shlo, _ = export_forward_stablehlo_ex(topo, params)
    buf = _io.BytesIO()
    write_bundle(buf, topo, params, meta={"stablehlo": stablehlo_meta(shlo)})
    buf.seek(0)
    _topo2, _p2, meta = read_bundle(buf)
    sh = meta["stablehlo"]
    assert sh["signature"]["inputs"][0]["name"] == "a"
    # the b64 artifact round-trips to a callable export
    exp = jax_export.deserialize(base64.b64decode(sh["artifact_b64"]))
    x1 = np.zeros((2, 8), np.float32)
    x2 = np.zeros((2, 4), np.float32)
    assert np.asarray(exp.call(x1, x2)[0]).shape == (2, 5)
    # meta is JSON-able end to end (write_bundle would have thrown, but
    # pin it explicitly — the C side parses this very JSON)
    json.dumps(sh["signature"])

    # skip path: reason lands in the meta the C side can introspect
    sp = layer.data(name="sp",
                    type=data_type.sparse_binary_vector(100, max_ids=8))
    out = layer.fc(input=sp, size=4, name="out")
    topo3 = Topology(out)
    p3 = paddle.parameters_create(topo3)
    shlo3, reason3 = export_forward_stablehlo_ex(topo3, p3)
    assert shlo3 is None
    buf = _io.BytesIO()
    write_bundle(buf, topo3, p3, meta={"stablehlo_skip_reason": reason3})
    buf.seek(0)
    _t, _p, meta3 = read_bundle(buf)
    assert "sparse" in meta3["stablehlo_skip_reason"]


# --- per-tick decode step export (r19, docs/serving.md "Step-module
# bundles"): driving the exported step module tick-by-tick to
# completion matches the whole-while_loop export AND live Python decode
# — ids/ticks bit for bit, scores allclose (separately-compiled modules
# accumulate floats in a different order; the r15 whole-loop parity
# test draws the same line) — for beam 1 and 4.

STEP_V, STEP_K, STEP_T, STEP_L = 120, 16, 5, 10


def _step_model(beam, mode, eos_bias=0.25, seed=0):
    """Tiny NMT generation topology with the eos logit nudged so
    hypotheses die at VARIED ticks (per-slot counters genuinely
    diverge; bias tuned so lengths span 2..max_length)."""
    import jax.numpy as jnp

    from paddle_tpu.models.text import nmt_decode_topology

    gen = nmt_decode_topology(
        src_dict_dim=STEP_V, trg_dict_dim=STEP_V, word_vector_dim=8,
        encoder_size=8, decoder_size=8, beam_size=beam,
        max_length=STEP_L, cand_k=STEP_K, mode=mode, name="m")
    topo = Topology(gen)
    params = topo.init_params(jax.random.PRNGKey(seed))
    b = np.array(params["_m_out.wbias"])
    b[..., 1] += eos_bias
    params["_m_out.wbias"] = jnp.asarray(b)
    P = Parameters.from_dict({k: np.asarray(v) for k, v in params.items()})
    return topo, params, P


def _step_requests(n, mode, seed=3):
    r = np.random.RandomState(seed)
    reqs = []
    for _ in range(n):
        src = r.randint(0, STEP_V, (STEP_T,)).astype(np.int32)
        feeds = {"src": src, "src:mask": np.ones(STEP_T, np.float32)}
        if mode != "dense":
            cand = r.choice(STEP_V, STEP_K, replace=False).astype(np.int32)
            if not (cand == 1).any():
                cand[0] = 1                      # eos in every row
            feeds["cand"] = cand.astype(np.float32)
        reqs.append(feeds)
    return reqs


def _live_decode(topo, params, feeds_list):
    """Live Python decode of the request batch (ctx extras)."""
    import jax.numpy as jnp

    src = np.stack([f["src"] for f in feeds_list])
    mk = np.stack([f["src:mask"] for f in feeds_list])
    feeds = {"src": Arg(jnp.asarray(src), jnp.asarray(mk))}
    if "cand" in feeds_list[0]:
        cand = np.stack([f["cand"] for f in feeds_list]).astype(np.int32)
        feeds["cand"] = Arg(jnp.asarray(cand))
    _outs, ctx = topo.forward(params, feeds, return_ctx=True)
    return (np.asarray(ctx.extras["m_gen:ids"]),
            np.asarray(ctx.extras["m_gen:scores"]),
            int(ctx.extras["m_gen:ticks"]))


@pytest.mark.parametrize("beam,mode,eos_bias",
                         [(1, "dense", 0.3), (4, "compact", 0.25)])
def test_step_export_tick_parity(beam, mode, eos_bias):
    """Satellite pin (ISSUE 14): S requests co-admitted into the slot
    array and ticked to completion through the step module reproduce
    the whole-loop module AND live decode — ids/ticks exact, scores
    allclose — for beam 1 (dense path) and beam 4 (compact-K path)."""
    S = 4
    topo, params, P = _step_model(beam, mode, eos_bias=eos_bias)
    res, reason = export_decode_step_stablehlo_ex(topo, P, seq_len=STEP_T,
                                                  slots=S)
    assert reason is None, reason
    whole, wreason = export_forward_stablehlo_ex(topo, P, seq_len=STEP_T,
                                                 static_batch=S)
    assert wreason is None, wreason
    sig = res["signature"]
    assert sig["beam"] == beam and sig["slots"] == S
    assert [e["name"] for e in sig["state"]][-2:] == ["state:t",
                                                      "state:cap"]
    assert all(e["shape"][0] == "b" for e in sig["state"] + sig["enc"])

    reqs = _step_requests(S, mode)
    # drain mode + S requests = ONE co-admitted batch, the whole-loop
    # shape; per-slot counters still diverge as hypotheses die early
    drv = StepDecodeDriver(res, drain=True)
    handles = [drv.submit(f) for f in reqs]
    drv.run()
    assert drv.admissions == {"fresh": S, "mid_batch": 0}

    ids_live, sc_live, ticks_live = _live_decode(topo, params, reqs)
    from jax import export as jax_export
    wexp = jax_export.deserialize(whole["artifact"])
    arrays = {"src": np.stack([f["src"] for f in reqs]),
              "src:mask": np.stack([f["src:mask"] for f in reqs])}
    if mode != "dense":
        arrays["cand"] = np.stack([f["cand"] for f in reqs])
    wout = wexp.call(*[arrays[s["name"]]
                       for s in whole["signature"]["inputs"]])
    wby = dict(zip([s["name"] for s in whole["signature"]["outputs"]],
                   wout))
    ids_w = np.asarray(wby["m_gen:ids"])
    sc_w = np.asarray(wby["m_gen:scores"])

    got_ids = np.stack([h.ids for h in sorted(handles,
                                              key=lambda h: h.slot)])
    got_sc = np.stack([h.scores for h in sorted(handles,
                                                key=lambda h: h.slot)])
    np.testing.assert_array_equal(got_ids, ids_w)
    np.testing.assert_array_equal(got_ids, ids_live)
    np.testing.assert_allclose(got_sc, sc_w, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_sc, sc_live, rtol=1e-5, atol=1e-5)
    # ticks: the whole loop runs until EVERY sample is dead — its tick
    # count is the max of the per-slot counters
    assert max(h.ticks for h in handles) == int(wby["m_gen:ticks"]) \
        == ticks_live
    # the per-slot counters genuinely diverged (the eos bias is tuned
    # for varied lengths — without divergence this test would never
    # exercise the per-slot t path)
    assert len({h.ticks for h in handles}) > 1


def test_step_mid_decode_admission_matches_solo_decode():
    """Mid-decode slot admission never changes results: a request
    admitted into a freed slot while other slots are mid-decode
    produces exactly the ids its solo decode produces (the r15
    'scheduling policy never changes results' property, now on the
    real model), and nonzero mid_batch admissions actually happened."""
    topo, params, P = _step_model(2, "compact")
    res, reason = export_decode_step_stablehlo_ex(topo, P, seq_len=STEP_T,
                                                  slots=2)
    assert reason is None, reason
    reqs = _step_requests(6, "compact")
    drv = StepDecodeDriver(res, drain=False)
    handles = [drv.submit(f) for f in reqs]
    drv.run()
    assert drv.admissions["mid_batch"] >= 1, \
        "varied decode lengths should have freed a slot mid-batch"
    for i, h in enumerate(handles):
        solo = StepDecodeDriver(res, drain=False)
        sh = solo.submit(reqs[i])
        solo.run()
        np.testing.assert_array_equal(h.ids, sh.ids)
        np.testing.assert_array_equal(h.tokens, sh.tokens)
        assert h.ticks == sh.ticks
        # and the solo decode matches live single-request decode
        ids_live, _sc, ticks_live = _live_decode(topo, params, [reqs[i]])
        np.testing.assert_array_equal(sh.ids[None], ids_live)
        assert sh.ticks == ticks_live


def test_step_per_slot_cap_matches_scheduler_truncation():
    """Carry-over pin (ISSUE 18): submit(max_new=k) rides the module's
    own carry bound ("state:cap") — the capped slot goes inert at k
    ticks with its streamed tokens EXACTLY the first k of the uncapped
    decode (scheduler-side truncation parity), while uncapped
    neighbors are bit-untouched by the neighbor's cap."""
    topo, params, P = _step_model(2, "compact")
    res, reason = export_decode_step_stablehlo_ex(topo, P, seq_len=STEP_T,
                                                  slots=2)
    assert reason is None, reason
    assert [e["name"] for e in res["signature"]["state"]][-1] == \
        "state:cap"
    reqs = _step_requests(3, "compact")

    # uncapped reference run (the scheduler-side-truncation baseline)
    ref = StepDecodeDriver(res, drain=False)
    rh = [ref.submit(f) for f in reqs]
    ref.run()
    assert rh[0].ticks >= 2, "need a decode long enough to cap short"
    k = rh[0].ticks - 1

    drv = StepDecodeDriver(res, drain=False)
    handles = [drv.submit(reqs[0], max_new=k),
               drv.submit(reqs[1]),
               drv.submit(reqs[2])]
    drv.run()
    capped = handles[0]
    # the module's bound, not the scheduler's: inert at exactly k ticks
    assert capped.ticks == k
    np.testing.assert_array_equal(capped.tokens, rh[0].tokens[:k])
    # neighbors never see the cap
    for h, r in zip(handles[1:], rh[1:]):
        assert h.ticks == r.ticks
        np.testing.assert_array_equal(h.ids, r.ids)
        np.testing.assert_array_equal(h.tokens, r.tokens)
    # a cap ABOVE the natural length is a no-op (clips to max_length)
    roomy = StepDecodeDriver(res, drain=False)
    h2 = roomy.submit(reqs[0], max_new=STEP_L + 7)
    roomy.run()
    assert h2.ticks == rh[0].ticks
    np.testing.assert_array_equal(h2.ids, rh[0].ids)


def test_step_skip_reason_recorded_not_silent(tmp_path):
    """Satellite: a generation topology whose decode cannot
    step-export records WHY in meta.stablehlo_step_skip_reason
    (mirroring r15's stablehlo_skip_reason) instead of silently
    emitting a whole-loop-only bundle; servable decodes embed
    meta.stablehlo_step with the carry signature."""
    from paddle_tpu.io.merged_model import merge_model
    from paddle_tpu.layers.recurrent_group import BeamSearchControlCallbacks
    from paddle_tpu.models.text import nmt_decode_topology

    # Python beam-control callbacks cannot ride a compiled step module
    def gen_with_hooks():
        g = nmt_decode_topology(
            src_dict_dim=STEP_V, trg_dict_dim=STEP_V, word_vector_dim=8,
            encoder_size=8, decoder_size=8, beam_size=2, max_length=6,
            cand_k=STEP_K, mode="compact", name="m")
        g.cfg["ctrl_callbacks"] = BeamSearchControlCallbacks(
            norm_or_drop=lambda ids, scores, lengths: scores)
        return g

    out = str(tmp_path / "hooks.ptpu")
    merge_model(config=gen_with_hooks, output=out,
                export_seq_len=STEP_T)
    meta = read_bundle_meta(out)
    assert "stablehlo_step" not in meta
    assert "beam-control callbacks" in meta["stablehlo_step_skip_reason"]
    # the whole-loop module still exported: drain-batch serving works
    assert "stablehlo" in meta

    # servable decode: the step meta rides next to the r15 signature
    def gen_plain():
        return nmt_decode_topology(
            src_dict_dim=STEP_V, trg_dict_dim=STEP_V, word_vector_dim=8,
            encoder_size=8, decoder_size=8, beam_size=2, max_length=6,
            cand_k=STEP_K, mode="compact", name="m")

    out2 = str(tmp_path / "plain.ptpu")
    merge_model(config=gen_plain, output=out2, export_seq_len=STEP_T,
                export_slots=4)
    meta2 = read_bundle_meta(out2)
    st = meta2["stablehlo_step"]
    assert st["slots"] == 4
    assert st["signature"]["state"][0]["name"].startswith("state:mem:")
    assert st["init_artifact_b64"] and st["step_artifact_b64"]
    assert "step_mlir_tpu_b64" in st and "init_mlir_cpu_b64" in st
    json.dumps(st["signature"])     # the C side parses this very JSON
    # a non-generation topology records NEITHER step meta nor a reason
    # (there is no decode to fall back from)
    x = layer.data(name="x", type=data_type.dense_vector(4))
    o = layer.fc(input=x, size=3, name="out")
    t3 = Topology(o)
    p3 = paddle.parameters_create(t3)
    out3 = str(tmp_path / "dense.ptpu")
    with open(out3, "wb") as f:
        write_bundle(f, t3, p3, meta={})
    m3 = read_bundle_meta(out3)
    assert "stablehlo_step" not in m3 \
        and "stablehlo_step_skip_reason" not in m3


def test_step_export_meta_roundtrip(tmp_path):
    """stablehlo_step_meta -> bundle -> read_bundle_meta -> driver:
    the b64 on-disk form rebuilds a working StepDecodeDriver."""
    from paddle_tpu.step_decode import driver_from_bundle_meta

    topo, params, P = _step_model(1, "dense")
    res, reason = export_decode_step_stablehlo_ex(topo, P, seq_len=STEP_T,
                                                  slots=2)
    assert reason is None, reason
    out = str(tmp_path / "g.ptpu")
    with open(out, "wb") as f:
        write_bundle(f, topo, P,
                     meta={"stablehlo_step": stablehlo_step_meta(res)})
    meta = read_bundle_meta(out)
    drv = driver_from_bundle_meta(meta["stablehlo_step"])
    reqs = _step_requests(2, "dense")
    hs = [drv.submit(f) for f in reqs]
    drv.run()
    ids_live, _sc, _t = _live_decode(topo, params, reqs)
    got = np.stack([h.ids for h in sorted(hs, key=lambda h: h.slot)])
    np.testing.assert_array_equal(got, ids_live)


def test_legacy_single_dense_keys_preserved():
    """Pre-r15 consumers (the 1xf32 runner shim, old tooling) read
    input/output/input_dim off the export dict — still there for the
    single-dense-input shape."""
    x = layer.data(name="x", type=data_type.dense_vector(7))
    out = layer.fc(input=x, size=3, act=activation.Softmax(), name="out")
    topo = Topology(out)
    params = paddle.parameters_create(topo)
    shlo = export_forward_stablehlo(topo, params)
    assert shlo["input"] == "x" and shlo["output"] == "out"
    assert shlo["input_dim"] == 7
    assert shlo["mlir_tpu"] == shlo["modules"]["tpu"]
