"""Sequence packing (ISSUE 6, docs/packing.md): packed-feed mode end to
end — the DataFeeder packing plan, segment-aware recurrent/attention/cost
layers, fused-kernel reset vectors, per-sequence evaluator counting — and
THE acceptance suite: a packed run and an unpacked run over the same
sample stream produce allclose losses, bit-identical evaluator totals and
identical per-sequence decode outputs, including snapshot/resume mid-pass
in packed mode; the unpacked train-step jaxpr is untouched.

Also pins the ISSUE 6 satellites: the segment_sum rewrite of
_segment_pool against the one-hot reference, bucket_rounding, the fused
LSTM/GRU mask/reset edge cases (interpret-mode vs scan-path), the
sort_within_buffer reader window with checkpointable resume, and the
packing-ready NMT (models/text.nmt_packed_cost) under one three-slot plan.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import activation, data_type, evaluator, layer, networks, \
    optimizer
from paddle_tpu.core.arg import Arg, packed_segment_count, \
    segment_start_resets
from paddle_tpu.core.layer import layer_name_scope
from paddle_tpu.data_type import SeqType
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.reader.decorator import checkpointable, sort_within_buffer
from paddle_tpu.trainer import event as v2_event
from paddle_tpu.trainer.feeder import DataFeeder, FeedBufferPool, \
    _bucket, _pack_plan
from paddle_tpu.trainer.trainer import SGD
from paddle_tpu.utils.error import Error

V, C = 40, 5
N_SAMPLES = 48
BATCH = 16


def _samples(seed=0, n=N_SAMPLES, lo=2, hi=12):
    rs = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        t = int(rs.randint(lo, hi))
        out.append((rs.randint(0, V, t).tolist(),
                    rs.randint(0, C, t).tolist()))
    return out


SAMPLES = _samples()


def _reader():
    for s in SAMPLES:
        yield s


def _make_tagger(cell="gru"):
    """Tiny packable tagger: emb -> recurrent -> fc softmax -> per-token
    xent; token- and sequence-level error evaluators."""
    with layer_name_scope():
        w = layer.data(name="w", type=data_type.integer_value_sequence(V))
        l = layer.data(name="l", type=data_type.integer_value_sequence(C))
        emb = layer.embedding(input=w, size=8, name="emb")
        if cell == "gru":
            h = networks.simple_gru(input=emb, size=8, name="g")
        else:
            h = networks.simple_lstm(input=emb, size=8, name="g")
        out = layer.fc(input=h, size=C, act=activation.Softmax(), name="out")
        cost = layer.classification_cost(input=out, label=l, name="cost")
    params = paddle.parameters_create(paddle.Topology(cost))
    evs = {"err": evaluator.classification_error(input="out", label="l"),
           "serr": evaluator.seq_classification_error(input="out",
                                                      label="l")}
    return SGD(cost=cost, parameters=params,
               update_equation=optimizer.Adam(learning_rate=1e-2),
               evaluators=evs)


def _run(pack, cell="gru", num_passes=2, **train_kw):
    t = _make_tagger(cell)
    costs = []

    def handler(ev):
        if isinstance(ev, v2_event.EndIteration):
            costs.append(float(ev.cost))

    t.train(paddle.batch(_reader, BATCH), num_passes=num_passes,
            event_handler=handler, pipeline_depth=0, pack_sequences=pack,
            **train_kw)
    params = {k: np.asarray(t.parameters.get(k))
              for k in t.parameters.names()}
    accs = {k: {kk: np.asarray(vv) for kk, vv in ev._acc.items()}
            for k, ev in t.evaluators.items()}
    return costs, accs, params, t


# --- feeder packing unit behavior -----------------------------------------

def test_pack_plan_multi_slot_alignment_and_determinism():
    lengths = {"a": [5, 3, 7, 2, 6], "b": [4, 4, 7, 1, 5]}
    caps = {"a": 8, "b": 8}
    plan = _pack_plan(lengths, caps)
    # every sample appears exactly once
    flat = sorted(i for row in plan for i in row)
    assert flat == list(range(5))
    # a sample fits a row only if it fits in EVERY slot
    for row in plan:
        for s in lengths:
            assert sum(lengths[s][i] for i in row) <= caps[s], (s, row)
    assert plan == _pack_plan(lengths, caps)      # deterministic


def test_feeder_packs_rows_with_seg_ids():
    feeder = DataFeeder([("w", data_type.integer_value_sequence(V)),
                         ("l", data_type.integer_value_sequence(C))],
                        pack_sequences=True, pack_row_rounding=1)
    batch = [([1, 2, 3], [0, 1, 2]), ([4, 5], [1, 1]), ([6], [2]),
             ([7, 8, 9, 10], [3, 3, 3, 3])]
    feeds = feeder(batch)
    w, l = feeds["w"], feeds["l"]
    assert w.seg_ids is not None and l.seg_ids is not None
    # the plan is shared: identical mask and seg layout in every slot
    np.testing.assert_array_equal(np.asarray(w.mask), np.asarray(l.mask))
    np.testing.assert_array_equal(np.asarray(w.seg_ids),
                                  np.asarray(l.seg_ids))
    # fewer rows than samples, all real tokens preserved in order
    assert w.value.shape[0] < len(batch)
    seg = np.asarray(w.seg_ids)
    mask = np.asarray(w.mask)
    assert (seg[mask > 0] >= 0).all() and (seg[mask == 0] == -1).all()
    # tokens of each sample are contiguous under one (row, seg) pair
    val = np.asarray(w.value)
    got = {}
    for r in range(val.shape[0]):
        for s in range(seg[r].max() + 1):
            got[(r, s)] = val[r][seg[r] == s].tolist()
    plan = feeder.last_pack_plan
    for r, members in enumerate(plan):
        for s, i in enumerate(members):
            assert got[(r, s)] == batch[i][0], (r, s, i)
    # total sequence count == sample count (the loss denominator)
    assert float(packed_segment_count(jnp.asarray(seg))) == len(batch)


def test_feeder_pack_rejects_zero_length_samples():
    """Review pin: a zero-length sample would occupy a segment index with
    no timesteps; the seg_ids-derived sequence count would silently drop
    a trailing empty segment, so the feeder refuses empties loudly."""
    feeder = DataFeeder([("w", data_type.integer_value_sequence(V))],
                        pack_sequences=True)
    with pytest.raises(Error, match="zero-length"):
        feeder([([1, 2],), ([],)])


def test_feeder_pack_rejects_unpackable_slots():
    with pytest.raises(Error):
        DataFeeder([("w", data_type.integer_value_sequence(V)),
                    ("y", data_type.integer_value(C))],   # non-sequence
                   pack_sequences=True)
    with pytest.raises(Error):
        DataFeeder([("w", data_type.integer_value_sub_sequence(V))],
                   pack_sequences=True)


def test_pack_pad_fraction_packed_label_and_exemplar_gauge():
    reg = obs_metrics.default_registry
    hist = reg.histogram("paddle_feed_pad_fraction",
                         labels=("feed", "packed"))
    child = hist.labels(feed="pw", packed="1")
    before = (child.count, child.sum)
    feeder = DataFeeder([("pw", data_type.integer_value_sequence(V))],
                        pack_sequences=True, pack_max_len=8,
                        pack_row_rounding=1)
    # 12 real tokens in 2 rows of 8 -> pad fraction 0.25
    feeder([([1] * 5,), ([2] * 3,), ([3] * 4,)])
    assert child.count - before[0] == 1
    assert child.sum - before[1] == pytest.approx(0.25)
    gauge = reg.gauge("paddle_feed_padded_len", labels=("feed", "packed"))
    assert gauge.labels(feed="pw", packed="1").value == 8


def test_bucket_rounding_satellite():
    # the ISSUE 6 case: T=65 pads to 128 under power-of-two (~49% waste)
    assert _bucket(65, True) == 128
    assert _bucket(65, True, rounding=8) == 72
    assert _bucket(64, True, rounding=8) == 64
    assert _bucket(1, True, rounding=8) == 8
    feeder = DataFeeder([("w", data_type.integer_value_sequence(V))],
                        bucket_rounding=8)
    arg = feeder([([1] * 65,), ([2] * 3,)])["w"]
    assert arg.value.shape == (2, 72)
    gauge = obs_metrics.default_registry.gauge(
        "paddle_feed_padded_len", labels=("feed", "packed"))
    assert gauge.labels(feed="w", packed="0").value == 72


def test_pack_row_rounding_bounds_feed_shapes():
    """Review pin (r11): the plan's natural row count varies batch to
    batch, and every distinct [R, T] feed shape recompiles the jitted
    train step — pack_row_rounding (default 8) pads R up with inert
    filler rows (mask 0, seg -1) so the compiled-shape set stays
    bounded, the same churn _bucket prevents on T."""
    types = [("w", data_type.integer_value_sequence(V))]
    feeder = DataFeeder(types, pack_sequences=True, pack_max_len=8)
    rs = np.random.RandomState(3)
    for _ in range(6):
        n = int(rs.randint(5, 40))
        batch = [([1] * int(rs.randint(1, 8)),) for _ in range(n)]
        a = feeder(batch)["w"]
        R = a.value.shape[0]
        assert R % 8 == 0 and R >= len(feeder.last_pack_plan)
        seg, mask = np.asarray(a.seg_ids), np.asarray(a.mask)
        for r in range(len(feeder.last_pack_plan), R):
            assert (mask[r] == 0).all() and (seg[r] == -1).all()
        # filler rows are invisible to the loss denominator
        assert float(packed_segment_count(jnp.asarray(seg))) == n
    # pack_row_rounding=1 keeps the plan's exact R (unit-scale pins)
    exact = DataFeeder(types, pack_sequences=True, pack_max_len=8,
                       pack_row_rounding=1)
    assert exact([([1, 2, 3],), ([4, 5],)])["w"].value.shape[0] == \
        len(exact.last_pack_plan)


def test_feeder_packed_pooled_matches_fresh():
    types = [("w", data_type.integer_value_sequence(V)),
             ("l", data_type.integer_value_sequence(C))]
    batch = [s for s in SAMPLES[:10]]
    plain = DataFeeder(types, pack_sequences=True)(batch)
    pooled = DataFeeder(types, pack_sequences=True,
                        buffers=FeedBufferPool(), rotate_buffers=2)
    # a denser batch first, so reused buffers hold stale steps to clear
    pooled([s for s in SAMPLES[10:20]])
    for _ in range(3):          # rotated generations stay correct
        got = pooled(batch)
    for k in plain:
        np.testing.assert_array_equal(np.asarray(plain[k].value),
                                      np.asarray(got[k].value))
        np.testing.assert_array_equal(np.asarray(plain[k].mask),
                                      np.asarray(got[k].mask))
        np.testing.assert_array_equal(np.asarray(plain[k].seg_ids),
                                      np.asarray(got[k].seg_ids))


# --- segment helpers ------------------------------------------------------

def test_segment_start_resets_forward_and_reverse():
    seg = jnp.asarray([[0, 0, 1, 1, 1, -1],
                       [0, 1, 2, -1, -1, -1]], jnp.int32)
    mask = (seg >= 0).astype(jnp.float32)
    fwd = np.asarray(segment_start_resets(seg, mask))
    np.testing.assert_array_equal(fwd, [[1, 0, 1, 0, 0, 0],
                                        [1, 1, 1, 0, 0, 0]])
    rev = np.asarray(segment_start_resets(seg, mask, reverse=True))
    np.testing.assert_array_equal(rev, [[0, 1, 0, 0, 1, 0],
                                        [1, 1, 1, 0, 0, 0]])


# --- _segment_pool segment_sum rewrite pinned to the one-hot path ---------

@pytest.mark.parametrize("how", ["sum", "average", "squarerootn", "max"])
def test_segment_pool_matches_onehot_exactly(how):
    from paddle_tpu.layers.sequence import _segment_pool, \
        _segment_pool_onehot

    rs = np.random.RandomState(3)
    B, T, D, S = 3, 9, 4, 5
    # integer-valued floats: every summation order is exact, so the pin
    # can be bit-identical rather than allclose
    v = jnp.asarray(rs.randint(-6, 7, (B, T, D)), jnp.float32)
    seg = np.full((B, T), -1, np.int32)
    seg[0, :4] = [0, 0, 1, 1]
    seg[1, :7] = [0, 1, 1, 1, 2, 3, 3]
    seg[2, :2] = [0, 0]
    mask = (seg >= 0).astype(np.float32)
    seg, mask = jnp.asarray(seg), jnp.asarray(mask)
    want = _segment_pool_onehot(v, mask, seg, S, how)
    got = _segment_pool(v, mask, seg, S, how)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("how", ["sum", "average", "squarerootn", "max"])
def test_segment_pool_random_floats_allclose(how):
    from paddle_tpu.layers.sequence import _segment_pool, \
        _segment_pool_onehot

    rs = np.random.RandomState(4)
    B, T, S = 2, 8, 4
    v = jnp.asarray(rs.randn(B, T, 3), jnp.float32)
    seg = jnp.asarray(rs.randint(0, S, (B, T)), jnp.int32)
    mask = jnp.asarray((rs.rand(B, T) > 0.2).astype(np.float32))
    want = _segment_pool_onehot(v, mask, seg, S, how)
    got = _segment_pool(v, mask, seg, S, how)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# --- fused kernel mask/reset edge cases (interpret vs scan bit-compare) ---

def _lstm_scan_ref(x4, W, b, mask, reset=None, reverse=False):
    from paddle_tpu import activation as am
    from paddle_tpu.layers.recurrent import lstm_cell

    TANH = am.resolve("tanh")
    B, T, H4 = x4.shape
    H = H4 // 4
    h = jnp.zeros((B, H))
    c = jnp.zeros((B, H))
    hs = [None] * T
    cs = [None] * T
    order = range(T - 1, -1, -1) if reverse else range(T)
    for t in order:
        if reset is not None:
            p = (1.0 - reset[:, t])[:, None]
            h, c = p * h, p * c
        hn, cn = lstm_cell(x4[:, t], h, c, W, b, TANH, TANH, H)
        m = mask[:, t][:, None]
        h = m * hn + (1 - m) * h
        c = m * cn + (1 - m) * c
        hs[t], cs[t] = h, c
    return jnp.stack(hs, 1), jnp.stack(cs, 1)


def _gru_scan_ref(x3, Wg, Wc, b, mask, reset=None, reverse=False):
    from paddle_tpu import activation as am
    from paddle_tpu.layers.recurrent import gru_cell

    SIG, TANH = am.resolve("sigmoid"), am.resolve("tanh")
    B, T, H3 = x3.shape
    H = H3 // 3
    h = jnp.zeros((B, H))
    hs = [None] * T
    order = range(T - 1, -1, -1) if reverse else range(T)
    for t in order:
        if reset is not None:
            h = (1.0 - reset[:, t])[:, None] * h
        hn = gru_cell(x3[:, t], h, Wg, Wc, b, SIG, TANH, H)
        m = mask[:, t][:, None]
        h = m * hn + (1 - m) * h
        hs[t] = h
    return jnp.stack(hs, 1)


def _edge_masks(B, T, rs):
    """The packing-relevant mask edge cases: all-dead row, mask flipping
    mid-row (dead gap between two live spans), plus a plain ragged row."""
    mask = np.ones((B, T), np.float32)
    mask[0, :] = 0.0                       # all-dead row
    mask[1, T // 3: 2 * T // 3] = 0.0      # flips 1 -> 0 -> 1 mid-row
    mask[2, T - 3:] = 0.0                  # ragged tail
    reset = np.zeros((B, T), np.float32)
    reset[:, 0] = 1.0
    reset[1, 2 * T // 3] = 1.0             # segment starts after the gap
    reset[2, 4] = 1.0
    reset[3, T // 2] = 1.0
    return jnp.asarray(mask), jnp.asarray(reset * mask)


@pytest.mark.parametrize("reverse", [False, True])
def test_fused_lstm_mask_edges_with_reset(reverse):
    from paddle_tpu.kernels.lstm import fused_lstm

    rs = np.random.RandomState(7)
    B, T, H = 8, 12, 128
    x4 = jnp.asarray(rs.randn(B, T, 4 * H) * 0.3, jnp.float32)
    W = jnp.asarray(rs.randn(H, 4 * H) * 0.1, jnp.float32)
    b = jnp.asarray(rs.randn(7 * H) * 0.1, jnp.float32)
    mask, reset = _edge_masks(B, T, rs)
    want_h, want_c = _lstm_scan_ref(x4, W, b, mask, reset, reverse=reverse)
    # the layer's reverse recipe: flip inputs (incl. the reset vector),
    # run the forward kernel, flip back
    xx, mm, rr = (jnp.flip(x4, 1), jnp.flip(mask, 1), jnp.flip(reset, 1)) \
        if reverse else (x4, mask, reset)
    hs, cs = fused_lstm(xx, W, b, mm, rr, True)
    if reverse:
        hs, cs = jnp.flip(hs, 1), jnp.flip(cs, 1)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(want_h),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cs), np.asarray(want_c),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("reverse", [False, True])
def test_fused_gru_mask_edges_with_reset(reverse):
    from paddle_tpu.kernels.gru import fused_gru

    rs = np.random.RandomState(8)
    B, T, H = 8, 12, 128
    x3 = jnp.asarray(rs.randn(B, T, 3 * H) * 0.3, jnp.float32)
    Wg = jnp.asarray(rs.randn(H, 2 * H) * 0.1, jnp.float32)
    Wc = jnp.asarray(rs.randn(H, H) * 0.1, jnp.float32)
    b = jnp.asarray(rs.randn(3 * H) * 0.1, jnp.float32)
    mask, reset = _edge_masks(B, T, rs)
    want = _gru_scan_ref(x3, Wg, Wc, b, mask, reset, reverse=reverse)
    xx, mm, rr = (jnp.flip(x3, 1), jnp.flip(mask, 1), jnp.flip(reset, 1)) \
        if reverse else (x3, mask, reset)
    hs = fused_gru(xx, Wg, Wc, b, mm, rr, True)
    if reverse:
        hs = jnp.flip(hs, 1)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_lstm_reset_grads_match_scan():
    from paddle_tpu.kernels.lstm import fused_lstm

    rs = np.random.RandomState(9)
    B, T, H = 8, 12, 128
    x4 = jnp.asarray(rs.randn(B, T, 4 * H) * 0.3, jnp.float32)
    W = jnp.asarray(rs.randn(H, 4 * H) * 0.1, jnp.float32)
    b = jnp.asarray(rs.randn(7 * H) * 0.1, jnp.float32)
    mask, reset = _edge_masks(B, T, rs)

    def loss_ref(x4, W, b):
        hs, cs = _lstm_scan_ref(x4, W, b, mask, reset)
        return (hs ** 2).sum() + 0.5 * (cs ** 2).sum()

    def loss_fused(x4, W, b):
        hs, cs = fused_lstm(x4, W, b, mask, reset, True)
        return (hs ** 2).sum() + 0.5 * (cs ** 2).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x4, W, b)
    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x4, W, b)
    for name, a, b_ in zip(("dx4", "dW", "db"), gr, gf):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_fused_gru_reset_grads_match_scan():
    from paddle_tpu.kernels.gru import fused_gru

    rs = np.random.RandomState(10)
    B, T, H = 8, 12, 128
    x3 = jnp.asarray(rs.randn(B, T, 3 * H) * 0.3, jnp.float32)
    Wg = jnp.asarray(rs.randn(H, 2 * H) * 0.1, jnp.float32)
    Wc = jnp.asarray(rs.randn(H, H) * 0.1, jnp.float32)
    b = jnp.asarray(rs.randn(3 * H) * 0.1, jnp.float32)
    mask, reset = _edge_masks(B, T, rs)

    def loss_ref(x3, Wg, Wc, b):
        return (_gru_scan_ref(x3, Wg, Wc, b, mask, reset) ** 2).sum()

    def loss_fused(x3, Wg, Wc, b):
        return (fused_gru(x3, Wg, Wc, b, mask, reset, True) ** 2).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x3, Wg, Wc, b)
    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(x3, Wg, Wc, b)
    for name, a, b_ in zip(("dx3", "dWg", "dWc", "db"), gr, gf):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


# --- attention segment masks ----------------------------------------------

def _attention_topo(causal):
    with layer_name_scope():
        w = layer.data(name="w", type=data_type.integer_value_sequence(V))
        l = layer.data(name="l", type=data_type.integer_value_sequence(C))
        emb = layer.embedding(input=w, size=8, name="emb")
        att = layer.multi_head_attention(query=emb, size=8, num_heads=2,
                                         causal=causal, name="att")
        out = layer.fc(input=att, size=C, act=activation.Softmax(),
                       name="out")
        cost = layer.classification_cost(input=out, label=l, name="cost")
    return paddle.Topology(cost)


@pytest.mark.parametrize("causal", [False, True])
def test_attention_segment_mask_matches_per_sequence(causal):
    """Self-attention over a packed row equals attention over each
    sequence in its own row: packed rows never attend across segments."""
    topo = _attention_topo(causal)
    params = topo.init_params(jax.random.PRNGKey(0))
    types = topo.data_type()
    feeding = {"w": 0, "l": 1}
    batch = SAMPLES[:6]
    f_pack = DataFeeder(types, feeding, pack_sequences=True)
    feeds_p = f_pack(batch)
    outs_p = topo.forward(params, feeds_p)
    val_p = np.asarray(outs_p["out"].value)
    seg = np.asarray(feeds_p["w"].seg_ids)
    f_pad = DataFeeder(types, feeding)
    feeds_u = f_pad(batch)
    outs_u = topo.forward(params, feeds_u)
    val_u = np.asarray(outs_u["out"].value)
    for r, members in enumerate(f_pack.last_pack_plan):
        for s, i in enumerate(members):
            idx = np.flatnonzero(seg[r] == s)
            t = len(batch[i][0])
            assert idx.size == t
            np.testing.assert_allclose(val_p[r, idx], val_u[i, :t],
                                       rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def devices():
    d = jax.devices()
    assert len(d) >= 8, "conftest must provide 8 virtual devices"
    return d


@pytest.mark.parametrize("backend", ["ring", "ulysses"])
def test_sp_backends_segment_mask_matches_reference(devices, backend):
    from jax.sharding import Mesh
    from paddle_tpu.parallel.ring_attention import (reference_attention,
                                                    ring_attention,
                                                    ulysses_attention)

    mesh = Mesh(np.asarray(devices[:4]).reshape(4), ("sp",))
    rs = np.random.RandomState(11)
    B, T, H, D = 2, 32, 4, 8
    q = jnp.asarray(rs.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rs.randn(B, T, H, D), jnp.float32)
    v = jnp.asarray(rs.randn(B, T, H, D), jnp.float32)
    seg = np.full((B, T), -1, np.int32)
    seg[0, :20] = [0] * 9 + [1] * 6 + [2] * 5
    seg[1, :32] = [0] * 15 + [1] * 17
    seg = jnp.asarray(seg)
    want = reference_attention(q, k, v, causal=True, seg_q=seg, seg_kv=seg)
    fn = ring_attention if backend == "ring" else ulysses_attention
    got = fn(q, k, v, mesh, axis_name="sp", causal=True, seg_q=seg,
             seg_kv=seg)
    # padding queries (seg -1) attend only padding; compare valid rows
    valid = np.asarray(seg) >= 0
    np.testing.assert_allclose(np.asarray(got)[valid], np.asarray(want)[valid],
                               rtol=2e-4, atol=2e-5)


# --- THE acceptance suite: packed == unpacked trajectory -------------------

@pytest.mark.parametrize("cell", ["gru", "lstm"])
def test_packed_trajectory_matches_unpacked(cell):
    """Same sample stream, packed vs padded feed: allclose per-batch
    losses, BIT-identical evaluator totals (token and sequence level),
    allclose final parameters."""
    c0, a0, p0, _ = _run(False, cell)
    c1, a1, p1, _ = _run(True, cell)
    assert len(c0) == len(c1) == 6
    np.testing.assert_allclose(c0, c1, rtol=2e-4, atol=2e-5)
    for name in a0:
        for k in a0[name]:
            np.testing.assert_array_equal(a0[name][k], a1[name][k],
                                          err_msg=f"{name}/{k}")
    for k in p0:
        np.testing.assert_allclose(p0[k], p1[k], rtol=2e-4, atol=2e-5,
                                   err_msg=k)


def test_packed_loss_counts_sequences_not_rows():
    """One batch, very ragged: the packed feed has fewer rows, but the
    loss normalizes by sequence count, matching the unpacked mean."""
    t = _make_tagger()
    topo = t.topology
    params = {k: jnp.asarray(v) for k, v in t.parameters.as_dict().items()}
    loss = topo.loss_fn("cost")
    batch = SAMPLES[:12]
    feeding = {"w": 0, "l": 1}
    f_pad = DataFeeder(topo.data_type(), feeding)
    f_pack = DataFeeder(topo.data_type(), feeding, pack_sequences=True)
    feeds_u, feeds_p = f_pad(batch), f_pack(batch)
    assert feeds_p["w"].value.shape[0] < feeds_u["w"].value.shape[0]
    cu = float(loss(params, feeds_u, training=False)[0])
    cp = float(loss(params, feeds_p, training=False)[0])
    assert cu == pytest.approx(cp, rel=1e-5)


def test_packed_decode_outputs_identical(tmp_path):
    """Greedy per-sequence decode after training: the packed-trained and
    unpacked-trained parameters emit IDENTICAL token sequences for every
    sample (the discrete-output equivalence bar)."""
    _, _, p0, t0 = _run(False)
    _, _, p1, t1 = _run(True)

    def decode(trainer):
        topo = trainer.topology
        params = {k: jnp.asarray(v)
                  for k, v in trainer.parameters.as_dict().items()}
        feeder = DataFeeder(topo.data_type(), {"w": 0, "l": 1})
        outs = topo.forward(params, feeder(SAMPLES))
        ids = np.asarray(jnp.argmax(outs["out"].value, axis=-1))
        return [ids[i, :len(s[0])].tolist()
                for i, s in enumerate(SAMPLES)]

    d0, d1 = decode(t0), decode(t1)
    assert d0 == d1


def test_packed_snapshot_resume_bit_identical(tmp_path):
    """Mid-pass crash + resume in PACKED mode: the resumed packed run
    lands on the uninterrupted packed run's exact final parameters (the
    r7 crash-safety contract holds under packing)."""
    _, _, ref, _ = _run(True, num_passes=2)

    class _Crash(RuntimeError):
        pass

    state = {"n": 0}

    def crash_handler(ev):
        if isinstance(ev, v2_event.EndIteration):
            state["n"] += 1
            if state["n"] >= 4:
                raise _Crash("scripted crash after batch 4")

    snap = str(tmp_path / "snaps")
    t1 = _make_tagger()
    with pytest.raises(_Crash):
        t1.train(checkpointable(paddle.batch(_reader, BATCH)),
                 num_passes=2, event_handler=crash_handler,
                 save_every_n_batches=2, snapshot_dir=snap,
                 pipeline_depth=0, pack_sequences=True)
    found = SGD.load_step_resume(snap)
    assert found is not None
    loaded, resume = found
    t2 = _make_tagger()
    for name in loaded.names():
        t2.parameters.set(name, loaded.get(name))
    t2.train(checkpointable(paddle.batch(_reader, BATCH)),
             num_passes=2, resume_state=resume, save_every_n_batches=2,
             snapshot_dir=snap, pipeline_depth=0, pack_sequences=True)
    got = {k: np.asarray(t2.parameters.get(k))
           for k in t2.parameters.names()}
    for k in ref:
        np.testing.assert_array_equal(got[k], ref[k], err_msg=k)


def test_packed_pipelined_matches_packed_sync():
    """Packing composes with the r10 pipelined loop: same trajectory."""
    _, _, p_sync, _ = _run(True)
    t = _make_tagger()
    t.train(paddle.batch(_reader, BATCH), num_passes=2, pipeline_depth=3,
            pack_sequences=True)
    got = {k: np.asarray(t.parameters.get(k)) for k in t.parameters.names()}
    for k in p_sync:
        np.testing.assert_array_equal(got[k], p_sync[k], err_msg=k)


# --- jaxpr pins ------------------------------------------------------------

def _tagger_step_jaxpr(packed):
    from paddle_tpu.trainer.trainer import make_train_step

    t = _make_tagger()
    topo = t.topology
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = optimizer.Adam(learning_rate=1e-2)
    opt_state = opt.init(params)
    loss = topo.loss_fn("cost")
    step = make_train_step(loss, opt, topo.static_map(), jit_compile=False)
    feeder = DataFeeder(topo.data_type(), {"w": 0, "l": 1},
                        pack_sequences=packed)
    feeds = feeder(SAMPLES[:8])
    return str(jax.make_jaxpr(step)(params, opt_state,
                                    jax.random.PRNGKey(1), feeds))


def test_unpacked_jaxpr_untouched_and_packed_differs_as_intended():
    """The acceptance pin: the UNPACKED train-step jaxpr is independent
    of the packing machinery (same program before and after a packed
    training run in this process), while enabling packing changes the
    compiled graph — and only then (segment masks / reset vectors enter
    the program solely through the packed feed structure)."""
    before = _tagger_step_jaxpr(packed=False)
    _run(True, num_passes=1)                  # a packed run in between
    after = _tagger_step_jaxpr(packed=False)
    assert before == after
    packed = _tagger_step_jaxpr(packed=True)
    assert packed != before


# --- packed guards ---------------------------------------------------------

def test_row_level_layers_refuse_packed_rows():
    with layer_name_scope():
        w = layer.data(name="w", type=data_type.integer_value_sequence(V))
        emb = layer.embedding(input=w, size=8, name="emb")
        pooled = layer.pooling(input=emb, pooling_type=paddle.pooling.Max(),
                               name="pool")
        out = layer.fc(input=pooled, size=2, act=activation.Softmax())
    topo = paddle.Topology(out)
    params = topo.init_params(jax.random.PRNGKey(0))
    feeder = DataFeeder([("w", data_type.integer_value_sequence(V))],
                        {"w": 0}, pack_sequences=True)
    feeds = feeder([([1, 2, 3],), ([4, 5],)])
    with pytest.raises(Error, match="packed"):
        topo.forward(params, feeds)


def test_to_sequence_pooling_refuses_packed_rows():
    """Review pin (r11): a packed feed's seg_ids must not slip into the
    NESTED sub-sequence pooling branch (agg_level='to_sequence') — it
    would strip seg_ids and re-normalize the downstream loss per packed
    row instead of per sample, silently diverging from the padded run."""
    from paddle_tpu.pooling import Max
    with layer_name_scope():
        w = layer.data(name="w", type=data_type.integer_value_sequence(V))
        emb = layer.embedding(input=w, size=8, name="emb")
        pooled = layer.pooling(input=emb, pooling_type=Max(),
                               agg_level="to_sequence", name="pool")
        out = layer.fc(input=pooled, size=2, act=activation.Softmax())
    topo = paddle.Topology(out)
    params = topo.init_params(jax.random.PRNGKey(0))
    feeder = DataFeeder([("w", data_type.integer_value_sequence(V))],
                        {"w": 0}, pack_sequences=True)
    feeds = feeder([([1, 2, 3],), ([4, 5],)])
    with pytest.raises(Error, match="packed"):
        topo.forward(params, feeds)


def test_recurrent_refuses_packed_feed_without_seg_ids():
    """Review pin (r11): seg_ids propagation is opt-in per layer, so a
    recurrent layer fed a packed sequence whose seg_ids were dropped
    upstream must refuse loudly — failing open (no resets) would leak
    state across packed boundaries with no error."""
    from paddle_tpu.layers.recurrent import _packed_resets

    class Ctx:
        packed = True

    a = Arg(jnp.zeros((2, 4, 8)), jnp.ones((2, 4)), None)
    with pytest.raises(Error, match="seg_ids"):
        _packed_resets(a, Ctx(), False)


def test_recurrent_group_refuses_packed_rows():
    with layer_name_scope():
        src = layer.data(name="w", type=data_type.integer_value_sequence(V))
        emb = layer.embedding(input=src, size=8, name="emb")

        def step(x):
            mem = layer.memory(name="m", size=8)
            nxt = layer.fc(input=[x, mem], size=8, act=activation.Tanh(),
                           name="m")
            return nxt

        seq = layer.recurrent_group(step=step, input=[emb], name="grp")
    topo = paddle.Topology(seq)
    params = topo.init_params(jax.random.PRNGKey(0))
    feeder = DataFeeder([("w", data_type.integer_value_sequence(V))],
                        {"w": 0}, pack_sequences=True)
    feeds = feeder([([1, 2, 3],), ([4, 5],)])
    with pytest.raises(Error, match="packed"):
        topo.forward(params, feeds)


def test_ctc_and_crf_layers_refuse_packed_rows():
    """Review pin (r11): the chain/alignment cost layers must refuse
    packed feeds — ctc would align the concatenation of several sequences
    as one, and crf_decoding/crf_error would score transitions across
    packed boundaries — all silently wrong if allowed through."""

    def _ctc_model():
        frames = layer.data(
            name="x", type=data_type.dense_vector_sequence(C + 1))
        lab = layer.data(name="l", type=data_type.integer_value_sequence(C))
        return layer.ctc(input=frames, label=lab, size=C + 1, name="ctc")

    def _crf_decoding_model():
        w = layer.data(name="x", type=data_type.dense_vector_sequence(C + 1))
        emit = layer.fc(input=w, size=C, name="emit")
        return layer.crf_decoding(input=emit, size=C, name="dec")

    ctc_samples = [([[0.1] * (C + 1)] * 4, [1, 2]),
                   ([[0.2] * (C + 1)] * 3, [3])]
    dec_samples = [([[0.1] * (C + 1)] * 4,), ([[0.2] * (C + 1)] * 3,)]
    for build, samples, feeding in [
            (_ctc_model, ctc_samples, {"x": 0, "l": 1}),
            (_crf_decoding_model, dec_samples, {"x": 0})]:
        with layer_name_scope():
            out = build()
        topo = paddle.Topology(out)
        params = topo.init_params(jax.random.PRNGKey(0))
        feeder = DataFeeder(topo.data_type(), feeding, pack_sequences=True)
        feeds = feeder(samples)
        with pytest.raises(Error, match="packed"):
            topo.forward(params, feeds)


# --- evaluators ------------------------------------------------------------

def test_chunk_evaluator_splits_packed_segments():
    ev_u = evaluator.chunk(input="p", label="l", chunk_scheme="IOB",
                           num_chunk_types=2)
    ev_p = evaluator.chunk(input="p", label="l", chunk_scheme="IOB",
                           num_chunk_types=2)
    # two sequences: tags in IOB2 encoding over 2 chunk types
    seq_a = [0, 1, 4, 0, 1]           # B-0 I-0 O B-0 I-0
    seq_b = [2, 3, 0]                 # B-1 I-1 B-0
    lab_a = [0, 1, 4, 2, 3]
    lab_b = [2, 3, 4]

    def arg(rows, seg=None):
        T = max(len(r) for r in rows)
        val = np.zeros((len(rows), T), np.int32)
        mask = np.zeros((len(rows), T), np.float32)
        for i, r in enumerate(rows):
            val[i, :len(r)] = r
            mask[i, :len(r)] = 1
        return Arg(jnp.asarray(val), jnp.asarray(mask),
                   None if seg is None else jnp.asarray(seg, jnp.int32))

    outs_u = {"p": arg([seq_a, seq_b]), "l": arg([lab_a, lab_b])}
    ev_u.accumulate(ev_u.compute(outs_u))
    # packed: both sequences in ONE row (packed_feed is what the trainer
    # harness stamps — seg_ids presence alone must NOT trigger the split,
    # nested SUB_SEQUENCE outputs carry seg_ids too)
    seg = [[0] * 5 + [1] * 3]
    outs_p = {"p": arg([seq_a + seq_b], seg), "l": arg([lab_a + lab_b], seg)}
    ev_p.packed_feed = True
    ev_p.accumulate(ev_p.compute(outs_p))
    assert ev_u._acc == ev_p._acc
    # without the split, the B-0 chunk straddling the boundary would
    # decode differently — prove the packed accumulate actually split
    assert ev_p._acc["ng"] == ev_u._acc["ng"]


def test_evaluators_ignore_nested_seg_ids_without_packed_feed():
    """Review pin (r11): nested SUB_SEQUENCE outputs carry seg_ids but
    are NOT packed — without the trainer stamping packed_feed=True, the
    evaluators must keep their pre-packing per-row semantics (and
    ctc_error must not refuse)."""
    seg = jnp.asarray([[0, 0, 1, 1]], jnp.int32)
    mask = jnp.ones((1, 4), jnp.float32)
    pred = Arg(jax.nn.one_hot(jnp.asarray([[1, 1, 1, 1]]), C), mask, seg)
    lab = Arg(jnp.asarray([[1, 1, 0, 1]], jnp.int32), mask, seg)
    ev = evaluator.seq_classification_error(input="p", label="l")
    assert ev.packed_feed is False
    stats = ev.compute({"p": pred, "l": lab})
    # per ROW: 1 sequence total, and it contains a wrong step
    assert float(stats["total"]) == 1.0 and float(stats["wrong"]) == 1.0
    ev.packed_feed = True
    stats = ev.compute({"p": pred, "l": lab})
    # per SEGMENT: 2 sequences, only the second holds the wrong step
    assert float(stats["total"]) == 2.0 and float(stats["wrong"]) == 1.0


# --- sort_within_buffer satellite ------------------------------------------

def test_sort_within_buffer_windows():
    data = [[1] * t for t in (5, 2, 9, 1, 7, 3, 8, 4)]

    def base():
        yield from data

    got = list(sort_within_buffer(base, 4)())
    # windows of 4, each sorted by len, stream order of windows kept
    assert [len(x) for x in got] == [1, 2, 5, 9, 3, 4, 7, 8]
    # everything delivered exactly once
    assert sorted(len(x) for x in got) == sorted(len(x) for x in data)


def test_sort_within_buffer_default_key_sorts_tuple_samples():
    """Review pin: samples are usually (seq, label, ...) tuples, where
    plain len(sample) is the constant slot count — the default key must
    dig into the first sized slot or the decorator silently sorts
    nothing."""
    data = [([1] * t, t % C) for t in (5, 2, 9, 1)]

    def base():
        yield from data

    got = list(sort_within_buffer(base, 4)())
    assert [len(s[0]) for s in got] == [1, 2, 5, 9]


def test_sort_within_buffer_cuts_padding_waste():
    rs = np.random.RandomState(0)
    lens = [int(rs.randint(1, 33)) for _ in range(64)]

    def base():
        for t in lens:
            yield ([1] * t,)

    def waste(reader):
        feeder = DataFeeder([("w", data_type.integer_value_sequence(V))])
        frac = []
        for b in paddle.batch(reader, 8)():
            arg = feeder(b)["w"]
            m = np.asarray(arg.mask)
            frac.append(1 - m.sum() / m.size)
        return float(np.mean(frac))

    sorted_reader = sort_within_buffer(base, 32, key=lambda s: len(s[0]))
    assert waste(sorted_reader) < waste(base)


def test_sort_within_buffer_checkpointable_resume():
    data = [([1] * t, t % C) for t in (5, 2, 9, 1, 7, 3, 8, 4, 6, 10)]

    def base():
        yield from data

    full = list(checkpointable(sort_within_buffer(base, 4))())
    r1 = checkpointable(sort_within_buffer(base, 4))
    it = iter(r1())
    first = [next(it) for _ in range(3)]
    state = r1.state()
    r2 = checkpointable(sort_within_buffer(base, 4))
    r2.restore(state)
    rest = list(r2())
    assert first + rest == full


# --- the packing-ready NMT (models/text.nmt_packed_cost) -------------------

def test_nmt_packed_cost_same_loss_from_fewer_less_padded_rows():
    """Three slots under ONE plan (src / trg / trg_next, trg length
    correlated with src): packing leaves fewer rows and a lower pad
    fraction in every slot, fills rows past half, and cross-attention
    through the segment mask sees only its own source sentence — the
    loss equals the padded feed's."""
    from paddle_tpu.models.text import nmt_packed_cost

    vocab = 64
    with layer_name_scope():
        cost = nmt_packed_cost(src_dict_dim=vocab, trg_dict_dim=vocab,
                               word_vector_dim=16, encoder_size=16,
                               decoder_size=16, num_heads=2, name="mp")
    topo = paddle.Topology(cost)
    rs = np.random.RandomState(0)
    samples = []
    for _ in range(16):
        ts = int(rs.randint(4, 13))
        tt = max(3, ts + int(rs.randint(-2, 3)))
        samples.append((rs.randint(0, vocab, ts).tolist(),
                        rs.randint(0, vocab, tt).tolist(),
                        rs.randint(0, vocab, tt).tolist()))
    feeding = {"src": 0, "trg": 1, "trg_next": 2}
    padded = DataFeeder(topo.data_type(), feeding)(samples)
    packed = DataFeeder(topo.data_type(), feeding, pack_sequences=True,
                        pack_max_len=24, pack_row_rounding=1)(samples)

    def pad_fraction(arg):
        m = np.asarray(arg.mask)
        return 1.0 - float(m.sum()) / m.size

    for slot in feeding:
        assert packed[slot].value.shape[0] < padded[slot].value.shape[0]
        assert pad_fraction(packed[slot]) < pad_fraction(padded[slot])
        assert pad_fraction(packed[slot]) < 0.5
    params = topo.init_params(jax.random.PRNGKey(0))
    loss = topo.loss_fn("cost")
    want = float(loss(params, padded, training=False)[0])
    got = float(loss(params, packed, training=False)[0])
    assert np.isfinite(want) and got == pytest.approx(want, rel=1e-5)
