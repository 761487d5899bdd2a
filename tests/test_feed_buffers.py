"""Reused, rotating host buffers of the train loop's feeder (ISSUE 26,
docs/pipeline.md "Host-buffer rotation").

A feeder built by the owner of a loop (``SGD.train``) assembles every
batch in a ``FeedBufferPool`` and rotates through as many generations as
the loop keeps batches unconsumed; a bare ``DataFeeder`` keeps returning
arrays of its own. Pins: the dense path is ``np.asarray(rows, float32)``
bit for bit for every way a row can be given; the generation rule;
``SGD.train`` fed from the pool gives the costs of a run fed from fresh
arrays at depth 1, 2, 3; ragged rows raise; tail batches; the
``paddle_feed_buffer_total`` counter and ``paddle_feed_buffer_bytes``
gauge.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import activation, data_type, layer, optimizer
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.trainer import event as v2_event
from paddle_tpu.trainer.feeder import DataFeeder, FeedBufferPool
from paddle_tpu.trainer.trainer import SGD

B, SHAPE = 6, (3, 4, 4)
DIM = int(np.prod(SHAPE))
TYPES = [("img", data_type.dense_vector(DIM)),
         ("lab", data_type.integer_value(10))]


def _rows(kind, seed=0, n=B):
    """n image rows, each a separate object, in one of the forms a reader
    hands them over."""
    rs = np.random.RandomState(seed)
    if kind == "float32":
        return [rs.rand(*SHAPE).astype(np.float32) for _ in range(n)]
    if kind == "float32_flat":
        return [rs.rand(DIM).astype(np.float32) for _ in range(n)]
    if kind == "uint8":
        return [rs.randint(0, 256, SHAPE).astype(np.uint8)
                for _ in range(n)]
    if kind == "float64":
        return [rs.rand(*SHAPE) for _ in range(n)]
    if kind == "strided":
        return [rs.rand(SHAPE[0], SHAPE[1], 2 * SHAPE[2])
                .astype(np.float32)[:, :, ::2] for _ in range(n)]
    if kind == "nested_list":
        return [rs.rand(*SHAPE).tolist() for _ in range(n)]
    if kind == "float_list":
        return [rs.rand(DIM).tolist() for _ in range(n)]
    if kind == "int_list":
        return [rs.randint(-5, 5, DIM).tolist() for _ in range(n)]
    raise AssertionError(kind)


ROW_KINDS = ["float32", "float32_flat", "uint8", "float64", "strided",
             "nested_list", "float_list", "int_list"]


def _batch(rows, seed=0):
    labs = np.random.RandomState(seed + 100).randint(0, 10, len(rows))
    return [(r, int(l)) for r, l in zip(rows, labs)]


def _pooled(depth=1, types=TYPES):
    return DataFeeder(types, buffers=FeedBufferPool(),
                      rotate_buffers=depth)


# --- (a) the dense path is np.asarray, bit for bit -------------------------

@pytest.mark.parametrize("kind", ROW_KINDS)
def test_pooled_dense_is_asarray_bit_for_bit(kind):
    feeder = _pooled(depth=2)
    for seed in range(4):       # generations come round twice
        rows = _rows(kind, seed)
        got = feeder(_batch(rows, seed))
        want = np.asarray(rows, np.float32).reshape(B, -1)
        assert got["img"].value.dtype == np.float32
        assert got["img"].value.shape == (B, DIM)
        assert got["img"].value.tobytes() == want.tobytes()
        want_lab = np.asarray([s[1] for s in _batch(rows, seed)],
                              np.int32).reshape(B, 1)
        assert got["lab"].value.dtype == np.int32
        np.testing.assert_array_equal(got["lab"].value, want_lab)


@pytest.mark.parametrize("kind", ["float32", "nested_list"])
def test_pooled_raw_arginfo_keeps_row_shape(kind):
    """Data layers declared with a shape only carry no InputType: the
    batch keeps the rows' own shape, as np.asarray(rows) gives it."""
    feeder = _pooled()
    for seed in range(2):
        rows = _rows(kind, seed)
        got = feeder.convert_one(rows, object(), slot="raw").value
        want = np.asarray(rows, np.float32)
        assert got.shape == (B,) + SHAPE
        assert got.tobytes() == want.tobytes()


# --- (b) the generation rule ----------------------------------------------

@pytest.mark.parametrize("depth", [1, 2, 3])
def test_generation_rule(depth):
    """The storage of call k is untouched by calls k+1 .. k+depth-1 and
    IS the storage of call k+depth."""
    feeder = _pooled(depth)
    calls, copies = [], []
    for k in range(3 * depth + 1):
        feeds = feeder(_batch(_rows("float32", k), k))
        calls.append(feeds)
        copies.append({n: a.value.copy() for n, a in feeds.items()})
        for j in range(max(0, k - depth + 1), k):
            for n in feeds:
                assert not np.shares_memory(calls[j][n].value,
                                            feeds[n].value)
                np.testing.assert_array_equal(calls[j][n].value,
                                              copies[j][n])
        if k >= depth:
            for n in feeds:
                assert calls[k - depth][n].value.ctypes.data == \
                    feeds[n].value.ctypes.data


# --- (c) SGD.train from the pool == SGD.train from fresh arrays ------------

N_TRAIN, BATCH = 56, 16        # three full batches and a tail of 8


def _train_costs(depth, pooled):
    x = layer.data(name="img", type=data_type.dense_vector(DIM))
    y = layer.data(name="lab", type=data_type.integer_value(10))
    out = layer.fc(input=x, size=10, act=activation.Softmax(), name="out")
    cost = layer.classification_cost(input=out, label=y, name="cost")
    params = paddle.parameters_create(paddle.Topology(cost))
    t = SGD(cost=cost, parameters=params,
            update_equation=optimizer.Momentum(learning_rate=0.1,
                                               momentum=0.9))
    if not pooled:
        t._feed_buffers = None              # a bare feeder: fresh arrays
    rows = _rows("float64", 7, N_TRAIN)
    samples = _batch(rows, 7)
    costs = []

    def handler(ev):
        if isinstance(ev, v2_event.EndIteration):
            costs.append(float(ev.cost))

    t.train(paddle.batch(lambda: iter(samples), BATCH), num_passes=3,
            event_handler=handler, pipeline_depth=depth)
    return np.asarray(costs, np.float64), t


@pytest.fixture(scope="module")
def fresh_costs():
    return _train_costs(1, pooled=False)[0]


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_train_from_pool_matches_fresh_arrays(depth, fresh_costs):
    costs, t = _train_costs(depth, pooled=True)
    assert len(costs) == 3 * 4
    assert costs.tobytes() == fresh_costs.tobytes()
    # depth generations of the full batch, image + label, and at most as
    # many of the tail (which generations it meets depends on the depth)
    row = DIM * 4 + 4
    assert depth * BATCH * row < t._feed_buffers.nbytes \
        <= depth * (BATCH + 8) * row


# --- (d) nothing broadcasts ------------------------------------------------

@pytest.mark.parametrize("bad", ["one_element", "short", "long",
                                 "one_element_list", "ragged_nested",
                                 "first_row_short"])
def test_wrong_width_row_raises(bad):
    rows = _rows("float32_flat")
    if bad == "one_element":
        rows[3] = rows[3][:1]
    elif bad == "short":
        rows[3] = rows[3][:DIM - 1]
    elif bad == "long":
        rows[3] = np.concatenate([rows[3], rows[3][:1]])
    elif bad == "one_element_list":
        rows = [r.tolist() for r in rows]
        rows[3] = [0.5]
    elif bad == "ragged_nested":
        rows = _rows("nested_list")
        rows[3][1][2] = rows[3][1][2][:-1]
    elif bad == "first_row_short":
        rows[0] = rows[0][:1]
    for feeder in (_pooled(), DataFeeder(TYPES)):
        with pytest.raises(ValueError):
            feeder(_batch(rows))


def test_index_row_of_two_raises():
    batch = _batch(_rows("float32_flat"))
    batch = [(r, [l, l]) for r, l in batch]
    for feeder in (_pooled(), DataFeeder(TYPES)):
        with pytest.raises(ValueError):
            feeder(batch)


# --- (e) a tail batch gets buffers of its own ------------------------------

@pytest.mark.parametrize("depth", [1, 2])
def test_tail_batch_has_its_own_buffers(depth):
    feeder = _pooled(depth)
    full = [feeder(_batch(_rows("float32", k), k)) for k in range(depth)]
    kept = [{n: a.value.copy() for n, a in f.items()} for f in full]
    tail_rows = _rows("float32", 50, n=B - 2)
    tail = feeder(_batch(tail_rows, 50))
    assert tail["img"].value.shape == (B - 2, DIM)
    assert tail["lab"].value.shape == (B - 2, 1)
    assert tail["img"].value.tobytes() == \
        np.asarray(tail_rows, np.float32).reshape(B - 2, -1).tobytes()
    for f, k in zip(full, kept):
        for n in f:
            assert not np.shares_memory(f[n].value, tail[n].value)
            np.testing.assert_array_equal(f[n].value, k[n])
    # and the full batch's buffers are still there to come back to
    before = feeder._pool.nbytes
    for k in range(depth):
        feeder(_batch(_rows("float32", k), k))
    assert feeder._pool.nbytes == before


# --- (f) a bare feeder owns no pool ----------------------------------------

@pytest.mark.parametrize("rotate", [1, 2])
def test_bare_feeder_returns_independent_arrays(rotate):
    feeder = DataFeeder(TYPES, rotate_buffers=rotate)
    held = [feeder(_batch(_rows("float32", k), k)) for k in range(5)]
    for k, f in enumerate(held):
        want = np.asarray(_rows("float32", k), np.float32).reshape(B, -1)
        np.testing.assert_array_equal(f["img"].value, want)
        for other in held[k + 1:]:
            for n in f:
                assert not np.shares_memory(f[n].value, other[n].value)


# --- (g) the counter and the gauge -----------------------------------------

def _buffer_counts(feed):
    ctr = obs_metrics.default_registry.counter(
        "paddle_feed_buffer_total", labels=("feed", "result"))
    return (ctr.labels(feed=feed, result="allocated").value,
            ctr.labels(feed=feed, result="reused").value)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_counter_allocated_then_reused(depth):
    types = [("cnt_img%d" % depth, TYPES[0][1]),
             ("cnt_seq%d" % depth, data_type.integer_value_sequence(50))]
    gauge = obs_metrics.default_registry.gauge("paddle_feed_buffer_bytes")
    held0 = gauge.value
    feeder = _pooled(depth, types)
    seqs = [[1, 2, 3], [4, 5], [6], [7, 8, 9, 10], [1], [2, 3]]
    img0, seq0 = _buffer_counts(types[0][0]), _buffer_counts(types[1][0])
    for k in range(3 * depth):
        feeder([(r, s) for r, s in zip(_rows("float32", k), seqs)])
        img = _buffer_counts(types[0][0])
        seq = _buffer_counts(types[1][0])
        # one request a batch for the image, two (value, mask) for the
        # sequence; the first `depth` batches of the shape allocate
        assert img[0] - img0[0] == min(k + 1, depth)
        assert img[1] - img0[1] == max(k + 1 - depth, 0)
        assert seq[0] - seq0[0] == 2 * min(k + 1, depth)
        assert seq[1] - seq0[1] == 2 * max(k + 1 - depth, 0)
    held = depth * (B * DIM * 4 + B * 4 * 4 + B * 4 * 4)
    assert feeder._pool.nbytes == held
    assert gauge.value - held0 == held
    # a batch of another size shows as allocated again
    feeder([(r, s) for r, s in zip(_rows("float32", 0, n=3), seqs)])
    assert _buffer_counts(types[0][0])[0] - img0[0] == depth + 1
    # the gauge gives the bytes back with the pool
    del feeder
    assert gauge.value == held0


def test_bare_feeder_counts_allocated():
    feeder = DataFeeder([("bare_img", TYPES[0][1])])
    a0, r0 = _buffer_counts("bare_img")
    for k in range(3):
        feeder([(r,) for r in _rows("float32", k)])
    a, r = _buffer_counts("bare_img")
    assert (a - a0, r - r0) == (3, 0)
