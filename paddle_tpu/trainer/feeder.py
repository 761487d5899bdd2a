"""DataFeeder: convert python minibatches to device Args.

Analog of paddle/py_paddle/dataprovider_converter.py (numpy -> Argument
with sequenceStartPositions) + paddle/gserver/dataproviders/PyDataProvider2
field scanners (Dense/Index/SparseNonValue/SparseValue/Sequence, reference
PyDataProvider2.cpp:670-833). Ragged sequences become padded+masked arrays;
sequence lengths are bucketed to powers of two (or a multiple-of-N
rounding, ``bucket_rounding``) to bound XLA recompiles.

Packed-feed mode (``pack_sequences=True``, docs/packing.md): instead of
one padded row per sample, several ragged samples pack back to back into
each fixed [R, T] row with per-row ``seg_ids`` marking which packed
sequence each timestep belongs to — the XLA-native rebuild of the
reference's zero-padding ragged batches (``Argument.
sequenceStartPositions`` / SequenceToBatch, SURVEY §5.7). The r10
``paddle_feed_pad_fraction`` histogram measured the bucketing waste this
deletes; in packed mode the same histogram reports the residual tail
waste under the ``packed="1"`` label.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from paddle_tpu.core.arg import Arg
from paddle_tpu.data_type import InputType, SeqType
from paddle_tpu.observability import metrics as _obs
from paddle_tpu.utils.error import enforce

# Padding waste of the sequence batching, per feed slot:
# 1 - real_timesteps / (rows * T_padded). Host-side accounting only — lets
# the v5e re-measure see bucketing overhead next to data-wait (a high
# pad fraction means the chip crunches mostly zeros). packed="0" is the
# one-sample-per-row padded path (power-of-two / bucket_rounding waste);
# packed="1" is the sequence-packing path, where the fraction is the
# residual tail waste packing could not fill. The chosen padded T of the
# last conversion is exposed as the paddle_feed_padded_len exemplar gauge.
_M_PAD_FRACTION = _obs.histogram(
    "paddle_feed_pad_fraction",
    "Fraction of a padded sequence batch that is padding: "
    "1 - real_timesteps / (rows * padded_T). packed=0: per-sample "
    "padding+bucketing waste; packed=1: residual tail waste of "
    "sequence-packed rows (docs/packing.md)",
    labels=("feed", "packed"),
    buckets=(0.01, 0.02, 0.05, 0.1, 0.15, 0.2, 0.3, 0.4, 0.5,
             0.6, 0.7, 0.8, 0.9, 1.0))
# exemplar companion for the pad-fraction histogram: the padded T the
# feeder actually chose for the last batch of each slot (the bucketing
# decision the fraction was measured against)
_M_PADDED_LEN = _obs.gauge(
    "paddle_feed_padded_len",
    "Padded sequence length (T) chosen for the last converted batch of "
    "this feed slot — the paddle_feed_pad_fraction exemplar",
    labels=("feed", "packed"))
# Where each array a feeder fills came from. A train loop's steady state
# reads 100% result="reused"; a reader that changes batch size or sequence
# bucket every step shows up as result="allocated" (as does every bare
# feeder, which owns no pool).
_M_BUFFER = _obs.counter(
    "paddle_feed_buffer_total",
    "Host arrays a DataFeeder filled, by origin: reused = a kept buffer "
    "of the owning loop's pool, allocated = fresh memory "
    "(docs/pipeline.md 'Host-buffer rotation')",
    labels=("feed", "result"))
_M_POOL_BYTES = _obs.gauge(
    "paddle_feed_buffer_bytes",
    "Host bytes held by the feed buffer pools of this process's train "
    "loops: generations x one buffer per distinct (slot, role, shape, "
    "dtype) fed so far")


class FeedBufferPool:
    """The host buffers a train loop's feeder writes its batches into.

    Owned by whoever owns the loop (``SGD`` keeps one across ``train()``
    calls), because only the owner knows when a batch has been consumed:
    the feeder rotates through ``rotate_buffers`` generations and the
    loop guarantees that a generation's step has been forced to finish
    before that generation comes round again (docs/pipeline.md). One
    buffer per (slot, role, generation, shape, dtype), plain numpy
    memory of whatever size the batch needs, never released: the number
    of distinct feed shapes is what bucketing already bounds for the
    compiled steps."""

    def __init__(self):
        self._bufs: Dict[tuple, np.ndarray] = {}

    def get(self, slot, role, gen, shape, dtype):
        """(array, reused): the buffer of this key; its contents are
        whatever the key's last user left there."""
        dtype = np.dtype(dtype)
        key = (slot, role, gen, tuple(shape), dtype.str)
        arr = self._bufs.get(key)
        if arr is not None:
            return arr, True
        arr = self._bufs[key] = np.empty(shape, dtype)
        _M_POOL_BYTES.inc(arr.nbytes)
        return arr, False

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self._bufs.values())

    def __del__(self):
        held = self.nbytes
        if held:
            _M_POOL_BYTES.dec(held)


def _bucket(n: int, bucketing: bool, rounding: Optional[int] = None) -> int:
    """Padded length for a max sequence length ``n``. Default: next power
    of two (few compiled shapes, up to ~49% waste right above a power of
    two — T=65 pads to 128). ``rounding=N`` rounds up to a multiple of N
    instead (more shapes, bounded waste N-1): the bucket_rounding knob."""
    if rounding:
        enforce(rounding >= 1, "bucket_rounding must be >= 1")
        return max(-(-max(n, 1) // rounding) * rounding, 1)
    if not bucketing or n <= 1:
        return max(n, 1)
    p = 1
    while p < n:
        p <<= 1
    return p


def _pack_plan(lengths: Dict[str, List[int]],
               caps: Dict[str, int]) -> List[List[int]]:
    """Greedy first-fit-decreasing packing plan shared by every feed slot.

    lengths: {slot: [per-sample sequence length]}; caps: {slot: row
    capacity}. A sample fits a row only if it fits in EVERY slot, so all
    slots of one sample land in the same row at the same segment index —
    the alignment the segment masks downstream rely on. Returns rows as
    lists of original sample indices (packing order = segment order).
    Deterministic: depends only on the lengths."""
    names = list(lengths)
    n = len(lengths[names[0]]) if names else 0
    order = sorted(range(n),
                   key=lambda i: (-max(lengths[s][i] for s in names), i))
    rows: List[tuple] = []          # (used: {slot: int}, members: [i])
    for i in order:
        for used, members in rows:
            if all(used[s] + lengths[s][i] <= caps[s] for s in names):
                for s in names:
                    used[s] += lengths[s][i]
                members.append(i)
                break
        else:
            rows.append(({s: lengths[s][i] for s in names}, [i]))
    return [members for _used, members in rows]


def resolve_pack_flags(pack_sequences=None, pack_max_len=None,
                       bucket_rounding=None):
    """Resolve the packing/bucketing knobs against their same-named
    flags (None = flag fallback). The ONE place the FLAGS defaults are
    interpreted — SGD.train/test and the CLI jobs all resolve through
    here so every surface feeds the shapes training compiles."""
    from paddle_tpu.utils.flags import FLAGS
    if pack_sequences is None:
        pack_sequences = bool(FLAGS.get("pack_sequences", False))
    if pack_max_len is None:
        pack_max_len = FLAGS.get("pack_max_len", 0) or None
    if bucket_rounding is None:
        bucket_rounding = FLAGS.get("bucket_rounding", 0) or None
    return bool(pack_sequences), pack_max_len, bucket_rounding


class DataFeeder:
    def __init__(self, data_types: Sequence, feeding: Optional[Dict[str, int]] = None,
                 bucket_seq_len: bool = True,
                 buffers: Optional[FeedBufferPool] = None,
                 rotate_buffers: int = 1, pack_sequences: bool = False,
                 pack_max_len: Optional[int] = None,
                 bucket_rounding: Optional[int] = None,
                 pack_row_rounding: Optional[int] = None):
        """data_types: [(name, InputType)] — from Topology.data_type().

        pack_sequences: pack several ragged samples into each fixed
        [R, T] row with seg_ids (docs/packing.md). Requires every feed
        slot to be a plain SEQUENCE input; segment-aware layers
        downstream (attention, lstmemory/grumemory, cost/evaluators)
        then treat each packed segment as its own sequence. The plan is
        shared across slots, so segment k of row r is the same original
        sample in every feed. ``last_pack_plan`` exposes the row->sample
        mapping of the most recent batch.

        pack_max_len: packed row capacity (per slot, before bucketing).
        None = 2x the batch's longest sample in that slot — long enough
        that the amortized per-row tail waste stays small, short enough
        to bound the quadratic attention cost of a row. Always at least
        the longest sample.

        bucket_rounding: pad T up to a multiple of N instead of the next
        power of two (the T=65 -> 128 ~49% waste case; satellite of
        ISSUE 6). None keeps power-of-two. Applies to both packed and
        unpacked conversion; the chosen T is recorded in the
        paddle_feed_padded_len exemplar gauge.

        pack_row_rounding: round the packed row count R up to a multiple
        of N with all-padding filler rows (mask 0, seg -1 — inert in
        every segment-aware consumer). The plan's natural R varies batch
        to batch, and each distinct [R, T] feed shape recompiles the
        jitted train step, so without this the packed path retraces
        every few batches — exactly the recompile churn ``_bucket``
        exists to prevent on T. 1 disables (exact R; unit-test scale);
        None = the default of 8.

        buffers / rotate_buffers: who may hold a feed array, and for how
        long. A bare ``DataFeeder(...)`` (``buffers=None``) returns
        arrays of its own on every call; hold as many batches as you
        like. A feeder built by the owner of a loop (``SGD.train``)
        writes every array into ``buffers``, a :class:`FeedBufferPool`
        it keeps, so steady-state assembly allocates nothing; the arrays
        of call k are then OVERWRITTEN by call k + ``rotate_buffers``.
        The owner passes the number of batches it keeps unconsumed:
        ``SGD.train`` passes ``pipeline_depth``, since after dispatching
        step N it drains until at most depth-1 steps are in flight, so
        the step that read generation (N+1) % depth has been forced to
        finish (and its H2D copy with it) before batch N+1 is assembled
        (docs/pipeline.md "Host-buffer rotation").
        """
        self.data_types = list(data_types)
        if feeding is None:
            feeding = {name: i for i, (name, _) in enumerate(self.data_types)}
        self.feeding = feeding
        self.bucket = bucket_seq_len
        self.pack = bool(pack_sequences)
        self.pack_max_len = pack_max_len
        self.bucket_rounding = bucket_rounding
        if pack_row_rounding is None:
            pack_row_rounding = 8
        enforce(pack_row_rounding >= 1, "pack_row_rounding must be >= 1")
        self.pack_row_rounding = int(pack_row_rounding)
        #: row -> [original sample indices] of the last packed batch
        self.last_pack_plan: Optional[List[List[int]]] = None
        if self.pack:
            for name, itype in self.data_types:
                enforce(isinstance(itype, InputType)
                        and itype.seq_type == SeqType.SEQUENCE
                        and itype.kind in ("index", "dense"),
                        f"pack_sequences: feed slot {name!r} must be a "
                        "plain index/dense SEQUENCE input (non-sequence, "
                        "nested and sparse slots cannot be packed)")
        self._pool = buffers
        self._rotate = max(1, int(rotate_buffers))
        self._gen = 0

    def _buffer(self, shape, dtype, slot, role="v", fill=None):
        """The array a batch is assembled in: the pool's buffer of this
        (slot, role, generation, shape, dtype) — role tells apart the
        same-shape arrays of one slot, e.g. a sequence's int32 value and
        its int32 seg_ids — or fresh memory for a bare feeder. ``fill``
        None is for callers that overwrite every element."""
        if self._pool is None:
            reused = False
            if fill is None:
                arr = np.empty(shape, dtype)
            elif fill == 0:
                arr = np.zeros(shape, dtype)
            else:
                arr = np.full(shape, fill, dtype)
        else:
            arr, reused = self._pool.get(slot, role, self._gen, shape,
                                         dtype)
            if fill is not None:
                arr.fill(fill)
        _M_BUFFER.labels(feed=slot or "unnamed",
                         result="reused" if reused else "allocated").inc()
        return arr

    def _stack(self, rows, dtype, slot) -> np.ndarray:
        """``np.asarray(rows, dtype)`` into a kept buffer. One assignment
        of the whole list runs numpy's own discovery and per-row casting
        copy, so float32 / uint8 / float64 arrays, nested lists and flat
        Python lists give the bit-identical batch, and a ragged row
        raises numpy's ValueError. The buffer takes its row shape from
        the first row: assigning to any other shape would broadcast."""
        arr = self._buffer((len(rows),) + np.shape(rows[0]), dtype, slot)
        arr[...] = rows
        return arr

    def __call__(self, batch: List[Sequence]) -> Dict[str, Arg]:
        self._gen = (self._gen + 1) % self._rotate
        if self.pack:
            return self._convert_packed(batch)
        feeds = {}
        for name, itype in self.data_types:
            col = self.feeding[name]
            rows = [sample[col] for sample in batch]
            feeds[name] = self.convert_one(rows, itype, slot=name)
        return feeds

    def _convert_packed(self, batch: List[Sequence]) -> Dict[str, Arg]:
        """Packed-feed conversion: one shared first-fit-decreasing plan
        across slots, then per-slot fill of [R, T] value/mask/seg_ids
        arrays (the same pool, roles and generations as the unpacked
        path)."""
        cols = {name: self.feeding[name] for name, _ in self.data_types}
        lengths = {name: [len(sample[cols[name]]) for sample in batch]
                   for name, _ in self.data_types}
        for name, ls in lengths.items():
            # a zero-length sample would occupy a segment index with no
            # timesteps; the downstream sequence count is derived from
            # seg_ids (max+1 per row), so a trailing empty segment would
            # silently vanish from loss normalization and evaluator
            # totals — refuse rather than diverge from the padded run
            enforce(all(t > 0 for t in ls),
                    f"pack_sequences: feed slot {name!r} contains a "
                    "zero-length sequence; packed mode requires every "
                    "sample to have >= 1 step in every slot (filter "
                    "empty samples out upstream)")
        caps = {}
        for name, _ in self.data_types:
            longest = max(lengths[name], default=1)
            if self.pack_max_len:
                # explicit row length: honor it exactly (T is constant
                # across batches, so there is nothing left to bucket) —
                # only a longer-than-cap sample forces a bucketed bump
                caps[name] = self.pack_max_len if longest <= self.pack_max_len \
                    else _bucket(longest, self.bucket, self.bucket_rounding)
            else:
                caps[name] = _bucket(max(2 * longest, 1), self.bucket,
                                     self.bucket_rounding)
        plan = _pack_plan(lengths, caps)
        self.last_pack_plan = plan
        # round the row count up with inert filler rows so the feed
        # shape (and with it the compiled train step) doesn't churn as
        # the plan's natural R drifts batch to batch
        rr = self.pack_row_rounding
        R = -(-max(len(plan), 1) // rr) * rr
        feeds = {}
        for name, itype in self.data_types:
            rows = [sample[cols[name]] for sample in batch]
            feeds[name] = self._fill_packed_slot(rows, itype, plan,
                                                 caps[name], name, R)
        return feeds

    def _fill_packed_slot(self, rows, itype, plan, cap, slot, R) -> Arg:
        if itype.kind == "index":
            value = self._buffer((R, cap), np.int32, slot, fill=0)
        else:
            value = self._buffer((R, cap, itype.dim), np.float32, slot,
                                 fill=0)
        mask = self._buffer((R, cap), np.float32, slot, "mask", fill=0)
        seg = self._buffer((R, cap), np.int32, slot, "seg", fill=-1)
        real = 0
        for r, members in enumerate(plan):
            off = 0
            for s_idx, i in enumerate(members):
                t = len(rows[i])        # > 0: enforced in _convert_packed
                if itype.kind == "index":
                    value[r, off:off + t] = np.asarray(
                        rows[i], np.int32).reshape(t)
                else:
                    value[r, off:off + t] = np.asarray(
                        rows[i], np.float32).reshape(t, itype.dim)
                mask[r, off:off + t] = 1.0
                seg[r, off:off + t] = s_idx
                off += t
                real += t
        _M_PAD_FRACTION.labels(feed=slot or "unnamed", packed="1").observe(
            1.0 - real / float(R * cap))
        _M_PADDED_LEN.labels(feed=slot or "unnamed", packed="1").set(cap)
        return Arg(value, mask, seg)

    def convert_one(self, rows, itype, slot="") -> Arg:
        # slot tags the pool's buffers; callers converting several feeds
        # must pass distinct slots or same-shape feeds alias one buffer
        if not isinstance(itype, InputType):
            # raw ArgInfo from data layers declared with shape only
            return Arg(self._stack(rows, np.float32, slot))
        if itype.seq_type == SeqType.NO_SEQUENCE:
            return self._convert_flat(rows, itype, slot)
        return self._convert_seq(rows, itype, slot)

    def _convert_flat(self, rows, itype, slot="") -> Arg:
        if itype.kind == "dense":
            return Arg(self._stack(rows, np.float32, slot)
                       .reshape(len(rows), -1))
        if itype.kind == "index":
            return Arg(self._stack(rows, np.int32, slot)
                       .reshape(len(rows), 1))
        # sparse: rows are id lists (or (id, value) lists) -> padded ids
        K = itype.max_ids
        ids = self._buffer((len(rows), K), np.int32, slot, "ids", fill=-1)
        vals = self._buffer((len(rows), K), np.float32, slot, "vals",
                            fill=0)
        for i, r in enumerate(rows):
            if itype.kind == "sparse_value":
                pairs = list(r)[:K]
                for j, (idx, v) in enumerate(pairs):
                    ids[i, j] = idx
                    vals[i, j] = v
            else:
                rr = list(r)[:K]
                ids[i, :len(rr)] = rr
                vals[i, :len(rr)] = 1.0
        if itype.kind == "sparse_value":
            # ids travel in a float32 channel next to the values: exact
            # only below 2^24 — hashed-id spaces beyond that need a
            # different encoding, so fail loudly rather than corrupt
            enforce(int(ids.max(initial=0)) < (1 << 24),
                    "sparse_value ids >= 2^24 are not representable")
            return Arg(np.stack([ids.astype(np.float32), vals], axis=-1))
        return Arg(ids)

    def _convert_seq(self, rows, itype, slot="") -> Arg:
        nested = itype.seq_type == SeqType.SUB_SEQUENCE
        if nested:
            # rows: list of list of sub-sequences
            flat_rows, seg_rows = [], []
            for r in rows:
                flat, segs = [], []
                for si, sub in enumerate(r):
                    for step in sub:
                        flat.append(step)
                        segs.append(si)
                flat_rows.append(flat)
                seg_rows.append(segs)
            rows = flat_rows
        T = _bucket(max((len(r) for r in rows), default=1), self.bucket,
                    self.bucket_rounding)
        B = len(rows)
        if B and T:
            real = sum(min(len(r), T) for r in rows)
            _M_PAD_FRACTION.labels(feed=slot or "unnamed",
                                   packed="0").observe(
                1.0 - real / float(B * T))
            _M_PADDED_LEN.labels(feed=slot or "unnamed", packed="0").set(T)
        if itype.kind == "index":
            value = self._buffer((B, T), np.int32, slot, fill=0)
            mask = self._buffer((B, T), np.float32, slot, "mask", fill=0)
            for i, r in enumerate(rows):
                t = min(len(r), T)
                value[i, :t] = np.asarray(r[:t], np.int32).reshape(t)
                mask[i, :t] = 1.0
        else:
            dim = itype.dim
            value = self._buffer((B, T, dim), np.float32, slot, fill=0)
            mask = self._buffer((B, T), np.float32, slot, "mask", fill=0)
            for i, r in enumerate(rows):
                t = min(len(r), T)
                if t:
                    value[i, :t] = np.asarray(r[:t], np.float32).reshape(t, dim)
                mask[i, :t] = 1.0
        seg_ids = None
        if nested:
            seg_ids = self._buffer((B, T), np.int32, slot, "seg", fill=-1)
            for i, segs in enumerate(seg_rows):
                t = min(len(segs), T)
                seg_ids[i, :t] = segs[:t]
        return Arg(value, mask, seg_ids)
