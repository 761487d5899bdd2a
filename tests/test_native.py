"""Native C++ runtime tests — the analog of the reference's in-process
distributed tests (test_ParameterServer2.cpp drives a real server through
client RPCs inside the test process; go/master service_internal_test.go
timeout/failure semantics).
"""

import os
import time

import pytest

from paddle_tpu import native


pytestmark = pytest.mark.skipif(not native.ensure_built(),
                                reason="native toolchain unavailable")


def test_native_recordio_roundtrip(tmp_path):
    p = str(tmp_path / "data.rec")
    with native.NativeRecordIOWriter(p) as w:
        for i in range(100):
            w.write(f"record-{i}".encode())
    with native.NativeRecordIOReader(p) as r:
        assert len(r) == 100
        assert r.read(0) == b"record-0"
        assert r.read(99) == b"record-99"
        assert list(r)[50] == b"record-50"


def test_native_recordio_python_interop(tmp_path):
    """Native writer output must parse with the pure-Python reader and
    vice versa (same on-disk format)."""
    from paddle_tpu.io.recordio import RecordIOReader, RecordIOWriter

    p1 = str(tmp_path / "native.rec")
    with native.NativeRecordIOWriter(p1) as w:
        w.write(b"alpha")
        w.write(b"beta")
    with RecordIOReader(p1) as r:
        assert list(r) == [b"alpha", b"beta"]

    p2 = str(tmp_path / "python.rec")
    with RecordIOWriter(p2) as w:
        w.write(b"gamma")
    with native.NativeRecordIOReader(p2) as r:
        assert list(r) == [b"gamma"]


def test_buddy_allocator():
    a = native.BuddyAllocator(arena_size=1 << 16, min_block=256)
    p1 = a.alloc(1000)       # -> 1024 block
    p2 = a.alloc(256)
    assert p1 and p2 and p1 != p2
    assert a.used == 1024 + 256
    a.free(p1)
    assert a.used == 256
    # merged space is reusable for a large block
    p3 = a.alloc(1 << 15)
    assert p3 is not None
    a.free(p3)
    a.free(p2)
    assert a.used == 0
    assert a.peak >= 1024 + 256
    with pytest.raises(ValueError):
        a.free(12345)
    a.destroy()


def test_master_task_lifecycle(tmp_path):
    from paddle_tpu.distributed import MasterClient

    snap = str(tmp_path / "snap.txt")
    with native.MasterServer(port=0, snapshot_path=snap, timeout_s=60,
                             max_failures=2) as srv:
        c = MasterClient(port=srv.port)
        assert c.ping()
        ids = [c.add_task(f"shard-{i}") for i in range(3)]
        assert len(set(ids)) == 3

        t1 = c.get_task()
        t2 = c.get_task()
        assert t1[1].startswith("shard-") and t2[1].startswith("shard-")
        c.task_done(t1[0])
        c.task_failed(t2[0])          # requeued
        st = c.status()
        assert st["done"] == 1 and st["todo"] == 2

        # drain the rest
        done = 1
        while True:
            t = c.get_task()
            if t is None:
                break
            if t[0] < 0:
                time.sleep(0.05)
                continue
            c.task_done(t[0])
            done += 1
        assert done == 3
        assert c.status()["done"] == 3

        # new pass
        c.reset_pass()
        assert c.status()["todo"] == 3
        c.close()


def test_master_stop_with_connected_client():
    """Stop() must not deadlock while a persistent client connection is
    still open (ADVICE r1 medium: Serve() blocked in recv forever)."""
    import threading

    from paddle_tpu.distributed import MasterClient

    srv = native.MasterServer(port=0, timeout_s=60, max_failures=2)
    c = MasterClient(port=srv.port)
    assert c.ping()
    done = threading.Event()
    t = threading.Thread(target=lambda: (srv.stop(), done.set()))
    t.start()
    assert done.wait(timeout=10), "master stop deadlocked with open client"
    t.join()


def test_master_timeout_requeue(tmp_path):
    from paddle_tpu.distributed import MasterClient

    with native.MasterServer(port=0, timeout_s=1, max_failures=5) as srv:
        c = MasterClient(port=srv.port)
        c.add_task("slow-shard")
        t = c.get_task()
        assert t[1] == "slow-shard"
        # don't report done; wait past the lease
        deadline = time.time() + 5
        while time.time() < deadline:
            st = c.status()
            if st["todo"] == 1:
                break
            time.sleep(0.2)
        assert c.status()["todo"] == 1, "pending task was not requeued"
        c.close()


def test_master_failure_cap(tmp_path):
    from paddle_tpu.distributed import MasterClient

    with native.MasterServer(port=0, timeout_s=60, max_failures=1) as srv:
        c = MasterClient(port=srv.port)
        c.add_task("poison")
        t = c.get_task()
        c.task_failed(t[0])           # failure 1 -> requeue
        t = c.get_task()
        c.task_failed(t[0])           # failure 2 > cap -> discard
        st = c.status()
        assert st["discarded"] == 1
        assert c.get_task() is None   # FINISHED (nothing left)
        c.close()


def test_master_snapshot_recovery(tmp_path):
    from paddle_tpu.distributed import MasterClient

    snap = str(tmp_path / "snap.txt")
    srv = native.MasterServer(port=0, snapshot_path=snap)
    c = MasterClient(port=srv.port)
    c.add_task("a")
    c.add_task("b")
    t = c.get_task()          # leave one pending at crash time
    c.close()
    srv.stop()                # "crash"

    srv2 = native.MasterServer(port=0, snapshot_path=snap)
    c2 = MasterClient(port=srv2.port)
    st = c2.status()
    # pending lease voided on recovery -> both tasks todo again
    assert st["todo"] == 2 and st["pending"] == 0
    c2.close()
    srv2.stop()


def test_master_reader_end_to_end(tmp_path):
    """Records flow: recordio shards -> master tasks -> reader stream
    (the go/master client.go NextRecord analog)."""
    from paddle_tpu.distributed import MasterClient, master_reader
    from paddle_tpu.distributed.master_client import recordio_task_records

    paths = []
    for s in range(3):
        p = str(tmp_path / f"shard{s}.rec")
        with native.NativeRecordIOWriter(p) as w:
            for i in range(10):
                w.write(f"{s}:{i}".encode())
        paths.append(p)

    with native.MasterServer(port=0) as srv:
        c = MasterClient(port=srv.port)
        for p in paths:
            c.add_task(p)
        reader = master_reader(c, recordio_task_records)
        records = sorted(reader())
        assert len(records) == 30
        assert records[0] == b"0:0"
        c.close()


def test_feed_pool_reuses_buffers():
    """A loop's feeder assembles batches in its FeedBufferPool: the same
    slot+role+shape is the SAME storage on the next call (Matrix-reuse
    analog) and is filled again, distinct roles never alias, and the
    pool accounts for every byte it holds."""
    import numpy as np

    from paddle_tpu.trainer.feeder import DataFeeder, FeedBufferPool

    pool = FeedBufferPool()
    f = DataFeeder([], buffers=pool)
    a1 = f._buffer((4, 8), np.float32, "x", fill=0)
    a1[:] = 7.0
    a2 = f._buffer((4, 8), np.float32, "x", fill=0)  # same key: same memory
    assert a2.ctypes.data == a1.ctypes.data
    assert (a2 == 0).all()                          # re-filled per batch
    b = f._buffer((4, 8), np.float32, "x", "seg", fill=-1)  # other role
    assert b.ctypes.data != a1.ctypes.data and (b == -1).all()
    c = f._buffer((4, 8), np.float32, "x")          # no fill: left as is
    assert c.ctypes.data == a1.ctypes.data
    assert pool.nbytes == 2 * 4 * 8 * 4


def test_feeder_pooled_batches_match_fresh():
    """Pool-assembled feeds == a bare feeder's for every field kind."""
    import numpy as np

    from paddle_tpu import data_type
    from paddle_tpu.trainer.feeder import DataFeeder, FeedBufferPool

    types = [("d", data_type.dense_vector(3)),
             ("i", data_type.integer_value(5)),
             ("s", data_type.dense_vector_sequence(2)),
             ("n", data_type.integer_value_sub_sequence(9))]
    batch = [
        ([0.1, 0.2, 0.3], 2, [[1.0, 2.0], [3.0, 4.0]], [[1, 2], [3]]),
        ([0.4, 0.5, 0.6], 4, [[5.0, 6.0]], [[4]]),
    ]
    fa = DataFeeder(types, buffers=FeedBufferPool())
    fb = DataFeeder(types)
    for _ in range(3):  # repeated batches: reuse must not corrupt
        ra, rb = fa(batch), fb(batch)
        for k in ("d", "i", "s", "n"):
            np.testing.assert_array_equal(np.asarray(ra[k].value),
                                          np.asarray(rb[k].value))
            if rb[k].mask is not None:
                np.testing.assert_array_equal(np.asarray(ra[k].mask),
                                              np.asarray(rb[k].mask))
            if rb[k].seg_ids is not None:
                np.testing.assert_array_equal(np.asarray(ra[k].seg_ids),
                                              np.asarray(rb[k].seg_ids))
