"""Crash-safe parameter service (r18): durable pserver snapshots,
restart recovery, and client failover — the deterministic tier-1 pins.

What must hold (ISSUE 13 acceptance):

- a snapshot is one consistent cut: params + version + optimizer state,
  host-table rows + per-row slots, and the ROWPUSH dedup map restore
  BIT-FOR-BIT, and a retransmit spanning the restart is answered "dup"
  (at-most-once survives the crash);
- torn snapshots (truncated state.pkl, missing meta.json commit record)
  fall back to the previous valid one, r7-style;
- the version counter is MONOTONE across restarts (restart epoch in the
  high bits), and a push tagged with a pre-crash base version gets the
  clear "rejected" verdict so the trainer drops it and re-pulls;
- a relaunched server supersedes its own still-leased discovery record
  immediately (durable ident), and a client fails over to the new
  endpoint through the registry without caller intervention;
- a connection dying mid-reply surfaces as a retryable connection
  failure on EVERY verb — never a short read parsed as truncated state
  (the r12 ROWPUSH EOF bug class, audited across PULL/PUSH/ROWPULL/
  ROWPUSH/STATS).

The real-process SIGKILL + relaunch variant lives in
tests/test_async_multiproc.py (slow tier); the kill-point × intensity
grid is tools/chaos_sweep.py --pserver (quick subset pinned here).
"""

import os
import socket
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

from paddle_tpu import optimizer
from paddle_tpu.distributed.async_pserver import (EPOCH_SHIFT,
                                                  AsyncParamServer,
                                                  AsyncPServerClient,
                                                  publish_pserver,
                                                  version_epoch)
from paddle_tpu.distributed.discovery import DiscoveryRegistry
from paddle_tpu.host_table import HostRowStore, PServerRowStore, make_row_init
from paddle_tpu.io import checkpoint
from paddle_tpu.utils.retry import (AmbiguousOperationError, RetryError,
                                    RetryPolicy)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.chaos


def _nonzero_summary(delta):
    """Compact nonzero-only summary of a registry delta: counter totals,
    gauge values, histogram (count, sum). Keys flatten to 'name{k=v}'."""
    from paddle_tpu.observability.metrics import _label_str

    out = {}
    for name, entry in delta.items():
        for key, v in entry["series"].items():
            flat = name + (_label_str(key) if key else "")
            if entry["type"] == "histogram":
                if v["count"]:
                    out[flat] = {"count": v["count"],
                                 "sum_s": round(v["sum"], 6)}
            elif v:
                out[flat] = round(v, 6) if isinstance(v, float) else v
    return out


def _policy(**kw):
    import random

    kw.setdefault("max_attempts", 8)
    kw.setdefault("base_delay", 0.01)
    kw.setdefault("max_delay", 0.05)
    kw.setdefault("deadline", 10.0)
    kw.setdefault("rng", random.Random(0))
    kw.setdefault("name", "pserver")
    return RetryPolicy(**kw)


def _params():
    return {"w": np.ones((4, 2), np.float32) * 0.5,
            "enc/l0.w": np.zeros((3,), np.float32)}


def _dense_rows(opt=None):
    rs = np.random.RandomState(3)
    return {"emb": HostRowStore(
        "emb", (8, 3), opt or optimizer.Momentum(learning_rate=0.1,
                                                 momentum=0.9),
        dense=rs.randn(8, 3).astype(np.float32))}


def _lazy_rows():
    attr = types.SimpleNamespace(initial_mean=None, initial_std=0.1,
                                 initial_strategy="normal",
                                 initial_value=None)
    return {"emb": HostRowStore(
        "emb", (1 << 20, 3), optimizer.SGD(learning_rate=0.1),
        row_init=make_row_init(attr, 3, seed=7, name="emb"))}


def _server(snap_dir, rows_factory=_dense_rows, **kw):
    return AsyncParamServer(
        _params(), optimizer.Momentum(learning_rate=0.1, momentum=0.9),
        max_lagged=4, row_tables=rows_factory(), snapshot_dir=snap_dir,
        **kw)


# --- snapshot / restore ----------------------------------------------------

def test_snapshot_restore_roundtrip_bit_for_bit(tmp_path):
    """Params, optimizer slots, host-table rows + per-row slots, version
    accounting and the dedup map all survive a snapshot -> relaunch
    bit-for-bit; the restored optimizer continues the SAME trajectory
    (momentum state included) as an uninterrupted server."""
    snap = str(tmp_path / "snap")
    srv = _server(snap).start()
    cl = AsyncPServerClient(port=srv.port, policy=_policy())
    g = {k: np.full_like(v, 0.25) for k, v in _params().items()}
    _p, v = cl.pull()
    assert cl.push(g, v) == "applied"
    assert cl.push(g, v + 1) == "applied"
    assert cl.row_push("emb", np.array([1, 4]),
                       np.ones((2, 3), np.float32), 1, "c1", 1) == "applied"
    cl.snap()
    pre_params = {k: v.copy() for k, v in srv.params.items()}
    pre_rows = srv.row_tables["emb"].gather(np.arange(8))
    pre_slots = srv.row_tables["emb"].dense_slot_snapshot()
    # uninterrupted twin: one more identical push from the live server
    twin = _server(None)
    twin.params = {k: v.copy() for k, v in pre_params.items()}
    import jax
    twin._opt_state = jax.tree_util.tree_map(np.asarray, srv._opt_state)
    twin.version = srv.version
    assert twin._apply(g, srv.version) == "applied"
    cl.close()
    srv.stop()

    srv2 = _server(snap).start()
    assert srv2.restored_from
    for k in pre_params:
        np.testing.assert_array_equal(srv2.params[k], pre_params[k])
    np.testing.assert_array_equal(
        srv2.row_tables["emb"].gather(np.arange(8)), pre_rows)
    got_slots = srv2.row_tables["emb"].dense_slot_snapshot()
    for k in pre_slots:
        np.testing.assert_array_equal(got_slots[k], pre_slots[k])
    assert srv2.num_applied == 2
    # momentum continues exactly: restored server's next apply matches
    # the uninterrupted twin's
    cl2 = AsyncPServerClient(port=srv2.port, policy=_policy())
    _p2, v2 = cl2.pull()
    assert cl2.push(g, v2) == "applied"
    for k in twin.params:
        np.testing.assert_allclose(srv2.params[k], twin.params[k],
                                   rtol=1e-6, atol=1e-7)
    # the restored dedup map answers "dup" to a retransmit spanning the
    # restart — the gradient is never applied twice
    rows_now = srv2.row_tables["emb"].gather(np.arange(8))
    assert cl2.row_push("emb", np.array([1, 4]),
                        np.ones((2, 3), np.float32), 1, "c1", 1) == "dup"
    np.testing.assert_array_equal(
        srv2.row_tables["emb"].gather(np.arange(8)), rows_now)
    cl2.close()
    srv2.stop()


def test_lazy_host_table_rows_survive_restart_bit_for_bit(tmp_path):
    """The 100M-row mode: a lazily-backed table snapshots only touched
    rows; after the restart touched rows restore bit-for-bit and
    never-touched rows regenerate from the deterministic row_init."""
    snap = str(tmp_path / "snap")
    srv = AsyncParamServer({}, optimizer.SGD(learning_rate=0.1),
                           row_tables=_lazy_rows(),
                           snapshot_dir=snap).start()
    cl = AsyncPServerClient(port=srv.port, policy=_policy())
    ids = np.array([3, 99_999_0, 12345])
    before = cl.row_pull("emb", ids)             # materializes lazily
    assert cl.row_push("emb", ids, np.ones((3, 3), np.float32),
                       1, "c", 1) == "applied"
    trained = cl.row_pull("emb", ids)
    untouched = cl.row_pull("emb", np.array([777]))
    cl.snap()
    cl.close()
    srv.stop()

    srv2 = AsyncParamServer({}, optimizer.SGD(learning_rate=0.1),
                            row_tables=_lazy_rows(),
                            snapshot_dir=snap).start()
    cl2 = AsyncPServerClient(port=srv2.port, policy=_policy())
    np.testing.assert_array_equal(cl2.row_pull("emb", ids), trained)
    np.testing.assert_array_equal(cl2.row_pull("emb", np.array([777])),
                                  untouched)
    assert not np.array_equal(trained, before)
    cl2.close()
    srv2.stop()


def test_torn_snapshot_falls_back_to_previous_valid(tmp_path):
    """Truncate the newest snapshot's state.pkl (and, separately, drop
    the meta.json commit record): restore lands on the previous valid
    snapshot and counts the invalid ones."""
    from paddle_tpu.observability.metrics import default_registry

    snap = str(tmp_path / "snap")
    srv = _server(snap).start()
    cl = AsyncPServerClient(port=srv.port, policy=_policy())
    g = {k: np.full_like(v, 0.25) for k, v in _params().items()}
    _p, v = cl.pull()
    cl.push(g, v)
    cl.snap()                                    # snapshot A (version 1)
    good_params = {k: v.copy() for k, v in srv.params.items()}
    cl.push(g, v + 1)
    cl.snap()                                    # snapshot B (version 2)
    cl.push(g, v + 2)
    cl.snap()                                    # snapshot C (version 3)
    cl.close()
    srv.stop()
    snaps = checkpoint.list_state_snapshots(snap, "pserver")
    assert len(snaps) == 3
    # tear C: truncate state.pkl to half; break B: remove the commit rec
    c_state = os.path.join(snaps[2][1], "state.pkl")
    blob = open(c_state, "rb").read()
    with open(c_state, "wb") as f:
        f.write(blob[:len(blob) // 2])
    os.remove(os.path.join(snaps[1][1], "meta.json"))
    # both broken dirs fail up-front validation with a clear error
    for broken in (snaps[2][1], snaps[1][1]):
        with pytest.raises(checkpoint.CheckpointError):
            checkpoint.validate_state_snapshot(broken)
    checkpoint.validate_state_snapshot(snaps[0][1])   # A still valid

    default_registry.delta()
    srv2 = _server(snap).start()
    delta = _nonzero_summary(default_registry.delta())
    assert srv2.restored_from == snaps[0][1]
    for k in good_params:
        np.testing.assert_array_equal(srv2.params[k], good_params[k])
    assert delta.get("paddle_checkpoint_invalid_snapshots_total", 0) >= 2
    srv2.stop()


def test_snapshot_cadence_and_metrics(tmp_path):
    """snapshot_every_applies takes snapshots synchronously on the apply
    cadence (no SNAP command needed) and the paddle_pserver_snapshot_*
    series record each one."""
    from paddle_tpu.observability.metrics import default_registry

    default_registry.delta()
    snap = str(tmp_path / "snap")
    srv = _server(snap, snapshot_every_applies=2).start()
    cl = AsyncPServerClient(port=srv.port, policy=_policy())
    g = {k: np.full_like(v, 0.25) for k, v in _params().items()}
    _p, v = cl.pull()
    cl.push(g, v)
    assert len(checkpoint.list_state_snapshots(snap, "pserver")) == 0
    cl.push(g, v + 1)                            # 2nd apply -> snapshot
    assert len(checkpoint.list_state_snapshots(snap, "pserver")) == 1
    cl.push(g, v + 2)
    cl.push(g, v + 3)                            # 4th apply -> snapshot
    assert len(checkpoint.list_state_snapshots(snap, "pserver")) == 2
    delta = _nonzero_summary(default_registry.delta())
    assert delta.get('paddle_pserver_snapshots_total{ok="true"}', 0) >= 2
    assert any(k.startswith("paddle_pserver_snapshot_seconds")
               for k in delta)
    cl.close()
    srv.stop()


# --- version monotonicity + pre-crash rejection ----------------------------

def test_version_monotone_across_restart_and_precrash_push_rejected(
        tmp_path):
    snap = str(tmp_path / "snap")
    srv = _server(snap).start()
    cl = AsyncPServerClient(port=srv.port, policy=_policy())
    g = {k: np.full_like(v, 0.25) for k, v in _params().items()}
    _p, v0 = cl.pull()
    assert version_epoch(v0) == 0
    cl.push(g, v0)
    cl.snap()
    cl.push(g, v0 + 1)                  # applied AFTER the snapshot
    pre_crash_version = cl.stats()["version"]
    cl.close()
    srv.stop()

    srv2 = _server(snap).start()
    cl2 = AsyncPServerClient(port=srv2.port, policy=_policy())
    st = cl2.stats()
    # monotone: the restart epoch folds into the high bits, so even the
    # post-snapshot apply's (lost) version bump is strictly exceeded
    assert st["version"] > pre_crash_version
    assert version_epoch(st["version"]) == 1
    assert st["version"] == 1 << EPOCH_SHIFT
    # a pre-crash base version is REJECTED with the clear verdict (drop
    # + re-pull), never silently applied against rolled-back state
    assert cl2.push(g, pre_crash_version) == "rejected"
    assert cl2.stats()["rejected"] == 1
    _p2, v2 = cl2.pull()
    assert cl2.push(g, v2) == "applied"
    cl2.close()
    srv2.stop()


def test_double_crash_without_cadence_snapshot_keeps_epochs_distinct(
        tmp_path):
    """The epoch must be durable the moment a restore happens: a second
    crash landing BEFORE the first post-restore cadence snapshot must
    still come back at a FRESH epoch (the restore-time snapshot persists
    it), so the intervening epoch's pushes are rejected — never silently
    applied against rolled-back state."""
    snap = str(tmp_path / "snap")
    srv = _server(snap).start()
    cl = AsyncPServerClient(port=srv.port, policy=_policy())
    g = {k: np.full_like(v, 0.25) for k, v in _params().items()}
    _p, v0 = cl.pull()
    cl.push(g, v0)
    cl.snap()
    cl.close()
    srv.stop()                                   # crash 1

    srv2 = _server(snap).start()                 # epoch 1 (+ boot snap)
    cl2 = AsyncPServerClient(port=srv2.port, policy=_policy())
    _p2, v2 = cl2.pull()
    assert version_epoch(v2) == 1
    cl2.close()
    srv2.stop()                                  # crash 2: NO cadence
                                                 # snapshot ever ran
    srv3 = _server(snap).start()
    assert version_epoch(srv3.version) == 2      # fresh epoch, not 1
    cl3 = AsyncPServerClient(port=srv3.port, policy=_policy())
    assert cl3.push(g, v2) == "rejected"         # epoch-1 base is dead
    _p3, v3 = cl3.pull()
    assert cl3.push(g, v3) == "applied"
    cl3.close()
    srv3.stop()


# --- discovery supersede + client failover ---------------------------------

def test_discovery_ident_supersedes_own_stale_lease(tmp_path):
    """A restarted service presenting the SAME durable ident replaces
    its still-leased pre-crash record immediately; anyone else still
    waits out the TTL."""
    root = str(tmp_path / "disc")
    a = DiscoveryRegistry(root, ttl=30.0)
    assert a.put("pserver/addr", "127.0.0.1:1111", ident="ID-A")
    # crash: no delete, lease live for another ~30s
    b = DiscoveryRegistry(root, ttl=30.0)
    assert not b.put("pserver/addr", "127.0.0.1:2222")           # no ident
    assert not b.put("pserver/addr", "127.0.0.1:2222", ident="ID-B")
    assert b.put("pserver/addr", "127.0.0.1:2222", ident="ID-A")  # ours
    assert b.get("pserver/addr") == "127.0.0.1:2222"


def test_pserver_restart_under_live_lease_and_client_failover(tmp_path):
    """End to end: server A publishes under its durable ident, crashes
    (lease still live), relaunches on a NEW port, re-registers by
    superseding its own seat — and a client mid-conversation fails over
    through the registry without caller intervention."""
    from paddle_tpu.observability.metrics import default_registry

    snap = str(tmp_path / "snap")
    root = str(tmp_path / "disc")
    srv = _server(snap).start()
    reg = DiscoveryRegistry(root, ttl=60.0)      # TTL far beyond the test
    assert publish_pserver(reg, "127.0.0.1", srv.port, ident=srv.ident)
    cl = AsyncPServerClient.from_registry(
        DiscoveryRegistry(root, ttl=60.0), timeout=5.0, policy=_policy())
    g = {k: np.full_like(v, 0.25) for k, v in _params().items()}
    _p, v = cl.pull()
    cl.push(g, v)
    cl.snap()
    old_port = srv.port
    reg.stop_all()                               # crash: heartbeat stops,
    srv.stop()                                   # lease stays live
    cl._reset()                                  # the TCP conn dies too

    srv2 = _server(snap).start()
    assert srv2.port != old_port or True         # port may differ
    assert srv2.ident == srv.ident               # durable identity
    reg2 = DiscoveryRegistry(root, ttl=60.0)     # NEW process owner
    assert publish_pserver(reg2, "127.0.0.1", srv2.port, ident=srv2.ident)
    default_registry.delta()
    _p2, v2 = cl.pull()                          # transparent failover
    assert v2 == srv2.version
    delta = _nonzero_summary(default_registry.delta())
    if srv2.port != old_port:
        assert delta.get("paddle_pserver_client_failovers_total", 0) >= 1
    cl.close()
    srv2.stop()
    reg2.stop_all()


# --- the trainer-restart half of at-most-once ------------------------------

def test_pserver_rowstore_state_roundtrip_keeps_at_most_once(tmp_path):
    """PServerRowStore.state_dict carries (client_id, seq): a trainer
    resumed from an r7 snapshot presents the same push identity, so a
    replayed batch's re-flush of an already-applied seq is answered
    'dup' instead of double-training the table."""
    srv = AsyncParamServer({}, optimizer.SGD(learning_rate=0.1),
                           row_tables=_dense_rows(
                               optimizer.SGD(learning_rate=0.1))).start()
    cl = AsyncPServerClient(port=srv.port, policy=_policy())
    store = PServerRowStore("emb", (8, 3), cl)
    ids = np.array([2, 5])
    store.apply_sparse(ids, np.ones((2, 3), np.float32), 1)   # seq 1
    saved = store.state_dict()
    assert saved["remote"] and saved["seq"] == 1
    store.apply_sparse(ids, np.ones((2, 3), np.float32), 2)   # seq 2
    rows_after = cl.row_pull("emb", np.arange(8))
    # trainer restart: a FRESH store restores the snapshot identity and
    # replays the post-snapshot batch — seq 2 again, deduped server-side
    store2 = PServerRowStore("emb", (8, 3), cl)
    store2.load_state(saved)
    assert store2.client_id == saved["client_id"] and store2._seq == 1
    store2.apply_sparse(ids, np.ones((2, 3), np.float32), 2)  # seq 2: dup
    np.testing.assert_array_equal(cl.row_pull("emb", np.arange(8)),
                                  rows_after)
    cl.close()
    srv.stop()


# --- EOF-mid-reply audit (the r12 ROWPUSH bug class, every verb) -----------

class _ScriptedPeer:
    """A fake pserver that reads the request then writes an exact byte
    string and slams the connection — the deterministic 'died mid-reply'
    peer. Serves connections until closed (retries reconnect)."""

    def __init__(self, reply: bytes):
        self.reply = reply
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(4)
        self.port = self.sock.getsockname()[1]
        self._stop = False
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self):
        while not self._stop:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            try:
                conn.settimeout(0.2)
                try:                 # drain the request (line + any blob)
                    while conn.recv(65536):
                        pass
                except socket.timeout:
                    pass
                conn.sendall(self.reply)
            except OSError:
                pass
            finally:
                try:
                    conn.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                conn.close()

    def close(self):
        self._stop = True
        self.sock.close()


def _one_shot_client(port):
    return AsyncPServerClient(
        port=port, timeout=2.0,
        policy=_policy(max_attempts=1, deadline=None))


@pytest.mark.parametrize("reply", [b"", b"OK 3", b"OK"])
def test_pull_eof_mid_status_line_is_connection_failure(reply):
    """A PULL reply cut mid-line ('OK 3' truncated from 'OK 35\\n') must
    surface as a retryable connection failure — the old readline() path
    would have PARSED the truncated version as real state."""
    peer = _ScriptedPeer(reply)
    cl = _one_shot_client(peer.port)
    with pytest.raises((RetryError, ConnectionError)):
        cl.pull()
    cl.close()
    peer.close()


def test_pull_eof_mid_blob_is_connection_failure():
    peer = _ScriptedPeer(b"OK 3\n" + b"\x10\x00\x00")   # 3 of 8 len bytes
    cl = _one_shot_client(peer.port)
    with pytest.raises((RetryError, ConnectionError)):
        cl.pull()
    cl.close()
    peer.close()


def test_push_eof_mid_verdict_is_ambiguous_not_misparse():
    """PUSH saw 'OK app' (cut from 'OK applied 12\\n'): bytes reached the
    server, so the failure must be the at-most-once ambiguity — never a
    ValueError from unpacking a truncated verdict."""
    peer = _ScriptedPeer(b"OK app")
    cl = _one_shot_client(peer.port)
    with pytest.raises(AmbiguousOperationError):
        cl.push({"w": np.ones((2, 2), np.float32)}, 0)
    cl.close()
    peer.close()


def test_rowpull_eof_mid_reply_is_connection_failure():
    peer = _ScriptedPeer(b"OK 1")
    cl = _one_shot_client(peer.port)
    with pytest.raises((RetryError, ConnectionError)):
        cl.row_pull("emb", np.array([1]))
    cl.close()
    peer.close()


def test_rowpush_eof_mid_verdict_retries_not_misparse():
    """ROWPUSH is seq-deduplicated, so mid-reply EOF is retried freely:
    with a real server behind a flaky first reply the retry converges.
    Here: the scripted peer always cuts the reply -> RetryError (a
    ConnectionError), never a misparsed verdict."""
    peer = _ScriptedPeer(b"OK appli")
    cl = _one_shot_client(peer.port)
    with pytest.raises((RetryError, ConnectionError)):
        cl.row_push("emb", np.array([1]), np.ones((1, 3), np.float32),
                    1, "c", 1)
    cl.close()
    peer.close()


def test_stats_eof_mid_reply_is_connection_failure():
    peer = _ScriptedPeer(b"OK 5 3")              # cut from "OK 5 3 1 0\n"
    cl = _one_shot_client(peer.port)
    with pytest.raises((RetryError, ConnectionError)):
        cl.stats()
    cl.close()
    peer.close()


def test_rowpush_eof_then_real_server_dedups():
    """The full retry story on one client: first attempt dies mid-reply
    against a real server AFTER the apply (pserver.crash drop), the
    retransmit hits the seq dedup and converges to exactly one apply."""
    from paddle_tpu.distributed import faults

    srv = AsyncParamServer({}, optimizer.SGD(learning_rate=0.1),
                           row_tables=_dense_rows(
                               optimizer.SGD(learning_rate=0.1))).start()
    cl = AsyncPServerClient(port=srv.port, policy=_policy())
    before = srv.row_tables["emb"].gather(np.arange(8))
    plan = faults.FaultPlan([faults.FaultSpec("pserver.crash", "drop",
                                              at=1)])
    with plan.installed():
        verdict = cl.row_push("emb", np.array([2]),
                              np.ones((1, 3), np.float32), 1, "c", 1)
    assert verdict == "dup"          # applied once, retransmit deduped
    after = srv.row_tables["emb"].gather(np.arange(8))
    np.testing.assert_allclose(after[2], before[2] - 0.1, rtol=1e-6)
    cl.close()
    srv.stop()


# --- retry hook hardening --------------------------------------------------

def test_on_retry_hook_failure_does_not_abort_retries():
    """A failover hook crashing (registry briefly unreadable) must not
    abort the retry loop — the retry itself still runs."""
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("down")
        return "ok"

    def bad_hook(_e, _i):
        raise OSError("registry unreadable")

    pol = _policy(max_attempts=5)
    pol.sleep = lambda _s: None
    assert pol.run(flaky, on_retry=bad_hook) == "ok"
    assert len(calls) == 3


# --- the tier-1 sweep wiring ----------------------------------------------

def test_chaos_sweep_pserver_quick():
    """tools/chaos_sweep.py --pserver --quick: SIGKILL-mid-pass (fault
    'kill' = os._exit in a REAL child process), torn-snapshot and drop
    cells against a live trainer, with the continuously-sampled
    version-monotonicity invariant — the CI acceptance grid."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_sweep.py"),
         "--pserver", "--quick"],
        env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "0 failures" in r.stdout
