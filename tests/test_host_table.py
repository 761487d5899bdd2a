"""HBM-overflow embedding tables (ISSUE 7, docs/embedding_cache.md).

Pins the acceptance criteria:
- trajectory equivalence: host-backed + forced-small device row cache
  trains allclose to HBM-resident on losses AND final tables (SGD and
  AdaGrad, where the lazy per-row update is exactly the dense one),
  pipelined and synchronous, including across an r7 snapshot/resume;
- jaxpr pins: the compiled train step of a host-resident config holds
  NO [V, *]-shaped value, and the HBM-resident step is bit-identical
  whether or not the host-table machinery is asked for;
- exact-staleness conflict drains (hot row touched every batch) keep
  the pipelined trajectory equal to the synchronous one;
- the pserver-backed store (ROWPULL/ROWPUSH + seq dedup) trains the
  same trajectory as the local store, and converges through injected
  drop/delay faults on the flush path (chaos);
- cache hit-rate / prefetch-overlap / flush-queue metrics land in the
  r9 registry and tools/metrics_dump.py --prefix surfaces them.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.core.layer import layer_name_scope
from paddle_tpu.core.parameters import Parameters
from paddle_tpu.core.topology import Topology
from paddle_tpu.host_table import (HostRowStore, HostTableRuntime,
                                   PServerRowStore, make_row_init)
from paddle_tpu.models.text import ctr_wide_deep
from paddle_tpu.trainer import event as v2_event
from paddle_tpu.trainer.trainer import SGD, make_train_step

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

FEEDING = {"wide_ids": 0, "deep_ids": 1, "click": 2}
W, V, K = 64, 131, 8          # V prime-ish: can't appear incidentally
HOST_TABLES = ["_deep_emb", "_wide_w"]


def _reader(n_batches, batch=16, seed=0, hot_row=None, deep_vocab=V):
    r = np.random.RandomState(seed)
    data = []
    for _ in range(n_batches):
        rows = []
        for _i in range(batch):
            wide = r.choice(W, r.randint(1, K), replace=False).tolist()
            deep = r.choice(deep_vocab, r.randint(1, K),
                            replace=False).tolist()
            if hot_row is not None and hot_row not in deep:
                deep[0] = hot_row
            rows.append((wide, deep, int(r.randint(0, 2))))
        data.append(rows)
    return lambda: iter(data)


def _trainer(opt=None, deep_vocab=V, host_resident=False):
    with layer_name_scope():
        _ins, _lab, _out, cost = ctr_wide_deep(
            wide_dim=W, deep_vocab=deep_vocab, emb_dim=4, max_ids=K,
            hidden=8, host_resident=host_resident)
    topo = Topology(cost)
    params = Parameters.from_topology(topo, jax.random.PRNGKey(7))
    return SGD(cost=cost, parameters=params,
               update_equation=opt or optimizer.SGD(learning_rate=0.1))


def _run(t, reader, host=False, costs=None, **kw):
    def handler(ev):
        if isinstance(ev, v2_event.EndIteration) and costs is not None:
            costs.append(ev.cost)
    if host:
        kw.setdefault("host_tables", HOST_TABLES)
    t.train(reader, num_passes=1, event_handler=handler, feeding=FEEDING,
            **kw)
    return t


def _host_tables_final(t):
    t._host_rt.barrier()
    return {p: np.asarray(s.gather(np.arange(s.shape[0])))
            for p, s in t._host_rt.tables.items()}


def _hbm_tables_final(t):
    return {p: np.asarray(t.parameters.get(p)) for p in HOST_TABLES}


# --- store units ----------------------------------------------------------

def test_store_dense_gather_apply_sgd():
    table0 = np.arange(20, dtype=np.float32).reshape(10, 2)
    store = HostRowStore("w", (10, 2), optimizer.SGD(learning_rate=0.5),
                         dense=table0)
    ids = np.array([3, 7])
    np.testing.assert_array_equal(store.gather(ids), table0[ids])
    g = np.ones((2, 2), np.float32)
    store.apply_sparse(ids, g, step=1)
    np.testing.assert_allclose(store.gather(ids), table0[ids] - 0.5 * g)
    # untouched rows unchanged
    np.testing.assert_array_equal(store.gather(np.array([0, 9])),
                                  table0[[0, 9]])


def test_store_apply_dedups_and_drops_negatives():
    table0 = np.zeros((8, 2), np.float32)
    store = HostRowStore("w", (8, 2), optimizer.SGD(learning_rate=1.0),
                         dense=table0)
    ids = np.array([2, 2, -1, 2])
    g = np.ones((4, 2), np.float32)
    store.apply_sparse(ids, g, step=1)
    got = store.gather(np.arange(8))
    np.testing.assert_allclose(got[2], -3.0 * np.ones(2))   # summed once
    assert np.all(got[[0, 1, 3, 4, 5, 6, 7]] == 0.0)


def test_store_lazy_rows_deterministic_and_snapshotable():
    init = make_row_init(paddle.attr.ParamAttr(), fan_in=4, seed=1,
                         name="w")
    store = HostRowStore("w", (10**8, 4),
                         optimizer.SGD(learning_rate=0.5), row_init=init)
    ids = np.array([5, 99_999_999, 12345])
    first = store.gather(ids)
    np.testing.assert_array_equal(store.gather(ids), first)   # stable
    assert first.std() > 0                                    # not zeros
    store.apply_sparse(ids[:2], np.ones((2, 4), np.float32), step=1)
    after = store.gather(ids)
    np.testing.assert_allclose(after[:2], first[:2] - 0.5)
    np.testing.assert_array_equal(after[2], first[2])
    assert store.touched_rows == 2
    # snapshot round-trip into a fresh store: touched rows restore,
    # untouched rows regenerate identically
    d = store.state_dict()
    store2 = HostRowStore("w", (10**8, 4),
                          optimizer.SGD(learning_rate=0.5), row_init=init)
    store2.load_state(d)
    np.testing.assert_array_equal(store2.gather(ids), after)


# --- trajectory equivalence (the acceptance pin) --------------------------

@pytest.mark.parametrize("opt_name", ["sgd", "adagrad"])
@pytest.mark.parametrize("depth", [0, 2])
def test_host_backed_matches_hbm_resident(opt_name, depth):
    """Host store + forced-small cache == HBM-resident training: allclose
    losses and final tables (lazy per-row SGD/AdaGrad IS the dense
    update), synchronous and pipelined."""
    def mk():
        return (optimizer.SGD(learning_rate=0.1) if opt_name == "sgd"
                else optimizer.AdaGrad(learning_rate=0.1))

    hbm_costs, host_costs = [], []
    t_hbm = _run(_trainer(mk()), _reader(6), costs=hbm_costs,
                 pipeline_depth=depth)
    t_host = _run(_trainer(mk()), _reader(6), host=True, costs=host_costs,
                  pipeline_depth=depth, host_cache_rows=128)
    np.testing.assert_allclose(hbm_costs, host_costs, rtol=1e-5, atol=1e-6)
    ref, got = _hbm_tables_final(t_hbm), _host_tables_final(t_host)
    for p in HOST_TABLES:
        np.testing.assert_allclose(got[p], ref[p], rtol=1e-5, atol=1e-6)
    t_host._host_rt.close()


def test_hot_row_conflicts_pipelined_equals_sync():
    """Every batch touches deep row 3 — the exact-staleness conflict
    path drains the pipeline so each gather sees the previous flush;
    depth-4 trajectory must equal the synchronous one (and the conflict
    counter must have fired)."""
    from paddle_tpu.observability.metrics import default_registry

    costs0, costs4 = [], []
    t0 = _run(_trainer(), _reader(6, hot_row=3), host=True, costs=costs0,
              pipeline_depth=0)
    before = default_registry.snapshot().get(
        "paddle_embcache_conflict_drains_total", {"series": {}})
    n_before = sum(before["series"].values()) if before["series"] else 0
    t4 = _run(_trainer(), _reader(6, hot_row=3), host=True, costs=costs4,
              pipeline_depth=4)
    after = default_registry.snapshot()[
        "paddle_embcache_conflict_drains_total"]
    assert sum(after["series"].values()) > n_before
    np.testing.assert_allclose(costs0, costs4, rtol=1e-6, atol=1e-7)
    for p in HOST_TABLES:
        np.testing.assert_allclose(_host_tables_final(t4)[p],
                                   _host_tables_final(t0)[p],
                                   rtol=1e-6, atol=1e-7)
    t0._host_rt.close()
    t4._host_rt.close()


def test_async_staleness_mode_trains():
    """host_staleness='async' (the reference async-pserver semantics):
    no conflict drains, bounded row staleness — must train end to end
    and actually move the touched rows."""
    t = _run(_trainer(), _reader(5, hot_row=3), host=True,
             pipeline_depth=3, host_staleness="async")
    final = _host_tables_final(t)
    assert np.abs(final["_deep_emb"][3]).sum() > 0
    t._host_rt.close()


def test_snapshot_resume_equivalence(tmp_path):
    """r7 crash/resume through the host path: crash mid-pass, resume
    from the step snapshot (params + host store rows + per-row slots),
    final tables match BOTH the uninterrupted host run and the
    HBM-resident reference."""
    class _Crash(RuntimeError):
        pass

    def crash_after(n):
        state = {"n": 0}

        def handler(ev):
            if isinstance(ev, v2_event.EndIteration):
                state["n"] += 1
                if state["n"] >= n:
                    raise _Crash()
        return handler

    ref = _hbm_tables_final(_run(_trainer(), _reader(8)))
    uninterrupted = _host_tables_final(
        _run(_trainer(), _reader(8), host=True))

    snap = str(tmp_path / "snaps")
    t1 = _trainer()
    with pytest.raises(_Crash):
        t1.train(_reader(8), num_passes=1, feeding=FEEDING,
                 event_handler=crash_after(5), host_tables=HOST_TABLES,
                 save_every_n_batches=2, snapshot_dir=snap)
    t1._host_rt.close()
    found = SGD.load_step_resume(snap)
    assert found is not None
    loaded, resume = found
    assert resume.get("host_tables"), "snapshot must carry host tables"

    t2 = _trainer()
    for name in loaded.names():
        t2.parameters.set(name, loaded.get(name))
    t2.train(_reader(8), num_passes=1, feeding=FEEDING,
             resume_state=resume, host_tables=HOST_TABLES,
             save_every_n_batches=2, snapshot_dir=snap)
    got = _host_tables_final(t2)
    for p in HOST_TABLES:
        np.testing.assert_allclose(got[p], uninterrupted[p],
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(got[p], ref[p], rtol=1e-5, atol=1e-6)
    t2._host_rt.close()


# --- jaxpr pins -----------------------------------------------------------

def _step_jaxpr(host: bool):
    with layer_name_scope():
        _ins, _lab, _out, cost = ctr_wide_deep(
            wide_dim=W, deep_vocab=V, emb_dim=4, max_ids=K, hidden=8)
    topo = Topology(cost)
    loss = topo.loss_fn(cost)
    static = topo.static_map()
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = optimizer.SGD(learning_rate=0.1)
    host_tables = tuple(HOST_TABLES) if host else ()
    if host:
        cache = 32
        for p in HOST_TABLES:
            params[p] = jnp.zeros((cache,) + params[p].shape[1:])
        static = {**static, **{p: True for p in HOST_TABLES}}
    opt_state = opt.init(params)
    if host:
        for p in HOST_TABLES:
            opt_state[p] = {}
    step = make_train_step(loss, opt, static, donate=False,
                           jit_compile=False, host_tables=host_tables)
    rng = jax.random.PRNGKey(0)
    feeds = _jaxpr_feeds()
    return jax.make_jaxpr(step)(params, opt_state, rng, feeds)


def _jaxpr_feeds():
    from paddle_tpu.core.arg import Arg

    return {"wide_ids": Arg(jnp.zeros((8, K), jnp.int32)),
            "deep_ids": Arg(jnp.zeros((8, K), jnp.int32)),
            "click": Arg(jnp.zeros((8, 1), jnp.int32))}


def test_host_resident_jaxpr_has_no_vocab_wide_value():
    """THE pin: with host tables, no value anywhere in the compiled
    train step has the vocab as a leading dim — the [V, D] table simply
    does not exist in the program."""
    jx = _step_jaxpr(host=True)

    def walk(jaxpr):
        for v in list(jaxpr.invars) + list(jaxpr.outvars):
            if hasattr(v, "aval"):
                yield v.aval
        for eqn in jaxpr.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                if hasattr(v, "aval"):
                    yield v.aval
        for sub in jax.core.subjaxprs(jaxpr):
            yield from walk(sub)

    bad = [a for a in walk(jx.jaxpr)
           if getattr(a, "shape", None) and V in a.shape]
    assert not bad, f"vocab-wide values leaked into the step: {bad[:5]}"


def test_hbm_jaxpr_identical_with_feature_off():
    """HBM-resident configs must compile the EXACT pre-PR program: the
    step traced with host_tables=() equals the step traced through the
    default path, byte for byte."""
    with layer_name_scope():
        _ins, _lab, _out, cost = ctr_wide_deep(
            wide_dim=W, deep_vocab=V, emb_dim=4, max_ids=K, hidden=8)
    topo = Topology(cost)
    loss = topo.loss_fn(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = optimizer.SGD(learning_rate=0.1)
    opt_state = opt.init(params)
    rng = jax.random.PRNGKey(0)
    feeds = _jaxpr_feeds()

    def jx(**kw):
        import re

        step = make_train_step(loss, opt, topo.static_map(), donate=False,
                               jit_compile=False, **kw)
        s = str(jax.make_jaxpr(step)(params, opt_state, rng, feeds))
        # object reprs in eqn params carry run-specific addresses
        return re.sub(r"0x[0-9a-f]+", "0x0", s)

    assert jx() == jx(host_tables=())


# --- selection / guard rails ---------------------------------------------

def test_host_param_selection_threshold_and_attr():
    with layer_name_scope():
        _ins, _lab, _out, cost = ctr_wide_deep(
            wide_dim=W, deep_vocab=V, emb_dim=4, max_ids=K, hidden=8)
    topo = Topology(cost)
    assert topo.host_param_names() == []
    # threshold: deep table has V=131 rows, wide has 64
    assert topo.host_param_names(min_rows=100) == ["_deep_emb"]
    assert topo.host_param_names(min_rows=10) == HOST_TABLES
    # attr opt-in materializes nothing for the table
    with layer_name_scope():
        _ins, _lab, _out, cost = ctr_wide_deep(
            wide_dim=W, deep_vocab=V, emb_dim=4, max_ids=K, hidden=8,
            host_resident=True)
    topo2 = Topology(cost)
    assert topo2.host_param_names() == HOST_TABLES
    params = topo2.init_params(jax.random.PRNGKey(0))
    assert "_deep_emb" not in params and "_wide_w" not in params
    # skipping host tables must NOT perturb other params' init draws
    params_all = topo.init_params(jax.random.PRNGKey(0))
    for k in params:
        np.testing.assert_array_equal(params[k], params_all[k])


def test_forced_small_cache_overflow_is_loud():
    t = _trainer()
    with pytest.raises(Exception, match="host_cache_rows"):
        t.train(_reader(2, batch=32), num_passes=1, feeding=FEEDING,
                host_tables=HOST_TABLES, host_cache_rows=4)


def test_feeds_mapping_rejects_non_embedding_consumer():
    from paddle_tpu import data_type, layer

    with layer_name_scope():
        x = layer.data(name="x", type=data_type.dense_vector(8))
        y = layer.data(name="y", type=data_type.integer_value(2))
        out = layer.fc(input=x, size=2,
                       param_attr=paddle.attr.ParamAttr(
                           name="_big_fc", host_resident=True))
        cost = layer.classification_cost(input=out, label=y)
    topo = Topology(cost)
    with pytest.raises(Exception, match="embedding"):
        topo.host_table_feeds(["_big_fc"])


# --- pserver-backed store -------------------------------------------------

def _pserver_setup(opt_factory):
    from paddle_tpu.distributed.async_pserver import (AsyncParamServer,
                                                      AsyncPServerClient)

    with layer_name_scope():
        _ins, _lab, _out, cost = ctr_wide_deep(
            wide_dim=W, deep_vocab=V, emb_dim=4, max_ids=K, hidden=8)
    topo = Topology(cost)
    params = Parameters.from_topology(topo, jax.random.PRNGKey(7))
    specs = topo.param_specs()
    row_tables = {p: HostRowStore(p, specs[p].shape, opt_factory(),
                                  dense=np.asarray(params[p]))
                  for p in HOST_TABLES}
    srv = AsyncParamServer({}, opt_factory(),
                           row_tables=row_tables).start()
    cli = AsyncPServerClient("127.0.0.1", srv.port)

    def factory(pname, spec):
        return PServerRowStore(pname, spec.shape, cli)

    return srv, cli, factory, row_tables


def test_pserver_backed_training_matches_local():
    """The 'pserver-process backed' option: same trajectory as the
    local host store (the server applies the identical per-row rule)."""
    def mk():
        return optimizer.SGD(learning_rate=0.1)

    local_costs = []
    t_local = _run(_trainer(mk()), _reader(5), host=True,
                   costs=local_costs)
    local = _host_tables_final(t_local)
    t_local._host_rt.close()

    srv, cli, factory, row_tables = _pserver_setup(mk)
    try:
        remote_costs = []
        t = _run(_trainer(mk()), _reader(5), host=True,
                 costs=remote_costs, host_store=factory)
        t._host_rt.barrier()
        np.testing.assert_allclose(local_costs, remote_costs,
                                   rtol=1e-6, atol=1e-7)
        for p in HOST_TABLES:
            got = row_tables[p].gather(
                np.arange(row_tables[p].shape[0]))
            np.testing.assert_allclose(got, local[p], rtol=1e-6,
                                       atol=1e-7)
        t._host_rt.close()
    finally:
        cli.close()
        srv.stop()


@pytest.mark.chaos
def test_flush_chaos_drop_delay_converges():
    """distributed/faults.py drops the first two ROWPUSHes and delays a
    later one: the seq-deduplicated retry path must converge to the
    no-fault trajectory (VERDICT: retries may not double-apply)."""
    from paddle_tpu.distributed import faults

    def mk():
        return optimizer.SGD(learning_rate=0.1)

    # no-fault reference
    srv0, cli0, factory0, tables0 = _pserver_setup(mk)
    try:
        _run(_trainer(mk()), _reader(5), host=True,
             host_store=factory0)._host_rt.barrier()
        ref = {p: tables0[p].gather(np.arange(tables0[p].shape[0]))
               for p in HOST_TABLES}
    finally:
        cli0.close()
        srv0.stop()

    plan = faults.FaultPlan([
        faults.FaultSpec("pserver.rowpush", "drop", at=1, count=2),
        faults.FaultSpec("pserver.rowpush", "delay", at=5, count=1,
                         seconds=0.05),
    ])
    srv, cli, factory, tables = _pserver_setup(mk)
    try:
        with plan.installed():
            t = _run(_trainer(mk()), _reader(5), host=True,
                     host_store=factory)
            t._host_rt.barrier()
        assert [pt for pt, _n, act in plan.fired()
                if act == "drop"] == ["pserver.rowpush"] * 2
        for p in HOST_TABLES:
            got = tables[p].gather(np.arange(tables[p].shape[0]))
            np.testing.assert_allclose(got, ref[p], rtol=1e-6, atol=1e-7)
        t._host_rt.close()
    finally:
        cli.close()
        srv.stop()


# --- observability / tools ------------------------------------------------

def test_cache_metrics_in_registry_and_dump():
    from paddle_tpu.observability.metrics import default_registry

    t = _run(_trainer(), _reader(4), host=True, pipeline_depth=2)
    t._host_rt.close()
    snap = default_registry.to_json()
    for fam in ("paddle_embcache_hit_rate",
                "paddle_embcache_prefetch_seconds",
                "paddle_embcache_prefetch_overlap_seconds",
                "paddle_embcache_flush_queue_depth",
                "paddle_embcache_rows_gathered_total",
                "paddle_embcache_rows_flushed_total"):
        assert fam in snap, fam
        assert snap[fam]["series"], fam
    # metrics_dump --prefix surfaces exactly the cache series with
    # histogram p50/p95 columns
    import io

    from metrics_dump import render

    buf = io.StringIO()
    rows = render(snap, out=buf, prefix="paddle_embcache")
    text = buf.getvalue()
    assert rows >= 6
    assert "paddle_embcache_hit_rate" in text
    assert "p95<=" in text
    assert "paddle_train_step_seconds" not in text


def test_hit_rate_reflects_row_reuse():
    """Unit-level reuse pin: staging the same ids twice with no flush in
    between serves every row from the resident copy (hit rate 1.0, no
    store gather); a flush in between dirties its rows and forces a
    re-gather for exactly those."""
    from paddle_tpu.core.arg import Arg

    store = HostRowStore("w", (32, 2), optimizer.SGD(learning_rate=1.0),
                         dense=np.arange(64, dtype=np.float32)
                         .reshape(32, 2))
    rt = HostTableRuntime({"w": store}, {"w": ["ids"]})
    feeds = {"ids": Arg(np.array([[1, 2, 3, -1]], np.int32))}
    s1 = rt.stage(feeds)
    np.testing.assert_array_equal(s1.feeds["ids"].value,
                                  [[0, 1, 2, -1]])          # slot space
    np.testing.assert_array_equal(s1.caches["w"][:3],
                                  store.gather(np.array([1, 2, 3])))
    s2 = rt.stage(feeds)                                    # warm: all hit
    np.testing.assert_array_equal(s2.caches["w"], s1.caches["w"])
    # flush row 2 -> dirty -> restaged cache picks up the new value
    rt.mark_dispatched(s2)
    rt.flush_async(s2, {"w": np.ones((s2.caches["w"].shape[0], 2),
                                     np.float32)}, step=1)
    rt.barrier()
    s3 = rt.stage(feeds)
    np.testing.assert_array_equal(s3.caches["w"][:3],
                                  store.gather(np.array([1, 2, 3])))
    assert not np.allclose(s3.caches["w"][:3], s2.caches["w"][:3])
    rt.close()


# --- post-review regression pins ------------------------------------------

def test_lazy_row_init_stable_across_hash_seeds():
    """make_row_init must not depend on Python hash(): PYTHONHASHSEED
    randomization would regenerate DIFFERENT never-touched rows after a
    process restart, silently breaking lazy snapshot/resume."""
    import subprocess

    script = (
        "import numpy as np\n"
        "from paddle_tpu.attr import ParamAttr\n"
        "from paddle_tpu.host_table import make_row_init\n"
        "init = make_row_init(ParamAttr(name='_t'), 16, 7, '_t')\n"
        "print(init(np.array([0, 3, 99999983]), (4,)).tobytes().hex())\n")
    outs = set()
    for hs in ("0", "424242"):
        env = dict(os.environ, PYTHONHASHSEED=hs, JAX_PLATFORMS="cpu")
        outs.add(subprocess.check_output(
            [sys.executable, "-c", script], env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ).strip())
    assert len(outs) == 1, "lazy row init varies with PYTHONHASHSEED"


def test_host_tables_refuse_global_clipping_and_model_average():
    """Both would silently diverge from the HBM run (cache grads are
    popped before the global norm; no slot to average a host table) —
    they must refuse loudly instead."""
    t = _trainer(optimizer.SGD(learning_rate=0.1,
                               gradient_clipping_threshold=1.0,
                               global_clipping=True))
    with pytest.raises(NotImplementedError, match="global_clipping"):
        _run(t, _reader(1), host=True)
    t2 = _trainer(optimizer.SGD(
        learning_rate=0.1,
        model_average=optimizer.ModelAverage(average_window=0.5)))
    with pytest.raises(NotImplementedError, match="model_average"):
        _run(t2, _reader(1), host=True)


def test_switch_host_mode_off_then_on_same_trainer():
    """train(host_tables=[...]) then train(host_tables=[]) on the SAME
    trainer: the host-mode compile state (static flags, 5-tuple step
    fns) must be undone, the synced-back table must keep training on
    device, and a third host run must reuse the store's trained rows."""
    t = _trainer()
    _run(t, _reader(3), host=True, host_cache_rows=256)
    synced = np.array(t.parameters.get("_deep_emb"))
    assert np.abs(synced).sum() > 0
    # off: trains the table on device from the synced values
    t.train(_reader(3, seed=5), num_passes=1, feeding=FEEDING,
            host_tables=[])
    after_hbm = np.array(t.parameters.get("_deep_emb"))
    assert not np.allclose(synced, after_hbm), \
        "table did not train after switching host mode off"
    # on again: the reused store must carry the device-trained values
    # forward? no — the store was closed; a fresh runtime seeds densely
    # from the CURRENT parameters, so training continues from after_hbm
    _run(t, _reader(3, seed=9), host=True, host_cache_rows=256)
    final = _host_tables_final(t)
    assert not np.allclose(final["_deep_emb"], after_hbm)
    t._host_rt.close()


def test_end_pass_parameters_carry_trained_table():
    """A user saving trainer.parameters in an EndPass handler (the v2
    checkpoint flow) must see the TRAINED table, not its init values."""
    t = _trainer()
    init = np.array(t.parameters.get("_deep_emb"))
    seen = {}

    def handler(ev):
        if isinstance(ev, v2_event.EndPass):
            seen["table"] = np.array(t.parameters.get("_deep_emb"))

    t.train(_reader(4), num_passes=1, feeding=FEEDING,
            event_handler=handler, host_tables=HOST_TABLES,
            host_cache_rows=256)
    assert "table" in seen
    assert not np.allclose(seen["table"], init), \
        "EndPass parameters still hold the init table"
    np.testing.assert_allclose(
        seen["table"],
        np.asarray(t._host_rt.tables["_deep_emb"].dense_snapshot()))
    t._host_rt.close()


def test_second_train_call_applies_changed_host_knobs():
    """A second train() on the same trainer reuses the runtime (trained
    rows) but must apply changed cache/staleness knobs, not silently
    keep the first call's."""
    t = _trainer()
    _run(t, _reader(2), host=True, host_cache_rows=256)
    rt = t._host_rt
    assert rt._fixed_cap == 256 and rt.staleness == "exact"
    _run(t, _reader(2, seed=4), host=True, host_cache_rows=512,
         host_staleness="async", host_flush_inflight=2)
    assert t._host_rt is rt, "same-table rerun must reuse the runtime"
    assert rt._fixed_cap == 512
    assert rt.staleness == "async"
    assert rt._queue.maxsize == 2
    # a forced-too-small cache on a rerun must now fail loudly
    with pytest.raises(Exception, match="host_cache_rows"):
        _run(t, _reader(1, batch=64), host=True, host_cache_rows=4)
    t._host_rt.close()


def test_stage_first_batch_with_no_touched_rows():
    """Auto-sizing mode must survive a first batch whose ids are all
    absent/negative for a table (was: KeyError from the uninitialized
    per-table cap)."""
    from paddle_tpu.core.arg import Arg

    store = HostRowStore("w", (32, 2), optimizer.SGD(learning_rate=1.0),
                         dense=np.zeros((32, 2), np.float32))
    rt = HostTableRuntime({"w": store}, {"w": ["ids"]})
    feeds = {"ids": Arg(np.array([[-1, -1]], np.int32))}
    s = rt.stage(feeds)                       # must not raise
    np.testing.assert_array_equal(s.feeds["ids"].value, [[-1, -1]])
    assert s.caches["w"].shape[0] >= 1
    # and a later real batch works from the seeded cap
    s2 = rt.stage({"ids": Arg(np.array([[3, 5]], np.int32))})
    np.testing.assert_array_equal(s2.feeds["ids"].value, [[0, 1]])
    rt.close()


def test_switch_to_different_host_table_set_unfreezes_dropped_table():
    """train(host_tables=[both]) then train(host_tables=['_deep_emb']):
    the dropped '_wide_w' must return to normal device training (was:
    stale _static=True froze it silently) and the old runtime's flush
    worker must be stopped."""
    t = _trainer()
    _run(t, _reader(2), host=True, host_cache_rows=256)
    old_rt = t._host_rt
    wide_before = np.array(t.parameters.get("_wide_w"))
    t.train(_reader(3, seed=6), num_passes=1, feeding=FEEDING,
            host_tables=["_deep_emb"], host_cache_rows=256)
    assert t._host_tables == ("_deep_emb",)
    assert not old_rt._worker.is_alive(), "old flush worker leaked"
    assert not t._static.get("_wide_w", False), \
        "_wide_w left frozen behind a stale static flag"
    wide_after = np.array(t.parameters.get("_wide_w"))
    assert not np.allclose(wide_before, wide_after), \
        "dropped host table did not train on device"
    t._host_rt.close()


def test_preemption_parameters_carry_trained_table():
    """A preempted run's returned Parameters must carry the trained
    host table (was: _strip_host dropped it and the preemption path
    never synced the store back)."""
    import threading

    t = _trainer()
    init = np.array(t.parameters.get("_deep_emb"))
    ev = threading.Event()
    state = {"n": 0}

    def handler(e):
        if isinstance(e, v2_event.EndIteration):
            state["n"] += 1
            if state["n"] >= 3:
                ev.set()

    t.train(_reader(6), num_passes=1, feeding=FEEDING,
            event_handler=handler, host_tables=HOST_TABLES,
            host_cache_rows=256, preempt_event=ev)
    assert t.preempted
    assert "_deep_emb" in t.parameters
    assert not np.allclose(np.array(t.parameters.get("_deep_emb")), init)
    t._host_rt.close()


def test_rowpush_retry_after_failed_apply_is_not_dropped():
    """A ROWPUSH whose server-side apply FAILS must not claim its seq:
    the client's retry of the same seq has to be applied, not answered
    'dup' (was: seq recorded before apply -> failed apply + retry =
    silently dropped gradient)."""
    def mk():
        return optimizer.SGD(learning_rate=1.0)

    srv, cli, factory, row_tables = _pserver_setup(mk)
    try:
        store = row_tables["_deep_emb"]
        real = store.apply_sparse
        calls = {"n": 0}

        def flaky(ids, values, step):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected apply failure")
            return real(ids, values, step)

        store.apply_sparse = flaky
        remote = PServerRowStore("_deep_emb", store.shape, cli)
        before = store.gather(np.array([5]))
        remote.apply_sparse(np.array([5]), np.ones((1, 4), np.float32),
                            step=1)
        after = store.gather(np.array([5]))
        assert calls["n"] == 2, "client did not retry the failed apply"
        assert not np.allclose(before, after), \
            "retried ROWPUSH was deduplicated away — gradient dropped"
    finally:
        cli.close()
        srv.stop()


def test_shared_feed_with_other_consumer_refuses():
    """A data layer consumed by a host-resident embedding AND any other
    layer must refuse: stage() rewrites the feed into cache-slot space
    globally, which would silently corrupt the other consumer's ids."""
    from paddle_tpu import activation as act
    from paddle_tpu import data_type, layer
    from paddle_tpu.attr import ParamAttr
    from paddle_tpu.utils.error import Error

    with layer_name_scope():
        ids = layer.data(name="ids",
                         type=data_type.sparse_binary_vector(64, max_ids=4))
        emb_host = layer.embedding(
            input=ids, size=4,
            param_attr=ParamAttr(name="_host_t", sparse_update=True))
        emb_hbm = layer.embedding(
            input=ids, size=4,
            param_attr=ParamAttr(name="_hbm_t", sparse_update=True))
        h = layer.fc(input=[layer.resize(input=emb_host, size=16),
                            layer.resize(input=emb_hbm, size=16)],
                     size=8, act=act.Relu())
        lab = layer.data(name="y", type=data_type.integer_value(2))
        out = layer.fc(input=h, size=2, act=act.Linear())
        cost = layer.classification_cost(input=out, label=lab)
    topo = Topology(cost)
    with pytest.raises(Error, match="also consumed"):
        topo.host_table_feeds(["_host_t"])
    with pytest.raises(Error, match="two host-resident"):
        topo.host_table_feeds(["_host_t", "_hbm_t"])


def test_rowpush_concurrent_retransmit_applies_once():
    """A retransmit racing the original mid-apply must wait on the
    per-key apply lock and then see the claimed seq — exactly one
    apply, never two."""
    import threading as _th
    import time as _time

    def mk():
        return optimizer.SGD(learning_rate=1.0)

    srv, cli, factory, row_tables = _pserver_setup(mk)
    try:
        from paddle_tpu.distributed.async_pserver import AsyncPServerClient

        store = row_tables["_deep_emb"]
        real = store.apply_sparse
        calls = {"n": 0}

        def slow(ids, values, step):
            calls["n"] += 1
            _time.sleep(0.2)
            return real(ids, values, step)

        store.apply_sparse = slow
        cli2 = AsyncPServerClient("127.0.0.1", srv.port)
        args = ("_deep_emb", np.array([7]), np.ones((1, 4), np.float32),
                1, "c1", 5)
        t1 = _th.Thread(target=lambda: cli.row_push(*args))
        t1.start()
        _time.sleep(0.05)                      # original is mid-apply
        verdict = cli2.row_push(*args)         # retransmit, same seq
        t1.join()
        assert verdict == "dup"
        assert calls["n"] == 1, "retransmit applied the gradient twice"
        cli2.close()
    finally:
        cli.close()
        srv.stop()


def test_enable_host_mode_after_hbm_pass_keeps_momentum():
    """HBM pass then host-mode pass on the same trainer must match an
    all-HBM run: the table's momentum slots are seeded into the store
    (stamped current), not discarded, and the [V,D] slot arrays leave
    the device state."""
    def mk():
        return optimizer.Momentum(momentum=0.8, learning_rate=0.1)

    ref = _trainer(mk())
    _run(ref, _reader(3))
    ref_costs = []
    _run(ref, _reader(3, seed=8), costs=ref_costs)

    t = _trainer(mk())
    _run(t, _reader(3))
    host_costs = []
    _run(t, _reader(3, seed=8), host=True, host_cache_rows=256,
         costs=host_costs)
    assert t._opt_state["_deep_emb"] == {}, \
        "[V,D] optimizer slots still live in device state"
    # every gathered row is caught up at touch, so the phase-2 loss
    # trajectory pins the seeded momentum (a discarded-slot bug shows
    # at ~1e-3+ from the second host batch; the f32 scatter-order noise
    # momentum amplifies sits under 1e-4); final raw tables
    # legitimately differ on never-again-touched rows (lazy catch-up
    # applies at next touch, docs/embedding_cache.md)
    np.testing.assert_allclose(host_costs, ref_costs, rtol=2e-4,
                               atol=1e-5)
    t._host_rt.close()


def test_disabling_host_mode_for_lazy_attr_table_fails_clearly():
    """ParamAttr(host_resident=True) tables were never materialized on
    device; explicitly disabling host mode must fail with a clear
    Error, not a KeyError deep in forward."""
    from paddle_tpu.utils.error import Error

    t = _trainer(host_resident=True)
    with pytest.raises(Error, match="never materialized"):
        t.train(_reader(1), num_passes=1, feeding=FEEDING, host_tables=[])


def test_lazy_row_init_moments():
    """The vectorized counter-based draw must still be the declared
    distribution: ~N(mean, 1/sqrt(fan_in)) for the default strategy."""
    from paddle_tpu.attr import ParamAttr
    from paddle_tpu.host_table import make_row_init

    init = make_row_init(ParamAttr(name="_m"), fan_in=16, seed=3,
                         name="_m")
    vals = init(np.arange(4096), (64,))
    assert abs(float(vals.mean())) < 0.01
    np.testing.assert_allclose(float(vals.std()), 0.25, atol=0.01)
    # per-row determinism: regenerating a subset matches
    np.testing.assert_array_equal(init(np.array([7, 99]), (64,)),
                                  vals[[7, 99]])


def test_dropping_pserver_backed_table_refuses():
    """A pserver-backed store has no dense twin to sync back: disabling
    host mode for it must refuse clearly instead of abandoning the
    trained rows and KeyError'ing in the next forward."""
    from paddle_tpu.utils.error import Error

    def mk():
        return optimizer.SGD(learning_rate=0.1)

    srv, cli, factory, _tables = _pserver_setup(mk)
    try:
        t = _trainer(mk())
        _run(t, _reader(2), host=True, host_store=factory)
        with pytest.raises(Error, match="pserver-backed"):
            t.train(_reader(1), num_passes=1, feeding=FEEDING,
                    host_tables=[])
        t._host_rt.close()
    finally:
        cli.close()
        srv.stop()
