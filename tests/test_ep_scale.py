"""EP embedding at realistic vocab scale (VERDICT r3 missing #5, part 2):
a vocab >= 1M sparse_update table EP-sharded over the 'model' axis of the
8-device mesh trains one step. (Round 3's dryrun used vocab=256; the real
chip's step time for the same config has not been re-measured.)"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu import optimizer
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.topology import Topology
from paddle_tpu.models.text import ctr_wide_deep
from paddle_tpu.parallel.sharding import ShardingRules


@pytest.mark.slow
def test_ctr_vocab_1m_ep_sharded_step():
    V = 1 << 20                              # 1,048,576 rows
    B, K = 32, 16
    devices = jax.devices()[:8]
    mesh = Mesh(np.asarray(devices).reshape(2, 4), ("data", "model"))
    _ins, _lab, _out, cost = ctr_wide_deep(
        wide_dim=V, deep_vocab=V, emb_dim=16, max_ids=K, hidden=32)
    topo = Topology(cost)
    rules = ShardingRules(mesh)
    specs = topo.param_specs()
    params = rules.shard_params(topo.init_params(jax.random.PRNGKey(0)),
                                specs)
    # the 1M-row tables must actually be EP-sharded, not replicated
    for name in ("_deep_emb", "_wide_w"):
        pname = [n for n in params if name in n][0]
        assert "model" in str(params[pname].sharding.spec), \
            (pname, params[pname].sharding)

    opt = optimizer.Adam(learning_rate=1e-3)
    opt_state = jax.device_put(opt.init(params), NamedSharding(mesh, P()))
    loss = topo.loss_fn(cost)
    static = topo.static_map()
    batch_sh = NamedSharding(mesh, P("data"))
    r = np.random.RandomState(0)

    def step(params, opt_state, feeds):
        (c, (_o, _aux)), grads = jax.value_and_grad(
            loss, has_aux=True)(params, feeds, training=True)
        new_params, new_opt = opt.update(grads, opt_state, params,
                                         None, static)
        return new_params, new_opt, c

    feeds = {
        "wide_ids": Arg(jax.device_put(
            jnp.asarray(r.randint(0, V, (B, K)), jnp.int32), batch_sh)),
        "deep_ids": Arg(jax.device_put(
            jnp.asarray(r.randint(0, V, (B, K)), jnp.int32), batch_sh)),
        "click": Arg(jax.device_put(
            jnp.asarray(r.randint(0, 2, (B, 1)), jnp.int32), batch_sh)),
    }
    with mesh:
        jstep = jax.jit(step)
        params, opt_state, c = jstep(params, opt_state, feeds)
        jax.block_until_ready(c)
        t0 = time.perf_counter()
        params, opt_state, c = jstep(params, opt_state, feeds)
        jax.block_until_ready(c)
        dt = time.perf_counter() - t0
    assert np.isfinite(float(c))
    # sanity: a second step on 8 virtual CPU devices with a 1M-row table
    # finishes in sane time (catches accidental dense one-hot matmuls,
    # which at V=1M would be ~"forever")
    assert dt < 60, f"EP step took {dt:.1f}s at vocab=1M"
