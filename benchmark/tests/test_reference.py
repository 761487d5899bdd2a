"""Each plain reference against the system, at a tiny size on the CPU.

The NMT goes through the harness's own pieces in the cell's precision (bf16
compute) and must stay inside the rehearsal cell's limits. ResNet is held in
float64, where the two must agree to rounding: with eight images a batch the
batch-norm statistics are too ill-conditioned for a float32 comparison to say
anything (two float32 evaluations of the same formula differ by 2% there).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, program, run, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [11, 4000000011])
def test_nmt_system_against_reference(seed):
    cell, config = load("workloads", "rehearsal-nmt"), load("configs", "rehearsal-nmt")
    pool = traffic.pool(traffic.load(cell["traffic"]), config["model"]["args"], seed)
    table = correct.load_module(config["reference"]).param_table(
        config["model"]["args"])
    trainer, static = program.build_trainer(
        config, cell, correct.init_params(table, seed))
    batches = [pool[i][0] for i in range(3)]
    _, prog = run.first_steps(config, trainer, static, batches, seed)
    ref = correct.reference_steps(config, batches, seed)
    ok, rows = correct.judge(correct.compare(prog, ref, static), cell["limits"])
    assert ok, rows


@pytest.fixture
def x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


def test_resnet_system_against_reference_float64(x64):
    from paddle_tpu.core.arg import Arg
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.trainer.feeder import DataFeeder

    config = load("configs", "rehearsal-resnet")
    a = config["model"]["args"]
    ref = correct.load_module(config["reference"])
    rows = traffic.pool(traffic.load("rehearsal-images"), a, 7)[0][0]
    cost = program.resolve(config["model"]["builder"])(**a)[config["model"]["cost_index"]]
    topo = Topology(cost)
    p = {k: v.astype(jnp.float64)
         for k, v in correct.init_params(ref.param_table(a), 7).items()}
    feeds = {k: Arg(jnp.asarray(v.value, jnp.float64 if np.asarray(v.value).dtype.kind == "f"
                                else None))
             for k, v in DataFeeder(topo.data_type(), config["feeding"])(rows).items()}
    (l1, (_, aux1)), g1 = jax.value_and_grad(topo.loss_fn(cost), has_aux=True)(
        p, feeds, rng=jax.random.PRNGKey(0), training=True)
    b = {k: jnp.asarray(v, jnp.float64 if v.dtype.kind == "f" else None)
         for k, v in ref.pad(rows, a).items()}
    none = lambda x: x
    (l2, aux2), g2 = jax.value_and_grad(
        lambda p: ref.loss(p, b, none, a), has_aux=True)(p)
    (l3, aux3), g3 = ref.value_and_grad(p, b, none, a)

    def rel(x, y):
        return float(jnp.linalg.norm(x - y) / (jnp.linalg.norm(y) + 1e-300))

    assert abs(float(l1) - float(l2)) < 1e-9 and abs(float(l3) - float(l2)) < 1e-9
    trained = [k for k in g2 if k not in ref.static_names(a)]
    assert max(rel(g1[k], g2[k]) for k in trained) < 1e-8
    assert max(rel(g3[k], g2[k]) for k in trained) < 1e-8
    assert set(aux1) == set(aux2) == set(aux3) == set(ref.static_names(a))
    assert max(rel(aux1[k], aux2[k]) for k in aux2) < 1e-9
    assert max(rel(aux3[k], aux2[k]) for k in aux2) < 1e-9


def test_seed_above_two_to_the_31_gives_other_weights():
    table = {"w": ((4, 4), ("normal", 1.0)), "b": ((4,), ("const", 0.5))}
    lo = correct.init_params(table, 5)
    hi = correct.init_params(table, 5 + 2 ** 31)
    again = correct.init_params(table, 5 + 2 ** 31)
    assert not np.allclose(lo["w"], hi["w"])
    assert np.array_equal(hi["w"], again["w"]) and float(hi["b"][0]) == 0.5


def test_traffic_same_sizes_for_every_seed():
    mix = traffic.load("rehearsal-pairs")
    a = load("configs", "rehearsal-nmt")["model"]["args"]
    p1, p2 = traffic.pool(mix, a, 1), traffic.pool(mix, a, 2)
    assert [w for _, w in p1] == [w for _, w in p2] and len({w for _, w in p1}) == 1
    assert sorted(len(r[0]) for r in p1[0][0]) == sorted(len(r[0]) for r in p2[3][0])
    assert p1[0][0] != p2[0][0] and p1[0][0] != p1[1][0]
    assert all(r[1][0] == 0 and r[2][-1] == 1 and r[1][1:] == r[2][:-1]
               for r in p1[0][0])
