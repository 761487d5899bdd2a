"""Config-reachable model parallelism (VERDICT r3 missing #2):
per-layer device annotations compile a Topology into heterogeneous GPipe
stages; forward and grads match the single-device topology exactly.

Reference: proto/ParameterConfig.proto:49 (per-layer device attr),
gserver/gradientmachines/ParallelNeuralNetwork.cpp (per-device layer
dispatch)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import paddle_tpu as paddle
from paddle_tpu import activation, data_type, layer
from paddle_tpu.core.topology import Topology
from paddle_tpu.parallel.topo_pipeline import (PipelinedTopology,
                                               assignment_report,
                                               balanced_stage_assignment,
                                               microbatch,
                                               stage_assignment)
from paddle_tpu.utils.error import Error


def _d(annotate, k):
    """v1/v2 surface: device rides ExtraAttr (ExtraLayerAttribute.device,
    the ParameterConfig.proto:49 attr)."""
    return {"layer_attr": paddle.attr.ExtraAttr(device=k)} if annotate else {}


def _model(annotate=True, sizes=(12, 20, 16, 3)):
    """Heterogeneous stack: widths differ per stage, residual crosses a
    stage boundary (transit tensor), label consumed in the last stage."""
    x = layer.data(name="x", type=data_type.dense_vector(sizes[0]))
    y = layer.data(name="y", type=data_type.integer_value(sizes[3]))
    h1 = layer.fc(input=x, size=sizes[1], act=activation.Tanh(),
                  name="h1", **_d(annotate, 0))
    h2 = layer.fc(input=h1, size=sizes[1], act=activation.Relu(),
                  name="h2", **_d(annotate, 1))
    res = layer.addto(input=[h1, h2], name="res",
                      **_d(annotate, 2))
    h3 = layer.fc(input=res, size=sizes[2], act=activation.Tanh(),
                  name="h3", **_d(annotate, 2))
    out = layer.fc(input=h3, size=sizes[3], act=activation.Softmax(),
                   name="out", **_d(annotate, 3))
    cost = layer.classification_cost(input=out, label=y, name="cost",
                                     **_d(annotate, 3))
    return cost


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]).reshape(n), ("stage",))


def _feeds(B, din, nclass, seed=0):
    r = np.random.RandomState(seed)
    return {"x": jnp.asarray(r.randn(B, din), jnp.float32),
            "y": jnp.asarray(r.randint(0, nclass, (B, 1)), jnp.int32)}


class TestStageAssignment:
    def test_device_attrs_and_inheritance(self):
        cost = _model(annotate=True)
        topo = Topology(cost)
        stages, S = stage_assignment(topo)
        assert S == 4
        assert stages["h1"] == 0 and stages["h2"] == 1
        assert stages["res"] == 2 and stages["cost"] == 3

    def test_unannotated_inherits(self):
        x = layer.data(name="x", type=data_type.dense_vector(4))
        a = layer.fc(input=x, size=4, name="a",
                     layer_attr=paddle.attr.ExtraAttr(device=1))
        b = layer.fc(input=a, size=4, name="b")        # inherits a's stage
        stages, S = stage_assignment(Topology(b))
        assert stages["b"] == stages["a"] and S == 1

    def test_monotonicity_enforced(self):
        x = layer.data(name="x", type=data_type.dense_vector(4))
        a = layer.fc(input=x, size=4, name="a",
                     layer_attr=paddle.attr.ExtraAttr(device=2))
        b = layer.fc(input=a, size=4, name="b",   # backwards
                     layer_attr=paddle.attr.ExtraAttr(device=1))
        with pytest.raises(Error):
            stage_assignment(Topology(b))

    def test_sparse_ids_compact(self):
        x = layer.data(name="x", type=data_type.dense_vector(4))
        a = layer.fc(input=x, size=4, name="a",
                     layer_attr=paddle.attr.ExtraAttr(device=0))
        b = layer.fc(input=a, size=4, name="b",
                     layer_attr=paddle.attr.ExtraAttr(device=5))
        stages, S = stage_assignment(Topology(b))
        assert S == 2 and stages["b"] == 1

    def test_nonmonotone_error_names_edge(self):
        """Review satellite: the non-monotone error names BOTH ends of
        the offending edge with their stage ids, not just the consumer."""
        x = layer.data(name="x", type=data_type.dense_vector(4))
        a = layer.fc(input=x, size=4, name="prod_layer",
                     layer_attr=paddle.attr.ExtraAttr(device=2))
        b = layer.fc(input=a, size=4, name="cons_layer",
                     layer_attr=paddle.attr.ExtraAttr(device=1))
        with pytest.raises(Error) as ei:
            stage_assignment(Topology(b))
        msg = str(ei.value)
        assert "'prod_layer'" in msg and "'cons_layer'" in msg
        assert "stage 2" in msg and "stage 1" in msg


def _nmt_topo(S=4, T=16, D=48, V=600):
    from paddle_tpu.core.layer import layer_name_scope
    from paddle_tpu.models.text import nmt_attention_cost, nmt_stage_map

    with layer_name_scope():
        cost = nmt_attention_cost(src_dict_dim=V, trg_dict_dim=V,
                                  word_vector_dim=D, encoder_size=D,
                                  decoder_size=D)
    return Topology(cost), nmt_stage_map(S)


class TestBalancedAssignment:
    def test_single_stage_degenerate(self):
        topo, _ = _nmt_topo()
        stages, S, report = balanced_stage_assignment(topo, 1)
        assert S == 1 and set(stages.values()) == {0}
        assert report["boundary_widths"] == []

    def test_pins_respected(self):
        topo, _ = _nmt_topo()
        pins = {"m_decoder": 3, "m_src_emb": 0}
        stages, _, _ = balanced_stage_assignment(topo, 4, stage_map=pins)
        assert stages["m_decoder"] == 3 and stages["m_src_emb"] == 0

    def test_pins_validated(self):
        topo, _ = _nmt_topo()
        with pytest.raises(Error):
            balanced_stage_assignment(topo, 4, stage_map={"nope": 1})
        with pytest.raises(Error):
            balanced_stage_assignment(topo, 4, stage_map={"m_out": 7})

    def test_balanced_beats_naive_on_nmt(self):
        """THE tentpole acceptance (static half): on the NMT enc|dec
        graph the balancer's partition cuts P_max well below the naive
        nmt_stage_map assignment — the padded [S, P_max] matrix stops
        being sized by the naive fattest stage and its padding ratio
        drops from the naive split's ~33% — WITHOUT regressing the per-tick
        critical path (max stage flops, which measured step time
        tracks) and without meaningfully widening the boundary."""
        T = 16
        topo, naive_map = _nmt_topo(T=T)
        naive_stages, S = stage_assignment(topo, stage_map=naive_map)
        naive = assignment_report(topo, naive_stages, S, seq_len_hint=T)
        _, _, bal = balanced_stage_assignment(topo, S, seq_len_hint=T)
        assert bal["p_max"] < 0.9 * naive["p_max"]
        assert max(bal["stage_flops"]) <= max(naive["stage_flops"]) * 1.001
        assert bal["d_max"] <= naive["d_max"] * 1.05
        assert naive["param_pad_frac"] > 0.3      # the naive split
        assert bal["param_pad_frac"] < 0.25

    def test_assignment_is_monotone(self):
        """Cuts over a topological chain are monotone by construction —
        verify against every edge anyway."""
        topo, _ = _nmt_topo()
        stages, _, _ = balanced_stage_assignment(topo, 4)
        from paddle_tpu.core.topology import FEED_TYPES
        for l in topo.layers:
            if l.type in FEED_TYPES:
                continue
            for i in l.inputs:
                if i.type in FEED_TYPES:
                    continue
                assert stages[i.name] <= stages[l.name], (i.name, l.name)

    def test_balance_requires_num_stages(self):
        topo, _ = _nmt_topo()
        with pytest.raises(Error):
            PipelinedTopology(topo, balance=True)

    def test_balanced_grads_match_single_device(self):
        """A balance=True pipeline is still the exact program: loss and
        grads match the plain single-device topology."""
        cost = _model(annotate=False)
        topo = Topology(cost)
        params = topo.init_params(jax.random.PRNGKey(0))
        feeds = _feeds(16, 12, 3)

        def ref_loss(p):
            outs = topo.forward(p, feeds, training=True)
            return jnp.mean(outs["cost"].value)

        ref_val, ref_grads = jax.value_and_grad(ref_loss)(params)
        pt = PipelinedTopology(topo, num_stages=4, balance=True,
                               stage_map={"cost": 3})
        assert pt.S == 4
        stacked = pt.stack_params(params)
        feeds_mb = microbatch(feeds, 4)
        val, g = jax.value_and_grad(
            lambda sp: pt.loss(sp, feeds_mb, _mesh(4)))(stacked)
        np.testing.assert_allclose(float(val), float(ref_val),
                                   rtol=1e-5, atol=1e-6)
        grads = pt.unstack_params(g)
        for k in ref_grads:
            np.testing.assert_allclose(np.asarray(grads[k]),
                                       np.asarray(ref_grads[k]),
                                       rtol=2e-4, atol=2e-6, err_msg=k)


@pytest.mark.quick
def test_pipeline_forward_and_grads_match_single_device():
    """The VERDICT acceptance: a device-annotated config trains under
    GPipe on the CPU mesh with grads matching the plain topology."""
    cost = _model(annotate=True)
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    B, M = 16, 4
    feeds = _feeds(B, 12, 3)

    # single-device reference loss: mean cost over the full batch
    def ref_loss(p):
        outs = topo.forward(p, feeds, training=True)
        return jnp.mean(outs["cost"].value)

    ref_val, ref_grads = jax.value_and_grad(ref_loss)(params)

    pt = PipelinedTopology(topo)
    assert pt.S == 4
    stacked = pt.stack_params(params)
    mesh = _mesh(4)
    feeds_mb = microbatch(feeds, M)

    def pipe_loss(sp):
        return pt.loss(sp, feeds_mb, mesh)

    val, grads_stacked = jax.value_and_grad(pipe_loss)(stacked)
    np.testing.assert_allclose(float(val), float(ref_val),
                               rtol=1e-5, atol=1e-6)
    grads = pt.unstack_params(grads_stacked)
    assert set(grads) == set(ref_grads)
    for k in ref_grads:
        np.testing.assert_allclose(np.asarray(grads[k]),
                                   np.asarray(ref_grads[k]),
                                   rtol=2e-4, atol=2e-6, err_msg=k)


def test_pipeline_trains_under_sgd():
    """A few pipelined SGD steps reduce the loss (end-to-end training
    through the stage-compiled program)."""
    cost = _model(annotate=True)
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(1))
    pt = PipelinedTopology(topo)
    stacked = pt.stack_params(params)
    mesh = _mesh(4)
    feeds = _feeds(32, 12, 3, seed=1)
    feeds_mb = microbatch(feeds, 4)

    @jax.jit
    def step(sp):
        val, g = jax.value_and_grad(
            lambda q: pt.loss(q, feeds_mb, mesh))(sp)
        return val, sp - 0.5 * g

    losses = []
    for _ in range(12):
        val, stacked = step(stacked)
        losses.append(float(val))
    assert losses[-1] < losses[0] * 0.8, losses


def test_pipeline_with_dropout_takes_rng():
    """Stochastic layers work when loss(rng=...) is given (review r4)."""
    x = layer.data(name="x", type=data_type.dense_vector(6))
    y = layer.data(name="y", type=data_type.integer_value(2))
    a = layer.fc(input=x, size=8, name="da",
                 layer_attr=paddle.attr.ExtraAttr(device=0, drop_rate=0.5))
    b = layer.fc(input=a, size=2, act=activation.Softmax(), name="db",
                 layer_attr=paddle.attr.ExtraAttr(device=1))
    c = layer.classification_cost(input=b, label=y, name="dc",
                                  layer_attr=paddle.attr.ExtraAttr(device=1))
    topo = Topology(c)
    pt = PipelinedTopology(topo)
    stacked = pt.stack_params(topo.init_params(jax.random.PRNGKey(0)))
    feeds_mb = microbatch(_feeds(8, 6, 2), 2)
    val = pt.loss(stacked, feeds_mb, _mesh(2), rng=jax.random.PRNGKey(3))
    assert np.isfinite(float(val))
    # different rng -> different dropout mask -> different loss
    val2 = pt.loss(stacked, feeds_mb, _mesh(2), rng=jax.random.PRNGKey(4))
    assert float(val) != float(val2)


@pytest.mark.quick
def test_pipeline_times_data_parallel_grads_match():
    """PP x DP composition: a 2x4 ('data','stage') mesh — feeds sharded
    over data, stages over the pipeline axis — reproduces the
    single-device gradients exactly (equal shards => mean of shard means
    == full-batch mean)."""
    cost = _model(annotate=True)
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    B, M = 16, 2
    feeds = _feeds(B, 12, 3)

    def ref_loss(p):
        outs = topo.forward(p, feeds, training=True)
        return jnp.mean(outs["cost"].value)

    ref_val, ref_grads = jax.value_and_grad(ref_loss)(params)

    pt = PipelinedTopology(topo)
    stacked = pt.stack_params(params)
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "stage"))
    feeds_mb = microbatch(feeds, M)

    val, g = jax.value_and_grad(
        lambda sp: pt.loss(sp, feeds_mb, mesh, data_axis="data"))(stacked)
    np.testing.assert_allclose(float(val), float(ref_val),
                               rtol=1e-5, atol=1e-6)
    grads = pt.unstack_params(g)
    for k in ref_grads:
        np.testing.assert_allclose(np.asarray(grads[k]),
                                   np.asarray(ref_grads[k]),
                                   rtol=2e-4, atol=2e-6, err_msg=k)


def test_round_trip_param_packing():
    cost = _model(annotate=True)
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(2))
    pt = PipelinedTopology(topo)
    stacked = pt.stack_params(params)
    back = pt.unstack_params(stacked)
    assert set(back) == set(params)
    for k in params:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(params[k]))


def test_cost_must_be_last_stage():
    x = layer.data(name="x", type=data_type.dense_vector(4))
    y = layer.data(name="y", type=data_type.integer_value(2))
    a = layer.fc(input=x, size=2, act=activation.Softmax(), name="a",
                 layer_attr=paddle.attr.ExtraAttr(device=0))
    c = layer.classification_cost(input=a, label=y, name="c",
                                  layer_attr=paddle.attr.ExtraAttr(device=0))
    b = layer.fc(input=a, size=2, name="b",   # cost not last
                 layer_attr=paddle.attr.ExtraAttr(device=1))
    topo = Topology([c, b])
    pt = PipelinedTopology(topo)
    with pytest.raises(Error):
        pt.loss(pt.stack_params(topo.init_params(jax.random.PRNGKey(0))),
                microbatch(_feeds(8, 4, 2), 2), _mesh(2), cost_layer="c")
