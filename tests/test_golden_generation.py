"""Golden beam-search generation regression + FP-trap coverage
(SURVEY §4: test_recurrent_machine_generation.cpp locks generation output
against a golden model dir; test_FPException.cpp proves the trap fires).

The golden here is self-sealing: deterministic params (fixed PRNG seed)
-> deterministic beam output; the recorded ids pin the whole
generation pipeline (encoder, attention, per-step projection, beam
bookkeeping) against silent behavior drift."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import data_type, layer, networks
from paddle_tpu.attr import ParamAttr
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.layer import layer_name_scope
from paddle_tpu.core.topology import Topology


def _gen_topo(V=16, D=8):
    with layer_name_scope():
        src = layer.data(name="src",
                         type=data_type.integer_value_sequence(V))
        gen = networks.gru_encoder_decoder(
            src_word_id=src, src_dict_dim=V, trg_dict_dim=V,
            word_vector_dim=D, encoder_size=D, decoder_size=D,
            is_generating=True, beam_size=3, max_length=5, name="g")
    return Topology(gen), gen


def test_generation_deterministic_and_stable():
    """Same params + same input -> identical ids across two runs AND
    across two independently-built topologies (no hidden state leaks,
    no auto-name dependence in the math)."""
    topo1, gen1 = _gen_topo()
    topo2, gen2 = _gen_topo()
    params = topo1.init_params(jax.random.PRNGKey(7))
    feeds = {"src": Arg(jnp.asarray([[3, 5, 2, 9]], jnp.int32),
                        jnp.ones((1, 4)))}
    ids1 = np.asarray(topo1.forward(params, feeds, return_ctx=True)[1]
                      .extras[f"{gen1.name}:ids"])
    ids2 = np.asarray(topo2.forward(params, feeds, return_ctx=True)[1]
                      .extras[f"{gen2.name}:ids"])
    np.testing.assert_array_equal(ids1, ids2)
    assert ids1.shape[-1] == 5                      # max_length
    assert ((ids1 >= 0) & (ids1 < 16)).all()


def test_golden_ids_locked():
    """The actual golden: PRNGKey(7) params + the fixed source sequence
    must keep producing these exact beam ids. If an intentional change
    to generation math lands, re-record by deleting tests/data/golden_gen_ids.npy.

    (Re-recorded for jax 0.9.0: its default `jax_threefry_partitionable`
    is True, which changes the PRNG stream and so the initial values.
    With that flag set back to False, today's decode reproduced the
    previous golden id for id — the generation math did not move.)"""
    topo, gen = _gen_topo()
    params = topo.init_params(jax.random.PRNGKey(7))
    feeds = {"src": Arg(jnp.asarray([[3, 5, 2, 9]], jnp.int32),
                        jnp.ones((1, 4)))}
    ctx = topo.forward(params, feeds, return_ctx=True)[1]
    ids = np.asarray(ctx.extras[f"{gen.name}:ids"])
    import os
    golden_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "data", "golden_gen_ids.npy")
    if not os.path.exists(golden_path):
        if os.environ.get("RECORD_GOLDEN") == "1":
            os.makedirs(os.path.dirname(golden_path), exist_ok=True)
            np.save(golden_path, ids)
            pytest.skip(f"golden recorded at {golden_path}; rerun to verify")
        pytest.fail(f"golden missing at {golden_path} — it is a committed "
                    "fixture; re-record ONLY for intentional generation "
                    "changes via RECORD_GOLDEN=1")
    golden = np.load(golden_path)
    np.testing.assert_array_equal(ids, golden)


def test_early_exit_default_matches_full_scan_on_golden_topo():
    """The r8 early-exit decode loop (lax.while_loop, the default) is
    bit-identical to the fixed max_length scan on the golden topology —
    the golden fixture stays valid across the loop-driver change. The
    executed-tick count lands in the ':ticks' extra."""
    def build(early_exit):
        with layer_name_scope():
            src = layer.data(name="src",
                             type=data_type.integer_value_sequence(16))
            gen = networks.gru_encoder_decoder(
                src_word_id=src, src_dict_dim=16, trg_dict_dim=16,
                word_vector_dim=8, encoder_size=8, decoder_size=8,
                is_generating=True, beam_size=3, max_length=5, name="g",
                early_exit=early_exit)
        return Topology(gen), gen

    topo_e, gen_e = build(True)
    topo_f, gen_f = build(False)
    params = topo_e.init_params(jax.random.PRNGKey(7))
    feeds = {"src": Arg(jnp.asarray([[3, 5, 2, 9]], jnp.int32),
                        jnp.ones((1, 4)))}
    ctx_e = topo_e.forward(params, feeds, return_ctx=True)[1]
    ctx_f = topo_f.forward(params, feeds, return_ctx=True)[1]
    np.testing.assert_array_equal(
        np.asarray(ctx_e.extras[f"{gen_e.name}:ids"]),
        np.asarray(ctx_f.extras[f"{gen_f.name}:ids"]))
    np.testing.assert_array_equal(
        np.asarray(ctx_e.extras[f"{gen_e.name}:scores"]),
        np.asarray(ctx_f.extras[f"{gen_f.name}:scores"]))
    assert 0 < int(ctx_e.extras[f"{gen_e.name}:ticks"]) <= 5
    assert int(ctx_f.extras[f"{gen_f.name}:ticks"]) == 5


def test_fp_trap_debug_nans_fires():
    """FLAGS debug_nans (test_FPException analog): a NaN produced inside
    the jitted computation raises instead of propagating silently."""
    try:
        jax.config.update("jax_debug_nans", True)

        @jax.jit
        def bad(x):
            return jnp.log(x - 2.0)     # log(-1) -> nan

        with pytest.raises(FloatingPointError) as ei:
            np.asarray(bad(jnp.ones(())))
        assert "nan" in str(ei.value).lower()
    finally:
        jax.config.update("jax_debug_nans", False)
