"""Selective vocab projection in beam-search decode (ISSUE r6 tentpole)
and the compact-K beam path + early-exit loop (ISSUE r8 tentpole).

networks.gru_encoder_decoder(trg_vocab_select=...) swaps the per-step
dense vocab projection for a selective_fc over a per-sentence candidate
id list. Three decode paths exist (docs/decode.md):

  dense      — fc over the whole vocab, beam top-k over [B*beam, V]
  selective  — selective_fc projection, beam still scores [B*beam, V]
               (compact_decode=False; the r6 wiring)
  compact-K  — projection AND beam entirely in candidate space
               ([B*beam, K]); winners map back to vocab ids at emission
               (compact_decode=True, the default)

Pinned here:

- FULL-coverage candidates reproduce the committed golden-generation
  ids bit-for-bit (tests/data/golden_gen_ids.npy — the same fixture
  test_golden_generation.py locks) through the dense-mask, forced-gather
  AND compact-K paths — including with candidate_adjust / norm_or_drop
  callbacks and num_results_per_sample > 1;
- the selective/compact graphs' parameter names AND shapes equal the
  dense graph's (weight_transposed keeps the fc layout), so checkpoints
  port between modes with no conversion;
- restricted candidate sets constrain the emitted ids to the set;
- the compact-K decode step's jaxpr contains NO [B*beam, V]-shaped
  value (the acceptance assertion — every per-tick O(V) op is gone);
- the early-exit loop (lax.while_loop, default) is bit-identical to the
  full-length scan and reports ticks-executed < max_length when every
  hypothesis dies early.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import data_type, layer, networks
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.layer import layer_name_scope
from paddle_tpu.core.topology import Topology

V, D = 16, 8
GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "data", "golden_gen_ids.npy")


def _gen_topo(select=False, K=V, gather_min=None, compact=True,
              early_exit=True, max_length=5, vocab=V, ctrl=None,
              num_results=1):
    with layer_name_scope():
        src = layer.data(name="src",
                         type=data_type.integer_value_sequence(vocab))
        sel = None
        if select:
            sel = layer.data(name="cand", type=data_type.dense_vector(K))
        gen = networks.gru_encoder_decoder(
            src_word_id=src, src_dict_dim=vocab, trg_dict_dim=vocab,
            word_vector_dim=D, encoder_size=D, decoder_size=D,
            is_generating=True, beam_size=3, max_length=max_length,
            name="g", trg_vocab_select=sel, vocab_select_gather_min=gather_min,
            compact_decode=compact, early_exit=early_exit)
    # beam-control hooks / multi-result ride on the layer cfg (the
    # networks preset mirrors the reference helper, which doesn't
    # expose them either)
    if ctrl is not None:
        gen.cfg["ctrl_callbacks"] = ctrl
    if num_results != 1:
        gen.cfg["num_results_per_sample"] = num_results
    return Topology(gen), gen


def _feeds():
    return {"src": Arg(jnp.asarray([[3, 5, 2, 9]], jnp.int32),
                       jnp.ones((1, 4)))}


def _decode(topo, gen, feeds, params):
    outs, ctx = topo.forward(params, feeds, return_ctx=True)
    return (np.asarray(ctx.extras[f"{gen.name}:ids"]),
            np.asarray(ctx.extras[f"{gen.name}:scores"]))


def _full_coverage_cand(B=1):
    return Arg(jnp.asarray(np.tile(np.arange(V), (B, 1)), jnp.int32))


@pytest.mark.parametrize("compact", [False, True])
def test_selective_params_are_checkpoint_compatible(compact):
    """Dense, selective (r6) and compact-K (r8) graphs declare identical
    parameter names and shapes — checkpoints port between all three."""
    topo_d, _ = _gen_topo(select=False)
    specs_d = {n: s.shape for n, s in topo_d.param_specs().items()}
    topo_s, _ = _gen_topo(select=True, compact=compact)
    specs_s = {n: s.shape for n, s in topo_s.param_specs().items()}
    assert specs_s == specs_d


@pytest.mark.parametrize("gather_min", [None, 0])
def test_selective_full_coverage_matches_golden(gather_min):
    """r6 path (compact off): beam ids/scores through the selective
    projection (candidate list = the whole vocab) match the dense decode
    AND the committed golden ids — for the dense-mask fallback and the
    forced gather path."""
    topo_d, gen_d = _gen_topo(select=False)
    params = topo_d.init_params(jax.random.PRNGKey(7))
    ids_d, sc_d = _decode(topo_d, gen_d, _feeds(), params)

    topo_s, gen_s = _gen_topo(select=True, gather_min=gather_min,
                              compact=False)
    feeds = dict(_feeds())
    feeds["cand"] = _full_coverage_cand()
    ids_s, sc_s = _decode(topo_s, gen_s, feeds, params)

    np.testing.assert_array_equal(ids_s, ids_d)
    np.testing.assert_allclose(sc_s, sc_d, rtol=1e-6, atol=1e-6)
    if os.path.exists(GOLDEN) and np.array_equal(ids_d, np.load(GOLDEN)):
        # on platforms that reproduce the committed golden, the selective
        # path must hit it too; elsewhere the dense decode IS the anchor
        # (test_golden_generation tracks the fixture itself)
        np.testing.assert_array_equal(ids_s, np.load(GOLDEN))


def test_compact_full_coverage_matches_dense_and_golden():
    """r8 acceptance: compact-K decode (candidate list = whole vocab)
    reproduces the dense decode ids bit-for-bit and the scores to fp
    equality — scoring in candidate space loses nothing."""
    topo_d, gen_d = _gen_topo(select=False)
    params = topo_d.init_params(jax.random.PRNGKey(7))
    ids_d, sc_d = _decode(topo_d, gen_d, _feeds(), params)

    topo_c, gen_c = _gen_topo(select=True, compact=True)
    feeds = dict(_feeds())
    feeds["cand"] = _full_coverage_cand()
    ids_c, sc_c = _decode(topo_c, gen_c, feeds, params)

    np.testing.assert_array_equal(ids_c, ids_d)
    np.testing.assert_allclose(sc_c, sc_d, rtol=1e-6, atol=1e-6)
    if os.path.exists(GOLDEN) and np.array_equal(ids_d, np.load(GOLDEN)):
        np.testing.assert_array_equal(ids_c, np.load(GOLDEN))


@pytest.mark.parametrize("compact", [False, True])
def test_restricted_candidates_constrain_output(compact):
    topo_s, gen_s = _gen_topo(select=True, K=6, gather_min=0,
                              compact=compact)
    topo_d, _ = _gen_topo(select=False)
    params = topo_d.init_params(jax.random.PRNGKey(7))
    cand = np.array([[1, 3, 5, 9, 2, -1]], np.int32)
    feeds = dict(_feeds())
    feeds["cand"] = Arg(jnp.asarray(cand))
    ids, scores = _decode(topo_s, gen_s, feeds, params)
    assert np.isin(ids, cand[cand >= 0]).all()
    assert np.isfinite(scores).all()


def _mode_agnostic_ban(banned):
    """candidate_adjust that bans a vocab id in BOTH spaces: vocab
    columns on the dense/selective paths, candidate slots (via
    state['cand_ids']) on the compact path."""
    def adjust(t, logp, state):
        ids = state.get("cand_ids")
        col = ids if ids is not None else jnp.arange(logp.shape[-1])[None, :]
        return jnp.where(col == banned, -1e30, logp)
    return adjust


def test_compact_callbacks_match_dense():
    """candidate_adjust + norm_or_drop fire identically in candidate
    space: full-coverage compact decode with both hooks equals the dense
    decode with the same hooks, and the ban holds."""
    banned = 7

    def norm(ids, scores, lengths):
        return scores / lengths.astype(scores.dtype)

    ctrl = layer.BeamSearchControlCallbacks(
        candidate_adjust=_mode_agnostic_ban(banned), norm_or_drop=norm)
    topo_d, gen_d = _gen_topo(select=False, ctrl=ctrl)
    params = topo_d.init_params(jax.random.PRNGKey(7))
    ids_d, sc_d = _decode(topo_d, gen_d, _feeds(), params)

    topo_c, gen_c = _gen_topo(select=True, compact=True, ctrl=ctrl)
    feeds = dict(_feeds())
    feeds["cand"] = _full_coverage_cand()
    ids_c, sc_c = _decode(topo_c, gen_c, feeds, params)

    np.testing.assert_array_equal(ids_c, ids_d)
    np.testing.assert_allclose(sc_c, sc_d, rtol=1e-6, atol=1e-6)
    assert not (ids_c == banned).any()


def test_compact_num_results_per_sample():
    """num_results_per_sample > 1 (nested top-N output) is identical
    through the compact path at full coverage — value, mask and seg_ids
    of the returned nested sequence."""
    topo_d, gen_d = _gen_topo(select=False, num_results=2)
    params = topo_d.init_params(jax.random.PRNGKey(7))
    out_d = topo_d.forward(params, _feeds())[gen_d.name]

    topo_c, gen_c = _gen_topo(select=True, compact=True, num_results=2)
    feeds = dict(_feeds())
    feeds["cand"] = _full_coverage_cand()
    out_c = topo_c.forward(params, feeds)[gen_c.name]

    np.testing.assert_array_equal(np.asarray(out_c.value),
                                  np.asarray(out_d.value))
    np.testing.assert_array_equal(np.asarray(out_c.mask),
                                  np.asarray(out_d.mask))
    np.testing.assert_array_equal(np.asarray(out_c.seg_ids),
                                  np.asarray(out_d.seg_ids))


def _jaxpr_eqns(jaxpr, acc):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            acc.append((eqn.primitive.name,
                        tuple(getattr(v.aval, "shape", ()))))
        for val in eqn.params.values():
            if hasattr(val, "jaxpr"):
                _jaxpr_eqns(val.jaxpr, acc)
            elif hasattr(val, "eqns"):
                _jaxpr_eqns(val, acc)
    return acc


def test_compact_jaxpr_has_no_vocab_wide_values():
    """THE r8 acceptance assertion: the compiled compact-K decode step
    contains no [B*beam, V]-shaped equation output (nor the [B*beam,
    V+1] scatter scratch or the [B, beam*V] top-k input) — every
    per-tick O(V) op is gone. The selective (r6) control DOES show them;
    that's the cost compact-K deletes."""
    vocab, K, beam, B = 50, 9, 3, 1
    BK = B * beam
    banned = {(BK, vocab), (BK, vocab + 1), (B, beam * vocab)}

    def shapes(compact):
        topo, gen = _gen_topo(select=True, K=K, gather_min=0,
                              compact=compact, vocab=vocab)
        params = topo.init_params(jax.random.PRNGKey(0))
        feeds = dict(_feeds())
        cand = np.array([[1, 3, 5, 9, 2, 7, 11, 30, 49]], np.int32)
        feeds["cand"] = Arg(jnp.asarray(cand))
        jaxpr = jax.make_jaxpr(
            lambda p, f: topo.forward(p, f, return_ctx=True)[1]
            .extras[f"{gen.name}:ids"])(params, feeds)
        return [s for _, s in _jaxpr_eqns(jaxpr.jaxpr, [])]

    compact_shapes = set(shapes(True))
    assert not (compact_shapes & banned), \
        f"vocab-wide values in compact-K decode: {compact_shapes & banned}"
    selective_shapes = set(shapes(False))
    assert selective_shapes & banned, \
        "selective control lost its vocab-wide ops — the jaxpr scan is broken"


def _force_eos_after(tick, eos=1):
    """Length model: every hypothesis is pushed onto eos once t >= tick,
    in whichever space the beam scores (the early-exit trigger)."""
    def adjust(t, logp, state):
        ids = state.get("cand_ids")
        col = ids if ids is not None else jnp.arange(logp.shape[-1])[None, :]
        return jnp.where(t >= tick,
                         jnp.where(col == eos, 0.0, -50.0), logp)
    return adjust


@pytest.mark.parametrize("mode", ["dense", "selective", "compact"])
def test_early_exit_bit_identical_to_full_scan(mode):
    """The while-loop early exit + closed-form completion reproduces the
    fixed max_length scan bit-for-bit on all three decode paths — ids,
    scores AND the layer's nested output — while executing fewer ticks
    (the :ticks extra) once every hypothesis is dead."""
    ctrl = layer.BeamSearchControlCallbacks(
        candidate_adjust=_force_eos_after(2))
    select = mode != "dense"
    kw = dict(select=select, compact=(mode == "compact"), max_length=8,
              ctrl=ctrl, gather_min=0 if select else None)
    topo_e, gen_e = _gen_topo(early_exit=True, **kw)
    topo_f, gen_f = _gen_topo(early_exit=False, **kw)
    params = topo_e.init_params(jax.random.PRNGKey(7))
    feeds = {"src": Arg(jnp.asarray([[3, 5, 2, 9], [1, 2, 0, 4]],
                                    jnp.int32), jnp.ones((2, 4)))}
    if select:
        feeds["cand"] = _full_coverage_cand(B=2)
    outs_e, ctx_e = topo_e.forward(params, feeds, return_ctx=True)
    outs_f, ctx_f = topo_f.forward(params, feeds, return_ctx=True)
    np.testing.assert_array_equal(
        np.asarray(ctx_e.extras[f"{gen_e.name}:ids"]),
        np.asarray(ctx_f.extras[f"{gen_f.name}:ids"]))
    np.testing.assert_array_equal(
        np.asarray(ctx_e.extras[f"{gen_e.name}:scores"]),
        np.asarray(ctx_f.extras[f"{gen_f.name}:scores"]))
    np.testing.assert_array_equal(np.asarray(outs_e[gen_e.name].value),
                                  np.asarray(outs_f[gen_f.name].value))
    ticks_e = int(ctx_e.extras[f"{gen_e.name}:ticks"])
    assert int(ctx_f.extras[f"{gen_f.name}:ticks"]) == 8
    assert ticks_e < 8, "early exit never fired despite forced eos"


def test_early_exit_noop_when_no_eos():
    """When no hypothesis ever dies the while loop runs the full
    max_length and is still bit-identical to the scan (the completion
    fixup must be a no-op)."""
    topo_e, gen_e = _gen_topo(early_exit=True)
    topo_f, gen_f = _gen_topo(early_exit=False)
    params = topo_e.init_params(jax.random.PRNGKey(7))
    ids_e, sc_e = _decode(topo_e, gen_e, _feeds(), params)
    ids_f, sc_f = _decode(topo_f, gen_f, _feeds(), params)
    np.testing.assert_array_equal(ids_e, ids_f)
    np.testing.assert_array_equal(sc_e, sc_f)


def test_training_mode_selective_projection_3d():
    """Training mode with trg_vocab_select runs the hoisted [B, T, H]
    projection through the 3D gather path ([B, K] selection broadcast
    over T) and only candidate columns carry probability mass (compact
    output never applies to training — labels index the full vocab)."""
    Bt, T, Kc = 2, 3, 6
    with layer_name_scope():
        src = layer.data(name="src",
                         type=data_type.integer_value_sequence(V))
        trg = layer.data(name="trg",
                         type=data_type.integer_value_sequence(V))
        sel = layer.data(name="cand", type=data_type.dense_vector(Kc))
        from paddle_tpu.attr import ParamAttr
        emb = layer.embedding(input=trg, size=D,
                              param_attr=ParamAttr(name="_trg_emb"))
        probs = networks.gru_encoder_decoder(
            src_word_id=src, trg_embedding=emb, src_dict_dim=V,
            trg_dict_dim=V, word_vector_dim=D, encoder_size=D,
            decoder_size=D, name="g", trg_vocab_select=sel,
            vocab_select_gather_min=0)
    topo = Topology(probs)
    params = topo.init_params(jax.random.PRNGKey(1))
    r = np.random.RandomState(0)
    cand = np.stack([r.choice(V, Kc, replace=False) for _ in range(Bt)])
    mask = jnp.ones((Bt, T), jnp.float32)
    feeds = {
        "src": Arg(jnp.asarray(r.randint(0, V, (Bt, T)), jnp.int32), mask),
        "trg": Arg(jnp.asarray(r.randint(0, V, (Bt, T)), jnp.int32), mask),
        "cand": Arg(jnp.asarray(cand, jnp.int32)),
    }
    out = np.asarray(topo.forward(params, feeds)[probs.name].value)
    assert out.shape == (Bt, T, V)
    for b in range(Bt):
        on = set(cand[b].tolist())
        off = [c for c in range(V) if c not in on]
        assert (out[b][:, off] < 1e-12).all()          # softmax of -1e30
        np.testing.assert_allclose(out[b].sum(-1), 1.0, rtol=1e-5)


# --- the three decode paths at the NMT generation topology ---------------

def _length_schedule(max_length, eos_id=1, beam=1):
    """A candidate_adjust callback giving each sample an output length in
    6 .. 3/4 * max_length, as a trained model's sentences have: past its
    length every hypothesis of a sample is pushed onto eos, so the
    early-exit loop ends like a production decode (random-init parameters
    essentially never emit eos). Works in vocabulary space ([BK, V]) and in
    candidate space ([BK, K], through state['cand_ids'])."""
    lo = min(6, max_length - 1)
    hi = max(lo + 1, (3 * max_length) // 4)

    def candidate_adjust(t, logp, state):
        bk = logp.shape[0]
        length = lo + ((jnp.arange(bk) // beam) % (hi - lo + 1))
        ids = state.get("cand_ids")
        col = ids if ids is not None else jnp.arange(logp.shape[-1])[None, :]
        return jnp.where((t >= length)[:, None],
                         jnp.where(col == eos_id, 0.0, -1e4), logp)

    return candidate_adjust


@pytest.mark.parametrize("mode", ["dense", "selective", "compact"])
def test_decode_modes_finish_under_max_length(mode):
    """Each decode path of models/text.nmt_decode_topology emits finite
    scores and, under the length schedule, stops before max_length: the
    early-exit loop does not pay for ticks no hypothesis needs."""
    from paddle_tpu.layer import BeamSearchControlCallbacks
    from paddle_tpu.models.text import nmt_decode_topology

    vocab, beam, K, B, T, max_length, eos_id = 500, 2, 32, 2, 4, 12, 1
    gen = nmt_decode_topology(src_dict_dim=vocab, trg_dict_dim=vocab,
                              word_vector_dim=16, encoder_size=16,
                              decoder_size=16, beam_size=beam,
                              max_length=max_length, cand_k=K, mode=mode,
                              name="m")
    gen.cfg["ctrl_callbacks"] = BeamSearchControlCallbacks(
        candidate_adjust=_length_schedule(max_length, eos_id, beam))
    topo = Topology(gen)
    params = topo.init_params(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    feeds = {"src": Arg(jnp.asarray(r.randint(0, vocab, (B, T)), jnp.int32),
                        jnp.ones((B, T), jnp.float32))}
    if mode != "dense":
        # unique candidate rows that hold eos (docs/decode.md's contract)
        cand = np.stack([r.choice(vocab, K, replace=False)
                         for _ in range(B)]).astype(np.int32)
        cand[~(cand == eos_id).any(axis=1), 0] = eos_id
        feeds["cand"] = Arg(jnp.asarray(cand))
    outs, ctx = topo.forward(params, feeds, return_ctx=True)
    assert np.isfinite(np.asarray(ctx.extras[f"{gen.name}:scores"])).all()
    assert float(outs[gen.name].mask.sum()) > 0     # tokens were emitted
    assert 0 < int(ctx.extras[f"{gen.name}:ticks"]) < max_length


def test_decode_flop_accounting():
    """flops.py prices beam_search layers per executed tick and prices
    the selective projection in candidate space: compact decode FLOPs
    are V-independent and far below dense, and scale with decode_ticks."""
    from paddle_tpu.flops import topology_fwd_flops
    from paddle_tpu.models.text import nmt_decode_topology

    def flops(mode, ticks=None, V=2000):
        gen = nmt_decode_topology(src_dict_dim=V, trg_dict_dim=V,
                                  word_vector_dim=16, encoder_size=16,
                                  decoder_size=16, beam_size=2,
                                  max_length=8, cand_k=32, mode=mode)
        return topology_fwd_flops(Topology(gen), batch=4, seq_len=6,
                                  decode_ticks=ticks)

    dense, compact = flops("dense"), flops("compact")
    assert compact < dense / 3          # K=32 << V=2000 projection rows
    # candidate-space pricing is V-independent
    assert flops("compact", V=4000) == pytest.approx(compact, rel=1e-6)
    # fewer executed ticks -> proportionally less beam work
    full, half = flops("compact", ticks=8), flops("compact", ticks=4)
    assert half < full
    # the selective (r6) projection also gathers K rows: same matmul
    # count as compact (what differs at runtime is non-matmul O(V) work)
    assert flops("selective") == pytest.approx(compact, rel=1e-6)
