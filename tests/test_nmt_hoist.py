"""The r4 NMT hoists (vocab projection + target-embedding projection
moved out of the decoder scan) must be numerically
IDENTICAL to the reference per-step formulation with shared params, and
parameter names must stay mode-portable (training <-> generation)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import activation as act
from paddle_tpu import data_type, layer, networks
from paddle_tpu.attr import ParamAttr
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.layer import layer_name_scope
from paddle_tpu.core.topology import Topology
from paddle_tpu.networks import act_linear, simple_attention, simple_gru

V, D = 12, 8
NAME = "m"


def _encoder(src):
    src_emb = layer.embedding(input=src, size=D,
                              param_attr=ParamAttr(name="_src_emb"),
                              name=f"{NAME}_src_emb")
    enc_fwd = simple_gru(input=src_emb, size=D, name=f"{NAME}_enc_fwd")
    enc_bwd = simple_gru(input=src_emb, size=D, reverse=True,
                         name=f"{NAME}_enc_bwd")
    encoded = layer.concat(input=[enc_fwd, enc_bwd], name=f"{NAME}_enc")
    encoded_proj = layer.fc(input=encoded, size=D, act=act_linear(),
                            bias_attr=False, name=f"{NAME}_enc_proj")
    boot = layer.fc(input=layer.first_seq(input=enc_bwd), size=D,
                    act=act.Tanh(), bias_attr=False, name=f"{NAME}_boot")
    return encoded, encoded_proj, boot


def _build_per_step():
    """The reference formulation: every projection per decoder tick."""
    src = layer.data(name="src", type=data_type.integer_value_sequence(V))
    trg = layer.data(name="trg", type=data_type.integer_value_sequence(V))
    emb = layer.embedding(input=trg, size=D,
                          param_attr=ParamAttr(name="_trg_emb"))
    encoded, encoded_proj, boot = _encoder(src)

    def step(enc_seq, enc_proj, cur_emb):
        dec_mem = layer.memory(name=f"{NAME}_dec", size=D, boot_layer=boot)
        context = simple_attention(encoded_sequence=enc_seq,
                                   encoded_proj=enc_proj,
                                   decoder_state=dec_mem,
                                   name=f"{NAME}_attn")
        dec_inputs = layer.fc(input=[context, cur_emb], size=D * 3,
                              act=act_linear(), bias_attr=False,
                              name=f"{NAME}_dec_in")
        gru = layer.gru_step(input=dec_inputs, output_mem=dec_mem, size=D,
                             name=f"{NAME}_dec")
        return layer.fc(input=gru, size=V, act=act.Softmax(),
                        name=f"{NAME}_out")

    return layer.recurrent_group(
        step=step, input=[layer.StaticInput(input=encoded),
                          layer.StaticInput(input=encoded_proj), emb],
        name=f"{NAME}_decoder")


def _build_hoisted():
    src = layer.data(name="src", type=data_type.integer_value_sequence(V))
    trg = layer.data(name="trg", type=data_type.integer_value_sequence(V))
    emb = layer.embedding(input=trg, size=D,
                          param_attr=ParamAttr(name="_trg_emb"))
    return networks.gru_encoder_decoder(
        src_word_id=src, trg_embedding=emb, src_dict_dim=V, trg_dict_dim=V,
        word_vector_dim=D, encoder_size=D, decoder_size=D, name=NAME)


def test_hoisted_decoder_matches_per_step():
    with layer_name_scope():
        old = _build_per_step()
    with layer_name_scope():
        new = _build_hoisted()
    topo_o, topo_n = Topology(old), Topology(new)
    po = topo_o.init_params(jax.random.PRNGKey(0))
    assert set(po) == set(topo_n.param_specs())
    r = np.random.RandomState(0)
    feeds = {"src": Arg(jnp.asarray(r.randint(0, V, (2, 5)), jnp.int32),
                        jnp.ones((2, 5))),
             "trg": Arg(jnp.asarray(r.randint(0, V, (2, 5)), jnp.int32),
                        jnp.ones((2, 5)))}
    o1 = np.asarray(topo_o.forward(po, feeds)[old.name].value)
    o2 = np.asarray(topo_n.forward(po, feeds)[new.name].value)
    np.testing.assert_allclose(o2, o1, rtol=1e-6, atol=1e-6)


def test_generation_shares_every_training_param():
    with layer_name_scope():
        new = _build_hoisted()
    with layer_name_scope():
        src2 = layer.data(name="src",
                          type=data_type.integer_value_sequence(V))
        gen = networks.gru_encoder_decoder(
            src_word_id=src2, src_dict_dim=V, trg_dict_dim=V,
            word_vector_dim=D, encoder_size=D, decoder_size=D, name=NAME,
            is_generating=True, max_length=4)
    pt = set(Topology(new).param_specs())
    pg = set(Topology(gen).param_specs())
    assert pt == pg, pt ^ pg
