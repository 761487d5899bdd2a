"""Network presets.

Analog of python/paddle/trainer_config_helpers/networks.py:
simple_img_conv_pool, img_conv_bn_pool, vgg_16_network, simple_lstm,
bidirectional_lstm, simple_gru, simple_attention, sequence_conv_pool,
dropout_layer, gru_encoder_decoder-style helpers.
"""

from __future__ import annotations

from paddle_tpu import activation as act
from paddle_tpu import layer
from paddle_tpu import pooling
from paddle_tpu.attr import ExtraAttr, ParamAttr


def simple_img_conv_pool(input, filter_size, num_filters, pool_size, name=None,
                         pool_type=None, act=None, groups=1, conv_stride=1,
                         conv_padding=0, bias_attr=None, num_channel=None,
                         param_attr=None, shared_bias=True, conv_layer_attr=None,
                         pool_stride=1, pool_padding=0, pool_layer_attr=None,
                         img_size=None, img_size_y=None):
    conv = layer.img_conv(input=input, filter_size=filter_size,
                          num_filters=num_filters, num_channels=num_channel,
                          stride=conv_stride, padding=conv_padding,
                          groups=groups, act=act, bias_attr=bias_attr,
                          param_attr=param_attr, shared_biases=shared_bias,
                          layer_attr=conv_layer_attr,
                          img_size=img_size, img_size_y=img_size_y,
                          name=name and f"{name}_conv")
    # pool geometry comes from shape inference (conv.out_info()), not
    # re-derived arithmetic
    return layer.img_pool(input=conv, pool_size=pool_size,
                          pool_type=pool_type, stride=pool_stride,
                          padding=pool_padding, layer_attr=pool_layer_attr,
                          name=name and f"{name}_pool")


def img_conv_bn_pool(input, filter_size, num_filters, pool_size, name=None,
                     pool_type=None, act=None, groups=1, conv_stride=1,
                     conv_padding=0, conv_bias_attr=None, num_channel=None,
                     conv_param_attr=None, shared_bias=True, conv_layer_attr=None,
                     bn_param_attr=None, bn_bias_attr=None, bn_layer_attr=None,
                     pool_stride=1, pool_padding=0, pool_layer_attr=None,
                     img_size=None, img_size_y=None):
    import paddle_tpu.activation as _act

    # conv stays linear before BN (reference img_conv_bn_pool passes
    # LinearActivation; the img_conv wrapper would default None -> Relu)
    conv = layer.img_conv(input=input, filter_size=filter_size,
                          num_filters=num_filters, num_channels=num_channel,
                          stride=conv_stride, padding=conv_padding, groups=groups,
                          act=_act.Linear(), bias_attr=conv_bias_attr,
                          param_attr=conv_param_attr, shared_biases=shared_bias,
                          layer_attr=conv_layer_attr, img_size=img_size,
                          img_size_y=img_size_y, name=name and f"{name}_conv")
    bn = layer.batch_norm(input=conv, act=act, num_channels=num_filters,
                          param_attr=bn_param_attr, bias_attr=bn_bias_attr,
                          layer_attr=bn_layer_attr, name=name and f"{name}_bn")
    return layer.img_pool(input=bn, pool_size=pool_size,
                          pool_type=pool_type, stride=pool_stride,
                          padding=pool_padding,
                          name=name and f"{name}_pool")


def simple_lstm(input, size, name=None, reverse=False, mat_param_attr=None,
                bias_param_attr=None, inner_param_attr=None, act=None,
                gate_act=None, state_act=None, mixed_layer_attr=None,
                lstm_cell_attr=None):
    """fc(4*size, identity act) -> lstmemory (networks.py:615-633 parity:
    the transform is IdentityActivation; act/gate_act/state_act configure the
    lstmemory cell, not the projection)."""
    mix = layer.fc(input=input, size=size * 4, act=act_linear(),
                   param_attr=mat_param_attr, bias_attr=False,
                   name=name and f"{name}_transform")
    return layer.lstmemory(input=mix, name=name, reverse=reverse,
                           act=act, gate_act=gate_act, state_act=state_act,
                           param_attr=inner_param_attr,
                           bias_attr=bias_param_attr,
                           layer_attr=lstm_cell_attr)


def act_linear():
    return act.Linear()


def bidirectional_lstm(input, size, name=None, return_seq=False, **kw):
    fwd = simple_lstm(input=input, size=size, name=name and f"{name}_fwd",
                      reverse=False)
    bwd = simple_lstm(input=input, size=size, name=name and f"{name}_bwd",
                      reverse=True)
    if return_seq:
        return layer.concat(input=[fwd, bwd], name=name)
    f_last = layer.last_seq(input=fwd)
    b_first = layer.first_seq(input=bwd)
    return layer.concat(input=[f_last, b_first], name=name)


def simple_gru(input, size, name=None, reverse=False, mixed_param_attr=None,
               mixed_bias_param_attr=None, gru_param_attr=None,
               gru_bias_attr=None, act=None, gate_act=None, **kw):
    mix = layer.fc(input=input, size=size * 3, act=act_linear(),
                   param_attr=mixed_param_attr, bias_attr=False,
                   name=name and f"{name}_transform")
    return layer.grumemory(input=mix, name=name, reverse=reverse,
                           param_attr=gru_param_attr, bias_attr=gru_bias_attr,
                           act=act, gate_act=gate_act)


def sequence_conv_pool(input, context_len, hidden_size, name=None,
                       context_start=None, pool_type=None, context_proj_param_attr=None,
                       fc_param_attr=None, fc_bias_attr=None, fc_act=None,
                       pool_bias_attr=None, fc_layer_attr=None, context_attr=None):
    """context_projection -> fc -> seq pooling (text conv, networks.py)."""
    ctx_proj = layer.mixed(
        size=input.size * context_len if input.size else None,
        input=[layer.context_projection(input, context_len, context_start)],
        name=name and f"{name}_proj")
    hidden = layer.fc(input=ctx_proj, size=hidden_size, act=fc_act or act.Tanh(),
                      param_attr=fc_param_attr, bias_attr=fc_bias_attr,
                      layer_attr=fc_layer_attr, name=name and f"{name}_fc")
    return layer.pooling(input=hidden, pooling_type=pool_type,
                         name=name and f"{name}_pool")


def simple_attention(encoded_sequence, encoded_proj, decoder_state,
                     transform_param_attr=None, softmax_param_attr=None,
                     name=None):
    """Bahdanau-style additive attention built from primitive layers, like
    the reference's simple_attention (networks.py): expand decoder state
    over the source sequence, add, tanh, score fc, sequence softmax,
    weighted sum."""
    expanded = layer.expand(input=decoder_state, expand_as=encoded_sequence,
                            name=name and f"{name}_expand")
    combined = layer.addto(input=[encoded_proj, expanded],
                           act=act.Tanh(), bias_attr=False,
                           name=name and f"{name}_combine")
    scores = layer.fc(input=combined, size=1, act=act.SequenceSoftmax(),
                      bias_attr=False, param_attr=softmax_param_attr,
                      name=name and f"{name}_weight")
    return scaled_weighted_sum(encoded_sequence, scores,
                               name=name and f"{name}_ctx")


def scaled_weighted_sum(seq, weights, name=None):
    scaled = layer.scaling(input=seq, weight=weights,
                           name=name and f"{name}_scaled")
    return layer.pooling(input=scaled, pooling_type=pooling.Sum(), name=name)


def dropout_layer(input, dropout_rate, name=None):
    return layer.dropout(input, dropout_rate, name=name)


def gru_encoder_decoder(src_word_id, trg_embedding=None, src_dict_dim=30000,
                        trg_dict_dim=30000, word_vector_dim=512,
                        encoder_size=512, decoder_size=512,
                        is_generating=False, beam_size=3, max_length=25,
                        bos_id=0, eos_id=1, name="gru_encdec",
                        trg_vocab_select=None, vocab_select_gather_min=None,
                        compact_decode=True, early_exit=True):
    """Attention seq2seq (the book NMT config built from
    trainer_config_helpers: bidirectional GRU encoder, Bahdanau attention,
    GRU decoder via recurrent_group; generation via beam_search —
    demo/seqToseq-style gru_encoder_decoder).

    Training mode returns the per-step probability sequence (feed
    trg_embedding = embedding of <s>-prefixed target); generation mode
    returns the beam_search layer.

    ``trg_vocab_select``: optional [B, K] per-sentence candidate-vocab id
    layer (-1 padded). The vocab projection becomes a selective_fc over
    the candidate rows — O(K*H) instead of O(V*H) per decode step (the
    classic NMT vocabulary-selection speedup; the reference wires
    SelectiveFullyConnectedLayer into generation the same way,
    RecurrentGradientMachine.cpp:964 generation + selection_pass_
    generation). The selective projection is named and weighted EXACTLY
    like the dense one (fc layout via weight_transposed), so checkpoints
    port between dense and selective modes with no conversion; scores of
    non-candidate tokens are -inf, so beam output ids always lie in the
    candidate set. In training mode the projection runs once over the
    hoisted [B, T, H] hidden sequence with the [B, K] selection broadcast
    over T (the 3D gather path) — the label ids must then lie inside the
    candidate set. ``vocab_select_gather_min`` overrides the gather
    crossover (layers/misc.py); generation is forward-only, so gather
    wins as soon as K << V — pass 0 to force it.

    ``compact_decode`` (generation + trg_vocab_select only): score the
    beam entirely in candidate space — the projection keeps its [B*beam,
    K] result (selective_fc compact_output) and the beam layer top-ks
    over beam*K, mapping winners back to vocab ids at emission, so no
    [B*beam, V] value exists in the compiled decode step (docs/decode.md).
    Candidate rows must contain eos_id (finished hypotheses extend with
    eos) — full-coverage lists trivially do. ``compact_decode=False``
    keeps the r6 selective-projection path (scatter to [B*beam, V]) for
    comparison. ``early_exit`` stops the decode loop when every
    hypothesis has emitted eos instead of always paying max_length ticks
    (bit-identical results; both decode paths).
    """
    src_emb = layer.embedding(input=src_word_id, size=word_vector_dim,
                              param_attr=ParamAttr(name="_src_emb"),
                              name=f"{name}_src_emb")
    enc_fwd = simple_gru(input=src_emb, size=encoder_size,
                         name=f"{name}_enc_fwd")
    enc_bwd = simple_gru(input=src_emb, size=encoder_size, reverse=True,
                         name=f"{name}_enc_bwd")
    encoded = layer.concat(input=[enc_fwd, enc_bwd], name=f"{name}_enc")
    encoded_proj = layer.fc(input=encoded, size=decoder_size,
                            act=act_linear(), bias_attr=False,
                            name=f"{name}_enc_proj")
    backward_first = layer.first_seq(input=enc_bwd)
    decoder_boot = layer.fc(input=backward_first, size=decoder_size,
                            act=act.Tanh(), bias_attr=False,
                            name=f"{name}_boot")

    def vocab_proj(hidden, select, compact=False):
        """The vocab projection: dense fc, or selective over a candidate
        id list — SAME layer name, SAME parameter names and shapes
        (weight_transposed keeps the fc (H, V) layout), so the three
        forms (dense / selective / compact-K) are
        checkpoint-interchangeable."""
        if select is None:
            return layer.fc(input=hidden, size=trg_dict_dim,
                            act=act.Softmax(), name=f"{name}_out")
        return layer.selective_fc(
            input=hidden, select=select, size=trg_dict_dim,
            act=act.Softmax(), name=f"{name}_out",
            select_is_id_list=True, weight_transposed=True,
            select_unique=True,      # candidate lists: unique by contract
            compact_output=compact,  # beam scores in candidate space
            gather_min_c=vocab_select_gather_min)

    def make_step(project_out, emb_preprojected=False, with_select=False):
        def step(*args):
            if with_select:
                enc_seq, enc_proj, cand, cur_emb = args
            else:
                (enc_seq, enc_proj, cur_emb), cand = args, None
            dec_mem = layer.memory(name=f"{name}_dec", size=decoder_size,
                                   boot_layer=decoder_boot)
            context = simple_attention(encoded_sequence=enc_seq,
                                       encoded_proj=enc_proj,
                                       decoder_state=dec_mem,
                                       name=f"{name}_attn")
            if emb_preprojected:
                # cur_emb is already cur_emb @ W1 (hoisted below); only
                # the context half of the two-input fc stays per tick.
                # Shared param names keep checkpoints mode-portable.
                ctx_proj = layer.fc(
                    input=context, size=decoder_size * 3, act=act_linear(),
                    bias_attr=False, name=f"{name}_dec_in",
                    param_attr=ParamAttr(name=f"_{name}_dec_in.w0"))
                dec_inputs = layer.addto(input=[ctx_proj, cur_emb],
                                         bias_attr=False,
                                         name=f"{name}_dec_in_sum")
            else:
                dec_inputs = layer.fc(input=[context, cur_emb],
                                      size=decoder_size * 3,
                                      act=act_linear(), bias_attr=False,
                                      name=f"{name}_dec_in")
            gru = layer.gru_step(input=dec_inputs, output_mem=dec_mem,
                                 size=decoder_size, name=f"{name}_dec")
            if not project_out:
                return gru
            return vocab_proj(gru, cand, compact=with_select and compact_decode)
        return step

    enc_in = layer.StaticInput(input=encoded)
    proj_in = layer.StaticInput(input=encoded_proj)
    if not is_generating:
        # TPU-first hoists (mathematically identical; profiled at r4):
        # 1. the target-embedding half of the dec_in projection is
        #    time-independent — one [B,T,D]@W1 matmul outside the scan
        #    (weight shared by name with the generation-mode two-input fc,
        #    so checkpoints are mode-portable);
        # 2. the vocab projection runs ONCE over the [B, T, H] hidden
        #    sequence (removes the scan's [T, B, V] stack + transpose,
        #    profiled at 1.7 GB/step of pure copy).
        # Generation still computes both per step (beam search consumes
        # per-step probs of generated tokens).
        emb_proj = layer.fc(
            input=trg_embedding, size=decoder_size * 3, act=act_linear(),
            bias_attr=False, name=f"{name}_emb_proj",
            param_attr=ParamAttr(name=f"_{name}_dec_in.w1"))
        hidden_seq = layer.recurrent_group(
            step=make_step(False, emb_preprojected=True),
            input=[enc_in, proj_in, emb_proj], name=f"{name}_decoder")
        # selective training projection: [B, T, H] hidden sequence with a
        # per-sentence [B, K] selection broadcast over T — the 3D gather
        return vocab_proj(hidden_seq, trg_vocab_select)
    gen_inputs = [enc_in, proj_in]
    if trg_vocab_select is not None:
        gen_inputs.append(layer.StaticInput(input=trg_vocab_select,
                                            is_seq=False))
    gen_inputs.append(layer.GeneratedInput(size=trg_dict_dim,
                                           embedding_name="_trg_emb",
                                           embedding_size=word_vector_dim,
                                           bos_id=bos_id, eos_id=eos_id))
    return layer.beam_search(
        # per-step projection: beam needs stepwise probs
        step=make_step(True, with_select=trg_vocab_select is not None),
        input=gen_inputs,
        bos_id=bos_id, eos_id=eos_id, beam_size=beam_size,
        max_length=max_length, name=f"{name}_gen", early_exit=early_exit)


def vgg_16_network(input_image, num_channels, num_classes=1000, img_size=224):
    """VGG-16 (networks.py vgg_16_network parity)."""
    from paddle_tpu.layers.conv import _out_dim

    def block(ipt, num_filter, times, ch, sz, idx):
        cur = ipt
        for t in range(times):
            cur = layer.img_conv(input=cur, filter_size=3, num_filters=num_filter,
                                 num_channels=ch if t == 0 else num_filter,
                                 padding=1, act=act.Relu(),
                                 img_size=sz, img_size_y=sz,
                                 name=f"conv{idx}_{t + 1}")
        pool = layer.img_pool(input=cur, pool_size=2, stride=2,
                              num_channels=num_filter, img_size=sz, img_size_y=sz,
                              pool_type=pooling.Max(), name=f"pool{idx}")
        return pool, sz // 2

    cur, sz = input_image, img_size
    for i, (nf, times, ch) in enumerate(
            [(64, 2, num_channels), (128, 2, 64), (256, 3, 128),
             (512, 3, 256), (512, 3, 512)], start=1):
        cur, sz = block(cur, nf, times, ch, sz, i)
    fc1 = layer.fc(input=cur, size=4096, act=act.Relu(),
                   layer_attr=ExtraAttr(drop_rate=0.5), name="fc6")
    fc2 = layer.fc(input=fc1, size=4096, act=act.Relu(),
                   layer_attr=ExtraAttr(drop_rate=0.5), name="fc7")
    return layer.fc(input=fc2, size=num_classes, act=act.Softmax(), name="fc8")
