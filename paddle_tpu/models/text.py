"""RNN text models (benchmark/paddle/rnn/{rnn.py,imdb.py} parity: stacked
LSTM classifier; imikolov-style ngram LM; sequence tagging nets from
v1_api_demo/sequence_tagging/{linear_crf,rnn_crf}.py)."""

from __future__ import annotations

from paddle_tpu import activation as act
from paddle_tpu import data_type, layer, networks, pooling
from paddle_tpu.attr import ParamAttr


def lstm_text_classification(dict_dim=30000, emb_dim=128, hidden=512,
                             num_layers=2, num_classes=2, name="lstm_cls"):
    """2xLSTM + fc text classifier (the benchmark RNN config: IMDB,
    seq len 100, dict 30k, h=512)."""
    words = layer.data(name="words",
                       type=data_type.integer_value_sequence(dict_dim))
    lab = layer.data(name="label", type=data_type.integer_value(num_classes))
    emb = layer.embedding(input=words, size=emb_dim)
    cur = emb
    for i in range(num_layers):
        cur = networks.simple_lstm(input=cur, size=hidden,
                                   name=f"{name}_l{i}")
    pooled = layer.pooling(input=cur, pooling_type=pooling.Max())
    out = layer.fc(input=pooled, size=num_classes, act=act.Linear(),
                   name="output")
    cost = layer.classification_cost(input=out, label=lab, name="cost")
    return words, lab, out, cost


def ngram_lm(dict_dim=2000, emb_dim=32, hidden=128, context=4, name="ngram"):
    """imikolov n-gram LM (word embedding demo): N-1 context words ->
    hsigmoid/softmax next-word."""
    ctx_words = [layer.data(name=f"w{i}", type=data_type.integer_value(dict_dim))
                 for i in range(context)]
    nxt = layer.data(name="next_word", type=data_type.integer_value(dict_dim))
    embs = [layer.embedding(input=w, size=emb_dim,
                            param_attr=ParamAttr(name="_ngram_emb"))
            for w in ctx_words]
    merged = layer.concat(input=embs)
    h = layer.fc(input=merged, size=hidden, act=act.Relu())
    out = layer.fc(input=h, size=dict_dim, act=act.Linear(), name="output")
    cost = layer.classification_cost(input=out, label=nxt, name="cost")
    return ctx_words, nxt, out, cost


def linear_crf_tagger(word_dim=5000, label_dim=67, emb_dim=32,
                      context_len=5):
    """v1_api_demo/sequence_tagging/linear_crf.py: context-window features
    -> linear projection -> CRF."""
    words = layer.data(name="words",
                       type=data_type.integer_value_sequence(word_dim))
    labels = layer.data(name="labels",
                        type=data_type.integer_value_sequence(label_dim))
    emb = layer.embedding(input=words, size=emb_dim)
    ctx = layer.mixed(
        size=emb_dim * context_len,
        input=[layer.context_projection(emb, context_len)])
    feat = layer.fc(input=ctx, size=label_dim, act=act.Linear(),
                    bias_attr=False, name="crf_feat")
    cost = layer.crf(input=feat, label=labels, size=label_dim, name="crf_cost")
    decode = layer.crf_decoding(input=feat, size=label_dim,
                                param_attr=ParamAttr(name="_crf_cost.w0"),
                                name="crf_decode")
    return words, labels, feat, cost, decode


def rnn_crf_tagger(word_dim=5000, label_dim=67, emb_dim=64, hidden=128):
    """v1_api_demo/sequence_tagging/rnn_crf.py: bidirectional GRU features
    -> CRF."""
    words = layer.data(name="words",
                       type=data_type.integer_value_sequence(word_dim))
    labels = layer.data(name="labels",
                        type=data_type.integer_value_sequence(label_dim))
    emb = layer.embedding(input=words, size=emb_dim)
    fwd = networks.simple_gru(input=emb, size=hidden, name="rnncrf_fwd")
    bwd = networks.simple_gru(input=emb, size=hidden, reverse=True,
                              name="rnncrf_bwd")
    feat = layer.fc(input=[fwd, bwd], size=label_dim, act=act.Linear(),
                    bias_attr=False, name="crf_feat")
    cost = layer.crf(input=feat, label=labels, size=label_dim, name="crf_cost")
    return words, labels, feat, cost


def ctr_wide_deep(wide_dim=10000, deep_vocab=10000, emb_dim=16, max_ids=32,
                  hidden=64, host_resident=False):
    """CTR wide&deep with sparse inputs (the sparse-embedding EP config;
    paddle/trainer/tests/simple_sparse_neural_network.py shape):
    wide: sparse binary ids -> embedding(sum-pool analog of sparse fc);
    deep: sparse ids -> embedding (sparse_update, shardable over 'model').

    ``host_resident=True`` marks both tables host-resident
    (docs/embedding_cache.md): they never exist in device memory — the
    trainer stages a per-batch row cache instead — which is what lets
    ``deep_vocab`` go to 100M+ rows (the SURVEY §2.3
    production-recommender scenario)."""
    wide_in = layer.data(name="wide_ids",
                         type=data_type.sparse_binary_vector(wide_dim,
                                                             max_ids=max_ids))
    deep_in = layer.data(name="deep_ids",
                         type=data_type.sparse_binary_vector(deep_vocab,
                                                             max_ids=max_ids))
    lab = layer.data(name="click", type=data_type.integer_value(2))
    wide_emb = layer.embedding(
        input=wide_in, size=1,
        param_attr=ParamAttr(name="_wide_w", sparse_update=True,
                             host_resident=host_resident))
    # ids arrive [B, K]; embedding -> [B, K, 1]; sum over K = sparse fc
    wide_feat = layer.resize(input=wide_emb, size=max_ids)
    deep_emb = layer.embedding(
        input=deep_in, size=emb_dim,
        param_attr=ParamAttr(name="_deep_emb", sparse_update=True,
                             host_resident=host_resident))
    deep_flat = layer.resize(input=deep_emb, size=max_ids * emb_dim)
    h = layer.fc(input=deep_flat, size=hidden, act=act.Relu())
    out = layer.fc(input=[h, wide_feat], size=2, act=act.Linear(),
                   name="output")
    cost = layer.classification_cost(input=out, label=lab, name="cost")
    return (wide_in, deep_in), lab, out, cost


def nmt_attention_cost(src_dict_dim=30000, trg_dict_dim=30000,
                       word_vector_dim=512, encoder_size=512,
                       decoder_size=512, name="m"):
    """The NMT benchmark training topology (the `nmt-train-b512` cell):
    bidirectional-GRU encoder + Bahdanau-attention GRU decoder
    (networks.gru_encoder_decoder) with teacher forcing and per-token
    cross entropy. Feeds: src / trg / trg_next integer sequences.

    Returns the cost layer; the whole graph — recurrent groups, attention,
    scan — is what the flagship DP and pipeline dryruns train
    (MultiGradientMachine.h:44 ran RecurrentGradientMachine under the DP
    ring daily; this is that claim, mesh-sharded)."""
    src = layer.data(name="src",
                     type=data_type.integer_value_sequence(src_dict_dim))
    trg = layer.data(name="trg",
                     type=data_type.integer_value_sequence(trg_dict_dim))
    lab = layer.data(name="trg_next",
                     type=data_type.integer_value_sequence(trg_dict_dim))
    emb = layer.embedding(input=trg, size=word_vector_dim,
                          param_attr=ParamAttr(name="_trg_emb"),
                          name=f"{name}_trg_emb")
    probs = networks.gru_encoder_decoder(
        src_word_id=src, trg_embedding=emb, src_dict_dim=src_dict_dim,
        trg_dict_dim=trg_dict_dim, word_vector_dim=word_vector_dim,
        encoder_size=encoder_size, decoder_size=decoder_size, name=name)
    return layer.classification_cost(input=probs, label=lab, name="cost")


def nmt_packed_cost(src_dict_dim=30000, trg_dict_dim=30000,
                    word_vector_dim=512, encoder_size=512,
                    decoder_size=512, num_heads=8, name="mp"):
    """Packing-ready NMT training topology (docs/packing.md): the
    attention seq2seq rebuilt from the SEGMENT-AWARE
    full-sequence layers, so the same graph trains on padded one-sample
    rows AND on packed multi-sequence rows with seg_ids —

      src -> emb -> bi-GRU (grumemory fwd/rev) -> concat -> enc proj
      trg -> emb -> GRU decoder state sequence
      multi_head_attention(query=dec states, kv=encoded)  [segment mask]
      addto(dec, ctx) -> fc softmax over trg vocab -> per-token xent

    Unlike ``nmt_attention_cost`` (recurrent_group + per-tick Bahdanau
    attention, which cannot pack: group memories have no segment-reset
    path), every layer here is one full-sequence op: the recurrent layers
    reset h at packed-segment starts, attention composes the
    block-diagonal segment mask, and the cost divides by sequences. The
    shared packing plan aligns segment k of a trg row with segment k of
    the same src row, so cross-attention sees exactly its own source
    sentence. Feeds: src / trg / trg_next integer sequences."""
    src = layer.data(name="src",
                     type=data_type.integer_value_sequence(src_dict_dim))
    trg = layer.data(name="trg",
                     type=data_type.integer_value_sequence(trg_dict_dim))
    lab = layer.data(name="trg_next",
                     type=data_type.integer_value_sequence(trg_dict_dim))
    src_emb = layer.embedding(input=src, size=word_vector_dim,
                              param_attr=ParamAttr(name="_src_emb"),
                              name=f"{name}_src_emb")
    enc_fwd = networks.simple_gru(input=src_emb, size=encoder_size,
                                  name=f"{name}_enc_fwd")
    enc_bwd = networks.simple_gru(input=src_emb, size=encoder_size,
                                  reverse=True, name=f"{name}_enc_bwd")
    encoded = layer.concat(input=[enc_fwd, enc_bwd], name=f"{name}_enc")
    enc_proj = layer.fc(input=encoded, size=decoder_size, act=act.Linear(),
                        bias_attr=False, name=f"{name}_enc_proj")
    trg_emb = layer.embedding(input=trg, size=word_vector_dim,
                              param_attr=ParamAttr(name="_trg_emb"),
                              name=f"{name}_trg_emb")
    dec = networks.simple_gru(input=trg_emb, size=decoder_size,
                              name=f"{name}_dec")
    ctx = layer.multi_head_attention(
        query=dec, key_value=enc_proj, size=decoder_size,
        num_heads=num_heads, causal=False, name=f"{name}_attn")
    combined = layer.addto(input=[dec, ctx], act=act.Tanh(),
                           bias_attr=False, name=f"{name}_comb")
    out = layer.fc(input=combined, size=trg_dict_dim, act=act.Softmax(),
                   name=f"{name}_out")
    return layer.classification_cost(input=out, label=lab, name="cost")


def nmt_decode_topology(src_dict_dim=30000, trg_dict_dim=30000,
                        word_vector_dim=512, encoder_size=512,
                        decoder_size=512, beam_size=4, max_length=16,
                        cand_k=1024, mode="compact", early_exit=True,
                        name="m"):
    """The NMT generation topology: the training preset's encoder/decoder in
    beam-search generation mode, with the decode path selected by
    ``mode`` (docs/decode.md):

      dense     — full-vocab projection, beam over [B*beam, V]
      selective — selective_fc gather projection, beam still O(V)/tick
                  (the r6 wiring; compact_decode=False)
      compact   — compact-K: projection AND beam in candidate space

    Feeds: ``src`` integer sequence; plus ``cand`` ([B, cand_k] unique
    candidate ids containing eos) for selective/compact. Returns the
    beam_search generation layer; decode ids/scores/ticks land in
    ctx.extras['<name>_gen:ids'/':scores'/':ticks']."""
    from paddle_tpu.core.layer import layer_name_scope

    assert mode in ("dense", "selective", "compact"), mode
    with layer_name_scope():
        src = layer.data(name="src",
                         type=data_type.integer_value_sequence(src_dict_dim))
        sel = None
        if mode != "dense":
            sel = layer.data(name="cand",
                             type=data_type.dense_vector(cand_k))
        return networks.gru_encoder_decoder(
            src_word_id=src, src_dict_dim=src_dict_dim,
            trg_dict_dim=trg_dict_dim, word_vector_dim=word_vector_dim,
            encoder_size=encoder_size, decoder_size=decoder_size,
            is_generating=True, beam_size=beam_size, max_length=max_length,
            name=name, trg_vocab_select=sel, vocab_select_gather_min=0,
            compact_decode=(mode == "compact"), early_exit=early_exit)


def nmt_stage_map(S, name="m"):
    """Encoder|decoder pipeline split of the NMT graph for
    PipelinedTopology (the natural benchmark pipeline): S=2 puts the
    whole encoder in stage 0 and the decoder + cost in stage 1; S=4
    further splits the encoder (src embedding + forward GRU | backward
    GRU + projections) and peels the vocab projection + cost into their
    own stage. Unpinned layers inherit their inputs' stages; the softmax
    output and cost stay co-located so the softmax-xent DCE fusion
    (layers/cost.py) still fires inside the stage."""
    if S == 2:
        return {f"{name}_trg_emb": 1, f"{name}_emb_proj": 1,
                f"{name}_decoder": 1, f"{name}_out": 1, "cost": 1}
    if S == 4:
        return {
            f"{name}_enc_bwd": 1, f"{name}_enc": 1, f"{name}_enc_proj": 1,
            f"{name}_boot": 1,
            f"{name}_trg_emb": 2, f"{name}_emb_proj": 2,
            f"{name}_decoder": 2,
            f"{name}_out": 3, "cost": 3,
        }
    raise ValueError(f"nmt_stage_map supports S in (2, 4), got {S}")


def qwen3_next_lm_cost(vocab_size=151936, hidden_size=2048,
                       num_hidden_layers=48, num_attention_heads=16,
                       num_key_value_heads=2, head_dim=256,
                       partial_rotary_factor=0.25, rope_theta=10000000,
                       full_attention_interval=4, linear_num_key_heads=16,
                       linear_num_value_heads=32, linear_key_head_dim=128,
                       linear_value_head_dim=128, linear_conv_kernel_dim=4,
                       moe_intermediate_size=512,
                       shared_expert_intermediate_size=512, num_experts=512,
                       num_experts_per_tok=10, experts_held=None,
                       first_expert=0, rms_norm_eps=1e-6, name="q"):
    """Next-token cost of a Qwen3-Next decoder (docs/qwen3_next.md): blocks
    of ``h = x + mixer(norm(x)); y = h + moe(norm(h))`` whose mixer is gated
    attention in every ``full_attention_interval``-th block and Gated
    DeltaNet in the others, a final norm and an untied head. Every block's
    MoE routes over all ``num_experts`` and holds ``experts_held`` of them
    from ``first_expert`` on: one chip's share of an expert-parallel layer
    (all of them by default). Feeds: ids / next_ids integer sequences."""
    ids = layer.data(name="ids",
                     type=data_type.integer_value_sequence(vocab_size))
    nxt = layer.data(name="next_ids",
                     type=data_type.integer_value_sequence(vocab_size))
    x = layer.embedding(input=ids, size=hidden_size, name=f"{name}_emb")
    for l in range(num_hidden_layers):
        b = f"{name}_l{l}"
        normed = layer.rms_norm(input=x, eps=rms_norm_eps, name=f"{b}_in_norm")
        if (l + 1) % full_attention_interval == 0:
            mixed = layer.gated_attention(
                input=normed, num_heads=num_attention_heads,
                num_kv_heads=num_key_value_heads, head_dim=head_dim,
                rotary_dim=int(head_dim * partial_rotary_factor),
                rope_theta=rope_theta, eps=rms_norm_eps,
                scope=f"qwen3next/l{l}/mixer", name=f"{b}_attn")
        else:
            mixed = layer.gated_delta_net(
                input=normed, num_k_heads=linear_num_key_heads,
                num_v_heads=linear_num_value_heads,
                head_k_dim=linear_key_head_dim,
                head_v_dim=linear_value_head_dim,
                conv_kernel=linear_conv_kernel_dim, eps=rms_norm_eps,
                scope=f"qwen3next/l{l}/mixer", name=f"{b}_gdn")
        h = layer.addto(input=[x, mixed], act=act.Linear(), bias_attr=False,
                        name=f"{b}_h")
        normed = layer.rms_norm(input=h, eps=rms_norm_eps,
                                name=f"{b}_post_norm")
        ffn = layer.moe_ffn(
            input=normed, num_experts=num_experts, top_k=num_experts_per_tok,
            expert_size=moe_intermediate_size,
            shared_size=shared_expert_intermediate_size,
            experts_held=experts_held, first_expert=first_expert,
            scope=f"qwen3next/l{l}/moe", name=f"{b}_moe")
        x = layer.addto(input=[h, ffn], act=act.Linear(), bias_attr=False,
                        name=f"{b}_out")
    x = layer.rms_norm(input=x, eps=rms_norm_eps, name=f"{name}_final_norm")
    probs = layer.fc(input=x, size=vocab_size, act=act.Softmax(),
                     bias_attr=False, name=f"{name}_head")
    return layer.classification_cost(input=probs, label=nxt, name="cost")


def sdar_lm_cost(vocab_size=151936, hidden_size=2048, num_hidden_layers=48,
                 num_attention_heads=32, num_key_value_heads=4, head_dim=128,
                 rope_theta=1000000, moe_intermediate_size=768,
                 num_experts=128, num_experts_per_tok=8, experts_held=None,
                 first_expert=0, rms_norm_eps=1e-6, seq_len=8192,
                 block_length=4, mask_token_id=151669, name="s"):
    """Block-diffusion training cost of an SDAR decoder (docs/sdar.md):
    blocks of ``h = x + attn(norm(x)); y = h + moe(norm(h))``, grouped-query
    attention with per-head q/k norms and a top-k MoE with no shared expert,
    a final norm and an untied head. A row of ``seq_len`` clean ids is noised
    block by block and the model runs once over the 2 x seq_len positions
    [noised ; clean] under the block-diffusion mask; the head and the loss
    (1 / t on every masked token, no shift) are on the noised half. Every
    MoE routes over all ``num_experts`` and holds ``experts_held`` of them
    from ``first_expert`` on (all by default). Feeds: ``ids`` (an integer
    sequence of seq_len tokens), ``mask_u`` (seq_len values in [-0.5, 0.5):
    token i is masked where mask_u + 0.5 < t of its block) and ``noise_t``
    (one value a block in [-0.5, 0.5): t = 1/256 + noise_t + 0.5)."""
    n_blocks = -(-seq_len // block_length)
    ids = layer.data(name="ids",
                     type=data_type.integer_value_sequence(vocab_size))
    u = layer.data(name="mask_u", type=data_type.dense_vector(seq_len))
    t = layer.data(name="noise_t", type=data_type.dense_vector(n_blocks))
    v = layer.slope_intercept(input=u, intercept=0.5, name=f"{name}_v")
    t = layer.slope_intercept(input=t, intercept=0.5 + 1.0 / 256,
                              name=f"{name}_t")
    both, weights = layer.block_diffusion_noise(
        ids, v, t, block=block_length, mask_id=mask_token_id,
        scope="sdar/noise", name=f"{name}_noise")
    x = layer.embedding(input=both, size=hidden_size, name=f"{name}_emb")
    for l in range(num_hidden_layers):
        b = f"{name}_l{l}"
        normed = layer.rms_norm(input=x, eps=rms_norm_eps, zero_centered=False,
                                name=f"{b}_in_norm")
        mixed = layer.gqa_attention(
            input=normed, num_heads=num_attention_heads,
            num_kv_heads=num_key_value_heads, head_dim=head_dim,
            rope_theta=rope_theta, eps=rms_norm_eps,
            mask=("block_diffusion", seq_len, block_length),
            scope=f"sdar/l{l}/attn", name=f"{b}_attn")
        h = layer.addto(input=[x, mixed], act=act.Linear(), bias_attr=False,
                        name=f"{b}_h")
        if l == num_hidden_layers - 1:
            # nothing reads the clean half past the last attention
            h = layer.noised_half(h, name=f"{b}_noised")
        normed = layer.rms_norm(input=h, eps=rms_norm_eps, zero_centered=False,
                                name=f"{b}_post_norm")
        ffn = layer.moe_ffn(
            input=normed, num_experts=num_experts, top_k=num_experts_per_tok,
            expert_size=moe_intermediate_size, experts_held=experts_held,
            first_expert=first_expert, scope=f"sdar/l{l}/moe",
            name=f"{b}_moe")
        x = layer.addto(input=[h, ffn], act=act.Linear(), bias_attr=False,
                        name=f"{b}_out")
    x = layer.rms_norm(input=x, eps=rms_norm_eps, zero_centered=False,
                       name=f"{name}_final_norm")
    probs = layer.fc(input=x, size=vocab_size, act=act.Softmax(),
                     bias_attr=False, name=f"{name}_head")
    return layer.classification_cost(
        input=probs, label=ids, weight=weights, name="cost")


def kimi_vl_lm_cost(vocab_size=163840, hidden_size=2048,
                    intermediate_size=11264, moe_intermediate_size=1408,
                    num_hidden_layers=27, num_attention_heads=16,
                    n_shared_experts=2, n_routed_experts=64,
                    routed_scaling_factor=2.446, kv_lora_rank=512,
                    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                    num_experts_per_tok=6, first_k_dense_replace=1,
                    rope_theta=800000, rms_norm_eps=1e-5, experts_held=None,
                    first_expert=0, bias_update_rate=1e-3, seq_len=None,
                    name="k"):
    """Next-token cost of Kimi-VL's language model, a DeepSeek-V3-style
    decoder (docs/kimi_vl.md): blocks of ``h = x + attn(norm(x)); y = h +
    ffn(norm(h))`` with multi-head latent attention, a dense gated MLP in
    the first ``first_k_dense_replace`` blocks and, in every later one, a
    sigmoid-routed MoE chosen through a selection bias that a rule (not a
    gradient) keeps level, with ``n_shared_experts`` ungated shared experts
    as one MLP; a final norm and an untied head. Every MoE routes over all
    ``n_routed_experts`` and holds ``experts_held`` of them from
    ``first_expert`` on (all by default). Rows are ``seq_len`` positions
    where given (any one length otherwise). Feeds: ids / next_ids integer
    sequences."""
    ids = layer.data(name="ids",
                     type=data_type.integer_value_sequence(vocab_size))
    nxt = layer.data(name="next_ids",
                     type=data_type.integer_value_sequence(vocab_size))
    x = layer.embedding(input=ids, size=hidden_size, name=f"{name}_emb")
    for l in range(num_hidden_layers):
        b = f"{name}_l{l}"
        normed = layer.rms_norm(input=x, eps=rms_norm_eps, zero_centered=False,
                                name=f"{b}_in_norm")
        mixed = layer.mla_attention(
            input=normed, num_heads=num_attention_heads,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            kv_lora_rank=kv_lora_rank, rope_theta=rope_theta, eps=rms_norm_eps,
            mask=("causal", seq_len), scope=f"kimivl/l{l}/attn",
            name=f"{b}_attn")
        h = layer.addto(input=[x, mixed], act=act.Linear(), bias_attr=False,
                        name=f"{b}_h")
        normed = layer.rms_norm(input=h, eps=rms_norm_eps, zero_centered=False,
                                name=f"{b}_post_norm")
        if l < first_k_dense_replace:
            ffn = layer.gated_mlp(input=normed, size=intermediate_size,
                                  scope=f"kimivl/l{l}/mlp", name=f"{b}_mlp")
        else:
            ffn = layer.moe_ffn(
                input=normed, num_experts=n_routed_experts,
                top_k=num_experts_per_tok, expert_size=moe_intermediate_size,
                shared_size=n_shared_experts * moe_intermediate_size,
                experts_held=experts_held, first_expert=first_expert,
                score="sigmoid", selection_bias=True,
                bias_rate=bias_update_rate, route_scale=routed_scaling_factor,
                shared_gate=False, scope=f"kimivl/l{l}/moe", name=f"{b}_moe")
        x = layer.addto(input=[h, ffn], act=act.Linear(), bias_attr=False,
                        name=f"{b}_out")
    x = layer.rms_norm(input=x, eps=rms_norm_eps, zero_centered=False,
                       name=f"{name}_final_norm")
    probs = layer.fc(input=x, size=vocab_size, act=act.Softmax(),
                     bias_attr=False, name=f"{name}_head")
    return layer.classification_cost(input=probs, label=nxt, name="cost")


def lfm2_moe_lm_cost(vocab_size=65536, hidden_size=2048,
                     intermediate_size=7168, moe_intermediate_size=1792,
                     num_hidden_layers=24, num_attention_heads=32,
                     num_key_value_heads=8, layer_types=None,
                     layers_held=None, num_dense_layers=2, num_experts=32,
                     num_experts_per_tok=4, routed_scaling_factor=1.0,
                     conv_L_cache=3, rope_theta=1000000, norm_eps=1e-5,
                     experts_held=None, first_expert=0, bias_update_rate=1e-3,
                     conv_layers=None, attention_layers=None, seq_len=None,
                     name="f"):
    """Next-token cost of an LFM2-MoE decoder (docs/lfm2.md): blocks of
    ``h = x + mixer(norm(x)); y = h + ffn(norm(h))`` whose mixer is the gated
    short convolution where ``layer_types`` says ``conv`` and grouped-query
    attention (per-head q / k norms, rotary on the whole head, causal) where
    it says ``full_attention``; the ffn is a dense gated MLP in the first
    ``num_dense_layers`` blocks and a sigmoid-routed MoE chosen through a
    selection bias (a rule, not a gradient, keeps it level), no shared
    expert, in the others; a final norm and a head tied to the embedding.
    ``layers_held`` names the blocks of ``layer_types`` built here (the
    first ``num_hidden_layers`` by default), and ``conv_layers`` /
    ``attention_layers`` (where given) must count their mixers. Every MoE
    routes over all ``num_experts`` and holds ``experts_held`` of them from
    ``first_expert`` on (all by default). Rows are ``seq_len`` positions
    where given (any one length otherwise). Feeds: ids / next_ids integer
    sequences."""
    from paddle_tpu.utils.error import enforce

    layer_types = list(layer_types or ["conv"] * num_hidden_layers)
    held = list(range(num_hidden_layers) if layers_held is None
                else layers_held)
    enforce(len(held) == num_hidden_layers
            and all(0 <= l < len(layer_types) for l in held),
            f"lfm2: layers_held {held} are not {num_hidden_layers} blocks of "
            f"the {len(layer_types)} in layer_types")
    kinds = [layer_types[l] for l in held]
    enforce(set(kinds) <= {"conv", "full_attention"},
            f"lfm2: layer types {sorted(set(kinds))} are not conv / "
            "full_attention")
    for want, kind in ((conv_layers, "conv"),
                       (attention_layers, "full_attention")):
        enforce(want is None or want == kinds.count(kind),
                f"lfm2: the blocks held have {kinds.count(kind)} {kind} "
                f"mixers, the arguments say {want}")
    ids = layer.data(name="ids",
                     type=data_type.integer_value_sequence(vocab_size))
    nxt = layer.data(name="next_ids",
                     type=data_type.integer_value_sequence(vocab_size))
    table = ParamAttr(name=f"_{name}_emb.w0")
    x = layer.embedding(input=ids, size=hidden_size, param_attr=table,
                        name=f"{name}_emb")
    head_dim = hidden_size // num_attention_heads
    for l in held:
        b = f"{name}_l{l}"
        normed = layer.rms_norm(input=x, eps=norm_eps, zero_centered=False,
                                name=f"{b}_in_norm")
        if layer_types[l] == "conv":
            mixed = layer.short_conv(input=normed, conv_kernel=conv_L_cache,
                                     scope=f"lfm2/l{l}/conv", name=f"{b}_conv")
        else:
            mixed = layer.gqa_attention(
                input=normed, num_heads=num_attention_heads,
                num_kv_heads=num_key_value_heads, head_dim=head_dim,
                rope_theta=rope_theta, eps=norm_eps, mask=("causal", seq_len),
                scope=f"lfm2/l{l}/attn", name=f"{b}_attn")
        h = layer.addto(input=[x, mixed], act=act.Linear(), bias_attr=False,
                        name=f"{b}_h")
        normed = layer.rms_norm(input=h, eps=norm_eps, zero_centered=False,
                                name=f"{b}_post_norm")
        if l < num_dense_layers:
            ffn = layer.gated_mlp(input=normed, size=intermediate_size,
                                  scope=f"lfm2/l{l}/mlp", name=f"{b}_mlp")
        else:
            ffn = layer.moe_ffn(
                input=normed, num_experts=num_experts,
                top_k=num_experts_per_tok, expert_size=moe_intermediate_size,
                experts_held=experts_held, first_expert=first_expert,
                score="sigmoid", selection_bias=True,
                bias_rate=bias_update_rate,
                route_scale=routed_scaling_factor, route_norm_eps=1e-6,
                scope=f"lfm2/l{l}/moe", name=f"{b}_moe")
        x = layer.addto(input=[h, ffn], act=act.Linear(), bias_attr=False,
                        name=f"{b}_out")
    x = layer.rms_norm(input=x, eps=norm_eps, zero_centered=False,
                       name=f"{name}_final_norm")
    probs = layer.mixed(
        size=vocab_size, act=act.Softmax(), bias_attr=False,
        input=layer.trans_full_matrix_projection(x, param_attr=table),
        name=f"{name}_head")
    return layer.classification_cost(input=probs, label=nxt, name="cost")
