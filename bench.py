"""Benchmark driver.

Headline metric (BASELINE.json north star): **ResNet-50 training
imgs/sec/chip**. vs_baseline compares against A100-class throughput
(~2500 imgs/sec for mixed-precision ResNet-50 training — the public
MLPerf-era figure the north star names); >1.0 means faster than an A100.

Protocol mirrors the reference benchmark scripts
(benchmark/paddle/image/run.sh: fixed batch, steady-state over repeated
iterations, first iteration excluded as compile/warmup).

Prints ONE JSON line. Extra models (smallnet, LSTM) can be benched via
`python bench.py --model smallnet|lstm|resnet50`.
"""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import optimizer
from paddle_tpu.core.topology import Topology
from paddle_tpu.observability import metrics as obs_metrics

#: ticks the early-exit decode loop actually executed per call — the r8
#: ':ticks' extra as a proper histogram (power-of-two buckets)
_M_DECODE_TICKS = obs_metrics.histogram(
    "paddle_decode_ticks",
    "Beam-decode ticks executed by the early-exit loop per generation "
    "call (max_length bounds it; fewer means eos exited early)",
    buckets=obs_metrics.COUNT_BUCKETS)


def _attach_metrics_extra(result, delta):
    """Fold the run's metric DELTA into the bench JSON extras, so BENCH
    artifacts carry data-stall / retry / checkpoint counters alongside
    the throughput numbers."""
    snap = obs_metrics.bench_extras(delta)
    if snap:
        result["extra"] = {**result.get("extra", {}), "metrics": snap}
    return result

A100_RESNET50_IMGS_PER_SEC = 2500.0   # mixed-precision A100 training rate
K40M_SMALLNET_MS = 18.184             # reference benchmark/README.md:56-60
K40M_LSTM_H512_BS64_MS = 184.0        # reference benchmark/README.md:117-121

# NMT north-star bar: derived in BASELINE.md ("NMT baseline derivation")
# and published in BASELINE.json — read from there so the three artifacts
# cannot drift (single source of truth).
def _nmt_bar():
    import os
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BASELINE.json")
    with open(path) as f:
        return float(json.load(f)["published"][
            "nmt_attention_train_tokens_per_sec_per_chip_bar"])


A100_CLASS_NMT_TOKENS_PER_SEC = _nmt_bar()   # ~257.8k tokens/sec


def _train_step_fn(topo, cost_name, opt, mixed=True):
    """bf16 compute + fp32 master weights, donated param/opt buffers —
    the exact jitted program the SGD trainer runs (shared builder)."""
    from paddle_tpu.trainer.trainer import make_train_step

    loss = topo.loss_fn(cost_name,
                        compute_dtype=jnp.bfloat16 if mixed else None)
    return make_train_step(loss, opt, topo.static_map(), donate=True)


def _measure(step, params, opt_state, feeds, iters, runs=1):
    """Median sec/step over `runs` back-to-back timing windows (one
    compile). runs=3 for the north stars: r4 runs scattered ~±2%
    run-to-run, so the driver's number should be a median with a
    recorded band (VERDICT r4 weak #8), not one draw."""
    rng = jax.random.PRNGKey(0)
    params, opt_state, c, _ = step(params, opt_state, rng, feeds)  # compile
    float(c)  # device->host fetch of the cost syncs the whole step
    secs = []
    for run in range(runs):
        t0 = time.perf_counter()
        for i in range(iters):
            params, opt_state, c, _ = step(params, opt_state,
                                           jax.random.fold_in(rng, i), feeds)
        # the final cost depends on the whole step chain, so fetching it
        # forces every queued step to execute. On the v5e through
        # libtpu, block_until_ready waits for the device just as well
        # (chip_smoke.py's probe: 0.143 s blocked, then 0.7 ms to fetch;
        # CHANGES.md PR 21) — the benchmark PR keeps one
        float(c)
        secs.append((time.perf_counter() - t0) / iters)
    secs.sort()
    return secs[len(secs) // 2], (secs[0], secs[-1])


def bench_resnet50(batch=256, iters=60):
    # iters=60 (was 20): the dispatch queue needs depth to amortise
    # per-launch latency; 20 iters under-reported steady state by
    # ~3.5 ms/step (r4, not re-measured: 99.85 ms at 20 vs 96.3 at 60,
    # device self-time 94.5). Reference protocol is steady-state too
    # (benchmark/paddle/image/run.sh --iterations=...).
    from paddle_tpu.models.resnet import resnet_cost

    img, lab, out, cost = resnet_cost(depth=50, img_size=224)
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9)
    opt_state = opt.init(params)
    step = _train_step_fn(topo, cost, opt)
    r = np.random.RandomState(0)
    # NHWC bf16 batches end-to-end (r3 perf note PERF_r03.md): the input
    # pipeline delivers what the TPU convs natively consume — no per-step
    # CHW->NHWC transpose, half the input HBM traffic. bs=256 measured
    # fastest of {128, 256, 384, 512} on v5e.
    feeds = {"image": jnp.asarray(r.rand(batch, 224, 224, 3), jnp.bfloat16),
             "label": jnp.asarray(r.randint(0, 1000, (batch, 1)), jnp.int32)}
    sec, (lo, hi) = _measure(step, params, opt_state, feeds, iters, runs=3)
    imgs_per_sec = batch / sec
    from paddle_tpu.flops import bench_flop_fields
    return {"metric": "resnet50_train_imgs_per_sec_per_chip",
            "value": round(imgs_per_sec, 1),
            "unit": "imgs/sec/chip",
            "band": [round(batch / hi, 1), round(batch / lo, 1)],
            "vs_baseline": round(imgs_per_sec / A100_RESNET50_IMGS_PER_SEC, 3),
            # absolute audit trail (paddle_tpu/flops.py): model TFLOPs per
            # step and mfu against the chip's published peak — perf claims
            # stop being baseline-relative only (VERDICT weak §2)
            "extra": bench_flop_fields(topo, batch, 1, sec)}


def _measure_loop(topo, cost, opt, feeds, steps_per_call=50, calls=4,
                  mixed=True):
    """Steady-state ms/step through a DEVICE-side training loop
    (make_train_loop): for small models per-dispatch overhead (~5-7 ms
    at r3, not re-measured) dwarfs the chip time, and a TPU-native
    trainer keeps the batch loop on-device anyway."""
    import os
    os.environ["PADDLE_TPU_ALLOW_SCAN_LOOP"] = "1"   # bench IS the sanctioned user
    from paddle_tpu.trainer.trainer import make_train_loop

    params = topo.init_params(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    loss = topo.loss_fn(cost, compute_dtype=jnp.bfloat16 if mixed else None)
    loop = make_train_loop(loss, opt, topo.static_map(), steps_per_call)
    rng = jax.random.PRNGKey(0)
    params, opt_state, c = loop(params, opt_state, rng, feeds)
    float(c)
    t0 = time.perf_counter()
    for i in range(calls):
        params, opt_state, c = loop(params, opt_state,
                                    jax.random.fold_in(rng, i), feeds)
    float(c)
    return (time.perf_counter() - t0) / (calls * steps_per_call)


def bench_smallnet(batch=128):
    from paddle_tpu.models.image_bench import smallnet_mnist_cifar

    img, lab, out, cost = smallnet_mnist_cifar()
    topo = Topology(cost)
    opt = optimizer.Momentum(learning_rate=0.01, momentum=0.9)
    r = np.random.RandomState(0)
    feeds = {"image": jnp.asarray(r.rand(batch, 3 * 32 * 32), jnp.float32),
             "label": jnp.asarray(r.randint(0, 10, (batch, 1)), jnp.int32)}
    ms = _measure_loop(topo, cost, opt, feeds) * 1e3
    return {"metric": "smallnet_cifar_bs128_train_ms_per_batch",
            "value": round(ms, 3), "unit": "ms/batch",
            "vs_baseline": round(K40M_SMALLNET_MS / ms, 3)}


def bench_lstm(batch=64, seq_len=100, hidden=512):
    from paddle_tpu.models.text import lstm_text_classification
    from paddle_tpu.core.arg import Arg

    words, lab, out, cost = lstm_text_classification(dict_dim=30000,
                                                     emb_dim=hidden,
                                                     hidden=hidden,
                                                     num_layers=2)
    topo = Topology(cost)
    opt = optimizer.Adam(learning_rate=1e-3)
    r = np.random.RandomState(0)
    feeds = {"words": Arg(jnp.asarray(r.randint(0, 30000, (batch, seq_len)),
                                      jnp.int32),
                          jnp.ones((batch, seq_len), jnp.float32)),
             "label": jnp.asarray(r.randint(0, 2, (batch, 1)), jnp.int32)}
    ms = _measure_loop(topo, cost, opt, feeds, steps_per_call=20) * 1e3
    return {"metric": "lstm_h512_bs64_seq100_train_ms_per_batch",
            "value": round(ms, 3), "unit": "ms/batch",
            "vs_baseline": round(K40M_LSTM_H512_BS64_MS / ms, 3)}


def _bench_image_model(build, model, baselines, batch, iters=20,
                       classes=1000, opt=None):
    """Shared image-model ms/batch protocol (benchmark/paddle/image).
    ``baselines``: {batch_size: reference ms} — vs_baseline is only
    reported when the measured batch has a published reference number
    (cross-batch ratios would be bogus)."""
    img, lab, out, cost = build()
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = opt or optimizer.Momentum(learning_rate=0.01, momentum=0.9)
    opt_state = opt.init(params)
    step = _train_step_fn(topo, cost, opt)
    size = topo.info(topo.layer_map[img.name]).size
    r = np.random.RandomState(0)
    feeds = {"image": jnp.asarray(r.rand(batch, size), jnp.float32),
             "label": jnp.asarray(r.randint(0, classes, (batch, 1)),
                                  jnp.int32)}
    ms = _measure(step, params, opt_state, feeds, iters)[0] * 1e3
    baseline = baselines.get(batch)
    return {"metric": f"{model}_bs{batch}_train_ms_per_batch",
            "value": round(ms, 3), "unit": "ms/batch",
            "vs_baseline": (round(baseline / ms, 3) if baseline else None)}


def bench_alexnet(batch=128, iters=40):
    from paddle_tpu.models.image_bench import alexnet

    # reference benchmark/README.md:35-39
    return _bench_image_model(alexnet, "alexnet",
                              {64: 195.0, 128: 334.0, 256: 602.0,
                               512: 1629.0}, batch, iters)


def bench_googlenet(batch=128, iters=10):
    from paddle_tpu.models.image_bench import googlenet

    # reference benchmark/README.md:48-52
    return _bench_image_model(googlenet, "googlenet",
                              {64: 613.0, 128: 1149.0, 256: 2348.0},
                              batch, iters)


def bench_vgg(batch=64, iters=10):
    # reference benchmark config exists but README publishes no number
    from paddle_tpu.models.image_bench import vgg

    return _bench_image_model(vgg, "vgg16", {}, batch, iters)


def bench_nmt(batch=256, seq_len=30, iters=100):
    # iters=100: queue-depth amortisation as in bench_resnet50, plus the
    # ~19ms NMT step needs a longer window — 30-iter (0.6s) measurements
    # scattered +-7% (r4 band: 376-431k tokens/sec, not re-measured);
    # 100 iters (~2s) tightens it
    """Attention seq2seq training tokens/sec/chip (the BASELINE.json north
    star's second metric). vs_baseline compares against the derived
    A100-class bar (A100_CLASS_NMT_TOKENS_PER_SEC above; full derivation
    in BASELINE.md). batch=256 is the measured throughput plateau on v5e
    (32/64/128/256/512 -> 61.8k/89.2k/127.5k/166.6k/164.4k tokens/sec,
    r3; r4's hoisted vocab projection lifted the plateau to ~292k)."""
    from paddle_tpu import data_type, layer, networks
    from paddle_tpu.attr import ParamAttr
    from paddle_tpu.core.arg import Arg

    V = 30000
    src = layer.data(name="src", type=data_type.integer_value_sequence(V))
    trg_ids = layer.data(name="trg",
                         type=data_type.integer_value_sequence(V))
    lab = layer.data(name="trg_next",
                     type=data_type.integer_value_sequence(V))
    trg_emb = layer.embedding(input=trg_ids, size=512,
                              param_attr=ParamAttr(name="_trg_emb"))
    probs = networks.gru_encoder_decoder(src_word_id=src,
                                         trg_embedding=trg_emb)
    cost = layer.classification_cost(input=probs, label=lab, name="cost")
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    opt = optimizer.Adam(learning_rate=5e-4)
    opt_state = opt.init(params)
    step = _train_step_fn(topo, cost, opt)
    r = np.random.RandomState(0)
    mask = jnp.ones((batch, seq_len), jnp.float32)
    feeds = {
        "src": Arg(jnp.asarray(r.randint(0, V, (batch, seq_len)), jnp.int32),
                   mask),
        "trg": Arg(jnp.asarray(r.randint(0, V, (batch, seq_len)), jnp.int32),
                   mask),
        "trg_next": Arg(jnp.asarray(r.randint(0, V, (batch, seq_len)),
                                    jnp.int32), mask),
    }
    sec, (lo, hi) = _measure(step, params, opt_state, feeds, iters, runs=3)
    tokens_per_sec = batch * seq_len / sec
    from paddle_tpu.flops import bench_flop_fields
    return {"metric": "nmt_attention_train_tokens_per_sec_per_chip",
            "value": round(tokens_per_sec, 1), "unit": "tokens/sec/chip",
            "band": [round(batch * seq_len / hi, 1),
                     round(batch * seq_len / lo, 1)],
            "vs_baseline": round(tokens_per_sec /
                                 A100_CLASS_NMT_TOKENS_PER_SEC, 3),
            "extra": bench_flop_fields(topo, batch, seq_len, sec)}


def bench_nmt_packed(batch=256, seq_lo=4, seq_hi=30, iters=60, V=30000,
                     dim=512, heads=8, pack_max_len=128, quick=False):
    """Padded-vs-packed NMT training (`--model nmt_packed`; ISSUE 6,
    docs/packing.md): the SAME packing-ready attention seq2seq
    (models/text.nmt_packed_cost) trained on one ragged sample stream,
    fed two ways — one padded sample per row (the r10-measured
    `paddle_feed_pad_fraction` waste) and sequence-packed rows with
    seg_ids. tokens/sec counts REAL target tokens, identical in both
    modes, so the speedup is exactly the step-time ratio.

    Headline value = packed tokens/sec/chip; ``vs_baseline`` = speedup
    over the padded feed. ``extra`` carries both columns, each mode's
    achieved pad fraction, the packing efficiency %, and the speedup the
    eliminated pad fraction predicts (compute scales ~ rows*T for the
    recurrent stack; attention's quadratic term makes the realized
    speedup workload-dependent). Lengths are NMT-like: trg correlated
    with src (+-2), the regime where the multi-slot packing plan fills
    rows to ~98%."""
    import jax.numpy as jnp

    from paddle_tpu.core.layer import layer_name_scope
    from paddle_tpu.models.text import nmt_packed_cost
    from paddle_tpu.trainer.feeder import DataFeeder

    if quick:
        batch, iters, V, dim, heads = 16, 3, 64, 32, 2
        seq_hi, pack_max_len = 12, 24
    with layer_name_scope():
        cost = nmt_packed_cost(src_dict_dim=V, trg_dict_dim=V,
                               word_vector_dim=dim, encoder_size=dim,
                               decoder_size=dim, num_heads=heads, name="mp")
    topo = Topology(cost)
    opt = optimizer.Adam(learning_rate=5e-4)
    step = _train_step_fn(topo, cost, opt, mixed=not quick)
    r = np.random.RandomState(0)
    samples = []
    for _ in range(batch):
        ts = int(r.randint(seq_lo, seq_hi + 1))
        tt = max(3, ts + int(r.randint(-2, 3)))
        samples.append((r.randint(0, V, ts).tolist(),
                        r.randint(0, V, tt).tolist(),
                        r.randint(0, V, tt).tolist()))
    feeding = {"src": 0, "trg": 1, "trg_next": 2}
    real_tokens = float(sum(len(s[1]) for s in samples))

    def run(pack):
        # pack_row_rounding=1: the bench times ONE fixed batch, so there
        # is a single compiled shape either way — the rounding default
        # (8, for real variable streams where the plan's R drifts) would
        # only dilute the compute measurement with filler rows
        feeder = DataFeeder(topo.data_type(), feeding, pack_sequences=pack,
                            pack_max_len=pack_max_len if pack else None,
                            pack_row_rounding=1)
        feeds = feeder(samples)
        params = topo.init_params(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        sec, (lo, hi) = _measure(step, params, opt_state, feeds, iters,
                                 runs=3)
        masks = {k: np.asarray(a.mask) for k, a in feeds.items()}
        pad_frac = {k: round(1.0 - float(m.sum()) / m.size, 4)
                    for k, m in masks.items()}
        rows, T = masks["trg"].shape
        return {"tokens_per_sec": round(real_tokens / sec, 1),
                "band": [round(real_tokens / hi, 1),
                         round(real_tokens / lo, 1)],
                "ms_per_batch": round(sec * 1e3, 3),
                "rows": int(rows), "padded_T": int(T),
                "pad_fraction": pad_frac}

    padded = run(False)
    packed = run(True)
    pf_pad = max(padded["pad_fraction"].values())
    pf_pack = max(packed["pad_fraction"].values())
    cells_ratio = (padded["rows"] * padded["padded_T"]) / float(
        packed["rows"] * packed["padded_T"])
    return {"metric": "nmt_packed_train_tokens_per_sec_per_chip",
            "value": packed["tokens_per_sec"], "unit": "tokens/sec/chip",
            "band": packed["band"],
            # the padded feed IS the baseline: >1.0 = packing deleted
            # padding compute from the hot loop
            "vs_baseline": round(packed["tokens_per_sec"] /
                                 max(padded["tokens_per_sec"], 1e-9), 3),
            "extra": {
                "padded": padded, "packed": packed,
                "pad_fraction_padded": pf_pad,
                "pad_fraction_packed": pf_pack,
                "packing_efficiency_pct": round(100.0 * (1.0 - pf_pack), 2),
                # rows*T shrink factor = the speedup the eliminated pad
                # fraction predicts for compute linear in padded cells
                "expected_speedup_from_pad_fraction": round(cells_ratio, 3),
            }}


def _decode_length_model(max_length, eos_id=1, beam=1):
    """Deterministic per-sample output-length schedule (6..3/4*max_length)
    emulating a trained model's varied sentence lengths: after a sample's
    target length every hypothesis is pushed onto eos, so the early-exit
    loop terminates like a production decode instead of always paying
    max_length ticks on random-init params (which essentially never emit
    eos). The beam copies of one sample share the sample's length (rows
    of one sample also keep it across parent reindexing — they are
    interchangeable within the sample's row block). Mode-agnostic: works
    on vocab-space ([BK, V]) and candidate-space ([BK, K], via
    state['cand_ids']) log-probs."""
    import jax.numpy as jnp

    lo = min(6, max_length - 1)
    hi = max(lo + 1, (3 * max_length) // 4)

    def lengths_for(bk):
        return lo + ((jnp.arange(bk) // beam) % (hi - lo + 1))

    def candidate_adjust(t, logp, state):
        bk = logp.shape[0]
        want_eos = (t >= lengths_for(bk))[:, None]
        ids = state.get("cand_ids")
        col = ids if ids is not None else jnp.arange(logp.shape[-1])[None, :]
        return jnp.where(want_eos,
                         jnp.where(col == eos_id, 0.0, -1e4), logp)

    return candidate_adjust


def bench_nmt_decode(batch=16, seq_len=10, beam=4, max_length=16,
                     cand_k=1024, iters=3, V=30000, mode="compact",
                     length_model=True, selective=None):
    """Beam-search decode throughput (tokens/sec/chip = generated tokens
    per wall second) — the one production path that had no performance
    story (VERDICT r5 items 2/4: RecurrentGradientMachine.cpp:964).

    ``mode`` selects the decode path (docs/decode.md):
      dense     — full-vocab projection + beam over [B*beam, V]
      selective — selective_fc gather projection, beam still over
                  [B*beam, V] (the r6 wiring)
      compact   — compact-K: projection AND beam in candidate space
                  ([B*beam, K]), no per-tick O(V) op (r8 tentpole)

    ``length_model=True`` adds the deterministic per-sample output-length
    schedule (_decode_length_model) so the early-exit loop terminates the
    way a trained model's decode does; the reported mean_ticks_executed
    extra is measured from the compiled loop. ``length_model=False``
    reproduces the r6 protocol (no eos — every tick runs).

    ``selective`` (bool) is the r6-era alias: True -> mode="selective",
    False -> mode="dense".
    """
    from paddle_tpu.core.arg import Arg
    from paddle_tpu.flops import decode_flop_fields
    from paddle_tpu.models.text import nmt_decode_topology

    if selective is not None:
        mode = "selective" if selective else "dense"
    eos_id = 1
    gen = nmt_decode_topology(src_dict_dim=V, trg_dict_dim=V,
                              beam_size=beam, max_length=max_length,
                              cand_k=cand_k, mode=mode, name="m")
    if length_model:
        from paddle_tpu.layer import BeamSearchControlCallbacks
        gen.cfg["ctrl_callbacks"] = BeamSearchControlCallbacks(
            candidate_adjust=_decode_length_model(max_length, eos_id,
                                                  beam=beam))
    topo = Topology(gen)
    params = topo.init_params(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    feeds = {"src": Arg(jnp.asarray(r.randint(0, V, (batch, seq_len)),
                                    jnp.int32),
                        jnp.ones((batch, seq_len), jnp.float32))}
    if mode != "dense":
        # unique candidate rows (select_unique contract) with eos present
        # (finished hypotheses extend with eos — docs/decode.md contract)
        cand = np.stack([r.choice(V, cand_k, replace=False)
                         for _ in range(batch)]).astype(np.int32)
        no_eos = ~(cand == eos_id).any(axis=1)
        cand[no_eos, 0] = eos_id
        feeds["cand"] = Arg(jnp.asarray(cand))

    ids_name, ticks_name = f"{gen.name}:ids", f"{gen.name}:ticks"

    @jax.jit
    def decode(params, feeds):
        outs, ctx = topo.forward(params, feeds, return_ctx=True)
        # emitted = the best beam's tokens up to and including eos (the
        # layer output's mask): with the length model the early-exit
        # loop stops short of max_length, so tokens/sec must count what
        # was actually generated, not batch*max_length
        emitted = outs[gen.name].mask.sum()
        return ctx.extras[ids_name], ctx.extras[ticks_name], emitted

    ids, ticks, emitted = decode(params, feeds)    # compile + warmup
    np.asarray(ids)
    secs = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            ids, ticks, emitted = decode(params, feeds)
        np.asarray(ids)                        # drain dispatch queue
        secs.append((time.perf_counter() - t0) / iters)
    secs.sort()
    sec, lo, hi = secs[1], secs[0], secs[-1]
    ticks = int(ticks)
    _M_DECODE_TICKS.observe(ticks)
    toks = float(emitted)                      # emitted tokens (best beam)
    return {"metric": "nmt_decode_tokens_per_sec_per_chip",
            "value": round(toks / sec, 1), "unit": "tokens/sec/chip",
            "band": [round(toks / hi, 1), round(toks / lo, 1)],
            "beam": beam, "mode": mode, "cand_k": cand_k,
            "vocab": V, "batch": batch, "max_length": max_length,
            "extra": {"mean_ticks_executed": ticks,
                      **decode_flop_fields(topo, batch, seq_len, ticks,
                                           sec)}}


def bench_nmt_decode_all(**kw):
    """`--model nmt_decode`: all three decode paths side by side — the
    headline value is the compact-K path; the dense and selective columns
    ride in the extras (the r8 compact-K column next to the r6 paths)."""
    cols = {m: bench_nmt_decode(mode=m, **kw)
            for m in ("dense", "selective", "compact")}
    out = dict(cols["compact"])
    out["extra"] = {**out.get("extra", {}),
                    "tokens_per_sec_by_mode":
                    {m: d["value"] for m, d in cols.items()},
                    "band_by_mode": {m: d["band"] for m, d in cols.items()}}
    return out


def bench_pipeline(batch=256, batches=60, pipeline_depth=2, feed_ms=4.0,
                   dim=512, hidden=512, classes=16, trainer="sgd",
                   num_micro=4, quick=False):
    """Data-bound train-loop workload: the SAME model/reader through
    `SGD.train` at ``pipeline_depth=0`` (the pre-ISSUE-5 synchronous
    loop) and at ``--pipeline_depth`` (default 2), side by side. The
    reader carries a deterministic ``feed_ms`` host cost per batch
    (emulating decode/augment/tokenize), sized against a model whose
    step time is comparable — the regime where the synchronous loop
    pays wait+feed+compute and the pipelined loop pays ~max of them
    (docs/pipeline.md).

    Headline value is the pipelined ms/batch; ``vs_baseline`` is the
    speedup over the synchronous loop. ``extra`` carries both columns
    with each mode's raw per-batch phase costs, plus
    ``overlapped_compute_ms_per_batch`` = sync compute - pipelined
    compute: compute_ms is dispatch+drain, which under pipelining only
    measures the NON-overlapped device time, so the difference is
    exactly the compute that left the critical path (wall ≈
    max(compute, wait+feed) instead of their sum — the data-wait
    seconds stop stacking on top of compute). NOTE: single-device CPU
    runs execute the step inline in the dispatch call (no async
    dispatch to hide work under), so the collapse shows on TPU and on
    sharded meshes (``trainer="dp"``/``"pp"``), not on the 1-CPU test
    client.

    ``trainer="pp"`` (r13, docs/pipeline.md "One pipeline") runs the
    PipelineParallelTrainer on a 4-stage mesh over a deliberately
    stage-UNBALANCED model, in FOUR columns: {naive, balanced} stage
    assignment x {sync, host-overlapped} loop — the naive column pays
    the annotation-inherited fat stage, the balanced column the
    width-balanced partitioner's, and the overlapped columns thread the
    GPipe schedule through the r10 host pipeline so batch N+1's feed
    hides in the bubble. Each column carries the static
    ``paddle_pp_stage_padding_fraction`` values next to its phase costs.
    """
    import time as _time

    import paddle_tpu as paddle
    from paddle_tpu import activation, data_type, layer

    if quick:
        batch, batches, feed_ms = 16, 6, 2.0
        dim, hidden, classes, num_micro = 32, 32, 4, 2

    rs = np.random.RandomState(0)
    X = rs.randn(batch * 4, dim).astype(np.float32)
    Y = (X @ rs.randn(dim, classes)).argmax(1).astype(np.int64)

    def make_reader(n_batches, sleep_s):
        def r():
            for b in range(n_batches):
                if sleep_s:
                    _time.sleep(sleep_s)
                base = (b * batch) % X.shape[0]
                yield [(X[(base + i) % X.shape[0]],
                        int(Y[(base + i) % X.shape[0]]))
                       for i in range(batch)]
        return r

    def make_trainer(balance=False):
        opt = optimizer.Momentum(learning_rate=0.01, momentum=0.9)
        if trainer == "pp":
            # stage-unbalanced chain: the device annotations dump three
            # of the five hidden matmuls on stage 1 (the naive
            # assignment); balance=True ignores the imbalance and
            # re-cuts the chain
            devs = (0, 1, 1, 1, 2)
            h = layer.data(name="x", type=data_type.dense_vector(dim))
            y = layer.data(name="y", type=data_type.integer_value(classes))
            for i, d in enumerate(devs):
                h = layer.fc(input=h, size=hidden, act=activation.Relu(),
                             name=f"h{i}",
                             layer_attr=paddle.attr.ExtraAttr(device=d))
            out = layer.fc(input=h, size=classes, act=activation.Softmax(),
                           name="out",
                           layer_attr=paddle.attr.ExtraAttr(device=3))
            cost = layer.classification_cost(
                input=out, label=y, name="cost",
                layer_attr=paddle.attr.ExtraAttr(device=3))
            params = paddle.parameters_create(paddle.Topology(cost))
            from paddle_tpu.parallel.pp import PipelineParallelTrainer
            kw = ({"balance": True, "num_stages": 4} if balance
                  else {"stage_map": None})
            return PipelineParallelTrainer(cost=cost, parameters=params,
                                           update_equation=opt,
                                           num_micro=num_micro, **kw)
        x = layer.data(name="x", type=data_type.dense_vector(dim))
        y = layer.data(name="y", type=data_type.integer_value(classes))
        h1 = layer.fc(input=x, size=hidden, act=activation.Relu())
        h2 = layer.fc(input=h1, size=hidden, act=activation.Relu())
        out = layer.fc(input=h2, size=classes, act=activation.Softmax())
        cost = layer.classification_cost(input=out, label=y)
        params = paddle.parameters_create(paddle.Topology(cost))
        if trainer == "dp":
            from paddle_tpu.parallel.dp import DataParallelTrainer
            return DataParallelTrainer(cost=cost, parameters=params,
                                       update_equation=opt)
        return paddle.SGD(cost=cost, parameters=params, update_equation=opt)

    hist = obs_metrics.default_registry.histogram(
        "paddle_train_step_seconds", labels=("phase",))

    def phase_sums():
        return {p: hist.labels(phase=p).sum
                for p in ("data_wait", "feed", "dispatch", "drain")}

    def run(depth, balance=False):
        t = make_trainer(balance)
        # warmup/compile excluded (two batches, no sleep)
        t.train(make_reader(2, 0.0), num_passes=1, pipeline_depth=depth)
        before = phase_sums()
        t0 = _time.perf_counter()
        t.train(make_reader(batches, feed_ms / 1e3), num_passes=1,
                pipeline_depth=depth)
        wall = _time.perf_counter() - t0
        d = {p: (v - before[p]) / batches * 1e3
             for p, v in phase_sums().items()}
        wall_ms = wall / batches * 1e3
        col = {"ms_per_batch": round(wall_ms, 3),
               "data_wait_ms": round(d["data_wait"], 3),
               "feed_ms": round(d["feed"], 3),
               "compute_ms": round(d["dispatch"] + d["drain"], 3),
               "data_wait_share": round(d["data_wait"] / wall_ms, 3)}
        if trainer == "pp":
            pad = obs_metrics.default_registry.gauge(
                "paddle_pp_stage_padding_fraction", labels=("kind",))
            col["stage_padding_fraction"] = {
                k: round(pad.labels(kind=k).value, 4)
                for k in ("param", "boundary")}
        return col

    depth = max(0, int(pipeline_depth))
    if trainer == "pp":
        cols = {"naive_sync": run(0, balance=False),
                "naive_overlapped": run(depth, balance=False),
                "balanced_sync": run(0, balance=True),
                "balanced_overlapped": run(depth, balance=True)}
        best = cols["balanced_overlapped"]
        base = cols["naive_sync"]
        return {"metric": "pipeline_pp_train_ms_per_batch",
                "value": best["ms_per_batch"], "unit": "ms/batch",
                # naive synchronous IS the pre-r13 state: balancer win x
                # host-overlap win combined
                "vs_baseline": round(base["ms_per_batch"] /
                                     best["ms_per_batch"], 3),
                "pipeline_depth": depth,
                "extra": {**cols,
                          "overlapped_compute_ms_per_batch": {
                              "naive": round(
                                  cols["naive_sync"]["compute_ms"]
                                  - cols["naive_overlapped"]["compute_ms"],
                                  3),
                              "balanced": round(
                                  cols["balanced_sync"]["compute_ms"]
                                  - cols["balanced_overlapped"][
                                      "compute_ms"], 3)},
                          "num_micro": num_micro, "num_stages": 4,
                          "feed_sleep_ms": feed_ms, "batches": batches,
                          "batch": batch, "trainer": trainer}}
    sync = run(0)
    pipe = run(depth)
    return {"metric": "pipeline_databound_train_ms_per_batch",
            "value": pipe["ms_per_batch"], "unit": "ms/batch",
            # the synchronous loop IS the baseline here: >1.0 means the
            # pipeline hid host feed/wait under device compute
            "vs_baseline": round(sync["ms_per_batch"] /
                                 pipe["ms_per_batch"], 3),
            "pipeline_depth": int(pipeline_depth),
            "extra": {"sync": sync, "pipelined": pipe,
                      "overlapped_compute_ms_per_batch":
                          round(sync["compute_ms"] - pipe["compute_ms"], 3),
                      "feed_sleep_ms": feed_ms, "batches": batches,
                      "batch": batch, "trainer": trainer}}


def bench_ctr(batch=256, batches=30, vocab=100_000_000, hbm_vocab=1_000_000,
              wide_dim=100_000, emb_dim=16, max_ids=32, hidden=64,
              cache_rows=8192, quick=False):
    """CTR wide&deep sparse-embedding training (`--model ctr`; the A.8
    CTR-sparse workload bar, VERDICT r5 item 3) — three columns over
    ``models/text.ctr_wide_deep``:

      hbm       — HBM-resident tables at ``hbm_vocab`` rows (the only
                  place the table still fits on device)
      host      — HOST-resident tables at the SAME vocab, forced-small
                  device row cache (docs/embedding_cache.md): the
                  apples-to-apples fraction of HBM throughput
      host_big  — host-resident at ``vocab`` rows (default 100M: table
                  would exceed any single device's memory budget; rows
                  materialize lazily, so neither host RAM nor HBM ever
                  holds [V, D]) — the production-recommender scenario no
                  HBM config can run at all

    Headline value = host_big examples/sec; ``vs_baseline`` = host/hbm
    at the matched vocab (the measured fraction of HBM-resident
    throughput the overflow path costs). Cache hit-rate / prefetch /
    flush metrics ride in ``extra.metrics`` via the registry delta."""
    import paddle_tpu as paddle
    from paddle_tpu.core.layer import layer_name_scope
    from paddle_tpu.core.parameters import Parameters
    from paddle_tpu.core.topology import Topology as _Topo
    from paddle_tpu.models.text import ctr_wide_deep
    from paddle_tpu.trainer.trainer import SGD

    if quick:
        batch, batches, max_ids, emb_dim, hidden = 8, 4, 4, 4, 8
        vocab, hbm_vocab, wide_dim, cache_rows = 50_000, 512, 256, 64

    feeding = {"wide_ids": 0, "deep_ids": 1, "click": 2}

    def make_reader(n_batches, deep_vocab, seed=0):
        r = np.random.RandomState(seed)
        data = []
        for _ in range(n_batches):
            rows = []
            for _i in range(batch):
                wk = r.randint(1, max_ids + 1)
                dk = r.randint(1, max_ids + 1)
                rows.append((np.unique(r.randint(0, wide_dim, wk)).tolist(),
                             np.unique(r.randint(0, deep_vocab, dk)).tolist(),
                             int(r.randint(0, 2))))
            data.append(rows)

        def reader():
            for b in data:
                yield b
        return reader

    def column(deep_vocab, host, host_attr):
        with layer_name_scope():
            _ins, _lab, _out, cost = ctr_wide_deep(
                wide_dim=wide_dim, deep_vocab=deep_vocab, emb_dim=emb_dim,
                max_ids=max_ids, hidden=hidden, host_resident=host_attr)
        topo = _Topo(cost)
        params = Parameters.from_topology(topo, jax.random.PRNGKey(0))
        opt = optimizer.SGD(learning_rate=0.05)
        t = SGD(cost=cost, parameters=params, update_equation=opt)
        kw = {}
        if host:
            kw = dict(host_tables=None if host_attr
                      else ["_deep_emb", "_wide_w"],
                      host_cache_rows=cache_rows)
        t.train(make_reader(2, deep_vocab), num_passes=1, feeding=feeding,
                **kw)                               # compile + warmup
        t0 = time.perf_counter()
        t.train(make_reader(batches, deep_vocab, seed=1), num_passes=1,
                feeding=feeding, **kw)
        wall = time.perf_counter() - t0
        col = {"examples_per_sec": round(batch * batches / wall, 1),
               "ms_per_batch": round(wall / batches * 1e3, 3),
               "deep_vocab": int(deep_vocab)}
        if host and t._host_rt is not None:
            t._host_rt.barrier()
            col["touched_rows"] = {p: s.touched_rows
                                   for p, s in t._host_rt.tables.items()}
            col["_stores"] = dict(t._host_rt.tables)
            t._host_rt.close()
        return col

    def snapshot_probe(stores):
        """Durability-cost probe (r18): snapshot the trained host stores
        through the crash-safe pserver's own writer — the
        ``paddle_pserver_snapshot_*`` series land in ``extra.metrics``
        via the registry delta, plus explicit ms/bytes columns so the
        overhead is visible in the bench trajectory."""
        import shutil as _sh
        import tempfile as _tf

        from paddle_tpu.distributed.async_pserver import AsyncParamServer

        d = _tf.mkdtemp(prefix="bench_pserver_snap_")
        srv = None
        try:
            srv = AsyncParamServer({}, optimizer.SGD(learning_rate=0.05),
                                   row_tables=stores, snapshot_dir=d,
                                   keep_snapshots=1)
            times, path = [], None
            for _ in range(3):
                t0 = time.perf_counter()
                path = srv.snapshot()
                times.append(time.perf_counter() - t0)
            size = os.path.getsize(os.path.join(path, "state.pkl"))
            return {"snapshot_ms": round(min(times) * 1e3, 3),
                    "snapshot_bytes": int(size)}
        finally:
            if srv is not None:
                srv.stop()
            _sh.rmtree(d, ignore_errors=True)

    hbm = column(hbm_vocab, host=False, host_attr=False)
    host = column(hbm_vocab, host=True, host_attr=False)
    host_big = column(vocab, host=True, host_attr=True)
    pserver_snapshot = snapshot_probe(host.pop("_stores"))
    pserver_snapshot_big = snapshot_probe(host_big.pop("_stores"))
    frac = host["examples_per_sec"] / max(hbm["examples_per_sec"], 1e-9)
    return {"metric": "ctr_wide_deep_host_table_examples_per_sec",
            "value": host_big["examples_per_sec"],
            "unit": "examples/sec/chip",
            # the HBM-resident run IS the baseline: the value is the
            # measured fraction of it the host-overflow path sustains at
            # the matched vocab (host_big has NO hbm comparator — that
            # table cannot exist on device)
            "vs_baseline": round(frac, 3),
            "vocab": int(vocab), "batch": batch,
            "cache_rows": int(cache_rows),
            "extra": {"hbm": hbm, "host": host, "host_big": host_big,
                      "host_fraction_of_hbm": round(frac, 3),
                      "max_ids": max_ids, "emb_dim": emb_dim,
                      # r18 durability cost: one atomic checksummed
                      # pserver snapshot of the trained stores (dense
                      # matched-vocab table; lazy 100M-row table saves
                      # touched rows only)
                      "pserver_snapshot": pserver_snapshot,
                      "pserver_snapshot_big": pserver_snapshot_big}}


def bench_multislice(batch=256, batches=40, dim=512, hidden=512, classes=16,
                     quick=False):
    """Multi-slice trainer columns (`--model multislice`; ISSUE 9,
    docs/multislice.md): the SAME fc model/batch stream through
    MultiSliceTrainer on the 2x4 slice x data mesh, in FOUR columns —
    {replicated, zero} optimizer-state layout x {flat, hierarchical}
    gradient reduction. Each column carries ms/batch, the per-chip
    optimizer-state MB (the ZeRO ~Nx drop — tools/zero_accounting.py
    prints the full per-optimizer table), and the measured
    gradient-sized per-axis all-reduce probes
    (paddle_ici/dcn_allreduce_seconds, riding extra.metrics).

    NOTE (CPU container): all 8 'chips' are host cores and both
    'ICI'/'DCN' hops are memcpys, so the flat-vs-hierarchical ms/batch
    split here is noise — the columns pin program SHAPE and state
    bytes; the latency asymmetry claim is the ROADMAP v5e re-measure.
    Headline = zero_hierarchical ms/batch; vs_baseline = replicated_flat
    / zero_hierarchical (the \"what the naive program costs\" ratio).
    """
    import time as _time

    import paddle_tpu as paddle
    from paddle_tpu import activation, data_type, layer
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.multislice import (MultiSliceTrainer,
                                                per_chip_opt_bytes)

    if quick:
        batch, batches = 16, 6
        dim, hidden, classes = 32, 32, 4

    rs = np.random.RandomState(0)
    Xd = rs.randn(batch * 4, dim).astype(np.float32)
    Yd = (Xd @ rs.randn(dim, classes)).argmax(1).astype(np.int64)

    def make_reader(n_batches):
        def r():
            for b in range(n_batches):
                base = (b * batch) % Xd.shape[0]
                yield [(Xd[(base + i) % Xd.shape[0]],
                        int(Yd[(base + i) % Xd.shape[0]]))
                       for i in range(batch)]
        return r

    def make_trainer(zero, hierarchical):
        opt = optimizer.Momentum(learning_rate=0.01, momentum=0.9)
        x = layer.data(name="x", type=data_type.dense_vector(dim))
        y = layer.data(name="y", type=data_type.integer_value(classes))
        h1 = layer.fc(input=x, size=hidden, act=activation.Relu())
        h2 = layer.fc(input=h1, size=hidden, act=activation.Relu())
        out = layer.fc(input=h2, size=classes, act=activation.Softmax())
        cost = layer.classification_cost(input=out, label=y)
        params = paddle.parameters_create(paddle.Topology(cost))
        return MultiSliceTrainer(cost=cost, parameters=params,
                                 update_equation=opt,
                                 mesh=make_mesh(slice=2, data=4),
                                 zero=zero, hierarchical=hierarchical)

    def run(zero, hierarchical):
        t = make_trainer(zero, hierarchical)
        t.train(make_reader(2), num_passes=1)        # compile/warmup
        t0 = _time.perf_counter()
        t.train(make_reader(batches), num_passes=1)
        wall_ms = (_time.perf_counter() - t0) / batches * 1e3
        mb = per_chip_opt_bytes(
            t._opt_state, t.mesh, zero=t.zero) / 1e6
        reg = obs_metrics.default_registry
        return {"ms_per_batch": round(wall_ms, 3),
                "per_chip_opt_state_mb": round(mb, 4),
                "ici_allreduce_ms": round(
                    reg.gauge("paddle_ici_allreduce_seconds").value * 1e3,
                    4),
                "dcn_allreduce_ms": round(
                    reg.gauge("paddle_dcn_allreduce_seconds").value * 1e3,
                    4)}

    cols = {"replicated_flat": run(False, False),
            "replicated_hierarchical": run(False, True),
            "zero_flat": run(True, False),
            "zero_hierarchical": run(True, True)}
    best = cols["zero_hierarchical"]
    base = cols["replicated_flat"]
    return {"metric": "multislice_train_ms_per_batch",
            "value": best["ms_per_batch"], "unit": "ms/batch",
            "vs_baseline": round(base["ms_per_batch"]
                                 / best["ms_per_batch"], 3),
            "mesh": "2x4 slice x data",
            "extra": {"columns": cols,
                      "opt_state_drop":
                          round(base["per_chip_opt_state_mb"]
                                / max(best["per_chip_opt_state_mb"], 1e-9),
                                2),
                      "batches": batches, "batch": batch,
                      "cpu_note": "flat-vs-hierarchical latency split is "
                                  "noise off-silicon; see ROADMAP v5e "
                                  "re-measure"}}


def bench_serving(quick=False, slots=None, tick_us=None, concurrency=None,
                  requests=None, max_new=None, quantize=False,
                  fleet=False, batch=False, window_ms=None,
                  host_table=False):
    """Serving daemon A/B (`--model serving`; ISSUE 10, docs/serving.md):
    drive the C++ daemon's decode queue at saturating load — more
    concurrent clients than slots — and compare --drain_batch (classic
    static batching: admit a batch, run until every member finishes)
    against continuous batching (admit into any freed slot mid-loop).

    The toy backend gives every tick a FIXED cost (real matmul +
    --toy_tick_us), independent of how many slots are live — the
    compiled-decode-step economics — so the columns isolate the
    SCHEDULER: requests/sec, p95 latency, mean slot occupancy
    (live-slot-ticks / (ticks * slots), from /metrics)."""
    import signal
    import subprocess
    import threading
    import urllib.request

    if host_table:
        return bench_serving_host_table(quick=quick,
                                        concurrency=concurrency,
                                        requests=requests)
    if fleet:
        return bench_serving_fleet(quick=quick, slots=slots,
                                   tick_us=tick_us,
                                   concurrency=concurrency,
                                   requests=requests, max_new=max_new)
    if quantize:
        return bench_serving_quantized(quick=quick,
                                       concurrency=concurrency,
                                       requests=requests)
    if batch:
        return bench_serving_batch(quick=quick, concurrency=concurrency,
                                   requests=requests, window_ms=window_ms)
    native = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "paddle_tpu", "native")
    daemon = os.path.join(native, "paddle_tpu_serving")
    r = subprocess.run(["make", "-C", native, "serving"],
                       capture_output=True)
    if r.returncode != 0 or not os.path.exists(daemon):
        raise RuntimeError("serving daemon build unavailable "
                           "(make -C paddle_tpu/native serving)")
    slots = slots or (4 if quick else 8)
    tick_us = tick_us or (500 if quick else 2000)
    concurrency = concurrency or (12 if quick else 48)
    requests = requests or (60 if quick else 400)
    max_new = max_new or (24 if quick else 48)

    def run_mode(drain):
        flags = [daemon, "--port", "0", "--backend", "toy",
                 "--slots", str(slots), "--toy_tick_us", str(tick_us),
                 "--threads", str(concurrency + 4),
                 "--max_queue", str(requests + concurrency),
                 "--max_new_cap", str(max_new)]
        if drain:
            flags.append("--drain_batch")
        proc = subprocess.Popen(flags, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        try:
            line = proc.stdout.readline()
            port = int(line.split("port")[1].split()[0])

            def post(path, obj):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}{path}",
                    data=json.dumps(obj).encode())
                with urllib.request.urlopen(req, timeout=300) as resp:
                    return json.loads(resp.read())

            # readiness
            deadline = time.time() + 10
            while time.time() < deadline:
                try:
                    urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/healthz", timeout=2)
                    break
                except OSError:
                    time.sleep(0.05)
            lat = []
            lat_mu = threading.Lock()
            idx = {"i": 0}

            def worker():
                while True:
                    with lat_mu:
                        i = idx["i"]
                        if i >= requests:
                            return
                        idx["i"] += 1
                    t0 = time.perf_counter()
                    post("/v1/decode", {"src": [i + 1, i * 13 + 5],
                                        "max_new": max_new})
                    dt = time.perf_counter() - t0
                    with lat_mu:
                        lat.append(dt)

            t0 = time.perf_counter()
            ts = [threading.Thread(target=worker)
                  for _ in range(concurrency)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            wall = time.perf_counter() - t0
            metrics = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) \
                .read().decode()

            def m(name, default=0.0):
                for ln in metrics.splitlines():
                    if ln.startswith(name + " "):
                        return float(ln.split()[-1])
                return default

            ticks = m("paddle_serving_decode_ticks_total")
            live = m("paddle_serving_decode_slot_live_ticks_total")
            lat.sort()
            return {
                "requests_per_sec": round(requests / wall, 1),
                "p95_latency_ms": round(
                    lat[int(len(lat) * 0.95) - 1] * 1e3, 2),
                "mean_latency_ms": round(sum(lat) / len(lat) * 1e3, 2),
                "mean_slot_occupancy": round(
                    live / max(ticks * slots, 1.0), 3),
                "ticks": int(ticks),
                "inflight_admissions": int(
                    m("paddle_serving_admitted_inflight_total")),
            }
        finally:
            proc.send_signal(signal.SIGKILL)
            proc.wait()

    drain = run_mode(drain=True)
    cont = run_mode(drain=False)
    speedup = round(cont["requests_per_sec"]
                    / max(drain["requests_per_sec"], 1e-9), 2)
    real = bench_serving_real_decode(quick=quick)
    return {"metric": "serving_requests_per_sec",
            "value": cont["requests_per_sec"], "unit": "requests/sec",
            "slots": slots, "concurrency": concurrency,
            "requests": requests, "tick_us": tick_us, "max_new": max_new,
            "extra": {"continuous": cont, "drain": drain,
                      "continuous_vs_drain_speedup": speedup,
                      "real_decode": real,
                      "cpu_note": "toy backend: fixed per-tick cost "
                                  "(matmul + tick_us); scheduler-only "
                                  "A/B. real_decode columns run the "
                                  "REAL NMT decode through the r19 "
                                  "per-tick step export — PJRT-backed "
                                  "silicon re-measure in ROADMAP"}}


def bench_serving_real_decode(quick=False, slots=None, requests=None,
                              max_length=None):
    """Real-decode continuous-vs-drain A/B (ISSUE 14): the NMT
    generation model's PER-TICK step export (io/merged_model
    export_decode_step_stablehlo_ex) driven through the daemon's slot
    scheduler semantics — mid-decode slot admission vs drain-batch —
    by paddle_tpu.step_decode.StepDecodeDriver. On this plugin-less
    container the exported modules execute through jax.export's CPU
    path (the 'interp' backend column); on a PJRT host the daemon's
    StepBundleBackend runs the SAME modules and scheduler natively
    (the v5e re-measure). The eos logit is nudged so decode lengths
    vary (geometric-ish), which is exactly the load shape where
    continuous batching wins: drain wastes (max_len_in_batch - len_i)
    ticks per member, continuous refills the slot mid-decode.

    Columns: requests/sec, p50/p95 completion latency, p50 TTFT (the
    streaming surface's time-to-first-token), mid-batch admission
    fraction, mean ticks."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.parameters import Parameters
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.io.merged_model import export_decode_step_stablehlo_ex
    from paddle_tpu.models.text import nmt_decode_topology
    from paddle_tpu.step_decode import StepDecodeDriver

    slots = slots or (4 if quick else 8)
    requests = requests or (16 if quick else 64)
    max_length = max_length or (12 if quick else 24)
    V, K, T, beam = 120, 16, 5, 2
    gen = nmt_decode_topology(src_dict_dim=V, trg_dict_dim=V,
                              word_vector_dim=8, encoder_size=8,
                              decoder_size=8, beam_size=beam,
                              max_length=max_length, cand_k=K,
                              mode="compact", name="m")
    topo = Topology(gen)
    params = topo.init_params(jax.random.PRNGKey(0))
    b = np.array(params["_m_out.wbias"])
    b[..., 1] += 0.25               # varied decode lengths (see above)
    params["_m_out.wbias"] = jnp.asarray(b)
    P = Parameters.from_dict({k: np.asarray(v) for k, v in params.items()})
    res, reason = export_decode_step_stablehlo_ex(topo, P, seq_len=T,
                                                  slots=slots)
    if res is None:
        return {"error": f"step export unavailable: {reason}"}

    rng = np.random.RandomState(7)
    reqs = []
    for _ in range(requests):
        src = rng.randint(0, V, (T,)).astype(np.int32)
        cand = rng.choice(V, K, replace=False).astype(np.int32)
        if not (cand == 1).any():
            cand[0] = 1
        reqs.append({"src": src, "src:mask": np.ones(T, np.float32),
                     "cand": cand.astype(np.float32)})

    def run_mode(drain):
        drv = StepDecodeDriver(res, drain=drain)
        t0 = time.perf_counter()
        handles = [drv.submit(f) for f in reqs]
        drv.run()
        wall = time.perf_counter() - t0
        lat = sorted(h.done_time - h.submit_time for h in handles)
        ttft = sorted(h.first_token_time - h.submit_time
                      for h in handles)
        lead = sorted(h.done_time - h.first_token_time for h in handles)
        n = len(handles)
        total_adm = max(sum(drv.admissions.values()), 1)
        return {
            "requests_per_sec": round(n / wall, 2),
            "p50_latency_ms": round(lat[n // 2] * 1e3, 2),
            "p95_latency_ms": round(lat[int(n * 0.95) - 1] * 1e3, 2),
            "p50_ttft_ms": round(ttft[n // 2] * 1e3, 2),
            # what streaming buys the client: the answer's first token
            # lands this long before the full decode completes
            "p50_stream_lead_ms": round(lead[n // 2] * 1e3, 2),
            "mid_batch_admission_fraction": round(
                drv.admissions["mid_batch"] / total_adm, 3),
            "mid_batch_admissions": drv.admissions["mid_batch"],
            "scheduler_ticks": drv.tick_count,
            "mean_ticks_per_request": round(
                sum(h.ticks for h in handles) / n, 2),
        }

    drain = run_mode(drain=True)
    cont = run_mode(drain=False)
    return {
        "backend": "interp (jax.export CPU path; StepBundleBackend "
                   "runs the same modules on a PJRT host)",
        "model": f"NMT compact-K decode V={V} K={K} beam={beam} "
                 f"max_length={max_length}",
        "slots": slots, "requests": requests,
        "continuous": cont, "drain": drain,
        "continuous_vs_drain_speedup": round(
            cont["requests_per_sec"]
            / max(drain["requests_per_sec"], 1e-9), 2),
        # the streaming acceptance bar: first token lands well before
        # the full decode completes under load
        "ttft_vs_full_decode_p50": round(
            cont["p50_ttft_ms"] / max(cont["p50_latency_ms"], 1e-9), 3),
        "cpu_note": "tick latency here is jax.export call dispatch on "
                    "CPU; the scheduler win (occupancy) is the "
                    "hardware-independent signal — silicon re-measure "
                    "via the daemon's pjrt step backend (ROADMAP)",
    }


def bench_serving_quantized(quick=False, concurrency=None, requests=None,
                            vocab=None, emb_dim=None, hidden=None):
    """Quantized-bundle serving A/B (`--model serving --quantize`;
    ISSUE 16): the SAME embedding+fc model merged at f32, bf16 and int8,
    each bundle served by the C++ daemon's interp backend under
    saturating /v1/infer load. Columns per precision: bundle bytes,
    parameter bytes by dtype (the /v1/signature accounting), requests/
    sec, and max |output - f32 python forward| over the driven batch
    (the golden-tolerance column). On this CPU container requests/sec
    mostly prices the daemon's scalar interp loops — the byte cut is the
    hardware-independent signal; the v5e re-measure rides ROADMAP."""
    import signal
    import subprocess
    import tempfile
    import threading
    import urllib.request

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import quant
    from paddle_tpu.core.arg import Arg
    from paddle_tpu.core.parameters import Parameters
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.io.merged_model import (export_forward_stablehlo_ex,
                                            stablehlo_meta, write_bundle)

    native = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "paddle_tpu", "native")
    daemon = os.path.join(native, "paddle_tpu_serving")
    r = subprocess.run(["make", "-C", native, "serving"],
                       capture_output=True)
    if r.returncode != 0 or not os.path.exists(daemon):
        raise RuntimeError("serving daemon build unavailable "
                           "(make -C paddle_tpu/native serving)")
    concurrency = concurrency or (4 if quick else 8)
    requests = requests or (40 if quick else 400)
    vocab = vocab or (64 if quick else 2000)
    emb_dim = emb_dim or (16 if quick else 64)
    hidden = hidden or (32 if quick else 256)
    T, B = 6, 4

    paddle.init(use_gpu=False)
    from paddle_tpu import activation, data_type, layer, pooling
    ids = layer.data(name="ids",
                     type=data_type.integer_value_sequence(vocab))
    den = layer.data(name="den", type=data_type.dense_vector(8))
    emb = layer.embedding(input=ids, size=emb_dim)
    pooled = layer.pooling(input=emb, pooling_type=pooling.Avg())
    h = layer.fc(input=[pooled, den], size=hidden,
                 act=activation.Relu())
    out = layer.fc(input=h, size=16, act=activation.Softmax(),
                   name="out")
    topo = Topology([out])
    params = paddle.parameters_create(topo)
    pdict = {k: params.get(k) for k in params.names()}

    rng = np.random.RandomState(0)
    iv = rng.randint(0, vocab, (B, T)).astype(np.int32)
    mk = np.ones((B, T), np.float32)
    dv = rng.rand(B, 8).astype(np.float32)
    golden = np.asarray(topo.forward(
        {k: jnp.asarray(v) for k, v in pdict.items()},
        {"ids": Arg(jnp.asarray(iv), jnp.asarray(mk)),
         "den": Arg(jnp.asarray(dv))})["out"].value)
    body = json.dumps({"inputs": {"ids": iv.tolist(),
                                  "ids:mask": mk.tolist(),
                                  "den": dv.tolist()}}).encode()

    tmp = tempfile.mkdtemp(prefix="ptpu_qbench_")
    columns = {}
    for mode in ("f32", "bf16", "int8"):
        if mode == "f32":
            P, meta_extra, qmeta = params, {}, None
        else:
            qd, qmeta = quant.quantize_params(topo, pdict, mode)
            P = Parameters.from_dict(qd)
            meta_extra = {"quantize": qmeta}
        shlo, reason = export_forward_stablehlo_ex(topo, P, seq_len=T,
                                                   qmeta=qmeta)
        meta = dict(meta_extra)
        if shlo is not None:
            meta["stablehlo"] = stablehlo_meta(shlo)
        path = os.path.join(tmp, f"bundle_{mode}.ptpu")
        with open(path, "wb") as f:
            write_bundle(f, topo, P, meta=meta)
        bundle_bytes = os.path.getsize(path)

        proc = subprocess.Popen(
            [daemon, "--bundle", path, "--port", "0",
             "--backend", "interp", "--threads", str(concurrency + 2)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        try:
            line = proc.stdout.readline()
            port = int(line.split("port")[1].split()[0])

            def get(path_):
                return urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path_}", timeout=30) \
                    .read().decode()

            deadline = time.time() + 10
            while time.time() < deadline:
                try:
                    get("/healthz")
                    break
                except OSError:
                    time.sleep(0.05)

            def post_infer():
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/infer", data=body)
                with urllib.request.urlopen(req, timeout=60) as resp:
                    return json.loads(resp.read())

            first = post_infer()        # warm + golden compare
            got = np.array(first["outputs"]["out"]["data"],
                           np.float32).reshape(golden.shape)
            max_err = float(np.max(np.abs(got - golden)))

            idx = {"i": 0}
            mu = threading.Lock()

            def worker():
                while True:
                    with mu:
                        if idx["i"] >= requests:
                            return
                        idx["i"] += 1
                    post_infer()

            t0 = time.perf_counter()
            ts = [threading.Thread(target=worker)
                  for _ in range(concurrency)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            wall = time.perf_counter() - t0
            sig = json.loads(get("/v1/signature"))
            columns[mode] = {
                "bundle_bytes": bundle_bytes,
                "param_bytes": sig.get("param_bytes"),
                "requests_per_sec": round(requests / wall, 1),
                "max_abs_err_vs_f32": round(max_err, 6),
            }
        finally:
            proc.send_signal(signal.SIGKILL)
            proc.wait()

    f32b = columns["f32"]["bundle_bytes"]
    return {
        "metric": "serving_quantized_requests_per_sec",
        "value": columns["int8"]["requests_per_sec"],
        "unit": "requests/sec",
        "requests": requests, "concurrency": concurrency,
        "model": f"embedding(V={vocab},D={emb_dim})+fc({hidden}) "
                 f"interp backend",
        "extra": {
            **columns,
            "bundle_bytes_cut": {
                m: round(f32b / max(columns[m]["bundle_bytes"], 1), 2)
                for m in ("bf16", "int8")},
            "cpu_note": "interp backend on CPU: requests/sec prices the "
                        "daemon's scalar loops, so the byte cut "
                        "(~2x bf16 / ~4x int8 on params) is the "
                        "hardware-independent signal; PJRT/v5e "
                        "re-measure rides ROADMAP",
        }}


def bench_serving_batch(quick=False, concurrency=None, requests=None,
                        window_ms=None):
    """Infer micro-batching A/B (`--model serving --batch`; ISSUE 18,
    docs/serving.md "Infer micro-batching"): the SAME saturating
    single-row /v1/infer load driven through the C++ daemon's interp
    backend twice — per-request execution (no --batch_window_ms) vs the
    deadline-aware gather window coalescing concurrent rows into ONE
    batched execute (--batch_max pinned to the client concurrency, so a
    saturated window closes on the row budget instead of idling to the
    window bound). Both modes run under --infer_exec_us — a fixed
    SERIALIZED per-execute cost, the infer twin of the scheduler A/B's
    --toy_tick_us: one device, one dispatch queue, the same price
    whether the execute carries 1 row or a whole window — so the
    columns isolate the BATCHER (per-request execution pays the
    dispatch N times, a gathered window once). Columns per mode:
    requests/sec, p50/p95 latency; the batched column adds batches
    executed and the mean gathered rows per execute
    (paddle_serving_batch_size sum/count). Acceptance: req/s up AND
    p95_batched <= p95_solo + batch_window_ms — the window never
    spends more latency than its bound. On this CPU container the
    interp loops price row compute on the host either way; the
    dispatch model is the hardware-independent signal (v5e re-measure
    rides ROADMAP)."""
    import shutil
    import signal
    import subprocess
    import tempfile
    import threading
    import urllib.request

    import paddle_tpu as paddle
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.io.merged_model import write_bundle

    native = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "paddle_tpu", "native")
    daemon = os.path.join(native, "paddle_tpu_serving")
    r = subprocess.run(["make", "-C", native, "serving"],
                       capture_output=True)
    if r.returncode != 0 or not os.path.exists(daemon):
        raise RuntimeError("serving daemon build unavailable "
                           "(make -C paddle_tpu/native serving)")
    concurrency = concurrency or (6 if quick else 12)
    requests = requests or (120 if quick else 600)
    window_ms = window_ms or (8 if quick else 10)
    exec_us = 2000
    vocab, emb_dim, hidden, T = (64, 16, 32, 6) if quick \
        else (2000, 64, 256, 6)

    paddle.init(use_gpu=False)
    from paddle_tpu import activation, data_type, layer, pooling
    ids = layer.data(name="ids",
                     type=data_type.integer_value_sequence(vocab))
    den = layer.data(name="den", type=data_type.dense_vector(8))
    emb = layer.embedding(input=ids, size=emb_dim)
    pooled = layer.pooling(input=emb, pooling_type=pooling.Avg())
    h = layer.fc(input=[pooled, den], size=hidden,
                 act=activation.Relu())
    out = layer.fc(input=h, size=16, act=activation.Softmax(),
                   name="out")
    topo = Topology([out])
    params = paddle.parameters_create(topo)

    rng = np.random.RandomState(0)
    body = json.dumps({"inputs": {
        "ids": rng.randint(0, vocab, (1, T)).tolist(),
        "ids:mask": np.ones((1, T), np.float32).tolist(),
        "den": rng.rand(1, 8).tolist()}}).encode()

    tmp = tempfile.mkdtemp(prefix="ptpu_bbench_")
    path = os.path.join(tmp, "bundle.ptpu")
    with open(path, "wb") as f:
        write_bundle(f, topo, params)

    def metric(text, name):
        for ln in text.splitlines():
            if ln.startswith(name + " ") or ln.startswith(name + "{"):
                return float(ln.split()[-1])
        return None

    def run_mode(batched):
        flags = [daemon, "--bundle", path, "--port", "0",
                 "--backend", "interp",
                 "--infer_exec_us", str(exec_us),
                 "--threads", str(concurrency + 2)]
        if batched:
            flags += ["--batch_window_ms", str(window_ms),
                      "--batch_max", str(concurrency)]
        proc = subprocess.Popen(flags, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        try:
            line = proc.stdout.readline()
            port = int(line.split("port")[1].split()[0])

            def get(path_):
                return urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path_}", timeout=30) \
                    .read().decode()

            deadline = time.time() + 10
            while time.time() < deadline:
                try:
                    get("/healthz")
                    break
                except OSError:
                    time.sleep(0.05)

            def post_infer():
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/infer", data=body)
                with urllib.request.urlopen(req, timeout=60) as resp:
                    return json.loads(resp.read())

            post_infer()                       # warm
            idx = {"i": 0}
            lats = []
            mu = threading.Lock()

            def worker():
                while True:
                    with mu:
                        if idx["i"] >= requests:
                            return
                        idx["i"] += 1
                    t0 = time.perf_counter()
                    post_infer()
                    dt = time.perf_counter() - t0
                    with mu:
                        lats.append(dt)

            t0 = time.perf_counter()
            ts = [threading.Thread(target=worker)
                  for _ in range(concurrency)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            wall = time.perf_counter() - t0
            lats.sort()
            cols = {
                "requests_per_sec": round(requests / wall, 1),
                "p50_ms": round(lats[len(lats) // 2] * 1000, 2),
                "p95_ms": round(lats[int(len(lats) * 0.95)] * 1000, 2),
            }
            if batched:
                mtext = get("/metrics")
                batches = metric(mtext, "paddle_serving_batches_total")
                bsum = metric(mtext, "paddle_serving_batch_size_sum")
                bcnt = metric(mtext, "paddle_serving_batch_size_count")
                cols["batches"] = int(batches or 0)
                cols["mean_batch_rows"] = \
                    round(bsum / bcnt, 2) if bcnt else None
            return cols
        finally:
            proc.send_signal(signal.SIGKILL)
            proc.wait()

    solo = run_mode(False)
    batched = run_mode(True)
    shutil.rmtree(tmp, ignore_errors=True)
    return {
        "metric": "serving_batched_requests_per_sec",
        "value": batched["requests_per_sec"],
        "unit": "requests/sec",
        "requests": requests, "concurrency": concurrency,
        "batch_window_ms": window_ms, "infer_exec_us": exec_us,
        "model": f"embedding(V={vocab},D={emb_dim})+fc({hidden}) "
                 f"interp backend, single-row clients, "
                 f"{exec_us}us serialized dispatch",
        "extra": {
            "per_request": solo, "batched": batched,
            "throughput_gain":
                round(batched["requests_per_sec"]
                      / max(solo["requests_per_sec"], 1e-9), 2),
            "p95_budget_ok":
                batched["p95_ms"] <= solo["p95_ms"] + window_ms,
            "cpu_note": "--infer_exec_us models the serialized device "
                        "dispatch a ladder rung prices once per "
                        "window on real hardware; raw CPU interp "
                        "prices compute per row, so without it the "
                        "gather machinery is pure overhead here (v5e "
                        "re-measure rides ROADMAP)",
        }}


def bench_serving_host_table(quick=False, concurrency=None,
                             requests=None):
    """Host row store serving A/B (`--model serving --host_table`;
    ISSUE 19, docs/serving.md "Host-backed tables"): the SAME
    saturating /v1/infer load against three bundles of the SAME model —
    ``dense`` (the table resident as an ordinary parameter, the pre-r23
    form), ``host`` (matched vocab, but the table lives ONLY as a
    ``__hostrows__/`` row sidecar and every request stages its touched
    rows through the bounded LRU), and ``host_big`` (the 100M-row
    vocab no dense bundle could even hold: ~3 TiB at f32 — the row
    sidecar carries just the trained rows). Columns: requests/sec,
    p50/p95 latency, staged rows/request and resident bytes
    (paddle_serving_rowstore_*). The matched-vocab pair prices the
    staging machinery; the host_big column is the existence proof that
    the price buys unbounded vocab inside a fixed footprint."""
    import shutil
    import signal
    import subprocess
    import tempfile
    import threading
    import urllib.request

    import paddle_tpu as paddle
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.host_table import HostRowStore
    from paddle_tpu.io.merged_model import write_bundle

    native = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "paddle_tpu", "native")
    daemon = os.path.join(native, "paddle_tpu_serving")
    r = subprocess.run(["make", "-C", native, "serving"],
                       capture_output=True)
    if r.returncode != 0 or not os.path.exists(daemon):
        raise RuntimeError("serving daemon build unavailable "
                           "(make -C paddle_tpu/native serving)")
    concurrency = concurrency or (4 if quick else 8)
    requests = requests or (80 if quick else 400)
    vocab, emb_dim, T = (200, 8, 4) if quick else (2000, 32, 6)
    big_vocab = 100_000_000
    cache_rows = 64 if quick else 512

    paddle.init(use_gpu=False)
    from paddle_tpu import activation, data_type, layer, optimizer, \
        pooling

    def build(v, host):
        ids = layer.data(name="ids",
                         type=data_type.integer_value_sequence(v))
        den = layer.data(name="den", type=data_type.dense_vector(8))
        attr = paddle.attr.ParamAttr(name="_hemb", host_resident=host)
        emb = layer.embedding(input=ids, size=emb_dim, param_attr=attr)
        pooled = layer.pooling(input=emb, pooling_type=pooling.Avg())
        out = layer.fc(input=[pooled, den], size=16,
                       act=activation.Softmax(), name="out")
        topo = Topology([out])
        return topo, paddle.parameters_create(topo)

    rng = np.random.RandomState(0)
    table = (rng.randn(vocab, emb_dim) * 0.1).astype(np.float32)
    tmp = tempfile.mkdtemp(prefix="ptpu_hostbench_")

    topo_d, params_d = build(vocab, host=False)
    params_d["_hemb"] = table
    dense_path = os.path.join(tmp, "dense.ptpu")
    with open(dense_path, "wb") as f:
        write_bundle(f, topo_d, params_d, version=1)

    def host_bundle(v, name):
        topo_h, params_h = build(v, host=True)
        for n in params_h.names():
            params_h[n] = params_d[n]
        store = HostRowStore("_hemb", (v, emb_dim),
                             optimizer.SGD(learning_rate=0.1))
        for i in range(vocab):
            store._rows[i] = table[i].copy()
        p = os.path.join(tmp, name)
        with open(p, "wb") as f:
            write_bundle(f, topo_h, params_h, version=1,
                         host_tables={"_hemb": store})
        return p

    host_path = host_bundle(vocab, "host.ptpu")
    big_path = host_bundle(big_vocab, "host_big.ptpu")

    bodies = []
    for _ in range(32):
        bodies.append(json.dumps({"inputs": {
            "ids": rng.randint(0, vocab, (1, T)).tolist(),
            "ids:mask": np.ones((1, T), np.float32).tolist(),
            "den": rng.rand(1, 8).tolist()}}).encode())

    def metric(text, name):
        for ln in text.splitlines():
            if ln.startswith(name + " ") or ln.startswith(name + "{"):
                return float(ln.split()[-1])
        return None

    def run_column(path):
        proc = subprocess.Popen(
            [daemon, "--bundle", path, "--port", "0",
             "--backend", "interp",
             "--host_cache_rows", str(cache_rows),
             "--threads", str(concurrency + 2)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        try:
            for _ in range(32):
                line = proc.stdout.readline()
                if "paddle_tpu_serving on port" in line:
                    break
            port = int(line.split("port")[1].split()[0])

            def get(p):
                return urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{p}", timeout=30) \
                    .read().decode()

            deadline = time.time() + 10
            while time.time() < deadline:
                try:
                    get("/healthz")
                    break
                except OSError:
                    time.sleep(0.05)

            def post_infer(i):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/infer",
                    data=bodies[i % len(bodies)])
                with urllib.request.urlopen(req, timeout=60) as resp:
                    return json.loads(resp.read())

            post_infer(0)                      # warm
            idx = {"i": 0}
            lats = []
            mu = threading.Lock()

            def worker():
                while True:
                    with mu:
                        if idx["i"] >= requests:
                            return
                        i = idx["i"]
                        idx["i"] += 1
                    t0 = time.perf_counter()
                    post_infer(i)
                    dt = time.perf_counter() - t0
                    with mu:
                        lats.append(dt)

            t0 = time.perf_counter()
            ts = [threading.Thread(target=worker)
                  for _ in range(concurrency)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            wall = time.perf_counter() - t0
            lats.sort()
            cols = {
                "requests_per_sec": round(requests / wall, 1),
                "p50_ms": round(lats[len(lats) // 2] * 1000, 2),
                "p95_ms": round(lats[int(len(lats) * 0.95)] * 1000, 2),
            }
            mtext = get("/metrics")
            ssum = metric(mtext,
                          "paddle_serving_rowstore_staged_rows_sum")
            scnt = metric(mtext,
                          "paddle_serving_rowstore_staged_rows_count")
            resident = metric(mtext,
                              "paddle_serving_rowstore_resident_bytes")
            if scnt:
                cols["staged_rows_per_request"] = round(ssum / scnt, 2)
            if resident is not None:
                cols["resident_bytes"] = int(resident)
                cols["resident_bound_ok"] = \
                    resident <= cache_rows * emb_dim * 4
            return cols
        finally:
            proc.send_signal(signal.SIGKILL)
            proc.wait()

    dense = run_column(dense_path)
    host = run_column(host_path)
    host_big = run_column(big_path)
    bundle_bytes = {"dense": os.path.getsize(dense_path),
                    "host": os.path.getsize(host_path),
                    "host_big": os.path.getsize(big_path)}
    shutil.rmtree(tmp, ignore_errors=True)
    return {
        "metric": "serving_host_table_requests_per_sec",
        "value": host_big["requests_per_sec"],
        "unit": "requests/sec",
        "requests": requests, "concurrency": concurrency,
        "host_cache_rows": cache_rows,
        "model": f"embedding(V={vocab} dense / V={big_vocab} host)"
                 f"+fc, interp backend, single-row clients",
        "extra": {
            "dense_resident": dense, "host_staged": host,
            "host_big_100m": host_big,
            "bundle_bytes": bundle_bytes,
            "staging_cost":
                round(dense["requests_per_sec"]
                      / max(host["requests_per_sec"], 1e-9), 3),
            "note": "dense vs host at matched vocab prices the staging "
                    "gather; host_big serves a vocab whose dense table "
                    "would be ~3 TiB f32 — the sidecar carries only "
                    "trained rows and the LRU bounds residency",
        }}


def bench_serving_fleet(quick=False, slots=None, tick_us=None,
                        concurrency=None, requests=None, max_new=None):
    """Fleet scaling A/B (`--model serving --fleet`; ISSUE 17,
    docs/serving.md "Running a fleet"): the SAME saturating decode load
    driven through tools/serving_router.py at 1, 2, and 4 registered
    replicas (2 under --quick). Each replica is a real toy-backend
    daemon launched and registered by ServingFleet; clients see ONE
    router endpoint. Columns: aggregate requests/sec, p95 latency,
    per-replica completed-request share and slot occupancy (from each
    replica's own /metrics), and scaling efficiency
    rps(N) / (N * rps(1))."""
    import signal  # noqa: F401  (symmetry with bench_serving)
    import subprocess
    import tempfile
    import threading
    import urllib.request

    from paddle_tpu.distributed.discovery import DiscoveryRegistry
    from paddle_tpu.serving_fleet import ServingFleet
    from paddle_tpu.serving_router import Router

    native = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "paddle_tpu", "native")
    daemon = os.path.join(native, "paddle_tpu_serving")
    r = subprocess.run(["make", "-C", native, "serving"],
                       capture_output=True)
    if r.returncode != 0 or not os.path.exists(daemon):
        raise RuntimeError("serving daemon build unavailable "
                           "(make -C paddle_tpu/native serving)")
    slots = slots or (2 if quick else 4)
    tick_us = tick_us or (500 if quick else 2000)
    concurrency = concurrency or (8 if quick else 32)
    requests = requests or (48 if quick else 240)
    max_new = max_new or (16 if quick else 32)
    sizes = (1, 2) if quick else (1, 2, 4)

    def scrape(url):
        metrics = urllib.request.urlopen(url + "/metrics", timeout=10) \
            .read().decode()

        def m(name, default=0.0):
            for ln in metrics.splitlines():
                if ln.startswith(name + " "):
                    return float(ln.split()[-1])
            return default

        ticks = m("paddle_serving_decode_ticks_total")
        return {"completed": int(m("paddle_serving_decode_completed_total")),
                "slot_occupancy": round(
                    m("paddle_serving_decode_slot_live_ticks_total")
                    / max(ticks * slots, 1.0), 3)}

    def run_n(n):
        with tempfile.TemporaryDirectory() as td:
            reg = DiscoveryRegistry(os.path.join(td, "registry"), ttl=10.0)
            fleet = ServingFleet(
                reg, model="bench", workdir=os.path.join(td, "fleet"),
                daemon_flags=("--backend", "toy",
                              "--slots", str(slots),
                              "--toy_tick_us", str(tick_us),
                              "--threads", str(concurrency + 4),
                              "--max_queue", str(requests + concurrency),
                              "--max_new_cap", str(max_new)),
                probe_interval=0.1)
            router = None
            try:
                fleet.launch(n)
                router = Router(reg, model="bench",
                                max_slots=fleet.max_slots,
                                default_deadline_ms=300000.0)
                base = f"http://127.0.0.1:{router.start()}"
                deadline = time.time() + 15
                while time.time() < deadline \
                        and len(router.state.urls()) < n:
                    time.sleep(0.05)
                if len(router.state.urls()) < n:
                    raise RuntimeError(
                        f"only {len(router.state.urls())}/{n} replicas "
                        "registered")

                def post(path, obj):
                    req = urllib.request.Request(
                        base + path, data=json.dumps(obj).encode())
                    with urllib.request.urlopen(req, timeout=300) as resp:
                        return json.loads(resp.read())

                lat = []
                lat_mu = threading.Lock()
                idx = {"i": 0}

                def worker():
                    while True:
                        with lat_mu:
                            i = idx["i"]
                            if i >= requests:
                                return
                            idx["i"] += 1
                        t0 = time.perf_counter()
                        post("/v1/decode", {"src": [i + 1, i * 13 + 5],
                                            "max_new": max_new})
                        dt = time.perf_counter() - t0
                        with lat_mu:
                            lat.append(dt)

                t0 = time.perf_counter()
                ts = [threading.Thread(target=worker)
                      for _ in range(concurrency)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                wall = time.perf_counter() - t0
                if len(lat) < requests:
                    raise RuntimeError(
                        f"dropped {requests - len(lat)} requests")
                per_replica = {f"slot{s}": scrape(url)
                               for s, url in fleet.registered()}
                lat.sort()
                return {
                    "replicas": n,
                    "requests_per_sec": round(requests / wall, 1),
                    "p95_latency_ms": round(
                        lat[int(len(lat) * 0.95) - 1] * 1e3, 2),
                    "mean_latency_ms": round(
                        sum(lat) / len(lat) * 1e3, 2),
                    "per_replica": per_replica,
                }
            finally:
                if router is not None:
                    router.stop()
                fleet.stop()
                reg.stop_all()

    results = {}
    for n in sizes:
        results[f"replicas_{n}"] = run_n(n)
    base_rps = results["replicas_1"]["requests_per_sec"]
    for n in sizes:
        r = results[f"replicas_{n}"]
        r["scaling_efficiency"] = round(
            r["requests_per_sec"] / max(n * base_rps, 1e-9), 2)
    top = results[f"replicas_{sizes[-1]}"]
    return {"metric": "serving_fleet_requests_per_sec",
            "value": top["requests_per_sec"], "unit": "requests/sec",
            "slots_per_replica": slots, "concurrency": concurrency,
            "requests": requests, "tick_us": tick_us, "max_new": max_new,
            "extra": {**results,
                      "cpu_note": "all replicas share one CPU container "
                                  "and the toy tick burns real matmul "
                                  "time, so scaling efficiency here is a "
                                  "LOWER bound — per-host replicas on "
                                  "v5e re-measure in ROADMAP"}}


BENCHES = {"resnet50": bench_resnet50, "smallnet": bench_smallnet,
           "lstm": bench_lstm, "alexnet": bench_alexnet,
           "googlenet": bench_googlenet, "vgg": bench_vgg,
           "nmt": bench_nmt, "nmt_decode": bench_nmt_decode_all,
           "pipeline": bench_pipeline, "nmt_packed": bench_nmt_packed,
           "ctr": bench_ctr, "multislice": bench_multislice,
           "serving": bench_serving}


def _force_virtual_devices(n=8):
    """Force the n-virtual-device host platform BEFORE the jax backend
    initializes (same trick as tools/pp_accounting.py and
    tools/zero_accounting.py; a no-op for real TPU backends)."""
    import os
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None, choices=sorted(BENCHES),
                    help="bench one model; default runs both north-star "
                         "metrics (ResNet-50 + NMT) and prints a combined "
                         "final line")
    ap.add_argument("--batch", type=int, default=None, nargs="?",
                    const=-1,
                    help="training benches: batch size override. "
                         "--model serving: run the infer micro-batching "
                         "A/B instead of the scheduler A/B — "
                         "per-request vs gather-window execution "
                         "(ISSUE 18); an optional value sets "
                         "--batch_window_ms")
    ap.add_argument("--pipeline_depth", type=int, default=None,
                    help="pipelined-loop depth for --model pipeline "
                         "(default 2); the sync depth-0 column is always "
                         "measured alongside")
    ap.add_argument("--pipeline_trainer", default=None,
                    choices=["sgd", "dp", "pp"],
                    help="--model pipeline: plain SGD (default), the "
                         "DataParallelTrainer over the device mesh, or "
                         "the PipelineParallelTrainer (pp: naive-vs-"
                         "balanced stage assignment x sync-vs-host-"
                         "overlapped columns on a 4-stage mesh)")
    ap.add_argument("--host_cache_rows", type=int, default=None,
                    help="ctr model: forced-small device row cache size "
                         "(default 8192 — the BENCH_EXTRA_r12 protocol)")
    ap.add_argument("--quantize", action="store_true",
                    help="--model serving: quantized-bundle A/B instead "
                         "of the scheduler A/B — f32 vs bf16 vs int8 "
                         "requests/sec + bundle bytes through the "
                         "daemon's interp backend (ISSUE 16)")
    ap.add_argument("--fleet", action="store_true",
                    help="--model serving: fleet scaling A/B instead of "
                         "the scheduler A/B — aggregate requests/sec at "
                         "1/2/4 replicas behind tools/serving_router.py "
                         "with per-replica occupancy and scaling "
                         "efficiency (ISSUE 17)")
    ap.add_argument("--host_table", action="store_true",
                    help="--model serving: host row store A/B instead "
                         "of the scheduler A/B — dense-resident vs "
                         "host-staged at matched vocab plus a 100M-row "
                         "host_big column (requests/sec, p95, staged "
                         "rows/request, resident bytes; ISSUE 19)")
    ap.add_argument("--quick", action="store_true",
                    help="--model nmt_packed|ctr|pipeline|multislice|"
                         "serving: tiny smoke-sized run (the tier-1 CI "
                         "configuration)")
    args = ap.parse_args()
    kw = {}
    if args.batch:
        if args.model == "serving":
            kw["batch"] = True
            if args.batch > 0:
                kw["window_ms"] = args.batch
        else:
            kw["batch"] = args.batch
    if args.model == "pipeline":
        if args.pipeline_depth is not None:
            kw["pipeline_depth"] = args.pipeline_depth
        if args.pipeline_trainer:
            kw["trainer"] = args.pipeline_trainer
        if args.pipeline_trainer == "pp":
            # the pp columns need a 4-device stage axis; on a CPU run
            # force the 8-virtual-device host platform BEFORE the jax
            # backend initializes (same trick as tools/pp_accounting.py;
            # a no-op for real TPU backends)
            import os
            if "xla_force_host_platform_device_count" not in \
                    os.environ.get("XLA_FLAGS", ""):
                os.environ["XLA_FLAGS"] = (
                    os.environ.get("XLA_FLAGS", "")
                    + " --xla_force_host_platform_device_count=8")
    if args.model == "ctr" and args.host_cache_rows is not None:
        kw["cache_rows"] = args.host_cache_rows
    if args.model == "multislice":
        # the 2x4 slice x data mesh needs 8 devices; force the virtual
        # host platform before the backend initializes (no-op on TPU)
        import os
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8")
    if args.model in ("nmt_packed", "ctr", "pipeline",
                      "multislice", "serving") and args.quick:
        kw["quick"] = True
    if args.model == "serving" and args.quantize:
        kw["quantize"] = True
    if args.model == "serving" and args.fleet:
        kw["fleet"] = True
    if args.model == "serving" and args.host_table:
        kw["host_table"] = True
    obs_metrics.default_registry.delta()       # open the delta window
    if args.model:
        result = BENCHES[args.model](**kw)
        _attach_metrics_extra(result, obs_metrics.default_registry.delta())
        print(json.dumps(result))
        return
    # Bare run = the driver's protocol: both BASELINE.json north-star
    # metrics. Individual lines first (human record), then ONE combined
    # final JSON line — the driver records the tail.
    resnet = bench_resnet50(**kw)
    print(json.dumps(resnet), flush=True)
    try:
        nmt = bench_nmt()
        print(json.dumps(nmt), flush=True)
    except Exception as e:  # ResNet headline must survive an NMT failure
        nmt = {"error": f"{type(e).__name__}: {e}"}
    decode = {}
    for b in (1, 4):  # per-beam try: a beam-4 failure must not discard
        try:          # the already-measured beam-1 result
            decode[f"beam{b}"] = d = bench_nmt_decode(beam=b)
            print(json.dumps(d), flush=True)
        except Exception as e:  # nor sink the headline
            decode[f"beam{b}"] = {"error": f"{type(e).__name__}: {e}"}
    combined = dict(resnet)
    combined["extra"] = {**resnet.get("extra", {}),
                         "nmt_attention_train_tokens_per_sec_per_chip":
                         nmt.get("value", nmt.get("error")),
                         "nmt_band": nmt.get("band"),
                         "nmt_vs_baseline": nmt.get("vs_baseline"),
                         "nmt_mfu": nmt.get("extra", {}).get("mfu"),
                         "nmt_decode_tokens_per_sec_per_chip":
                         {b: d.get("value", d) if isinstance(d, dict) else d
                          for b, d in decode.items()},
                         "nmt_decode_band":
                         {b: d.get("band") for b, d in decode.items()
                          if isinstance(d, dict)}}
    _attach_metrics_extra(combined, obs_metrics.default_registry.delta())
    print(json.dumps(combined))


if __name__ == "__main__":
    import paddle_tpu

    paddle_tpu.compile_cache()
    main()
