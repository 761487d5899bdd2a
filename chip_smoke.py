#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU chip: train, then serve
    python chip_smoke.py --multichip  # four chips: SGD vs DP(4) vs MultiSlice(2x2)

Drives the two halves of the main path through the entry points users call:

  train   the GRU-attention NMT at its published width (vocab 30k/30k, 512
          wide, batch 256) through `paddle.SGD(...).train(...)`; checks the
          costs, that the compiled step holds the fused GRU kernel, the fused
          kernels against the scan path on the chip, a checkpoint round trip
          and `paddle.infer`.
  serve   train -> `python -m paddle_tpu.cli merge_model` ->
          `paddle_tpu_serving --backend pjrt --pjrt_plugin libtpu.so` ->
          POST /v1/infer against `paddle.infer`; then a generation bundle ->
          concurrent POST /v1/decode against the live decode.

This process never imports jax or paddle_tpu: a chip belongs to one process
at a time, so every phase runs in a child that exits — and releases the chip —
before the next starts, and the daemon starts only when no JAX child is alive.
A phase that fails ends the script at once with a non-zero code; nothing is
caught, skipped or retried on the CPU, and JAX_PLATFORMS is left alone. Every
line on stdout is one JSON object; the last one is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Times printed on earlier lines are smoke timings (one cold run, compilation
and start-up in or near them), not measurements.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".chip_smoke")          # scratch, git-ignored
DAEMON = os.path.join(REPO, "paddle_tpu", "native", "paddle_tpu_serving")
CHILD_TIMEOUT_S = 1000

# The NMT the repo publishes (models/text.py nmt_attention_cost defaults):
# nothing cut but the number of batches.
TRAIN = dict(vocab=30000, width=512, batch=256, n_batches=8, max_len=31,
             lr=5e-4, seed=21,
             # the same program's cost per token on the CPU (jax 0.9.0, this
             # seed; tests/test_chip_smoke.py re-derives it, slow-marked):
             # the chip must follow it, spike at batch 5 included (Adam at
             # 5e-4 with no warm-up; bf16 compute on both)
             cpu_cost_per_token=[10.3103, 10.3059, 10.2981, 10.2547, 12.0335,
                                 10.2771, 10.2632, 10.2419],
             # step program: 2 encoder directions x (forward + backward kernel)
             min_kernel_calls=4,
             # the fused kernels at the shapes users reach them with:
             # (B, H, T) of the NMT encoder and of the LSTM classifier
             gru_parity=(256, 512, 30), lstm_parity=(64, 512, 100),
             interpret=False,
             # block_until_ready probe: a chain of `iters` n x n matmuls
             # (~27 TFLOP: 0.15 s at the v5e's peak)
             sync_probe=(4096, 200))
# /v1/infer serves the LSTM text classifier at its default widths (27.7 MiB
# of parameters: under the exporter's 32 MiB cap). The decode topology is
# REDUCED: the NMT's three 30,000x512 tables alone are 176 MiB, so it keeps
# the published hidden width and cuts the vocabulary until it exports.
SERVE = dict(cls_batch=64, cls_batches=2, cls_max_len=100, cls_rows=8,
             dec_vocab=768, dec_width=512, dec_beam=4, dec_max_length=16,
             dec_src_len=16, dec_slots=4, dec_requests=10,
             # nudges the eos logit so that decode lengths vary (1..16 over
             # the ten requests); without it every request runs max_length
             dec_eos_bias=0.05, seed=22)
MULTICHIP = dict(TRAIN, chips=4)
# seq_pairs yields (src, trg, trg_next); the topology orders its data layers
# by graph traversal, so the feeding is named
NMT_FEEDING = {"src": 0, "trg": 1, "trg_next": 2}

# fused kernel vs scan path. f32: tools/tpu_parity.py's lstm/gru tolerances,
# with the absolute term scaled to the tensor. bf16: 8 mantissa bits over
# T recurrent steps, compared at the tensor's scale.
TOL = {"float32": dict(rtol=5e-4, atol=5e-5),
       "bfloat16": dict(rtol=0.0, atol=6e-2)}
# /v1/infer vs paddle.infer: both on the chip, different programs (the old
# on-chip PJRT test's bf16-matmul tolerance)
INFER_RTOL, INFER_ATOL = 2e-2, 2e-3


def emit(**kv):
    print(json.dumps(kv), flush=True)


# --------------------------------------------------------------------------
# children: everything below imports jax / paddle_tpu inside the function
# --------------------------------------------------------------------------

def enter_child():
    """First thing in every JAX child: fail unless the device is a TPU with
    a known peak, then place the compile cache. Returns the device tags
    every output line carries."""
    import jax

    dev = jax.devices()[0]
    tags = {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX reports {dev.platform!r} "
                 f"({dev.device_kind}), not a TPU — nothing ran")
    import paddle_tpu
    from paddle_tpu import flops

    if flops.device_peak_flops(dev) is None:
        sys.exit(f"chip_smoke: device kind {dev.device_kind!r} has no entry "
                 "in paddle_tpu.flops._PEAK_FLOPS — add its published peak")
    tags["compile_cache_dir"] = paddle_tpu.compile_cache()
    return tags


def count_cache_events():
    """Persistent-cache hits and misses of this process, as JAX itself
    counts them. Returns the live counter dict."""
    import jax.monitoring

    counts = {"cache_hits": 0, "cache_misses": 0}

    def listener(event, **kw):
        key = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and key in counts:
            counts[key] += 1

    jax.monitoring.register_event_listener(listener)
    return counts


def cache_entries(tags):
    """Programs in the persistent cache (JAX keeps each as <key>-cache)."""
    d = tags.get("compile_cache_dir")
    if not d or not os.path.isdir(d):
        return 0
    return sum(name.endswith("-cache") for name in os.listdir(d))


def memory_stat(name):
    """One memory_stats() figure per device (None where none is kept)."""
    import jax

    return [(d.memory_stats() or {}).get(name) for d in jax.devices()]


def device_result(tags):
    """What the parent puts in the script's last line."""
    return {"device": {"platform": tags["platform"],
                       "kind": tags["device_kind"],
                       "count": tags["device_count"]}}


def cost_per_token(costs, batches):
    """The NMT cost is summed over a sequence's target tokens and averaged
    over the batch; per token, an untrained softmax over V costs ln V."""
    return [c / (sum(len(s[2]) for s in b) / len(b))
            for c, b in zip(costs, batches)]


def nmt_reader(cfg):
    import paddle_tpu as paddle
    from paddle_tpu.dataset import synthetic

    return paddle.batch(
        synthetic.seq_pairs(cfg["vocab"], cfg["vocab"],
                            cfg["n_batches"] * cfg["batch"],
                            max_len=cfg["max_len"], seed=cfg["seed"]),
        cfg["batch"])


def nmt_cost(cfg):
    from paddle_tpu.models import text

    return text.nmt_attention_cost(
        src_dict_dim=cfg["vocab"], trg_dict_dim=cfg["vocab"],
        word_vector_dim=cfg["width"], encoder_size=cfg["width"],
        decoder_size=cfg["width"])


def run_trainer(trainer, reader, feeding=None):
    """trainer.train over one pass; returns per-batch costs, the wall
    time of each drain, and the number of BeginIteration events."""
    import paddle_tpu as paddle

    costs, drained, begins = [], [], [0]

    def handler(ev):
        if isinstance(ev, paddle.event.BeginIteration):
            begins[0] += 1
        elif isinstance(ev, paddle.event.EndIteration):
            costs.append(float(ev.cost))
            drained.append(time.perf_counter())

    t0 = time.perf_counter()
    trainer.train(reader, num_passes=1, event_handler=handler,
                  feeding=feeding)
    return costs, [t - t0 for t in drained], begins[0]


def placed_feeds(trainer, batch, feeding=None):
    """One batch as the train loop feeds and places it (feeder, then the
    prefetch's device_put)."""
    from paddle_tpu.trainer.feeder import DataFeeder

    feeder = DataFeeder(trainer.topology.data_type(), feeding)
    return trainer._device_put_feeds(trainer._prepare_feeds(feeder(batch)))


def median_step(drained):
    """Median wall time between drains, the first (compile) left out."""
    steps = sorted(b - a for a, b in zip(drained[1:], drained[2:]))
    return steps[len(steps) // 2]


def step_lowering(trainer, feeds):
    """Lower the step function the trainer compiled, on the arguments its
    loop ends with. Returns (shape key, jax.stages.Lowered)."""
    import jax

    assert len(trainer._step_fns) == 1, \
        f"expected one compiled step shape, got {list(trainer._step_fns)}"
    (key, fn), = trainer._step_fns.items()
    assert trainer._shape_key(feeds) == key, (trainer._shape_key(feeds), key)
    return key, fn.lower(trainer.parameters.as_dict(), trainer._opt_state,
                         jax.random.PRNGKey(0), feeds)


def _recurrent_case(kind, B, H, T, dtype, seed):
    """Seeded inputs for one fused-vs-scan comparison: pre-projected
    inputs, recurrent weights at 1/sqrt(H) scale, ragged lengths."""
    import jax.numpy as jnp
    import numpy as np

    r = np.random.RandomState(seed)
    k = {"gru": 3, "lstm": 4}[kind]
    s = 1.0 / math.sqrt(H)
    x = r.randn(B, T, k * H) * 0.5
    lens = r.randint(3, T + 1, size=B)
    lens[0] = T
    mask = (np.arange(T)[None, :] < lens[:, None]).astype(np.float32)
    cot = r.randn(B, T, H)
    if kind == "gru":
        ws = [r.uniform(-s, s, (H, 2 * H)), r.uniform(-s, s, (H, H)),
              r.uniform(-s, s, (3 * H,))]
    else:
        ws = [r.uniform(-s, s, (H, 4 * H)), r.uniform(-s, s, (7 * H,))]
    cast = lambda a: jnp.asarray(a, dtype)
    return cast(x), [cast(w) for w in ws], jnp.asarray(mask), \
        jnp.asarray(cot, jnp.float32)


def _scan_path(kind, x, ws, mask):
    """The layer's lax.scan recurrence (layers/recurrent.py: same cell
    functions, same mask-gated carry)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import activation as act_mod
    from paddle_tpu.layers.recurrent import (_scan_time, _to_time_major,
                                             gru_cell, lstm_cell)

    sig, tanh = act_mod.resolve("sigmoid"), act_mod.resolve("tanh")
    B, T, kH = x.shape
    xs = _to_time_major(x)
    ms = _to_time_major(mask.astype(x.dtype))[..., None]
    if kind == "gru":
        H = kH // 3
        wg, wc, b = ws

        def step(h, xm):
            xt, m = xm
            h = m * gru_cell(xt, h, wg, wc, b, sig, tanh, H) + (1 - m) * h
            return h, h

        _, hs = _scan_time(step, jnp.zeros((B, H), x.dtype), (xs, ms))
    else:
        H = kH // 4
        w, b = ws

        def step(carry, xm):
            h, c = carry
            xt, m = xm
            hn, cn = lstm_cell(xt, h, c, w, b, tanh, tanh, H, sig)
            h = m * hn + (1 - m) * h
            c = m * cn + (1 - m) * c
            return (h, c), h

        z = jnp.zeros((B, H), x.dtype)
        _, hs = _scan_time(step, (z, z), (xs, ms))
    return jax.numpy.swapaxes(hs, 0, 1)


def _fused_path(kind, x, ws, mask, interpret):
    from paddle_tpu.kernels.gru import fused_gru
    from paddle_tpu.kernels.lstm import fused_lstm

    if kind == "gru":
        return fused_gru(x, ws[0], ws[1], ws[2], mask, None, interpret)
    return fused_lstm(x, ws[0], ws[1], mask, None, interpret)[0]


def kernel_parity(kind, shape, dtype_name, interpret, seed):
    """Forward and gradients of the fused Pallas kernel against the scan
    path, same inputs, on this device. Returns the worst error of each
    tensor as a fraction of that tensor's allowance (<= 1 passes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    B, H, T = shape
    dtype = jnp.dtype(dtype_name)
    x, ws, mask, cot = _recurrent_case(kind, B, H, T, dtype, seed)

    def make(path):
        # mask and cot are arguments, not closed-over constants: baked
        # into the program they made 55 MB executables that pushed the
        # train step out of the size-capped compile cache
        def loss(x, ws, mask, cot):
            hs = path(x, ws, mask).astype(jnp.float32) * mask[..., None]
            return jnp.sum(hs * cot) / B, hs

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))

    # f32 compares the two programs, not the MXU's default bf16 passes:
    # both are traced at 'highest', as tools/tpu_parity.py runs its cases
    prec = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        t0 = time.perf_counter()
        (_, hs_f), (dx_f, dw_f) = make(
            lambda x, ws, mask: _fused_path(kind, x, ws, mask, interpret))(
                x, ws, mask, cot)
        jax.block_until_ready((hs_f, dx_f, dw_f))
        t1 = time.perf_counter()
        (_, hs_s), (dx_s, dw_s) = make(
            lambda x, ws, mask: _scan_path(kind, x, ws, mask))(
                x, ws, mask, cot)
        jax.block_until_ready((hs_s, dx_s, dw_s))
        t2 = time.perf_counter()
    tol = TOL[dtype_name]
    names = ["hs", "dx"] + [f"dw{i}" for i in range(len(ws))]
    worst = {}
    for name, got, want in zip(names, [hs_f, dx_f, *dw_f],
                               [hs_s, dx_s, *dw_s]):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        assert np.isfinite(got).all() and np.isfinite(want).all(), name
        scale = max(float(np.abs(want).max()), 1e-30)
        allow = tol["atol"] * scale + tol["rtol"] * np.abs(want)
        worst[name] = float((np.abs(got - want) / allow).max())
    return {"kernel": f"fused_{kind}", "B": B, "H": H, "T": T,
            "dtype": dtype_name, "tolerance": tol,
            "worst_error_over_allowance": worst,
            "smoke_compile_and_run_s": {"fused": round(t1 - t0, 3),
                                        "scan": round(t2 - t1, 3)},
            "ok": max(worst.values()) <= 1.0}


def sync_probe(n, iters):
    """Does block_until_ready wait for the device on this backend?
    (On a retired transport it did not, and timing code fetched a value.) One
    jitted chain of matmuls, long enough to tell dispatch from execution."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def chain(x):
        def body(i, y):
            return (y @ x) * jnp.bfloat16(1.0 / n)
        return jax.lax.fori_loop(0, iters, body, x)[0, 0].astype(jnp.float32)

    float(chain(x))                                  # compile + warm
    t0 = time.perf_counter()
    y = chain(x)
    t1 = time.perf_counter()
    y.block_until_ready()
    t2 = time.perf_counter()
    float(y)
    t3 = time.perf_counter()
    return {"dispatch_s": t1 - t0, "block_until_ready_s": t2 - t1,
            "then_fetch_s": t3 - t2,
            "block_until_ready_waits": (t2 - t1) > 5 * (t3 - t2)}


def phase_train(cfg, tags, work):
    """Train, check, checkpoint, infer. ``cfg`` is TRAIN on the chip; the
    CPU rehearsal (tests/test_chip_smoke.py) passes tiny widths."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import flops, optimizer
    from paddle_tpu.core.parameters import Parameters
    from paddle_tpu.core.topology import Topology

    cost = nmt_cost(cfg)
    parameters = paddle.parameters_create(Topology(cost))
    trainer = paddle.SGD(cost, parameters, optimizer.Adam(cfg["lr"]),
                         mixed_precision=True)
    reader = nmt_reader(cfg)
    batches = list(reader())
    costs, drained, begins = run_trainer(trainer, reader, NMT_FEEDING)

    n = cfg["n_batches"]
    assert len(costs) == n and begins == n, (len(costs), begins, n)
    assert all(math.isfinite(c) for c in costs), costs
    per_tok = cost_per_token(costs, batches)
    ln_v = math.log(cfg["vocab"])
    assert abs(per_tok[0] - ln_v) <= 0.05 * ln_v, (per_tok[0], ln_v)
    assert per_tok[-1] < per_tok[0], per_tok
    ref = cfg["cpu_cost_per_token"]
    assert ref is None or all(abs(c - r) <= 0.01 * r
                              for c, r in zip(per_tok, ref)), (per_tok, ref)
    assert not trainer._prefetch_put_failed, trainer._prefetch_put_failed
    step_s = median_step(drained)

    key, lowered = step_lowering(
        trainer, placed_feeds(trainer, batches[0], NMT_FEEDING))
    n_kernels = lowered.as_text().count("tpu_custom_call")
    assert n_kernels >= cfg["min_kernel_calls"], \
        (f"the compiled step holds {n_kernels} tpu_custom_call, expected >= "
         f"{cfg['min_kernel_calls']}: the encoder's grumemory layers went "
         "to lax.scan (see the 'lax.scan, not fused_gru' log lines)")
    B, T = dict((k[0], k[1]) for k in key)["src"]       # (name, shape, mask)
    fl = flops.train_flops(trainer.topology, B, T)
    peak = flops.device_peak_flops()       # enter_child refused a None
    emit(phase="train", what="SGD.train", **tags,
         model=f"nmt_attention vocab {cfg['vocab']} width {cfg['width']}",
         batch=B, padded_T=T, batches=n,
         cost_per_token=[round(c, 4) for c in per_tok], ln_vocab=ln_v,
         cpu_cost_per_token=ref,
         smoke_drained_at_s=[round(t, 2) for t in drained],
         smoke_step_s=round(step_s, 4),
         smoke_model_flops_share=round(fl / step_s / peak, 4)
         if fl and peak else None,
         tpu_custom_calls_in_step=n_kernels,
         prefetch_put_failed=sorted(trainer._prefetch_put_failed),
         peak_bytes_in_use=memory_stat("peak_bytes_in_use"))

    for kind, shape in (("gru", cfg["gru_parity"]),
                        ("lstm", cfg["lstm_parity"])):
        for dtype_name in ("float32", "bfloat16"):
            res = kernel_parity(kind, shape, dtype_name, cfg["interpret"],
                                cfg["seed"])
            emit(phase="train", what="fused kernel vs scan path", **tags,
                 **res)
            assert res["ok"], res

    # checkpoint round trip, then paddle.infer on both sets of parameters
    path = os.path.join(work, "nmt_params.tar")
    t0 = time.perf_counter()
    trainer.parameters.to_file(path)
    reloaded = Parameters.from_file(path)
    t1 = time.perf_counter()
    for name in trainer.parameters.names():
        np.testing.assert_array_equal(np.asarray(trainer.parameters.get(name)),
                                      np.asarray(reloaded.get(name)), name)
    sample = batches[0][:8]
    a = paddle.infer(cost, trainer.parameters, sample, feeding=NMT_FEEDING)
    b = paddle.infer(cost, reloaded, sample, feeding=NMT_FEEDING)
    assert np.isfinite(a).all() and a.shape[0] == len(sample), a
    np.testing.assert_array_equal(a, b)
    emit(phase="train", what="checkpoint round trip + paddle.infer", **tags,
         tar_bytes=os.path.getsize(path), smoke_save_load_s=round(t1 - t0, 2),
         infer_cost_per_sample=[round(float(v), 3) for v in a.ravel()])
    os.remove(path)
    emit(phase="train", what="block_until_ready", **tags,
         **sync_probe(*cfg["sync_probe"]))
    return device_result(tags)


# ---- serve: export side ---------------------------------------------------

CLS_CONF = '''\
# chip_smoke.py serve phase: the LSTM text classifier at its default widths
from paddle.trainer_config_helpers import outputs
from paddle_tpu.models.text import lstm_text_classification

outputs(lstm_text_classification()[2])
'''


def _decode_topology(cfg):
    from paddle_tpu.models import text

    return text.nmt_decode_topology(
        src_dict_dim=cfg["dec_vocab"], trg_dict_dim=cfg["dec_vocab"],
        word_vector_dim=cfg["dec_width"], encoder_size=cfg["dec_width"],
        decoder_size=cfg["dec_width"], beam_size=cfg["dec_beam"],
        max_length=cfg["dec_max_length"], mode="dense", name="m")


def _param_bytes(topology):
    import numpy as np

    return int(sum(np.prod(s.shape) * 4
                   for s in topology.param_specs().values()))


def _check_bundle(path, step):
    """A bundle on the smoke path must carry every TPU module."""
    from paddle_tpu.io.merged_model import read_bundle_meta

    meta = read_bundle_meta(path)
    assert "stablehlo" in meta, meta.get("stablehlo_skip_reason")
    sh = meta["stablehlo"]
    errs = {k: v for k, v in sh["signature"].items()
            if k in ("module_errors", "ladder_errors")}
    assert "mlir_tpu_b64" in sh, errs
    assert "tpu" not in sh["signature"].get("module_errors", {}), errs
    sizes = {"bundle_bytes": os.path.getsize(path),
             "mlir_tpu_bytes": len(sh["mlir_tpu_b64"]) * 3 // 4}
    if step:
        assert "stablehlo_step" in meta, \
            meta.get("stablehlo_step_skip_reason")
        for k in ("init_mlir_tpu_b64", "step_mlir_tpu_b64"):
            assert k in meta["stablehlo_step"], sorted(meta["stablehlo_step"])
            sizes[k[:-4] + "_bytes"] = len(meta["stablehlo_step"][k]) * 3 // 4
    return sizes, errs


def phase_serve_export(cfg, tags, work):
    """Everything the serve phase needs from JAX, before the daemon may
    have the chip: build the daemon from the committed sources, train the
    classifier and compute `paddle.infer`, build the generation bundle and
    the live decode. Writes its answers to ``work``/expected.json."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import native, optimizer
    from paddle_tpu.core.parameters import Parameters
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.dataset import synthetic
    from paddle_tpu.io.merged_model import merge_model
    from paddle_tpu.models import text

    t0 = time.perf_counter()
    native.build("serving", fresh=True)
    emit(phase="serve", what="make -B serving (from the committed sources)",
         **tags, smoke_build_s=round(time.perf_counter() - t0, 1))

    # -- /v1/infer model: train -> tar + config for the CLI -> paddle.infer
    words, label, out, cost = text.lstm_text_classification()
    V = words.size
    parameters = paddle.parameters_create(Topology(cost))
    trainer = paddle.SGD(cost, parameters, optimizer.Adam(1e-3))
    n = cfg["cls_batch"] * cfg["cls_batches"]
    reader = paddle.batch(synthetic.classification(
        V, 2, n, seed=cfg["seed"], seq=True, max_len=cfg["cls_max_len"],
        vocab=V), cfg["cls_batch"])
    costs, drained, _ = run_trainer(trainer, reader)
    assert len(costs) == cfg["cls_batches"] and \
        all(math.isfinite(c) for c in costs), costs
    trainer.parameters.to_file(os.path.join(work, "cls_params.tar"))
    with open(os.path.join(work, "cls_conf.py"), "w") as f:
        f.write(CLS_CONF)
    T = cfg["cls_max_len"]
    rows = [s[0] for s in synthetic.classification(
        V, 2, cfg["cls_rows"], seed=cfg["seed"] + 1, seq=True, max_len=T,
        vocab=V)()]
    want = paddle.infer(out, trainer.parameters, [(r,) for r in rows])
    assert want.shape == (len(rows), 2) and np.isfinite(want).all(), want
    ids = [r + [0] * (T - len(r)) for r in rows]
    mask = [[1.0] * len(r) + [0.0] * (T - len(r)) for r in rows]
    cls_bytes = _param_bytes(Topology(out))
    emit(phase="serve", what="classifier trained, paddle.infer computed",
         **tags, model="lstm_text_classification (default widths)",
         param_bytes=cls_bytes, train_costs=[round(c, 4) for c in costs],
         smoke_train_s=round(drained[-1], 2))

    # -- /v1/decode model: REDUCED vocabulary; same seed -> same weights
    # for the bundle and the live decode. Exported and decoded at full f32
    # matmul precision: equal ids need the two programs to agree on every
    # near-tie, which the MXU's default bf16 passes do not promise.
    jax.config.update("jax_default_matmul_precision", "highest")
    topo = Topology(_decode_topology(cfg))
    params = topo.init_params(jax.random.PRNGKey(cfg["seed"]))
    b = np.array(params["_m_out.wbias"])
    b[..., 1] += cfg["dec_eos_bias"]
    params["_m_out.wbias"] = jnp.asarray(b)
    P = Parameters.from_dict({k: np.asarray(v) for k, v in params.items()})
    dec_dir = os.path.join(work, "decode")
    os.makedirs(dec_dir)
    P.to_file(os.path.join(dec_dir, "params.tar"))
    t0 = time.perf_counter()
    merge_model(config=lambda: _decode_topology(cfg),
                output=os.path.join(dec_dir, "m.ptpu"),
                param_tar=os.path.join(dec_dir, "params.tar"),
                export_seq_len=cfg["dec_src_len"],
                export_slots=cfg["dec_slots"], bundle_version=1)
    sizes, errs = _check_bundle(os.path.join(dec_dir, "m.ptpu"), step=True)
    dec_bytes = _param_bytes(topo)
    full = _param_bytes(Topology(text.nmt_decode_topology(mode="dense")))
    emit(phase="serve", what="generation bundle (merge_model, config=callable)",
         **tags, reduced=f"vocabulary {cfg['dec_vocab']} of 30000 at the "
         f"published width {cfg['dec_width']}: the exporter's 32 MiB cap "
         "(io/merged_model.py, ROADMAP D4)", param_bytes=dec_bytes,
         param_bytes_full_width=full, classifier_param_bytes=cls_bytes,
         matmul_precision="highest", other_platform_errors=errs,
         smoke_export_s=round(time.perf_counter() - t0, 1), **sizes)

    r = np.random.RandomState(cfg["seed"])
    L, Ts = cfg["dec_max_length"], cfg["dec_src_len"]
    srcs, want_ids = [], []
    live = jax.jit(lambda p, s, m: _live_ids(topo, p, s, m))
    for _ in range(cfg["dec_requests"]):
        n_src = int(r.randint(3, Ts + 1))
        src = r.randint(2, cfg["dec_vocab"], n_src).astype(np.int32)
        pad = np.zeros((1, Ts), np.int32)
        pad[0, :n_src] = src
        m = (np.arange(Ts)[None, :] < n_src).astype(np.float32)
        ids_b, sc_b = live(params, pad, m)
        row = np.asarray(ids_b)[0, int(np.argmax(np.asarray(sc_b)[0]))]
        cut = [int(t) for t in row[:L]]
        if 1 in cut:
            cut = cut[:cut.index(1) + 1]
        srcs.append([int(t) for t in src])
        want_ids.append(cut)
    assert len({len(w) for w in want_ids}) > 1, \
        f"live decode lengths do not vary: {want_ids}"
    with open(os.path.join(work, "expected.json"), "w") as f:
        json.dump({"cls": {"ids": ids, "mask": mask, "want": want.tolist()},
                   "decode": {"srcs": srcs, "want_ids": want_ids,
                              "max_new": L}}, f)
    emit(phase="serve", what="live decode computed", **tags,
         requests=len(srcs), lengths=[len(w) for w in want_ids],
         peak_bytes_in_use=memory_stat("peak_bytes_in_use"))
    return {}


def _live_ids(topo, params, src, mask):
    from paddle_tpu.core.arg import Arg

    _outs, ctx = topo.forward(params, {"src": Arg(src, mask)},
                              return_ctx=True)
    return ctx.extras["m_gen:ids"], ctx.extras["m_gen:scores"]


# ---- multichip ------------------------------------------------------------

def phase_multichip(cfg, tags, work):
    """The same NMT batches through plain SGD on one device, through
    DataParallelTrainer on make_mesh(data=N) and through
    MultiSliceTrainer(make_mesh(slice=2, data=N/2), zero=True)."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.core.parameters import Parameters
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.parallel import (DataParallelTrainer, MultiSliceTrainer,
                                     make_mesh)

    N = cfg["chips"]
    assert len(jax.devices()) == N, \
        f"--multichip needs {N} devices, JAX reports {len(jax.devices())}"
    cost = nmt_cost(cfg)
    init = paddle.parameters_create(Topology(cost))
    init = {k: np.array(init.get(k)) for k in init.names()}
    batches = list(nmt_reader(cfg)())
    per = cfg["batch"] // N

    def build(kind):
        P = Parameters.from_dict({k: v.copy() for k, v in init.items()})
        opt = optimizer.Adam(cfg["lr"])
        if kind == "sgd":
            return paddle.SGD(cost, P, opt, mixed_precision=True)
        if kind == "dp":
            return DataParallelTrainer(cost, P, opt, mesh=make_mesh(data=N),
                                       mixed_precision=True)
        return MultiSliceTrainer(cost, P, opt,
                                 mesh=make_mesh(slice=2, data=N // 2),
                                 zero=True, mixed_precision=True)

    def device_sets(tree):
        return sorted({len(x.sharding.device_set)
                       for x in jax.tree_util.tree_leaves(tree)
                       if hasattr(x, "sharding")})

    all_costs = {}
    for kind in ("sgd", "dp", "multislice"):
        trainer = build(kind)
        mem0 = memory_stat("bytes_in_use")
        costs, drained, _ = run_trainer(trainer, nmt_reader(cfg), NMT_FEEDING)
        assert len(costs) == cfg["n_batches"] and \
            all(math.isfinite(c) for c in costs), costs
        assert not trainer._prefetch_put_failed, trainer._prefetch_put_failed
        all_costs[kind] = costs
        line = dict(phase="multichip", what=kind, **tags,
                    costs=[round(c, 4) for c in costs],
                    smoke_drained_at_s=[round(t, 2) for t in drained],
                    smoke_step_s=round(median_step(drained), 4),
                    bytes_in_use_before=mem0,
                    bytes_in_use=memory_stat("bytes_in_use"),
                    peak_bytes_in_use=memory_stat("peak_bytes_in_use"))
        if kind != "sgd":
            feeds = placed_feeds(trainer, batches[0], NMT_FEEDING)
            for name, a in feeds.items():
                shards = a.value.addressable_shards
                assert len({s.device for s in shards}) == N, (name, shards)
                assert all(s.data.shape[0] == per for s in shards), \
                    (name, [s.data.shape for s in shards])
            params = trainer.parameters.as_dict()
            assert device_sets(params) == [N], device_sets(params)
            # the steady-state program again: a compile-cache hit
            key, lowered = step_lowering(trainer, feeds)
            hlo = lowered.compile().as_text()
            line.update(
                feed_shard_rows=per, param_device_set=N,
                tpu_custom_calls_in_step=lowered.as_text().count(
                    "tpu_custom_call"),
                collectives={c: hlo.count(f" {c}(") + hlo.count(f" {c}-start(")
                             for c in ("all-reduce", "reduce-scatter",
                                       "all-gather", "all-to-all",
                                       "collective-permute")})
            # replicated parameters alone put this much on every device
            param_bytes = sum(v.nbytes for v in params.values())
            in_use = line["bytes_in_use"]
            if all(b is not None for b in in_use):      # the CPU reports none
                assert min(in_use) >= param_bytes, (in_use, param_bytes)
            line.update(param_bytes_replicated=param_bytes)
        if kind == "multislice":
            # ZeRO-1: every param-shaped Adam slot is a flat array split
            # over the 'data' axis (1/2 per data rank), replicated over
            # 'slice' — four shards, each half the padded length
            n_data = N // 2
            checked = 0
            for pname, slots in trainer._opt_state.items():
                for sname, v in (slots.items() if isinstance(slots, dict)
                                 else ()):
                    if getattr(v, "ndim", 0) != 1:
                        continue
                    shards = v.addressable_shards
                    assert len({s.device for s in shards}) == N, (pname, sname)
                    assert all(s.data.shape[0] * n_data == v.shape[0]
                               for s in shards), (pname, sname, v.shape)
                    checked += 1
            assert checked > 0
            line.update(zero_sharded_slots=checked,
                        zero_fraction_per_data_rank=1.0 / n_data)
        emit(**line)
        del trainer, line

    # the float tolerance tests/test_multihost.py holds its trainers to
    for kind in ("dp", "multislice"):
        np.testing.assert_allclose(all_costs[kind], all_costs["sgd"],
                                   rtol=1e-4, atol=1e-5, err_msg=kind)
    return device_result(tags)


CHILDREN = {"train": (phase_train, TRAIN),
            "serve_export": (phase_serve_export, SERVE),
            "multichip": (phase_multichip, MULTICHIP)}


def child_main(name, result_path):
    fn, cfg = CHILDREN[name]
    tags = enter_child()
    n_cache0, events = cache_entries(tags), count_cache_events()
    result = fn(cfg, tags, WORK)
    # a miss is a program compiled anew (kept if it took over a second);
    # a second run on the same cache shows hits and no new entries
    emit(phase=name, what="compile cache", **tags, **events,
         entries_before=n_cache0, entries_after=cache_entries(tags))
    with open(result_path, "w") as f:
        json.dump(result, f)


# --------------------------------------------------------------------------
# parent: stdlib only
# --------------------------------------------------------------------------

def run_child(name):
    """Run one phase in its own process; exit with its code if it fails."""
    result_path = os.path.join(WORK, f"{name}.result.json")
    t0 = time.perf_counter()
    rc = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; chip_smoke.child_main(*sys.argv[1:])",
         name, result_path],
        cwd=REPO, timeout=CHILD_TIMEOUT_S).returncode
    if rc != 0:
        print(f"chip_smoke: phase {name} failed (exit code {rc})",
              file=sys.stderr)
        sys.exit(rc if rc > 0 else 1)
    with open(result_path) as f:
        result = json.load(f)
    emit(phase=name, what="child exited, chip released",
         smoke_child_s=round(time.perf_counter() - t0, 1))
    return result


def run_tool(what, cmd):
    t0 = time.perf_counter()
    r = subprocess.run(cmd, cwd=REPO, timeout=CHILD_TIMEOUT_S,
                       stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                       text=True)
    if r.returncode != 0:
        print(r.stdout[-4000:], file=sys.stderr)
        print(f"chip_smoke: {what} failed (exit code {r.returncode})",
              file=sys.stderr)
        sys.exit(r.returncode if r.returncode > 0 else 1)
    emit(phase="serve", what=what, smoke_s=round(time.perf_counter() - t0, 1),
         output_tail=r.stdout[-600:])


def libtpu_path():
    spec = importlib.util.find_spec("libtpu")
    if spec is None or not spec.submodule_search_locations:
        sys.exit("chip_smoke: no installed libtpu package to serve through")
    path = os.path.join(list(spec.submodule_search_locations)[0], "libtpu.so")
    if not os.path.exists(path):
        sys.exit(f"chip_smoke: {path} does not exist")
    return path


class Daemon:
    """paddle_tpu_serving --backend pjrt on a free port; stderr to a file."""

    def __init__(self, bundle, tag, *flags):
        self.log = os.path.join(WORK, f"daemon_{tag}.stderr")
        t0 = time.perf_counter()
        with open(self.log, "w") as err:
            self.proc = subprocess.Popen(
                [DAEMON, "--bundle", bundle, "--backend", "pjrt",
                 "--pjrt_plugin", libtpu_path(), "--port", "0", *flags],
                stdout=subprocess.PIPE, stderr=err, text=True)
        # the banner comes after the bundle loaded and every module compiled
        box = []
        reader = threading.Thread(
            target=lambda: box.append(self.proc.stdout.readline()),
            daemon=True)
        reader.start()
        reader.join(600)
        line = box[0] if box else ""
        if "paddle_tpu_serving on port" not in line:
            self.kill()
            print(self.stderr_tail(4000), file=sys.stderr)
            sys.exit(f"chip_smoke: daemon ({tag}) did not come up: {line!r}")
        self.port = int(line.split("port")[1].split()[0])
        self.banner = line.strip()
        self.start_s = time.perf_counter() - t0

    def url(self, path):
        return f"http://127.0.0.1:{self.port}{path}"

    def get(self, path):
        with urllib.request.urlopen(self.url(path), timeout=60) as r:
            return r.read().decode()

    def post(self, path, obj):
        req = urllib.request.Request(self.url(path),
                                     data=json.dumps(obj).encode())
        with urllib.request.urlopen(req, timeout=300) as r:
            return json.loads(r.read())

    def stderr_tail(self, n=1500):
        with open(self.log) as f:
            return f.read()[-n:]

    def terminate(self):
        """SIGTERM -> graceful drain; returns the exit code."""
        self.proc.send_signal(signal.SIGTERM)
        return self.proc.wait(120)

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


def metric(text, name):
    """Sum of a Prometheus family's samples (labels ignored)."""
    vals = [float(l.rsplit(" ", 1)[1]) for l in text.splitlines()
            if l.startswith(name) and l[len(name)] in " {"]
    return sum(vals) if vals else None


def close_enough(got, want):
    return all(abs(g - w) <= INFER_ATOL + INFER_RTOL * abs(w)
               for g, w in zip(got, want))


def serve_infer(expected):
    bundle = os.path.join(WORK, "infer", "m.ptpu")
    os.makedirs(os.path.dirname(bundle))
    run_tool("python -m paddle_tpu.cli merge_model (classifier bundle)",
             [sys.executable, "-m", "paddle_tpu.cli", "merge_model",
              "--config", os.path.join(WORK, "cls_conf.py"),
              "--model_tar", os.path.join(WORK, "cls_params.tar"),
              "--output", bundle, "--bundle_version", "1",
              "--export_seq_len", str(len(expected["ids"][0]))])
    d = Daemon(bundle, "infer")
    try:
        sig = json.loads(d.get("/v1/signature"))
        assert "skip_reason" not in sig, sig
        out_name = sig["outputs"][0]["name"]
        ids, mask, want = expected["ids"], expected["mask"], expected["want"]
        secs, worst = [], 0.0
        # the static batch, a short batch (padded up), one row
        for rows in (range(len(ids)), range(3), range(5, 6)):
            t0 = time.perf_counter()
            resp = d.post("/v1/infer", {"inputs": {
                "words": [ids[i] for i in rows],
                "words:mask": [mask[i] for i in rows]}})
            secs.append(time.perf_counter() - t0)
            got = resp["outputs"][out_name]
            assert got["shape"] == [len(rows), 2], got["shape"]
            flat_want = [v for i in rows for v in want[i]]
            assert all(math.isfinite(v) for v in got["data"]), got
            assert close_enough(got["data"], flat_want), (got["data"],
                                                          flat_want)
            worst = max(worst, max(abs(g - w) for g, w in
                                   zip(got["data"], flat_want)))
        m = d.get("/metrics")
        n_ok = metric(m, "paddle_serving_requests_total")
        errors = metric(m, "paddle_serving_backend_errors_total") or 0
        assert n_ok and n_ok >= 3 and errors == 0, (n_ok, errors)
        rc = d.terminate()
        tail = d.stderr_tail()
        assert rc == 0, (rc, tail)
        assert "compile failed" not in tail, tail
        emit(phase="serve", what="/v1/infer through --backend pjrt",
             banner=d.banner, bundle_bytes=os.path.getsize(bundle),
             smoke_daemon_start_s=round(d.start_s, 1),
             note="start includes reading the bundle and compiling its "
                  "StableHLO: the C++ runner has no compile cache",
             smoke_request_s=[round(s, 4) for s in secs],
             max_abs_diff_vs_paddle_infer=worst,
             tolerance={"rtol": INFER_RTOL, "atol": INFER_ATOL},
             requests_total=n_ok, daemon_exit_code=rc, daemon_stderr_tail=tail)
    finally:
        d.kill()


def serve_decode(expected):
    bundle = os.path.join(WORK, "decode", "m.ptpu")
    d = Daemon(bundle, "decode")
    try:
        up = d.stderr_tail(4000)
        assert "continuous per-tick step decode" in up, up
        srcs, want = expected["srcs"], expected["want_ids"]
        got = [None] * len(srcs)
        secs = [None] * len(srcs)

        def one(i):
            t0 = time.perf_counter()
            got[i] = d.post("/v1/decode", {"src": srcs[i],
                                           "max_new": expected["max_new"]})
            secs[i] = time.perf_counter() - t0

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(srcs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        assert all(g is not None for g in got), got
        for i, (g, w) in enumerate(zip(got, want)):
            assert g.get("ids") == w, (i, g, w)
        m = d.get("/metrics")
        errors = metric(m, "paddle_serving_backend_errors_total") or 0
        assert errors == 0, errors
        rc = d.terminate()
        tail = d.stderr_tail()
        assert rc == 0, (rc, tail)
        emit(phase="serve", what="/v1/decode through --backend pjrt",
             banner=d.banner, bundle_bytes=os.path.getsize(bundle),
             smoke_daemon_start_s=round(d.start_s, 1),
             requests=len(srcs), slots=SERVE["dec_slots"],
             ids_equal_live_decode=True,
             continuous_admits=sum(1 for g in got
                                   if g.get("continuous_admit")),
             ticks=[g.get("ticks") for g in got],
             smoke_request_s=[round(s, 3) for s in secs],
             daemon_exit_code=rc, daemon_stderr_tail=tail)
    finally:
        d.kill()


def main(argv):
    if argv not in ([], ["--multichip"]):
        sys.exit(__doc__)
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    try:
        if argv:
            device = run_child("multichip")["device"]
        else:
            device = run_child("train")["device"]
            run_child("serve_export")
            with open(os.path.join(WORK, "expected.json")) as f:
                expected = json.load(f)
            serve_infer(expected["cls"])
            serve_decode(expected["decode"])
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
