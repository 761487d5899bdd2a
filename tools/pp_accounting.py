"""PP perf accounting (VERDICT r4 next item 9 / ISSUE 8 balancer): bubble
fraction, padded-buffer overhead and stage balance of PipelinedTopology
on the NMT flagship pipeline, measured on the 8-virtual-device CPU mesh —
for BOTH the naive (annotation/inherit) assignment and the r13
width-balanced partitioner, side by side.

The GPipe schedule in parallel/topo_pipeline.py runs M + S - 1 ticks for
M microbatches over S stages; every device is busy in M of them, so

    efficiency(M)     = M / (M + S - 1)
    bubble_fraction   = (S - 1) / (M + S - 1)

and with the global batch fixed (B_mb = B / M) the modelled step time is

    T(M) = T_work * (M + S - 1) / M + c * (M + S - 1)

(T_work = all-microbatch compute; c = per-tick dispatch overhead).
The fit is the accounting's self-check: the measured step times must BE
the bubble model plus a constant per-tick cost within ~4-5%, else the
schedule has unexplained overhead.

The padded-buffer overhead is static: every boundary flattens to the
widest boundary's D_max and every stage's params to P_max
(ParallelNeuralNetwork.cpp:24 is the reference's threaded analog; it
pays in idle threads instead of padding). The per-stage boundary width /
param rows / flops columns printed here are the balancer's objective
made visible: balanced mode should show a flatter param column and a
narrower widest boundary than naive.

Usage:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_PLATFORMS=cpu python tools/pp_accounting.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core.arg import Arg
from paddle_tpu.core.layer import layer_name_scope
from paddle_tpu.core.topology import Topology
from paddle_tpu.models.text import nmt_attention_cost, nmt_stage_map
from paddle_tpu.parallel.topo_pipeline import (PipelinedTopology,
                                               assignment_report,
                                               microbatch)


def static_accounting(pt, params):
    """Padding-waste fractions of the boundary buffer and param matrix,
    measured from the BUILT plan (packers + stacked rows), not the
    seq_len_hint estimate — plus the per-stage columns of the balancer's
    objective."""
    import math
    stacked = pt.stack_params(params)
    p_max = stacked.shape[1]
    stage_sizes = [sum(int(np.prod(shape)) or 1 for _, shape, _ in rec)
                   for rec in pt._param_recs]
    param_pad = 1.0 - sum(stage_sizes) / (len(stage_sizes) * p_max)
    widths = []
    for packer in pt._packers:
        w = 0
        for _, tail, _, mask_dt, has_seg in packer.infos:
            w += int(math.prod(tail)) if tail else 1
            if mask_dt is not None:
                w += tail[0]
            if has_seg:
                w += tail[0]
        widths.append(w)
    d_max = pt._d_max
    bound_pad = 1.0 - sum(widths) / (len(widths) * d_max) if widths else 0.0
    return {"p_max": p_max, "stage_param_sizes": stage_sizes,
            "param_pad_frac": param_pad, "d_max": d_max,
            "boundary_widths": widths, "boundary_pad_frac": bound_pad}


def measure_mode(topo, params, mesh, S, T, make_pt, feeds, iters=8,
                 micro=(2, 4, 8)):
    """Timing sweep over microbatch counts for one stage assignment.
    Returns {"rows": [(M, ms, eff, bubble)], "acct": ..., "fit": ...}."""
    rows = []
    acct = None
    for M in micro:
        pt = make_pt()
        stacked = jax.device_put(pt.stack_params(params),
                                 NamedSharding(mesh, P("stage")))
        feeds_mb = microbatch(feeds, M)

        f = jax.jit(jax.value_and_grad(
            lambda sp: pt.loss(sp, feeds_mb, mesh)))
        for _ in range(4):                  # compile + thread-pool warmup
            v, g = f(stacked)
            jax.block_until_ready(g)
        windows = []
        for _ in range(8):      # this container's CPU collectives jitter
            t0 = time.perf_counter()        # 1.5-2x between windows; the
            for _ in range(iters):          # MIN window is the stable
                v, g = f(stacked)           # estimate of the true cost
            jax.block_until_ready(g)
            float(v)
            windows.append((time.perf_counter() - t0) / iters * 1e3)
        dt = min(windows)
        if acct is None:
            acct = static_accounting(pt, params)
            acct["per_stage"] = assignment_report(topo, pt.stages, S,
                                                  seq_len_hint=T)
        rows.append((M, dt, M / (M + S - 1), (S - 1) / (M + S - 1)))
        print(f"  M={M}: {dt:8.1f} ms/step  ticks={M + S - 1}  "
              f"efficiency={M / (M + S - 1):.3f}  "
              f"bubble={(S - 1) / (M + S - 1):.3f}")
    # fit T(M) = a*(M+S-1)/M + c*(M+S-1) by least squares
    A = np.array([[(M + S - 1) / M, (M + S - 1)] for M, *_ in rows])
    y = np.array([dt for _, dt, *_ in rows])
    coef, *_ = np.linalg.lstsq(A, y, rcond=None)
    pred = A @ coef
    err = float(np.abs(pred - y).max() / y.max())
    print(f"  model fit: T_work={coef[0]:.1f} ms, per-tick "
          f"overhead={coef[1]:.2f} ms; predicted={np.round(pred, 1)} "
          f"measured={np.round(y, 1)} (max rel err {err:.1%}"
          f"{' — OK' if err < 0.05 else ' — UNEXPLAINED OVERHEAD'})")
    return {"rows": rows, "acct": acct,
            "fit": {"t_work_ms": float(coef[0]),
                    "per_tick_ms": float(coef[1]), "max_rel_err": err}}


def _feeds(B, T, V):
    r = np.random.RandomState(0)
    mask = jnp.ones((B, T), jnp.float32)
    return {k: Arg(jnp.asarray(r.randint(0, V, (B, T)), jnp.int32), mask)
            for k in ("src", "trg", "trg_next")}


def main(S=4, B=64, T=16, D=96, V=600, iters=3):
    # defaults sized so compute dominates per-tick dispatch noise on the
    # CPU container: at the r5 sizes (B=32 D=48) the bubble-model
    # fit degrades to ~10-15% because tiny per-tick work is nonlinear in
    # B_mb on CPU; at B=64 D=96 the fit lands within the ~4-5% check
    devices = jax.devices()[:S]
    mesh = Mesh(np.asarray(devices), ("stage",))
    with layer_name_scope():
        cost = nmt_attention_cost(src_dict_dim=V, trg_dict_dim=V,
                                  word_vector_dim=D, encoder_size=D,
                                  decoder_size=D)
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))

    print(f"# NMT {S}-stage pipeline, B={B} T={T} D={D} V={V} "
          f"({len(params)} params)")
    results = {}
    for mode, make_pt in (
            ("naive", lambda: PipelinedTopology(
                topo, stage_map=nmt_stage_map(S))),
            ("balanced", lambda: PipelinedTopology(
                topo, num_stages=S, balance=True, seq_len_hint=T))):
        print(f"\n## {mode} assignment")
        res = measure_mode(topo, params, mesh, S, T, make_pt,
                           _feeds(B, T, V), iters)
        a = res["acct"]
        per = a["per_stage"]
        print(f"  per-stage params: {a['stage_param_sizes']}  "
              f"(P_max={a['p_max']}, waste {a['param_pad_frac']:.1%})")
        print(f"  boundary widths:  {a['boundary_widths']}  "
              f"(D_max={a['d_max']}, waste {a['boundary_pad_frac']:.1%})")
        print(f"  per-stage flops (est, batch=1): "
              f"{[round(f / 1e6, 2) for f in per['stage_flops']]} MFLOP")
        results[mode] = res

    n, b = results["naive"]["acct"], results["balanced"]["acct"]
    tn = min(dt for _, dt, *_ in results["naive"]["rows"])
    tb = min(dt for _, dt, *_ in results["balanced"]["rows"])
    print(f"\n# balanced vs naive: P_max {n['p_max']} -> {b['p_max']} "
          f"(param waste {n['param_pad_frac']:.1%} -> "
          f"{b['param_pad_frac']:.1%}); D_max {n['d_max']} -> "
          f"{b['d_max']} (boundary buffer "
          f"{b['d_max'] / n['d_max'] - 1:+.1%}); best step "
          f"{tn:.1f} -> {tb:.1f} ms ({tn / tb:.2f}x)")
    return results


if __name__ == "__main__":
    main()
