"""Beam-search decode sweep: dense vs selective vs compact-K.

The r8 tentpole's evidence harness (BENCH_EXTRA_r08.md): for each vocab
size V and beam width, measure one jitted generation call
(networks.gru_encoder_decoder(is_generating=True)) through the three
decode paths (docs/decode.md):

  dense     — full-vocab projection, beam top-k over [B*beam, V]
  selective — selective_fc gather projection (r6), beam still O(V)/tick
  compact   — compact-K: projection AND beam in candidate space (r8)

By default the sweep disables the length model (no eos is ever emitted,
every tick runs) so the per-tick cost structure is isolated from
early-exit savings — the r6-comparable protocol; --term adds the
bench.py output-length schedule to also show the early-exit win.

Run:  python tools/decode_sweep.py [--quick] [--vs 65536,...] [--beams 1,4]
      [--k 1024] [--iters 3] [--term]
Prints one markdown table per beam width, one row per V.
"""

from __future__ import annotations

import argparse
import os
import sys

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(V, beam, K, mode, batch=16, seq_len=10, max_length=16,
            iters=3, term=False):
    """One grid cell through bench.py's exact decode protocol (shared
    builder, feed construction, warmup + 3x-median timing — one source
    of truth); returns (tokens/sec, ticks executed)."""
    from bench import bench_nmt_decode

    r = bench_nmt_decode(batch=batch, seq_len=seq_len, beam=beam,
                         max_length=max_length, cand_k=min(K, V),
                         iters=iters, V=V, mode=mode, length_model=term)
    return r["value"], r["extra"]["mean_ticks_executed"]


MODES = ("dense", "selective", "compact")


def run_sweep(vs, beams, K=1024, iters=3, batch=16, seq_len=10,
              max_length=16, term=False, emit=print):
    """Full grid; returns {(V, beam, mode): (tokens/sec, ticks)}. ``emit``
    receives markdown lines (pass a no-op for programmatic use)."""
    results = {}
    dev = jax.devices()[0]
    emit(f"platform: {dev.platform} "
         f"({getattr(dev, 'device_kind', '?')}), B={batch} "
         f"src_len={seq_len} max_length={max_length} K={K} "
         f"term={'on' if term else 'off'}")
    for beam in beams:
        emit(f"\nbeam={beam} (tokens/sec; ticks in parens when <max):\n"
             f"| V | dense | selective (K={K}) | compact-K |\n"
             f"|---|---|---|---|")
        for V in vs:
            cells = []
            for mode in MODES:
                tps, ticks = measure(V, beam, K, mode, batch, seq_len,
                                     max_length, iters, term)
                results[(V, beam, mode)] = (tps, ticks)
                cell = f"{tps:.1f}"
                if ticks < max_length:
                    cell += f" ({ticks}t)"
                cells.append(cell)
            emit(f"| {V} | " + " | ".join(cells) + " |")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny grid for smoke-testing the harness itself")
    ap.add_argument("--vs", default="30000,65536,131072,262144,524288,1048576")
    ap.add_argument("--beams", default="1,4")
    ap.add_argument("--k", type=int, default=1024)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--term", action="store_true",
                    help="add the bench.py output-length schedule (early "
                         "exit fires; default isolates per-tick cost)")
    args = ap.parse_args()
    if args.quick:
        run_sweep(vs=[2000], beams=[2], K=64, iters=1, batch=4, seq_len=6,
                  max_length=12, term=args.term)
        return
    run_sweep(vs=[int(v) for v in args.vs.split(",")],
              beams=[int(b) for b in args.beams.split(",")],
              K=args.k, iters=args.iters, term=args.term)


if __name__ == "__main__":
    import paddle_tpu

    paddle_tpu.compile_cache()
    main()
