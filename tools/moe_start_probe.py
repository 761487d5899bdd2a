"""python3 tools/moe_start_probe.py --seeds 1,2,3 [--set NAME=VALUE ...]
(on the chip: chiprun -- python3 tools/moe_start_probe.py --seeds ... --set
EMBEDDING_START=0.0221)

Whether a start makes positions route alike (PERF.md section 6, PR 32): for
each seed the plain reference of `kimi-vl-a3b-ep8` (float32, the benchmark's
weights and traffic from the seed, no trainer) makes ONE forward pass over
the first batch, block by block, and the probe prints, a MoE layer, the share
of (token, chosen expert) pairs that fall on the experts this share holds and
the busiest expert's load over the mean of all 64. Where every position of a
layer routes alike the held share follows the seed's weights (0.8-31.1% in
SDAR at its old start); where positions differ it stays near held / experts.
`--set` moves a start the reference's `param_table` reads from a module
constant (EMBEDDING_START) for this reading alone.
One JSON line a seed, and a summary line. A rehearsal configuration runs on
the CPU (`--config rehearsal-kimivl --traffic rehearsal-lm`).
"""
import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import correct, run, traffic  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--config", default="kimi-vl-a3b-ep8")
    ap.add_argument("--traffic", default="lm8192-b2")
    ap.add_argument("--set", action="append", default=[])
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp

    config = run.load_json("configs", a.config + ".json")
    ref = correct.load_module(config["reference"])
    for item in a.set:
        name, value = item.split("=")
        assert hasattr(ref, name), name
        setattr(ref, name, float(value))
    args = config["model"]["args"]
    mix = dict(traffic.load(a.traffic), pool_batches=1)
    first, held = args["first_expert"], args["experts_held"]
    q = correct.load_module("reference/lowprec.py").BY_NAME["none"]
    block = jax.jit(lambda pb, x, real, dense: ref._block(pb, x, real, args, q,
                                                          dense),
                    static_argnames="dense")
    shares = []
    with jax.default_matmul_precision("highest"):
        for seed in (int(s) for s in a.seeds.split(",")):
            p = correct.init_params(ref.param_table(args), seed)
            rows = traffic.pool(mix, args, seed)[0][0]
            b = {k: jnp.asarray(v) for k, v in ref.pad(rows, args).items()}
            counts = {}
            for r in range(b["ids"].shape[0]):
                x = p[f"_{args.get('name', 'k')}_emb.w0"][b["ids"][r]]
                for l in range(args["num_hidden_layers"]):
                    x, c = block(ref._block_params(p, args, l), x,
                                 b["ids_mask"][r], dense=ref.is_dense(args, l))
                    if c is not None:
                        counts[l] = counts.get(l, 0) + c
            by_layer = {l: float(c[first:first + held].sum() / c.sum())
                        for l, c in counts.items()}
            pairs = float(sum(c[first:first + held].sum()
                              for c in counts.values()))
            shares.append(pairs / float(sum(c.sum() for c in counts.values())))
            print(json.dumps({
                "seed": seed, "set": a.set, "held_share_by_layer": by_layer,
                "held_pairs": pairs, "held_share": shares[-1],
                "load_max_over_mean": {l: float(c.max() / c.mean())
                                       for l, c in counts.items()},
                "platform": jax.default_backend()}), flush=True)
            del p
    qs = statistics.quantiles(shares, n=4) if len(shares) > 1 else shares * 3
    print(json.dumps({"set": a.set, "seeds": len(shares),
                      "held_share_min_median_max": [
                          min(shares), statistics.median(shares), max(shares)],
                      "iqr_over_median": (qs[2] - qs[0]) / qs[1]}), flush=True)


if __name__ == "__main__":
    main()
