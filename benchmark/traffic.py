"""The one traffic generator. A mix is a data file, benchmark/traffic/<mix>.json;
this module turns it and a seed into a fixed pool of batches.

Every seed gives the same set of sizes in another order: each batch holds the
same multiset of sequence lengths (the lengths `lo..hi` dealt round-robin over
its rows, then permuted by the seed), so each batch has the same number of
real tokens and the same padded shape, and a rate does not move with the seed.

Column types:
  id_seq        {"vocab_arg", "lengths": [lo, hi], "low_id"}: a list of ids
  id_seq_shift  the same, as two columns: [bos] + w and w + [eos], len(w)+1
                in lo..hi (a decoder's input and its targets)
  dense         {"shape_args": [...]}: a flat float32 vector of random bytes
                scaled to [-0.5, 0.5), as decoded pixels are; an entry of
                shape_args is a number or the name of a model argument
  label         {"classes_arg"}: one class id

"work" names what a batch's work is counted in: {"unit", "column"} counts the
items of that column's lists, {"unit"} alone counts rows.
"""

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(mix):
    with open(os.path.join(HERE, "traffic", mix + ".json")) as f:
        return json.load(f)


def _arg(model_args, v):
    return model_args[v] if isinstance(v, str) else v


def _lengths(rng, lo, hi, n):
    return rng.permutation(np.resize(np.arange(lo, hi + 1), n))


def _column(col, rng, model_args, B):
    """The rows of one column of one batch: a list (or two) of B entries."""
    kind = col["type"]
    if kind in ("id_seq", "id_seq_shift"):
        lo, hi = col["lengths"]
        V = _arg(model_args, col["vocab_arg"])
        lens = _lengths(rng, lo, hi, B)
        if kind == "id_seq":
            return [[rng.integers(col["low_id"], V, int(n)).tolist()
                     for n in lens]]
        words = [rng.integers(col["low_id"], V, int(n) - 1).tolist()
                 for n in lens]
        return [[[col["bos"]] + w for w in words],
                [w + [col["eos"]] for w in words]]
    if kind == "dense":
        dim = int(np.prod([_arg(model_args, s) for s in col["shape_args"]]))
        block = np.frombuffer(rng.bytes(B * dim), np.uint8).astype(np.float32)
        block *= 1.0 / 256.0
        block -= 0.5
        block = block.reshape(B, dim)
        return [list(block)]           # B views of one array: nothing copied
    if kind == "label":
        return [rng.integers(0, _arg(model_args, col["classes_arg"]), B)
                .tolist()]
    raise ValueError(f"traffic column type {kind!r} is not known")


def pool(mix, model_args, seed, batch=None):
    """[(rows, work)] for `pool_batches` batches; rows is a list of tuples as
    a paddle reader yields them."""
    rng = np.random.default_rng(int(seed))
    B = batch or mix["batch"]
    out = []
    for _ in range(mix["pool_batches"]):
        cols = [c for col in mix["columns"]
                for c in _column(col, rng, model_args, B)]
        rows = list(zip(*cols))
        w = mix["work"]
        work = sum(len(r[w["column"]]) for r in rows) if "column" in w else B
        out.append((rows, work))
    return out
