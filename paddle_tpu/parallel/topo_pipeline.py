"""Config-reachable pipeline parallelism: compile a Topology into
heterogeneous GPipe stages from per-layer device annotations.

The reference lets a config pin layers to devices
(proto/ParameterConfig.proto:49 `device`; gserver/gradientmachines/
ParallelNeuralNetwork.cpp dispatches each layer onto its device's thread
and synchronises on input-ready) — model parallelism reachable from the
config surface. The TPU-native form: the same per-layer `device`
annotation (ExtraAttr.device / `device=` layer kwarg) partitions the
layer graph into pipeline stages; microbatches flow stage-to-stage over a
mesh 'stage' axis via `ppermute` (parallel/pipeline.py schedule), and the
whole thing is one differentiable SPMD program, so backward and the
optimizer need nothing special.

Heterogeneity under SPMD: every device runs ONE program that
`lax.switch`es on its stage index. Stage boundaries are flattened into a
single padded [B_mb, D_max] buffer (so every branch has identical
input/output types), and each stage's parameters are flattened into one
row of a padded [S, P_max] matrix sharded over the stage axis. Feeds are
replicated, so data layers (e.g. the label at the final-stage cost)
evaluate locally in whichever stage consumes them — the analog of the
reference feeding every ParallelNeuralNetwork thread the full Argument
vector.

Because D_max and P_max are maxima over stages, BOTH buffers are sized by
the single fattest stage: r5 measured ~33% padding waste from the
naive inherit-from-inputs assignment on the NMT enc|dec split.
:func:`balanced_stage_assignment` (``PipelinedTopology(balance=True)``)
replaces it with a width-balanced partition: per-layer costs (boundary
tensor widths, param rows, forward FLOPs from flops.py) over the
topologically sorted layer chain, then DP over the chain's cut points to
minimize the maximum of (normalized boundary width, per-stage param rows,
per-stage flops), honoring explicit ``stage_map`` pins and
shared-parameter co-location as hard constraints.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P
from paddle_tpu.parallel.pipeline import pipeline_schedule, schedule_ticks

from paddle_tpu.core.arg import Arg, as_arg
from paddle_tpu.core.layer import ForwardContext
from paddle_tpu.core.topology import FEED_TYPES, Topology
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.utils.error import enforce

#: static padding waste of the two stage-uniform buffers (set when the
#: plan's packers/param matrix are built): kind="param" is the [S, P_max]
#: matrix fraction that is padding, kind="boundary" the boundary buffer's.
#: The balancer exists to push these down; tools/pp_accounting.py
#: surfaces them.
_M_PP_PAD = obs_metrics.gauge(
    "paddle_pp_stage_padding_fraction",
    "Fraction of the stage-uniform pipeline buffer that is padding "
    "(kind=param: the [S, P_max] flattened parameter matrix; "
    "kind=boundary: the [B_mb, D_max] inter-stage boundary buffer)",
    labels=("kind",))


def stage_assignment(topology: Topology,
                     stage_map: Optional[Dict[str, int]] = None,
                     num_stages: Optional[int] = None):
    """Per-layer stage ids from explicit ``stage_map`` or the layers'
    ``device`` annotations (ExtraAttr.device / `device=` kwarg, the
    ParameterConfig.proto:49 attr). Unannotated layers inherit the max of
    their inputs' stages (data layers are stage-free: they evaluate where
    consumed). Stages must be monotone along every edge."""
    stages: Dict[str, int] = {}
    for l in topology.layers:
        if l.type in FEED_TYPES:
            continue
        s = None
        if stage_map and l.name in stage_map:
            s = stage_map[l.name]
        else:
            dev = l.attr("device")
            if dev is None and l.extra is not None:
                dev = l.extra.device
            if dev is not None and dev >= 0:    # -1 = reference "CPU" hint
                s = int(dev)
        inherited, src = 0, None
        for i in l.inputs:
            si = stages.get(i.name)
            if si is not None and (src is None or si > inherited):
                inherited, src = si, i.name
        if s is None:
            s = inherited
        enforce(s >= inherited,
                f"stage assignment is non-monotone on edge "
                f"{src!r} (stage {inherited}) -> {l.name!r} (stage {s}): "
                f"a layer cannot consume an output produced in a later "
                f"stage — repin one end of the edge")
        stages[l.name] = s
    used = sorted(set(stages.values()))
    # compact to 0..S-1 (configs may use sparse device ids)
    remap = {v: i for i, v in enumerate(used)}
    stages = {k: remap[v] for k, v in stages.items()}
    S = len(used)
    if num_stages is not None:
        enforce(S == num_stages,
                f"config uses {S} distinct stages but the mesh stage axis "
                f"has {num_stages} devices")
    return stages, S


# --- width-balanced assignment (ISSUE 8 tentpole) -------------------------

def _est_width(topology: Topology, name: str, seq_len_hint: int) -> int:
    """Estimated packed width of one tensor crossing a stage boundary —
    the per-row channel count the _Packer will flatten it to: feature
    size (x T for sequence tensors), plus T mask channels for sequence
    tensors and T seg-id channels for nested ones. ``seq_len_hint``
    stands in for the runtime T (shapes are not known at plan time);
    relative stage comparisons only need a consistent estimate, and when
    the hint equals the runtime T the estimate is exact."""
    info = topology.info(name)
    if info.is_seq:
        w = info.size * seq_len_hint + seq_len_hint       # value + mask
        if info.is_nested:
            w += seq_len_hint                             # seg_ids
        return w
    return max(int(info.size), 1)


def _chain_costs(topology: Topology, seq_len_hint: int,
                 order: str = "alap"):
    """Static per-layer costs over one topological order of the non-feed
    layer chain.

    ``order``: "dfs" keeps the construction (DFS post-order) chain;
    "alap" re-sorts by descending longest path to the sink (stable), so
    a layer sits as close to its consumers as the DAG allows — e.g. the
    NMT target embedding lands next to the decoder instead of transiting
    every boundary from position 0. The balancer's cuts are contiguous
    prefix splits of the chosen chain, so different orders expose
    different families of monotone partitions; the sweep tries both.

    Returns (chain, P, F, cutw, forbidden):
      chain[i]      — layer at chain position i
      P[i]          — parameter elements first owned at position i
      F[i]          — forward FLOPs (flops.py pricing, batch=1, T=hint)
      cutw[j]       — boundary width if a stage cut lands before
                      position j (tensors produced < j, consumed >= j)
      forbidden     — cut positions that would split a shared parameter's
                      consumers across stages (stack_params refuses that)
    """
    from paddle_tpu.flops import layer_fwd_flops

    chain = [l for l in topology.layers if l.type not in FEED_TYPES]
    if order == "alap":
        # longest path to any sink: every edge u->v has dist(u) >
        # dist(v), so descending-dist is a valid topological order too
        dist = {l.name: 0 for l in chain}
        for l in reversed(chain):           # reverse topo order
            for i in l.inputs:
                if i.name in dist:
                    dist[i.name] = max(dist[i.name], dist[l.name] + 1)
        idx = sorted(range(len(chain)), key=lambda i: -dist[chain[i].name])
        chain = [chain[i] for i in idx]     # Python sort is stable
    pos = {l.name: i for i, l in enumerate(chain)}
    L = len(chain)
    P_elems = [0] * L
    F = [0.0] * L
    param_positions: Dict[str, List[int]] = {}
    for i, l in enumerate(chain):
        for suffix, pname in topology._layer_params[l.name].items():
            param_positions.setdefault(pname, []).append(i)
        try:
            F[i] = float(layer_fwd_flops(topology, l, 1, seq_len_hint))
        except Exception:
            F[i] = 0.0
    specs = topology.param_specs()
    forbidden = set()
    for pname, ps in param_positions.items():
        numel = int(np.prod(specs[pname].shape)) or 1
        P_elems[min(ps)] += numel
        # shared parameter: every consumer must land in one stage
        for j in range(min(ps) + 1, max(ps) + 1):
            forbidden.add(j)
    # crossing widths: tensor produced at p, last consumed at q transits
    # every cut j with p < j <= q
    last_use = {}
    for l in chain:
        for i in l.inputs:
            if i.type in FEED_TYPES or i.name not in pos:
                continue
            last_use[i.name] = max(last_use.get(i.name, 0), pos[l.name])
    cutw = [0] * (L + 1)
    for name, q in last_use.items():
        w = _est_width(topology, name, seq_len_hint)
        for j in range(pos[name] + 1, q + 1):
            cutw[j] += w
    return chain, P_elems, F, cutw, forbidden


#: flops tolerance of the lexicographic partition score: candidates
#: whose F_max/F_opt ratios differ by less than this are treated as
#: compute-equal (the flops estimate is matmul-only and can't split
#: finer hairs), and the tie breaks on P_max, then D_max.
_F_TIER = 0.03


def balanced_stage_assignment(topology: Topology, num_stages: int,
                              stage_map: Optional[Dict[str, int]] = None,
                              seq_len_hint: int = 16):
    """Width-balanced layer->stage partition (the fat-stage padding's fix).

    Chooses ``num_stages - 1`` cut points over the ALAP-sorted layer
    chain to minimize the maxima that size the pipeline's uniform
    buffers and critical path: boundary width at any cut (the
    [B_mb, D_max] ppermute buffer), per-stage parameter elements (the
    [S, P_max] row) and per-stage forward FLOPs (the per-tick compute).

    Search: each dimension's best achievable maximum is found by its own
    min-max DP over the chain of valid cut points (the normalizers), an
    epsilon-constraint sweep over candidate boundary caps generates
    Pareto candidates (min-max DP on the normalized param/flop terms +
    a convex leveling pass), and a KL-style single-move refinement
    escapes the chain-contiguity restriction. Candidates are compared
    LEXICOGRAPHICALLY: per-tick flops first (F_max is the schedule's
    critical path — the measured step time tracks it directly, so a
    partition that flattens padding by fattening the busiest stage is a
    net loss; ties within ``_F_TIER``), then P_max (sizes the [S, P_max]
    memory footprint AND the padding ratio), then D_max (per-tick
    ppermute bandwidth).

    ``stage_map`` entries are hard pins: the named layer lands in exactly
    that stage. Shared-parameter consumers always land in one stage
    (stack_params requires it). Free layers keep chain (topological)
    order — a cut is a contiguous prefix split, so the result is
    monotone along every edge by construction.

    Returns (stages, S, report) with ``report`` the
    :func:`assignment_report` of the chosen partition.
    """
    S = int(num_stages)
    if stage_map:
        known = {l.name for l in topology.layers
                 if l.type not in FEED_TYPES}
        for name, st in stage_map.items():
            enforce(name in known,
                    f"stage_map pins unknown layer {name!r}")
            enforce(0 <= int(st) < S,
                    f"stage_map pins {name!r} to stage {st}, outside "
                    f"0..{S - 1}")

    INF = float("inf")
    candidates: List[Dict[str, int]] = []
    P_opt = D_opt = F_opt = INF
    for order in ("alap", "dfs"):
        got = _order_candidates(topology, S, stage_map, seq_len_hint,
                                order)
        if got is None:
            continue
        cands, po, do, fo = got
        candidates.extend(cands)
        P_opt, D_opt, F_opt = min(P_opt, po), min(D_opt, do), min(F_opt, fo)
    enforce(bool(candidates),
            "no width-balanced stage assignment satisfies the stage_map "
            "pins and shared-parameter co-location constraints for "
            f"{S} stages (pins must be feasible in topological order)")
    P_opt, D_opt = max(P_opt, 1.0), max(D_opt, 1.0)
    score_of = _make_scorer(topology, S, seq_len_hint, P_opt, D_opt,
                            F_opt)

    best_score, best_stages = None, None
    seen = set()
    for stages in candidates:
        key = tuple(sorted(stages.items()))
        if key in seen:
            continue
        seen.add(key)
        # KL-style refinement: the DP explores contiguous splits of two
        # chain orders; single-group moves between stages reach the
        # monotone partitions neither chain can express (e.g. the NMT
        # split where the target embedding balances the param rows
        # without fattening the busiest stage)
        stages, score = _refine(topology, stages, S, seq_len_hint,
                                score_of, stage_map)
        if best_score is None or score < best_score:
            best_score, best_stages = score, stages
    return best_stages, S, assignment_report(topology, best_stages, S,
                                             seq_len_hint)


def _make_scorer(topology, S, seq_len_hint, P_opt, D_opt, F_opt):
    """Precompute per-layer costs once and return the O(L)
    lexicographic partition score: (flops tier, P_max ratio, D_max
    ratio). Cheap enough for the refinement's pair-move neighborhood."""
    from paddle_tpu.flops import layer_fwd_flops

    chain = [l for l in topology.layers if l.type not in FEED_TYPES]
    specs = topology.param_specs()
    owner: Dict[str, str] = {}
    P_of: Dict[str, int] = {l.name: 0 for l in chain}
    F_of: Dict[str, float] = {}
    for l in chain:
        for suffix, pname in topology._layer_params[l.name].items():
            if pname not in owner:
                owner[pname] = l.name
                P_of[l.name] += int(np.prod(specs[pname].shape)) or 1
        try:
            F_of[l.name] = float(layer_fwd_flops(topology, l, 1,
                                                 seq_len_hint))
        except Exception:
            F_of[l.name] = 0.0
    # crossing tensors: (producer layer, width, consumer layers)
    cons: Dict[str, List[str]] = {}
    for l in chain:
        for i in l.inputs:
            if i.type not in FEED_TYPES:
                cons.setdefault(i.name, []).append(l.name)
    widths = {n: _est_width(topology, n, seq_len_hint) for n in cons}

    def score_of(stages):
        stage_p = [0] * S
        stage_f = [0.0] * S
        for l in chain:
            s = stages[l.name]
            stage_p[s] += P_of[l.name]
            stage_f[s] += F_of[l.name]
        bw = [0] * max(S - 1, 1)
        for n, cs in cons.items():
            last = max(stages[c] for c in cs)
            for b in range(stages[n], last):
                bw[b] += widths[n]
        d_max = max(bw) if S > 1 else 0
        f_max = max(stage_f) if stage_f else 0.0
        f_tier = int(f_max / F_opt / _F_TIER) if F_opt > 0 else 0
        # P carries 4x the weight of D below the flops tier: P_max sizes
        # the [S, P_max] memory footprint and the padding ratio, while
        # D_max only pays per-tick ppermute bandwidth — but without the
        # D term at all, a marginal P gain can blow the boundary up
        # 1.5x, which real interconnects do notice
        return (f_tier, max(stage_p) / P_opt + 0.25 * d_max / D_opt)

    return score_of


def _refine(topology, stages, S, seq_len_hint, score_of, stage_map):
    """Local descent over ``stages``: move one layer (or one
    shared-parameter co-location group) to any stage the DAG allows —
    at or above every producer, at or below every consumer — keeping
    pins, output/cost layers in the last stage, and every stage
    non-empty. Steepest-descent on single moves until stuck, then one
    round of PAIR moves (the fat stage usually needs a donor AND a
    recipient adjustment at once) and back to single moves."""
    chain = [l for l in topology.layers if l.type not in FEED_TYPES]
    pinned = set(stage_map or ())
    pinned.update(o.name for o in topology.outputs)
    # shared-parameter co-location groups move as one unit
    group_of = {l.name: [l.name] for l in chain}
    by_param: Dict[str, List[str]] = {}
    for l in chain:
        for suffix, pname in topology._layer_params[l.name].items():
            by_param.setdefault(pname, []).append(l.name)
    for members in by_param.values():
        if len(members) > 1:
            merged = sorted({m for n in members for m in group_of[n]})
            for n in merged:
                group_of[n] = merged
    groups = [g for g in {id(g): g for g in group_of.values()}.values()
              if not any(n in pinned for n in g)]
    prods: Dict[str, List[str]] = {l.name: [i.name for i in l.inputs
                                            if i.type not in FEED_TYPES]
                                   for l in chain}
    cons: Dict[str, List[str]] = {}
    for l in chain:
        for i in prods[l.name]:
            cons.setdefault(i, []).append(l.name)

    def moves(stages, g):
        cur = stages[g[0]]
        gset = set(g)
        lo = max((stages[p] for n in g for p in prods[n]
                  if p not in gset), default=0)
        hi = min((stages[c] for n in g for c in cons.get(n, ())
                  if c not in gset), default=S - 1)
        for tgt in range(lo, hi + 1):
            if tgt != cur:
                yield tgt

    def apply(stages, g, tgt):
        trial = dict(stages)
        for n in g:
            trial[n] = tgt
        return trial if len(set(trial.values())) == S else None

    stages = dict(stages)
    score = score_of(stages)
    for _ in range(8 * len(chain)):
        best_move, best_s = None, score
        for g in groups:
            for tgt in moves(stages, g):
                trial = apply(stages, g, tgt)
                if trial is not None:
                    s = score_of(trial)
                    if s < best_s:
                        best_move, best_s = trial, s
        if best_move is None:
            # single moves exhausted: try one pair move (donate from one
            # group while rehoming another) before giving up
            for g1 in groups:
                for t1 in moves(stages, g1):
                    mid = apply(stages, g1, t1)
                    if mid is None:
                        continue
                    for g2 in groups:
                        if g2 is g1:
                            continue
                        for t2 in moves(mid, g2):
                            trial = apply(mid, g2, t2)
                            if trial is not None:
                                s = score_of(trial)
                                if s < best_s:
                                    best_move, best_s = trial, s
            if best_move is None:
                break
        stages, score = best_move, best_s
    return stages, score


def _order_candidates(topology, S, stage_map, seq_len_hint, order):
    """Candidate partitions for one chain order: for every candidate
    boundary-width cap, a min-max DP over the normalized param/flop
    terms plus a convex leveling pass. Returns (candidates, P_opt,
    D_opt, F_opt) — the per-order single-objective optima — or None
    when the constraints are infeasible on this chain."""
    chain, P_elems, F, cutw, forbidden = _chain_costs(topology,
                                                      seq_len_hint, order)
    L = len(chain)
    enforce(L >= S >= 1,
            f"cannot split {L} non-feed layers into {S} pipeline stages")
    pin = [None] * L
    if stage_map:
        pos = {l.name: i for i, l in enumerate(chain)}
        for name, st in stage_map.items():
            pin[pos[name]] = int(st)

    pP = np.concatenate([[0], np.cumsum(P_elems)])
    pF = np.concatenate([[0.0], np.cumsum(F)])
    INF = float("inf")

    def feasible(k, j, i):
        if k > 1 and j in forbidden:
            return False
        return not any(pin[p] is not None and pin[p] != k - 1
                       for p in range(j, i))

    def run_dp(seg_cost, combine):
        """Chain DP: best[k][i] = combined cost of splitting chain[0:i]
        into k stages; seg_cost(k, j, i) prices segment k-1 = [j, i)
        entered through the cut at j (None = infeasible)."""
        best = [[INF] * (L + 1) for _ in range(S + 1)]
        choice = [[-1] * (L + 1) for _ in range(S + 1)]
        best[0][0] = 0.0
        for k in range(1, S + 1):
            for i in range(k, L + 1):
                if k == S and i != L:
                    continue
                for j in range(k - 1, i):
                    if best[k - 1][j] == INF or not feasible(k, j, i):
                        continue
                    c = seg_cost(k, j, i)
                    if c is None:
                        continue
                    cost = combine(best[k - 1][j], c)
                    if cost < best[k][i]:
                        best[k][i] = cost
                        choice[k][i] = j
        return best[S][L], choice

    def cuts_of(choice):
        """(stages dict, cut positions) reconstructed from a DP table."""
        stages, cuts = {}, []
        i = L
        for k in range(S, 0, -1):
            j = choice[k][i]
            for p in range(j, i):
                stages[chain[p].name] = k - 1
            if k > 1:
                cuts.append(j)
            i = j
        return stages, cuts

    # per-dimension achievable optima under the same constraints — the
    # normalizers (ratio 1.0 = as good as that dimension alone can get)
    P_opt, _ = run_dp(lambda k, j, i: float(pP[i] - pP[j]), max)
    if P_opt == INF:
        return None
    P_opt = max(P_opt, 1.0)
    F_opt, _ = run_dp(lambda k, j, i: float(pF[i] - pF[j]), max)
    D_opt, _ = run_dp(lambda k, j, i: float(cutw[j]) if k > 1 else 0.0,
                      max)
    D_opt = max(D_opt, 1.0)

    def pf_ratio(k, j, i, cap):
        if k > 1 and cutw[j] > cap:
            return None
        r = (pP[i] - pP[j]) / P_opt
        if F_opt > 0:
            r = max(r, (pF[i] - pF[j]) / F_opt)
        return r

    caps = sorted({cutw[j] for j in range(1, L) if j not in forbidden}) \
        or [0]
    candidates = []
    for cap in caps:
        m_pf, _ = run_dp(lambda k, j, i: pf_ratio(k, j, i, cap), max)
        if m_pf == INF:
            continue
        bound = m_pf * (1 + 1e-9)

        def balanced_cost(k, j, i):
            r = pf_ratio(k, j, i, cap)
            if r is None or r > bound:
                return None
            p = (pP[i] - pP[j]) / P_opt
            f = (pF[i] - pF[j]) / F_opt if F_opt > 0 else 0.0
            return p * p + f * f

        total, choice = run_dp(balanced_cost, lambda a, b: a + b)
        if total == INF:
            continue
        stages, _cuts = cuts_of(choice)
        candidates.append(stages)
    return candidates, P_opt, D_opt, F_opt


def _segments_of(stages: Dict[str, int], chain) -> List[Tuple[int, int]]:
    """[(start, end)] chain spans of each stage (stages are contiguous
    prefix splits of the chain by construction)."""
    bounds = {}
    for p, l in enumerate(chain):
        s = stages[l.name]
        j, i = bounds.get(s, (p, p + 1))
        bounds[s] = (min(j, p), max(i, p + 1))
    return [bounds[s] for s in sorted(bounds)]


def assignment_report(topology: Topology, stages: Dict[str, int], S: int,
                      seq_len_hint: int = 16) -> Dict[str, object]:
    """Static accounting of ANY stage assignment: per-stage parameter
    elements, forward FLOPs, boundary widths (the balancer's objective,
    visible next to the padding ratios in tools/pp_accounting.py).
    Widths use the same ``seq_len_hint`` estimate the balancer plans
    with — exact when the hint equals the runtime T."""
    from paddle_tpu.flops import layer_fwd_flops

    stage_params = [0] * S
    stage_flops = [0.0] * S
    seen = set()
    specs = topology.param_specs()
    for l in topology.layers:
        if l.type in FEED_TYPES:
            continue
        s = stages[l.name]
        for suffix, pname in topology._layer_params[l.name].items():
            if pname in seen:
                continue
            seen.add(pname)
            stage_params[s] += int(np.prod(specs[pname].shape)) or 1
        try:
            stage_flops[s] += float(layer_fwd_flops(topology, l, 1,
                                                    seq_len_hint))
        except Exception:
            pass
    # boundary b carries tensors produced at stage<=b, consumed at >b
    consumers: Dict[str, int] = {}
    for l in topology.layers:
        if l.type in FEED_TYPES:
            continue
        for i in l.inputs:
            if i.type in FEED_TYPES:
                continue
            consumers[i.name] = max(consumers.get(i.name, 0),
                                    stages[l.name])
    widths = []
    for b in range(S - 1):
        widths.append(sum(_est_width(topology, n, seq_len_hint)
                          for n, last in consumers.items()
                          if stages[n] <= b < last))
    p_max = max(stage_params) if stage_params else 1
    d_max = max(widths) if widths else 0
    return {
        "stage_params": stage_params,
        "stage_flops": stage_flops,
        "boundary_widths": widths,
        "p_max": p_max,
        "d_max": d_max,
        "param_pad_frac": (1.0 - sum(stage_params) / (S * p_max)
                           if p_max else 0.0),
        "boundary_pad_frac": (1.0 - sum(widths) / (len(widths) * d_max)
                              if widths and d_max else 0.0),
    }


class _Packer:
    """Flatten a fixed ordered set of [B, ...] Args into one padded
    [B, D_max] buffer (the uniform boundary type every lax.switch branch
    must share). Sequence Args ride too: the [B, T] mask (and int32
    seg_ids, exact in f32 below 2^24 — _make_packers enforces a >= f32
    boundary dtype when seg_ids cross) are appended as extra float
    channels and reconstructed on unpack, so ragged tensors (the NMT
    encoder's output) can cross stage boundaries."""

    def __init__(self, infos, d_max, dtype):
        # [(name, shape_tail, dtype, mask_dtype|None, has_seg)]
        self.infos = infos
        self.d_max = d_max
        self.dtype = dtype

    def pack(self, args: Dict[str, Arg], batch: int) -> jax.Array:
        parts = []
        for name, tail, _dt, mask_dt, has_seg in self.infos:
            a = args[name]
            parts.append(a.value.reshape(batch, -1).astype(self.dtype))
            if mask_dt is not None:
                parts.append(a.mask.reshape(batch, -1).astype(self.dtype))
            if has_seg:
                parts.append(a.seg_ids.reshape(batch, -1)
                             .astype(self.dtype))
        if not parts:
            return jnp.zeros((batch, self.d_max), self.dtype)
        flat = jnp.concatenate(parts, axis=1)
        pad = self.d_max - flat.shape[1]
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        return flat

    def unpack(self, buf: jax.Array) -> Dict[str, Arg]:
        out, off = {}, 0
        batch = buf.shape[0]
        for name, tail, dt, mask_dt, has_seg in self.infos:
            n = int(np.prod(tail)) if tail else 1
            v = buf[:, off:off + n].reshape((batch,) + tuple(tail))
            off += n
            mask = seg = None
            if mask_dt is not None:
                T = tail[0]
                mask = buf[:, off:off + T].astype(mask_dt)
                off += T
            if has_seg:
                T = tail[0]
                seg = jnp.round(buf[:, off:off + T]).astype(jnp.int32)
                off += T
            out[name] = Arg(v.astype(dt), mask, seg)
        return out


class PipelinedTopology:
    """A Topology compiled into S heterogeneous GPipe stages.

    forward/loss run on a mesh axis (default 'stage') with M microbatches;
    gradients are exact (the pipeline is just a rearranged evaluation
    order, and autodiff flows through scan + ppermute + switch), so
    ``jax.grad`` of :meth:`loss` matches the single-device topology.

    ``balance=True`` replaces the annotation/inherit assignment with the
    width-balanced DP partition (:func:`balanced_stage_assignment`) over
    ``num_stages`` stages; ``stage_map`` entries become hard pins and
    ``seq_len_hint`` prices ragged boundary tensors. The chosen plan's
    static accounting is kept on ``self.plan``.
    """

    def __init__(self, topology: Topology,
                 stage_map: Optional[Dict[str, int]] = None,
                 num_stages: Optional[int] = None,
                 boundary_dtype=jnp.float32,
                 stacked_dtype=jnp.float32,
                 balance: bool = False,
                 seq_len_hint: int = 16):
        self.topology = topology
        enforce(jnp.issubdtype(jnp.dtype(stacked_dtype), jnp.floating),
                f"stacked_dtype must be a float dtype, got "
                f"{jnp.dtype(stacked_dtype).name}")
        if balance:
            enforce(num_stages is not None,
                    "PipelinedTopology(balance=True) needs num_stages= "
                    "(the balancer chooses cuts for a FIXED stage count)")
            self.stages, self.S, self.plan = balanced_stage_assignment(
                topology, num_stages, stage_map, seq_len_hint)
        else:
            self.stages, self.S = stage_assignment(topology, stage_map,
                                                   num_stages)
            self.plan = assignment_report(topology, self.stages, self.S,
                                          seq_len_hint)
        self.boundary_dtype = boundary_dtype
        self.stacked_dtype = jnp.dtype(stacked_dtype)
        self._build_plan()

    # --- static planning --------------------------------------------------
    def _build_plan(self):
        topo = self.topology
        S = self.S
        self.stage_layers: List[List] = [[] for _ in range(S)]
        for l in topo.layers:
            if l.type in FEED_TYPES:
                continue
            self.stage_layers[self.stages[l.name]].append(l)
        # boundary b carries every non-feed tensor produced at stage<=b and
        # consumed at stage>b (tensors transit intermediate stages)
        consumers: Dict[str, int] = {}
        for l in topo.layers:
            if l.type in FEED_TYPES:
                continue
            for i in l.inputs:
                if i.type in FEED_TYPES:
                    continue
                consumers[i.name] = max(consumers.get(i.name, 0),
                                        self.stages[l.name])
        self.boundaries: List[List[str]] = []
        for b in range(S - 1):
            names = sorted(n for n, last in consumers.items()
                           if self.stages[n] <= b < last)
            self.boundaries.append(names)
        # packer infos per boundary need concrete shape tails; resolved at
        # trace time from the layer ArgInfos (dense [B, size] crossings)
        self._packers: Optional[List[_Packer]] = None
        self._out_packers: Dict[Tuple[str, ...], _Packer] = {}

    def _packer_infos(self, names: Sequence[str], outs_by_name):
        """(infos, width) for one packed buffer over ``names`` — shared
        by the stage boundaries and the last-stage eval-output buffer."""
        infos = []
        width = 0
        for n in names:
            a = outs_by_name[n]
            enforce(jnp.issubdtype(a.value.dtype, jnp.floating),
                    f"pipeline boundary tensor {n!r} is "
                    f"{a.value.dtype}; integer/bool tensors cannot "
                    "ride the float boundary buffer — co-locate "
                    "producer and consumer in one stage")
            if a.seg_ids is not None:
                # seg ids round-trip through the float boundary buffer;
                # anything below f32 (or ids >= 2^24) would corrupt
                # them silently
                enforce(jnp.finfo(self.boundary_dtype).nmant >= 23,
                        f"boundary tensor {n!r} carries seg_ids, which "
                        f"need >= f32 to ride the boundary buffer "
                        f"exactly; boundary_dtype is "
                        f"{jnp.dtype(self.boundary_dtype).name}")
            tail = tuple(a.value.shape[1:])
            infos.append((n, tail, a.value.dtype,
                          None if a.mask is None else a.mask.dtype,
                          a.seg_ids is not None))
            width += int(np.prod(tail)) if tail else 1
            if a.mask is not None:
                width += tail[0]
            if a.seg_ids is not None:
                width += tail[0]
        return infos, width

    def _make_packers(self, outs_by_name):
        infos_per_b, widths = [], []
        d_max = 1
        for names in self.boundaries:
            infos, width = self._packer_infos(names, outs_by_name)
            infos_per_b.append(infos)
            widths.append(width)
            d_max = max(d_max, width)
        if widths:
            _M_PP_PAD.labels(kind="boundary").set(
                1.0 - sum(widths) / (len(widths) * d_max))
        return [_Packer(infos, d_max, self.boundary_dtype)
                for infos in infos_per_b], d_max

    # --- parameter flattening --------------------------------------------
    def stage_param_names(self) -> List[List[str]]:
        topo = self.topology
        names: List[List[str]] = [[] for _ in range(self.S)]
        seen = {}
        for l in topo.layers:
            if l.type in FEED_TYPES:
                continue
            s = self.stages[l.name]
            for suffix, pname in topo._layer_params[l.name].items():
                if pname in seen:
                    enforce(seen[pname] == s,
                            f"parameter {pname!r} is shared across stages "
                            f"{seen[pname]} and {s}; pin both layers to one "
                            "stage")
                    continue
                seen[pname] = s
                names[s].append(pname)
        return [sorted(ns) for ns in names]

    def stack_params(self, params: Dict[str, jax.Array]):
        """dict -> ([S, P_max] matrix, per-stage unflatten records).

        The matrix dtype is ``stacked_dtype`` (default f32). A bf16
        matrix halves the stage-sharded footprint: params are rounded to
        bf16 at stacking (inside the jitted step) and widened back per
        stage by ``_unflatten_row``'s astype, so the caller's master
        params stay f32 and gradients flow through both casts."""
        per_stage = self.stage_param_names()
        recs, rows, p_max = [], [], 1
        for ns in per_stage:
            rec = [(n, tuple(params[n].shape), params[n].dtype) for n in ns]
            recs.append(rec)
            p_max = max(p_max, sum(int(np.prod(s)) or 1 for _, s, _ in rec))
        sizes = [sum(int(np.prod(s)) or 1 for _, s, _ in rec)
                 for rec in recs]
        if sizes:
            _M_PP_PAD.labels(kind="param").set(
                1.0 - sum(sizes) / (len(sizes) * p_max))
        for rec in recs:
            if rec:
                row = jnp.concatenate(
                    [jnp.asarray(params[n]).astype(self.stacked_dtype)
                     .reshape(-1) for n, _, _ in rec])
            else:
                row = jnp.zeros((0,), self.stacked_dtype)
            rows.append(jnp.pad(row, (0, p_max - row.shape[0])))
        self._param_recs = recs
        return jnp.stack(rows)

    def unstack_params(self, stacked: jax.Array) -> Dict[str, jax.Array]:
        out = {}
        for s, rec in enumerate(self._param_recs):
            off = 0
            for n, shape, dt in rec:
                k = int(np.prod(shape)) if shape else 1
                out[n] = stacked[s, off:off + k].reshape(shape).astype(dt)
                off += k
        return out

    def _unflatten_row(self, row, rec):
        out, off = {}, 0
        for n, shape, dt in rec:
            k = int(np.prod(shape)) if shape else 1
            out[n] = row[off:off + k].reshape(shape).astype(dt)
            off += k
        return out

    # --- stage bodies -----------------------------------------------------
    def _run_stage(self, s, params, boundary_in: Dict[str, Arg], feeds,
                   rng=None, training: bool = True):
        topo = self.topology
        ctx = ForwardContext(training=training, rng=rng, mesh=None)
        ctx.outputs.update(boundary_in)
        for l in topo.layers:
            if l.type in FEED_TYPES:
                ctx.outputs[l.name] = as_arg(feeds[l.name])
        for l in self.stage_layers[s]:
            lparams = {suffix: params[pname]
                       for suffix, pname in topo._layer_params[l.name].items()}
            ins = [ctx.outputs[i.name] for i in l.inputs]
            with jax.named_scope(l.scope):     # as Topology.forward does
                ctx.outputs[l.name] = l.forward(lparams, ins, ctx)
        return ctx.outputs

    # --- public API -------------------------------------------------------
    def loss(self, stacked_params, feeds_mb, mesh: Mesh,
             cost_layer: Optional[str] = None, axis_name: str = "stage",
             remat: bool = False, rng=None, data_axis: Optional[str] = None,
             training: bool = True,
             eval_outputs: Optional[Sequence[str]] = None):
        """Mean cost over microbatches, evaluated as a GPipe pipeline.

        feeds_mb: {name: [M, B_mb, ...]} microbatched dense feeds.
        ``data_axis``: optional second mesh axis for PP x DP composition —
        each data-shard pipelines its slice of every microbatch and the
        losses average over the axis (so grads of the mean match
        single-device exactly for equal shards). ``rng`` (optional) seeds
        stochastic layers (dropout): each (data shard, microbatch, stage)
        gets its own fold. Returns a scalar differentiable w.r.t.
        ``stacked_params``.

        ``eval_outputs``: names of LAST-stage layers whose full-batch
        outputs the caller needs back (evaluator inputs under the
        pipeline-parallel trainer). They ride a second uniform buffer
        emitted only by the last stage, are reassembled across
        microbatches outside the schedule, and turn the return value
        into ``(cost, {name: Arg})``. Not composable with ``data_axis``
        (the reassembled batch would be data-sharded).
        """
        topo = self.topology
        enforce(hasattr(self, "_param_recs"),
                "loss() requires stack_params() to have been called on this "
                "PipelinedTopology first (it records per-stage flattening)")
        enforce(mesh.shape[axis_name] == self.S,
                f"mesh axis {axis_name!r} has {mesh.shape[axis_name]} "
                f"devices but the config uses {self.S} stages")
        cost_name = cost_layer or topo.outputs[0].name
        enforce(self.stages[cost_name] == self.S - 1,
                f"cost layer {cost_name!r} must live in the last stage "
                f"({self.S - 1}), got {self.stages[cost_name]}")
        eval_outputs = tuple(eval_outputs) if eval_outputs else ()
        enforce(not (eval_outputs and data_axis is not None),
                "eval_outputs does not compose with data_axis (the "
                "reassembled eval batch would be sharded over the data "
                "axis); run evaluators outside the pipeline instead")
        for n in eval_outputs:
            enforce(n in self.stages,
                    f"eval output {n!r} is not a non-feed layer of this "
                    "topology (feeds are replicated — read them from the "
                    "feed dict instead)")
            enforce(self.stages[n] == self.S - 1,
                    f"eval output {n!r} lives in stage {self.stages[n]}; "
                    f"only last-stage ({self.S - 1}) outputs can be "
                    "collected — pin it there (stage_map) or drop the "
                    "evaluator")
        M = jax.tree_util.tree_leaves(feeds_mb)[0].shape[0]
        B_mb = jax.tree_util.tree_leaves(feeds_mb)[0].shape[1]
        if data_axis is not None:
            enforce(data_axis != axis_name,
                    "data_axis must differ from the pipeline stage axis")
            enforce(data_axis in mesh.shape,
                    f"mesh has no {data_axis!r} axis "
                    f"(axes: {tuple(mesh.axis_names)})")
            dsize = mesh.shape[data_axis]
            enforce(B_mb % dsize == 0,
                    f"microbatch size {B_mb} not divisible by the "
                    f"{data_axis!r} axis ({dsize} shards)")
            B_mb = B_mb // dsize            # branches see LOCAL batches

        # trace one microbatch through the plain topology to size packers
        if self._packers is None or (
                eval_outputs and eval_outputs not in self._out_packers):
            probe = {k: jax.eval_shape(
                        lambda a: jax.tree_util.tree_map(lambda x: x[0], a),
                        v)
                     for k, v in feeds_mb.items()}
            outs = jax.eval_shape(
                lambda p, f: {k: a for k, a in topo.forward(
                    self.unstack_params(p), f, training=True,
                    rng=jax.random.PRNGKey(0)).items()},
                stacked_params, probe)
            outs = {k: as_arg(v) if not isinstance(v, Arg) else v
                    for k, v in outs.items()}
            if self._packers is None:
                self._packers, self._d_max = self._make_packers(outs)
            if eval_outputs and eval_outputs not in self._out_packers:
                # the eval buffer rides the schedule's aux (stage-local,
                # never ppermuted), so it stays f32 even when the
                # inter-stage boundary is bf16: evaluator totals remain
                # bit-identical to the unpipelined model
                infos, width = self._packer_infos(eval_outputs, outs)
                self._out_packers[eval_outputs] = _Packer(
                    infos, max(width, 1), jnp.float32)

        packers, d_max = self._packers, self._d_max
        out_packer = self._out_packers[eval_outputs] if eval_outputs \
            else None
        recs = self._param_recs
        S = self.S

        if rng is None:
            rng = jnp.zeros((2,), jnp.uint32)   # unused unless dropout asks
            have_rng = False
        else:
            have_rng = True

        def branch(s):
            def run(p_row, x_flat, feeds_one, rng_mb):
                params = self._unflatten_row(p_row, recs[s])
                b_in = packers[s - 1].unpack(x_flat) if s > 0 else {}
                stage_rng = (jax.random.fold_in(rng_mb, s)
                             if have_rng else None)
                outs = self._run_stage(s, params, b_in, feeds_one,
                                       stage_rng, training)
                if s < S - 1:
                    outs.update(b_in)       # transit tensors ride through
                    y = packers[s].pack(outs, B_mb)
                    o = (jnp.zeros((B_mb, out_packer.d_max),
                                   out_packer.dtype)
                         if out_packer is not None else jnp.zeros((),
                                                                  jnp.float32))
                    return y, (jnp.zeros((), jnp.float32), o)
                # last stage: the per-microbatch mean cost rides the
                # schedule's aux (stage-local, never permuted) as f32 so
                # a bf16 boundary_dtype cannot round it; the boundary
                # buffer itself wraps to stage 0 unused
                c = outs[cost_name].value
                c = jnp.mean(c.astype(jnp.float32))
                y = jnp.zeros((B_mb, d_max), self.boundary_dtype)
                o = (out_packer.pack(outs, B_mb)
                     if out_packer is not None else jnp.zeros((),
                                                              jnp.float32))
                return y, (c, o)
            return jax.checkpoint(run) if remat else run

        branches = [branch(s) for s in range(S)]

        def local(p_stacked, feeds, rng_base):
            s = jax.lax.axis_index(axis_name)
            if data_axis is not None and have_rng:
                # decorrelate dropout across data shards
                rng_base = jax.random.fold_in(
                    rng_base, jax.lax.axis_index(data_axis))
            p_row = p_stacked[0]
            zero = jnp.zeros((B_mb, d_max), self.boundary_dtype)
            is_last = s == S - 1

            def step(mb, active, stage_in):
                f_mb = jax.tree_util.tree_map(lambda a: a[mb], feeds)
                rng_mb = jax.random.fold_in(rng_base, mb) if have_rng \
                    else rng_base
                return jax.lax.switch(s, branches, p_row, stage_in, f_mb,
                                      rng_mb)

            def emit(mb, active, y, aux):
                # last-stage active ticks contribute their microbatch's
                # mean cost (carried on the f32 aux, not the boundary
                # buffer); every other stage emits zeros, so the psum
                # below is just the sum over microbatches
                c_mb, o = aux
                c = jnp.where(active & is_last, c_mb,
                              jnp.zeros((), jnp.float32))
                if out_packer is None:
                    return c
                return c, jnp.where(active & is_last, o,
                                    jnp.zeros_like(o))

            emitted = pipeline_schedule(step, emit, zero, s, M, S,
                                        axis_name)
            costs = emitted[0] if out_packer is not None else emitted
            total = jax.lax.psum(costs.sum(), axis_name) / M
            if data_axis is not None:
                total = jax.lax.pmean(total, data_axis)
            if out_packer is None:
                return total
            # the last stage ran microbatch mb at tick mb + S - 1: the
            # static tail slice of the tick axis is the [M, B_mb, o_max]
            # eval buffer (zeros everywhere else before the psum)
            outs_mb = jax.lax.psum(emitted[1], axis_name)[S - 1:]
            return total, outs_mb

        feeds_spec = P() if data_axis is None else P(None, data_axis)
        out_specs = P() if out_packer is None else (P(), P())
        res = shard_map(
            local, mesh=mesh,
            in_specs=(P(axis_name), feeds_spec, P()), out_specs=out_specs,
            check_vma=False)(stacked_params, feeds_mb, rng)
        if out_packer is None:
            return res
        total, outs_mb = res
        per_mb = [out_packer.unpack(outs_mb[m]) for m in range(M)]
        full = {}
        for name in eval_outputs:
            full[name] = Arg(
                jnp.concatenate([per_mb[m][name].value for m in range(M)]),
                (jnp.concatenate([per_mb[m][name].mask for m in range(M)])
                 if per_mb[0][name].mask is not None else None),
                (jnp.concatenate([per_mb[m][name].seg_ids
                                  for m in range(M)])
                 if per_mb[0][name].seg_ids is not None else None))
        return total, full


def microbatch(feeds: Dict[str, jax.Array], num_micro: int):
    """Split [B, ...] feeds into [M, B/M, ...] microbatches. Sequence
    feeds ride as Arg (value/mask/seg_ids each split along batch)."""

    def split(v):
        v = jnp.asarray(v)
        enforce(v.shape[0] % num_micro == 0,
                f"batch {v.shape[0]} not divisible by {num_micro} "
                "microbatches")
        return v.reshape((num_micro, v.shape[0] // num_micro)
                         + tuple(v.shape[1:]))

    out = {}
    for k, v in feeds.items():
        if isinstance(v, Arg):
            out[k] = Arg(split(v.value),
                         None if v.mask is None else split(v.mask),
                         None if v.seg_ids is None else split(v.seg_ids))
        else:
            out[k] = split(v)
    return out
