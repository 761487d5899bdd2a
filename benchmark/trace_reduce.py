"""From a profiler trace (.xplane.pb) to the numbers the per-layer metrics
read. A pure function of the file: nothing here looks at the device or the
program. Read with `jax.profiler.ProfileData`, which needs only JAX.

A TPU's plane is named `/device:TPU:<n>`; its line `XLA Ops` holds one event
per executed HLO operation (the instruction's whole text as its name, start,
duration; `short_name` keeps the instruction's name and opcode). The host's planes hold
the `bench:<phase>` spans that benchmark/program.py writes around the
trainer's loop from its own reader and event handler.

  busy        union of the op intervals of a device, in the traced window
  window      from the first op start to the last op end over all devices
  idle share  1 - busy / window, averaged over the devices
  per op      summed duration by op name (the names the trace gives)
  collectives the part of collective ops' time in which no other op runs on
              that device (exposed), and their total
  gaps        every idle interval of device 0 longer than `min_gap_ns`, put
              to the bench phase that overlaps it most
"""

import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
PHASE_PREFIX = "bench:"


def short_name(text):
    """`%fusion.3 = f32[8]{0} fusion(...)` -> `fusion.3 fusion`: the
    instruction's name and its opcode, from the HLO text the trace gives as an
    op's name. Other names come back as they are."""
    if not text.startswith("%") or " = " not in text:
        return text
    name, rest = text[1:].split(" = ", 1)
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            if ch == "(" and depth == 0 and i and rest[i - 1] not in " ,":
                j = rest.rfind(" ", 0, i)
                return f"{name} {rest[j + 1:i]}"
            depth += 1
        elif ch in ")]}":
            depth -= 1
    return name


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    """{"devices": {n: [(name, start_ns, dur_ns)]},
        "phases": [(phase, start_ns, dur_ns)]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, phases = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[int(m.group(1))] = [
                    (short_name(e.name), int(e.start_ns), int(e.duration_ns))
                    for e in line.events]
            elif not m:
                for e in line.events:
                    if e.name.startswith(PHASE_PREFIX):
                        phases.append((e.name[len(PHASE_PREFIX):],
                                       int(e.start_ns), int(e.duration_ns)))
    return {"devices": devices, "phases": sorted(phases, key=lambda p: p[1])}


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(merged):
    return sum(e - s for s, e in merged)


def _subtract(a, b):
    """Length of the merged intervals `a` not covered by the merged `b`."""
    total, j = 0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def reduce(trace, min_gap_ns=50_000, top=10):
    """See the module's docstring. Times in seconds."""
    devs = trace["devices"]
    if not devs or not any(devs.values()):
        return None
    t0 = min(ev[1] for evs in devs.values() for ev in evs)
    t1 = max(ev[1] + ev[2] for evs in devs.values() for ev in evs)
    window = t1 - t0
    busy, exposed, coll_total = [], [], []
    per_op = defaultdict(int)
    for n, evs in sorted(devs.items()):
        merged = union((s, s + d) for _, s, d in evs)
        busy.append(_length(merged))
        coll = union((s, s + d) for name, s, d in evs
                     if COLLECTIVE.match(name))
        rest = union((s, s + d) for name, s, d in evs
                     if not COLLECTIVE.match(name))
        coll_total.append(_length(coll))
        exposed.append(_subtract(coll, rest))
        if n == min(devs):
            for name, _, d in evs:
                per_op[name] += d
            first = merged
    gaps = defaultdict(int)
    longest = []
    prev_end = t0
    for s, e in first + [(t1, t1)]:
        if s - prev_end >= min_gap_ns:
            phase = _phase_of(trace["phases"], prev_end, s)
            gaps[phase] += s - prev_end
            longest.append((s - prev_end, phase))
        prev_end = max(prev_end, e)
    n = len(busy)
    return {
        "window_s": window / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "idle_share": 1.0 - sum(busy) / n / window,
        "collective_s": sum(coll_total) / n / 1e9,
        "collective_exposed_s": sum(exposed) / n / 1e9,
        "device_ops": [[k, v / 1e9] for k, v in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
        "longest_gap_s": max(longest)[0] / 1e9 if longest else 0.0,
        "per_op_s": {k: v / 1e9 for k, v in per_op.items()},
        "devices": n,
    }


def _phase_of(phases, s, e):
    """The bench phase that covers most of [s, e); 'unattributed' if none."""
    best, name = 0, "unattributed"
    for ph, ps, pd in phases:
        if ps >= e:
            break
        ov = min(e, ps + pd) - max(s, ps)
        if ov > best:
            best, name = ov, ph
    return name
