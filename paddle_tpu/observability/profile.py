"""python -m paddle_tpu.observability.profile <trace_dir | file.xplane.pb> [--json]

The program's reader of its own profile: where a step's device time goes, by
the scopes the program writes, and which host phase each idle gap of the chip
falls in. A pure function of the ``.xplane.pb`` that ``jax.profiler.start_trace``
leaves under ``<dir>/plugins/profile/<run>/``; standard library only, so that
importing it costs a run's set-up nothing worth naming.

Why not ``jax.profiler.ProfileData``: it gives an ``XLA Ops`` event its name
(the HLO instruction's text) and three stats of the event itself. The scope
(``jit(step)/jvp(sdar/l0/attn)/while/body/.../dot_general``) is a stat of the
event's *metadata*, ``tf_op``, beside ``hlo_category``, ``flops`` and
``bytes_accessed``, and ``ProfileData`` does not expose metadata stats. So
this module reads the file's protobuf wire format itself
(tsl/profiler/protobuf/xplane.proto; the field numbers are in ``load``).

What it reports (``reduce``):

  busy, window, idle share   as benchmark/trace_reduce.py defines them (union
      of a device's op intervals; first op start to last op end over all
      devices; 1 - mean busy / window), on the same truncated nanoseconds, so
      the two agree to the digit
  by scope and direction     an op's scope is its ``tf_op`` with JAX's wrappers
      taken off (``scope_of``); direction is ``bwd`` under a ``transpose(``.
      The ops of a ``while`` body are events inside the loop's own event, so
      time is SELF time: an event's duration less the events it contains. A
      row loop's kernels, its XLA part and the loop's own overhead are then
      three numbers that add up, and the scopes sum to busy
  inside a scope             seconds of each Mosaic kernel by the name its
      ``pl.pallas_call`` gave it, seconds by ``hlo_category`` (the products,
      the elementwise loops, the layout copies), and the largest ops with
      ``flops`` and ``bytes_accessed`` where the trace has them
  (none)                     ops no scope names, by ``hlo_category``
  idle gaps of device 0      every gap of at least 50 us, put to the innermost
      ``paddle:`` span (utils/stat.py ``timer_scope``; the loop's phases) that
      covers most of it, with the span's ``step``

An executable loaded from a compile-cache entry that another tree wrote carries
that tree's scopes (the cache key leaves metadata out): take a profile meant to
be read by scope with ``JAX_COMPILATION_CACHE_DIR`` pointed at an empty
directory. More than half of the busy time without a scope prints a warning.
"""

import glob
import json
import os
import re
import struct
import sys
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "paddle:"
MIN_GAP_NS = 50_000
#: a layer's scope (up to three parts as the decoder models write them:
#: ``qwen3next/l0/mixer``) and two levels under it
SCOPE_DEPTH = 5
NONE = "(none)"
UNATTRIBUTED = "unattributed"
#: how much of each list a reduction keeps and the table prints
TOP_OPS, TOP_GAPS, TABLE_ROWS, DETAIL_ROWS = 5, 10, 40, 8


# ---- the wire format --------------------------------------------------------

def _varint(buf, pos):
    b = buf[pos]
    pos += 1
    if b < 0x80:
        return b, pos
    value, shift = b & 0x7F, 7
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, pos
        shift += 7


def _fields(buf, pos, end):
    """(field number, wire type, value) of a message's top level. A varint is
    an int; a length-delimited value is its (start, end) in ``buf``; a fixed
    value is its bytes."""
    while pos < end:
        tag, pos = _varint(buf, pos)
        wire = tag & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value = (pos, pos + n)
            pos += n
        elif wire == 1:
            value = buf[pos:pos + 8]
            pos += 8
        elif wire == 5:
            value = buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not an XSpace")
        yield tag >> 3, wire, value


def _text(buf, span):
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _signed(value):
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, span, stat_names):
    """(name, value) of an XStat{metadata_id 1, double 2, uint64 3, int64 4,
    str 5, bytes 6, ref 7}; a ref is the name of the stat metadata it points
    to."""
    name = value = None
    for f, _, v in _fields(buf, *span):
        if f == 1:
            name = stat_names.get(v)
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = _text(buf, v)
        elif f == 6:
            value = buf[v[0]:v[1]]
        elif f == 7:
            value = stat_names.get(v)
    return name, value


def _event(buf, span):
    """(metadata id, offset ps, duration ps, [stat spans]) of an
    XEvent{metadata_id 1, offset_ps 2, duration_ps 3, stats 4,
    num_occurrences 5}."""
    mid = off = dur = 0
    stats = []
    for f, _, v in _fields(buf, *span):
        if f == 1:
            mid = v
        elif f == 2:
            off = v
        elif f == 3:
            dur = v
        elif f == 4:
            stats.append(v)
    return mid, off, dur, stats


def _event_metadata_id(buf, span):
    """An XEvent's metadata id alone. A host line holds hundreds of thousands
    of events of which a few are the program's spans: the id is the first
    field as the profiler writes it, one varint to read before deciding."""
    pos = span[0]
    if pos < span[1] and buf[pos] == 0x08:
        return _varint(buf, pos + 1)[0]
    return _event(buf, span)[0]


def _map_entry(buf, span):
    """(key, value span) of a protobuf map's entry (key 1, value 2)."""
    key, value = 0, (span[1], span[1])
    for f, _, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _planes(buf):
    """Every XPlane{id 1, name 2, lines 3, event_metadata 4 (map),
    stat_metadata 5 (map), stats 6} of an XSpace{planes 1} as (name, [line
    spans], {id: event metadata span}, {id: stat name})."""
    for f, _, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for g, _, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
            elif g == 3:
                lines.append(v)
            elif g == 4:
                key, value = _map_entry(buf, v)
                event_meta[key] = value
            elif g == 5:
                key, value = _map_entry(buf, v)
                # XStatMetadata{id 1, name 2}
                stat_names[key] = next(
                    (_text(buf, s) for h, _, s in _fields(buf, *value)
                     if h == 2), "")
        yield name, lines, event_meta, stat_names


def _line(buf, span):
    """(name, timestamp ns, [event spans]) of an XLine{id 1, name 2,
    timestamp_ns 3, events 4, duration_ps 9, display_id 10, display_name
    11}."""
    name, t0, events = "", 0, []
    for f, _, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            t0 = _signed(v)
        elif f == 4:
            events.append(v)
    return name, t0, events


def _event_meta(buf, span, stat_names):
    """{"name", "display_name", stat name: value, ...} of an
    XEventMetadata{id 1, name 2, metadata 3, display_name 4, stats 5,
    child_id 6}."""
    out = {"name": "", "display_name": ""}
    for f, _, v in _fields(buf, *span):
        if f == 2:
            out["name"] = _text(buf, v)
        elif f == 4:
            out["display_name"] = _text(buf, v)
        elif f == 5:
            key, value = _stat(buf, v, stat_names)
            if key is not None:
                out[key] = value
    return out


def find_xplane(path):
    """``path`` itself if it is a file, else the newest ``.xplane.pb`` under
    it (``<dir>/plugins/profile/<run>/`` is where the profiler writes)."""
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path):
    """{"devices": {n: {"ops": [(start ps, duration ps, metadata id)],
                        "modules": [(start ps, duration ps, metadata id)],
                        "meta": {id: event metadata}}},
        "spans": [(name, start ps, duration ps, step or None)]}
    Times are picoseconds on the profile's one clock (a line's
    ``timestamp_ns`` plus the event's ``offset_ps``)."""
    with open(find_xplane(path), "rb") as f:
        buf = f.read()
    devices, spans = {}, []
    for name, lines, event_meta, stat_names in _planes(buf):
        m = DEVICE_PLANE.match(name)
        if m:
            dev = {"ops": [], "modules": [], "meta": {}}
            for span in lines:
                lname, t0, events = _line(buf, span)
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(lname)
                if key is None:
                    continue
                for ev in events:
                    mid, off, dur, _ = _event(buf, ev)
                    dev[key].append((t0 * 1000 + off, dur, mid))
                    if mid not in dev["meta"] and mid in event_meta:
                        dev["meta"][mid] = _event_meta(
                            buf, event_meta[mid], stat_names)
            devices[int(m.group(1))] = dev
            continue
        # a host plane: the program's spans lie on its thread lines
        wanted = {}
        for mid, span in event_meta.items():
            # XEventMetadata's name is field 2
            name = next((_text(buf, v) for f, _, v in _fields(buf, *span)
                         if f == 2), "")
            if name.startswith(SPAN_PREFIX):
                wanted[mid] = name
        if not wanted:
            continue
        for span in lines:
            _, t0, events = _line(buf, span)
            for ev in events:
                if _event_metadata_id(buf, ev) not in wanted:
                    continue
                mid, off, dur, stats = _event(buf, ev)
                args = dict(_stat(buf, s, stat_names) for s in stats)
                spans.append(_span(wanted[mid], t0 * 1000 + off, dur, args))
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def _span(name, start, dur, args):
    """A TraceMe's arguments reach the file as the event's stats, or, where
    nothing decoded them, in its name as ``name#key=value,key=value#``."""
    if "#" in name:
        name, _, rest = name.partition("#")
        for pair in rest.rstrip("#").split(","):
            key, eq, value = pair.partition("=")
            if eq:
                args.setdefault(key, value)
    step = args.get("step", args.get("step_num"))
    try:
        step = int(step)
    except (TypeError, ValueError):
        step = None
    return name, start, dur, step


# ---- from an op's metadata to its scope -------------------------------------

#: what JAX's transformations put into a name stack beside the scopes
_TRANSFORMS = {"jvp", "transpose", "vmap", "pmap", "shard_map", "custom_jvp",
               "custom_vjp"}
_WRAPPERS = {"checkpoint", "remat", "remat2", "rematted_computation",
             "closed_call", "core_call", "pjit", "custom_jvp_call",
             "custom_vjp_call", "custom_vjp_call_jaxpr", "custom_lin"}
_CALL = re.compile(r"^([\w.\-]+)\((.*)\)$", re.S)


def _split(path):
    """The parts of a name stack: cut at ``/`` outside parentheses."""
    parts, depth, cur = [], 0, []
    for ch in path:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += ch == "("
        depth -= ch == ")" and depth > 0
        cur.append(ch)
    parts.append("".join(cur))
    return [p for p in parts if p]


def scope_of(op_name):
    """(scope, direction) of an op from its ``tf_op`` / ``op_name``:
    ``jit(step)/transpose(jvp(l0))/while/body/closed_call/l0/checkpoint/
    rematted_computation/dot_general:`` -> (``l0``, ``bwd``). Off go
    ``jit(...)``, the transformations around a name (``jvp(x)`` -> ``x``),
    ``checkpoint`` / ``remat`` / ``closed_call`` and their kin, ``while/body``,
    ``while/cond``, ``cond/branch_N``, a scope JAX wrote twice in a row, the
    names XLA joined on behind the first when it merged like ops, and the
    trailing primitive. Direction is ``bwd`` where a ``transpose(`` was
    present. No scope left: ``(none)``."""
    text = (op_name or "").partition(";")[0]     # XLA joins merged ops' names
    head, colon, tail = text.rpartition(":")
    if colon and "/" not in tail and "(" not in tail:
        text = head                     # tf_op is `<name>:<type>`
    flat, bwd = [], False
    parts = _split(text)
    if parts and parts[0] in parts[1:]:
        # XLA merged like ops of several layers and joined their names
        # (`jit(step)/jvp(l4/moe)/jit(f)/jit(step)/jvp(l3/moe)/...`): the
        # first one's speaks for them
        parts = parts[:parts.index(parts[0], 1)]
    stack = parts[:-1][::-1]            # the trailing primitive goes
    while stack:
        part = stack.pop()
        m = _CALL.match(part)
        if m is None:
            flat.append(part)
        elif m.group(1) in _TRANSFORMS:
            bwd = bwd or m.group(1) == "transpose"
            stack.extend(_split(m.group(2))[::-1])
        # jit(f), pjit(f) and any other call's own name: not a scope
    out, i = [], 0
    while i < len(flat):
        part = flat[i]
        nxt = flat[i + 1] if i + 1 < len(flat) else ""
        if (part == "while" and nxt in ("body", "cond")) or \
                (part == "cond" and nxt.startswith("branch_")):
            i += 2
            continue
        i += 1
        if part in _WRAPPERS or part in ("while", "cond"):
            continue
        out.append(part)
        # under a checkpoint's transpose JAX writes the open scope again:
        # `l0/moe/l0/moe/...` is `l0/moe/...`
        for k in range(1, len(out) // 2 + 1):
            if out[-k:] == out[-2 * k:-k]:
                del out[-k:]
                break
    return "/".join(out[:SCOPE_DEPTH]) or NONE, "bwd" if bwd else "fwd"


def short_name(text):
    """`%fusion.3 = f32[8]{0} fusion(...)` -> `fusion.3 fusion`, as
    benchmark/trace_reduce.py names an op."""
    if not text.startswith("%") or " = " not in text:
        return text
    name, rest = text[1:].split(" = ", 1)
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            if ch == "(" and depth == 0 and i and rest[i - 1] not in " ,":
                return f"{name} {rest[rest.rfind(' ', 0, i) + 1:i]}"
            depth += 1
        elif ch in ")]}":
            depth -= 1
    return name


_KERNEL_WRAP = re.compile(
    r"^(?:(?:shard_map|transpose|jvp|vmap|remat|checkpoint)_+)+")


def kernel_of(meta):
    """The ``pl.pallas_call(name=...)`` literal of a Mosaic launch, else
    None. The launch is a ``custom-call`` whose instruction JAX names after
    the kernel, wrapped by the transformations it was traced under:
    ``%transpose_jvp_flash_attn_bwd__.7`` -> ``flash_attn_bwd``."""
    op = short_name(meta["name"])
    name, _, opcode = op.partition(" ")
    if opcode != "custom-call" or name.startswith("custom-call"):
        return None
    name = re.sub(r"\.\d+$", "", name)
    return _KERNEL_WRAP.sub("", name).strip("_") or None


# ---- the reduction ------------------------------------------------------------

def _ns(ps):
    """As ``jax.profiler.ProfileData`` hands a time to
    benchmark/trace_reduce.py, which truncates it."""
    return int(ps / 1000.0)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(ops):
    """[(start, duration, key)] -> [(self time, key)] in the events' order by
    start: an event's duration less the events directly inside it (a child
    that ends after its parent is cut at the parent's end)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    inside = [0] * len(ops)
    stack = []                          # (end, index)
    for i in order:
        start, dur, _ = ops[i]
        while stack and stack[-1][0] <= start:
            stack.pop()
        end = start + dur
        if stack:
            end = min(end, stack[-1][0])
            inside[stack[-1][1]] += end - start
        stack.append((end, i))
    return [(max(ops[i][1] - inside[i], 0), ops[i][2]) for i in order]


def attribute_gap(spans, start, end):
    """(span name, step) of the innermost ``paddle:`` span that covers most of
    [start, end): of the spans that cover more than half of it the shortest
    (``paddle:feed`` holds ``feed_convert`` and ``feed_h2d``: innermost wins),
    else the one that covers the most; (``unattributed``, None) if none
    touches it."""
    best = None                         # (covers most?, rank, name, step)
    for name, s, d, step in spans:
        if s >= end:
            break
        ov = min(end, s + d) - max(start, s)
        if ov <= 0:
            continue
        most = 2 * ov > end - start
        rank = (most, -d if most else ov)
        if best is None or rank > best[0]:
            best = (rank, name, step)
    return (best[1], best[2]) if best else (UNATTRIBUTED, None)


def _annotate(dev):
    """Every event metadata of a device gets its ``scope``, ``direction``,
    ``kernel`` and ``op``."""
    for meta in dev["meta"].values():
        meta["scope"], meta["direction"] = scope_of(
            meta.get("tf_op") or meta.get("op_name"))
        meta["kernel"] = kernel_of(meta)
        meta["op"] = short_name(meta["name"])
    # `pl.pallas_call` opens a scope of the kernel's name around its launch:
    # that time belongs to the row of the scope the launch stands in, where
    # the kernels' column names it
    kernels = {m["kernel"] for m in dev["meta"].values() if m["kernel"]}
    for meta in dev["meta"].values():
        head, _, last = meta["scope"].rpartition("/")
        if last in kernels:
            meta["scope"] = head or NONE


def _by_scope(dev):
    """(rows by falling self time, self time by ``hlo_category`` of the ops no
    scope names, total self time), picoseconds turned to seconds."""
    rows = defaultdict(lambda: {"self": 0, "events": 0,
                                "kernels": defaultdict(int),
                                "categories": defaultdict(int),
                                "ops": defaultdict(lambda: [0, 0])})
    for self_ps, mid in self_times(dev["ops"]):
        meta = dev["meta"][mid]
        row = rows[(meta["scope"], meta["direction"])]
        row["self"] += self_ps
        row["events"] += 1
        if meta["kernel"]:
            row["kernels"][meta["kernel"]] += self_ps
        row["categories"][meta.get("hlo_category") or "?"] += self_ps
        op = row["ops"][mid]
        op[0] += self_ps
        op[1] += 1
    total = sum(row["self"] for row in rows.values())

    def falling(d):
        return {k: v / 1e12 for k, v in sorted(d.items(),
                                               key=lambda kv: -kv[1])}

    def op_row(mid, self_ps, events):
        meta = dev["meta"][mid]
        out = {"op": meta["op"], "self_s": self_ps / 1e12, "events": events,
               "hlo_category": meta.get("hlo_category")}
        out.update((k, meta[k]) for k in ("flops", "bytes_accessed")
                   if meta.get(k))
        return out

    scopes, none = [], defaultdict(int)
    for (scope, direction), row in sorted(rows.items(),
                                          key=lambda kv: -kv[1]["self"]):
        ops = sorted(row["ops"].items(), key=lambda kv: -kv[1][0])[:TOP_OPS]
        scopes.append({
            "scope": scope, "direction": direction,
            "self_s": row["self"] / 1e12, "events": row["events"],
            "share": row["self"] / total if total else 0.0,
            "kernels": falling(row["kernels"]),
            "by_category": falling(row["categories"]),
            "ops": [op_row(mid, s, n) for mid, (s, n) in ops]})
        if scope == NONE:
            for category, ps in row["categories"].items():
                none[category] += ps
    return scopes, falling(none), total / 1e12


def _gaps(merged, t0, t1, spans, min_gap_ns):
    """A device's idle gaps inside [t0, t1] (nanoseconds) of at least
    ``min_gap_ns``, by the program's spans."""
    by_span = defaultdict(lambda: [0, 0])
    longest = []
    prev = t0
    for s, e in merged + [[t1, t1]]:
        if s - prev >= min_gap_ns:
            name, step = attribute_gap(spans, prev, s)
            by_span[name][0] += s - prev
            by_span[name][1] += 1
            longest.append({"seconds": (s - prev) / 1e9, "span": name,
                            "step": step, "at_s": (prev - t0) / 1e9})
        prev = max(prev, e)
    longest.sort(key=lambda g: -g["seconds"])
    return {"min_gap_s": min_gap_ns / 1e9,
            "total_s": sum(v[0] for v in by_span.values()) / 1e9,
            "count": sum(v[1] for v in by_span.values()),
            "by_span": [{"span": k, "seconds": s / 1e9, "gaps": n}
                        for k, (s, n) in sorted(by_span.items(),
                                                key=lambda kv: -kv[1][0])],
            "longest": longest[:TOP_GAPS]}


def reduce(trace, min_gap_ns=MIN_GAP_NS):
    """See the module's docstring. Seconds throughout; None for a trace with
    no device operation. Scopes, modules and gaps are the first device's."""
    devs = {n: d for n, d in trace["devices"].items() if d["ops"]}
    if not devs:
        return None
    # busy / window / idle on trace_reduce's truncated nanoseconds
    ns = {n: [(_ns(s), _ns(s) + _ns(d)) for s, d, _ in dev["ops"]]
          for n, dev in devs.items()}
    t0 = min(s for iv in ns.values() for s, _ in iv)
    t1 = max(e for iv in ns.values() for _, e in iv)
    merged = {n: _union(iv) for n, iv in ns.items()}
    busy = {n: sum(e - s for s, e in m) for n, m in merged.items()}
    mean_busy = sum(busy.values()) / len(busy)
    first = min(devs)
    dev = devs[first]

    _annotate(dev)
    scopes, none, self_s = _by_scope(dev)
    unscoped = sum(r["self_s"] for r in scopes if r["scope"] == NONE)
    # the programs that ran: one event a run on the device's module line
    modules = defaultdict(lambda: [0, 0])
    for _, dur, mid in dev["modules"]:
        m = modules[dev["meta"][mid]["name"]]
        m[0] += dur
        m[1] += 1
    spans = [(n, _ns(s), _ns(d), step) for n, s, d, step in trace["spans"]]
    out = {
        "devices": len(devs),
        "window_s": (t1 - t0) / 1e9,
        "busy_s": mean_busy / 1e9,
        "idle_share": 1.0 - mean_busy / (t1 - t0),
        "per_device": [{"device": n, "busy_s": busy[n] / 1e9,
                        "ops": len(devs[n]["ops"])} for n in sorted(devs)],
        "device": first,
        "self_s": self_s,
        "unscoped_share": unscoped / self_s if self_s else 0.0,
        "modules": [{"module": k, "runs": n, "seconds": s / 1e12}
                    for k, (s, n) in sorted(modules.items(),
                                            key=lambda kv: -kv[1][0])],
        "scopes": scopes,
        "none_by_category": [{"hlo_category": k, "self_s": v}
                             for k, v in none.items()],
        "gaps": _gaps(merged[first], t0, t1, spans, min_gap_ns),
        "warnings": [],
    }
    if out["unscoped_share"] > 0.5:
        out["warnings"].append(
            f"{100 * out['unscoped_share']:.0f}% of the busy time has no "
            "scope: the executable may come from a compile-cache entry that a "
            "tree without the scopes wrote; point JAX_COMPILATION_CACHE_DIR "
            "at an empty directory and take the profile again")
    return out


# ---- the table ------------------------------------------------------------------

def render(red, path=""):
    """The text form: PERF.md section 5's table."""
    if red is None:
        return f"profile {path}\nthe trace holds no device operation"
    lines = [f"profile {path}",
             f"devices {red['devices']}   window {red['window_s']:.4f} s   "
             f"busy {red['busy_s']:.4f} s   idle {100 * red['idle_share']:.2f}%"]
    lines += [f"warning: {w}" for w in red["warnings"]]
    runs = red["modules"][0]["runs"] if red["modules"] else 1
    for m in red["modules"][:4]:
        lines.append(f"  ran {m['module']}: {m['runs']} runs, "
                     f"{m['seconds']:.4f} s "
                     f"({1e3 * m['seconds'] / m['runs']:.3f} ms a run)")
    lines.append(f"device {red['device']}: self time by scope and direction, "
                 f"ms a run over {runs} runs of the first program above "
                 f"(sum {1e3 * red['self_s'] / runs:.3f}; "
                 f"{100 * red['unscoped_share']:.1f}% without a scope)")
    lines.append(f"  {'scope':<44} {'dir':<4} {'ms/run':>10} {'share':>7} "
                 f"{'events':>7}  kernels (ms/run)")
    for row in red["scopes"][:TABLE_ROWS]:
        kernels = ", ".join(f"{k} {1e3 * v / runs:.3f}"
                            for k, v in row["kernels"].items())
        lines.append(f"  {row['scope']:<44} {row['direction']:<4} "
                     f"{1e3 * row['self_s'] / runs:>10.3f} "
                     f"{100 * row['share']:>6.2f}% {row['events']:>7}  "
                     f"{kernels}")
    rest = red["scopes"][TABLE_ROWS:]
    if rest:
        lines.append(f"  ... {len(rest)} more rows, "
                     f"{1e3 * sum(r['self_s'] for r in rest) / runs:.3f} ms/run")
    if red["none_by_category"]:
        lines.append(f"{NONE} by hlo_category (ms a run): " + ", ".join(
            f"{c['hlo_category']} {1e3 * c['self_s'] / runs:.3f}"
            for c in red["none_by_category"][:8]))
    for row in red["scopes"][:DETAIL_ROWS]:
        lines.append(f"inside {row['scope']} {row['direction']}, by "
                     "hlo_category: " + ", ".join(
                         f"{k} {1e3 * v / runs:.3f}"
                         for k, v in list(row["by_category"].items())[:6])
                     + "; largest ops (ms a run; flops and bytes an event)")
        for op in row["ops"]:
            extra = "".join(f" {key} {op[key]:,}"
                            for key in ("flops", "bytes_accessed")
                            if key in op)
            lines.append(f"  {1e3 * op['self_s'] / runs:>10.3f}  "
                         f"{op['op'][:60]} x{op['events']}{extra}")
    gaps = red["gaps"]
    lines.append(f"idle gaps of device {red['device']} of at least "
                 f"{1e6 * gaps['min_gap_s']:.0f} us: {gaps['count']} gaps, "
                 f"{1e3 * gaps['total_s']:.3f} ms")
    for g in gaps["by_span"]:
        lines.append(f"  {g['span']:<24} {1e3 * g['seconds']:>10.3f} ms  "
                     f"{g['gaps']} gaps")
    for g in gaps["longest"][:5]:
        step = "" if g["step"] is None else f" step {g['step']}"
        lines.append(f"  longest: {1e3 * g['seconds']:.3f} ms in {g['span']}"
                     f"{step} at +{g['at_s']:.4f} s")
    return "\n".join(lines)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    as_json = "--json" in argv
    paths = [a for a in argv if a != "--json"]
    if len(paths) != 1:
        sys.exit(__doc__.splitlines()[0])
    path = find_xplane(paths[0])
    red = reduce(load(path))
    if as_json:
        print(json.dumps(dict(red or {}, file=path)))
    else:
        print(render(red, path))


if __name__ == "__main__":
    main()
