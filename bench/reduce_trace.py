"""From a jax.profiler trace (``.xplane.pb``) to numbers: for each device
the busy union, the idle share, time by op and by op class, collective
time that no compute covers, and the longest idle gaps named by what
the host was doing. Kept with the benchmark so that every PR computes
the same number the same way; checked in bench/tests against a trace
recorded on the v5e and a hand-built two-device case.

``load`` turns the profiler's file into plain lists; ``reduce`` works on
those lists only, so a test can hand it a trace built by hand.
"""
from __future__ import annotations

import collections
import re

SLICE_BEGIN = "bench.slice_begin"  # the benchmark's marks round its slice
SLICE_END = "bench.slice_end"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
LINES = {"XLA Ops": "ops", "Async XLA Ops": "async", "XLA Modules": "modules"}
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^%(\S+) = (.*?) ([\w\-]+)\(")
_KIND = re.compile(r"kind=(\w+)")


def load(path):
    """{"devices": {id: {"ops", "async", "modules"}}, "host": [...]};
    a device event is (name, start_ns, duration_ns), a host event is
    (line, name, start_ns, duration_ns)."""
    from jax.profiler import ProfileData

    raw = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = raw["devices"].setdefault(
                int(m.group(1)), {"ops": [], "async": [], "modules": []})
            for line in plane.lines:
                key = LINES.get(line.name)
                if key:
                    dev[key] += [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                raw["host"] += [(line.name, e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
    return raw


def parse_op(text):
    """(short name, opcode, fusion kind) of a device event's HLO text.
    An event that is not HLO text (a hand-built trace) is its own name
    and opcode."""
    m = _HLO.match(_LAYOUT.sub("", text))
    if not m:
        return text, text.split(".")[0], ""
    kind = _KIND.search(text)
    return m.group(1), m.group(3), kind.group(1) if kind else ""


def op_class(short, opcode, kind):
    """convolution | collective | copy | custom_call | fusion | other.
    The TPU compiler fuses a convolution or dot with its neighbours into
    a fusion of kind kOutput, and its name does not say so; the kind is
    what tells such a fusion from an elementwise (kLoop) or reduction
    (kInput) one."""
    if COLLECTIVE.search(opcode) or COLLECTIVE.search(short):
        return "collective"
    if opcode in ("convolution", "dot") or (
            opcode == "fusion" and kind == "kOutput"):
        return "convolution"
    if opcode.startswith(("copy", "slice", "dynamic-slice", "async",
                          "dynamic-update-slice")):
        return "copy"
    if opcode == "custom-call":
        return "custom_call"
    if opcode == "fusion":
        return "fusion"
    return "other"


# -- intervals ---------------------------------------------------------------

def union(intervals):
    """Sorted, merged list of (start, end)."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(merged):
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """The part of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _clip(events, window):
    w0, w1 = window
    for name, start, dur in events:
        s, e = max(start, w0), min(start + dur, w1)
        if e > s:
            yield name, s, e


def slice_window(host, begin, end):
    """From the end of the first ``begin`` annotation to the start of the
    last ``end`` annotation, or None if either is missing."""
    b = [s + d for _, n, s, d in host if n == begin]
    e = [s for _, n, s, d in host if n == end]
    if not b or not e or max(e) <= min(b):
        return None
    return (min(b), max(e))


def _name_gap(gap, host):
    """What the host was doing in an idle gap: the innermost host event
    that covers at least half of it (else the one that overlaps it most),
    with the benchmark's own annotation in front where one covers it."""
    g0, g1 = gap
    half = (g1 - g0) / 2.0
    best, best_overlap, inner, bench = None, 0.0, None, None
    for _, name, s, d in host:
        overlap = min(g1, s + d) - max(g0, s)
        if overlap <= 0:
            continue
        if name.startswith("bench."):
            if overlap >= half and (bench is None or d < bench[1]):
                bench = (name, d)
            continue
        if overlap > best_overlap:
            best, best_overlap = name, overlap
        if overlap >= half and (inner is None or d < inner[1]):
            inner = (name, d)
    what = inner[0] if inner else best
    parts = [p for p in (bench[0] if bench else None, what) if p]
    return " > ".join(parts) if parts else "no host event"


def reduce(raw, window=None, top=10):
    """Reduce a loaded trace over ``window`` (start_ns, end_ns). Without
    one, the window is the benchmark's annotated slice, or failing that
    the span of the device events."""
    if window is None:
        window = slice_window(raw["host"], SLICE_BEGIN, SLICE_END)
    if window is None:
        starts = [s for d in raw["devices"].values() for _, s, _ in d["ops"]]
        ends = [s + n for d in raw["devices"].values() for _, s, n in d["ops"]]
        if not starts:
            return None
        window = (min(starts), max(ends))
    w0, w1 = window
    width = float(w1 - w0)
    host = [h for h in raw["host"] if h[2] < w1 and h[2] + h[3] > w0]
    out = {"window_s": width / 1e9, "devices": {}}
    for dev_id in sorted(raw["devices"]):
        d = raw["devices"][dev_id]
        by_name = collections.Counter()
        by_class = collections.Counter()
        every, compute, coll = [], [], []
        for text, s, e in _clip(d["ops"], window):
            short, opcode, kind = parse_op(text)
            cls = op_class(short, opcode, kind)
            label = short
            if kind:
                label += " " + kind
            elif not short.startswith(opcode):
                label += " " + opcode
            by_name[label] += e - s
            by_class[cls] += e - s
            every.append((s, e))
            (coll if cls == "collective" else compute).append((s, e))
        for text, s, e in _clip(d["async"], window):
            short, opcode, kind = parse_op(text)
            if op_class(short, opcode, kind) == "collective":
                coll.append((s, e))
        busy = union(every)
        coll_u = union(coll)
        gaps = sorted(subtract([(w0, w1)], busy),
                      key=lambda g: g[0] - g[1])
        named = collections.Counter()
        for g in gaps[:4 * top]:
            named[_name_gap(g, host)] += g[1] - g[0]
        modules = collections.Counter()
        for name, s, e in _clip(d["modules"], window):
            modules[name.split("(")[0]] += 1
        out["devices"][dev_id] = {
            "busy_s": total(busy) / 1e9,
            "idle_share": 1.0 - total(busy) / width,
            "ops": len(every),
            "by_class_s": {k: v / 1e9 for k, v in by_class.items()},
            "top_ops": [[n, v / 1e9] for n, v in by_name.most_common(top)],
            "collective_s": total(coll_u) / 1e9,
            "collective_exposed_s":
                total(subtract(coll_u, union(compute))) / 1e9,
            "longest_gap_s": (gaps[0][1] - gaps[0][0]) / 1e9 if gaps else 0.0,
            "idle_gaps": [[n, v / 1e9] for n, v in named.most_common(top)],
            "modules": dict(modules),
        }
    return out
