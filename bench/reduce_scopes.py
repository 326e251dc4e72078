"""From the traced slice to the two tables a ``perf_opt`` PR starts from:
device 0's idle time by the program span the host was in, and its busy
time by the named scope each device op carries.

The program (``mxnet_tpu.telemetry.span``) enters a
``jax.profiler.TraceAnnotation`` for every span, so the spans lie in the
host plane of the same ``.xplane.pb`` as the device ops, on one clock.
The fused step is traced under ``jax.named_scope``: its phases
(``fwd_bwd``, ``update``, ``guard``, ``amp_cast``) and, inside
``fwd_bwd``, ``<op class>/<node name>`` per symbol node (classes conv,
fc, bn, pool, act, loss, other); a backward op keeps the forward scope
inside JAX's ``transpose(...)`` wrapper. The profiler keeps an op's scope
path as the ``tf_op`` stat of its event metadata, which
``jax.profiler.ProfileData`` does not hand out, so ``scope_names`` reads
that one stat from the file's wire format. A fusion carries the scope of
its root instruction.

Events and the slice's window come from ``reduce_trace`` (``load``,
``slice_window``), so idle and busy time here are those of
``device_idle_share`` and ``step_device_ms``; the readers check the sums.
A program without the spans or the scopes (an older commit) reads as
``None``, never as zero.

    python3 bench/reduce_scopes.py <file.xplane.pb> [steps]

prints the reduction of one trace (the split of conv and bn time into
forward, backward, dgrad and wgrad is read from there).
"""
from __future__ import annotations

import collections
import glob
import json
import os
import re
import sys

import reduce_trace

INPUT_SPANS = ("fit.input", "io.feed_fill")
DISPATCH_SPANS = ("module.update", "fit.step_group")
METRIC_SPANS = ("module.update_metric",)
STEP_SPAN = "fit.step"
# every span the program opens inside fit.step (docs/observability.md)
CHILD_SPANS = INPUT_SPANS + DISPATCH_SPANS + METRIC_SPANS + (
    "module.stage", "train_step.dispatch", "fit.callbacks",
    "fit.after_steps")
PHASES = ("fwd_bwd", "update", "guard", "amp_cast")
CLASSES = ("conv", "fc", "bn", "pool", "act", "loss", "other")
_PHASE = re.compile(r"(?:^|/)(%s)(?=/|:|$)" % "|".join(PHASES))
_CLASS = re.compile(r"[/(](%s)/" % "|".join(CLASSES))
_GRAD = re.compile(r"/(dgrad|wgrad)/")


# -- the tf_op stat, from the file's wire format -----------------------------

def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: a varint
    is an int, a length-delimited field a memoryview, fixed ones bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError("wire type %d in an .xplane.pb" % wire)
        yield field, wire, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def scope_names(path):
    """{device id: {event name: scope path}} for the TPU planes of an
    ``.xplane.pb``: XSpace.planes(1) -> XPlane{name(2), event_metadata(4),
    stat_metadata(5)}; an XEventMetadata{name(2), stats(5)} holds the
    scope as the XStat{metadata_id(1), str_value(5) | ref_value(7)} whose
    XStatMetadata{id(1), name(2)} is named ``tf_op``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        name, events, stats = "", [], []
        for pf, _, value in _fields(plane):
            if pf == 2:
                name = _text(value)
            elif pf in (4, 5):  # a map entry: key(1), value(2)
                (events if pf == 4 else stats).extend(
                    v for ef, _, v in _fields(value) if ef == 2)
        m = reduce_trace.DEVICE_PLANE.match(name)
        if not m:
            continue
        stat_name = {}
        for raw in stats:
            sid, sname = 0, ""
            for sf, _, v in _fields(raw):
                if sf == 1:
                    sid = v
                elif sf == 2:
                    sname = _text(v)
            stat_name[sid] = sname
        scopes = out.setdefault(int(m.group(1)), {})
        for raw in events:
            ename, scope = "", None
            for ef, _, v in _fields(raw):
                if ef == 2:
                    ename = _text(v)
                elif ef == 5:
                    sid, text, ref = 0, None, None
                    for sf, _, sv in _fields(v):
                        if sf == 1:
                            sid = sv
                        elif sf == 5:
                            text = _text(sv)
                        elif sf == 7:
                            ref = sv
                    if stat_name.get(sid) == "tf_op":
                        scope = text if text is not None else \
                            stat_name.get(ref, "")
            if scope:
                scopes[ename] = scope
    return out


# -- what a scope path says --------------------------------------------------

def classify(scope):
    """(phase, op class, direction, gradient) of a scope path; any of
    them None where the path does not say. ``direction`` is ``bwd`` under
    a ``transpose(`` wrapper and ``fwd`` otherwise, inside ``fwd_bwd``;
    ``gradient`` is ``dgrad`` or ``wgrad`` where a conv lever names it."""
    if not scope:
        return None, None, None, None
    m = _PHASE.search(scope)
    phase = m.group(1) if m else None
    m = _CLASS.search(scope)
    cls = m.group(1) if m else None
    direction = None
    if phase == "fwd_bwd":
        direction = "bwd" if "transpose(" in scope else "fwd"
    m = _GRAD.search(scope)
    return phase, cls, direction, m.group(1) if m else None


def self_times(events):
    """[(name, self_ns)] of (name, start, end) events that nest properly
    (one device line): an event's own time is its length less what the
    events inside it cover, so the self times sum to the busy union."""
    out, stack = [], []  # stack: [name, end, self]

    def close():
        name, _, own = stack.pop()
        out.append((name, own))

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            close()
        if stack:
            e = min(e, stack[-1][1])  # a child never outlasts its parent
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    while stack:
        close()
    return out


def intersect(a, b):
    """The part of merged ``a`` that merged ``b`` covers."""
    return reduce_trace.subtract(a, reduce_trace.subtract(a, b))


# -- the reduction -----------------------------------------------------------

def reduce(raw, scopes, window=None, device=0):
    """Reduce a loaded trace (``reduce_trace.load``) and its scope names
    over ``window`` (default: the benchmark's annotated slice) on one
    device. Times in seconds over the whole window:

    ``idle_s``, ``idle_under_s`` {input, dispatch, metric, other} (None
    where the program opened no ``fit.step`` span), ``fit_steps``,
    ``fit_self_s`` (mean own time of the ``fit.step`` spans that lie
    inside the window), ``busy_s``, ``phase_s`` {fwd, bwd, update,
    unscoped} (None where no op carries a phase scope), ``by_phase_s``,
    ``by_class_s`` {class: {fwd, bwd, dgrad, wgrad}}."""
    if window is None:
        window = reduce_trace.slice_window(
            raw["host"], reduce_trace.SLICE_BEGIN, reduce_trace.SLICE_END)
    if window is None or device not in raw["devices"]:
        return None
    w0, w1 = window
    ops = list(reduce_trace._clip(raw["devices"][device]["ops"], window))
    busy = reduce_trace.union([(s, e) for _, s, e in ops])
    idle = reduce_trace.subtract([(w0, w1)], busy)
    out = {"window_s": (w1 - w0) / 1e9,
           "busy_s": reduce_trace.total(busy) / 1e9,
           "idle_s": reduce_trace.total(idle) / 1e9}

    # idle time by the span the host was in
    spans = collections.defaultdict(list)
    for _, name, s, d in raw["host"]:
        if name == STEP_SPAN or name in CHILD_SPANS:
            spans[name].append((s, s + d))
    steps = [(s, e) for s, e in spans[STEP_SPAN] if s >= w0 and e <= w1]
    out["fit_steps"] = len(steps)
    if spans[STEP_SPAN]:
        left, under = idle, {}
        for key, names in (("input", INPUT_SPANS),
                           ("dispatch", DISPATCH_SPANS),
                           ("metric", METRIC_SPANS)):
            cover = reduce_trace.union(
                [iv for n in names for iv in spans[n]])
            under[key] = reduce_trace.total(intersect(left, cover)) / 1e9
            left = reduce_trace.subtract(left, cover)
        under["other"] = reduce_trace.total(left) / 1e9
        out["idle_under_s"] = under
    else:
        out["idle_under_s"] = None
    if steps:
        children = reduce_trace.union(
            [iv for n in CHILD_SPANS for iv in spans[n]])
        own = reduce_trace.total(
            reduce_trace.subtract(reduce_trace.union(steps), children))
        out["fit_self_s"] = own / 1e9 / len(steps)
    else:
        out["fit_self_s"] = None

    # busy time by the scope a device op carries
    names = scopes.get(device, {})
    by_phase = collections.Counter()
    by_class = {}
    four = collections.Counter()
    for text, own in self_times(ops):
        phase, cls, direction, grad = classify(names.get(text))
        by_phase[phase or "unscoped"] += own
        if phase == "fwd_bwd":
            four[direction] += own
        elif phase == "update":
            four["update"] += own
        else:
            four["unscoped"] += own
        if cls:
            slot = by_class.setdefault(cls, collections.Counter())
            slot[grad or direction or "fwd"] += own
    out["by_phase_s"] = {k: v / 1e9 for k, v in by_phase.items()}
    out["by_class_s"] = {c: {k: v / 1e9 for k, v in slot.items()}
                         for c, slot in by_class.items()}
    scoped = any(p in by_phase for p in PHASES)
    out["phase_s"] = ({k: four.get(k, 0) / 1e9
                       for k in ("fwd", "bwd", "update", "unscoped")}
                      if scoped else None)
    return out


# -- for the readers in layer_metrics/ ---------------------------------------

def slice_path(run):
    """The ``.xplane.pb`` of this run's slice, by run.py's own rule: under
    ``--out``, or ``<checkout>/.bench_out/<workload>``, in ``trace/``."""
    out_dir = None
    argv = sys.argv
    for i, a in enumerate(argv):
        if a == "--out" and i + 1 < len(argv):
            out_dir = argv[i + 1]
        elif a.startswith("--out="):
            out_dir = a[len("--out="):]
    if out_dir is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out_dir = os.path.join(root, ".bench_out", run["cell"]["name"])
    found = sorted(glob.glob(os.path.join(
        out_dir, "trace", "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return found[-1] if found else None


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["scopes"]`` where a test
    hands one in), or None where there is no slice."""
    if "scopes" in run:
        return run["scopes"]
    path = slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path), scope_names(path))
    return _cache[path]


def per_step_ms(run, seconds):
    return 1e3 * seconds / run["trace_steps"]


def _part_ms(trace, run, table, pick):
    """``pick`` of the reduction's ``table``, per step; None without a
    slice or where the program has no such spans or scopes."""
    if not trace or not run.get("trace_steps"):
        return None
    red = of(run)
    if not red or not red[table]:
        return None
    return per_step_ms(run, pick(red))


def idle_under_ms(trace, run, key):
    """``idle_under_<key>_ms``."""
    return _part_ms(trace, run, "idle_under_s",
                    lambda red: red["idle_under_s"][key])


def phase_ms(trace, run, key):
    """``step_<key>_device_ms``."""
    return _part_ms(trace, run, "phase_s", lambda red: red["phase_s"][key])


def class_ms(trace, run, cls):
    """``<cls>_device_ms``, forward and backward together."""
    return _part_ms(
        trace, run, "phase_s",
        lambda red: sum(red["by_class_s"].get(cls, {}).values()))


def sums_to(parts, whole, tol, what):
    """(ok, why) of a sum check: ``parts`` against ``whole`` within
    ``tol`` of ``whole``."""
    total = sum(parts.values())
    ok = abs(total - whole) <= tol * max(abs(whole), 1e-12)
    detail = " + ".join("%s %.4f" % kv for kv in parts.items())
    return ok, "%s = %.4f against %s %.4f (tolerance %g%%)" % (
        detail, total, what, whole, 100 * tol)


if __name__ == "__main__":
    path = sys.argv[1]
    red = reduce(reduce_trace.load(path), scope_names(path))
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else (
        red["fit_steps"] or 1)

    def ms(x):
        if isinstance(x, dict):
            return {k: ms(v) for k, v in sorted(x.items())}
        return None if x is None else round(1e3 * x / steps, 4)

    print(json.dumps({
        "steps": steps, "fit_steps_in_window": red["fit_steps"],
        "ms_per_step": {k: ms(red[k]) for k in (
            "window_s", "busy_s", "idle_s", "idle_under_s", "phase_s",
            "by_phase_s", "by_class_s")},
        "fit_self_ms": (None if red["fit_self_s"] is None
                        else round(1e3 * red["fit_self_s"], 4)),
    }, indent=1))
