"""A traced slice's convolution time pass by pass and node by node, each
beside the least time the chip could take for it: the table a sweep of
the conv-backward paths starts from.

The program traces a ``Convolution`` node's ops under ``conv/<node>``
(``executor._compute_node``) and its two gradient convolutions under
``dgrad`` and ``wgrad`` inside it (``ops/nn.py::_conv_named_grads``, on
every path since PR 66), so ``reduce_scopes.classify`` files a device op
under (node, pass), pass = ``fwd`` | ``dgrad`` | ``wgrad``; a backward op
of class ``conv`` that carries neither name is ``bwd``.

**Whole steps.** The slice holds a whole number of host steps, not of
device steps: the device runs behind the host, so a step program is cut
at each edge of the slice and the slice holds 4.07 to 4.8 periods where
the cell's ``trace_steps`` says 5. The readers here count the events of
the step program (``jit_step`` on the device's ``XLA Modules`` line) that
lie WHOLE inside the slice, sum the own times of the ops inside those
events and divide by their number, never by ``trace_steps``: true
milliseconds a step, which a share of a roofline needs.
``periods`` (slice length over the median distance between two step
programs' starts) is what ``reduce_scopes.per_step_ms`` should have
divided by.

**The bounds** come from the cell's symbol alone (``infer_shape`` at the
cell's batch a chip). A node's operations (2 a multiply-add, the same in
all three passes) over the bf16 peak; its bytes over the HBM peak at the
width the cell's arrays have: input + filter + output forward; output's
cotangent + filter + input's cotangent for ``dgrad`` (nothing where the
data input is the batch: the program computes no such gradient); input +
output's cotangent + filter's gradient for ``wgrad``. Of the input only
the elements some output touches count (a 1 x 1 convolution of stride 2
touches a quarter), so no bound asks for bytes the chip need not move. A
node's bound is the LARGER of the two. XLA fuses the optimizer's update
into a weight gradient's epilogue and BatchNorm's reductions into the
convolution whose output they read: their time is in the pass's
milliseconds and not in its bound, so a share reads low by them, never
high. What reads HIGH: a node beats its bytes' bound where the compiler
keeps an operand in VMEM between its producer and the convolution (a
layout ``S(1)`` in the compiled step: ``pooling0``'s 103 MB into
``stage1_unit1_conv1``) or hands a float32 cell's convolution a bf16 copy;
the command counts such rows and the ms they are under by, and a pass's
share is high by at most that.

A program without the ``conv`` scopes, or a slice without a whole step,
reads as ``None``, never as zero.

    python3 bench/conv_scopes.py <file.xplane.pb> <workload>

prints one row a node and pass (shapes, ms a step, the two bounds, the
share, ms over the bound), furthest over its bound first, then the
convolution time the scopes do NOT hand to a ``conv`` node: own time of
kOutput fusions and ``convolution`` ops whose scope's class is another
or none (``fc`` is the head's own products; the rest is fusions that
straddle scopes).
"""
from __future__ import annotations

import collections
import json
import os
import re
import statistics
import sys

import lib
import reduce_scopes
import reduce_trace

STEP_PROGRAM = "jit_step"
PASSES = ("fwd", "dgrad", "wgrad")
_NODE = re.compile(r"[/(]conv/([^/()]+)")


# -- whole steps -------------------------------------------------------------

def whole_steps(modules, window):
    """([(start, end)] of the step program's events that lie whole inside
    ``window``, the periods the window holds or None with under two
    events). The recording cuts the step that runs as it starts and the
    one that runs as it stops, and writes each as an event of what it
    saw: the line's first and last event are never whole."""
    w0, w1 = window
    events = sorted((s, s + d) for name, s, d in modules
                    if name.split("(")[0] == STEP_PROGRAM)
    whole = [(s, e) for s, e in events[1:-1] if s >= w0 and e <= w1]
    starts = [s for s, _ in events]
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    periods = (w1 - w0) / statistics.median(gaps) if gaps else None
    return whole, periods


def reduce(raw, scopes, device=0):
    """``steps`` (whole step programs in the slice), ``periods``, and in
    seconds a WHOLE STEP of ``device``: ``table_s`` {(node, pass)},
    ``pass_s`` {fwd, dgrad, wgrad, bwd} (a pass no op carries is absent)
    and ``stray_s`` {class or "none"}: own time of convolution-class ops
    (``reduce_trace.op_class``) outside every ``conv`` node. None without
    a slice, a whole step or a ``conv`` scope."""
    window = reduce_trace.slice_window(
        raw["host"], reduce_trace.SLICE_BEGIN, reduce_trace.SLICE_END)
    if window is None or device not in raw["devices"]:
        return None
    dev = raw["devices"][device]
    whole, periods = whole_steps(dev["modules"], window)
    if not whole:
        return None
    inside = []
    for name, s, d in dev["ops"]:
        if any(a <= s and s + d <= b for a, b in whole):
            inside.append((name, s, s + d))
    names = scopes.get(device, {})
    table = collections.Counter()
    stray = collections.Counter()
    for text, own in reduce_scopes.self_times(inside):
        scope = names.get(text) or ""
        _, cls, direction, grad = reduce_scopes.classify(scope)
        if cls == "conv":
            node = _NODE.search(scope).group(1)
            table[node, grad or ("bwd" if direction == "bwd" else "fwd")] \
                += own
        elif reduce_trace.op_class(
                *reduce_trace.parse_op(text)) == "convolution":
            stray[cls or "none"] += own
    if not table:
        return None
    per = 1e9 * len(whole)
    by_pass = collections.Counter()
    for (_, which), own in table.items():
        by_pass[which] += own
    return {"steps": len(whole), "periods": periods,
            "table_s": {k: v / per for k, v in table.items()},
            "pass_s": {k: v / per for k, v in by_pass.items()},
            "stray_s": {k: v / per for k, v in stray.items()}}


# -- the bounds, from the symbol alone ---------------------------------------

def _tuple(text, nd, default):
    values = [int(v) for v in re.findall(r"-?\d+", str(text or ""))]
    return tuple(values) if values else (default,) * nd


def _touched(extent, out, kernel, stride, dilate, pad):
    """How many of a dimension's ``extent`` input elements some output
    reads."""
    return len({o * stride + j * dilate - pad
                for o in range(out) for j in range(kernel)}
               & set(range(extent)))


def _count(shape):
    n = 1
    for d in shape:
        n *= int(d)
    return n


def conv_nodes(symbol, **input_shapes):
    """[{name, data, weight, out, stride, macs, touched, reads_batch}] of
    a symbol's Convolution nodes at ``input_shapes``: ``macs`` the
    multiply-adds of one pass, ``touched`` the input elements some output
    reads, ``reads_batch`` where the data input is one of ``input_shapes``
    (a variable that is no parameter)."""
    graph = json.loads(symbol.tojson())
    internals = symbol.get_internals()
    _, out_shapes, _ = internals.infer_shape(**input_shapes)
    shape_of = dict(zip(internals.list_outputs(), out_shapes))
    nodes = graph["nodes"]

    def shape(entry):  # of a variable, or of an op's first output
        source = nodes[entry[0]]
        return shape_of[source["name"] if source["op"] == "null"
                        else source["name"] + "_output"]

    found = []
    for node in nodes:
        if node["op"] != "Convolution":
            continue
        attr = node.get("attr") or node.get("attrs") or {}
        data, weight = shape(node["inputs"][0]), shape(node["inputs"][1])
        out = shape_of[node["name"] + "_output"]
        nd = len(weight) - 2
        stride = _tuple(attr.get("stride"), nd, 1)
        dilate = _tuple(attr.get("dilate"), nd, 1)
        pad = _tuple(attr.get("pad"), nd, 0)
        touched = int(data[0]) * int(data[1])
        for i in range(nd):
            touched *= _touched(int(data[2 + i]), int(out[2 + i]),
                                int(weight[2 + i]), stride[i], dilate[i],
                                pad[i])
        source = nodes[node["inputs"][0][0]]
        found.append({
            "name": node["name"], "data": tuple(data),
            "weight": tuple(weight), "out": tuple(out), "stride": stride,
            # every output element reduces over the filter past its first axis
            "macs": _count(out) * _count(weight[1:]),
            "touched": touched,
            "reads_batch": (source["op"] == "null"
                            and source["name"] in input_shapes)})
    return found


def bounds(node, which, width, peak):
    """(seconds by operations, seconds by bytes) of one pass of a node at
    ``width`` bytes an element, or None where the program computes no
    such pass (the data gradient of the batch)."""
    if which == "dgrad" and node["reads_batch"]:
        return None
    elements = (node["touched"] + _count(node["weight"])
                + _count(node["out"]))
    return (2.0 * node["macs"] / peak["bf16_flops"],
            float(width) * elements / peak["hbm_bytes_s"])


def array_width(cfg):
    """Bytes an element of the arrays a cell's convolutions move: 2 where
    the symbol is built in bfloat16 or the cell sets ``MXTPU_AMP=bf16``,
    else float32's 4."""
    amp = cfg.get("env", {}).get("MXTPU_AMP", {}).get("value")
    bf16 = cfg.get("kwargs", {}).get("dtype") == "bfloat16" or amp == "bf16"
    return 2 if bf16 else 4


def cell_nodes(cfg, batch_a_chip):
    """``conv_nodes`` of a cell's symbol at its batch a chip."""
    symbol = lib.resolve(cfg["factory"])(**cfg["kwargs"])
    return conv_nodes(
        symbol, data=(int(batch_a_chip),) + tuple(cfg["input_shape"]))


def rows(red, nodes, width, peak):
    """One row a (node, pass) the slice or the symbol knows: ms a step
    (None where no op carries it), the two bounds and the larger in ms
    (None for a pass the program does not compute or a node the symbol
    lacks), furthest over its bound first."""
    by_name = {n["name"]: n for n in nodes}
    keys = set(red["table_s"]) | {(n["name"], p) for n in nodes
                                  for p in PASSES if bounds(n, p, 1, peak)}
    out = []
    for name, which in keys:
        node = by_name.get(name)
        both = (bounds(node, which, width, peak)
                if node and which in PASSES else None)
        seconds = red["table_s"].get((name, which))
        row = {"node": name, "pass": which, "node_shapes": node,
               "ms": None if seconds is None else 1e3 * seconds,
               "flops_ms": both and 1e3 * both[0],
               "bytes_ms": both and 1e3 * both[1],
               "bound_ms": both and 1e3 * max(both)}
        row["over_ms"] = (row["ms"] - row["bound_ms"]
                          if row["ms"] is not None and both else None)
        out.append(row)
    return sorted(out, key=lambda r: (-(r["over_ms"] or 0.0), r["node"],
                                      r["pass"]))


# -- for the readers in layer_metrics/ ---------------------------------------

_cache = {}


def of(run):
    """The reduction of this run's slice (``run["conv_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "conv_scopes" in run:
        return run["conv_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path),
                              reduce_scopes.scope_names(path))
    return _cache[path]


def pass_ms(trace, run, which):
    """``conv_<which>_device_ms``: ms a whole step in ops of class
    ``conv`` of that pass; None without a slice or where no op carries
    the pass's name."""
    red = of(run) if trace else None
    if not red or which not in red["pass_s"]:
        return None
    return 1e3 * red["pass_s"][which]


def pass_bound_ms(run, which):
    """The sum over the cell's nodes of the larger bound of one pass."""
    cfg = run["cfg"]
    nodes = cell_nodes(cfg, run["batch"] // run["chips"])
    width = array_width(cfg)
    return 1e3 * sum(max(b) for b in (
        bounds(n, which, width, run["peak"]) for n in nodes) if b)


def pass_roofline_share(trace, run, which):
    """``conv_<which>_roofline_share``: that sum over the pass's
    milliseconds, in percent."""
    busy_ms = pass_ms(trace, run, which)
    if not busy_ms or not run.get("peak"):
        return None
    return 100.0 * pass_bound_ms(run, which) / busy_ms


def unnamed_check(red, tol=0.01):
    """(ok, why): not ok where backward time of class ``conv`` that
    carries neither gradient's name is over ``tol`` of the two that do.
    ``why`` says the whole steps counted and the periods held: what the
    ``trace_steps`` readers' divisor should have been."""
    named = red["pass_s"].get("dgrad", 0.0) + red["pass_s"].get("wgrad", 0.0)
    bare = red["pass_s"].get("bwd", 0.0)
    return bare <= tol * named, (
        "%d whole steps counted, %s periods held; backward conv ms a step: "
        "dgrad %.4f + wgrad %.4f named, %.4f unnamed (tolerance %g%%)" % (
            red["steps"],
            "?" if red["periods"] is None else "%.3f" % red["periods"],
            1e3 * red["pass_s"].get("dgrad", 0.0),
            1e3 * red["pass_s"].get("wgrad", 0.0), 1e3 * bare, 100 * tol))


# -- the command -------------------------------------------------------------

def _shapes(node):
    if not node:
        return "?"
    return "%s * %s /%s" % (
        "x".join(map(str, node["data"])), "x".join(map(str, node["weight"])),
        "x".join(map(str, node["stride"])))


def main(argv):
    path, workload = argv[1], argv[2]
    cell = lib.load_json(lib.find("cells", workload, ".json"))
    mix = lib.load_json(lib.find("traffic", cell["traffic"], ".json"))
    cfg = lib.load_json(lib.find("configs", cell["config"], ".json"))
    (kind, peak), = lib.load_json(os.path.join(lib.BENCH,
                                               "peaks.json")).items()
    red = reduce(reduce_trace.load(path), reduce_scopes.scope_names(path))
    if red is None:
        print("no whole step, or no op under a conv node, in %s" % path)
        return 1
    width = array_width(cfg)
    nodes = cell_nodes(cfg, mix["batch"] // cell["chips"])
    print("%s: %d whole steps counted, %s periods held (trace_steps %s); "
          "bounds for %s at %d bytes an element, batch %d a chip" % (
              workload, red["steps"],
              "?" if red["periods"] is None else "%.3f" % red["periods"],
              mix.get("trace_steps"), kind, width,
              mix["batch"] // cell["chips"]))
    table = rows(red, nodes, width, peak)
    for which in PASSES + ("bwd",):
        mine = [r for r in table if r["pass"] == which]
        bound = sum(r["bound_ms"] or 0.0 for r in mine)
        if which not in red["pass_s"]:
            if which != "bwd":
                print("pass %-5s no op carries it (bound %.3f ms)" % (
                    which, bound))
            continue
        ms = 1e3 * red["pass_s"][which]
        if which == "bwd":
            print("pass bwd   %8.3f ms a step under neither gradient's "
                  "name" % ms)
            continue
        under = [r["over_ms"] for r in mine if (r["over_ms"] or 0.0) < 0]
        print("pass %-5s %8.3f ms a step, bound %7.3f ms (%d of %d nodes "
              "bound by bytes), share %.1f%%; %d rows under their bound by "
              "%.3f ms" % (
                  which, ms, bound,
                  sum(1 for r in mine if r["bound_ms"]
                      and r["bytes_ms"] >= r["flops_ms"]), len(mine),
                  100 * bound / ms, len(under), -sum(under)))
    print("%-28s %-5s %-34s %8s %8s %8s %6s %8s" % (
        "node", "pass", "data * filter /stride", "ms", "flops_ms",
        "bytes_ms", "share", "over_ms"))

    def cell_(v, fmt="%8.3f"):
        return fmt % v if v is not None else " " * 7 + "-"

    for r in table:
        share = (100 * r["bound_ms"] / r["ms"]
                 if r["ms"] and r["bound_ms"] else None)
        print("%-28s %-5s %-34s %s %s %s %s %s" % (
            r["node"], r["pass"], _shapes(r["node_shapes"]), cell_(r["ms"]),
            cell_(r["flops_ms"]), cell_(r["bytes_ms"]),
            cell_(share, "%5.1f%%"), cell_(r["over_ms"])))
    print("convolution-class time outside every conv node, ms a step: %s" % (
        json.dumps({k: round(1e3 * v, 4)
                    for k, v in sorted(red["stray_s"].items())})))
    return 0


if __name__ == "__main__":
    sys.path.insert(1, lib.ROOT)  # the program, for the cell's symbol
    sys.exit(main(sys.argv))
