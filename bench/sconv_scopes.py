"""Device time of a traced slice under the nodes a gated
short-convolution model adds. The program traces a ``ShortConv`` node's
ops under ``sconv/<node name>`` (``executor.op_class``), and inside it
``gate_in`` (``B * x``), ``conv1d`` (the taps' shifted multiply-adds)
and ``gate_out`` (``C * .``); the backward pass and what it recomputes
of the forward keep those names inside JAX's ``transpose(jvp(...))``,
``checkpoint`` and ``rematted_computation`` wrappers. The two projections
round the op are the ``FullyConnected`` nodes named
``layer<i>_conv_{in,out}_proj``.

``TABLE`` is all this file adds: which scope is filed under which name.
Events, the slice's window, scope names, self times and the slice's
path are ``reduce_trace``'s and ``reduce_scopes``'s. A program without
the ``sconv`` scopes (an older commit, another model) reads as ``None``,
never as zero.

    python3 bench/sconv_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import reduce_scopes
import reduce_trace
import share_scopes

_NODE = r"[/(]sconv/[^/()]+"
_INSIDE = r"(?:.*/)?%s(?=/|\)|:|$)"
# name -> pattern, tried in this order; the first that matches files the op
TABLE = collections.OrderedDict(
    [(part, re.compile(_NODE + r"\)*/" + _INSIDE % part))
     for part in ("gate_in", "conv1d", "gate_out")]
    + [("other", re.compile(_NODE)),
       ("proj", re.compile(r"[/(][a-z]+/layer\d+_conv_(?:in|out)_proj\b"))])
CORE = ("gate_in", "conv1d", "gate_out", "other")


def part_of(scope):
    """The name of ``TABLE`` an op of this scope is filed under, or
    None."""
    for name, pattern in TABLE.items():
        if pattern.search(scope):
            return name
    return None


def reduce(raw, scopes, device=0):
    """Seconds of ``device`` over the benchmark's slice by ``TABLE``'s
    names, and ``sconv`` (every op under a ``ShortConv`` node); a name no
    op carries reads None. None without a slice or where no op is under
    such a node: projections named alike do not make a short-convolution
    model."""
    window = reduce_trace.slice_window(
        raw["host"], reduce_trace.SLICE_BEGIN, reduce_trace.SLICE_END)
    if window is None or device not in raw["devices"]:
        return None
    ops = reduce_trace._clip(raw["devices"][device]["ops"], window)
    names = scopes.get(device, {})
    found = collections.Counter()
    for text, own in reduce_scopes.self_times(list(ops)):
        part = part_of(names.get(text) or "")
        if part:
            found[part] += own
    if not any(part in found for part in CORE):
        return None
    out = {name: found[name] / 1e9 if name in found else None
           for name in TABLE}
    out["sconv"] = sum(found[part] for part in CORE) / 1e9
    return out


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["sconv_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "sconv_scopes" in run:
        return run["sconv_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path),
                              reduce_scopes.scope_names(path))
    return _cache[path]


def ms(trace, run, part):
    """ms/step of ``part`` (sconv, gate_in, conv1d, gate_out, other,
    proj); None without a slice or without these scopes."""
    if not trace or not run.get("trace_steps"):
        return None
    red = of(run)
    if not red or red.get(part) is None:
        return None
    return reduce_scopes.per_step_ms(run, red[part])


def lfm2_flops(run):
    """The run's operations module where it is this model's (it counts
    ``sconv_bytes``), or None."""
    flops = share_scopes.flops_of(run)
    return flops if getattr(flops, "sconv_bytes", None) else None


if __name__ == "__main__":
    path = sys.argv[1]
    red = reduce(reduce_trace.load(path), reduce_scopes.scope_names(path))
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    print(json.dumps({"steps": steps, "ms_per_step": {
        k: None if v is None else round(1e3 * v / steps, 4)
        for k, v in sorted(red.items())}} if red else None, indent=1))
