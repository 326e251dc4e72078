"""Device time of a traced slice in the parts of an afmoe (Trinity)
attention layer that no other table names. The program traces a node's
ops under ``<op class>/<node name>`` (``executor.op_class``) and, inside
an ``Attention`` node, the kernels under ``window`` or ``full``
(``share_scopes`` reads those) and the sigmoid gate on their output
under ``gate``; the backward pass keeps those names inside JAX's
``transpose(jvp(...))`` wrappers. A layer's five projections are the
``FullyConnected`` nodes ``layer<i>_{q,k,v,o}_proj`` and
``layer<i>_attn_gate_proj``; its six norms the ``RMSNorm`` nodes
``layer<i>_{attn_norm, attn_post_norm, ffn_norm, ffn_post_norm, q_norm,
k_norm}`` (the last two over a head's own columns; ``final_norm`` is the
head's and not counted here).

``TABLE`` is all this file adds: which scope is filed under which name.
Events, the slice's window, scope names, self times and the slice's
path are ``reduce_trace``'s and ``reduce_scopes``'s. A program without a
gated attention node (an older commit, another model) reads as ``None``,
never as zero.

    python3 bench/afmoe_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import reduce_scopes
import reduce_trace
import share_scopes

# name -> pattern, tried in this order; the first that matches files the
# op. A transform's wrapper closes between the node and what it scoped:
# ``jvp(attn/<node>)/gate``, ``transpose(jvp(attn/<node>))/gate``
TABLE = collections.OrderedDict([
    ("gate", re.compile(r"[/(]attn/[^/()]+\)*/gate(?=/|\)|$)")),
    ("attn_proj", re.compile(
        r"[/(]fc/layer\d+_(?:q|k|v|o|attn_gate)_proj\b")),
    ("norm", re.compile(
        r"[/(]norm/layer\d+_(?:attn_norm|attn_post_norm|ffn_norm|"
        r"ffn_post_norm|q_norm|k_norm)\b"))])


def part_of(scope):
    """The name of ``TABLE`` an op of this scope is filed under, or
    None."""
    for name, pattern in TABLE.items():
        if pattern.search(scope):
            return name
    return None


def reduce(raw, scopes, device=0):
    """Seconds of ``device`` over the benchmark's slice by ``TABLE``'s
    names; a name no op carries reads None. None without a slice or
    where no op is a gate's."""
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
    if "gate" not in found:
        return None
    return {name: found[name] / 1e9 if name in found else None
            for name in TABLE}


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["afmoe_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "afmoe_scopes" in run:
        return run["afmoe_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path),
                              reduce_scopes.scope_names(path))
    return _cache[path]


def ms(trace, run, part):
    """ms/step of ``part`` (a name of ``TABLE``); None without a slice or
    without these scopes."""
    if not trace or not run.get("trace_steps"):
        return None
    red = of(run)
    if not red or red.get(part) is None:
        return None
    return reduce_scopes.per_step_ms(run, red[part])


def afmoe_flops(run):
    """The configuration's operations module where it counts a gated
    attention layer's projections beside a window (``projection_flops``
    and ``window_pairs``), or None."""
    flops = share_scopes.flops_of(run)
    return flops if (getattr(flops, "window_pairs", None)
                     and getattr(flops, "projection_flops", None)) else None


if __name__ == "__main__":
    path = sys.argv[1]
    red = reduce(reduce_trace.load(path), reduce_scopes.scope_names(path))
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    print(json.dumps({"steps": steps, "ms_per_step": {
        k: None if v is None else round(1e3 * v / steps, 4)
        for k, v in sorted(red.items())}} if red else None, indent=1))
