"""Device time of a traced slice under the nodes a gated delta-rule
model adds. The program traces a ``GatedDeltaNet`` node's ops under
``gdn/<node name>`` (``executor.op_class``), and inside it ``conv1d``
(the three causal depthwise convolutions and their silu), ``delta_rule``
(unit keys and queries, write strengths, decays, the chunk form's
triangular systems and products, the recurrence over chunks) and
``gate_norm`` (the per-head RMSNorm and its gate); the backward pass and
what it recomputes of the forward keep those names inside JAX's
``transpose(jvp(...))``, ``checkpoint`` and ``rematted_computation``
wrappers. The five wide projections round the core are the
``FullyConnected`` nodes named ``layer<i>_gdn_{q,k,v,g,o}_proj`` (the two
of one column a head, ``_a_proj`` and ``_b_proj``, are in neither); the
dense SwiGLUs are the nodes named ``layer<i>_{gate,up,down}_proj``.

``TABLE`` is all this file adds: which scope is filed under which name.
Events, the slice's window, scope names, self times and the slice's
path are ``reduce_trace``'s and ``reduce_scopes``'s. A program without
the ``gdn`` scopes (an older commit, another model) reads as ``None``,
never as zero.

    python3 bench/gdn_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import reduce_scopes
import reduce_trace

_NODE = r"[/(]gdn/[^/()]+"
_INSIDE = r"(?:.*/)?%s(?=/|\)|:|$)"
# name -> pattern, tried in this order; the first that matches files the op
TABLE = collections.OrderedDict(
    [(part, re.compile(_NODE + r"\)*/" + _INSIDE % part))
     for part in ("conv1d", "delta_rule", "gate_norm")]
    + [("other", re.compile(_NODE)),
       ("proj", re.compile(r"[/(][a-z]+/layer\d+_gdn_[qkvgo]_proj\b")),
       ("mlp", re.compile(r"[/(][a-z]+/layer\d+_(?:gate|up|down)_proj\b"))])
CORE = ("conv1d", "delta_rule", "gate_norm", "other")


def part_of(scope):
    """The name of ``TABLE`` an op of this scope is filed under, or
    None."""
    for name, pattern in TABLE.items():
        if pattern.search(scope):
            return name
    return None


def reduce(raw, scopes, device=0):
    """Seconds of ``device`` over the benchmark's slice by ``TABLE``'s
    names, and ``gdn`` (every op under a ``GatedDeltaNet`` node); a name
    no op carries reads None. None without a slice or where no op is
    under such a node: projections named alike do not make a delta-rule
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
    out["gdn"] = sum(found[part] for part in CORE) / 1e9
    return out


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["gdn_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "gdn_scopes" in run:
        return run["gdn_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path),
                              reduce_scopes.scope_names(path))
    return _cache[path]


def ms(trace, run, part):
    """ms/step of ``part`` (gdn, conv1d, delta_rule, gate_norm, proj,
    mlp); None without a slice or without these scopes."""
    if not trace or not run.get("trace_steps"):
        return None
    red = of(run)
    if not red or red.get(part) is None:
        return None
    return reduce_scopes.per_step_ms(run, red[part])


if __name__ == "__main__":
    path = sys.argv[1]
    red = reduce(reduce_trace.load(path), reduce_scopes.scope_names(path))
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    print(json.dumps({"steps": steps, "ms_per_step": {
        k: None if v is None else round(1e3 * v / steps, 4)
        for k, v in sorted(red.items())}} if red else None, indent=1))
