"""Device time of a traced slice under the nodes a decoder-hybrid-decoder
of Mamba-1, differential attention and gated memory units adds. The
program traces a node's ops under ``<class>/<node name>``
(``executor.op_class``): a ``Mamba1`` node ``ssm/<node>`` with ``conv1d``
(the causal taps and their silu), ``x_proj`` and ``dt_proj`` (the two small
projections and the softplus), ``sscan`` (the selective scan) and ``gate``
inside it; a ``DiffAttention`` node ``attn/<node>`` with its two flash
calls under ``diff/window``, ``diff/full`` or ``diff/cross`` and the
difference, norm and factor under ``diff/combine``; the ``FullyConnected``
nodes ``fc/layer<l>_mamba_{in,out}_proj`` and ``fc/layer<l>_gmu_{in,out}_
proj``; the gated memory unit's gate and product ``act/layer<l>_gmu_gate``
and ``act/layer<l>_gmu``. The backward pass and what it recomputes keep
those names inside JAX's ``transpose(jvp(...))`` wrappers.

``TABLE`` is all this file adds: which scope is filed under which name.
Events, the slice's window, scope names, self times and the slice's path
are ``reduce_trace``'s and ``reduce_scopes``'s. A program in which no op
carries an ``sscan`` scope (an older commit, another model) reads as
``None``, never as zero.

    python3 bench/sscan_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import reduce_scopes
import reduce_trace

_SSM = r"[/(]ssm/[^/()]+"
_ATTN = r"[/(]attn/[^/()]+"
_INSIDE = r"(?:.*/)?%s(?=/|\)|:|$)"
# name -> pattern, tried in this order; the first that matches files the op
TABLE = collections.OrderedDict(
    [(part, re.compile(_SSM + r"\)*/" + _INSIDE % part))
     for part in ("sscan", "conv1d", "x_proj", "dt_proj", "gate")]
    + [("mamba_other", re.compile(_SSM + r"\b")),
       ("mamba_proj", re.compile(r"[/(]fc/layer\d+_mamba_(?:in|out)_proj\b"))]
    + [("diff_" + part, re.compile(_ATTN + r"\)*/" + _INSIDE % ("diff/" + part)))
       for part in ("window", "full", "cross", "combine")]
    + [("gmu", re.compile(
        r"[/(](?:fc/layer\d+_gmu_(?:in|out)_proj|act/layer\d+_gmu(?:_gate)?)"
        r"\b"))])
MAMBA = ("sscan", "conv1d", "x_proj", "dt_proj", "gate", "mamba_other",
         "mamba_proj")
FLASH = ("diff_window", "diff_full", "diff_cross")
DIFF = FLASH + ("diff_combine",)


def part_of(scope):
    """The name of ``TABLE`` an op of this scope is filed under, or
    None."""
    for name, pattern in TABLE.items():
        if pattern.search(scope):
            return name
    return None


def reduce(raw, scopes, device=0):
    """Seconds of ``device`` over the benchmark's slice by ``TABLE``'s
    names, ``mamba`` (the whole mixer with its projections), ``flash``
    (the differential layers' flash calls) and ``diff`` (those and the
    combination); a name no op carries reads None. None without a slice
    or where no op carries an ``sscan`` scope."""
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
    if "sscan" not in found:
        return None
    out = {name: found[name] / 1e9 if name in found else None
           for name in TABLE}
    for name, parts in (("mamba", MAMBA), ("flash", FLASH), ("diff", DIFF)):
        out[name] = (sum(found[part] for part in parts) / 1e9
                     if any(part in found for part in parts) else None)
    return out


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["sscan_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "sscan_scopes" in run:
        return run["sscan_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path),
                              reduce_scopes.scope_names(path))
    return _cache[path]


def ms(trace, run, part):
    """ms/step of ``part`` (a name of ``TABLE``, ``mamba``, ``flash`` or
    ``diff``); None without a slice or without these scopes."""
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
