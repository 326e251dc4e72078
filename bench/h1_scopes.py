"""Device time of a traced slice under the nodes a block of two parallel
mixers adds (Falcon-H1: ``Mamba2`` and ``Attention`` side by side off one
norm, under fixed multipliers). The program traces a node's ops under
``<class>/<node name>`` (``executor.op_class``): ``ssm/layer<i>_ssm``
with ``conv1d``, ``scan`` and ``gate_norm`` inside it, ``attn/
layer<i>_attn`` with its kernels under ``full``, ``attn/layer<i>_{q,k}_
rope``, the ``FullyConnected`` nodes ``fc/layer<i>_{in,out,q,k,v,o,gate,
up,down}_proj``, and the multipliers' and the sum's nodes, class ``act``,
named for what they scale: ``layer<i>_attn_in_scale``, ``layer<i>_k_proj_
scale``, ``layer<i>_mixer_sum`` with the residual add ``layer<i>_mixer_
add``, ``layer<i>_gate_proj_scale``, ``layer<i>_down_proj_scale``; the
backward pass and what it recomputes keep those names inside JAX's
``transpose(jvp(...))`` wrappers. (``Mamba2``'s five multipliers are
inside the node: no op of their own.)

``TABLE`` is all this file adds: which scope is filed under which name.
Events, the slice's window, scope names, self times and the slice's
path are ``reduce_trace``'s and ``reduce_scopes``'s. A program without a
layer that holds BOTH an ``ssm`` node and an ``attn`` node (an older
commit, another model: Nemotron's blocks hold one or the other) reads as
``None``, never as zero.

    python3 bench/h1_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import reduce_scopes
import reduce_trace

_SSM = r"[/(]ssm/layer\d+_ssm"
_ATTN = r"[/(]attn/layer\d+_attn"
_INSIDE = r"(?:.*/)?%s(?=/|\)|:|$)"
# name -> pattern, tried in this order; the first that matches files the op
TABLE = collections.OrderedDict(
    [(part, re.compile(_SSM + r"\)*/" + _INSIDE % part))
     for part in ("conv1d", "scan", "gate_norm")]
    + [("ssm_other", re.compile(_SSM + r"\b")),
       ("attn_full", re.compile(_ATTN + r"\)*/" + _INSIDE % "full")),
       ("attn_other", re.compile(
           r"[/(]attn/layer\d+_(?:attn|q_rope|k_rope)\b")),
       ("mixer_proj", re.compile(
           r"[/(]fc/layer\d+_(?:in|out|q|k|v|o)_proj\b")),
       ("mixer_scale", re.compile(
           r"[/(]act/layer\d+_(?:attn_in_scale|k_proj_scale|mixer_sum|"
           r"mixer_add)\b")),
       ("mlp", re.compile(
           r"[/(](?:fc/layer\d+_(?:gate|up|down)_proj|"
           r"act/layer\d+_(?:gate|down)_proj_scale)\b"))])
SSM = ("conv1d", "scan", "gate_norm", "ssm_other")
MIXER = SSM + ("attn_full", "attn_other", "mixer_proj", "mixer_scale")
_LAYER = re.compile(r"[/(](ssm|attn)/layer(\d+)_(?:ssm|attn)\b")


def part_of(scope):
    """The name of ``TABLE`` an op of this scope is filed under, or
    None."""
    for name, pattern in TABLE.items():
        if pattern.search(scope):
            return name
    return None


def reduce(raw, scopes, device=0):
    """Seconds of ``device`` over the benchmark's slice by ``TABLE``'s
    names, ``ssm`` (every op under a ``Mamba2`` node) and ``mixer`` (every
    op of the parallel mixers); a name no op carries reads None. None
    without a slice or where no layer holds both kinds of node."""
    window = reduce_trace.slice_window(
        raw["host"], reduce_trace.SLICE_BEGIN, reduce_trace.SLICE_END)
    if window is None or device not in raw["devices"]:
        return None
    ops = reduce_trace._clip(raw["devices"][device]["ops"], window)
    names = scopes.get(device, {})
    found = collections.Counter()
    layers = {"ssm": set(), "attn": set()}
    for text, own in reduce_scopes.self_times(list(ops)):
        scope = names.get(text) or ""
        part = part_of(scope)
        if part:
            found[part] += own
            m = _LAYER.search(scope)
            if m:
                layers[m.group(1)].add(m.group(2))
    if not layers["ssm"] & layers["attn"]:
        return None
    out = {name: found[name] / 1e9 if name in found else None
           for name in TABLE}
    out["ssm"] = sum(found[part] for part in SSM) / 1e9
    out["mixer"] = sum(found[part] for part in MIXER) / 1e9
    return out


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["h1_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "h1_scopes" in run:
        return run["h1_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path),
                              reduce_scopes.scope_names(path))
    return _cache[path]


def ms(trace, run, part):
    """ms/step of ``part`` (a name of ``TABLE``, ``ssm`` or ``mixer``);
    None without a slice or without these scopes."""
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
