"""Device time of a traced slice in the projections round the mixers of a
Kimi Linear model, which no other table names. The program traces a
``FullyConnected`` node's ops under ``fc/<node name>``
(``executor.op_class``): a KDA layer's nine are
``layer<i>_kda_{q,k,v,o}_proj`` (wide), ``layer<i>_kda_{f,g}_{a,b}_proj``
(the low-rank pairs into the decay and the gate) and
``layer<i>_kda_b_proj`` (the write strength's); a latent layer's three
are ``layer<i>_{q,kv_a,o}_proj``; the backward pass keeps those names
inside JAX's ``transpose(jvp(...))`` wrappers. The nodes themselves are
``gdn_scopes``' (``gdn/layer<i>_kda``) and ``mla_scopes``'
(``attn/layer<i>_attn``), the experts ``lm_scopes``' class ``moe``.

``TABLE`` is all this file adds: which scope is filed under which name.
Events, the slice's window, scope names, self times and the slice's
path are ``reduce_trace``'s and ``reduce_scopes``'s. A program without a
KDA projection (an older commit, another model: Kanana's latent layers
have the same three names) reads as ``None``, never as zero.

    python3 bench/kda_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import reduce_scopes
import reduce_trace
import share_scopes

# name -> pattern, tried in this order; the first that matches files the op
TABLE = collections.OrderedDict([
    ("kda_proj", re.compile(
        r"[/(]fc/layer\d+_kda_(?:q|k|v|o|b|[fg]_[ab])_proj\b")),
    ("mla_proj", re.compile(r"[/(]fc/layer\d+_(?:q|kv_a|o)_proj\b"))])


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
    where no op is a KDA projection's."""
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
    if "kda_proj" not in found:
        return None
    return {name: found[name] / 1e9 if name in found else None
            for name in TABLE}


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["kda_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "kda_scopes" in run:
        return run["kda_scopes"]
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


def kimi_flops(run):
    """The configuration's operations module where it counts a KDA
    core (``kda_core_flops``), or None."""
    flops = share_scopes.flops_of(run)
    return flops if getattr(flops, "kda_core_flops", None) else None


if __name__ == "__main__":
    path = sys.argv[1]
    red = reduce(reduce_trace.load(path), reduce_scopes.scope_names(path))
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    print(json.dumps({"steps": steps, "ms_per_step": {
        k: None if v is None else round(1e3 * v / steps, 4)
        for k, v in sorted(red.items())}} if red else None, indent=1))
