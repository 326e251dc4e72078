"""Device time of a traced slice in the parts of a dots3 attention layer
that no other table names. The program traces a node's ops under ``<op
class>/<node name>`` (``executor.op_class``); inside a ``KeyIndexer`` node
everything is under ``index`` (its three projections, the key's
LayerNorm, the rotations, the blocked scores) and the choice of the keys
under ``index/topk``; inside a ``LatentAttention`` node the latent's
norm, rescale, up-projection and rotations are under ``latent``, the
kernels under ``select`` (a keep-mask chooses the keys), ``window`` or
``full``, and the gate a head on their output under ``gate``; the
backward pass keeps those names inside JAX's ``transpose(jvp(...))``
wrappers. A layer's projections outside the two ops are the
``FullyConnected`` nodes ``layer<i>_{q_a,q_b,kv_a,attn_gate,o}_proj``,
the query latent's norm ``layer<i>_q_a_norm`` and its rescale
``layer<i>_q_a_scale``.

``TABLE`` is all this file adds: which scope is filed under which name.
Events, the slice's window, scope names, self times and the slice's path
are ``reduce_trace``'s and ``reduce_scopes``'s. A program without an
indexer or a selected attention node (an older commit, another model)
reads as ``None``, never as zero.

    python3 bench/dots3_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import reduce_scopes
import reduce_trace
import share_scopes


def _inside(scope):
    # a transform's wrapper closes between the node and what it scoped:
    # ``jvp(attn/<node>)/select``, ``transpose(jvp(attn/<node>))/gate``
    return re.compile(r"[/(]attn/[^/()]+\)*/%s(?=/|\)|$)" % scope)


# name -> pattern, tried in this order; the first that matches files the op
TABLE = collections.OrderedDict([
    ("index_topk", _inside("index/topk")),
    ("index", _inside("index")),
    ("select", _inside("select")),
    ("window", _inside("window")),
    ("gate", _inside("gate")),
    ("latent", _inside("latent")),
    ("proj", re.compile(
        r"[/(](?:fc/layer\d+_(?:q_a|q_b|kv_a|attn_gate|o)_proj"
        r"|norm/layer\d+_q_a_norm|act/layer\d+_q_a_scale)\b"))])


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
    where no op is an indexer's or a selected attention's."""
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
    if "index" not in found and "select" not in found:
        return None
    return {name: found[name] / 1e9 if name in found else None
            for name in TABLE}


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["dots3_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "dots3_scopes" in run:
        return run["dots3_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path),
                              reduce_scopes.scope_names(path))
    return _cache[path]


def ms(trace, run, *parts):
    """ms/step of ``parts`` (names of ``TABLE``) together; None without a
    slice, without these scopes or where one of the parts is absent."""
    if not trace or not run.get("trace_steps"):
        return None
    red = of(run)
    if not red or any(red.get(part) is None for part in parts):
        return None
    return reduce_scopes.per_step_ms(run, sum(red[part] for part in parts))


def dots3_flops(run):
    """The configuration's operations module where it counts a selection
    (``selected_pairs`` and ``attn_select_flops``), or None."""
    flops = share_scopes.flops_of(run)
    return flops if (getattr(flops, "selected_pairs", None)
                     and getattr(flops, "attn_select_flops", None)) else None


if __name__ == "__main__":
    path = sys.argv[1]
    red = reduce(reduce_trace.load(path), reduce_scopes.scope_names(path))
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    print(json.dumps({"steps": steps, "ms_per_step": {
        k: None if v is None else round(1e3 * v / steps, 4)
        for k, v in sorted(red.items())}} if red else None, indent=1))
