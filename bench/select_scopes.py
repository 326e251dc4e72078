"""Device time of a traced slice in a keep-mask's two halves, whichever
model traces them: the indexer that makes it and the attention that reads
it. The program traces a node's ops under ``<op class>/<node name>``
(``executor.op_class``); inside a ``KeyIndexer`` node everything is under
``index`` (its projections, the key's LayerNorm, the rotations, the
blocked scores) and the choice of the keys under ``index/topk``; an
attention node's kernels under a keep-mask are under ``select``, and a
``LatentAttention`` node (which has a selected form of its own, read by
entries of its own) also traces a scope ``latent`` that a plain
``Attention`` node never does. The backward pass keeps those names inside
JAX's ``transpose(jvp(...))`` wrappers.

``reduce`` files the slice's self times by node and part; ``ms`` sums a
part over the nodes a reader wants. Events, the slice's window, scope
names, self times and the slice's path are ``reduce_trace``'s and
``reduce_scopes``'s. A program without an indexer or a selected attention
node (an older commit, another model) reads as ``None``, never as zero.

    python3 bench/select_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import reduce_scopes
import reduce_trace

PARTS = ("index_topk", "index", "select", "latent")
# a transform's wrapper closes between the node and what it scoped:
# ``jvp(attn/<node>)/select``, ``transpose(jvp(attn/<node>))/select``
_PART = re.compile(
    r"[/(]attn/([^/()]+)\)*/(index/topk|index|select|latent)(?=/|\)|$)")


def part_of(scope):
    """(node, part) of an op's scope path, or None."""
    m = _PART.search(scope or "")
    return (m.group(1), m.group(2).replace("/", "_")) if m else None


def reduce(raw, scopes, device=0):
    """{node: {part: seconds}} of ``device`` over the benchmark's slice;
    None without a slice or where no op is an indexer's or a selected
    attention's."""
    window = reduce_trace.slice_window(
        raw["host"], reduce_trace.SLICE_BEGIN, reduce_trace.SLICE_END)
    if window is None or device not in raw["devices"]:
        return None
    ops = reduce_trace._clip(raw["devices"][device]["ops"], window)
    names = scopes.get(device, {})
    found = collections.defaultdict(collections.Counter)
    for text, own in reduce_scopes.self_times(list(ops)):
        filed = part_of(names.get(text))
        if filed:
            found[filed[0]][filed[1]] += own
    if not any("select" in parts or "index" in parts
               for parts in found.values()):
        return None
    return {node: {part: ns / 1e9 for part, ns in parts.items()}
            for node, parts in found.items()}


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["select_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "select_scopes" in run:
        return run["select_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path),
                              reduce_scopes.scope_names(path))
    return _cache[path]


def ms(trace, run, parts, plain_only=False):
    """ms/step of ``parts`` together over every node (``plain_only``: over
    the nodes that trace no ``latent`` scope, plain ``Attention``'s); None
    without a slice or where no such node traces the first of them."""
    if not trace or not run.get("trace_steps"):
        return None
    red = of(run)
    if not red:
        return None
    nodes = [found for found in red.values()
             if parts[0] in found and not (plain_only and "latent" in found)]
    if not nodes:
        return None
    return reduce_scopes.per_step_ms(
        run, sum(found.get(part, 0.0) for found in nodes for part in parts))


if __name__ == "__main__":
    path = sys.argv[1]
    red = reduce(reduce_trace.load(path), reduce_scopes.scope_names(path))
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    print(json.dumps({"steps": steps, "ms_per_step": {
        node: {k: round(1e3 * v / steps, 4) for k, v in sorted(parts.items())}
        for node, parts in sorted(red.items())}} if red else None, indent=1))
