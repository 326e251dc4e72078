"""Device time of a traced slice under the nodes a latent-attention
model adds. The program traces a ``LatentAttention`` node's ops under
``attn/<node name>``, and inside it ``latent`` (the latent's norm, the
up-projection, the two rotations, the key's concatenation, and their
backward) and ``full`` (the attention kernel's three calls, as
``Attention`` scopes them); a node counts as a latent one where some op
of it carries ``latent``. The shared experts are the ``FullyConnected``
nodes named ``layer<i>_shared_{gate,up,down}_proj``. Same pieces as
``lm_scopes``: events and the slice's window from ``reduce_trace``,
scope names and self times from ``reduce_scopes``. A program without
these scopes (an older commit, another model) reads as ``None``, never
as zero.

    python3 bench/mla_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import reduce_scopes
import reduce_trace

# a transform's wrapper closes between the node and what it scoped:
# ``jvp(attn/<node>)/latent``, ``transpose(jvp(attn/<node>))/full``
_NODE = re.compile(r"[/(]attn/([^/()]+)\)*(?:/(latent|full)(?=/|\)|$))?")
_SHARED = re.compile(r"[/(][a-z]+/layer\d+_shared_(?:gate|up|down)_proj\b")


def reduce(raw, scopes, device=0):
    """Seconds of ``device`` over the benchmark's slice: ``mla`` (every
    op under a latent node), ``latent`` and ``full`` (its two scopes) and
    ``shared`` (the shared experts' three nodes), each None where no op
    carries such a scope; None without a slice."""
    window = reduce_trace.slice_window(
        raw["host"], reduce_trace.SLICE_BEGIN, reduce_trace.SLICE_END)
    if window is None or device not in raw["devices"]:
        return None
    ops = list(reduce_trace._clip(raw["devices"][device]["ops"], window))
    names = scopes.get(device, {})
    by_node = collections.defaultdict(collections.Counter)
    shared, found_shared = 0, False
    for text, own in reduce_scopes.self_times(ops):
        scope = names.get(text) or ""
        m = _NODE.search(scope)
        if m:
            by_node[m.group(1)][m.group(2) or "other"] += own
        elif _SHARED.search(scope):
            shared, found_shared = shared + own, True
    nodes = [parts for parts in by_node.values() if "latent" in parts]
    if not nodes and not found_shared:
        return None
    out = {"mla": None, "latent": None, "full": None,
           "shared": shared / 1e9 if found_shared else None}
    if nodes:
        out["mla"] = sum(sum(p.values()) for p in nodes) / 1e9
        out["latent"] = sum(p["latent"] for p in nodes) / 1e9
        out["full"] = sum(p["full"] for p in nodes) / 1e9
    return out


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["mla_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "mla_scopes" in run:
        return run["mla_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path),
                              reduce_scopes.scope_names(path))
    return _cache[path]


def ms(trace, run, part):
    """ms/step of ``part`` (mla, latent, full, shared); None without a
    slice or without these scopes."""
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
