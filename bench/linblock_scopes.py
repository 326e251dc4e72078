"""Device time of a traced slice under the nodes a linear-attention /
block-sparse hybrid adds, whichever model traces them. The program traces a
``LinearAttention`` node's ops under ``linattn/<node name>``
(``executor.op_class``), and inside it ``core`` (the recurrence with a fixed
decay a head: the state-space scan's kernel pair ``ssd_fwd_*`` / ``ssd_bwd_*``
where the shapes take it, its tables and the scale), ``norm`` (the RMSNorm a
head behind it) and ``gate`` (the sigmoid gate); the five wide projections
round it are the ``FullyConnected`` nodes
``layer<i>_linattn_{q,k,v,g,o}_proj``.
A ``BlockSelect`` node's ops are under ``attn/<node name>/blocks``: ``pool``
(the pooled keys' means), ``score`` (the group's queries against them, the
softmax a head, the sum over the heads, a block's largest window) and
``choose`` (the candidates, ``topk_mask_*``, the keep-mask a key). The
backward pass and what it recomputes of the forward keep those names inside
JAX's ``transpose(jvp(...))``, ``checkpoint`` and ``rematted_computation``
wrappers.

``TABLE`` is all this file adds: which scope is filed under which name.
Events, the slice's window, scope names, self times and the slice's path are
``reduce_trace``'s and ``reduce_scopes``'s. A program without these scopes (an
older commit, another model) reads as ``None``, never as zero.

    python3 bench/linblock_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import reduce_scopes
import reduce_trace

_LIN = r"[/(]linattn/[^/()]+"
_BLOCKS = r"[/(]attn/[^/()]+\)*/blocks"
_INSIDE = r"\)*/(?:.*/)?%s(?=/|\)|:|$)"
# name -> pattern, tried in this order; the first that matches files the op
TABLE = collections.OrderedDict(
    [("linattn_" + part, re.compile(_LIN + _INSIDE % part))
     for part in ("core", "norm", "gate")]
    + [("linattn_other", re.compile(_LIN)),
       ("linattn_proj", re.compile(
           r"[/(][a-z]+/layer\d+_linattn_[qkvgo]_proj\b"))]
    + [("blocks_" + part, re.compile(_BLOCKS + _INSIDE % part))
       for part in ("pool", "score", "choose")]
    + [("blocks_other", re.compile(_BLOCKS + r"(?=/|\)|:|$)"))])
# what a reader may ask for besides TABLE's names: their sums
SUMS = {"linattn": ("linattn_core", "linattn_norm", "linattn_gate",
                    "linattn_other"),
        "blocks": ("blocks_pool", "blocks_score", "blocks_choose",
                   "blocks_other")}


def part_of(scope):
    """The name of ``TABLE`` an op of this scope is filed under, or
    None."""
    for name, pattern in TABLE.items():
        if pattern.search(scope):
            return name
    return None


def reduce(raw, scopes, device=0):
    """Seconds of ``device`` over the benchmark's slice by ``TABLE``'s names
    and ``SUMS``'; a name no op carries reads None. None without a slice or
    where no op is under a ``LinearAttention`` or a ``BlockSelect`` node:
    projections named alike do not make such a model."""
    window = reduce_trace.step_window(raw)
    if window is None or device not in raw["devices"]:
        return None
    ops = reduce_trace._clip(raw["devices"][device]["ops"], window)
    names = scopes.get(device, {})
    found = collections.Counter()
    for text, own in reduce_scopes.self_times(list(ops)):
        part = part_of(names.get(text) or "")
        if part:
            found[part] += own
    out = {name: found[name] / 1e9 if name in found else None
           for name in TABLE}
    for name, parts in SUMS.items():
        held = [found[part] for part in parts if part in found]
        out[name] = sum(held) / 1e9 if held else None
    if out["linattn"] is None and out["blocks"] is None:
        return None
    return out


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["linblock_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "linblock_scopes" in run:
        return run["linblock_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(*reduce_scopes.loaded(path))
    return _cache[path]


def ms(trace, run, part):
    """ms/step of ``part`` (a name of ``TABLE`` or of ``SUMS``); None
    without a slice or without these scopes."""
    if not trace or not run.get("whole_steps"):
        return None
    red = of(run)
    if not red or red.get(part) is None:
        return None
    return reduce_scopes.per_step_ms(run, red[part])


def least_ms(run, count, moved, held):
    """The least ms the chip could take for ONE pass over ``held(cfg)``
    layers, each bound by its operations ``count(cfg)`` over the bf16 peak
    or its bytes ``moved(cfg)`` over the HBM peak, whichever is larger
    (the three functions are an operations module's); None without the
    peaks or without one of them."""
    peak = run.get("peak")
    if not peak or not count or not moved or not held:
        return None
    cfg = run["cfg"]
    each = run["batch"] * held(cfg) / float(run["chips"])
    return 1e3 * each * max(count(cfg) / peak["bf16_flops"],
                            moved(cfg) / peak["hbm_bytes_s"])


if __name__ == "__main__":
    path = sys.argv[1]
    red = reduce(reduce_trace.load(path), reduce_scopes.scope_names(path))
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    print(json.dumps({"steps": steps, "ms_per_step": {
        k: None if v is None else round(1e3 * v / steps, 4)
        for k, v in sorted(red.items())}} if red else None, indent=1))
