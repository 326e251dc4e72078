"""Device time of a traced slice under the nodes a state-space model
adds. The program traces a ``Mamba2`` node's ops under ``ssm/<node
name>`` (``executor.op_class``), and inside it ``conv1d`` (the causal
depthwise convolution and its silu), ``scan`` (step sizes, decays, the
chunked scan's four products, the recurrence over chunks, the skip) and
``gate_norm`` (the gate and the grouped RMSNorm); the backward pass and
what it recomputes of the forward keep those names inside JAX's
``transpose(jvp(...))``, ``checkpoint`` and ``rematted_computation``
wrappers. The two projections round the core are the ``FullyConnected``
nodes named ``layer<i>_in_proj`` and ``layer<i>_out_proj``. Same pieces
as ``lm_scopes``: events and the slice's window from ``reduce_trace``,
scope names and self times from ``reduce_scopes``. A program without
these scopes (an older commit, another model) reads as ``None``, never
as zero.

    python3 bench/ssm_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import reduce_scopes
import reduce_trace

PARTS = ("conv1d", "scan", "gate_norm")
_NODE = re.compile(r"[/(]ssm/([^/()]+)")
_PART = re.compile(r"/(%s)(?=/|\)|$)" % "|".join(PARTS))
_PROJ = re.compile(r"[/(][a-z]+/layer\d+_(?:in|out)_proj\b")


def part_of(scope):
    """``ssm`` with the inner scope (or ``other``) for an op under a
    ``Mamba2`` node, ``proj`` for one under its two projections, else
    None."""
    m = _NODE.search(scope)
    if m:
        part = _PART.search(scope, m.end())
        return "ssm", part.group(1) if part else "other"
    return ("proj", None) if _PROJ.search(scope) else None


def reduce(raw, scopes, device=0):
    """Seconds of ``device`` over the benchmark's slice: ``ssm`` (every
    op under a ``Mamba2`` node), ``conv1d``, ``scan``, ``gate_norm`` (its
    inner scopes) and ``proj`` (the in and out projections), each None
    where no op carries such a scope; None without a slice or where
    nothing does."""
    window = reduce_trace.slice_window(
        raw["host"], reduce_trace.SLICE_BEGIN, reduce_trace.SLICE_END)
    if window is None or device not in raw["devices"]:
        return None
    ops = list(reduce_trace._clip(raw["devices"][device]["ops"], window))
    names = scopes.get(device, {})
    by_part = collections.Counter()
    proj, found_proj = 0, False
    for text, own in reduce_scopes.self_times(ops):
        kind = part_of(names.get(text) or "")
        if kind is None:
            continue
        if kind[0] == "ssm":
            by_part[kind[1]] += own
        else:
            proj, found_proj = proj + own, True
    # the projections' names alone do not make a state-space model
    if not by_part:
        return None
    out = {part: by_part.get(part, 0) / 1e9 for part in PARTS}
    out["ssm"] = sum(by_part.values()) / 1e9
    out["proj"] = proj / 1e9 if found_proj else None
    return out


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["ssm_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "ssm_scopes" in run:
        return run["ssm_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path),
                              reduce_scopes.scope_names(path))
    return _cache[path]


def ms(trace, run, part):
    """ms/step of ``part`` (ssm, conv1d, scan, gate_norm, proj); None
    without a slice or without these scopes."""
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
