"""Device time of a traced slice inside the ``attn`` class by the kind
of layer: the program traces an ``Attention`` node's kernels under
``attn/<node name>/window`` or ``attn/<node name>/full`` (RoPE nodes are
``attn`` too and under neither). Same pieces as ``lm_scopes``: events
and the slice's window from ``reduce_trace``, scope names and self times
from ``reduce_scopes``. A program without these scopes (an older commit,
a conv net) reads as ``None``, never as zero.

    python3 bench/share_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import lib
import reduce_scopes
import reduce_trace

KINDS = ("window", "full")
# a transform's wrapper closes between the node and what it scoped:
# ``jvp(attn/<node>)/window``, ``transpose(jvp(attn/<node>))/window``
_KIND = re.compile(r"[/(]attn/[^/()]+\)*/(%s)(?=/|\)|$)" % "|".join(KINDS))


def reduce(raw, scopes, device=0):
    """Seconds of ``device`` over the benchmark's slice by the kind of
    attention layer, {window, full}; None without a slice or where no op
    carries such a scope."""
    window = reduce_trace.slice_window(
        raw["host"], reduce_trace.SLICE_BEGIN, reduce_trace.SLICE_END)
    if window is None or device not in raw["devices"]:
        return None
    ops = list(reduce_trace._clip(raw["devices"][device]["ops"], window))
    names = scopes.get(device, {})
    by_kind = collections.Counter()
    for text, own in reduce_scopes.self_times(ops):
        m = _KIND.search(names.get(text) or "")
        if m:
            by_kind[m.group(1)] += own
    if not by_kind:
        return None
    return {k: by_kind.get(k, 0) / 1e9 for k in KINDS}


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["share_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "share_scopes" in run:
        return run["share_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path),
                              reduce_scopes.scope_names(path))
    return _cache[path]


def attn_ms(trace, run, kind):
    """ms/step in attention kernels of ``kind`` layers; None without a
    slice or without these scopes."""
    if not trace or not run.get("trace_steps"):
        return None
    red = of(run)
    if not red:
        return None
    return reduce_scopes.per_step_ms(run, red[kind])


def roofline_share(run, forward_flops, busy_ms):
    """``forward_flops`` (one step's, all layers) forward and backward
    over the bf16 peak, as a share of ``busy_ms``."""
    if not forward_flops or not busy_ms or not run.get("peak"):
        return None
    flops = (run["flops_multiplier"] * forward_flops * run["batch"]
             / run["chips"])
    return 100.0 * (1e3 * flops / run["peak"]["bf16_flops"]) / busy_ms


def flops_of(run):
    """The configuration's operations module, or None."""
    name = run.get("cfg", {}).get("flops")
    return lib.load_module("flops", name) if name else None


def held_rows(run):
    """Rows each expert layer's held experts received in the last step
    (the whole batch's): from the model's count outputs, over the
    share's experts, whose number the configuration spells
    ``n_routed_experts`` or ``num_experts``. None where the run has no
    counts or the configuration no share of its experts."""
    cfg = run.get("cfg", {})
    counts, share = run.get("expert_counts"), cfg.get("share")
    held = cfg.get("n_routed_experts", cfg.get("num_experts"))
    if not counts or not share or not held:
        return None
    lo = share.get("expert_offset", 0)
    return [sum(layer[lo:lo + held]) for layer in counts]


if __name__ == "__main__":
    path = sys.argv[1]
    red = reduce(reduce_trace.load(path), reduce_scopes.scope_names(path))
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    print(json.dumps({"steps": steps, "ms_per_step": {
        k: round(1e3 * v / steps, 4) for k, v in sorted(red.items())}}
        if red else None, indent=1))
