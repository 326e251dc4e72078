"""Device time of a traced slice by the op classes a transformer adds
(``attn``, ``moe``, ``norm``, ``embed``), by the head and its loss, and
inside the expert layer by ``router`` / ``dispatch`` / ``experts`` /
``combine``.

``reduce_scopes.CLASSES`` is a closed tuple of the conv-net classes, so
the match for the new ones lives here, over the same pieces: events and
the slice's window from ``reduce_trace``, scope names and self times
from ``reduce_scopes``. The program traces a node under
``<op class>/<node name>`` (``executor.op_class``); the head and the
loss are the nodes named ``lm_head*`` and ``loss`` whatever their class.
XLA lowers ``jax.lax.ragged_dot`` on the TPU to a Mosaic fusion whose
metadata it names ``ragged-dot-*`` itself, dropping the program's scope;
the expert layers' grouped matmuls are the only ragged dots there are,
so that name is filed as ``moe`` / ``experts`` (``reduce_scopes`` files
it as unscoped). A program without these scopes (an older commit, a
conv net) reads as ``None``, never as zero.

    python3 bench/lm_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import lib
import reduce_scopes
import reduce_trace

CLASSES = ("attn", "moe", "norm", "embed")
MOE_PARTS = ("router", "dispatch", "experts", "combine")
_CLASS = re.compile(r"[/(](%s)/" % "|".join(CLASSES))
_HEAD = re.compile(r"[/(][a-z]+/(?:lm_head|loss)[^/)]*")
_PART = re.compile(r"/(%s)(?=/|\)|$)" % "|".join(MOE_PARTS))
_RAGGED = re.compile(r"ragged[-_]dot")


def reduce(raw, scopes, device=0):
    """Seconds of ``device`` over the benchmark's slice: ``class_s``
    {attn, moe, norm, embed}, ``head_loss_s``, ``moe_part_s`` {router,
    dispatch, experts, combine, other}; None without a slice or where no
    op carries one of these scopes."""
    window = reduce_trace.slice_window(
        raw["host"], reduce_trace.SLICE_BEGIN, reduce_trace.SLICE_END)
    if window is None or device not in raw["devices"]:
        return None
    ops = list(reduce_trace._clip(raw["devices"][device]["ops"], window))
    names = scopes.get(device, {})
    by_class = collections.Counter()
    by_part = collections.Counter()
    head = 0
    for text, own in reduce_scopes.self_times(ops):
        scope = names.get(text) or ""
        m = _CLASS.search(scope)
        if not m and (_RAGGED.search(scope) or _RAGGED.search(text)):
            by_class["moe"] += own
            by_part["experts"] += own
        elif m:
            by_class[m.group(1)] += own
            if m.group(1) == "moe":
                part = _PART.search(scope)
                by_part[part.group(1) if part else "other"] += own
        elif _HEAD.search(scope):
            head += own
    if not by_class and not head:
        return None
    return {"class_s": {c: by_class.get(c, 0) / 1e9 for c in CLASSES},
            "head_loss_s": head / 1e9,
            "moe_part_s": {k: v / 1e9 for k, v in by_part.items()}}


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["lm_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "lm_scopes" in run:
        return run["lm_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path),
                              reduce_scopes.scope_names(path))
    return _cache[path]


def ms(trace, run, pick):
    """``pick(reduction)`` seconds as ms/step; None without a slice or
    without these scopes."""
    if not trace or not run.get("trace_steps"):
        return None
    red = of(run)
    if not red:
        return None
    return reduce_scopes.per_step_ms(run, pick(red))


def class_ms(trace, run, cls):
    return ms(trace, run, lambda red: red["class_s"][cls])


def roofline_share(trace, run, cls, flops_function):
    """Required operations of the class's matrix products, forward and
    backward (three forwards), over the bf16 peak, as a share of the
    class's device time per step. ``flops_function`` names the function
    of the configuration's operations module that counts one layer's
    forward; None where the configuration has no such layer."""
    name = run.get("cfg", {}).get("flops")
    count = name and getattr(lib.load_module("flops", name),
                             flops_function, None)
    busy_ms = class_ms(trace, run, cls)
    if not count or not busy_ms or not run.get("peak"):
        return None
    flops = (run["flops_multiplier"] * count(run["cfg"])
             * run["cfg"]["num_hidden_layers"] * run["batch"] / run["chips"])
    return 100.0 * (1e3 * flops / run["peak"]["bf16_flops"]) / busy_ms


if __name__ == "__main__":
    path = sys.argv[1]
    red = reduce(reduce_trace.load(path), reduce_scopes.scope_names(path))
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 1

    def per_step(x):
        if isinstance(x, dict):
            return {k: per_step(v) for k, v in sorted(x.items())}
        return round(1e3 * x / steps, 4)

    print(json.dumps({"steps": steps, "ms_per_step": per_step(red)}
                     if red else None, indent=1))
