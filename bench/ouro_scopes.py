"""Device time of a traced slice in the parts of a looped stack
(``models/ouro.py``) that no other table names. The program traces a
node's ops under ``<op class>/<node name>`` (``executor.op_class``), and
the symbol names a node for its pass and its layer
(``loop<t>_layer<i>_*``, passes from 1), so a scope says which pass a
device op belongs to though every pass reads the same weights; the
backward pass keeps those names inside JAX's ``transpose(jvp(...))``
wrappers. A visit's four attention projections are the
``FullyConnected`` nodes ``loop<t>_layer<i>_{q,k,v,o}_proj``, its SwiGLU
``loop<t>_layer<i>_{gate,up,down}_proj`` (the activation and the product
between them are auto-named nodes: their ops count where XLA fuses
them); after a pass come ``loop<t>_final_norm``, ``loop<t>_lm_head*``
and ``loop<t>_exit_gate``, and after the last the exit mixing
(``exit_*`` nodes) and ``loss``.

An op is added to EVERY name of ``PARTS`` its scope matches (a
projection is its layer's, its pass's and ``proj``); ``pass_s`` is the
layers' time by pass. The attention kernels' scope
(``attn/<node>/full``) is ``share_scopes``', the classes ``attn`` and
``norm`` ``lm_scopes``', the kernels' own ops ``solar2_scopes``'.
Events, the slice's window, scope names, self times and the slice's path
are ``reduce_trace``'s and ``reduce_scopes``'s. A program without a
looped node (an older commit, another model) reads as ``None``, never as
zero.

    python3 bench/ouro_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import lm_scopes
import reduce_scopes
import reduce_trace
import share_scopes

_LAYER = r"[/(][a-z]+/loop(\d+)_layer\d+_"
PARTS = collections.OrderedDict([
    ("layers", re.compile(_LAYER)),
    ("proj", re.compile(r"[/(]fc/loop\d+_layer\d+_[qkvo]_proj\b")),
    ("mlp", re.compile(r"[/(]fc/loop\d+_layer\d+_(?:gate|up|down)_proj\b")),
    ("exit", re.compile(
        r"[/(][a-z]+/(?:loop\d+_(?:lm_head|exit_gate)|exit_|loss\b)"))])


def parts_of(scope):
    """(the names of ``PARTS`` an op of this scope is added to, the pass
    its layer node belongs to or None)."""
    names = [name for name, pattern in PARTS.items() if pattern.search(scope)]
    m = PARTS["layers"].search(scope)
    return names, int(m.group(1)) if m else None


def reduce(raw, scopes, device=0):
    """Seconds of ``device`` over the benchmark's slice by ``PARTS``'
    names, and ``pass_s`` {pass: seconds of its layer nodes}. None
    without a slice or where no op is a looped layer's."""
    window = reduce_trace.slice_window(
        raw["host"], reduce_trace.SLICE_BEGIN, reduce_trace.SLICE_END)
    if window is None or device not in raw["devices"]:
        return None
    ops = reduce_trace._clip(raw["devices"][device]["ops"], window)
    names = scopes.get(device, {})
    found = collections.Counter()
    by_pass = collections.Counter()
    for text, own in reduce_scopes.self_times(list(ops)):
        parts, which = parts_of(names.get(text) or "")
        for part in parts:
            found[part] += own
        if which is not None:
            by_pass[which] += own
    if not by_pass:
        return None
    out = {name: found[name] / 1e9 for name in PARTS}
    out["pass_s"] = {k: v / 1e9 for k, v in sorted(by_pass.items())}
    return out


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["ouro_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "ouro_scopes" in run:
        return run["ouro_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path),
                              reduce_scopes.scope_names(path))
    return _cache[path]


def ms(trace, run, part):
    """ms/step of ``part`` (a name of ``PARTS``); None without a slice or
    without these scopes."""
    if not trace or not run.get("trace_steps"):
        return None
    red = of(run)
    if not red:
        return None
    return reduce_scopes.per_step_ms(run, red[part])


def class_ms(trace, run, cls):
    """ms/step of the op class ``cls`` (``lm_scopes``' table) in a run of
    a looped stack; None for any other program."""
    if ms(trace, run, "layers") is None:
        return None
    return lm_scopes.class_ms(trace, run, cls)


def ouro_flops(run):
    """The configuration's operations module where it counts a looped
    stack (``visits`` and ``attention_flops``), or None."""
    flops = share_scopes.flops_of(run)
    return flops if (getattr(flops, "visits", None)
                     and getattr(flops, "attention_flops", None)) else None


if __name__ == "__main__":
    path = sys.argv[1]
    red = reduce(reduce_trace.load(path), reduce_scopes.scope_names(path))
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 1

    def per_step(x):
        if isinstance(x, dict):
            return {str(k): per_step(v) for k, v in sorted(x.items())}
        return round(1e3 * x / steps, 4)

    print(json.dumps({"steps": steps, "ms_per_step": per_step(red)}
                     if red else None, indent=1))
