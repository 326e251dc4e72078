"""Device time of a traced slice under the nodes a model with several
residual streams, a query latent and a prediction module adds. The
program traces a ``HyperCoeff`` / ``HyperMix`` node's ops under
``hc/<node name>`` and inside it ``hc_coeff`` (the one pass over the
stream: the coefficient products and the mean square, then sigmoid, clamp
and exp), ``hc_sinkhorn`` (the normalising iterations and what they left)
and ``hc_mix`` (the read off the streams and the write back beside the
carried ones); the query latent's three nodes are named ``*_q_latent_*``
(``fc`` the two projections, ``norm`` the latent's norm); every node of
prediction module k is named ``mtp<k>_*`` whatever its class (its
embedding read, norms, projection, block with its own mixing, head and
loss). ``mtp`` therefore overlaps the three ``hc_*`` parts and
``q_latent``: they are cuts of one step, not terms of a sum. Same pieces
as ``mla_scopes``: events and the slice's window from ``reduce_trace``,
scope names and self times from ``reduce_scopes``. A program without these
scopes (an older commit, another model) reads as ``None``, never as zero.

    python3 bench/hc_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import reduce_scopes
import reduce_trace

PARTS = ("hc_coeff", "hc_sinkhorn", "hc_mix")
# a transform's wrapper closes between the node and what it scoped, and
# ``jax.checkpoint`` puts a scope of its own between the two
_HC = re.compile(r"[/(]hc/[^/()]+")
_PART = re.compile(r"/(%s)(?=/|\)|$)" % "|".join(PARTS))
_Q_LATENT = re.compile(r"[/(](?:fc|norm)/[A-Za-z0-9]+_q_latent_")
_MTP = re.compile(r"[/(][a-z]+/mtp\d+_")


def reduce(raw, scopes, device=0):
    """Seconds of ``device`` over the benchmark's slice: ``hc_coeff``,
    ``hc_sinkhorn``, ``hc_mix``, ``hc_other`` (ops under an ``hc`` node and
    none of the three), ``q_latent`` and ``mtp``, each None where no op
    carries such a scope; None without a slice or where none does."""
    window = reduce_trace.slice_window(
        raw["host"], reduce_trace.SLICE_BEGIN, reduce_trace.SLICE_END)
    if window is None or device not in raw["devices"]:
        return None
    ops = list(reduce_trace._clip(raw["devices"][device]["ops"], window))
    names = scopes.get(device, {})
    found = collections.Counter()
    for text, own in reduce_scopes.self_times(ops):
        scope = names.get(text) or ""
        if _HC.search(scope):
            part = _PART.search(scope)
            found[part.group(1) if part else "hc_other"] += own
        if _Q_LATENT.search(scope):
            found["q_latent"] += own
        if _MTP.search(scope):
            found["mtp"] += own
    if not found:
        return None
    return {k: found[k] / 1e9 if k in found else None
            for k in PARTS + ("hc_other", "q_latent", "mtp")}


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["hc_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "hc_scopes" in run:
        return run["hc_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path),
                              reduce_scopes.scope_names(path))
    return _cache[path]


def ms(trace, run, part):
    """ms/step of ``part``; None without a slice or without the scope."""
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
