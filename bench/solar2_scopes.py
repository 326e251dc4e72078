"""What the Solar Open 2 cell's readers need beside the tables that are
there: which configuration counts as one (``solar2_flops``), and the
device time of a traced slice inside the ops of a kernel family
(``kernel_ms``), so that a roofline share is read of the kernels it is
named for and not of a fallback (``lowered_to``: the family's share of
its scope's time; the slice cuts its first and last step, so calls
cannot be counted).

The device times are the other tables': a ``GatedDeltaNet`` node's scopes
``gdn/<node>/{conv1d, delta_rule, gate_norm}`` (``gdn_scopes``), its nine
projections ``fc/layer<i>_kda_*_proj`` (``kda_scopes``), an ``Attention``
node's kernels ``attn/<node>/full`` (``share_scopes``), its gate
``attn/<node>/gate`` and its five projections
``fc/layer<i>_{q,k,v,o,attn_gate}_proj`` (``afmoe_scopes``), the class
``moe`` (``lm_scopes``) and the shared expert's three nodes
(``mla_scopes``). A Pallas kernel is a device op named after the
kernel: ``kda_fwd_<dtype>_c<chunk>_k<K>_v<V>_pre`` / ``kda_bwd_...``
(``ops/kernels/gdn.py``), ``flash_{fwd,bwd,dq,dkv}_<dtype>_q<rows>_k<rows>``
(``ops/kernels/flash.py``). A program without them reads as ``None`` or
zero calls, never as an error.

    python3 bench/solar2_scopes.py <file.xplane.pb> [steps]
"""
from __future__ import annotations

import collections
import json
import re
import sys

import reduce_scopes
import reduce_trace
import share_scopes

# family -> the device op's name; the first that matches counts the op
KERNELS = collections.OrderedDict([
    ("kda_fwd", re.compile(r"\bkda_fwd_")),
    ("kda_bwd", re.compile(r"\bkda_bwd_")),
    ("flash_fwd", re.compile(r"\bflash_fwd_")),
    ("flash_bwd", re.compile(r"\bflash_(?:bwd|dq|dkv)_"))])


def reduce(raw, device=0):
    """Seconds of ``device`` over the benchmark's slice inside the ops of
    each kernel family, {family: seconds}; None without a slice. A
    kernel is a leaf of the op line: its length is its own time."""
    window = reduce_trace.slice_window(
        raw["host"], reduce_trace.SLICE_BEGIN, reduce_trace.SLICE_END)
    if window is None or device not in raw["devices"]:
        return None
    found = collections.Counter()
    for text, start, end in reduce_trace._clip(
            raw["devices"][device]["ops"], window):
        for family, pattern in KERNELS.items():
            if pattern.search(text):
                found[family] += end - start
                break
    return {family: found[family] / 1e9 for family in KERNELS}


_cache = {}


def of(run):
    """The reduction of this run's slice (``run["solar2_scopes"]`` where a
    test hands one in), or None where there is nothing to read."""
    if "solar2_scopes" in run:
        return run["solar2_scopes"]
    path = reduce_scopes.slice_path(run)
    if path is None:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(reduce_trace.load(path))
    return _cache[path]


def kernel_ms(trace, run, families):
    """ms/step inside the ops of ``families``; None without a slice."""
    if not trace or not run.get("trace_steps"):
        return None
    red = of(run)
    if not red:
        return None
    return reduce_scopes.per_step_ms(run, sum(red[f] for f in families))


# a scope whose kernels ran spends all but a few percent of its time in
# them; with one node of three in a ``jax.numpy`` form (four to five
# times a kernel's time, PERF.md section 7) they hold a third of it
KERNEL_SHARE_MIN = 0.5


def lowered_to(trace, run, families, scope_ms):
    """(ok, why): the ops of ``families`` hold at least
    ``KERNEL_SHARE_MIN`` of the ``scope_ms`` a step their scope took."""
    ms = kernel_ms(trace, run, families)
    share = ms / scope_ms if ms is not None and scope_ms else None
    ok = share is not None and share >= KERNEL_SHARE_MIN
    return ok, "%s ops %s ms of the scope's %.4f a step: share %s, want %s" % (
        "/".join(families), None if ms is None else round(ms, 4), scope_ms,
        None if share is None else round(share, 4), KERNEL_SHARE_MIN)


def roofline_share(trace, run, layers, flops, bytes_, busy_ms, families):
    """(share, ok, why) for a reader to return: the least time the chip
    could take for ``layers`` nodes of a step, forward and backward —
    the larger of ``flops`` over the bf16 peak and ``bytes_`` over the
    HBM peak, one node's forward each — as a percentage of ``busy_ms``,
    and ``lowered_to``'s verdict on ``families``."""
    peak = run["peak"]
    per_step = run["flops_multiplier"] * layers * run["batch"] / run["chips"]
    least_ms = 1e3 * per_step * max(flops / peak["bf16_flops"],
                                    bytes_ / peak["hbm_bytes_s"])
    return (100.0 * least_ms / busy_ms,) + lowered_to(
        trace, run, families, busy_ms)


def solar2_flops(run):
    """The configuration's operations module where it counts a KDA core
    beside a gated grouped-attention kernel (``kda_core_flops`` and
    ``gqa_kernel_flops``), or None."""
    flops = share_scopes.flops_of(run)
    return flops if (getattr(flops, "kda_core_flops", None)
                     and getattr(flops, "gqa_kernel_flops", None)) else None


if __name__ == "__main__":
    red = reduce(reduce_trace.load(sys.argv[1]))
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    print(json.dumps({"steps": steps, "ms_per_step": {
        k: round(1e3 * v / steps, 4) for k, v in sorted(red.items())}}
        if red else None, indent=1))
