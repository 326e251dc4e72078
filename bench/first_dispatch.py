"""The first dispatch from inside: set-up's trace and lowering seconds
by what was traced, read from the program's registry as the window
opened (``setup_phases.registry_at_open``).

The program (``mxnet_tpu.telemetry.setup``, PR 51) counts the host
seconds of the fused step's trace by part of its body
(``jit.trace_seconds{part, under}``: forward, backward, update)
and of each symbol node's ``fcompute`` by op class
(``jit.node_trace_seconds{class, under}``), on the clock on which jax's
own phases are counted (``jit.seconds{phase, fun, under}``). Five terms
partition ``jit.seconds{phase in (trace, lower)}``::

    trace + lower = forward + backward + update + lower + unattributed

``unattributed`` is the rest of the body (the AMP casts, the gradient
pins, the guard), jax's own work round it (the jaxpr's closing,
constants), and the traces under roots where
no step is traced (the initializers' programs, ``make_state``'s).
``under`` = ``-`` (outside every span: the harness's own programs) and
``telemetry.cost_capture`` are left out by label, as
``setup_phases.trace_lower_s`` leaves them out. A program without
``jit.trace_seconds`` (an older commit) reads as ``None`` in every
term, its lowering seconds among them: the terms are one partition.
"""
from __future__ import annotations

import setup_phases

TRACE_SECONDS = "jit.trace_seconds"
NODE_SECONDS = "jit.node_trace_seconds"
JIT_SECONDS = "jit.seconds"
BODY_PARTS = ("forward", "backward", "update")
NOT_THE_PROGRAMS = ("-", setup_phases.COST_CAPTURE)
# what a root that traces a step may hold outside forward, backward and
# update before the run fails: below the floor two parts counted one
# interval twice, above the limit a site of size has no part
REMAINDER_FLOOR_S = -0.5
REMAINDER_MIN_LIMIT_S = 1.0
REMAINDER_LIMIT_SHARE = 0.15


def _ours(labels):
    return labels.get("under") not in NOT_THE_PROGRAMS


def _streams(snap, metric):
    """``metric``'s streams under the program's own roots, or None for
    a registry without the step's partition."""
    if not setup_phases._streams(snap, TRACE_SECONDS):
        return None
    return [s for s in setup_phases._streams(snap, metric)
            if _ours(s["labels"])]


def _folded(streams):
    """Whether the registry's cardinality guard folded label sets of
    these streams into ``{overflow="true"}``."""
    return any("overflow" in s["labels"] for s in streams)


def _by_root(streams, **labels):
    out = {}
    for s in streams:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            root = s["labels"]["under"]
            out[root] = out.get(root, 0.0) + s["value"]
    return out


def part_s(run, part):
    """Seconds of the step's trace inside ``part`` of its body."""
    return setup_phases.labelled(
        setup_phases.registry_at_open(run), TRACE_SECONDS,
        lambda lb: lb.get("part") == part and _ours(lb))


def lower_s(run):
    """jax's lowering seconds (jaxpr to MLIR module, Mosaic's kernels
    among it) under the program's roots."""
    snap = setup_phases.registry_at_open(run)
    if setup_phases.labelled(snap, TRACE_SECONDS, _ours) is None:
        return None
    return setup_phases.labelled(
        snap, JIT_SECONDS,
        lambda lb: lb.get("phase") == "lower" and _ours(lb))


def ms_node(run):
    """Host milliseconds a node costs to trace, all classes."""
    streams = _streams(setup_phases.registry_at_open(run), NODE_SECONDS)
    count = sum(s["count"] for s in streams or ())
    if not count:
        return None
    return 1e3 * sum(s["sum"] for s in streams) / count


def remainder(run):
    """``unattributed`` as ``(value, ok, why)``: jax's trace seconds
    less forward, backward and update. The limits hold on the roots
    under which a step was traced (what is left there is the body's
    rest and jax's own work round it); the other roots' trace seconds
    are the initializers' and the optimizer state's programs, named and
    taken out first."""
    snap = setup_phases.registry_at_open(run)
    parts, jit = _streams(snap, TRACE_SECONDS), _streams(snap, JIT_SECONDS)
    if parts is None:
        return None
    folded = _folded(parts) or _folded(jit)
    parts, jit = ([s for s in streams if "overflow" not in s["labels"]]
                  for streams in (parts, jit))
    traced = _by_root(jit, phase="trace")
    named = {p: _by_root(parts, part=p) for p in BODY_PARTS}
    step_roots = sorted({s["labels"]["under"] for s in parts})
    value = sum(traced.values()) - sum(
        sum(named[p].values()) for p in BODY_PARTS)
    step_trace = sum(traced.get(r, 0.0) for r in step_roots)
    checked = step_trace - sum(
        named[p].get(r, 0.0) for p in BODY_PARTS for r in step_roots)
    limit = max(REMAINDER_MIN_LIMIT_S, REMAINDER_LIMIT_SHARE * step_trace)
    why = ("trace %.3f = %s + unattributed %.3f; under %s: trace %.3f "
           "less the three %.3f (limits %.1f .. %.3f); under roots that "
           "trace no step: %s"
           % (sum(traced.values()),
              " + ".join("%s %.3f" % (p, sum(named[p].values()))
                         for p in BODY_PARTS),
              value, "+".join(step_roots), step_trace, checked,
              REMAINDER_FLOOR_S, limit,
              ", ".join("%s %.3f" % (r, v)
                        for r, v in sorted(traced.items())
                        if r not in step_roots) or "nothing"))
    if folded:
        # seconds with no phase, no part and no root: no term holds them
        return value, False, (
            "label sets folded into {overflow=\"true\"}, the terms are "
            "short of them; " + why)
    return value, REMAINDER_FLOOR_S <= checked <= limit, why
