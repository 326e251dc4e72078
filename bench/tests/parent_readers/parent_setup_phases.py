"""Set-up from inside: the program's own account of where ``setup_s``
went, read from its registry by metric name and label.

The program (``mxnet_tpu.telemetry``, PR 35) opens a span round each
piece of set-up (``module.bind``, ``module.init_params``,
``module.init_optimizer``, ``train_step.first_dispatch``,
``telemetry.cost_capture``), counts jax's own seconds by phase
(``jit.seconds{phase, under}``) and the host memory handed to
``jax.device_put`` (``device.h2d_bytes{under}``), ``under`` being the
outermost span open on the calling thread, and publishes two stamps of
its import (``process.import_t0`` on the ``perf_counter`` clock,
``process.import_seconds``).

The registry is cumulative and the readers run after the run: what an
LM cell's reference check binds after the window would count as set-up.
So the values are taken from the dump the harness itself took as the
window opened (``lib.Session.window_open``), which the program keeps
(``telemetry.snapshots_taken()``): the first one stamped at or after
``run["open_t"]``. A program without these streams (an older commit)
reads as ``None``, never as zero and never as a raise.

The eight terms of the partition, in seconds::

    setup_s = runtime + import + bind + init_params + init_optimizer
              + first_dispatch + telemetry + unattributed
              + (open_t - first_step_t)        the harness's own interval

``telemetry.cost_capture`` never nests in ``train_step.first_dispatch``
(the program opens it after the dispatch has returned), so nothing is
subtracted from that term; its jax seconds carry ``under`` =
``telemetry.cost_capture`` wherever it nests, so ``trace_lower_s``
leaves them out by label.
"""
from __future__ import annotations

SPAN_SECONDS = "mxtpu.span_seconds"
COST_CAPTURE = "telemetry.cost_capture"
# span -> the partition's term it is
SPAN_TERMS = {
    "bind": "module.bind",
    "init_params": "module.init_params",
    "init_optimizer": "module.init_optimizer",
    "first_dispatch": "train_step.first_dispatch",
    "telemetry": COST_CAPTURE,
}
TERMS = ("runtime", "import") + tuple(SPAN_TERMS) + ("unattributed",)
H2D_ROOTS = ("module.bind", "module.init_params", "module.init_optimizer")
# what the remainder may hold, jax's seconds outside every span apart,
# before the run fails (PERF.md section 5 says what was found in it)
REMAINDER_FLOOR_S = -0.5
REMAINDER_MIN_LIMIT_S = 2.0
REMAINDER_LIMIT_SHARE = 0.05


def registry_at_open(run):
    """The program's registry as the window opened, or None."""
    if run.get("open_t") is None:
        return None
    try:
        from mxnet_tpu import telemetry
    except ImportError:
        return None
    taken = getattr(telemetry, "snapshots_taken", None)
    if taken is None:
        return None
    for stamp, dump in taken():
        if stamp >= run["open_t"]:
            return dump
    return None


def _streams(snap, metric):
    return (snap or {}).get(metric, {}).get("streams", [])


def span_seconds(snap, span):
    """Summed seconds of the spans named ``span``; None without one."""
    found = [s["sum"] for s in _streams(snap, SPAN_SECONDS)
             if s["labels"].get("span") == span]
    return sum(found) if found else None


def labelled(snap, metric, keep):
    """Sum of ``metric``'s streams whose labels ``keep`` accepts; None
    where the registry holds no such metric."""
    streams = _streams(snap, metric)
    if not streams:
        return None
    return sum(s["value"] for s in streams if keep(s["labels"]))


def gauge(snap, metric):
    streams = _streams(snap, metric)
    return streams[0]["value"] if streams else None


def terms(run, snap=None):
    """The partition's terms by name, None for one the registry cannot
    give; ``unattributed`` is None unless the import stamps are there
    (a program that lacks them lacks the spans too)."""
    snap = registry_at_open(run) if snap is None else snap
    out = dict.fromkeys(TERMS)
    if not snap or run.get("setup_s") is None:
        return out
    t0 = gauge(snap, "process.import_t0")
    if t0 is not None:
        out["runtime"] = t0 - (run["open_t"] - run["setup_s"])
    out["import"] = gauge(snap, "process.import_seconds")
    for term, span in SPAN_TERMS.items():
        out[term] = span_seconds(snap, span)
    if out["runtime"] is not None and out["import"] is not None:
        out["unattributed"] = (
            run["setup_s"] - harness_s(run)
            - sum(out[t] or 0.0 for t in TERMS[:-1]))
    return out


def harness_s(run):
    """The harness's own known interval of set-up: its warm-up steps
    and, traced, the profiler's slice and ``stop_trace``."""
    return run["open_t"] - run.get("first_step_t", run["open_t"])


def term(run, name):
    return terms(run)[name]


def outside_jit_s(run):
    """jax's seconds under no span, all phases: the harness's own
    programs (the resident batch's lowering is 1.5 s of the conv cells'
    remainder, its cold compile 12 s) and what the program jits outside
    its spans (seeding)."""
    return labelled(registry_at_open(run), "jit.seconds",
                    lambda lb: lb.get("under") == "-") or 0.0


def remainder(run):
    """``unattributed`` as ``(value, ok, why)``: below the floor two
    terms counted one interval twice; above the limit, once jax's
    seconds outside every span are taken out, something of size has no
    span."""
    found = terms(run)
    value = found["unattributed"]
    if value is None:
        return None
    limit = max(REMAINDER_MIN_LIMIT_S, REMAINDER_LIMIT_SHARE * run["setup_s"])
    outside = outside_jit_s(run)
    why = ("setup_s %.3f = %s + harness %.3f + unattributed %.3f, of which "
           "jax outside every span %.3f (limits %.1f .. %.3f on the rest)"
           % (run["setup_s"],
              " + ".join("%s %.3f" % (t, found[t] or 0.0)
                         for t in TERMS[:-1]),
              harness_s(run), value, outside, REMAINDER_FLOOR_S, limit))
    return value, (REMAINDER_FLOOR_S <= value
                   and value - outside <= limit), why


def trace_lower_s(run):
    """jax's trace and lowering seconds under any span of the program
    but ``telemetry.cost_capture``; ``-`` is what ran outside every span
    (the harness's own programs among it)."""
    return labelled(
        registry_at_open(run), "jit.seconds",
        lambda lb: lb.get("phase") in ("trace", "lower")
        and lb.get("under") not in ("-", COST_CAPTURE))


def h2d_gb(run):
    value = labelled(registry_at_open(run), "device.h2d_bytes",
                     lambda lb: lb.get("under") in H2D_ROOTS)
    return None if value is None else value / 1e9
