"""Step-time anatomy: MFU attribution, roofline accounting, recompiles.

Turns PR 1's raw spans/metrics into an answer to "why is MFU 14%?":

- **Cost capture** — after the first dispatch of each dispatch-plan
  signature the fused trainer hands this module a thunk that LOWERS the
  same program (no backend compile: telemetry builds no executable the
  untraced run does not build); :func:`capture_cost` runs it once per
  signature, reads XLA's ``cost_analysis()`` of the lowering
  (costmodel.extract_cost) or, where the backend offers none before
  compilation (the TPU's), the hand count from the symbol's shapes, and
  exports live ``anatomy.model_flops`` / ``anatomy.model_bytes_accessed``
  gauges.
- **Phase decomposition** — the fit loop calls :func:`begin_loop` /
  :func:`on_steps`; every MXTPU_ANATOMY_INTERVAL steps (and at epoch
  end) :func:`emit_interval` takes registry deltas of the phase-time
  histograms (input wait, staging, dispatch, device sync, collectives),
  subtracts them from measured wall time, and writes one
  ``{"type": "anatomy"}`` JSONL record in which the *unattributed*
  remainder is an explicit field rather than invisible — plus MFU and a
  roofline classification when the cost model and peak rates are known.
- **Recompile detector** — the dispatch-plan signature cache
  (executor._GraphProgram.dispatch_plan) reports every miss here; after
  the warmup compile each miss increments ``anatomy.recompiles`` and
  logs a structured fingerprint diff (per-input shape/dtype/sharding,
  mesh, donation) so "it recompiled" always comes with "because this
  changed".

Everything is a no-op unless telemetry is enabled AND MXTPU_ANATOMY is
not "0"; all hooks are exception-safe observers — anatomy must never
break a dispatch.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time

from . import costmodel
from . import export as _export
from . import registry as _registry

_LOG = logging.getLogger("mxnet_tpu.telemetry.anatomy")

_lock = threading.Lock()


def enabled():
    """Anatomy rides on telemetry: off when collection is off, and
    MXTPU_ANATOMY=0 switches just this layer off."""
    return (_registry.enabled()
            and os.environ.get("MXTPU_ANATOMY", "1") not in ("", "0"))


def cost_pending(program_uid, key):
    """Whether :func:`capture_cost` has yet to run for this program and
    signature: the trainers build the abstract arguments of a lowering
    only then. Once captured, asking makes that program's cost the
    current one (what a cache hit of ``capture_cost`` does)."""
    global _current_cost
    if not enabled():
        return False
    with _lock:
        if (program_uid, key) not in _cost_cache:
            return True
        cost = _cost_cache[(program_uid, key)]
        if cost:
            _current_cost = cost
    _C_COST_HITS.inc()
    return False


def _interval_steps():
    try:
        return max(int(os.environ.get("MXTPU_ANATOMY_INTERVAL", "32")), 1)
    except ValueError:
        return 32


_C_RECOMPILES = _registry.counter(
    "anatomy.recompiles",
    "Dispatch-plan signature cache misses AFTER the warmup compile — "
    "each one is a fresh trace/lower on the hot path; the paired "
    "JSONL 'recompile' record carries the structured fingerprint diff")
_C_COST_HITS = _registry.counter(
    "anatomy.cost_cache_hits",
    "Cost-model lookups served from the per-signature cache")
_C_COST_MISSES = _registry.counter(
    "anatomy.cost_cache_misses",
    "Cost-model lookups that lowered the program for cost_analysis()")
_G_MFU = _registry.gauge(
    "anatomy.mfu",
    "Model FLOPs utilization over the last anatomy interval: "
    "flops_per_step * steps / wall / peak_flops (wall-rate based)")
_G_MODEL_FLOPS = _registry.gauge(
    "anatomy.model_flops",
    "Per-step FLOPs of the active compiled program (XLA cost analysis)")
_G_MODEL_BYTES = _registry.gauge(
    "anatomy.model_bytes_accessed",
    "Per-step HBM bytes accessed by the active compiled program "
    "(XLA cost analysis)")


# ---------------------------------------------------------------------------
# cost capture (per compiled program, cached by dispatch-plan signature)
# ---------------------------------------------------------------------------

_cost_cache = {}  # (program_uid, key) -> {"flops", "bytes_accessed"} | None
_current_cost = None  # the cost dict of the most recently dispatched program


def capture_cost(program_uid, key, lower_thunk, dtype=None, devices=1,
                 analytic=None):
    """Resolve the per-step, per-device cost of one program.

    ``lower_thunk`` must return a jax stage with ``cost_analysis()``
    built from the SAME abstract args the dispatch used — a ``Lowered``,
    whose analysis needs no backend compile (its numbers are of the
    program before XLA's optimizations and partitioning: operations
    within a few percent of the executable's, bytes an upper bound, both
    for all ``devices`` together). It runs at most once per (program,
    signature). Where it yields nothing, ``analytic()`` gives the cost
    per device instead. ``dtype`` tags the program's compute dtype
    ("bf16"/"f32") so MFU is computed against the right roofline — fp32
    compute can never reach the bf16 peak the tables quote. Failures
    cache as None — never retried, never raised.
    """
    global _current_cost
    ck = (program_uid, key)
    with _lock:
        if ck in _cost_cache:
            _C_COST_HITS.inc()
            cost = _cost_cache[ck]
            if cost:
                _current_cost = cost
            return cost
    _C_COST_MISSES.inc()
    cost = None
    try:
        raw = costmodel.extract_cost(lower_thunk())
        per = max(devices, 1)
        if not (raw["flops"] or raw["bytes_accessed"]) and analytic:
            raw, per = analytic(), 1
        if raw["flops"] or raw["bytes_accessed"]:
            cost = {
                "flops": (raw["flops"] or 0.0) / per,
                "bytes_accessed": (raw["bytes_accessed"] or 0.0) / per,
            }
            if dtype:
                cost["compute_dtype"] = str(dtype)
    except Exception as exc:
        _LOG.debug("cost capture failed (program=%s): %s", program_uid, exc)
    with _lock:
        _cost_cache[ck] = cost
        if cost:
            _current_cost = cost
    if cost:
        _G_MODEL_FLOPS.set(cost["flops"])
        _G_MODEL_BYTES.set(cost["bytes_accessed"])
    return cost


# ---------------------------------------------------------------------------
# recompile detector
# ---------------------------------------------------------------------------

_program_meta = {}  # program_uid -> {"mesh": ..., "donation": ...}
_last_fp = {}  # program_uid -> fingerprint dict


def register_program(program_uid, **meta):
    """Attach trace-level context (mesh layout, donation policy) that a
    dispatch signature alone cannot see; it joins every fingerprint."""
    clean = {k: v for k, v in meta.items() if v is not None}
    if clean:
        _program_meta[program_uid] = clean


def _fingerprint(program_uid, sig):
    inputs = {}
    tags = []
    for entry in sig:
        if (isinstance(entry, tuple) and len(entry) == 4
                and isinstance(entry[0], str)):
            name, shape, dtype, sharding = entry
            inputs[name] = {"shape": list(shape), "dtype": str(dtype),
                            "sharding": str(sharding)}
        else:
            tags.append(str(entry))
    fp = {"inputs": inputs}
    if tags:
        fp["tags"] = tags
    fp.update(_program_meta.get(program_uid, {}))
    return fp


def fingerprint_diff(prev, now):
    """Structured diff between two program fingerprints: per-input field
    changes plus added/removed inputs and changed program meta."""
    pi, ni = prev.get("inputs", {}), now.get("inputs", {})
    changed = {}
    for name in sorted(set(pi) & set(ni)):
        fields = {}
        for f in ("shape", "dtype", "sharding"):
            if pi[name].get(f) != ni[name].get(f):
                fields[f] = {"was": pi[name].get(f), "now": ni[name].get(f)}
        if fields:
            changed[name] = fields
    out = {"changed": changed,
           "added": sorted(set(ni) - set(pi)),
           "removed": sorted(set(pi) - set(ni))}
    meta = {}
    for f in sorted(set(prev) | set(now) - {"inputs"}):
        if f == "inputs":
            continue
        if prev.get(f) != now.get(f):
            meta[f] = {"was": prev.get(f), "now": now.get(f)}
    if meta:
        out["meta"] = meta
    return out


def note_plan_miss(program_uid, sig):
    """Called by _GraphProgram.dispatch_plan on every signature-cache
    miss. The first miss per program is the warmup compile; each later
    miss is a recompile: counter + structured JSONL diff + warning."""
    if not enabled():
        return
    fp = _fingerprint(program_uid, sig)
    with _lock:
        prev = _last_fp.get(program_uid)
        _last_fp[program_uid] = fp
    if prev is None:
        return
    _C_RECOMPILES.inc()
    diff = fingerprint_diff(prev, fp)
    _export.emit_record({"type": "recompile", "t": time.time(),
                         "program": program_uid, "diff": diff,
                         "fingerprint": fp})
    _LOG.warning("recompile: program=%s diff=%s", program_uid,
                 json.dumps(diff, sort_keys=True))


# ---------------------------------------------------------------------------
# per-interval step anatomy
# ---------------------------------------------------------------------------

# (phase name, source metric). Phases are DISJOINT host-wall regions of
# the fit loop; dispatch_host is special-cased below because its
# measurement window includes the staging slice.
_PHASES = (
    ("input_wait", "io.feed_wait_seconds"),
    ("stage_host", "module.stage_host_seconds"),
    ("dispatch_host", "module.dispatch_host_seconds"),
    ("device_sync", "module.output_sync_seconds"),
    ("collective", "parallel.collective_seconds"),
)


def _phase_totals():
    return {name: _registry.REGISTRY.total(metric)
            for name, metric in _PHASES}


_state = None  # active interval accumulator (fit-loop thread only)


def note_op_costs(ops, device_kind=None, compute_dtype=None):
    """Emit the per-op analytic cost table (costmodel.analytic_op_costs)
    as one ``{"type": "op_costs"}`` JSONL record. perf_doctor joins it
    with the peak tables to rank memory-bound ops as Pallas-kernel
    candidates. Best-effort: truncates to 64 ops, never raises."""
    if not enabled() or not ops:
        return
    try:
        _export.emit_record({
            "type": "op_costs",
            "t": time.time(),
            "device_kind": device_kind or _device_kind(),
            "compute_dtype": compute_dtype,
            "n_ops": len(ops),
            "ops": list(ops)[:64],
        })
    except Exception as exc:  # noqa: BLE001 — observers must not raise
        _LOG.debug("note_op_costs failed: %s", exc)


def begin_loop():
    """Arm the interval accumulator at the top of a fit loop."""
    global _state
    if not enabled():
        _state = None
        return
    _state = {
        "t0": time.perf_counter(),
        "totals": _phase_totals(),
        "steps": 0,
        "interval": 0,
        "step_end": 0,
        "recompiles0": _C_RECOMPILES.value(),
    }


def on_steps(n=1):
    """Record n completed optimizer steps; emits when the interval
    fills."""
    if _state is None or n <= 0:
        return
    _state["steps"] += n
    if _state["steps"] >= _interval_steps():
        emit_interval()


def emit_interval(force=False):
    """Close the current interval: phase deltas vs wall time, MFU,
    roofline, recompile count — one JSONL record. ``force`` flushes a
    partial interval (epoch end); empty intervals never emit."""
    st = _state
    if st is None:
        return None
    steps = st["steps"]
    if steps <= 0 or (steps < _interval_steps() and not force):
        return None
    now = time.perf_counter()
    wall = now - st["t0"]
    totals = _phase_totals()
    phases = {name: max(totals[name] - st["totals"][name], 0.0)
              for name, _ in _PHASES}
    # the dispatch measurement window includes staging — report only the
    # non-stage remainder so the phases stay disjoint
    phases["dispatch_host"] = max(
        phases["dispatch_host"] - phases["stage_host"], 0.0)
    record = {
        "type": "anatomy",
        "t": time.time(),
        "interval": st["interval"],
        # cumulative steps completed at interval close — the step id the
        # fleet aggregator aligns cross-rank intervals on
        "step_end": st["step_end"] + steps,
        "steps": steps,
        "wall_seconds": wall,
        "step_ms": 1000.0 * wall / steps,
        "phases": phases,
        # NOT clamped: phases + unattributed must sum to wall exactly
        "unattributed_seconds": wall - sum(phases.values()),
        "recompiles": _C_RECOMPILES.value() - st["recompiles0"],
    }
    cost = _current_cost
    if cost:
        record["flops_per_step"] = cost["flops"]
        record["bytes_per_step"] = cost["bytes_accessed"]
        kind = _device_kind()
        dtype = cost.get("compute_dtype")
        if dtype:
            record["compute_dtype"] = dtype
        record["device_kind"] = kind
        # dtype-aware roofline: fp32 programs are measured against the
        # derated fp32 peak, not the bf16 number the chip is sold on
        pf = costmodel.peak_flops_for_kind(kind, dtype)
        pb = costmodel.peak_bytes_for_kind(kind)
        if cost["flops"] and pf and wall > 0:
            mfu = cost["flops"] * steps / wall / pf
            if mfu <= 1.0:
                record["mfu"] = mfu
                _G_MFU.set(mfu)
            else:
                # sanity gate: >100% means the peak table or
                # the cost model is wrong for this device — say so
                # instead of reporting a nonsense utilization
                record["mfu_error"] = (
                    "mfu %.2f > 1: check peak table / "
                    "MXTPU_ANATOMY_PEAK_TFLOPS for kind %r" % (mfu, kind))
        record["roofline"] = costmodel.classify(
            cost["flops"] * steps if cost["flops"] else None,
            (cost["bytes_accessed"] * steps
             if cost["bytes_accessed"] else None),
            wall, phases["collective"], pf, pb)
    _export.emit_record(record)
    st["t0"] = now
    st["totals"] = totals
    st["step_end"] += steps
    st["steps"] = 0
    st["interval"] += 1
    st["recompiles0"] += record["recompiles"]
    return record


_kind_cache = None


def _device_kind():
    global _kind_cache
    if _kind_cache is None:
        import jax

        _kind_cache = str(jax.devices()[0].device_kind)
    return _kind_cache


def reset_state():
    """Drop caches, fingerprints, and the active interval (telemetry
    reset path — test isolation)."""
    global _state, _current_cost
    with _lock:
        _cost_cache.clear()
        _last_fp.clear()
        _program_meta.clear()
        _state = None
        _current_cost = None
