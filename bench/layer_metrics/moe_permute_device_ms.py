"""Busy milliseconds of device 0 per step in the expert layer's row
moves: ops under ``moe/<node>/dispatch`` (the sort by expert, the row
count, the gather into expert order) and ``moe/<node>/combine`` (the
gather back and the weighted sum), forward and backward together. None
where the trace has no expert layer or its stages carry no scope."""
import lm_scopes
import reduce_scopes


def compute(trace, counters, run):
    if not trace or not run.get("trace_steps"):
        return None
    parts = (lm_scopes.of(run) or {}).get("moe_part_s") or {}
    if "dispatch" not in parts and "combine" not in parts:
        return None
    return reduce_scopes.per_step_ms(
        run, parts.get("dispatch", 0) + parts.get("combine", 0))
