"""Idle milliseconds of device 0 per step under none of the input,
dispatch and metric spans (callbacks, bookkeeping, the loop itself).
The four ``idle_under_*`` partition the idle time of the slice: this
reader fails the run if they differ from ``device_idle_share`` x slice /
steps by more than 2%."""
import reduce_scopes


def compute(trace, counters, run):
    value = reduce_scopes.idle_under_ms(trace, run, "other")
    if value is None:
        return None
    d = trace["devices"][0]
    whole = reduce_scopes.per_step_ms(
        run, d["idle_share"] * trace["window_s"])
    parts = {k: reduce_scopes.idle_under_ms(trace, run, k)
             for k in ("input", "dispatch", "metric", "other")}
    ok, why = reduce_scopes.sums_to(parts, whole, 0.02, "idle ms/step")
    return value, ok, why
