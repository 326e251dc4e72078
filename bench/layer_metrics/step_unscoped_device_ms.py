"""Busy milliseconds of device 0 per step in ops under neither
``fwd_bwd`` nor ``update``: ``guard``, ``amp_cast``, other programs of
the slice, and ops the compiler left without a scope. The four
``step_*_device_ms`` partition the busy time: this reader fails the run
if they differ from ``step_device_ms`` by more than 1%."""
import reduce_scopes


def compute(trace, counters, run):
    value = reduce_scopes.phase_ms(trace, run, "unscoped")
    if value is None:
        return None
    whole = reduce_scopes.per_step_ms(run, trace["devices"][0]["busy_s"])
    parts = {k: reduce_scopes.phase_ms(trace, run, k)
             for k in ("fwd", "bwd", "update", "unscoped")}
    ok, why = reduce_scopes.sums_to(parts, whole, 0.01, "step_device_ms")
    return value, ok, why
