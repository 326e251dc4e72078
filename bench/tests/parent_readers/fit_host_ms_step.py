"""Host milliseconds a step of the fit loop spends staging its batch,
enqueueing the fused step and blocked on the metric's output fetch
(the last is where device time surfaces on the host thread)."""

NAMES = ("module.stage_host_seconds", "module.dispatch_host_seconds",
         "module.output_sync_seconds")


def compute(trace, counters, run):
    t = counters["telemetry"]
    if not run.get("steps") or not any(n in t for n in NAMES):
        return None
    # dispatch_host_seconds already holds the staging slice
    # (module.update times them from one start)
    host = (t.get("module.dispatch_host_seconds", {}).get("sum", 0.0)
            + t.get("module.output_sync_seconds", {}).get("sum", 0.0))
    return 1e3 * host / run["steps"]
