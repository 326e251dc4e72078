"""Device-busy milliseconds per step: the busy union of device 0 over
the traced steps, from the profiler's trace."""


def compute(trace, counters, run):
    if not trace or 0 not in trace["devices"] or not run.get("trace_steps"):
        return None
    return 1e3 * trace["devices"][0]["busy_s"] / run["trace_steps"]
