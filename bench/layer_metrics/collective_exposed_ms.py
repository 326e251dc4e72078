"""Milliseconds per step in which a collective runs on device 0 and no
compute op does: the part of the gradient and parameter collectives the
step does not hide."""


def compute(trace, counters, run):
    if (not trace or 0 not in trace["devices"] or not run.get("trace_steps")
            or run["chips"] < 2):
        return None
    return (1e3 * trace["devices"][0]["collective_exposed_s"]
            / run["trace_steps"])
