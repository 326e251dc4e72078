"""Device-busy milliseconds per dispatched batch in the traced slice."""


def compute(trace, counters, run):
    if (not trace or 0 not in trace["devices"]
            or not run.get("slice_batches")):
        return None
    return 1e3 * trace["devices"][0]["busy_s"] / run["slice_batches"]
