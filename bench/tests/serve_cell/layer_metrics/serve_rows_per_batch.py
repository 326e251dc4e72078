"""``serve.requests`` over ``serve.batches`` in the window: filled rows
per device call (padding rows are ``serve.pad_rows``)."""


def compute(trace, counters, run):
    t = counters["telemetry"]
    batches = t.get("serve.batches", {}).get("value")
    if not batches:
        return None
    return t.get("serve.requests", {}).get("value", 0.0) / batches
