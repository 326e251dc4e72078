"""Median of ``serve.queue_wait_seconds`` in the window: how long a
request waited between enqueue and its batch's dispatch."""
import lib


def compute(trace, counters, run):
    h = counters["telemetry"].get("serve.queue_wait_seconds")
    if not h:
        return None
    p50 = lib.bucket_percentile(h, 50)
    return None if p50 is None else 1e3 * p50
