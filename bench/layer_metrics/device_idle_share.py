"""1 - busy union over the traced slice, device 0 (every device is
printed on a ``device_trace`` line)."""


def compute(trace, counters, run):
    if not trace or 0 not in trace["devices"]:
        return None
    return 100.0 * trace["devices"][0]["idle_share"]
