"""Share of the window the fit loop spent inside ``DeviceFeedIter.next``
(``io.feed_wait_seconds``): the input layer's stall as the host sees it."""


def compute(trace, counters, run):
    h = counters["telemetry"].get("io.feed_wait_seconds")
    if not h or not h["count"]:
        return None
    return 100.0 * h["sum"] / run["window_s"]
