"""How late the open-loop sender ran against its own schedule, 99th
percentile: a starved generator must not read as a fast server."""


def compute(trace, counters, run):
    return run.get("gen_late_ms_p99")
