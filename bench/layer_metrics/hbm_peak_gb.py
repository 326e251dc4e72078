"""Peak device memory on the fullest chip, ``lib.memory_peak_bytes``:
the allocator's peak of live buffers plus its peak of executable
scratch, an upper bound of the true peak (PERF.md, Open questions)."""


def compute(trace, counters, run):
    peak = run.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
