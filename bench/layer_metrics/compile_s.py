"""Seconds jax spent in backend compilation during set-up, loading from
the persistent cache included (``jax.monitoring``); hits and misses are
printed on the run's ``compile`` line."""


def compute(trace, counters, run):
    return counters["compile"]["setup"]["seconds"]
