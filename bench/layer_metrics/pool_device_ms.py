"""Busy milliseconds of device 0 per step in ops whose scope's class is
``pool`` (Pooling), forward and backward together. A fusion carries the
scope of its root instruction."""
import reduce_scopes


def compute(trace, counters, run):
    return reduce_scopes.class_ms(trace, run, "pool")
