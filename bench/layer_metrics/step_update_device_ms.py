"""Busy milliseconds of device 0 per step in ops whose scope is
``update``: the optimizer (per key, flat or sharded, its collectives and
the slab kernel included)."""
import reduce_scopes


def compute(trace, counters, run):
    return reduce_scopes.phase_ms(trace, run, "update")
