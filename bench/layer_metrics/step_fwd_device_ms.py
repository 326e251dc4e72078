"""Busy milliseconds of device 0 per step in ops whose scope is
``fwd_bwd`` outside a ``transpose(`` wrapper: the forward pass."""
import reduce_scopes


def compute(trace, counters, run):
    return reduce_scopes.phase_ms(trace, run, "fwd")
