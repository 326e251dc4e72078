"""Busy milliseconds of device 0 per step in the state-space blocks' two
projections (the ``FullyConnected`` nodes named ``layer<i>_in_proj``,
2688 -> 10304, and ``layer<i>_out_proj``, 4096 -> 2688), forward and
backward together: the part of a Mamba-2 block that is plain matrix
products."""
import ssm_scopes


def compute(trace, counters, run):
    return ssm_scopes.ms(trace, run, "proj")
