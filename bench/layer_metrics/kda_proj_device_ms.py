"""Busy milliseconds of device 0 per step in the KDA layers' nine
projections (the ``FullyConnected`` nodes named
``layer<i>_kda_{q,k,v,o}_proj``: 2304 -> 4096 three times and back; the
low-rank pairs ``layer<i>_kda_{f,g}_{a,b}_proj``: 2304 -> 128 -> 4096
into the decay and into the gate; ``layer<i>_kda_b_proj``: 2304 -> 32),
forward and backward together: the part of a KDA layer that is plain
matrix products."""
import kda_scopes


def compute(trace, counters, run):
    return kda_scopes.ms(trace, run, "kda_proj")
