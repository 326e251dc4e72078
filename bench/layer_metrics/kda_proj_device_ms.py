"""Busy milliseconds of device 0 per step in the KDA layers' nine
projections (the ``FullyConnected`` nodes named
``layer<i>_kda_{q,k,v,o}_proj``: hidden -> the heads held three times
and back; the low-rank pairs ``layer<i>_kda_{f,g}_{a,b}_proj``: hidden
-> 128 -> the heads held, into the decay and into the gate;
``layer<i>_kda_b_proj``: hidden -> a column a head), every KDA layer,
forward and backward together: the part of a KDA layer that is plain
matrix products."""
import kda_scopes


def compute(trace, counters, run):
    return kda_scopes.ms(trace, run, "kda_proj")
